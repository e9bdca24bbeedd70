"""Event-loop time per update: the window's wall time less the drains'
and the client fan-outs' spans, over the updates aggregated. Evaluation,
behaviour draws, compression and event bookkeeping fall here."""


def read(run):
    if not run.updates:
        return None
    loop = run.window_s - sum(run.server_s) - run.client_s
    return loop / run.updates * 1e3

"""Client fan-out time per update: the host spans around the fan-out call,
each ending when its deltas are ready, over the updates aggregated."""


def read(run):
    if not run.updates:
        return None
    return run.client_s / run.updates * 1e3

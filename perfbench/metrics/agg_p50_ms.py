"""Median server reply latency: the same spans as agg_p95_ms, from the
drain call until the replies' models are ready on the device."""
import statistics


def read(run):
    if not run.server_s:
        return None
    return statistics.median(run.server_s) * 1e3

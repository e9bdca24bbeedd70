"""Share of the HBM roofline in the server's device time: the least time
each drain's Eq. 5-7 update needs on the chip (read the model and write it
once, read each update's stale model and its delta at wire width, over the
peak bandwidth; its FLOPs bind far later), over the device busy time
inside the drain spans. It counts the work whatever implements it, so
kernels fused, replaced or removed do not change what it measures."""


def read(run):
    if run.trace is None or not run.peaks or not run.least_agg_s:
        return None
    busy = run.trace["busy_in_server_s"]
    if busy <= 0:
        return None
    return 100.0 * sum(run.least_agg_s) / busy

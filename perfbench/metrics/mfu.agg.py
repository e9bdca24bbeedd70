"""The whole drain's share of the chip's peak: the least time each drain's
Eq. 5-7 update needs (the larger of its bytes over peak bandwidth and its
FLOPs over peak FLOP/s), over the drain spans' wall time. It bounds what
the fedagg kernels' roofline share can give the reply latency."""


def read(run):
    if not run.peaks or not run.least_agg_s or not sum(run.server_s):
        return None
    return 100.0 * sum(run.least_agg_s) / sum(run.server_s)

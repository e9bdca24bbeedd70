"""Model FLOP utilization of client training: the forward and backward
FLOPs of every local step of the window's updates (the configuration's
model file counts them from shapes, no recompute), over the window's
seconds times the chips times their bf16 peak."""


def read(run):
    if not run.flops or not run.peaks:
        return None
    peak = run.peaks["bf16_flops_per_s"] * run.chips
    return 100.0 * run.flops / (run.window_s * peak)

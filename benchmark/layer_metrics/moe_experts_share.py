"""The grouped product's share of the decode step's device time: the summed
device time of ``strom_moe_gmm`` inside ``_paged_step`` over the summed
device time of the steps that ran it."""

from benchmark.layer_metrics import _moe_trace as T


def read(ctx):
    runs = T.runs(ctx.trace, T.STEP)
    if not runs:
        return None
    total = sum(ns for ns, _ in runs)
    return 100.0 * T.kernel_seconds(ctx.trace, T.STEP)[1] / (total / 1e9)

"""The grouped product's share of the decode step's device time: the summed
device time of ``strom_moe_gmm`` inside ``_paged_step`` over the summed
device time of the steps that ran it."""

from benchmark.layer_metrics import _kernel_trace as K, _moe_trace as T


def read(ctx):
    got = K.runs(ctx.trace, K.STEP, T.KERNEL)
    calls, seconds = T.kernel_seconds(got)
    if not calls:
        return None
    return 100.0 * seconds / (K.totals(got)[0] / 1e9)

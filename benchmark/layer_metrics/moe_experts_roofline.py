"""The grouped product's share of its roofline in the decode step: over the
calls of ``strom_moe_gmm`` inside ``_paged_step`` in the trace, the least
time their bytes and operations allow (``costs_moe.experts_cost`` at the
window's mean pairs and touched experts per call, from the program's load
histogram — each touched expert's three matrices once: memory-bound at 128
rows) over their device time."""

from benchmark.layer_metrics import _kernel_trace as K, _moe_trace as T


def read(ctx):
    return T.experts_roofline(ctx, K.STEP, "")

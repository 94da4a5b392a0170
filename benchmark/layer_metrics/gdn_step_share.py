"""The delta-rule state-update kernel's share of the decode step's device
time: the summed device time of ``strom_gdn_update`` inside ``_paged_step``
over the summed device time of the steps that ran it — how much of a step is
the recurrent state's traffic."""

from benchmark.layer_metrics import _kernel_trace as K
from benchmark.layer_metrics.gdn_update_roofline import KERNEL, is_gdn


def read(ctx):
    return K.share(ctx.trace, K.STEP, KERNEL) if is_gdn(ctx.config) else None

"""The window layers' decode kernel's share of the decode step's device
time: the summed device time of ``strom_window_attn`` inside ``_paged_step``
over the summed device time of the steps that ran it."""

from benchmark.layer_metrics import _swa_trace as T


def read(ctx):
    return T.attn_share(ctx, T.WINDOW) if T.is_swa(ctx.config) else None

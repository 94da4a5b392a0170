"""The full layers' decode kernel's share of the decode step's device time:
the summed device time of ``strom_paged_attn`` inside ``_paged_step`` over
the summed device time of the steps that ran it — ``window_attn_share``'s
twin; the two and the projections around them make ``step_attn_share``."""

from benchmark.layer_metrics import _kernel_trace as K, _swa_trace as T


def read(ctx):
    return K.share(ctx.trace, K.STEP, T.FULL) if T.is_swa(ctx.config) \
        else None

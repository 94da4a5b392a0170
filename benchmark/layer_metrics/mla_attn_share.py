"""The latent-attention kernel's share of the decode step's device time:
the summed device time of ``strom_mla_attn`` inside ``_paged_step`` over the
summed device time of the steps that ran it."""

from benchmark.layer_metrics import _kernel_trace as K, _mla_trace as T


def read(ctx):
    return K.share(ctx.trace, K.STEP, T.KERNEL)

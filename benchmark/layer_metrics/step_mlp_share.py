"""Share of the decode step's device time under ``strom.mlp``: the MLP's
norm, its products (an expert layer's routing, grouped products and shared
expert, ``strom.moe.*``, nested in it) and the residual add.  A product fused
with the next layer's norm's sum counts here, with the product
(``step_attn_share``)."""

from benchmark.layer_metrics import _scope_trace as T


def read(ctx):
    return T.family_share(ctx, T.STEP, ("mlp",))

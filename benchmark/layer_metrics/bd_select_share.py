"""Share of a diffusion server's forward under ``strom.bd.select``: the
confidence (a maximum, an arg-max and a sum of exponentials over rows x
vocabulary float32 logits), the choice of a block's most confident masked
positions and the slots' block state after the forward — what the step pays
to turn logits into commits, beside ``step_head_share``'s product that makes
them."""

from benchmark.layer_metrics import _scope_trace as T


def read(ctx):
    return T.family_share(ctx, T.STEP, ("bd",))

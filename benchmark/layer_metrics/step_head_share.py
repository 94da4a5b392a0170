"""Share of the decode step's device time under ``strom.head`` (final norm,
the logits' product, the per-slot sampler) and ``strom.embed`` (the token
rows, positions and the free-slot masks): what a step pays outside its
layers."""

from benchmark.layer_metrics import _scope_trace as T


def read(ctx):
    return T.family_share(ctx, T.STEP, ("head", "embed"))

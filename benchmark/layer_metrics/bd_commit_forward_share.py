"""Share of a diffusion server's slot-forwards that only WRITE a finished
block's clean K/V to the cache and commit no token (``bd_forwards_write``
over all three phases): 1 / (T + 1) of the forwards that do anything, a third
at the cell's T = 2 — what fusing a finished block's forward with the next
block's first would take away."""

from benchmark.layer_metrics.bd_tokens_per_forward import forwards


def read(ctx):
    n = forwards(ctx.facts.get("timings") or {})
    return 100.0 * n[1] / sum(n) if n else None

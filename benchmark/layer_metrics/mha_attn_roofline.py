"""The paged decode-attention kernel's share of its roofline where every
query head has its own keys and values (30 KV heads, group size 1): over the
calls of ``strom_paged_attn`` inside ``_paged_step`` in the trace (one per
full layer per decode step), the least time their bytes and operations allow
(``costs_olmoh.attn_cost`` at the window's mean live tokens, from the
runner's count: every live K and V row of the 30 KV heads read once, 4
operations a feature a row — 1 operation a byte against a ridge of 240, so
the bytes bound it) over their device time.  The kernel fetches whole blocks
of 128 rows; the rows past a slot's position are the kernel's, not the
algorithm's."""

from benchmark import costs_olmoh
from benchmark.layer_metrics import _kernel_trace as K
from benchmark.layer_metrics.delta_update_roofline import is_olmoh

KERNEL = "strom_paged_attn"


def read(ctx):
    live = ctx.facts.get("live_tokens")
    _, spent, calls = K.totals(K.runs(ctx.trace, K.STEP, KERNEL))
    if not calls or live is None or not is_olmoh(ctx.config):
        return None
    least = K.least_seconds(
        costs_olmoh.attn_cost(ctx.config, ctx.facts["slots"], live),
        ctx.peaks)
    return 100.0 * least * calls / (spent / 1e9)

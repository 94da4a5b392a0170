"""The latent-attention kernel's share of its roofline: over the calls of
``strom_mla_attn`` inside ``_paged_step`` in the trace, the least time their
bytes and operations allow (``costs_mla.mla_attn_cost`` at the window's mean
live latent rows, from the runner's count of live tokens: every live row
read once, 139,264 operations a row at the published sizes — the bytes bound
it on a v5e, 121 operations a byte against a ridge of 240) over their device
time.  The kernel fetches whole blocks of 128 rows; the rows past a slot's
position are the kernel's, not the algorithm's."""

from benchmark import costs_mla
from benchmark.layer_metrics import _kernel_trace as K, _mla_trace as T


def read(ctx):
    live = ctx.facts.get("live_tokens")
    _, spent, calls = K.totals(K.runs(ctx.trace, K.STEP, T.KERNEL))
    if live is None or not calls or not T.is_latent(ctx.config):
        return None
    least = K.least_seconds(costs_mla.mla_attn_cost(
        ctx.config, ctx.facts["slots"], live), ctx.peaks)
    return 100.0 * least * calls / (spent / 1e9)

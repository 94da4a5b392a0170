"""The state-update kernel's share of its roofline: over its calls in the
trace (one per mamba layer per decode step), the least time the call's bytes
and operations allow (``costs_hybrid.ssm_update_cost``: the state read and
written once each — memory-bound) over its device time."""

from benchmark import costs_hybrid
from benchmark.layer_metrics import _kernel_trace as T

KERNEL = "strom_ssm_update"


def read(ctx):
    calls = T.events(ctx.trace, KERNEL)
    if not calls or "mamba_n_heads" not in ctx.config:
        return None
    least = T.least_seconds(
        costs_hybrid.ssm_update_cost(ctx.config, ctx.facts["slots"]),
        ctx.peaks)
    return 100.0 * least * len(calls) / sum(t for _, t in calls)

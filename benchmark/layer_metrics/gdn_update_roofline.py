"""The delta-rule state-update kernel's share of its roofline: over the calls
of ``strom_gdn_update`` inside ``_paged_step`` in the trace (one per
delta-rule layer per decode step), the least time a call's bytes allow
(``costs_gdn.update_cost``: every slot's state read and written once in
float32, the step's operands beside it; its 6 operations a state element are
two orders under the ridge, so the bytes bound it) over the calls' device
time."""

from benchmark import costs_gdn
from benchmark.layer_metrics import _kernel_trace as K

KERNEL = "strom_gdn_update"


def is_gdn(config: dict) -> bool:
    return "linear_num_value_heads" in config


def read(ctx):
    _, spent, calls = K.totals(K.runs(ctx.trace, K.STEP, KERNEL))
    if not calls or not is_gdn(ctx.config):
        return None
    least = K.least_seconds(
        costs_gdn.update_cost(ctx.config, ctx.facts["slots"]), ctx.peaks)
    return 100.0 * least * calls / (spent / 1e9)

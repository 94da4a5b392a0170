"""The delta-rule state-update kernel's share of its roofline in an
Olmo-Hybrid-shaped configuration: over the calls of ``strom_gdn_update``
inside ``_paged_step`` in the trace (one per delta-rule layer per decode
step), the least time a call's bytes allow (``costs_olmoh.update_cost``:
every slot's state read and written once in float32 at its UNPADDED size, 96
x 192 a head, the step's operands beside it; its 6 operations a state element
are two orders under the ridge, so the bytes bound it) over the calls' device
time.  (``gdn_update_roofline`` reads the same kernel with Qwen3-Next's
costs.)"""

from benchmark import costs_olmoh
from benchmark.layer_metrics import _kernel_trace as K

KERNEL = "strom_gdn_update"


def is_olmoh(config: dict) -> bool:
    return config.get("model_type") == "olmo_hybrid"


def read(ctx):
    _, spent, calls = K.totals(K.runs(ctx.trace, K.STEP, KERNEL))
    if not calls or not is_olmoh(ctx.config):
        return None
    least = K.least_seconds(
        costs_olmoh.update_cost(ctx.config, ctx.facts["slots"]), ctx.peaks)
    return 100.0 * least * calls / (spent / 1e9)

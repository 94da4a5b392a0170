"""The paged attention kernel's share of its roofline at R rows a slot (R x
the group's heads are the query rows of a KV head: 32 at the cell's 4 x 8):
over the calls of ``strom_paged_attn`` inside ``_paged_step`` in the trace
(one a layer a forward), the least time their bytes and operations allow
(``costs_sdar.attn_cost`` at the window's mean live tokens, from the runner's
count: every live K and V row read ONCE for all the slot's rows, 4 operations
a feature a row of K a query row) over their device time.  The kernel fetches
whole blocks of 128 rows; the rows past a slot's block are the kernel's, not
the algorithm's."""

from benchmark import costs_sdar
from benchmark.layer_metrics import _kernel_trace as K
from benchmark.layer_metrics.bd_step_roofline import is_sdar, rows_a_slot

KERNEL = "strom_paged_attn"


def read(ctx):
    live = ctx.facts.get("live_tokens")
    _, spent, calls = K.totals(K.runs(ctx.trace, K.STEP, KERNEL))
    if not calls or live is None or not is_sdar(ctx.config):
        return None
    least = K.least_seconds(
        costs_sdar.attn_cost(ctx.config, ctx.facts["slots"], live,
                             rows_a_slot(ctx.config)), ctx.peaks)
    return 100.0 * least * calls / (spent / 1e9)

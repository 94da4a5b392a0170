"""The delta-rule prefill scan's share of its roofline in an
Olmo-Hybrid-shaped configuration: over the calls of ``strom_gdn_scan`` in the
trace (one per delta-rule layer per admission), the least time each call's
bytes and operations allow (``costs_olmoh.scan_cost``) over the calls'
device time.  The operations are THE RECURRENCE'S OWN — 6 dk dv a head a
valid row — not the chunked form's extra products, so no choice of chunk can
read over 100 %.  A call's padded rows are read off its result's shape
((prompts, heads, chunks, rows a chunk, dv)); the valid share of them is the
window's, from the server's counters (``scan_tokens`` over
``prefill_tokens``)."""

from benchmark import costs_olmoh
from benchmark.layer_metrics import _kernel_trace as K
from benchmark.layer_metrics.delta_update_roofline import is_olmoh

KERNEL = "strom_gdn_scan"


def read(ctx):
    calls = K.events(ctx.trace, KERNEL)
    t = ctx.facts.get("timings") or {}
    if (not calls or not is_olmoh(ctx.config) or not t.get("scan_tokens")
            or not t.get("prefill_tokens")):
        return None
    valid = t["scan_tokens"] / t["prefill_tokens"]
    least = spent = 0.0
    for name, seconds in calls:
        dims = K.first_result_dims(name)       # o: (b, H, chunks, c, dv)
        if dims is None or len(dims) != 5:
            continue
        rows = dims[0] * dims[2] * dims[3]
        least += K.least_seconds(
            costs_olmoh.scan_cost(ctx.config, dims[0], valid * rows),
            ctx.peaks)
        spent += seconds
    return 100.0 * least / spent if spent else None

"""The prefill scan kernel's share of its roofline: over its calls in the
trace (one per mamba layer per admission), the least time each call's bytes
and operations allow (``costs_hybrid.ssm_scan_cost`` at the call's own padded
row count, read off its result's shape) over the calls' device time."""

from benchmark import costs_hybrid
from benchmark.layer_metrics import _kernel_trace as T

KERNEL = "strom_ssm_scan"


def read(ctx):
    calls = T.events(ctx.trace, KERNEL)
    if not calls or "mamba_n_heads" not in ctx.config:
        return None
    least = spent = 0.0
    for name, seconds in calls:
        dims = T.first_result_dims(name)       # y: (1, H, rows, P)
        if dims is None or len(dims) != 4:
            continue
        least += T.least_seconds(
            costs_hybrid.ssm_scan_cost(ctx.config, dims[0] * dims[2]),
            ctx.peaks)
        spent += seconds
    return 100.0 * least / spent if spent else None

"""The decode step's share of its memory roofline: bytes one step must read
(weights once + the live keys and values, from shapes: ``costs.py``) over the
chip's HBM bandwidth, over the step's median device time.  The step is
memory-bound at 16 rows: its FLOP bound is far below (PERF.md §3)."""

from benchmark import costs, xplane
from benchmark.layer_metrics.decode_step_dev_ms import PROGRAM


def read(ctx):
    live = ctx.facts.get("live_tokens")
    ms = xplane.median_program_ms(ctx.trace, PROGRAM) if ctx.trace else None
    if live is None or not ms:
        return None
    need_bytes = costs.decode_step_bytes(ctx.config, ctx.facts["slots"], live)
    need_flops = costs.decode_step_flops(ctx.config, ctx.facts["slots"], live)
    least_s = max(need_bytes / ctx.peaks["hbm_bytes_per_s"],
                  need_flops / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least_s / (ms / 1e3)

"""An LFM2-MoE decode step as a share of its memory roofline: the bytes one
step must move (``costs_moe.decode_step_bytes``: everything outside the
experts and the tied head once, the experts the load histogram says were
touched once each, the conv tails read and written, the live keys and values
of the attention layers) over the chip's HBM bandwidth — or its operations
over the bf16 peak, whichever is more — over the step's median device time."""

from benchmark import costs_moe, xplane
from benchmark.layer_metrics import _kernel_trace as T


def read(ctx):
    live = ctx.facts.get("live_tokens")
    t = ctx.facts.get("timings") or {}
    ms = xplane.median_program_ms(ctx.trace, T.STEP) if ctx.trace else None
    if live is None or not ms or not t.get("moe_calls") or not t.get("steps"):
        return None
    slots = ctx.facts["slots"]
    touched = t["moe_experts_touched"] / t["steps"]      # all layers, a step
    least = T.least_seconds(
        (costs_moe.decode_step_bytes(ctx.config, slots, live, touched),
         costs_moe.decode_step_flops(ctx.config, slots, live)), ctx.peaks)
    return 100.0 * least / (ms / 1e3)

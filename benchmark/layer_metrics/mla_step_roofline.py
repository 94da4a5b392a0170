"""A latent-attention decode step as a share of its roofline: the bytes one
step must read (``costs_mla.decode_step_bytes``: the weights outside the
routed experts once, the experts the load histogram says were touched once
each, every live latent row of every layer) over the chip's HBM bandwidth —
or its operations over the bf16 peak, whichever is more — over the step's
median device time."""

from benchmark import costs_mla, xplane
from benchmark.layer_metrics import _kernel_trace as K, _mla_trace as T


def read(ctx):
    live = ctx.facts.get("live_tokens")
    t = ctx.facts.get("timings") or {}
    ms = xplane.median_program_ms(ctx.trace, K.STEP) if ctx.trace else None
    if (live is None or not ms or not t.get("steps")
            or not T.is_latent(ctx.config)):
        return None
    slots, steps = ctx.facts["slots"], t["steps"]
    least = K.least_seconds(
        (costs_mla.decode_step_bytes(
            ctx.config, slots, live, t.get("moe_experts_touched", 0) / steps),
         costs_mla.decode_step_flops(
            ctx.config, slots, live, t.get("moe_pairs", 0) / steps)),
        ctx.peaks)
    return 100.0 * least / (ms / 1e3)

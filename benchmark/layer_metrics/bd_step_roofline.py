"""A diffusion server's forward as a share of its roofline: the least time
the bytes one forward of R rows a slot must move (``costs_sdar.step_bytes``:
everything outside the experts and the head once, the experts the load
histogram says were touched once each, the live keys and values of every
layer once for all of a slot's rows) and its operations
(``costs_sdar.step_flops``) allow on the chip, over ``_paged_step``'s median
device time — selection and confidence included in the time, left out of the
work."""

from benchmark import costs_sdar, xplane
from benchmark.layer_metrics import _kernel_trace as T


def is_sdar(config: dict) -> bool:
    return config.get("model_type") == "sdar_moe"


def rows_a_slot(config: dict) -> int:
    return config["serving"]["diffusion"]["block_length"]


def read(ctx):
    live = ctx.facts.get("live_tokens")
    t = ctx.facts.get("timings") or {}
    ms = xplane.median_program_ms(ctx.trace, T.STEP) if ctx.trace else None
    if (live is None or not ms or not t.get("moe_calls")
            or not t.get("steps") or not is_sdar(ctx.config)):
        return None
    slots, rows = ctx.facts["slots"], rows_a_slot(ctx.config)
    touched = t["moe_experts_touched"] / t["steps"]      # all layers, a step
    least = T.least_seconds(
        (costs_sdar.step_bytes(ctx.config, slots, live, touched, rows),
         costs_sdar.step_flops(ctx.config, slots, live, rows)), ctx.peaks)
    return 100.0 * least / (ms / 1e3)

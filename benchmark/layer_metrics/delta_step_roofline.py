"""An Olmo-Hybrid-shaped configuration's decode step as a share of its
roofline: the bytes one step must move (``costs_olmoh.decode_step_bytes``:
every weight once, every slot's recurrent state read AND written at its
unpadded size, the live K and V rows of the full layers) over the chip's HBM
bandwidth — or its operations over the bf16 peak, whichever is more — over
the step's median device time: the whole step's share of the chip's peak."""

from benchmark import costs_olmoh, xplane
from benchmark.layer_metrics import _kernel_trace as K
from benchmark.layer_metrics.delta_update_roofline import is_olmoh


def read(ctx):
    t = ctx.facts.get("timings") or {}
    steps, live = t.get("steps"), ctx.facts.get("live_tokens")
    ms = xplane.median_program_ms(ctx.trace, K.STEP) if ctx.trace else None
    if not steps or live is None or not ms or not is_olmoh(ctx.config):
        return None
    slots = ctx.facts["slots"]
    least = K.least_seconds(
        (costs_olmoh.decode_step_bytes(ctx.config, slots, live),
         costs_olmoh.decode_step_flops(ctx.config, slots, live)), ctx.peaks)
    return 100.0 * least / (ms / 1e3)

"""A window-attention configuration's decode step as a share of its
roofline: the bytes one step must read (``costs_swa.decode_step_bytes``: the
weights outside the routed experts once, the experts the load histogram says
were touched once each, every live K and V row of the full layers, the live
rows of the window layers' rings) over the chip's HBM bandwidth — or its
operations over the bf16 peak, whichever is more — over the step's median
device time: the whole step's share."""

from benchmark import costs_swa, xplane
from benchmark.layer_metrics import _kernel_trace as K, _swa_trace as T


def read(ctx):
    mean = T.per_step(ctx.facts)
    ms = xplane.median_program_ms(ctx.trace, K.STEP) if ctx.trace else None
    if not mean or not ms or not T.is_swa(ctx.config):
        return None
    least = K.least_seconds(
        (costs_swa.decode_step_bytes(
            ctx.config, mean["slots"], mean["live"], mean["touched"],
            mean["window_rows"]),
         costs_swa.decode_step_flops(
            ctx.config, mean["slots"], mean["live"], mean["pairs"],
            mean["window_rows"])), ctx.peaks)
    return 100.0 * least / (ms / 1e3)

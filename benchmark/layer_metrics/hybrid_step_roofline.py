"""A hybrid decoder's decode step as a share of its memory roofline: the
bytes one step must move (``costs_hybrid.decode_step_bytes``: weights and the
tied head once, every slot's recurrent state read and written, the live keys
and values of the attention layers) over the chip's HBM bandwidth — or its
operations over the bf16 peak, whichever is more — over the step's median
device time.  ``decode_step_roofline`` counts a dense decoder (it would read
7.9 GB for a step that moves 16.3) and does not list a hybrid cell."""

from benchmark import costs_hybrid, xplane
from benchmark.layer_metrics import _kernel_trace as T
from benchmark.layer_metrics.decode_step_dev_ms import PROGRAM


def read(ctx):
    live = ctx.facts.get("live_tokens")
    ms = xplane.median_program_ms(ctx.trace, PROGRAM) if ctx.trace else None
    if live is None or not ms or "mamba_n_heads" not in ctx.config:
        return None
    slots = ctx.facts["slots"]
    least = T.least_seconds(
        (costs_hybrid.decode_step_bytes(ctx.config, slots, live),
         costs_hybrid.decode_step_flops(ctx.config, slots, live)), ctx.peaks)
    return 100.0 * least / (ms / 1e3)

"""The state-update kernel's share of the decode step's device time: its
summed device time over the summed device time of program ``_paged_step`` in
the traced span — how much of a step is the recurrent state's traffic."""

from benchmark import xplane
from benchmark.layer_metrics import _kernel_trace as T
from benchmark.layer_metrics.decode_step_dev_ms import PROGRAM
from benchmark.layer_metrics.ssm_update_roofline import KERNEL


def read(ctx):
    calls = T.events(ctx.trace, KERNEL)
    steps = xplane.program_durations_ms(ctx.trace, PROGRAM) if ctx.trace \
        else []
    if not calls or not steps:
        return None
    return 100.0 * sum(t for _, t in calls) / (sum(steps) / 1e3)

"""The admission program's share of the device's busy time: the summed
device time of ``_paged_prefill`` over the seconds in which any operation
ran on the device, in the traced part of the window."""

from benchmark import xplane

PROGRAM = "_paged_prefill"


def read(ctx):
    if not ctx.trace:
        return None
    d = xplane.program_durations_ms(ctx.trace, PROGRAM)
    busy = xplane.busy_seconds(ctx.trace)
    return 100.0 * sum(d) / 1e3 / busy if d and busy else None

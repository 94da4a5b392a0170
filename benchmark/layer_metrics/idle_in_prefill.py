"""Share of the traced window in which the device idled while the host was
inside an admission's prefill call (the gap's start lies under the program's
``strom.serve.prefill`` span)."""

from benchmark import program_spans as ps


def read(ctx):
    idle = ps.admission_idle(ctx.trace, ctx.trace_window_s)
    return idle[ps.ADMISSION[0]] if idle else None

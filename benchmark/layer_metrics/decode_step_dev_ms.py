"""Median device duration of the jitted decode step (program
``_paged_step``) in the traced part of the window."""

from benchmark import xplane

PROGRAM = "_paged_step"


def read(ctx):
    return xplane.median_program_ms(ctx.trace, PROGRAM) if ctx.trace else None

"""The longest ``step_many`` call of the traced span, on the host (the
program's ``strom.serve.step`` span): a host stall shows here and in no
median."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.longest_ms(ctx.trace, "strom.serve.step")

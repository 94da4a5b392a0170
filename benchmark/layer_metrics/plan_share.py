"""Share of the traced window the restoring thread spent planning reads: the
program's ``strom.restore.plan`` span (the safetensors slice plan,
``plan_and_submit`` and ``join_pieces`` of one row span)."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.share(ctx.trace, "strom.restore.plan", ps.LOAD,
                    ctx.trace_window_s)

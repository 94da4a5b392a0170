"""Share of the traced window the restoring thread spent waiting for the engine
to finish a chunk's read: the program's ``strom.restore.read_wait`` span around
each ``wait()``."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.share(ctx.trace, "strom.restore.read_wait", ps.LOAD,
                    ctx.trace_window_s)

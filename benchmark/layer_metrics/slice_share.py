"""Share of the traced window the restoring thread spent gathering column shards
on the host: the program's ``strom.restore.slice`` span (the strided copy of a
chunk's columns for one device; the CRC pass under STROM_VERIFY).  0 where no
tensor is cut along its columns."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.share(ctx.trace, "strom.restore.slice", ps.LOAD,
                    ctx.trace_window_s)

"""Share of the traced window the restoring thread spent retiring staging
buffers: the program's ``strom.restore.retire`` span (``StagingRetirePool.push``
and ``.flush``, which block on transfers when the pool is full)."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.share(ctx.trace, "strom.restore.retire", ps.LOAD,
                    ctx.trace_window_s)

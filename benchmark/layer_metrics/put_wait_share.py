"""Share of the traced window the restoring thread spent waiting for room in
the transfer stage: the program's ``strom.restore.put_wait`` span
(``ops/bridge.PutStage.put``, which blocks while ``depth`` chunks are with the
workers).  Near zero: the reading thread is the critical path; large: the puts
are.  0 where the program has restore spans and no stage (the parent of PR
45)."""

from benchmark import program_spans as ps


def read(ctx):
    return ps.share(ctx.trace, "strom.restore.put_wait", ps.LOAD,
                    ctx.trace_window_s)

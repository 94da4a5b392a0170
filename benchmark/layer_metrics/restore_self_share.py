"""Share of the traced window spent inside ``strom.restore.load`` under none
of its parts' spans (plan, read_wait, slice, ``strom.h2d``, retire, join): the
restore loop's own host work."""

from benchmark import program_spans as ps


def read(ctx):
    s = ps.restore_self_seconds(ctx.trace)
    if s is None or not ctx.trace_window_s:
        return None
    return 100.0 * s / ctx.trace_window_s

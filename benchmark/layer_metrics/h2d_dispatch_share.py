"""Share of the traced window the host spent inside ``host_to_device``'s
``strom.h2d`` annotations (the program writes them into the profiler's
trace): dispatching transfers, not reading files."""

from benchmark import xplane


def read(ctx):
    if not ctx.trace or "restores" not in ctx.facts:
        return None
    return 100.0 * xplane.host_seconds(ctx.trace, "strom.h2d") \
        / ctx.trace_window_s

"""1 - union of the device-busy intervals over the traced window."""

from benchmark import xplane


def read(ctx):
    if not ctx.trace:
        return None
    return xplane.idle_share(ctx.trace, ctx.trace_window_s)

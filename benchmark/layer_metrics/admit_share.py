"""Share of the window the serving loop spent in admission (eager prefill):
``srv.timings["admit_s"]`` over the window, host clock."""


def read(ctx):
    t = ctx.facts.get("timings")
    return 100.0 * t["admit_s"] / ctx.window_s if t else None

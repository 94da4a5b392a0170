"""Share of the window the serving loop spent inside the prefill calls of its
admissions: the program's counter ``prefill_s`` (``srv.timings``; host
seconds, the eager prefill dispatches op by op) over the window.  A part of
``admit_share``."""


def read(ctx):
    t = ctx.facts.get("timings") or {}
    if "prefill_s" not in t:
        return None
    return 100.0 * t["prefill_s"] / ctx.window_s

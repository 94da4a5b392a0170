"""Mean number of prompts one prefill program held in the window: the
program's counters ``admits`` over ``prefill_calls`` (``srv.timings``; a
count, the same on any device).  1.0 where every admission runs a program of
its own; a program from before admissions were groups has no
``prefill_calls`` and gives nothing."""


def read(ctx):
    t = ctx.facts.get("timings") or {}
    if not t.get("prefill_calls"):
        return None
    return t["admits"] / t["prefill_calls"]

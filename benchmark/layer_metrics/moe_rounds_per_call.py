"""Layouts an expert layer's call ran, the window's mean over the calls of
the decode steps and of the admissions together: ``moe_rounds`` +
``moe_rounds_prefill`` over ``moe_calls`` + ``moe_calls_prefill``
(``srv.timings``; counts read back from the device, the same on any
device).  1.0 unless a call's local pairs overflowed the bounded layout a
device that holds a share of the experts lays out (``models/moe.pair_bound``)
and took further rounds through it; a step's call is at the floor and takes
one.  A program from before the counter gives nothing."""


def read(ctx):
    t = ctx.facts.get("timings") or {}
    calls = t.get("moe_calls", 0) + t.get("moe_calls_prefill", 0)
    if not calls or "moe_rounds" not in t:
        return None
    return (t["moe_rounds"] + t.get("moe_rounds_prefill", 0)) / calls

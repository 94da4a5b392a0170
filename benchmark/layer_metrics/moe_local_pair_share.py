"""Share of the (row, expert) pairs the router selected in the decode steps
that fell on an expert this device holds and were computed here:
``moe_pairs`` over ``moe_pairs_routed`` (``srv.timings``; counts, the same
on any device), in percent.  100 where every expert is held; 12 of 384 would
give 3.1 if routing were even."""


def read(ctx):
    t = ctx.facts.get("timings") or {}
    if not t.get("moe_pairs_routed"):
        return None
    return 100.0 * t["moe_pairs"] / t["moe_pairs_routed"]

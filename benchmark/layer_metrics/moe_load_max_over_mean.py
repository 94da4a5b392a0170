"""How uneven the router's load is in the decode steps: the busiest expert's
rows over the mean expert's, averaged over the calls of the expert layers in
the window (``srv.timings``: ``moe_load_max`` x experts / ``moe_pairs``).  1
is a uniform router."""


def read(ctx):
    t = ctx.facts.get("timings") or {}
    if not t.get("moe_pairs") or "num_experts" not in ctx.config:
        return None
    return t["moe_load_max"] * ctx.config["num_experts"] / t["moe_pairs"]

"""Median over the sampled requests of first token delivered minus the time
the request was DUE (not submitted): a stall of the loop counts against every
request it delays.  A failed request has no first token and counts as the
drain limit."""

import statistics


def ttfts_ms(ctx):
    reqs = [r for r in ctx.facts.get("requests", []) if r.get("due") is not None]
    worst = 1000.0 * (ctx.window_s + ctx.traffic.get("drain_limit_s", 0))
    return [1000.0 * (r["t_first"] - r["due"]) if r["t_first"] is not None
            else worst for r in reqs]


def read(ctx):
    v = ttfts_ms(ctx)
    return statistics.median(v) if v else None

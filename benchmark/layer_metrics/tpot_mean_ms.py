"""Mean gap between output tokens: sum over sampled requests of (last token
delivered - first token delivered) over sum of (tokens - 1).  One ratio over
some thousands of gaps, not a median of some tens of per-request ratios.
Recorded, not judged: it spread by 6-9 % over runs of one seed (PERF.md)."""


def read(ctx):
    reqs = [r for r in ctx.facts.get("requests", [])
            if r.get("due") is not None and r["t_last"] is not None
            and r["n"] > 1]
    gaps = sum(r["n"] - 1 for r in reqs)
    if not gaps:
        return None
    return 1000.0 * sum(r["t_last"] - r["t_first"] for r in reqs) / gaps

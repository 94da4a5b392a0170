"""Median over sampled requests of each request's own mean token gap (PR 22's
judged metric, kept as a recorded number: it sits on a ladder of values)."""

import statistics


def read(ctx):
    v = [1000.0 * (r["t_last"] - r["t_first"]) / (r["n"] - 1)
         for r in ctx.facts.get("requests", [])
         if r.get("due") is not None and r["t_last"] is not None
         and r["n"] > 1]
    return statistics.median(v) if v else None

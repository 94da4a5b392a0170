"""Median of admitted minus due (the program stamps the admission; the due
time is the benchmark's)."""

import statistics


def read(ctx):
    w = [1000.0 * (r["t_admit"] - r["due"])
         for r in ctx.facts.get("requests", [])
         if r.get("due") is not None and r.get("t_admit") is not None]
    return statistics.median(w) if w else None

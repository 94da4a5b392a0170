"""How late the load generator ran: submit minus due, 90th percentile.  It
can only submit between two ``step_many`` calls."""

import statistics


def read(ctx):
    late = [1000.0 * (r["t_submit"] - r["due"])
            for r in ctx.facts.get("requests", [])
            if r.get("due") is not None and r["t_submit"] is not None]
    return statistics.quantiles(late, n=10)[-1] if len(late) >= 2 else None

"""Share of the tokens handed to prefill in the window that were padding:
1 - ``prompt_tokens`` / ``prefill_tokens`` (the program's counters; a count,
the same on any device)."""


def read(ctx):
    t = ctx.facts.get("timings") or {}
    if not t.get("prefill_tokens"):
        return None
    return 100.0 * (1.0 - t["prompt_tokens"] / t["prefill_tokens"])

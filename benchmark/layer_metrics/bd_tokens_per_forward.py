"""Tokens a slot-forward commits on a diffusion server: the positions
committed in the window (``timings["bd_tokens"]``) over the slot-forwards
dispatched in it for slots that held a request — denoising, cache-writing and
holding position.  With T denoising steps a block of Bl the first two make
Bl / (T + 1) by construction (4 / 3 at the cell's 4 and 2); less is forwards
spent holding position: a slot past its last block, until the batch's
readback retires it.  ``None`` where the program has no such counters."""


def forwards(t: dict):
    """(denoising, cache-writing, holding) slot-forwards of a timings
    difference, or None where the program counts none."""
    got = tuple(t.get("bd_forwards_" + phase)
                for phase in ("denoise", "write", "hold"))
    return got if None not in got and sum(got) else None


def read(ctx):
    t = ctx.facts.get("timings") or {}
    n = forwards(t)
    return t["bd_tokens"] / sum(n) if n else None

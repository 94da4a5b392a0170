"""Grid steps ONE layer's decode attention call issues, the window's mean per
decode step: the program's counter ``attn_grid_steps`` over ``steps``
(``srv.timings``; a count from the host's position mirror, the same on any
device).  ``strom_paged_attn`` issues a step a live table entry and one a
free slot; ``strom_mla_attn`` still walks slots x the longest slot's entries
in fours, dead steps included.  A program from before the counter gives
nothing."""


def read(ctx):
    t = ctx.facts.get("timings") or {}
    if not t.get("steps") or not t.get("attn_grid_steps"):
        return None
    return t["attn_grid_steps"] / t["steps"]

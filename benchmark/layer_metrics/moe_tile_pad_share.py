"""Share of the rows the grouped product ran in the decode steps that were
tile padding: 1 - ``moe_pairs`` / ``moe_rows_computed`` (``srv.timings``),
in percent.  Every group starts on a tile boundary, so a group of 5 rows in
a tile of 16 computes 11 rows of zeros."""


def read(ctx):
    t = ctx.facts.get("timings") or {}
    if not t.get("moe_rows_computed"):
        return None
    return 100.0 * (1.0 - t["moe_pairs"] / t["moe_rows_computed"])

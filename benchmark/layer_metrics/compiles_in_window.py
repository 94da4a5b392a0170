"""Backend compiles JAX reported inside the window (should be 0)."""


def read(ctx):
    c = ctx.facts.get("compiles_in_window")
    return None if c is None else float(c)

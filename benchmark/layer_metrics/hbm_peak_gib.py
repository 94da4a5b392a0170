"""Peak device memory on the fullest chip, after the window and before the
reference runs."""


def read(ctx):
    return ctx.memory_peak_bytes / 2**30 if ctx.memory_peak_bytes else None

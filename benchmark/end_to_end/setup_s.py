"""Start of the process to the start of the window: loading, weights,
warm-up, compilation where a run compiles (and the chat cell's lead-in)."""


def read(ctx):
    return ctx.setup_s

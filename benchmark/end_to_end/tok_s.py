"""Output tokens delivered to the host inside the window over the window's
seconds (the window ends with the serving call that crosses ``--seconds``)."""


def read(ctx):
    if ctx.facts.get("open_loop") is not False:
        return None
    return ctx.facts["tokens_in_window"] / ctx.window_s

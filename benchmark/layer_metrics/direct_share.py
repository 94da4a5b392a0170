"""Share of the engine's bytes read O_DIRECT: ``bytes_direct`` over
``bytes_direct + bytes_fallback`` inside the window (0 where the checkout's
file system refuses O_DIRECT and every read is buffered)."""


def read(ctx):
    e = ctx.facts.get("engine")
    if not e:
        return None
    total = e.get("bytes_direct", 0) + e.get("bytes_fallback", 0)
    return 100.0 * e.get("bytes_direct", 0) / total if total else None

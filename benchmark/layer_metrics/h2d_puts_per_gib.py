"""``device_put``s a restore issues for each GiB it lands on the devices:
the engine's ``restore_puts_staged`` + ``restore_puts_inline`` (arrays put
out of staging views, on a worker of the transfer stage or on the reading
thread) + ``restore_puts_assembled`` (a column shard gathered into one host
buffer a (tensor, device) and put whole, counted once under its own name)
over the GiB of ``bytes_to_device`` inside the window.  A put costs per
call, not per byte (PERF.md §5), so fewer puts a GiB is less serialised
host work a restore.  A program without the third counter (the parent of
PR 49) reads the first two."""


def read(ctx):
    e = ctx.facts.get("engine")
    if not e or not e.get("bytes_to_device"):
        return None
    puts = sum(e.get(k, 0) for k in ("restore_puts_staged",
                                     "restore_puts_inline",
                                     "restore_puts_assembled"))
    return puts / (e["bytes_to_device"] / 2**30)

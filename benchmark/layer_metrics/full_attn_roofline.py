"""A full layer's decode kernel as a share of its roofline: over the calls of
``strom_paged_attn`` inside ``_paged_step`` in the trace, the least time
their bytes and operations allow (``costs_swa.attn_cost`` at the window's
mean live tokens, from the runner's count: every live K and V row of 4 KV
heads read once, 40,960 operations a row — the bytes bound it on a v5e, 16
operations a byte against a ridge of 240) over their device time.  The
kernel fetches whole blocks of 128 rows; the rows past a slot's position are
the kernel's, not the algorithm's."""

from benchmark.layer_metrics import _swa_trace as T


def read(ctx):
    return T.attn_roofline(ctx, "full", T.FULL)

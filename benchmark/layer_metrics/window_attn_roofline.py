"""A window layer's decode kernel as a share of its roofline: over the calls
of ``strom_window_attn`` inside ``_paged_step`` in the trace, the least time
their bytes and operations allow (``costs_swa.attn_cost`` at the window's
mean live ring rows, from the program's counter ``window_rows_live``: at
most 128 rows a slot, K and V of 8 KV heads read once) over their device
time.  The kernel fetches the two ring blocks that hold those rows whole —
256 rows for 128: the other half is the kernel's, not the algorithm's, so
this share cannot pass 50 % for slots past their first 128 rows."""

from benchmark.layer_metrics import _swa_trace as T


def read(ctx):
    return T.attn_roofline(ctx, "window", T.WINDOW)

"""Share of the decode step's device time under ``strom.attn.*``: the
mixer's norm and projections (``strom.attn.proj``), the row writer and the
attention kernel (``strom.attn.paged`` / ``strom.attn.mla``), ``wo`` and the
residual add (``strom.attn.out``).  A fusion that spans two families
counts where XLA's one label puts it, and that is the product's: m7b's ``wo``
fused with the MLP norm's sum of squares reads ``strom.attn.out`` (PERF.md
§5).  Not here: weights the compiler stages behind operations that never run
(m7b's ``wq`` and ``wk``, transposed every step: they carry no label and
read as ``step_unscoped_share``)."""

from benchmark.layer_metrics import _scope_trace as T


def read(ctx):
    return T.family_share(ctx, T.STEP, ("attn",))

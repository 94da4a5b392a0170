"""Share of the admission program's device time (``_paged_prefill``, all
compiled shapes) in the layers' mixers: ``strom.attn.*`` (projections, cache
write, attention, ``wo``), ``strom.ssm.*`` (projections, conv, the scan) and
``strom.conv``.  The rest is the MLPs, the head and the program's own gather
and scatter (``strom.prefill.*``)."""

from benchmark.layer_metrics import _scope_trace as T


def read(ctx):
    return T.family_share(ctx, T.PREFILL, ("attn", "ssm", "conv"))

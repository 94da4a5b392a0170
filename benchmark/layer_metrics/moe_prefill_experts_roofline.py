"""The grouped product's share of its roofline in admission: as
``moe_experts_roofline`` over the calls inside ``_paged_prefill``, at the
admissions' own mean pairs (valid prompt rows x k) and touched experts."""

from benchmark.layer_metrics import _kernel_trace as K, _moe_trace as T


def read(ctx):
    return T.experts_roofline(ctx, K.PREFILL, "_prefill")

"""``step_unscoped_share`` for the admission program ``_paged_prefill``, all
its compiled shapes together."""

from benchmark.layer_metrics import _scope_trace as T


def read(ctx):
    return T.family_share(ctx, T.PREFILL, (None,))

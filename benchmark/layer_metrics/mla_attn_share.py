"""The latent-attention kernel's share of the decode step's device time:
the summed device time of ``strom_mla_attn`` inside ``_paged_step`` over the
summed device time of the steps that ran it."""

from benchmark.layer_metrics import _mla_trace as T


def read(ctx):
    runs = T.step_runs(ctx.trace)
    if not runs:
        return None
    return 100.0 * sum(k for _, k, _ in runs) / sum(ns for ns, _, _ in runs)

"""Plain reference of configuration ``lfm2-24b-a2b``: the LFM2-MoE decoder of
``benchmark/reference/lfm2_moe.py`` (gated short convs, q/k-normed GQA with
rotary, sigmoid-routed experts as a masked loop over all of them; float32,
highest matmul precision, no cache, no kernels).  The comparison and its
limits are declared in ``lfm2-24b-a2b.json`` under ``correct``."""

from benchmark.reference.lfm2_moe import logits_at  # noqa: F401

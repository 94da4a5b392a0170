"""Plain reference of configuration ``mistral-7b-v0.3``: the dense
grouped-query decoder of ``benchmark/reference/dense_gqa.py`` (float32,
highest matmul precision, no cache, no kernels).  The comparison and its
limits are declared in ``mistral-7b-v0.3.json`` under ``correct``."""

from benchmark.reference.dense_gqa import logits_at  # noqa: F401

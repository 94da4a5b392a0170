"""Plain reference of configuration ``granite-4.0-h-micro``: the hybrid
decoder of ``benchmark/reference/granite_hybrid.py`` (Mamba-2 recurrence
token by token, attention without positional encoding, float32, highest
matmul precision, no cache, no kernels).  The comparison and its limits are
declared in ``granite-4.0-h-micro.json`` under ``correct``."""

from benchmark.reference.granite_hybrid import logits_at  # noqa: F401

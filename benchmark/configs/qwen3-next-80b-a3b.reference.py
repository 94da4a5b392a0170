"""Plain reference of configuration ``qwen3-next-80b-a3b``: the
Qwen3-Next-shaped decoder of ``benchmark/reference/qwen3_next.py`` (gated-
delta-rule layers — 16 key and 32 value heads of 128, the recurrence a token
at a time — beside full GQA layers 256 wide under an output gate, rotary on
the first 64 features, zero-centred norms in their published 1 + w form; in
every layer 512-way softmax routing over the 32 experts this chip holds,
beside a shared expert under its sigmoid gate; float32, highest matmul
precision, no cache, no kernels).  The comparison and its limits are declared
in ``qwen3-next-80b-a3b.json`` under ``correct``."""

from benchmark.reference.qwen3_next import logits_at  # noqa: F401

"""Plain reference of configuration ``olmo-hybrid-7b``: the
Olmo-Hybrid-shaped decoder of ``benchmark/reference/olmo_hybrid.py``
(post-norm blocks; gated-delta-rule layers — 30 heads, keys of 96, values of
192, β = 2 sigmoid(b), the recurrence a token at a time — beside full
attention layers of 30 heads of 128 with their own keys and values, q and k
normalised over the whole projection, no rotary; a dense MLP of 11,008; an
untied head over 100,352; float32, highest matmul precision, no cache, no
kernels).  The comparison and its limits are declared in
``olmo-hybrid-7b.json`` under ``correct``."""

from benchmark.reference.olmo_hybrid import logits_at  # noqa: F401

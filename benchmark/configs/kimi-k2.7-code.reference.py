"""Plain reference of configuration ``kimi-k2.7-code``: the DeepSeek-V3-shaped
decoder of ``benchmark/reference/kimi_mla.py`` (latent attention in its
expanded form with YaRN's frequencies and scale, a dense MLP in layer 0, then
384-way sigmoid routing over the 12 experts this chip holds beside a shared
expert; float32, highest matmul precision, no cache, no kernels).  The
comparison and its limits are declared in ``kimi-k2.7-code.json`` under
``correct``."""

from benchmark.reference.kimi_mla import logits_at  # noqa: F401

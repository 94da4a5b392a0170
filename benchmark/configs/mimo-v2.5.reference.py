"""Plain reference of configuration ``mimo-v2.5``: the MiMo-V2-shaped decoder
of ``benchmark/reference/mimo_swa.py`` (full and window-128 GQA layers with
their own KV-head counts and rotary bases, 192-wide keys and 128-wide values,
rotary on the first 64 features, a value scale, a sink column in the window
layers; a dense MLP in layer 0, then 256-way sigmoid routing over the 16
experts this chip holds; float32, highest matmul precision, no cache, no
kernels).  The comparison and its limits are declared in ``mimo-v2.5.json``
under ``correct``."""

from benchmark.reference.mimo_swa import logits_at  # noqa: F401

"""Plain reference of configuration ``sdar-30b-a3b-chat``: the SDAR-MoE
decoder of ``benchmark/reference/sdar_bd.py`` (per-head q/k-normed GQA with
rotary under a mask that is data, softmax-routed experts as a masked loop
over all of them, generation by diffusion over blocks replayed through the
[noisy | clean] two-stream forward; float32, highest matmul precision, no
cache, no kernels).  The comparison and its limits are declared in
``sdar-30b-a3b-chat.json`` under ``correct``."""

from benchmark.reference.sdar_bd import (  # noqa: F401
    generate, logits, replay, gaps, served_choice, control_choice)

"""Runner of the serving cells of a Qwen3-Next-shaped configuration
(``model_type`` qwen3_next: gated-delta-rule layers beside gated attention):
THE timed loop of ``runners/serve.py`` — called, not copied — with the
weights drawn by ``benchmark/weights_gdn.py``.

``serve.run`` reaches its generator through its module global ``W``; this
binds the name to the delta-rule generator for the call, as ``serve_swa.py``
does for mimo (a shim until ``serve.py`` takes the generator from the
configuration: PERF.md §7)."""

from __future__ import annotations

from benchmark import weights_gdn
from benchmark.runners import serve


def run(ctx) -> dict:
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    try:
        cfg = config_from_hf(ctx.config)
    except Exception as e:          # a checkout that cannot read the file
        raise SystemExit(f"benchmark: this checkout's program cannot read "
                         f"a qwen3_next configuration ({e})")
    if "gdn" not in getattr(cfg, "layer_kinds", ()):
        # a checkout from before the program knew the delta rule: fail at
        # once, before a weight is drawn
        raise SystemExit("benchmark: this checkout's program does not serve "
                         "gated-delta-rule layers (config_from_hf gives "
                         "none)")
    dense = serve.W
    serve.W = weights_gdn
    try:
        return serve.run(ctx)
    finally:
        serve.W = dense

"""Runner of the serving cells of an Olmo-Hybrid-shaped configuration
(``model_type`` olmo_hybrid: post-norm blocks, gated-delta-rule layers beside
full attention): THE timed loop of ``runners/serve.py`` — called, not copied
— with the weights drawn by ``benchmark/weights_olmoh.py``.

``serve.run`` reaches its generator through its module global ``W``; this
binds the name to this family's generator for the call, as ``serve_gdn.py``
does for qwen3_next (a shim until ``serve.py`` takes the generator from the
configuration: PERF.md §7)."""

from __future__ import annotations

from benchmark import weights_olmoh
from benchmark.runners import serve


def run(ctx) -> dict:
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    try:
        cfg = config_from_hf(ctx.config)
    except Exception as e:          # a checkout that cannot read the file
        raise SystemExit(f"benchmark: this checkout's program cannot read "
                         f"an olmo_hybrid configuration ({e})")
    if not getattr(cfg, "post_norm", False) \
            or "gdn" not in getattr(cfg, "layer_kinds", ()):
        # a checkout from before the program knew this family (its
        # config_from_hf reads the file as a dense pre-norm decoder): fail
        # at once, before a weight is drawn
        raise SystemExit("benchmark: this checkout's program does not serve "
                         "olmo_hybrid (config_from_hf gives no post-norm "
                         "block and no delta-rule layers)")
    dense = serve.W
    serve.W = weights_olmoh
    try:
        return serve.run(ctx)
    finally:
        serve.W = dense

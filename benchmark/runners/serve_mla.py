"""Runner of the serving cells of a DeepSeek-V3-shaped configuration
(``model_type`` kimi_k2): THE timed loop of ``runners/serve.py`` — called,
not copied — with the weights drawn by ``benchmark/weights_mla.py``.

``serve.run`` reaches its generator through its module global ``W``; this
binds the name to the MLA generator for the call, as ``serve_moe.py`` does
for lfm2 (a shim until ``serve.py`` takes the generator from the
configuration: PERF.md §7)."""

from __future__ import annotations

from benchmark import weights_mla
from benchmark.runners import serve


def run(ctx) -> dict:
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    try:
        cfg = config_from_hf(ctx.config)
    except Exception as e:          # a checkout that cannot read the file
        raise SystemExit(f"benchmark: this checkout's program cannot read "
                         f"a kimi_k2 configuration ({e})")
    if not getattr(cfg, "latent", False):
        # a checkout from before the program knew latent attention reads
        # the file as a dense decoder: fail at once, before a weight is drawn
        raise SystemExit("benchmark: this checkout's program does not serve "
                         "latent attention (config_from_hf gives no latent "
                         "config)")
    dense = serve.W
    serve.W = weights_mla
    try:
        return serve.run(ctx)
    finally:
        serve.W = dense

"""Runner of the serving cells of a MiMo-V2-shaped configuration
(``model_type`` mimo_v2: window and full attention layers mixed): THE timed
loop of ``runners/serve.py`` — called, not copied — with the weights drawn by
``benchmark/weights_swa.py``.

``serve.run`` reaches its generator through its module global ``W``; this
binds the name to the window-attention generator for the call, as
``serve_mla.py`` does for kimi (a shim until ``serve.py`` takes the generator
from the configuration: PERF.md §7)."""

from __future__ import annotations

from benchmark import weights_swa
from benchmark.runners import serve


def run(ctx) -> dict:
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    try:
        cfg = config_from_hf(ctx.config)
    except Exception as e:          # a checkout that cannot read the file
        raise SystemExit(f"benchmark: this checkout's program cannot read "
                         f"a mimo_v2 configuration ({e})")
    if not getattr(cfg, "window_layers", ()):
        # a checkout from before the program knew window layers would read
        # the file as a 64-wide dense decoder: fail at once, before a weight
        # is drawn
        raise SystemExit("benchmark: this checkout's program does not serve "
                         "window attention (config_from_hf gives no window "
                         "layers)")
    dense = serve.W
    serve.W = weights_swa
    try:
        return serve.run(ctx)
    finally:
        serve.W = dense

"""Runner of the serving cells of an LFM2-MoE configuration: THE timed loop
of ``runners/serve.py`` — called, not copied — with the weights drawn by
``benchmark/weights_moe.py``.

``serve.run`` reaches its generator through its module global ``W``; this
binds the name to the MoE generator for the call, as ``serve_hybrid.py`` does
for granite (a shim until ``serve.py`` takes the generator from the
configuration: PERF.md §7)."""

from __future__ import annotations

from benchmark import weights_moe
from benchmark.runners import serve


def run(ctx) -> dict:
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    try:
        cfg = config_from_hf(ctx.config)
    except Exception as e:          # a checkout that cannot read the file
        raise SystemExit(f"benchmark: this checkout's program cannot read "
                         f"an lfm2_moe configuration ({e})")
    if not getattr(cfg, "expert_layers", None) or "conv" not in getattr(
            cfg, "layer_kinds", ()):
        # a checkout from before the program knew conv mixers and exact
        # expert layers reads the file as a dense decoder: fail at once,
        # cleanly, before a weight is drawn
        raise SystemExit("benchmark: this checkout's program does not serve "
                         "conv mixers and expert layers (config_from_hf "
                         "gives no expert_layers)")
    dense = serve.W
    serve.W = weights_moe
    try:
        return serve.run(ctx)
    finally:
        serve.W = dense

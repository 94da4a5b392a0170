"""Runner of the serving cells of a hybrid configuration: THE timed loop of
``runners/serve.py`` — called, not copied — with the weights drawn by
``benchmark/weights_hybrid.py``.

``serve.run`` reaches its generator through its module global ``W``
(``benchmark.weights``, which knows the dense layout only); this binds the
name to the hybrid generator for the call.  A shim until ``serve.py`` takes
the generator from the configuration (PERF.md §7)."""

from __future__ import annotations

from benchmark import weights_hybrid
from benchmark.runners import serve


def run(ctx) -> dict:
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    if not getattr(config_from_hf(ctx.config), "layer_kinds", None):
        # a checkout from before the program knew recurrent layers reads the
        # file as a dense decoder: fail at once, cleanly
        raise SystemExit("benchmark: this checkout's program does not serve "
                         "recurrent layers (config_from_hf gives no "
                         "layer_kinds)")
    dense = serve.W
    serve.W = weights_hybrid
    try:
        return serve.run(ctx)
    finally:
        serve.W = dense

"""How fast the seeded weights are made on the device and copied back, and
what a trace of one restore looks like (plane, line and event names): read by
hand once, when the reduction in ``xplane.py`` was written."""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import numpy as np

    from benchmark import harness
    from benchmark import weights as W
    harness.require_chips(1)
    harness.enable_compile_cache()
    hf = harness.load_json("benchmark", "configs", "mistral-7b-v0.3.json")
    out = {}
    t0 = time.monotonic()
    p = W.make_params(hf, 1)
    jax.block_until_ready(p)
    out["make_params_first_s"] = time.monotonic() - t0
    del p
    t0 = time.monotonic()
    p = W.make_params(hf, 2)
    jax.block_until_ready(p)
    out["make_params_second_s"] = time.monotonic() - t0
    a = p["layers.0.w_gate"]
    t0 = time.monotonic()
    h = np.asarray(a)
    out["d2h_gib_s"] = a.nbytes / 2**30 / (time.monotonic() - t0)
    t0 = time.monotonic()
    b = jax.device_put(h)
    b.block_until_ready()
    out["h2d_gib_s"] = a.nbytes / 2**30 / (time.monotonic() - t0)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

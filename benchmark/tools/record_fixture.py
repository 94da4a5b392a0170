"""Records the small xplane file the trace reduction is tested on
(``benchmark/tests/fixture.xplane.pb``): three executions of a jitted program
called ``_paged_step`` 50 ms apart under ``step`` annotations, and one
``host_to_device`` of the program's bridge (its ``strom.h2d`` annotation), on
the chip.  Prints what the test then asserts."""

from __future__ import annotations

import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import harness, xplane
    from nvme_strom_tpu.io import StromEngine
    from nvme_strom_tpu.ops.bridge import host_to_device
    harness.require_chips(1)

    def _paged_step(x):
        return jnp.tanh(x @ x)

    step = jax.jit(_paged_step)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    step(x).block_until_ready()
    eng = StromEngine()
    out = os.path.join(ROOT, "chiprun_out", "fixture")
    shutil.rmtree(out, ignore_errors=True)
    tw = harness.TraceWindow(True, "fixture")
    tw.dir = out
    tw.start()
    for _ in range(3):
        with tw.annotate("step"):
            step(x).block_until_ready()
        time.sleep(0.05)
    host_to_device(eng, np.zeros(1 << 20, np.uint8),
                   jax.devices()[0]).block_until_ready()
    tw.stop()
    eng.close_all()
    path = tw.file()
    tr = xplane.load(path)
    shutil.copy(path, os.path.join(ROOT, "chiprun_out", "fixture.xplane.pb"))
    print("size", os.path.getsize(path), "window_s", tw.t1 - tw.t0,
          "busy_s", xplane.busy_seconds(tr),
          "step_ms", xplane.program_durations_ms(tr, "_paged_step"),
          "h2d_s", xplane.host_seconds(tr, "strom.h2d"),
          "top", xplane.top_device_ops(tr, 3),
          "gaps", xplane.idle_gaps(tr, ("step",)))
    for p in glob.glob(os.path.join(out, "**", "*"), recursive=True):
        if os.path.isfile(p):
            os.unlink(p)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""transfer_diag — evidence for the zero-copy device boundary.

The reference's whole value proposition is "no host bounce" (SURVEY.md
§3.1); on the JAX side our claim is: bytes land in a pinned staging
buffer via O_DIRECT DMA, and ``jax.device_put`` consumes *that exact
memory* — no Python-side copy exists.  This tool produces the evidence,
in two parts:

1. **Alias proof (definitive).**  ``PendingRead.wait()`` returns a numpy
   view; we check its data pointer lies inside
   ``[pool_base, pool_base + pool_bytes)`` (the engine's mlock'd staging
   pool).  If it does, every byte PJRT reads comes straight from the
   DMA target — zero copies on our side of the boundary, by
   construction, not by assertion.

2. **Boundary timing (inference).**  Whether PJRT itself stages the
   transfer through an internal pinned buffer is not observable from
   Python; we time three host→device variants (median of N):

   - ``staging``: device_put of the aligned, pinned staging view;
   - ``heap``: device_put of an ordinary unpinned heap array;
   - ``copy+heap``: explicit host memcpy first, then device_put — an
     intentional bounce, the lower bound on what a hidden copy costs.

   staging ≈ heap < copy+heap ⇒ any internal staging PJRT does is the
   same for both sources, and our path adds no measurable copy on top.
   staging < heap would indicate PJRT exploits the pinned/aligned
   source directly (true DMA).

Usage: python -m nvme_strom_tpu.tools.transfer_diag [--bytes N]
Prints one JSON line with the alias verdict and the three medians.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time


def run(nbytes: int, repeats: int = 5) -> dict:
    import numpy as np
    import jax
    from nvme_strom_tpu.io.engine import StromEngine
    from nvme_strom_tpu.utils.config import EngineConfig

    dev = jax.devices()[0]
    cfg = EngineConfig()
    nbytes = min(nbytes, cfg.chunk_bytes)
    out: dict = {"device": str(dev), "bytes": nbytes}

    with tempfile.NamedTemporaryFile(delete=False) as f:
        f.write(os.urandom(nbytes))
        path = f.name
    try:
        with StromEngine(cfg) as eng:
            pool = eng.pool_info()
            out["pool_locked"] = bool(pool["locked"])
            fh = eng.open(path)
            pr = eng.submit_read(fh, 0, nbytes)
            view = pr.wait()

            # -- 1. alias proof --
            addr = view.__array_interface__["data"][0]
            base, size = pool["pool_base"], pool["pool_bytes"]
            out["view_in_pool"] = bool(base <= addr < base + size)
            # alignment follows the engine config (O_DIRECT requirement),
            # not a hard-coded 4096 — sub-4K alignments are legal
            out["view_aligned"] = addr % cfg.alignment == 0
            out["alignment"] = cfg.alignment

            # -- 2. boundary timing --
            def med(fn) -> float:
                fn().block_until_ready()  # warmup, fully drained
                ts = []
                for _ in range(repeats):
                    t0 = time.monotonic()
                    fn().block_until_ready()
                    ts.append(time.monotonic() - t0)
                return statistics.median(ts)

            heap = np.array(view)           # unpinned copy of same bytes
            out["t_staging_s"] = round(med(
                lambda: jax.device_put(view, dev)), 6)
            out["t_heap_s"] = round(med(
                lambda: jax.device_put(heap, dev)), 6)
            out["t_copy_heap_s"] = round(med(
                lambda: jax.device_put(np.array(heap), dev)), 6)

            pr.release()
            eng.close(fh)

        ratio = out["t_staging_s"] / max(out["t_heap_s"], 1e-9)
        out["verdict"] = (
            "zero-copy to PJRT boundary"
            if out["view_in_pool"] else
            "BROKEN: view does not alias the staging pool")
        out["staging_vs_heap"] = round(ratio, 3)
        return out
    finally:
        os.unlink(path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="transfer_diag",
        description="zero-copy boundary evidence (alias proof + timing)")
    ap.add_argument("--bytes", type=int, default=4 << 20)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args(argv)
    res = run(args.bytes, args.repeats)
    print(json.dumps(res))
    return 0 if res.get("view_in_pool") else 1


if __name__ == "__main__":
    sys.exit(main())

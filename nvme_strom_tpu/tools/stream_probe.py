#!/usr/bin/env python
"""Stream-efficiency probe: where does NVMe→HBM bandwidth go?

When the stream runs below the simultaneously-measured link ceiling,
this probe says where the gap is, emitting one JSON line per
experiment (each names ``platform``, ``device_kind``, ``device_count``):

1. ``link``      — interleaved host→device ceiling at the stream's own
                   concurrency (depth × chunk), the honest denominator.
2. ``depth=N``   — stream rate at pipeline depths 4/8/16/32, blocking
                   drain (round-2 policy) vs opportunistic ``is_ready``
                   drain: separates "pipeline too shallow" from "drain
                   policy stalls the read side".
3. ``chunk=M``   — stream rate at 4/8/16 MiB chunks at fixed byte
                   budget: on a high-latency link, per-transfer
                   overhead amortizes with chunk size; if rate rises
                   with chunk, the gap is dispatch latency, not
                   bandwidth.
4. ``boundary``  — device_put GiB/s from (a) a heap numpy array, (b) a
                   locked staging-pool view, (c) the same view with
                   ``may_alias=True``: if (b)≈(a), PJRT re-stages host
                   memory internally either way and a "pinned" source
                   buys nothing; answered with controlled repeats.

One process, on the chip: without a TPU the probe exits non-zero unless
the caller set ``JAX_PLATFORMS=cpu`` (functional testing; every line
then says ``"platform": "cpu"``).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _log(msg: str) -> None:
    print(f"stream_probe: {msg}", file=sys.stderr, flush=True)


_DEVICE: dict = {}     # platform / device_kind / device_count, set by main


def _emit(obj: dict) -> None:
    print(json.dumps({**obj, **_DEVICE}), flush=True)


def _median_rate(fn, repeats: int = 3):
    rates = []
    for _ in range(repeats):
        rates.append(fn())
    return statistics.median(rates)


def probe_link(dev, chunk_bytes: int, outstanding: int,
               repeats: int = 3) -> float:
    """Host→device ceiling at the stream's own concurrency shape."""
    import jax
    import numpy as np
    bufs = [np.random.default_rng(i).integers(
        0, 256, size=chunk_bytes, dtype=np.uint8)
        for i in range(outstanding)]
    jax.device_put(bufs[0], dev).block_until_ready()

    def one() -> float:
        t0 = time.monotonic()
        arrs = [jax.device_put(b, dev) for b in bufs]
        for a in arrs:
            a.block_until_ready()
        return sum(b.nbytes for b in bufs) / (1 << 30) / (
            time.monotonic() - t0)

    return _median_rate(one, repeats)


def probe_stream(engine, path: str, dev, depth: int, drain: str,
                 repeats: int = 2) -> float:
    """Cold-cache NVMe→HBM stream rate at one (depth, drain) point."""
    from nvme_strom_tpu.ops.bridge import DeviceStream
    import bench
    ds = DeviceStream(engine, device=dev, depth=depth, drain=drain)
    size = os.path.getsize(path)

    def one() -> float:
        bench.evict_file(path)
        t0 = time.monotonic()
        n = 0
        for arr in ds.stream_file(path):
            n += arr.nbytes
        assert n == size
        return size / (1 << 30) / (time.monotonic() - t0)

    return _median_rate(one, repeats)


def probe_boundary(engine, dev, repeats: int = 7) -> dict:
    """device_put bandwidth by source-buffer kind.

    Uses one staging buffer acquired from the engine pool (mlocked,
    io_uring-registered) vs a plain heap array of the same size, with
    alternating order across repeats so link drift cancels."""
    import jax
    import numpy as np
    sz = engine.config.chunk_bytes
    heap = np.random.default_rng(0).integers(0, 256, size=sz,
                                             dtype=np.uint8)
    # a real pool view: read sz bytes of the bench file through the
    # engine and KEEP the request open so the view stays valid
    tmp = os.path.join(REPO, ".probe_pool.bin")
    with open(tmp, "wb") as f:
        f.write(heap.tobytes())
    fh = engine.open(tmp)
    pr = engine.submit_read(fh, 0, sz)
    pool_view = pr.wait()

    def put_rate(buf, **kw) -> float:
        t0 = time.monotonic()
        jax.device_put(buf, dev, **kw).block_until_ready()
        return sz / (1 << 30) / (time.monotonic() - t0)

    jax.device_put(heap[:4096], dev).block_until_ready()   # warmup
    rates: dict = {"heap": [], "pool": [], "pool_alias": []}
    for _ in range(repeats):
        rates["heap"].append(put_rate(heap))
        rates["pool"].append(put_rate(pool_view))
        rates["pool_alias"].append(put_rate(pool_view, may_alias=True))
    out = {k: round(statistics.median(v), 4) for k, v in rates.items()}
    out["staging_vs_heap"] = round(out["pool"] / out["heap"], 3) \
        if out["heap"] else None
    pr.release()
    engine.close(fh)
    os.unlink(tmp)
    return out


def main() -> int:
    sys.path.insert(0, REPO)   # direct-script mode: repo root first
    from nvme_strom_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import bench
    from nvme_strom_tpu.utils.device import require_tpu
    _DEVICE.update(require_tpu("stream_probe"))
    import jax
    from nvme_strom_tpu.io import StromEngine
    from nvme_strom_tpu.utils.config import EngineConfig
    from nvme_strom_tpu.utils.stats import StromStats

    nbytes = int(os.environ.get("STROM_PROBE_BYTES", 512 << 20))
    path = os.path.join(
        os.environ.get("STROM_BENCH_DIR", REPO), ".probe_data.bin")
    bench.make_file(path, nbytes)
    dev = jax.devices()[0]
    _log(f"device = {dev}")

    def quick_raw(engine) -> float:
        """One cold raw pass (payload discarded) in the same minute as
        the row's stream run, so an NVMe-side collapse can be told from
        stream inefficiency."""
        return bench.bench_raw(engine, path, repeats=1)

    # 1+2: per-depth sweep, both drain policies, with a same-minute link
    # ceiling before each depth so the ratio survives link drift
    for depth in (4, 8, 16, 32):
        cfg = EngineConfig(queue_depth=max(depth, 8))
        with StromEngine(cfg, stats=StromStats()) as engine:
            link = probe_link(dev, cfg.chunk_bytes,
                              outstanding=max(2, depth))
            raw = quick_raw(engine)
            for drain in ("blocking", "ready"):
                rate = probe_stream(engine, path, dev, depth, drain)
                _emit({"probe": "depth", "depth": depth, "drain": drain,
                       "chunk_mib": cfg.chunk_bytes >> 20,
                       "stream_gibs": round(rate, 4),
                       "link_gibs": round(link, 4),
                       "raw_gibs": round(raw, 4),
                       "ratio": round(rate / link, 3) if link else None})
                _log(f"depth={depth} drain={drain}: stream={rate:.3f} "
                     f"link={link:.3f} raw={raw:.3f}")

    # 3: chunk-size sweep at fixed depth budget (depth scaled so
    # depth×chunk stays constant — same outstanding bytes)
    for chunk_mib in (4, 8, 16, 32):
        depth = max(2, 64 // chunk_mib)
        cfg = EngineConfig(chunk_bytes=chunk_mib << 20,
                           queue_depth=depth,
                           buffer_pool_bytes=max(
                               256 << 20,
                               2 * depth * (chunk_mib << 20)))
        with StromEngine(cfg, stats=StromStats()) as engine:
            link = probe_link(dev, cfg.chunk_bytes,
                              outstanding=max(2, depth))
            raw = quick_raw(engine)
            rate = probe_stream(engine, path, dev, depth, "ready")
            _emit({"probe": "chunk", "chunk_mib": chunk_mib,
                   "depth": depth, "stream_gibs": round(rate, 4),
                   "link_gibs": round(link, 4),
                   "raw_gibs": round(raw, 4),
                   "ratio": round(rate / link, 3) if link else None})
            _log(f"chunk={chunk_mib}MiB depth={depth}: "
                 f"stream={rate:.3f} link={link:.3f} raw={raw:.3f}")

    # 4: the PJRT boundary question
    with StromEngine(EngineConfig(), stats=StromStats()) as engine:
        b = probe_boundary(engine, dev)
        b["probe"] = "boundary"
        _emit(b)
        _log(f"boundary: {b}")

    try:
        os.unlink(path)
    except OSError:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())

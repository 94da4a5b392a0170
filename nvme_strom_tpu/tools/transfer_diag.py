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

3. **The host→device ceiling (``--sizes``).**  For every size and thread
   count: ``threads`` Python threads, each with a staging view of its
   own, thread *t* putting onto device ``t % devices``.  First each put
   is blocked on (``return_us``: the call's median time to return,
   ``ready_us``: to ``block_until_ready``); then every thread issues
   ``repeats`` puts back to back and blocks on them all (``gib_s``: all
   threads' bytes over the wall time to the last one ready — what a
   pipelined consumer such as ``load_sharded`` can reach;
   ``gib_s_blocking``: the same for the put-and-wait loop).  What a
   second thread adds onto ONE device says how much of a put is
   serialised under the GIL or inside the client; the 64 MiB row is
   the link.  One JSON line a (size, threads) pair, ``"sweep": true``.
   With ``--gil-seconds S`` each pair also runs its threads for S
   seconds, 8 puts in flight each, beside a thread that only counts in
   Python: ``puts_per_s``, and ``gil_held_share`` — the part of its
   solo rate the counting thread lost, which is the time the putting
   threads held the GIL (the switch interval is 0.1 ms meanwhile).
   ``--sources staging,heap,batched`` repeats every pair out of other
   memory (``"source"`` on each line): ``heap`` — a plain numpy buffer a
   thread, allocated once and reused, as the restore's assembly buffers
   are (``ops/bridge.HostAssembly``; ``first_touch_us`` is the first
   copy into the fresh buffer, ``copy_us`` the second) — and
   ``batched``, ONE thread making one ``jax.make_array_from_callback``
   call over all the devices out of those heap buffers (``bytes`` a
   device; the call's ``return_us`` stands against ``devices`` puts).

Usage: python -m nvme_strom_tpu.tools.transfer_diag [--bytes N]
           [--sizes N,N,... [--threads 1,2,4] [--devices D]
            [--sources staging,heap,batched]]
Prints one JSON line with the alias verdict and the three medians, then
the sweep's lines; every line names ``platform``, ``device_kind`` and
``device_count``.  A measuring command: without a TPU it exits non-zero
and prints nothing, unless the caller set ``JAX_PLATFORMS=cpu`` (the
mechanics only; every line then says ``"platform": "cpu"``).
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


def _threads_run(n: int, work) -> float:
    """Run ``work(t)`` on ``n`` threads released together; the wall
    seconds from the release to the last one's return."""
    import threading

    gate = threading.Barrier(n + 1)
    errs: list = []

    def body(t):
        gate.wait()
        try:
            work(t)
        except BaseException as e:   # surfaced below, never swallowed
            errs.append(e)

    ths = [threading.Thread(target=body, args=(t,), daemon=True)
           for t in range(n)]
    for th in ths:
        th.start()
    gate.wait()
    t0 = time.monotonic()
    for th in ths:
        th.join()
    wall = time.monotonic() - t0
    if errs:
        raise errs[0]
    return wall


def _gil_probe(n: int, put, seconds: float) -> dict:
    """``n`` threads calling ``put(t)`` for ``seconds`` (8 results in
    flight each) beside a thread that only counts: the puts a second,
    and the share of its solo rate the counting thread lost."""
    import collections
    import threading

    def count_for(stop) -> float:
        n_loops, t0 = 0, time.monotonic()
        while not stop.is_set():
            n_loops += 1
        return n_loops / (time.monotonic() - t0)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        stop = threading.Event()
        threading.Timer(seconds / 2, stop.set).start()
        solo = count_for(stop)
        stop = threading.Event()
        puts = [0] * n
        beside: list = []
        spinner = threading.Thread(
            target=lambda: beside.append(count_for(stop)), daemon=True)

        def work(t):
            live: collections.deque = collections.deque()
            end = time.monotonic() + seconds
            while time.monotonic() < end:
                live.append(put(t))
                puts[t] += 1
                if len(live) > 8:
                    live.popleft().block_until_ready()
            for a in live:
                a.block_until_ready()

        spinner.start()
        wall = _threads_run(n, work)
        stop.set()
        spinner.join()
    finally:
        sys.setswitchinterval(old)
    return {"puts_per_s": round(sum(puts) / wall, 1),
            "gil_held_share": round(max(0.0, 1 - beside[0] / solo), 4)}


def _measure(n: int, put, repeats: int, size: int,
             gil_seconds: float) -> dict:
    """One (size, thread count) of the sweep: ``put(t)`` is thread
    ``t``'s ``device_put``."""
    ret: list = []
    rdy: list = []

    def blocking(t):
        for _ in range(repeats):
            t0 = time.monotonic()
            a = put(t)
            t1 = time.monotonic()
            a.block_until_ready()
            ret.append(t1 - t0)
            rdy.append(time.monotonic() - t0)

    def pipelined(t):
        for a in [put(t) for _ in range(repeats)]:
            a.block_until_ready()

    wall_b = _threads_run(n, blocking)
    wall_p = _threads_run(n, pipelined)
    gib = n * repeats * size / 2**30
    return {"return_us": round(statistics.median(ret) * 1e6, 1),
            "ready_us": round(statistics.median(rdy) * 1e6, 1),
            "gib_s": round(gib / wall_p, 3),
            "gib_s_blocking": round(gib / wall_b, 3),
            **(_gil_probe(n, put, gil_seconds) if gil_seconds > 0 else {})}


def sweep(sizes, threads=(1,), n_devices: int = 1,
          repeats: int = 8, gil_seconds: float = 0.0,
          sources=("staging",)) -> list:
    """The host→device ceiling: one dict a (source, size, thread count),
    see the module docstring's part 3.  A ``staging`` put's source is an
    engine staging view (one a thread), as ``load_sharded``'s whole-row
    puts are; a ``heap`` put's a reused numpy buffer, as its assembled
    puts are; ``batched`` is one call for all the devices."""
    import numpy as np
    import jax
    from nvme_strom_tpu.io.engine import StromEngine
    from nvme_strom_tpu.utils.config import EngineConfig

    devs = jax.devices()[:max(1, n_devices)]
    base = EngineConfig()
    rows = []
    for size in sizes:
        size = -(-int(size) // base.alignment) * base.alignment
        n_max = max(threads)
        cfg = EngineConfig(chunk_bytes=size, buffer_pool_bytes=max(
            base.buffer_pool_bytes, 2 * n_max * size))
        with tempfile.NamedTemporaryFile(delete=False) as f:
            f.write(os.urandom(size))
            path = f.name
        try:
            with StromEngine(cfg) as eng:
                fh = eng.open(path)
                reads = [eng.submit_read(fh, 0, size) for _ in range(n_max)]
                views = [pr.wait() for pr in reads]
                jax.device_put(views[0], devs[0]).block_until_ready()
                touch: dict = {}
                heaps: list = []
                if set(sources) - {"staging"}:
                    for v in views:
                        t0 = time.monotonic()
                        h = np.empty(size, np.uint8)
                        h[:] = v
                        t1 = time.monotonic()
                        h[:] = v
                        touch = {"first_touch_us": round((t1 - t0) * 1e6, 1),
                                 "copy_us": round(
                                     (time.monotonic() - t1) * 1e6, 1)}
                        heaps.append(h)
                sharding = jax.sharding.NamedSharding(
                    jax.sharding.Mesh(np.array(devs), ("d",)),
                    jax.sharding.PartitionSpec("d"))
                by_dev = {d: heaps[k % len(heaps)]
                          for k, d in enumerate(devs)} if heaps else {}

                def put_from(bufs):
                    return lambda t: jax.device_put(bufs[t],
                                                    devs[t % len(devs)])

                def put_batched(_t):
                    # one call, every device's buffer handed over whole
                    return jax.make_array_from_callback(
                        (len(devs) * size,), sharding,
                        lambda idx: by_dev[devs[
                            (idx[0].start or 0) // size]])

                for source in sources:
                    put = {"staging": put_from(views),
                           "heap": put_from(heaps),
                           "batched": put_batched}[source]
                    for n in ((1,) if source == "batched" else threads):
                        rows.append({
                            "sweep": True, "source": source, "bytes": size,
                            "threads": n, "devices": len(devs),
                            "repeats": repeats,
                            "platform": devs[0].platform,
                            **(touch if source == "heap" else {}),
                            **_measure(n, put, repeats,
                                       size * (len(devs)
                                               if source == "batched"
                                               else 1), gil_seconds)})
                for pr in reads:
                    pr.release()
                eng.close(fh)
        finally:
            os.unlink(path)
    return rows


def _ints(text: str) -> list:
    return [int(x) for x in text.split(",") if x.strip()]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="transfer_diag",
        description="zero-copy boundary evidence (alias proof + timing)")
    ap.add_argument("--bytes", type=int, default=4 << 20)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--sizes", type=_ints, default=[],
                    help="bytes, comma-separated: sweep puts of these "
                         "sizes out of staging views")
    ap.add_argument("--threads", type=_ints, default=[1],
                    help="thread counts of the sweep (1,2,4)")
    ap.add_argument("--devices", type=int, default=1,
                    help="devices the sweep's threads put onto")
    ap.add_argument("--sources", default="staging",
                    type=lambda text: [x for x in text.split(",") if x],
                    help="memory the sweep's puts read: staging, heap "
                         "(a reused numpy buffer), batched (one call "
                         "for all the devices out of heap buffers)")
    ap.add_argument("--gil-seconds", type=float, default=0.0,
                    help="seconds a (size, threads) pair puts beside a "
                         "counting thread (0: not measured)")
    args = ap.parse_args(argv)
    from nvme_strom_tpu.utils.device import require_tpu
    device = require_tpu("transfer_diag")   # exits where there is no TPU
    res = run(args.bytes, args.repeats)
    print(json.dumps({**res, **device}), flush=True)
    for row in sweep(args.sizes, args.threads, args.devices,
                     max(args.repeats, 2), args.gil_seconds,
                     args.sources):
        print(json.dumps({**row, **device}), flush=True)
    return 0 if res.get("view_in_pool") else 1


if __name__ == "__main__":
    sys.exit(main())

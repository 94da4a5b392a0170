#!/usr/bin/env python
"""Per-config benchmark suite: one JSON line per BASELINE.json config.

`bench.py` is the driver-facing headline (sustained NVMe→HBM streaming);
this suite covers the full config list so every capability row has a
number:

  1 raw     — raw sequential engine read, payload discarded (ssd2gpu_test
              analogue, SURVEY.md §3.4)
  2 arrow   — Arrow column file → single-chip device columns
  3 loader  — WebDataset shards → sharded dataloader → device batches
  4 weights — safetensors shards → lazy sharded HBM param load
  5 sql     — Parquet row-group scan → on-device GROUP BY aggregate
  6 decode  — autoregressive generation, tokens/sec (compute row)
  7 train   — train-step model-FLOPs utilisation (compute row)
  8 multi   — N concurrent streams through one engine vs serial (the
              striped-raid0 scaling story's engine-side requirement)
  9 ckpt    — checkpoint save bandwidth, durable GiB/s (inverse path;
              no read-derived ceiling → vs_baseline null)
 10 kvoff   — SSD-backed decode, tokens/sec with most KV history on
              NVMe (models/kv_offload.py; deliberately storage-bound —
              the capability is decode BEYOND HBM, its cost is the
              stream → vs_baseline null)
 11 serve   — continuous-batching aggregate throughput, tokens/sec
              across mixed-length requests on fixed slots
              (models/serving.py; compute row → vs_baseline null)
 12 zstd    — zstd-compressed Parquet scan, direct path vs pyarrow on
              the same file (compressed spans ride O_DIRECT, host
              decompress, device decode → vs_baseline null; the
              speedup-vs-pyarrow tag is the claim)
 13 dict    — dictionary-encoded Parquet scan with the on-device
              bit-unpack; the bounce_vs_idx_raw tag is the claim (host
              touches only the raw index stream, never expanded rows)

Usage: python bench_suite.py [--config N ... | --all]
(stdout is already JSON-only — one line per config; logs go to stderr)

I/O rows (1–5, 8): {"metric", "value" (GiB/s payload→device), "unit",
"vs_baseline" (value / 0.9·min(raw SSD, host→device link) — the
BASELINE.json north star; ≥1.0 means target met)}.  Discipline per the
round-1 verdict: run 0 warms jit/IPC caches and is DISCARDED, the page
cache is evicted before every timed run (cold = NVMe, not DRAM), and the
reported value is the MEDIAN of the timed runs, never best-of.

A host→device link shared with other work drifts, so a step-start link
ceiling can be stale by the time a config's passes run.  On a TPU every
_steady pass is therefore PAIRED with a link burst measured seconds
before it, and vs_baseline is the median of PER-PASS ratios against
0.9·min(raw, that pass's link) — bench.py's interleaved same-minute
discipline, applied per pass (raw is local NVMe; one step-start measure
suffices).

Runs on the TPU or not at all: without one the command exits non-zero,
unless the caller set JAX_PLATFORMS=cpu (the functional path the tests
drive), and then every row says ``"platform": "cpu"`` and carries no
vs_baseline.  Every row names ``platform``, ``device_kind`` and
``device_count``.

Compute rows (6–7) have no BASELINE.json target (the reference is a
storage engine, SURVEY.md §1) → vs_baseline is always null; they exist so
the framework's perf claims cover compute, not just I/O.

Env: STROM_SUITE_BYTES (per-config payload, default 256 MiB),
STROM_BENCH_DIR (scratch dir, default repo root),
STROM_KVOFF_QUANT=int8 / STROM_KVOFF_HOSTCACHE=N (config-10 variants),
STROM_SERVE_PAGED=1 (config 11 through the block-pool paged server),
STROM_SERVE_SHARED_PREFIX=N (config-11 variant: every request shares an
N-token system prompt — the paged server's prefix caching prefills it
once; gauges in the tag).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench  # noqa: E402  (shared helpers: make_file, evict_file, ...)

_log = bench._log

#: timed runs per I/O config AFTER the discarded jit-warmup run(s)
_RUNS = 3
#: discarded warmup calls at the head of every _steady loop — shared
#: with consumers that record side data from inside timed_fn and must
#: drop the same prefix (bench_sql's per-pass phase pairing); ONE
#: constant, so the run structure and the slicing cannot drift apart
_STEADY_WARMUPS = 1

#: same-run raw-SSD and host->device link rates (GiB/s), set by run()
#: before any config executes — the normalization base for rows whose
#: number is medium-bound (config 14's moment stream)
_CEILINGS: dict = {}

#: per-pass link pairing for io_row ratios (module header ¶3):
#: "probe" is a quick host→device burst installed by run() on a live
#: device; "last" holds the most recent _steady call's
#: [(pass_rate, link_gibs), ...] for the config result assembly
_PASS_LINK: dict = {"probe": None, "last": None}


class _SuiteWatchdog:
    """Convert a mid-suite hang into a self-diagnosing row instead of a
    silent timeout-burn.

    Python can't interrupt a hung ``block_until_ready``, so when a
    device op wedges the only honest move is: print WHERE we were
    wedged as a JSON line, flush, and ``os._exit`` so the run ends at
    its budget (``STROM_SUITE_BUDGET_S``) instead of at the caller's
    kill.

    Two modes:
      * ``arm(budget_s)`` — fires while configs still run → rc=3
        ("HUNG" row names the phase; work was incomplete);
      * ``teardown(grace_s)`` — armed after every result line has been
        printed; engine close / JAX runtime teardown hanging must not
        cost the window anything → rc=0 (the results already landed).
    """

    def __init__(self) -> None:
        self._phase = "startup"
        self._t_phase = time.monotonic()
        self._timer = None

    def phase(self, name: str) -> None:
        self._phase = name
        self._t_phase = time.monotonic()

    def _cancel(self) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None

    def arm(self, budget_s: float) -> None:
        import threading
        self._cancel()
        self._timer = threading.Timer(budget_s, self._fire_hung,
                                      args=(budget_s,))
        self._timer.daemon = True
        self._timer.start()

    def teardown(self, grace_s: float = 90.0) -> None:
        import threading
        self._cancel()
        self.phase("teardown")
        self._timer = threading.Timer(grace_s, self._fire_teardown,
                                      args=(grace_s,))
        self._timer.daemon = True
        self._timer.start()

    def _fire_hung(self, budget_s: float) -> None:
        stuck_s = round(time.monotonic() - self._t_phase, 1)
        print(json.dumps({
            "metric": f"WATCHDOG-HUNG in {self._phase} "
                      f"(stuck {stuck_s}s, budget {budget_s:.0f}s)",
            "value": stuck_s, "unit": "s", "vs_baseline": None,
        }), flush=True)
        _log(f"suite: WATCHDOG — hung in {self._phase} for {stuck_s}s; "
             "hard-exiting (rc=3) so the step ends at its budget")
        sys.stderr.flush()
        os._exit(3)

    def _fire_teardown(self, grace_s: float) -> None:
        _log(f"suite: WATCHDOG — teardown hung >{grace_s:.0f}s after all "
             "results printed; hard-exiting rc=0 (results already landed)")
        sys.stderr.flush()
        os._exit(0)


_WATCHDOG = _SuiteWatchdog()


def _steady(evict_paths, timed_fn) -> float:
    """Warmup + _RUNS cold timed runs → median rate.

    ``timed_fn()`` performs one full pass and returns its rate;
    ``evict_paths`` are dropped from the page cache before every run so
    each pass reads the NVMe, not DRAM (freshly generated bench data is
    100% cache-resident otherwise, and the residency planner would —
    correctly — serve it from memory).

    When run() installed a link probe (live device), each timed pass is
    preceded by one quick host→device burst and the (rate, link) pairs
    land in ``_PASS_LINK["last"]`` — the flap-proof per-pass ceilings
    the result assembly ratios against (module header ¶3).

    CONTRACT: exactly _STEADY_WARMUPS discarded warmup call(s), then
    _RUNS timed calls.  Consumers that record side data from inside
    ``timed_fn`` (bench_sql's per-pass phase pairing) slice off the
    same ``_STEADY_WARMUPS`` prefix — the shared constant is the
    coupling, not a comment."""
    probe = _PASS_LINK["probe"]
    rates, pairs = [], []
    for i in range(_RUNS + _STEADY_WARMUPS):
        for p in evict_paths:
            bench.evict_file(p)
        timed = i >= _STEADY_WARMUPS   # head runs warm jit/IPC caches
        link = probe() if (probe is not None and timed) else 0.0
        r = timed_fn()
        if timed:
            rates.append(r)
            if link > 0:
                pairs.append((r, link))
    if probe is not None:
        _PASS_LINK["last"] = pairs
    return statistics.median(rates)


def _paired_passes(path, direct_fn, fallback_fn) -> list:
    """Per-pass PAIRED comparison: evict → direct → evict → fallback,
    back to back within each pass so a link flap between the two
    measurements cancels out of the per-pass ratio (the window-9
    config-12 row read 0.61x while its own phase tag showed direct 4x
    faster — the two _steady runs had sampled the flapping link
    minutes apart).  Both fns receive ``timed`` (False during the
    _STEADY_WARMUPS prefix — same contract as _steady) so they can
    bracket side data for timed passes only.  Returns the timed
    (t_direct, t_fallback) pairs."""
    pairs = []
    for i in range(_RUNS + _STEADY_WARMUPS):
        timed = i >= _STEADY_WARMUPS
        bench.evict_file(path)
        td = direct_fn(timed)
        bench.evict_file(path)
        tp = fallback_fn(timed)
        if timed:
            pairs.append((td, tp))
    return pairs


def _scratch_dir() -> str:
    d = os.environ.get("STROM_BENCH_DIR",
                       os.path.dirname(os.path.abspath(__file__)))
    sub = os.path.join(d, ".bench_suite")
    os.makedirs(sub, exist_ok=True)
    return sub


def _suite_bytes() -> int:
    return int(os.environ.get("STROM_SUITE_BYTES", 256 << 20))


def _needs_regen(tag: str, nbytes: int, gen: int = 1) -> bool:
    """Size- and generation-aware scratch cache: True if data tagged
    `tag` must be (re)generated.  The .meta sentinel records the size a
    previous run FINISHED generating (written by _mark_generated after
    success), so changing STROM_SUITE_BYTES — or an interrupted
    generation — regenerates instead of silently benchmarking stale or
    truncated data.  ``gen`` is bumped when a generator's OUTPUT format
    changes (e.g. parquet switching to non-dictionary PLAIN), so an old
    scratch file can't silently bench the wrong code path."""
    meta = os.path.join(_scratch_dir(), f".{tag}.meta")
    try:
        return open(meta).read().strip() != f"{nbytes}/g{gen}"
    except OSError:
        return True


def _mark_generated(tag: str, nbytes: int, gen: int = 1) -> None:
    with open(os.path.join(_scratch_dir(), f".{tag}.meta"), "w") as f:
        f.write(f"{nbytes}/g{gen}")


# --------------------------- data generators ---------------------------

def make_arrow_file(path: str, nbytes: int) -> int:
    """Multi-batch Arrow IPC file of float32/int32 columns; returns size."""
    import numpy as np
    import pyarrow as pa
    if not _needs_regen("arrow", nbytes) and os.path.exists(path):
        return os.path.getsize(path)
    rows_total = max(1024, nbytes // 12)     # 3 cols × 4 bytes
    per_batch = max(1024, rows_total // 16)
    rng = np.random.default_rng(0)
    schema = pa.schema([("a", pa.float32()), ("b", pa.float32()),
                        ("k", pa.int32())])
    with pa.OSFile(path, "wb") as f, pa.ipc.new_file(f, schema) as w:
        left = rows_total
        while left > 0:
            n = min(per_batch, left)
            w.write_batch(pa.record_batch(
                [pa.array(rng.standard_normal(n, dtype=np.float32)),
                 pa.array(rng.standard_normal(n, dtype=np.float32)),
                 pa.array(rng.integers(0, 64, n, dtype=np.int32))],
                schema=schema))
            left -= n
    _mark_generated("arrow", nbytes)
    return os.path.getsize(path)


def make_wds_shards(dirpath: str, nbytes: int, n_shards: int = 4,
                    item_bytes: int = 1 << 20) -> list:
    """Tar shards of fixed-size .bin samples; returns shard paths."""
    import io as _io
    import tarfile
    import numpy as np
    os.makedirs(dirpath, exist_ok=True)
    per_shard = max(2, nbytes // n_shards // item_bytes)
    rng = np.random.default_rng(0)
    # sentinel keyed per DATASET DIR: config 3 and config 17 both build
    # wds shards with different sizes — one shared "wds" tag made each
    # run invalidate the other's cache and regenerate every cycle
    tag = "wds-" + os.path.basename(os.path.normpath(dirpath))
    regen = _needs_regen(tag, nbytes)
    paths = []
    for s in range(n_shards):
        p = os.path.join(dirpath, f"shard-{s:04d}.tar")
        paths.append(p)
        if os.path.exists(p) and not regen:
            continue
        with tarfile.open(p, "w") as tf:
            for i in range(per_shard):
                payload = rng.integers(0, 256, item_bytes,
                                       dtype=np.uint8).tobytes()
                ti = tarfile.TarInfo(f"{s:04d}{i:05d}.bin")
                ti.size = item_bytes
                tf.addfile(ti, _io.BytesIO(payload))
    _mark_generated(tag, nbytes)
    return paths


def make_safetensors_shards(dirpath: str, nbytes: int,
                            n_shards: int = 2) -> list:
    import numpy as np
    from nvme_strom_tpu.formats import write_safetensors
    os.makedirs(dirpath, exist_ok=True)
    per_shard = nbytes // n_shards
    n_tensors = 4
    rows = max(64, per_shard // n_tensors // (1024 * 4))
    rng = np.random.default_rng(0)
    regen = _needs_regen("st", nbytes)
    paths = []
    for s in range(n_shards):
        p = os.path.join(dirpath,
                         f"model-{s + 1:05d}-of-{n_shards:05d}.safetensors")
        paths.append(p)
        if os.path.exists(p) and not regen:
            continue
        write_safetensors(p, {
            f"w{s}_{i}": rng.standard_normal(
                (rows, 1024), dtype=np.float32)
            for i in range(n_tensors)})
    _mark_generated("st", nbytes)
    return paths


def make_parquet_file(path: str, nbytes: int, num_groups: int = 64,
                      compression: str = "none") -> int:
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    tag = "parquet" if compression == "none" else f"parquet_{compression}"
    if not _needs_regen(tag, nbytes, gen=2) and os.path.exists(path):
        return os.path.getsize(path)
    rows = max(4096, nbytes // 8)            # int32 key + float32 value
    rng = np.random.default_rng(0)
    tbl = pa.table({
        "k": pa.array(rng.integers(0, num_groups, rows, dtype=np.int32)),
        "v": pa.array(rng.standard_normal(rows, dtype=np.float32))})
    # PLAIN pages: the shape PG-Strom-style on-device decode handles
    # (sql/pq_direct.py) — config 5 measures the uncompressed direct
    # scan, config 12 the compressed one (engine-read compressed spans,
    # host decompress, device decode).
    pq.write_table(tbl, path, row_group_size=max(4096, rows // 16),
                   compression=compression, use_dictionary=False)
    _mark_generated(tag, nbytes, gen=2)
    return os.path.getsize(path)


def make_topk_parquet(path: str, nbytes: int) -> int:
    """Table for config 15: a random float column (ORDER BY must scan
    everything) plus a monotonically increasing int64 "ts" column whose
    tight per-row-group statistics make LIMIT elimination provable."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    if not _needs_regen("parquet_topk", nbytes) and os.path.exists(path):
        return os.path.getsize(path)
    rows = max(4096, nbytes // 12)           # float32 v + int64 ts
    rng = np.random.default_rng(1)
    tbl = pa.table({
        "v": pa.array(rng.standard_normal(rows, dtype=np.float32)),
        "ts": pa.array(np.arange(rows, dtype=np.int64))})
    pq.write_table(tbl, path, row_group_size=max(4096, rows // 16),
                   compression="none", use_dictionary=False)
    _mark_generated("parquet_topk", nbytes)
    return os.path.getsize(path)


def make_sql_scan_parquet(path: str, nbytes: int,
                          num_groups: int = 64) -> int:
    """Table for config 23: a key column, three float32 payload
    columns, and a monotonically increasing int32 "ts" column (int32,
    not int64, so the direct page walk stays eligible under x32 JAX)
    with tight per-row-group AND per-page statistics.  The layout is
    the zone-map worst case the paper motivates pushdown with: TWO
    large row groups, so a predicate band straddling their boundary
    defeats row-group pruning outright — the pre-PR scan reads the
    whole table — while the late-materializing scan fetches the filter
    column plus just the 256 KiB payload pages the band touches.  The
    wide fact-table payload (16 value columns — TPC-DS store_sales
    width) keeps the filter column a small fraction of the bytes
    pushdown must still read in full."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    if not _needs_regen("parquet_scan", nbytes, gen=4) \
            and os.path.exists(path):
        return os.path.getsize(path)
    rows = max(8192, nbytes // 72)   # k,ts int32 + v0..v15 float32
    rng = np.random.default_rng(2)
    data = {"k": pa.array(rng.integers(0, num_groups, rows,
                                       dtype=np.int32))}
    for i in range(16):
        data[f"v{i}"] = pa.array(
            rng.standard_normal(rows, dtype=np.float32))
    data["ts"] = pa.array(np.arange(rows, dtype=np.int32))
    pq.write_table(pa.table(data), path, row_group_size=(rows + 1) // 2,
                   compression="none", use_dictionary=False,
                   data_page_size=256 << 10)
    _mark_generated("parquet_scan", nbytes, gen=4)
    return os.path.getsize(path)


# ------------------------------ benches --------------------------------

def bench_arrow(engine, nbytes: int, device=None) -> tuple[float, int]:
    path = os.path.join(_scratch_dir(), "cols.arrow")
    size = make_arrow_file(path, nbytes)
    from nvme_strom_tpu.formats.arrow import ArrowFileReader
    reader = ArrowFileReader(path)

    def one_pass() -> float:
        t0 = time.monotonic()
        cols = reader.read_columns_to_device(engine, device=device)
        for v in cols.values():
            v.block_until_ready()
        dt = time.monotonic() - t0
        return sum(int(v.nbytes) for v in cols.values()) / (1 << 30) / dt

    return _steady([path], one_pass), size


def bench_loader(engine, nbytes: int, batch: int = 8) -> tuple[float, str]:
    """Config 3: WebDataset shards → device batches.  Headline is the
    wds_raw batch-coalesced zero-copy path (round-2 verdict #6 — raw
    members go staging→device with no host copy, so on an accelerator
    the epoch's bounce is 0); the standard decode path's rate rides in
    the tag for comparison."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from nvme_strom_tpu.data.loader import ShardedLoader
    paths = make_wds_shards(os.path.join(_scratch_dir(), "wds"), nbytes)
    mesh = Mesh(np.array(jax.local_devices()[:1]).reshape(1), ("dp",))

    def epoch_rate(fmt) -> float:
        with ShardedLoader(paths, mesh, global_batch=batch, fmt=fmt,
                           engine=engine) as loader:
            def one_epoch() -> float:
                n = 0
                t0 = time.monotonic()
                for arr in loader:
                    arr.block_until_ready()
                    n += int(arr.nbytes)
                return n / (1 << 30) / (time.monotonic() - t0)
            return _steady(paths, one_epoch)

    engine.sync_stats()
    pre = engine.stats.snapshot()["bounce_bytes"]
    raw_rate = epoch_rate("wds_raw")
    raw_pairs = _PASS_LINK["last"]   # headline pairing, not std's
    engine.sync_stats()
    # per-epoch, matching config 13's convention (_steady runs
    # _RUNS + 1 epochs including the discarded warmup)
    raw_bounce = (engine.stats.snapshot()["bounce_bytes"] - pre) \
        // (_RUNS + 1)
    std_rate = epoch_rate("wds")
    _PASS_LINK["last"] = raw_pairs
    _log(f"suite: loader wds_raw={raw_rate:.3f} GiB/s "
         f"(bounce/epoch={raw_bounce}) std={std_rate:.3f} GiB/s")
    return raw_rate, (f"wds_raw bounce/epoch={raw_bounce}, "
                      f"std_path={std_rate:.3f} GiB/s")


def bench_weights(engine, nbytes: int, device=None) -> tuple[float, int]:
    import jax
    from jax.sharding import SingleDeviceSharding
    from nvme_strom_tpu.parallel.weights import LazyCheckpoint
    paths = make_safetensors_shards(
        os.path.join(_scratch_dir(), "st"), nbytes)
    ckpt = LazyCheckpoint(paths)
    dev = device or jax.local_devices()[0]
    sh = SingleDeviceSharding(dev)
    payload = [0]

    def one_load() -> float:
        t0 = time.monotonic()
        params = ckpt.load_sharded(lambda name, shape: sh, engine=engine)
        for v in params.values():
            v.block_until_ready()
        dt = time.monotonic() - t0
        payload[0] = sum(int(v.nbytes) for v in params.values())
        del params
        return payload[0] / (1 << 30) / dt

    return _steady(paths, one_load), payload[0]


def bench_sql(engine, nbytes: int, num_groups: int = 64,
              device=None) -> tuple[float, str]:
    """Config 5: Parquet scan → on-device GROUP BY, with the round-3
    verdict's phase attribution: the tag decomposes the query into
    plan (footer+page walk, host), stream (pipelined spans→device,
    measured by a fold-free pass over the same cold file), and the
    fold's share (full time minus stream time) — so an on-silicon row
    that misses its ceiling names the phase that lost it."""
    import jax
    from nvme_strom_tpu.sql.parquet import ParquetScanner
    from nvme_strom_tpu.sql.groupby import (iter_device_columns,
                                            sql_groupby,
                                            sql_window_bytes)
    path = os.path.join(_scratch_dir(), "table.parquet")
    size = make_parquet_file(path, nbytes, num_groups)
    scanner = ParquetScanner(path, engine)
    rows = scanner.num_rows
    dev = device or jax.local_devices()[0]

    # phase 1: plan (pure host metadata walk, no payload I/O)
    from nvme_strom_tpu.sql import pq_direct
    t0 = time.monotonic()
    plans = pq_direct.plan_columns(scanner, ["k", "v"])
    t_plan = time.monotonic() - t0

    # phase 2: stream — the same columns, cold cache, NO aggregation;
    # the delta between this and the full query is the fold's cost.
    # (Blocking on the last group's arrays suffices: transfers retire
    # in submission order on a single device stream.)
    def stream_pass() -> float:
        t0 = time.monotonic()
        last = None
        for cols in iter_device_columns(scanner, ["k", "v"], dev,
                                        narrow_int32=("k",),
                                        plans=plans):
            last = cols
        for v in last.values():
            v.block_until_ready()
        return time.monotonic() - t0

    # Per-PASS phase pairing (window-7 diagnosis 1 applied to the phase
    # attribution, not just the ceiling): each timed scan subtracts a
    # stream pass run SECONDS after it, so a link flap between the two
    # phase measurements cancels instead of landing in fold_overhead —
    # window 8 ledgered fold 0.18→2.57 s across captures from exactly
    # this mispairing (the lone stream pass caught a 1.09 GiB/s moment,
    # the scans ~0.5 ones).  Order matters: the SCAN runs first, right
    # after _steady's link burst, so the (rate, link) ceiling pair
    # stays adjacent too; the stream pass follows the scan.  _steady's
    # discarded run 0 warms both paths' jit/dispatch caches.
    stream_ts, fold_ts = [], []

    # fold bisect knob: the v5 paired row put the fold at ~1.4 s on a
    # healthy link — method (matmul one-hot vs scatter segment-sum)
    # and window size are the two levers that split dispatch cost from
    # device-side fold cost.  Absent explicit env, the LEDGERED winner
    # of the bisect is adopted (utils/tuning.best_sql_fold — the
    # flash-tiling adoption pattern), so once suite_5_scatter/w256/
    # sw256 land their rows, every later config-5 run measures the
    # best known operating point by default.
    method = os.environ.get("STROM_SQL_METHOD")
    adopted_window = False
    if method is None and os.environ.get("STROM_SQL_WINDOW_BYTES") is None:
        # BOTH knobs unset = the plain contract row; a bisect step that
        # pins one knob must measure exactly what its label says, so
        # adoption never fills in its other knob
        from nvme_strom_tpu.utils.tuning import best_sql_fold
        tuned = best_sql_fold() or {}
        if tuned:
            _log(f"suite: sql fold adopting ledgered best {tuned}")
            method = tuned["method"]
            # sql_window_bytes() reads the env at each call — the
            # adoption rides the same knob the operator would set,
            # scoped to THIS config's scans (restored below: a --all
            # run's other configs must keep their own operating point)
            os.environ["STROM_SQL_WINDOW_BYTES"] = str(
                tuned["window_bytes"])
            adopted_window = True
    method = method or "matmul"

    def one_scan() -> float:
        t0 = time.monotonic()
        out = sql_groupby(scanner, "k", "v", num_groups,
                          aggs=("count", "sum", "mean"), method=method,
                          device=device)
        for v in out.values():
            v.block_until_ready()
        dt = time.monotonic() - t0
        bench.evict_file(path)   # the stream pass re-reads the NVMe too
        stream_ts.append(stream_pass())
        fold_ts.append(max(dt - stream_ts[-1], 0.0))
        _log(f"suite: sql scanned {rows} rows ({size >> 20} MiB) "
             f"in {dt:.3f}s = {rows / dt / 1e6:.1f} Mrows/s "
             f"(paired stream={stream_ts[-1]:.3f}s)")
        return size / (1 << 30) / dt

    try:
        rate = _steady([path], one_scan)
        # drop _steady's warmup-call prefix, same constant it runs by
        gib = size / (1 << 30)
        stream_rate = statistics.median(
            gib / t for t in (stream_ts[_STEADY_WARMUPS:] or stream_ts))
        fold_s = statistics.median(fold_ts[_STEADY_WARMUPS:] or fold_ts)
        tag = (f"rows={rows} plan={t_plan * 1e3:.0f}ms "
               f"stream={stream_rate:.3f} GiB/s "
               f"fold_overhead={fold_s:.3f}s paired=per-pass "
               f"method={method} window={sql_window_bytes() >> 20}MiB")
        _log(f"suite: sql phases: {tag}")
        return rate, tag
    finally:
        if adopted_window:
            os.environ.pop("STROM_SQL_WINDOW_BYTES", None)


def bench_sql_parallel(engine, nbytes: int, num_groups: int = 64,
                       device=None) -> tuple[float, str]:
    """Config 23: partition-parallel pushdown scan (sql/scan_plan.py)
    vs its own same-run serial arm — a ~10% selectivity range predicate
    on the monotone ts column whose band STRADDLES the two row groups'
    boundary, so zone-map pruning saves nothing and the whole win is
    page-level late materialization.  Three arms back to back on the
    same cold file: serial (workers=1, pushdown off — the exact pre-PR
    path), parallel (best workers, pushdown off), parallel+pushdown.
    The TIMED section is the scan stage (iter_scan_columns draining
    every column to the device) — the stage this engine owns; the
    group-by fold downstream of it is byte-for-byte the same work in
    every arm, and each arm's FULL query result is computed untimed
    and asserted bit-identical to serial every run, so a divergence
    fails the config loudly rather than benching a wrong answer.
    Headline is the parallel+pushdown effective table scan rate
    (surviving-row-group bytes over wall time); the tag stamps
    ``workers=N`` (utils/tuning.best_sql_workers adopts the ledgered
    winner as the STROM_SQL_WORKERS=0 auto width), the serial/parallel
    rates, speedups, rows/s, and the skip counters."""
    import numpy as np
    from nvme_strom_tpu.sql import scan_plan
    from nvme_strom_tpu.sql.groupby import sql_groupby
    from nvme_strom_tpu.sql.parquet import ParquetScanner
    path = os.path.join(_scratch_dir(), "scan.parquet")
    size = make_sql_scan_parquet(path, nbytes, num_groups)
    scanner = ParquetScanner(path, engine)
    rows = scanner.num_rows
    lo, hi = int(rows * 0.45), int(rows * 0.55) - 1    # ~10% survives
    wr = [("ts", lo, hi)]
    vcols = [f"v{i}" for i in range(16)]
    cols = ["k", *vcols, "ts"]
    window = 32 << 20          # fixed across arms: identical windowing
    knobs = ("STROM_SQL_WORKERS", "STROM_SQL_PUSHDOWN",
             "STROM_SQL_WINDOW_BYTES")
    saved = {k: os.environ.get(k) for k in knobs}

    def query():
        out = sql_groupby(scanner, "k", vcols, num_groups,
                          aggs=("count", "sum", "mean"), device=device,
                          where_ranges=wr)
        for v in out.values():
            v.block_until_ready()
        return {a: np.asarray(v) for a, v in out.items()}

    results = {}

    def arm(tag_, workers, pushdown):
        os.environ["STROM_SQL_WORKERS"] = str(workers)
        os.environ["STROM_SQL_PUSHDOWN"] = str(pushdown)
        rgs = (list(scan_plan.plan_scan(scanner, cols, wr).row_groups)
               if pushdown and scan_plan.pushdown_enabled()
               else scanner.prune_row_groups(wr))
        ts = []
        for i in range(_RUNS + _STEADY_WARMUPS):
            bench.evict_file(path)
            t0 = time.monotonic()
            for out in scan_plan.iter_scan_columns(
                    scanner, cols, device, row_groups=rgs,
                    where_ranges=wr, window_bytes=window):
                for v in out.values():
                    v.block_until_ready()
            if i >= _STEADY_WARMUPS:
                ts.append(time.monotonic() - t0)
        results[tag_] = query()        # untimed: fold bit-check
        dt = statistics.median(ts)
        _log(f"suite: sql-parallel arm {tag_}: {dt:.3f}s "
             f"({size / (1 << 30) / dt:.3f} GiB/s)")
        return dt

    try:
        os.environ["STROM_SQL_WINDOW_BYTES"] = str(window)
        env_w = int(saved["STROM_SQL_WORKERS"] or "0")
        widths = [env_w] if env_w > 1 else [2, 4]
        t_serial = arm("serial", 1, 0)
        t_par, best_w = None, widths[0]
        for w in widths:
            t = arm(f"par{w}", w, 0)
            if t_par is None or t < t_par:
                t_par, best_w = t, w
        snap0 = engine.stats.snapshot()
        t_push = arm("push", best_w, 1)
        snap1 = engine.stats.snapshot()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    base = results["serial"]
    for tag_, res in results.items():
        for a in base:
            if not np.array_equal(base[a], res[a], equal_nan=True):
                raise AssertionError(
                    f"config 23: arm {tag_} diverged from serial on "
                    f"{a!r} — scan correctness bug, not a perf number")
    rg_skip = (snap1.get("sql_rowgroups_skipped", 0)
               - snap0.get("sql_rowgroups_skipped", 0))
    # push arm: _RUNS + warmup timed scan passes plus the one untimed
    # bit-check query, each a late-materializing pass over the band
    by_skip = ((snap1.get("sql_bytes_skipped", 0)
                - snap0.get("sql_bytes_skipped", 0))
               // (_RUNS + _STEADY_WARMUPS + 1))
    gib = size / (1 << 30)
    rate = gib / t_push
    tag = (f"workers={best_w} rows={rows} sel=10% "
           f"serial={gib / t_serial:.3f} par={gib / t_par:.3f} "
           f"push={rate:.3f} GiB/s "
           f"speedup_par={t_serial / t_par:.2f}x "
           f"speedup_push={t_serial / t_push:.2f}x "
           f"mrows_s={rows / t_push / 1e6:.2f} "
           f"rg_skipped={rg_skip} bytes_skipped={by_skip}")
    _log(f"suite: sql-parallel: {tag}")
    return rate, tag


def bench_sql_zstd(engine, nbytes: int, num_groups: int = 64,
                   device=None) -> tuple[float, str]:
    """Config 12: zstd-compressed scan, direct path vs pyarrow fallback
    on the SAME file (round-2 verdict #4 — real tables are compressed).

    Direct path: compressed page spans ride O_DIRECT, host decompress,
    on-device bitcast + GROUP BY.  Fallback: pyarrow decodes the table
    on host.  Reports the direct rate (compressed GiB/s off the SSD)
    with the fallback rate and speedup in the tag."""
    from nvme_strom_tpu.sql.parquet import ParquetScanner
    from nvme_strom_tpu.sql.groupby import groupby_aggregate
    path = os.path.join(_scratch_dir(), "table_zstd.parquet")
    size = make_parquet_file(path, nbytes, num_groups,
                             compression="zstd")
    scanner = ParquetScanner(path, engine)
    rows = scanner.num_rows

    def scan(direct: str) -> float:
        t0 = time.monotonic()
        cols = scanner.read_columns_to_device(["k", "v"], direct=direct,
                                              device=device)
        out = groupby_aggregate(cols["k"], cols["v"], num_groups,
                                aggs=("count", "sum"))
        for v in out.values():
            v.block_until_ready()
        return time.monotonic() - t0

    # both paths ship the same decompressed bytes over the same link
    # moment, so the flap cancels out of the per-pass ratio
    from nvme_strom_tpu.sql import pq_direct
    ph: dict = {}

    def direct(timed):
        td = scan("always")
        if timed:
            ph.clear()
            ph.update(pq_direct.LAST_COMPRESSED_PHASES)
        return td

    pairs = _paired_passes(path, direct, lambda timed: scan("never"))
    d_times = [td for td, _ in pairs]
    p_times = [tp for _, tp in pairs]
    ratios = [tp / td for td, tp in pairs]
    dt_direct = 1.0 / statistics.median(d_times)
    dt_pyarrow = 1.0 / statistics.median(p_times)
    # host-decode-only pyarrow time: what the direct path's
    # stall+decomp phases race against — BOTH paths then ship the same
    # decompressed bytes over the same link, so the transfer term
    # cancels out of the comparison (round-3 verdict #5: the 0.24x
    # on-silicon row was uninterpretable without this split)
    import pyarrow.parquet as pq
    bench.evict_file(path)
    t0 = time.monotonic()
    pq.read_table(path, columns=["k", "v"])
    t_pa_host = time.monotonic() - t0
    rate = size / (1 << 30) * dt_direct          # dt_* are 1/seconds
    speedup = statistics.median(ratios)          # of per-pass ratios
    _log(f"suite: zstd scan {rows} rows ({size >> 20} MiB compressed): "
         f"direct={1 / dt_direct:.3f}s pyarrow={1 / dt_pyarrow:.3f}s "
         f"speedup={speedup:.2f}x (per-pass paired) phases={ph}")
    tag = (f"speedup_vs_pyarrow={speedup:.2f}x paired=per-pass; "
           f"direct phases: "
           f"stall={ph.get('read_stall_s', -1):.2f}s "
           f"decomp={ph.get('decomp_s', -1):.2f}s "
           f"put={ph.get('put_s', -1):.2f}s "
           f"({ph.get('decompressed_bytes', 0) >> 20}MiB to device); "
           f"pyarrow host decode={t_pa_host:.2f}s + same put")
    return rate, tag


def bench_topk(engine, nbytes: int, device=None) -> tuple[float, str]:
    """Config 15: ORDER BY ... LIMIT pushdown (sql/topk.py).

    Two queries on one table: ORDER BY a random float column (no usable
    statistics order → the streaming device top-k merge scans every row
    group; the reported GiB/s is that full scan) and ORDER BY a sorted
    int64 "ts" column (tight footer stats → the LIMIT elimination skips
    every row group but one; the tag carries skipped/total and the
    query's wall time — the scan-elimination claim as a measured row)."""
    from nvme_strom_tpu.sql.parquet import ParquetScanner
    from nvme_strom_tpu.sql.topk import sql_topk
    path = os.path.join(_scratch_dir(), "table_topk.parquet")
    size = make_topk_parquet(path, nbytes)
    scanner = ParquetScanner(path, engine)
    rows = scanner.num_rows
    nrg = scanner.num_row_groups

    def full_scan() -> float:
        t0 = time.monotonic()
        res = sql_topk(scanner, "v", columns=["ts"], k=10,
                       device=device)
        dt = time.monotonic() - t0
        assert len(res["v"]) == 10
        # HONEST rate: even a random column's stats eliminate some
        # groups once the carried k-th value is high; only bytes the
        # scan actually read may count toward the GiB/s row
        scanned = size * (nrg - res["_skipped_row_groups"]) / nrg
        _log(f"suite: topk scanned {rows} rows in {dt:.3f}s "
             f"({res['_skipped_row_groups']}/{nrg} rgs eliminated)")
        return scanned / (1 << 30) / dt

    rate = _steady([path], full_scan)
    bench.evict_file(path)
    t0 = time.monotonic()
    res = sql_topk(scanner, "ts", columns=["v"], k=10, device=device)
    dt_ts = time.monotonic() - t0
    skipped = res["_skipped_row_groups"]
    tag = (f"rows={rows} k=10; sorted-col elimination skipped "
           f"{skipped}/{nrg} rgs in {dt_ts * 1e3:.0f}ms")
    return rate, tag


def bench_dict_scan(engine, nbytes: int, cardinality: int = 4096,
                    device=None) -> tuple[float, str]:
    """Config 13: dictionary-encoded column scan with the on-device
    bit-unpack (round-2 verdict #5).  The tag reports host-touched
    payload (bounce) against the raw index-stream bytes — the claim is
    bounce ≈ raw stream (engine-read only), NOT 4 bytes/row of
    host-expanded indices — AND, per the round-4 verdict ("give
    config 13 a bar"), the per-pass-paired speedup over the pyarrow
    fallback shipping the same decoded column to the same device: the
    ×pyarrow bar config 12 already carries.  The direct path now runs
    the whole-column batched decode (one device program set + one sync
    for all row groups — a per-row-group walk pays one dispatch and
    one sync per row group)."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    from nvme_strom_tpu.sql.parquet import ParquetScanner
    from nvme_strom_tpu.sql import pq_direct
    path = os.path.join(_scratch_dir(), "table_dict.parquet")
    if _needs_regen("parquet_dict", nbytes) or not os.path.exists(path):
        rows = max(4096, nbytes // 4)
        rng = np.random.default_rng(0)
        pq.write_table(
            pa.table({"v": pa.array(
                rng.integers(0, cardinality, rows, dtype=np.int32))}),
            path, row_group_size=max(4096, rows // 8),
            compression="none", use_dictionary=True)
        _mark_generated("parquet_dict", nbytes)
    size = os.path.getsize(path)
    scanner = ParquetScanner(path, engine)
    plans = pq_direct.plan_columns(scanner, ["v"])
    idx_raw = sum(p.span[1] for plan in plans["v"]
                  for p in plan.parts if p.kind == "dict")
    stats = engine.stats

    def scan(direct: str) -> float:
        t0 = time.monotonic()
        out = scanner.read_columns_to_device(["v"], direct=direct,
                                             device=device)
        out["v"].block_until_ready()
        return time.monotonic() - t0

    # bounce accounting brackets only the DIRECT passes so the pyarrow
    # handoff can't pollute the bounce_vs_idx_raw claim
    bounce = [0]

    def direct(timed):
        engine.sync_stats()
        pre = stats.snapshot()["bounce_bytes"]
        td = scan("always")
        engine.sync_stats()
        if timed:
            bounce[0] += stats.snapshot()["bounce_bytes"] - pre
        return td

    pairs = _paired_passes(path, direct, lambda timed: scan("never"))
    d_times = [td for td, _ in pairs]
    p_times = [tp for _, tp in pairs]
    ratios = [tp / td for td, tp in pairs]
    rate = size / (1 << 30) / statistics.median(d_times)
    speedup = statistics.median(ratios)
    per_pass = bounce[0] / _RUNS
    _log(f"suite: dict scan rows={scanner.num_rows} idx_raw={idx_raw} "
         f"bounce/pass={per_pass:.0f} "
         f"({per_pass / max(idx_raw, 1):.2f}x of raw stream) "
         f"direct={statistics.median(d_times):.3f}s "
         f"pyarrow={statistics.median(p_times):.3f}s "
         f"speedup={speedup:.2f}x")
    return rate, (f"speedup_vs_pyarrow={speedup:.2f}x paired=per-pass; "
                  f"bounce_vs_idx_raw={per_pass / max(idx_raw, 1):.2f}x"
                  f", idx_raw={idx_raw}")


def bench_overlap(nbytes: int) -> tuple[float, str]:
    """Config 20: zero-copy overlap pipeline (docs/PERF.md §6) —
    overlapped streaming GiB/s through the double-buffered host→HBM
    stage, tagged with the speedup over the serialized arm and the
    SQPOLL submission-syscall reduction.  Delegates to
    ``bench.bench_overlap`` (pad-emulated hop on a CPU run,
    real paths on a TPU with the pad at 0); own engines, own file —
    like configs 6/11 no read-ceiling ratio applies (the serialized/
    SQPOLL-off arms in the tag are the claim)."""
    d = _scratch_dir()
    path = os.path.join(d, "overlap.bin")
    bench.make_file(path, max(nbytes, 16 << 20))
    out = bench.bench_overlap(path)
    tag = (f"serialized={out['serialized_gib_s']} GiB/s "
           f"({out['overlap_speedup_pct']:+.1f}%), "
           f"syscalls/GiB {out['sqpoll_off']['enters_per_gib']}"
           f"->{out['sqpoll_on']['enters_per_gib']} "
           f"({out['syscalls_per_gib_reduction_pct']:-.1f}%), "
           f"pad={out['pad_ms']}ms")
    return out["overlapped_gib_s"], tag


def bench_scatter(nbytes: int) -> tuple[float, str]:
    """Config 21: read-once/ICI-scatter restore (docs/PERF.md §7) —
    aggregate restore GiB/s when each virtual host reads 1/N off flash
    and the mesh exchanges shares, tagged with the read-all arm and the
    flash-byte reduction the ``ici_*`` counters prove.  Delegates to
    ``bench.bench_scatter`` (own engines, own file); a 1-device process
    grows the 8-host mesh in a throwaway subprocess.  Paired with its
    own same-run read-all arm — the N·T→T flash reduction in the tag is
    the claim, so no read-ceiling ratio applies."""
    import jax
    d = _scratch_dir()
    path = os.path.join(d, "scatter.bin")
    bench.make_file(path, max(nbytes, 16 << 20))
    if jax.device_count() >= 2:
        out = bench.bench_scatter(path)
    else:
        out = bench._bench_scatter_subprocess(path)
    if out is None:
        return 0.0, "scatter=unavailable (subprocess failed)"
    tag = (f"read_all={out['read_all_gib_s']} GiB/s, N={out['n_hosts']}"
           f", flash_bytes={out['n_hosts'] * out['payload_bytes']}"
           f"->{out['ici_bytes_read']}"
           + (", FELL BACK to read-all"
              if out["scatter_fell_back"] else ""))
    return out["scatter_gib_s"], tag


def bench_tenant_storm(nbytes: int) -> tuple[float, str]:
    """Config 22: multi-tenant isolation storm (docs/RESILIENCE.md
    "Multi-tenant isolation") — an open-loop victim + aggressor
    session trace served with tenancy off vs on, ALTERNATING storm
    trials with the median-p99 trial per arm (the bench_mixed
    discipline: clock drift hits both arms equally).  Delegates to
    ``bench.bench_tenants`` (own engines, own store file).  Headline
    is the isolation win — victim TTFT p99 tier-off / tier-on under
    the SAME storm; the tag carries the no-aggressor reference, both
    degradations, and the shed counters proving only the aggressor's
    tier paid."""
    d = _scratch_dir()
    path = os.path.join(d, "tenants.bin")
    bench.make_file(path, max(nbytes, 8 << 20))
    trials = 2 if _tiny_compute() else 3
    out = bench.bench_tenants(path, trials=trials)
    tag = (f"victim_p99={out['base']['victim_ttft_p99_ms']} ms alone"
           f", {out['tier_off']['victim_ttft_p99_ms']} tier-off"
           f", {out['tier_on']['victim_ttft_p99_ms']} tier-on "
           f"({out['victim_p99_degradation_on_pct']:+.1f}% vs alone), "
           f"sheds={out['tier_on']['tenant_sheds']}, "
           f"storm_dumps={out['tier_on']['tenant_storm_dumps']}, "
           f"trials={out['trials']}")
    return float(out["isolation_win"] or 0.0), tag


def bench_coldstart_suite(nbytes: int) -> tuple[float, str]:
    """Config 24: elastic cold-start (docs/RESILIENCE.md "Elastic
    cold-start") — time-to-first-token-from-boot, restore-then-serve
    vs serve-while-restoring, median over trials, with
    time-to-p99-steady and the token-identity verdict in the tag.
    Delegates to ``bench.bench_coldstart`` (own engines, own
    checkpoint + warm-payload files).  Headline is the TTFT-from-boot
    speedup (off/on); paired with its own same-run off arm, so no
    read-ceiling ratio applies."""
    d = _scratch_dir()
    path = os.path.join(d, "coldstart.bin")
    bench.make_file(path, max(nbytes, 64 << 20))
    trials = 2 if _tiny_compute() else 3
    out = bench.bench_coldstart(path, trials=trials)
    tag = (f"ttft_boot={out['off']['ttft_boot_s']}s off"
           f", {out['on']['ttft_boot_s']}s on; steady="
           f"{out['off']['steady_s']}s off"
           f", {out['on']['steady_s']}s on"
           f", faults={out['on']['coldstart_faults']}"
           f", bulk={out['on']['coldstart_bulk_tensors']}"
           f", tokens_identical={out['tokens_identical']}"
           f", pad={out['service_pad_ms']}ms"
           f", trials={out['trials']}")
    return float(out["ttft_boot_speedup"]), tag


def bench_handoff_suite(nbytes: int) -> tuple[float, str]:
    """Config 25: drain & warm handoff (docs/RESILIENCE.md "Drain &
    handoff") — rolling replica replacement, replacement
    TTFT-from-boot with vs without a shipped warm-state bundle,
    median over trials, with the zero-drop ledger and token-identity
    verdict in the tag.  Delegates to ``bench.bench_handoff`` (own
    engines, own checkpoint/store/bundle files).  Headline is the
    TTFT-from-boot speedup (off/on); paired with its own same-run off
    arm, so no read-ceiling ratio applies."""
    d = _scratch_dir()
    path = os.path.join(d, "handoff.bin")
    bench.make_file(path, max(nbytes, 64 << 20))
    trials = 2 if _tiny_compute() else 3
    out = bench.bench_handoff(path, trials=trials)
    tag = (f"ttft_boot={out['off']['ttft_boot_s']}s off"
           f", {out['on']['ttft_boot_s']}s on"
           f", exported={out['on']['sessions_exported']}"
           f", restored={out['on']['sessions_restored']}"
           f", dropped={out['dropped_requests']}"
           f", tokens_identical={out['tokens_identical']}"
           f", pad={out['service_pad_ms']}ms"
           f", trials={out['trials']}")
    return float(out["ttft_boot_speedup"]), tag


def bench_tar_index(engine, nbytes: int) -> tuple[float, str]:
    """Config 16: WebDataset shard-index rate (members/s), native C
    header walk vs Python tarfile — the first-epoch metadata cost of a
    many-shard dataset.  Cold-cache per pass like every I/O row; the
    member count scales with the suite budget (~4.5 KiB/member)."""
    import tarfile as _tarfile
    import io as _io
    from nvme_strom_tpu.io.engine import tar_index
    d = _scratch_dir()
    members = max(1000, nbytes // 4608)
    path = os.path.join(d, "tar_index.tar")
    tag = "tar_index"
    if _needs_regen(tag, members) or not os.path.exists(path):
        payload = b"x" * 4096
        tmp = path + ".tmp"
        with _tarfile.open(tmp, "w", format=_tarfile.GNU_FORMAT) as tf:
            for i in range(members):
                ti = _tarfile.TarInfo(f"train/{i:08d}.bin")
                ti.size = len(payload)
                tf.addfile(ti, _io.BytesIO(payload))
        os.replace(tmp, path)
        _mark_generated(tag, members)

    def native():
        t0 = time.monotonic()
        n = len(tar_index(path))
        dt = time.monotonic() - t0
        assert n == members, (n, members)
        return members / dt

    def python():
        t0 = time.monotonic()
        with _tarfile.open(path, "r:") as tf:
            n = sum(1 for m in tf if m.isfile())
        dt = time.monotonic() - t0
        assert n == members, (n, members)
        return members / dt

    r_native = _steady([path], native)
    r_py = _steady([path], python)
    return (r_native / 1e6,
            f"members={members} native={r_native / 1e3:.0f}k/s "
            f"tarfile={r_py / 1e3:.0f}k/s speedup={r_native / r_py:.1f}x")


def bench_checkpoint_write(engine, nbytes: int) -> tuple[float, str]:
    """Config 9: the inverse path — checkpoint save bandwidth.  Times
    CheckpointManager.save end to end (tile snapshot, engine writes,
    meta fsync, atomic rename) through the suite's shared engine, which
    is what a training run actually pays.  Every repeat writes a fresh
    step (no pruning inside the timed window); the tag says whether the
    payload actually went O_DIRECT (durable past the page cache) or the
    fs forced buffered writes — a page-cache memcpy number must not wear
    a 'durable' label.  The read side is config 4."""
    import shutil

    import numpy as np
    from nvme_strom_tpu.checkpoint.manager import CheckpointManager

    d = os.path.join(_scratch_dir(), "ckpt_bench")
    shutil.rmtree(d, ignore_errors=True)
    n_tensors = 8
    rows = max(1, nbytes // n_tensors // (1024 * 4))
    rng = np.random.default_rng(0)
    state = {f"w{i}": rng.standard_normal((rows, 1024), dtype=np.float32)
             for i in range(n_tensors)}
    payload = sum(v.nbytes for v in state.values())
    mgr = CheckpointManager(d, max_to_keep=None, engine=engine)

    # The row's own ceiling: the SAME payload through the engine's
    # aligned O_DIRECT streaming writer as ONE structureless tensor —
    # a write row without a write ceiling can't say whether 0.4 GiB/s
    # is the writer or the disk, and the delta to the full save prices
    # the checkpoint structure (tiles, manifest, durability flushes).
    # (A naive submit_write of unaligned user memory measures the page
    # cache, not the disk — 2.2 "GiB/s" on a 0.5 GiB/s device.)
    from nvme_strom_tpu.formats.safetensors import write_safetensors_engine
    raw_path = os.path.join(d, "raw_write.safetensors")
    blob = {"blob": np.concatenate([v.view(np.uint8).reshape(-1)
                                    for v in state.values()])}
    engine.sync_stats()
    pre_raw_direct = engine.stats.bytes_written_direct
    raw_rates = []
    for _ in range(2):
        t0 = time.monotonic()
        write_safetensors_engine(raw_path, blob, engine)
        raw_rates.append(payload / (1 << 30)
                         / (time.monotonic() - t0))
        os.unlink(raw_path)
    del blob            # don't hold a 2nd payload copy through the saves
    engine.sync_stats()
    # a buffered ceiling is a page-cache number, not a disk ceiling —
    # grade against it only when the bytes actually went O_DIRECT
    raw_is_direct = (engine.stats.bytes_written_direct - pre_raw_direct
                     >= payload * 2)
    raw_write = max(raw_rates)

    engine.sync_stats()
    pre_direct = engine.stats.bytes_written_direct
    rates = []
    for step in range(_RUNS + 1):
        t0 = time.monotonic()
        mgr.save(step, state)
        r = payload / (1 << 30) / (time.monotonic() - t0)
        if step > 0:           # step 0 warms jit/allocator paths
            rates.append(r)
    engine.sync_stats()
    direct_w = engine.stats.bytes_written_direct - pre_direct
    mode = ("durable O_DIRECT" if direct_w >= payload * _RUNS
            else "BUFFERED (unaligned spans or fs rejects O_DIRECT; "
                 "page-cache speed)")
    ph = getattr(mgr, "last_save_phases", {})
    shutil.rmtree(d, ignore_errors=True)
    rate = statistics.median(rates)
    ceiling = (f"raw_write={raw_write:.3f} GiB/s same-run "
               f"(save at {rate / raw_write:.0%} of it)"
               if raw_is_direct else
               f"raw_write=BUFFERED {raw_write:.3f} GiB/s "
               "(page-cache number, no disk ceiling on this fs)")
    return rate, (
        f"{payload >> 20}MiB/save, {mode}, {ceiling}, phases: "
        f"tiles={ph.get('tiles_s', -1):.3f}s "
        f"commit={ph.get('commit_s', -1):.3f}s (commit = manifest+"
        f"rename durability flushes; amortizes at real sizes)")


def bench_multistream(engine, nbytes: int,
                      n_streams: int = 4) -> tuple[float, str]:
    """Config 8: N concurrent file streams through ONE engine vs the same
    files read serially.  The reference's striped-raid0 story is multiple
    NVMe queues busy at once (BASELINE.md 6–10 GB/s over 3–4 SSDs); the
    engine-side requirement that story rests on is that concurrent
    streams share the queue without collapsing — scaling ≈1.0 on one SSD
    (both serial and concurrent saturate the device), >1 only on striped
    or multi-device rigs."""
    from concurrent.futures import ThreadPoolExecutor
    per = max(1 << 20, nbytes // n_streams) & ~4095
    paths = []
    for s in range(n_streams):
        p = os.path.join(_scratch_dir(), f"ms-{s}.bin")
        bench.make_file(p, per)
        paths.append(p)

    def read_one(path: str, depth: int) -> None:
        _pipelined_read(engine, path, depth)

    # Same TOTAL in-flight budget for both passes (the full queue depth):
    # serial runs one stream at full depth, concurrent N streams at
    # depth/N.  A throttled serial baseline would fake >1.0 scaling on a
    # single SSD, which is exactly the dishonesty this row must not have.
    full_depth = max(2, engine.config.queue_depth)
    per_stream_depth = max(2, engine.config.queue_depth // n_streams)

    def serial_pass() -> float:
        t0 = time.monotonic()
        for p in paths:
            read_one(p, full_depth)
        return n_streams * per / (1 << 30) / (time.monotonic() - t0)

    def concurrent_pass() -> float:
        t0 = time.monotonic()
        with ThreadPoolExecutor(n_streams) as ex:
            list(ex.map(lambda p: read_one(p, per_stream_depth), paths))
        return n_streams * per / (1 << 30) / (time.monotonic() - t0)

    serial = _steady(paths, serial_pass)
    conc = _steady(paths, concurrent_pass)
    scaling = conc / serial if serial > 0 else 0.0

    # Two-ENGINE aggregate at fixed per-stream depth (round-2 verdict
    # #8): the striped story's other half — independent engines (one per
    # member, each with its own ring/pool) must aggregate near-linearly
    # when the devices can take it.  Per-member attribution runs via the
    # simulated stripe geometry, so the accounting path the real-raid
    # rig would use is exercised and reported here.
    agg, agg_tag = _two_engine_aggregate(paths[:2])
    return conc, (f"streams={n_streams} scaling={scaling:.2f}x vs "
                  f"serial, {agg_tag}")


def _pipelined_read(eng, path: str, depth: int) -> int:
    """Whole-file depth-windowed engine read, payload discarded; the one
    read loop configs 1/8 (and the two-engine aggregate) share."""
    fh = eng.open(path)
    try:
        size = eng.file_size(fh)
        chunk = eng.config.chunk_bytes
        pend = []
        for off in range(0, size, chunk):
            pend.append(eng.submit_read(fh, off,
                                        min(chunk, size - off)))
            if len(pend) >= depth:
                p = pend.pop(0)
                p.wait()
                p.release()
        for p in pend:
            p.wait()
            p.release()
        return size
    finally:
        eng.close(fh)


def _two_engine_aggregate(paths) -> tuple[float, str]:
    from contextlib import ExitStack
    from concurrent.futures import ThreadPoolExecutor
    from nvme_strom_tpu.io.engine import StromEngine
    from nvme_strom_tpu.utils.config import EngineConfig
    from nvme_strom_tpu.utils.stats import StromStats

    saved = {k: os.environ.get(k)
             for k in ("STROM_STRIPE_ACCT", "STROM_STRIPE_SIM")}
    os.environ["STROM_STRIPE_ACCT"] = "1"
    os.environ.setdefault("STROM_STRIPE_SIM", "256:2")
    try:
        with ExitStack() as stack:
            stats = [StromStats(), StromStats()]
            engines = [StromEngine(EngineConfig(), stats=s)
                       for s in stats]
            for eng in engines:
                stack.callback(eng.close_all)
            depth = max(2, engines[0].config.queue_depth // 2)

            def single() -> float:
                t0 = time.monotonic()
                n = _pipelined_read(engines[0], paths[0], depth)
                return n / (1 << 30) / (time.monotonic() - t0)

            def both() -> float:
                t0 = time.monotonic()
                with ThreadPoolExecutor(2) as ex:
                    ns = list(ex.map(
                        lambda a: _pipelined_read(engines[a[0]], a[1],
                                                  depth),
                        enumerate(paths)))
                return sum(ns) / (1 << 30) / (time.monotonic() - t0)

            one = _steady(paths[:1], single)
            agg = _steady(paths, both)
            members: dict = {}
            for s in stats:
                for m, v in s.member_bytes.items():
                    members[m] = members.get(m, 0) + v
        total = max(1, sum(members.values()))
        dist = "/".join(f"{100 * v / total:.0f}%"
                        for _, v in sorted(members.items()))
        return agg, (f"2-engine agg={agg:.3f} GiB/s "
                     f"({agg / one:.2f}x of one, members {dist})")
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# --------------------------- compute rows ------------------------------

#: per-chip dense bf16 peak FLOP/s (public spec sheets), matched by
#: substring against ``device_kind``.  MFU needs a denominator; on an
#: unrecognized device the suite reports achieved TFLOP/s with mfu=null
#: rather than inventing a peak.
_TPU_PEAK_BF16 = (("v5p", 459e12), ("v5 lite", 197e12), ("v5e", 197e12),
                  ("trillium", 918e12), ("v6", 918e12), ("v4", 275e12))


def _peak_flops(dev) -> float | None:
    kind = (getattr(dev, "device_kind", "") or "").lower()
    for key, val in _TPU_PEAK_BF16:
        if key in kind:
            return val
    return None


def _matmul_param_count(params) -> int:
    """Matmul-participating parameter count: every ≥2-d weight except the
    token embedding (a gather, not a matmul).  6·T·this is the standard
    fwd+bwd matmul-FLOPs estimate (PaLM appendix B convention)."""
    return sum(int(v.size) for k, v in params.items()
               if getattr(v, "ndim", 0) >= 2 and k != "tok_embed")


def _tiny_compute() -> bool:
    """STROM_SUITE_TINY_COMPUTE=1 shrinks the compute rows to CI scale
    (the CPU-pinned test suite can't push half a TFLOP per step)."""
    return os.environ.get("STROM_SUITE_TINY_COMPUTE") == "1"


def _bench_cfg(train_override: bool = False):
    """One config for both compute rows.  Sized by measurement on the
    v5e: MFU scales with matmul size (d=512 → 8.8%, d=1024 → 15.7%,
    d=2048 → 35.3% at b=8 s=1024), so the row uses d=2048 — large enough
    for real MXU tiles, small enough to compile in ~20 s.  remat stays
    off: it costs ~6 points of measured MFU here (recompute FLOPs are
    real but not model FLOPs) and HBM fits the activations at this
    size.

    ``train_override=True`` (the train/profile rows ONLY) honors
    STROM_TRAIN_CFG; decode/kv/serving rows ignore it — their ledger
    tags carry no shape, so an override there would produce rows
    indistinguishable from default-config ones."""
    from nvme_strom_tpu.models.transformer import TransformerConfig
    if _tiny_compute():
        if train_override and os.environ.get("STROM_TRAIN_CFG"):
            _log("suite: STROM_TRAIN_CFG ignored under "
                 "STROM_SUITE_TINY_COMPUTE=1 (tiny shape wins)")
        return TransformerConfig(vocab=256, d_model=64, n_layers=2,
                                 n_heads=4, n_kv_heads=2, d_ff=128,
                                 max_seq=256)
    cfg = TransformerConfig(vocab=16384, d_model=2048, n_layers=8,
                            n_heads=16, n_kv_heads=8, d_ff=5632,
                            max_seq=2048)
    # STROM_TRAIN_CFG="d=4096,L=2,ff=11008,heads=32,kv=8[,vocab=N]"
    # overrides the model shape — the MFU curve is matmul-size-bound
    # (still rising at d=2048), so the sweep needs points where the
    # per-layer matmuls are bigger than the default's.  A bad spec is
    # logged and ignored: one typo must not lose a scarce TPU window.
    spec = os.environ.get("STROM_TRAIN_CFG", "") if train_override else ""
    if spec:
        alias = {"d": "d_model", "L": "n_layers", "ff": "d_ff",
                 "heads": "n_heads", "kv": "n_kv_heads",
                 "vocab": "vocab", "xc": "xent_chunks",
                 "s": "max_seq"}
        try:
            kw = {}
            for part in spec.split(","):
                k, v = part.split("=")
                kw[alias[k.strip()]] = int(v)
            cfg = dataclasses.replace(cfg, **kw)
            _log(f"suite: train cfg override {kw}")
        except (ValueError, KeyError) as e:
            _log(f"suite: ignoring bad STROM_TRAIN_CFG {spec!r} ({e}); "
                 f"want 'd=4096,L=2,ff=11008,heads=32,kv=8'")
    return cfg


def bench_decode(device=None) -> tuple[float, str]:
    """Config 6: autoregressive decode throughput.  The whole generation
    is one jitted lax.scan (models/decode.py), so the number measures
    on-device steady-state decode, not per-token dispatch.

    Two regimes (measured on the v5e, d=2048, prefill-subtracted): short
    cache, where XLA's fused einsum wins (6726 vs 4916 tok/s at S≈160),
    and long cache, where the Pallas decode-attention kernel is ~1.7x
    faster (3066 vs 1813 tok/s at S≈1856) — each regime runs its winner;
    the short number is the headline value, the long-context one rides
    the metric tag."""
    import functools
    import jax
    import jax.numpy as jnp
    from nvme_strom_tpu.models.decode import generate
    from nvme_strom_tpu.models.transformer import init_params
    from nvme_strom_tpu.ops.decode_attention import make_decode_attn
    cfg = _bench_cfg()
    # tiny: 48 decode steps vs an 8-token prefill so the prefill-
    # subtracted decode time stays well clear of CPU timing noise
    batch, prompt_len, new = (2, 8, 48) if _tiny_compute() else (8, 32, 128)
    dev = device or jax.devices()[0]
    params = jax.device_put(init_params(jax.random.key(0), cfg), dev)

    def run_gen(plen: int, n_new: int, cache_attn) -> float:
        """Steady-state decode tok/s: the timed window of a full
        generate() includes the prompt prefill, so a prefill-only run
        (max_new_tokens=1) is measured too and subtracted — the rate is
        (n_new - 1) decode steps over decode-only time, not prefill
        amortized over the generated tokens."""
        prompt = jax.device_put(jax.random.randint(
            jax.random.key(1), (batch, plen), 0, cfg.vocab,
            dtype=jnp.int32), dev)

        def med_time(n_tok: int) -> float:
            gen = jax.jit(functools.partial(
                generate, cfg=cfg, max_new_tokens=n_tok,
                cache_attn=cache_attn))
            gen(params, prompt).block_until_ready()  # compile (discarded)
            ts = []
            for _ in range(_RUNS):
                t0 = time.monotonic()
                gen(params, prompt).block_until_ready()
                ts.append(time.monotonic() - t0)
            return statistics.median(ts)

        t_full = med_time(n_new)
        t_prefill = med_time(1)
        if t_full <= t_prefill * 1.02:
            # Timing noise swallowed the decode phase (tiny configs on a
            # loaded CPU).  0.0 is visibly invalid; a clamped division
            # would record an absurd tok/s as if it were real.
            _log(f"suite: WARNING decode timing invalid "
                 f"(t_full={t_full:.4f}s <= t_prefill={t_prefill:.4f}s) "
                 f"— reporting 0.0")
            return 0.0
        return batch * (n_new - 1) / (t_full - t_prefill)

    short = run_gen(prompt_len, new, None)
    tag = f"batch={batch} new={new}"
    # int8 weight-only leg: decode is weight-streaming bound, so the
    # halved weight bytes should show directly (models/quant.py); the
    # fp params are swapped out so both legs fit side by side
    from nvme_strom_tpu.models.quant import quantize_weights_int8
    qparams = jax.device_put(quantize_weights_int8(
        jax.device_get(params)), dev)
    fp_params, params = params, qparams
    int8_rate = run_gen(prompt_len, new, None)
    params = fp_params
    if short > 0 and int8_rate > 0:
        tag += f", int8={int8_rate:.0f}tok/s ({int8_rate / short:.2f}x)"
    else:   # the 0.0 timing-invalid sentinel must not fabricate a ratio
        tag += f", int8={int8_rate:.0f}tok/s (ratio n/a)"
    # Long-context leg: TPU only — off-TPU the Pallas kernel runs in the
    # interpreter, where a d=2048 S~1856 scan would take hours.
    if not _tiny_compute() and jax.default_backend() == "tpu":
        long_plen = cfg.max_seq - 256
        long_rate = run_gen(long_plen, 64, make_decode_attn())
        tag += (f", longctx={long_rate:.0f}tok/s"
                f"@S{long_plen + 64}(pallas)")
    return short, tag


def bench_kv_offload(engine, device=None) -> tuple[float, str]:
    """Config 10: decode throughput with the SSD-backed KV cache.

    The HBM window holds only a fraction of the attention history; the
    rest streams back from NVMe through the engine every step.  The
    tok/s is storage-bound BY DESIGN — the row prices the capability of
    decoding past HBM, and the tag reports the per-token streamed bytes
    so the number can be sanity-checked against raw bandwidth."""
    import jax
    import jax.numpy as jnp
    from nvme_strom_tpu.models import decode as _dec
    from nvme_strom_tpu.models.kv_offload import (
        OffloadConfig, PagedKVCache, offload_decode_step)
    from nvme_strom_tpu.models.transformer import init_params
    cfg = _bench_cfg()
    if _tiny_compute():
        batch, plen, steps, page_len, wpages = 2, 24, 8, 8, 1
    else:
        batch, plen, steps, page_len, wpages = 8, 1024, 16, 128, 2
    dev = device or jax.devices()[0]
    params = jax.device_put(init_params(jax.random.key(0), cfg), dev)
    prompt = jax.device_put(jax.random.randint(
        jax.random.key(1), (batch, plen), 0, cfg.vocab, dtype=jnp.int32),
        dev)
    dense = _dec.init_cache(cfg, batch, plen)
    logits, dense = _dec.prefill(params, prompt, cfg, dense)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    quant = os.environ.get("STROM_KVOFF_QUANT") or None
    host_cache = int(os.environ.get("STROM_KVOFF_HOSTCACHE", "0") or 0)
    ocfg = OffloadConfig(
        path=os.path.join(_scratch_dir(), "kvoff.bin"),
        page_len=page_len, window_pages=wpages, quantize=quant,
        host_cache_pages=host_cache)
    stats = engine.stats
    with PagedKVCache(cfg, ocfg, engine, batch, device=dev) as cache:
        cache.append(dense["k"], dense["v"])
        del dense
        # first step compiles the per-layer segments — discard it
        logits = offload_decode_step(params, tok, cfg, cache)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        # Cold discipline (suite docstring): the pages were JUST
        # written, so without eviction a buffered-fs run would stream
        # them from DRAM and call it SSD bandwidth.  Mid-loop evictions
        # re-dirty the cache; the direct-read share in the tag is the
        # honest label for whatever the fs allowed.
        bench.evict_file(ocfg.path)
        engine.sync_stats()
        dev0, dir0 = stats.bytes_to_device, stats.bytes_direct
        rd0 = dir0 + stats.bytes_fallback
        ts = []
        for _ in range(steps):
            t0 = time.monotonic()
            logits = offload_decode_step(params, tok, cfg, cache)
            logits.block_until_ready()
            ts.append(time.monotonic() - t0)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        engine.sync_stats()
        streamed = (stats.bytes_to_device - dev0) / steps
        read_total = stats.bytes_direct + stats.bytes_fallback - rd0
        direct_share = ((stats.bytes_direct - dir0) / read_total
                        if read_total else 0.0)
        # measured AFTER the loop: the steps themselves evict pages
        cold_frac = 1 - cache.count / cache.pos
    rate = batch / statistics.median(ts)
    tag = (f"ctx={plen} window={ocfg.window} cold={cold_frac:.0%} "
           f"stream/tok={streamed / 2**20:.1f}MiB "
           f"direct={direct_share:.0%}")
    if quant:
        tag += f" quant={quant}"
    if host_cache:
        tag += f" hostcache={host_cache}p"
    return rate, tag


def bench_serving(device=None) -> tuple[float, str]:
    """Config 11: continuous-batching aggregate decode throughput.

    Mixed-length requests keep every slot busy (a freed slot admits the
    next request mid-flight); the number is total generated tokens over
    wall-clock from first step to drain, admission prefills included —
    the end-to-end serving rate, not a per-step best case."""
    import jax
    from nvme_strom_tpu.models.serving import DecodeServer
    from nvme_strom_tpu.models.transformer import init_params
    cfg = _bench_cfg()
    if _tiny_compute():
        slots, n_req, max_len = 2, 4, 64
        lens = [5, 9, 13, 7]
        news = [6, 8, 5, 7]
    else:
        slots, n_req, max_len = 8, 24, 1536
        lens = [128 + 61 * (i % 7) for i in range(n_req)]
        news = [64 + 17 * (i % 5) for i in range(n_req)]
    dev = device or jax.devices()[0]
    params = jax.device_put(init_params(jax.random.key(0), cfg), dev)
    paged = os.environ.get("STROM_SERVE_PAGED") == "1"
    block_len = 16 if _tiny_compute() else 128
    # pool sized for the live-token high-water mark: the `slots`
    # largest concurrent worst cases (the paged design point — far
    # below slots × max_len)
    shared_prefix = os.environ.get("STROM_SERVE_SHARED_PREFIX")
    shared = []
    if shared_prefix:
        # config-11 variant: every request shares a system prompt of N
        # tokens — the paged server's automatic prefix caching prefills
        # it once and reuses the blocks (tag reports the cache gauges)
        import numpy as np
        shared = np.random.default_rng(2).integers(
            0, cfg.vocab, int(shared_prefix)).tolist()
    worst = sorted((len(shared) + l + n for l, n in zip(lens, news)),
                   reverse=True)[:slots]
    total_blocks = sum(-(-w // block_len) for w in worst)

    def make():
        return DecodeServer(params, cfg, max_batch=slots, max_len=max_len,
                            total_blocks=total_blocks if paged else None,
                            block_len=block_len)

    def submit_all(srv):
        import numpy as np
        rng = np.random.default_rng(1)
        for i in range(n_req):
            srv.submit(i, shared
                       + rng.integers(0, cfg.vocab, lens[i]).tolist(),
                       news[i])

    # decode sub-steps per host readback: the round-3 on-silicon row
    # (43.6 tok/s vs 6,826 decode) was one blocking readback per token
    # over a high-latency link; lookahead amortizes it (verdict #6)
    lookahead = int(os.environ.get("STROM_SERVE_LOOKAHEAD", "8"))

    # warmup run compiles the step + admission buckets (discarded)
    srv = make()
    submit_all(srv)
    srv.run(lookahead=lookahead)
    ts = []
    for _ in range(_RUNS):
        srv = make()
        submit_all(srv)
        t0 = time.monotonic()
        out = srv.run(lookahead=lookahead)
        ts.append(time.monotonic() - t0)
    total = sum(news)
    wall = statistics.median(ts)
    rate = total / wall
    # phase attribution from the LAST run (its wall time for scale):
    # admission+prefill, back-to-back dispatch, readback syncs, and
    # the host-scheduling remainder
    tm = srv.timings
    other = max(ts[-1] - tm["admit_s"] - tm["dispatch_s"]
                - tm["readback_s"], 0.0)
    tag = (f"slots={slots} reqs={n_req} tok/req~{total // n_req} "
           f"lookahead={lookahead}; phases(last run "
           f"{ts[-1]:.2f}s): admit={tm['admit_s']:.2f}s "
           f"dispatch={tm['dispatch_s']:.2f}s "
           f"readback={tm['readback_s']:.2f}s "
           f"sched={other:.2f}s, steps={tm['steps']}")
    if paged:
        tag += (f" paged={total_blocks}x{block_len} "
                f"({total_blocks * block_len * 100 // (slots * max_len)}"
                f"% of dense)")
        if shared:
            st = srv.stats()
            tag += (f", shared_prefix={len(shared)}tok "
                    f"hits={st['prefix_hits']} "
                    f"reused_blocks={st['prefix_shared_blocks']}")
    return rate, tag


def bench_kvserve(engine, device=None) -> tuple[float, str]:
    """Config 19: serving throughput with the content-addressed NVMe
    KV prefix store (models/kv_offload.py PrefixStore, docs/PERF.md
    §5).

    Mixed-length requests share a system prompt; the run measures the
    store-ON steady state (prefix pages restored from NVMe through the
    decode-class batched read path instead of re-prefilled) and pairs
    it with an identical store-OFF run in the same process — the tag
    carries both TTFT averages, the aggregate-rate ratio, and the
    store's dedupe/hit counters.  tok/s is the headline because the
    prefix win IS admission time: every re-prefilled shared token is
    wall-clock the batch spends not decoding."""
    import jax
    from nvme_strom_tpu.models.kv_offload import PrefixStore
    from nvme_strom_tpu.models.serving import DecodeServer
    from nvme_strom_tpu.models.transformer import init_params
    cfg = _bench_cfg()
    # the shared prefix must be LONG relative to a page: the win is
    # admission prefill skipped, and a too-short prefix costs as much
    # to restore as to recompute — so the tiny row keeps the tiny
    # WIDTH but serves real sequence lengths (the prefill cost being
    # skipped is attention-length-bound, dispatch included)
    if _tiny_compute():
        cfg = dataclasses.replace(cfg, max_seq=1024)
        slots, n_req, max_len, page_tokens, n_pages, max_new = \
            2, 6, 512, 32, 8, 6
    else:
        slots, n_req, max_len, page_tokens, n_pages, max_new = \
            8, 24, 1536, 64, 8, 48
    dev = device or jax.devices()[0]
    params = jax.device_put(init_params(jax.random.key(0), cfg), dev)
    import numpy as np
    rng = np.random.default_rng(5)
    shared = rng.integers(0, cfg.vocab, n_pages * page_tokens).tolist()
    reqs = [(i, shared + rng.integers(
        0, cfg.vocab, 2 + int(rng.integers(0, 5))).tolist(), max_new)
        for i in range(n_req)]
    lookahead = int(os.environ.get("STROM_SERVE_LOOKAHEAD", "8"))
    store_path = os.path.join(_scratch_dir(), "suite.kvstore")
    stats = engine.stats
    snap0 = stats.snapshot()

    def run(store) -> tuple[float, float]:
        srv = DecodeServer(params, cfg, max_batch=slots,
                           max_len=max_len, kv_store=store)
        for rid, p, m in reqs:
            srv.submit(rid, p, m)
        t0 = time.monotonic()
        srv.run(lookahead=lookahead)
        wall = time.monotonic() - t0
        ttft = (sum(v["ttft_ms"] for v in srv.request_metrics.values())
                / max(1, len(srv.request_metrics)))
        return sum(m for _r, _p, m in reqs) / wall, ttft

    run(None)                      # warm: compiles the store-off phases
    with PrefixStore(cfg, engine, store_path, page_tokens=page_tokens,
                     capacity_bytes=64 << 20) as store:
        run(store)                 # seed: writes the shared pages once
        #                            and compiles the restore phases
        # alternating trials + medians (the bench_mixed discipline):
        # host noise drifts within a suite step, and a single
        # off-then-on pair ratios one mode against the other's minute
        offs, ons = [], []
        for _ in range(3):
            offs.append(run(None))
            ons.append(run(store))
    rate_off, ttft_off = sorted(offs)[len(offs) // 2]
    rate_on, ttft_on = sorted(ons)[len(ons) // 2]
    snap1 = stats.snapshot()
    d = lambda k: int(snap1.get(k, 0)) - int(snap0.get(k, 0))  # noqa: E731
    hits, misses = d("kv_prefix_hits"), d("kv_prefix_misses")
    tag = (f"reqs={n_req} shared={n_pages * page_tokens}tok "
           f"page={page_tokens}tok; TTFT off={ttft_off:.1f}ms "
           f"on={ttft_on:.1f}ms ({100 * (ttft_off - ttft_on) / ttft_off:+.1f}% "
           f"off-rate={rate_off:.1f}tok/s ratio={rate_on / rate_off:.2f}); "
           f"hit_rate={hits / max(1, hits + misses):.3f} "
           f"deduped={d('kv_pages_deduped')} "
           f"saved={_human_int(d('kv_bytes_saved'))} "
           f"restored={d('kv_pages_restored')}")
    return rate_on, tag


def _human_int(n: int) -> str:
    from nvme_strom_tpu.utils.stats import human_bytes
    return human_bytes(float(n)).replace(" ", "")


def _train_setup(cfg, batch: int, seq: int, dev, attn: str = "dense"):
    """(params, opt_state, tokens, step, flops_step) shared by the
    synthetic (config 7) and NVMe-fed (config 17) train rows — ONE
    copy of the donated-step construction and the 6·T·P + attention
    model-FLOP formula, so the two TFLOP/s rows cannot diverge."""
    import jax
    import jax.numpy as jnp
    import optax
    from nvme_strom_tpu.models.transformer import (init_params,
                                                   make_train_step)
    attn_fn = None
    if attn == "flash":
        from nvme_strom_tpu.ops.flash_attention import make_flash_attn
        attn_fn = make_flash_attn()
    elif attn != "dense":
        raise ValueError(f"attn {attn!r}: expected dense|flash")
    params = jax.device_put(init_params(jax.random.key(0), cfg), dev)
    opt = optax.adamw(1e-3)
    opt_state = jax.device_put(opt.init(params), dev)
    tokens = jax.device_put(jax.random.randint(
        jax.random.key(1), (batch, seq), 0, cfg.vocab, dtype=jnp.int32),
        dev)
    n_matmul = _matmul_param_count(params)
    flops_step = (6 * batch * seq * n_matmul
                  + 12 * cfg.n_layers * batch * seq * seq * cfg.d_model)
    step = jax.jit(make_train_step(cfg, opt, attn_fn=attn_fn),
                   donate_argnums=(0, 1))
    return params, opt_state, tokens, step, flops_step


def _loss_sanity(vals: list) -> None:
    """A real Adam trajectory moves the loss every step and keeps it
    finite; anything else means the device did not actually run the
    program."""
    if not all(math.isfinite(v) for v in vals) or len(set(vals)) <= 1:
        raise RuntimeError(f"loss sanity failed (runtime returned "
                           f"garbage without raising): losses={vals[:6]}")


def _train_variant(cfg, batch: int, seq: int, dev,
                   profile_dir: str | None = None,
                   attn: str = "dense") -> float:
    """Aggregate model-FLOP/s of one (config, batch, attn) train-step
    variant — _RUNS chained steps in ONE timed window bracketed by
    data-dependent host transfers (not per-step medians: the chain's
    last value cannot reach the host before every step ran); optionally
    capture a 3-step jax profiler trace while at it.  ``attn``:
    "dense" (XLA) or "flash" (the Pallas fused kernel — O(s) memory,
    the long-context/occupancy lever)."""
    import jax
    params, opt_state, tokens, step, flops_step = _train_setup(
        cfg, batch, seq, dev, attn=attn)
    if profile_dir:
        # the post-optimization HLO names the profiler's events: the
        # valid window-7 parses put ~70% of device time in bare
        # "%fusion.NN" buckets, which explains nothing — dumping the
        # compiled module lets profile_report resolve each fusion to
        # its constituent ops (dot/reduce/elementwise) and attribute
        # the MFU ceiling for real.  AOT lower+compile of the SAME jit
        # hits the compile cache; donation only applies at execution.
        try:
            txt = step.lower(params, opt_state, tokens).compile().as_text()
            os.makedirs(profile_dir, exist_ok=True)
            with open(os.path.join(profile_dir, "optimized_hlo.txt"),
                      "w") as f:
                f.write(txt)
        except Exception as e:          # the dump is an extra, not the row
            _log(f"suite: optimized-HLO dump unavailable: {e!r}")
    params, opt_state, loss = step(params, opt_state, tokens)  # compile
    jax.block_until_ready((params, opt_state, loss))
    # Timing discipline: bracket N CHAINED steps between data-dependent
    # host transfers.  float(loss) before the clock pins the start; the
    # final float() cannot produce bytes until every chained step has
    # executed (step k consumes step k-1's donated params), so
    # dispatch-only timing is impossible by construction.
    float(loss)                       # host round-trip: timeline start
    losses = []
    t0 = time.monotonic()
    for _ in range(_RUNS):
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(loss)
    float(losses[-1])                 # forces the whole chain
    elapsed = time.monotonic() - t0
    rate = _RUNS * flops_step / elapsed
    _loss_sanity([float(x) for x in jax.device_get(losses)])
    if profile_dir:
        # the committed profile breakdown for the MFU story: 3 traced
        # steps, viewable in TensorBoard/xprof
        with jax.profiler.trace(profile_dir):
            for _ in range(3):
                params, opt_state, loss = step(params, opt_state,
                                               tokens)
            # data-dependent host fetch, NOT block_until_ready: on the
            # shapes where blocking returns early the trace context
            # would close before the steps execute, committing an
            # empty trace as MFU "evidence"
            float(loss)
        _log(f"suite: wrote jax profiler trace to {profile_dir}")
    del params, opt_state
    return rate


def bench_opt_offload(engine) -> tuple[float, str]:
    """Config 14: NVMe-offloaded Adam (parallel/opt_offload) priced
    against the in-HBM optax step on the same tree.

    The value is the moment-streaming rate: 4× moment payload (2 reads +
    2 writes) per update over the update's wall time — the number that
    says whether the engine keeps the optimizer fed.  The tag prices the
    capability: step-time overhead vs in-HBM adamw, and the HBM the
    moments actually occupy (one group) vs what in-HBM Adam would pin."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from nvme_strom_tpu.parallel.opt_offload import OffloadedAdam

    tiny = _tiny_compute()
    leaf = (1 << 18) if tiny else (1 << 22)       # elements per leaf
    n_leaves = 4 if tiny else 16                  # 4 MiB / 256 MiB params
    ks = jax.random.split(jax.random.key(0), n_leaves)
    params = {f"w{i:02d}": jax.random.normal(k, (leaf,), jnp.float32)
              for i, k in enumerate(ks)}
    grads = {k: jax.random.normal(jax.random.key(hash(k) % (1 << 30)),
                                  v.shape, jnp.float32)
             for k, v in params.items()}
    payload = 2 * sum(v.nbytes for v in params.values())

    # in-HBM reference: one fused jitted adamw step
    opt = optax.adamw(1e-3)
    state = opt.init(params)

    @jax.jit
    def hbm_step(p, s, g):
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s

    hbm_step(params, state, grads)  # compile
    t0 = time.monotonic()
    reps = 3
    p = params
    for _ in range(reps):
        p, state = hbm_step(p, state, grads)
    jax.block_until_ready(p)
    t_hbm = (time.monotonic() - t0) / reps

    # fresh state every invocation: a stale dir would either resume old
    # moments (not a step-1 benchmark) or refuse on a layout change
    odir = os.path.join(_scratch_dir(), "opt_offload")
    shutil.rmtree(odir, ignore_errors=True)
    with OffloadedAdam(odir, params, lr=1e-3, weight_decay=1e-4,
                       engine=engine,
                       group_bytes=(1 << 22) if tiny else (64 << 20)
                       ) as off:
        off.update(params, grads)   # compile + first touch
        t0 = time.monotonic()
        p = params
        for _ in range(reps):
            p = off.update(p, grads)
        jax.block_until_ready(p)
        t_off = (time.monotonic() - t0) / reps
        peak = off.peak_group_bytes()
        groups = off.num_groups()
    gibs = 2 * payload / t_off / (1 << 30)        # 2R + 2W of the payload
    over = (t_off - t_hbm) / t_hbm if t_hbm > 0 else float("inf")
    # Medium normalization: an overhead figure with no link context
    # says nothing about the implementation.  The step must move 2x the
    # moment payload; at the same-run measured link that takes
    # t_floor = bytes/link, so overhead below is bounded by the medium,
    # not the implementation.  The projection column re-prices the step
    # at the same-run RAW SSD rate — the rate a local deployment's
    # storage path actually delivers — and the LINK-BOUND tag fires
    # when >=50% of the step went to link-floor time, telling a reader
    # the headline overhead measures the host→device link.
    raw_ceiling = _CEILINGS.get("raw", 0.0)
    link_ceiling = _CEILINGS.get("link", 0.0)
    moved = 2 * payload
    extra = ""
    if link_ceiling > 0 and raw_ceiling > 0:
        t_floor = moved / (link_ceiling * (1 << 30))
        t_local = max(moved / (raw_ceiling * (1 << 30)), 1e-9)
        over_local = ((t_hbm + t_local) - t_hbm) / t_hbm \
            if t_hbm > 0 else float("inf")
        bound = "LINK-BOUND, " if t_floor >= 0.5 * t_off else ""
        extra = (f", link-normalized: {bound}link-floor="
                 f"{t_floor * 1e3:.0f}ms of {t_off * 1e3:.0f}ms at "
                 f"{link_ceiling:.3f} GiB/s; projected at same-run raw "
                 f"{raw_ceiling:.3f} GiB/s: step="
                 f"{(t_hbm + t_local) * 1e3:.0f}ms "
                 f"overhead={over_local:+.0%}")
    return gibs, (f"moments={payload >> 20}MiB step={t_off * 1e3:.0f}ms "
                  f"overhead={over:+.0%} vs in-HBM "
                  f"({t_hbm * 1e3:.0f}ms), hbm_peak={peak >> 20}MiB of "
                  f"{payload >> 20}MiB, groups={groups}{extra}")


def bench_act_offload(engine, device=None) -> tuple[float, str]:
    """Config 18: NVMe-offloaded saved activations
    (parallel/act_offload, remat_policy="nvme") priced against
    remat="full" — the honest in-HBM comparison, since BOTH recompute
    every layer in backward; the delta is exactly the activation round
    trip (device→host→NVMe→host→device per layer per step) that buys
    O(1)-layers HBM activations below full remat's O(n_layers).

    The value is the activation-streaming rate (2 × layers × act
    bytes per step over the step time); the tag prices step overhead
    vs remat="full" and link-normalizes it like config 14 (where the
    link floor, not the implementation, bounds the overhead)."""
    import jax
    import numpy as np
    from nvme_strom_tpu.parallel.act_offload import ActivationStore
    cfg = _bench_cfg(train_override=True)
    batch, seq = (2, 64) if _tiny_compute() else (8, 1024)
    # honor an applied s= override exactly like bench_train, so a
    # long-context window's config-18 row shares config 7's shape
    if not _tiny_compute() and cfg.max_seq != _bench_cfg().max_seq:
        seq = cfg.max_seq
    dev = device or jax.devices()[0]
    rcfg = dataclasses.replace(cfg, remat_policy="full")
    ncfg = dataclasses.replace(cfg, remat_policy="nvme")
    params, opt_state, tokens, _step_unused, flops_step = _train_setup(
        rcfg, batch, seq, dev)

    import optax
    opt = optax.adamw(1e-3)

    def run(step, p, s, reps=3):
        p, s, loss = step(p, s, tokens)          # compile + warm slots
        jax.block_until_ready(loss)
        float(loss)
        losses = []
        t0 = time.monotonic()
        for _ in range(reps):
            p, s, loss = step(p, s, tokens)
            losses.append(loss)
        float(losses[-1])
        dt = (time.monotonic() - t0) / reps
        _loss_sanity([float(x) for x in jax.device_get(losses)])
        return dt

    from nvme_strom_tpu.models.transformer import make_train_step
    t_full = run(jax.jit(make_train_step(rcfg, opt)), params, opt_state)

    adir = os.path.join(_scratch_dir(), "act_offload")
    shutil.rmtree(adir, ignore_errors=True)
    act_bytes = (batch * seq * cfg.d_model
                 * np.dtype(cfg.dtype).itemsize)
    with ActivationStore(os.path.join(adir, "acts.bin"),
                         cfg.n_layers, engine=engine) as st:
        t_nvme = run(jax.jit(make_train_step(ncfg, opt, act_store=st)),
                     params, opt_state)
    moved = 2 * cfg.n_layers * act_bytes          # 1W + 1R per layer
    gibs = moved / t_nvme / (1 << 30)
    over = (t_nvme - t_full) / t_full if t_full > 0 else float("inf")
    raw_c, link_c = _CEILINGS.get("raw", 0.0), _CEILINGS.get("link", 0.0)
    extra = ""
    if raw_c > 0 and link_c > 0:
        t_floor = moved / (link_c * (1 << 30))
        t_local = moved / (raw_c * (1 << 30))
        bound = "LINK-BOUND, " if t_floor >= 0.5 * t_nvme else ""
        extra = (f", link-normalized: {bound}link-floor="
                 f"{t_floor * 1e3:.0f}ms of {t_nvme * 1e3:.0f}ms at "
                 f"{link_c:.3f} GiB/s; projected at same-run raw "
                 f"{raw_c:.3f} GiB/s: step="
                 f"{(t_full + t_local) * 1e3:.0f}ms "
                 f"overhead={t_local / t_full:+.0%}")
    tag = (f"acts={moved >> 20}MiB/step ({cfg.n_layers} layers x "
           f"{act_bytes >> 20}MiB x2) step={t_nvme * 1e3:.0f}ms "
           f"overhead={over:+.0%} vs remat-full "
           f"({t_full * 1e3:.0f}ms){extra}")
    _log(f"suite: act-offload {tag}")
    return gibs, tag


def bench_fed_train(engine, device=None) -> tuple[float, str]:
    """Config 17: the reference's core identity as ONE number — train
    while the NVMe pipeline feeds REAL token batches, paired in the
    same run against the identical model chained on a device-resident
    batch.  fed/synthetic ≈ 1.0 means storage never starves the MXU
    (the SSD→accelerator direct path doing the job the reference's
    SSD2GPU DMA does for PG-Strom's kernels, SURVEY.md §3.5, applied
    to the training loop); the tag carries both rates, the ratio, and
    the pipeline's byte demand so a sub-1.0 row names its own cause.

    Tokens ride the zero-copy wds_raw path: each tar member is one
    sample row of ``seq`` int32 tokens; bytes go staging→device
    untouched and the int32 assembly + vocab clamp run on device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from nvme_strom_tpu.data.loader import ShardedLoader
    cfg = _bench_cfg(train_override=True)
    batch, seq = (2, 64) if _tiny_compute() else (8, 1024)
    n_steps = 4 if _tiny_compute() else 16
    dev = device or jax.devices()[0]
    item = seq * 4
    paths = make_wds_shards(os.path.join(_scratch_dir(), "fedtrain"),
                            n_steps * batch * item, item_bytes=item)
    params, opt_state, tokens0, step, flops_step = _train_setup(
        cfg, batch, seq, dev)

    @jax.jit
    def decode_tokens(arr):
        # (batch, seq*4) uint8 → (batch, seq) int32 tokens: assemble
        # little-endian words on the VPU, clamp into the vocab — the
        # raw member bytes ARE the training data, no host touch
        b = arr.reshape(batch, seq, 4).astype(jnp.int32)
        word = b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16)
        return word % cfg.vocab

    params, opt_state, loss = step(params, opt_state, tokens0)  # compile
    jax.block_until_ready((params, opt_state, loss))

    # synthetic window — _train_variant's chained bracket discipline,
    # loss-sanity-gated like every other train row
    float(loss)
    losses = []
    t0 = time.monotonic()
    for _ in range(n_steps):
        params, opt_state, loss = step(params, opt_state, tokens0)
        losses.append(loss)
    float(losses[-1])
    t_syn = time.monotonic() - t0
    _loss_sanity([float(x) for x in jax.device_get(losses)])
    rate_syn = n_steps * flops_step / t_syn

    mesh = Mesh(np.array([dev]).reshape(1), ("dp",))
    with ShardedLoader(paths, mesh, global_batch=batch, fmt="wds_raw",
                       engine=engine) as loader:
        for arr in loader:        # warm: loader jit + decode compile
            params, opt_state, loss = step(params, opt_state,
                                           decode_tokens(arr))
        for p in paths:
            bench.evict_file(p)   # the timed epoch reads the NVMe
        float(loss)
        losses = []
        t0 = time.monotonic()
        for arr in loader:
            params, opt_state, loss = step(params, opt_state,
                                           decode_tokens(arr))
            losses.append(loss)
        float(losses[-1])
        t_fed = time.monotonic() - t0
    n = len(losses)
    _loss_sanity([float(x) for x in jax.device_get(losses)])
    rate_fed = n * flops_step / t_fed
    ratio = rate_fed / rate_syn if rate_syn else float("nan")
    demand = n * batch * item / (1 << 30) / t_fed
    peak = _peak_flops(dev)
    suspect = (" SUSPECT-TIMING (above device peak)"
               if peak and max(rate_fed, rate_syn) > peak else "")
    tag = (f"fed={rate_fed / 1e12:.2f} TFLOP/s over {n} NVMe-fed steps "
           f"vs synthetic={rate_syn / 1e12:.2f} (same run) "
           f"ratio={ratio:.3f}{suspect}; "
           f"pipeline demand={demand:.4f} GiB/s "
           f"d={cfg.d_model} b={batch} s={seq}")
    _log(f"suite: fed-train {tag}")
    return rate_fed / 1e12, tag


def bench_train(device=None) -> tuple[float, str]:
    """Config 7: train-step throughput as model TFLOP/s (and MFU when the
    chip's peak is known).  FLOPs are the 6·T·P matmul estimate plus the
    12·L·b·s²·d attention term — model FLOPs, not hardware FLOPs, so
    remat or XLA fusion can't inflate the number.

    STROM_TRAIN_SWEEP="<batch>:<remat>[:<attn>],..." (remat
    none|dots|full, attn dense|flash) runs several variants and reports
    the best, each in the tag — the MFU lever sweep (batch amortizes
    weight streaming; dots-remat keeps the bigger batch inside HBM at a
    fraction of full remat's recompute; flash trades XLA's fused dense
    attention for the Pallas kernel's O(s) memory).
    STROM_PROFILE_DIR captures a 3-step jax profiler trace of the LAST
    sweep variant (order the sweep so the variant to profile is last —
    tracing rides that variant's measuring run, no re-compile)."""
    import jax
    cfg = _bench_cfg(train_override=True)
    batch, seq = (2, 64) if _tiny_compute() else (8, 1024)
    # an APPLIED max_seq override in STROM_TRAIN_CFG trains at that
    # sequence (the long-context rows); detected from the parsed
    # config — not by re-reading the env var — so a malformed spec
    # (which _bench_cfg logs and ignores) safely keeps the historical
    # s=1024 shape instead of silently training at the default
    # max_seq.  An explicit s= equal to the default is the one
    # indistinguishable case and keeps s=1024.
    if not _tiny_compute() and cfg.max_seq != _bench_cfg().max_seq:
        seq = cfg.max_seq
    dev = device or jax.devices()[0]
    sweep = os.environ.get("STROM_TRAIN_SWEEP", "")
    variants = []
    if sweep:
        for spec in sweep.split(","):
            spec = spec.strip()
            if not spec:
                continue
            parts = spec.split(":")
            try:
                variants.append((int(parts[0]),
                                 parts[1] if len(parts) > 1 and parts[1]
                                 else "none",
                                 parts[2] if len(parts) > 2
                                 and parts[2] else "dense"))
            except (ValueError, IndexError):
                # one typo must not lose the whole (scarce) TPU step
                _log(f"suite: ignoring bad sweep spec {spec!r} "
                     "(want '<batch>:<none|dots|full>[:<dense|flash>]')")
    if not variants:
        variants = [(batch, cfg.remat_policy or "none", "dense")]
    prof = os.environ.get("STROM_PROFILE_DIR")
    results, failures = [], []
    for i, (b, pol, attn) in enumerate(variants):
        vcfg = dataclasses.replace(cfg, remat_policy=pol, remat=False)
        try:
            # trace rides the measuring call of the final variant — no
            # separate re-compile/re-run just to profile
            fs = _train_variant(vcfg, b, seq, dev,
                                profile_dir=(prof if prof and
                                             i == len(variants) - 1
                                             else None), attn=attn)
        except Exception as e:  # noqa: BLE001 — OOM on a sweep point
            reason = (f"b={b} remat={pol} attn={attn} failed: "
                      f"{type(e).__name__}: {str(e)[:160]}")
            _log(f"suite: train variant {reason}")
            failures.append(reason)
            continue
        results.append((fs, b, pol, attn))
        _log(f"suite: train b={b} remat={pol} attn={attn}: "
             f"{fs / 1e12:.3f} TFLOP/s")
    if not results:
        # the reasons must ride the exception: a caller that keeps only
        # the stderr TAIL would otherwise see a bare traceback
        raise RuntimeError("every train variant failed: "
                           + " | ".join(failures))
    best = max(results)
    peak = _peak_flops(dev)
    note = (f"mfu={best[0] / peak:.1%}" if peak
            else "mfu=null (unknown peak)")
    if peak and best[0] > peak:
        # physically impossible — keep the row but say it's broken so
        # no reader quotes it as a result (and the coverage scheduler
        # retries: _captured_steps treats SUSPECT rows as not-landed)
        note = (f"mfu=SUSPECT-TIMING ({best[0] / peak:.1f}x over "
                f"device peak {peak / 1e12:.0f} TFLOP/s)")
    per = " ".join(f"b{b}/{p}/{a}={fs / 1e12:.2f}"
                   for fs, b, p, a in results)
    # model shape in the tag: the d3072/d4096 sweep rows must be
    # distinguishable from the default-d2048 row in the ledger (every
    # field the STROM_TRAIN_CFG alias map can override appears)
    shape = (f"d={cfg.d_model} L={cfg.n_layers} ff={cfg.d_ff} "
             f"h={cfg.n_heads}/{cfg.n_kv_heads} v={cfg.vocab}"
             + (f" xc={cfg.xent_chunks}" if cfg.xent_chunks > 1 else ""))
    return best[0] / 1e12, (f"{note} {shape} b={best[1]} s={seq} "
                            f"remat={best[2]} attn={best[3]} [{per}]")


# ------------------------------- main ----------------------------------

def run(configs: list[int], emit=None) -> list[dict]:
    """Run ``configs``; returns the result rows.  ``emit`` (if given) is
    called with each row THE MOMENT it exists, so a row printed before
    a later config hangs or dies is still on stdout."""
    from nvme_strom_tpu.io import StromEngine
    from nvme_strom_tpu.utils.compile_cache import enable_compile_cache
    from nvme_strom_tpu.utils.config import EngineConfig
    from nvme_strom_tpu.utils.stats import StromStats

    from nvme_strom_tpu.utils.device import require_tpu
    dev_info = require_tpu("bench_suite")       # no TPU: exit non-zero
    on_tpu = dev_info["platform"] == "tpu"
    dev_tag = dev_info["platform"]
    enable_compile_cache()

    # hang budget (STROM_SUITE_BUDGET_S, set by the caller to its own
    # time limit minus a margin): a wedged device op self-reports its
    # phase instead of silently burning the caller's timeout
    budget_s = float(os.environ.get("STROM_SUITE_BUDGET_S", "0") or 0)
    if budget_s > 0:
        _WATCHDOG.arm(budget_s)

    nbytes = _suite_bytes()
    raw_path = os.path.join(_scratch_dir(), "raw.bin")
    bench.make_file(raw_path, nbytes)
    stats = StromStats()
    results = []
    with StromEngine(EngineConfig(), stats=stats) as engine:
        _log(f"suite: backend={engine.backend} bytes/config={nbytes >> 20}"
             f"MiB dev={dev_tag}")
        # Backing-device topology: makes a striped (md-raid0) rig — the
        # reference's 6-10 GB/s configuration — observable in the log.
        from nvme_strom_tpu.io.engine import resolve_device
        dinfo = resolve_device(_scratch_dir())
        _log(f"suite: blockdev={dinfo.device or 'none'} "
             f"nvme={dinfo.is_nvme} "
             f"raid_level={dinfo.raid_level if dinfo.is_raid else None} "
             f"members={list(dinfo.members)}")
        raw = bench.bench_raw(engine, raw_path)
        link = bench.bench_link()
        # same-run ceilings, visible to configs that normalize against
        # the medium (config 14 prices its moment stream against the
        # link it actually rode — round-3 verdict #9)
        _CEILINGS.update(raw=raw, link=link)
        ceiling = 0.9 * (min(raw, link) if raw > 0 and link > 0
                         else max(raw, link, 1.0))
        _log(f"suite: raw={raw:.3f} GiB/s link={link:.3f} GiB/s "
             f"target=0.9·min={ceiling:.3f} GiB/s")
        link_probe = None
        if on_tpu:
            # per-pass link pairing (module header ¶3): one quick burst
            # before every timed pass; plain numpy→device_put, so the
            # engine's bounce/direct accounting never sees probe bytes
            import jax
            _pdev = jax.devices()[0]
            _pbufs = bench._link_bufs(6, engine.config.chunk_bytes)
            jax.device_put(_pbufs[0], _pdev).block_until_ready()
            link_probe = lambda: bench._link_pass(_pbufs, _pdev)  # noqa: E731

        # (label, fn, unit, io_row) — io_row=True rows are GiB/s against
        # the north-star ceiling; compute rows have no BASELINE.json
        # target (the reference is a storage engine) → vs_baseline null.
        names = {
            1: ("raw-sequential-read", lambda: (raw, nbytes),
                "GiB/s", True),
            2: ("arrow-to-device", lambda: bench_arrow(engine, nbytes),
                "GiB/s", True),
            3: ("wds-sharded-loader", lambda: bench_loader(engine, nbytes),
                "GiB/s", True),
            4: ("safetensors-lazy-load",
                lambda: bench_weights(engine, nbytes), "GiB/s", True),
            5: ("parquet-groupby-scan", lambda: bench_sql(engine, nbytes),
                "GiB/s", True),
            6: ("decode-throughput", bench_decode, "tok/s", False),
            7: ("train-step-flops", bench_train, "TFLOP/s", False),
            8: ("multistream-scaling",
                lambda: bench_multistream(engine, nbytes), "GiB/s", True),
            # write bandwidth has no read-derived ceiling: io_row=False
            # keeps vs_baseline null rather than faking a ratio
            9: ("checkpoint-write",
                lambda: bench_checkpoint_write(engine, nbytes),
                "GiB/s", False),
            # storage-bound by design (decode beyond HBM): tok/s is not
            # a GiB/s row, so no north-star ratio applies
            10: ("kv-offload-decode",
                 lambda: bench_kv_offload(engine), "tok/s", False),
            11: ("serving-throughput", bench_serving, "tok/s", False),
            # decompression-bound, not link-bound: the speedup vs the
            # pyarrow fallback (in the tag) is the claim, not a ratio
            # against the raw-read ceiling
            12: ("parquet-zstd-scan",
                 lambda: bench_sql_zstd(engine, nbytes), "GiB/s", False),
            # accounting row: the tag's bounce_vs_idx_raw ratio is the
            # claim (host touches only the raw index stream); decode-
            # bound, so no north-star ceiling ratio (like config 12)
            13: ("parquet-dict-scan",
                 lambda: bench_dict_scan(engine, nbytes), "GiB/s", False),
            # moment-streaming rate (2R+2W of the payload per step);
            # compute+write mixed, so no read-ceiling ratio
            14: ("offloaded-optimizer-step",
                 lambda: bench_opt_offload(engine), "GiB/s", False),
            15: ("parquet-topk-scan",
                 lambda: bench_topk(engine, nbytes), "GiB/s", True),
            # metadata path, not payload: members/s of the shard-index
            # header walk (native C vs tarfile in the tag) — the
            # first-epoch cost of a many-shard WebDataset dataset
            16: ("tar-index-rate",
                 lambda: bench_tar_index(engine, nbytes), "Mmembers/s",
                 False),
            # compute row paired with its own same-run synthetic
            # baseline (the ratio in the tag is the claim) — no
            # read-ceiling ratio applies
            17: ("fed-train-mfu",
                 lambda: bench_fed_train(engine), "TFLOP/s", False),
            # activation round-trip rate; priced vs remat-full (both
            # recompute — the delta IS the NVMe leg), link-normalized
            # like config 14, so no read-ceiling ratio
            18: ("offloaded-activations-step",
                 lambda: bench_act_offload(engine), "GiB/s", False),
            # serving with the NVMe KV prefix store: aggregate tok/s
            # under shared-prefix traffic, paired with its own same-run
            # store-off baseline (the TTFT/ratio in the tag is the
            # claim) — no read-ceiling ratio, like configs 6/11
            19: ("kv-serving-prefix",
                 lambda: bench_kvserve(engine), "tok/s", False),
            # overlapped streaming through the double-buffered host→HBM
            # stage, paired with its own same-run serialized + SQPOLL-off
            # arms (the speedup/reduction in the tag is the claim) — the
            # hop is pad-emulated on a CPU run, so no read-ceiling
            # ratio applies
            20: ("overlap-stream",
                 lambda: bench_overlap(nbytes), "GiB/s", False),
            # read-once/ICI-scatter restore: aggregate GiB/s with each
            # host reading 1/N off flash, paired with its own same-run
            # read-all arm (the N·T→T flash reduction in the tag is the
            # claim) — emulated mesh on a CPU run, so no
            # read-ceiling ratio applies
            21: ("scatter-restore",
                 lambda: bench_scatter(nbytes), "GiB/s", False),
            # multi-tenant isolation storm: victim-p99 ratio tier-off /
            # tier-on under the same aggressor, alternating trials with
            # medians — paired with its own same-run no-aggressor and
            # tier-off arms (the containment in the tag is the claim),
            # so no read-ceiling ratio applies
            22: ("tenant-isolation-storm",
                 lambda: bench_tenant_storm(nbytes), "x", False),
            # partition-parallel pushdown scan: effective table GiB/s
            # with zone-map skips, paired with its own same-run serial
            # arm (the speedups in the tag are the claim; the headline
            # legitimately exceeds the link because skipped bytes never
            # cross it) — so no read-ceiling ratio applies
            23: ("sql-parallel-pushdown",
                 lambda: bench_sql_parallel(engine, nbytes), "GiB/s",
                 False),
            # elastic cold-start: TTFT-from-boot speedup of
            # serve-while-restoring over restore-then-serve, paired
            # with its own same-run off arm and the time-to-p99-steady
            # + token-identity verdict in the tag (the claim is boot
            # elasticity, pad-emulated service time on a page-cached
            # dev box) — so no read-ceiling ratio applies
            24: ("cold-start-restore",
                 lambda: bench_coldstart_suite(nbytes), "x", False),
            # drain & warm handoff: replacement TTFT-from-boot speedup
            # of a bundle-fed boot over an abrupt-kill cold boot, with
            # the zero-drop session ledger in the tag — same pairing
            # rationale as config 24
            25: ("drain-handoff",
                 lambda: bench_handoff_suite(nbytes), "x", False),
        }
        # only configs whose _steady passes move payload ACROSS the
        # link get per-pass pairing: config 8's passes are pure engine
        # reads (raw-bound, and raw does not flap) and config 1 has no
        # pass loop — pairing either with link bursts would ratio the
        # wrong medium and waste window seconds on compute rows
        link_paired = {2, 3, 4, 5, 15}
        try:
            for c in configs:
                label, fn, unit, io_row = names[c]
                _WATCHDOG.phase(f"config{c}:{label}")
                _PASS_LINK["probe"] = link_probe if c in link_paired else None
                _PASS_LINK["last"] = None       # no stale cross-config pairs
                val, extra = fn()
                pairs = _PASS_LINK["last"] if (io_row and on_tpu) else None
                pass_ratios = [r / (0.9 * min(raw, l)) for r, l in pairs or []
                               if r > 0 and l > 0] if raw > 0 else []
                tag = f"dev={dev_tag}"
                if isinstance(extra, str):
                    tag += f", {extra}"
                if pass_ratios:
                    tag += (", per-pass rate@link=" + " ".join(
                        f"{r:.3f}@{l:.2f}" for r, l in pairs))
                results.append({
                    "metric": f"config{c}:{label} ({tag})",
                    # 4 significant figures, not 3 decimals: a tiny-compute
                    # CI run on a loaded box can dip below 0.0005 TFLOP/s
                    # and 3-decimal rounding would floor it to a 0.0 row
                    "value": float(f"{val:.4g}"),
                    "unit": unit,
                    # machine-readable device tags: a functional CPU row
                    # must never be read as a chip row after the fact
                    **dev_info,
                    # Ratios against a CPU-derived ceiling are not the north
                    # star — never emit a number a reader could mistake for
                    # "target met" from a JAX_PLATFORMS=cpu run.  On a TPU,
                    # prefer the median of per-pass ratios against
                    # interleaved link ceilings (module header ¶3) over the
                    # stale step-start pairing.
                    "vs_baseline": (
                        round(statistics.median(pass_ratios), 3)
                        if pass_ratios else
                        round(val / ceiling, 3)
                        if io_row and on_tpu else None),
                })
                if emit is not None:
                    emit(results[-1])
                ratio = results[-1]["vs_baseline"]
                _log(f"suite: config {c} {label}: {val:.3f} {unit} "
                     + (f"({ratio:.2f}x of target)" if ratio is not None
                        else f"(vs_baseline=null: "
                             f"{'no target' if not io_row else 'platform cpu'})"))
        finally:
            # no stale device-bound probe may survive an
            # aborted run for later in-process _steady callers
            _PASS_LINK["probe"] = None

        # every result row is out the door: from here on a hang (engine
        # close, JAX runtime teardown) must cost at most the grace
        # period, and exits 0 — the rows landed.
        # Gated on the budget: a direct run() caller (REPL, test) that
        # never asked for a watchdog must not get os._exit'd under it.
        if budget_s > 0:
            _WATCHDOG.teardown()
        engine.sync_stats()
    _log(f"suite: stats bounce={stats.bounce_bytes} "
         f"direct={stats.bytes_direct} fallback={stats.bytes_fallback}")
    return results


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", type=int, action="append",
                    choices=range(1, 26))
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()
    configs = sorted(set(args.config or [])) if args.config else []
    if args.all or not configs:
        configs = list(range(1, 26))
    run(configs, emit=lambda row: print(json.dumps(row), flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())

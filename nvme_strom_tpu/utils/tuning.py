"""Ledger-informed operating points, shared by the benchmarks and consumers.

A probe ledger (JSON lines: ``{"step", "rc", "device", "results": [...]}``
rows as tools/stream_probe.py, tools/kernel_probe.py and bench_suite.py
print them) can hold measured operating points — stream (depth, drain,
chunk), SQL fold method/window and worker count, flash-attention tile
sizes.  This module is the one place every consumer reads them.

No ledger ships with the repository: until a chip run writes
``BENCH_tpu_ledger.jsonl`` at the checkout root, every lookup here
returns None and consumers run on their own defaults (``EngineConfig``,
128x128 flash tiles).

Credibility filter: a stream cannot beat its own ceiling, so rows with
ratio > 1.05 paired their ceiling with the wrong minute of a drifting
link and carry no information about the operating point.  Among
credible rows the ABSOLUTE stream rate ranks (the highest ratio often
belongs to a collapsed-link minute).
"""

from __future__ import annotations

import functools
import json
import os
import re

_LEDGER = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..",
                 "BENCH_tpu_ledger.jsonl"))

#: per-process ledger-mtime pin (see best_attn_blocks): adoption is
#: stable for a process's lifetime even while another process appends
_MTIME_PIN: dict = {}


def _row_invalid(rec: dict) -> bool:
    """True for a ledger row that must never steer an operating point:
    voided (``valid: false``), failed (rc != 0), empty, taken on another
    platform than a TPU, or carrying a result its own benchmark tagged
    SUSPECT (a rate above the device's peak)."""
    return (rec.get("valid") is False
            or rec.get("rc") != 0
            or not rec.get("results")
            or not str(rec.get("device", "")).startswith("tpu")
            or any("SUSPECT" in str(r.get("metric", ""))
                   for r in rec["results"]))


def _iter_results(step_prefix: str, path: str):
    """Result dicts from VALID ledger rows whose step matches."""
    try:
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if not str(rec.get("step", "")).startswith(step_prefix):
                    continue
                if _row_invalid(rec):
                    continue
                yield from rec.get("results", [])
    except OSError:
        return


def best_probe_config(path: str | None = None,
                      chunk_mib: int | None = None) -> dict | None:
    """Best CREDIBLE ledgered stream operating point, or None.

    ``chunk_mib`` restricts to rows measured at that chunk size — a
    depth measured on a 32 MiB-chunk probe engine says nothing about
    the right depth for a 4 MiB-chunk consumer."""
    best = None
    best_key = None
    for r in _iter_results("stream_probe", path or _LEDGER):
        if r.get("probe") not in ("depth", "chunk"):
            continue
        if chunk_mib is not None and r.get("chunk_mib") != chunk_mib:
            continue
        ratio = r.get("ratio")
        if ratio is None or not 0 < ratio <= 1.05:
            continue
        key = (r.get("stream_gibs", 0.0), ratio)
        if best_key is None or key > best_key:
            best, best_key = r, key
    return best


@functools.lru_cache(maxsize=64)
def _attn_blocks_cached(q_seq: int, kv_seq: int, path: str,
                        mtime: float):
    best_q = best_k = None
    gap_q = gap_k = None
    for r in _iter_results("kernel_probe", path):
        if r.get("probe") != "attn_best" or r.get("timing") != "chained":
            continue
        m = re.search(r"s(\d+)d", str(r.get("shape", "")))
        if not m:
            continue
        s = int(m.group(1))
        gq, gk = abs(s - q_seq), abs(s - kv_seq)
        # per-axis nearest shape: block_q is tuned for the Q length,
        # block_k for the KV length — they can come from different
        # probed shapes when q_seq != kv_seq (ring/cross attention).
        # Later rows win ties: the newest verdict.
        if gap_q is None or gq <= gap_q:
            best_q, gap_q = int(r["block_q"]), gq
        if gap_k is None or gk <= gap_k:
            best_k, gap_k = int(r["block_k"]), gk
    return (best_q, best_k) if best_q is not None else None


_SQL_FOLD = re.compile(r"method=(\w+) window=(\d+)MiB")


def best_sql_fold(path: str | None = None) -> dict | None:
    """Ledgered best config-5 fold operating point, or None.

    The round-5 bisect ledgers suite_5 variants whose tags carry
    ``method=<matmul|scatter> window=<N>MiB`` (bench_sql stamps every
    row); the winner by measured GiB/s among VALID dev=tpu rows with a
    credible ratio (≤1.05 — over-ceiling rows are link-drift evidence)
    becomes the default operating point of later runs, exactly like
    the flash-tiling adoption (best_attn_blocks).  Explicit
    STROM_SQL_METHOD / STROM_SQL_WINDOW_BYTES env always win;
    STROM_BENCH_AUTO_TUNE=0 opts out entirely."""
    if os.environ.get("STROM_BENCH_AUTO_TUNE", "1") == "0":
        return None
    best, best_rate = None, 0.0
    for r in _iter_results("suite_5", path or _LEDGER):
        m = _SQL_FOLD.search(str(r.get("metric", "")))
        if not m:
            continue
        vb = r.get("vs_baseline")
        if vb is None or not 0 < vb <= 1.05:
            # same credibility bar as best_probe_config: a row WITHOUT
            # a ceiling ratio carries no evidence either — it must not
            # become the adopted default just by posting a big number
            continue
        rate = r.get("value") or 0.0
        if rate > best_rate:
            best_rate = rate
            best = {"method": m.group(1),
                    "window_bytes": int(m.group(2)) << 20,
                    "gibs": rate}
    return best


_SQL_WORKERS = re.compile(r"workers=(\d+)")


def best_sql_workers(path: str | None = None) -> int | None:
    """Ledgered best partition-parallel scan worker count, or None.

    bench_suite config 23 stamps every row's metric with ``workers=N``
    (the sql/scan_plan.py fan-out width it measured); the winner by
    measured GiB/s among VALID rows with a credible ceiling ratio
    (≤1.05, same bar as best_sql_fold) becomes the auto operating
    point of STROM_SQL_WORKERS=0 consumers.  An explicit non-zero
    STROM_SQL_WORKERS always wins; STROM_BENCH_AUTO_TUNE=0 opts out."""
    if os.environ.get("STROM_BENCH_AUTO_TUNE", "1") == "0":
        return None
    best, best_rate = None, 0.0
    for r in _iter_results("suite_23", path or _LEDGER):
        m = _SQL_WORKERS.search(str(r.get("metric", "")))
        if not m:
            continue
        vb = r.get("vs_baseline")
        if vb is None or not 0 < vb <= 1.05:
            continue
        rate = r.get("value") or 0.0
        if rate > best_rate:
            best_rate = rate
            best = int(m.group(1))
    return best


def tuned_sql_workers() -> int:
    """Resolved partition-parallel scan width for STROM_SQL_WORKERS=0
    (auto): the best credible ledgered width when config 23 has posted
    one, else a conservative CPU-derived default — enough workers to
    keep several QoS-class streams in flight without oversubscribing
    the submission path on a small box."""
    best = best_sql_workers()
    if best is not None and best >= 1:
        return best
    return max(1, min(4, (os.cpu_count() or 2) // 2))


def best_attn_blocks(q_seq: int, kv_seq: int,
                     path: str | None = None) -> tuple[int, int] | None:
    """Ledgered best flash-attention (block_q, block_k) for the probed
    shapes nearest ``q_seq``/``kv_seq``, or None.

    Only rows carrying ``timing: "chained"`` qualify (kernel_probe's
    data-dependent chain; a per-call ``block_until_ready`` timing is
    not accepted).  (STROM_BENCH_AUTO_TUNE=0 opts out.)  The ledger
    mtime is PINNED at this process's first lookup per path: a
    concurrent append must not flip a running job's tiling mid-stream
    (an unplanned compile plus an accumulation-order numerics shift
    between steps); a fresh process adopts the newest verdict."""
    if os.environ.get("STROM_BENCH_AUTO_TUNE", "1") == "0":
        return None
    p = path or _LEDGER
    mtime = _MTIME_PIN.get(p)
    if mtime is None:
        try:
            mtime = os.path.getmtime(p)
        except OSError:
            return None
        _MTIME_PIN[p] = mtime
    return _attn_blocks_cached(q_seq, kv_seq, p, mtime)


@functools.lru_cache(maxsize=32)
def _tuned_chunk_cached(cap: int, path: str, mtime: float) -> int:
    best = best_probe_config(path)
    if best and best.get("chunk_mib"):
        ck = int(best["chunk_mib"]) << 20
        if 0 < ck <= cap:
            return ck
    return cap


def tuned_chunk_bytes(engine) -> int:
    """Read-split size for the extent planner (io/plan.py): the engine's
    chunk_bytes (the staging-buffer capacity, the hard cap), lowered to
    the best CREDIBLE ledgered probe chunk when one exists and fits —
    the one place the planner's split granularity reads the measured
    verdict instead of each consumer hard-coding its own loop bound.
    STROM_BENCH_AUTO_TUNE=0 opts out (raw engine chunk).

    Cached against the ledger's PINNED mtime (same discipline as
    best_attn_blocks): the planner calls this per submission batch —
    on the wds per-sample path that is once per training sample, and
    re-parsing the whole ledger there would cost more than the
    syscalls the planner saves."""
    cap = engine.config.chunk_bytes
    if os.environ.get("STROM_BENCH_AUTO_TUNE", "1") == "0":
        return cap
    p = _LEDGER
    mtime = _MTIME_PIN.get(p)
    if mtime is None:
        try:
            mtime = os.path.getmtime(p)
        except OSError:
            return cap
        _MTIME_PIN[p] = mtime
    return _tuned_chunk_cached(cap, p, mtime)


def tuned_stream_params(engine, default_drain: str = "ready"
                        ) -> tuple[int, str]:
    """(depth, drain) for a DeviceStream over ``engine``: the engine's
    defaults, overridden by the best credible ledgered probe point
    MEASURED AT THIS ENGINE'S CHUNK SIZE when one exists
    (STROM_BENCH_AUTO_TUNE=0 opts out and restores the raw defaults).
    A tuned depth is capped at half the staging pool so the engine
    keeps reading ahead while transfers drain."""
    depth = engine.config.queue_depth
    drain = default_drain
    if os.environ.get("STROM_BENCH_AUTO_TUNE", "1") != "0":
        best = best_probe_config(
            chunk_mib=engine.config.chunk_bytes >> 20)
        if best:
            depth = min(int(best.get("depth", depth)),
                        max(2, engine.n_buffers // 2))
            drain = best.get("drain", default_drain)
    return max(2, depth), drain

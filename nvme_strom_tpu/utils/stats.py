"""Transfer statistics — the TPU equivalent of NVMe-Strom's STAT_INFO ioctl.

The reference kernel module exposes counters for DMA'd bytes vs
page-cache-fallback bytes and request counts via ``STROM_IOCTL__STAT_INFO``
(SURVEY.md §2 "Stats / debug", §5 "Metrics/logging").  This module is the
userspace analogue.  The single most important counter is ``bounce_bytes``:
bytes that were memcpy'd by host CPU between the NVMe DMA completion and the
host→TPU transfer.  The north star (BASELINE.json) requires it to be zero on
the direct path.

Semantics of the byte counters:

- ``bytes_direct``   — payload bytes read via O_DIRECT/io_uring straight into
  engine-owned locked staging buffers (NVMe DMA target == TPU transfer
  source: no host copy in between).
- ``bytes_fallback`` — payload bytes that took the buffered-read fallback
  (page cache involved), the analogue of the reference's page-cache fallback
  chunks in ``MEMCPY_SSD2GPU`` (SURVEY.md §3.1).
- ``bounce_bytes``   — bytes additionally memcpy'd on the host after landing
  (fallback reads count; any Python-side copy counts; the direct path
  contributes zero).
- ``bytes_to_device`` — bytes handed to the accelerator via the JAX bridge.

Metrics registry (docs/OBSERVABILITY.md): beyond the flat counter block,
this module carries the TYPED metric layer fleet tooling consumes —
:class:`MCounter` / :class:`MGauge` / :class:`Log2Histogram` with label
support (class, ring, tenant-ready) collected by a
:class:`MetricsRegistry`, an OpenMetrics/Prometheus text exporter
(:func:`openmetrics_from_snapshot`, served by ``strom_stat --prom``),
an opt-in textfile writer (``STROM_METRICS_FILE``), and a periodic
:class:`MetricsSnapshotter` so benches and fleet scrapers get TIME
SERIES instead of one-shot dumps.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import math
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from nvme_strom_tpu.utils.lockwitness import make_lock

#: Every public counter on StromStats, derived once from the dataclass —
#: snapshot/reset/merge iterate this so a new counter needs exactly one edit.
COUNTER_FIELDS: tuple = ()  # filled in after the class definition

_export_seq = itertools.count()


@dataclass
class StromStats:
    """Mutable counter block. Thread-safe increments; cheap reads."""

    bytes_direct: int = 0
    bytes_fallback: int = 0
    bounce_bytes: int = 0
    bytes_to_device: int = 0
    bytes_written_direct: int = 0
    requests_submitted: int = 0
    requests_completed: int = 0
    requests_failed: int = 0
    retries: int = 0
    # planned page-cache reads (submit-time residency probe chose the
    # buffered path; subset of bytes_fallback, never a rescue)
    bytes_resident: int = 0
    # -- batched-submission counters (io/plan.py + strom_submit_readv) -----
    # extents the planner merged into a shared span read (a k-extent
    # merge counts k-1): the fewer-larger-NVMe-commands half of the win
    spans_coalesced: int = 0
    # vectored submit calls (strom_submit_readv batches, n >= 1), and
    # the per-extent submission round trips they avoided (extents per
    # batch beyond the first — io_uring_enter doorbells on the uring
    # backend, one Python→C crossing each either way): the
    # fewer-syscalls half of the win
    submit_batches: int = 0
    submit_syscalls_saved: int = 0
    # -- zero-copy overlap pipeline (PR 12: registered files + SQPOLL +
    # unified arena + bridge double buffering; docs/PERF.md §6) ----------
    # submission doorbells actually rung (io_uring_enter submit/wakeup
    # calls on the uring backend, dispatch wakeups on the worker pool):
    # enters/GiB is the steady-state submission-syscall rate SQPOLL
    # drives toward zero — submit_syscalls_saved counts the elisions
    submit_enters: int = 0
    # arena carves that could not fit (io/arena.py): the consumer fell
    # back to its private pre-arena mapping — correct but unpooled, so
    # budget starvation must be visible rather than silent
    arena_fallbacks: int = 0
    # chunks/bytes that rode the bridge's double-buffered host→HBM
    # stage (ops/bridge.py): the overlapped path's traffic share, so a
    # silently-disengaged overlap (platform gate, slab fallback) shows
    # as zeros next to a busy stream
    overlap_chunks: int = 0
    overlap_bytes: int = 0
    # device puts of a weight restore (ops/bridge.PutStage): issued by
    # one of the stage's workers beside the reading thread / issued on
    # the reading thread itself (a staging pool with no room for a
    # queue, a single tensor's load).  load_sharded over a default
    # pool counts no inline put.  Both count arrays put out of staging
    # views (whole rows); a column shard gathered into a host buffer
    # (ops/bridge.HostAssembly) crosses in one put of that buffer,
    # counted once, here, whichever thread made it
    restore_puts_staged: int = 0
    restore_puts_inline: int = 0
    restore_puts_assembled: int = 0
    # -- resilience counters (io/faults.py, io/resilient.py) --------------
    # faults injected by an active FaultPlan (test/chaos runs; 0 in prod)
    faults_injected: int = 0
    # ResilientEngine recovery actions: failed/short reads resubmitted
    # after backoff; hedges issued past the latency threshold; hedges
    # that completed before the original; stuck requests cancelled and
    # resubmitted after wait_timeout
    resilient_retries: int = 0
    hedges_issued: int = 0
    hedges_won: int = 0
    stuck_cancelled: int = 0
    # graceful-degradation actions in consumers: shards skipped under the
    # loader's error budget; checkpoint restores that fell back to an
    # older intact step
    shards_quarantined: int = 0
    restore_fallbacks: int = 0
    # -- QoS scheduler (io/sched.py over the multi-ring engine) -----------
    # planned batches queued at the scheduler, batches dispatched to a
    # ring, and aging promotions (batches that hit the starvation bound
    # and jumped the weight/priority order); per-class breakdowns live
    # in class_stats (add_class_stat)
    sched_enqueued: int = 0
    sched_dispatches: int = 0
    sched_promotions: int = 0
    # hedged reads refused because the request's latency class had
    # exhausted its concurrent-hedge budget (per-class isolation: a
    # scrub storm starves its OWN hedges, never the decode class's)
    hedges_denied: int = 0
    # -- write-path resilience + end-to-end integrity (io/resilient.py
    # submit_write, utils/checksum.py) ------------------------------------
    # failed/short writes resubmitted by ResilientEngine's write mirror
    write_retries: int = 0
    # payload bytes checksummed on the read path (STROM_VERIFY) — the
    # integrity tax
    bytes_verified: int = 0
    # stamped-checksum mismatches detected (each is a silent corruption
    # that would otherwise have flowed into training state)
    checksum_failures: int = 0
    # -- tiered pinned-host DRAM cache (io/hostcache.py, docs/PERF.md §4) --
    # planner-boundary probe outcomes: spans (or parts of spans) served
    # from resident cache lines vs sent to the engine; per-class
    # breakdowns live in class_stats
    cache_hits: int = 0
    cache_misses: int = 0
    # payload bytes served straight from the pinned arena — the repeat
    # traffic that no longer pays SSD latency
    bytes_served_cache: int = 0
    # fills accepted by the ghost-list admission gate / misses the gate
    # refused to admit (one-shot streaming scans land here, by design)
    cache_admissions: int = 0
    cache_admission_rejections: int = 0
    # admitted fills that could not land anyway: arena full with nothing
    # reclaimable (all lines pinned/referenced) or voided by a racing
    # write — budget starvation, NOT healthy scan filtering, so it must
    # not hide inside cache_admission_rejections
    cache_fill_failures: int = 0
    # resident lines reclaimed under budget/quota pressure, and lines
    # dropped because an engine write overlapped them (staleness guard)
    cache_evictions: int = 0
    cache_invalidations: int = 0
    # -- serving KV prefix store (models/kv_offload.py PrefixStore,
    # docs/PERF.md §5) -----------------------------------------------------
    # content-addressed prompt pages served from NVMe instead of being
    # re-prefilled (hits) vs pages the store had to let the server
    # compute (misses) — the cross-request dedupe win, page units
    kv_prefix_hits: int = 0
    kv_prefix_misses: int = 0
    # pages written to the store / restored from it through the decode-
    # class batched read path
    kv_pages_written: int = 0
    kv_pages_restored: int = 0
    # put() calls that found the page already resident under its chain
    # key (identical system prompts across sessions write ONCE), and the
    # NVMe write bytes that dedupe avoided
    kv_pages_deduped: int = 0
    kv_bytes_saved: int = 0
    # SSD-resident prefixes reclaimed by the benefit-scored eviction
    # (reuse frequency x restore cost, docs/PERF.md §5)
    kv_store_evictions: int = 0
    # SLO-governor actions: decode hedge-budget/weight raises after a
    # restore-p99 target (STROM_KV_P99_MS) violation, and pages dropped
    # after a failed restore (I/O or CRC) or a failed eviction write —
    # either way healed through recompute on the next admission
    kv_slo_boosts: int = 0
    kv_restore_failures: int = 0
    # -- failure-domain supervision (io/health.py, docs/RESILIENCE.md
    # "failure domains") ---------------------------------------------------
    # circuit-breaker trips (per-ring error budget / stall detector,
    # plus the device-level breaker whose open state is degraded mode)
    breaker_trips: int = 0
    # hot ring restarts performed, and the in-flight extents a restart
    # cancelled for requeue (their waiters resubmitted onto healthy
    # rings — one longer wait, never a consumer error)
    ring_restarts: int = 0
    extents_requeued: int = 0
    # degraded buffered mode: spans served as plain preads while every
    # fast domain was sick, their payload bytes, and the half-open
    # probes that rode the real path to test recovery
    degraded_reads: int = 0
    degraded_bytes: int = 0
    degraded_probes: int = 0
    # serving-side load shedding: prefill admissions deferred while the
    # engine reported degraded (requests wait queued; nothing fails)
    serve_admissions_shed: int = 0
    # -- observability layer (utils/trace.py, io/flightrec.py,
    # docs/OBSERVABILITY.md) ------------------------------------------------
    # spans the tracer dropped at its in-memory cap (previously visible
    # only in the exported file's metadata — a long run silently losing
    # its tail must show in strom_stat)
    trace_spans_dropped: int = 0
    # flight-recorder post-mortem dumps written (breaker trip, ring
    # restart, SLO violation, watchdog stall)
    flight_dumps: int = 0
    # -- goodput/waste ledger (obs/ledger.py, docs/OBSERVABILITY.md) ------
    # every completed byte is either goodput (delivered and useful) or
    # one of these waste classes; goodput is DERIVED (delivered minus
    # waste) so the classes can never double-count it
    # bytes read by the losing side of a hedge race (the duplicate that
    # completed pointlessly — hedging's bandwidth price)
    waste_hedge_loss_bytes: int = 0
    # bytes re-read by retry recovery that an earlier attempt had
    # already delivered (short-read resubmits re-read the whole range;
    # stuck-cancelled requests usually complete into the void)
    waste_retry_reread_bytes: int = 0
    # dead gap bytes the planner deliberately read through when merging
    # near-adjacent extents (STROM_COALESCE_GAP) — cheaper than extra
    # NVMe round trips, but bandwidth nonetheless
    waste_coalesce_gap_bytes: int = 0
    # host-tier line bytes filled from NVMe and evicted before a single
    # hit — admission that never paid off (the ghost gate exists to
    # keep this near zero; growth means the gate or quotas are wrong)
    waste_evicted_unused_bytes: int = 0
    # bytes served through the degraded buffered brown-out (delivered,
    # but via page cache + bounce at reduced bandwidth — the capacity
    # lost to an unhealthy device)
    waste_degraded_bytes: int = 0
    # -- critical-path attribution (obs/attrib.py) ------------------------
    # retired requests folded into attribution profiles, and spans the
    # collector dropped at its per-trace bound (an incomplete fold must
    # be visible, exactly like trace_spans_dropped)
    attrib_requests: int = 0
    attrib_spans_dropped: int = 0
    # -- read-once/ICI-scatter restore (ops/ici.py, docs/PERF.md §7) ------
    # restore payload this process pulled off local NVMe as its share of
    # a scatter-mode restore (its 1/N; read-all would bill the total)
    ici_bytes_read: int = 0
    # restore payload obtained from peers over the interconnect instead
    # of local flash — the bytes the mesh moved so this host didn't.
    # Stays 0 in single-process emulation: no peers, every byte is a
    # local NVMe read, and phantom savings would skew the ledger
    ici_bytes_received: int = 0
    # scatter attempts that fell back to plain local full reads (breaker
    # open, exchange failure, single-host mesh) — a brown-out, never an
    # error the consumer sees
    ici_fallbacks: int = 0
    # -- multi-tenant isolation (io/tenants.py, docs/RESILIENCE.md) -------
    # serving requests refused admission by the tenant layer (tier shed
    # under backlog pressure or token-bucket exhaustion); the per-tenant
    # breakdown rides "tenant_stats"
    tenant_admissions_shed: int = 0
    # residency reclaimed FROM an over-quota tenant under pressure (host
    # cache lines + KV prefix pages) — borrowing paying itself back
    tenant_quota_evictions: int = 0
    # admissions a tenant landed past its residency quota while free
    # space existed (the borrowing the evictions above reclaim)
    tenant_borrows: int = 0
    # per-tenant SLO-governor share boosts (the tenant-scoped analogue
    # of kv_slo_boosts: weight only, never the device hedge budget)
    tenant_slo_boosts: int = 0
    # flight-recorder dumps triggered by a tenant's shed/borrow storm
    tenant_storm_dumps: int = 0
    # -- Direct SQL pushdown scans (sql/scan_plan.py, docs/PERF.md §8) ----
    # pushdown-planned scans (one per plan_scan call — each WHERE-ranged
    # sql_groupby/sql_scalar_agg/union scan with pushdown on)
    sql_scans: int = 0
    # row groups that survived zone-map planning and were read
    sql_rowgroups_scanned: int = 0
    # row groups provably excluded by min/max statistics before any
    # NVMe command was issued
    sql_rowgroups_skipped: int = 0
    # selected-column compressed bytes that never left the SSD: skipped
    # row groups' chunks plus late-materialization's skipped pages
    sql_bytes_skipped: int = 0
    # payload pages never fetched because no row in their range
    # survived the predicate mask (late materialization)
    sql_pages_skipped: int = 0
    # scans that fanned windows across the partition-parallel pool
    sql_parallel_scans: int = 0
    # -- elastic cold-start (io/coldstart.py, parallel/weights.py
    # FaultingCheckpoint, docs/RESILIENCE.md "Elastic cold-start") ----
    # tensors demand-faulted at decode class ahead of the bulk stream
    # (a request touched them before the background restore arrived)
    coldstart_faults: int = 0
    # NVMe bytes moved by those demand faults
    coldstart_fault_bytes: int = 0
    # tensors the background bulk-restore thread loaded at restore class
    coldstart_bulk_tensors: int = 0
    # hostcache warmup-hint spans prefetched from a .warmhints.json
    # manifest during the warming phase
    coldstart_warm_spans: int = 0
    # KV prefix pages re-read at prefetch class during warming
    coldstart_warm_pages: int = 0
    # coldstart_stall flight-recorder dumps actually published (fault
    # p99 over SLO while still in the faulting phase)
    coldstart_stall_dumps: int = 0
    # degraded-mode (brown-out) entries observed while a cold start was
    # still in flight — the restore stream survived a ring failure
    coldstart_brownouts: int = 0
    # -- drain & warm handoff (io/handoff.py, docs/RESILIENCE.md
    # "Drain & handoff") ----------------------------------------------
    # drains entered (serving -> draining transitions)
    handoff_drains: int = 0
    # prefill admission opportunities deferred while draining (the
    # requests stay queued and ride the bundle — never dropped)
    handoff_deferred: int = 0
    # sessions exported into a bundle (queued + still decoding past
    # the drain deadline): prompt token chain + KV page keys
    handoff_sessions_exported: int = 0
    # exported sessions a replacement re-admitted from a bundle at boot
    handoff_sessions_restored: int = 0
    # .handoff.json bundles atomically published
    handoff_bundles: int = 0
    # serialized size of those bundles
    handoff_bundle_bytes: int = 0
    # bundles a replacement REJECTED at boot (torn/stale/missing) —
    # each one is a brown-out to a plain cold start, never an error
    handoff_brownouts: int = 0
    # handoff_stall flight-recorder dumps actually published (drain
    # outlived its deadline with sessions still in flight)
    handoff_stall_dumps: int = 0
    _lock: threading.Lock = field(
        default_factory=lambda: make_lock("stats.StromStats._lock"),
        repr=False)
    _t0: float = field(default_factory=time.monotonic, repr=False)
    _gauges: dict = field(default_factory=dict, repr=False)
    # per-raid-member payload attribution (striped-scaling evidence,
    # SURVEY.md §6): {member name: bytes}; filled only when stripe
    # accounting is on (EngineConfig.stripe_accounting)
    _member_bytes: dict = field(default_factory=dict, repr=False)
    # per-latency-class tallies (QoS scheduler + per-class resilience
    # budgets): {class: {counter: value}}; exported as "class_stats"
    _class_stats: dict = field(default_factory=dict, repr=False)
    # per-tenant tallies (multi-tenant isolation): {tenant id:
    # {counter: value}}; exported as "tenant_stats" — the {tenant=}
    # label breakdown behind the flat tenant_* counters above
    _tenant_stats: dict = field(default_factory=dict, repr=False)

    def add(self, **deltas: int) -> None:
        with self._lock:
            for name, d in deltas.items():
                setattr(self, name, getattr(self, name) + d)

    def add_class_stat(self, klass: str, **deltas) -> None:
        """Accumulate per-latency-class counters (scheduler dispatches,
        per-class hedges/retries) under one lock with the flat block."""
        with self._lock:
            blk = self._class_stats.setdefault(klass, {})
            for name, d in deltas.items():
                blk[name] = blk.get(name, 0) + d

    def class_stat_gauges(self, klass: str, **values: float) -> None:
        """Per-class point-in-time values: each keeps a running max and
        a running sum/count (so the export carries avg + worst-case
        queue wait per class without a reservoir)."""
        with self._lock:
            blk = self._class_stats.setdefault(klass, {})
            for name, v in values.items():
                blk[f"{name}_max"] = max(blk.get(f"{name}_max", 0.0), v)
                blk[f"{name}_sum"] = blk.get(f"{name}_sum", 0.0) + v
                blk[f"{name}_n"] = blk.get(f"{name}_n", 0) + 1

    @property
    def class_stats(self) -> dict:
        with self._lock:
            return {k: dict(v) for k, v in self._class_stats.items()}

    def add_tenant_stat(self, tenant: str, **deltas) -> None:
        """Accumulate per-tenant counters (dispatches, sheds, borrows)
        under one lock with the flat block — the class_stats mechanism
        keyed by tenant id instead of latency class."""
        with self._lock:
            blk = self._tenant_stats.setdefault(tenant, {})
            for name, d in deltas.items():
                blk[name] = blk.get(name, 0) + d

    @property
    def tenant_stats(self) -> dict:
        with self._lock:
            return {k: dict(v) for k, v in self._tenant_stats.items()}

    def add_member_bytes(self, members, deltas) -> None:
        """Accumulate per-raid-member payload bytes (parallel lists)."""
        with self._lock:
            for m, d in zip(members, deltas):
                if d:
                    self._member_bytes[m] = (
                        self._member_bytes.get(m, 0) + int(d))

    @property
    def member_bytes(self) -> dict:
        with self._lock:
            return dict(self._member_bytes)

    def set_gauges(self, **values) -> None:
        """Point-in-time values (latency percentiles etc.) carried in the
        export alongside the counters; unlike counters they overwrite."""
        with self._lock:
            self._gauges.update(values)

    def merge_engine(self, engine_stats: dict) -> None:
        """Fold counters read from the C++ engine into this block."""
        self.add(**{k: v for k, v in engine_stats.items()
                    if k in COUNTER_FIELDS})

    @property
    def total_payload_bytes(self) -> int:
        return self.bytes_direct + self.bytes_fallback

    def throughput_gib_s(self) -> float:
        dt = time.monotonic() - self._t0
        return (self.total_payload_bytes / (1 << 30)) / dt if dt > 0 else 0.0

    def snapshot(self) -> dict:
        with self._lock:
            snap = {name: getattr(self, name) for name in COUNTER_FIELDS}
            snap.update(self._gauges)
            if self._member_bytes:
                snap["member_bytes"] = dict(self._member_bytes)
            if self._class_stats:
                snap["class_stats"] = {k: dict(v)
                                       for k, v in self._class_stats.items()}
            if self._tenant_stats:
                snap["tenant_stats"] = {
                    k: dict(v) for k, v in self._tenant_stats.items()}
            return snap

    def dump_json(self) -> str:
        return json.dumps(self.snapshot(), sort_keys=True)

    def reset(self) -> None:
        with self._lock:
            for name in COUNTER_FIELDS:
                setattr(self, name, 0)
            self._gauges.clear()
            self._member_bytes.clear()
            self._class_stats.clear()
            self._tenant_stats.clear()
            self._t0 = time.monotonic()

    def maybe_export(self) -> None:
        """Write the counter block to ``$STROM_STATS_EXPORT`` (if set).

        This is how out-of-process observers (the strom_stat CLI, the
        reference's stat-reader analogue — SURVEY.md §2) see an engine's
        counters: the reference reads kernel-global state via an ioctl; an
        in-process engine instead snapshots to a well-known file.  The write
        is atomic (rename) so readers never see a torn block.
        """
        path = os.environ.get("STROM_STATS_EXPORT")
        mpath = os.environ.get("STROM_METRICS_FILE")
        if not path and not mpath:
            return
        snap = self.snapshot()
        snap["_exported_at"] = time.time()
        snap["_pid"] = os.getpid()
        if path:
            try:
                _atomic_write_text(path, json.dumps(snap, sort_keys=True))
            except OSError:
                pass
        # the OpenMetrics textfile rides the same sync points —
        # INDEPENDENTLY of the JSON export, so setting only
        # STROM_METRICS_FILE still gets every post-sync snapshot
        if mpath:
            try:
                write_openmetrics_file(mpath, snap)
            except OSError:
                pass


COUNTER_FIELDS = tuple(
    f.name for f in dataclasses.fields(StromStats)
    if not f.name.startswith("_"))

global_stats = StromStats()


#: geometric mean of a [2^i, 2^(i+1)) bucket relative to its lower edge:
#: sqrt(2^i * 2^(i+1)) = 2^i * sqrt(2) — the unbiased point estimate for
#: log-uniform samples (the old 1.5 arithmetic midpoint systematically
#: over-reported by ~6%)
_LOG2_BUCKET_MEAN = math.sqrt(2.0)


def percentiles_from_log2_hist(hist: list, ps=(50, 90, 99)) -> dict:
    """Approximate percentiles from a log2-bucketed histogram.

    ``hist[i]`` counts samples in [2^i, 2^(i+1)); each percentile reports
    the bucket's GEOMETRIC MEAN (2^i·√2 — consistently, for every p):
    at most a √2 multiplicative error against the exact sample, which
    tests/test_stats.py pins against ground truth.  Returns {p: value}
    with value 0 when the histogram is empty.
    """
    total = sum(hist)
    out = {}
    for p in ps:
        if total == 0:
            out[p] = 0
            continue
        rank = total * p / 100.0
        acc = 0
        val = 0
        for i, c in enumerate(hist):
            acc += c
            if acc >= rank and c > 0:
                val = int((2 ** i) * _LOG2_BUCKET_MEAN)
                break
        out[p] = val
    return out


def human_bytes(n: float) -> str:
    """1536 → '1.50 KiB'; handles negative deltas (counter resets)."""
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024:
            return f"{n:.2f} {unit}"
        n /= 1024
    return f"{n:.2f} TiB"


# ---------------------------------------------------------------------------
# Typed metrics registry (docs/OBSERVABILITY.md)
# ---------------------------------------------------------------------------

def _label_key(labelnames: Tuple[str, ...], labels: dict) -> tuple:
    if set(labels) != set(labelnames):
        raise ValueError(
            f"labels {sorted(labels)} != declared {sorted(labelnames)}")
    return tuple(str(labels[n]) for n in labelnames)


class _Metric:
    """Shared shape of the typed metrics: a name, a help string, fixed
    label names, and one value per label combination."""

    kind = "untyped"

    def __init__(self, name: str, help: str = "",
                 labelnames: Tuple[str, ...] = ()):
        if not name or not name.replace("_", "a").isalnum():
            raise ValueError(f"metric name {name!r} must be [a-z0-9_]+")
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._lock = make_lock("stats._Metric._lock")
        self._values: Dict[tuple, float] = {}

    def samples(self) -> List[Tuple[tuple, float]]:
        with self._lock:
            return sorted(self._values.items())

    def value(self, **labels) -> float:
        with self._lock:
            return self._values.get(
                _label_key(self.labelnames, labels), 0)


class MCounter(_Metric):
    """Monotone counter with labels: ``inc(n, ring="0", klass="decode")``.
    (``M``-prefixed to keep the name clear of typing.Counter.)"""

    kind = "counter"

    def inc(self, n: float = 1, **labels) -> None:
        if n < 0:
            raise ValueError("counters only go up")
        key = _label_key(self.labelnames, labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0) + n


class MGauge(_Metric):
    """Point-in-time value with labels: ``set(v, ring="0")``."""

    kind = "gauge"

    def set(self, v: float, **labels) -> None:
        key = _label_key(self.labelnames, labels)
        with self._lock:
            self._values[key] = v


class Log2Histogram:
    """Log2-bucketed histogram: ``observe(v)`` lands v in bucket
    ``floor(log2(v))`` — the same convention as the engine's native
    latency histogram and :func:`percentiles_from_log2_hist`, so one
    percentile walk serves both.  Thread-safe; O(1) observe."""

    kind = "histogram"

    def __init__(self, name: str, help: str = "", buckets: int = 40):
        self.name = name
        self.help = help
        self._lock = make_lock("stats.Log2Histogram._lock")
        self._counts = [0] * buckets
        self._sum = 0.0

    def observe(self, v: float) -> None:
        i = max(0, int(v).bit_length() - 1) if v >= 1 else 0
        with self._lock:
            self._counts[min(i, len(self._counts) - 1)] += 1
            self._sum += v

    @property
    def total(self) -> int:
        with self._lock:
            return sum(self._counts)

    def counts(self) -> List[int]:
        with self._lock:
            return list(self._counts)

    def percentile(self, p: int) -> int:
        return percentiles_from_log2_hist(self.counts(), ps=(p,))[p]

    def samples(self):
        """OpenMetrics histogram series: cumulative ``_bucket{le=2^i}``
        rows plus ``_count``/``_sum``."""
        with self._lock:
            counts = list(self._counts)
            hsum = self._sum
        acc = 0
        out = []
        for i, c in enumerate(counts):
            acc += c
            if c:
                out.append(((("le", str(float(2 ** (i + 1)))),), acc))
        return out, acc, hsum


class MetricsRegistry:
    """A named collection of typed metrics; renders OpenMetrics text.

    Fleet tooling registers here (the flight recorder does; per-tenant
    serving metrics will), while the legacy flat :class:`StromStats`
    block is bridged in at render time by
    :func:`openmetrics_from_snapshot` — one exporter, two sources."""

    def __init__(self):
        self._lock = make_lock("stats.MetricsRegistry._lock")
        self._metrics: Dict[str, object] = {}

    def counter(self, name: str, help: str = "",
                labelnames: Tuple[str, ...] = ()) -> MCounter:
        return self._register(MCounter, name, help, labelnames)

    def gauge(self, name: str, help: str = "",
              labelnames: Tuple[str, ...] = ()) -> MGauge:
        return self._register(MGauge, name, help, labelnames)

    def histogram(self, name: str, help: str = "",
                  buckets: int = 40) -> Log2Histogram:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = Log2Histogram(name, help, buckets)
                self._metrics[name] = m
            elif not isinstance(m, Log2Histogram):
                raise ValueError(f"{name} already registered as {m.kind}")
            return m

    def _register(self, cls, name, help, labelnames):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, help, labelnames)
                self._metrics[name] = m
            elif not isinstance(m, cls):
                raise ValueError(f"{name} already registered as {m.kind}")
            return m

    def metrics(self) -> list:
        with self._lock:
            return [self._metrics[k] for k in sorted(self._metrics)]

    def render_openmetrics(self, eof: bool = True) -> str:
        lines: List[str] = []
        for m in self.metrics():
            _render_family(lines, m)
        if eof:
            lines.append("# EOF")
        return "\n".join(lines) + "\n"


def _fmt_labels(pairs) -> str:
    if not pairs:
        return ""
    body = ",".join(f'{k}="{_escape(v)}"' for k, v in pairs)
    return "{" + body + "}"


def _escape(v) -> str:
    return str(v).replace("\\", r"\\").replace('"', r'\"') \
        .replace("\n", r"\n")


def _fmt_val(v) -> str:
    f = float(v)
    return str(int(f)) if f == int(f) else repr(f)


def _render_family(lines: List[str], m) -> None:
    name = m.name
    lines.append(f"# TYPE {name} {m.kind}")
    if m.help:
        lines.append(f"# HELP {name} {_escape(m.help)}")
    if isinstance(m, Log2Histogram):
        buckets, count, total = m.samples()
        for pairs, v in buckets:
            lines.append(f"{name}_bucket{_fmt_labels(pairs)} "
                         f"{_fmt_val(v)}")
        lines.append(f'{name}_bucket{{le="+Inf"}} {_fmt_val(count)}')
        lines.append(f"{name}_count {_fmt_val(count)}")
        lines.append(f"{name}_sum {_fmt_val(total)}")
        return
    suffix = "_total" if m.kind == "counter" else ""
    samples = m.samples()
    for key, v in samples:
        pairs = tuple(zip(m.labelnames, key))
        lines.append(f"{name}{suffix}{_fmt_labels(pairs)} {_fmt_val(v)}")
    if not samples:
        lines.append(f"{name}{suffix} 0")


#: per-class counters in ``class_stats`` exported as counters; the
#: running max/sum/n triplets class_stat_gauges maintains export as
#: gauges (they reset with the block, not monotone across it)
_CLASS_GAUGE_SUFFIXES = ("_max", "_sum", "_n")


def openmetrics_from_snapshot(snap: dict) -> str:
    """Render a :meth:`StromStats.snapshot` dict as OpenMetrics text —
    the bridge that gives the flat counter block typed, labeled output:
    counters → ``strom_<name>_total``, gauges → ``strom_<name>``,
    ``class_stats`` → ``{class=...}`` labels, ``ring_depths``/
    ``ring_health`` → ``{ring=...}``, ``member_bytes`` → ``{member=...}``
    (served by ``strom_stat --prom`` and the ``STROM_METRICS_FILE``
    textfile writer)."""
    reg = MetricsRegistry()
    for name in COUNTER_FIELDS:
        c = reg.counter(f"strom_{name}", f"strom-io counter {name}")
        c.inc(int(snap.get(name, 0)))
    cls = snap.get("class_stats") or {}
    names = sorted({n for blk in cls.values() for n in blk})
    for n in names:
        is_gauge = n.endswith(_CLASS_GAUGE_SUFFIXES)
        m = (reg.gauge(f"strom_class_{n}",
                       f"per-class gauge {n}", ("klass",)) if is_gauge
             else reg.counter(f"strom_class_{n}",
                              f"per-class counter {n}", ("klass",)))
        for k, blk in sorted(cls.items()):
            if n in blk:
                (m.set if is_gauge else m.inc)(blk[n], klass=k)
    # per-tenant breakdowns label with {tenant=}; the family name takes
    # a by_tenant prefix so it can never collide with the flat
    # tenant_* totals rendered from COUNTER_FIELDS above
    ten = snap.get("tenant_stats") or {}
    tnames = sorted({n for blk in ten.values() for n in blk})
    for n in tnames:
        m = reg.counter(f"strom_by_tenant_{n}",
                        f"per-tenant counter {n}", ("tenant",))
        for t, blk in sorted(ten.items()):
            if n in blk:
                m.inc(blk[n], tenant=t)
    depths = snap.get("ring_depths")
    if depths:
        g = reg.gauge("strom_ring_depth",
                      "in-flight I/O per ring", ("ring",))
        for i, d in enumerate(depths):
            g.set(int(d), ring=i)
    # zero-copy submission state (docs/PERF.md §6): per-ring 0/1 gauges
    # — fleet dashboards alert on a ring whose registrations silently
    # soft-failed (slow-but-working is the failure mode to catch)
    for key, mname, mhelp in (
            ("ring_fixed_bufs", "strom_ring_fixed_bufs",
             "1 while the staging pool is registered as fixed buffers"),
            ("ring_reg_files", "strom_ring_reg_files",
             "1 while the fd slot table is registered (FIXED_FILE)"),
            ("ring_sqpoll", "strom_ring_sqpoll",
             "1 while submissions ride SQPOLL (no doorbell syscalls)")):
        vals = snap.get(key)
        if vals:
            g = reg.gauge(mname, mhelp, ("ring",))
            for i, v in enumerate(vals):
                g.set(int(v), ring=i)
    health = snap.get("ring_health")
    if health:
        g = reg.gauge("strom_ring_breaker_open",
                      "1 while the ring's circuit breaker is not closed",
                      ("ring", "state"))
        for i, s in enumerate(health):
            g.set(0 if s == "closed" else 1, ring=i, state=s)
    # per-ring time-in-state accounting (obs/ledger.py RingTimeLedger):
    # cumulative seconds each ring spent busy/idle/stalled/restarting
    ring_state = snap.get("ring_state_s")
    if ring_state:
        g = reg.gauge("strom_ring_state_seconds",
                      "cumulative seconds per ring per state",
                      ("ring", "state"))
        for state, per_ring in sorted(ring_state.items()):
            for i, v in enumerate(per_ring):
                g.set(round(float(v), 3), ring=i, state=state)
    members = snap.get("member_bytes")
    if members:
        g = reg.counter("strom_member_bytes",
                        "payload bytes per raid member", ("member",))
        for m_, v in sorted(members.items()):
            g.inc(int(v), member=m_)
    skip = (set(COUNTER_FIELDS)
            | {"class_stats", "tenant_stats", "ring_depths",
               "ring_health", "member_bytes", "ring_fixed_bufs",
               "ring_reg_files", "ring_sqpoll", "ring_state_s"})
    for name in sorted(snap):
        if name in skip or name.startswith("_"):
            continue
        v = snap[name]
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            reg.gauge(f"strom_{name}",
                      f"strom-io gauge {name}").set(v)
    return reg.render_openmetrics()


def _atomic_write_text(path: str, text: str) -> None:
    """The ONE atomic-publish primitive for exporter files: write to a
    unique temp (pid+thread+sequence — two engines exporting
    concurrently must not share one, or the rename publishes torn
    content), then rename; the temp is unlinked on failure.  Raises
    OSError for callers that need to know; exporters swallow it."""
    tmp = (f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
           f".{next(_export_seq)}")
    try:
        with open(tmp, "w") as f:
            f.write(text)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def write_openmetrics_file(path: str, snap: dict) -> None:
    """Atomically write ``snap`` as OpenMetrics text (the
    ``STROM_METRICS_FILE`` textfile-collector contract)."""
    _atomic_write_text(path, openmetrics_from_snapshot(snap))


class MetricsSnapshotter:
    """Periodic snapshotter: every ``interval_s`` it snapshots a
    StromStats block into an in-memory series (bounded) and, when
    ``path`` is set, rewrites the OpenMetrics textfile — the time-series
    half of the registry (a fleet scraper tails the file).  Daemon
    thread; ``close()`` (or the context manager) takes a final snapshot
    so short runs never export empty."""

    def __init__(self, stats: StromStats, interval_s: float = 10.0,
                 path: Optional[str] = None, keep: int = 512,
                 sync=None):
        if interval_s <= 0:
            raise ValueError("interval_s must be > 0")
        self.stats = stats
        self.interval_s = interval_s
        self.path = path
        self.keep = keep
        #: optional callable run before each snapshot (an engine's
        #: ``sync_stats`` — drains the C counters into the block).
        #: Guarded by ``_sync_lock`` so :meth:`set_sync` (engine
        #: teardown detaches here) can never race a drain against the
        #: C handle being destroyed.
        self._sync = sync
        self._sync_lock = make_lock("stats.MetricsSnapshotter._sync_lock")
        self.series: List[dict] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="strom-metrics")
        self._thread.start()

    def set_sync(self, sync) -> None:
        """Attach/detach the pre-snapshot drain hook.  Blocks until any
        in-flight drain finishes, so detaching before engine teardown
        guarantees no snapshot is mid-``sync_stats`` when the C handle
        dies."""
        with self._sync_lock:
            self._sync = sync

    def detach_sync(self, sync) -> None:
        """Compare-and-clear: detach ONLY when the current hook is
        ``sync`` — a closing engine must not rip out a hook a LATER
        live engine (sharing the same stats block) installed over its
        own.  Same blocking guarantee as :meth:`set_sync`."""
        with self._sync_lock:
            if self._sync == sync:
                self._sync = None

    def snap_once(self) -> None:
        """Take one snapshot now (the periodic thread calls this; bench
        code calls it at pass boundaries for aligned series points)."""
        with self._sync_lock:
            if self._sync is not None:
                try:
                    self._sync()
                except Exception:
                    pass    # a dying engine must not kill the exporter
        snap = self.stats.snapshot()
        snap["_t"] = time.time()
        self.series.append(snap)
        if len(self.series) > self.keep:
            del self.series[:len(self.series) - self.keep]
        if self.path:
            try:
                write_openmetrics_file(self.path, snap)
            except OSError:
                pass

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            self.snap_once()

    def close(self) -> None:
        if not self._stop.is_set():
            self._stop.set()
            self._thread.join(timeout=5)
            self.snap_once()    # final point: short runs export too

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


_writer_lock = make_lock("stats._writer_lock")
_writer: Optional[MetricsSnapshotter] = None


def maybe_start_metrics_writer(stats: StromStats,
                               sync=None) -> Optional[MetricsSnapshotter]:
    """Start the process-wide ``STROM_METRICS_FILE`` textfile writer
    (interval ``STROM_METRICS_INTERVAL_S``, default 10 s) the first time
    an engine comes up — the continuous-scrape counterpart of the
    snapshot written at every ``maybe_export``.  No env → no thread."""
    global _writer
    path = os.environ.get("STROM_METRICS_FILE")
    if not path:
        return None
    with _writer_lock:
        if _writer is None:
            try:
                interval = float(os.environ.get(
                    "STROM_METRICS_INTERVAL_S", 10.0))
            except ValueError:
                interval = 10.0
            _writer = MetricsSnapshotter(stats, max(0.05, interval),
                                         path=path, sync=sync)
        return _writer

"""strom_stat — print strom-io transfer counters.

Analogue of the reference's stat CLI reading ``STROM_IOCTL__STAT_INFO``
(SURVEY.md §2 "Stat CLI", §5 "Metrics/logging").  The reference reads
kernel-module-global counters; our engines are in-process, so engines
export their counter block to ``$STROM_STATS_EXPORT`` (atomic JSON file,
written on engine shutdown / sync) and this tool reads that file.

    STROM_STATS_EXPORT=/tmp/strom.json python train.py &
    python -m nvme_strom_tpu.tools.strom_stat /tmp/strom.json --watch 1

The headline line is the north-star check (BASELINE.json): direct bytes
with ``bounce_bytes == 0``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from nvme_strom_tpu.utils.stats import human_bytes as _human

_COUNTERS = (
    "bytes_direct", "bytes_fallback", "bytes_resident", "bounce_bytes",
    "bytes_to_device", "bytes_written_direct", "requests_submitted",
    "requests_completed", "requests_failed", "retries",
)

#: recovery-path counters (io/resilient.py, io/faults.py, loader
#: quarantine, checkpoint restore-fallback — docs/RESILIENCE.md);
#: rendered in their own block, and only when any is non-zero: a
#: healthy run's report stays exactly as short as before
_RESILIENCE_COUNTERS = (
    "faults_injected", "resilient_retries", "hedges_issued",
    "hedges_won", "stuck_cancelled", "shards_quarantined",
    "restore_fallbacks", "write_retries",
)

#: end-to-end integrity counters (STROM_VERIFY + the write-path
#: CRC32C stamps — utils/checksum.py, docs/RESILIENCE.md); own block,
#: shown only when verification ran or a corruption was caught
_INTEGRITY_COUNTERS = (
    "bytes_verified", "checksum_failures",
)

#: batched-submission counters (io/plan.py planner + the engine's
#: strom_submit_readv — docs/PERF.md); own block, shown only when the
#: vectored path ran
_BATCH_COUNTERS = (
    "spans_coalesced", "submit_batches", "submit_syscalls_saved",
)

#: zero-copy submission/overlap counters (registered files + SQPOLL +
#: unified arena + bridge double buffering — docs/PERF.md §6); the
#: engine block also renders the per-ring registration gauges, because
#: a pool whose try_register silently soft-failed is SLOW, not broken —
#: it must be visible here, not only in a flamegraph
_ENGINE_COUNTERS = (
    "submit_enters", "arena_fallbacks", "overlap_chunks",
    "overlap_bytes", "restore_puts_staged", "restore_puts_inline",
    "restore_puts_assembled",
)

#: QoS scheduler counters (io/sched.py over the multi-ring engine —
#: docs/PERF.md); own block with per-ring depth and per-class tallies,
#: shown only when a scheduler dispatched anything
_SCHED_COUNTERS = (
    "sched_enqueued", "sched_dispatches", "sched_promotions",
    "hedges_denied",
)

#: pinned-host DRAM tier counters (io/hostcache.py — docs/PERF.md §4);
#: own block, shown only when the tier saw traffic
_HOSTCACHE_COUNTERS = (
    "cache_hits", "cache_misses", "bytes_served_cache",
    "cache_admissions", "cache_admission_rejections",
    "cache_fill_failures", "cache_evictions", "cache_invalidations",
)

#: serving KV prefix-store counters (models/kv_offload.py PrefixStore —
#: docs/PERF.md §5); own block, shown only when a store saw traffic
_KV_COUNTERS = (
    "kv_prefix_hits", "kv_prefix_misses", "kv_pages_deduped",
    "kv_bytes_saved", "kv_pages_written", "kv_pages_restored",
    "kv_store_evictions", "kv_slo_boosts", "kv_restore_failures",
)

#: failure-domain supervision counters (io/health.py —
#: docs/RESILIENCE.md "failure domains"); own block, shown only when a
#: breaker ever acted or the ring_health gauge reports a non-closed
#: state — a healthy run's report stays exactly as short as before
_HEALTH_COUNTERS = (
    "breaker_trips", "ring_restarts", "extents_requeued",
    "degraded_reads", "degraded_bytes", "degraded_probes",
    "serve_admissions_shed",
)

#: observability-layer counters (utils/trace.py tracer drops +
#: io/flightrec.py post-mortem dumps — docs/OBSERVABILITY.md); own
#: block, shown only when either fired: dropped spans mean the trace
#: is incomplete, a flight dump means a trigger captured a post-mortem
_OBS_COUNTERS = (
    "trace_spans_dropped", "flight_dumps", "attrib_requests",
    "attrib_spans_dropped",
)

#: goodput/waste ledger counters (obs/ledger.py —
#: docs/OBSERVABILITY.md §5); own block with the derived goodput line,
#: shown only when any waste class fired: a fully-useful run's report
#: stays exactly as short as before
_LEDGER_COUNTERS = (
    "waste_hedge_loss_bytes", "waste_retry_reread_bytes",
    "waste_coalesce_gap_bytes", "waste_evicted_unused_bytes",
    "waste_degraded_bytes",
)

#: read-once/ICI-scatter restore counters (ops/ici.py —
#: docs/PERF.md §7); own block, shown only when a scatter restore ran
#: (or fell back): the read/received split is the win made visible —
#: each host bills its 1/N to flash and the rest to the interconnect.
#: Single-process emulation reports received=0 (no peers; every byte
#: is a local read), so the flash-share line honestly shows 1.000
_ICI_COUNTERS = (
    "ici_bytes_read", "ici_bytes_received", "ici_fallbacks",
)

#: multi-tenant isolation counters (io/tenants.py carried through
#: serving admission, hostcache/KV quotas, and the per-tenant SLO lane
#: — docs/RESILIENCE.md "Multi-tenant isolation"); own block with the
#: per-tenant breakdown, shown only when tenancy ever acted
_TENANT_COUNTERS = (
    "tenant_admissions_shed", "tenant_quota_evictions",
    "tenant_borrows", "tenant_slo_boosts", "tenant_storm_dumps",
)

#: Direct SQL pushdown-scan counters (sql/scan_plan.py —
#: docs/PERF.md §8); own block, shown only when a pushdown-planned
#: scan ran: the zone-map eliminations and never-fetched pages are the
#: scan's win made visible (bytes_skipped = bytes that never left the
#: SSD, projection-aware)
_SQL_COUNTERS = (
    "sql_scans", "sql_parallel_scans", "sql_rowgroups_scanned",
    "sql_rowgroups_skipped", "sql_pages_skipped", "sql_bytes_skipped",
)

#: elastic cold-start counters (io/coldstart.py, parallel/weights.py
#: FaultingCheckpoint — docs/RESILIENCE.md "Elastic cold-start"); own
#: block with the boot-phase gauge, shown only when a cold start ever
#: ran: the fault/bulk split is serve-while-restoring made visible —
#: demand faults are the tensors requests could not wait for
_COLDSTART_COUNTERS = (
    "coldstart_faults", "coldstart_fault_bytes",
    "coldstart_bulk_tensors", "coldstart_warm_spans",
    "coldstart_warm_pages", "coldstart_stall_dumps",
    "coldstart_brownouts",
)

#: drain & warm handoff counters (io/handoff.py — docs/RESILIENCE.md
#: "Drain & handoff"); own block with the drain-phase gauge, shown only
#: when a drain or bundle consumption ever ran: deferred admissions are
#: the closed gate made visible, exported/restored sessions are the
#: rolling restart's zero-drop ledger, and brown-outs count bundles a
#: replacement REJECTED (each one a plain cold start, never an error)
_HANDOFF_COUNTERS = (
    "handoff_drains", "handoff_deferred",
    "handoff_sessions_exported", "handoff_sessions_restored",
    "handoff_bundles", "handoff_bundle_bytes",
    "handoff_brownouts", "handoff_stall_dumps",
)

#: every counter block above, in render order — the counter-drift CI
#: check (tests/test_observability.py) asserts the union covers ALL of
#: StromStats.COUNTER_FIELDS, so a new counter cannot silently vanish
#: from the tooling
ALL_COUNTER_BLOCKS = (
    _COUNTERS, _RESILIENCE_COUNTERS, _INTEGRITY_COUNTERS,
    _BATCH_COUNTERS, _ENGINE_COUNTERS, _SCHED_COUNTERS,
    _HOSTCACHE_COUNTERS, _KV_COUNTERS, _HEALTH_COUNTERS, _OBS_COUNTERS,
    _LEDGER_COUNTERS, _ICI_COUNTERS, _TENANT_COUNTERS, _SQL_COUNTERS,
    _COLDSTART_COUNTERS, _HANDOFF_COUNTERS,
)


def render_device(path: str) -> str:
    """Backing-device topology of ``path`` — the observable form of the
    reference's md-raid0 member walk (SURVEY.md §2/§3.1): a striped rig
    shows its members here, so a multi-SSD setup is verifiable from the
    CLI before any benchmark runs."""
    from nvme_strom_tpu.io.engine import resolve_device
    d = resolve_device(path)
    lines = [f"device topology for {path}:"]
    if not d.device:
        lines.append("  no visible backing blockdev "
                     "(overlay/tmpfs/network fs)")
        return "\n".join(lines)
    kind = ("nvme" if d.is_nvme else
            "rotational" if d.rotational == 1 else "non-nvme")
    lines.append(f"  blockdev    {d.device} ({kind})")
    if d.is_raid:
        lvl = f"raid{d.raid_level}" if d.raid_level >= 0 else "md (unknown)"
        lines.append(f"  md level    {lvl}, {len(d.members)} members")
        for m in d.members:
            tag = "nvme" if m.startswith("nvme") else "non-nvme"
            lines.append(f"    member    {m} ({tag})")
    lines.append(f"  direct-DMA eligible (nvme or all-nvme raid0): "
                 f"{'yes' if d.nvme_backed else 'no'}")
    return "\n".join(lines)


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def render(snap: dict, prev: dict | None = None, dt: float | None = None
           ) -> str:
    lines = []
    exported = snap.get("_exported_at")
    if exported:
        age = time.time() - exported
        lines.append(f"exported {age:.1f}s ago by pid {snap.get('_pid', '?')}")
    for name in _COUNTERS:
        v = int(snap.get(name, 0))
        suffix = ""
        if prev is not None and dt and name.startswith(("bytes", "bounce")):
            rate = (v - int(prev.get(name, 0))) / dt
            suffix = f"   ({_human(rate)}/s)"
        shown = _human(v) if name.startswith(("bytes", "bounce")) else str(v)
        lines.append(f"  {name:<22} {shown:>14}{suffix}")
    for name in sorted(k for k in snap if k.startswith("lat_")):
        lines.append(f"  {name:<22} {snap[name]:>14.1f}")
    if any(int(snap.get(n, 0)) for n in _BATCH_COUNTERS):
        lines.append("  batched submission (planner + submit_readv):")
        for name in _BATCH_COUNTERS:
            lines.append(f"    {name:<20} {int(snap.get(name, 0)):>14}")
        subs = int(snap.get("requests_submitted", 0))
        if subs:
            merged = int(snap.get("spans_coalesced", 0))
            lines.append(
                f"    coalesce ratio       "
                f"{merged / (merged + subs):>14.3f}   "
                "(extents merged / extents planned)")
    if (any(int(snap.get(n, 0)) for n in _ENGINE_COUNTERS)
            or snap.get("ring_fixed_bufs") is not None):
        lines.append("  engine (zero-copy submission: registered bufs/"
                     "files, SQPOLL, arena, overlap):")
        for name in _ENGINE_COUNTERS:
            v = int(snap.get(name, 0))
            shown = _human(v) if name.endswith("bytes") else str(v)
            lines.append(f"    {name:<22} {shown:>14}")
        enters = int(snap.get("submit_enters", 0))
        saved = int(snap.get("submit_syscalls_saved", 0))
        if enters + saved:
            lines.append(
                f"    {'doorbells elided':<22} "
                f"{saved / (enters + saved):>14.3f}   "
                "(saved / (saved + rung))")
        for key, label in (("ring_fixed_bufs", "fixed buffers"),
                           ("ring_reg_files", "registered files"),
                           ("ring_sqpoll", "sqpoll active")):
            vals = snap.get(key)
            if vals is not None:
                shown = " ".join(str(int(v)) for v in vals)
                lines.append(f"    {label:<22} {shown:>14}   (per ring)")
        if snap.get("pool_arena") is not None:
            lines.append(f"    {'pool from arena':<22} "
                         f"{int(snap.get('pool_arena', 0)):>14}")
        if (snap.get("ring_fixed_bufs")
                and not all(snap["ring_fixed_bufs"])
                # reg_files is uring-only state: its presence proves the
                # rings ARE urings, so a missing buffer registration is
                # real per-op pinning (the worker pool registers
                # nothing and must not trip this)
                and any(int(d) for d in snap.get("ring_reg_files") or [])
                and any(int(d) for d in snap.get("ring_sqpoll") or [])):
            lines.append(
                "    UNREGISTERED POOL under SQPOLL — per-op page "
                "pinning is eating the doorbell win; check "
                "RLIMIT_MEMLOCK / kernel support")
    if (any(int(snap.get(n, 0)) for n in _SCHED_COUNTERS)
            or snap.get("class_stats") or snap.get("ring_depths")):
        lines.append("  scheduler (QoS classes over the ring shards):")
        for name in _SCHED_COUNTERS:
            lines.append(f"    {name:<20} {int(snap.get(name, 0)):>14}")
        depths = snap.get("ring_depths")
        if depths:
            shown = " ".join(str(int(d)) for d in depths)
            lines.append(f"    ring depth           {shown:>14}   "
                         "(in-flight I/O per ring)")
        cls = snap.get("class_stats") or {}
        for k in sorted(cls, key=lambda c: -cls[c].get("dispatches", 0)):
            blk = cls[k]
            n_w = int(blk.get("queue_wait_s_n", 0))
            avg_ms = (1000.0 * blk.get("queue_wait_s_sum", 0.0) / n_w
                      if n_w else 0.0)
            max_ms = 1000.0 * blk.get("queue_wait_s_max", 0.0)
            lines.append(
                f"    class {k:<12} "
                f"dispatches={int(blk.get('dispatches', 0))} "
                f"spans={int(blk.get('spans', 0))} "
                f"promoted={int(blk.get('promotions', 0))} "
                f"wait avg/max={avg_ms:.2f}/{max_ms:.2f} ms "
                f"hedges={int(blk.get('hedges_issued', 0))}"
                f"/{int(blk.get('hedges_won', 0))} "
                f"denied={int(blk.get('hedges_denied', 0))} "
                f"retries={int(blk.get('retries', 0))}")
    if (any(int(snap.get(n, 0)) for n in _HOSTCACHE_COUNTERS)
            or snap.get("cache_bytes_resident")):
        lines.append("  host cache (pinned DRAM tier, NVMe<->HBM):")
        for name in _HOSTCACHE_COUNTERS:
            v = int(snap.get(name, 0))
            shown = _human(v) if name.startswith("bytes") else str(v)
            lines.append(f"    {name:<26} {shown:>14}")
        resident = snap.get("cache_bytes_resident")
        if resident is not None:
            lines.append(f"    {'bytes_resident (lines)':<26} "
                         f"{_human(int(resident)):>14}   "
                         f"({int(snap.get('cache_lines_resident', 0))} "
                         f"lines)")
        hits = int(snap.get("cache_hits", 0))
        misses = int(snap.get("cache_misses", 0))
        if hits + misses:
            lines.append(f"    {'hit rate':<26} "
                         f"{hits / (hits + misses):>14.3f}")
        cls = snap.get("class_stats") or {}
        for k in sorted(cls):
            ch = int(cls[k].get("cache_hits", 0))
            cm = int(cls[k].get("cache_misses", 0))
            if ch + cm:
                lines.append(
                    f"    class {k:<12} hits={ch} misses={cm} "
                    f"rate={ch / (ch + cm):.3f} "
                    f"served={_human(int(cls[k].get('bytes_served_cache', 0)))}")
    if (any(int(snap.get(n, 0)) for n in _KV_COUNTERS)
            or snap.get("kv_store_pages_resident")):
        lines.append("  kv serving (content-addressed prefix store):")
        for name in _KV_COUNTERS:
            v = int(snap.get(name, 0))
            shown = _human(v) if "bytes" in name else str(v)
            lines.append(f"    {name:<22} {shown:>14}")
        hits = int(snap.get("kv_prefix_hits", 0))
        misses = int(snap.get("kv_prefix_misses", 0))
        if hits + misses:
            lines.append(f"    {'prefix hit rate':<22} "
                         f"{hits / (hits + misses):>14.3f}")
        resident = snap.get("kv_store_pages_resident")
        if resident is not None:
            lines.append(f"    {'pages resident':<22} "
                         f"{int(resident):>14}")
        p99 = snap.get("kv_restore_p99_ms")
        if p99:
            lines.append(f"    {'restore p99':<22} "
                         f"{float(p99):>11.2f} ms")
    ring_health = snap.get("ring_health") or []
    if (any(int(snap.get(n, 0)) for n in _HEALTH_COUNTERS)
            or any(s != "closed" for s in ring_health)
            or int(snap.get("engine_degraded", 0))):
        lines.append("  health (failure domains: breakers / restarts "
                     "/ degraded mode):")
        for name in _HEALTH_COUNTERS:
            v = int(snap.get(name, 0))
            shown = _human(v) if name.startswith("degraded_bytes") \
                else str(v)
            lines.append(f"    {name:<22} {shown:>14}")
        if ring_health:
            lines.append(f"    {'ring breakers':<22} "
                         f"{' '.join(ring_health):>14}")
        degraded = int(snap.get("engine_degraded", 0))
        lines.append(f"    {'device state':<22} "
                     f"{'DEGRADED (buffered brown-out)' if degraded else 'ok':>14}")
        if degraded:
            lines.append(
                "    BROWNED OUT — all fast domains unhealthy; serving "
                "rides plain preads until a half-open probe recovers")
    if any(int(snap.get(n, 0)) for n in _ICI_COUNTERS):
        lines.append("  ici scatter (read-once restore over the "
                     "interconnect):")
        for name in _ICI_COUNTERS:
            v = int(snap.get(name, 0))
            shown = _human(v) if "bytes" in name else str(v)
            lines.append(f"    {name:<22} {shown:>14}")
        read = int(snap.get("ici_bytes_read", 0))
        recv = int(snap.get("ici_bytes_received", 0))
        if read + recv:
            lines.append(
                f"    {'flash share':<22} "
                f"{read / (read + recv):>14.3f}   "
                "(local NVMe / restore payload)")
    if any(int(snap.get(n, 0)) for n in _RESILIENCE_COUNTERS):
        lines.append("  resilience (recoveries + degradations):")
        for name in _RESILIENCE_COUNTERS:
            v = int(snap.get(name, 0))
            suffix = ""
            if prev is not None and dt:
                d = v - int(prev.get(name, 0))
                if d:
                    suffix = f"   (+{d})" if d > 0 else f"   ({d})"
            lines.append(f"    {name:<20} {v:>14}{suffix}")
    if any(int(snap.get(n, 0)) for n in _INTEGRITY_COUNTERS):
        lines.append("  integrity (STROM_VERIFY checksums):")
        for name in _INTEGRITY_COUNTERS:
            v = int(snap.get(name, 0))
            shown = _human(v) if name.startswith("bytes") else str(v)
            lines.append(f"    {name:<20} {shown:>14}")
        if int(snap.get("checksum_failures", 0)):
            lines.append(
                "    CORRUPTION CAUGHT — scrub the namespace "
                "(strom-scrub) before trusting older data")
    # shown only when a waste class fired — a fully-useful run's report
    # stays exactly as short as before (ring time-in-state is always on
    # /ledger and --prom; here it rides along inside the waste block)
    if any(int(snap.get(n, 0)) for n in _LEDGER_COUNTERS):
        lines.append("  ledger (goodput vs waste, per-ring "
                     "time-in-state — docs/OBSERVABILITY.md):")
        from nvme_strom_tpu.obs.ledger import ledger_view
        view = ledger_view(snap)
        lines.append(f"    {'delivered':<26} "
                     f"{_human(view['delivered_bytes']):>14}")
        lines.append(f"    {'goodput':<26} "
                     f"{_human(view['goodput_bytes']):>14}   "
                     f"(fraction {view['goodput_fraction']:.4f})")
        for name in _LEDGER_COUNTERS:
            v = int(snap.get(name, 0))
            if v:
                lines.append(f"    {name:<26} {_human(v):>14}")
        rs = view.get("ring_state_s")
        if rs:
            for state in ("busy", "idle", "stalled", "restarting"):
                vals = rs.get(state)
                if vals and any(v > 0 for v in vals):
                    shown = " ".join(f"{v:.1f}" for v in vals)
                    lines.append(f"    ring {state + '_s':<21} "
                                 f"{shown:>14}")
    if (any(int(snap.get(n, 0)) for n in _TENANT_COUNTERS)
            or snap.get("tenant_stats")):
        lines.append("  multi-tenant (tier shedding / quotas / SLO "
                     "boosts — docs/RESILIENCE.md):")
        for name in _TENANT_COUNTERS:
            lines.append(f"    {name:<24} {int(snap.get(name, 0)):>14}")
        ten = snap.get("tenant_stats") or {}
        for t in sorted(ten, key=lambda t: -ten[t].get(
                "admissions_shed", 0)):
            blk = ten[t]
            lines.append(
                f"    tenant {t:<12} "
                f"finished={int(blk.get('requests_finished', 0))} "
                f"shed={int(blk.get('admissions_shed', 0))} "
                f"dispatches={int(blk.get('dispatches', 0))} "
                f"borrows={int(blk.get('borrows', 0))} "
                f"evicted={int(blk.get('quota_evictions', 0))} "
                f"boosts={int(blk.get('slo_boosts', 0))} "
                f"hedges={int(blk.get('hedges_issued', 0))}")
    if any(int(snap.get(n, 0)) for n in _SQL_COUNTERS):
        lines.append("  sql scan (pushdown-planned direct scans — "
                     "docs/PERF.md §8):")
        for name in _SQL_COUNTERS:
            v = int(snap.get(name, 0))
            shown = _human(v) if name == "sql_bytes_skipped" else v
            lines.append(f"    {name:<24} {shown:>14}")
        scanned = int(snap.get("sql_rowgroups_scanned", 0))
        skipped = int(snap.get("sql_rowgroups_skipped", 0))
        if scanned + skipped:
            lines.append(
                f"    {'zone-map elimination':<24} "
                f"{100.0 * skipped / (scanned + skipped):>13.1f}%")
    if (any(int(snap.get(n, 0)) for n in _COLDSTART_COUNTERS)
            or snap.get("boot_phase")):
        lines.append("  cold start (serve-while-restoring — "
                     "docs/RESILIENCE.md):")
        phase = snap.get("boot_phase")
        if phase:
            lines.append(f"    {'boot_phase':<24} {str(phase):>14}")
        for name in _COLDSTART_COUNTERS:
            v = int(snap.get(name, 0))
            shown = _human(v) if name == "coldstart_fault_bytes" else v
            lines.append(f"    {name:<24} {shown:>14}")
    if (any(int(snap.get(n, 0)) for n in _HANDOFF_COUNTERS)
            or snap.get("drain_phase")):
        lines.append("  handoff (drain & warm handoff — "
                     "docs/RESILIENCE.md):")
        phase = snap.get("drain_phase")
        if phase:
            lines.append(f"    {'drain_phase':<24} {str(phase):>14}")
        for name in _HANDOFF_COUNTERS:
            v = int(snap.get(name, 0))
            shown = _human(v) if name == "handoff_bundle_bytes" else v
            lines.append(f"    {name:<24} {shown:>14}")
    if any(int(snap.get(n, 0)) for n in _OBS_COUNTERS):
        lines.append("  observability (tracer / flight recorder):")
        for name in _OBS_COUNTERS:
            lines.append(f"    {name:<22} {int(snap.get(name, 0)):>14}")
        if int(snap.get("trace_spans_dropped", 0)):
            lines.append(
                "    TRACE INCOMPLETE — the span buffer capped out; "
                "raise STROM_TRACE_MAX_EVENTS or trace a shorter window")
    members = snap.get("member_bytes")
    if members:
        total = max(1, sum(members.values()))
        lines.append("  per-member payload (stripe attribution):")
        for m in sorted(members):
            v = int(members[m])
            lines.append(f"    {m:<20} {_human(v):>14}"
                         f"   ({100.0 * v / total:.1f}%)")
    direct = int(snap.get("bytes_direct", 0))
    bounce = int(snap.get("bounce_bytes", 0))
    if direct and bounce == 0:
        lines.append("north star: OK — direct path with zero host bounces")
    elif bounce:
        pct = 100.0 * bounce / max(1, direct + int(snap.get(
            "bytes_fallback", 0)))
        lines.append(f"north star: {_human(bounce)} bounced "
                     f"({pct:.1f}% of payload)")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="strom_stat", description="strom-io counter reader")
    ap.add_argument("path", nargs="?",
                    default=os.environ.get("STROM_STATS_EXPORT"),
                    help="stats export file (default: $STROM_STATS_EXPORT)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="dump raw JSON instead of the table")
    ap.add_argument("--prom", action="store_true", dest="as_prom",
                    help="emit OpenMetrics/Prometheus text exposition "
                         "instead of the table (counters as "
                         "strom_*_total, class/ring/member labels; "
                         "docs/OBSERVABILITY.md)")
    ap.add_argument("--watch", type=float, default=None, metavar="SECS",
                    help="re-read and print rates every SECS seconds")
    ap.add_argument("--device", metavar="PATH", default=None,
                    help="print backing-device topology (md-raid members) "
                         "for PATH and exit")
    args = ap.parse_args(argv)

    if args.device is not None:
        try:
            print(render_device(args.device))
        except OSError as e:
            print(f"strom_stat: cannot resolve {args.device}: {e}",
                  file=sys.stderr)
            return 2
        return 0

    if not args.path:
        print("strom_stat: no stats file — pass a path or set "
              "STROM_STATS_EXPORT in the producing process", file=sys.stderr)
        return 2
    try:
        snap = load(args.path)
    except (OSError, json.JSONDecodeError) as e:
        print(f"strom_stat: cannot read {args.path}: {e}", file=sys.stderr)
        return 2

    def emit(s, prev=None, dt=None):
        if args.as_prom:
            from nvme_strom_tpu.utils.stats import \
                openmetrics_from_snapshot
            print(openmetrics_from_snapshot(s), end="")
        elif args.as_json:
            print(json.dumps(s, sort_keys=True))
        else:
            print(render(s, prev, dt))

    if args.watch is None:
        emit(snap)
        return 0

    prev, t_prev = snap, time.monotonic()
    emit(snap)
    try:
        while True:
            time.sleep(args.watch)
            try:
                snap = load(args.path)
            except (OSError, json.JSONDecodeError):
                continue
            now = time.monotonic()
            if not args.as_prom:
                # '---' would corrupt an OpenMetrics stream; exposition
                # records are already delimited by their '# EOF'
                print("---")
            emit(snap, prev, now - t_prev)
            prev, t_prev = snap, now
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())

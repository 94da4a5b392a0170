"""Observability ANALYSIS layer (docs/OBSERVABILITY.md §§4-6):
critical-path attribution (obs/attrib.py), the goodput/waste ledger
(obs/ledger.py), the live debug endpoint (obs/debugsrv.py) + strom-top,
Perfetto counter tracks, and the bench regression gate.  Hardware-free
(real engines on tmp files only)."""

import json
import threading
import time

import numpy as np
import pytest

from nvme_strom_tpu.io.engine import StromEngine
from nvme_strom_tpu.obs import attrib as attrib_mod
from nvme_strom_tpu.obs.attrib import (AttributionCollector, component_of,
                                       fold_events)
from nvme_strom_tpu.obs.debugsrv import (DebugServer,
                                         maybe_start_debug_server)
from nvme_strom_tpu.obs.ledger import (RingTimeLedger, charge_waste,
                                       ledger_view)
from nvme_strom_tpu.utils.config import EngineConfig
from nvme_strom_tpu.utils.stats import StromStats
from nvme_strom_tpu.utils.trace import TraceContext, Tracer, use_context


def _cfg(**kw):
    base = dict(chunk_bytes=1 << 20, queue_depth=8,
                buffer_pool_bytes=16 << 20)
    base.update(kw)
    return EngineConfig(**base)


# -- fold_events: the conservation contract ----------------------------------

def test_fold_conservation_sequential():
    """Deterministic sequential spans: component sum + unattributed
    equals wall time within 1% (the acceptance invariant)."""
    us = 1000   # ns per µs
    spans = [
        ("strom.sched.queue", 0, 100 * us),
        ("strom.read", 100 * us, 600 * us),
        ("strom.bridge.hop", 600 * us, 700 * us),
    ]
    fold = fold_events(spans, 0, 1000 * us)
    comps = fold["components"]
    assert comps["sched_queue"] == pytest.approx(100.0)
    assert comps["nvme_read"] == pytest.approx(500.0)
    assert comps["bridge"] == pytest.approx(100.0)
    total = sum(comps.values()) + fold["unattributed_us"]
    assert total == pytest.approx(fold["wall_us"], rel=0.01)
    assert fold["overlap_us"] == 0.0


def test_fold_interval_union_no_double_count():
    """Two parallel reads of one request charge their covered wall time
    ONCE — attribution can never report more nvme time than elapsed."""
    spans = [("strom.read", 0, 800_000),
             ("strom.read", 200_000, 1_000_000)]
    fold = fold_events(spans, 0, 1_000_000)
    assert fold["components"]["nvme_read"] == pytest.approx(1000.0)
    assert fold["unattributed_us"] == pytest.approx(0.0)


def test_fold_clips_to_window_and_skips_structural():
    spans = [
        ("strom.serve.request", 0, 1_000_000),     # structural: excluded
        ("strom.serve.admit", 0, 900_000),         # structural: excluded
        ("strom.read", -500_000, 500_000),         # clipped to window
        ("strom.read.degraded", 900_000, 2_000_000),
    ]
    fold = fold_events(spans, 0, 1_000_000)
    assert fold["components"]["nvme_read"] == pytest.approx(500.0)
    assert fold["components"]["degraded"] == pytest.approx(100.0)
    assert fold["unattributed_us"] == pytest.approx(400.0)


def test_component_mapping():
    assert component_of("strom.sched.queue") == "sched_queue"
    assert component_of("strom.cache.hit") == "hostcache"
    assert component_of("strom.cache.fill") == "hostcache"
    assert component_of("strom.read") == "nvme_read"
    assert component_of("strom.read.fallback") == "nvme_read"
    assert component_of("strom.resilient.retry") == "retry_backoff"
    assert component_of("strom.resilient.hedge") == "hedge"
    assert component_of("strom.resilient.future_kind") == "retry_backoff"
    assert component_of("strom.read.degraded") == "degraded"
    assert component_of("strom.bridge.hop") == "bridge"
    assert component_of("strom.h2d.dispatch") == "bridge"
    assert component_of("strom.serve.request") is None
    assert component_of("something.else") is None


# -- the collector ------------------------------------------------------------

def test_collector_cross_thread_folding(tmp_path):
    """Spans emitted from OTHER threads under explicitly-attached child
    contexts fold into the root request's breakdown (the cross-thread
    folding the acceptance asks for)."""
    tracer = Tracer(str(tmp_path / "t.json"))
    col = AttributionCollector()
    tracer.add_sink(col.sink)
    root = TraceContext.new()
    t0 = time.monotonic_ns()

    def emit(name, ctx, b, e):
        tracer.add_span(name, b, e, ctx=ctx)

    threads = [
        threading.Thread(target=emit, args=(
            "strom.read", root.child(), t0 + 100_000, t0 + 400_000)),
        threading.Thread(target=emit, args=(
            "strom.sched.queue", root.child(), t0, t0 + 100_000)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    fold = col.request_retired(root.trace_id, t0, t0 + 1_000_000,
                               klass="decode")
    assert fold["spans"] == 2
    assert fold["components"]["nvme_read"] == pytest.approx(300.0)
    assert fold["components"]["sched_queue"] == pytest.approx(100.0)
    total = sum(fold["components"].values()) + fold["unattributed_us"]
    assert total == pytest.approx(fold["wall_us"], rel=0.01)
    prof = col.profiles()
    assert prof["requests"] == 1
    assert "decode" in prof["classes"]
    comps = prof["classes"]["decode"]["components"]
    assert comps["nvme_read"]["p50_us"] > 0
    assert comps["nvme_read"]["p99_us"] >= comps["nvme_read"]["p50_us"]


def test_collector_bounds_and_drop_accounting(tmp_path):
    stats = StromStats()
    col = AttributionCollector(max_traces=2, max_spans=3, stats=stats)
    tracer = Tracer(str(tmp_path / "t.json"))
    tracer.add_sink(col.sink)
    root = TraceContext.new()
    for i in range(5):
        tracer.add_span("strom.read", i * 10, i * 10 + 5,
                        ctx=root.child())
    assert col.dropped == 2
    assert stats.attrib_spans_dropped == 2
    # trace LRU: a third trace evicts the oldest
    for _ in range(3):
        tracer.add_span("strom.read", 0, 5,
                        ctx=TraceContext.new().child())
    assert len(col._traces) <= 2


def test_collector_sink_only_tracer_keeps_no_events():
    """STROM_ATTRIB without STROM_TRACE must not accumulate events in
    memory: spans flow to the sink and are gone."""
    tracer = Tracer()                 # no path
    col = AttributionCollector()
    tracer.add_sink(col.sink)
    assert tracer.enabled
    ctx = TraceContext.new()
    tracer.add_span("strom.read", 0, 1000, ctx=ctx.child())
    assert len(tracer) == 0           # sink-only: nothing retained
    assert len(col._traces) == 1
    tracer.remove_sink(col.sink)
    assert not tracer.enabled


def test_engine_attaches_collector_under_strom_attrib(
        tmp_data_file, monkeypatch):
    """STROM_ATTRIB=1: the engine wires the process collector into its
    tracer as a sink, engine read spans fold at retire, and the flight
    recorder carries the attribution summary in its dumps."""
    path, payload = tmp_data_file
    monkeypatch.setenv("STROM_ATTRIB", "1")
    attrib_mod.reset()
    tracer = Tracer()                 # private, no export path
    try:
        stats = StromStats()
        with StromEngine(_cfg(), stats=stats, tracer=tracer) as eng:
            col = attrib_mod.get_collector()
            assert col is not None and eng._attrib is col
            assert tracer.enabled     # sink-only activation
            if eng.flight is not None:
                assert eng.flight.attrib is col
            root = TraceContext.new()
            t0 = time.monotonic_ns()
            fh = eng.open(path)
            with use_context(root):
                for off in (0, 1 << 20):
                    with eng.submit_read(fh, off, 1 << 20) as p:
                        p.wait()
            fold = col.request_retired(root.trace_id, t0,
                                       time.monotonic_ns(),
                                       klass="decode")
            eng.close(fh)
        assert fold["spans"] >= 2
        assert fold["components"]["nvme_read"] > 0
        total = sum(fold["components"].values()) \
            + fold["unattributed_us"]
        assert total == pytest.approx(fold["wall_us"], rel=0.01)
        assert stats.attrib_requests == 1
    finally:
        tracer._sinks.clear()
        attrib_mod.reset()


# -- ledger -------------------------------------------------------------------

def test_charge_waste_and_ledger_view():
    stats = StromStats()
    charge_waste(stats, "hedge_loss", 1000)
    charge_waste(stats, "retry_reread", 500)
    charge_waste(stats, "coalesce_gap", 250)
    charge_waste(stats, "evicted_unused", 125)
    charge_waste(stats, "degraded", 100)
    charge_waste(stats, "degraded", 0)        # no-op
    charge_waste(None, "degraded", 10)        # no stats: no-op
    stats.add(bytes_direct=10_000)
    view = ledger_view(stats.snapshot())
    assert view["delivered_bytes"] == 10_000
    assert view["waste_bytes"] == 1975
    assert view["goodput_bytes"] == 10_000 - 1975
    assert view["waste"]["hedge_loss"] == 1000
    assert 0 < view["goodput_fraction"] < 1


def test_plan_gap_bytes_counted(tmp_data_file):
    """Near-adjacent extents merged through a gap charge the
    coalesce-gap waste class for exactly the dead bytes."""
    from nvme_strom_tpu.io.plan import plan_and_submit, plan_extents
    plan = plan_extents([(0, 0, 4096), (0, 8192, 4096)],
                        chunk_bytes=1 << 20, gap=4096)
    assert len(plan.spans) == 1
    assert plan.gap_bytes == 4096
    # adjacent/overlapping merges carry no gap
    plan2 = plan_extents([(0, 0, 4096), (0, 4096, 4096)],
                         chunk_bytes=1 << 20, gap=4096)
    assert plan2.gap_bytes == 0
    path, _ = tmp_data_file
    stats = StromStats()
    with StromEngine(_cfg(), stats=stats) as eng:
        fh = eng.open(path)
        views = plan_and_submit(eng, [(fh, 0, 4096), (fh, 8192, 4096)],
                                gap=4096)
        for pieces in views:
            for p in pieces:
                p.wait()
                p.release()
        eng.close(fh)
    assert stats.waste_coalesce_gap_bytes == 4096


def test_resilient_short_read_charges_retry_reread(tmp_data_file,
                                                   tmp_path):
    from nvme_strom_tpu.io.faults import FaultPlan, FaultyEngine
    from nvme_strom_tpu.io.resilient import ResilientEngine
    from nvme_strom_tpu.utils.config import ResilientConfig
    path, payload = tmp_data_file
    stats = StromStats()
    plan = FaultPlan.parse("short:every=1:frac=0.5:max_count=1")
    eng = ResilientEngine(
        FaultyEngine(StromEngine(_cfg(), stats=stats), plan),
        ResilientConfig(max_retries=2, backoff_base_s=0.0,
                        hedging=False, stuck_timeout_s=30.0))
    with eng:
        fh = eng.open(path)
        with eng.submit_read(fh, 0, 1 << 20) as p:
            view = p.wait()
            assert view.nbytes == 1 << 20
        eng.close(fh)
    # the short attempt delivered half the range; those bytes were
    # discarded and re-read
    assert stats.waste_retry_reread_bytes == (1 << 20) // 2
    assert stats.resilient_retries == 1


def test_degraded_read_charges_waste(tmp_data_file):
    from nvme_strom_tpu.io.health import DegradedRead
    path, payload = tmp_data_file
    stats = StromStats()
    with StromEngine(_cfg(), stats=stats) as eng:
        fh = eng.open(path)
        d = DegradedRead(eng, fh, 0, 8192, stats)
        view = d.wait()
        assert bytes(view) == payload[:8192]
        d.release()
        eng.close(fh)
    assert stats.waste_degraded_bytes == 8192
    assert stats.degraded_bytes == 8192


def test_hostcache_evicted_unused_waste():
    """A line filled from NVMe and evicted before any hit charges the
    evicted-before-reuse waste class; a line that served hits does
    not."""
    from nvme_strom_tpu.io.hostcache import _Line
    from nvme_strom_tpu.io import hostcache as hc
    stats = StromStats()

    class _FakeCache:
        _clock_evict = hc.HostCache._clock_evict
        # untenanted lines short-circuit both, but the real method
        # calls them unconditionally
        _tenant_over = hc.HostCache._tenant_over
        _tenant_drop_locked = hc.HostCache._tenant_drop_locked
        _tenant_slots: dict = {}

    cache = _FakeCache()
    line = _Line(("fk", 0), 0, "prefetch")
    line.valid = 4096
    cache._clock = {"prefetch": __import__("collections").deque(
        [line.key])}
    cache._lines = {line.key: line}
    cache._class_slots = {"prefetch": 1}
    cache.bytes_resident = 4096
    cache._over_quota = lambda k: True
    slot = cache._clock_evict("prefetch", stats)
    assert slot == 0
    assert stats.waste_evicted_unused_bytes == 4096
    # a hit line pays nothing
    line2 = _Line(("fk", 4096), 1, "prefetch")
    line2.valid = 4096
    line2.hits = 3
    cache._clock = {"prefetch": __import__("collections").deque(
        [line2.key])}
    cache._lines = {line2.key: line2}
    cache._class_slots = {"prefetch": 1}
    cache.bytes_resident = 4096
    cache._clock_evict("prefetch", stats)
    assert stats.waste_evicted_unused_bytes == 4096   # unchanged


def test_ring_time_ledger():
    led = RingTimeLedger(2)
    t0 = time.monotonic()
    led._last = t0
    led.sample([1, 0], None, now=t0 + 1.0)            # busy, idle
    led.sample([0, 0], ["open", "closed"], now=t0 + 1.5)  # stalled, idle
    led.note_restart(0, 0.25)
    snap = led.snapshot()
    assert snap["busy"][0] == pytest.approx(1.0)
    assert snap["idle"][1] == pytest.approx(1.5)
    assert snap["stalled"][0] == pytest.approx(0.5)
    assert snap["restarting"][0] == pytest.approx(0.25)
    stats = StromStats()
    led.export(stats)
    snap2 = stats.snapshot()
    assert "ring_state_s" in snap2
    from nvme_strom_tpu.utils.stats import openmetrics_from_snapshot
    prom = openmetrics_from_snapshot(snap2)
    assert 'strom_ring_state_seconds{ring="0",state="busy"} 1' in prom


def test_engine_exports_ring_state_gauge(tmp_data_file):
    path, _ = tmp_data_file
    stats = StromStats()
    with StromEngine(_cfg(), stats=stats) as eng:
        fh = eng.open(path)
        with eng.submit_read(fh, 0, 4096) as p:
            p.wait()
        time.sleep(0.12)            # past the sample gate
        eng.sync_stats()
        eng.close(fh)
    snap = stats.snapshot()
    assert "ring_state_s" in snap
    total = sum(sum(v) for v in snap["ring_state_s"].values())
    assert total > 0


# -- debug endpoint -----------------------------------------------------------

def _fetch(port, route):
    from nvme_strom_tpu.tools.strom_top import fetch
    return fetch("127.0.0.1", port, route)


def test_debug_server_off_by_default(monkeypatch):
    from nvme_strom_tpu.obs import debugsrv
    monkeypatch.delenv("STROM_DEBUG_PORT", raising=False)
    debugsrv.reset()
    assert maybe_start_debug_server(StromStats()) is None


def test_debug_server_routes_and_shutdown(tmp_data_file):
    """All six routes serve valid JSON/OpenMetrics against a LIVE
    engine; close() is a clean shutdown."""
    import urllib.error
    import urllib.request
    path, _ = tmp_data_file
    stats = StromStats()
    with StromEngine(_cfg(), stats=stats) as eng:
        fh = eng.open(path)
        with eng.submit_read(fh, 0, 8192) as p:
            p.wait()
        srv = DebugServer(stats, port=0)
        srv.attach_engine(eng)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/metrics",
                    timeout=5) as r:
                text = r.read().decode()
            assert "# TYPE strom_bytes_direct counter" in text
            assert text.rstrip().endswith("# EOF")
            assert "strom_waste_hedge_loss_bytes_total" in text
            attrib = _fetch(srv.port, "/attrib")
            assert "enabled" in attrib
            ledger = _fetch(srv.port, "/ledger")
            assert ledger["delivered_bytes"] > 0
            assert "waste" in ledger and "goodput_bytes" in ledger
            flight = _fetch(srv.port, "/flight")
            if eng.flight is not None:
                assert flight["enabled"] and flight["n_ops"] >= 1
            health = _fetch(srv.port, "/health")
            assert "ring_health" in health and "degraded" in health
            locks = _fetch(srv.port, "/locks")
            assert "armed" in locks and "edges" in locks
            index = _fetch(srv.port, "/")
            assert set(index["routes"]) == {
                "/metrics", "/attrib", "/ledger", "/flight",
                "/health", "/locks"}
        finally:
            port = srv.port
            srv.close()
        with pytest.raises((urllib.error.URLError, OSError)):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=1)
        eng.close(fh)


def test_maybe_start_debug_server_env(tmp_data_file, monkeypatch):
    from nvme_strom_tpu.obs import debugsrv
    monkeypatch.setenv("STROM_DEBUG_PORT", "0")
    debugsrv.reset()
    try:
        stats = StromStats()
        with StromEngine(_cfg(), stats=stats) as eng:
            srv = eng._debug_srv
            assert srv is not None
            assert _fetch(srv.port, "/health")["degraded"] is False
            # the engine detaches at close; the server itself survives
        assert _fetch(srv.port, "/ledger") is not None
    finally:
        debugsrv.reset()


def test_strom_top_renders_against_live_engine(tmp_data_file, capsys):
    """Acceptance: strom-top renders a frame against a live engine's
    debug endpoint (attribution on, one retired fold)."""
    from nvme_strom_tpu.obs import debugsrv
    from nvme_strom_tpu.tools import strom_top
    path, _ = tmp_data_file
    stats = StromStats()
    tracer = Tracer()
    col = AttributionCollector(stats=stats)
    tracer.add_sink(col.sink)
    try:
        with StromEngine(_cfg(), stats=stats, tracer=tracer) as eng:
            fh = eng.open(path)
            root = TraceContext.new()
            t0 = time.monotonic_ns()
            with use_context(root):
                with eng.submit_read(fh, 0, 1 << 20) as p:
                    p.wait()
            col.request_retired(root.trace_id, t0, time.monotonic_ns(),
                                klass="decode")
            srv = DebugServer(stats, port=0)
            srv.attach_engine(eng)
            # monkey-free: point /attrib at this collector via the
            # process singleton
            attrib_mod._collector = col
            attrib_mod._collector_init = True
            try:
                rc = strom_top.main(["--port", str(srv.port), "--once"])
            finally:
                attrib_mod.reset()
                srv.close()
            eng.close(fh)
    finally:
        tracer._sinks.clear()
    assert rc == 0
    out = capsys.readouterr().out
    assert "critical-path attribution" in out
    assert "decode" in out
    assert "goodput" in out


def test_strom_top_render_frame_unit():
    from nvme_strom_tpu.tools.strom_top import render_frame
    attrib = {"enabled": True, "requests": 2, "spans_dropped": 0,
              "classes": {"decode": {
                  "n": 2, "wall_p50_us": 1000, "wall_p99_us": 2000,
                  "wall_total_us": 3000.0,
                  "components": {c: {"p50_us": 1, "p99_us": 2,
                                     "total_us": 10.0, "share": 0.1}
                                 for c in ("sched_queue", "hostcache",
                                           "nvme_read", "retry_backoff",
                                           "hedge", "degraded", "bridge",
                                           "unattributed")}}}}
    ledger = {"delivered_bytes": 1000, "goodput_bytes": 900,
              "waste_bytes": 100, "goodput_fraction": 0.9,
              "waste": {"hedge_loss": 100},
              "ring_state_s": {"busy": [1.0], "idle": [3.0],
                               "stalled": [0.0], "restarting": [0.0]}}
    health = {"ring_health": ["closed"], "degraded": False}
    out = render_frame(attrib, ledger, health)
    assert "decode" in out and "goodput" in out and "ring 0" in out


# -- Perfetto counter tracks --------------------------------------------------

def test_tracer_counter_events_export(tmp_path):
    out = tmp_path / "t.json"
    t = Tracer(str(out))
    t.add_counter("strom.ring.inflight", {"0": 3, "1": 1})
    t.add_counter("strom.ring.inflight", {"0": 0, "1": 0})
    t.export()
    evs = json.load(open(out))["traceEvents"]
    cs = [e for e in evs if e.get("ph") == "C"]
    assert len(cs) == 2
    assert cs[0]["name"] == "strom.ring.inflight"
    assert cs[0]["args"] == {"0": 3.0, "1": 1.0}
    # disabled / sink-only tracers record no counters
    t2 = Tracer()
    t2.add_counter("x", {"a": 1})
    assert len(t2) == 0


def test_sched_emits_queue_depth_counter(tmp_path):
    from nvme_strom_tpu.io.sched import QoSScheduler
    tracer = Tracer(str(tmp_path / "t.json"))
    sched = QoSScheduler(
        submit_ring=lambda spans, ring: [object() for _ in spans],
        ring_free=lambda: [4, 4],
        stats=None, tracer=tracer, ring_cap=4)
    b = sched.enqueue([(0, 0, 4096)], "prefetch")
    sched.step()
    sched.ack_submitted(b)
    names = [e["name"] for e in tracer.events()
             if e.get("ph") == "C"]
    assert "strom.sched.queue_depth" in names


def test_arena_emits_occupancy_counter(tmp_path, monkeypatch):
    from nvme_strom_tpu.io.arena import PinnedArena
    from nvme_strom_tpu.utils import trace as trace_mod
    t = Tracer(str(tmp_path / "t.json"))
    monkeypatch.setattr(trace_mod, "global_tracer", t)
    arena = PinnedArena(1 << 20, lock_pages=False)
    slab = arena.carve(8192, "staging", lock=False)
    slab.release()
    arena.close()
    cs = [e for e in t.events() if e.get("ph") == "C"]
    assert len(cs) >= 2
    assert cs[0]["name"] == "strom.arena.occupancy"
    assert cs[0]["args"].get("carved_staging", 0) >= 8192


# -- flight recorder: attribution summary in dumps ---------------------------

def test_flight_dump_embeds_attrib_summary(tmp_path):
    from nvme_strom_tpu.io.flightrec import FlightRecorder
    from nvme_strom_tpu.utils.config import FlightConfig
    col = AttributionCollector()
    col.request_retired(1, 0, 1_000_000, klass="decode")
    fr = FlightRecorder(FlightConfig(enabled=True, ops=16,
                                     dir=str(tmp_path),
                                     min_interval_s=0.0), StromStats())
    fr.attrib = col
    fr.record("read", "decode", 0, 1, 0, 4096, 10, "ok")
    path = fr.dump("unit")
    doc = json.load(open(path))
    assert doc["attrib"]["requests"] == 1
    assert "decode" in doc["attrib"]["shares"]

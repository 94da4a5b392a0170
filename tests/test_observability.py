"""Observability layer (docs/OBSERVABILITY.md): request-scoped causal
tracing (TraceContext + contextvar propagation + explicit attachment),
the typed metrics registry with its OpenMetrics exporter and periodic
snapshotter, the flight recorder's ring buffer + trigger dumps, and the
counter-drift CI check that pins every StromStats counter to the
strom_stat tooling.  Hardware-free."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from nvme_strom_tpu.io.engine import StromEngine
from nvme_strom_tpu.io.flightrec import FlightRecorder
from nvme_strom_tpu.utils.config import EngineConfig, FlightConfig
from nvme_strom_tpu.utils.stats import (COUNTER_FIELDS, Log2Histogram,
                                        MetricsRegistry,
                                        MetricsSnapshotter, StromStats,
                                        openmetrics_from_snapshot,
                                        write_openmetrics_file)
from nvme_strom_tpu.utils.trace import (TraceContext, Tracer,
                                        attach_context, connected_tree,
                                        current_context, use_context)


def _engine(tracer=None, stats=None, **cfg):
    kw = dict(chunk_bytes=1 << 20, queue_depth=8,
              buffer_pool_bytes=16 << 20)
    kw.update(cfg)
    return StromEngine(EngineConfig(**kw),
                       stats=stats or StromStats(), tracer=tracer)


# -- TraceContext / causal propagation ---------------------------------------

def test_trace_context_child_links():
    root = TraceContext.new()
    c = root.child()
    g = c.child()
    assert c.trace_id == root.trace_id == g.trace_id
    assert c.parent_id == root.span_id
    assert g.parent_id == c.span_id
    assert root.parent_id is None
    a = g.args()
    assert a["trace"] == f"{root.trace_id:x}"
    assert a["span"] == g.span_id and a["parent"] == c.span_id


def test_contextvar_propagation_and_nested_spans(tmp_path):
    t = Tracer(str(tmp_path / "t.json"))
    assert current_context() is None
    root = TraceContext.new()
    with use_context(root):
        assert current_context() is root
        with t.span("outer"):
            inner_ctx = current_context()   # the outer span's identity
            assert inner_ctx is not root
            with t.span("inner"):
                pass
    assert current_context() is None
    evs = t.events()
    outer = next(e for e in evs if e["name"] == "outer")
    inner = next(e for e in evs if e["name"] == "inner")
    assert outer["args"]["parent"] == root.span_id
    assert inner["args"]["parent"] == outer["args"]["span"]
    t.add_span("req", 0, 1, ctx=root)       # emit the root itself
    assert connected_tree(t.events())


def test_attach_context_for_cross_thread_completion(tmp_path):
    """The explicit-attachment half: a pending's span completes on
    another thread, where the contextvar is empty — the child ctx
    captured at submit must still land it in the tree."""
    import threading
    t = Tracer(str(tmp_path / "t.json"))
    root = TraceContext.new()
    with use_context(root):
        ctx = attach_context()
    done = threading.Event()

    def completer():
        assert current_context() is None    # other thread: no scope
        t.add_span("io.complete", 0, 5, ctx=ctx)
        done.set()

    threading.Thread(target=completer).start()
    assert done.wait(5)
    ev = t.events()[0]
    assert ev["args"]["trace"] == f"{root.trace_id:x}"
    assert ev["args"]["parent"] == root.span_id
    assert connected_tree(t.events())


def test_no_context_means_flat_spans(tmp_path):
    from nvme_strom_tpu.utils.trace import NO_CONTEXT
    t = Tracer(str(tmp_path / "t.json"))
    t.add_span("flat", 0, 1, bytes=4)
    assert "trace" not in t.events()[0]["args"]
    assert attach_context() is NO_CONTEXT


def test_no_context_sentinel_blocks_cross_request_adoption(tmp_path):
    """Review regression: work captured OUTSIDE any scope must not be
    adopted by whatever request is current on the thread that later
    emits its span — NO_CONTEXT beats the contextvar; None still
    auto-attaches."""
    from nvme_strom_tpu.utils.trace import NO_CONTEXT
    t = Tracer(str(tmp_path / "t.json"))
    captured = attach_context()          # outside any scope
    assert captured is NO_CONTEXT
    other = TraceContext.new()
    with use_context(other):             # an unrelated request's scope
        t.add_span("foreign.work", 0, 1, ctx=captured)
        t.add_span("auto.work", 0, 1)    # None → auto (the contract)
    foreign = next(e for e in t.events() if e["name"] == "foreign.work")
    auto = next(e for e in t.events() if e["name"] == "auto.work")
    assert "trace" not in foreign.get("args", {})
    assert auto["args"]["trace"] == f"{other.trace_id:x}"


def test_sched_queue_span_not_adopted_by_dispatching_request(tmp_path):
    """An out-of-scope batch granted during ANOTHER request's dispatch
    round must emit a flat queue span, not join that request's tree."""
    from nvme_strom_tpu.io.sched import QoSScheduler
    t = Tracer(str(tmp_path / "t.json"))
    sched = QoSScheduler(submit_ring=lambda spans, ring: [],
                         ring_free=lambda: [4], tracer=t)
    b = sched.enqueue([(1, 0, 4096)], "prefetch")   # no scope
    other = TraceContext.new()
    with use_context(other):             # the dispatching request
        assert sched.step()
    assert b.granted
    q = next(e for e in t.events() if e["name"] == "strom.sched.queue")
    assert "trace" not in q.get("args", {}), q
    # and a batch enqueued INSIDE a scope still lands in its tree
    mine = TraceContext.new()
    with use_context(mine):
        b2 = sched.enqueue([(1, 0, 4096)], "prefetch")
    sched.step()
    q2 = [e for e in t.events()
          if e["name"] == "strom.sched.queue"][-1]
    assert q2["args"]["trace"] == f"{mine.trace_id:x}"
    assert b2.granted


def test_engine_wires_tracer_drop_counter_to_its_stats(tmp_data_file,
                                                       tmp_path):
    """Review regression: an engine built with a PRIVATE stats block
    must charge tracer drops to THAT block (the one it exports), not
    silently to global_stats."""
    path, _ = tmp_data_file
    tracer = Tracer(str(tmp_path / "t.json"), max_events=1)
    st = StromStats()
    with _engine(tracer=tracer, stats=st) as eng:
        fh = eng.open(path)
        for off in (0, 4096, 8192):
            with eng.submit_read(fh, off, 4096) as p:
                p.wait()
        eng.close(fh)
    assert tracer.dropped == 2
    assert st.trace_spans_dropped == 2


# -- tracer drop accounting (satellite) --------------------------------------

def test_tracer_drop_counts_into_stromstats(tmp_path):
    st = StromStats()
    t = Tracer(str(tmp_path / "t.json"), max_events=3, stats=st)
    for _ in range(5):
        t.add_span("s", 0, 1)
    assert len(t) == 3
    assert t.dropped == 2
    assert st.trace_spans_dropped == 2
    t.export()
    doc = json.loads((tmp_path / "t.json").read_text())
    assert doc["metadata"]["strom_dropped_events"] == 2


def test_tracer_drop_row_in_strom_stat():
    from nvme_strom_tpu.tools.strom_stat import render
    out = render({"bytes_direct": 1, "bounce_bytes": 0,
                  "trace_spans_dropped": 7, "flight_dumps": 2})
    assert "observability" in out
    assert "trace_spans_dropped" in out and "7" in out
    assert "TRACE INCOMPLETE" in out
    quiet = render({"bytes_direct": 1, "bounce_bytes": 0})
    assert "observability" not in quiet


def test_tracer_atexit_export(tmp_path):
    """STROM_TRACE's contract: the file exists after interpreter exit
    even when the program never called export()."""
    out = tmp_path / "atexit.trace.json"
    code = ("from nvme_strom_tpu.utils.trace import global_tracer\n"
            "global_tracer.add_span('x', 0, 10, bytes=1)\n")
    env = dict(os.environ, STROM_TRACE=str(out), JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    doc = json.loads(out.read_text())
    assert doc["traceEvents"][0]["name"] == "x"


# -- metrics registry ---------------------------------------------------------

def test_typed_counter_gauge_labels():
    reg = MetricsRegistry()
    c = reg.counter("reqs", "requests", ("klass", "ring"))
    c.inc(2, klass="decode", ring=0)
    c.inc(1, klass="decode", ring=0)
    c.inc(5, klass="scrub", ring=1)
    assert c.value(klass="decode", ring=0) == 3
    g = reg.gauge("depth", "", ("ring",))
    g.set(4, ring=0)
    g.set(2, ring=0)                      # gauges overwrite
    assert g.value(ring=0) == 2
    with pytest.raises(ValueError):
        c.inc(1, klass="decode")          # missing label
    with pytest.raises(ValueError):
        reg.gauge("reqs")                 # type clash
    text = reg.render_openmetrics()
    assert '# TYPE reqs counter' in text
    assert 'reqs_total{klass="decode",ring="0"} 3' in text
    assert 'depth{ring="0"} 2' in text
    assert text.rstrip().endswith("# EOF")


def test_log2_histogram_percentiles_and_export():
    h = Log2Histogram("lat_us", "latency")
    for v in (100,) * 90 + (100_000,) * 10:
        h.observe(v)
    assert h.total == 100
    assert h.percentile(50) == int(2 ** 6 * 2 ** 0.5)    # 100 → bucket 6
    assert h.percentile(99) == int(2 ** 16 * 2 ** 0.5)
    reg = MetricsRegistry()
    reg._metrics["lat_us"] = h
    text = reg.render_openmetrics()
    assert "lat_us_count 100" in text
    assert 'lat_us_bucket{le="+Inf"} 100' in text


def test_openmetrics_from_snapshot_labels():
    st = StromStats()
    st.add(bytes_direct=4096, cache_hits=3, breaker_trips=1)
    st.add_class_stat("decode", dispatches=4, hedges_issued=1)
    st.class_stat_gauges("decode", queue_wait_s=0.25)
    st.set_gauges(ring_depths=[0, 3], ring_health=["closed", "open"],
                  lat_read_p99_us=88.0, engine_degraded=0)
    st.add_member_bytes(["nvme0n1"], [1 << 20])
    text = openmetrics_from_snapshot(st.snapshot())
    for needle in (
            "# TYPE strom_bytes_direct counter",
            "strom_bytes_direct_total 4096",
            'strom_class_dispatches_total{klass="decode"} 4',
            'strom_class_queue_wait_s_max{klass="decode"} 0.25',
            'strom_ring_depth{ring="1"} 3',
            'strom_ring_breaker_open{ring="1",state="open"} 1',
            'strom_member_bytes_total{member="nvme0n1"} 1048576',
            "strom_lat_read_p99_us 88",
    ):
        assert needle in text, needle
    # every flat counter has a family line, even at zero
    assert "strom_requests_failed_total 0" in text
    assert text.rstrip().endswith("# EOF")


def test_strom_stat_prom_flag(tmp_path, capsys):
    from nvme_strom_tpu.tools import strom_stat
    st = StromStats()
    st.add(bytes_direct=123, kv_prefix_hits=2)
    export = tmp_path / "s.json"
    os.environ["STROM_STATS_EXPORT"] = str(export)
    try:
        st.maybe_export()
    finally:
        del os.environ["STROM_STATS_EXPORT"]
    rc = strom_stat.main([str(export), "--prom"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "strom_bytes_direct_total 123" in out
    assert "strom_kv_prefix_hits_total 2" in out
    assert "# EOF" in out


def test_metrics_file_written_at_export(tmp_path, monkeypatch):
    """STROM_METRICS_FILE: the OpenMetrics textfile rides every
    maybe_export sync point."""
    export = tmp_path / "s.json"
    mfile = tmp_path / "metrics.prom"
    monkeypatch.setenv("STROM_STATS_EXPORT", str(export))
    monkeypatch.setenv("STROM_METRICS_FILE", str(mfile))
    st = StromStats()
    st.add(bytes_direct=7)
    st.maybe_export()
    text = mfile.read_text()
    assert "strom_bytes_direct_total 7" in text
    assert text.rstrip().endswith("# EOF")


def test_metrics_file_standalone_without_stats_export(tmp_path,
                                                      monkeypatch):
    """The documented standalone configuration: ONLY STROM_METRICS_FILE
    set — sync points must still write the textfile (review finding:
    an early return on the unset JSON path used to skip it)."""
    mfile = tmp_path / "metrics.prom"
    monkeypatch.delenv("STROM_STATS_EXPORT", raising=False)
    monkeypatch.setenv("STROM_METRICS_FILE", str(mfile))
    st = StromStats()
    st.add(bytes_direct=9)
    st.maybe_export()
    assert "strom_bytes_direct_total 9" in mfile.read_text()


def test_metrics_snapshotter_series_and_file(tmp_path):
    st = StromStats()
    mfile = tmp_path / "m.prom"
    with MetricsSnapshotter(st, interval_s=0.05,
                            path=str(mfile)) as snap:
        st.add(bytes_direct=100)
        deadline = time.monotonic() + 5
        while not snap.series and time.monotonic() < deadline:
            time.sleep(0.02)
    assert snap.series, "no periodic snapshot within 5s"
    assert snap.series[-1]["bytes_direct"] == 100
    assert all("_t" in s for s in snap.series)
    assert "strom_bytes_direct_total 100" in mfile.read_text()


def test_write_openmetrics_file_atomic(tmp_path):
    p = tmp_path / "out.prom"
    write_openmetrics_file(str(p), {"bytes_direct": 5})
    assert "strom_bytes_direct_total 5" in p.read_text()
    assert not list(tmp_path.glob("out.prom.tmp*"))


# -- counter-drift CI check (satellite; thin shim since PR 13) ----------------
# The logic moved into the strom-lint driver
# (nvme_strom_tpu/analysis/counters.py) so one CLI run covers it; these
# shims keep tier-1 coverage identical.

def test_every_counter_rendered_by_strom_stat():
    """The drift gate: every StromStats counter must appear in SOME
    strom_stat block (render) — a new counter that skips the tooling
    fails here, not in a production triage session."""
    from nvme_strom_tpu.analysis.counters import check_counter_drift
    violations = [v for v in check_counter_drift()
                  if not v.key.startswith(("json:", "prom:"))]
    assert not violations, "\n".join(v.format() for v in violations)


def test_every_counter_in_json_and_prom():
    """--json and --prom both carry every counter (the fleet-tooling
    half of the drift gate)."""
    from nvme_strom_tpu.analysis.counters import check_counter_drift
    violations = [v for v in check_counter_drift()
                  if v.key.startswith(("json:", "prom:"))]
    assert not violations, "\n".join(v.format() for v in violations)


# -- flight recorder ----------------------------------------------------------

def test_flight_records_and_bounded_ring(tmp_path):
    st = StromStats()
    fr = FlightRecorder(FlightConfig(enabled=True, ops=16,
                                     dir=str(tmp_path),
                                     min_interval_s=0.0), st)
    for i in range(40):
        fr.record("read", "decode", i % 4, 1, i * 4096, 4096, 120, "ok")
    assert len(fr) == 16                      # bounded
    ops = fr.snapshot_ops()
    assert ops[0]["offset"] == 24 * 4096      # oldest kept = #24
    assert ops[-1]["klass"] == "decode"
    path = fr.dump("unit_test", extra={"k": 1})
    assert path and os.path.exists(path)
    doc = json.loads(open(path).read())
    assert doc["reason"] == "unit_test"
    assert doc["n_ops"] == 16
    assert doc["extra"] == {"k": 1}
    assert doc["stats"]["flight_dumps"] == 0  # snapshot precedes count
    assert doc["latency_us_p50"] > 0
    assert st.flight_dumps == 1


def test_flight_dump_rate_limited_per_reason(tmp_path):
    """The rate limit is PER REASON: a breaker_trip dump must not
    shadow the slo_violation dump that follows it inside the window —
    they are different incidents' first post-mortems."""
    fr = FlightRecorder(FlightConfig(enabled=True, ops=16,
                                     dir=str(tmp_path),
                                     min_interval_s=60.0), StromStats())
    fr.record("read", None, 0, 1, 0, 4096, 10, "ok")
    assert fr.dump("breaker_trip") is not None
    assert fr.dump("breaker_trip") is None    # same reason, in-window
    assert fr.dump("slo_violation") is not None   # different reason
    assert fr.dump("slo_violation") is None
    assert fr.dump("breaker_trip", force=True) is not None


def test_engine_records_ops_with_class_and_ring(tmp_data_file, tmp_path,
                                                monkeypatch):
    monkeypatch.setenv("STROM_FLIGHT_DIR", str(tmp_path))
    path, _ = tmp_data_file
    with _engine() as eng:
        assert eng.flight is not None         # always-on default
        fh = eng.open(path)
        with eng.submit_read(fh, 0, 4096, klass="decode") as p:
            p.wait()
        ps = eng.submit_readv([(fh, 0, 4096), (fh, 8192, 4096)],
                              klass="restore")
        for p in ps:
            p.wait()
            p.release()
        eng.close(fh)
        ops = eng.flight.snapshot_ops()
    assert len(ops) == 3
    assert ops[0]["klass"] == "decode"
    assert {o["klass"] for o in ops[1:]} == {"restore"}
    assert all(o["outcome"] in ("ok", "fallback") for o in ops)
    assert all(o["ring"] >= 0 for o in ops)
    assert all(o["bytes"] == 4096 for o in ops)


def test_flight_off_switch(monkeypatch, tmp_data_file):
    monkeypatch.setenv("STROM_FLIGHT", "0")
    path, _ = tmp_data_file
    with _engine() as eng:
        assert eng.flight is None
        fh = eng.open(path)
        with eng.submit_read(fh, 0, 4096) as p:
            p.wait()
        eng.close(fh)


def test_breaker_trip_dumps_flight_recorder(tmp_path):
    """The acceptance chaos path, deterministic and hardware-free: feed
    the supervisor errors until the ring breaker trips; the dump must
    exist and carry the failing ops that preceded the trip."""
    import errno
    from nvme_strom_tpu.io.health import EngineSupervisor
    from nvme_strom_tpu.utils.config import BreakerConfig

    class FakeEngine:
        n_rings = 2

        def __init__(self):
            self.stats = StromStats()
            self.flight = FlightRecorder(
                FlightConfig(enabled=True, ops=64, dir=str(tmp_path),
                             min_interval_s=0.0), self.stats)

    eng = FakeEngine()
    sup = EngineSupervisor(eng, BreakerConfig(
        enabled=True, ring_errors=3, device_errors=100))
    # the ops that will appear in the post-mortem
    for i in range(3):
        eng.flight.record("read", "decode", 0, 1, i * 4096, 0, 0,
                          "error", err=errno.EIO)
        sup.note_error(ring=0, err=errno.EIO)
    assert sup.ring_states()[0] == "open"
    assert eng.stats.breaker_trips == 1
    assert eng.stats.flight_dumps == 1
    dumps = sorted(tmp_path.glob("strom_flight_*breaker_trip*.json"))
    assert len(dumps) == 1
    doc = json.loads(dumps[0].read_text())
    assert doc["reason"] == "breaker_trip"
    assert doc["extra"]["ring"] == 0
    errors = [o for o in doc["ops"] if o["outcome"] == "error"]
    assert len(errors) == 3                   # the failing ops made it
    assert all(o["err"] == errno.EIO for o in errors)
    assert doc["stats"]["breaker_trips"] == 1


def test_degraded_entry_dumps_and_recovery_stops(tmp_path):
    import errno
    from nvme_strom_tpu.io.health import EngineSupervisor
    from nvme_strom_tpu.utils.config import BreakerConfig

    class FakeEngine:
        n_rings = 1

        def __init__(self):
            self.stats = StromStats()
            self.flight = FlightRecorder(
                FlightConfig(enabled=True, ops=16, dir=str(tmp_path),
                             min_interval_s=0.0), self.stats)

    eng = FakeEngine()
    sup = EngineSupervisor(eng, BreakerConfig(
        enabled=True, ring_errors=100, device_errors=2))
    sup.note_error(ring=0, err=errno.EIO)
    sup.note_error(ring=0, err=errno.EIO)
    assert sup.degraded()
    assert list(tmp_path.glob("strom_flight_*device_degraded*.json"))


def test_watchdog_stall_dumps_flight_recorder(tmp_path):
    import io as _io
    from nvme_strom_tpu.utils.watchdog import StepWatchdog

    class Eng:
        def __init__(self):
            self.stats = StromStats()
            self.stats.add(trace_spans_dropped=3)
            self.flight = FlightRecorder(
                FlightConfig(enabled=True, ops=16, dir=str(tmp_path),
                             min_interval_s=0.0), self.stats)

        def sync_stats(self):
            return {}

    eng = Eng()
    eng.flight.record("read", "decode", 0, 1, 0, 4096, 999, "ok")
    stream = _io.StringIO()
    wd = StepWatchdog(deadline_s=0.05, engine=eng, stream=stream,
                      max_reports=1)
    with wd.step("stalled"):
        time.sleep(0.2)
    wd.close()
    dump = stream.getvalue()
    assert "flight recorder: dumped" in dump
    assert "observability: trace_spans_dropped=3" in dump
    dumps = list(tmp_path.glob("strom_flight_*watchdog_stall*.json"))
    assert dumps
    doc = json.loads(dumps[0].read_text())
    assert doc["extra"]["label"] == "stalled"
    assert doc["ops"][0]["latency_us"] == 999


@pytest.mark.chaos
def test_ring_stall_chaos_produces_flight_dump(monkeypatch, tmp_path,
                                               tmp_data_file):
    """The acceptance chaos drive against the REAL engine: wedge a
    ring with the C-level stall injection, let the supervisor detect
    the stall and trip the breaker — the flight-recorder dump must
    exist and carry the ops recorded before the trip."""
    monkeypatch.setenv("STROM_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("STROM_FLIGHT_MIN_S", "0")
    monkeypatch.setenv("STROM_BREAKER_STALL_S", "0.1")
    monkeypatch.setenv("STROM_BREAKER_RESTART_S", "3600")  # no restart:
    #                      the trip itself is under test
    monkeypatch.setenv("STROM_SCHED", "0")   # deterministic round-robin
    path, _ = tmp_data_file
    st = StromStats()
    eng = _engine(stats=st, chunk_bytes=1 << 16,
                  buffer_pool_bytes=4 << 20, queue_depth=4)
    try:
        if eng.n_rings < 2:
            pytest.skip("engine did not shard here")
        fh = eng.open(path)
        # healthy traffic first: these ops populate the recorder and
        # must appear in the post-mortem
        for p in eng.submit_readv([(fh, 0, 4096), (fh, 8192, 4096)],
                                  klass="decode"):
            p.wait()
            p.release()
        eng.set_ring_stall(1, True)
        pend = eng.submit_readv([(fh, 16384, 4096)])  # parks on ring 1
        time.sleep(0.25)                     # > stall_s
        eng.supervisor.tick(force=True)      # stall → trip → dump
        # the trip may already have hot-restarted the ring (the first
        # restart is never backoff-gated) — open OR half-open both
        # prove the breaker acted; the dump is what's under test
        assert any(s != "closed"
                   for s in eng.supervisor.ring_states())
        assert st.breaker_trips >= 1
        assert st.flight_dumps >= 1
        dumps = sorted(tmp_path.glob(
            "strom_flight_*breaker_trip*.json"))
        assert dumps
        doc = json.loads(dumps[0].read_text())
        assert doc["reason"] == "breaker_trip"
        assert doc["n_ops"] >= 2             # the pre-trip ops made it
        assert {o["klass"] for o in doc["ops"]} >= {"decode"}
        assert doc["stats"]["breaker_trips"] >= 1
        eng.set_ring_stall(1, False)         # unwedge for clean close
        import errno as _errno
        for p in pend:
            try:
                p.wait(timeout=10.0)
            except OSError as e:
                # the un-backoff-gated first restart may have cancelled
                # the parked read; bare engine reads (no Resilient
                # wrapper) surface that as ECANCELED — the requeue
                # story is test_health's, not this test's
                assert e.errno == _errno.ECANCELED
            p.release()
        eng.close(fh)
    finally:
        eng.close_all()


# -- end-to-end causal tracing ------------------------------------------------

def test_engine_reads_tagged_under_request_context(tmp_data_file,
                                                   tmp_path):
    path, _ = tmp_data_file
    tracer = Tracer(str(tmp_path / "t.json"))
    with _engine(tracer=tracer) as eng:
        fh = eng.open(path)
        root = TraceContext.new()
        with use_context(root):
            ps = eng.submit_readv([(fh, 0, 4096), (fh, 1 << 20, 4096)],
                                  klass="decode")
            for p in ps:
                p.wait()
                p.release()
        eng.close(fh)
    reads = [e for e in tracer.events()
             if e["name"].startswith("strom.read")]
    assert len(reads) == 2
    assert all(e["args"]["trace"] == f"{root.trace_id:x}"
               for e in reads)
    assert all(e["args"]["parent"] == root.span_id for e in reads)
    assert connected_tree(tracer.events())


def test_sched_queue_wait_span_in_tree(tmp_data_file, tmp_path,
                                       monkeypatch):
    """A multi-ring engine's scheduler emits strom.sched.queue under
    the requester's context."""
    monkeypatch.setenv("STROM_RINGS", "2")
    path, _ = tmp_data_file
    tracer = Tracer(str(tmp_path / "t.json"))
    with _engine(tracer=tracer) as eng:
        if eng.scheduler is None:
            pytest.skip("engine too small to shard here")
        fh = eng.open(path)
        root = TraceContext.new()
        with use_context(root):
            ps = eng.submit_readv([(fh, 0, 4096)], klass="prefetch")
            for p in ps:
                p.wait()
                p.release()
        eng.close(fh)
    evs = tracer.events()
    q = [e for e in evs if e["name"] == "strom.sched.queue"]
    assert len(q) == 1
    assert q[0]["args"]["trace"] == f"{root.trace_id:x}"
    assert q[0]["args"]["klass"] == "prefetch"
    assert q[0]["args"]["ring"] >= 0
    assert connected_tree(evs)


@pytest.mark.perf
def test_hostcache_hit_and_fill_spans(tmp_data_file, tmp_path,
                                      monkeypatch):
    """The host-tier paths stay visible in a request trace: the fill on
    first touch, the DRAM hit on the repeat read."""
    from nvme_strom_tpu.io import hostcache
    from nvme_strom_tpu.io.plan import plan_and_submit
    from nvme_strom_tpu.utils.config import HostCacheConfig
    path, _ = tmp_data_file
    tracer = Tracer(str(tmp_path / "t.json"))
    hostcache.configure(HostCacheConfig(budget_mb=4,
                                        line_bytes=1 << 20))
    try:
        with _engine(tracer=tracer) as eng:
            fh = eng.open(path)
            root = TraceContext.new()
            with use_context(root):
                for _ in range(3):   # ghost round, fill round, hit round
                    for pieces in plan_and_submit(
                            eng, [(fh, 0, 1 << 20)], klass="decode"):
                        for p in pieces:
                            p.wait()
                            p.release()
            eng.close(fh)
    finally:
        hostcache.reset()
    names = [e["name"] for e in tracer.events()]
    assert "strom.cache.fill" in names
    assert "strom.cache.hit" in names
    hit = next(e for e in tracer.events()
               if e["name"] == "strom.cache.hit")
    assert hit["args"]["trace"] == f"{root.trace_id:x}"
    assert hit["args"]["bytes"] == 1 << 20
    fill = next(e for e in tracer.events()
                if e["name"] == "strom.cache.fill")
    assert fill["args"]["trace"] == f"{root.trace_id:x}"
    assert connected_tree(tracer.events())


@pytest.mark.perf
def test_serving_request_trace_tree_with_store(tmp_path):
    """The acceptance walkthrough: ONE serving request's trace connects
    admission → KV restore → (sched queue on a sharded engine) →
    engine I/O under one trace_id — including the restore-from-NVMe
    path on the second same-prefix request."""
    import jax
    import jax.numpy as jnp
    from nvme_strom_tpu.models.kv_offload import PrefixStore
    from nvme_strom_tpu.models.serving import DecodeServer
    from nvme_strom_tpu.models.transformer import (TransformerConfig,
                                                   init_params,
                                                   tiny_config)
    PAGE = 4
    cfg = TransformerConfig(**{**tiny_config().__dict__,
                               "dtype": jnp.float32})
    params = init_params(jax.random.key(0), cfg)
    tracer = Tracer(str(tmp_path / "serve.trace.json"))
    eng = _engine(tracer=tracer)
    page_bytes = (2 * cfg.n_layers * cfg.n_kv_heads * PAGE
                  * cfg.head_dim * 4)
    store = PrefixStore(cfg, eng, str(tmp_path / "p.kvstore"),
                        page_tokens=PAGE,
                        capacity_bytes=64 * page_bytes)
    # no HBM block cache: it would serve "b" before the store is asked
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64,
                       kv_store=store, prefix_cache=False)
    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(0, cfg.vocab, 3 * PAGE).tolist()
    srv.submit("a", sys_prompt + [7, 8], 4)
    srv.run()
    srv.submit("b", sys_prompt + [9], 4)      # restores pages via NVMe
    srv.run()
    store.close()
    eng.close_all()
    evs = tracer.events()
    req_spans = [e for e in evs if e["name"] == "strom.serve.request"]
    assert len(req_spans) == 2
    # request b: the restore path — its tree must span serving
    # admission, the kv restore, and real engine reads
    tid = req_spans[1]["args"]["trace"]
    mine = {e["name"] for e in evs
            if e.get("args", {}).get("trace") == tid}
    assert "strom.serve.request" in mine
    assert "strom.serve.admit" in mine
    assert "strom.serve.kv_restore" in mine
    assert "strom.kv.restore" in mine
    assert any(n.startswith("strom.read") for n in mine)
    if eng.n_rings > 1 and eng.scheduler is not None:
        assert "strom.sched.queue" in mine
    assert connected_tree(evs, tid)
    # and the two requests are SEPARATE trees
    assert req_spans[0]["args"]["trace"] != tid
    assert connected_tree(evs, req_spans[0]["args"]["trace"])
    # exported file round-trips
    out = tracer.export()
    doc = json.loads(open(out).read())
    assert connected_tree(doc["traceEvents"], tid)


@pytest.mark.perf
def test_degraded_read_span_carries_context(tmp_data_file, tmp_path):
    """Brown-out service stays visible in the request tree: DegradedRead
    emits strom.read.degraded tagged with the submit-time context."""
    from nvme_strom_tpu.io.health import DegradedRead
    path, _ = tmp_data_file
    tracer = Tracer(str(tmp_path / "t.json"))
    with _engine(tracer=tracer) as eng:
        fh = eng.open(path)
        root = TraceContext.new()
        with use_context(root):
            d = DegradedRead(eng, fh, 0, 4096, stats=eng.stats)
        view = d.wait()                       # outside the scope
        assert view.nbytes == 4096
        d.release()
        eng.close(fh)
        assert eng.stats.degraded_bytes == 4096
        flight_ops = eng.flight.snapshot_ops()
    ev = next(e for e in tracer.events()
              if e["name"] == "strom.read.degraded")
    assert ev["args"]["trace"] == f"{root.trace_id:x}"
    assert ev["args"]["parent"] == root.span_id
    assert flight_ops[-1]["outcome"] == "degraded"

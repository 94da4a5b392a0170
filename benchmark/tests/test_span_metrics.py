"""The readers of the program's spans and counters, on hand-built traces (as
``test_xplane.py`` builds one) and hand-built ``facts["timings"]``."""

import types

import pytest

from benchmark import program_spans as ps
from benchmark import xplane
from benchmark.layer_metrics import (idle_in_admit_rest, idle_in_prefill,
                                     plan_share, prefill_pad_share,
                                     prefill_share, queue_wait_mean_ms,
                                     read_wait_share, restore_self_share,
                                     retire_wait_share, slice_share,
                                     step_host_ms_max)

MS = 1_000_000
SPAN_READERS = (idle_in_prefill, idle_in_admit_rest, step_host_ms_max,
                plan_share, read_wait_share, slice_share, retire_wait_share,
                restore_self_share)
COUNTER_READERS = (queue_wait_mean_ms, prefill_share, prefill_pad_share)


def _ctx(trace=None, window_s=0.1, **facts):
    return types.SimpleNamespace(trace=trace, trace_window_s=window_s,
                                 window_s=10.0, facts=facts)


def _serving_trace():
    """Device busy 0..10, 30..40, 60..70, 95..100 ms: gaps of 20 ms starting
    at 10 (under prefill), 20 ms at 40 (under scatter), 25 ms at 70 (under
    admit alone) — and, in a second step, 0 under first_token."""
    ops = [("%a.1 = f32[2]{0} add(", 0, 10 * MS),
           ("%a.2 = f32[2]{0} add(", 30 * MS, 40 * MS),
           ("%a.3 = f32[2]{0} add(", 60 * MS, 70 * MS),
           ("%a.4 = f32[2]{0} add(", 95 * MS, 100 * MS)]
    host = [("step", 0, 100 * MS), ("strom.serve.step", 0, 80 * MS),
            ("strom.serve.step", 81 * MS, 100 * MS),
            ("admit", 4 * MS, 78 * MS), ("strom.serve.admit", 5 * MS, 78 * MS),
            ("strom.serve.prefill", 6 * MS, 35 * MS),
            ("strom.serve.scatter", 36 * MS, 50 * MS),
            ("strom.serve.first_token", 51 * MS, 55 * MS)]
    return xplane.Trace(ops={"/device:TPU:0": ops},
                        modules={"/device:TPU:0": []}, host=host)


def test_admission_idle_goes_to_the_innermost_span():
    ctx = _ctx(_serving_trace())
    assert idle_in_prefill.read(ctx) == pytest.approx(20.0)
    # scatter's 20 ms + admit's own 25 ms; nothing under first_token
    assert idle_in_admit_rest.read(ctx) == pytest.approx(45.0)
    # together they are the breakdown's ``admit`` gap over the window
    outer = dict(map(tuple, xplane.idle_gaps(ctx.trace, ("admit", "step"))))
    assert idle_in_prefill.read(ctx) + idle_in_admit_rest.read(ctx) \
        == pytest.approx(100.0 * outer["admit"] / 0.1)
    assert step_host_ms_max.read(ctx) == pytest.approx(80.0)


def _restore_trace():
    """One whole load of 100 ms and its parts; a stray ``strom.h2d`` outside
    any load (the comparison after the window) counts for the bridge's share
    and not against the load's own time."""
    host = [("restore", 0, 101 * MS), ("strom.restore.load", 0, 100 * MS),
            ("strom.restore.tensor", 1 * MS, 60 * MS),
            ("strom.restore.plan", 2 * MS, 7 * MS),
            ("strom.restore.read_wait", 8 * MS, 18 * MS),
            ("strom.h2d", 20 * MS, 40 * MS),
            ("strom.restore.retire", 41 * MS, 44 * MS),
            ("strom.restore.join", 50 * MS, 58 * MS),
            ("strom.restore.tensor", 60 * MS, 99 * MS),
            ("strom.restore.read_wait", 61 * MS, 71 * MS),
            ("strom.h2d", 72 * MS, 92 * MS),
            ("strom.h2d", 150 * MS, 160 * MS)]
    return xplane.Trace(ops={}, modules={}, host=host)


def test_restore_shares_and_self_time_add_up_to_the_load():
    ctx = _ctx(_restore_trace(), window_s=0.2)
    assert plan_share.read(ctx) == pytest.approx(2.5)
    assert read_wait_share.read(ctx) == pytest.approx(10.0)
    assert retire_wait_share.read(ctx) == pytest.approx(1.5)
    # the program has spans, this one never opened: 0, not nothing
    assert slice_share.read(ctx) == 0.0
    # 100 ms of load - (5 + 20 + 40 + 3 + 8) ms of parts
    assert restore_self_share.read(ctx) == pytest.approx(12.0)
    parts = (plan_share.read(ctx) + read_wait_share.read(ctx)
             + slice_share.read(ctx) + retire_wait_share.read(ctx)
             + 100.0 * (xplane.host_seconds(ctx.trace, "strom.h2d") - 0.010
                        + xplane.host_seconds(ctx.trace,
                                              "strom.restore.join")) / 0.2)
    assert parts + restore_self_share.read(ctx) == pytest.approx(
        100.0 * xplane.host_seconds(ctx.trace, ps.LOAD) / 0.2)


def test_inside_clips_to_the_outer_span():
    assert ps.inside([(5, 8), (9, 15), (20, 22)], [(0, 10)]) == [
        (5, 8), (9, 10)]


def test_a_program_without_spans_reports_nothing():
    """The parent commit: only the benchmark's own annotations and
    ``strom.h2d`` are in the trace, and no traced run at all gives None."""
    old = xplane.Trace(
        ops={"/device:TPU:0": [("%a.1 = f32[2]{0} add(", 0, 10 * MS),
                               ("%a.2 = f32[2]{0} add(", 30 * MS, 40 * MS)]},
        modules={"/device:TPU:0": []},
        host=[("admit", 5 * MS, 35 * MS), ("step", 0, 40 * MS),
              ("restore", 0, 40 * MS), ("strom.h2d", 1 * MS, 2 * MS)])
    for ctx in (_ctx(old), _ctx(None, window_s=None)):
        for mod in SPAN_READERS:
            assert mod.read(ctx) is None, mod.__name__


def test_counter_readers():
    t = {"admit_s": 4.0, "admits": 8, "queue_wait_s": 2.0, "prefill_s": 3.0,
         "prefill_tokens": 3328, "prompt_tokens": 3008}
    ctx = _ctx(timings=t)
    assert queue_wait_mean_ms.read(ctx) == pytest.approx(250.0)
    assert prefill_share.read(ctx) == pytest.approx(30.0)
    assert prefill_pad_share.read(ctx) == pytest.approx(100 * 320 / 3328)
    whole = _ctx(timings=dict(t, prefill_tokens=1280, prompt_tokens=1280))
    assert prefill_pad_share.read(whole) == 0.0


@pytest.mark.parametrize("facts", [
    {},                                             # a restore cell
    {"timings": {"admit_s": 4.0, "steps": 10}},     # the parent's keys
    {"timings": {"admit_s": 0.0, "admits": 0, "queue_wait_s": 0.0,
                 "prefill_tokens": 0, "prompt_tokens": 0}},   # no admission
])
def test_counter_readers_with_nothing_to_read(facts):
    ctx = _ctx(**facts)
    assert queue_wait_mean_ms.read(ctx) is None
    assert prefill_pad_share.read(ctx) is None
    if "prefill_s" not in facts.get("timings", {}):
        assert prefill_share.read(ctx) is None


def test_every_new_metric_is_in_benchmark_json_under_its_layer():
    from benchmark import harness
    per = {m["name"]: m for m in
           harness.load_json("BENCHMARK.json")["per_layer"]}
    serving = "decode servers (models/serving.py)"
    # queue_wait_mean_ms has a reader and no entry yet: the generator submits
    # on the stepping thread, so no accepted cell can move it (PERF.md §7)
    want = {}
    for base in ("prefill_share", "prefill_pad_share", "idle_in_prefill",
                 "idle_in_admit_rest", "step_host_ms_max"):
        want.update({f"{base}.chat": serving, f"{base}.flood": serving})
    for base, layer in (
            ("plan_share", "planner (io/plan.py)"),
            ("read_wait_share", "C engine (csrc/strom_io.cc)"),
            ("slice_share", "weight restore (parallel/weights.py)"),
            ("retire_wait_share", "bridge (ops/bridge.py)"),
            ("restore_self_share", "weight restore (parallel/weights.py)")):
        want[f"{base}.restore"] = layer       # one entry, both restore cells
    assert len(want) == 15
    for name, layer in want.items():
        assert per[name]["layer"] == layer and per[name]["better"] == "lower"
    for base in ("plan_share", "slice_share"):
        assert per[f"{base}.restore"]["workloads"] == [
            "m7b.restore", "m7b-tp4.restore4"]

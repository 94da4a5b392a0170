"""``h2d_puts_per_gib.restore`` (PR 49): its reader on hand-built ``facts``
and its entry in ``BENCHMARK.json``."""

import types

import pytest

from benchmark.layer_metrics import h2d_puts_per_gib

GIB = 2**30


def _ctx(engine):
    facts = {} if engine is None else {"engine": engine}
    return types.SimpleNamespace(trace=None, trace_window_s=None,
                                 window_s=45.0, facts=facts)


def test_the_ratio_counts_each_kind_of_put_once():
    ctx = _ctx({"restore_puts_staged": 1_400, "restore_puts_inline": 12,
                "restore_puts_assembled": 648,
                "bytes_to_device": 27 * GIB // 2})
    assert h2d_puts_per_gib.read(ctx) == pytest.approx(2_060 / 13.5)


def test_a_program_without_the_third_counter_reads_the_two_it_has():
    """The parent commit: every put is out of a staging view."""
    ctx = _ctx({"restore_puts_staged": 10_884, "bytes_to_device": 27 * GIB // 2})
    assert h2d_puts_per_gib.read(ctx) == pytest.approx(10_884 / 13.5)


@pytest.mark.parametrize("engine", [
    None,                                           # no facts.engine
    {},                                             # an empty one
    {"restore_puts_staged": 3},                     # no byte landed
    {"restore_puts_staged": 3, "bytes_to_device": 0},
])
def test_without_counters_or_bytes_it_reports_nothing(engine):
    assert h2d_puts_per_gib.read(_ctx(engine)) is None


def test_the_entry_is_in_benchmark_json_under_its_layer():
    from benchmark import harness
    bench = harness.load_json("BENCHMARK.json")
    per = {m["name"]: m for m in bench["per_layer"]}
    assert per["h2d_puts_per_gib.restore"] == {
        "name": "h2d_puts_per_gib.restore", "unit": "puts/GiB",
        "better": "lower", "source": "program_counter",
        "layer": "bridge (ops/bridge.py)", "moves": "data_gib_s",
        "workloads": ["m7b.restore", "m7b-tp4.restore4"]}
    assert bench["per_layer"][-1]["name"] == "h2d_puts_per_gib.restore"

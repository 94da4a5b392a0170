"""``put_wait_share.restore`` (PR 45): its reader on hand-built traces, as
``test_span_metrics.py`` builds them, and its entry in ``BENCHMARK.json``."""

import types

import pytest

from benchmark import xplane
from benchmark.layer_metrics import put_wait_share

MS = 1_000_000


def _ctx(host, window_s=0.2):
    return types.SimpleNamespace(
        trace=xplane.Trace(ops={}, modules={}, host=host),
        trace_window_s=window_s, window_s=10.0, facts={})


def test_put_wait_share_sums_the_reading_threads_waits():
    host = [("restore", 0, 101 * MS), ("strom.restore.load", 0, 100 * MS),
            ("strom.restore.tensor", 1 * MS, 60 * MS),
            ("strom.restore.read_wait", 8 * MS, 18 * MS),
            ("strom.restore.put_wait", 20 * MS, 24 * MS),
            ("strom.restore.put_wait", 30 * MS, 36 * MS),
            # a worker's line, beside the reader's
            ("strom.h2d", 20 * MS, 40 * MS)]
    assert put_wait_share.read(_ctx(host)) == pytest.approx(5.0)


def test_a_program_with_restore_spans_and_no_stage_reads_zero():
    """The parent commit: ``strom.restore.load`` is there, the span is not."""
    host = [("restore", 0, 101 * MS), ("strom.restore.load", 0, 100 * MS),
            ("strom.h2d", 20 * MS, 40 * MS)]
    assert put_wait_share.read(_ctx(host)) == 0.0


@pytest.mark.parametrize("host", [
    [("restore", 0, 40 * MS), ("strom.h2d", 1 * MS, 2 * MS)],   # no spans
    None,                                                       # no trace
])
def test_a_program_without_spans_reports_nothing(host):
    ctx = _ctx(host) if host is not None else types.SimpleNamespace(
        trace=None, trace_window_s=None, window_s=10.0, facts={})
    assert put_wait_share.read(ctx) is None


def test_the_entry_is_in_benchmark_json_under_its_layer():
    from benchmark import harness
    per = {m["name"]: m for m in
           harness.load_json("BENCHMARK.json")["per_layer"]}
    m = per["put_wait_share.restore"]
    assert m == {"name": "put_wait_share.restore", "unit": "%",
                 "better": "lower", "source": "program_span",
                 "layer": "weight restore (parallel/weights.py)",
                 "moves": "data_gib_s",
                 "workloads": ["m7b.restore", "m7b-tp4.restore4"]}

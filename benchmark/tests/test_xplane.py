"""The trace reduction on a small file recorded on the chip
(``tools/record_fixture.py``: three executions of a program called
``_paged_step`` 50 ms apart under ``step`` annotations, then one
``host_to_device`` of the program's bridge), and on hand-made intervals."""

import os

import pytest

from benchmark import xplane

FIXTURE = os.path.join(os.path.dirname(__file__), "fixture.xplane.pb")


def test_union():
    assert xplane.union_ns([(0, 10), (5, 20), (30, 40), (32, 35)]) == 30
    assert xplane.union_ns([]) == 0


def test_names():
    assert xplane.program_name("jit__paged_step(1234567)") == "_paged_step"
    assert xplane.program_name("jit_concatenate(99)") == "concatenate"
    assert xplane.op_key("%copy-start.12 = (bf16[4096,14336]{1,0:T(8,128)"
                         "(2,1)S(1)}, bf16[") == "copy-start bf16[4096,14336]"
    assert xplane.op_key("%fusion.3 = f32[16]{0} fusion(") == "fusion f32[16]"


def test_hand_made_trace():
    ms = 1_000_000
    tr = xplane.Trace(
        ops={"/device:TPU:0": [("%add.1 = f32[2]{0} add(", 0, 10 * ms),
                               ("%multiply.7 = f32[2]{0} multiply(", 5 * ms, 20 * ms),
                               ("%add.2 = f32[2]{0} add(", 60 * ms, 70 * ms)]},
        modules={"/device:TPU:0": [("jit__paged_step(1)", 0, 20 * ms),
                                   ("jit__paged_step(1)", 60 * ms, 70 * ms),
                                   ("jit_other(2)", 80 * ms, 81 * ms)]},
        host=[("admit", 15 * ms, 50 * ms), ("step", 0, 100 * ms),
              ("strom.h2d", 1 * ms, 3 * ms), ("strom.h2d", 4 * ms, 5 * ms)])
    assert xplane.busy_seconds(tr) == pytest.approx(0.030)
    assert xplane.idle_share(tr, 0.1) == pytest.approx(70.0)
    assert xplane.program_durations_ms(tr, "_paged_step") == [20.0, 10.0]
    assert xplane.median_program_ms(tr, "_paged_step") == 15.0
    assert xplane.median_program_ms(tr, "absent") is None
    assert xplane.host_seconds(tr, "strom.h2d") == pytest.approx(0.003)
    assert xplane.top_device_ops(tr)[0] == ["add f32[2]", pytest.approx(0.02)]
    assert xplane.top_programs(tr)[0][0] == "_paged_step"
    # the one gap (20..60 ms) starts under "admit", which takes precedence
    assert xplane.idle_gaps(tr, ("admit", "step")) == [
        ["admit", pytest.approx(0.040)]]
    assert xplane.idle_gaps(tr, ("step",)) == [["step", pytest.approx(0.040)]]


def test_recorded_trace():
    tr = xplane.load(FIXTURE)
    assert list(tr.ops) == ["/device:TPU:0"]
    steps = xplane.program_durations_ms(tr, "_paged_step")
    assert len(steps) == 3 and all(0.005 < d < 1.0 for d in steps)
    busy = xplane.busy_seconds(tr)
    assert 0 < busy < 0.001                       # three 13 us programs
    assert 99.0 < xplane.idle_share(tr, 0.16) < 100.0
    assert xplane.host_seconds(tr, "strom.h2d") > 0
    assert len([1 for n, _, _ in tr.host if n == "step"]) == 3
    gaps = dict(map(tuple, xplane.idle_gaps(tr, ("step",))))
    assert gaps["other"] == pytest.approx(0.1, abs=0.03)   # the two sleeps
    assert xplane.top_device_ops(tr)[0][0].startswith("convolution")

"""The benchmark's trace reduction (``benchmark/xplane.py``), the one
parser of the profiler's ``.xplane.pb`` the repository has: an operation is
keyed by what it IS and never by what it consumes, equal work adds up, the
first phase that covers an idle gap takes it, and a trace captured here on
the CPU loads and gives back the host's annotations by their bare names.
"""

import glob
import os
import time

import jax
import pytest

from benchmark import xplane

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests", "fixture.xplane.pb")
MS = 1_000_000


@pytest.mark.parametrize("event,key", [
    # an explicit copy of a dot's result is a copy: the operand says nothing
    ("%copy.9 = bf16[8]{0} copy(%dot.3)", "copy bf16[8]"),
    # a fusion that consumes a transpose is still that fusion
    ("%fusion.212 = bf16[4,8]{1,0} fusion(%transpose.1), kind=kLoop",
     "fusion bf16[4,8]"),
    # a kernel that consumes a dot
    ('%tpu_custom_call.3 = f32[16,128]{1,0} custom-call(%dot.1), '
     'custom_call_target="tpu_custom_call"', "tpu_custom_call f32[16,128]"),
    # a name made of its constituents keeps all of them, in order
    ("%convolution_reduce_fusion.5 = f32[] fusion(%custom-call.2)",
     "convolution_reduce_fusion f32[]"),
    # a tuple result: the first member's type, tiling and memory space off
    ("%copy-start.12 = (bf16[4096,14336]{1,0:T(8,128)(2,1)S(1)}, "
     "bf16[4096,14336]{1,0}, u32[]) copy-start(%p)",
     "copy-start bf16[4096,14336]"),
    # host planes log the name without the sigil
    ("fusion.7 = bf16[2]{0} fusion(p)", "fusion bf16[2]"),
    # nothing to parse: the name as it is
    ("%while.7", "%while.7"),
], ids=["copy_of_dot", "fusion_of_transpose", "kernel_of_dot",
        "constituents", "tuple_result", "no_sigil", "bare_name"])
def test_op_key_reads_the_operation_not_its_operands(event, key):
    assert xplane.op_key(event) == key


def test_op_key_is_bounded_where_nothing_parses():
    assert len(xplane.op_key("$" + "x" * 200)) == 48


@pytest.mark.parametrize("event,program", [
    ("jit__paged_step(1234567)", "_paged_step"),
    ("jit__paged_step", "_paged_step"),
    ("jit_concatenate(99)", "concatenate"),
    ("_paged_prefill(3)", "_paged_prefill")])
def test_program_name_drops_jit_and_the_run_id(event, program):
    assert xplane.program_name(event) == program


@pytest.mark.parametrize("intervals,length", [
    ([], 0),
    ([(0, 10), (10, 20)], 20),                  # touching
    ([(0, 100), (10, 20), (30, 40)], 100),      # nested
    ([(30, 40), (0, 10), (5, 20)], 30),         # unordered, overlapping
    (iter([(0, 1), (2, 3)]), 2)],               # a generator, as given
    ids=["empty", "touching", "nested", "unordered", "generator"])
def test_union_ns(intervals, length):
    assert xplane.union_ns(intervals) == length


def test_equal_operations_add_up_whatever_their_number():
    """Three ``add`` instructions of one type are one row; the same
    opcode at another type is another."""
    tr = xplane.Trace(ops={"/device:TPU:0": [
        ("%add.1 = f32[2]{0} add(%p.0, %p.1)", 0, 10 * MS),
        ("%add.22 = f32[2]{0} add(%copy.4, %p.1)", 20 * MS, 30 * MS),
        ("%add.3 = f32[4]{0} add(%p.0, %p.1)", 40 * MS, 45 * MS),
        ("%copy.4 = f32[2]{0} copy(%add.1)", 50 * MS, 52 * MS)]})
    assert xplane.top_device_ops(tr) == [
        ["add f32[2]", pytest.approx(0.020)],
        ["add f32[4]", pytest.approx(0.005)],
        ["copy f32[2]", pytest.approx(0.002)]]
    assert xplane.top_device_ops(tr, k=1) == [
        ["add f32[2]", pytest.approx(0.020)]]
    assert xplane.top_device_ops(xplane.Trace()) == []


def test_busy_seconds_is_a_union_averaged_over_the_planes_that_ran():
    """An operation inside a fusion overlaps it and is not counted twice;
    a plane on which nothing ran does not halve the figure."""
    tr = xplane.Trace(ops={
        "/device:TPU:0": [("%fusion.1 = f32[2]{0} fusion(", 0, 20 * MS),
                          ("%add.1 = f32[2]{0} add(", 5 * MS, 10 * MS)],
        "/device:TPU:1": [("%fusion.1 = f32[2]{0} fusion(", 0, 40 * MS)],
        "/device:TPU:2": []})
    assert xplane.busy_seconds(tr) == pytest.approx(0.030)
    assert xplane.idle_share(tr, 0.060) == pytest.approx(50.0)
    assert xplane.busy_seconds(xplane.Trace()) == 0.0


def test_program_durations_come_from_the_first_plane_that_ran_it():
    tr = xplane.Trace(modules={
        "/device:TPU:0": [("jit_other(2)", 0, MS)],
        "/device:TPU:1": [("jit__paged_step(1)", 0, 20 * MS),
                          ("jit__paged_step(1)", 60 * MS, 70 * MS),
                          ("jit__paged_step(1)", 80 * MS, 110 * MS)]})
    assert xplane.program_durations_ms(tr, "_paged_step") == [20.0, 10.0,
                                                              30.0]
    assert xplane.median_program_ms(tr, "_paged_step") == 20.0
    assert xplane.median_program_ms(tr, "absent") is None
    assert xplane.top_programs(tr) == [["other", pytest.approx(0.001)]]


@pytest.mark.parametrize("phases,gaps", [
    # innermost first: the gap that starts under both goes to ``admit``
    (("admit", "step"), {"admit": 0.040, "step": 0.010}),
    # the order IS the precedence
    (("step", "admit"), {"step": 0.050}),
    (("admit",), {"admit": 0.040, "other": 0.010}),
    ((), {"other": 0.050})],
    ids=["inner_first", "outer_first", "uncovered_is_other", "no_phases"])
def test_idle_gap_goes_to_the_first_phase_that_covers_its_start(phases, gaps):
    tr = xplane.Trace(
        ops={"/device:TPU:0": [("%a.1 = f32[2]{0} add(", 0, 20 * MS),
                               ("%a.2 = f32[2]{0} add(", 60 * MS, 70 * MS),
                               ("%a.3 = f32[2]{0} add(", 80 * MS, 90 * MS)]},
        host=[("admit", 15 * MS, 50 * MS), ("step", 0, 100 * MS)])
    found = dict(map(tuple, xplane.idle_gaps(tr, phases)))
    assert found == {name: pytest.approx(s) for name, s in gaps.items()}
    assert xplane.idle_gaps(xplane.Trace(), phases) == []


@pytest.mark.parametrize("plane,device", [
    ("/device:TPU:0", True), ("/device:TPU:3", True),
    ("/device:CUSTOM:Megascale Trace", False), ("/host:CPU", False)])
def test_only_a_chips_own_plane_is_a_device_plane(plane, device):
    assert xplane._is_device_plane(plane) is device


def test_recorded_trace_keys_every_operation_without_sigil_or_number():
    """The file recorded on a v5e: every ``XLA Ops`` event parses to an
    opcode and a type, and what ran is found under the program's name."""
    tr = xplane.load(FIXTURE)
    (ops,) = tr.ops.values()
    assert ops
    for name, start, end in ops:
        key = xplane.op_key(name)
        assert end >= start
        assert not key.startswith("%") and "=" not in key, (name, key)
        assert not key.split(" ")[0][-1].isdigit(), (name, key)
    assert [p for p, _ in xplane.top_programs(tr)][0] == "_paged_step"
    assert sum(t for _, t in xplane.top_device_ops(tr, k=100)) \
        >= xplane.busy_seconds(tr)


def test_capture_and_load_on_the_cpu(tmp_path):
    """End to end where there is no chip: a session traced here holds no
    device plane, so nothing is busy, and the host's annotations come back
    under their bare names with the time they covered."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1          # as the benchmark's traced runs
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(2):
            with jax.profiler.TraceAnnotation("strom.h2d", bytes=4096):
                time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    tr = xplane.load(path)
    assert tr.ops == {} and xplane.busy_seconds(tr) == 0.0
    assert len([1 for n, _, _ in tr.host if n == "strom.h2d"]) == 2
    assert 0.04 <= xplane.host_seconds(tr, "strom.h2d") < 0.5
    assert xplane.host_seconds(tr, "strom.absent") == 0.0


def test_load_of_a_missing_file_raises(tmp_path):
    with pytest.raises(RuntimeError, match="absent.xplane.pb"):
        xplane.load(str(tmp_path / "absent.xplane.pb"))

"""The readers of device time by scope (``layer_metrics/_scope_trace.py``):
the wire-format table reader on the two recorded files, the join and the
own-time rule on hand-built traces, and every reader's value against
arithmetic done by hand."""

import os
import types

import pytest

from benchmark import xplane
from benchmark.layer_metrics import (_scope_trace as S, prefill_mixer_share,
                                     prefill_unscoped_share,
                                     prefill_us_per_row, step_attn_share,
                                     step_head_share, step_mlp_share,
                                     step_staged_copy_share,
                                     step_unscoped_share)
from benchmark.tools import scope_table

HERE = os.path.dirname(os.path.abspath(__file__))
OLD = os.path.join(HERE, "fixture.xplane.pb")
NEW = os.path.join(HERE, "fixture_scopes.xplane.pb")
READERS = (step_unscoped_share, step_attn_share, step_mlp_share,
           step_head_share, step_staged_copy_share, prefill_unscoped_share,
           prefill_mixer_share, prefill_us_per_row)
DEV = "/device:TPU:0"


def _ctx(trace, table):
    ctx = types.SimpleNamespace(trace=trace, workload="none")
    ctx._scoped = S.Scoped(trace, table)
    return ctx


def _ctx_of(path):
    return _ctx(xplane.load(path), S.tables(path))


# ------------------------------------------------------- the recorded files

def test_the_table_reader_on_the_unscoped_fixture():
    """What the raw proto holds for the one fusion of ``record_fixture.py``'s
    program, read without ``ProfileData`` and without TensorFlow."""
    table = S.tables(OLD)
    (pid, name), rec = next(
        (k, v) for k, v in table.items()
        if k[1].split(" = ")[0] == "%convolution_tanh_fusion")
    assert pid == 4950857752023182173
    assert rec["tf_op"] == "jit(_paged_step)/dot_general:"
    assert rec["category"] == "convolution fusion"
    assert rec["flops"] == 2149580800 and rec["bytes"] == 6291456
    assert rec["source"].endswith("benchmark/tools/record_fixture.py:31")
    assert {v["category"] for v in table.values()} == {
        "convolution fusion", "copy-start", "copy-done"}
    # every device event of the trace finds its record
    sc = S.Scoped(xplane.load(OLD), table)
    assert len(sc.execs) == 3
    found = list(sc.records("_paged_step"))
    assert len(found) == 3 and all(calls == 3 and rec is not None
                                   for *_, calls, rec in found)


def test_a_trace_without_scopes_reads_none():
    ctx = _ctx_of(OLD)
    for reader in READERS:
        if reader is step_staged_copy_share:
            continue
        assert reader.read(ctx) is None, reader.__name__
    # the copies are told by their category, scopes or none: two small ones
    # beside a 12.6 us product
    sc = ctx._scoped
    copies = sum(ns for _, name, ns, _, _ in sc.records("_paged_step")
                 if name.startswith("%copy-"))
    assert step_staged_copy_share.read(ctx) == pytest.approx(
        100.0 * copies / sc.program_ns("_paged_step"))
    assert 0.05 < step_staged_copy_share.read(ctx) < 0.5


def test_no_trace_and_no_device_plane_read_none():
    host_only = xplane.Trace(host=[("step", 0, 10)])
    for ctx in (types.SimpleNamespace(trace=None, workload="none"),
                _ctx(host_only, {})):
        for reader in READERS:
            assert reader.read(ctx) is None


# ---------------------------------------------------------- hand-built traces

def test_scope_of_a_tf_op_path():
    assert S.scope_of("jit(_paged_step)/strom.attn.proj/dot_general:") == (
        None, "attn", "strom.attn.proj")
    assert S.scope_of("jit(_paged_prefill)/strom.prefill.4x512x1024/"
                      "strom.mlp/strom.moe.route/top_k") == (
        (4, 512, 1024), "mlp", "strom.mlp")
    # the bucket label alone is no family; gather and scatter are
    assert S.scope_of("jit(_paged_prefill)/strom.prefill.1x128x128/add") == (
        (1, 128, 128), None, None)
    assert S.scope_of("jit(f)/strom.prefill.1x128x128/strom.prefill.gather/"
                      "concatenate")[1:] == ("prefill", "strom.prefill.gather")
    assert S.scope_of("") == (None, None, None)


def test_own_time_is_duration_less_children():
    ops = [("while", 0, 100), ("a", 10, 30), ("b", 40, 90), ("b1", 50, 60),
           ("c", 100, 120)]
    own = {n: t for n, _, t in S.self_ns(ops)}
    assert own == {"while": 30, "a": 20, "b": 40, "b1": 10, "c": 20}
    assert sum(own.values()) == xplane.union_ns(
        (s, e) for _, s, e in ops) == 120


def _rec(tf_op, category):
    return {"tf_op": tf_op, "scope": S.scope_of(tf_op), "via": None,
            "category": category, "source": ""}


def _two_programs():
    """Two programs that both hold an instruction called ``%fusion.3``: in
    program 11 (the step) it lies under ``strom.mlp``, in program 22 (a
    prefill of 2 x 64 rows over 128) under ``strom.attn.proj``."""
    ops = [("%fusion.3", 0, 60), ("%copy.1", 60, 80), ("%argmax", 80, 100),
           ("%fusion.3", 200, 290), ("%gather", 290, 320)]
    mods = [("jit__paged_step(11)", 0, 100),
            ("jit__paged_prefill(22)", 200, 328)]
    label = "jit(_paged_prefill)/strom.prefill.2x64x128/"
    table = {
        (11, "%fusion.3"): _rec("jit(_paged_step)/strom.mlp/dot",
                                "convolution fusion"),
        (11, "%copy.1"): _rec("", "data formatting"),
        (11, "%argmax"): _rec("jit(_paged_step)/strom.head/argmax", "fusion"),
        (22, "%fusion.3"): _rec(label + "strom.attn.proj/dot",
                                "convolution fusion"),
        (22, "%gather"): _rec(label + "strom.prefill.gather/gather",
                              "data formatting")}
    return xplane.Trace(ops={DEV: ops}, modules={DEV: mods}), table


def test_two_programs_with_one_instruction_name_are_kept_apart():
    ctx = _ctx(*_two_programs())
    assert step_mlp_share.read(ctx) == pytest.approx(60.0)
    assert step_attn_share.read(ctx) == pytest.approx(0.0)
    assert step_head_share.read(ctx) == pytest.approx(20.0)
    assert step_unscoped_share.read(ctx) == pytest.approx(20.0)   # the copy
    assert step_staged_copy_share.read(ctx) == pytest.approx(20.0)
    # 90 of the prefill's 128 ns under attn, 30 under prefill, 8 ns of gaps
    assert prefill_mixer_share.read(ctx) == pytest.approx(100 * 90 / 128)
    assert prefill_unscoped_share.read(ctx) == pytest.approx(0.0)
    assert prefill_us_per_row.read(ctx) == pytest.approx(0.128 / (2 * 64))
    assert ctx._scoped.buckets() == {(2, 64, 128): [128]}


def test_an_execution_without_a_label_is_no_bucket():
    trace, table = _two_programs()
    for key in [k for k in table if k[0] == 22]:
        table[key] = _rec("jit(_paged_prefill)/dot", table[key]["category"])
    ctx = _ctx(trace, table)
    assert ctx._scoped.buckets() == {None: [128]}
    assert prefill_us_per_row.read(ctx) is None
    assert prefill_mixer_share.read(ctx) is None      # no scope: no share
    assert step_mlp_share.read(ctx) == pytest.approx(60.0)


def test_a_compiler_made_operation_takes_its_consumers_scope():
    """``slice-start`` → ``slice-done`` → the fusion that reads it; a copy
    nobody scoped reads; and a hole (a ``tf_op`` without a scope) stays."""
    mlp = "jit(_paged_step)/strom.mlp/dot_general"
    table = S.inherit({
        (1, "%slice-start.2 = (bf16[8]) slice-start(bf16[64] %p.1)"):
            _rec("", "async-start"),
        (1, "%slice-done.2 = bf16[8] slice-done((bf16[8]) %slice-start.2)"):
            _rec("", "async-done"),
        (1, "%fusion.7 = f32[4] fusion(bf16[8] %slice-done.2, f32[4] %q), "
            "kind=kOutput, calls=%fused.7"): _rec(mlp, "convolution fusion"),
        (1, "%copy.9 = bf16[8] copy(bf16[8] %p.2)"):
            _rec("params['layers.0.wq']", "data formatting"),
        (1, "%copy.8 = bf16[8] copy(bf16[8] %p.3)"):
            _rec("params['layers.0.wk']", "data formatting"),
        (1, "%fusion.8 = f32[4] fusion(bf16[8] %copy.8), kind=kLoop"):
            _rec("jit(_paged_step)/strom.attn.proj/mul", "loop fusion"),
        (1, "%add.3 = f32[4] add(f32[4] %fusion.7, f32[4] %copy.9)"):
            _rec("jit(_paged_step)/add", "non-fusion elementwise"),
        # the same names in another program are another program's
        (2, "%slice-done.2 = bf16[8] slice-done((bf16[8]) %slice-start.2)"):
            _rec("", "async-done")})
    got = {k[1].split(" = ")[0]: (v["scope"][1], v["via"])
           for k, v in table.items() if k[0] == 1}
    assert got == {"%slice-start.2": ("mlp", "%fusion.7"),
                   "%slice-done.2": ("mlp", "%fusion.7"),
                   "%fusion.7": ("mlp", None),
                   "%copy.9": (None, None),      # its reader has no scope
                   "%copy.8": ("attn", "%fusion.8"),   # a parameter's copy
                   "%fusion.8": ("attn", None),
                   "%add.3": (None, None)}             # a hole stays one
    assert [v["scope"][1] for k, v in table.items() if k[0] == 2] == [None]


# -------------------------------------------- the scoped fixture, by hand

#: ``record_scope_fixture.py``'s toy step: which scope each device event of
#: it lies under, as the recorded table says (checked against the tool's
#: print of the recording); a ``copy-start`` / ``copy-done`` is the
#: compiler's and takes its consumer's.  The unscoped product swallowed the
#: argmax (``strom.head``): a fusion keeps the product's label.
STEP_FAMILY = {"%fusion": "embed", "%copy-start": "embed",
               "%copy-done": "embed", "%rev.1": "embed", "%iota.5": "embed",
               "%compare_and_fusion": "embed", "%fusion.6": "embed",
               "%fusion.1": "attn", "%copy-start.1": "attn",
               "%copy-done.1": "attn", "%convolution_tanh_fusion.2": "mlp",
               "%copy.9": "mlp", "%iota_reduce_fusion": None, "%while": None}
INSIDE_WHILE = ("%copy.9", "%convolution_tanh_fusion.2")


def test_every_reader_on_the_scoped_fixture():
    tr = xplane.load(NEW)
    ops, mods = tr.ops[DEV], tr.modules[DEV]
    steps = [(s, e) for n, s, e in mods if "_paged_step" in n]
    assert len(steps) == 3
    dur = {}
    for name, s, e in ops:
        if any(a <= s < b for a, b in steps):
            head = name.split(" = ")[0]
            dur[head] = dur.get(head, 0) + e - s
    assert set(dur) == set(STEP_FAMILY)
    # the loop's own time is what its three trips leave of it
    dur["%while"] -= sum(dur[n] for n in INSIDE_WHILE)
    assert 0 < dur["%while"] < 300
    total = sum(e - s for s, e in steps)
    by = {}
    for head, ns in dur.items():
        by[STEP_FAMILY[head]] = by.get(STEP_FAMILY[head], 0) + ns

    ctx = _ctx_of(NEW)
    assert step_attn_share.read(ctx) == pytest.approx(100 * by["attn"] / total)
    assert step_mlp_share.read(ctx) == pytest.approx(100 * by["mlp"] / total)
    assert step_head_share.read(ctx) == pytest.approx(
        100 * by["embed"] / total)
    assert step_unscoped_share.read(ctx) == pytest.approx(
        100 * by[None] / total)
    copies = sum(ns for head, ns in dur.items() if head.startswith("%copy"))
    assert step_staged_copy_share.read(ctx) == pytest.approx(
        100 * copies / total)
    # ... and as numbers (ns of the recording: 105,553 in three steps)
    assert total == 105553
    assert step_attn_share.read(ctx) == pytest.approx(8.953, abs=1e-3)
    assert step_mlp_share.read(ctx) == pytest.approx(27.193, abs=1e-3)
    assert step_head_share.read(ctx) == pytest.approx(14.863, abs=1e-3)
    assert step_unscoped_share.read(ctx) == pytest.approx(48.934, abs=1e-3)
    assert step_staged_copy_share.read(ctx) == pytest.approx(3.186, abs=1e-3)
    # the partition adds up: families + unscoped + gaps = the steps
    sc = ctx._scoped
    inside = sum(sc.by_family(S.STEP).values())
    assert inside == pytest.approx(sum(dur.values()))
    assert 0.999 < inside / total <= 1.0


def test_buckets_of_the_scoped_fixture():
    """Two compiled shapes, 3 + 2 executions: every execution under exactly
    one label, the labels the host spans' ``program=`` values."""
    tr = xplane.load(NEW)
    prefills = [e - s for n, s, e in tr.modules[DEV] if "_paged_prefill" in n]
    assert prefills == [6280, 5307, 5247, 18697, 16931]
    ctx = _ctx_of(NEW)
    assert ctx._scoped.buckets() == {(2, 128, 128): prefills[:3],
                                     (4, 256, 256): prefills[3:]}
    assert scope_table.span_programs(NEW) == {"2x128x128": 3, "4x256x256": 2}
    rows = 3 * 2 * 128 + 2 * 4 * 256
    assert prefill_us_per_row.read(ctx) == pytest.approx(
        sum(prefills) / 1e3 / rows)
    # attn 14,737 + ssm 11,204 ns of 52,462 (the cumsum's pieces and the
    # weights' copies under their consumers'); gather and scatter 10,570
    assert prefill_mixer_share.read(ctx) == pytest.approx(
        100 * (14737 + 11204) / 52462)
    assert prefill_unscoped_share.read(ctx) == pytest.approx(0.0)
    rep = scope_table.report(NEW, 5)
    assert rep["programs"]["_paged_prefill"]["families"] == pytest.approx(
        {"mlp": 15857e-9, "attn": 14737e-9, "ssm": 11204e-9,
         "prefill": 10570e-9})
    assert set(rep["buckets"]) == set(rep["span_programs"])
    assert rep["top"][0]["op"].startswith("iota_reduce_fusion")
    assert rep["top"][0]["source"] == \
        "benchmark/tools/record_scope_fixture.py:41"

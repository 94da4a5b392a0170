"""The one walk every kernel's reader shares (``layer_metrics/_kernel_trace``):
what counts as a call, the walk against the four helpers' logic it replaced,
every kernel reader's value against what the parent commit's readers gave on
the same traces (written down from the parent, PR 41), and the readers PR 41
added, on the file ``tools/record_kernel_fixture.py`` recorded on the chip."""

import bisect
import os
import types

import pytest

import test_granite_hybrid as G
import test_kimi_mla as M
import test_lfm2_moe as L
import test_mimo_swa as S
from benchmark import costs_moe, harness, run, xplane
from benchmark.layer_metrics import _kernel_trace as K

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "fixture_kernels.xplane.pb")
NO_KERNELS = [os.path.join(HERE, f) for f in ("fixture.xplane.pb",
                                              "fixture_scopes.xplane.pb")]
B = harness.load_json("BENCHMARK.json")
PEAKS = harness.load_json("benchmark", "peaks.json")["TPU v5 lite"]
MS = 1_000_000


def _hybrid_trace():
    upd = ("%strom_ssm_update.7 = (f32[64,2,64,32]{3,2,1,0}, "
           "f32[65,64,64,128]{3,2,1,0}) custom-call(...)")
    scan = ("%strom_ssm_scan.2 = (bf16[1,64,1024,64]{3,2,1,0}, "
            "f32[1,64,128,64]{3,2,1,0}) custom-call(...)")
    ops = [(upd, 0, MS), (upd, 2 * MS, 3 * MS), (scan, 50 * MS, 51 * MS),
           ("%fusion.1 = bf16[64,8192]{1,0} fusion(...)", 4 * MS, 5 * MS)]
    return xplane.Trace(ops={"/device:TPU:0": ops},
                        modules={"/device:TPU:0": [
                            ("jit__paged_step(1)", 0, 40 * MS)]})


def _cases() -> dict:
    """{cell: the ctx its family's test file reads its synthetic trace with}:
    each trace holds an operation that names a kernel among its operands."""
    return {"g4hm.flood": G._ctx(_hybrid_trace()),
            "lfm2.flood": L._ctx(L._synthetic_trace(), timings=L.TIMINGS),
            "k2c.flood8k": M._ctx(M._synthetic_trace(), timings=M.TIMINGS),
            "mimo.flood16k": S._ctx(S._synthetic_trace(), timings=S.TIMINGS)}


#: what the PARENT's readers (the four private helpers, commit 4b3c9ac) gave
#: on those traces, every digit: scratch run of PR 41 before the helpers went
PARENT = {
    "g4hm.flood": {"hybrid_step_roofline": 50.15017963369963,
                   "ssm_update_roofline": 33.16809768009768,
                   "ssm_scan_roofline": 2.68865641025641,
                   "ssm_step_share": 5.0},
    "lfm2.flood": {"moe_step_roofline": 38.95336634920635,
                   "moe_experts_roofline": 46.389992348392354,
                   "moe_prefill_experts_roofline": 37.71320732600732,
                   "moe_experts_share": 15.0, "moe_route_share": 3.75},
    "k2c.flood8k": {"mla_attn_roofline": 29.67764551892552,
                    "mla_attn_share": 25.0,
                    "mla_step_roofline": 86.91623228327228,
                    "prefill_mfu": 46.47178274653131},
    "mimo.flood16k": {"swa_step_roofline": 92.1116335042735,
                      "swa_prefill_mfu": 76.93012084087987,
                      "full_attn_roofline": 47.04648595848595,
                      "window_attn_roofline": 21.765313797313798,
                      "kv_prefill_roofline": 65.15564724196278,
                      "window_attn_share": 5.0}}


@pytest.mark.parametrize("cell", sorted(PARENT))
def test_every_kernel_reader_gives_the_parents_value(cell):
    ctx = _cases()[cell]
    entries = {m["name"].split(".")[0]: m["name"]
               for m in run.cell_metrics(B, cell)[1]}
    for reader, want in PARENT[cell].items():
        got = harness.plugin("layer_metrics", entries[reader]).read(ctx)
        assert got == want, (entries[reader], got, want)   # to the last digit


# ------------------------------------------- what the four helpers did (PR 40)

def _old_moe_runs(trace, program, kernel):
    """``_moe_trace.runs``: every operation of the executions that ran the
    kernel, on the first plane that did."""
    is_k = lambda n: kernel in n.split("=", 1)[0]                 # noqa: E731
    for plane, ops in trace.ops.items():
        if not any(is_k(n) for n, _, _ in ops):
            continue
        ops = sorted(ops, key=lambda o: o[1])
        starts = [s for _, s, _ in ops]
        out = []
        for name, s, e in trace.modules.get(plane, []):
            if xplane.program_name(name) != program:
                continue
            inside = ops[bisect.bisect_left(starts, s):
                         bisect.bisect_left(starts, e)]
            if any(is_k(n) for n, _, _ in inside):
                out.append((e - s, inside))
        return out
    return []


def _old_swa_runs(trace, program, kernels):
    """``_swa_trace.runs`` (``_mla_trace.step_runs`` for one name): (ns of
    the execution, ns of the kernels' calls in it, the calls)."""
    is_k = lambda n: any(k in n.split("=", 1)[0] for k in kernels)  # noqa
    for plane, ops in trace.ops.items():
        hits = sorted((s, e) for n, s, e in ops if is_k(n))
        if not hits:
            continue
        starts = [s for s, _ in hits]
        out = []
        for mod, s, e in trace.modules.get(plane, []):
            if xplane.program_name(mod) != program:
                continue
            inside = hits[bisect.bisect_left(starts, s):
                          bisect.bisect_left(starts, e)]
            if inside:
                out.append((e - s, sum(b - a for a, b in inside),
                            len(inside)))
        return out
    return []


def _old_ssm_events(trace, kernel):
    """``_ssm_trace.kernel_events``: the name ANYWHERE in the event's text."""
    for ops in trace.ops.values():
        hits = [(n, (e - s) / 1e9) for n, s, e in ops if kernel in n]
        if hits:
            return hits
    return []


KERNELS = ("strom_ssm_update", "strom_ssm_scan", "strom_moe_gmm",
           "strom_mla_attn", "strom_paged_attn", "strom_window_attn",
           ("strom_kv_prefill", "strom_window_prefill"))


def _traces():
    out = [ctx.trace for ctx in _cases().values()]
    out += [xplane.load(p) for p in NO_KERNELS + [RECORDED]]
    return out


def test_the_shared_walk_is_the_old_helpers_walk():
    found = 0
    for tr in _traces():
        for kernels in KERNELS:
            tup = (kernels,) if isinstance(kernels, str) else kernels
            for program in (K.STEP, K.PREFILL):
                new = K.runs(tr, program, kernels)
                old = _old_swa_runs(tr, program, tup)
                assert [(ns, sum(e - s for _, s, e in c), len(c))
                        for ns, c in new] == old
                assert K.totals(new) == (sum(r[0] for r in old),
                                         sum(r[1] for r in old),
                                         sum(r[2] for r in old))
                found += len(old)
                if isinstance(kernels, str):
                    assert K.runs(tr, program, kernels, every=True) \
                        == _old_moe_runs(tr, program, kernels)
    assert found == 19          # executions that ran a kernel: 8 synthetic, 11 recorded


def test_the_name_anywhere_rule_counted_consumers_and_is_gone():
    """The recorded file: the operation that consumes a one-result kernel
    names it among its operands, and ``_ssm_trace.kernel_events`` would have
    counted it; a two-result kernel's consumers name a ``get-tuple-element``
    (why the four ``g4hm`` entries read the same either way: PERF.md §6)."""
    tr = xplane.load(RECORDED)
    for kernel, calls, anywhere in (("strom_paged_attn", 3, 6),
                                    ("strom_window_attn", 3, 6),
                                    ("strom_kv_prefill", 2, 4),
                                    ("strom_ssm_update", 3, 3)):
        assert len(K.events(tr, kernel)) == calls, kernel
        assert len(_old_ssm_events(tr, kernel)) == anywhere, kernel
    for tr in _traces():
        for kernel in ("strom_ssm_update", "strom_ssm_scan"):
            assert K.events(tr, kernel) == _old_ssm_events(tr, kernel)


def test_is_call_reads_the_text_left_of_the_equals_sign_only():
    call = "%strom_moe_gmm.21 = bf16[1536,2048]{1,0} custom-call(bf16[..] %x)"
    consumer = ("%fusion.2 = bf16[128,2048]{1,0} fusion(bf16[1536,2048]{1,0} "
                "%strom_moe_gmm.21, bf16[128,2048]{1,0} %copy.3)")
    assert K.is_call(call, "strom_moe_gmm")
    assert not K.is_call(consumer, "strom_moe_gmm")
    assert K.is_call(call, ("strom_kv_prefill", "strom_moe_gmm"))
    assert not K.is_call(consumer, ("strom_kv_prefill", "strom_moe_gmm"))
    assert not K.is_call("%fusion.3 = f32[16]{0} fusion(...)", "strom_moe_gmm")
    # a trace whose only mention of the kernel is an operand ran no call
    tr = xplane.Trace(ops={"/device:TPU:0": [(consumer, 0, MS)]},
                      modules={"/device:TPU:0": [
                          ("jit__paged_step(1)", 0, 2 * MS)]})
    assert K.events(tr, "strom_moe_gmm") == []
    assert K.runs(tr, K.STEP, "strom_moe_gmm") == []
    assert K.share(tr, K.STEP, "strom_moe_gmm") is None
    assert K.events(None, "strom_moe_gmm") == []
    assert K.runs(None, K.STEP, "strom_moe_gmm") == []


def test_only_the_shared_modules_walk_the_device_events():
    """No reader under ``layer_metrics/`` goes through ``trace.ops`` itself:
    a kernel's calls come from ``_kernel_trace`` (by the name left of the
    ``=``), an operation's scope from ``_scope_trace`` (by its record), so no
    kernel's name can be matched in an event's operands."""
    where = os.path.join(harness.BENCH_DIR, "layer_metrics")
    walkers = [f for f in sorted(os.listdir(where)) if f.endswith(".py")
               and ".ops" in open(os.path.join(where, f)).read()]
    assert walkers == ["_kernel_trace.py", "_scope_trace.py"]
    assert not os.path.exists(os.path.join(where, "_ssm_trace.py"))


# ------------------------------------------------------- the readers PR 41 adds

def _read(name, ctx):
    return harness.plugin("layer_metrics", name).read(ctx)


def test_full_attn_share_on_the_recorded_file_and_by_hand():
    hf = harness.load_json("benchmark", "configs", "mimo-v2.5.json")
    tr = xplane.load(RECORDED)
    ctx = types.SimpleNamespace(trace=tr, config=hf, facts={})
    (plane, ops), = tr.ops.items()
    steps = [(s, e) for n, s, e in tr.modules[plane]
             if xplane.program_name(n) == "_paged_step"]
    assert len(steps) == 3
    for reader, kernel in (("full_attn_share.mimo", "strom_paged_attn"),
                           ("window_attn_share.mimo", "strom_window_attn")):
        spent = sum(e - s for n, s, e in ops
                    if n.startswith("%" + kernel)
                    and any(a <= s < b for a, b in steps))
        want = 100.0 * spent / sum(b - a for a, b in steps)
        assert 0 < want < 100
        assert _read(reader, ctx) == pytest.approx(want, rel=1e-12)
    # the synthetic step of test_mimo_swa: two calls of 2 ms in 10 ms
    ctx = S._ctx(S._synthetic_trace(), timings=S.TIMINGS)
    assert _read("full_attn_share.mimo", ctx) == pytest.approx(100 * 4 / 10)
    dense = harness.load_json("benchmark", "configs", "mistral-7b-v0.3.json")
    for none in (S._ctx(None), S._ctx(S._synthetic_trace(), dense),
                 S._ctx(xplane.load(NO_KERNELS[1]))):
        assert _read("full_attn_share.mimo", none) is None


def test_the_two_counter_readers():
    facts = lambda **t: types.SimpleNamespace(facts={"timings": t})  # noqa
    ctx = facts(steps=200, attn_grid_steps=217_600, moe_calls=1_200,
                moe_rounds=1_200, moe_calls_prefill=60, moe_rounds_prefill=90)
    assert _read("attn_grid_steps.flood", ctx) == 1088.0
    assert _read("moe_rounds_per_call.flood", ctx) == pytest.approx(
        1290 / 1260)
    assert _read("moe_rounds_per_call.flood", facts(
        steps=5, moe_calls=30, moe_rounds=30)) == 1.0
    # a program from before the counters, no expert layer, no facts
    for old in (facts(steps=200, moe_calls=1_200), facts(steps=0),
                types.SimpleNamespace(facts={})):
        assert _read("attn_grid_steps.flood", old) is None
    for old in (facts(steps=200, moe_calls=1_200), facts(steps=200),
                facts(steps=200, moe_calls=0, moe_rounds=0),
                types.SimpleNamespace(facts={})):
        assert _read("moe_rounds_per_call.flood", old) is None


def test_prefill_batch_mean():
    facts = lambda **t: types.SimpleNamespace(facts={"timings": t})  # noqa
    assert _read("prefill_batch_mean.flood",
                 facts(admits=311, prefill_calls=100)) == 3.11
    assert _read("prefill_batch_mean.flood",
                 facts(admits=7, prefill_calls=7)) == 1.0
    # a program from before admissions were groups; an empty window
    for old in (facts(admits=7), facts(admits=0, prefill_calls=0),
                types.SimpleNamespace(facts={})):
        assert _read("prefill_batch_mean.flood", old) is None


@pytest.mark.parametrize("config, held", [("kimi-k2.7-code", 12),
                                          ("mimo-v2.5", 16)])
def test_experts_rooflines_read_a_share_of_the_experts(config, held):
    """A ``n_routed_experts`` file (a device that holds a share): the cost is
    the touched experts' matrices once, at the file's own widths."""
    hf = harness.load_json("benchmark", "configs", config + ".json")
    assert "num_experts" not in hf and hf["n_routed_experts"] == held
    d, fe = hf["hidden_size"], hf["moe_intermediate_size"]
    nbytes, flops = costs_moe.experts_cost(hf, 16.0, 9.0)
    assert nbytes == (9.0 * 3 * d * fe + 16.0 * (2 * d + 2 * fe)) * 2
    assert flops == 2.0 * 16.0 * 3 * d * fe
    gmm = "%strom_moe_gmm.{} = bf16[704,{}]{{1,0}} custom-call(...)"
    step = [(gmm.format(1, fe), 0, 0.7 * MS), (gmm.format(2, d), MS, 1.4 * MS),
            (gmm.format(3, fe), 2 * MS, 2.7 * MS),
            (gmm.format(4, d), 3 * MS, 3.4 * MS)]
    pre = [(gmm.format(5, fe), 50 * MS, 53 * MS),
           (gmm.format(6, d), 53 * MS, 54 * MS),
           # a second round through the same layout: the call overflowed
           (gmm.format(5, fe), 54 * MS, 57 * MS),
           (gmm.format(6, d), 57 * MS, 58 * MS)]
    tr = xplane.Trace(
        ops={"/device:TPU:0": step + pre},
        modules={"/device:TPU:0": [("jit__paged_step(1)", 0, 10 * MS),
                                   ("jit__paged_prefill(2)", 49 * MS, 60 * MS)]})
    timings = {"moe_calls": 400, "moe_pairs": 6_400, "moe_rounds": 400,
               "moe_experts_touched": 3_600, "moe_calls_prefill": 10,
               "moe_rounds_prefill": 20, "moe_pairs_prefill": 20_480,
               "moe_experts_touched_prefill": 10 * held}
    ctx = types.SimpleNamespace(trace=tr, config=hf, peaks=PEAKS,
                                facts={"timings": timings})
    # two layers' calls in the step: 2 x least over (0.7 + 0.4) x 2 ms
    assert _read("moe_experts_roofline.flood", ctx) == pytest.approx(
        100 * 2 * (nbytes / 819e9) / 2.2e-3)
    assert 0 < _read("moe_experts_roofline.flood", ctx) < 100
    # ONE layer's call in two rounds: its cost once over both rounds' 8 ms
    pb, pf = costs_moe.experts_cost(hf, 2048.0, float(held))
    assert _read("moe_prefill_experts_roofline.flood", ctx) == pytest.approx(
        100 * max(pb / 819e9, pf / 197e12) / 8e-3)
    # a program from before ``moe_rounds``: a call is one layout
    del timings["moe_rounds_prefill"]
    assert _read("moe_prefill_experts_roofline.flood", ctx) == pytest.approx(
        100 * 2 * max(pb / 819e9, pf / 197e12) / 8e-3)
    dense = harness.load_json("benchmark", "configs", "mistral-7b-v0.3.json")
    ctx.config = dense
    assert _read("moe_experts_roofline.flood", ctx) is None

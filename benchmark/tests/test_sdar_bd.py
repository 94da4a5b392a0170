"""The SDAR configuration's side of the yardstick, on the CPU at a tiny size:
the generator's bits and the load its router gains give, the plain reference
against a second literal transcription in numpy loops (the two-stream mask
against block-by-block forwards), every cost of ``costs_sdar.py`` by hand at
the cell's shapes, each new reader on a synthetic trace, the cell end to end
through ``run.execute`` (sound, and with each control of
``tools/control_sdar.py`` not correct), and the cell's own rows of what
``test_contract.py`` and ``test_traffic.py`` would hold (those two files are a
``benchmark`` PR's to edit)."""

import json
import os

import numpy as np
import pytest

from benchmark import costs_moe, costs_sdar, harness, run, xplane
from benchmark import weights_sdar as WS
from benchmark.reference import sdar_bd as ref
from benchmark.tools import control_sdar

CELL = "sdar.flood-bd"
HF = harness.load_json("benchmark", "configs", "sdar-30b-a3b-chat.json")
BL, MASK = 4, 255
TINY = dict(hidden_size=64, vocab_size=256, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, intermediate_size=128,
            moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
            num_hidden_layers=2, max_position_embeddings=128,
            serving=dict(slots=4, max_len=128, block_len=16, total_blocks=32,
                         diffusion=dict(block_length=BL, denoising_steps=2,
                                        remasking="low_confidence_static",
                                        mask_token_id=MASK)),
            # sound runs read 3e-3 - 6e-3, 0.2 - 0.4 and 0.01 - 0.03 here
            # (bf16 at width 64, logits of the order of 4); the controls'
            # means 0.013 (bf16: the precision served, no fault) and 0.04 -
            # 0.46, their confidence gaps 0.12 - 0.70
            correct=dict(served_mean_gap_limit=0.025,
                         served_max_gap_limit=0.9,
                         served_conf_mean_gap_limit=0.01,
                         served_conf_gap_limit=0.08))
TRAFFIC = dict(requests=40, prompts=[16, 32, 48, 64], budgets=[8, 12, 16],
               lookahead=4)
TINY_HF = {**HF, **TINY}
FAULTS = tuple(c for c in control_sdar.CONTROLS if c != "bf16")


def _run(seed=2**31 + 77, trace=0, **test):
    test = dict(allow_cpu=True, config=TINY, traffic=TRAFFIC, **test)
    return run.execute(["--workload", CELL, "--seed", str(seed),
                        "--seconds", "3", "--trace", str(trace)], test=test)


# -- the generator -----------------------------------------------------------

def _np_params(hf, seed):
    """The program's parameter dict in numpy float64, ``router_gain``
    folded as ``weights_sdar.served`` folds it (float32, rounded to bf16)."""
    import ml_dtypes
    out = {}
    for i, (name, shape) in enumerate(WS.tensor_specs(hf)):
        out[name] = WS.make_tensor_np(seed, i, name, shape)
    for i in range(hf["num_hidden_layers"]):
        gain = out.pop(f"layers.{i}.router_gain").astype(np.float32)
        r = f"layers.{i}.router"
        out[r] = (out[r].astype(np.float32) * gain[None, :]).astype(
            ml_dtypes.bfloat16)
    return {k: v.astype(np.float64) for k, v in out.items()}


def test_generator_bits_are_the_same_in_numpy_and_under_jit():
    import jax
    params = WS.make_params(TINY_HF, 2**31 + 5)
    want = _np_params(TINY_HF, 2**31 + 5)
    assert set(params) == set(want)
    assert not any("router_gain" in k for k in params)
    for name, w in want.items():
        got = np.asarray(jax.device_get(params[name])).astype(np.float64)
        np.testing.assert_array_equal(got, w, err_msg=name)


def test_one_experts_slice_is_the_stacked_tensors_slice():
    import jax
    shape = WS.layer_shapes(TINY_HF)["moe_w_down"]
    base = np.uint32(12345)
    whole = np.asarray(jax.jit(
        lambda b: WS.make_tensor(b, "moe_w_down", shape))(base))
    n = shape[1] * shape[2]
    for e in (0, 3, 7):
        part = np.asarray(jax.jit(lambda b, first: WS.make_tensor(
            b, "moe_w_down", shape[1:], first))(base, np.uint32(e * n)))
        np.testing.assert_array_equal(part.view(np.uint16),
                                      whole[e].view(np.uint16))


def test_the_router_gains_load_the_experts_unevenly():
    """At the published router (2048 -> 128, top-8) with the generator's
    gains, rows of unit RMS load a layer's busiest expert with 2-3.5x the
    mean and its idlest with under half."""
    idx, shapes = WS.layer_indices(HF), WS.layer_shapes(HF)
    h = np.random.default_rng(3).standard_normal((4096, 2048)).astype(
        np.float32)
    ratios = []
    for layer in (0, 3, 5):
        def leaf(name):
            full = f"layers.{layer}.{name}"
            return WS.make_tensor_np(17, idx[full], full,
                                     shapes[name]).astype(np.float32)
        logits = h @ (leaf("router") * leaf("router_gain")[None, :])
        sel = np.argsort(-logits, axis=1)[:, :8]
        load = np.bincount(sel.reshape(-1), minlength=128)
        ratios.append((load.max() / load.mean(), load.min() / load.mean()))
    assert all(2.0 <= hi <= 3.5 and lo < 0.5 for hi, lo in ratios), ratios


# -- the reference against a second transcription ----------------------------

def _np_forward(p, hf, tokens, see, positions):
    """The equations once more, in numpy float64 with loops over rows, heads
    and experts: tokens (L,), see (L, L), positions (L,) -> logits (L, V)."""
    z = WS.sizes(hf)
    nh, nkv, hd, eps = z["nh"], z["nkv"], z["hd"], hf["rms_norm_eps"]

    def norm(y, w):
        return y / np.sqrt((y * y).mean(-1, keepdims=True) + eps) * w

    def rope(t, pos):
        half = hd // 2
        ang = pos * z["theta"] ** (-np.arange(half) / half)
        a, b = t[:half], t[half:]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               b * np.cos(ang) + a * np.sin(ang)])

    x = p["tok_embed"][tokens]
    L = len(tokens)
    for i in range(hf["num_hidden_layers"]):
        w = {k.split(".", 2)[2]: v for k, v in p.items()
             if k.startswith(f"layers.{i}.")}
        h = norm(x, w["attn_norm"])
        q = (h @ w["wq"]).reshape(L, nh, hd)
        k = (h @ w["wk"]).reshape(L, nkv, hd)
        v = (h @ w["wv"]).reshape(L, nkv, hd)
        a = np.zeros((L, nh, hd))
        for t in range(L):
            for hh in range(nh):
                g = hh // (nh // nkv)
                qt = rope(norm(q[t, hh], w["q_norm"]), positions[t])
                js = np.nonzero(see[t])[0]
                sc = np.asarray([qt @ rope(norm(k[j, g], w["k_norm"]),
                                           positions[j]) for j in js])
                pr = np.exp(sc / np.sqrt(hd) - (sc / np.sqrt(hd)).max())
                a[t, hh] = (pr / pr.sum()) @ v[js, g]
        x = x + a.reshape(L, -1) @ w["wo"]
        h = norm(x, w["mlp_norm"])
        out = np.zeros_like(h)
        for t in range(L):
            lg = h[t] @ w["router"]
            pr = np.exp(lg - lg.max())
            pr /= pr.sum()
            sel = np.argsort(-pr, kind="stable")[:z["k"]]
            for e in sel:
                gate = h[t] @ w["moe_w_gate"][e]
                f = (gate / (1 + np.exp(-gate))) * (h[t] @ w["moe_w_up"][e])
                out[t] += pr[e] / pr[sel].sum() * (f @ w["moe_w_down"][e])
        x = x + out
    return norm(x, p["final_norm"]) @ p["lm_head"]


def test_two_stream_replay_is_block_by_block_forwards():
    """The reference's one forward of [noisy | clean] under its mask gives,
    at every (block, step) of a served request, what the second
    transcription gives for that step's own sequence — the earlier blocks
    clean, the block as its input held it — forwarded alone under the plain
    block-causal mask."""
    seed = 11
    prompt = np.random.default_rng(1).integers(0, MASK, 10).tolist()
    toks, steps = ref.generate(TINY_HF, seed, prompt, 10, BL, MASK, steps=2)
    sample = [{"prompt": prompt, "tokens": toks, "steps": steps}]
    p = _np_params(TINY_HF, seed)
    seq = np.asarray(prompt + toks)
    cstep = np.asarray([-1] * len(prompt) + steps)
    for step in (0, 1):
        t2, see, pos, at, valid = ref.replay_inputs(sample, BL, MASK, step)
        got = np.asarray(ref.logits(TINY_HF, seed, t2, see, pos, at))[0]
        for start in range(8, 20, BL):
            rows = seq[:start + BL].copy()
            block = slice(start, start + BL)
            rows[block] = np.where(cstep[block] < step, seq[block], MASK)
            L = start + BL
            want = _np_forward(p, TINY_HF, rows, ref.block_causal(L, BL),
                               np.arange(L))
            for r in range(max(start, 10), start + BL):
                np.testing.assert_allclose(got[r - 10], want[r], atol=2e-4,
                                           rtol=0, err_msg=f"{step} {r}")


def test_reference_padding_is_inert_and_int8_is_another_answer():
    rng = np.random.default_rng(4)
    toks = rng.integers(0, 256, (2, 24)).astype(np.int32)
    at = np.broadcast_to(np.arange(16), (2, 16)).copy()
    see = np.broadcast_to(ref.block_causal(24, BL), (2, 24, 24))
    pos = np.broadcast_to(np.arange(24), (2, 24))
    full = np.asarray(ref.logits(TINY_HF, 9, toks, see, pos, at))
    assert full.shape == (2, 16, 256) and np.isfinite(full).all()
    padded = toks.copy()
    padded[:, 16:] = 0          # block-causal: later blocks are inert
    np.testing.assert_array_equal(
        np.asarray(ref.logits(TINY_HF, 9, padded, see, pos, at)), full)
    low = np.asarray(ref.logits(TINY_HF, 9, toks, see, pos, at, low="int8"))
    assert np.abs(low - full).max() > 1e-3 * np.abs(full).max()


def test_commit_rules_by_hand():
    assert [ref.commit_count(4, 2, s) for s in (0, 1)] == [2, 2]
    assert [ref.commit_count(3, 2, s) for s in (0, 1)] == [2, 1]
    assert [ref.commit_count(4, 3, s) for s in (0, 1, 2)] == [2, 1, 1]
    assert ref.commit_count(4, 0, 3) == 1
    conf = np.asarray([0.2, 0.5, 0.5, 0.9])
    masked = np.asarray([True, True, True, False])
    assert ref.select(conf, masked, 1, np.inf).tolist() == [0, 1, 0, 0]
    assert ref.select(conf, masked, 2, np.inf).tolist() == [0, 1, 1, 0]
    assert ref.select(conf, masked, 1, 0.1).tolist() == [1, 1, 1, 0]
    see, pos = ref.two_stream(8, 4)
    assert see[5, :8].tolist() == [0, 0, 0, 0, 1, 1, 1, 1]      # noisy: own
    assert see[5, 8:].tolist() == [1, 1, 1, 1, 0, 0, 0, 0]      # ... clean before
    assert see[9, :8].sum() == 0 and see[9, 8:].tolist() == [1] * 4 + [0] * 4
    assert pos.tolist() == list(range(8)) * 2


# -- the cost functions ------------------------------------------------------

def test_parameter_count_is_the_sum_over_the_tensor_list():
    total = sum(int(np.prod(s, dtype=np.int64))
                for n, s in WS.tensor_specs(HF) if "router_gain" not in n)
    p = costs_sdar.param_count(HF)
    assert p["total"] == total
    assert p["attn"] == 2 * 2048 * 4096 + 2 * 2048 * 512 == 18_874_368
    assert p["expert"] == 3 * 2048 * 768 == 4_718_592
    assert p["layer"] == 18_874_368 + 2048 * 128 + 2 * 2048 + 2 * 128 \
        + 128 * 4_718_592 == 623_120_640
    assert 8.11 < total * 2 / 2**30 < 8.13              # GiB in bf16
    full = 48 * p["layer"] + p["embed"] + p["head"] + 2048
    assert 30.5e9 < full < 30.6e9                       # the model's name


def test_costs_by_hand_at_the_cells_shapes():
    assert costs_sdar.kv_bytes_per_token(HF) == 6 * 2 * 4 * 128 * 2 == 12288
    live, slots, rows = 86_000.0, 128, 4
    nbytes, flops = costs_sdar.attn_cost(HF, slots, live, rows)
    assert nbytes == 2 * 4 * 128 * 2 * live + 2 * 128 * 4 * 32 * 128 * 2
    assert flops == 4.0 * 4 * 32 * 128 * live
    # 32 query rows a KV head: 16 operations a byte, still under the ridge
    assert nbytes / 819e9 > flops / 197e12
    p = costs_sdar.param_count(HF)
    touched = 700.0
    eb, ef = costs_moe.experts_cost(HF, 128 * 4 * 8 * 6, touched)
    assert eb == (700 * 4_718_592 + 24_576 * (2 * 2048 + 2 * 768)) * 2
    step = costs_sdar.step_bytes(HF, slots, live, touched, rows)
    assert step == pytest.approx(
        (6 * p["layer_rest"] + p["head"] + 2048 + 512 * 2048) * 2 + eb
        + live * 12288)
    sf = costs_sdar.step_flops(HF, slots, live, rows)
    assert sf == pytest.approx(
        2.0 * 512 * (6 * (p["attn"] + p["router"]) + p["head"]) + ef
        + 6 * flops)
    # the bytes bound a forward: 8-9 GB against 0.7 TFLOP
    assert 8e9 < step < 9.5e9 and 0.6e12 < sf < 0.8e12
    assert step / 819e9 > sf / 197e12
    pf = costs_sdar.prefill_flops(HF, 1024)
    assert pf == pytest.approx(
        2.0 * (1024 * 6 * (p["attn"] + p["router"]) + p["head"])
        + 2.0 * 1024 * 8 * 6 * 4_718_592
        + 4.0 * 6 * 32 * 128 * 1024 * 1025 / 2)


# -- the cell, end to end ----------------------------------------------------

def test_cell_end_to_end_is_correct_and_every_fault_is_not():
    """One window of the cell at a tiny size: correct, every budget
    returned, 4 / 3 tokens a forward that did anything — and, on the very
    sample compared, each control's fault fails a limit (``bf16``, the
    precision the cell serves in, is no fault and is reported)."""
    got = {}

    def after(ctx, sample):
        for c in control_sdar.CONTROLS:
            got[c] = control_sdar.control_gaps(
                ctx.config, ctx.seed, sample, ctx.config["reference"], MASK,
                c)

    out, ctx = _run(after_window=after)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"tok_s", "setup_s"}
    assert set(out["checks"]) == {
        "served.mean_logit_gap", "served.max_logit_gap",
        "served.mean_confidence_gap", "served.max_confidence_gap",
        "served.requests_with_wrong_token_count"}
    assert ctx.facts["compiles_in_window"] == 0
    t = ctx.facts["timings"]
    # two tokens a denoising forward, and a block's third forward writes it
    # (the window's two ends cut a block of each slot)
    assert t["bd_tokens"] == 2 * t["bd_forwards_denoise"]
    assert abs(t["bd_forwards_denoise"] - 2 * t["bd_forwards_write"]) <= 8
    assert t["bd_rows"] == BL * (t["bd_forwards_denoise"]
                                 + t["bd_forwards_write"])
    assert t["moe_pairs"] == t["moe_pairs_routed"] == t["bd_rows"] * 2 * 2
    lim = TINY["correct"]
    for c in FAULTS:
        g = got[c]
        assert (g["mean_gap"] > lim["served_mean_gap_limit"]
                or g["max_gap"] > lim["served_max_gap_limit"]
                or g["conf_mean_gap"] > lim["served_conf_mean_gap_limit"]
                or g["conf_gap"] > lim["served_conf_gap_limit"]), (c, g)
    # the choice by confidence is the mean confidence gap's to tell
    assert got["lowest_first"]["conf_mean_gap"] \
        > 10 * lim["served_conf_mean_gap_limit"]


def test_traced_run_reports_the_cells_per_layer_metrics():
    """On the CPU the trace has no device plane: the device readers return
    nothing and the line leaves them out; the counters are there."""
    out, _ = _run(trace=1)
    assert out["correct"] is True
    assert {"admit_share.flood", "prefill_share.flood",
            "compiles_in_window.flood", "attn_grid_steps.flood",
            "moe_local_pair_share.flood", "moe_rounds_per_call.flood",
            "bd_tokens_per_forward.sdar", "bd_commit_forward_share.sdar"} \
        <= set(out["metrics"])
    assert "bd_step_roofline.sdar" not in out["metrics"]
    assert 0.9 < out["metrics"]["bd_tokens_per_forward.sdar"]["value"] \
        <= 4 / 3
    assert 20 < out["metrics"]["bd_commit_forward_share.sdar"]["value"] \
        <= 100 / 3
    assert out["metrics"]["moe_local_pair_share.flood"]["value"] == 100.0


# -- the new readers ---------------------------------------------------------

def _ctx(trace, config=HF, timings=None):
    import types
    peaks = harness.load_json("benchmark", "peaks.json")["TPU v5 lite"]
    return types.SimpleNamespace(
        trace=trace, config=config, peaks=peaks, workload=CELL,
        traffic=harness.load_json("benchmark", "traffic", "flood-bd.json"),
        facts={"slots": 128, "live_tokens": 86_000.0, "timings": timings})


#: a window of 100 forwards and 20 admissions of 4: 6 expert layers a call
TIMINGS = {"steps": 100, "moe_calls": 600, "moe_experts_touched": 70_000,
           "bd_tokens": 16_000, "bd_forwards_denoise": 8_000,
           "bd_forwards_write": 4_000, "bd_forwards_hold": 800,
           "prefill_calls": 20, "admits": 80}


def _synthetic_trace():
    ms = 1_000_000
    attn = "%strom_paged_attn.{} = bf16[128,4,32,128]{{3,2,1,0}} custom-call()"
    step = [(attn.format(1), 1 * ms, 1.4 * ms),
            (attn.format(2), 3 * ms, 3.4 * ms),
            ("%fusion.9 = f32[128,4]{1,0} fusion(bf16[128,4,32,128]{3,2,1,0} "
             "%strom_paged_attn.2)", 4 * ms, 5 * ms)]
    plane = "/device:TPU:0"
    return xplane.Trace(
        ops={plane: step},
        modules={plane: [("jit__paged_step(1)", 0, 16 * ms),
                         ("jit__paged_step(1)", 20 * ms, 36 * ms),
                         ("jit__paged_prefill(2)", 40 * ms, 100 * ms),
                         ("jit_other(3)", 110 * ms, 111 * ms)]})


def test_new_readers_on_a_synthetic_trace():
    ctx = _ctx(_synthetic_trace(), timings=TIMINGS)
    read = lambda name: harness.plugin("layer_metrics", name).read(ctx)  # noqa
    assert read("bd_tokens_per_forward.sdar") == pytest.approx(
        16_000 / 12_800)
    assert read("bd_commit_forward_share.sdar") == pytest.approx(
        100 * 4_000 / 12_800)
    nbytes, flops = costs_sdar.attn_cost(HF, 128, 86_000.0, 4)
    assert read("block_attn_roofline.sdar") == pytest.approx(
        100 * 2 * (nbytes / 819e9) / 0.8e-3)
    step = costs_sdar.step_bytes(HF, 128, 86_000.0, 700.0, 4)
    assert read("bd_step_roofline.sdar") == pytest.approx(
        100 * (step / 819e9) / 16e-3)
    ops = np.mean([costs_sdar.prefill_flops(HF, n)
                   for n in (128, 256, 512, 1024)])
    assert read("bd_prefill_mfu.sdar") == pytest.approx(
        100 * ops * 4 / 60e-3 / 197e12)
    for name in ("block_attn_roofline.sdar", "bd_step_roofline.sdar",
                 "bd_prefill_mfu.sdar"):
        assert 0 < read(name) < 100, name


@pytest.mark.parametrize("name", [
    "bd_tokens_per_forward.sdar", "bd_commit_forward_share.sdar",
    "bd_step_roofline.sdar", "bd_prefill_mfu.sdar",
    "block_attn_roofline.sdar", "bd_select_share.sdar"])
def test_new_readers_find_nothing_where_there_is_nothing(name):
    """No trace, a trace without the kernel or the scopes, a program without
    the counters (the parent's), another configuration: None, never an
    exception."""
    reader = harness.plugin("layer_metrics", name)
    dense = harness.load_json("benchmark", "configs", "mistral-7b-v0.3.json")
    empty = xplane.Trace(
        ops={"/device:TPU:0": [("%fusion.1 = bf16[8]{0} fusion()", 0, 9)]},
        modules={"/device:TPU:0": [("jit_other(1)", 0, 9)]})
    old = {"steps": 100, "admit_s": 1.0}            # the parent's timings
    for ctx in (_ctx(None), _ctx(empty), _ctx(empty, dense),
                _ctx(empty, timings=old), _ctx(empty, dense, old),
                _ctx(None, timings=old)):
        assert reader.read(ctx) is None


# -- the cell's rows of the contract and of the traffic ----------------------

def test_the_cells_entries_in_the_contract():
    bench = harness.load_json("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("sdar-30b-a3b-chat", "flood-bd", 1)
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"]
                 if c["name"] == "sdar-30b-a3b-chat")
    assert entry["source"] == HF["source"]
    assert sorted(entry["reduced"]) == sorted(HF["reduced"]) \
        == ["max_position_embeddings", "num_hidden_layers"]
    e2e, per = run.cell_metrics(bench, CELL)
    assert {m["name"] for m in e2e} == {"tok_s", "setup_s"}
    names = {m["name"] for m in per}
    tagged = {m["name"] for m in bench["per_layer"]
              if m["name"].endswith(".sdar")}
    assert len(tagged) == 6 and tagged <= names
    assert all(m["moves"] == "tok_s" for m in per)
    # (at least: a later PR's entries follow these)
    assert len(bench["configs"]) >= 9 and len(bench["workloads"]) >= 11 \
        and len(bench["per_layer"]) >= 90
    for m in per:                       # every entry has a reader by name
        assert hasattr(harness.plugin("layer_metrics", m["name"]), "read")
    layers = {m["layer"] for m in bench["per_layer"]
              if not m["name"].endswith(".sdar")}
    assert {m["layer"] for m in per} <= layers      # no new layer names


def test_config_and_traffic_hold_the_cells_parameters():
    sv = HF["serving"]
    assert (sv["slots"], sv["max_len"], sv["block_len"], sv["total_blocks"]) \
        == (128, 1536, 128, 1536)
    assert sv["diffusion"] == {"block_length": 4, "denoising_steps": 2,
                               "remasking": "low_confidence_static"}
    assert len(HF["assumed"]) >= 5 and "eight-stage" in HF["deployment"]
    tr = harness.load_json("benchmark", "traffic", "flood-bd.json")
    assert (tr["kind"], tr["runner"], tr["lookahead"], tr["requests"]) \
        == ("closed_queue", "serve_bd", 8, 4096)
    assert tr["prompts"] == [128, 256, 512, 1024]
    assert tr["budgets"] == [256, 384, 512]
    assert max(tr["prompts"]) + max(tr["budgets"]) == sv["max_len"]


def test_traffic_is_a_fixed_count_and_a_fixed_multiset():
    """Every seed offers the same number of requests, the same budget at
    the same place in the queue, and in every 4 consecutive requests each
    prompt length once: a window reaches the same multiset whatever the
    seed."""
    tr = harness.load_json("benchmark", "traffic", "flood-bd.json")
    kind = harness.plugin("traffic.kinds", tr["kind"])
    a = kind.schedule(tr, 1, 45.0)["requests"]
    b = kind.schedule(tr, 2**31 + 9, 45.0)["requests"]
    assert len(a) == len(b) == 4096
    assert [r["budget"] for r in a] == [r["budget"] for r in b] \
        == [tr["budgets"][i % 3] for i in range(4096)]
    assert [r["prompt_len"] for r in a] != [r["prompt_len"] for r in b]
    for reqs in (a, b):
        for i in range(0, 4096, 4):
            assert sorted(r["prompt_len"] for r in reqs[i:i + 4]) \
                == tr["prompts"]
    assert all(r["due"] is None and r["sampled"] for r in a)


def test_config_file_holds_the_catalog_rows_numbers():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    assert HF["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in HF["reduced"]:
            assert HF[key] == value, key
    assert HF["published"] == {k: row["config"][k] for k in HF["reduced"]}

"""The Qwen3-Next configuration's side of the yardstick, on the CPU at a tiny
size: the generator's bits and layout, the cost functions against the tensor
list and hand counts at the cell's shapes, the plain reference against a
second, literal transcription of the equations, its padding and its
controls, the new cell end to end through ``run.execute`` (sound; with the
state or the conv tail not carried from prefill into decode, which must come
out as not correct), the five readers on a synthetic trace, and the file
against the catalog."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import costs_gdn, harness, run, xplane
from benchmark import weights_gdn as WG
from benchmark.reference import qwen3_next as ref
from benchmark.runners import serve
from benchmark.tools import control_gdn

HF = harness.load_json("benchmark", "configs", "qwen3-next-80b-a3b.json")
TINY = dict(hidden_size=64, vocab_size=256, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, intermediate_size=128,
            linear_key_head_dim=16, linear_value_head_dim=16,
            linear_num_key_heads=2, linear_num_value_heads=4,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            num_experts=4, expert_share={"routed": 16, "offset": 4,
                                         "chips": 4},
            num_experts_per_tok=3, num_hidden_layers=4, rope_theta=10000,
            max_position_embeddings=256,
            serving=dict(slots=4, max_len=256, block_len=16, total_blocks=64),
            # sound runs read ~0.01 and ~0.5 here (bf16 at width 64, a
            # routing flip or two in a hundred tokens); with the state not
            # carried the mean is over 0.5
            correct=dict(served_mean_gap_limit=0.15,
                         served_max_gap_limit=2.0))
TRAFFIC = dict(requests=40, prompts=[16, 48, 80, 112], budgets=[24, 40],
               lookahead=4)
TINY_HF = {**HF, **TINY}


def _run(seed=2**31 + 77, trace=0, **test):
    test = dict(allow_cpu=True, config=TINY, traffic=TRAFFIC, **test)
    return run.execute(["--workload", "q3n.flood4k", "--seed", str(seed),
                        "--seconds", "3", "--trace", str(trace)], test=test)


# -- the generator -----------------------------------------------------------

def test_generator_bits_are_the_same_in_numpy_and_under_jit():
    import jax
    specs, idx = WG.tensor_specs(TINY_HF), WG.layer_indices(TINY_HF)
    bs = WG.bases(TINY_HF, 5)
    for name in ("tok_embed", "layers.0.gdn_in", "layers.1.gdn_A_log",
                 "layers.2.gdn_conv_w", "layers.3.wq", "layers.3.q_norm",
                 "layers.0.moe_w_down", "layers.2.shared_gate"):
        shape = dict(specs)[name]
        want = WG.make_tensor_np(5, idx[name], name, shape)
        got = jax.jit(lambda b, n=name, s=shape: WG.make_tensor(b, n, s))(
            bs[idx[name]])
        np.testing.assert_array_equal(np.asarray(got).view(np.uint16),
                                      want.view(np.uint16))
    # a zero-centred norm is drawn around 0 and served as 1 + w, float32;
    # the gated norm is drawn around 1 and served as drawn
    params = WG.make_params(TINY_HF, 5)
    w = WG.make_tensor_np(5, idx["layers.3.q_norm"], "q_norm", (32,))
    assert abs(float(w.astype(np.float32).mean())) < 0.1
    np.testing.assert_array_equal(np.asarray(params["layers.3.q_norm"]),
                                  1.0 + w.astype(np.float32))
    assert params["layers.3.q_norm"].dtype == np.float32
    assert abs(float(np.asarray(params["layers.0.gdn_norm"],
                                np.float32).mean()) - 1) < 0.1
    # a token's log-decay spans heads that forget and heads that keep
    a = np.asarray(params["layers.0.gdn_A_log"], np.float32)
    dt = np.asarray(params["layers.0.gdn_dt_bias"], np.float32)
    assert a.std() > 0.3 and dt.mean() < -2


def test_the_layout_is_the_programs():
    import jax
    from nvme_strom_tpu.models import transformer as tr
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    for hf in (TINY_HF, HF):
        cfg = config_from_hf(hf)
        want = jax.eval_shape(lambda: tr.init_params(jax.random.key(0), cfg))
        got = dict(WG.tensor_specs(hf))
        assert set(got) == set(want)
        assert all(tuple(want[k].shape) == tuple(got[k]) for k in got)
    assert [WG.layer_kind(HF, i) for i in range(16)] == [
        "linear", "linear", "linear", "full"] * 4


# -- the costs, by hand at the cell's shapes ---------------------------------

def test_parameter_count_is_the_sum_over_the_tensor_list():
    p = costs_gdn.param_count(HF)
    total = sum(int(np.prod(s)) for _, s in WG.tensor_specs(HF))
    assert p["total"] == total
    assert (p["n_linear"], p["n_full"]) == (12, 4)
    # the issue's arithmetic: 33.72 M, 27.26 M, 3.146 M an expert, 2.27 G
    assert p["linear"] == 2048 * 12288 + 2048 * 64 + 4 * 8192 + 64 + 128 \
        + 4096 * 2048 + 2048 == 33_720_512
    assert p["full"] == 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 512 \
        + 2048 == 27_265_536
    assert p["expert"] == 3 * 2048 * 512 == 3_145_728
    assert p["expert_layer_rest"] == 2048 * 512 + 3 * 2048 * 512 + 2048 \
        + 2048
    assert round(total * 2 / 2**30, 2) == 4.23


def test_state_cache_and_kernel_costs_by_hand():
    assert costs_gdn.state_bytes_per_slot(HF) == 12 * (
        32 * 128 * 128 * 4 + 3 * 8192 * 2) == 12 * (2 * 2**20 + 48 * 2**10)
    assert costs_gdn.kv_bytes_per_token(HF) == 4 * 2 * 2 * 256 * 2 == 8192
    nbytes, flops = costs_gdn.update_cost(HF, 128)
    state = 128 * 32 * 128 * 128 * 4
    assert nbytes == 2 * state + 128 * 32 * 6 * 128 * 4
    assert flops == 6.0 * 128 * 32 * 128 * 128
    # memory-bound by two orders: 0.75 operations a byte against a ridge of 240
    assert flops / nbytes < 1
    nbytes, flops = costs_gdn.scan_cost(HF, 2, 3000.0)
    assert nbytes == 3000 * ((2 * 2048 + 2 * 4096) * 2 + 2 * 32 * 4) \
        + 2 * 2 * 32 * 128 * 128 * 4
    assert flops == 6.0 * 3000 * 32 * 128 * 128


def test_decode_step_bytes_follow_slots_touched_experts_and_live_rows():
    p = costs_gdn.param_count(HF)
    base = costs_gdn.decode_step_bytes(HF, 128, 0.0, 0.0)
    assert base == (p["outside_experts"] + 128 * 2048) * 2 \
        + 2 * 128 * costs_gdn.state_bytes_per_slot(HF)
    more = costs_gdn.decode_step_bytes(HF, 128, 1000.0, 10.0)
    assert more - base == 10 * 3_145_728 * 2 + 1000 * 8192
    # the issue's step: 13.2 GB, half of them the state pool's
    step = costs_gdn.decode_step_bytes(HF, 128, 128 * 2300.0, 16 * 29.0)
    state = 2 * 128 * costs_gdn.state_bytes_per_slot(HF)
    assert 13.0e9 < step < 13.4e9 and 0.48 < state / step < 0.52
    flops = costs_gdn.decode_step_flops(HF, 128, 128 * 2300.0, 1280.0)
    assert flops == 2.0 * (128 * p["outside_experts"] + 1280 * 3_145_728) \
        + 12 * 6.0 * 128 * 32 * 128 * 128 + 4.0 * 4 * 16 * 256 * 128 * 2300


def test_prefill_flops_count_the_recurrence_and_the_causal_half_once():
    p = costs_gdn.param_count(HF)
    got = costs_gdn.prefill_flops(HF, 1024, 640.0)
    mats = 2.0 * (1024 * (p["outside_experts"] - p["head"]) + p["head"]
                  + 640 * p["expert"])
    attn = 4.0 * 4 * 16 * 256 * 1024 * 1025 / 2
    scan = 12 * 6.0 * 1024 * 32 * 128 * 128
    assert got == mats + attn + scan
    # ~1.3 GFLOP a row at the cell's mean prompt
    assert 1.2e9 < costs_gdn.prefill_flops(HF, 1920, 1920 * 10.0) / 1920 \
        < 1.5e9


# -- the reference -----------------------------------------------------------

def _literal(hf, seed, tokens):
    """A second transcription of the equations, as literal as numpy allows:
    one sequence, python loops over layers, rows, heads and experts, float64,
    every weight drawn with the generator's numpy definition."""
    z, idx = WG.sizes(hf), WG.layer_indices(hf)
    shapes = {**WG.layer_shapes(hf), **WG.top_shapes(hf)}
    eps = hf["rms_norm_eps"]

    def w(name):
        leaf = name.rsplit(".", 1)[-1]
        return WG.make_tensor_np(seed, idx[name], leaf, shapes[leaf]).astype(
            np.float64)

    def norm(x, g):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1 + g)

    def silu(a):
        return a / (1 + np.exp(-a))

    def sigmoid(a):
        return 1 / (1 + np.exp(-a))

    x = w("tok_embed")[np.asarray(tokens)]
    T = len(tokens)
    for i in range(hf["num_hidden_layers"]):
        L = f"layers.{i}."
        h = norm(x, w(L + "attn_norm"))
        if WG.layer_kind(hf, i) == "linear":
            mixed, ba = h @ w(L + "gdn_in"), h @ w(L + "gdn_ba")
            u, gate = mixed[:, :z["conv"]], mixed[:, z["conv"]:]
            taps = w(L + "gdn_conv_w")
            conv = np.zeros_like(u)
            for t in range(T):
                for j in range(z["K"]):
                    if t - 3 + j >= 0:
                        conv[t] += taps[j] * u[t - 3 + j]
            u = silu(conv)
            beta = sigmoid(ba[:, :z["Hv"]])
            alpha = np.exp(-np.exp(w(L + "gdn_A_log")) * np.log1p(
                np.exp(ba[:, z["Hv"]:] + w(L + "gdn_dt_bias"))))
            y = np.zeros((T, z["Hv"], z["dv"]))
            for j in range(z["Hv"]):
                kh = j // (z["Hv"] // z["Hk"])
                S = np.zeros((z["dk"], z["dv"]))
                for t in range(T):
                    q = u[t, kh * z["dk"]:(kh + 1) * z["dk"]]
                    k = u[t, z["key"] + kh * z["dk"]:
                          z["key"] + (kh + 1) * z["dk"]]
                    v = u[t, 2 * z["key"] + j * z["dv"]:
                          2 * z["key"] + (j + 1) * z["dv"]]
                    q = q / np.sqrt((q * q).sum() + 1e-6) / np.sqrt(z["dk"])
                    k = k / np.sqrt((k * k).sum() + 1e-6)
                    S = alpha[t, j] * S
                    S = S + np.outer(k, beta[t, j] * (v - S.T @ k))
                    o = S.T @ q
                    g = gate[t, j * z["dv"]:(j + 1) * z["dv"]]
                    y[t, j] = (w(L + "gdn_norm") * o
                               / np.sqrt((o * o).mean() + eps) * silu(g))
            x = x + y.reshape(T, -1) @ w(L + "gdn_out")
        else:
            nh, nkv, hd, r = z["nh"], z["nkv"], z["hd"], z["rotary"]
            qg = (h @ w(L + "wq")).reshape(T, nh, 2 * hd)
            q, gate = qg[..., :hd], qg[..., hd:]
            k = (h @ w(L + "wk")).reshape(T, nkv, hd)
            v = (h @ w(L + "wv")).reshape(T, nkv, hd)
            q, k = norm(q, w(L + "q_norm")), norm(k, w(L + "k_norm"))

            def turn(t, pos):
                out = t.copy()
                for j in range(r // 2):
                    ang = pos * z["theta"] ** (-2 * j / r)
                    a, b = t[..., j], t[..., r // 2 + j]
                    out[..., j] = a * np.cos(ang) - b * np.sin(ang)
                    out[..., r // 2 + j] = b * np.cos(ang) + a * np.sin(ang)
                return out
            q = np.stack([turn(q[t], t) for t in range(T)])
            k = np.stack([turn(k[t], t) for t in range(T)])
            a = np.zeros((T, nh, hd))
            for hh in range(nh):
                kv = hh // (nh // nkv)
                for t in range(T):
                    s = k[:t + 1, kv] @ q[t, hh] / np.sqrt(hd)
                    p = np.exp(s - s.max())
                    a[t, hh] = (p / p.sum()) @ v[:t + 1, kv]
            x = x + (a * sigmoid(gate)).reshape(T, -1) @ w(L + "wo")
        h = norm(x, w(L + "mlp_norm"))
        p = np.exp(h @ w(L + "router"))
        p = p / p.sum(-1, keepdims=True)
        w1, w3, w2 = (w(L + n) for n in ("moe_w_gate", "moe_w_up",
                                         "moe_w_down"))
        f = np.zeros_like(x)
        for t in range(T):
            sel = np.argsort(-p[t])[:z["k"]]
            for e in sel:
                if z["offset"] <= e < z["offset"] + z["held"]:
                    le = e - z["offset"]
                    f[t] += p[t, e] / p[t, sel].sum() * (
                        (silu(h[t] @ w1[le]) * (h[t] @ w3[le])) @ w2[le])
        shared = (silu(h @ w(L + "shared_w_gate")) * (
            h @ w(L + "shared_w_up"))) @ w(L + "shared_w_down")
        x = x + f + sigmoid(h @ w(L + "shared_gate")) * shared
    return norm(x, w("final_norm")) @ w("lm_head")


def test_reference_is_the_equations_transcribed_a_second_time():
    toks = np.random.default_rng(3).integers(0, TINY["vocab_size"], 21)
    want = _literal(TINY_HF, 11, toks)
    got = np.asarray(ref.logits_at(TINY_HF, 11, toks[None],
                                   np.arange(21)[None]))[0]
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_reference_padding_is_inert_and_each_control_is_another_answer():
    rng = np.random.default_rng(4)
    toks = rng.integers(0, TINY["vocab_size"], (2, 40))
    at = np.asarray([[10, 30], [5, 39]])
    sound = np.asarray(ref.logits_at(TINY_HF, 11, toks, at))
    padded = np.concatenate([toks, rng.integers(0, 256, (2, 24))], 1)
    np.testing.assert_allclose(
        np.asarray(ref.logits_at(TINY_HF, 11, padded, at)), sound, atol=1e-5)
    for low in control_gdn.CONTROLS:
        other = np.asarray(ref.logits_at(TINY_HF, 11, toks, at, low=low))
        assert np.isfinite(other).all(), low
        assert np.abs(other - sound).max() > 1e-2, low


# -- the cell, end to end ----------------------------------------------------

def test_cell_end_to_end_is_correct():
    out, ctx = _run()
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"tok_s", "setup_s"}
    assert ctx.facts["compiles_in_window"] == 0
    t = ctx.facts["timings"]
    assert t["scan_tokens"] == t["prompt_tokens"] > 0
    assert t["moe_pairs_routed_prefill"] == t["prompt_tokens"] * 3 * 4
    assert 0 < t["moe_pairs"] < t["moe_pairs_routed"]


@pytest.mark.parametrize("key", ["s", "conv"])
def test_state_or_tail_not_carried_into_decode_is_not_correct(key):
    out, _ = _run(server_built=lambda srv: control_gdn.drop_rows(srv, key))
    assert out["correct"] is False
    assert out["failed"] == 0               # every budget still returned


def test_a_reference_side_control_lies_outside_the_limits():
    """The sample a sound run compares, with the token the reference WITHOUT
    the delta rule's correction puts first in the served token's place:
    outside the test-size limits, as every control is on the chip."""
    got = {}

    def after(ctx, sample):
        got["gaps"] = serve.control_gaps(ctx.config, ctx.seed, sample,
                                         ctx.config["reference"],
                                         low="no_correction")
    out, _ = _run(after_window=after)
    assert out["correct"] is True
    lim = TINY["correct"]
    assert (got["gaps"]["mean_gap"] > lim["served_mean_gap_limit"]
            or got["gaps"]["max_gap"] > lim["served_max_gap_limit"])


def test_traced_run_reports_the_counters_and_leaves_the_device_out():
    out, _ = _run(trace=1)
    assert out["correct"] is True
    assert {"admit_share.flood", "prefill_share.flood",
            "prefill_pad_share.flood", "compiles_in_window.flood",
            "prefill_batch_mean.flood", "moe_local_pair_share.flood",
            "attn_grid_steps.flood", "moe_rounds_per_call.flood"} <= set(
                out["metrics"])
    # no device plane on the CPU: nothing a kernel's time would be read from
    assert not {m for m in out["metrics"] if m.endswith(".q3n")}


# -- the new readers ---------------------------------------------------------

SLOTS, LIVE = 128, 300_000.0


def _ctx(trace, config=HF, timings=None):
    peaks = harness.load_json("benchmark", "peaks.json")["TPU v5 lite"]
    return types.SimpleNamespace(
        trace=trace, config=config, peaks=peaks,
        traffic={"prompts": [512, 1024, 2048, 4096]},
        facts={"slots": SLOTS, "live_tokens": LIVE, "timings": timings})


#: a window of 100 steps and 6 admissions of one prompt each, 16 layers
TIMINGS = {"steps": 100, "moe_calls": 1600, "moe_pairs": 128_000,
           "moe_pairs_routed": 2_048_000, "moe_experts_touched": 46_400,
           "prompt_tokens": 11_520, "prefill_tokens": 12_288,
           "scan_tokens": 11_520, "moe_pairs_prefill": 115_200,
           "prefill_calls": 6, "admits": 6}


def _synthetic_trace():
    ms = 1_000_000
    upd = "%strom_gdn_update.{} = (f32[128,2,16,128]{{3,2,1,0}}, " \
          "f32[129,32,128,128]{{3,2,1,0}}) custom-call(.)"
    scan = "%strom_gdn_scan.{} = (bf16[{},32,{},64,128]{{4,3,2,1,0}}, " \
           "f32[{},32,128,128]{{3,2,1,0}}) custom-call(.)"
    step = [("%fusion.1 = bf16[128,2048]{1,0} fusion(...)", 0, ms),
            (upd.format(1), 1 * ms, 2 * ms),
            # the consumer of a kernel's result names it among its operands
            ("%fusion.2 = f32[128,4096]{1,0} fusion(f32[128,2,16,128] "
             "%strom_gdn_update.1, ...)", 2 * ms, 2.5 * ms),
            (upd.format(2), 3 * ms, 4.5 * ms),
            ("%strom_paged_attn.3 = bf16[128,2,8,256]{3,2,1,0} "
             "custom-call(.)", 5 * ms, 6 * ms)]
    pre = [("%fusion.9 = bf16[4096,2048]{1,0} fusion(...)", 50 * ms, 90 * ms),
           (scan.format(1, 1, 64, 1), 90 * ms, 100 * ms),
           ("%fusion.10 = bf16[4096,4096]{1,0} fusion(bf16[1,32,64,64,128] "
            "%strom_gdn_scan.1, ...)", 100 * ms, 110 * ms),
           (scan.format(2, 1, 32, 1), 260 * ms, 266 * ms)]
    plane = "/device:TPU:0"
    return xplane.Trace(
        ops={plane: step + pre},
        # (a step of 25 ms: its 13 GB take 16 at the chip's bandwidth)
        modules={plane: [("jit__paged_step(1)", 0, 25 * ms),
                         ("jit__paged_prefill(2)", 50 * ms, 250 * ms),
                         ("jit__paged_prefill(4)", 260 * ms, 300 * ms),
                         ("jit_other(3)", 310 * ms, 311 * ms)]})


NEW = ("gdn_update_roofline.q3n", "gdn_scan_roofline.q3n",
       "gdn_step_share.q3n", "gdn_step_roofline.q3n", "gdn_prefill_mfu.q3n")


def test_new_readers_on_a_synthetic_trace():
    ctx = _ctx(_synthetic_trace(), timings=TIMINGS)
    read = lambda name: harness.plugin("layer_metrics", name).read(ctx)  # noqa
    nbytes, _ = costs_gdn.update_cost(HF, SLOTS)
    # two calls in the step (the fusion that names one is no call): 2 x
    # least over 1 + 1.5 ms
    assert read("gdn_update_roofline.q3n") == pytest.approx(
        100 * 2 * (nbytes / 819e9) / 2.5e-3)
    assert read("gdn_step_share.q3n") == pytest.approx(100 * 2.5 / 25)
    step = costs_gdn.decode_step_bytes(HF, SLOTS, LIVE, 464.0)
    assert read("gdn_step_roofline.q3n") == pytest.approx(
        100 * (step / 819e9) / 25e-3)
    # the scans' padded rows are 4,096 and 2,048, 15/16 of them valid
    least = sum(max(b / 819e9, f / 197e12) for b, f in (
        costs_gdn.scan_cost(HF, 1, 4096 * 0.9375),
        costs_gdn.scan_cost(HF, 1, 2048 * 0.9375)))
    assert read("gdn_scan_roofline.q3n") == pytest.approx(
        100 * least / 16e-3)
    lengths = (512, 1024, 2048, 4096)
    ops = np.mean([costs_gdn.prefill_flops(HF, n, 10.0 * n)
                   for n in lengths])
    assert read("gdn_prefill_mfu.q3n") == pytest.approx(
        100 * 2 * ops / 0.24 / 197e12)
    for name in NEW:
        assert 0 < read(name) < 100, name


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_where_there_is_nothing(name):
    """No trace, a trace without the kernels (the parent's), a program
    without the counters, and a configuration of another family: None,
    never an exception."""
    reader = harness.plugin("layer_metrics", name)
    dense = harness.load_json("benchmark", "configs", "mistral-7b-v0.3.json")
    empty = xplane.Trace(
        ops={"/device:TPU:0": [("%fusion.1 = bf16[8]{0} fusion()", 0, 9)]},
        modules={"/device:TPU:0": [("jit__paged_step(1)", 0, 9)]})
    old = {"steps": 100, "admit_s": 1.0}            # the parent's timings
    for ctx in (_ctx(None), _ctx(empty, dense, old), _ctx(None, timings=old),
                _ctx(empty, dense), _ctx(None, dense, old),
                _ctx(_synthetic_trace(), dense, TIMINGS)):
        assert reader.read(ctx) is None
    if name != "gdn_step_roofline.q3n":     # (which reads no kernel's time)
        assert reader.read(_ctx(empty, HF, TIMINGS)) is None
        assert reader.read(_ctx(empty, HF, old)) is None


# -- the file ------------------------------------------------------------------

def test_config_file_holds_the_catalog_rows_numbers():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    for key, value in row["config"].items():
        if key not in HF["reduced"]:
            assert HF[key] == value, key
    assert HF["source"] == row["source_url"]
    assert set(HF["reduced"]) == {"num_hidden_layers", "num_experts",
                                  "vocab_size", "max_position_embeddings"}
    assert HF["published"] == {k: row["config"][k] for k in HF["reduced"]}
    assert HF["num_hidden_layers"] % HF["full_attention_interval"] == 0
    assert HF["expert_share"]["routed"] == row["config"]["num_experts"]
    assert HF["num_experts"] * HF["expert_share"]["chips"] == 512
    assert HF["vocab_size"] * HF["vocab_share"]["chips"] == 151936
    bench = harness.load_json("BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "qwen3-next-80b-a3b")
    assert set(entry["reduced"]) == set(HF["reduced"])
    assert entry["source"] == row["source_url"]
    cell = next(w for w in bench["workloads"]
                if w["config"] == "qwen3-next-80b-a3b")
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        "q3n.flood4k", "flood4k", 1)
    for text in (entry["why"], entry["source"], cell["why"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    sv = HF["serving"]
    assert sv["total_blocks"] == sv["slots"] * -(-sv["max_len"]
                                                 // sv["block_len"])
    assert sv["max_len"] == HF["max_position_embeddings"] == 4096 + 1024
    traffic = harness.load_json("benchmark", "traffic", "flood4k.json")
    assert traffic["prompts"] == [512, 1024, 2048, 4096]
    assert traffic["budgets"] == [512, 768, 1024]
    assert (traffic["requests"], traffic["lookahead"], traffic["kind"],
            traffic["runner"]) == (2048, 8, "closed_queue", "serve_gdn")


# -- the cell in BENCHMARK.json, and its mix ---------------------------------
# (``test_contract.py`` and ``test_traffic.py`` are a ``benchmark`` PR's to
# edit: the new cell's rows of their tables are kept here.)

#: the ``.flood`` entries the cell can read with no code of its own
FLOOD_ENTRIES = {
    "admit_share", "compiles_in_window", "decode_step_dev_ms", "device_idle",
    "hbm_peak_gib", "prefill_share", "prefill_pad_share", "idle_in_prefill",
    "idle_in_admit_rest", "step_host_ms_max", "prefill_batch_mean",
    "step_unscoped_share", "step_attn_share", "prefill_us_per_row",
    "step_head_share", "prefill_unscoped_share", "prefill_mixer_share",
    "prefill_dev_share", "moe_local_pair_share", "attn_grid_steps",
    "moe_rounds_per_call", "moe_experts_roofline",
    "moe_prefill_experts_roofline"}


def test_the_cell_reports_its_23_flood_entries_and_its_five_readers():
    bench = harness.load_json("BENCHMARK.json")
    e2e, per = run.cell_metrics(bench, "q3n.flood4k")
    assert {m["name"] for m in e2e} == {"tok_s", "setup_s"}
    names = [m["name"] for m in per]
    assert len(names) == len(set(names)) == 23 + 5
    assert {n for n in names if n.endswith(".q3n")} == set(NEW)
    assert ({n.split(".", 1)[0] for n in names if n.endswith(".flood")}
            == FLOOD_ENTRIES)
    assert all(n.endswith((".q3n", ".flood")) for n in names)
    config_of = {w["name"]: w["config"] for w in bench["workloads"]}
    perf = open(os.path.join(harness.ROOT, "PERF.md")).read()
    for m in bench["per_layer"]:
        if m["name"].endswith(".q3n"):      # no cell reports under its tag
            assert m["workloads"] == ["q3n.flood4k"]
            assert config_of[m["workloads"][0]] == "qwen3-next-80b-a3b"
            assert m["moves"] == "tok_s" and m["layer"] in perf
            assert callable(harness.plugin("layer_metrics", m["name"]).read)
    assert len(bench["per_layer"]) == 76 and bench["per_layer"][-5:] == [
        m for m in bench["per_layer"] if m["name"].endswith(".q3n")]


def _schedule(seed):
    traffic = harness.load_json("benchmark", "traffic", "flood4k.json")
    kind = harness.plugin("traffic.kinds", traffic["kind"])
    return kind.schedule(traffic, seed, 45.0)


def test_flood4k_fixed_count_and_multiset():
    """2,048 requests, each prompt length 512 times and each budget its
    share of the thirds, whatever the seed; every 4 consecutive requests
    hold each length once (the marginals are fixed, as the older floods');
    the same seed gives the same schedule."""
    from collections import Counter
    a, b = _schedule(1), _schedule(2**31 + 9)
    assert a == _schedule(1)
    for s in (a, b):
        assert len(s["requests"]) == 2048 and s["lookahead"] == 8
        assert Counter(r["prompt_len"] for r in s["requests"]) == {
            512: 512, 1024: 512, 2048: 512, 4096: 512}
        assert Counter(r["budget"] for r in s["requests"]) == {
            512: 683, 768: 683, 1024: 682}
        assert all(r["due"] is None for r in s["requests"])
    for lo in (0, 128, 1000, 2044):
        cut = slice(lo, lo + 4)
        assert (sorted(r["prompt_len"] for r in a["requests"][cut])
                == sorted(r["prompt_len"] for r in b["requests"][cut])
                == [512, 1024, 2048, 4096])
    assert [r["prompt_len"] for r in a["requests"]] != [
        r["prompt_len"] for r in b["requests"]]


def test_the_parent_commit_is_turned_away_at_once(monkeypatch):
    """A checkout whose ``config_from_hf`` cannot read the file, or reads it
    without a delta-rule layer, exits before a weight is drawn."""
    from benchmark.runners import serve_gdn
    from nvme_strom_tpu.tools import convert_llama

    def refuses(hf):
        raise ValueError("unsupported explicit head_dim=256")
    monkeypatch.setattr(convert_llama, "config_from_hf", refuses)
    with pytest.raises(SystemExit, match="cannot read a qwen3_next"):
        serve_gdn.run(types.SimpleNamespace(config=TINY_HF))
    monkeypatch.setattr(convert_llama, "config_from_hf",
                        lambda hf: types.SimpleNamespace(layer_kinds=()))
    with pytest.raises(SystemExit,
                       match="does not serve gated-delta-rule layers"):
        serve_gdn.run(types.SimpleNamespace(config=TINY_HF))

"""The Olmo-Hybrid configuration's side of the yardstick, on the CPU at a
tiny size: the generator's bits and layout, the cost functions against the
tensor list and hand counts at the cell's shapes, the plain reference against
a second, literal transcription of the equations, its padding and its
controls, the new cell end to end through ``run.execute`` (sound; with the
state or the conv tail not carried from prefill into decode, which must come
out as not correct), the new readers on a synthetic trace, the file against
the catalog, and the cell's own rows of what ``test_contract.py`` and
``test_traffic.py`` hold for the older cells."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import costs_olmoh, harness, run, xplane
from benchmark import weights_olmoh as WO
from benchmark.reference import olmo_hybrid as ref
from benchmark.runners import serve
from benchmark.tools import control_olmoh

CELL = "olmoh.flood-cot"
HF = harness.load_json("benchmark", "configs", "olmo-hybrid-7b.json")
TINY = dict(hidden_size=96, vocab_size=256, num_attention_heads=6,
            num_key_value_heads=6, intermediate_size=160,
            linear_key_head_dim=12, linear_value_head_dim=24,
            linear_num_key_heads=6, linear_num_value_heads=6,
            num_hidden_layers=4,
            layer_types=["linear_attention"] * 3 + ["full_attention"],
            max_position_embeddings=256,
            serving=dict(slots=4, max_len=256, block_len=16, total_blocks=64),
            # sound runs read ~0.005 and ~0.2 here (bf16 at width 96); with
            # the state not carried the mean is over 0.3
            correct=dict(served_mean_gap_limit=0.1,
                         served_max_gap_limit=2.0))
TRAFFIC = dict(requests=40, prompts=[16, 48, 80, 112], budgets=[24, 40],
               lookahead=4)
TINY_HF = {**HF, **TINY}


def _run(seed=2**31 + 77, trace=0, **test):
    test = dict(allow_cpu=True, config=TINY, traffic=TRAFFIC, **test)
    return run.execute(["--workload", CELL, "--seed", str(seed),
                        "--seconds", "3", "--trace", str(trace)], test=test)


# -- the generator -----------------------------------------------------------

def test_generator_bits_are_the_same_in_numpy_and_under_jit():
    import jax
    specs, idx = WO.tensor_specs(TINY_HF), WO.layer_indices(TINY_HF)
    bs = WO.bases(TINY_HF, 5)
    for name in ("tok_embed", "layers.0.gdn_in", "layers.1.gdn_A_log",
                 "layers.2.gdn_conv_w", "layers.3.wq", "layers.3.q_norm",
                 "layers.0.w_down", "layers.2.attn_norm"):
        shape = dict(specs)[name]
        want = WO.make_tensor_np(5, idx[name], name, shape)
        got = jax.jit(lambda b, n=name, s=shape: WO.make_tensor(b, n, s))(
            bs[idx[name]])
        np.testing.assert_array_equal(np.asarray(got).view(np.uint16),
                                      want.view(np.uint16))
    params = WO.make_params(TINY_HF, 5)
    # every norm is drawn around 1 and served as drawn; the q/k norms span
    # the whole projection
    assert params["layers.3.q_norm"].shape == (96,)
    assert abs(float(np.asarray(params["layers.3.q_norm"],
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
        got = dict(WO.tensor_specs(hf))
        assert set(got) == set(want)
        assert all(tuple(want[k].shape) == tuple(got[k]) for k in got)
    assert [WO.layer_kind(HF, i) for i in range(16)] == [
        "linear", "linear", "linear", "full"] * 4


# -- the costs, by hand at the cell's shapes ---------------------------------

def test_parameter_count_is_the_sum_over_the_tensor_list():
    p = costs_olmoh.param_count(HF)
    total = sum(int(np.prod(s)) for _, s in WO.tensor_specs(HF))
    assert p["total"] == total
    assert (p["n_linear"], p["n_full"]) == (12, 4)
    # the issue's arithmetic: 88.7 M, 59.0 M, 126.8 M, 4,100 M = 7.64 GiB
    assert p["linear"] == 3840 * 17280 + 3840 * 60 + 4 * 11520 + 60 + 192 \
        + 5760 * 3840 + 3840 == 88_754_172
    assert p["full"] == 4 * 3840 * 3840 + 2 * 3840 + 3840 == 58_993_920
    assert p["mlp"] == 3 * 3840 * 11008 + 3840 == 126_816_000
    assert p["embed"] == p["head"] == 100352 * 3840
    assert 4100 <= total / 1e6 < 4101
    assert round(total * 2 / 2**30, 2) == 7.64


def test_state_cache_and_kernel_costs_by_hand():
    assert costs_olmoh.state_bytes_per_slot(HF) == 12 * (
        30 * 96 * 192 * 4 + 3 * 11520 * 2) == 12 * (2_211_840 + 69_120)
    assert costs_olmoh.kv_bytes_per_token(HF) == 4 * 2 * 30 * 128 * 2 \
        == 61_440
    nbytes, flops = costs_olmoh.update_cost(HF, 32)
    state = 32 * 30 * 96 * 192 * 4                   # unpadded
    assert nbytes == 2 * state + 32 * 30 * (3 * 96 + 3 * 192) * 4
    assert flops == 6.0 * 32 * 30 * 96 * 192
    nbytes, flops = costs_olmoh.scan_cost(HF, 2, 1500.0)
    assert nbytes == 1500 * ((2 * 2880 + 2 * 5760) * 2 + 2 * 30 * 4) \
        + 2 * 2 * 30 * 96 * 192 * 4
    assert flops == 6.0 * 1500 * 30 * 96 * 192
    nbytes, flops = costs_olmoh.attn_cost(HF, 32, 27_000.0)
    assert nbytes == 2 * 30 * 128 * 2 * 27_000 + 2 * 32 * 30 * 128 * 2
    assert flops == 4.0 * 30 * 128 * 27_000


def test_decode_step_bytes_follow_slots_and_live_rows():
    p = costs_olmoh.param_count(HF)
    base = costs_olmoh.decode_step_bytes(HF, 32, 0.0)
    assert base == (p["read_a_step"] + 32 * 3840) * 2 \
        + 2 * 32 * costs_olmoh.state_bytes_per_slot(HF)
    assert costs_olmoh.decode_step_bytes(HF, 32, 1000.0) - base \
        == 1000 * 61_440
    # the issue's step at a mean live length of ~860: 7.4 GB of weights, 1.7
    # of state and 1.7 of pages; the caches 31 % of it
    step = costs_olmoh.decode_step_bytes(HF, 32, 32 * 860.0)
    state = 2 * 32 * costs_olmoh.state_bytes_per_slot(HF)
    pages = 32 * 860 * 61_440
    assert 10.7e9 < step < 10.9e9
    assert 1.70e9 < state < 1.80e9 and 1.65e9 < pages < 1.72e9
    assert 0.30 < (state + pages) / step < 0.33
    flops = costs_olmoh.decode_step_flops(HF, 32, 32 * 860.0)
    assert flops == 2.0 * 32 * p["read_a_step"] \
        + 12 * 6.0 * 32 * 30 * 96 * 192 + 4.0 * 4 * 30 * 128 * 32 * 860


def test_prefill_flops_count_the_recurrence_and_the_causal_half_once():
    p = costs_olmoh.param_count(HF)
    got = costs_olmoh.prefill_flops(HF, 1024)
    mats = 2.0 * (1024 * (p["read_a_step"] - p["head"]) + p["head"])
    attn = 4.0 * 4 * 30 * 128 * 1024 * 1025 / 2
    scan = 12 * 6.0 * 1024 * 30 * 96 * 192
    assert got == mats + attn + scan
    assert 6.6e9 < got / 1024 < 7.2e9        # ~6.7 GFLOP a row + the head


# -- the reference -----------------------------------------------------------

def _literal(hf, seed, tokens):
    """A second transcription of the equations, as literal as numpy allows:
    one sequence, python loops over layers, rows and heads, float64, every
    weight drawn with the generator's numpy definition."""
    z, idx = WO.sizes(hf), WO.layer_indices(hf)
    shapes = {**WO.layer_shapes(hf), **WO.top_shapes(hf)}
    eps = hf["rms_norm_eps"]

    def w(name):
        leaf = name.rsplit(".", 1)[-1]
        return WO.make_tensor_np(seed, idx[name], leaf, shapes[leaf]).astype(
            np.float64)

    def norm(x, g):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * g

    def silu(a):
        return a / (1 + np.exp(-a))

    def sigmoid(a):
        return 1 / (1 + np.exp(-a))

    x = w("tok_embed")[np.asarray(tokens)]
    T = len(tokens)
    for i in range(hf["num_hidden_layers"]):
        L = f"layers.{i}."
        if WO.layer_kind(hf, i) == "linear":
            mixed, ba = x @ w(L + "gdn_in"), x @ w(L + "gdn_ba")
            u, gate = mixed[:, :z["conv"]], mixed[:, z["conv"]:]
            taps = w(L + "gdn_conv_w")
            conv = np.zeros_like(u)
            for t in range(T):
                for j in range(z["K"]):
                    if t - 3 + j >= 0:
                        conv[t] += taps[j] * u[t - 3 + j]
            u = silu(conv)
            beta = 2 * sigmoid(ba[:, :z["Hv"]])
            alpha = np.exp(-np.exp(w(L + "gdn_A_log")) * np.log1p(
                np.exp(ba[:, z["Hv"]:] + w(L + "gdn_dt_bias"))))
            y = np.zeros((T, z["Hv"], z["dv"]))
            for j in range(z["Hv"]):
                S = np.zeros((z["dk"], z["dv"]))
                for t in range(T):
                    q = u[t, j * z["dk"]:(j + 1) * z["dk"]]
                    k = u[t, z["key"] + j * z["dk"]:
                          z["key"] + (j + 1) * z["dk"]]
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
            a = y.reshape(T, -1) @ w(L + "gdn_out")
        else:
            nh, hd = z["nh"], z["hd"]
            q = norm(x @ w(L + "wq"), w(L + "q_norm")).reshape(T, nh, hd)
            k = norm(x @ w(L + "wk"), w(L + "k_norm")).reshape(T, nh, hd)
            v = (x @ w(L + "wv")).reshape(T, nh, hd)
            a = np.zeros((T, nh, hd))
            for hh in range(nh):
                for t in range(T):
                    s = k[:t + 1, hh] @ q[t, hh] / np.sqrt(hd)
                    p = np.exp(s - s.max())
                    a[t, hh] = (p / p.sum()) @ v[:t + 1, hh]
            a = a.reshape(T, -1) @ w(L + "wo")
        x = x + norm(a, w(L + "attn_norm"))
        f = (silu(x @ w(L + "w_gate")) * (x @ w(L + "w_up"))) @ w(
            L + "w_down")
        x = x + norm(f, w(L + "mlp_norm"))
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
    for low in control_olmoh.CONTROLS:
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


@pytest.mark.parametrize("key", ["s", "conv"])
def test_state_or_tail_not_carried_into_decode_is_not_correct(key):
    out, _ = _run(server_built=lambda srv: control_olmoh.drop_rows(srv, key))
    assert out["correct"] is False
    assert out["failed"] == 0               # every budget still returned


def test_a_reference_side_control_lies_outside_the_limits():
    """The sample a sound run compares, with the token the reference under
    β = sigmoid(b) puts first in the served token's place: outside the
    test-size limits."""
    got = {}

    def after(ctx, sample):
        got["gaps"] = serve.control_gaps(ctx.config, ctx.seed, sample,
                                         ctx.config["reference"],
                                         low="beta1")
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
            "prefill_batch_mean.flood", "attn_grid_steps.flood"} <= set(
                out["metrics"])
    # no device plane on the CPU: nothing a kernel's time would be read from
    assert not {m for m in out["metrics"] if m.endswith(".olmoh")}


# -- the new readers ---------------------------------------------------------

SLOTS, LIVE = 32, 27_000.0


def _ctx(trace, config=HF, timings=None):
    peaks = harness.load_json("benchmark", "peaks.json")["TPU v5 lite"]
    return types.SimpleNamespace(
        trace=trace, config=config, peaks=peaks,
        traffic={"prompts": [128, 256, 512, 1024]},
        facts={"slots": SLOTS, "live_tokens": LIVE, "timings": timings})


#: a window of 100 steps and 6 admissions of one prompt each
TIMINGS = {"steps": 100, "prompt_tokens": 2_880, "prefill_tokens": 3_072,
           "scan_tokens": 2_880, "prefill_calls": 6, "admits": 6}


def _synthetic_trace():
    ms = 1_000_000
    upd = "%strom_gdn_update.{} = (f32[32,3,5,384]{{3,2,1,0}}, " \
          "f32[33,15,96,384]{{3,2,1,0}}) custom-call(.)"
    scan = "%strom_gdn_scan.{} = (bf16[{},30,{},64,192]{{4,3,2,1,0}}, " \
           "f32[{},30,96,192]{{3,2,1,0}}) custom-call(.)"
    step = [("%fusion.1 = bf16[32,3840]{1,0} fusion(...)", 0, ms),
            (upd.format(1), 1 * ms, 1.2 * ms),
            # the consumer of a kernel's result names it among its operands
            ("%fusion.2 = f32[32,5760]{1,0} fusion(f32[32,3,5,384] "
             "%strom_gdn_update.1, ...)", 2 * ms, 2.5 * ms),
            (upd.format(2), 3 * ms, 3.3 * ms),
            ("%strom_paged_attn.3 = bf16[32,30,1,128]{3,2,1,0} "
             "custom-call(.)", 5 * ms, 5.8 * ms)]
    pre = [("%fusion.9 = bf16[1024,3840]{1,0} fusion(...)", 50 * ms, 90 * ms),
           (scan.format(1, 1, 16, 1), 90 * ms, 91 * ms),
           ("%fusion.10 = bf16[1024,5760]{1,0} fusion(bf16[1,30,16,64,192] "
            "%strom_gdn_scan.1, ...)", 100 * ms, 110 * ms),
           (scan.format(2, 2, 2, 2), 260 * ms, 260.5 * ms)]
    plane = "/device:TPU:0"
    return xplane.Trace(
        ops={plane: step + pre},
        # (a step of 18 ms: its 10.9 GB take 13.3 at the chip's bandwidth)
        modules={plane: [("jit__paged_step(1)", 0, 18 * ms),
                         ("jit__paged_prefill(2)", 50 * ms, 150 * ms),
                         ("jit__paged_prefill(4)", 260 * ms, 300 * ms),
                         ("jit_other(3)", 310 * ms, 311 * ms)]})


NEW = ("delta_update_roofline.olmoh", "delta_scan_roofline.olmoh",
       "gdn_step_share.olmoh", "delta_step_roofline.olmoh",
       "delta_prefill_mfu.olmoh", "mha_attn_roofline.olmoh")


def test_new_readers_on_a_synthetic_trace():
    ctx = _ctx(_synthetic_trace(), timings=TIMINGS)
    read = lambda name: harness.plugin("layer_metrics", name).read(ctx)  # noqa
    nbytes, _ = costs_olmoh.update_cost(HF, SLOTS)
    # two calls in the step (the fusion that names one is no call): 2 x
    # least over 0.2 + 0.3 ms
    assert read("delta_update_roofline.olmoh") == pytest.approx(
        100 * 2 * (nbytes / 819e9) / 0.5e-3)
    assert read("gdn_step_share.olmoh") == pytest.approx(100 * 0.5 / 18)
    step = costs_olmoh.decode_step_bytes(HF, SLOTS, LIVE)
    assert read("delta_step_roofline.olmoh") == pytest.approx(
        100 * (step / 819e9) / 18e-3)
    abytes, _ = costs_olmoh.attn_cost(HF, SLOTS, LIVE)
    assert read("mha_attn_roofline.olmoh") == pytest.approx(
        100 * (abytes / 819e9) / 0.8e-3)
    # the scans' padded rows are 1,024 and 2 x 128, 15/16 of them valid
    least = sum(max(b / 819e9, f / 197e12) for b, f in (
        costs_olmoh.scan_cost(HF, 1, 1024 * 0.9375),
        costs_olmoh.scan_cost(HF, 2, 256 * 0.9375)))
    assert read("delta_scan_roofline.olmoh") == pytest.approx(
        100 * least / 1.5e-3)
    lengths = (128, 256, 512, 1024)
    ops = np.mean([costs_olmoh.prefill_flops(HF, n) for n in lengths])
    assert read("delta_prefill_mfu.olmoh") == pytest.approx(
        100 * 2 * ops / 0.14 / 197e12)
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
    contexts = [_ctx(None), _ctx(None, timings=old)]
    if name != "gdn_step_share.olmoh":      # (which asks no model_type)
        contexts += [_ctx(empty, dense, old), _ctx(empty, dense),
                     _ctx(None, dense, old),
                     _ctx(_synthetic_trace(), dense, TIMINGS)]
    for ctx in contexts:
        assert reader.read(ctx) is None
    if name != "delta_step_roofline.olmoh":  # (which reads no kernel's time)
        assert reader.read(_ctx(empty, HF, TIMINGS)) is None
        assert reader.read(_ctx(empty, HF, old)) is None


# -- the file ------------------------------------------------------------------

def test_config_file_holds_the_catalog_rows_numbers():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    for key, value in row["config"].items():
        if key not in HF["reduced"]:
            assert HF[key] == value, key
    assert HF["source"] == row["source_url"]
    assert set(HF["reduced"]) == {"num_hidden_layers", "layer_types",
                                  "max_position_embeddings"}
    assert HF["layer_types"] == row["config"]["layer_types"][:16]
    assert HF["published"]["num_hidden_layers"] == 32
    assert HF["published"]["max_position_embeddings"] == 65536
    # every published width unchanged
    assert (HF["hidden_size"], HF["num_attention_heads"],
            HF["num_key_value_heads"], HF["intermediate_size"],
            HF["linear_num_value_heads"], HF["linear_key_head_dim"],
            HF["linear_value_head_dim"], HF["linear_conv_kernel_dim"],
            HF["vocab_size"]) == (3840, 30, 30, 11008, 30, 96, 192, 4,
                                  100352)
    assert len(HF["assumed"]) >= 4
    bench = harness.load_json("BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "olmo-hybrid-7b")
    assert set(entry["reduced"]) == set(HF["reduced"])
    assert entry["source"] == row["source_url"]
    cell = next(w for w in bench["workloads"]
                if w["config"] == "olmo-hybrid-7b")
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "flood-cot", 1)
    for text in (entry["why"], entry["source"], cell["why"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    sv = HF["serving"]
    assert sv["total_blocks"] == sv["slots"] * -(-sv["max_len"]
                                                 // sv["block_len"])
    assert sv["max_len"] == HF["max_position_embeddings"] == 1024 + 1024
    traffic = harness.load_json("benchmark", "traffic", "flood-cot.json")
    assert traffic["prompts"] == [128, 256, 512, 1024]
    assert traffic["budgets"] == [512, 768, 1024]
    assert (traffic["requests"], traffic["lookahead"], traffic["kind"],
            traffic["runner"]) == (1024, 8, "closed_queue", "serve_olmoh")


# -- the cell in BENCHMARK.json, and its mix ---------------------------------
# (``test_contract.py`` and ``test_traffic.py`` are a ``benchmark`` PR's to
# edit: the new cell's rows of their tables are kept here.)

#: the ``.flood`` entries the cell can read with no code of its own
FLOOD_ENTRIES = {
    "admit_share", "compiles_in_window", "decode_step_dev_ms", "device_idle",
    "hbm_peak_gib", "prefill_share", "prefill_pad_share", "idle_in_prefill",
    "idle_in_admit_rest", "step_host_ms_max", "prefill_batch_mean",
    "step_unscoped_share", "step_attn_share", "step_mlp_share",
    "prefill_us_per_row", "step_head_share", "prefill_unscoped_share",
    "prefill_mixer_share", "prefill_dev_share", "attn_grid_steps"}


def test_the_cell_reports_its_20_flood_entries_and_its_six_readers():
    bench = harness.load_json("BENCHMARK.json")
    e2e, per = run.cell_metrics(bench, CELL)
    assert {m["name"] for m in e2e} == {"tok_s", "setup_s"}
    names = [m["name"] for m in per]
    assert len(names) == len(set(names)) == 20 + 6
    assert {n for n in names if n.endswith(".olmoh")} == set(NEW)
    assert ({n.split(".", 1)[0] for n in names if n.endswith(".flood")}
            == FLOOD_ENTRIES)
    assert all(n.endswith((".olmoh", ".flood")) for n in names)
    config_of = {w["name"]: w["config"] for w in bench["workloads"]}
    perf = open(os.path.join(harness.ROOT, "PERF.md")).read()
    for m in bench["per_layer"]:
        if m["name"].endswith(".olmoh"):    # no cell reports under its tag
            assert m["workloads"] == [CELL]
            assert config_of[m["workloads"][0]] == "olmo-hybrid-7b"
            assert m["moves"] == "tok_s" and m["layer"] in perf
            assert callable(harness.plugin("layer_metrics", m["name"]).read)
    assert bench["per_layer"][-6:] == [
        m for m in bench["per_layer"] if m["name"].endswith(".olmoh")]
    assert len(bench["workloads"]) == 10
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def _schedule(seed):
    traffic = harness.load_json("benchmark", "traffic", "flood-cot.json")
    kind = harness.plugin("traffic.kinds", traffic["kind"])
    return kind.schedule(traffic, seed, 45.0)


def test_flood_cot_fixed_count_and_multiset():
    """1,024 requests, each prompt length 256 times and each budget its
    share of the thirds, whatever the seed; every 4 consecutive requests
    hold each length once; the same seed gives the same schedule."""
    from collections import Counter
    a, b = _schedule(1), _schedule(2**31 + 9)
    assert a == _schedule(1)
    for s in (a, b):
        assert len(s["requests"]) == 1024 and s["lookahead"] == 8
        assert Counter(r["prompt_len"] for r in s["requests"]) == {
            128: 256, 256: 256, 512: 256, 1024: 256}
        assert Counter(r["budget"] for r in s["requests"]) == {
            512: 342, 768: 341, 1024: 341}
        assert all(r["due"] is None for r in s["requests"])
        # answers are longer than questions on the whole
        assert sum(r["budget"] for r in s["requests"]) > sum(
            r["prompt_len"] for r in s["requests"])
    for lo in (0, 128, 1000, 1020):
        cut = slice(lo, lo + 4)
        assert (sorted(r["prompt_len"] for r in a["requests"][cut])
                == sorted(r["prompt_len"] for r in b["requests"][cut])
                == [128, 256, 512, 1024])
    assert [r["prompt_len"] for r in a["requests"]] != [
        r["prompt_len"] for r in b["requests"]]


def test_the_parent_commit_is_turned_away_at_once(monkeypatch):
    """A checkout whose ``config_from_hf`` cannot read the file, or reads it
    as a dense pre-norm decoder (what the parent's does with a model_type it
    does not know), exits before a weight is drawn."""
    from benchmark.runners import serve_olmoh
    from nvme_strom_tpu.tools import convert_llama

    def refuses(hf):
        raise ValueError("unsupported model_type")
    monkeypatch.setattr(convert_llama, "config_from_hf", refuses)
    with pytest.raises(SystemExit, match="cannot read an olmo_hybrid"):
        serve_olmoh.run(types.SimpleNamespace(config=TINY_HF))
    monkeypatch.setattr(convert_llama, "config_from_hf",
                        lambda hf: types.SimpleNamespace(layer_kinds=()))
    with pytest.raises(SystemExit, match="does not serve olmo_hybrid"):
        serve_olmoh.run(types.SimpleNamespace(config=TINY_HF))

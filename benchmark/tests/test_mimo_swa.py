"""The MiMo-V2.5 configuration's side of the yardstick, on the CPU at a tiny
size: the generator's bits, the cost functions against the tensor list and
hand counts, the plain reference's own properties and its controls, the new
cell end to end through ``run.execute`` (sound; with the window layers' rings
not carried into decode and with one reference-side control, which must come
out as not correct), the new readers on a synthetic trace, and the file
against the catalog."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import costs_swa, harness, run, xplane
from benchmark import weights_swa as WS
from benchmark.reference import mimo_swa as ref
from benchmark.runners import serve
from benchmark.tools import control_swa

HF = harness.load_json("benchmark", "configs", "mimo-v2.5.json")
TINY = dict(hidden_size=64, vocab_size=256, num_attention_heads=4,
            swa_num_attention_heads=4, num_key_value_heads=1,
            swa_num_key_value_heads=2, head_dim=24, swa_head_dim=24,
            v_head_dim=16, swa_v_head_dim=16, intermediate_size=128,
            moe_intermediate_size=32, n_routed_experts=4,
            expert_share={"routed": 16, "offset": 4, "chips": 4},
            num_experts_per_tok=4, num_hidden_layers=4,
            hybrid_layer_pattern=[0, 1, 1, 0], moe_layer_freq=[0, 1, 1, 1],
            sliding_window=32, sliding_window_size=32,
            attention_chunk_size=32, rope_theta=10000, swa_rope_theta=100,
            max_position_embeddings=256,
            serving=dict(slots=4, max_len=256, block_len=16, total_blocks=64),
            # sound runs read 0.01 - 0.03 and 0.3 - 0.8 here (bf16 at width
            # 64, a routing flip or two in 60 tokens); with the rings not
            # carried the mean is over 1
            correct=dict(served_mean_gap_limit=0.15,
                         served_max_gap_limit=2.0))
TRAFFIC = dict(requests=40, prompts=[16, 48, 80, 112], budgets=[24, 40],
               lookahead=4)
TINY_HF = {**HF, **TINY}


def _run(seed=2**31 + 77, trace=0, **test):
    test = dict(allow_cpu=True, config=TINY, traffic=TRAFFIC, **test)
    return run.execute(["--workload", "mimo.flood16k", "--seed", str(seed),
                        "--seconds", "3", "--trace", str(trace)], test=test)


# -- the generator -----------------------------------------------------------

def test_generator_bits_are_the_same_in_numpy_and_under_jit():
    import jax
    specs = WS.tensor_specs(TINY_HF)
    params = WS.make_params(TINY_HF, 2**31 + 5)
    assert set(params) == {n for n, _ in specs}
    for i, (name, shape) in enumerate(specs):
        want = WS.make_tensor_np(2**31 + 5, i, name, shape)
        got = np.asarray(jax.device_get(params[name]))
        assert got.dtype == want.dtype and got.shape == tuple(shape)
        np.testing.assert_array_equal(got.view(np.uint16),
                                      want.view(np.uint16), err_msg=name)
    other = WS.make_params(TINY_HF, 2**31 + 6)
    name = "layers.1.sink"
    assert (np.asarray(other[name]) != np.asarray(params[name])).any()
    # sinks N(0, 1): large enough that a dropped sink cannot hide
    sinks = np.concatenate([np.asarray(WS.make_tensor_np(
        7, i, n, s), np.float32) for i, (n, s) in enumerate(
            WS.tensor_specs(HF)) if n.endswith(".sink")])
    assert sinks.size == 5 * 64 and 0.8 < sinks.std() < 1.2


def test_the_layout_is_the_programs():
    """Every leaf ``init_params`` makes for the config, at its shape: a full
    layer's 4 KV heads and a window layer's 8 (with its sinks), keys 192 and
    values 128 wide at the published sizes."""
    import jax

    from nvme_strom_tpu.models.transformer import init_params
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    for hf in (TINY_HF, HF):
        cfg = config_from_hf(hf)
        want = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
        got = dict(WS.tensor_specs(hf))
        assert {k: tuple(v.shape) for k, v in want.items()} == got
    assert got["layers.0.wk"] == (4096, 4 * 192)
    assert got["layers.1.wk"] == (4096, 8 * 192)
    assert got["layers.1.wv"] == (4096, 8 * 128)
    assert got["layers.1.wo"] == (64 * 128, 4096)
    assert "layers.0.sink" not in got and got["layers.6.sink"] == (64,)


# -- the costs ---------------------------------------------------------------

def test_parameter_count_is_the_sum_over_the_tensor_list():
    for hf in (HF, TINY_HF):
        total = sum(int(np.prod(s)) for _, s in WS.tensor_specs(hf))
        assert costs_swa.param_count(hf)["total"] == total
    p = costs_swa.param_count(HF)
    # by hand, at the published widths: the issue's arithmetic
    assert p["attn"]["full"] == (4096 * 12288 + 4096 * 4 * 320
                                 + 8192 * 4096 + 4096)
    assert round(p["attn"]["full"] / 1e6, 2) == 89.13
    assert round(p["attn"]["window"] / 1e6, 2) == 94.38
    assert p["expert"] == 3 * 4096 * 2048 == 25_165_824
    assert (p["n_full"], p["n_window"], p["n_dense"],
            p["n_expert_layers"]) == (2, 5, 1, 6)
    assert p["total"] * 2 / 2**30 == pytest.approx(6.39, abs=5e-3)


def test_cache_and_kernel_costs_by_hand():
    assert costs_swa.kv_bytes_per_token(HF) == 2 * 4 * 320 * 2 == 5120
    assert costs_swa.window_bytes_per_row(HF) == 5 * 8 * 320 * 2 == 25600
    nbytes, flops = costs_swa.attn_cost(HF, "full", 64, 400_000.0)
    assert flops == 400_000 * 40_960          # 2 x 64 x (192 + 128) a row
    assert nbytes == (400_000 * 4 + 64 * 64) * 320 * 2
    # 16 operations a byte: far under the v5e's ridge of 240
    assert 15 < flops / nbytes < 16.1
    wbytes, wflops = costs_swa.attn_cost(HF, "window", 64, 64 * 128.0)
    assert wflops == 64 * 128 * 40_960
    assert wbytes == (64 * 128 * 8 + 64 * 64) * 320 * 2


def test_decode_step_bytes_follow_touched_experts_and_live_rows():
    base = costs_swa.decode_step_bytes(HF, 64, 0.0, 0.0, 0.0)
    p = costs_swa.param_count(HF)
    assert base == (p["outside_experts"] + 64 * 4096) * 2
    more = costs_swa.decode_step_bytes(HF, 64, 1000.0, 3.0, 200.0)
    assert more - base == 3 * p["expert"] * 2 + 1000 * 5120 + 200 * 25600
    # a window layer's rows stop at 128 a slot however long the context
    flops = costs_swa.decode_step_flops(HF, 64, 400_000.0, 100.0, 64 * 128.0)
    assert flops == (2.0 * (64 * p["outside_experts"] + 100 * p["expert"])
                     + 2 * 400_000 * 40_960 + 5 * 64 * 128 * 40_960)


def test_prefill_flops_count_the_causal_half_once_and_the_band_only():
    p = costs_swa.param_count(HF)
    one = costs_swa.prefill_flops(HF, 1, 0.0)
    assert one == 2.0 * p["outside_experts"] + 2.0 * 7 * 64 * 320
    n = 16384
    attn = costs_swa.prefill_attn_flops(HF, n)
    assert attn["full"] == 2.0 * 2 * 64 * 320 * n * (n + 1) / 2
    assert attn["window"] == 2.0 * 5 * 64 * 320 * (
        128 * 129 / 2 + (n - 128) * 128)
    # the band is 1/64 of the causal half at 16,384 rows, a layer
    assert (attn["window"] / 5) / (attn["full"] / 2) == pytest.approx(
        1 / 64, rel=0.01)
    assert costs_swa.prefill_flops(HF, n, 10.0) \
        - costs_swa.prefill_flops(HF, n, 0.0) == 20.0 * p["expert"]
    # a prompt shorter than the window: its causal half
    assert costs_swa.prefill_attn_flops(HF, 100)["window"] == \
        2.0 * 5 * 64 * 320 * 100 * 101 / 2


# -- the reference ------------------------------------------------------------

def test_reference_padding_is_inert_and_each_control_is_another_answer():
    rng = np.random.default_rng(3)
    toks = rng.integers(0, 256, (2, 72)).astype(np.int32)
    at = np.tile(np.arange(40, 60)[None], (2, 1))
    base = np.asarray(ref.logits_at(TINY_HF, 5, toks, at))
    padded = np.concatenate([toks[:, :60], np.zeros((2, 36), np.int32)], 1)
    np.testing.assert_allclose(
        np.asarray(ref.logits_at(TINY_HF, 5, padded, at)), base, atol=1e-5)
    for low in control_swa.CONTROLS:
        other = np.asarray(ref.logits_at(TINY_HF, 5, toks, at, low=low))
        assert np.abs(other - base).max() > 1e-3, low


def test_reference_window_sees_exactly_its_last_rows():
    """A window layer's row i reads keys i - w < j <= i: changing the token
    w rows back leaves a one-window-layer model's logits at row i alone,
    changing the one w - 1 back does not; a full layer sees both."""
    hf = dict(TINY_HF, num_hidden_layers=1, hybrid_layer_pattern=[1],
              moe_layer_freq=[0])
    toks = np.random.default_rng(4).integers(0, 256, (1, 64)).astype(np.int32)
    at = np.asarray([[50]])

    def moved(j, hf=hf):
        other = toks.copy()
        other[0, j] = (other[0, j] + 1) % 256
        return np.abs(np.asarray(ref.logits_at(hf, 9, other, at))
                      - np.asarray(ref.logits_at(hf, 9, toks, at))).max()
    assert moved(50 - 32) == 0.0 and moved(50 - 31) > 1e-4
    full = dict(hf, hybrid_layer_pattern=[0])
    assert moved(50 - 32, full) > 1e-5


def test_reference_weighs_over_all_the_selected_experts():
    """The weights of a row sum to 1 over ALL 16 experts (no scaling
    factor); the held four get their part of it, and ``norm_held`` all of
    it."""
    import jax.numpy as jnp
    z = WS.sizes(TINY_HF)
    rng = np.random.default_rng(1)
    w = {"router": jnp.asarray(rng.normal(size=(64, 16)), jnp.float32),
         "router_bias": jnp.zeros((16,), jnp.float32)}
    h = jnp.asarray(rng.normal(size=(5, 64)), jnp.float32)
    wt = np.asarray(ref.routing(h, w, TINY_HF))
    assert ((wt > 0).sum(-1) == z["k"]).all()
    np.testing.assert_allclose(wt.sum(-1), 1.0, rtol=1e-5)
    held = np.asarray(ref.routing(h, w, TINY_HF, low="norm_held"))
    assert (held[:, :4] == 0).all() and (held[:, 8:] == 0).all()
    some = held.sum(-1) > 0
    np.testing.assert_allclose(held.sum(-1)[some], 1.0, rtol=1e-5)


# -- the cell, end to end ----------------------------------------------------

def test_cell_end_to_end_is_correct():
    out, ctx = _run()
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"tok_s", "setup_s"}
    assert ctx.facts["compiles_in_window"] == 0
    t = ctx.facts["timings"]
    assert t["moe_pairs_routed_prefill"] == t["prompt_tokens"] * 4 * 3
    assert 0 < t["moe_pairs"] < t["moe_pairs_routed"]
    # every active slot's window layers read at most 32 rows a step
    assert 0 < t["window_rows_live"] <= 32 * 4 * t["steps"]
    assert t["attn_blocks_live"] > 0


def test_rings_not_carried_into_decode_are_not_correct():
    out, _ = _run(server_built=control_swa.drop_rings)
    assert out["correct"] is False
    assert out["failed"] == 0               # every budget still returned


def test_a_reference_side_control_lies_outside_the_limits():
    """The sample a sound run compares, with the token the reference WITHOUT
    the window mask puts first in the served token's place: outside the
    test-size limits, as every control is on the chip."""
    got = {}

    def after(ctx, sample):
        got["gaps"] = serve.control_gaps(ctx.config, ctx.seed, sample,
                                         ctx.config["reference"],
                                         low="no_window")
    out, _ = _run(after_window=after)
    assert out["correct"] is True
    lim = TINY["correct"]
    assert (got["gaps"]["mean_gap"] > lim["served_mean_gap_limit"]
            or got["gaps"]["max_gap"] > lim["served_max_gap_limit"])


def test_traced_run_reports_the_counters_and_leaves_the_device_out():
    out, _ = _run(trace=1)
    assert out["correct"] is True
    assert {"admit_share.flood", "prefill_share.flood", "prefill_pad_share.flood",
            "compiles_in_window.flood", "prefill_batch_mean.flood",
            "moe_local_pair_share.flood"} <= set(out["metrics"])
    # no device plane on the CPU: nothing a kernel's time would be read from
    assert not {m for m in out["metrics"] if m.endswith(".mimo")}
    assert 5 < out["metrics"]["moe_local_pair_share.flood"]["value"] < 60


# -- the new readers ---------------------------------------------------------

SLOTS, LIVE = 64, 300_000.0


def _ctx(trace, config=HF, timings=None):
    peaks = harness.load_json("benchmark", "peaks.json")["TPU v5 lite"]
    return types.SimpleNamespace(
        trace=trace, config=config, peaks=peaks,
        traffic={"prompts": [2048, 4096, 8192, 16384]},
        facts={"slots": SLOTS, "live_tokens": LIVE, "timings": timings})


#: a window of 100 steps and 6 admissions, 6 expert layers
TIMINGS = {"steps": 100, "moe_calls": 600, "moe_pairs": 14_400,
           "moe_pairs_routed": 230_400, "moe_experts_touched": 7_800,
           "window_rows_live": 100 * SLOTS * 128,
           "prompt_tokens": 46_080, "moe_pairs_prefill": 138_240,
           "moe_pairs_routed_prefill": 2_211_840, "prefill_calls": 6}


def _synthetic_trace():
    ms = 1_000_000
    full = "%strom_paged_attn.{} = bf16[64,4,16,128]{{3,2,1,0}} custom-call(.)"
    win = "%strom_window_attn.{} = bf16[64,8,8,128]{{3,2,1,0}} custom-call(.)"
    step = [("%fusion.1 = bf16[64,4096]{1,0} fusion(...)", 0, ms),
            ("%strom_kv_write.1 = bf16[2,8705,4,128,192]{3,4,2,1,0} "
             "custom-call(...)", 1 * ms, 1.1 * ms),
            (full.format(2), 2 * ms, 4 * ms),
            # the consumer of a kernel's result names it among its operands
            ("%fusion.2 = bf16[64,8192]{1,0} fusion(bf16[64,4,16,128] "
             "%strom_paged_attn.2, ...)", 4 * ms, 4.5 * ms),
            (win.format(3), 5 * ms, 5.25 * ms),
            (win.format(4), 6 * ms, 6.25 * ms),
            (full.format(5), 7 * ms, 9 * ms)]
    pre = [("%fusion.9 = bf16[16384,4096]{1,0} fusion(...)", 50 * ms,
            150 * ms),
           ("%strom_kv_prefill.1 = bf16[1,4,16,4096,128]{4,3,2,1,0} "
            "custom-call(...)", 150 * ms, 190 * ms),
           ("%strom_window_prefill.2 = bf16[1,8,8,4096,128]{4,3,2,1,0} "
            "custom-call(...)", 190 * ms, 200 * ms),
           ("%strom_kv_prefill.3 = bf16[1,4,16,2048,128]{4,3,2,1,0} "
            "custom-call(...)", 260 * ms, 270 * ms)]
    plane = "/device:TPU:0"
    return xplane.Trace(
        ops={plane: step + pre},
        modules={plane: [("jit__paged_step(1)", 0, 10 * ms),
                         ("jit__paged_prefill(2)", 50 * ms, 250 * ms),
                         ("jit__paged_prefill(4)", 260 * ms, 300 * ms),
                         ("jit_other(3)", 310 * ms, 311 * ms)]})


NEW = ("swa_step_roofline.mimo", "swa_prefill_mfu.mimo",
       "full_attn_roofline.mimo", "window_attn_roofline.mimo",
       "kv_prefill_roofline.mimo", "window_attn_share.mimo")


def test_new_readers_on_a_synthetic_trace():
    ctx = _ctx(_synthetic_trace(), timings=TIMINGS)
    read = lambda name: harness.plugin("layer_metrics", name).read(ctx)  # noqa
    nbytes, _ = costs_swa.attn_cost(HF, "full", SLOTS, LIVE)
    # two calls of the full kernel in the step: 2 x least over (2 + 2) ms
    assert read("full_attn_roofline.mimo") == pytest.approx(
        100 * 2 * (nbytes / 819e9) / 4e-3)
    wbytes, _ = costs_swa.attn_cost(HF, "window", SLOTS, SLOTS * 128.0)
    assert read("window_attn_roofline.mimo") == pytest.approx(
        100 * 2 * (wbytes / 819e9) / 0.5e-3)
    assert read("window_attn_share.mimo") == pytest.approx(100 * 0.5 / 10)
    step = costs_swa.decode_step_bytes(HF, SLOTS, LIVE, 78.0, SLOTS * 128.0)
    assert read("swa_step_roofline.mimo") == pytest.approx(
        100 * (step / 819e9) / 10e-3)
    lengths = (2048, 4096, 8192, 16384)
    ops = np.mean([costs_swa.prefill_flops(HF, n, 3.0 * n) for n in lengths])
    assert read("swa_prefill_mfu.mimo") == pytest.approx(
        100 * 2 * ops / 0.24 / 197e12)
    attn = np.mean([sum(costs_swa.prefill_attn_flops(HF, n).values())
                    for n in lengths])
    # both prefills ran a kernel: 40 + 10 + 10 ms of kernels
    assert read("kv_prefill_roofline.mimo") == pytest.approx(
        100 * 2 * attn / 0.06 / 197e12)
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
                _ctx(_synthetic_trace(), dense, TIMINGS),
                _ctx(empty, HF, old)):
        assert reader.read(ctx) is None
    if name != "swa_step_roofline.mimo":    # (which reads no kernel's time)
        assert reader.read(_ctx(empty, HF, TIMINGS)) is None


# -- the file ------------------------------------------------------------------

def test_config_file_holds_the_catalog_rows_numbers():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2.5")
    for key, value in row["config"].items():
        if key not in HF["reduced"]:
            assert HF[key] == value, key
    assert HF["source"] == row["source_url"]
    assert set(HF["reduced"]) == {
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size", "max_position_embeddings"}
    assert HF["published"] == {k: row["config"][k] for k in HF["reduced"]}
    assert HF["hybrid_layer_pattern"] == row["config"][
        "hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1]
    assert HF["moe_layer_freq"] == row["config"]["moe_layer_freq"][:7]
    assert HF["expert_share"]["routed"] == row["config"]["n_routed_experts"]
    assert HF["n_routed_experts"] * HF["expert_share"]["chips"] == 256
    assert HF["vocab_size"] * HF["vocab_share"]["chips"] == 152576
    bench = harness.load_json("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "mimo-v2.5")
    assert set(entry["reduced"]) == set(HF["reduced"])
    assert entry["source"] == row["source_url"]
    cell = next(w for w in bench["workloads"] if w["config"] == "mimo-v2.5")
    for text in (entry["why"], entry["source"], cell["why"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    sv = HF["serving"]
    assert sv["total_blocks"] == sv["slots"] * -(-sv["max_len"]
                                                 // sv["block_len"])
    assert sv["max_len"] == HF["max_position_embeddings"] == 16384 + 1024
    traffic = harness.load_json("benchmark", "traffic", "flood16k.json")
    assert traffic["prompts"] == [2048, 4096, 8192, 16384]
    assert traffic["budgets"] == [512, 768, 1024]
    assert (traffic["requests"], traffic["lookahead"], traffic["kind"],
            traffic["runner"]) == (1024, 8, "closed_queue", "serve_swa")


def test_the_parent_commit_is_turned_away_at_once(monkeypatch):
    """A checkout whose ``config_from_hf`` cannot read the file, or reads it
    as a dense decoder, exits before a weight is drawn."""
    from benchmark.runners import serve_swa
    from nvme_strom_tpu.tools import convert_llama

    def refuses(hf):
        raise ValueError("unsupported explicit head_dim=192")
    monkeypatch.setattr(convert_llama, "config_from_hf", refuses)
    with pytest.raises(SystemExit, match="cannot read a mimo_v2"):
        serve_swa.run(types.SimpleNamespace(config=TINY_HF))
    monkeypatch.setattr(convert_llama, "config_from_hf",
                        lambda hf: types.SimpleNamespace(n_layers=7))
    with pytest.raises(SystemExit, match="does not serve window attention"):
        serve_swa.run(types.SimpleNamespace(config=TINY_HF))

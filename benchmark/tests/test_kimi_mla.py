"""The Kimi-K2 configuration's side of the yardstick, on the CPU at a tiny
size: the generator's bits, the cost functions against the tensor list and
hand counts, the plain reference's own properties and its controls, the new
cell end to end through ``run.execute`` (sound, and with the latent rows not
carried into decode, which must come out as not correct), the new readers
on a synthetic trace, and the file against the catalog."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import costs_mla, harness, run, xplane
from benchmark import weights_mla as WM
from benchmark.reference import kimi_mla as ref
from benchmark.tools import control_mla

HF = harness.load_json("benchmark", "configs", "kimi-k2.7-code.json")
TINY = dict(hidden_size=64, vocab_size=256, num_attention_heads=4,
            num_key_value_heads=4, intermediate_size=128,
            moe_intermediate_size=32, n_routed_experts=4,
            expert_share={"routed": 16, "offset": 4, "chips": 4},
            num_experts_per_tok=4, num_hidden_layers=3, q_lora_rank=24,
            kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
            v_head_dim=8, max_position_embeddings=256,
            serving=dict(slots=4, max_len=256, block_len=16, total_blocks=64),
            # sound runs read 0.011 - 0.015 and 0.3 - 0.6 here (bf16 at width
            # 64, logits up to 4.2, a routing flip or two in 60 tokens);
            # without the carried latents the mean is 2.2 - 2.3
            correct=dict(served_mean_gap_limit=0.1,
                         served_max_gap_limit=1.5))
TRAFFIC = dict(requests=40, prompts=[16, 32, 48, 64], budgets=[8, 12],
               lookahead=4)
TINY_HF = {**HF, **TINY}


def _run(seed=2**31 + 77, trace=0, **test):
    test = dict(allow_cpu=True, config=TINY, traffic=TRAFFIC, **test)
    return run.execute(["--workload", "k2c.flood8k", "--seed", str(seed),
                        "--seconds", "3", "--trace", str(trace)], test=test)


# -- the generator -----------------------------------------------------------

def test_generator_bits_are_the_same_in_numpy_and_under_jit():
    import jax
    specs = WM.tensor_specs(TINY_HF)
    params = WM.make_params(TINY_HF, 2**31 + 5)
    assert set(params) == {n for n, _ in specs}
    for i, (name, shape) in enumerate(specs):
        want = WM.make_tensor_np(2**31 + 5, i, name, shape)
        got = np.asarray(jax.device_get(params[name]))
        assert got.dtype == want.dtype and got.shape == tuple(shape)
        np.testing.assert_array_equal(got.view(np.uint16),
                                      want.view(np.uint16), err_msg=name)
    again = WM.make_params(TINY_HF, 2**31 + 5)
    other = WM.make_params(TINY_HF, 2**31 + 6)
    name = "layers.1.wkv_b"
    assert (np.asarray(again[name]) == np.asarray(params[name])).all()
    assert (np.asarray(other[name]) != np.asarray(params[name])).any()


def test_one_experts_slice_is_the_stacked_tensors_slice():
    import jax
    shape = WM.layer_shapes(TINY_HF)["moe_w_down"]
    whole = np.asarray(jax.jit(lambda b: WM.make_tensor(
        b, "moe_w_down", shape))(np.uint32(77)))
    n = shape[1] * shape[2]
    one = np.asarray(jax.jit(lambda b, e: WM.make_tensor(
        b, "moe_w_down", shape[1:], e * np.uint32(n)))(
            np.uint32(77), np.uint32(2)))
    np.testing.assert_array_equal(one.view(np.uint16),
                                  whole[2].view(np.uint16))


def test_the_layout_is_the_programs():
    """Every leaf ``init_params`` makes for the config, at its shape."""
    import jax

    from nvme_strom_tpu.models.transformer import init_params
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    cfg = config_from_hf(TINY_HF)
    want = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    got = dict(WM.tensor_specs(TINY_HF))
    assert {k: tuple(v.shape) for k, v in want.items()} == got


# -- the costs ---------------------------------------------------------------

def test_parameter_count_is_the_sum_over_the_tensor_list():
    for hf in (HF, TINY_HF):
        total = sum(int(np.prod(s)) for _, s in WM.tensor_specs(hf))
        assert costs_mla.param_count(hf)["total"] == total
    p = costs_mla.param_count(HF)
    # by hand, at the published widths: the issue's arithmetic
    assert p["attn"] == (7168 * 1536 + 1536 + 1536 * 64 * 192 + 7168 * 576
                         + 512 + 512 * 64 * 256 + 64 * 128 * 7168 + 7168)
    assert round(p["attn"] / 1e6, 2) == 101.13
    assert p["expert"] == 3 * 7168 * 2048 == 44_040_192
    assert p["total"] * 2 / 2**30 == pytest.approx(6.513, abs=2e-3)


def test_cache_and_kernel_costs_by_hand():
    assert costs_mla.latent_bytes_per_token(HF) == 5 * 576 * 2 == 5760
    nbytes, flops = costs_mla.mla_attn_cost(HF, 64, 256_000.0)
    assert flops == 256_000 * 139_264          # 2 x 64 x (576 + 512) a row
    assert nbytes == (256_000 * 576 + 64 * 64 * (576 + 512)) * 2
    # 121 operations a byte: under the v5e's ridge of 240, the bytes bound it
    assert 115 < flops / nbytes < 121
    assert nbytes / 819e9 > flops / 197e12


def test_decode_step_bytes_follow_touched_experts_and_live_rows():
    base = costs_mla.decode_step_bytes(HF, 64, 0.0, 0.0)
    p = costs_mla.param_count(HF)
    assert base == (p["outside_experts"] + 64 * 7168) * 2
    assert costs_mla.decode_step_bytes(HF, 64, 1000.0, 3.0) - base == \
        3 * p["expert"] * 2 + 1000 * 5760


def test_prefill_flops_count_the_causal_half_once():
    one = costs_mla.prefill_flops(HF, 1, 0.0)
    p = costs_mla.param_count(HF)
    assert one == 2.0 * p["outside_experts"] + 2.0 * 5 * 64 * 320
    n = 8192
    attn = (costs_mla.prefill_flops(HF, n, 0.0)
            - 2.0 * (n * (p["outside_experts"] - p["head"]) + p["head"]))
    assert attn == 2.0 * 5 * 64 * 320 * n * (n + 1) / 2
    assert costs_mla.prefill_flops(HF, n, 10.0) \
        - costs_mla.prefill_flops(HF, n, 0.0) == 20.0 * p["expert"]


# -- the reference ------------------------------------------------------------

def test_reference_padding_is_inert_and_each_control_is_another_answer():
    rng = np.random.default_rng(3)
    toks = rng.integers(0, 256, (2, 24)).astype(np.int32)
    at = np.tile(np.arange(8, 20)[None], (2, 1))
    base = np.asarray(ref.logits_at(TINY_HF, 5, toks, at))
    padded = np.concatenate([toks[:, :20], np.zeros((2, 12), np.int32)], 1)
    np.testing.assert_allclose(
        np.asarray(ref.logits_at(TINY_HF, 5, padded, at)), base, atol=1e-5)
    for low in control_mla.CONTROLS:
        other = np.asarray(ref.logits_at(TINY_HF, 5, toks, at, low=low))
        assert np.abs(other - base).max() > 1e-3, low


def test_reference_weighs_over_all_the_selected_experts():
    """The weights of a row sum to the scaling factor over ALL 16 experts;
    the held four get their part of it, and ``norm_held`` all of it."""
    import jax.numpy as jnp
    z = WM.sizes(TINY_HF)
    rng = np.random.default_rng(1)
    w = {"router": jnp.asarray(rng.normal(size=(64, 16)), jnp.float32),
         "router_bias": jnp.zeros((16,), jnp.float32)}
    h = jnp.asarray(rng.normal(size=(5, 64)), jnp.float32)
    wt = np.asarray(ref.routing(h, w, TINY_HF))
    assert ((wt > 0).sum(-1) == z["k"]).all()
    np.testing.assert_allclose(wt.sum(-1), 2.827, rtol=1e-5)
    held = np.asarray(ref.routing(h, w, TINY_HF, low="norm_held"))
    assert (held[:, :4] == 0).all() and (held[:, 8:] == 0).all()
    some = held.sum(-1) > 0
    np.testing.assert_allclose(held.sum(-1)[some], 2.827, rtol=1e-5)


# -- the cell, end to end ----------------------------------------------------

def test_cell_end_to_end_is_correct():
    out, ctx = _run()
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"tok_s", "setup_s"}
    assert ctx.facts["compiles_in_window"] == 0
    t = ctx.facts["timings"]
    assert t["moe_pairs_routed_prefill"] == t["prompt_tokens"] * 4 * 2
    assert 0 < t["moe_pairs"] < t["moe_pairs_routed"]
    assert t["moe_calls"] == 2 * t["steps"]


def test_latents_not_carried_into_decode_are_not_correct():
    out, _ = _run(server_built=control_mla.drop_latents)
    assert out["correct"] is False
    assert out["failed"] == 0               # every budget still returned


def test_traced_run_reports_the_counters_and_leaves_the_device_out():
    out, _ = _run(trace=1)
    assert out["correct"] is True
    assert {"admit_share.flood", "prefill_share.flood", "prefill_pad_share.flood",
            "compiles_in_window.flood", "prefill_batch_mean.flood",
            "moe_local_pair_share.flood"} <= set(out["metrics"])
    assert "mla_attn_roofline.k2c" not in out["metrics"]
    assert 5 < out["metrics"]["moe_local_pair_share.flood"]["value"] < 60


# -- the new readers ---------------------------------------------------------

def _ctx(trace, config=HF, timings=None):
    peaks = harness.load_json("benchmark", "peaks.json")["TPU v5 lite"]
    return types.SimpleNamespace(
        trace=trace, config=config, peaks=peaks,
        traffic={"prompts": [1024, 2048, 4096, 8192]},
        facts={"slots": 64, "live_tokens": 256_000.0, "timings": timings})


#: a window of 100 steps and 24 admissions, 4 expert layers
TIMINGS = {"steps": 100, "moe_calls": 400, "moe_pairs": 6_400,
           "moe_pairs_routed": 204_800, "moe_experts_touched": 3_600,
           "prompt_tokens": 92_160, "moe_pairs_prefill": 92_160,
           "moe_pairs_routed_prefill": 2_949_120, "prefill_calls": 24}


def _synthetic_trace():
    ms = 1_000_000
    attn = "%strom_mla_attn.{} = bf16[64,64,512]{{2,1,0}} custom-call(...)"
    step = [("%fusion.1 = bf16[64,7168]{1,0} fusion(...)", 0, ms),
            ("%strom_latent_write.1 = bf16[5,4225,576,128]{3,2,1,0} "
             "custom-call(...)", 1 * ms, 1.2 * ms),
            (attn.format(2), 2 * ms, 3 * ms),
            # the consumer of the kernel's result names it among its operands
            ("%fusion.2 = bf16[64,8192]{1,0} fusion(bf16[64,64,512]{2,1,0} "
             "%strom_mla_attn.2, ...)", 3 * ms, 4 * ms),
            (attn.format(3), 5 * ms, 6.5 * ms)]
    pre = [("%fusion.9 = bf16[8192,7168]{1,0} fusion(...)", 50 * ms,
            250 * ms),
           ("%fusion.10 = bf16[1024,7168]{1,0} fusion(...)", 260 * ms,
            300 * ms)]
    plane = "/device:TPU:0"
    return xplane.Trace(
        ops={plane: step + pre},
        modules={plane: [("jit__paged_step(1)", 0, 10 * ms),
                         ("jit__paged_prefill(2)", 50 * ms, 250 * ms),
                         ("jit__paged_prefill(4)", 260 * ms, 300 * ms),
                         ("jit_other(3)", 310 * ms, 311 * ms)]})


def test_new_readers_on_a_synthetic_trace():
    ctx = _ctx(_synthetic_trace(), timings=TIMINGS)
    read = lambda name: harness.plugin("layer_metrics", name).read(ctx)  # noqa
    nbytes, _ = costs_mla.mla_attn_cost(HF, 64, 256_000.0)
    # two calls of the kernel in the step: 2 x least over (1 + 1.5) ms
    assert read("mla_attn_roofline.k2c") == pytest.approx(
        100 * 2 * (nbytes / 819e9) / 2.5e-3)
    assert read("mla_attn_share.k2c") == pytest.approx(100 * 2.5 / 10)
    step = costs_mla.decode_step_bytes(HF, 64, 256_000.0, touched=36.0)
    assert read("mla_step_roofline.k2c") == pytest.approx(
        100 * (step / 819e9) / 10e-3)
    # busy: the step's 4.7 ms of operations and the prefills' 240 ms
    assert read("prefill_dev_share.flood") == pytest.approx(
        100 * 240 / 244.7)
    ops = np.mean([costs_mla.prefill_flops(HF, n, 1.0 * n)
                   for n in (1024, 2048, 4096, 8192)])
    assert read("prefill_mfu.k2c") == pytest.approx(
        100 * 2 * ops / 0.24 / 197e12)
    assert read("moe_local_pair_share.flood") == pytest.approx(
        100 * 6_400 / 204_800)
    for name in ("mla_attn_roofline.k2c", "mla_step_roofline.k2c"):
        assert 0 < read(name) < 100, name


@pytest.mark.parametrize("name", [
    "mla_attn_roofline.k2c", "mla_attn_share.k2c", "mla_step_roofline.k2c",
    "prefill_mfu.k2c", "moe_local_pair_share.flood"])
def test_new_readers_find_nothing_where_there_is_nothing(name):
    """No trace, a trace without the kernel (the parent's), a program
    without the counters, and a configuration of another family: None,
    never an exception."""
    reader = harness.plugin("layer_metrics", name)
    dense = harness.load_json("benchmark", "configs", "mistral-7b-v0.3.json")
    empty = xplane.Trace(
        ops={"/device:TPU:0": [("%fusion.1 = bf16[8]{0} fusion()", 0, 9)]},
        modules={"/device:TPU:0": [("jit__paged_step(1)", 0, 9)]})
    old = {"steps": 100, "admit_s": 1.0}            # the parent's timings
    for ctx in (_ctx(None), _ctx(empty, dense, old), _ctx(None, timings=old),
                _ctx(empty, dense), _ctx(None, dense, old)):
        assert reader.read(ctx) is None
    assert harness.plugin("layer_metrics", "prefill_dev_share.flood").read(
        _ctx(None)) is None


# -- the file ------------------------------------------------------------------

def test_config_file_holds_the_catalog_rows_numbers():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-K2.7-Code")
    for key, value in row["config"].items():
        if key not in HF["reduced"]:
            assert HF[key] == value, key
    assert HF["source"] == row["source_url"]
    assert set(HF["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                  "vocab_size", "max_position_embeddings"}
    assert HF["published"] == {k: row["config"][k] for k in HF["reduced"]}
    assert HF["expert_share"]["routed"] == row["config"]["n_routed_experts"]
    assert HF["n_routed_experts"] * HF["expert_share"]["chips"] == 384
    assert HF["vocab_size"] * HF["vocab_share"]["chips"] == 163840
    bench = harness.load_json("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "kimi-k2.7-code")
    assert set(entry["reduced"]) == set(HF["reduced"])
    sv = HF["serving"]
    assert sv["total_blocks"] == sv["slots"] * -(-sv["max_len"]
                                                 // sv["block_len"])


def test_the_parent_commit_is_turned_away_at_once(monkeypatch):
    """A checkout whose ``config_from_hf`` cannot read the file, or reads it
    as a dense decoder, exits before a weight is drawn."""
    from benchmark.runners import serve_mla
    from nvme_strom_tpu.tools import convert_llama

    def refuses(hf):
        raise ValueError("unsupported rope_scaling type 'yarn'")
    monkeypatch.setattr(convert_llama, "config_from_hf", refuses)
    with pytest.raises(SystemExit, match="cannot read a kimi_k2"):
        serve_mla.run(types.SimpleNamespace(config=TINY_HF))
    monkeypatch.setattr(convert_llama, "config_from_hf",
                        lambda hf: types.SimpleNamespace(n_layers=5))
    with pytest.raises(SystemExit, match="does not serve latent attention"):
        serve_mla.run(types.SimpleNamespace(config=TINY_HF))

"""The LFM2-MoE configuration's side of the yardstick, on the CPU at a tiny
size: the generator's bits and the load its router bias gives, the cost
functions against the tensor list and hand counts, the plain reference's own
properties, the new cell end to end through ``run.execute`` (sound, and with
each of the three broken timed paths of ``tools/control_moe.py``, which must
come out as not correct), and the new readers on a synthetic trace."""

import json
import os

import numpy as np
import pytest

from benchmark import costs_moe, harness, run, xplane
from benchmark import weights_moe as WM
from benchmark.reference import lfm2_moe as ref
from benchmark.tools import control_moe

HF = harness.load_json("benchmark", "configs", "lfm2-24b-a2b.json")
TINY = dict(hidden_size=64, vocab_size=256, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=128,
            moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
            num_hidden_layers=8,
            layer_types=["conv", "conv", "full_attention", "conv"] * 2,
            max_position_embeddings=256,
            serving=dict(slots=4, max_len=256, block_len=16, total_blocks=64),
            # sound runs read 5.8e-4 - 6.2e-4 and 7e-3 - 1.3e-2 here (bf16 at
            # width 64, logits of the order of 0.03); the broken paths'
            # means 1.7e-3 - 3.5e-3 (no bias), 3.2e-3 - 4.7e-3 (capacity),
            # 7e-3 - 9e-3 (no tail)
            correct=dict(served_mean_gap_limit=1.2e-3,
                         served_max_gap_limit=0.05))
TRAFFIC = dict(requests=40, prompts=[16, 32, 48, 64], budgets=[8, 12],
               lookahead=4)
TINY_HF = {**HF, **TINY}


def _run(seed=2**31 + 77, trace=0, **test):
    test = dict(allow_cpu=True, config=TINY, traffic=TRAFFIC, **test)
    return run.execute(["--workload", "lfm2.flood", "--seed", str(seed),
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


def test_one_experts_slice_is_the_stacked_tensors_slice():
    """The reference draws an expert layer expert by expert: the slice drawn
    alone is the stacked tensor's."""
    import jax
    shape = WM.layer_shapes(TINY_HF)["moe_w_down"]
    base = np.uint32(12345)
    whole = np.asarray(jax.jit(
        lambda b: WM.make_tensor(b, "moe_w_down", shape))(base))
    n = shape[1] * shape[2]
    for e in (0, 3, 7):
        part = np.asarray(jax.jit(lambda b, first: WM.make_tensor(
            b, "moe_w_down", shape[1:], first))(base, np.uint32(e * n)))
        np.testing.assert_array_equal(part.view(np.uint16),
                                      whole[e].view(np.uint16))


def test_the_router_bias_loads_the_experts_unevenly():
    """At the published router (2048 -> 64, top-4) with the generator's
    bias, rows of unit RMS load a layer's busiest expert with 2-3x the mean
    and its idlest with under half: what PERF.md section 4 quotes from the
    chip."""
    idx = WM.layer_indices(HF)
    shapes = WM.layer_shapes(HF)
    rng = np.random.default_rng(3)
    h = rng.standard_normal((4096, 2048)).astype(np.float32)
    ratios = []
    for layer in (2, 7, 11):
        def leaf(name):
            full = f"layers.{layer}.{name}"
            return WM.make_tensor_np(17, idx[full], full,
                                     shapes[name]).astype(np.float32)
        s = 1.0 / (1.0 + np.exp(-(h @ leaf("router"))))
        sel = np.argsort(-(s + leaf("router_bias")), axis=1)[:, :4]
        load = np.bincount(sel.reshape(-1), minlength=64)
        ratios.append((load.max() / load.mean(), load.min() / load.mean()))
    assert all(2.0 <= hi <= 3.5 and lo < 0.5 for hi, lo in ratios), ratios


# -- the cost functions ------------------------------------------------------

def test_parameter_count_is_the_sum_over_the_tensor_list():
    total = sum(int(np.prod(s, dtype=np.int64))
                for _, s in WM.tensor_specs(HF))
    p = costs_moe.param_count(HF)
    assert p["total"] == total
    assert (p["n_conv"], p["n_attn"], p["n_dense"],
            p["n_expert_layers"]) == (9, 3, 2, 10)
    assert p["expert"] == 3 * 2048 * 1536 == 9_437_184
    assert 12.10 < total * 2 / 2**30 < 12.12            # GiB in bf16
    assert 640 * p["expert"] / total > 0.92             # the experts' share


def test_cache_and_state_bytes_by_hand():
    assert costs_moe.kv_bytes_per_token(HF) == 3 * 2 * 8 * 64 * 2 == 6144
    assert costs_moe.state_bytes_per_slot(HF) == 9 * 2 * 2048 * 2


def test_experts_cost_counts_touched_experts_once_and_every_pair():
    nbytes, flops = costs_moe.experts_cost(HF, rows=512, touched=60)
    assert flops == 2.0 * 512 * 9_437_184
    assert nbytes == (60 * 9_437_184 + 512 * (2 * 2048 + 2 * 1536)) * 2
    # memory-bound at a decode step's 512 pairs, and still at 4,096
    for rows in (512, 4096):
        b, f = costs_moe.experts_cost(HF, rows, 64)
        assert b / 819e9 > f / 197e12


def test_decode_step_bytes_follow_the_touched_experts():
    some = costs_moe.decode_step_bytes(HF, 128, 80_000.0, touched=600.0)
    every = costs_moe.decode_step_bytes(HF, 128, 80_000.0, touched=640.0)
    assert every - some == 40 * 9_437_184 * 2
    p = costs_moe.param_count(HF)
    assert every == pytest.approx(
        (p["total"] + 128 * 2048) * 2 + 2 * 128 * 73_728 + 80_000 * 6144)
    flops = costs_moe.decode_step_flops(HF, 128, 80_000.0)
    assert flops > 2.0 * 128 * 10 * 4 * 9_437_184       # the experts' part


# -- the plain reference -----------------------------------------------------

def test_reference_padding_is_inert_and_int8_is_another_answer():
    rng = np.random.default_rng(4)
    toks = rng.integers(0, 256, (2, 24)).astype(np.int32)
    at = np.broadcast_to(np.arange(16), (2, 16)).copy()
    full = np.asarray(ref.logits_at(TINY_HF, 9, toks, at))
    assert full.shape == (2, 16, 256) and np.isfinite(full).all()
    padded = toks.copy()
    padded[:, 16:] = 0                       # causal: what follows is inert
    np.testing.assert_array_equal(
        np.asarray(ref.logits_at(TINY_HF, 9, padded, at)), full)
    low = np.asarray(ref.logits_at(TINY_HF, 9, toks, at, low="int8"))
    assert np.abs(low - full).max() > 1e-3 * np.abs(full).max()


def test_reference_routing_weighs_by_the_unbiased_scores():
    import jax.numpy as jnp
    w = {"router": jnp.eye(4, 8), "router_bias": jnp.asarray(
        [0., 0, 0, 0, 5, 5, 0, 0])}
    hf = dict(TINY_HF, num_experts=8, num_experts_per_tok=2)
    h = jnp.asarray([[3.0, 0, 0, 0]])
    chosen, wt = ref.routing(h, w, hf)
    assert np.asarray(chosen)[0].tolist() == [0, 0, 0, 0, 1, 1, 0, 0]
    # experts 4 and 5 were chosen by the bias; both score sigmoid(0) = 0.5
    np.testing.assert_allclose(np.asarray(wt)[0, 4:6], [0.5, 0.5], atol=1e-5)


# -- the cell, end to end ----------------------------------------------------

def test_cell_end_to_end_is_correct():
    out, ctx = _run()
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"tok_s", "setup_s"}
    assert ctx.facts["compiles_in_window"] == 0
    t = ctx.facts["timings"]
    # no pair dropped: the valid rows x k of every expert layer
    assert t["moe_pairs_prefill"] == t["prompt_tokens"] * 2 * 6
    assert t["moe_pairs"] <= 4 * 2 * t["moe_calls"]
    assert t["moe_calls"] == 6 * t["steps"]
    assert t["moe_rows_computed"] > t["moe_pairs"] > 0


@pytest.fixture
def restore_route():
    from nvme_strom_tpu.models import moe
    import jax
    inner = moe.route
    jax.clear_caches()          # a broken path is traced into the programs
    yield
    moe.route = inner
    jax.clear_caches()


@pytest.mark.parametrize("path", ["capacity", "nobias", "notail"])
def test_broken_timed_paths_are_not_correct(path, restore_route):
    """The timed path broken where a new mechanism lives
    (``tools/control_moe.py``): the capacity-dropping rule in the exact
    layer's place, the bias left out of the selection, the conv tail not
    carried from prefill into decode."""
    test = {}
    if path == "capacity":
        # 8 experts, top-2 and 4 slots load no expert far over the mean, so
        # the cell's own factor 1.25 drops next to nothing here: half the
        # mean load is a capacity that binds at this size
        control_moe.drop_over_capacity(factor=0.5)
    elif path == "nobias":
        control_moe.leave_bias_out()
    else:
        test["server_built"] = control_moe.drop_tail
    out, _ = _run(**test)
    assert out["correct"] is False
    assert out["failed"] == 0               # every budget still returned


def test_traced_run_reports_the_cells_per_layer_metrics():
    """On the CPU the trace has no device plane: the device readers return
    nothing and the line leaves them out; the counters are there."""
    out, _ = _run(trace=1)
    assert out["correct"] is True
    assert {"admit_share.flood", "prefill_share.flood",
            "prefill_pad_share.flood", "compiles_in_window.flood",
            "moe_load_max_over_mean.lfm2", "moe_tile_pad_share.lfm2"} \
        <= set(out["metrics"])
    assert "moe_experts_roofline.flood" not in out["metrics"]
    assert 1.0 <= out["metrics"]["moe_load_max_over_mean.lfm2"]["value"] <= 8
    assert 0 < out["metrics"]["moe_tile_pad_share.lfm2"]["value"] < 100


# -- the new readers ---------------------------------------------------------

def _ctx(trace, config=HF, timings=None):
    import types
    peaks = harness.load_json("benchmark", "peaks.json")["TPU v5 lite"]
    return types.SimpleNamespace(
        trace=trace, config=config, peaks=peaks,
        facts={"slots": 128, "live_tokens": 80_000.0, "timings": timings})


#: a window of 100 steps and 20 admissions: 10 expert layers a call
TIMINGS = {"steps": 100, "moe_calls": 1000, "moe_pairs": 512_000,
           "moe_experts_touched": 60_000, "moe_rows_computed": 1_040_000,
           "moe_load_max": 20_000, "moe_calls_prefill": 200,
           "moe_pairs_prefill": 384_000, "moe_experts_touched_prefill": 12_800}


def _synthetic_trace():
    ms = 1_000_000
    gmm = "%strom_moe_gmm.{} = bf16[1536,{}]{{1,0}} custom-call(...)"
    scores = ("%broadcast_add_fusion.3 = (f32[128,64]{0,1}, f32[128,64]{0,1})"
              " fusion(...)")
    step = [("%fusion.1 = bf16[128,2048]{1,0} fusion(...)", 0, ms),
            (scores, 1 * ms, 1.1 * ms),
            ("%sort.1 = (f32[128,64]{0,1}, s32[128,64]{0,1}) sort(...)",
             1.1 * ms, 1.5 * ms),
            (gmm.format(20, 1536), 2 * ms, 4 * ms),
            (gmm.format(21, 2048), 4 * ms, 5 * ms),
            # the consumer of a kernel's result names it among its operands
            ("%fusion.2 = bf16[128,2048]{1,0} fusion(bf16[1536,2048]{1,0} "
             "%strom_moe_gmm.21, ...)", 5 * ms, 6 * ms),
            (scores, 6 * ms, 6.2 * ms),
            (gmm.format(22, 1536), 6.5 * ms, 8.5 * ms),
            (gmm.format(23, 2048), 8.5 * ms, 9.5 * ms)]
    pre = [(gmm.format(4, 1536), 50 * ms, 53 * ms),
           (gmm.format(5, 2048), 53 * ms, 54 * ms)]
    plane = "/device:TPU:0"
    return xplane.Trace(
        ops={plane: step + pre},
        modules={plane: [("jit__paged_step(1)", 0, 40 * ms),
                         ("jit__paged_prefill(2)", 49 * ms, 55 * ms),
                         ("jit_other(3)", 60 * ms, 61 * ms)]})


def test_new_readers_on_a_synthetic_trace():
    ctx = _ctx(_synthetic_trace(), timings=TIMINGS)
    read = lambda name: harness.plugin("layer_metrics", name).read(ctx)  # noqa
    nbytes, flops = costs_moe.experts_cost(HF, 512.0, 60.0)
    assert nbytes / 819e9 > flops / 197e12
    # two layers' calls in the step: 2 x least over (2 + 1 + 2 + 1) ms
    assert read("moe_experts_roofline.flood") == pytest.approx(
        100 * 2 * (nbytes / 819e9) / 6e-3)
    pb, pf = costs_moe.experts_cost(HF, 1920.0, 64.0)
    assert read("moe_prefill_experts_roofline.flood") == pytest.approx(
        100 * max(pb / 819e9, pf / 197e12) / 4e-3)
    assert read("moe_experts_share.lfm2") == pytest.approx(100 * 6 / 40)
    # from the first mention of the scores to the layer's first product
    assert read("moe_route_share.lfm2") == pytest.approx(
        100 * (1.0 + 0.5) / 40)
    step = costs_moe.decode_step_bytes(HF, 128, 80_000.0, touched=600.0)
    assert read("moe_step_roofline.lfm2") == pytest.approx(
        100 * (step / 819e9) / 40e-3)
    assert read("moe_load_max_over_mean.lfm2") == pytest.approx(
        20_000 * 64 / 512_000)
    assert read("moe_tile_pad_share.lfm2") == pytest.approx(
        100 * (1 - 512_000 / 1_040_000))
    # a share of a roofline stays under 100 % for times a chip could give
    for name in ("moe_experts_roofline.flood", "moe_step_roofline.lfm2",
                 "moe_prefill_experts_roofline.flood"):
        assert 0 < read(name) < 100, name


@pytest.mark.parametrize("name", [
    "moe_step_roofline.lfm2", "moe_experts_roofline.flood",
    "moe_prefill_experts_roofline.flood", "moe_experts_share.lfm2",
    "moe_route_share.lfm2", "moe_load_max_over_mean.lfm2",
    "moe_tile_pad_share.lfm2"])
def test_new_readers_find_nothing_where_there_is_nothing(name):
    """No trace, a trace without the kernel (the parent's), a program without
    the counters, and a dense configuration: None, never an exception."""
    reader = harness.plugin("layer_metrics", name)
    dense = harness.load_json("benchmark", "configs", "mistral-7b-v0.3.json")
    empty = xplane.Trace(
        ops={"/device:TPU:0": [("%fusion.1 = bf16[8]{0} fusion()", 0, 9)]},
        modules={"/device:TPU:0": [("jit__paged_step(1)", 0, 9)]})
    old = {"steps": 100, "admit_s": 1.0}            # the parent's timings
    for ctx in (_ctx(None), _ctx(empty), _ctx(empty, dense),
                _ctx(empty, timings=old), _ctx(empty, dense, old),
                _ctx(None, timings=old)):
        assert reader.read(ctx) is None


def test_config_file_holds_the_catalog_rows_numbers():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    for key, value in row["config"].items():
        if key == "layer_types":
            assert HF[key] == value[:12]
        elif key not in HF["reduced"]:
            assert HF[key] == value, key
    assert HF["source"] == row["source_url"]
    assert set(HF["reduced"]) == {"num_hidden_layers",
                                  "max_position_embeddings"}
    assert HF["published"] == {k: row["config"][k] for k in HF["reduced"]}
    bench = harness.load_json("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-24b-a2b")
    assert set(entry["reduced"]) == set(HF["reduced"])


def test_the_parent_commit_is_turned_away_at_once(monkeypatch):
    """A checkout whose ``config_from_hf`` reads the file as a dense decoder
    (no ``expert_layers``) exits before a weight is drawn."""
    import types

    from benchmark.runners import serve_moe
    from nvme_strom_tpu.tools import convert_llama
    monkeypatch.setattr(convert_llama, "config_from_hf",
                        lambda hf: types.SimpleNamespace(n_layers=12))
    with pytest.raises(SystemExit, match="does not serve"):
        serve_moe.run(types.SimpleNamespace(config=TINY_HF))

"""The hybrid configuration's side of the yardstick, on the CPU at a tiny
size: the generator's bits, the cost functions against the tensor list, the
plain reference against a hand-rolled Python loop, the new cell end to end
through ``run.execute`` (sound, and with the state dropped between prefill
and decode, which must come out as not correct), and the new readers on a
synthetic trace."""

import json
import os

import numpy as np
import pytest

from benchmark import costs_hybrid, harness, run, xplane
from benchmark import weights_hybrid as WH
from benchmark.reference import granite_hybrid as ref
from benchmark.tools.control_hybrid import drop_state

HF = harness.load_json("benchmark", "configs", "granite-4.0-h-micro.json")
TINY = dict(hidden_size=64, vocab_size=128, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=128,
            shared_intermediate_size=128, num_hidden_layers=4,
            max_position_embeddings=256,
            layer_types=["mamba", "mamba", "attention", "mamba"],
            mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
            mamba_chunk_size=16, attention_multiplier=1 / 16,
            serving=dict(slots=4, max_len=256, block_len=16, total_blocks=64),
            # sound runs read 5e-7 and 3e-5 here, the dropped state 6e-4 and
            # 4e-3 (logits are of the order of 0.004 at this size)
            correct=dict(served_mean_gap_limit=2e-5,
                         served_max_gap_limit=3e-4))
TRAFFIC = dict(requests=40, prompts=[16, 32, 48, 64], budgets=[8, 12],
               lookahead=4)
TINY_HF = {**HF, **TINY}


def _run(seed=2**31 + 77, trace=0, **test):
    test = dict(allow_cpu=True, config=TINY, traffic=TRAFFIC, **test)
    return run.execute(["--workload", "g4hm.flood", "--seed", str(seed),
                        "--seconds", "3", "--trace", str(trace)], test=test)


# -- the generator -----------------------------------------------------------

def test_generator_bits_are_the_same_in_numpy_and_under_jit():
    import jax
    specs = WH.tensor_specs(TINY_HF)
    params = WH.make_params(TINY_HF, 2**31 + 5)
    assert set(params) == {n for n, _ in specs}
    for i, (name, shape) in enumerate(specs):
        want = WH.make_tensor_np(2**31 + 5, i, name, shape)
        got = np.asarray(jax.device_get(params[name]))
        assert got.dtype == want.dtype and got.shape == tuple(shape)
        np.testing.assert_array_equal(got.view(np.uint16),
                                      want.view(np.uint16), err_msg=name)


def test_decay_spans_heads_that_forget_and_heads_that_remember():
    """The stated distributions: over a layer's 64 heads the per-step decay
    exp(softplus(dt_bias) · -exp(A_log)) at the bias alone reaches below
    0.5 (gone in a few tokens) and above 0.995 (hundreds of tokens)."""
    idx = WH.layer_indices(HF)
    def leaf(name):
        return WH.make_tensor_np(11, idx[name], name, (64,)).astype(np.float64)
    a = -np.exp(leaf("layers.0.ssm_A_log"))
    delta = np.log1p(np.exp(leaf("layers.0.ssm_dt_bias")))
    decay = np.exp(delta * a)
    assert decay.min() < 0.5 and decay.max() > 0.995


# -- the cost functions ------------------------------------------------------

def test_parameter_count_is_the_sum_over_the_tensor_list():
    total = sum(int(np.prod(s, dtype=np.int64))
                for _, s in WH.tensor_specs(HF))
    p = costs_hybrid.param_count(HF)
    assert p["total"] == total == 3_191_396_096
    assert (p["n_mamba"], p["n_attn"]) == (36, 4)
    assert p["mamba_layer"] == 76_182_976 and p["attn_layer"] == 60_821_504


def test_step_bytes_are_weights_state_twice_and_live_keys():
    slots, live = 64, 32_000
    state = costs_hybrid.state_bytes_per_slot(HF)
    assert state == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert costs_hybrid.kv_bytes_per_token(HF) == 8192
    got = costs_hybrid.decode_step_bytes(HF, slots, live)
    want = (3_191_396_096 + slots * 2048) * 2 + 2 * slots * state + live * 8192
    assert got == want and 16.0e9 < got < 16.9e9
    nbytes, flops = costs_hybrid.ssm_update_cost(HF, slots)
    assert nbytes >= 2 * slots * 64 * 64 * 128 * 4          # read and written
    assert flops / 197e12 < nbytes / 819e9                  # memory-bound


# -- the reference -----------------------------------------------------------

def test_reference_mixer_against_a_python_loop():
    """``mamba_mixer`` (conv, the scan over tokens, D, gate, norm, out)
    against the equations written out one sequence, one position, one head
    at a time in float64."""
    import jax
    import jax.numpy as jnp
    z = WH.sizes(TINY_HF)
    shapes = WH.layer_shapes(TINY_HF, "mamba")
    idx = WH.layer_indices(TINY_HF)
    w = {leaf: WH.make_tensor_np(3, idx[f"layers.0.{leaf}"], leaf,
                                 shapes[leaf]).astype(np.float64)
         for leaf in WH.MAMBA_LEAVES}
    rng = np.random.default_rng(0)
    h = rng.normal(size=(1, 7, z["d"]))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.mamba_mixer(
            jnp.asarray(h, jnp.float32),
            {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}, TINY_HF))
    H, P, N, K, inner = z["H"], z["P"], z["N"], z["K"], z["inner"]
    silu = lambda t: t / (1 + np.exp(-t))                       # noqa: E731
    zu = h[0] @ w["ssm_in"]
    gate, u, dt = zu[:, :inner], zu[:, inner:inner + z["conv"]], \
        zu[:, inner + z["conv"]:]
    out = np.zeros((7, z["d"]))
    state = np.zeros((H, P, N))
    for t in range(7):
        conv = w["ssm_conv_b"].copy()
        for j in range(K):
            if t - (K - 1) + j >= 0:
                conv += w["ssm_conv_w"][j] * u[t - (K - 1) + j]
        conv = silu(conv)
        x, b, c = (conv[:inner].reshape(H, P), conv[inner:inner + N],
                   conv[inner + N:])
        y = np.zeros((H, P))
        for hh in range(H):
            delta = np.log1p(np.exp(dt[t, hh] + w["ssm_dt_bias"][hh]))
            decay = np.exp(-delta * np.exp(w["ssm_A_log"][hh]))
            state[hh] = decay * state[hh] + delta * np.outer(x[hh], b)
            y[hh] = state[hh] @ c + w["ssm_D"][hh] * x[hh]
        y = y.reshape(inner) * silu(gate[t])
        y = y / np.sqrt(np.mean(y * y) + TINY_HF["rms_norm_eps"]) \
            * w["ssm_norm"]
        out[t] = y @ w["ssm_out"]
    np.testing.assert_allclose(got[0], out, rtol=0,
                               atol=2e-5 * np.abs(out).max())


def test_reference_is_causal_and_int8_moves_it():
    toks = np.random.default_rng(1).integers(0, 128, (2, 24)).astype(np.int32)
    at = np.asarray([[5, 11], [3, 17]], np.int32)
    full = np.asarray(ref.logits_at(TINY_HF, 9, toks, at))
    padded = toks.copy()
    padded[:, 18:] = 0                      # right padding is inert
    np.testing.assert_array_equal(
        np.asarray(ref.logits_at(TINY_HF, 9, padded, at)), full)
    low = np.asarray(ref.logits_at(TINY_HF, 9, toks, at, low="int8"))
    assert np.abs(low - full).max() > 1e-3 * np.abs(full).max()


# -- the cell, end to end ----------------------------------------------------

def test_cell_end_to_end_is_correct():
    out, ctx = _run()
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"tok_s", "setup_s"}
    assert ctx.facts["compiles_in_window"] == 0
    t = ctx.facts["timings"]
    assert 0 < t["scan_tokens"] <= t["prefill_tokens"]


def test_dropped_state_is_not_correct():
    """The timed path broken where the new mechanism lives
    (``tools/control_hybrid.drop_state``): what the prefill left in the
    admitted slot's state and conv tail is zeroed before the first decode
    step."""
    out, _ = _run(server_built=drop_state)
    assert out["correct"] is False
    assert out["failed"] == 0               # every budget still returned


def test_traced_run_reports_the_cells_per_layer_metrics():
    """On the CPU the trace has no device plane: the device readers return
    nothing and the line leaves them out; the counters are there."""
    out, _ = _run(trace=1)
    assert out["correct"] is True
    assert {"admit_share.flood", "prefill_share.flood",
            "prefill_pad_share.flood", "compiles_in_window.flood"} \
        <= set(out["metrics"])
    assert "ssm_update_roofline.g4hm" not in out["metrics"]


# -- the new readers ---------------------------------------------------------

def _ctx(trace, config=HF):
    import types
    peaks = harness.load_json("benchmark", "peaks.json")["TPU v5 lite"]
    return types.SimpleNamespace(
        trace=trace, config=config, peaks=peaks,
        facts={"slots": 64, "live_tokens": 32_000.0})


def test_new_readers_on_a_synthetic_trace():
    ms = 1_000_000
    upd = ("%strom_ssm_update.7 = (f32[64,2,64,32]{3,2,1,0}, "
           "f32[65,64,64,128]{3,2,1,0}) custom-call(...)")
    scan = ("%strom_ssm_scan.2 = (bf16[1,64,1024,64]{3,2,1,0}, "
            "f32[1,64,128,64]{3,2,1,0}) custom-call(...)")
    ops = [(upd, 0, ms), (upd, 2 * ms, 3 * ms), (scan, 50 * ms, 51 * ms),
           ("%fusion.1 = bf16[64,8192]{1,0} fusion(...)", 4 * ms, 5 * ms)]
    tr = xplane.Trace(ops={"/device:TPU:0": ops},
                      modules={"/device:TPU:0": [
                          ("jit__paged_step(1)", 0, 40 * ms)]})
    ctx = _ctx(tr)
    read = lambda name: harness.plugin("layer_metrics", name).read(ctx)  # noqa
    nbytes, _ = costs_hybrid.ssm_update_cost(HF, 64)
    assert read("ssm_update_roofline.g4hm") == pytest.approx(
        100 * (nbytes / 819e9) / 1e-3)
    assert read("ssm_step_share.g4hm") == pytest.approx(100 * 2 / 40)
    sbytes, sflops = costs_hybrid.ssm_scan_cost(HF, 1024)
    assert read("ssm_scan_roofline.g4hm") == pytest.approx(
        100 * max(sbytes / 819e9, sflops / 197e12) / 1e-3)
    step = costs_hybrid.decode_step_bytes(HF, 64, 32_000.0)
    assert read("hybrid_step_roofline.g4hm") == pytest.approx(
        100 * (step / 819e9) / 40e-3)
    # every share stays under 100 % for times a chip could give
    assert read("hybrid_step_roofline.g4hm") < 100


@pytest.mark.parametrize("name", ["ssm_update_roofline.g4hm",
                                  "ssm_scan_roofline.g4hm",
                                  "ssm_step_share.g4hm",
                                  "hybrid_step_roofline.g4hm"])
def test_new_readers_find_nothing_where_there_is_nothing(name):
    """No trace, a trace without the kernels (the parent's), and a dense
    configuration: None, never an exception."""
    reader = harness.plugin("layer_metrics", name)
    dense = harness.load_json("benchmark", "configs", "mistral-7b-v0.3.json")
    empty = xplane.Trace(
        ops={"/device:TPU:0": [("%fusion.1 = bf16[8]{0} fusion()", 0, 9)]},
        modules={"/device:TPU:0": [("jit_other(1)", 0, 9)]})
    for ctx in (_ctx(None), _ctx(empty), _ctx(empty, dense)):
        assert reader.read(ctx) is None


def test_config_file_holds_the_catalog_rows_numbers():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-micro")
    for key, value in row["config"].items():
        if key not in HF["reduced"]:
            assert HF[key] == value, key
    assert HF["source"] == row["source_url"]
    assert set(HF["reduced"]) == {"max_position_embeddings"}

"""LFM2-MoE decoders (gated short convs beside q/k-normed GQA, dense MLPs
then sigmoid-routed experts) through the program: the full forward and the
paged server — compiled prefill of right-padded prompts, then decode through
the K/V pool and the conv-tail pool — against the benchmark's plain
reference (``benchmark/reference/lfm2_moe.py``, which imports nothing of the
program) on the benchmark's seeded weights; the exact expert layer row by
row; the selection bias; the conv tail.  A tiny size, on the CPU, float32."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import weights_moe as WM                         # noqa: E402
from benchmark.reference import lfm2_moe as ref                 # noqa: E402
from nvme_strom_tpu.models import decode, moe, serving, ssm     # noqa: E402
from nvme_strom_tpu.models.serving import DecodeServer          # noqa: E402
from nvme_strom_tpu.models.transformer import forward           # noqa: E402
from nvme_strom_tpu.tools.convert_llama import config_from_hf   # noqa: E402

#: LFM2-24B-A2B's keys at a tiny size: two periods (c, c, a, c), 2 dense
#: layers then 6 expert layers of 8 experts, top-2
HF = dict(
    model_type="lfm2_moe", hidden_size=64, vocab_size=96,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=128, moe_intermediate_size=32, num_experts=8,
    num_experts_per_tok=2, num_dense_layers=2, conv_L_cache=3,
    conv_bias=False, norm_eps=1e-5, norm_topk_prob=True,
    use_expert_bias=True, routed_scaling_factor=1,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    layer_types=["conv", "conv", "full_attention", "conv"] * 2,
    max_position_embeddings=64, tie_word_embeddings=True)
SEED = 11
BLOCK = 8


def _model(hf=HF):
    cfg = dataclasses.replace(config_from_hf(hf), dtype=jnp.float32)
    params = {k: v.astype(jnp.float32)
              for k, v in WM.make_params(hf, SEED).items()}
    return cfg, params


@pytest.fixture(scope="module")
def model():
    return _model()


def _server(model, slots=4):
    cfg, params = model
    return DecodeServer(params, cfg, max_batch=slots, max_len=64,
                        total_blocks=32, block_len=BLOCK)


def _prompt(n, salt=0):
    return np.random.default_rng([n, salt]).integers(
        0, HF["vocab_size"], n).tolist()


def _reference(prompt, tokens, hf=HF):
    """Reference logits (len(tokens), vocab) at the positions that predict
    each served token, teacher-forced on them."""
    seq = np.asarray([prompt + tokens], np.int32)
    at = len(prompt) - 1 + np.arange(len(tokens))[None]
    return np.asarray(ref.logits_at(hf, SEED, seq, at)[0])


@pytest.fixture
def spy(monkeypatch):
    """Record the logits every token of every request was sampled from: the
    prefill's (``_admit_first``, a group's rows), then each decode step's (``paged_logits``
    compiled as the step compiles it, minus the donation)."""
    rows = {}
    step_logits = jax.jit(serving.paged_logits, static_argnums=(1,))

    def run(srv, lookahead=1):
        first = srv._admit_first

        def first_spy(group, logits):
            for i, plan in enumerate(group):
                rows.setdefault(plan["req"].rid, []).append(
                    np.asarray(logits[i]))
            return first(group, logits)

        def step_spy(params, cfg, tok, k_pool, v_pool, blk, off, table,
                     pos, temps, top_ps, seeds, *recur):
            logits, k_pool, v_pool, state = step_logits(
                params, cfg, tok, k_pool, v_pool, blk, off, table, pos,
                *recur)
            for b, req in enumerate(srv.slots):
                if req is not None:
                    rows[req.rid].append(np.asarray(logits[b]))
            nxt = serving._sample_slots(logits, temps, top_ps, seeds, pos)
            return nxt, k_pool, v_pool, state

        if srv._admit_first.__name__ != "first_spy":
            srv._admit_first = first_spy
        monkeypatch.setattr(serving, "_paged_step", step_spy)
        out = srv.run(lookahead=lookahead)
        return {rid: (toks, np.stack(rows[rid][:len(toks)]))
                for rid, toks in out.items()}
    return run


def _close(got, want, tol=2e-4):
    """float32 end to end: equal to rounding at the logits' own scale."""
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * scale)


# -- (1) the program against the reference ---------------------------------

def test_full_forward_matches_the_reference(model):
    cfg, params = model
    toks = np.stack([_prompt(40, s) for s in (1, 2)]).astype(np.int32)
    got = np.asarray(forward(params, jnp.asarray(toks), cfg))
    at = np.broadcast_to(np.arange(40), (2, 40))
    _close(got, np.asarray(ref.logits_at(HF, SEED, toks, at)))


@pytest.mark.parametrize("n_prompt,lookahead", [(13, 1), (21, 2), (8, 3)])
def test_server_logits_match_the_reference(model, spy, n_prompt, lookahead):
    """Prefill in the compiled admission program (right-padded to a block
    multiple: 13 → 16, 21 → 24), then decode through the K/V pool and the
    conv-tail pool with ``lookahead`` sub-steps a readback: the logits at
    every served position against the reference's full forward."""
    srv = _server(model)
    prompt = _prompt(n_prompt)
    srv.submit("r", prompt, 9)
    toks, logits = spy(srv, lookahead)["r"]
    assert len(toks) == 9
    _close(logits, _reference(prompt, toks))


def test_unequal_prompts_side_by_side_match_the_reference(model, spy):
    """Three right-padded prompts of unequal length in one server, a free
    slot beside them, lookahead 4."""
    srv = _server(model)
    prompts = {"a": _prompt(5, 1), "b": _prompt(19, 2), "c": _prompt(32, 3)}
    for rid, p in prompts.items():
        srv.submit(rid, p, 7)
    out = spy(srv, 4)
    for rid, (toks, logits) in out.items():
        _close(logits, _reference(prompts[rid], toks))
    t = srv.timings
    rows = sum(len(p) for p in prompts.values())
    n_exp, k = len(srv.cfg.expert_layers), HF["num_experts_per_tok"]
    # no pair is dropped, and no pad row or free slot is routed
    assert t["moe_pairs_prefill"] == rows * k * n_exp
    # 32 rows alone, 24 and 8 in one call of the program of (2, 24): a call
    # of an expert layer is a program's, whatever it holds
    assert (t["admits"], t["prefill_calls"]) == (3, 2)
    assert t["moe_calls_prefill"] == 2 * n_exp
    assert t["moe_pairs"] % (k * n_exp) == 0 and t["moe_pairs"] > 0
    assert t["moe_pairs"] <= 3 * k * t["moe_calls"]
    assert t["moe_rows_computed"] >= t["moe_pairs"]
    assert srv.moe_load.shape == (n_exp, HF["num_experts"])
    assert srv.moe_load.sum() == t["moe_pairs"]


# -- (2) routing is per row -------------------------------------------------

def _layer_inputs(model, rows, salt=0):
    cfg, params = model
    x = jax.random.normal(jax.random.key(salt), (1, rows, cfg.d_model),
                          jnp.float32)
    return cfg, params, x, f"layers.{cfg.expert_layers[0]}."


def test_a_row_in_a_batch_equals_the_row_alone_bit_for_bit(model):
    """A row's expert-layer output among 98 other live rows equals the
    output of the same row ALONE in the call — every other row a pad row or
    a free slot, holding other numbers — in float32 bit for bit: no
    capacity, nothing shared between rows, nothing read from a row that is
    not valid."""
    cfg, params, x, L = _layer_inputs(model, 128)
    valid = jnp.ones((1, 128), bool).at[0, 100:].set(False).at[0, 7].set(False)
    full, counts, _ = moe.expert_mlp(x, params, L, cfg, valid)
    full = np.asarray(full)
    assert int(counts.sum()) == 99 * cfg.expert_top_k
    assert not full[0, 100:].any() and not full[0, 7].any()
    noise = jax.random.normal(jax.random.key(9), x.shape, jnp.float32) * 1e3
    for row in (0, 6, 8, 57, 99):
        only = jnp.zeros((1, 128), bool).at[0, row].set(True)
        alone, c1, _ = moe.expert_mlp(
            jnp.where(only[..., None], x, noise), params, L, cfg, only)
        assert int(c1.sum()) == cfg.expert_top_k
        np.testing.assert_array_equal(np.asarray(alone)[0, row], full[0, row])
    # and the batch without the mask computes the same valid rows
    unmasked = np.asarray(moe.expert_mlp(x, params, L, cfg)[0])
    np.testing.assert_array_equal(unmasked[0, :7], full[0, :7])
    # a call of one row agrees to rounding (its matrix products have
    # another shape, so not to the bit)
    one = np.asarray(moe.expert_mlp(x[:, 57:58], params, L, cfg)[0])
    np.testing.assert_allclose(one[0, 0], full[0, 57], rtol=0, atol=1e-5)


@pytest.mark.parametrize("case", ["one_expert_holds_every_row",
                                  "an_expert_with_no_rows"])
def test_extreme_loads_are_computed_exactly(model, case):
    """A bias that sends every row to experts 3 and 5 (the other six get
    no rows and are not read), or that shuts expert 0 out: the layer agrees
    with the plain masked loop of the reference."""
    cfg, params, x, L = _layer_inputs(model, 40, salt=3)
    bias = np.zeros(8, np.float32)
    if case == "one_expert_holds_every_row":
        bias[[3, 5]] = 10.0
    else:
        bias[0] = -10.0
    p = dict(params, **{L + "router_bias": jnp.asarray(bias)})
    out, counts, work = moe.expert_mlp(x, p, L, cfg)
    counts = np.asarray(counts)
    if case == "one_expert_holds_every_row":
        assert counts[3] == counts[5] == 40 and counts.sum() == 80
    else:
        assert counts[0] == 0 and counts.sum() == 80
    assert int(work[0]) >= 80 and int(work[1]) == 1     # rows, rounds
    w = {k[len(L):]: v for k, v in p.items() if k.startswith(L)}
    stacked = (w["moe_w_gate"], w["moe_w_up"], w["moe_w_down"])
    with jax.default_matmul_precision("highest"):
        want = ref.expert_mlp(x, w, HF,
                              lambda e: tuple(m[e] for m in stacked))
    _close(np.asarray(out), np.asarray(want), tol=1e-5)


def test_group_rows_lays_every_pair_on_its_experts_tile():
    from nvme_strom_tpu.ops import moe as ops
    expert = jnp.asarray([2, 0, 2, 5, 4, 2, 0, 4, 2, 2, 4, 0], jnp.int32)
    expert = expert.at[4].set(4)                # 4 = "not computed" below
    dest, tile_expert, n_tiles, counts = ops.group_rows(expert, 4, 2)
    counts, dest = np.asarray(counts), np.asarray(dest)
    assert counts.tolist() == [3, 0, 5, 0]      # experts 4, 5: nowhere
    rows = ops.padded_rows(12, 4, 2)
    assert int(n_tiles) == 2 + 3 and rows == (12 // 2 + 4) * 2
    live = np.asarray(expert) < 4
    assert (dest[~live] == rows).all()
    assert len(set(dest[live])) == live.sum()           # no two share a row
    te = np.asarray(tile_expert)
    assert te[:5].tolist() == [0, 0, 2, 2, 2]
    assert (te[dest[live] // 2] == np.asarray(expert)[live]).all()


# -- (3) the bias chooses and does not weigh --------------------------------

def _with_bias(model, spread=0.5):
    cfg, params = model
    p = dict(params)
    for i in cfg.expert_layers:
        p[f"layers.{i}.router_bias"] = spread * jax.random.normal(
            jax.random.key(100 + i), (cfg.n_experts,), jnp.float32)
    return cfg, p


def _reference_layer(p, L, x, hf=HF, weigh_bias=False):
    w = {k[len(L):]: v for k, v in p.items() if k.startswith(L)}
    stacked = (w["moe_w_gate"], w["moe_w_up"], w["moe_w_down"])
    if weigh_bias:      # the fault: the biased scores as the weights
        s = jax.nn.sigmoid(x @ w["router"]) + w["router_bias"]
        _, sel = jax.lax.top_k(s, hf["num_experts_per_tok"])
        chosen = jnp.any(sel[..., None] == jnp.arange(hf["num_experts"]), -2)
        wt = jnp.where(chosen, s, 0.0)
        wt = wt / (wt.sum(-1, keepdims=True) + 1e-6)
        out = 0
        for e in range(hf["num_experts"]):
            f = ref.dense_mlp(x, {"w_gate": stacked[0][e],
                                  "w_up": stacked[1][e],
                                  "w_down": stacked[2][e]})
            out = out + wt[..., e:e + 1] * f
        return out
    return ref.expert_mlp(x, w, hf, lambda e: tuple(m[e] for m in stacked))


@pytest.mark.parametrize("fault", [None, "bias_dropped_from_selection",
                                   "bias_added_into_the_weights"])
def test_the_bias_chooses_and_does_not_weigh(model, fault):
    """With a bias that changes the selection the program follows the
    reference; a reference that leaves the bias out of the selection, or
    weighs with the biased scores, is NOT what the program computes."""
    cfg, p = _with_bias(model)
    L = f"layers.{cfg.expert_layers[0]}."
    x = jax.random.normal(jax.random.key(5), (1, 64, cfg.d_model))
    got = np.asarray(moe.expert_mlp(x, p, L, cfg)[0])
    with jax.default_matmul_precision("highest"):
        hf = dict(HF, use_expert_bias=fault != "bias_dropped_from_selection")
        want = np.asarray(_reference_layer(
            p, L, x, hf, weigh_bias=fault == "bias_added_into_the_weights"))
        plain = np.asarray(_reference_layer(
            p, L, x, dict(HF, use_expert_bias=False)))
    scale = np.abs(want).max()
    if fault is None:
        _close(got, want, tol=1e-5)
        # the bias did change the selection: this is no vacuous agreement
        assert np.abs(plain - want).max() > 1e-2 * scale
    else:
        assert np.abs(got - want).max() > 1e-2 * scale


# -- (4) the conv tail -------------------------------------------------------

def _conv_inputs(model, rows, salt=0):
    cfg, params = model
    h = jax.random.normal(jax.random.key(salt), (2, rows, cfg.d_model))
    return cfg, params, h, "layers.0."


@pytest.mark.parametrize("last,k", [(5, 3), (9, 1), (2, 6)])
def test_prefill_then_decode_steps_equal_one_longer_prefill(model, last, k):
    """``conv_block`` to row ``last`` (right padding behind it), then ``k``
    ``conv_step``s against the tail pool, equal ``conv_block`` over ``last
    + 1 + k`` rows: outputs and the carried tail."""
    cfg, params, h, L = _conv_inputs(model, 16, salt=last)
    n = last + 1
    whole, tail_whole = ssm.conv_block(h[:, :n + k], params, L, cfg)
    out, tail = ssm.conv_block(h, params, L, cfg, n_valid=n)   # pads behind
    np.testing.assert_allclose(out[:, :n], whole[:, :n], atol=1e-5)
    pool = jnp.zeros((3, 2, cfg.d_model)).at[jnp.asarray([2, 0])].set(tail)
    sidx = jnp.asarray([2, 0], jnp.int32)
    for j in range(k):
        y, pool = ssm.conv_step(h[:, n + j:n + j + 1], params, L, cfg, pool,
                                sidx)
        np.testing.assert_allclose(y[:, 0], whole[:, n + j], atol=1e-5)
    np.testing.assert_allclose(pool[sidx], tail_whole, atol=1e-6)
    assert not np.asarray(pool[1]).any()           # nobody's row: untouched


def test_pad_rows_stay_out_of_the_tail(model):
    """The tail after a right-padded block is the tail after its valid
    rows, whatever the pad rows hold."""
    cfg, params, h, L = _conv_inputs(model, 12)
    _, want = ssm.conv_block(h[:, :7], params, L, cfg)
    noisy = h.at[:, 7:].set(1e3)
    _, got = ssm.conv_block(noisy, params, L, cfg, n_valid=7)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # a one-row prompt: the tail is a row of zeros and the row itself
    _, one = ssm.conv_block(noisy, params, L, cfg, n_valid=1)
    assert not np.asarray(one[:, 0]).any() and np.asarray(one[:, 1]).any()


def test_a_released_slot_answers_as_a_fresh_server(model, spy):
    """A slot that served one request and was released starts the next
    prompt from zeros: nothing of the first request's tail is left."""
    srv = _server(model, slots=1)
    srv.submit("first", _prompt(20, 1), 5)
    spy(srv)
    srv.submit("second", _prompt(11, 2), 6)
    toks, logits = spy(srv, 2)["second"]
    fresh = _server(model, slots=1)
    fresh.submit("second", _prompt(11, 2), 6)
    toks2, logits2 = spy(fresh, 2)["second"]
    assert toks == toks2
    np.testing.assert_array_equal(logits, logits2)


def test_free_slots_step_into_the_sacrificial_row_only(model):
    srv = _server(model)
    srv.submit("r", _prompt(10), 4)
    srv.step()                                  # admission: slot 0's rows
    before = [np.asarray(a) for a in srv.state["conv"]]
    srv.step()
    for a, b in zip(srv.state["conv"], before):
        a = np.asarray(a)
        assert (a[1:srv.B] == b[1:srv.B]).all()       # free slots' rows
        assert (a[0] != b[0]).any()                   # the live slot moved
    st = srv.stats()
    assert st["state_slots"] == srv.B + 1 and st["kv_layers"] == 2
    assert st["moe_layers"] == 6 and not srv.state["s"]
    assert st["state_bytes"] == sum(a.nbytes for a in srv.state["conv"]) \
        == 6 * (srv.B + 1) * 2 * 64 * 4


# -- (5) the config ----------------------------------------------------------

def test_config_from_hf_reads_the_per_layer_description():
    cfg = config_from_hf(HF)
    assert cfg.layer_kinds == ("conv", "conv", "attention", "conv") * 2
    assert cfg.mlp_kinds == ("dense",) * 2 + ("experts",) * 6
    assert cfg.attn_layers == (2, 6) and cfg.mamba_layers == ()
    assert cfg.recurrent_layers == (0, 1, 3, 4, 5, 7)
    assert cfg.expert_layers == (2, 3, 4, 5, 6, 7)
    assert (cfg.n_experts, cfg.expert_top_k, cfg.expert_width) == (8, 2, 32)
    assert cfg.router_kind == "sigmoid" and cfg.router_bias
    assert cfg.qk_norm and cfg.tie_embed and cfg.rope_theta == 1e6
    assert cfg.conv_taps == 3 and cfg.norm_eps == 1e-5
    with pytest.raises(NotImplementedError, match="recurrent state"):
        cfg.require_no_recurrent("a test")


def test_a_serving_config_cannot_reach_the_dropping_layer(model):
    """``mlp_block`` runs the capacity-dropping layer only for a config that
    places it by ``moe_every``; one that describes a model's own router and
    forgets ``mlp_kinds`` gets an error, not a dropped token."""
    from nvme_strom_tpu.models.transformer import (TransformerConfig,
                                                   tiny_moe_config)
    cfg, params = model
    h = jnp.zeros((1, 4, 64))
    assert tiny_moe_config().mlp_kind(1) == "gshard"
    for bad in (dict(router_kind="sigmoid"), dict(router_bias=True),
                dict(d_expert=32)):
        wrong = TransformerConfig(vocab=96, d_model=64, n_layers=2,
                                  n_heads=4, n_kv_heads=2, d_ff=128,
                                  n_experts=8, moe_every=1, **bad)
        with pytest.raises(NotImplementedError, match="capacity-dropping"):
            decode.mlp_block(h, params, "layers.0.", wrong)
    with pytest.raises(ValueError, match="mlp_kinds"):
        dataclasses.replace(cfg, mlp_kinds=("dense", "moe") * 4)
    with pytest.raises(ValueError, match="expert_top_k"):
        dataclasses.replace(cfg, expert_top_k=9)


def test_a_mesh_refuses_the_exact_expert_layer(model):
    """No exchange of rows between devices exists for the exact layer: an
    attention-only config with expert layers gets an error from
    ``param_specs``, as a recurrent one does."""
    from nvme_strom_tpu.parallel.shardings import param_specs
    cfg, _ = model
    with pytest.raises(NotImplementedError, match="recurrent state"):
        param_specs(cfg)
    plain = dataclasses.replace(cfg, layer_kinds=())
    with pytest.raises(NotImplementedError, match="exact expert layer"):
        param_specs(plain)

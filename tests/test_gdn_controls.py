"""Gated-delta-rule layers beside gated full attention at the tiny size of
``tests/test_gdn.py`` (its model, server and reference): each mechanism
against the benchmark's plain reference with it switched off; the shares of
a deployment adding up; the configuration as ``config_from_hf`` reads it;
what such a configuration refuses."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_gdn import (  # noqa: F401 — ``model`` is a fixture
    ATOL, BLOCK, HF, _prompt, _reference, _server, model)
from benchmark import weights_gdn as WG
from benchmark.reference import qwen3_next as ref
from nvme_strom_tpu.models import decode, moe, ssm
from nvme_strom_tpu.models import transformer as tr
from nvme_strom_tpu.models.serving import DecodeServer
from nvme_strom_tpu.tools.convert_llama import config_from_hf


# -- (1) each mechanism against the reference with it switched off -------------

CONTROLS = {
    "beta": dict(low="beta1"),
    "decay": dict(low="alpha1"),
    "correction": dict(low="no_correction"),
    "l2_norms": dict(low="no_l2"),
    "zero_centred_norm": dict(low="w_norm"),
    "attention_gate": dict(low="no_attn_gate"),
    "shared_gate": dict(low="no_shared_gate"),
    "weights_over_all_selected": dict(low="norm_held"),
    # rotary on all 32 features of a head, not the first 8
    "partial_rotary": dict(low="rotary_all"),
}


@pytest.fixture(scope="module")
def served(model):
    """One request served without the spy's patches: (prompt, tokens, the
    program's logits at every served token, teacher-forced through
    ``block_step`` — prefill and decode agree with it by the tests above)."""
    cfg, params = model
    prompt = _prompt(45, salt=5)
    srv = _server(model, slots=1)
    srv.submit("r", prompt, 10)
    toks = srv.run()["r"]
    cache = decode.init_cache(cfg, 1, 64)
    logits, _ = decode.block_step(
        params, jnp.asarray([prompt + toks[:-1]], jnp.int32), cfg, cache)
    return prompt, toks, np.asarray(logits[0, len(prompt) - 1:])


@pytest.mark.parametrize("name", list(CONTROLS))
def test_each_mechanism_is_in_the_program(served, name):
    """The program agrees with the sound reference and NOT with the
    reference that lacks the mechanism."""
    prompt, toks, logits = served
    np.testing.assert_allclose(logits, _reference(prompt, toks), atol=ATOL)
    off = _reference(prompt, toks, **CONTROLS[name])
    assert np.abs(logits - off).max() > 30 * ATOL, name


# -- (2) the share of a deployment --------------------------------------------------

def test_the_shares_of_an_expert_layer_add_up_to_the_whole():
    """64 small experts over 16 shares of 4, top-6 by softmax: the routed
    parts all sixteen shares give, plus the GATED shared expert once, equal
    the uncut layer — the router is whole on every share and the weights are
    normalised over all 6 selected experts, held or not."""
    whole = tr.TransformerConfig(
        vocab=32, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2, d_ff=64,
        mlp_kinds=("experts",), n_experts=64, expert_top_k=6, d_expert=8,
        d_shared=8, shared_gate=True, router_kind="softmax",
        dtype=jnp.float32)
    keys = iter(jax.random.split(jax.random.key(4), 16))
    p = moe.init_moe_params(keys, whole, "", tr.dense_init)
    p["router"] = p["router"] * 4.0            # scores well apart
    p["shared_gate"] = p["shared_gate"] * 8.0  # a gate that spans (0, 1)
    x = jax.random.normal(jax.random.key(1), (2, 9, 32), jnp.float32)
    valid = jnp.ones((2, 9), bool).at[1, 6:].set(False)
    want, counts, _ = moe.expert_mlp(x, p, "", whole, valid)
    gate = jax.nn.sigmoid(x @ p["shared_gate"])
    assert float(gate.min()) < 0.2 and float(gate.max()) > 0.8
    shared = gate * tr.mlp(x, p, "shared_")
    total, pairs = jnp.zeros_like(want), 0
    for share in range(16):
        cfg = dataclasses.replace(whole, experts_held=4,
                                  expert_offset=4 * share)
        ps = dict(p, **{k: p[k][4 * share:4 * share + 4]
                        for k in ("moe_w_gate", "moe_w_up", "moe_w_down")})
        out, c, _ = moe.expert_mlp(x, ps, "", cfg, valid)
        np.testing.assert_array_equal(c, counts[4 * share:4 * share + 4])
        total = total + (out - shared)
        pairs += int(c.sum())
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               atol=2e-5)
    assert pairs == int(counts.sum()) == 15 * 6        # 15 valid rows, k 6
    # a pad row is routed nowhere and comes out as the gated shared expert
    np.testing.assert_allclose(np.asarray(want[1, 6:]),
                               np.asarray(shared[1, 6:]), atol=1e-6)
    # and the uncut layer is the reference's, equation for equation
    hf = dict(HF, hidden_size=32, moe_intermediate_size=8,
              shared_expert_intermediate_size=8, num_experts=64,
              num_experts_per_tok=6, expert_share=None)
    w = {"router": p["router"], "shared_gate": p["shared_gate"],
         **{k: p[k] for k in ("shared_w_gate", "shared_w_up",
                              "shared_w_down")}}
    with jax.default_matmul_precision("highest"):
        plain = ref.moe(x[:1], w, hf, lambda e: (
            p["moe_w_gate"][e], p["moe_w_up"][e], p["moe_w_down"][e]))
    np.testing.assert_allclose(np.asarray(want[:1]), np.asarray(plain),
                               atol=2e-5)


# -- (3) the configuration --------------------------------------------------------------

def test_config_from_hf_reads_the_benchmarks_file():
    from benchmark import harness
    hf = harness.load_json("benchmark", "configs", "qwen3-next-80b-a3b.json")
    cfg = config_from_hf(hf)
    assert cfg.layer_kinds == ("gdn", "gdn", "gdn", "attention") * 4
    assert (cfg.d_model, cfg.vocab, cfg.max_seq) == (2048, 18992, 5120)
    assert (cfg.gdn_k_heads, cfg.gdn_v_heads, cfg.gdn_k_dim,
            cfg.gdn_v_dim, cfg.gdn_conv) == (16, 32, 128, 128, 4)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.rotary_dim,
            cfg.attn_gate, cfg.qk_norm) == (16, 2, 256, 64, True, True)
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_offset,
            cfg.expert_top_k, cfg.d_expert, cfg.d_shared,
            cfg.shared_gate) == (512, 32, 0, 10, 512, 512, True)
    shapes = jax.eval_shape(lambda: ssm.init_state(cfg, 129))
    assert [a.shape for a in shapes["s"]] == [(129, 32, 128, 128)] * 12
    assert [a.shape for a in shapes["conv"]] == [(129, 3, 8192)] * 12
    assert shapes["s"][0].dtype == jnp.float32
    # the generator's tensors are the program's leaves, shape for shape
    want = jax.eval_shape(lambda: tr.init_params(jax.random.key(0), cfg))
    got = dict(WG.tensor_specs(hf))
    assert set(got) == set(want)
    assert all(tuple(want[k].shape) == tuple(got[k]) for k in got)


def test_gdn_layers_need_their_sizes():
    with pytest.raises(ValueError, match="gdn layers need gdn_k_heads"):
        tr.TransformerConfig(n_layers=2, layer_kinds=("attention", "gdn"))
    with pytest.raises(ValueError, match="a multiple of gdn_k_heads"):
        tr.TransformerConfig(n_layers=1, layer_kinds=("gdn",), gdn_k_heads=4,
                             gdn_v_heads=6, gdn_k_dim=8, gdn_v_dim=8)
    with pytest.raises(ValueError, match="'conv', 'gdn'"):
        tr.TransformerConfig(n_layers=1, layer_kinds=("delta",))


# -- (4) what such a configuration refuses --------------------------------------------

def test_what_cannot_hold_the_state_refuses(model):
    """No prefix store, hand-off, mesh or training step: one plain sentence
    each, naming the kinds of layer that carry a state."""
    cfg, params = model

    class Store:
        page_tokens = BLOCK

    with pytest.raises(NotImplementedError, match="kv_store"):
        _server(model, kv_store=Store())
    srv = _server(model)
    with pytest.raises(NotImplementedError,
                       match="mamba or conv or gdn layers"):
        srv.export_sessions()
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    sharded = dict(params)
    sharded["layers.0.gdn_out"] = jax.device_put(
        params["layers.0.gdn_out"], NamedSharding(mesh, P(None, "tp")))
    with pytest.raises(NotImplementedError, match="mesh"):
        DecodeServer(sharded, cfg, max_batch=2, max_len=64,
                     total_blocks=8, block_len=BLOCK)
    toks = jnp.zeros((1, 8), jnp.int32)
    for what in (lambda: tr.forward(params, toks, cfg),
                 lambda: tr.loss_fn(params, toks, cfg)):
        with pytest.raises(NotImplementedError, match="training step"):
            what()
    # and no prefix keys: a page without the state at its boundary is not one
    srv.submit("x", _prompt(30), 2)
    assert srv._req_keys(srv.queue[0]) == []

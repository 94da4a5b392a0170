"""Window and full GQA layers mixed, with stated head widths (keys wider
than values), partial rotary, a value scale and a sink, through the program
at a tiny size on the CPU in float32: the paged server — compiled prefill
through the blocked kernel, then decode through BOTH caches, the full
layers' pages and the window layers' rings — against the benchmark's plain
reference (``benchmark/reference/mimo_swa.py``, which imports nothing of the
program) on the benchmark's seeded weights, in logits; each mechanism against
the reference with it switched off; what a freed slot leaves behind; what
such a configuration refuses.  (The kernels in interpret mode at 192-wide
keys and 128-wide values: ``tests/test_swa_kernels.py``.)"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import weights_swa as WS                         # noqa: E402
from benchmark.reference import mimo_swa as ref                 # noqa: E402
from nvme_strom_tpu.models import decode, serving               # noqa: E402
from nvme_strom_tpu.models import transformer as tr             # noqa: E402
from nvme_strom_tpu.models.serving import DecodeServer          # noqa: E402
from nvme_strom_tpu.tools.convert_llama import config_from_hf   # noqa: E402

#: MiMo-V2.5's keys at a tiny size: full, window, window, full, window; a
#: dense MLP then expert layers; the router scores 16 experts top-4 and this
#: device holds 4 of them (4..7).  A window of 16 under blocks of 8 is a ring
#: of 3 blocks, 24 rows: a context of 40 crosses the window's edge, several
#: block boundaries and wraps the ring
HF = dict(
    model_type="mimo_v2", hidden_size=64, vocab_size=96,
    num_hidden_layers=5, hybrid_layer_pattern=[0, 1, 1, 0, 1],
    moe_layer_freq=[0, 1, 1, 1, 1], num_attention_heads=4,
    swa_num_attention_heads=4, num_key_value_heads=1,
    swa_num_key_value_heads=2, head_dim=24, swa_head_dim=24, v_head_dim=16,
    swa_v_head_dim=16, partial_rotary_factor=0.334, rope_theta=10000,
    swa_rope_theta=100, rope_scaling={"rope_type": "default"},
    sliding_window=16, sliding_window_size=16, attention_chunk_size=16,
    attention_value_scale=0.707, add_swa_attention_sink_bias=True,
    add_full_attention_sink_bias=False, intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=4,
    expert_share={"routed": 16, "offset": 4}, n_shared_experts=None,
    num_experts_per_tok=4, norm_topk_prob=True, routed_scaling_factor=None,
    scoring_func="sigmoid", topk_method="noaux_tc", n_group=1, topk_group=1,
    layernorm_epsilon=1e-5, hidden_act="silu", attention_bias=False,
    tie_word_embeddings=False, max_position_embeddings=64)
SEED = 23
BLOCK = 8


def _model(hf=HF):
    cfg = dataclasses.replace(config_from_hf(hf), dtype=jnp.float32)
    params = {k: v.astype(jnp.float32)
              for k, v in WS.make_params(hf, SEED).items()}
    return cfg, params


@pytest.fixture(scope="module")
def model():
    return _model()


def _server(model, slots=3, **kw):
    cfg, params = model
    return DecodeServer(params, cfg, max_batch=slots, max_len=64,
                        total_blocks=32, block_len=BLOCK, **kw)


def _prompt(n, salt=0):
    return np.random.default_rng([n, salt]).integers(
        0, HF["vocab_size"], n).tolist()


def _reference(prompt, tokens, hf=HF, low=None):
    """Reference logits (len(tokens), vocab) at the positions that predict
    each served token, teacher-forced on them."""
    seq = np.asarray([prompt + tokens], np.int32)
    at = len(prompt) - 1 + np.arange(len(tokens))[None]
    return np.asarray(ref.logits_at(hf, SEED, seq, at, low=low)[0])


@pytest.fixture
def spy(monkeypatch):
    """Record the logits every token of every request was sampled from: the
    prefill's (``_admit_first``), then each decode step's (``paged_logits``
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

        srv._admit_first = first_spy
        monkeypatch.setattr(serving, "_paged_step", step_spy)
        out = srv.run(lookahead=lookahead)
        return {rid: (toks, np.stack(rows[rid][:len(toks)]))
                for rid, toks in out.items()}
    return run


# -- (1) the program against the reference ----------------------------------

@pytest.mark.parametrize("lookahead", [1, 3])
def test_prefill_then_decode_through_both_caches(model, spy, lookahead):
    """Mixed prompt lengths — under the window, over it, under a block, no
    block multiple, longer than the ring — and budgets that carry the
    longest over the ring's wrap (41 + 20 rows over a ring of 24), more
    requests than slots so that slots free and refill: every token's logits
    are the reference's, prefill's and decode's alike."""
    srv = _server(model, slots=3)
    prompts = {"a": _prompt(10), "b": _prompt(17), "c": _prompt(3),
               "d": _prompt(41), "e": _prompt(8), "f": _prompt(24)}
    budgets = {"a": 12, "b": 9, "c": 30, "d": 20, "e": 6, "f": 5}
    for rid, p in prompts.items():
        srv.submit(rid, p, budgets[rid])
    out = spy(srv, lookahead)
    assert set(out) == set(prompts)
    for rid, (toks, logits) in out.items():
        assert len(toks) == budgets[rid]
        want = _reference(prompts[rid], toks)
        np.testing.assert_allclose(logits, want, atol=3e-4, err_msg=rid)
    st = srv.stats()
    # full layers: K 24 + V 16 floats of ONE KV head in 2 layers; a window
    # layer's ring: 3 blocks of 8 rows of 2 KV heads, 3 layers
    assert st["kv_layers"] == 2 and st["window_layers"] == 3
    assert st["kv_bytes_per_token"] == 2 * (24 + 16) * 4
    assert st["window_bytes_per_slot"] == 3 * 3 * BLOCK * 2 * (24 + 16) * 4
    assert srv.k_pool.shape == (2, 33, 1, BLOCK, 24)
    assert srv.v_pool.shape == (2, 33, 1, BLOCK, 16)
    assert srv.state["wk"].shape == (3, 4 * 3, 2, BLOCK, 24)
    assert srv.state["wv"].shape == (3, 4 * 3, 2, BLOCK, 16)
    t = srv.timings
    assert 0 < t["window_rows_live"] <= 16 * sum(budgets.values())
    assert t["attn_blocks_live"] > 0
    assert 0 < t["moe_pairs"] < t["moe_pairs_routed"]
    # no window layer ever held a block of the pool: all are free again
    assert st["blocks_free"] == st["blocks_total"] == 32
    assert st["prefix_hits"] == 0 and st["prefix_cached_blocks"] == 0


def test_a_prompt_of_many_query_and_key_blocks(model, spy, monkeypatch):
    """The prefill's kernel walks blocks of query rows and of keys, a window
    layer only those its band touches; with 8 score rows a step (2 query
    rows of 4 heads: one KV head's group) and key blocks of 8 a 45-row
    prompt is 24 query blocks a KV head, and the logits do not move."""
    from nvme_strom_tpu.ops import kv_prefill
    for name, n in (("ROWS", 8), ("WINDOW_ROWS", 8), ("BLOCK_K", 8),
                    ("WINDOW_BLOCK_K", 8)):
        monkeypatch.setattr(kv_prefill, name, n)
    srv = _server(model, slots=2)
    prompt = _prompt(45)
    srv.submit("long", prompt, 4)
    toks, logits = spy(srv)["long"]
    np.testing.assert_allclose(logits, _reference(prompt, toks), atol=3e-4)


def test_generate_is_the_servers_tokens(model):
    """``decode.generate`` (dense caches for both kinds of layer, every step
    through the blocked kernel) and the server (pages and rings, the paged
    kernels) produce the same greedy tokens."""
    cfg, params = model
    prompt = _prompt(19)
    want = np.asarray(decode.generate(
        params, jnp.asarray([prompt], jnp.int32), cfg, 14))[0]
    srv = _server(model, slots=1)
    srv.submit("g", prompt, 14)
    assert srv.run()["g"] == want.tolist()


# -- (2) each mechanism against the reference with it switched off -------------

CONTROLS = {
    "window_mask": dict(low="no_window"),
    "sink": dict(low="no_sink"),
    "value_scale": dict(low="no_value_scale"),
    "two_thetas": dict(low="one_theta"),
    # rotary on all 24 features of a head, not the first 8
    "partial_rotary": dict(hf=dict(HF, partial_rotary_factor=1.0)),
}


@pytest.fixture(scope="module")
def served(model):
    """One request served without the spy's patches: (prompt, tokens, the
    program's logits at every served token, teacher-forced through
    ``block_step`` — prefill and decode agree with it by the tests above)."""
    cfg, params = model
    prompt = _prompt(37, salt=5)
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
    reference that lacks the mechanism: a dropped window mask, sink, value
    scale, second theta or partial rotary moves the logits by hundreds of
    times the tolerance."""
    prompt, toks, logits = served
    np.testing.assert_allclose(logits, _reference(prompt, toks), atol=3e-4)
    kw = CONTROLS[name]
    off = _reference(prompt, toks, hf=kw.get("hf", HF), low=kw.get("low"))
    assert np.abs(logits - off).max() > 30 * 3e-4, name


# -- (3) what a freed slot leaves behind -----------------------------------------

def test_a_freed_slots_ring_and_pages_cannot_reach_the_next_request(model,
                                                                    spy):
    """One slot serves two requests in turn.  Between them every row of the
    pool and of the rings is overwritten with NaN — worse than anything the
    first request could leave — and the second request's logits are the
    reference's all the same: its prefill writes its pages and its whole
    ring, and the kernels mask (and zero) every row outside its own."""
    srv = _server(model, slots=1)
    first, second = _prompt(30, salt=1), _prompt(13, salt=2)
    srv.submit("first", first, 25)
    srv.run()
    assert srv.stats()["blocks_free"] == 32
    nan = lambda a: jnp.full(a.shape, jnp.nan, a.dtype)     # noqa: E731
    srv.k_pool, srv.v_pool = nan(srv.k_pool), nan(srv.v_pool)
    srv.state = dict(srv.state, wk=nan(srv.state["wk"]),
                     wv=nan(srv.state["wv"]))
    srv.submit("second", second, 30)
    toks, logits = spy(srv)["second"]
    np.testing.assert_allclose(logits, _reference(second, toks), atol=3e-4)


def test_stated_widths_without_a_window_share_a_prefix():
    """A config that states its head widths but has no window layer keeps
    every layer in pages, so the HBM prefix cache serves it: the second
    prompt's shared blocks are not computed again — its suffix runs through
    the blocked kernel BEHIND them, the prefix's length as data — and its
    tokens are the miss's."""
    cfg = tr.TransformerConfig(
        vocab=96, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
        max_seq=64, qk_head_dim=24, v_head_dim=16, rotary_dim=8,
        value_scale=0.707, dtype=jnp.float32)
    params = tr.init_params(jax.random.key(1), cfg)
    shared = _prompt(2 * BLOCK + 3, salt=7)

    def serve(prompts):
        srv = DecodeServer(params, cfg, max_batch=2, max_len=64,
                           total_blocks=24, block_len=BLOCK)
        out = {}
        for i, p in enumerate(prompts):     # one after the other
            srv.submit(i, p, 6)
            out.update(srv.run())
        return out, srv.stats()
    tail = _prompt(5, salt=8)
    hit, st = serve([shared + [1, 2], shared + tail])
    miss, _ = serve([shared + tail])
    assert st["prefix_hits"] == 1 and st["prefix_shared_blocks"] == 2
    assert hit[1] == miss[0]


# -- (4) the config -----------------------------------------------------------------

def test_config_from_hf_reads_the_benchmarks_file():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mimo-v2.5.json")) as f:
        hf = json.load(f)
    cfg = config_from_hf(hf)
    assert cfg.layer_kinds == ("attention", "window", "window", "window",
                               "window", "attention", "window")
    assert cfg.mlp_kinds == ("dense",) + ("experts",) * 6
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.v_dim,
            cfg.rotary_dim) == (4096, 64, 192, 128, 64)
    assert (cfg.n_kv_heads, cfg.window_kv_heads, cfg.window) == (4, 8, 128)
    assert [cfg.kv_heads(i) for i in (0, 1, 5)] == [4, 8, 4]
    assert [cfg.theta(i) for i in (0, 1, 5)] == [1e7, 1e4, 1e7]
    assert cfg.window_sink and cfg.value_scale == 0.707
    assert cfg.attn_layers == (0, 5) and cfg.window_layers == (1, 2, 3, 4, 6)
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_offset,
            cfg.expert_top_k, cfg.d_expert, cfg.d_shared, cfg.d_ff) == (
        256, 16, 0, 8, 2048, 0, 16384)
    assert cfg.router_kind == "sigmoid" and cfg.router_bias
    assert cfg.router_scale == 1.0 and cfg.router_norm_topk
    assert cfg.vocab == 19072 and cfg.max_seq == 17408 and not cfg.tie_embed
    assert cfg.rope_scaling is None and cfg.norm_eps == 1e-5
    assert cfg.stated_kv and not cfg.latent
    # two blocks of 128 hold any 128-row window
    assert serving.ring_blocks(cfg, 128) == 2
    # the published model is its own share
    whole = config_from_hf(dict(hf, n_routed_experts=256, expert_share=None))
    assert whole.experts_held == 0 and whole.experts_local == 256


@pytest.mark.parametrize("change,message", [
    (dict(add_full_attention_sink_bias=True), "only window layers take"),
    (dict(swa_head_dim=32), "swa_head_dim=32"),
    (dict(attention_chunk_size=64), "read as one window"),
    (dict(rope_scaling={"rope_type": "yarn", "factor": 4}), "only 'default'"),
    (dict(n_shared_experts=1), "n_shared_experts=1"),
    (dict(hybrid_layer_pattern=[0, 1]), "for each of the 5 layers"),
    (dict(n_group=2), "no group-limited routing")])
def test_config_from_hf_refuses_what_is_not_implemented(change, message):
    with pytest.raises(ValueError, match=message):
        config_from_hf(dict(HF, **change))


def test_window_layers_need_a_window():
    with pytest.raises(ValueError, match="window layers need window >= 1"):
        tr.TransformerConfig(n_layers=2, layer_kinds=("attention", "window"))


# -- (5) what such a configuration refuses --------------------------------------------

def test_what_reads_kv_pages_refuses_a_window_config(model):
    """The prefix store, the paged offload cache, the hand-off bundle, a
    mesh and the training path hold K and V pages of one width for every
    layer; a window layer keeps a ring: one plain sentence each."""
    from nvme_strom_tpu.models.kv_offload import (OffloadConfig,
                                                  PagedKVCache, PrefixStore)
    from nvme_strom_tpu.parallel.shardings import param_specs
    cfg, params = model
    toks = jnp.zeros((1, 8), jnp.int32)
    for what in (lambda: param_specs(cfg),
                 lambda: PrefixStore(cfg, None, "/nonexistent", BLOCK, 1 << 20),
                 lambda: PagedKVCache(cfg, OffloadConfig(path="/nonexistent"),
                                      None, 1),
                 lambda: _server(model).export_sessions(),
                 lambda: tr.forward(params, toks, cfg),
                 lambda: tr.loss_fn(params, toks, cfg)):
        with pytest.raises(NotImplementedError,
                           match="3 window layers that keep a ring of their "
                                 "last 16 rows, not pages"):
            what()

    class Store:                 # anything with a page size: refused first
        page_tokens = BLOCK
    with pytest.raises(NotImplementedError, match="kv_store"):
        _server(model, kv_store=Store())
    # and no prefix keys: a prefix would need the window layers' last rows
    srv = _server(model)
    srv.submit("x", _prompt(30), 2)
    assert srv._req_keys(srv.queue[0]) == []

"""Latent attention (MLA) and one device's share of a routed expert layer
through the program, at a tiny size on the CPU in float32: the full forward
and the paged server — compiled prefill in the expanded form, then decode in
the absorbed form through the latent pool — against the benchmark's plain
reference (``benchmark/reference/kimi_mla.py``, which imports nothing of the
program) on the benchmark's seeded weights; the two forms against each
other; YaRN's numbers against a hand calculation; the kernels in interpret
mode; the grouped product at a deep contraction; the shares of an expert
layer against the whole; what a latent configuration refuses."""

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

from benchmark import weights_mla as WM                         # noqa: E402
from benchmark.reference import kimi_mla as ref                 # noqa: E402
from nvme_strom_tpu.models import mla, moe, serving             # noqa: E402
from nvme_strom_tpu.models import transformer as tr             # noqa: E402
from nvme_strom_tpu.models.serving import DecodeServer          # noqa: E402
from nvme_strom_tpu.ops import moe as ops_moe                   # noqa: E402
from nvme_strom_tpu.ops.mla_attention import (latent_write,     # noqa: E402
                                              mla_attention)
from nvme_strom_tpu.tools.convert_llama import config_from_hf   # noqa: E402

#: Kimi-K2.7-Code's keys at a tiny size: a dense layer then two expert
#: layers; the router scores 16 experts top-4, this device holds 4 of them
#: (4..7) beside the shared expert
HF = dict(
    model_type="kimi_k2", hidden_size=64, vocab_size=96,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=4,
    expert_share={"routed": 16, "offset": 4}, n_shared_experts=1,
    num_experts_per_tok=4, first_k_dense_replace=1, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
    rms_norm_eps=1e-5, rope_theta=50000, routed_scaling_factor=2.827,
    norm_topk_prob=True, scoring_func="sigmoid", topk_method="noaux_tc",
    n_group=1, topk_group=1, moe_layer_freq=1, hidden_act="silu",
    attention_bias=False, tie_word_embeddings=False,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 64,
                  "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096, "type": "yarn"},
    max_position_embeddings=64)
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


def _server(model, slots=4, **kw):
    cfg, params = model
    return DecodeServer(params, cfg, max_batch=slots, max_len=64,
                        total_blocks=32, block_len=BLOCK, **kw)


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

def test_forward_is_the_references_full_pass(model):
    cfg, params = model
    toks = np.random.default_rng(0).integers(0, 96, (2, 23)).astype(np.int32)
    at = np.tile(np.arange(23)[None], (2, 1))
    want = np.asarray(ref.logits_at(HF, SEED, toks, at))
    got = np.asarray(tr.forward(params, jnp.asarray(toks), cfg))
    np.testing.assert_allclose(got, want, atol=2e-4)


@pytest.mark.parametrize("lookahead", [1, 3])
def test_prefill_then_decode_through_the_latent_pool(model, spy, lookahead):
    """Mixed prompt lengths (under a block, not a block multiple, several
    blocks), more requests than slots so that slots free and refill, one
    slot free throughout the tail: every token's logits are the
    reference's, prefill's and decode's alike."""
    srv = _server(model, slots=3)
    prompts = {"a": _prompt(10), "b": _prompt(17), "c": _prompt(3),
               "d": _prompt(33), "e": _prompt(8)}
    for rid, p in prompts.items():
        srv.submit(rid, p, 6 if rid != "c" else 9)
    out = spy(srv, lookahead)
    assert set(out) == set(prompts)
    for rid, (toks, logits) in out.items():
        want = _reference(prompts[rid], toks)
        np.testing.assert_allclose(logits, want, atol=3e-4, err_msg=rid)
    st = srv.stats()
    assert st["latent_bytes_per_token"] == 3 * (16 + 8) * 4
    assert st["experts_held"] == 4 and st["kv_layers"] == 3
    assert srv.v_pool is None and srv.k_pool.shape == (3, 33, 24, BLOCK)
    t = srv.timings
    assert 0 < t["moe_pairs"] < t["moe_pairs_routed"]
    assert 0 < t["moe_pairs_prefill"] < t["moe_pairs_routed_prefill"]
    assert t["moe_pairs_routed_prefill"] == sum(
        len(p) for p in prompts.values()) * 4 * 2
    # the latent kernel keeps the (slots x longest slot) walk, four table
    # entries a grid step: three slots times one step, or two while "d"
    # (33 + 6 rows: five blocks of 8) is among them
    assert 3 * t["steps"] < t["attn_grid_steps"] < 3 * 2 * t["steps"]


def test_a_prompt_longer_than_one_query_block(model, spy, monkeypatch):
    """The prefill's attention walks blocks of query rows and of keys; with
    blocks of 8 and 16 a 37-row prompt (padded to 40) is five query blocks,
    each over the key blocks up to its own end, and the logits do not
    move."""
    from nvme_strom_tpu.ops import mla_attention as ops_mla
    monkeypatch.setattr(ops_mla, "BLOCK_Q", 8)
    monkeypatch.setattr(ops_mla, "BLOCK_K", 16)
    srv = _server(model, slots=2)
    prompt = _prompt(37)
    srv.submit("long", prompt, 4)
    toks, logits = spy(srv)["long"]
    np.testing.assert_allclose(logits, _reference(prompt, toks), atol=3e-4)


@pytest.mark.parametrize("pos,m,S", [(0, 24, 24), (16, 8, 24), (5, 12, 32),
                                     (31, 1, 32)])
def test_prefill_attention_kernel_against_a_dense_computation(pos, m, S):
    """m query rows at cache positions pos.. against S cached keys, unequal
    widths of q.k and of v, blocks smaller than either: a whole prompt, a
    suffix behind a prefix, a block mid-cache (rows past it unseen), one
    row."""
    from nvme_strom_tpu.ops.mla_attention import mla_prefill_attention
    rng = np.random.default_rng(pos + m)
    q = jnp.asarray(rng.normal(size=(2, 3, m, 12)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 3, S, 12)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 3, S, 8)), jnp.float32)
    got = mla_prefill_attention(q, k, v, jnp.int32(pos), scale=0.3,
                                block_q=4, block_k=8, interpret=True)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.3
    seen = jnp.arange(S)[None, :] <= pos + jnp.arange(m)[:, None]
    want = jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_a_prefix_hit_serves_what_a_miss_serves(model):
    """A latent page IS a prefix (the rows depend on the tokens before them
    and on nothing else), so the HBM prefix cache reuses it: the second
    prompt's shared blocks are not computed again and its tokens are the
    ones a server without the cache gives."""
    shared = _prompt(24)

    def run(prefix_cache):
        srv = _server(model, slots=2, prefix_cache=prefix_cache)
        out = {}
        for rid, tail in (("a", [1, 2, 3]), ("b", [7, 8, 9, 10])):
            srv.submit(rid, shared + tail, 5)
            out.update(srv.run())
        return out, srv.stats()["prefix_hits"]

    hit, n_hit = run(True)
    miss, n_miss = run(False)
    assert (n_hit, n_miss) == (1, 0) and hit == miss


# -- (2) the two forms ---------------------------------------------------------

def test_absorbed_form_equals_expanded_form(model):
    cfg, params = model
    L = "layers.1."
    rng = np.random.default_rng(5)
    b, S = 3, 21
    h = jnp.asarray(rng.normal(size=(b, S, 64)), jnp.float32)
    q, rows = mla.project(h, params, L, cfg, None)
    want = mla.attend(q, rows, jnp.int32(0), params, L, cfg)[:, -1]
    qa = mla.absorb_q(q[:, -1], params, L, cfg)                # (b, nh, 24)
    s = jnp.einsum("bhw,bsw->bhs", qa, rows)
    o_lat = jnp.einsum("bhs,bsc->bhc", jax.nn.softmax(s, -1),
                       rows[..., :cfg.kv_lora_rank])
    got = mla.unabsorb(o_lat, params, L, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# -- (3) YaRN, by hand -------------------------------------------------------

def test_yarn_frequencies_and_scale_at_the_published_parameters():
    """dim 64, theta 50000, factor 64 over 4096, beta 32 / 1: the correction
    range is [floor 8.914, ceil 19.164] = [8, 20]; pair j keeps
    theta^(-j/32) below 8, has it divided by 64 from 20 on and blends
    linearly between; m = 0.1 ln 64 + 1 = 1.41589, scale = 192^-0.5 m^2."""
    sc = {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
          "mscale": 1, "mscale_all_dim": 1,
          "original_max_position_embeddings": 4096}
    f = tr.yarn_freqs(32, 50000.0, sc)
    by_hand = {0: 1.0, 8: 0.0668740305, 9: 0.0437766560, 14: 0.00446584875,
               19: 0.000158374991, 20: 1.80702339e-05, 31: 4.38220646e-07}
    for j, want in by_hand.items():
        assert f[j] == pytest.approx(want, rel=2e-5), j
    np.testing.assert_allclose(ref.inv_freq(
        dict(qk_rope_head_dim=64, rope_theta=50000, rope_scaling=sc)), f,
        rtol=1e-6)
    assert tr.yarn_mscale(64, 1) == pytest.approx(1.41588831)
    full = dict(HF, qk_nope_head_dim=128, qk_rope_head_dim=64)
    assert config_from_hf(full).attn_scale == pytest.approx(0.1446796258)
    assert ref.softmax_scale(full) == pytest.approx(0.1446796258)
    # cos and sin are scaled by mscale(factor, 1) / mscale(factor, 1) = 1
    cos, _ = tr._rope_cos_sin(32, 50000.0, jnp.zeros((1,)), sc, 1)
    assert float(cos[0, 0]) == 1.0


# -- (4) the kernels, interpreted ------------------------------------------------

def _dense_latent_attention(q, pool, table, pos, layer, dc):
    out = []
    bk = pool.shape[3]
    for i in range(q.shape[0]):
        n = int(pos[i]) + 1
        rows = jnp.concatenate([pool[layer, int(j)].T
                                for j in table[i, :-(-n // bk)]])[:n]
        p = jax.nn.softmax(q[i] @ rows.T, axis=-1)
        out.append(p @ rows[:, :dc])
    return jnp.stack(out)


@pytest.mark.parametrize("group", [1, 2, 4])
def test_mla_attention_kernel_against_a_dense_computation(group):
    """Random tables and positions, a slot of one row, a slot whose last
    block is full, blocks the table names twice; the rows past a position
    hold NaN-free garbage that must not count."""
    rng = np.random.default_rng(group)
    L, NB, W, bk, dc, nh, b, mb = 2, 20, 48, 8, 32, 4, 6, 7
    pool = jnp.asarray(rng.normal(size=(L, NB, W, bk)), jnp.float32)
    table = jnp.asarray(rng.integers(0, NB, size=(b, mb)), jnp.int32)
    pos = jnp.asarray([0, 5, 17, 40, 55, 7], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, nh, W)) * 0.3, jnp.float32)
    got = mla_attention(q, pool, table, pos, layer=1, dc=dc, group=group,
                        interpret=True)
    want = _dense_latent_attention(q, pool, table, pos, 1, dc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_latent_write_places_each_slots_row():
    rng = np.random.default_rng(3)
    pool = jnp.asarray(rng.normal(size=(2, 9, 24, 8)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(4, 24)), jnp.float32)
    blk, off = jnp.asarray([3, 4, 8, 0]), jnp.asarray([0, 7, 3, 2])
    got = latent_write(pool, rows, blk, off, layer=1, interpret=True)
    want = np.array(pool)
    for i in range(4):
        want[1, int(blk[i]), :, int(off[i])] = np.asarray(rows[i])
    np.testing.assert_array_equal(np.asarray(got), want)


# -- (5) the grouped product, deep ----------------------------------------------

def test_column_tile_narrows_for_a_deep_contraction_only():
    # lfm2's shapes keep their 512 columns (gated 2048 deep, down 1536 deep)
    assert ops_moe.column_tile(2048, 1536, 2, 2) == 512
    assert ops_moe.column_tile(1536, 2048, 1, 2) == 512
    # 7168 deep: two double-buffered (7168, 512) bf16 blocks would be 28 MiB
    assert ops_moe.column_tile(7168, 2048, 2, 2) == 256
    assert ops_moe.column_tile(2048, 7168, 1, 2) == 512
    assert ops_moe.column_tile(64, 96, 2, 4) == 96      # narrow: whole


@pytest.mark.parametrize("gated", [True, False])
def test_gmm_at_a_deep_contraction_against_an_einsum(gated, monkeypatch):
    """A contraction whose blocks would not fit the budget at the widest
    column tile: the tile narrows (here 512 -> 128) and the product is the
    einsum's."""
    monkeypatch.setattr(ops_moe, "_WEIGHT_VMEM", 2 ** 20)
    rng = np.random.default_rng(2)
    E, K, N, tm = 3, 640, 512, 16
    assert ops_moe.column_tile(K, N, 1 + gated, 4) == 128
    expert = jnp.asarray([0, 2, 2, 0, 2, 3, 3, 2, 0, 2], jnp.int32)  # 3: none
    dest, te, nt, counts = ops_moe.group_rows(expert, E, tm)
    rows = ops_moe.padded_rows(expert.shape[0], E, tm)
    x = np.zeros((rows + 1, K), np.float32)
    x[np.asarray(dest)] = rng.normal(size=(expert.shape[0], K))
    x = jnp.asarray(x[:rows])
    ws = tuple(jnp.asarray(rng.normal(size=(E, K, N)) / 25, jnp.float32)
               for _ in range(1 + gated))
    got = ops_moe.gmm(x, ws, te, nt, tm=tm, interpret=True)
    for i, e in enumerate(np.asarray(expert)):
        if e == E:
            continue
        xi = x[int(dest[i])]
        want = xi @ ws[0][e]
        if gated:
            want = want * jax.nn.sigmoid(want) * (xi @ ws[1][e])
        np.testing.assert_allclose(np.asarray(got[int(dest[i])]),
                                   np.asarray(want), rtol=2e-4, atol=2e-4)
    assert counts.tolist() == [3, 0, 5]


# -- (6) the share of a deployment ---------------------------------------------

def _expert_layer(cfg_kw, seed=4):
    cfg = tr.TransformerConfig(**dict(dict(
        vocab=32, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2, d_ff=64,
        mlp_kinds=("experts",), n_experts=16, expert_top_k=4, d_expert=16,
        d_shared=16, router_kind="sigmoid", router_bias=True,
        router_scale=2.827, dtype=jnp.float32), **cfg_kw))
    keys = iter(jax.random.split(jax.random.key(seed), 16))
    p = moe.init_moe_params(keys, cfg, "", tr.dense_init)
    return cfg, p


@pytest.mark.parametrize("shared_expert", [True, False],
                         ids=["kimi_shared_expert", "mimo_no_shared_expert"])
def test_the_shares_of_an_expert_layer_add_up_to_the_whole(shared_expert):
    """16 experts over 4 shares of 4: the routed parts all four shares give,
    plus the shared expert ONCE (where the model has one: Kimi's; MiMo-V2.5
    has none and no scale), equal the uncut layer — the router is whole on
    every share and the weights are normalised over all 4 selected experts,
    held or not."""
    whole_cfg, p = _expert_layer(
        {} if shared_expert else dict(d_shared=0, router_scale=1.0))
    p[("router_bias")] = jax.random.normal(jax.random.key(9), (16,)) * 0.2
    x = jax.random.normal(jax.random.key(1), (2, 9, 32), jnp.float32)
    valid = jnp.ones((2, 9), bool).at[1, 6:].set(False)
    want, counts, _ = moe.expert_mlp(x, p, "", whole_cfg, valid)
    shared = tr.mlp(x, p, "shared_") if shared_expert else jnp.zeros_like(x)
    total, pairs = jnp.zeros_like(want), 0
    for share in range(4):
        cfg = dataclasses.replace(whole_cfg, experts_held=4,
                                  expert_offset=4 * share)
        ps = dict(p, **{k: p[k][4 * share:4 * share + 4]
                        for k in ("moe_w_gate", "moe_w_up", "moe_w_down")})
        out, c, _ = moe.expert_mlp(x, ps, "", cfg, valid)
        assert c.shape == (4,)
        np.testing.assert_array_equal(c, counts[4 * share:4 * share + 4])
        total = total + (out - shared)
        pairs += int(c.sum())
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               atol=2e-5)
    assert pairs == int(counts.sum()) == 15 * 4        # 15 valid rows, k 4
    # pad rows come out as the shared expert alone routes nothing
    np.testing.assert_allclose(np.asarray(want[1, 6:]),
                               np.asarray(shared[1, 6:]), atol=1e-6)


def _share_layer():
    """The expert layer of a device that holds experts 8..11 of 16."""
    cfg, p = _expert_layer(dict(experts_held=4, expert_offset=8))
    return cfg, dict(p, **{k: p[k][:4] for k in ("moe_w_gate", "moe_w_up",
                                                 "moe_w_down")})


#: name: (the router's bias sends every row to the four held experts,
#: rows of right padding, HEADROOM, MIN_PAIRS, the layout's pairs, rounds)
#: over 50 rows x 4 = 200 pairs
BOUNDED = {
    "under_the_bound":          (False, 5, 2, 8, 100, 1),
    "no_pad_rows":              (False, 0, 2, 8, 100, 1),
    "exactly_at_the_bound":     (True, 5, 0, 180, 180, 1),
    "one_pair_over":            (True, 5, 0, 179, 179, 2),
    "a_hot_share_two_rounds":   (True, 5, 2, 8, 100, 2),
    "a_hot_share_three_rounds": (True, 5, 1, 60, 60, 3),
    # a row's pairs are never split: 6 pairs a layout take one row of 4
    "a_row_a_round":            (True, 5, 0, 6, 6, 45),
    "never_under_a_rows_pairs": (True, 5, 0, 1, 4, 45),
}


@pytest.mark.parametrize("case", list(BOUNDED))
def test_a_bounded_layout_computes_every_local_pair(case, monkeypatch):
    """The layout of a device that holds a share is made for ``pair_bound``
    pairs; local pairs past it take further rounds through the same layout.
    Output and load histogram are those of ONE layout of all the call's
    pairs (the bound at its default floor), whatever the rounds: nothing is
    dropped, and ``work`` says how many ran."""
    hot, pad, headroom, floor, bound, rounds = BOUNDED[case]
    cfg, p = _share_layer()
    if hot:
        p["router_bias"] = jnp.zeros((16,)).at[8:12].set(10.0)
    x = jax.random.normal(jax.random.key(2), (2, 25, 32), jnp.float32)
    valid = jnp.ones((2, 25), bool).at[0, 25 - pad:].set(False)
    assert moe.pair_bound(200, cfg) == 200              # one layout of all
    want, counts, work = moe.expert_mlp(x, p, "", cfg, valid)
    assert work[1] == 1
    local = int(counts.sum())
    assert local == (4 * (50 - pad) if hot else local) and 0 < local < 200
    monkeypatch.setattr(moe, "HEADROOM", headroom)
    monkeypatch.setattr(moe, "MIN_PAIRS", floor)
    assert moe.pair_bound(200, cfg) == bound
    got, c2, work = moe.expert_mlp(x, p, "", cfg, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_array_equal(c2, counts)
    assert int(work[1]) == rounds >= -(-local // bound)
    assert int(work[0]) % 16 == 0 and int(work[0]) >= local
    assert not np.asarray(got)[0, 25 - pad:].any() or cfg.d_shared


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the loops inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _arrays(jaxpr):
    return (v.aval for eqn in _eqns(jaxpr) for v in eqn.outvars)


def _primitives(jaxpr):
    return {eqn.primitive.name for eqn in _eqns(jaxpr)}


def test_a_device_that_holds_every_expert_traces_no_compaction(monkeypatch):
    """Where every expert is held (``experts_held`` 0) the layer is one
    layout of all its pairs with no loop of rounds, however low the floor;
    a share under the floor likewise (a decode step).  A share over it
    carries nothing as long as the call's pairs but int32 and the pairs'
    float32 weights: every array d or d_expert wide has at most the call's
    rows or the bounded layout's."""
    monkeypatch.setattr(moe, "MIN_PAIRS", 8)
    x = jax.random.normal(jax.random.key(2), (1, 50, 32), jnp.float32)

    def traced(cfg, p):
        return jax.make_jaxpr(
            lambda x: moe.expert_mlp(x, p, "", cfg))(x).jaxpr

    def wide(jaxpr):       # rows of the longest float array d or d_expert wide
        return max(a.shape[0] for a in _arrays(jaxpr) if a.ndim == 2
                   and a.shape[1] in (32, 16)
                   and jnp.issubdtype(a.dtype, jnp.floating))

    whole = traced(*_expert_layer({}))
    assert "while" not in _primitives(whole)
    assert wide(whole) == ops_moe.padded_rows(200, 16, 16) == 448

    share = traced(*_share_layer())
    assert "while" in _primitives(share)
    assert "scatter-add" not in _primitives(share)
    bound = moe.pair_bound(200, _share_layer()[0])
    assert bound == 100
    assert wide(share) == ops_moe.padded_rows(bound, 4, 16) == 160

    monkeypatch.setattr(moe, "MIN_PAIRS", 512)          # the default floor
    small = traced(*_share_layer())
    assert "while" not in _primitives(small)
    assert wide(small) == ops_moe.padded_rows(200, 4, 16) == 256


@pytest.mark.parametrize("overflow", [False, True],
                         ids=["nothing_overflows", "a_hot_share_overflows"])
def test_the_server_counts_the_rounds_its_expert_layers_ran(model, overflow,
                                                            monkeypatch):
    """``moe_rounds`` / ``moe_rounds_prefill`` in ``timings`` and
    ``stats()``: the calls' own number when no call's local pairs pass its
    layout; more where a router sends every row to the experts held here
    under a bound of one row's pairs — and the tokens served are the same."""
    cfg, params = model
    if overflow:
        held = slice(cfg.expert_offset, cfg.expert_offset + cfg.experts_held)
        params = dict(params, **{
            key: bias.at[held].add(10.0) for key, bias in params.items()
            if key.endswith("router_bias")})
    prompts = {"a": _prompt(10), "b": _prompt(21), "c": _prompt(5)}

    def serve(cfg):
        srv = _server((cfg, params), slots=2)
        for rid, prompt in prompts.items():
            srv.submit(rid, prompt, 5)
        return srv.run(lookahead=2), srv

    want, srv = serve(cfg)
    if overflow:
        monkeypatch.setattr(moe, "HEADROOM", 0)
        monkeypatch.setattr(moe, "MIN_PAIRS", 1)
        # a config of its own (the field is the training path's), so that
        # the server's programs are compiled under this bound
        got, srv = serve(dataclasses.replace(cfg, xent_chunks=2))
        assert got == want
    t, st = srv.timings, srv.stats()
    assert t["moe_calls"] == 2 * t["steps"] > 0
    assert t["moe_calls_prefill"] == 2 * t["prefill_calls"] > 0
    for key in ("moe_rounds", "moe_rounds_prefill", "moe_calls_prefill"):
        assert st[key] == t[key]
    if overflow:
        # a round a live row: every pair of a row is local, k of them fill
        # the layout; a call with no live row still runs its one
        k = cfg.expert_top_k
        assert t["moe_calls"] < t["moe_pairs"] // k <= t["moe_rounds"] \
            <= t["moe_pairs"] // k + t["moe_calls"]
        assert t["moe_rounds_prefill"] == t["moe_pairs_prefill"] // k \
            == 2 * sum(len(p) for p in prompts.values())
    else:
        assert t["moe_rounds"] == t["moe_calls"]
        assert t["moe_rounds_prefill"] == t["moe_calls_prefill"]


# -- (7) the config --------------------------------------------------------------

def test_config_from_hf_reads_the_benchmarks_file():
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-k2.7-code.json")) as f:
        hf = json.load(f)
    cfg = config_from_hf(hf)
    assert cfg.latent and cfg.latent_width == 576
    assert (cfg.d_model, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim) == (
        7168, 64, 1536, 512, 128, 64, 128)
    assert cfg.mlp_kinds == ("dense",) + ("experts",) * 4
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_offset,
            cfg.expert_top_k, cfg.d_expert, cfg.d_shared, cfg.d_ff) == (
        384, 12, 0, 8, 2048, 2048, 18432)
    assert cfg.router_kind == "sigmoid" and cfg.router_bias
    assert cfg.router_scale == 2.827 and cfg.router_norm_topk
    assert cfg.vocab == 20480 and cfg.max_seq == 8448 and not cfg.tie_embed
    assert cfg.attn_scale == pytest.approx(0.1446796258)
    assert cfg.rope_scaling_dict["rope_type"] == "yarn"
    assert cfg.rope_theta == 50000.0 and cfg.norm_eps == 1e-5
    # the whole layer is its own share: nothing held back
    whole = config_from_hf(dict(hf, n_routed_experts=384, expert_share=None))
    assert whole.experts_held == 0 and whole.experts_local == 384


@pytest.mark.parametrize("knob", ["n_group", "topk_group"])
def test_config_from_hf_refuses_group_limited_routing(knob):
    with pytest.raises(ValueError, match="choosing groups of experts first "
                                         "is not implemented"):
        config_from_hf(dict(HF, **{knob: 2}))


def test_config_refuses_a_share_outside_the_router():
    with pytest.raises(ValueError, match="held of 16 routed"):
        _expert_layer(dict(experts_held=4, expert_offset=14))


# -- (8) what a latent configuration refuses --------------------------------------

def test_what_reads_kv_pages_refuses_a_latent_config(model):
    """The prefix store, the hand-off bundle and a mesh hold K and V pages
    at KV-head width; a latent pool has no format there yet: one plain
    sentence each, never a latent pool read as K/V."""
    from nvme_strom_tpu.models.kv_offload import (OffloadConfig,
                                                  PagedKVCache, PrefixStore)
    from nvme_strom_tpu.parallel.shardings import param_specs
    cfg, params = model
    for what in (lambda: param_specs(cfg),
                 lambda: PrefixStore(cfg, None, "/nonexistent", BLOCK, 1 << 20),
                 lambda: PagedKVCache(cfg, OffloadConfig(path="/nonexistent"),
                                      None, 1),
                 lambda: _server(model).export_sessions()):
        with pytest.raises(NotImplementedError,
                           match="caches one 24-wide latent row a token"):
            what()

    class Store:                 # anything with a page size: refused first
        page_tokens = BLOCK
    with pytest.raises(NotImplementedError, match="kv_store"):
        _server(model, kv_store=Store())

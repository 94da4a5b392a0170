"""SDAR-MoE's mechanisms through the program at a tiny size on the CPU in
float32: generation by diffusion over blocks of four — the admission under
the block-causal mask, then a step that forwards a slot's current four rows
through the paged cache with no mask inside the block and commits the most
confident, the block finished before riding along, clean, to write its K/V
once (eight rows a slot, a limit a row) — over softmax-routed experts.  The
paged server against the benchmark's plain reference
(``benchmark/reference/sdar_bd.py``, which imports nothing of the program and
has no cache) on the benchmark's seeded weights: in logits at every (block,
denoising step), in served tokens and commit steps under both rules; the
block-causal forward against transformers' own Qwen3-MoE under a 4-D mask;
each mechanism against the reference with it switched off; the kernel at four
rows a slot; the config's keys and what refuses such a config by name."""

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

from conftest import within                                     # noqa: E402
from benchmark import weights_sdar as WS                        # noqa: E402
from benchmark.reference import sdar_bd as ref                  # noqa: E402
from nvme_strom_tpu.models import decode, serving               # noqa: E402
from nvme_strom_tpu.models.serving import DecodeServer          # noqa: E402
from nvme_strom_tpu.tools import convert_llama                  # noqa: E402
from nvme_strom_tpu.tools.convert_llama import config_from_hf   # noqa: E402

BL, MASK = 4, 95
#: SDAR-MoE's keys at a tiny size: 2 layers, 4 query heads over 2 KV heads of
#: a STATED 32 (hidden / heads is 16), 8 experts of 32, top-2 renormalised
HF = dict(
    model_type="sdar_moe", hidden_size=64, vocab_size=96,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    head_dim=32, intermediate_size=128, moe_intermediate_size=32,
    num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[], hidden_act="silu",
    attention_bias=False, rms_norm_eps=1e-6, rope_theta=1000000,
    rope_scaling=None, sliding_window=None, use_sliding_window=False,
    tie_word_embeddings=False, max_position_embeddings=256,
    serving={"diffusion": {"block_length": BL, "mask_token_id": MASK,
                           "denoising_steps": 2,
                           "remasking": "low_confidence_static"}})
SEED = 50
BLOCK = 8
#: float32 on both sides; what is left is the order of the sums (the paged
#: softmax a pool block at a time, the grouped expert product, the router's
#: 1e-6) through 2 layers: ~1e-5 on logits of size ~3.  bfloat16 misses it
#: by 100x (``test_bfloat16_fails_the_tolerance``).
ATOL = 2e-4


def _hf(**diffusion):
    return dict(HF, serving={"diffusion": dict(
        HF["serving"]["diffusion"], **diffusion)})


def _model(hf=HF, dtype=jnp.float32):
    cfg = dataclasses.replace(config_from_hf(hf), dtype=dtype)
    params = {k: v.astype(dtype) for k, v in WS.make_params(hf, SEED).items()}
    return cfg, params


@pytest.fixture(scope="module")
def model():
    return _model()


def _server(model, slots=3, **kw):
    cfg, params = model
    return DecodeServer(params, cfg, max_batch=slots, max_len=64,
                        total_blocks=24, block_len=BLOCK, **kw)


def _prompt(n, salt=0):
    return np.random.default_rng([n, salt]).integers(0, MASK, n).tolist()


def _steps(srv, rid):
    return list(srv.request_metrics[rid]["commit_steps"])


# -- (1) logits at every (block, denoising step) ------------------------------

def _admit(srv):
    """The admissions of a ``step_many`` and none of its forwards."""
    plans = srv._plan_admissions()
    for group in srv._form_groups(plans, {}):
        srv._finish_traced(group, {})


def _program_forwards(model, prompt, budget, broken=None):
    """Every denoising forward's logits [(block start, step, (Bl, vocab))] of
    one request: the server's own admission, then ``bd_rows``,
    ``paged_logits`` and ``bd_select`` by hand — the step's parts, with the
    logits between them in the open.  ``broken`` switches a mechanism off:
    ``"no_clean_write"`` sends the finished block's rows to the trash block
    (the pages stay as the block's last denoising forward wrote them),
    ``"no_lag"`` lets them see the block after them."""
    from nvme_strom_tpu.ops import paged_attention as pa
    cfg, params = model
    srv = _server(model, slots=1)
    srv.submit(0, prompt, budget)
    _admit(srv)
    table, trash = srv._table(), srv._trash
    attend = pa.paged_attention

    def forward(tok, k, v, pos, bd, state):
        hold = pos >= bd["end"]
        rows, blk, off = serving.bd_rows(cfg, tok, pos, bd, hold, table,
                                         trash, BLOCK)
        if broken == "no_clean_write":
            blk = blk.at[:, 0].set(trash)
        return serving.paged_logits(params, cfg, rows, k, v, blk, off,
                                    table, pos, state, blk[:, 1])

    out = []
    tok, pos, bd = srv.tok, srv.pos, srv.bd
    k, v, state = srv.k_pool, srv.v_pool, srv.state
    hold = jnp.zeros((1,), bool)
    try:
        if broken == "no_lag":
            pa.paged_attention = lambda *a, lag, **kw: attend(*a, **kw)
        forward = jax.jit(forward)
        fused = 0
        while int(pos[0]) < int(bd["end"][0]):
            assert bool(bd["masked"].any())     # no forward only writes
            fused += bool(bd["pending"][0])
            logits, k, v, state = forward(tok, k, v, pos, bd, state)
            out.append((int(pos[0]), int(bd["step"][0]),
                        np.asarray(logits[0])))
            _, tok, pos, bd = serving.bd_select(logits, tok, pos, bd, hold)
    finally:
        pa.paged_attention = attend
    # every block but the last was written, by the forward after it
    assert fused == len({at for at, _, _ in out}) - 1
    assert trash not in np.asarray(table)[0, :2]
    return out


def _reference_forwards(prompt, budget, hf=HF, **kw):
    seen = []
    d = hf["serving"]["diffusion"]
    toks, steps = ref.generate(
        hf, SEED, prompt, budget, BL, MASK, steps=d.get("denoising_steps", 0),
        threshold=d.get("threshold", 0.0),
        on_forward=lambda at, s, lg: seen.append((at, s, lg)), **kw)
    return seen, toks, steps


@within(120)
@pytest.mark.parametrize("P,budget", [(16, 12), (13, 9)])
def test_logits_at_every_block_and_step_through_the_paged_cache(model, P,
                                                                budget):
    """Block-causal prefill, then every denoising forward of every block
    through the paged cache: the four rows at their own positions, their K/V
    written where they lie, no mask among them, every earlier block as its
    clean forward left it — the reference's logits at that step's input,
    position for position (a prompt of 13 leaves one row to the first block
    as given)."""
    prompt = _prompt(P)
    want, _, _ = _reference_forwards(prompt, budget)
    got = _program_forwards(model, prompt, budget)
    assert [(a, s) for a, s, _ in got] == [(a, s) for a, s, _ in want]
    assert len(got) == 2 * -(-(P % BL + budget) // BL)
    for (at, s, lg), (_, _, wl) in zip(got, want):
        np.testing.assert_allclose(lg, wl, atol=ATOL, rtol=0,
                                   err_msg=f"block {at} step {s}")


def test_bfloat16_fails_the_tolerance():
    """The tolerance is float32's: the same comparison in bfloat16 — the
    precision the cell serves in — misses it by two orders."""
    prompt = _prompt(16)
    want, _, _ = _reference_forwards(prompt, 4)
    got = _program_forwards(_model(dtype=jnp.bfloat16), prompt, 4)
    worst = max(np.abs(lg - wl).max()
                for (_, s, lg), (_, _, wl) in zip(got, want) if s == 0)
    assert worst > 20 * ATOL, worst


@pytest.mark.parametrize("broken", ["no_clean_write", "no_lag"])
def test_a_finished_blocks_pages_come_from_its_clean_tokens(model, broken):
    """Without the clean rows' write the second block reads the first as its
    LAST denoising forward left it, half of it masks; with the clean rows
    allowed to see the block after them the first block's pages hold what no
    forward of the reference computes: other logits, either way."""
    prompt = _prompt(16)
    want, _, _ = _reference_forwards(prompt, 8)
    got = _program_forwards(model, prompt, 8, broken=broken)
    first = [np.abs(lg - wl).max() for (a, _, lg), (_, _, wl)
             in zip(got, want) if a == 16]
    later = [np.abs(lg - wl).max() for (a, s, lg), (_, _, wl)
             in zip(got, want) if a == 20 and s == 0]
    assert max(first) < ATOL and min(later) > 100 * ATOL, (first, later)


# -- (2) served tokens and commit steps, both rules ---------------------------

REQUESTS = [(8, 8), (10, 7), (5, 12), (16, 4), (3, 2)]


def _serve(model, requests, lookahead=3, slots=3, eos_id=None, **kw):
    srv = _server(model, slots=slots, **kw)
    prompts = {i: _prompt(P, i) for i, (P, _) in enumerate(requests)}
    for i, (_, budget) in enumerate(requests):
        srv.submit(i, prompts[i], budget, eos_id=eos_id)
    out = srv.run(lookahead=lookahead)
    return srv, prompts, {i: (out[i], _steps(srv, i)) for i in out}


@within(180)
@pytest.mark.parametrize("lookahead", [1, 8])
def test_static_rule_serves_the_references_tokens_and_steps(model, lookahead):
    """Five requests on three slots — prompts and budgets that are no
    multiples of four among them — under T = 2: the answer and the denoising
    step of every token are the reference's own generation, whatever the
    forwards a readback; a budget is returned exactly."""
    srv, prompts, got = _serve(model, REQUESTS, lookahead)
    for i, (P, budget) in enumerate(REQUESTS):
        toks, steps = ref.generate(HF, SEED, prompts[i], budget, BL, MASK,
                                   steps=2)
        assert got[i] == (toks, steps), i
        assert len(toks) == budget and set(steps) <= {0, 1}
    t = srv.timings
    # T = 2: two forwards a block and none that only writes one — every
    # block but a request's last is written by the forward after it (a
    # block that starts with fewer than two masked positions takes one)
    blocks = [-(-(P + budget) // BL) - P // BL for P, budget in REQUESTS]
    assert t["bd_forwards_write"] == 0
    assert t["bd_writes_fused"] == sum(blocks) - len(REQUESTS)
    short = sum(P % BL == BL - 1 for P, _ in REQUESTS)
    assert t["bd_forwards_denoise"] == 2 * sum(blocks) - short
    assert t["bd_rows"] == BL * (t["bd_forwards_denoise"]
                                 + t["bd_writes_fused"])
    stats = srv.stats()
    assert stats["diffusion_block"] == BL
    assert stats["bd_writes_fused"] == t["bd_writes_fused"]
    assert t["bd_tokens"] == BL * sum(blocks) - sum(P % BL
                                                    for P, _ in REQUESTS)
    # (the forwards a slot holds position for count below the line)
    assert 0 < stats["bd_tokens_per_forward"] <= BL / 2 \
        < 1.5 * t["bd_tokens"] / t["bd_forwards_denoise"]


@within(180)
def test_dynamic_rule_commits_one_or_several_a_step():
    """Under a threshold every masked position whose confidence passes it
    commits, and the most confident one always: with tau between the
    confidences this model has, some forwards commit one and some several —
    and tokens and steps are still the reference's."""
    hf = _hf(remasking="low_confidence_dynamic", threshold=0.0125)
    model = _model(hf)
    assert (model[0].diffusion_steps, model[0].diffusion_threshold) \
        == (0, 0.0125)
    srv, prompts, got = _serve(model, REQUESTS[:4])
    per_step = []
    for i, (P, budget) in enumerate(REQUESTS[:4]):
        toks, steps = ref.generate(hf, SEED, prompts[i], budget, BL, MASK,
                                   threshold=0.0125)
        assert got[i] == (toks, steps), i
        blocks = (P + np.arange(len(steps))) // BL
        per_step += [int(np.sum((blocks == b) & (np.asarray(steps) == s)))
                     for b in np.unique(blocks)
                     for s in range(max(steps) + 1)]
    assert 1 in per_step and max(per_step) > 1, per_step


def test_an_eos_inside_a_block_cuts_the_answer(model):
    """The EOS is the second token of the second block: the answer ends with
    it, and the slot is released."""
    srv, prompts, got = _serve(model, [(8, 12)])
    eos = got[0][0][5]
    assert eos not in got[0][0][:5]
    srv2, _, cut = _serve(model, [(8, 12)], eos_id=eos)
    assert cut[0] == (got[0][0][:6], got[0][1][:6])
    assert srv2.idle and len(srv2.free) == srv2.total_blocks


def test_a_commit_that_is_the_mask_id_stays_a_commit(model):
    """With the mask's id moved onto a token that this model's arg-max then
    is, committed positions hold that id and are NOT masked again: the state
    is a flag beside the token, and the answer is the reference's."""
    _, prompts, got = _serve(model, [(8, 8)])
    for mask in sorted(set(got[0][0]), key=got[0][0].count, reverse=True):
        hf = _hf(mask_token_id=int(mask))
        _, _, again = _serve(_model(hf), [(8, 8)])
        if mask in again[0][0]:
            break
    else:
        pytest.fail("no token of this model's answers is its own arg-max "
                    "when it is the mask")
    toks, steps = ref.generate(hf, SEED, prompts[0], 8, BL, int(mask),
                               steps=2)
    assert again[0] == (toks, steps) and mask in toks


@within(180)
def test_slots_in_different_phases_of_one_step_many(model):
    """Three requests admitted at three calls, two forwards apart: at every
    sub-step of the ``step_many(8)`` that follows one slot's forward carries
    the block it has just finished while another's carries none — what a
    slot's first four rows do is data, the program one — and each answer is
    what the request gets when served alone."""
    cfg, params = model
    srv = _server(model)
    reqs = [(9, 12), (16, 8), (6, 11)]
    prompts = [_prompt(P, 7 + i) for i, (P, _) in enumerate(reqs)]
    out = {}
    for i, (_, budget) in enumerate(reqs):
        srv.submit(i, prompts[i], budget)
        out.update(srv.step_many(1 + i))
    phases = np.asarray(srv.bd["step"]), np.asarray(srv.bd["pending"])
    assert len({(int(s), bool(m)) for s, m in zip(*phases)}) > 1, phases
    fused = srv.timings["bd_writes_fused"]
    while not srv.idle:
        out.update(srv.step_many(8))
    blocks = [-(-(P + budget) // BL) - P // BL for P, budget in reqs]
    assert 0 < fused < srv.timings["bd_writes_fused"] \
        == sum(blocks) - len(reqs)
    for i, (_, budget) in enumerate(reqs):
        alone = _server(model, slots=1)
        alone.submit(0, prompts[i], budget)
        want = alone.run(lookahead=1)[0]
        assert out[i] == want and _steps(srv, i) == _steps(alone, 0), i
    assert srv.timings["bd_forwards_hold"] > 0      # the tail of a batch


def test_a_shared_prefix_is_reused_from_the_prefix_cache(model):
    """A pool block's K/V depend only on tokens up to its own end (4 | 8),
    so the chain keys hold as they are: the second request reuses the first
    one's two prompt blocks — bit for bit what it computes alone — prefills
    its suffix under the block-causal mask behind them, and serves the same
    tokens at the same steps."""
    shared = _prompt(16, 3)
    a, b = shared + _prompt(5, 4), shared + _prompt(7, 5)
    srv = _server(model, slots=1)
    srv.submit("a", a, 6)
    srv.run(lookahead=2)
    srv.submit("b", b, 6)
    got = srv.run(lookahead=2)["b"]
    assert srv.stats()["prefix_hits"] == 1
    assert srv.stats()["prefix_shared_blocks"] == 2
    alone = _server(model, slots=1, prefix_cache=False)
    alone.submit("b", b, 6)
    assert alone.run(lookahead=2)["b"] == got
    assert _steps(srv, "b") == _steps(alone, "b")
    toks, steps = ref.generate(HF, SEED, b, 6, BL, MASK, steps=2)
    assert (got, _steps(srv, "b")) == (toks, steps)


# -- (3) the mask, the kernel, the counters ----------------------------------

def test_block_length_one_is_the_causal_prefill_bit_for_bit():
    """``diffusion_block`` 1 is the causal mask: the prefill's logits and
    cache are those of the same config without it, bit for bit (a config
    whose head width is hidden / heads: with a stated one the causal prefill
    is the blocked kernel's, another order of the same sums)."""
    from nvme_strom_tpu.models.transformer import (TransformerConfig,
                                                   init_params)
    cfg = TransformerConfig(vocab=96, d_model=64, n_layers=2, n_heads=4,
                            n_kv_heads=2, d_ff=128, dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg)
    toks = jnp.asarray([_prompt(24)], jnp.int32)
    got = {}
    for bl in (0, 1, BL):
        c = dataclasses.replace(cfg, diffusion_block=bl)
        lg, cache = decode.block_step(params, toks, c,
                                      decode.init_cache(c, 1, 24))
        got[bl] = np.asarray(lg), np.asarray(cache["k"])
    np.testing.assert_array_equal(got[0][0], got[1][0])
    np.testing.assert_array_equal(got[0][1], got[1][1])
    # ... and four is another mask, at every row (a block's last row sees
    # the rows the causal mask shows it, but not what THEY saw)
    assert np.abs(got[0][0] - got[BL][0]).max(-1).min() > 1e-3


def test_prefill_is_block_causal_over_the_prompt(model):
    """The admission's logits at a prompt's last row are the reference's
    under the block-causal mask, and NOT those of the causal mask (a row
    that is not the last of its block sees later rows)."""
    cfg, params = model
    prompt = _prompt(14)
    L = 16
    toks = np.zeros((1, L), np.int32)
    toks[0, :14] = prompt
    lg, _ = decode.block_step(params, jnp.asarray(toks), cfg,
                              decode.init_cache(cfg, 1, L))
    want = np.asarray(ref.logits(
        HF, SEED, toks, ref.block_causal(L, BL)[None], np.arange(L)[None],
        np.arange(L)[None]))
    np.testing.assert_allclose(np.asarray(lg)[0, :14], want[0, :14],
                               atol=ATOL, rtol=0)
    causal = np.asarray(ref.logits(
        HF, SEED, toks, np.tril(np.ones((L, L), bool))[None],
        np.arange(L)[None], np.arange(L)[None]))
    assert np.abs(causal[0, :12] - want[0, :12]).max() > 100 * ATOL


@pytest.mark.parametrize("nkv,g,hd", [(2, 2, 32), (4, 8, 128)])
def test_paged_kernel_at_four_rows_a_slot(nkv, g, hd):
    """``strom_kv_write`` places a slot's four rows and ``strom_paged_attn``
    runs them as g x 4 query rows a KV head over one walk with one limit
    (32 a head at the cell's group of 8): ``cache_attention`` with every row
    of the block seeing up to the block's end, slots at different lengths, a
    free slot beside them."""
    from nvme_strom_tpu.models.transformer import TransformerConfig
    from nvme_strom_tpu.ops.paged_attention import (paged_attention,
                                                    write_rows)
    B, R, bk, width = 3, BL, 16, 4
    cfg = TransformerConfig(n_heads=nkv * g, n_kv_heads=nkv,
                            d_model=nkv * g * hd, dtype=jnp.float32)
    rng = np.random.default_rng(5)
    pos = np.asarray([20, 44, 0], np.int32)         # block starts
    table = np.asarray([[3, 5, 0, 0], [1, 2, 6, 0], [0, 0, 0, 0]], np.int32)
    trash = 8
    k_pool = jnp.asarray(rng.normal(size=(2, trash + 1, nkv, bk, hd)),
                         jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=k_pool.shape), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, nkv * g, R, hd)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(B, nkv, R, hd)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(B, nkv, R, hd)), jnp.float32)
    blk = np.asarray([5, 6, trash], np.int32)
    k2, v2 = write_rows(k_pool, v_pool, kn, vn, blk, pos % bk, layer=1)
    for pool, new, old in ((k2, kn, k_pool), (v2, vn, v_pool)):
        for b in range(2):
            at = int(pos[b] % bk)
            np.testing.assert_array_equal(
                np.asarray(pool[1, blk[b], :, at:at + R]), np.asarray(new[b]))
        changed = np.asarray(pool != old)
        assert changed[0].sum() == 0 and changed[1, :5].sum() == 0
        assert changed[1, 5].sum() == changed[1, 6].sum() == nkv * R * hd
    limit = np.asarray([23, 47, 0], np.int32)
    got = paged_attention(q, k2, v2, table, limit, layer=1)
    assert got.shape == (B, nkv * g, R, hd)
    for b in range(2):
        n = int(limit[b]) + 1
        dense = [jnp.concatenate([p[1, j] for j in table[b]], axis=1)[:, :n]
                 for p in (k2, v2)]
        want = decode.cache_attention(
            q[b:b + 1], dense[0][None], dense[1][None],
            jnp.full((1, R), n - 1), cfg)
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want[0]),
                                   atol=2e-5, rtol=0)


@pytest.mark.parametrize("nkv,g,hd", [(2, 2, 32), (4, 8, 128)])
def test_paged_kernel_with_a_finished_block_beside_the_current(nkv, g, hd):
    """Eight rows a slot, ``[finished block | current block]``: a
    ``strom_kv_write`` call a half — the halves in one sublane tile, in two
    tiles of one pool block, in two pool blocks (``pos % block == 0``), the
    first half of a slot that owes nothing in the trash block — and
    ``strom_paged_attn`` with ``lag`` 4 (64 query rows a KV head at the
    cell's group of 8): ``cache_attention`` with each row seeing up to the
    end of its OWN block, the pages outside the written rows untouched."""
    from nvme_strom_tpu.models.transformer import TransformerConfig
    from nvme_strom_tpu.ops.paged_attention import (paged_attention,
                                                    write_rows)
    B, R, bk = 5, BL, 16
    cfg = TransformerConfig(n_heads=nkv * g, n_kv_heads=nkv,
                            d_model=nkv * g * hd, dtype=jnp.float32)
    rng = np.random.default_rng(6)
    # the current blocks' starts: same tile as the block before (f32: a
    # tile of 8 tokens), the next tile, the next pool block, nothing owed,
    # a free slot
    pos = np.asarray([20, 40, 32, 28, 0], np.int32)
    owed = np.asarray([True, True, True, False, False])
    table = np.asarray([[3, 5, 0, 0], [1, 2, 6, 0], [4, 7, 9, 0],
                        [10, 11, 0, 0], [0, 0, 0, 0]], np.int32)
    trash = 12
    k_pool = jnp.asarray(rng.normal(size=(2, trash + 1, nkv, bk, hd)),
                         jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=k_pool.shape), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, nkv * g, 2 * R, hd)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(B, nkv, 2 * R, hd)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(B, nkv, 2 * R, hd)), jnp.float32)
    at = np.stack([pos - R, pos], axis=1)
    live = np.stack([owed, np.arange(B) < 4], axis=1)
    blk = np.where(live, np.take_along_axis(table, np.maximum(at, 0) // bk,
                                            axis=1), trash).astype(np.int32)
    assert blk[2, 0] != blk[2, 1] and (blk[:2, 0] == blk[:2, 1]).all()
    k2, v2 = k_pool, v_pool
    for j in range(2):
        k2, v2 = write_rows(k2, v2, kn[:, :, j * R:(j + 1) * R],
                            vn[:, :, j * R:(j + 1) * R], blk[:, j],
                            at[:, j] % bk, layer=1)
    for pool, new, old in ((k2, kn, k_pool), (v2, vn, v_pool)):
        written = 0
        for b, j in zip(*np.nonzero(live)):
            o = int(at[b, j] % bk)
            np.testing.assert_array_equal(
                np.asarray(pool[1, blk[b, j], :, o:o + R]),
                np.asarray(new[b, :, j * R:(j + 1) * R]))
            written += nkv * R * hd
        changed = np.asarray(pool != old)
        assert changed[0].sum() == 0
        assert changed[1, :trash].sum() == written
    limit = np.where(np.arange(B) < 4, pos + R - 1, 0).astype(np.int32)
    got = paged_attention(q, k2, v2, table, limit, layer=1, lag=R)
    assert got.shape == (B, nkv * g, 2 * R, hd)
    assert np.isfinite(np.asarray(got)).all()
    for b in range(4):
        n = int(limit[b]) + 1
        dense = [jnp.concatenate([p[1, j] for j in table[b]], axis=1)[:, :n]
                 for p in (k2, v2)]
        sees = jnp.asarray([[n - 1 - R] * R + [n - 1] * R])
        want = decode.cache_attention(q[b:b + 1], dense[0][None],
                                      dense[1][None], sees, cfg)
        rows = slice(0, 2 * R) if owed[b] else slice(R, 2 * R)
        np.testing.assert_allclose(np.asarray(got[b][:, rows]),
                                   np.asarray(want[0][:, rows]),
                                   atol=2e-5, rtol=0)


def test_rows_that_do_not_fit_a_tile_are_refused():
    from nvme_strom_tpu.ops.paged_attention import write_rows
    pool = jnp.zeros((1, 3, 2, 16, 32), jnp.float32)
    new = jnp.zeros((2, 2, 3, 32), jnp.float32)         # 3 rows, a tile of 8
    with pytest.raises(NotImplementedError, match="3 rows a slot"):
        write_rows(pool, pool, new, new, np.zeros(2, np.int32),
                   np.zeros(2, np.int32), layer=0)


def test_expert_counters_count_four_rows_a_slot(model):
    """Every live row of a slot's forward is routed: the device's pair count
    is the host's, R x top-k x expert layers a slot-forward that takes part
    and as much again where a finished block rides along; a finished block's
    rows with nothing owed and a slot that holds position are routed
    nowhere."""
    srv, _, _ = _serve(model, REQUESTS[:4], lookahead=8)
    t = srv.timings
    assert t["bd_forwards_hold"] > 0 and t["bd_writes_fused"] > 0
    assert t["bd_rows"] == BL * (t["bd_forwards_denoise"]
                                 + t["bd_writes_fused"])
    assert t["moe_pairs"] == t["moe_pairs_routed"] == t["bd_rows"] * 2 * 2
    assert t["attn_grid_steps"] > t["attn_blocks_live"] > 0


# -- (4) transformers' own Qwen3-MoE under a 4-D block mask ------------------

@pytest.fixture(scope="module")
def hf_qwen3_moe(tmp_path_factory):
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    if not hasattr(transformers, "Qwen3MoeForCausalLM"):
        pytest.skip("this transformers has no Qwen3-MoE")
    d = tmp_path_factory.mktemp("hf_qwen3_moe")
    keys = {k: v for k, v in HF.items() if k not in ("model_type", "serving")}
    cfg = transformers.Qwen3MoeConfig(
        **dict(keys, vocab_size=128, max_position_embeddings=128),
        attn_implementation="eager")
    torch.manual_seed(0)
    model = transformers.Qwen3MoeForCausalLM(cfg).eval()
    with torch.no_grad():                   # every norm off its init
        for name, p in model.named_parameters():
            if name.endswith("norm.weight"):
                p.add_(0.3 * torch.randn_like(p))
    model.save_pretrained(d, safe_serialization=True)
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(dict(HF, vocab_size=128, max_position_embeddings=128), f)
    return str(d), model


@within(120)
def test_block_causal_forward_matches_hf_qwen3_moe(hf_qwen3_moe, tmp_path):
    """Converted Qwen3-MoE weights (``mlp.gate``, ``mlp.experts.E.*_proj``,
    ``self_attn.{q,k}_norm``) through the program's prefill under
    ``diffusion_block`` 4: per-head q/k norms before rotary at a stated head
    width, softmax -> top-k -> renormalise, and the logits of EVERY row are
    transformers' own under the 4-D block mask."""
    torch = pytest.importorskip("torch")
    from nvme_strom_tpu.models.transformer import TransformerConfig
    from nvme_strom_tpu.parallel.weights import LazyCheckpoint
    hf_dir, model = hf_qwen3_moe
    out = str(tmp_path / "converted")
    summary = convert_llama.convert(hf_dir, out)
    assert summary["skipped"] == []
    with open(os.path.join(out, "strom_config.json")) as f:
        cfg = TransformerConfig(dtype=jnp.float32, **json.load(f))
    assert (cfg.diffusion_block, cfg.mask_token_id, cfg.head_dim) \
        == (BL, MASK, 32)
    params = LazyCheckpoint(out).load_sharded(
        lambda name, shape: jax.sharding.SingleDeviceSharding(
            jax.devices()[0]))
    assert params["layers.1.moe_w_gate"].shape == (8, 64, 32)
    assert params["layers.0.q_norm"].shape == (32,)
    L = 24
    toks = np.random.default_rng(0).integers(0, 128, (2, L))
    see = torch.from_numpy(ref.block_causal(L, BL))
    mask = torch.zeros(L, L).masked_fill(~see, float("-inf"))[None, None]
    with torch.no_grad():
        want = model(torch.from_numpy(toks),
                     attention_mask=mask.expand(2, 1, L, L)
                     ).logits.float().numpy()
        causal = model(torch.from_numpy(toks)).logits.float().numpy()
    assert np.abs(want - causal).max() > 1e-2
    with jax.default_matmul_precision("highest"):
        ours, _ = decode.block_step(params, jnp.asarray(toks, jnp.int32),
                                    cfg, decode.init_cache(cfg, 2, L))
    np.testing.assert_allclose(np.asarray(ours), want, atol=3e-4, rtol=3e-4)


# -- (5) the config's keys and what refuses it -------------------------------

def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        return next(r for r in map(json.loads, f)
                    if r["name"] == "SDAR-30B-A3B-Chat")


def test_config_from_the_catalog_rows_keys():
    """The row's config as published (it states neither the block length nor
    the mask: the assumed defaults), and the benchmark's file: every width
    the row's, six layers, the cell's ``serving.diffusion``."""
    row = _catalog_row()
    cfg = config_from_hf(row["config"])
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.vocab) == (48, 2048, 32, 4, 128, 151936)
    assert (cfg.n_experts, cfg.expert_top_k, cfg.d_expert,
            cfg.router_kind, cfg.router_norm_topk, cfg.d_shared) \
        == (128, 8, 768, "softmax", True, 0)
    assert cfg.expert_layers == tuple(range(48)) and cfg.qk_norm
    assert cfg.rope_theta == 1e6 and not cfg.tie_embed
    assert (cfg.diffusion_block, cfg.mask_token_id, cfg.diffusion_steps,
            cfg.diffusion_threshold) == (4, 151669, 0, 0.0)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sdar-30b-a3b-chat.json")) as f:
        file = json.load(f)
    for key, value in row["config"].items():
        if key not in file["reduced"]:
            assert file[key] == value, key
    assert sorted(file["reduced"]) == ["max_position_embeddings",
                                       "num_hidden_layers"]
    cell = config_from_hf(file)
    assert dataclasses.replace(cfg, n_layers=6, max_seq=1536,
                               mlp_kinds=("experts",) * 6,
                               diffusion_steps=2) == cell


@pytest.mark.parametrize("key,value,msg", [
    ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("mlp_only_layers", [0], "mlp_only_layers"),
    ("use_sliding_window", True, "sliding window"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4}, "rope_scaling"),
    ("serving", {"diffusion": {"remasking": "entropy_bounded"}}, "remasking"),
    ("serving", {"diffusion": {"remasking": "low_confidence_dynamic"}},
     "threshold"),
])
def test_config_raises_on_what_is_not_implemented(key, value, msg):
    with pytest.raises(ValueError, match=msg):
        config_from_hf(dict(HF, **{key: value}))


def test_a_pool_block_holds_whole_diffusion_blocks(model):
    cfg, params = model
    with pytest.raises(ValueError, match="multiples of the config's "
                                         "diffusion_block 4"):
        DecodeServer(params, cfg, max_batch=2, max_len=64, block_len=6)
    with pytest.raises(ValueError, match="diffusion_block"):
        DecodeServer(params, cfg, max_batch=2, max_len=62, block_len=8)


def test_what_makes_a_token_a_step_refuses_it_by_name(model):
    """Sampling, ``decode.generate``, speculative decoding, a mesh, the
    training path, a session exported inside a block: each says what it
    cannot do and where such a config is served."""
    from nvme_strom_tpu.models import speculative, transformer
    from nvme_strom_tpu.parallel import shardings
    cfg, params = model
    toks = jnp.asarray([_prompt(8)], jnp.int32)
    srv = _server(model)
    for call in (
            lambda: srv.submit(0, _prompt(8), 4, temperature=0.7),
            lambda: srv.export_sessions(),
            lambda: decode.generate(params, toks, cfg, 4),
            lambda: speculative.speculative_generate(params, params, toks,
                                                     cfg, 4),
            lambda: shardings.param_specs(cfg),
            lambda: transformer.forward(params, toks, cfg)):
        with pytest.raises(NotImplementedError,
                           match="diffusion over blocks of 4"):
            call()

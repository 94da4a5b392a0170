"""Gated-delta-rule layers beside gated full attention, every MLP routed
experts beside a gated shared expert, through the program at a tiny size on
the CPU in float32: the two kernels in interpret mode against the recurrence
a token at a time; the paged server — compiled prefill through the chunked
scan, then decode through BOTH caches, the full layers' pages and the state
pools — against the benchmark's plain reference
(``benchmark/reference/qwen3_next.py``, which imports nothing of the
program) on the benchmark's seeded weights, in logits; what a freed slot leaves
behind.  (Each mechanism against the reference with it switched off, the
shares of a deployment and what such a configuration refuses:
``tests/test_gdn_controls.py``.)"""

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

from benchmark import weights_gdn as WG                         # noqa: E402
from delta_rule_helpers import (                                # noqa: E402,F401
    recurrence as _recurrence, spy)
from benchmark.reference import qwen3_next as ref               # noqa: E402
from nvme_strom_tpu.models import decode, serving, ssm          # noqa: E402
from nvme_strom_tpu.models.serving import DecodeServer          # noqa: E402
from nvme_strom_tpu.ops import gdn                              # noqa: E402
from nvme_strom_tpu.ops.gdn import gdn_scan, gdn_update         # noqa: E402
from nvme_strom_tpu.tools.convert_llama import config_from_hf   # noqa: E402

#: Qwen3-Next's keys at a tiny size: one period of 3 delta-rule layers and
#: a full one; 2 key heads and 4 value heads of 16; 4 query heads over 2 KV
#: heads of 32 with rotary on the first 8; the router scores 16 experts
#: top-3 and this device holds 4 of them (4..7) beside the shared expert
HF = dict(
    model_type="qwen3_next", hidden_size=64, vocab_size=96,
    num_hidden_layers=4, full_attention_interval=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, partial_rotary_factor=0.25,
    rope_theta=10000, rope_scaling=None, linear_conv_kernel_dim=4,
    linear_key_head_dim=16, linear_value_head_dim=16, linear_num_key_heads=2,
    linear_num_value_heads=4, intermediate_size=128,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    num_experts=4, expert_share={"routed": 16, "offset": 4},
    num_experts_per_tok=3, norm_topk_prob=True, decoder_sparse_step=1,
    mlp_only_layers=[], rms_norm_eps=1e-6, hidden_act="silu",
    use_sliding_window=False, tie_word_embeddings=False,
    max_position_embeddings=256)
SEED = 31
BLOCK = 8
#: float32 on both sides; what is left is the order of the sums (the chunked
#: scan's products against the recurrence's, the blocked softmax, the grouped
#: expert product) through 4 layers: a few 1e-5 on logits of size ~4
ATOL = 3e-4


def _model(hf=HF):
    cfg = dataclasses.replace(config_from_hf(hf), dtype=jnp.float32,
                              gdn_chunk=16)
    params = {k: v.astype(jnp.float32)
              for k, v in WG.make_params(hf, SEED).items()}
    return cfg, params


@pytest.fixture(scope="module")
def model():
    return _model()


def _server(model, slots=3, **kw):
    cfg, params = model
    return DecodeServer(params, cfg, max_batch=slots, max_len=128,
                        total_blocks=48, block_len=BLOCK, **kw)


def _prompt(n, salt=0):
    return np.random.default_rng([n, salt]).integers(
        0, HF["vocab_size"], n).tolist()


def _reference(prompt, tokens, hf=HF, low=None):
    """Reference logits (len(tokens), vocab) at the positions that predict
    each served token, teacher-forced on them."""
    seq = np.asarray([prompt + tokens], np.int32)
    at = len(prompt) - 1 + np.arange(len(tokens))[None]
    return np.asarray(ref.logits_at(hf, SEED, seq, at, low=low)[0])


# -- (1) the kernels against the recurrence a token at a time ---------------

def _draw(b, m, H=4, dk=16, dv=32, seed=0):
    rng = np.random.default_rng([seed, b, m])
    q = rng.normal(size=(b, m, H, dk))
    k = rng.normal(size=(b, m, H, dk))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    # log-decays from a few tokens to thousands — some so steep that α is 0
    # in float32 — and some β near 1
    alpha = -np.exp(2.0 * rng.normal(size=(b, m, H)) - 3.0)
    alpha[:, m // 3, 0] = -200.0
    beta = 1 / (1 + np.exp(-2.0 * rng.normal(size=(b, m, H))))
    return tuple(jnp.asarray(t, jnp.float32) for t in (
        q, k, rng.normal(size=(b, m, H, dv)), alpha, beta,
        rng.normal(size=(b, H, dk, dv))))


@pytest.mark.parametrize("m,n_valid,chunk", [
    (64, (64, 64), 64),         # one whole chunk
    (128, (128, 91), 64),       # two, one sequence ragged
    (100, (100, 37), 64),       # no multiple of the chunk
    (200, (1, 200), 64),        # one valid row beside four chunks
    (7, (7, 3), 64),            # shorter than a chunk: one of 8 rows
    (96, (96, 0), 32),          # a row that holds no prompt
    (48, (48, 20), 16),
    (20, (20, 11), 64),         # one chunk of 24 rows: a block and a half
    (40, (40, 33), 64),         # of 40: two blocks and a half
    (56, (56, 17), 64),         # of 56: three and a half
    (144, (144, 130), 64),      # three chunks, the last of one block
])
def test_gdn_scan_matches_the_recurrence(m, n_valid, chunk):
    """The chunked form — the triangular solve in blocks of rows, the
    products, the state carried between chunks — equals the recurrence row
    by row at lengths that are and are not multiples of the chunk, and at
    chunks the solve's blocks do and do not divide; rows past
    ``n_valid`` leave the state where the last valid row left it, and a
    sequence with none keeps the state it came in with."""
    q, k, v, alpha, beta, s0 = _draw(2, m)
    valid = jnp.arange(m)[None] < jnp.asarray(n_valid)[:, None]
    o, s = gdn_scan(q, k, v, alpha, beta, s0, valid, chunk=chunk)
    want_o, want_s = _recurrence(q, k, v, alpha, beta, s0, valid)
    np.testing.assert_allclose(
        np.where(valid[..., None, None], o, 0),
        np.where(valid[..., None, None], want_o, 0), atol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5)
    if 0 in n_valid:
        np.testing.assert_array_equal(s[1], s0[1])


def test_gdn_scan_carries_its_state_across_two_calls():
    """A prompt in two calls — the second starting from the state the first
    left — gives the rows and the state of one call over the whole."""
    q, k, v, alpha, beta, s0 = _draw(1, 150, seed=3)
    o, s = gdn_scan(q, k, v, alpha, beta, s0, chunk=32)
    o1, s1 = gdn_scan(q[:, :70], k[:, :70], v[:, :70], alpha[:, :70],
                      beta[:, :70], s0, chunk=32)
    o2, s2 = gdn_scan(q[:, 70:], k[:, 70:], v[:, 70:], alpha[:, 70:],
                      beta[:, 70:], s1, chunk=32)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), o, atol=2e-5)
    np.testing.assert_allclose(s2, s, atol=2e-5)


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_gdn_scan_with_keys_that_repeat(chunk):
    """Every row of a chunk with the SAME key and β = 1: the matrix the
    chunk solves is all ones under its diagonal, whose powers grow like
    binomials before they cancel — the substitution does not care, in one
    block (chunk 16) or with one block's correction handed to the next
    three (chunk 64)."""
    q, k, v, alpha, beta, s0 = _draw(1, 64, seed=5)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    beta, alpha = jnp.ones_like(beta), jnp.full_like(alpha, -0.001)
    o, s = gdn_scan(q, k, v, alpha, beta, s0, chunk=chunk)
    want_o, want_s = _recurrence(q, k, v, alpha, beta, s0)
    np.testing.assert_allclose(o, want_o, atol=5e-5)
    np.testing.assert_allclose(s, want_s, atol=5e-5)


@pytest.mark.parametrize("c", [8, 24, 64])
@pytest.mark.parametrize("block", [8, 16])
def test_the_blocked_solve_is_the_substitution_row_by_row(block, c):
    """(I + A) X = B for a drawn A = tril(β (K Kᵀ ⊙ Γ), −1) — unit keys, β
    in (0, 2), decays — and B: a block's rows by substitution and every
    later row by one product equal the substitution a row at a time — at
    blocks that divide the chunk, that do not (24 by 16) and that hold it
    whole (8 by 16)."""
    rng = np.random.default_rng([c, block])
    k = rng.normal(size=(c, 16))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = np.cumsum(-np.exp(2.0 * rng.normal(size=c) - 3.0))
    a = np.tril(rng.uniform(0, 2, (c, 1)) * (k @ k.T)
                * np.exp(g[:, None] - g[None]), -1).astype(np.float32)
    b = rng.normal(size=(c, 40)).astype(np.float32)
    want = b.copy()
    for r in range(c - 1):
        want = want - a[:, r:r + 1] * want[r:r + 1, :]
    got, = gdn.solve_unit_lower(jnp.asarray(a)[None], jnp.asarray(b)[None],
                                block)
    np.testing.assert_allclose(got, want, atol=1e-6 * np.abs(want).max())
    # and it IS a solve, against float64
    np.testing.assert_allclose(
        (np.eye(c) + a.astype(np.float64)) @ np.asarray(got, np.float64), b,
        atol=1e-5 * np.abs(want).max())


def test_the_solves_couplings_ask_for_float32s_own_precision():
    """Every product the solve makes has float32 operands and asks for
    ``Precision.HIGHEST``: interpret mode multiplies exactly whatever a
    product asks for, so only its request shows here that the chip will not
    run a coupling in one bfloat16 pass (on the chip: ``kernel_probe
    gdn_scan``'s ``solve_rel_err``)."""
    jaxpr = jax.make_jaxpr(lambda a, x: gdn.solve_unit_lower(a, x, 16))(
        jnp.zeros((3, 64, 64)), jnp.zeros((3, 64, 40)))
    dots = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 3                  # one a block but the last
    for e in dots:
        assert {v.aval.dtype for v in e.invars} == {jnp.dtype("float32")}
        assert set(e.params["precision"]) == {jax.lax.Precision.HIGHEST}
        assert e.params["preferred_element_type"] == jnp.float32


@pytest.mark.parametrize("case", ["q3n", "olmoh"])
def test_the_kernel_probe_counts_what_a_scan_solves(case, capsys):
    """``kernel_probe gdn_scan`` at its CPU size (mechanics only: no time it
    prints here is a device's): one line a prompt shape, the (head, chunk)
    solves of the call and the bytes of its operands, and the first rows
    within rounding of the recurrence — bfloat16's as served, float32's
    with float32 operands, where interpret mode's products are exact — and
    the chunk's solve alone within float32's rounding of a float64 solve."""
    import json

    from nvme_strom_tpu.tools import kernel_probe
    kernel_probe.probe_gdn_scan(case, repeats=1)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    H, dk, dv, beta_max, shapes = kernel_probe.GDN_SCAN_CASES[case]
    assert [ln["prompts"] for ln in lines] == [b for b, _ in shapes]
    for ln in lines:
        assert ln["kernel"] == "strom_gdn_scan" and ln["beta_max"] == beta_max
        h, (k, v), b, m = ln["heads"], ln["widths"], ln["prompts"], ln["rows"]
        assert (H % h, dk / k, dv / v) == (0, 8, 8)
        assert ln["head_chunks"] == b * h * -(-m // 64)
        assert ln["mib_a_call"] == round(
            (b * m * h * (4 * (k + v) + 8) + 8 * b * h * k * v) / 2 ** 20, 2)
        assert ln["rows_checked"] <= m
        assert ln["rel_err_rows"] < 2e-2 and ln["rel_err_rows_f32"] < 2e-6
        assert ln["solve_rel_err"] < 2e-6
        assert ln["ms_a_call"] > 0 and "bytes_roofline_pct" not in ln


def test_gdn_update_is_one_step_in_place():
    """One token of five slots against a pool of seven rows: the slots' rows
    move as the recurrence says, free slots (rows 5 and 6 named twice) touch
    the sacrificial rows only, and nobody else's row changes."""
    q, k, v, alpha, beta, s0 = _draw(5, 1, H=32, seed=7)
    pool = jnp.concatenate([s0, 1.0 + jnp.zeros((2,) + s0.shape[1:])])
    sidx = jnp.asarray([3, 0, 6, 1, 6], jnp.int32)
    o, new = jax.jit(gdn_update, donate_argnums=(0,))(
        jnp.array(pool), sidx, q[:, 0], k[:, 0], v[:, 0], alpha[:, 0],
        beta[:, 0])
    want_o, want_s = _recurrence(q, k, v, alpha, beta, pool[sidx])
    np.testing.assert_allclose(o, want_o[:, 0], atol=1e-5)
    live = np.asarray([0, 1, 3])                  # slots 0, 1, 3 of sidx
    np.testing.assert_allclose(new[sidx[live]], want_s[live], atol=1e-5)
    for untouched in (2, 4, 5):
        np.testing.assert_array_equal(new[untouched], pool[untouched])


# -- (2) the program against the reference -------------------------------------

@pytest.mark.parametrize("lookahead", [1, 3])
def test_prefill_then_decode_through_both_caches(model, spy, lookahead):
    """Mixed prompt lengths — under a chunk of 16, over several, no multiple
    of chunk or block — and more requests than slots so that slots free and
    refill: every token's logits are the reference's full forward pass,
    prefill's and decode's alike."""
    srv = _server(model, slots=3)
    prompts = {"a": _prompt(10), "b": _prompt(37), "c": _prompt(3),
               "d": _prompt(64), "e": _prompt(50)}
    budgets = {"a": 9, "b": 7, "c": 14, "d": 11, "e": 6}
    for rid, p in prompts.items():
        srv.submit(rid, p, budgets[rid])
    out = spy(srv, lookahead)
    assert set(out) == set(prompts)
    for rid, (toks, logits) in out.items():
        assert len(toks) == budgets[rid]
        np.testing.assert_allclose(logits, _reference(prompts[rid], toks),
                                   atol=ATOL, err_msg=rid)
    st = srv.stats()
    # 3 delta-rule layers: S (4, 16, 16) float32 and 3 rows of 2·32 + 64
    # conv channels; 1 full layer: K and V of 2 KV heads of 32
    assert st["state_layers"] == 3 and st["kv_layers"] == 1
    assert st["state_bytes_per_slot"] == 3 * (4 * 16 * 16 + 3 * 128) * 4
    assert st["state_slots"] == 4
    assert st["kv_bytes_per_token"] == 2 * 2 * 32 * 4
    assert srv.state["s"][0].shape == (4, 4, 16, 16)
    assert srv.state["conv"][2].shape == (4, 3, 128)
    t = srv.timings
    assert t["scan_tokens"] == sum(len(p) for p in prompts.values())
    assert 0 < t["moe_pairs"] < t["moe_pairs_routed"]
    assert st["blocks_free"] == st["blocks_total"]
    assert st["prefix_hits"] == 0 and st["prefix_cached_blocks"] == 0


def test_generate_is_the_servers_tokens(model):
    """``decode.generate`` (dense caches, every step a block of one row
    through the scan kernel) and the server (pages and state pools, the
    update kernel) produce the same greedy tokens."""
    cfg, params = model
    prompt = _prompt(29)
    want = np.asarray(decode.generate(
        params, jnp.asarray([prompt], jnp.int32), cfg, 12))[0]
    srv = _server(model, slots=1)
    srv.submit("g", prompt, 12)
    assert srv.run()["g"] == want.tolist()


def test_alone_and_among_three_others_gives_the_same_logits(model, spy):
    """No slot reads another's row of the state pools: a request served
    alone and served in a full batch has the same logits."""
    prompt = _prompt(23, salt=9)
    alone = _server(model, slots=1)
    alone.submit("r", prompt, 8)
    _, want = spy(alone)["r"]
    srv = _server(model, slots=4)
    for i in range(3):
        srv.submit(i, _prompt(23, salt=i), 10)
    srv.submit("r", prompt, 8)
    _, got = spy(srv)["r"]
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_released_slot_answers_as_a_fresh_server(model):
    """Releasing a slot clears nothing; the next prompt's prefill overwrites
    its state and tail whole, so a second request gives what a fresh server
    gives."""
    srv = _server(model, slots=1)
    srv.submit("first", _prompt(2, salt=1), 6)
    srv.run()
    prompt = _prompt(2, salt=2)         # shorter than the conv's reach
    srv.submit("second", prompt, 7)
    fresh = _server(model, slots=1)
    fresh.submit("second", prompt, 7)
    assert srv.run()["second"] == fresh.run()["second"]


def test_pad_rows_leave_state_and_conv_tail_untouched(model):
    """A right-padded block: state and tail are those of the valid rows
    alone, whatever the padding holds."""
    cfg, params = model
    h = jax.random.normal(jax.random.key(2), (2, 24, 64), jnp.float32)
    n = jnp.asarray([24, 9])
    out, s, tail = ssm.gdn_block(h, params, "layers.0.", cfg, n_valid=n)
    o9, s9, tail9 = ssm.gdn_block(h[1:, :9], params, "layers.0.", cfg)
    np.testing.assert_allclose(s[1], s9[0], atol=1e-5)
    np.testing.assert_allclose(tail[1], tail9[0], atol=1e-6)
    np.testing.assert_allclose(out[1, :9], o9[0], atol=1e-5)

"""The decode step writes the donated pool in place (models/serving.py):
the server serves what it served before that step, traces nothing of the
pool's size outside the kernels, and counts its live blocks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nvme_strom_tpu.models.serving import DecodeServer
from nvme_strom_tpu.models.transformer import (
    TransformerConfig, init_params, tiny_config)
from test_serving import _solo


#: what the server below served at PR 26 (commit 254ed1e: rows scattered
#: into the pool with ``.at[].set`` and one layer sliced out for the kernel),
#: recorded on this CPU; the step that writes the donated pool in place and
#: reads it where it lies has to serve the very same tokens
SERVED_AT_PR26 = {
    "plain_f32": {
        "r0": [42, 68, 50, 44, 54, 19, 40, 26, 58],
        "r1": [75, 6, 45, 70, 68, 39],
        "r2": [49, 123, 79, 117, 90, 58, 100, 67, 19, 40, 26],
        "r3": [48, 99, 57, 49, 98],
        "r4": [100, 125, 3, 107, 94, 26, 58, 100],
    },
    "plain_bf16": {
        "r0": [42, 68, 50, 44, 54, 19, 40, 26, 58],
        "r1": [75, 6, 53, 108, 40, 39],
        "r2": [49, 123, 79, 117, 90, 58, 100, 67, 19, 40, 26],
        "r3": [48, 99, 57, 49, 98],
        "r4": [100, 125, 3, 107, 94, 26, 58, 100],
    },
    "hybrid": {
        "r0": [93, 6, 5, 93, 72, 31, 5, 5, 27],
        "r1": [12, 18, 41, 41, 73, 41],
        "r2": [36, 88, 74, 83, 31, 93, 66, 62, 79, 3, 33],
        "r3": [79, 26, 76, 43, 12],
        "r4": [57, 79, 32, 93, 65, 6, 63, 53],
    },
}


def _pr26_model(kind):
    """(params, cfg, block_len, total_blocks) of the recorded runs: the tiny
    decoder of this file in float32 and in bfloat16, and test_hybrid.py's
    hybrid (its attention layer keeps K/V, its three mamba layers state)."""
    if kind == "hybrid":
        import dataclasses

        import test_hybrid as H
        cfg = dataclasses.replace(H.config_from_hf(H.HF), dtype=jnp.float32)
        params = {k: v.astype(jnp.float32)
                  for k, v in H.WH.make_params(H.HF, H.SEED).items()}
        return params, cfg, 8, 12
    dtype = jnp.float32 if kind == "plain_f32" else jnp.bfloat16
    cfg = TransformerConfig(**{**tiny_config().__dict__, "dtype": dtype})
    params = {k: v.astype(dtype)
              for k, v in init_params(jax.random.key(0), cfg).items()}
    return params, cfg, 4, 24


@pytest.mark.parametrize("kind", sorted(SERVED_AT_PR26))
def test_paged_server_serves_what_it_served_before_the_in_place_step(kind):
    """Five requests behind one shared head on two slots, lookahead 2:
    every slot is freed and admitted again, the plain decoder's later
    admissions hit the prefix cache (a hybrid has no prefix reuse), free
    slots write the trash block meanwhile — token for token what the
    scatter-and-slice step served."""
    params, cfg, bk, blocks = _pr26_model(kind)
    rng = np.random.default_rng(27)
    shared = rng.integers(0, cfg.vocab, 3 * bk + 1).tolist()
    reqs = [(f"r{i}", shared + rng.integers(0, cfg.vocab, n).tolist(), m)
            for i, (n, m) in enumerate([(2, 9), (5, 6), (1, 11), (7, 5),
                                        (3, 8)])]
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64,
                       total_blocks=blocks, block_len=bk)
    for rid, prompt, budget in reqs:
        srv.submit(rid, prompt, budget)
    assert srv.run(lookahead=2) == SERVED_AT_PR26[kind]
    assert srv.timings["admits"] == 5               # 2 slots: 3 re-admitted
    assert srv.stats()["prefix_hits"] == (0 if kind == "hybrid" else 3)


def _pool_sized_eqns(jaxpr, sizes, found):
    """Equations outside the kernels that make something pool-sized."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pool_sized_eqns(sub, sizes, found)
        if any(int(np.prod(v.aval.shape)) in sizes for v in eqn.outvars
               if hasattr(v.aval, "shape")):
            found.append(eqn.primitive.name)
    return found


@pytest.mark.parametrize("kind", ["plain_f32", "hybrid"])
def test_decode_step_traces_no_pool_sized_op_outside_the_kernels(kind):
    """The structure of the step (``paged_logits``) on any platform: nothing
    but the two kernels produces a value of the pool's size or of one
    layer's — no scatter, no slice, no gather.  What the TPU's compiler
    makes of it is pinned by tests/test_chip_compile.py."""
    from nvme_strom_tpu.models import serving, ssm
    params, cfg, bk, blocks = _pr26_model(kind)
    B, L = 2, len(cfg.attn_layers)
    pool = jnp.zeros((L, blocks + 1, cfg.n_kv_heads, bk, cfg.head_dim),
                     cfg.dtype)
    state = ssm.init_state(cfg, B + 1) if cfg.mamba_layers else None
    i32 = jnp.zeros((B,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: serving.paged_logits(params, cfg, *a))(
        i32, pool, pool, i32, i32, jnp.zeros((B, 64 // bk), jnp.int32), i32,
        state, i32)
    assert str(jaxpr).count("pallas_call") >= 2 * L
    assert not _pool_sized_eqns(jaxpr.jaxpr, {pool.size, pool.size // L}, [])


# -- paged attention walks only what is live --------------------------------

@pytest.mark.parametrize("kind", ["plain_f32", "hybrid"])
def test_mixed_batch_serves_generates_tokens_and_counts_its_live_blocks(
        kind):
    """Short and long prompts on two slots of a table 8 blocks wide, one
    step a call: slot 0 finishes twice and is admitted again while slot 1's
    long request runs on, and at the end slot 1 lies free (a stale ``pos``
    over a table row of zeros) beside the last request.  Tokens are
    ``generate()``'s, and the two counters are what the prompts' lengths
    say: a request of prompt ``s`` and budget ``m`` takes ``m - 1`` decode
    steps at positions ``s .. s + m - 2`` (its first token is the
    prefill's), each reading ``pos // block + 1`` table entries, where an
    unbounded walk reads slots x table width at every step; the kernel's
    grid is a step for each of those entries and one for a free slot."""
    params, cfg, _, _ = _pr26_model(kind)
    bk = 8
    rng = np.random.default_rng(29)
    reqs = {f"r{i}": (rng.integers(0, cfg.vocab, s).tolist(), m)
            for i, (s, m) in enumerate([(3, 4), (30, 9), (17, 6), (5, 3)])}
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64,
                       total_blocks=12, block_len=bk)
    for rid, (prompt, budget) in reqs.items():
        srv.submit(rid, prompt, budget)
    got = srv.run()
    for rid, (prompt, budget) in reqs.items():
        assert got[rid] == _solo(params, cfg, prompt, budget), rid
    # r0 holds slot 0 for calls 1-3, r2 for 4-8, r3 for 9-10; r1 slot 1 for
    # calls 1-8: ten steps, the last two with slot 1 free
    assert srv.timings["steps"] == 10 and srv.timings["admits"] == 4
    live = sum(pos // bk + 1 for prompt, budget in reqs.values()
               for pos in range(len(prompt), len(prompt) + budget - 1))
    assert live == 3 * 1 + (2 * 4 + 6 * 5) + 5 * 3 + 2 * 1 == 58
    stats = srv.stats()
    # 58 of the 160 entries ten unbounded steps would have walked
    assert (stats["attn_blocks_live"], stats["attn_blocks_table"]) \
        == (live, 10 * 2 * (64 // bk))
    # the grid of one layer's call is a step a live entry, and in calls 9
    # and 10 one more for the free slot 1 (handed ``pos`` 0): 60, where the
    # (slots x longest slot) grid made 2 x 2 x 4 + 6 x 2 x 5 + 2 x 2 x 1 = 80
    assert stats["attn_grid_steps"] == 58 + 2 * 1
    assert stats["attn_blocks_live"] <= stats["attn_grid_steps"]

"""Paged-attention kernel (ops/paged_attention.py) vs dense
block-gather reference, ragged slot lengths, and the row writer against
the scatter it replaced; interpret mode on CPU.  The pools are 5-D, every
layer's in one array: a 4-D pool of the reference is a pool of one layer.
The kernel's walk ends at a slot's last live block (one grid step a live
table entry, ``walk_list``'s list, every KV head of a block in one grid
step): pools poisoned outside what a slot owns, free slots, a batch as
ragged as a long-context cell's, the list and the grid itself are pinned
below, in both layouts of the pool."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nvme_strom_tpu.ops.paged_attention import (paged_attention, walk_list,
                                                write_rows)


def _reference(q, kp, vp, table, pos):
    b, nh, _, d = q.shape
    nkv = kp.shape[1]
    g = nh // nkv
    out = np.empty_like(q)
    for bi in range(b):
        ks = np.concatenate([kp[t] for t in table[bi]], axis=1)
        vs = np.concatenate([vp[t] for t in table[bi]], axis=1)
        S = ks.shape[1]
        qf = q[bi].reshape(nkv, g, d)
        s = np.einsum("kgd,ksd->kgs", qf, ks) / np.sqrt(d)
        s = np.where(np.arange(S)[None, None, :] <= pos[bi], s, -1e30)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[bi] = np.einsum("kgs,ksd->kgd", p, vs).reshape(nh, 1, d)
    return out


def test_paged_matches_dense_ragged():
    rng = np.random.default_rng(0)
    b, nh, nkv, d = 3, 4, 2, 16
    block_k, n_pool, max_blocks = 8, 12, 4
    kp = rng.standard_normal((n_pool, nkv, block_k, d)).astype(np.float32)
    vp = rng.standard_normal((n_pool, nkv, block_k, d)).astype(np.float32)
    q = rng.standard_normal((b, nh, 1, d)).astype(np.float32)
    table = np.array([[3, 7, 1, 0], [5, 2, 0, 0], [9, 4, 8, 11]],
                     np.int32)
    pos = np.array([20, 9, 31], np.int32)    # lengths 21, 10, 32
    got = np.asarray(paged_attention(
        jnp.asarray(q), jnp.asarray(kp)[None], jnp.asarray(vp)[None],
        jnp.asarray(table), jnp.asarray(pos)))
    want = _reference(q, kp, vp, table, pos)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_padding_blocks_hold_garbage_safely():
    """Padding table entries point at a block full of NaN — the masked
    columns must not poison the output (the 0·NaN hazard)."""
    rng = np.random.default_rng(1)
    b, nh, nkv, d = 1, 2, 2, 8
    block_k = 4
    kp = rng.standard_normal((3, nkv, block_k, d)).astype(np.float32)
    vp = rng.standard_normal((3, nkv, block_k, d)).astype(np.float32)
    kp[2] = np.nan
    vp[2] = np.nan
    q = rng.standard_normal((b, nh, 1, d)).astype(np.float32)
    table = np.array([[1, 2]], np.int32)     # second block = NaN pad
    pos = np.array([block_k - 1], np.int32)  # only block 1 visible
    got = np.asarray(paged_attention(
        jnp.asarray(q), jnp.asarray(kp)[None], jnp.asarray(vp)[None],
        jnp.asarray(table), jnp.asarray(pos)))
    assert np.isfinite(got).all()
    want = _reference(q, kp[:2], vp[:2], np.array([[1]]), pos)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_validation():
    q = jnp.zeros((2, 4, 1, 8))
    kp = jnp.zeros((1, 4, 2, 8, 8))
    with pytest.raises(ValueError, match="table"):
        paged_attention(q, kp, kp, jnp.zeros((3, 2), jnp.int32),
                        jnp.zeros((2,), jnp.int32))
    with pytest.raises(ValueError, match="q"):
        paged_attention(jnp.zeros((2, 4, 8)), kp, kp,
                        jnp.zeros((2, 2), jnp.int32),
                        jnp.zeros((2,), jnp.int32))
    # several query rows a slot share one limit: not under a window
    with pytest.raises(ValueError, match="one query row a slot"):
        paged_attention(jnp.zeros((2, 4, 2, 8)), kp, kp,
                        jnp.zeros((2, 2), jnp.int32),
                        jnp.zeros((2,), jnp.int32), window=4)
    with pytest.raises(ValueError, match="lag 2 of 2"):  # rows left of it
        paged_attention(jnp.zeros((2, 4, 2, 8)), kp, kp,
                        jnp.zeros((2, 2), jnp.int32),
                        jnp.zeros((2,), jnp.int32), lag=2)
    with pytest.raises(ValueError, match="pools"):       # a layer's slice
        paged_attention(q, kp[0], kp[0], jnp.zeros((2, 2), jnp.int32),
                        jnp.zeros((2,), jnp.int32))
    with pytest.raises(ValueError, match="layer"):
        paged_attention(q, kp, kp, jnp.zeros((2, 2), jnp.int32),
                        jnp.zeros((2,), jnp.int32), layer=1)
    with pytest.raises(ValueError, match="new rows"):
        write_rows(kp, kp, jnp.zeros((2, 2, 4)), jnp.zeros((2, 2, 4)),
                   jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
                   layer=0)


# (block, head_dim): head_dim 64 under block 128 is the shape the device
# keeps with the tokens along the lanes (the kernels' swapped view)
GEOMETRIES = [(8, 16), (16, 128), (128, 64)]


@pytest.mark.parametrize("block_k,d", GEOMETRIES)
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_reads_only_its_layer(layer, block_k, d):
    """Layer ``layer`` of a 3-layer pool: the other layers are NaN, and the
    result is the one-layer reference's."""
    rng = np.random.default_rng([layer, d])
    b, nh, nkv, n_pool = 2, 4, 2, 6
    kp = np.full((3, n_pool, nkv, block_k, d), np.nan, np.float32)
    vp = kp.copy()
    kp[layer] = rng.standard_normal(kp.shape[1:])
    vp[layer] = rng.standard_normal(vp.shape[1:])
    q = rng.standard_normal((b, nh, 1, d)).astype(np.float32)
    table = np.array([[4, 1, 3], [2, 5, 0]], np.int32)
    pos = np.array([2 * block_k + 3, block_k - 1], np.int32)
    got = np.asarray(paged_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(table), jnp.asarray(pos), layer=layer))
    want = _reference(q, kp[layer], vp[layer], table, pos)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def _slots(rng, block_k, d, pos, max_blocks, nkv=2, g=2, spare=2):
    """(q, k, v, table) for slots at ``pos``: each owns the table entries up
    to its last live block, drawn from a shuffled pool; everything else in
    the pool — blocks nobody owns, rows past a slot's ``pos`` in its last
    block, the table's padding entries (block 0, which nobody owns
    either) — is NaN in K and Inf in V."""
    b = len(pos)
    n_pool = 1 + b * max_blocks + spare
    kp = np.full((n_pool, nkv, block_k, d), np.nan, np.float32)
    vp = np.full((n_pool, nkv, block_k, d), np.inf, np.float32)
    ids = 1 + rng.permutation(n_pool - 1)
    table = np.zeros((b, max_blocks), np.int32)
    for i, p in enumerate(pos):
        n = p // block_k + 1
        table[i, :n] = ids[i * max_blocks:i * max_blocks + n]
        for j, blk in enumerate(table[i, :n]):
            rows = min(block_k, p + 1 - j * block_k)
            kp[blk, :, :rows] = rng.standard_normal((nkv, rows, d))
            vp[blk, :, :rows] = rng.standard_normal((nkv, rows, d))
    q = rng.standard_normal((b, nkv * g, 1, d)).astype(np.float32)
    return q, kp, vp, table


def _dense(q, kp, vp, table, pos, block_k):
    """``_reference`` over each slot's live rows alone (no poison enters)."""
    out = np.empty_like(q)
    for i, p in enumerate(pos):
        n = p // block_k + 1
        kd = np.nan_to_num(kp[table[i, :n]], nan=0.0)
        vd = np.nan_to_num(vp[table[i, :n]], posinf=0.0)
        out[i] = _reference(q[i:i + 1], kd, vd,
                            np.arange(n, dtype=np.int32)[None],
                            np.array([p]))[0]
    return out


MAX_BLOCKS = 4
# where a slot's newest entry lies: the first row, the last row of the
# first block, the first row of the second, mid-block, the table's end
POSITIONS = {"0": lambda bk: 0, "block-1": lambda bk: bk - 1,
             "block": lambda bk: bk, "mid": lambda bk: 2 * bk + bk // 2,
             "full": lambda bk: MAX_BLOCKS * bk - 1}


@pytest.mark.parametrize("block_k,d", GEOMETRIES)
@pytest.mark.parametrize("where", sorted(POSITIONS))
def test_poison_outside_the_live_rows_never_enters(where, block_k, d):
    """Blocks a slot does not own, rows past ``pos`` and the table's padding
    are NaN / Inf: the output is finite and the dense reference's, for a
    slot at ``where`` beside a neighbour of another length."""
    rng = np.random.default_rng([block_k, d, len(where)])
    pos = [POSITIONS[where](block_k), block_k + 1]
    q, kp, vp, table = _slots(rng, block_k, d, pos, MAX_BLOCKS)
    got = np.asarray(paged_attention(
        jnp.asarray(q), jnp.asarray(kp)[None], jnp.asarray(vp)[None],
        jnp.asarray(table), jnp.asarray(pos, jnp.int32)))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, _dense(q, kp, vp, table, pos, block_k),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("block_k,d", GEOMETRIES)
@pytest.mark.parametrize("stale", ["as_the_step_hands_it", "stale_pos"])
def test_free_slot_is_finite_and_leaves_the_live_slots_alone(stale, block_k,
                                                             d):
    """A free slot between two live ones: a table row of zeros and, as the
    step hands it over, ``pos`` 0 — or, straight from the server's vector,
    the ``pos`` its last request ended at.  Its output is finite either way
    (block 0 holds finite rows here: in the server it is some request's or
    zeros) and the live slots' outputs are bit for bit what they are
    without it."""
    rng = np.random.default_rng([block_k, d])
    pos = [block_k + 3, 0, 3 * block_k - 1]
    q, kp, vp, table = _slots(rng, block_k, d, pos, MAX_BLOCKS)
    kp[0] = rng.standard_normal(kp[0].shape)
    vp[0] = rng.standard_normal(vp[0].shape)
    table[1] = 0
    if stale == "stale_pos":
        pos[1] = MAX_BLOCKS * block_k - 2
    pools = jnp.asarray(kp)[None], jnp.asarray(vp)[None]
    got = np.asarray(paged_attention(
        jnp.asarray(q), *pools, jnp.asarray(table),
        jnp.asarray(pos, jnp.int32)))
    assert np.isfinite(got).all()
    live = [0, 2]
    alone = np.asarray(paged_attention(
        jnp.asarray(q[live]), *pools, jnp.asarray(table[live]),
        jnp.asarray(np.array(pos)[live], jnp.int32)))
    np.testing.assert_array_equal(got[live], alone)
    np.testing.assert_allclose(
        alone, _dense(q[live], kp, vp, table[live], np.array(pos)[live],
                      block_k), atol=2e-5, rtol=2e-5)


# the slots' limits (of the block length and the rows R a half) under a lag
# of R: both halves inside one pool block, the later half the first rows of
# a pool block (the earlier one's limit the end of the block before), a slot
# whose earlier half sees nothing at all, the table's last rows
LAGGED = {
    "one_pool_block": lambda bk, R: [2 * R - 1, bk + 2 * R - 1, 3 * R - 1],
    "two_pool_blocks": lambda bk, R: [bk + R - 1, 2 * bk + R - 1, R - 1],
    "nothing_before": lambda bk, R: [R - 1, R - 1, bk + R - 1],
    "tables_end": lambda bk, R: [MAX_BLOCKS * bk - 1, R - 1, bk - 1],
}


@pytest.mark.parametrize("block_k,d", GEOMETRIES)
@pytest.mark.parametrize("where", list(LAGGED))
def test_a_lag_gives_a_slots_first_rows_an_earlier_limit(where, block_k, d):
    """2 R query rows a slot with ``lag`` R: a head's first R rows see the
    columns up to ``pos - R``, its last R up to ``pos`` — a dense float32
    softmax with a limit a row — over one walk of the slot's blocks; rows
    that see nothing come out finite, and without the lag every row sees up
    to ``pos``: another answer."""
    R, nkv, g = 4, 2, 2
    rng = np.random.default_rng(7)
    pos = np.asarray(LAGGED[where](block_k, R), np.int32)
    b = len(pos)
    n_pool = b * MAX_BLOCKS + 1
    kp, vp = (rng.standard_normal((2, n_pool, nkv, block_k, d))
              .astype(np.float32) for _ in range(2))
    table = rng.permutation(n_pool - 1)[:b * MAX_BLOCKS].reshape(
        b, MAX_BLOCKS).astype(np.int32)
    q = rng.standard_normal((b, nkv * g, 2 * R, d)).astype(np.float32)
    got = np.asarray(paged_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table),
        jnp.asarray(pos), layer=1, lag=R))
    flat = np.asarray(paged_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table),
        jnp.asarray(pos), layer=1))
    assert np.isfinite(got).all()
    limit = pos[:, None] - np.where(np.arange(2 * R) < R, R, 0)   # (b, 2R)
    for bi in range(b):
        ks, vs = (np.concatenate([p[1, t] for t in table[bi]], axis=1)
                  for p in (kp, vp))                        # (nkv, S, d)
        qf = q[bi].reshape(nkv, g, 2 * R, d)
        s = np.einsum("kgrd,ksd->kgrs", qf, ks) / np.sqrt(d)
        seen = np.arange(ks.shape[1])[None, :] <= limit[bi][:, None]
        s = np.where(seen[None, None], s, -1e30)
        w = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("kgrs,ksd->kgrd", w / w.sum(-1, keepdims=True),
                         vs).reshape(nkv * g, 2 * R, d)
        rows = limit[bi] >= 0               # the others see nothing: unread
        np.testing.assert_allclose(got[bi][:, rows], want[:, rows],
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(got[bi][:, R:], flat[bi][:, R:],
                                   atol=2e-5, rtol=2e-5)
        if rows[0]:
            assert np.abs(got[bi][:, :R] - flat[bi][:, :R]).max() > 1e-3


def test_without_a_lag_the_kernel_is_what_it_was():
    """``lag`` absent or 0 changes nothing of the call: the same kernel
    under the same name with the same parameters, whatever the rows."""
    pool = jnp.zeros((1, 5, 2, 16, 128), jnp.float32)
    table, pos = jnp.zeros((3, 4), jnp.int32), jnp.zeros((3,), jnp.int32)
    for rows in (1, 4):
        q = jnp.zeros((3, 4, rows, 128), jnp.float32)
        plain, zero, lagged = (
            str(jax.make_jaxpr(functools.partial(paged_attention, **kw))(
                q, pool, pool, table, pos))
            for kw in ({}, {"lag": 0}, {"lag": rows // 2}))
        assert plain == zero
        assert (plain == lagged) == (rows == 1)


def _pallas_eqns(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_eqns(sub, found)
    return found


# slots' positions (of the block length) -> the grid steps of one call: a
# slot's live table entries, at least one, at most the table's width
WALKS = {
    "all_at_0": (lambda bk: [0, 0, 0], 3),
    "inside_the_first_block": (lambda bk: [bk - 1, 0, 3], 3),
    "one_into_its_second_block": (lambda bk: [bk, 0, 3], 4),
    "one_at_the_tables_end": (
        lambda bk: [1, MAX_BLOCKS * bk - 1, 0], MAX_BLOCKS + 2),
    "one_past_the_table": (
        lambda bk: [1, 9 * MAX_BLOCKS * bk, 0], MAX_BLOCKS + 2),
}


@pytest.mark.parametrize("block_k,d", GEOMETRIES)
@pytest.mark.parametrize("walk", list(WALKS))
def test_grid_is_one_axis_over_the_live_entries_with_every_kv_head(walk,
                                                                   block_k,
                                                                   d):
    """The grid read off the traced ``pallas_call``: ONE axis — no axis
    over the slots, none over the KV heads, one K/V block holds them all —
    whose bound is data, the sum of the slots' live table entries, never
    more than the table holds."""
    from nvme_strom_tpu.ops.paged_attention import _tokens_on_lanes
    b, nkv, g, n_pool = 3, 2, 2, 9
    pool = jnp.zeros((2, n_pool, nkv, block_k, d), jnp.float32)
    q = jnp.zeros((b, nkv * g, 1, d), jnp.float32)
    table = jnp.zeros((b, MAX_BLOCKS), jnp.int32)
    positions, steps = WALKS[walk]
    pos = jnp.asarray(positions(block_k), jnp.int32)
    jaxpr = jax.make_jaxpr(functools.partial(paged_attention, layer=1))(
        q, pool, pool, table, pos)
    call, = _pallas_eqns(jaxpr.jaxpr, [])
    mapping = call.params["grid_mapping"]
    assert call.params["name"] == "strom_paged_attn"
    assert len(mapping.grid) == 1
    assert mapping.num_dynamic_grid_bounds == 1
    kv = tuple(mapping.block_mappings[1].block_shape)
    heads_and_block = ((nkv, d, block_k) if _tokens_on_lanes(pool.shape)
                       else (nkv, block_k, d))
    assert tuple(getattr(n, "block_size", n)
                 for n in kv[-3:]) == heads_and_block
    # the bound itself: the call's first operand, computed from pos
    bound = int(jax.jit(lambda p: jax.core.eval_jaxpr(
        jaxpr.jaxpr.replace(outvars=[call.invars[0]]), jaxpr.consts,
        q, pool, pool, table, p)[0])(pos))
    assert bound == steps <= b * MAX_BLOCKS


@pytest.mark.parametrize("block_k,d", GEOMETRIES)
def test_a_ragged_batch_equals_each_slot_alone(block_k, d):
    """Slots of 1, 2 and the table's ``MAX_BLOCKS`` blocks and a free slot
    in ONE call — as far apart as a long-context cell's slots are — against
    each slot in a call of its own: bit for bit, so a slot's blocks are
    visited in the same order whoever its neighbours are; and the dense
    reference's."""
    rng = np.random.default_rng([block_k, d, 40])
    pos = [block_k - 2, MAX_BLOCKS * block_k - 1, 0, block_k + 1]
    q, kp, vp, table = _slots(rng, block_k, d, pos, MAX_BLOCKS)
    kp[0] = rng.standard_normal(kp[0].shape)     # a free slot's block 0
    vp[0] = rng.standard_normal(vp[0].shape)
    table[2] = 0
    pools = jnp.asarray(kp)[None], jnp.asarray(vp)[None]
    got = np.asarray(paged_attention(
        jnp.asarray(q), *pools, jnp.asarray(table),
        jnp.asarray(pos, jnp.int32)))
    assert np.isfinite(got).all()
    for i in range(len(pos)):
        alone = np.asarray(paged_attention(
            jnp.asarray(q[i:i + 1]), *pools, jnp.asarray(table[i:i + 1]),
            jnp.asarray(pos[i:i + 1], jnp.int32)))
        np.testing.assert_array_equal(got[i:i + 1], alone)
    live = [0, 1, 3]
    np.testing.assert_allclose(
        got[live], _dense(q[live], kp, vp, table[live], np.array(pos)[live],
                          block_k), atol=2e-5, rtol=2e-5)


def walk_list_by_loop(table, pos, block_k, window=0):
    """``walk_list`` as a loop over the slots and their entries."""
    b, width = table.shape
    slot, block, start = [], [], [0]
    for i in range(b):
        last = pos[i] // block_k
        first = max(pos[i] - window + 1, 0) // block_k if window else 0
        n = min(max(last - first + 1, 1), width)
        slot += [i] * n
        block += [table[i, (first + j) % width] for j in range(n)]
        start.append(start[-1] + n)
    return np.array(slot), np.array(block), np.array(start)


@pytest.mark.parametrize("block_k,d", GEOMETRIES)
@pytest.mark.parametrize("window", [0, 1.5], ids=["full", "window"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_built_list_equals_a_numpy_loops(seed, window, block_k, d):
    """The (slot, block) list of the live table entries and the slots'
    first steps, for random positions — free slots' 0, a position past the
    table — over a table of distinct entries, which a window (of a block
    and a half) reads as a ring from the block of its oldest visible row;
    past the grid's length the lists repeat its last step."""
    rng = np.random.default_rng([seed, block_k])
    window = int(window * block_k)
    b, width = 7, 5
    pos = rng.integers(0, width * block_k, b).astype(np.int32)
    pos[rng.integers(b)] = 0
    pos[rng.integers(b)] = 3 * width * block_k
    table = rng.permutation(b * width).astype(np.int32).reshape(b, width)
    slot, block, start = (np.asarray(a) for a in jax.jit(
        walk_list, static_argnums=(2, 3))(jnp.asarray(table),
                                          jnp.asarray(pos), block_k, window))
    want_slot, want_block, want_start = walk_list_by_loop(table, pos,
                                                          block_k, window)
    steps = want_start[-1]
    assert slot.shape == block.shape == (b * width,) and steps <= b * width
    np.testing.assert_array_equal(start, want_start)
    np.testing.assert_array_equal(slot[:steps], want_slot)
    np.testing.assert_array_equal(block[:steps], want_block)
    assert (slot[steps:] == want_slot[-1]).all()
    assert (block[steps:] == want_block[-1]).all()


@pytest.mark.parametrize("case", ["mimo.ragged", "mimo.window", "m7b.chat"])
def test_the_kernel_probe_counts_what_a_call_reads(case, capsys):
    """``kernel_probe paged`` at its CPU size (mechanics only: no time it
    prints here is a device's): a finite result, the live entries of the
    case's slot mix — a free slot is one — and the steps the (slots x
    longest slot) grid made of them."""
    import json

    from nvme_strom_tpu.tools import kernel_probe
    kernel_probe.probe_paged(case, repeats=1)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["case"] == case and line["finite"]
    slots = line["slots"]
    assert slots <= line["blocks_live"] <= line["steps_rect"] \
        <= slots * line["table"][1]
    if case == "m7b.chat":      # one live slot among free ones
        assert line["steps_rect"] == slots * (line["blocks_live"] - slots + 1)


def _pools(rng, shape, dtype):
    return (jnp.asarray(rng.standard_normal(shape), dtype),
            jnp.asarray(rng.standard_normal(shape), dtype))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("block_k,d", [(16, 64), (128, 64), (16, 128),
                                       (128, 128)])
def test_write_rows_matches_the_scatter(block_k, d, dtype):
    """``write_rows`` against the scatter it replaced,
    ``pool.at[i, blk, :, off, :].set``, bit for bit: first and last row of
    a block, rows of one packed tile pair, and two free slots aimed at the
    same row of the trash block, where either row may win."""
    rng = np.random.default_rng([block_k, d])
    L, n_pool, nkv, layer = 3, 5, 2, 1
    trash = n_pool - 1
    kp, vp = _pools(rng, (L, n_pool, nkv, block_k, d), dtype)
    k_new, v_new = _pools(rng, (5, nkv, d), dtype)
    blk = np.array([2, 0, trash, 1, trash], np.int32)
    off = np.array([0, block_k - 1, 6, 7, 6], np.int32)
    k_got, v_got = write_rows(kp, vp, k_new, v_new, jnp.asarray(blk),
                              jnp.asarray(off), layer=layer)
    assert k_got.dtype == dtype and k_got.shape == kp.shape
    for got, pool, new in ((k_got, kp, k_new), (v_got, vp, v_new)):
        got = np.asarray(got.astype(jnp.float32))
        new = np.asarray(new.astype(jnp.float32))
        live = [0, 1, 3]
        want = np.array(pool.at[layer, blk[live], :, off[live], :].set(
            new[live].astype(dtype)).astype(jnp.float32))
        row = got[layer, trash, :, 6, :]
        assert (row == new[2]).all() or (row == new[4]).all()
        want[layer, trash, :, 6, :] = row
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("block_k,d", [(16, 128), (128, 64)])
def test_write_rows_updates_the_donated_pools_in_place(block_k, d):
    """Under ``jit`` with the pools donated the result IS the buffer that
    was passed in: the argument is consumed and no second pool exists."""
    rng = np.random.default_rng(5)
    kp, vp = _pools(rng, (2, 4, 2, block_k, d), jnp.bfloat16)
    k_new, v_new = _pools(rng, (3, 2, d), jnp.bfloat16)
    blk = jnp.asarray([0, 1, 2], jnp.int32)
    off = jnp.asarray([1, 2, 3], jnp.int32)
    step = jax.jit(functools.partial(write_rows, layer=1),
                   donate_argnums=(0, 1))
    compiled = step.lower(kp, vp, k_new, v_new, blk, off).compile()
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= kp.nbytes + vp.nbytes
    want = np.asarray(kp.at[1, blk, :, off, :].set(k_new)
                      .astype(jnp.float32))
    where = kp.unsafe_buffer_pointer(), vp.unsafe_buffer_pointer()
    k_got, v_got = step(kp, vp, k_new, v_new, blk, off)
    assert kp.is_deleted() and vp.is_deleted()
    assert (k_got.unsafe_buffer_pointer(),
            v_got.unsafe_buffer_pointer()) == where
    np.testing.assert_array_equal(np.asarray(k_got.astype(jnp.float32)),
                                  want)

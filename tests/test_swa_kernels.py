"""The kernels of a window / full GQA mix in interpret mode on the CPU, at
192-wide keys and 128-wide values: the blocked prefill against a dense
computation, paged attention with values narrower than keys, the window
kernel's walk of a ring, and rows written into pools of unequal widths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nvme_strom_tpu.ops.kv_prefill import kv_prefill_attention
from nvme_strom_tpu.ops.paged_attention import paged_attention, write_rows


def _dense_attention(q, k, v, pos, scale, window=0, sink=None):
    """q (b, nh, m, dq) at positions pos.. over k, v (b, nkv, S, d)."""
    b, nh, m, _ = q.shape
    nkv, S = k.shape[1], k.shape[2]
    ke = jnp.repeat(k, nh // nkv, axis=1)
    ve = jnp.repeat(v, nh // nkv, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, ke) * scale
    rows = jnp.reshape(pos, (-1, 1, 1, 1)) + jnp.arange(m)[:, None]
    cols = jnp.arange(S)
    seen = cols <= rows
    if window:
        seen = seen & (cols > rows - window)
    s = jnp.where(seen, s, -jnp.inf)
    if sink is not None:
        s = jnp.concatenate([s, jnp.broadcast_to(
            sink[None, :, None, None], (b, nh, m, 1))], -1)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1)[..., :S], ve)


@pytest.mark.parametrize("pos,m,S,window,sink", [
    (0, 48, 48, 0, False), (32, 16, 64, 0, False), (0, 64, 64, 16, True),
    (29, 24, 64, 20, True), (63, 1, 64, 20, True), (0, 32, 32, 128, True)],
    ids=["prompt", "behind_a_prefix", "band", "band_mid_cache",
         "band_one_row", "window_wider_than_the_cache"])
def test_kv_prefill_kernel_against_a_dense_computation(pos, m, S, window,
                                                       sink):
    """m query rows at cache positions pos.. against S cached keys: 8 query
    heads over 2 KV heads, keys 192 and values 128 wide, blocks smaller than
    either; causal, and a band with the sink column."""
    rng = np.random.default_rng(pos + m + window)
    q = jnp.asarray(rng.normal(size=(2, 8, m, 192)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 2, S, 192)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 2, S, 128)), jnp.float32)
    sk = jnp.asarray(rng.normal(size=(8,)), jnp.float32) if sink else None
    got = kv_prefill_attention(q, k, v, jnp.int32(pos), scale=0.07,
                               window=window, sink=sk,
                               block_q=8 if m > 1 else None, block_k=16,
                               interpret=True)
    want = _dense_attention(q, k, v, jnp.int32(pos), 0.07, window, sk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)


def _pools(rng, layers, blocks, nkv, block, dk, dv):
    return (jnp.asarray(rng.normal(size=(layers, blocks, nkv, block, dk)),
                        jnp.float32),
            jnp.asarray(rng.normal(size=(layers, blocks, nkv, block, dv)),
                        jnp.float32))


@pytest.mark.parametrize("block,dk,dv", [(128, 192, 128), (8, 24, 16)],
                         ids=["k_on_lanes_v_not", "tiny"])
def test_paged_attention_with_values_narrower_than_keys(block, dk, dv):
    """A full layer's kernel over a K pool 192 wide (which the device keeps
    tokens-on-lanes: the kernel reads its blocks as (192, block)) and a V
    pool 128 wide (which it does not), 8 query heads over 2 KV heads, slots
    at positions in their first, second and third block."""
    rng = np.random.default_rng(block)
    k_pool, v_pool = _pools(rng, 2, 7, 2, block, dk, dv)
    table = jnp.asarray([[3, 1, 5], [0, 2, 4], [6, 6, 6]], jnp.int32)
    pos = jnp.asarray([2 * block + 3, block - 1, 5], jnp.int32)
    q = jnp.asarray(rng.normal(size=(3, 8, 1, dk)), jnp.float32)
    got = paged_attention(q, k_pool, v_pool, table, pos, layer=1,
                          interpret=True)
    assert got.shape == (3, 8, 1, dv)
    for b in range(3):
        dense = [p[1][table[b]].transpose(1, 0, 2, 3).reshape(
            1, 2, 3 * block, -1) for p in (k_pool, v_pool)]
        want = _dense_attention(q[b:b + 1], *dense, pos[b], dk ** -0.5)
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want[0]),
                                   atol=3e-6)


def _ring_case(rng, block, window, ring, positions, S):
    """Slots at ``positions`` over rings of ``ring`` blocks: (q, the dense
    k and v (slots, nkv, S, d), sink, pos, the ring pools — slot b's block j
    at pool block ``b * ring + j % ring``, every row outside a slot's window
    NaN —, the table, the score scale)."""
    dk, dv, nkv, nh = (192, 128, 2, 8) if block == 128 else (24, 16, 2, 4)
    slots = len(positions)
    k = jnp.asarray(rng.normal(size=(slots, nkv, S, dk)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(slots, nkv, S, dv)), jnp.float32)
    sink = jnp.asarray(rng.normal(size=(nh,)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(slots, nh, 1, dk)), jnp.float32)
    k_pool = np.full((1, slots * ring, nkv, block, dk), np.nan, np.float32)
    v_pool = np.full((1, slots * ring, nkv, block, dv), np.nan, np.float32)
    for b, p in enumerate(positions):
        for row in range(max(p - window + 1, 0), p + 1):
            blk = b * ring + (row // block) % ring
            k_pool[0, blk, :, row % block] = k[b, :, row]
            v_pool[0, blk, :, row % block] = v[b, :, row]
    table = jnp.arange(slots * ring, dtype=jnp.int32).reshape(slots, ring)
    return (q, k, v, sink, jnp.asarray(positions, jnp.int32),
            (jnp.asarray(k_pool), jnp.asarray(v_pool)), table, dk ** -0.5)


@pytest.mark.parametrize("block,window,ring", [(128, 128, 2), (8, 16, 3),
                                               (8, 20, 4)])
def test_window_attention_walks_a_ring(block, window, ring):
    """A window layer's kernel: slot b's block j lies at ``table[b, j %
    ring]``; positions before the first wrap, at a block's first and last
    row, and after several wraps read exactly the last ``window`` rows, and
    the sink takes its share.  Rows of the ring outside the window hold NaN:
    none reaches the output."""
    positions = [3, block - 1, block, 2 * block + 5, 5 * block + block // 2,
                 7 * block - 1]
    q, k, v, sink, pos, pools, table, scale = _ring_case(
        np.random.default_rng(window), block, window, ring, positions,
        8 * block)
    got = paged_attention(q, *pools, table, pos, layer=0, window=window,
                          sink=sink, interpret=True)
    want = jnp.concatenate([_dense_attention(
        q[b:b + 1], k[b:b + 1], v[b:b + 1], pos[b], scale, window, sink)
        for b in range(len(positions))])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)


@pytest.mark.parametrize("block,window,ring", [(128, 128, 2), (8, 16, 3),
                                               (8, 20, 4)])
def test_window_slots_of_unequal_walks_equal_each_slot_alone(block, window,
                                                             ring):
    """The window call's grid is the full layers': one step a ring entry a
    slot has to read.  Slots before the window fills (``pos < window``: one
    entry), across a block boundary (two), after the ring has wrapped and a
    free slot (``pos`` 0) in ONE call, with the sink, against each slot in a
    call of its own: bit for bit; and against the dense computation."""
    positions = [window - 2, 0, 3 * block + 1, (2 * ring + 1) * block - 1,
                 block // 2]
    slots = len(positions)
    q, k, v, sink, pos, pools, table, scale = _ring_case(
        np.random.default_rng([block, window]), block, window, ring,
        positions, (2 * ring + 1) * block)
    got = paged_attention(q, *pools, table, pos, layer=0, window=window,
                          sink=sink, interpret=True)
    for b in range(slots):
        alone = paged_attention(q[b:b + 1], *pools, table[b:b + 1],
                                pos[b:b + 1], layer=0, window=window,
                                sink=sink, interpret=True)
        np.testing.assert_array_equal(np.asarray(got[b:b + 1]),
                                      np.asarray(alone))
    want = jnp.concatenate([_dense_attention(
        q[b:b + 1], k[b:b + 1], v[b:b + 1], pos[b], scale, window, sink)
        for b in range(slots)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)


@pytest.mark.parametrize("block,window,ring", [(128, 128, 2), (8, 16, 3),
                                               (8, 20, 4)])
def test_window_grid_is_one_axis_over_the_ring_entries_in_the_window(
        block, window, ring):
    """The traced window call: ``strom_window_attn``, one grid axis whose
    bound is data — the ring entries that hold each slot's last ``window``
    rows, summed over the slots."""
    positions = [3, block - 1, block, 2 * block + 5, 7 * block - 1]
    slots = len(positions)
    k_pool, v_pool = _pools(np.random.default_rng(0), 1, slots * ring, 2,
                            block, 24, 16)
    q = jnp.zeros((slots, 4, 1, 24), jnp.float32)
    table = jnp.arange(slots * ring, dtype=jnp.int32).reshape(slots, ring)
    pos = jnp.asarray(positions, jnp.int32)
    fn = lambda p: paged_attention(q, k_pool, v_pool, table, p,  # noqa: E731
                                   window=window, interpret=True)
    jaxpr = jax.make_jaxpr(fn)(pos)
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    call, = calls
    mapping = call.params["grid_mapping"]
    assert call.params["name"] == "strom_window_attn"
    assert len(mapping.grid) == 1 and mapping.num_dynamic_grid_bounds == 1
    bound = int(jax.jit(lambda p: jax.core.eval_jaxpr(
        jaxpr.jaxpr.replace(outvars=[call.invars[0]]), jaxpr.consts,
        p)[0])(pos))
    assert bound == sum(p // block - max(p - window + 1, 0) // block + 1
                        for p in positions) <= slots * ring


def test_a_ring_too_short_for_its_window_is_refused():
    k_pool, v_pool = _pools(np.random.default_rng(0), 1, 4, 1, 8, 8, 8)
    with pytest.raises(ValueError, match="cannot hold a window of 16"):
        paged_attention(jnp.zeros((2, 2, 1, 8)), k_pool, v_pool,
                        jnp.zeros((2, 2), jnp.int32),
                        jnp.zeros((2,), jnp.int32), window=16,
                        interpret=True)


def test_write_rows_into_pools_of_unequal_widths():
    """One new K row 192 wide and one V row 128 wide per slot, each pool in
    the layout the device keeps it in (K's tokens on the lanes, V's not):
    exactly those rows change."""
    rng = np.random.default_rng(3)
    k_pool, v_pool = _pools(rng, 2, 5, 2, 128, 192, 128)
    k_new = jnp.asarray(rng.normal(size=(3, 2, 192)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(3, 2, 128)), jnp.float32)
    blk, off = jnp.asarray([4, 0, 2]), jnp.asarray([127, 0, 77])
    k_out, v_out = write_rows(k_pool, v_pool, k_new, v_new, blk, off,
                              layer=1, name="strom_window_write",
                              interpret=True)
    k_want, v_want = np.array(k_pool), np.array(v_pool)
    for b in range(3):
        k_want[1, int(blk[b]), :, int(off[b])] = k_new[b]
        v_want[1, int(blk[b]), :, int(off[b])] = v_new[b]
    np.testing.assert_array_equal(np.asarray(k_out), k_want)
    np.testing.assert_array_equal(np.asarray(v_out), v_want)

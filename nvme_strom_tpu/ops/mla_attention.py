"""Pallas paged attention over a LATENT pool (``strom_mla_attn``), the row
writer of that pool (``strom_latent_write``), and the expanded form's causal
attention over a dense cache (``strom_mla_prefill``).

A latent-attention layer (``models/mla.py``) caches one row a token —
``[c_kv | k_pe]``, 512 + 64 values at DeepSeek-V3's sizes — and in the
absorbed form a decode step's query is as wide as that row: every head's
score is its query against the SAME row, and every head's output is the
probability-weighted sum of the rows' first ``dc`` columns.  So a block of
latents is fetched once and used twice, as keys and as values, by all the
heads (64 of them at 1,152 bytes a token: 121 operations a byte).

The pool of every layer is one array ``(layers, blocks, width, block)``:
a block lies TRANSPOSED, the tokens along the lanes.  A width of 576 is no
multiple of the 128 lanes, so a ``(block, width)`` tile would be padded to
640; ``(576, 128)`` is whole tiles, scores are the plain product ``q (heads,
width) x block (width, tokens)`` and the values' product contracts the
tokens of both operands.  Operands go to the MXU in the pool's dtype
(bfloat16), accumulation and the online softmax are float32.

The walk is the one ``paged_attention`` had until it took a list of the
live entries (ops/paged_attention.py): the block table and the positions
ride scalar prefetch, the grid is (slots, the batch's longest slot) — the
block axis data, not a compiled shape — and a shorter slot's steps past its
last block fetch nothing and do nothing, at ~0.16 us each.  One grid step
covers ``GROUP`` consecutive table entries — the pool is handed to the
kernel that many times, each with its own index map — because a step costs
its fixed ~0.35 us whatever it carries and one block's 144 KiB moves in
half that.  Columns past a slot's position are masked and zeroed (a block's
tail may hold another request's rows).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nvme_strom_tpu.ops.flash_attention import _pick_block
from nvme_strom_tpu.ops.paged_attention import _NEG_INF, _interpret

#: table entries one grid step covers
GROUP = 4
#: query rows and keys of one step of the prefill's walk: 512 x 1024 scores
#: are 2 MiB of float32 in VMEM and 0.34 GFLOP against the step's fixed cost
BLOCK_Q, BLOCK_K = 512, 1024


def _mla_kernel(table_ref, pos_ref, q_ref, *refs, block_k, group, dc):
    c_refs, o_ref = refs[:group], refs[group]
    m_ref, l_ref, acc_ref = refs[group + 1:]
    bi, ji = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[bi]

    @pl.when(ji == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                       # (heads, width)
    for g in range(group):
        first = (ji * group + g) * block_k

        @pl.when(first <= pos)
        def _update(g=g, first=first):
            cols = first + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            c = c_refs[g][0, 0]                        # (width, block)
            c = jnp.where(cols <= pos, c, jnp.zeros_like(c))
            s = jnp.dot(q, c, preferred_element_type=jnp.float32)
            s = jnp.where(cols <= pos, s, _NEG_INF)    # (heads, block)
            m = m_ref[...]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            m_ref[...] = m_new
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p.astype(c.dtype), c[:dc], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)    # (heads, dc)

    @pl.when(ji == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def mla_attention(q, pool, table, pos, *, layer: int, dc: int,
                  group: int = GROUP, interpret: bool = None):
    """q (b, heads, width) — the absorbed query, its scale folded in —
    attends to its block-table history in layer ``layer`` of the latent
    pool ``(layers, blocks, width, block)``.  table (b, max_blocks) int32
    and pos (b,) int32 as ``paged_attention`` takes them: slot b's rows are
    in blocks ``table[b, 0 .. pos[b] // block]``, the later entries are
    never dereferenced.  Returns (b, heads, dc): each head's weighted sum of
    the rows' first ``dc`` columns, in q's dtype."""
    b, nh, width = q.shape
    if pool.ndim != 4 or pool.shape[2] != width or not 0 < dc <= width:
        raise ValueError(f"expected a pool (layers, blocks, {width}, block) "
                         f"and dc <= {width}, got {pool.shape}, dc {dc}")
    n_layers, _, _, block_k = pool.shape
    if not 0 <= layer < n_layers:
        raise ValueError(f"layer {layer} not in a pool of {n_layers}")
    if table.ndim != 2 or table.shape[0] != b:
        raise ValueError(f"table must be ({b}, max_blocks), "
                         f"got {table.shape}")
    max_blocks = table.shape[1]
    table = jnp.asarray(table, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    n_walk = jnp.clip((jnp.max(pos) // block_k + group) // group, 1,
                      -(-max_blocks // group))

    def block_at(g):
        # past the slot's last live block the index stays where it is
        return lambda bi, ji, tbl, ps: (
            layer, tbl[bi, jnp.minimum(ji * group + g, ps[bi] // block_k)],
            0, 0)

    c_specs = [pl.BlockSpec((1, 1, width, block_k), block_at(g))
               for g in range(group)]
    return pl.pallas_call(
        functools.partial(_mla_kernel, block_k=block_k, group=group, dc=dc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, n_walk),
            in_specs=[pl.BlockSpec((1, nh, width),
                                   lambda bi, ji, tbl, ps: (bi, 0, 0))]
            + c_specs,
            out_specs=pl.BlockSpec((1, nh, dc),
                                   lambda bi, ji, tbl, ps: (bi, 0, 0)),
            scratch_shapes=[pltpu.VMEM((nh, 1), jnp.float32),
                            pltpu.VMEM((nh, 1), jnp.float32),
                            pltpu.VMEM((nh, dc), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, nh, dc), q.dtype),
        name="strom_mla_attn",
        interpret=_interpret(interpret),
    )(table, pos, q.astype(pool.dtype), *([pool] * group))


def _prefill_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                    acc_ref, *, scale, bq, bk):
    qi, ki = pl.program_id(2), pl.program_id(3)
    first = pos_ref[0] + qi * bq            # the block's first row's position

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki * bk <= first + bq - 1)     # some row sees into the block
    def _update():
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q_ref[0, 0], k_ref[0, 0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(cols <= rows, s, _NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def mla_prefill_attention(q, k, v, pos, *, scale: float,
                          block_q: int = None, block_k: int = None,
                          interpret: bool = None):
    """Causal attention of m query rows over a dense cache of S: q (b, heads,
    m, dq) sits at cache positions ``pos .. pos + m - 1`` (pos () int32:
    data, so a prefix of any length is one program) and row t sees the
    positions <= pos + t of k (b, heads, S, dq) and v (b, heads, S, dv),
    dq and dv unequal as the expanded form has them.  Returns (b, heads, m,
    dv).  Online softmax in float32 over key blocks; a key block past a
    query block's last row is neither fetched nor computed, so a prompt's
    causal half is all that runs, and no score leaves VMEM."""
    b, nh, m, dq = q.shape
    S, dv = k.shape[2], v.shape[3]
    if k.shape != (b, nh, S, dq) or v.shape != (b, nh, S, dv):
        raise ValueError(f"q {q.shape} against k {k.shape}, v {v.shape}")
    bq = _pick_block(m, block_q or BLOCK_Q)
    bk = _pick_block(S, block_k or BLOCK_K)

    def kv_block(bi, hi, qi, ki, ps):
        # past the query block's last row the index stays where it is
        return (bi, hi, jnp.minimum(ki, (ps[0] + (qi + 1) * bq - 1) // bk),
                0)

    def q_block(bi, hi, qi, ki, ps):
        return (bi, hi, qi, 0)

    return pl.pallas_call(
        functools.partial(_prefill_kernel, scale=float(scale), bq=bq, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, nh, m // bq, S // bk),
            in_specs=[pl.BlockSpec((1, 1, bq, dq), q_block),
                      pl.BlockSpec((1, 1, bk, dq), kv_block),
                      pl.BlockSpec((1, 1, bk, dv), kv_block)],
            out_specs=pl.BlockSpec((1, 1, bq, dv), q_block),
            scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, nh, m, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="strom_mla_prefill",
        interpret=_interpret(interpret),
    )(jnp.reshape(jnp.asarray(pos, jnp.int32), (1,)), q, k, v)


def _write_kernel(blk_ref, off_ref, new_ref, old_ref, out_ref, *, tile):
    at = off_ref[pl.program_id(0)] % tile
    have = old_ref[...].astype(jnp.float32)            # (width, tile)
    hit = jax.lax.broadcasted_iota(jnp.int32, have.shape, 1) == at
    out_ref[...] = jnp.where(hit, new_ref[...], have).astype(out_ref.dtype)


def latent_write(pool, rows, blk, off, *, layer: int,
                 interpret: bool = None):
    """One new latent row per slot IN the pool: ``pool[layer, blk[b], :,
    off[b]] = rows[b]``.  pool (layers, blocks, width, block), aliased
    input to output (under ``jit`` with the pool donated nothing pool-sized
    moves); rows (b, width); blk/off (b,) int32.  A grid step reads the
    lane tile that holds its slot's column, replaces the column and writes
    the tile back.  Free slots all aim at the trash block and leave one of
    their rows there, any one."""
    n_layers, _, width, block = pool.shape
    b = rows.shape[0]
    if rows.shape != (b, width) or not 0 <= layer < n_layers:
        raise ValueError(f"rows {rows.shape} into layer {layer} of a pool "
                         f"{pool.shape}")
    tile = min(block, 128)
    tile_spec = pl.BlockSpec(
        (None, None, width, tile),
        lambda bi, bl, of: (layer, bl[bi], 0, of[bi] // tile))
    # each slot's new column, spread along the lanes inside the kernel
    # (float32: exact for bf16)
    new = rows.astype(jnp.float32)[..., None]
    return pl.pallas_call(
        functools.partial(_write_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[pl.BlockSpec((None, width, 1),
                                   lambda bi, bl, of: (bi, 0, 0)),
                      tile_spec],
            out_specs=tile_spec),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="strom_latent_write",
        interpret=_interpret(interpret),
    )(jnp.asarray(blk, jnp.int32), jnp.asarray(off, jnp.int32), new, pool)

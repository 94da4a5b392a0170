"""Pallas paged attention: decode against a BLOCK-TABLE KV pool.

The serving-memory move (vLLM's PagedAttention, done TPU-style): instead
of reserving ``max_len`` cache rows per slot, all slots share one pool
of fixed-size blocks and a per-slot table lists which pool blocks hold
its history.  Capacity is sized for the TOTAL live tokens, not
slots × max_len — heterogeneous requests stop paying for the longest
one's reservation.

Kernel shape (``strom_paged_attn``): a grid step is one LIVE table entry.
``walk_list`` builds, on the device from ``table`` and ``pos``, the list of
the (slot, entry) pairs a call has to read, slot-major — per slot the
entries ``0 .. pos // block`` (a window layer: from the block of its
oldest visible row) — with each pair's pool block looked up beside it, and
the grid is ONE axis over that list, as long as the list (a dynamic grid
bound computed on the device: data, not a compiled shape).  The lists ride
scalar prefetch (``pltpu.PrefetchScalarGridSpec``): each grid step's K/V
BlockSpec ``index_map`` reads its pool block from them and the DMA fetches
exactly that block, q's and the output's read the slot — the indirection
costs nothing extra over the contiguous-cache kernel
(ops/decode_attention.py), and no gathered copy of the cache ever
materializes in HBM.  Everything else is the same fused position-masked
online softmax, in float32, a slot's blocks in their order.

One grid step covers EVERY KV head of one pool block — for a fixed layer
and block the heads lie next to each other in the pool, so K and V come in
one DMA each (256 KiB at 8 heads of 128 under block 128) and scores and
``p·v`` are one ``dot_general`` batched over the heads.  No step is issued
for a table entry past a slot's last live block, so a short slot beside a
long one costs its own blocks and nothing more: under the (slots x longest
slot) grid this replaced, a step that fetched nothing and did nothing still
cost 0.16 us (64 slots at 2k-17k rows: 4,544 such steps beside 3,968 live
ones of 0.67 us, PERF.md section 6, PR 40).  Table entries past a slot's
last live block are never dereferenced: the list does not hold them.

Within a slot's last block the rows past ``pos`` (and whatever block a
caller's table names there) may hold garbage: their columns are masked,
and their V rows are zeroed before use so garbage cannot ride a 0·NaN.

The pool of EVERY layer is one array ``(layers, blocks, kv_heads, block,
d)`` and both kernels here take it whole, with a static layer index in
their index maps: a decode step never slices a layer out of it, and
``write_rows`` (``strom_kv_write``) places the step's new rows in the
donated buffer itself (``input_output_aliases``), one aligned tile read,
patched and written back per slot.  Nothing pool-sized is copied.

The device keeps an array whose minor dimension is narrower than a lane
row with the next dimension on the lanes (``_tokens_on_lanes``): such a
pool (head_dim 64 under block 128) is handed to the kernels with its last
two axes swapped — a relabelling of the same bytes, no copy — and they
read K/V blocks as ``(d, block)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _interpret(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _tokens_on_lanes(pool_shape) -> bool:
    """Whether the device holds this pool with the block's tokens, not the
    head's features, along the lanes.  The TPU's compact layout of an array
    whose minor dimension is no multiple of the 128 lanes while the next
    one is puts that next dimension on the lanes (bf16 and f32 alike:
    ``(…, 128, 64)`` lies as ``(…, 64, 128)``; ``(…, 128, 128)`` and
    ``(…, 64, 64)`` as they read).  A Mosaic kernel takes its operands
    row-major, so the kernels here see such a pool through
    ``_kernel_view``.  A wrong answer costs copies, never results: XLA
    then transposes for real (tests/test_chip_compile_kernels.py pins
    both cells' shapes)."""
    block, d = pool_shape[-2:]
    return d % 128 != 0 and block % 128 == 0


def _kernel_view(pool, lanes: bool):
    """The pool as the kernels index it: as it is, or with ``(block, d)``
    swapped to ``(d, block)`` — the same bytes in the device's layout, so
    XLA makes the swap (and the swap back of a kernel's result) a bitcast."""
    return jnp.swapaxes(pool, 3, 4) if lanes else pool


def walk_list(table, pos, block_k: int, window: int = 0):
    """The grid of one ``paged_attention`` call, from the data: (slot,
    block, start), all int32.  ``slot[i]`` is the slot grid step ``i``
    belongs to and ``block[i]`` the pool block it reads, slot-major, a
    slot's blocks oldest first; ``start[b] .. start[b + 1]`` are slot b's
    steps, so ``start[-1]`` is the grid's length.  A slot walks the table
    entries ``first .. pos // block_k`` — ``first`` 0, or under ``window``
    the block of its oldest visible row, entry j at ``table[b, j % width]``
    — at least one and at most the table's width.  The lists are as long
    as the table has entries; past the grid's length they repeat its last
    step.  No gather but the table's own (and, under ``window``, of the
    slots' first blocks): a step's slot and its place in the slot are
    counts over the slots' ends."""
    b, width = table.shape
    first = (jnp.maximum(pos - window + 1, 0) // block_k if window
             else jnp.zeros_like(pos))
    n = jnp.clip(pos // block_k - first + 1, 1, width)
    ends = jnp.cumsum(n)
    i = jnp.minimum(jnp.arange(b * width, dtype=jnp.int32), ends[-1] - 1)
    done = ends[:, None] <= i[None, :]          # (b, steps): slot b ended
    slot = jnp.sum(done, axis=0, dtype=jnp.int32)
    entry = i - jnp.sum(jnp.where(done, n[:, None], 0), axis=0)
    if window:
        entry = (entry + first[slot]) % width
    return (slot, table.reshape(-1)[slot * width + entry],
            jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]))


def _paged_kernel(slot_ref, blk_ref, start_ref, pos_ref, q_ref, k_ref, v_ref,
                  *refs, scale, block_k, tok, tok_v, window=0, sink=False,
                  lag=0, q_rows=1):
    """One step of ``walk_list``'s list: every KV head of one live pool
    block of one slot at once.  ``tok`` / ``tok_v``: whether K's / V's
    block lies tokens-on-lanes.  With ``window`` the slot's walk starts at
    the block that holds its oldest visible row, and with ``sink`` a
    per-head score (``refs[0]``) joins the last normalisation.  With
    ``lag`` the first ``lag`` of each head's ``q_rows`` query rows see the
    columns up to ``pos - lag``, the rest up to ``pos``."""
    if sink:
        s_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    i = pl.program_id(0)
    bi = slot_ref[i]
    pos = pos_ref[bi]
    step = i - start_ref[bi]
    ji = step
    if window:
        # the walk's step is the slot's block lo // block_k + step
        lo = jnp.maximum(pos - window + 1, 0)
        ji = lo // block_k + step

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ji * block_k <= pos)
    def _update():
        q = q_ref[0].astype(jnp.float32) * scale         # (nkv, g, d)
        # (nkv, bk, d), tokens on axis 1 + ``tok`` = 1; (nkv, d, bk) and 2
        # on a swapped pool
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        # rows past pos carry zero weight, but the block's tail (and a
        # foreign block) may hold garbage and 0·NaN = NaN — zero those V
        # rows outright
        rows = ji * block_k + jax.lax.broadcasted_iota(
            jnp.int32, v.shape, 1 + tok_v)
        rows_ok = rows <= pos
        if window:
            rows_ok = rows_ok & (rows >= lo)
        v = jnp.where(rows_ok, v, 0.0)
        s = jax.lax.dot_general(q, k, (((2,), (2 - tok,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        cols = ji * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        seen = cols <= pos
        if lag:
            # a KV head's query rows are (head in group) x q_rows + r
            r = jax.lax.rem(jax.lax.broadcasted_iota(jnp.int32, s.shape, 1),
                            q_rows)
            seen = cols <= pos - jnp.where(r < lag, lag, 0)
        if window:
            seen = seen & (cols >= lo)
        s = jnp.where(seen, s, _NEG_INF)                 # (nkv, g, bk)

        m = m_ref[...]                                   # (nkv, g, 1)
        m_new = jnp.maximum(m, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((2,), (1 + tok_v,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(i == start_ref[bi + 1] - 1)
    def _finish():
        l = l_ref[...]
        if sink:
            # one more column of the softmax, with no value behind it
            l = l + jnp.exp(s_ref[...] - m_ref[...])
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, table, pos, *, layer: int = 0,
                    scale=None, window: int = 0, sink=None, lag: int = 0,
                    interpret: bool = None):
    """q (b, n_heads, R, d) attends to its block-table history in one
    layer of the pool: R = 1, a decode step's one row a slot, or the R
    rows of a slot's current diffusion block, which ALL see the slot's
    history up to ``pos`` (no mask among them) — they ride as R times the
    query heads of each KV group over one walk of the slot's blocks, so a
    pool block is fetched once for all of them.  ``lag`` (static, < R): the
    slot's first ``lag`` rows see up to ``pos - lag`` and the others up to
    ``pos`` — a finished diffusion block's rows beside the next block's
    (``lag`` the block's length, R twice that), one limit a row on the
    same walk.  Without it the kernel and its program are what they were.

    k_pool/v_pool (n_layers, n_blocks, n_kv_heads, block_k, d): the shared
    pool of EVERY layer, read where it lies; ``layer`` (static) picks the
    one this call attends to.  A value head may be narrower than a key's
    (``v_pool`` (..., dv)): the result is then (b, n_heads, 1, dv).
    table (b, max_blocks) int32: slot b's sequence lives in pool blocks
    ``table[b, 0] .. table[b, pos[b] // block_k]``; the entries past that
    are never dereferenced.  pos (b,) int32: index of slot b's newest
    entry in its OWN coordinate space (block j covers positions
    [j·block_k, (j+1)·block_k)) — with R rows, the last of them.

    ``window`` w (static; the kernel is then ``strom_window_attn``): the
    slot sees its last w rows only, ``pos - w < j <= pos``, and the table is
    a RING — (b, r) with r >= ceil(w / block_k) + 1 entries, the slot's
    block j at ``table[b, j % r]`` — of which the walk reads the entries
    that hold those rows and no more (a lower bound beside the upper one).
    ``sink`` (n_heads,) or None: a learned score per head that joins the
    softmax as one more column and carries no value.

    Returns (b, n_heads, R, dv).  ``interpret`` defaults to True off-TPU.
    """
    if q.ndim != 4:
        raise ValueError(f"expected q (b, h, rows, d), got {q.shape}")
    b, nh, rows, d = q.shape
    if rows != 1 and (window or sink is not None):
        raise ValueError(f"a window or a sink takes one query row a slot, "
                         f"got q {q.shape}")
    if not 0 <= lag < rows:
        raise ValueError(f"lag {lag} of {rows} query rows a slot")
    if (k_pool.ndim != 5 or v_pool.shape[:-1] != k_pool.shape[:-1]
            or k_pool.shape[-1] != d):
        raise ValueError("expected pools (layers, blocks, kv_heads, "
                         f"block, {d} | dv), got {k_pool.shape}, "
                         f"{v_pool.shape}")
    n_layers, _, nkv, block_k, _ = k_pool.shape
    dv = v_pool.shape[-1]
    if not 0 <= layer < n_layers:
        raise ValueError(f"layer {layer} not in a pool of {n_layers}")
    if nh % nkv:
        raise ValueError(f"{nh} query heads not divisible by {nkv} "
                         "kv heads")
    if table.shape[0] != b or table.ndim != 2:
        raise ValueError(f"table must be ({b}, max_blocks), "
                         f"got {table.shape}")
    # a KV head's query rows: its group's heads, each with its R rows
    g = nh // nkv * rows
    max_blocks = table.shape[1]
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    lanes, lanes_v = (_tokens_on_lanes(k_pool.shape),
                      _tokens_on_lanes(v_pool.shape))
    table = jnp.asarray(table, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    if window and max_blocks * block_k < window + block_k - 1:
        raise ValueError(f"a ring of {max_blocks} blocks of {block_k} "
                         f"cannot hold a window of {window}")
    # one grid step a live table entry: a grid bound that is data, so one
    # compiled program for every mix of lengths
    slot, blocks, start = walk_list(table, pos, block_k, window)

    def kv_spec(width, on_lanes):
        return pl.BlockSpec((1, 1, nkv, width, block_k) if on_lanes
                            else (1, 1, nkv, block_k, width),
                            lambda i, sl, bl, st, ps: (layer, bl[i], 0, 0, 0))

    def qo_spec(width):
        return pl.BlockSpec((1, nkv, g, width),
                            lambda i, sl, bl, st, ps: (sl[i], 0, 0, 0))

    in_specs = [qo_spec(d), kv_spec(d, lanes), kv_spec(dv, lanes_v)]
    args = [q.reshape(b, nkv, g, d), _kernel_view(k_pool, lanes),
            _kernel_view(v_pool, lanes_v)]
    if sink is not None:
        in_specs.append(pl.BlockSpec((nkv, g, 1),
                                     lambda i, sl, bl, st, ps: (0, 0, 0)))
        args.append(sink.astype(jnp.float32).reshape(nkv, g, 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(start[-1],),
        in_specs=in_specs,
        out_specs=qo_spec(dv),
        scratch_shapes=[
            pltpu.VMEM((nkv, g, 1), jnp.float32),
            pltpu.VMEM((nkv, g, 1), jnp.float32),
            pltpu.VMEM((nkv, g, dv), jnp.float32),
        ],
    )
    extra = dict(window=int(window), sink=sink is not None) \
        if window or sink is not None else {}
    if lag:
        extra.update(lag=int(lag), q_rows=rows)
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=float(scale),
                          block_k=block_k, tok=int(lanes),
                          tok_v=int(lanes_v), **extra),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, g, dv), q.dtype),
        name="strom_window_attn" if window else "strom_paged_attn",
        interpret=_interpret(interpret),
    )(slot, blocks, start, pos, *args)
    return out.reshape(b, nh, rows, dv)


def _write_kernel(blk_ref, off_ref, kn_ref, vn_ref, kp_ref, vp_ref,
                  ko_ref, vo_ref, *, tiles, toks, rows=1):
    """One slot: its tile of each pool with the slot's row replaced — or
    its ``rows`` rows from ``off`` on, which lie in one tile
    (``tiles`` / ``toks``: K's and V's tile length and whether it lies
    tokens-on-lanes).  The select runs in float32 (exact both ways for
    bf16), where every shape of broadcast and compare is at home."""
    bi = pl.program_id(0)
    ats = {}
    for new, old, out, tile, tok in zip((kn_ref, vn_ref), (kp_ref, vp_ref),
                                        (ko_ref, vo_ref), tiles, toks):
        if tile not in ats:
            ats[tile] = off_ref[bi] % tile
        have = old[...].astype(jnp.float32)      # (nkv, tile, d) | (nkv, d, tile)
        at = jax.lax.broadcasted_iota(jnp.int32, have.shape, 1 + tok)
        if rows == 1:
            hit = at == ats[tile]
            row = (new[...].astype(jnp.float32) if tok  # (nkv, d, tile), or
                   else new[:, pl.ds(bi, 1), :])        # (nkv, 1, d) of (nkv, b, d)
            out[...] = jnp.where(hit, row, have).astype(out.dtype)
            continue
        for r in range(rows):               # (nkv, 1, d) of (nkv, b * rows, d)
            have = jnp.where(at == ats[tile] + r,
                             new[:, pl.ds(bi * rows + r, 1), :], have)
        out[...] = have.astype(out.dtype)


def _row_specs(pool, new, layer: int):
    """How ``write_rows`` hands one pool and its new rows to the kernel:
    (the rows as the kernel takes them, their BlockSpec, the pool tile's
    BlockSpec, the tile's length, whether the pool lies tokens-on-lanes)."""
    _, _, nkv, block, d = pool.shape
    b = new.shape[0]
    new = new.astype(pool.dtype)
    if new.ndim == 4:
        # R rows a slot (b, nkv, R, d), kv-head-major as the projections
        # emit them: slot b's row r is row b * R + r of (nkv, b * R, d)
        rows = new.shape[2]
        tile = min(block, 8 * 4 // pool.dtype.itemsize)
        if _tokens_on_lanes(pool.shape) or tile % rows:
            raise NotImplementedError(
                f"{rows} rows a slot into a pool {pool.shape}: they must "
                f"divide its sublane tile of {tile} tokens, the head's "
                f"features along the lanes")
        return (new.transpose(1, 0, 2, 3).reshape(nkv, b * rows, d)
                .astype(jnp.float32),
                pl.BlockSpec((nkv, b * rows, d), lambda bi, bl, of: (0, 0, 0)),
                pl.BlockSpec(
                    (None, None, nkv, tile, d),
                    lambda bi, bl, of: (layer, bl[bi], 0, of[bi] // tile, 0)),
                tile, 0)
    if _tokens_on_lanes(pool.shape):
        # tokens along the lanes: the tile is a lane row of them, and each
        # slot's new row comes in already spread along it
        tile = 128
        return (jnp.broadcast_to(new[..., None], (b, nkv, d, tile)),
                pl.BlockSpec((None, nkv, d, tile),
                             lambda bi, bl, of: (bi, 0, 0, 0)),
                pl.BlockSpec(
                    (None, None, nkv, d, tile),
                    lambda bi, bl, of: (layer, bl[bi], 0, 0, of[bi] // tile)),
                tile, 1)
    # tokens along the sublanes: one packed sublane tile of them.  The new
    # rows stay whole in VMEM, kv-head-major as the projections emit them
    # (no re-layout between the matmul and this call) and in float32 (exact;
    # a single row of a packed type cannot be loaded)
    tile = min(block, 8 * 4 // pool.dtype.itemsize)
    return (jnp.swapaxes(new, 0, 1).astype(jnp.float32),
            pl.BlockSpec((nkv, b, d), lambda bi, bl, of: (0, 0, 0)),
            pl.BlockSpec(
                (None, None, nkv, tile, d),
                lambda bi, bl, of: (layer, bl[bi], 0, of[bi] // tile, 0)),
            tile, 0)


def write_rows(k_pool, v_pool, k_new, v_new, blk, off, *, layer: int,
               name: str = "strom_kv_write", interpret: bool = None):
    """Place one new K and V row per slot in layer ``layer`` of the pools,
    IN the pools: ``pool[layer, blk[b], :, off[b], :] = new[b]`` — or, with
    k_new/v_new (b, kv_heads, R, d), a slot's R rows of one diffusion block:
    ``pool[layer, blk[b], :, off[b] + r, :] = new[b, :, r]``, ``off`` a
    multiple of R and R a divisor of the pool's sublane tile, so that the R
    rows lie in the one tile the slot's grid step patches.

    k_pool/v_pool (layers, blocks, kv_heads, block, d) — V's ``d`` may
    differ from K's, and each pool is handed over in the layout the device
    keeps it in —, aliased input to
    output: under ``jit`` with the pools donated nothing pool-sized is
    copied or re-laid-out (the ``.at[].set`` scatter this replaces had XLA
    transpose the whole pool into the scatter's layout and back, every
    step).  k_new/v_new (b, kv_heads, d); blk/off (b,) int32.  Each grid
    step reads the aligned tile that holds its slot's row, replaces the
    row and writes the tile back.  Two slots aimed at one row (free slots
    and the trash block) leave one of their rows there, either one; the
    slots of live requests never share a block they write.  ``name`` is the
    kernel's in a device trace (a window layer's ring writer says so).

    Returns (k_pool, v_pool)."""
    n_layers, _, nkv, block, d = k_pool.shape
    if v_pool.shape[:-1] != k_pool.shape[:-1] or not 0 <= layer < n_layers:
        raise ValueError(f"layer {layer} of pools {k_pool.shape}, "
                         f"{v_pool.shape}")
    b = k_new.shape[0]
    rows = k_new.shape[2:-1]             # () or (R,)
    if (k_new.shape != (b, nkv) + rows + (d,)
            or v_new.shape != (b, nkv) + rows + (v_pool.shape[-1],)):
        raise ValueError(f"expected new rows ({b}, {nkv}, [R,] {d} | "
                         f"{v_pool.shape[-1]}), got {k_new.shape}, "
                         f"{v_new.shape}")
    extra = {"rows": rows[0]} if rows else {}
    (k_new, kn_spec, kt_spec, k_tile, k_tok), \
        (v_new, vn_spec, vt_spec, v_tile, v_tok) = (
            _row_specs(k_pool, k_new, layer), _row_specs(v_pool, v_new, layer))
    kv, vv = _kernel_view(k_pool, k_tok), _kernel_view(v_pool, v_tok)
    kv, vv = pl.pallas_call(
        functools.partial(_write_kernel, tiles=(k_tile, v_tile),
                          toks=(k_tok, v_tok), **extra),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[kn_spec, vn_spec, kt_spec, vt_spec],
            out_specs=[kt_spec, vt_spec]),
        out_shape=[jax.ShapeDtypeStruct(kv.shape, kv.dtype),
                   jax.ShapeDtypeStruct(vv.shape, vv.dtype)],
        # operands 4 and 5 (after the two scalar-prefetch ones) are the
        # pools, and so are the results
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=name,
        interpret=_interpret(interpret),
    )(jnp.asarray(blk, jnp.int32), jnp.asarray(off, jnp.int32),
      k_new, v_new, kv, vv)
    return _kernel_view(kv, k_tok), _kernel_view(vv, v_tok)

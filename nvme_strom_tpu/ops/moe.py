"""Pallas grouped matmul of an expert layer (``strom_moe_gmm``).

The rows of a batch, once routed, are (row, expert) pairs.  ``group_rows``
lays them out grouped by expert, every group starting on a tile boundary:

    expert 0: rows 0 .. c0-1, zeros up to the tile   | expert 1: ...

so that a tile of ``tm`` rows belongs to ONE expert and the product of the
whole batch is a walk over tiles, each against its own expert's matrix.  The
number of rows is static ((pairs // tm + experts) tiles); how many
tiles hold anything, and which expert each belongs to, is data and rides
scalar prefetch (``pltpu.PrefetchScalarGridSpec``): the weight BlockSpec's
``index_map`` dereferences ``tile_expert[i]``.

Grid ``(n tiles of the output's columns, row tiles)`` with the row tiles
innermost: for one column tile the walk visits the experts in order, and
consecutive row tiles of one expert name the same weight block, which is
then not fetched again — every (expert, column tile) block of the weights
moves at most once a call, and an expert with no rows is never named.  Row
tiles past the last used one do nothing: their index maps hold the last
used indices (an unchanged index fetches nothing) and the body runs under
``pl.when``.  The whole contraction is one block, so there is no
accumulator; where it is deep (7168 against LFM2's 2048 or 1536) the column
tile narrows instead, until the weight blocks in flight fit their share of
VMEM (``column_tile``).

Two forms, one kernel: ``gmm(x, (w_gate, w_up), ...)`` gives
``silu(x w_gate) * (x w_up)`` (both products in float32, one pass over x),
``gmm(h, (w_down,), ...)`` the plain product.  Rows of the padding are zeros
in and zeros out; rows of unused tiles are never written and never read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: widest column tile: a (2048, 512) bf16 block of weights is 2 MiB, the
#: gated form holds two and Pallas double-buffers them: 8 MiB of VMEM
_TILE_N = 512
#: what the weight blocks in flight may take of the kernel's 32 MiB (the row
#: tile, the result and the float32 products share the rest)
_WEIGHT_VMEM = 16 * 2 ** 20


def _interpret(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


def tile_rows(pairs: int, n_experts: int) -> int:
    """Rows of a tile for a call of ``pairs`` (row, expert) pairs: the mean
    group rounded up to a power of two, between the packed sublane tile of
    bf16 (16) and the MXU's 128."""
    mean = max(1, -(-pairs // n_experts))
    return min(128, max(16, 1 << (mean - 1).bit_length()))


def padded_rows(pairs: int, n_experts: int, tm: int) -> int:
    """Static row count of the grouped layout: every group may waste up to
    ``tm - 1`` rows (a multiple of ``tm``)."""
    return (pairs // tm + n_experts) * tm


def group_rows(expert, n_experts: int, tm: int):
    """Where each pair goes in the grouped layout.

    expert (P,) int32: the pair's expert, or ``n_experts`` for a pair that
    is not to be computed (a pad row, a free slot).  Returns (dest (P,)
    int32 — the pair's row in the layout, ``rows`` for one not computed —,
    tile_expert (rows // tm,) int32, n_tiles () int32, counts (E,) int32)
    with ``rows = padded_rows(P, n_experts, tm)``.  No sort: a pair's rank
    inside its group is a running count."""
    n_pairs = expert.shape[0]
    rows = padded_rows(n_pairs, n_experts, tm)
    hot = (expert[:, None] == jnp.arange(n_experts)[None, :]).astype(jnp.int32)
    counts = hot.sum(axis=0)
    rank = jnp.sum((jnp.cumsum(hot, axis=0) - hot) * hot, axis=1)
    tiles = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    n_tiles = tile_end[-1]
    start = (tile_end - tiles) * tm             # first row of each group
    live = expert < n_experts
    dest = jnp.where(live, start[jnp.minimum(expert, n_experts - 1)] + rank,
                     rows)
    # tile i belongs to the first expert whose tiles end past i; tiles past
    # the last used one repeat its expert (the kernel skips them)
    tile_expert = jnp.searchsorted(
        tile_end, jnp.minimum(jnp.arange(rows // tm),
                              jnp.maximum(n_tiles - 1, 0)), side="right")
    tile_expert = jnp.minimum(tile_expert, n_experts - 1).astype(jnp.int32)
    return dest.astype(jnp.int32), tile_expert, n_tiles.astype(jnp.int32), \
        counts


def column_tile(kdim: int, n: int, n_weights: int, itemsize: int) -> int:
    """Columns of a weight block: the widest power-of-two share of
    ``_TILE_N`` that divides ``n`` and keeps ``n_weights`` double-buffered
    (kdim, tile) blocks within ``_WEIGHT_VMEM`` (512 at LFM2's depths, 256
    for a gated product 7168 deep)."""
    tn = min(n, _TILE_N)
    while n % tn or (tn > 128 and 2 * n_weights * kdim * tn * itemsize
                     > _WEIGHT_VMEM):
        tn //= 2
    return tn


def _gmm_kernel(te_ref, nt_ref, x_ref, *refs, gated: bool):
    w_refs, o_ref = refs[:-1], refs[-1]

    @pl.when(pl.program_id(1) < nt_ref[0])
    def _tile():
        x = x_ref[...]
        a = jnp.dot(x, w_refs[0][0], preferred_element_type=jnp.float32)
        if gated:
            u = jnp.dot(x, w_refs[1][0], preferred_element_type=jnp.float32)
            a = a * jax.nn.sigmoid(a) * u
        o_ref[...] = a.astype(o_ref.dtype)


def gmm(x, weights: tuple, tile_expert, n_tiles, *, tm: int,
        interpret: bool = None):
    """Grouped product of the laid-out rows with their experts' matrices.

    x (rows, K), ``rows`` a multiple of ``tm``, grouped as ``group_rows``
    says; weights: one (E, K, N) array, or two for the gated form;
    tile_expert (rows // tm,) and n_tiles () int32 from ``group_rows``.
    Returns (rows, N) in x's dtype; rows of tiles past ``n_tiles`` hold
    nothing meaningful."""
    rows, kdim = x.shape
    n_exp, kw, n = weights[0].shape
    if kw != kdim or rows % tm or any(w.shape != weights[0].shape
                                      for w in weights):
        raise ValueError(f"gmm: x {x.shape} (tile {tm}) against "
                         f"{[w.shape for w in weights]}")
    tn = column_tile(kdim, n, len(weights), x.dtype.itemsize)
    weights = tuple(w.astype(x.dtype) for w in weights)

    def used(i, nt):                 # a tile past the end holds the last one
        return jnp.minimum(i, jnp.maximum(nt[0] - 1, 0))

    x_spec = pl.BlockSpec((tm, kdim), lambda j, i, te, nt: (used(i, nt), 0))
    w_spec = pl.BlockSpec((1, kdim, tn),
                          lambda j, i, te, nt: (te[used(i, nt)], 0, j))
    o_spec = pl.BlockSpec((tm, tn), lambda j, i, te, nt: (used(i, nt), j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, gated=len(weights) == 2),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // tn, rows // tm),
            in_specs=[x_spec] + [w_spec] * len(weights),
            out_specs=o_spec),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 * 2**20),
        name="strom_moe_gmm",
        interpret=_interpret(interpret),
    )(tile_expert, jnp.reshape(n_tiles, (1,)), x, *weights)

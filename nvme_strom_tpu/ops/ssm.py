"""Pallas kernels of the Mamba-2 (SSD) recurrence.

    S_t = exp(Δ_t A) · S_{t-1} + Δ_t · x_t ⊗ B_t        S: (H, P, N) float32
    y_t = S_t C_t                                        (the D·x skip, the
                                                          gate and the norm
                                                          are the mixer's:
                                                          models/ssm.py)

The state is KEPT state-major with the heads packed on the lanes:
``(rows, H/g, N, g·P)`` float32, lane ``j·P + p`` of packed head ``hp`` the
element ``p`` of head ``hp·g + j``, where ``g`` (``heads_per_lane_row``) is
the largest divisor of H not above ``128 // P`` — two heads of 64 side by
side fill a lane row, a head of 128 or more stands alone.  So the sum over
the state that ``y`` needs runs over SUBLANES (vector adds), ``y`` and the
per-head vectors are lane-dense rows that are plain reshapes of ``(B, H·P)``,
and the scan's blocks ``(H, N, P)`` are a swap of major dimensions away.
``pack_state`` / ``unpack_state`` go between that form and ``(b, H, P, N)``.

Two kernels, one per serving phase:

``ssm_scan`` (``strom_ssm_scan``) — a padded prompt, chunk by chunk.  Inside
a chunk of Q rows the recurrence is three matrix products on the MXU (the
"state-space duality" form): ``(C Bᵀ ⊙ L) (Δx)`` with ``L[t, s] =
exp(Σ_{s<r≤t} Δ_r A)`` below the diagonal, the carried state's share
``exp(cs_t) · C S``, and the chunk's own state ``Bᵀ (w ⊙ Δx)``; between
chunks the state is carried in VMEM.  Pad rows come in with Δ = 0: decay 1,
input 0, so the state after the last row is the state after the last valid
row.

``ssm_update`` (``strom_ssm_update``) — one token for every slot.  The
state pool is read and written through the SAME buffer
(``input_output_aliases``): every byte of it moves twice a step and a copy
would move it twice more.  A packed head's block is decayed by a row,
takes ``B`` (turned into a column across the lanes once a grid step: every
head shares it) times a row, and is summed against ``C`` over its sublanes:
float32 on the VPU, as ``strom_gdn_update`` walks its own pool.  ``sidx``
picks each slot's pool row, so a free slot writes a sacrificial row the way
a free slot's K/V write lands in the trash block.

Both run in interpret mode off the TPU like the repo's other kernels.

The gated delta rule — the other recurrence with a state matrix, ``S ← αS; S
← S + k ⊗ β(v − Sᵀk)``, which reads the state through a key before it writes
it — has its own pair of kernels of the same two shapes in ``ops/gdn.py``
(``strom_gdn_scan``, ``strom_gdn_update``); ``_heads_per_step`` and
``_interpret`` below serve both files.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: heads per grid step.  Update: 32 heads are 16 packed heads of (N, 2·P) =
#: (128, 128) float32 at granite-4.0-h-micro's sizes, a 1 MiB block of the
#: pool, in and out and double-buffered 4 MiB of VMEM (what the block's size
#: and the form are worth on a v5e: PERF.md §6, PR 44).
#: Scan: 4 / 8 / 16 heads time alike (a call is launch-bound).
_UPDATE_HEADS = 32
_SCAN_HEADS = 8


def _heads_per_step(n_heads: int, want: int) -> int:
    hb = min(n_heads, want)
    while n_heads % hb:
        hb -= 1
    return hb


def heads_per_lane_row(n_heads: int, head_dim: int) -> int:
    """``g``: the heads the state keeps side by side on the 128 lanes — the
    largest divisor of ``n_heads`` not above ``128 // head_dim`` (1: nothing
    is packed)."""
    return _heads_per_step(n_heads, max(1, 128 // head_dim))


def pool_shape(rows: int, n_heads: int, head_dim: int, n_state: int):
    """The state of ``rows`` sequences as it is kept: (rows, H/g, N, g·P)."""
    g = heads_per_lane_row(n_heads, head_dim)
    return rows, n_heads // g, n_state, g * head_dim


def pack_state(s):
    """(b, H, P, N) → the kept form (b, H/g, N, g·P)."""
    b, n_heads, p, n = s.shape
    g = heads_per_lane_row(n_heads, p)
    return (s.reshape(b, n_heads // g, g, p, n).transpose(0, 1, 4, 2, 3)
            .reshape(b, n_heads // g, n, g * p))


def unpack_state(s, n_heads: int):
    """The kept form (b, H/g, N, g·P) → (b, H, P, N)."""
    b, hp, n, lanes = s.shape
    g = n_heads // hp
    return (s.reshape(b, hp, n, g, lanes // g).transpose(0, 1, 3, 4, 2)
            .reshape(b, n_heads, lanes // g, n))


def _interpret(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


# ------------------------------------------------------------------ scan

def _scan_kernel(x_ref, cs_col_ref, cs_row_ref, bt_ref, c_ref, s0_ref,
                 y_ref, s_ref, *, hb, q):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = s0_ref[...]

    mm = x_ref.dtype                       # the MXU's operand type
    bt = bt_ref[0]                         # (N, q)
    c = c_ref[0]                           # (q, N)
    g = jnp.dot(c, bt, preferred_element_type=jnp.float32)       # (q, q)
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lower = cols <= rows
    cs_cols = cs_col_ref[0, 0]             # (q, hb) inclusive cumsum of Δ·A
    for h in range(hb):
        cs_c = cs_cols[:, h:h + 1]         # (q, 1)
        cs_r = cs_row_ref[0, h]            # (1, q)
        x = x_ref[0, h]                    # (q, P): Δ_s · x_s
        # decay from row s to row t (s <= t), 0 above the diagonal
        lmat = jnp.exp(jnp.where(lower, cs_c - cs_r, -jnp.inf))
        y = jnp.dot((g * lmat).astype(mm), x,
                    preferred_element_type=jnp.float32)
        st = s_ref[0, h]                   # (N, P): the carried state, Sᵀ
        y = y + jnp.exp(cs_c) * jnp.dot(
            c, st.astype(mm), preferred_element_type=jnp.float32)
        y_ref[0, h] = y.astype(y_ref.dtype)
        # the chunk's own state: row s decays over the rest of the chunk
        last = cs_r[:, q - 1:q]            # (1, 1)
        w = jnp.exp(last - cs_r)           # (1, q)
        s_ref[0, h] = jnp.exp(last) * st + jnp.dot(
            (bt.astype(jnp.float32) * w).astype(mm), x,
            preferred_element_type=jnp.float32)


def ssm_scan(x, dt, a, b, c, s0, valid=None, *, chunk: int = 256,
             interpret=None):
    """The recurrence over a (right-padded) block of rows.

    x (bt, m, H, P); dt (bt, m, H) float32, Δ after its softplus; a (H,)
    float32, negative; b, c (bt, m, N); s0 (bt, H/g, N, g·P) float32, the
    state before row 0 in the kept form; valid (bt, m) bool or None — rows
    that are not valid leave the state untouched (their y is meaningless).

    Returns (y (bt, m, H, P) in x's dtype, state (bt, H/g, N, g·P) float32
    after the last valid row)."""
    bsz, m, n_heads, p = x.shape
    n = b.shape[-1]
    dt = dt.astype(jnp.float32)
    if valid is not None:
        dt = jnp.where(valid[..., None], dt, 0.0)
    q = min(chunk, -(-m // 8) * 8)
    pad = -m % q
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    mp = m + pad
    nc = mp // q
    hb = _heads_per_step(n_heads, _SCAN_HEADS)
    nh = n_heads // hb
    # inclusive cumulative log-decay inside each chunk, float32
    cs = jnp.cumsum((dt * a).reshape(bsz, nc, q, n_heads), axis=2)
    cs = cs.reshape(bsz, mp, n_heads)
    cs_col = cs.reshape(bsz, mp, nh, hb).transpose(0, 2, 1, 3)   # (b,nh,m,hb)
    cs_row = cs.transpose(0, 2, 1)[:, :, None, :]                # (b,H,1,m)
    dtx = (dt[..., None] * x.astype(jnp.float32)).astype(x.dtype)
    dtx = dtx.transpose(0, 2, 1, 3)                              # (b,H,m,P)
    bt_ = b.transpose(0, 2, 1)                                   # (b,N,m)
    # the kept form ↔ the kernel's (b, H, N, P): packed heads apart, P stays
    # on the lanes
    hp, g = s0.shape[1], n_heads // s0.shape[1]
    s0t = (s0.astype(jnp.float32).reshape(bsz, hp, n, g, p)
           .transpose(0, 1, 3, 2, 4).reshape(bsz, n_heads, n, p))
    y, st = pl.pallas_call(
        functools.partial(_scan_kernel, hb=hb, q=q),
        grid=(bsz, nh, nc),
        in_specs=[
            pl.BlockSpec((1, hb, q, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, q, hb), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, hb, 1, q), lambda bi, hi, ci: (bi, hi, 0, ci)),
            pl.BlockSpec((1, n, q), lambda bi, hi, ci: (bi, 0, ci)),
            pl.BlockSpec((1, q, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, hb, n, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, q, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, hb, n, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bsz, n_heads, mp, p), x.dtype),
                   jax.ShapeDtypeStruct((bsz, n_heads, n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="strom_ssm_scan",
        interpret=_interpret(interpret),
    )(dtx, cs_col, cs_row, bt_, c, s0t)
    st = (st.reshape(bsz, hp, g, n, p).transpose(0, 1, 3, 2, 4)
          .reshape(bsz, hp, n, g * p))
    return y.transpose(0, 2, 1, 3)[:, :m], st


# ---------------------------------------------------------------- update

def _update_kernel(sidx_ref, s_ref, rows_ref, bc_ref, y_ref, s_out_ref, *,
                   hb):
    del sidx_ref                           # used by the index maps only
    rows = rows_ref[0, 0]                  # (2 hb, g·P): exp(Δ·A) | Δ·x a row
    n, lanes = s_ref.shape[2:]
    # B and C are every head's: each row a column across the lanes, once a
    # grid step
    bv, cv = (jnp.broadcast_to(bc_ref[0, i:i + 1], (lanes, n)).T
              for i in range(2))
    for h in range(hb):
        s = s_ref[0, h] * rows[h:h + 1] + bv * rows[hb + h:hb + h + 1]
        s_out_ref[0, h] = s                # (N, g·P)
        y_ref[0, 0, h:h + 1, :] = jnp.sum(s * cv, axis=0, keepdims=True)


def ssm_update(s_pool, sidx, x, dt, a, b, c, *, interpret=None):
    """One step of the recurrence for every slot, the pool updated in place.

    s_pool (rows, H/g, N, g·P) float32, the kept form — donate it: the
    result aliases it; sidx (B,) int32, slot b's row of the pool (free
    slots: the sacrificial row); x (B, H, P); dt (B, H) float32 after its
    softplus; a (H,); b, c (B, N).  Returns (y (B, H, P) float32, s_pool)."""
    bsz, n_heads, p = x.shape
    _, hp, n, lanes = s_pool.shape         # hp packed heads of g = lanes / P
    hb = _heads_per_step(hp, max(1, _UPDATE_HEADS * hp // n_heads))
    nh = hp // hb
    f32 = jnp.float32
    dt = dt.astype(f32)
    da = jnp.broadcast_to(jnp.exp(dt * a)[:, :, None], (bsz, n_heads, p))
    dtx = dt[:, :, None] * x.astype(f32)
    row = jnp.concatenate([da.reshape(bsz, nh, hb, lanes),
                           dtx.reshape(bsz, nh, hb, lanes)], axis=2)
    bc = jnp.stack([b.astype(f32), c.astype(f32)], axis=1)      # (B, 2, N)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, nh),
        in_specs=[
            pl.BlockSpec((1, hb, n, lanes),
                         lambda bi, hi, sx: (sx[bi], hi, 0, 0)),
            pl.BlockSpec((1, 1, 2 * hb, lanes),
                         lambda bi, hi, sx: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 2, n), lambda bi, hi, sx: (bi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, hb, lanes), lambda bi, hi, sx: (bi, hi, 0, 0)),
            pl.BlockSpec((1, hb, n, lanes),
                         lambda bi, hi, sx: (sx[bi], hi, 0, 0)),
        ],
    )
    y, s_pool = pl.pallas_call(
        functools.partial(_update_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((bsz, nh, hb, lanes), f32),
                   jax.ShapeDtypeStruct(s_pool.shape, f32)],
        # operand 1 (after the scalar prefetch) is the pool; result 1 is too
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="strom_ssm_update",
        interpret=_interpret(interpret),
    )(jnp.asarray(sidx, jnp.int32), s_pool, row, bc)
    return y.reshape(bsz, n_heads, p), s_pool

"""Pallas kernels of the gated delta rule (the Gated DeltaNet recurrence).

A value head keeps a state ``S`` (dk, dv) float32 and reads it THROUGH its
key before it writes it, a token at a time:

    S ← α_t S                               α_t in (0, 1): the head's decay
    u  = β_t (v_t − Sᵀ k_t)                 β_t in (0, 1), or (0, 2) where the
    S ← S + k_t ⊗ u                         model lets I − β k kᵀ turn a key's
    o_t = Sᵀ q_t                            slot over: how much of the value
                                            the slot takes (β is data to both
                                            kernels; nothing here bounds it)

(q and k come in L2-normalised and scaled, β as a number and the decay as
its logarithm g = log α ≤ 0 — a head that forgets at once has α = 0 in
float32 and a finite g: the projections, the causal conv, the norms and the
gates are the mixer's, models/ssm.py.)  Two kernels, one per serving phase,
as ops/ssm.py has for Mamba-2:

``gdn_update`` (``strom_gdn_update``) — one token for every slot against the
state pool, read and written through the SAME buffer
(``input_output_aliases``): each (slot, head) block is read once, decayed,
multiplied by k, corrected, multiplied by q and written back once — 2 x 64
KiB of traffic a head at 128 x 128 and nothing more.  The two products are
broadcast multiplies and sublane sums on the VPU over the sixteen vector
registers a head's state fills; everything is float32.  ``sidx`` picks each
slot's pool row, so a free slot writes the sacrificial row.  Where dv is no
multiple of the 128 lanes the pool keeps g = ``heads_per_lane_row`` heads
side by side on them — (rows, H/g, dk, g·dv), two heads of 192 on 384 —
so that no row of the state is stored or moved padded (a 192-wide row alone
would take 256 lanes, a third more bytes on the step's largest term); the
kernel then spreads a head's k, q and α over its own dv lanes and the
sublane sums serve the g heads at once (``pack_state`` / ``unpack_state``).

``gdn_scan`` (``strom_gdn_scan``) — a right-padded prompt in chunks of C
rows.  With g the running sum of log α inside the chunk, Γ[t, s] = exp(g_t −
g_s) for s ≤ t (never a quotient of decays: every exponent is ≤ 0) and
A = tril(diag(β) (K Kᵀ ⊙ Γ), −1), the chunk's corrections solve

    (I + A) [W | U'] = [β e^g ⊙ K | β ⊙ V]        U = U' − W S₀

by FORWARD SUBSTITUTION in float32, in blocks of R = 16 rows
(``solve_unit_lower``).  Inside a block, on the VPU: R − 1 rank-one updates
of the block's own (R, dk + dv) rows — four vector registers at 128 + 128,
six at 96 + 192 — row r final before it is used.  Between blocks, on the
MXU: every later row takes a finished block's correction at once,
X[later] −= A[later, block] X[block], three small products a chunk of 64 —
of float32 operands at float32's full precision (``Precision.HIGHEST``),
never in the activations' type: these rows are what the rest of the
substitution is built on, and one bfloat16 pass there leaves 1e-2 where the
substitution leaves 1e-6.  (Row by row over the whole chunk the same solve
was C − 1 updates of all C rows, sixteen registers and eight lane broadcasts
of a column of A each.)  What is left is a chain — a step is a row's
broadcast, a multiply and a subtract that wait for one another, a coupling a
product's way through the MXU and back — so the heads of a grid step are
solved as ONE stacked array, each step one expression over all of them: step
j of every head comes before step j + 1 of any, and one head's wait is
another's work.  And still not the nilpotent product
Π (I + (−A)^(2^i)): that form squares A five times, and with keys that
repeat (|k_s · k_r| near 1, which trained keys do and random ones do not)
its powers grow before they cancel, in float32.  Here a row is only ever
corrected by FINISHED rows and A is never multiplied by itself: blocked or
not, it is the recurrence's own arithmetic order.  Then, on the MXU with
float32 accumulation (operands in the activations' type):

    O  = e^g ⊙ (Q S₀) + tril(Q Kᵀ ⊙ Γ) U
    S₁ = e^{g_C} S₀ + (K ⊙ e^{g_C − g})ᵀ U

and the state is carried in VMEM between chunks.  A pad row comes in with g
= 0 and β = 0: it corrects nothing and leaves the state as the last valid
row left it.

Both run in interpret mode off the TPU like the repo's other kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nvme_strom_tpu.ops.ssm import _heads_per_step, _interpret

#: value heads per grid step.  Update: a (16, 128, 128) float32 block of the
#: pool is 1 MiB, in and out and double-buffered 4 MiB of VMEM.  Scan: the
#: heads of a step share its launch and are solved as one stacked array.
_UPDATE_HEADS = 16
_SCAN_HEADS = 4
#: rows of a block of the scan's solve: two sublane tiles of float32, so a
#: rank-one update inside a block touches (dk + dv) / 64 vector registers
SCAN_SOLVE_ROWS = 16


def heads_per_lane_row(n_heads: int, dv: int) -> int:
    """``g``: the heads whose states the pool keeps side by side on the
    lanes — the fewest (a divisor of ``n_heads``, at most 4) that make g·dv a
    multiple of 128; 1 where dv is one already, or where none does."""
    for g in (1, 2, 4):
        if n_heads % g == 0 and (g * dv) % 128 == 0:
            return g
    return 1


def pool_shape(rows: int, n_heads: int, dk: int, dv: int) -> tuple:
    """The state of ``rows`` sequences as it is kept: (rows, H/g, dk, g·dv)."""
    g = heads_per_lane_row(n_heads, dv)
    return rows, n_heads // g, dk, g * dv


def pack_state(s):
    """(b, H, dk, dv) → the kept form (b, H/g, dk, g·dv); s itself at g = 1."""
    b, n_heads, dk, dv = s.shape
    g = heads_per_lane_row(n_heads, dv)
    if g == 1:
        return s
    return (s.reshape(b, n_heads // g, g, dk, dv).transpose(0, 1, 3, 2, 4)
            .reshape(b, n_heads // g, dk, g * dv))


def unpack_state(s, n_heads: int):
    """The kept form (b, H/g, dk, g·dv) → (b, H, dk, dv)."""
    b, packs, dk, lanes = s.shape
    g = n_heads // packs
    if g == 1:
        return s
    return (s.reshape(b, packs, dk, g, lanes // g).transpose(0, 1, 3, 2, 4)
            .reshape(b, n_heads, dk, lanes // g))


# ---------------------------------------------------------------- update

def _update_kernel(sidx_ref, s_ref, cols_ref, rows_ref, o_ref, s_out_ref, *,
                   hb, g=1):
    del sidx_ref                           # used by the index maps only
    cols = cols_ref[0, 0]                  # (dk, 3 n): k | q | α a column
    rows = rows_ref[0, 0]                  # (2 hb, g dv): β v | β a row
    n = hb * g                             # heads of this step, g a pack
    if g > 1:
        dk, lanes = s_ref.shape[2:]
        head = jax.lax.broadcasted_iota(jnp.int32, (dk, lanes), 1) \
            // (lanes // g)

    def spread(c):
        """Pack h's g columns from ``c`` on, each over its own head's dv
        lanes: (dk, g dv) — the one column itself at g = 1."""
        out = cols[:, c:c + 1]
        for j in range(1, g):
            out = jnp.where(head >= j, cols[:, c + j:c + j + 1], out)
        return out

    for h in range(hb):
        k = spread(h * g)                                  # (dk, 1 | g dv)
        q = spread(n + h * g)
        s = s_ref[0, h] * spread(2 * n + h * g)            # α S
        u = rows[h:h + 1] - rows[hb + h:hb + h + 1] * jnp.sum(
            s * k, axis=0, keepdims=True)                  # β (v − Sᵀ k)
        s = s + k * u
        s_out_ref[0, h] = s
        o_ref[0, 0, h:h + 1, :] = jnp.sum(s * q, axis=0, keepdims=True)


def gdn_update(s_pool, sidx, q, k, v, g, beta, *, interpret=None):
    """One step of the recurrence for every slot, the pool updated in place.

    s_pool (rows, H/g, dk, g·dv) float32, ``pool_shape``'s form — donate
    it: the result aliases it;
    sidx (B,) int32, slot b's row of the pool (free slots: the sacrificial
    row); q, k (B, H, dk) — one a VALUE head, normalised and scaled; v (B,
    H, dv); g (B, H) float32, the decay's logarithm; beta (B, H) float32.
    Returns (o (B, H, dv) float32, s_pool)."""
    bsz, n_heads, dk = k.shape
    dv = v.shape[-1]
    packs = s_pool.shape[1]
    pk = n_heads // packs                  # heads a pack (1: none packed)
    hb = _heads_per_step(packs, max(1, _UPDATE_HEADS // pk))
    nh = packs // hb
    lanes = pk * dv                        # a pack's lanes
    f32 = jnp.float32
    alpha, beta = jnp.exp(g.astype(f32)), beta.astype(f32)

    def cols(t):                           # (B, H, dk) → (B, nh, dk, hb pk)
        return t.astype(f32).reshape(bsz, nh, hb * pk, dk).transpose(
            0, 1, 3, 2)

    col = jnp.concatenate(
        [cols(k), cols(q),
         cols(jnp.broadcast_to(alpha[..., None], (bsz, n_heads, dk)))],
        axis=-1)                                            # (B, nh, dk, 3hb)
    row = jnp.concatenate(
        [(beta[..., None] * v.astype(f32)).reshape(bsz, nh, hb, lanes),
         jnp.broadcast_to(beta[..., None],
                          v.shape).reshape(bsz, nh, hb, lanes)],
        axis=2)                                          # (B, nh, 2hb, lanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, nh),
        in_specs=[
            pl.BlockSpec((1, hb, dk, lanes),
                         lambda bi, hi, sx: (sx[bi], hi, 0, 0)),
            pl.BlockSpec((1, 1, dk, 3 * hb * pk),
                         lambda bi, hi, sx: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, 2 * hb, lanes),
                         lambda bi, hi, sx: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, hb, lanes),
                         lambda bi, hi, sx: (bi, hi, 0, 0)),
            pl.BlockSpec((1, hb, dk, lanes),
                         lambda bi, hi, sx: (sx[bi], hi, 0, 0)),
        ],
    )
    o, s_pool = pl.pallas_call(
        functools.partial(_update_kernel, hb=hb, g=pk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((bsz, nh, hb, lanes), f32),
                   jax.ShapeDtypeStruct(s_pool.shape, f32)],
        # operand 1 (after the scalar prefetch) is the pool; result 1 is too
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="strom_gdn_update",
        interpret=_interpret(interpret),
    )(jnp.asarray(sidx, jnp.int32), s_pool, col, row)
    return o.reshape(bsz, n_heads, dv), s_pool


# ------------------------------------------------------------------ scan

def solve_unit_lower(a, x, block):
    """(I + A) X = B for a stack of systems — a (h, c, c), each strictly
    lower; x (h, c, n), the right-hand sides; float32 — in blocks of
    ``block`` rows (the last may be shorter).  Inside a block the forward
    substitution: row j, final, leaves the rows under it (a column of A is 0
    down to its diagonal, so the rows above lose nothing).  Then every later
    row takes the block's correction at once, as one product of float32
    operands at float32's own precision.  A row is only ever corrected by
    finished rows, and no power of A is formed.

    The h systems are one array and each step is one expression over it:
    a step waits for the one before it (a row's broadcast, a multiply, a
    subtract; a product's way through the MXU), the units keep their
    operations in the order they were written, and written so step j of
    every system comes before step j + 1 of any — one system's wait is
    another's work."""
    c = a.shape[1]
    done, rest = [], x
    for lo in range(0, c, block):
        hi = min(lo + block, c)
        xb = rest[:, :hi - lo]
        for j in range(hi - lo - 1):
            xb = xb - a[:, lo:hi, lo + j:lo + j + 1] * xb[:, j:j + 1, :]
        done.append(xb)
        if hi < c:
            rest = rest[:, hi - lo:] - jnp.einsum(
                "hrb,hbn->hrn", a[:, hi:, lo:hi], xb,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
    return jnp.concatenate(done, axis=1)


def _scan_kernel(q_ref, k_ref, kt_ref, v_ref, g_col_ref, g_row_ref, b_ref,
                 s0_ref, o_ref, s_ref, *, hb, c):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = s0_ref[...]

    mm = q_ref.dtype                       # the MXU's operand type
    f32 = jnp.float32
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)

    def system(h):
        k, kt, v = k_ref[0, h, 0], kt_ref[0, h, 0], v_ref[0, h, 0]
        g_col = g_col_ref[0, h, 0]         # (c, 1) running Σ log α, float32
        g_row = g_row_ref[0, h, 0]         # (1, c)
        beta = b_ref[0, h, 0]              # (c, 1)
        # Γ[t, s] = decay from row s to row t (s <= t), 0 above the diagonal
        gam = jnp.exp(jnp.where(cols <= rows, g_col - g_row, -jnp.inf))
        eg = jnp.exp(g_col)        # (c, 1): decay from the chunk's start
        a = jnp.where(cols < rows, beta * gam * jnp.dot(
            k, kt, preferred_element_type=f32), 0.0)       # strictly lower
        x = jnp.concatenate([beta * eg * k.astype(f32),
                             beta * v.astype(f32)], axis=1)  # (c, dk + dv)
        return gam, eg, a, x

    def finish(h, gam, eg, x):
        q, kt = q_ref[0, h, 0], kt_ref[0, h, 0]    # (c, dk) (dk, c)
        g_row = g_row_ref[0, h, 0]
        dk = q.shape[1]
        w, u = x[:, :dk], x[:, dk:]
        s0 = s_ref[0, h]                   # (dk, dv): the carried state
        s0m = s0.astype(mm)
        u = u - jnp.dot(w.astype(mm), s0m, preferred_element_type=f32)
        um = u.astype(mm)
        m = gam * jnp.dot(q, kt, preferred_element_type=f32)
        o = eg * jnp.dot(q, s0m, preferred_element_type=f32) + jnp.dot(
            m.astype(mm), um, preferred_element_type=f32)
        o_ref[0, h, 0] = o.astype(o_ref.dtype)
        # the chunk's own state: row s decays over the rest of the chunk
        last = g_row[:, c - 1:c]           # (1, 1)
        s_ref[0, h] = jnp.exp(last) * s0 + jnp.dot(
            (kt.astype(f32) * jnp.exp(last - g_row)).astype(mm), um,
            preferred_element_type=f32)

    # the step's heads stacked, not one after the other: see the solve
    gam, eg, a, x = zip(*[system(h) for h in range(hb)])
    x = solve_unit_lower(jnp.stack(a), jnp.stack(x), SCAN_SOLVE_ROWS)
    for h in range(hb):
        finish(h, gam[h], eg[h], x[h])


def gdn_scan(q, k, v, g, beta, s0, valid=None, *, chunk: int = 64,
             interpret=None):
    """The recurrence over a (right-padded) block of rows.

    q, k (bt, m, H, dk) — one a VALUE head, normalised and scaled; v (bt, m,
    H, dv); g (bt, m, H) float32, the decay's logarithm; beta (bt, m, H)
    float32; s0 (bt, H, dk, dv) float32, the state before row 0; valid (bt,
    m) bool or None — rows that are not valid leave the state untouched
    (their o is meaningless).

    Returns (o (bt, m, H, dv) in v's dtype, state (bt, H, dk, dv) float32
    after the last valid row)."""
    bsz, m, n_heads, dk = k.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    log_a = g.astype(f32)
    beta = beta.astype(f32)
    if valid is not None:
        log_a = jnp.where(valid[..., None], log_a, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
    c = min(chunk, -(-m // 8) * 8)
    pad = -m % c
    if pad:
        q, k, v, log_a, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, log_a, beta))
    nc = (m + pad) // c
    hb = _heads_per_step(n_heads, _SCAN_HEADS)

    def chunks(t):                         # (b, m, H, w) → (b, H, nc, c, w)
        return t.reshape(bsz, nc, c, n_heads, -1).transpose(0, 3, 1, 2, 4)

    # running log-decay inside each chunk, float32
    cs = jnp.cumsum(log_a.reshape(bsz, nc, c, n_heads), axis=2)
    g_col = cs.transpose(0, 3, 1, 2)[..., None]                # (b,H,nc,c,1)
    g_row = cs.transpose(0, 3, 1, 2)[..., None, :]             # (b,H,nc,1,c)
    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    ktc = kc.swapaxes(-1, -2)                                  # (b,H,nc,dk,c)
    bc = chunks(beta[..., None])                               # (b,H,nc,c,1)

    def spec(*tail):
        return pl.BlockSpec((1, hb, 1) + tail,
                            lambda bi, hi, ci: (bi, hi, ci, 0, 0))

    state = pl.BlockSpec((1, hb, dk, dv), lambda bi, hi, ci: (bi, hi, 0, 0))
    o, s = pl.pallas_call(
        functools.partial(_scan_kernel, hb=hb, c=c),
        grid=(bsz, n_heads // hb, nc),
        in_specs=[spec(c, dk), spec(c, dk), spec(dk, c), spec(c, dv),
                  spec(c, 1), spec(1, c), spec(c, 1), state],
        out_specs=[spec(c, dv), state],
        out_shape=[jax.ShapeDtypeStruct((bsz, n_heads, nc, c, dv), v.dtype),
                   jax.ShapeDtypeStruct((bsz, n_heads, dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="strom_gdn_scan",
        interpret=_interpret(interpret),
    )(qc, kc, ktc, vc, g_col, g_row, bc, s0.astype(f32))
    o = o.transpose(0, 2, 3, 1, 4).reshape(bsz, nc * c, n_heads, dv)
    return o[:, :m], s

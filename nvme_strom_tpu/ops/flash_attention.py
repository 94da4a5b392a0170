"""Pallas flash attention — the flagship model's hot op, TPU-native.

The reference has no compute kernels at all (it is a storage engine,
SURVEY.md §1); its consumer, PG-Strom, runs CUDA kernels over the DMA'd
data (SURVEY.md §3.5).  This module is that consumer-side analogue for the
TPU build: a fused, tiled, online-softmax attention kernel so the model
exercising the NVMe→HBM data path never materialises the (s, s) score
matrix in HBM.

Design (classic FlashAttention, re-tiled for the TPU memory hierarchy):

- forward: grid over (batch, head, q-block); K/V for the head live in VMEM
  and the kernel walks k-blocks with a ``fori_loop`` whose trip count is
  causally bounded (later q-blocks do more work; earlier ones skip their
  masked-out tail entirely).  Running max/denominator (m, l) keep the
  softmax numerically exact; accumulation is fp32 regardless of input
  dtype; the log-sum-exp per row is written out as a residual.
- backward: two kernels recompute probabilities blockwise from the saved
  lse (no s×s residual): one accumulates dQ over k-blocks, the other
  dK/dV over q-blocks.  Wrapped in ``jax.custom_vjp``.
- CPU (tests, virtual meshes) runs the same kernels in interpreter mode —
  selected automatically from the default backend.

VMEM sizing: one head's K and V (s × head_dim each) must fit in VMEM,
which holds to s ≈ 16k at head_dim 128 in bf16.  Beyond that, shard the
sequence with ring attention (parallel/ring_attention.py), which can run
this kernel as its per-block inner via ``flash_attention_lse``: the
(out, lse) pair is differentiable — the LSE cotangent folds into the
existing backward kernels as ``delta_eff = delta - dlse`` (the score
gradient is ``ds = p·(dp - delta + dlse)·scale``), so the ring's
LSE-weighted block combine trains end-to-end with no extra kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_NEG_INF = -1e30


def _vma(*arrays):
    """Union of the inputs' varying-mesh-axes sets, so pallas_call
    out_shapes type-check under shard_map's VMA system (outside a manual
    context this is the empty set and has no effect)."""
    out = frozenset()
    for a in arrays:
        out |= getattr(jax.typeof(a), "vma", frozenset())
    return out


def _struct(shape, dtype, vma):
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _pick_block(seq: int, want: int) -> int:
    """Largest divisor of ``seq`` that is <= want (block shapes must tile
    the sequence exactly)."""
    b = min(want, seq)
    while seq % b:
        b -= 1
    return b


# ----------------------------- forward -----------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                scale, block_q, block_k, causal, kv_seq):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, d)
    d = q.shape[-1]

    m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)

    if causal:
        # k-blocks strictly after this q-block's last row are fully masked
        n_kb = ((qi + 1) * block_q + block_k - 1) // block_k
    else:
        n_kb = kv_seq // block_k

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, n_kb, body, (m0, l0, acc0))
    o_ref[0, 0] = (acc / l[:, None]).astype(o_ref.dtype)
    # lse carried 4D with a trailing singleton: TPU block tiling requires
    # the last two block dims divisible by (8, 128) or equal to the array
    # dims — (block_q, 1) satisfies that where (1, 1, block_q) cannot.
    lse_ref[0, 0, :, 0] = m + jnp.log(l)


def _fwd(q, k, v, scale, block_q, block_k, causal, interpret):
    b, h, s, d = q.shape
    skv = k.shape[2]                 # may differ from s when non-causal
    grid = (b, h, s // block_q)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal, kv_seq=skv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, skv, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, skv, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_shape=[
            _struct(q.shape, q.dtype, _vma(q, k, v)),
            _struct((b, h, s, 1), jnp.float32, _vma(q, k, v)),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse


# ----------------------------- backward -----------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               scale, block_q, block_k, causal, kv_seq):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)                  # (bq, d)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0, :, 0]                            # (bq,)
    delta = delta_ref[0, 0, :, 0]
    d = q.shape[-1]

    n_kb = (((qi + 1) * block_q + block_k - 1) // block_k) if causal \
        else kv_seq // block_k

    def body(i, dq):
        k = k_ref[0, 0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])                    # exact probs
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, n_kb, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, scale, block_q, block_k, causal, seq):
    ki = pl.program_id(2)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)
    d = k.shape[-1]
    n_qb = seq // block_q
    q_start = (ki * block_k) // block_q if causal else 0

    def body(j, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(j * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, 0, pl.ds(j * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(j * block_q, block_q), 0]
        delta = delta_ref[0, 0, pl.ds(j * block_q, block_q), 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = j * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])                    # (bq, bk)
        dv_new = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_new, dv_new

    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(q_start, n_qb, body, (dk0, dv0))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _bwd_pallas(scale, block_q, block_k, causal, interpret,
                q, k, v, lse, dout, delta):
    """Shared backward: ``delta`` is (b, h, s, 1) fp32.  For the plain
    output VJP it is Σ_d do·o; when an LSE cotangent exists it is
    Σ_d do·o − dlse (the dlse term enters ds with the opposite sign of
    delta, so folding it here reuses both kernels unchanged)."""
    b, h, s, d = q.shape
    skv = k.shape[2]
    kw = dict(scale=scale, block_q=block_q, block_k=block_k, causal=causal)
    blk_q = lambda bi, hi, qi: (bi, hi, qi, 0)       # noqa: E731
    full = lambda bi, hi, qi: (bi, hi, 0, 0)         # noqa: E731

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, kv_seq=skv, **kw),
        grid=(b, h, s // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), blk_q),
            pl.BlockSpec((1, 1, skv, d), full),
            pl.BlockSpec((1, 1, skv, d), full),
            pl.BlockSpec((1, 1, block_q, d), blk_q),
            pl.BlockSpec((1, 1, block_q, 1), blk_q),
            pl.BlockSpec((1, 1, block_q, 1), blk_q),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), blk_q),
        out_shape=_struct(q.shape, q.dtype, _vma(q, k, v, dout, lse, delta)),
        interpret=interpret,
    )(q, k, v, dout, lse, delta)

    blk_k = lambda bi, hi, ki: (bi, hi, ki, 0)       # noqa: E731
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, seq=s, **kw),
        grid=(b, h, skv // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, s, d), full),
            pl.BlockSpec((1, 1, block_k, d), blk_k),
            pl.BlockSpec((1, 1, block_k, d), blk_k),
            pl.BlockSpec((1, 1, s, d), full),
            pl.BlockSpec((1, 1, s, 1), full),
            pl.BlockSpec((1, 1, s, 1), full),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), blk_k),
            pl.BlockSpec((1, 1, block_k, d), blk_k),
        ],
        out_shape=[
            _struct(k.shape, k.dtype, _vma(q, k, v, dout, lse, delta)),
            _struct(v.shape, v.dtype, _vma(q, k, v, dout, lse, delta)),
        ],
        interpret=interpret,
    )(q, k, v, dout, lse, delta)
    return dq, dk, dv


# ----------------------------- public API -----------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, scale, block_q, block_k, causal, interpret):
    out, lse = _fwd(q, k, v, scale, block_q, block_k, causal, interpret)
    return out, lse[..., 0]


def _flash_lse_fwd(q, k, v, scale, block_q, block_k, causal, interpret):
    out, lse = _fwd(q, k, v, scale, block_q, block_k, causal, interpret)
    return (out, lse[..., 0]), (q, k, v, out, lse)


def _flash_lse_bwd(scale, block_q, block_k, causal, interpret, res, cts):
    q, k, v, out, lse = res
    dout, dlse = cts
    delta = (jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                     axis=-1, keepdims=True)
             - dlse.astype(jnp.float32)[..., None])      # (b, h, s, 1)
    return _bwd_pallas(scale, block_q, block_k, causal, interpret,
                       q, k, v, lse, dout, delta)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention(q, k, v, *, causal: bool = True, scale: float = None,
                    block_q: int = None, block_k: int = None,
                    interpret: bool = None):
    """Fused attention over (batch, heads, seq, head_dim) tensors.

    Differentiable (custom VJP with blockwise-recompute backward).
    ``interpret`` defaults to True off-TPU so CPU tests and virtual meshes
    run the identical kernel in the Pallas interpreter.

    ``block_q``/``block_k`` default to 128; either way the block used is
    the largest divisor of its sequence no larger (``_pick_block``).

    K/V may have a different sequence length than Q when ``causal=False``
    (blockwise/ring combines, cross-attention); causal masking assumes
    aligned positions and therefore requires equal lengths.
    """
    out, _ = _flash_lse(q, k, v, *_prep(q, k, causal, scale, block_q,
                                        block_k, interpret))
    return out


def _prep(q, k, causal, scale, block_q, block_k, interpret):
    """Shared argument normalisation: returns the static tail
    (scale, block_q, block_k, causal, interpret) for ``_flash_lse``."""
    if q.ndim != 4:
        raise ValueError(f"expected (b, h, s, d), got {q.shape}")
    s, skv = q.shape[2], k.shape[2]
    if causal and skv != s:
        raise ValueError(
            f"causal attention requires equal q/kv lengths, got {s} vs "
            f"{skv} (position alignment is ambiguous otherwise)")
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return (float(scale),
            _pick_block(s, 128 if block_q is None else block_q),
            _pick_block(skv, 128 if block_k is None else block_k),
            bool(causal), bool(interpret))


def flash_attention_lse(q, k, v, *, causal: bool = True, scale: float = None,
                        block_q: int = None, block_k: int = None,
                        interpret: bool = None):
    """Like :func:`flash_attention` but also returns the per-row
    log-sum-exp, shape (b, h, s) fp32 — the residual a blockwise combine
    needs (ring attention weights per-block outputs by LSE).  The pair is
    differentiable: cotangents on BOTH outputs flow through the shared
    backward kernels.
    """
    return _flash_lse(q, k, v, *_prep(q, k, causal, scale, block_q,
                                      block_k, interpret))


def make_flash_attn(causal: bool = True, **kw):
    """attn_fn for models.transformer.forward — drop-in replacement for
    dense_causal_attention with O(s) memory."""
    return functools.partial(flash_attention, causal=causal, **kw)

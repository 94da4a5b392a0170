"""Ring attention: sequence/context parallelism over an ``sp`` mesh axis.

Long-context support the TPU way: the sequence dimension is sharded across
devices, each holding one block of Q/K/V, and K/V blocks rotate around the
ring with ``lax.ppermute`` (ICI neighbor exchanges — the collective pattern
XLA maps to the torus) while each device accumulates its block's attention
output with a numerically-stable online softmax (flash-attention style
m/l/o accumulation).  Peak memory per device is O(s_local²) per block pair
instead of O(s²), and the rotation overlaps with the block matmuls.

The reference has no model or parallelism concepts at all (SURVEY.md §2
"Parallelism strategies: NOT PRESENT") — this module exists because
long-context sequence parallelism is a first-class requirement of the TPU
framework build, exercised by the flagship transformer
(models/transformer.py) and the driver's multi-chip dry run.

Math note: per ring step t, device i holds K/V block j = (i - t) mod n.
Causality admits j < i fully, j == i with the in-block causal mask, and
j > i not at all; masking is done in the score domain with a large negative
and re-applied to the probabilities so fully-masked blocks contribute
exactly zero.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from nvme_strom_tpu.models.transformer import pv_apply, qk_scores

_NEG = -1e30  # mask value: finite so exp() underflows instead of NaN-ing


def _to_varying(x, axis_names: tuple):
    return jax.lax.pcast(x, axis_names, to="varying")


def _ring_block(q, k, v, axis_name: str, n_sp: int, causal: bool,
                mesh_axes: tuple = ()):
    """Per-device computation. q/k/v: (b, h, s_blk, d) local blocks."""
    b, h, s_blk, d = q.shape
    idx = jax.lax.axis_index(axis_name)
    scale = 1.0 / np.sqrt(d)
    q_pos = idx * s_blk + jnp.arange(s_blk)

    m0 = jnp.full((b, h, s_blk), _NEG, jnp.float32)
    l0 = jnp.zeros((b, h, s_blk), jnp.float32)
    o0 = jnp.zeros((b, h, s_blk, d), jnp.float32)
    # The loop carry becomes varying over every manual mesh axis (it mixes
    # with q/k/v, which are), so the invariant initial values must be cast
    # to varying for shard_map's VMA type system.
    vary = tuple(mesh_axes) or (axis_name,)
    m0, l0, o0 = (_to_varying(x, vary) for x in (m0, l0, o0))
    perm = [(i, (i + 1) % n_sp) for i in range(n_sp)]

    def body(t, carry):
        k_t, v_t, m, l, o = carry
        j = (idx - t) % n_sp
        # The attention precision gates (models/transformer.qk_scores /
        # pv_apply): matmul inputs stay in the activation dtype (bf16
        # on TPU → MXU) with f32 accumulation, and the BACKWARD matmuls
        # do too — plain autodiff kept the f32 scores/output cotangents
        # and promoted q/k/v, so the ring's backward dots lowered
        # f32×f32 (the round-4 rms_norm promotion bug's sibling; the
        # dot census counted 8 in the sp train step).
        s = qk_scores(q, k_t) * scale
        if causal:
            kv_pos = j * s_blk + jnp.arange(s_blk)
            mask = kv_pos[None, :] <= q_pos[:, None]
            s = jnp.where(mask, s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        if causal:
            p = jnp.where(mask, p, 0.0)  # fully-masked rows: exactly zero
        correction = jnp.exp(m - m_new)
        l = l * correction + p.sum(-1)
        # pv_apply downcasts the f32 probs to V's dtype internally for
        # the MXU matmul; its dp cotangent stays f32 for the exp VJP.
        o = o * correction[..., None] + pv_apply(p, v_t)
        # Rotate K/V to the next device (skippable on the last step, but a
        # uniform body keeps the loop fusible).
        k_t = jax.lax.ppermute(k_t, axis_name, perm)
        v_t = jax.lax.ppermute(v_t, axis_name, perm)
        return k_t, v_t, m_new, l, o

    _, _, _, l, o = jax.lax.fori_loop(0, n_sp, body, (k, v, m0, l0, o0))
    return (o / l[..., None]).astype(q.dtype)


def _ring_block_flash(q, k, v, axis_name: str, n_sp: int, causal: bool,
                      mesh_axes: tuple = (), block_q: int = 128,
                      block_k: int = 128):
    """Per-device ring step with the Pallas flash kernel as the inner.

    Each rotation runs ``flash_attention_lse`` on (local Q, visiting K/V
    block) and merges the per-block (out, lse) pairs with the stable
    LSE-weighted combine:  m' = max(m, lse_j);  num' = num·e^{m−m'} +
    o_j·e^{lse_j−m'};  den' likewise.  Fully-masked blocks (j > i under
    causality) skip the kernel entirely via ``lax.cond`` and contribute
    lse = −1e30, whose weight underflows to exactly 0 once any real
    block has been merged (every device merges its own diagonal block,
    so the final denominator is always positive).  Training
    differentiates through the combine into the kernel's (out, lse) VJP.
    """
    from nvme_strom_tpu.ops.flash_attention import flash_attention_lse

    b, h, s_blk, d = q.shape
    idx = jax.lax.axis_index(axis_name)
    vary = tuple(mesh_axes) or (axis_name,)

    m0 = jnp.full((b, h, s_blk), _NEG, jnp.float32)
    den0 = jnp.zeros((b, h, s_blk), jnp.float32)
    num0 = jnp.zeros((b, h, s_blk, d), jnp.float32)
    m0, den0, num0 = (_to_varying(x, vary) for x in (m0, den0, num0))
    perm = [(i, (i + 1) % n_sp) for i in range(n_sp)]
    kw = dict(block_q=block_q, block_k=block_k)

    def _diag(op):
        qq, kk, vv = op
        return flash_attention_lse(qq, kk, vv, causal=True, **kw)

    def _full(op):
        qq, kk, vv = op
        return flash_attention_lse(qq, kk, vv, causal=False, **kw)

    def _skip(op):
        qq = op[0]
        o = _to_varying(jnp.zeros(qq.shape, qq.dtype), vary)
        lse = _to_varying(jnp.full((b, h, s_blk), _NEG, jnp.float32), vary)
        return o, lse

    def body(t, carry):
        k_t, v_t, m, den, num = carry
        j = (idx - t) % n_sp
        op = (q, k_t, v_t)
        if causal:
            o_j, lse_j = jax.lax.cond(
                j == idx, _diag,
                lambda o: jax.lax.cond(j < idx, _full, _skip, o), op)
        else:
            o_j, lse_j = _full(op)
        m_new = jnp.maximum(m, lse_j)
        c = jnp.exp(m - m_new)
        w = jnp.exp(lse_j - m_new)
        den = den * c + w
        num = num * c[..., None] + w[..., None] * o_j.astype(jnp.float32)
        k_t = jax.lax.ppermute(k_t, axis_name, perm)
        v_t = jax.lax.ppermute(v_t, axis_name, perm)
        return k_t, v_t, m_new, den, num

    _, _, _, den, num = jax.lax.fori_loop(0, n_sp, body,
                                          (k, v, m0, den0, num0))
    return (num / den[..., None]).astype(q.dtype)


def ring_attention(q, k, v, mesh, sp_axis: str = "sp",
                   dp_axis: str = "dp", tp_axis: str = "tp",
                   causal: bool = True, inner: str = "dense",
                   **inner_kw):
    """Causal attention with the sequence dim sharded over ``sp_axis``.

    q/k/v: (batch, heads, seq, head_dim) global arrays — batch sharded over
    ``dp_axis`` (if present in the mesh), heads over ``tp_axis`` (if
    present), seq over ``sp_axis``.  K/V must already be GQA-expanded to
    the same head count as Q.  Returns the same layout as q.

    ``inner`` selects the per-block computation: ``"dense"`` (jnp block
    math, materialises one (s_local, s_local) score block at a time) or
    ``"flash"`` (the Pallas kernel via ``flash_attention_lse`` — O(block)
    memory inside each ring step, the right choice once s_local is large
    enough that a score block hurts; extra ``block_q``/``block_k`` kwargs
    pass through to the kernel).
    """
    n_sp = mesh.shape[sp_axis]
    dp = dp_axis if dp_axis in mesh.shape else None
    tp = tp_axis if tp_axis in mesh.shape else None
    spec = P(dp, tp, sp_axis, None)

    if inner == "dense":
        block_fn = _ring_block
    elif inner == "flash":
        block_fn = _ring_block_flash
    else:
        raise ValueError(f"inner must be 'dense' or 'flash', got {inner!r}")

    manual = tuple(a for a in (dp, tp, sp_axis) if a is not None)
    # Interpret-mode pallas (CPU tests) mixes varying refs with invariant
    # slice indices, which the VMA checker rejects (jax suggests exactly
    # this workaround); the dense inner keeps the check.
    extra = {"check_vma": False} if inner == "flash" else {}
    fn = jax.shard_map(
        partial(block_fn, axis_name=sp_axis, n_sp=n_sp, causal=causal,
                mesh_axes=manual, **inner_kw),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, **extra)
    return fn(q, k, v)


def make_ring_attn(mesh, sp_axis: str = "sp", dp_axis: str = "dp",
                   tp_axis: str = "tp", inner: str = "dense", **inner_kw):
    """attn_fn(q, k, v) -> out for models/transformer.forward(...,
    attn_fn=...): the drop-in sequence-parallel replacement for the dense
    softmax(QKᵀ)V block."""

    def attn_fn(q, k, v):
        return ring_attention(q, k, v, mesh, sp_axis=sp_axis,
                              dp_axis=dp_axis, tp_axis=tp_axis,
                              inner=inner, **inner_kw)

    return attn_fn

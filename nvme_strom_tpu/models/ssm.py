"""The recurrent mixers of a hybrid decoder — Mamba-2, the gated short
convolution and the gated delta rule — and the state a sequence carries
through them.

Mamba-2:

    [z, u, dt] = split(h W_in)             widths inner | inner + 2N | H
    u_t  = silu(Σ_j w_conv[j] · u_{t-K+1+j} + b_conv)      depthwise, causal
    [x, B, C] = split(u_t)                 x: (H, P);  B, C: (N,), one group
    Δ_t  = softplus(dt_t + dt_bias)        A = −exp(A_log)        (per head)
    S_t  = exp(Δ_t A) · S_{t−1} + Δ_t · x_t ⊗ B_t          S: (H, P, N) f32,
    y_t  = S_t C_t + D ⊙ x_t                   kept as (H/g, N, g·P): ops/ssm.py
    out  = RMSNorm(y ⊙ silu(z); g_norm over all of inner) W_out

The gated short convolution (``layer_kinds`` "conv"; the LFM2 family's
operator, ``conv_block`` / ``conv_step`` at the end of this file):

    [B, C, u] = split(h W_in)              three times d_model, in that order
    v_t  = B_t ⊙ u_t
    c_t  = Σ_j w[j] ⊙ v_{t-K+1+j}          depthwise, causal, K = conv_taps,
                                           no bias, no activation
    out  = (C ⊙ c) W_out

The gated delta rule (``layer_kinds`` "gdn"; the Qwen3-Next family's linear
layer, ``gdn_block`` / ``gdn_step`` at the end of this file): Hk key heads
and Hv value heads, value head j reading key head j // (Hv / Hk):

    [q, k, v, z] = split(h W_in)           widths Hk·dk | Hk·dk | Hv·dv | Hv·dv
    [b, a] = split(h W_ba)                 Hv | Hv
    [q, k, v]_t = silu(Σ_j w_conv[j] ⊙ [q, k, v]_{t-K+1+j})   depthwise,
                                           causal, no bias
    q̃ = q / sqrt(Σq² + 1e-6) / sqrt(dk)   k̃ = k / sqrt(Σk² + 1e-6)   per head
    β_t = sigmoid(b_t), or 2·sigmoid(b_t) with ``gdn_neg_eigval``
    α_t = exp(−exp(A_log) · softplus(a_t + dt_bias))
    S ← α_t S;  u = β_t (v_t − Sᵀ k̃_t);  S ← S + k̃_t ⊗ u;  o_t = Sᵀ q̃_t
                                           S: (Hv, dk, dv) float32, kept as
                                           (Hv/g, dk, g·dv): ops/gdn.py
    out  = (g_norm ⊙ o / sqrt(mean o² + eps) ⊙ silu(z)) W_out   per head

What a sequence carries from one call to the next is, for Mamba-2 and the
delta rule, ``S`` and the last K−1 rows of what their conv sees (``u``; q | k
| v); for the short conv the last K−1 rows of ``v``.  ``init_state`` holds
every kind: ``"s"`` one array per Mamba-2 or delta-rule layer, ``"conv"``
one tail per recurrent layer of any kind, in layer order.  ``mamba_block``
runs a block of rows (prefill, a full forward) through
``ops.ssm.ssm_scan``; ``mamba_step`` runs one token of every serving slot
through ``ops.ssm.ssm_update`` against the server's state pool
(``gdn_block`` / ``gdn_step``: ``ops.gdn.gdn_scan`` / ``gdn_update``).  Parameter leaves of layer ``L``: ``ssm_in`` (d, 2·inner
+ 2N + H), ``ssm_conv_w`` (K, inner + 2N), ``ssm_conv_b``, ``ssm_dt_bias``,
``ssm_A_log``, ``ssm_D`` (H,), ``ssm_norm`` (inner,), ``ssm_out`` (inner, d);
of a delta-rule layer ``gdn_in`` (d, 2·Hk·dk + 2·Hv·dv), ``gdn_ba`` (d, 2·Hv),
``gdn_conv_w`` (K, 2·Hk·dk + Hv·dv), ``gdn_dt_bias``, ``gdn_A_log`` (Hv,),
``gdn_norm`` (dv,), ``gdn_out`` (Hv·dv, d).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from nvme_strom_tpu.models.transformer import (TransformerConfig, rms_norm,
                                               valid_rows, wmat)
from nvme_strom_tpu.ops import gdn as _gdn
from nvme_strom_tpu.ops.gdn import gdn_scan, gdn_update
from nvme_strom_tpu.ops.ssm import pool_shape, ssm_scan, ssm_update


def init_mamba_params(keys, cfg: TransformerConfig, L: str, dense) -> Dict:
    """Mamba-2's own initialisation: A in [1, 16], Δ's bias the inverse
    softplus of a log-uniform step in [1e-3, 1e-1], D = 1."""
    d, inner, H = cfg.d_model, cfg.ssm_inner, cfg.ssm_heads
    conv = cfg.ssm_conv_dim
    dt = jnp.exp(jax.random.uniform(next(keys), (H,), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    return {
        L + "ssm_in": dense(next(keys), d, (d, 2 * inner
                                            + 2 * cfg.ssm_state + H)),
        L + "ssm_conv_w": dense(next(keys), cfg.ssm_conv,
                                (cfg.ssm_conv, conv)),
        L + "ssm_conv_b": jnp.zeros((conv,), jnp.float32),
        L + "ssm_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        L + "ssm_A_log": jnp.log(jax.random.uniform(
            next(keys), (H,), jnp.float32, 1.0, 16.0)),
        L + "ssm_D": jnp.ones((H,), jnp.float32),
        L + "ssm_norm": jnp.ones((inner,), jnp.float32),
        L + "ssm_out": dense(next(keys), inner, (inner, d)),
    }


def init_state(cfg: TransformerConfig, rows: int) -> Dict:
    """Zeroed recurrent state for ``rows`` sequences, whatever each mixer
    declares per sequence.  Under ``"s"`` one state per layer that keeps one
    (``cfg.state_layers``), float32: (rows, H/g, N, g·P) for Mamba-2 —
    state-major, the g = ``ops.ssm.heads_per_lane_row`` heads that fill the
    128 lanes side by side (two of 64; one of 128 or more), the form both
    of its kernels and the server's scatter take it in —, (rows, Hv/g, dk,
    g·dv) for the delta rule, g = ``ops.gdn.heads_per_lane_row`` heads side
    by side where dv alone is no multiple of 128 (two of 192; one of 128).
    Under ``"conv"`` one conv tail per
    recurrent layer of any kind: (rows, K−1, inner + 2N) for Mamba-2, (rows,
    gdn_conv − 1, 2·Hk·dk + Hv·dv) for the delta rule, (rows, conv_taps − 1,
    d_model) for the short conv.  Both in layer order.  Tuples of per-layer
    arrays, never one stacked array: each is donated to the step and updated
    in place, and indexing a stacked one by layer would copy the lot (the KV
    pool's copies in PERF.md §5)."""
    s = {"gdn": _gdn.pool_shape(rows, cfg.gdn_v_heads, cfg.gdn_k_dim,
                                cfg.gdn_v_dim)}
    if cfg.mamba_layers:                   # a head width to pack by
        s["mamba"] = pool_shape(rows, cfg.ssm_heads, cfg.ssm_head_dim,
                                cfg.ssm_state)
    tail = {"mamba": (rows, cfg.ssm_conv - 1, cfg.ssm_conv_dim),
            "gdn": (rows, cfg.gdn_conv - 1, cfg.gdn_conv_dim),
            "conv": (rows, cfg.conv_taps - 1, cfg.d_model)}
    return {"s": tuple(jnp.zeros(s[cfg.mixer(i)], jnp.float32)
                       for i in cfg.state_layers),
            "conv": tuple(jnp.zeros(tail[cfg.mixer(i)], cfg.dtype)
                          for i in cfg.recurrent_layers)}


def _tail_at(window, n_valid, k1: int):
    """What each sequence carries out of a right-padded block: rows
    ``n_valid[i] .. n_valid[i] + K - 2`` of its ``window`` (b, K - 1 + m, C)
    — window row n_valid + j is the block's row n_valid - (K - 1) + j, so
    these are its last K - 1 valid rows (the tail it came in with where the
    block holds fewer).  ``n_valid`` () or (b,)."""
    starts = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32),
                              window.shape[:1])
    return jax.vmap(lambda w, n: jax.lax.dynamic_slice_in_dim(
        w, n, k1, axis=0))(window, starts)


def _project_in(h, p, L, cfg):
    inner = cfg.ssm_inner
    zu = h @ wmat(p, L + "ssm_in", h.dtype)
    return (zu[..., :inner], zu[..., inner:inner + cfg.ssm_conv_dim],
            zu[..., inner + cfg.ssm_conv_dim:])


def _split_conv(u, cfg):
    """conv output (..., inner + 2N) → x (..., H, P), B, C (..., N)."""
    inner, n = cfg.ssm_inner, cfg.ssm_state
    x = u[..., :inner].reshape(*u.shape[:-1], cfg.ssm_heads, cfg.ssm_head_dim)
    return x, u[..., inner:inner + n], u[..., inner + n:]


def _delta(dt, p, L):
    return jax.nn.softplus(dt.astype(jnp.float32)
                           + p[L + "ssm_dt_bias"].astype(jnp.float32))


def _project_out(y, x, z, p, L, cfg):
    """y (..., H, P) float32 from the recurrence → the mixer's output."""
    y = y + p[L + "ssm_D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    y = y.reshape(*y.shape[:-2], cfg.ssm_inner)
    zf = z.astype(jnp.float32)
    y = (y * (zf * jax.nn.sigmoid(zf))).astype(z.dtype)
    y = rms_norm(y, p[L + "ssm_norm"], cfg.norm_eps)
    return y @ wmat(p, L + "ssm_out", y.dtype)


def mamba_block(h, p: Dict, L: str, cfg: TransformerConfig, s0=None,
                tail=None, n_valid=None):
    """A block of rows through the mixer.

    h (b, m, d) post-norm; s0 (b, H/g, N, g·P) float32, ``init_state``'s
    form, and tail (b, K−1, inner + 2N): what the sequences carried in
    (None: nothing yet, zeros);
    n_valid, () or one count a sequence (b,): rows past it are right padding
    — they leave state and tail as the last valid row left them (a sequence
    with none keeps what it came in with).  Returns (out (b, m, d), S,
    tail)."""
    b, m, _ = h.shape
    k1 = cfg.ssm_conv - 1
    with jax.named_scope("strom.ssm.proj"):
        z, u, dt = _project_in(h, p, L, cfg)
        if tail is None:
            tail = jnp.zeros((b, k1, cfg.ssm_conv_dim), u.dtype)
        if s0 is None:
            s0 = jnp.zeros(pool_shape(b, cfg.ssm_heads, cfg.ssm_head_dim,
                                      cfg.ssm_state), jnp.float32)
        window = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
        w = p[L + "ssm_conv_w"].astype(jnp.float32)
        conv = sum(w[j] * window[:, j:j + m].astype(jnp.float32)
                   for j in range(cfg.ssm_conv))
        conv = conv + p[L + "ssm_conv_b"].astype(jnp.float32)
        x, bm, cm = _split_conv(jax.nn.silu(conv).astype(u.dtype), cfg)
        valid = None
        if n_valid is None:
            new_tail = window[:, m:]
        else:
            valid = valid_rows(n_valid, b, m)
            new_tail = _tail_at(window, n_valid, k1)
        a = -jnp.exp(p[L + "ssm_A_log"].astype(jnp.float32))
    with jax.named_scope("strom.ssm.scan"):
        y, s = ssm_scan(x, _delta(dt, p, L), a, bm, cm, s0, valid,
                        chunk=cfg.ssm_chunk)
    with jax.named_scope("strom.ssm.out"):
        out = _project_out(y.astype(jnp.float32), x, z, p, L, cfg)
    return out, s, new_tail


def mamba_step(h, p: Dict, L: str, cfg: TransformerConfig, s_pool,
               tail_pool, sidx):
    """One token of every slot through the mixer, against the server's
    pools.  h (B, 1, d); s_pool (rows, H/g, N, g·P) float32 and tail_pool
    (rows, K−1, inner + 2N), both updated in place when donated; sidx (B,)
    each slot's row.  Returns (out (B, 1, d), s_pool, tail_pool)."""
    with jax.named_scope("strom.ssm.proj"):
        z, u, dt = _project_in(h[:, 0], p, L, cfg)
        window = jnp.concatenate(
            [tail_pool[sidx].astype(u.dtype), u[:, None]], axis=1)  # (B,K,C)
        tail_pool = tail_pool.at[sidx].set(
            window[:, 1:].astype(tail_pool.dtype))
        w = p[L + "ssm_conv_w"].astype(jnp.float32)
        conv = (jnp.einsum("bkc,kc->bc", window.astype(jnp.float32), w)
                + p[L + "ssm_conv_b"].astype(jnp.float32))
        x, bv, cv = _split_conv(jax.nn.silu(conv).astype(u.dtype), cfg)
        a = -jnp.exp(p[L + "ssm_A_log"].astype(jnp.float32))
    with jax.named_scope("strom.ssm.update"):
        y, s_pool = ssm_update(s_pool, sidx, x, _delta(dt, p, L), a, bv, cv)
    with jax.named_scope("strom.ssm.out"):
        out = _project_out(y, x, z, p, L, cfg)[:, None]
    return out, s_pool, tail_pool


# ------------------------------------------------- the gated short conv

def init_conv_params(keys, cfg: TransformerConfig, L: str, dense) -> Dict:
    d = cfg.d_model
    return {L + "conv_in": dense(next(keys), d, (d, 3 * d)),
            L + "conv_w": dense(next(keys), cfg.conv_taps,
                                (cfg.conv_taps, d)),
            L + "conv_out": dense(next(keys), d, (d, d))}


def _gates(h, p, L):
    """h (..., d) -> (C, v = B ⊙ u), each (..., d)."""
    bcu = h @ wmat(p, L + "conv_in", h.dtype)
    b, c, u = jnp.split(bcu, 3, axis=-1)
    return c, b * u


def conv_block(h, p: Dict, L: str, cfg: TransformerConfig, tail=None,
               n_valid=None):
    """A block of rows through the short-conv mixer.  h (b, m, d)
    post-norm; tail (b, K−1, d): the rows of ``v`` the sequences carried in
    (None: zeros); n_valid as in ``mamba_block``.  Returns (out (b, m, d),
    tail)."""
    b, m, d = h.shape
    k1 = cfg.conv_taps - 1
    with jax.named_scope("strom.conv"):
        c, v = _gates(h, p, L)
        if tail is None:
            tail = jnp.zeros((b, k1, d), v.dtype)
        window = jnp.concatenate([tail.astype(v.dtype), v], axis=1)
        w = p[L + "conv_w"].astype(jnp.float32)
        conv = sum(w[j] * window[:, j:j + m].astype(jnp.float32)
                   for j in range(cfg.conv_taps))
        if n_valid is None:
            new_tail = window[:, m:]
        else:
            new_tail = _tail_at(window, n_valid, k1)
        y = (c * conv.astype(c.dtype)) @ wmat(p, L + "conv_out", c.dtype)
    return y, new_tail


def conv_step(h, p: Dict, L: str, cfg: TransformerConfig, tail_pool, sidx):
    """One token of every slot through the short-conv mixer, against the
    server's tail pool (rows, K−1, d), updated in place when donated; sidx
    (B,) each slot's row.  Returns (out (B, 1, d), tail_pool)."""
    with jax.named_scope("strom.conv"):
        c, v = _gates(h[:, 0], p, L)
        window = jnp.concatenate(
            [tail_pool[sidx].astype(v.dtype), v[:, None]], axis=1)  # (B,K,d)
        tail_pool = tail_pool.at[sidx].set(
            window[:, 1:].astype(tail_pool.dtype))
        conv = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                          p[L + "conv_w"].astype(jnp.float32))
        y = (c * conv.astype(c.dtype)) @ wmat(p, L + "conv_out", c.dtype)
        y = y[:, None]
    return y, tail_pool


# -------------------------------------------------- the gated delta rule

def init_gdn_params(keys, cfg: TransformerConfig, L: str, dense) -> Dict:
    """A and Δ's bias as Mamba-2 initialises them (``init_mamba_params``)."""
    d, H = cfg.d_model, cfg.gdn_v_heads
    conv, value = cfg.gdn_conv_dim, cfg.gdn_v_heads * cfg.gdn_v_dim
    dt = jnp.exp(jax.random.uniform(next(keys), (H,), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    return {
        L + "gdn_in": dense(next(keys), d, (d, conv + value)),
        L + "gdn_ba": dense(next(keys), d, (d, 2 * H)),
        L + "gdn_conv_w": dense(next(keys), cfg.gdn_conv,
                                (cfg.gdn_conv, conv)),
        L + "gdn_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        L + "gdn_A_log": jnp.log(jax.random.uniform(
            next(keys), (H,), jnp.float32, 1.0, 16.0)),
        L + "gdn_norm": jnp.ones((cfg.gdn_v_dim,), jnp.float32),
        L + "gdn_out": dense(next(keys), value, (value, d)),
    }


def _gdn_project(h, p, L, cfg):
    """h (..., d) -> (u (..., conv) what the conv sees: q | k | v, z (...,
    Hv, dv), β and log α (..., Hv) float32)."""
    conv = cfg.gdn_conv_dim
    uz = h @ wmat(p, L + "gdn_in", h.dtype)
    ba = (h @ wmat(p, L + "gdn_ba", h.dtype)).astype(jnp.float32)
    H = cfg.gdn_v_heads
    beta = jax.nn.sigmoid(ba[..., :H])
    if cfg.gdn_neg_eigval:
        beta = 2.0 * beta
    # the decay by its logarithm: a head that forgets at once has α = 0 in
    # float32, and the scan works in differences of log α
    log_alpha = -jnp.exp(p[L + "gdn_A_log"].astype(jnp.float32)) \
        * jax.nn.softplus(ba[..., H:]
                          + p[L + "gdn_dt_bias"].astype(jnp.float32))
    z = uz[..., conv:].reshape(*uz.shape[:-1], H, cfg.gdn_v_dim)
    return uz[..., :conv], z, beta, log_alpha


def _gdn_heads(u, cfg):
    """conv output (..., conv) float32, after its silu -> q̃, k̃ (..., Hv,
    dk) — L2-normalised per KEY head, q scaled, each repeated over the value
    heads that read it — and v (..., Hv, dv), all float32."""
    hk, dk = cfg.gdn_k_heads, cfg.gdn_k_dim
    key = hk * dk
    lead = u.shape[:-1]

    def unit(t):
        t = t.reshape(*lead, hk, dk)
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    rep = cfg.gdn_v_heads // hk
    q = jnp.repeat(unit(u[..., :key]) * dk ** -0.5, rep, axis=-2)
    k = jnp.repeat(unit(u[..., key:2 * key]), rep, axis=-2)
    v = u[..., 2 * key:].reshape(*lead, cfg.gdn_v_heads, cfg.gdn_v_dim)
    return q, k, v


def _gdn_out(o, z, p, L, cfg):
    """o (..., Hv, dv) from the recurrence -> the mixer's output: the
    per-head RMS norm (its weight as stored), silu(z)'s gate, W_out."""
    zf = z.astype(jnp.float32)
    y = rms_norm(o.astype(jnp.float32), p[L + "gdn_norm"], cfg.norm_eps)
    y = (y * (zf * jax.nn.sigmoid(zf))).astype(z.dtype)
    y = y.reshape(*y.shape[:-2], -1)
    return y @ wmat(p, L + "gdn_out", y.dtype)


def gdn_block(h, p: Dict, L: str, cfg: TransformerConfig, s0=None,
              tail=None, n_valid=None):
    """A block of rows through the delta-rule mixer.  h (b, m, d), what
    the mixer takes; s0 (b, Hv/g, dk, g·dv) float32, ``init_state``'s form,
    and tail (b, K−1, conv): what the sequences carried in (None: zeros);
    n_valid as in ``mamba_block``.  Returns (out (b, m, d), S, tail)."""
    b, m, _ = h.shape
    k1 = cfg.gdn_conv - 1
    with jax.named_scope("strom.ssm.proj"):
        u, z, beta, log_alpha = _gdn_project(h, p, L, cfg)
        if tail is None:
            tail = jnp.zeros((b, k1, cfg.gdn_conv_dim), u.dtype)
        if s0 is None:
            s0 = jnp.zeros(_gdn.pool_shape(b, cfg.gdn_v_heads, cfg.gdn_k_dim,
                                           cfg.gdn_v_dim), jnp.float32)
        window = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
        w = p[L + "gdn_conv_w"].astype(jnp.float32)
        conv = sum(w[j] * window[:, j:j + m].astype(jnp.float32)
                   for j in range(cfg.gdn_conv))
        # the scan's products take the activations' type on the MXU
        q, k, v = (t.astype(h.dtype)
                   for t in _gdn_heads(jax.nn.silu(conv), cfg))
        valid = None
        if n_valid is None:
            new_tail = window[:, m:]
        else:
            valid = valid_rows(n_valid, b, m)
            new_tail = _tail_at(window, n_valid, k1)
    with jax.named_scope("strom.ssm.scan"):
        o, s = gdn_scan(q, k, v, log_alpha, beta,
                        _gdn.unpack_state(s0, cfg.gdn_v_heads), valid,
                        chunk=cfg.gdn_chunk)
        s = _gdn.pack_state(s)
    with jax.named_scope("strom.ssm.out"):
        out = _gdn_out(o, z, p, L, cfg)
    return out, s, new_tail


def gdn_step(h, p: Dict, L: str, cfg: TransformerConfig, s_pool, tail_pool,
             sidx):
    """One token of every slot through the delta-rule mixer, against the
    server's pools.  h (B, 1, d); s_pool (rows, Hv/g, dk, g·dv) float32 and
    tail_pool (rows, K−1, conv), both updated in place when donated; sidx
    (B,) each slot's row.  Returns (out (B, 1, d), s_pool, tail_pool)."""
    with jax.named_scope("strom.ssm.proj"):
        u, z, beta, log_alpha = _gdn_project(h[:, 0], p, L, cfg)
        window = jnp.concatenate(
            [tail_pool[sidx].astype(u.dtype), u[:, None]], axis=1)  # (B,K,C)
        tail_pool = tail_pool.at[sidx].set(
            window[:, 1:].astype(tail_pool.dtype))
        conv = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                          p[L + "gdn_conv_w"].astype(jnp.float32))
        q, k, v = _gdn_heads(jax.nn.silu(conv), cfg)
    with jax.named_scope("strom.ssm.update"):
        o, s_pool = gdn_update(s_pool, sidx, q, k, v, log_alpha, beta)
    with jax.named_scope("strom.ssm.out"):
        out = _gdn_out(o, z, p, L, cfg)[:, None]
    return out, s_pool, tail_pool

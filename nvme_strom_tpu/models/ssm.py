"""The recurrent mixers of a hybrid decoder — Mamba-2 and the gated short
convolution — and the state a sequence carries through them.

Mamba-2:

    [z, u, dt] = split(h W_in)             widths inner | inner + 2N | H
    u_t  = silu(Σ_j w_conv[j] · u_{t-K+1+j} + b_conv)      depthwise, causal
    [x, B, C] = split(u_t)                 x: (H, P);  B, C: (N,), one group
    Δ_t  = softplus(dt_t + dt_bias)        A = −exp(A_log)        (per head)
    S_t  = exp(Δ_t A) · S_{t−1} + Δ_t · x_t ⊗ B_t          S: (H, P, N) f32
    y_t  = S_t C_t + D ⊙ x_t
    out  = RMSNorm(y ⊙ silu(z); g_norm over all of inner) W_out

The gated short convolution (``layer_kinds`` "conv"; the LFM2 family's
operator, ``conv_block`` / ``conv_step`` at the end of this file):

    [B, C, u] = split(h W_in)              three times d_model, in that order
    v_t  = B_t ⊙ u_t
    c_t  = Σ_j w[j] ⊙ v_{t-K+1+j}          depthwise, causal, K = conv_taps,
                                           no bias, no activation
    out  = (C ⊙ c) W_out

What a sequence carries from one call to the next is, for Mamba-2, ``S`` and
the last K−1 rows of ``u`` before the conv; for the short conv the last K−1
rows of ``v``.  ``init_state`` holds both kinds: ``"s"`` one array per
Mamba-2 layer, ``"conv"`` one tail per recurrent layer of either kind, in
layer order.  ``mamba_block`` runs a block of rows
(prefill, a full forward) through ``ops.ssm.ssm_scan``; ``mamba_step`` runs
one token of every serving slot through ``ops.ssm.ssm_update`` against the
server's state pool.  Parameter leaves of layer ``L``: ``ssm_in`` (d, 2·inner
+ 2N + H), ``ssm_conv_w`` (K, inner + 2N), ``ssm_conv_b``, ``ssm_dt_bias``,
``ssm_A_log``, ``ssm_D`` (H,), ``ssm_norm`` (inner,), ``ssm_out`` (inner, d).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from nvme_strom_tpu.models.transformer import (TransformerConfig, rms_norm,
                                               valid_rows, wmat)
from nvme_strom_tpu.ops.ssm import ssm_scan, ssm_update


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
    declares per sequence: per mamba layer one ``S`` (rows, H, P, N) float32
    under ``"s"``, and per recurrent layer of either kind one conv tail under
    ``"conv"`` — (rows, K−1, inner + 2N) for Mamba-2, (rows, conv_taps − 1,
    d_model) for the short conv — in layer order.  Tuples of per-layer
    arrays, never one stacked array: each is donated to the step and updated
    in place, and indexing a stacked one by layer would copy the lot (the KV
    pool's copies in PERF.md §5)."""
    s = (rows, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
    tails = [(rows, cfg.ssm_conv - 1, cfg.ssm_conv_dim)
             if cfg.is_mamba_layer(i) else (rows, cfg.conv_taps - 1,
                                            cfg.d_model)
             for i in cfg.recurrent_layers]
    return {"s": tuple(jnp.zeros(s, jnp.float32) for _ in cfg.mamba_layers),
            "conv": tuple(jnp.zeros(t, cfg.dtype) for t in tails)}


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

    h (b, m, d) post-norm; s0 (b, H, P, N) float32 and tail (b, K−1, inner
    + 2N): what the sequences carried in (None: nothing yet, zeros);
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
            s0 = jnp.zeros((b, cfg.ssm_heads, cfg.ssm_head_dim,
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
    pools.  h (B, 1, d); s_pool (rows, H, P, N) float32 and tail_pool
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

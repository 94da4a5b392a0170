"""Latent attention (MLA, DeepSeek-V2/V3's): queries through a low-rank
bottleneck, and keys and values that are expansions of ONE latent row a
token — which is all a cache keeps.

    c_q = rmsnorm(h W_qa)                      d -> q_lora_rank
    q   = c_q W_qb   -> heads x (nope | rope)
    [c_kv | k_pe] = h W_kva                    d -> kv_lora_rank + rope
    c_kv = rmsnorm(c_kv);  q_pe, k_pe rotated (k_pe is one head, shared)
    [k_nope | v] = c_kv W_kvb  -> heads x (nope | v)
    score = (q_nope . k_nope + q_pe . k_pe) * attn_scale,  out = (P v) W_o

The cached row of a token is ``[c_kv after its norm | k_pe after its
rotation]`` (``cfg.latent_width`` values).  Two forms of the same numbers:

* expanded (``attend``): keys and values rebuilt from the cached rows — the
  prefill's, where the rows are many and the products are matrix products.
  The attention itself is a Pallas kernel over blocks of query rows and of
  keys (``strom_mla_prefill``): no (heads, rows, keys) score tensor exists —
  at 8,192 rows and 64 heads it would be 17 GB, and XLA's softmax over
  score blocks kept in HBM took three quarters of a prefill's time — and a
  block's keys end where its last row's causal mask does.
* absorbed (``absorb_q`` / ``unabsorb``): W_kvb's key half folded into the
  query (``q_abs[h] = q_nope[h] W_UK[h]^T``) and its value half applied to
  the attention's output, so that a decode step attends over the latent
  rows themselves (``ops/mla_attention.py``) and expands nothing.

The rotary pair layout: HF's DeepSeek-V3 de-interleaves q_pe and k_pe
((d/2, 2) -> (2, d/2)) before its half-split rotation.  That is one fixed
permutation of W_qb's and W_kva's rotary output columns; a converted
checkpoint carries it in the weights (``tools/convert_llama``) and the
rotation here is the half-split one, as everywhere else in this model.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from nvme_strom_tpu.models import transformer as _tr


def init_mla_params(keys, cfg, prefix: str, dense) -> dict:
    """One layer's latent-attention weights, (in, out) as everywhere."""
    d, nh = cfg.d_model, cfg.n_heads
    dq, dc = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        prefix + "wq_a": dense(next(keys), d, (d, dq)),
        prefix + "q_a_norm": jnp.ones((dq,), jnp.float32),
        prefix + "wq_b": dense(next(keys), dq, (dq, nh * (dn + dr))),
        prefix + "wkv_a": dense(next(keys), d, (d, dc + dr)),
        prefix + "kv_a_norm": jnp.ones((dc,), jnp.float32),
        prefix + "wkv_b": dense(next(keys), dc, (dc, nh * (dn + dv))),
        prefix + "wo": dense(next(keys), nh * dv, (nh * dv, d)),
    }


def project(h, p, prefix: str, cfg, positions):
    """h (b, m, d) -> (q (b, m, heads, nope + rope) with its rope part
    rotated, rows (b, m, latent_width): what the cache keeps of each token).
    ``positions`` (m,) or (b, m) as ``transformer._rope`` takes them."""
    b, m, _ = h.shape
    nh, dn, dr, dc = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                      cfg.kv_lora_rank)
    cq = _tr.rms_norm(h @ _tr.wmat(p, prefix + "wq_a", h.dtype),
                      p[prefix + "q_a_norm"], cfg.norm_eps)
    q = (cq @ _tr.wmat(p, prefix + "wq_b", h.dtype)).reshape(b, m, nh,
                                                           dn + dr)
    kv = h @ _tr.wmat(p, prefix + "wkv_a", h.dtype)
    c = _tr.rms_norm(kv[..., :dc], p[prefix + "kv_a_norm"], cfg.norm_eps)
    cos, sin = _tr._rope_cos_sin(dr // 2, cfg.rope_theta, positions,
                                 cfg.rope_scaling_dict, m)
    q_pe = _tr._apply_rope(q[..., dn:], cos[..., :, None, :],
                           sin[..., :, None, :])
    k_pe = _tr._apply_rope(kv[..., dc:], cos, sin)
    return (jnp.concatenate([q[..., :dn], q_pe], axis=-1),
            jnp.concatenate([c, k_pe], axis=-1))


def _kv_b(p, prefix: str, cfg, dtype):
    """W_kvb as (kv_lora_rank, heads, nope + v)."""
    return _tr.wmat(p, prefix + "wkv_b", dtype).reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_dim + cfg.v_head_dim)


def attend(q, rows, pos, p, prefix: str, cfg):
    """The expanded form: q (b, m, heads, nope + rope), the rows of cache
    positions ``pos .. pos + m - 1`` (pos () int32, data), against the
    cached ``rows`` (b, S, latent_width) — the block's own among them —,
    causally: row t sees positions <= pos + t.  Keys and values are rebuilt
    from the rows head-major, and ``ops/mla_attention.mla_prefill_attention``
    walks them in blocks with an online softmax: no (heads, m, S) score
    tensor exists, and the causal half is all that runs.  Returns (b, m,
    heads * v) before W_o."""
    from nvme_strom_tpu.ops.mla_attention import mla_prefill_attention
    b, m, nh, _ = q.shape
    S = rows.shape[1]
    dn, dc = cfg.qk_nope_dim, cfg.kv_lora_rank
    kv = jnp.einsum("bsc,chx->bhsx", rows[..., :dc],
                    _kv_b(p, prefix, cfg, rows.dtype))
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(rows[:, None, :, dc:],
                                        (b, nh, S, cfg.qk_rope_dim))], -1)
    a = mla_prefill_attention(q.transpose(0, 2, 1, 3), k, kv[..., dn:], pos,
                              scale=cfg.attn_scale)
    return a.transpose(0, 2, 1, 3).reshape(b, m, -1)


def self_attention(h, p, prefix: str, cfg, positions=None):
    """Causal latent attention of h (b, s, d) over itself (the training
    path's and ``forward``'s: no cache), W_o applied."""
    q, rows = project(h, p, prefix, cfg, positions)
    a = attend(q, rows, jnp.zeros((), jnp.int32), p, prefix, cfg)
    return a @ _tr.wmat(p, prefix + "wo", a.dtype)


def absorb_q(q, p, prefix: str, cfg):
    """q (b, heads, nope + rope) -> (b, heads, latent_width): the query of
    the absorbed form, ``attn_scale`` folded in, so that its product with a
    cached row IS the score."""
    dn = cfg.qk_nope_dim
    w_uk = _kv_b(p, prefix, cfg, q.dtype)[..., :dn]
    q_lat = jnp.einsum("bhn,chn->bhc", q[..., :dn], w_uk,
                       preferred_element_type=jnp.float32)
    qa = jnp.concatenate([q_lat, q[..., dn:].astype(jnp.float32)], axis=-1)
    return (qa * jnp.float32(cfg.attn_scale)).astype(q.dtype)


def unabsorb(o_lat, p, prefix: str, cfg):
    """The absorbed form's output o_lat (b, heads, kv_lora_rank) — each
    head's probability-weighted sum of latents — to (b, heads * v)."""
    w_uv = _kv_b(p, prefix, cfg, o_lat.dtype)[..., cfg.qk_nope_dim:]
    return jnp.einsum("bhc,chv->bhv", o_lat, w_uv).reshape(
        o_lat.shape[0], -1)

"""Plain reference of a dense grouped-query decoder (Mistral-7B-v0.3's
architecture), written from the published description and importing nothing
of the program:

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * g_attn
                q, k, v = h Wq, h Wk, h Wv        (weights stored (in, out))
                rotary on q and k: half-split pairs (x[:d/2], x[d/2:]),
                    angle = position * theta^(-2j/d)   (HF rotate_half)
                kv heads repeated to the query heads (GQA), causal softmax
                x += (attn) Wo
                h = rmsnorm(x) * g_mlp
                x += (silu(h Wgate) * (h Wup)) Wdown
    logits = (rmsnorm(x) * g_final) Wlm_head

float32 throughout under ``jax.default_matmul_precision("highest")``, no
cache, no kernels, no batching tricks.  Weights are drawn layer by layer from
the benchmark's seeded generator (never taken from the program), so only one
layer is resident at a time.  Departure from the checkpoint format of the
published model: none in the mathematics; the weights are random.

``low`` selects the control's arithmetic: "int8" quantises each weight per
output channel and each activation row to int8 before every matmul (W8A8,
symmetric, dynamic) — the nearest precision below bf16."""

from __future__ import annotations

import functools

import numpy as np

from benchmark import weights as W

_LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
                 "w_up", "w_down")


def _rmsnorm(x, g, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * g


def _rope(t, theta):
    """t: (S, L, H, D) float32; half-split rotation by absolute position."""
    import jax.numpy as jnp
    d = t.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.arange(t.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)


def _q8(a, axis):
    """Symmetric int8 fake-quantisation along ``axis`` (values stay float32
    but take only 255 levels per row/channel)."""
    import jax.numpy as jnp
    scale = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True),
                        1e-30) / 127.0
    return jnp.clip(jnp.round(a / scale), -127, 127) * scale


def _mm(x, w, low):
    import jax.numpy as jnp
    if low == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w)


def _layer(x, w, hf, low):
    import jax
    import jax.numpy as jnp
    S, L, d = x.shape
    nh, nkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or d // nh
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    h = _rmsnorm(x, w["attn_norm"], eps)
    q = _rope(_mm(h, w["wq"], low).reshape(S, L, nh, hd), theta)
    k = _rope(_mm(h, w["wk"], low).reshape(S, L, nkv, hd), theta)
    v = _mm(h, w["wv"], low).reshape(S, L, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)

    def one_seq(qkv):
        q1, k1, v1 = qkv
        sc = jnp.einsum("qhd,khd->hqk", q1, k1) / np.sqrt(hd)
        mask = jnp.tril(jnp.ones((L, L), bool))
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v1)

    a = jax.lax.map(one_seq, (q, k, v)).reshape(S, L, nh * hd)
    x = x + _mm(a, w["wo"], low)
    h = _rmsnorm(x, w["mlp_norm"], eps)
    g = _mm(h, w["w_gate"], low)
    ff = (g * jax.nn.sigmoid(g)) * _mm(h, w["w_up"], low)
    return x + _mm(ff, w["w_down"], low)


@functools.lru_cache(maxsize=None)
def _programs(hf_items: tuple, low):
    """(embed, layer, head) jitted once per configuration and precision; the
    weights are generated inside from traced stream ids."""
    import jax
    import jax.numpy as jnp
    hf = dict(hf_items)
    shapes = dict(W.tensor_specs(hf))

    def gen(base, name):
        return W.make_tensor(base, name, shapes[name]).astype(jnp.float32)

    def embed(base, tokens):
        return gen(base, "tok_embed")[tokens]

    def layer(x, bases9):
        w = {leaf: W.make_tensor(bases9[j], "layers.0." + leaf,
                                 shapes["layers.0." + leaf]
                                 ).astype(jnp.float32)
             for j, leaf in enumerate(_LAYER_LEAVES)}
        return _layer(x, w, hf, low)

    def head(x, base_norm, base_head, at):
        # logits only where a served token is compared: at (S, K) positions
        xs = jnp.take_along_axis(x, at[:, :, None], axis=1)
        h = _rmsnorm(xs, gen(base_norm, "final_norm"), hf["rms_norm_eps"])
        return _mm(h, gen(base_head, "lm_head"), low)

    return (jax.jit(embed), jax.jit(layer, donate_argnums=(0,)),
            jax.jit(head))


def logits_at(hf: dict, seed: int, tokens, at, low=None):
    """Reference logits (S, K, vocab) float32 at positions ``at`` (S, K) of
    the sequences ``tokens`` (S, L) int32 (causal: right padding is inert)."""
    import jax
    keys = ("hidden_size", "vocab_size", "num_attention_heads",
            "num_key_value_heads", "intermediate_size", "num_hidden_layers",
            "rms_norm_eps", "rope_theta", "head_dim")
    hf_small = {k: hf[k] for k in keys if hf.get(k) is not None}
    embed, layer, head = _programs(tuple(sorted(hf_small.items())), low)
    bs = W.bases(hf_small, seed)
    idx = W.layer_indices(hf_small)
    with jax.default_matmul_precision("highest"):
        x = embed(bs[idx["tok_embed"]], np.asarray(tokens, np.int32))
        for i in range(hf_small["num_hidden_layers"]):
            b9 = np.asarray([bs[idx[f"layers.{i}.{leaf}"]]
                             for leaf in _LAYER_LEAVES], np.uint32)
            x = layer(x, b9)
        return head(x, bs[idx["final_norm"]], bs[idx["lm_head"]],
                    np.asarray(at, np.int32))

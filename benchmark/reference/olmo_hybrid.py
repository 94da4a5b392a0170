"""Plain reference of an Olmo-Hybrid-shaped decoder (``model_type``
olmo_hybrid: Olmo-Hybrid-7B's language model), written from the catalog row's
``config``, from Olmo 3's block and FLA's GatedDeltaNet as the row's key
names point to them, and importing nothing of the program.  ``x`` (T, d);
``N(y) = y / sqrt(mean y² + eps) ⊙ w`` with ``w`` as stored (no 1 + w); no
bias anywhere; an untied head:

    x = E[tokens]
    per layer i (POST-norm: nothing normalises what a sub-layer takes):
        x += N_a(mixer_i(x));   x += N_f(W_down(silu(W_gate x) ⊙ W_up x))
    logits = N(x) W_head

    mixer, ``layer_types[i]`` "linear_attention" — the gated delta rule, H =
    30 heads, a key head a value head, dk = 96, dv = 192:
        [q | k | v | g] = x W_in       widths 2,880 | 2,880 | 5,760 | 5,760
        [b | a] = x W_ba               30 | 30
        [q|k|v]_t = silu(sum_{j<4} w_conv[j] * [q|k|v]_{t-3+j})   depthwise,
                                       causal (zeros before row 0), no bias
        q~ = q / sqrt(sum q² + 1e-6) / sqrt(96),  k~ = k / sqrt(sum k² +
        1e-6), a head at a time
        beta = 2 sigmoid(b)  (``linear_allow_neg_eigval``; sigmoid(b) without)
        alpha = exp(-exp(A_log) softplus(a + dt_bias))
        S (96 x 192 a head, zeros before row 0), A TOKEN AT A TIME:
            S <- alpha_t S;  u = beta_t (v_t - S^T k~_t);  S <- S + k~_t (x) u
            o_t = S^T q~_t
        out = (w_o * o / sqrt(mean o² + eps) * silu(g)) W_out, the norm a
            head over its 192 with one weight vector of 192
    mixer, "full_attention" — 30 heads of 128, every query head its own keys
    and values:
        q = N_q(x W_q),  k = N_k(x W_k): each norm over ALL 3,840 features
        of the projection, then the split into heads;  v = x W_v
        no rotary (``rope_parameters.rope_theta`` null)
        P = causal softmax(q . k / sqrt(128));  out = concat_heads(P v) W_o

Assumed (``model_type`` olmo_hybrid is not in the installed transformers; its
two halves are — ``models/olmo3`` and ``models/qwen3_next`` — and were read):
null ``rope_theta`` means NO rotary; the linear layers sit in the same
post-norm block as the full ones; the two 1e-6 inside the L2 norms and q's
1/sqrt(96) as transformers' qwen3_next has them; FLA's six projections and
three convs are laid side by side as ``W_in``, ``W_ba`` and one conv over q
| k | v (the same products, the converter's layout).

float32 throughout under ``jax.default_matmul_precision("highest")``, no
cache, no kernels, the recurrence never in its chunked form.  Departures
from the equations, both of form only: sequences go through a layer one at a
time and full attention walks the query rows in blocks of 128 against ALL
the keys under the causal mask, so that a replay of six 2,048-token
sequences at the published widths fits one chip; weights are drawn layer by
layer.

``low`` selects a control's arithmetic (``benchmark/tools/control_olmoh.py``):
"int8" quantises every weight per output channel and every activation row to
int8 before each matrix product (W8A8, as in ``dense_gqa.py``); "beta1" puts
β = sigmoid(b), without the 2; "pre_norm" puts both norms of a block BEFORE
their sub-layers (x += f(N(x)), the Llama order, same weights); "head_norm"
normalises q and k a head at a time (each head's 128 features by their own
mean square, the same weights); "rotary" turns q and k half-split at theta
500,000 (Olmo 3's)."""

from __future__ import annotations

import functools

import numpy as np

from benchmark import weights_olmoh as WO
# the arithmetic every plain reference shares: the int8 control's quantised
# product
from benchmark.reference.dense_gqa import _mm as _mm8

#: query rows full attention handles at once (a sequence shorter than two
#: blocks, or no multiple of it, is one block)
QUERY_BLOCK = 128
#: the rotary base of the "rotary" control (Olmo 3's ``rope_theta``)
CONTROL_THETA = 500000.0


def _mm(x, w, low):
    return _mm8(x, w, "int8" if low == "int8" else None)


def _silu(a):
    import jax
    return a * jax.nn.sigmoid(a)


def norm(x, w, eps):
    """The RMS norm, its weight as stored: x̂ ⊙ w."""
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(t, theta: float):
    """t (L, heads, hd) float32 at positions 0..L-1, turned half-split."""
    import jax.numpy as jnp
    half = t.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t.shape[0], dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)


def delta_rule(q, k, v, alpha, beta):
    """The recurrence, a token at a time from an empty state: q, k (L, H,
    dk), v (L, H, dv), alpha, beta (L, H) -> o (L, H, dv)."""
    import jax
    import jax.numpy as jnp

    def step(s, x):
        q, k, v, a, b = x
        s = a[:, None, None] * s
        u = b[:, None] * (v - jnp.einsum("hkv,hk->hv", s, k))
        s = s + k[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q)

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(step, s0, (q, k, v, alpha, beta))[1]


def linear_mixer(x, w, hf, low=None):
    """x (S, L, d) -> the delta-rule layer's output (S, L, d), W_out
    applied."""
    import jax
    import jax.numpy as jnp
    z = WO.sizes(hf)
    L = x.shape[1]
    hk, hv, dk, dv, taps = z["Hk"], z["Hv"], z["dk"], z["dv"], z["K"]
    eps = hf["rms_norm_eps"]
    beta_max = 1.0 if low == "beta1" else z["beta_max"]

    def unit(t):                                       # (L, Hk, dk)
        return t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    def one_seq(xs):                                   # (L, d)
        mixed = _mm(xs, w["gdn_in"], low)
        u, gate = mixed[:, :z["conv"]], mixed[:, z["conv"]:]
        ba = _mm(xs, w["gdn_ba"], low)
        window = jnp.concatenate(
            [jnp.zeros((taps - 1, z["conv"]), jnp.float32), u])
        u = _silu(sum(w["gdn_conv_w"][j] * window[j:j + L]
                      for j in range(taps)))
        q = unit(u[:, :z["key"]].reshape(L, hk, dk)) * dk ** -0.5
        k = unit(u[:, z["key"]:2 * z["key"]].reshape(L, hk, dk))
        v = u[:, 2 * z["key"]:].reshape(L, hv, dv)
        beta = beta_max * jax.nn.sigmoid(ba[:, :hv])
        alpha = jnp.exp(-jnp.exp(w["gdn_A_log"])
                        * jax.nn.softplus(ba[:, hv:] + w["gdn_dt_bias"]))
        rep = hv // hk                 # value head j reads key head j // rep
        o = delta_rule(jnp.repeat(q, rep, axis=1), jnp.repeat(k, rep, axis=1),
                       v, alpha, beta)
        y = norm(o, w["gdn_norm"], eps) * _silu(gate.reshape(L, hv, dv))
        return _mm(y.reshape(L, hv * dv), w["gdn_out"], low)

    return jax.lax.map(one_seq, x)


def full_mixer(x, w, hf, low=None):
    """x (S, L, d) -> the attention layer's output (S, L, d), W_o applied."""
    import jax
    import jax.numpy as jnp
    z = WO.sizes(hf)
    L = x.shape[1]
    nh, nkv, hd = z["nh"], z["nkv"], z["hd"]
    g = nh // nkv
    eps = hf["rms_norm_eps"]
    theta = CONTROL_THETA if low == "rotary" else z["theta"]
    qb = QUERY_BLOCK if L % QUERY_BLOCK == 0 and L > QUERY_BLOCK else L

    def qk_norm(t, wn, heads):                         # (L, heads * hd)
        if low == "head_norm":
            return norm(t.reshape(L, heads, hd), wn.reshape(heads, hd), eps)
        return norm(t, wn, eps).reshape(L, heads, hd)

    def one_seq(xs):                                   # (L, d)
        q = qk_norm(_mm(xs, w["wq"], low), w["q_norm"], nh)
        k = qk_norm(_mm(xs, w["wk"], low), w["k_norm"], nkv)
        if theta is not None:
            q, k = _rope(q, theta), _rope(k, theta)
        q = q.reshape(L, nkv, g, hd)
        v = _mm(xs, w["wv"], low).reshape(L, nkv, hd)

        def block(t0):
            qs = jax.lax.dynamic_slice_in_dim(q, t0, qb)
            s = jnp.einsum("qngd,knd->ngqk", qs, k) * hd ** -0.5
            rows = t0 + jnp.arange(qb)[:, None]
            s = jnp.where(jnp.arange(L)[None, :] <= rows, s, -jnp.inf)
            return jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(s, axis=-1), v)

        a = jax.lax.map(block, jnp.arange(0, L, qb)).reshape(L, nh * hd)
        return _mm(a, w["wo"], low)

    return jax.lax.map(one_seq, x)


def gated_mlp(x, w, low=None):
    """W_down (silu(W_gate x) * W_up x), one sequence at a time."""
    import jax
    return jax.lax.map(
        lambda xs: _mm(_silu(_mm(xs, w["w_gate"], low))
                       * _mm(xs, w["w_up"], low), w["w_down"], low), x)


def block(x, w, hf, mixer, low=None):
    """One layer: the post-norm order, or the "pre_norm" control's."""
    eps = hf["rms_norm_eps"]
    if low == "pre_norm":
        x = x + mixer(norm(x, w["attn_norm"], eps), w, hf, low)
        return x + gated_mlp(norm(x, w["mlp_norm"], eps), w, low)
    x = x + norm(mixer(x, w, hf, low), w["attn_norm"], eps)
    return x + norm(gated_mlp(x, w, low), w["mlp_norm"], eps)


_KEYS = ("hidden_size", "vocab_size", "intermediate_size",
         "num_attention_heads", "num_key_value_heads",
         "linear_conv_kernel_dim", "linear_key_head_dim",
         "linear_num_key_heads", "linear_num_value_heads",
         "linear_value_head_dim", "linear_allow_neg_eigval",
         "num_hidden_layers", "rms_norm_eps")


@functools.lru_cache(maxsize=None)
def _programs(hf_items: tuple, low):
    """(embed, {kind: layer}, head), jitted once per configuration and
    arithmetic; weights are generated inside from traced stream ids."""
    import jax
    import jax.numpy as jnp
    hf = dict(hf_items)
    hf["layer_types"] = list(hf["layer_types"])
    hf["rope_parameters"] = dict(hf["rope_parameters"])
    top, shapes = WO.top_shapes(hf), WO.layer_shapes(hf)

    def gen(base, name, shape):
        return WO.make_tensor(base, name, shape).astype(jnp.float32)

    def embed(base, tokens):
        return gen(base, "tok_embed", top["tok_embed"])[tokens]

    def layer_of(kind):
        leaves = WO.layer_leaves(kind)
        mixer = linear_mixer if kind == "linear" else full_mixer

        def layer(x, layer_bases):
            w = {leaf: gen(layer_bases[j], leaf, shapes[leaf])
                 for j, leaf in enumerate(leaves)}
            return block(x, w, hf, mixer, low)
        return jax.jit(layer, donate_argnums=(0,))

    def head(x, base_norm, base_head, at):
        xs = jnp.take_along_axis(x, at[:, :, None], axis=1)
        h = norm(xs, gen(base_norm, "final_norm", top["final_norm"]),
                 hf["rms_norm_eps"])
        return _mm(h, gen(base_head, "lm_head", top["lm_head"]), low)

    kinds = {WO.layer_kind(hf, i) for i in range(hf["num_hidden_layers"])}
    return (jax.jit(embed), {k: layer_of(k) for k in kinds}, jax.jit(head))


def logits_at(hf: dict, seed: int, tokens, at, low=None):
    """Reference logits (S, K, vocab) float32 at positions ``at`` (S, K) of
    the sequences ``tokens`` (S, L) int32 (causal: right padding is inert)."""
    import jax
    small = {k: hf[k] for k in _KEYS if hf.get(k) is not None}
    small["layer_types"] = tuple(hf["layer_types"])
    small["rope_parameters"] = tuple(sorted(
        (hf.get("rope_parameters") or {}).items()))
    embed, layers, head = _programs(tuple(sorted(small.items())), low)
    bs, idx = WO.bases(hf, seed), WO.layer_indices(hf)
    with jax.default_matmul_precision("highest"):
        x = embed(bs[idx["tok_embed"]], np.asarray(tokens, np.int32))
        for i in range(hf["num_hidden_layers"]):
            kind = WO.layer_kind(hf, i)
            lb = np.asarray([bs[idx[f"layers.{i}.{leaf}"]]
                             for leaf in WO.layer_leaves(kind)], np.uint32)
            x = layers[kind](x, lb)
        return head(x, bs[idx["final_norm"]], bs[idx["lm_head"]],
                    np.asarray(at, np.int32))

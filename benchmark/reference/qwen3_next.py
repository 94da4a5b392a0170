"""Plain reference of a Qwen3-Next-shaped decoder (``model_type`` qwen3_next:
Qwen3-Next-80B-A3B's language model), written from the catalog row's
``config`` and ``described_as`` and importing nothing of the program.  ``x``
(T, d); every norm but one ZERO-CENTRED, ``N(x) = x / sqrt(mean x² + eps) ⊙
(1 + w)`` with the stored ``w`` as published; no bias anywhere; an untied
head:

    x = E[tokens]
    per layer i:   x += mixer_i(N(x));  x += moe_i(N(x))
    logits = N(x) W_head

    mixer, layer i LINEAR where (i + 1) % full_attention_interval != 0 — the
    gated delta rule, Hk = 16 key heads and Hv = 32 value heads of 128,
    value head j reading key head j // 2, h = N(x):
        [q | k | v | z] = h W_qkvz     widths 2,048 | 2,048 | 4,096 | 4,096
        [b | a] = h W_ba               32 | 32
        [q|k|v]_t = silu(sum_{j<4} w_conv[j] * [q|k|v]_{t-3+j})   depthwise,
                                       causal (zeros before row 0), no bias
        q~ = q / sqrt(sum q² + 1e-6) / sqrt(128),  k~ = k / sqrt(sum k² +
        1e-6), a head at a time
        beta = sigmoid(b),  alpha = exp(-exp(A_log) softplus(a + dt_bias))
        S (128 x 128 a value head, zeros before row 0), A TOKEN AT A TIME:
            S <- alpha_t S;  u = beta_t (v_t - S^T k~_t);  S <- S + k~_t (x) u
            o_t = S^T q~_t
        y = w_n * o / sqrt(mean o² + eps) * silu(z), a head over its 128
            (this norm's weight is NOT zero-centred);  out = y W_out
    mixer, otherwise FULL — GQA under an output gate:
        [q | g] = h W_q as 16 heads of (256 | 256);  k, v = 2 KV heads of 256
        q, k through N (the head norm) a head, then rotary on the FIRST 64 of
        the 256 (partial_rotary_factor 0.25), half-split pairs (x[:32],
        x[32:64]), angle = position x theta^(-2j/64), theta 1e7
        P = causal softmax(q . k / sqrt(256)); query head h reads KV head
        h // 8
        out = (concat_heads(P v) * sigmoid(g)) W_o
    moe:
        p   = softmax(h W_r) over all E = 512, float32
        sel = the top 10;  w = p[sel] / sum p[sel]      (norm_topk_prob)
        f   = sum_{e in sel} w_e expert_e(h), gated MLPs 2,048 -> 512 ->
              2,048, + sigmoid(h . w_sg) MLP_shared(h)

The configuration's file is one chip's SHARE of a deployment
(``deployment``, ``expert_share``): the router keeps its E outputs and its
top-k, the weights are normalised over all k selected experts, and the sum
runs over the experts HELD here (``num_experts`` of them from
``expert_share["offset"]``) — what the absent ones would add is left out,
here as in the program; the shared expert is whole; the embedding and the
head are the held slice of the vocabulary.  The expert layer is the plain
form: a loop over the held experts, each computed on every row and weighted
by a mask.

Assumed (no modelling file or checkpoint could be read: no network): the
order of ``W_qkvz`` / ``W_ba`` above is what the converter's de-interleave
leaves (the published tensors interleave them by key head); the two 1e-6
inside the L2 norms; no ``+ eps`` in the routing weights' sum (the program's
``moe.route`` has ``1e-6``).  The MTP layer is not part of the row's
``config`` and is not here.

float32 throughout under ``jax.default_matmul_precision("highest")``, no
cache, no kernels, the recurrence never in its chunked form.  Sequences go
through a layer one at a time and full attention walks the query rows in
blocks of 128 against ALL the keys under the causal mask, so that a replay of
six 5,120-token sequences at the published widths fits one chip; weights are
drawn layer by layer (the routed experts expert by expert).

``low`` selects a control's arithmetic (``benchmark/tools/control_gdn.py``):
"int8" quantises every weight per output channel and every activation row to
int8 before each matrix product (W8A8, as in ``dense_gqa.py``; the router's
product too); "beta1" writes the whole correction (β = 1); "alpha1" never
decays (α = 1); "no_correction" drops ``− Sᵀk̃`` (plain gated linear
attention); "no_l2" drops the L2 norms of q and k (the 1/sqrt(128) stays);
"w_norm" puts ``w`` in the place of ``1 + w``; "no_attn_gate" drops the
output gate; "rotary_all" rotates all 256 features of a head; "no_shared_gate"
drops the shared expert's gate; "norm_held" normalises the weights over the
selected experts held here only."""

from __future__ import annotations

import functools

import numpy as np

from benchmark import weights_gdn as WG
# the arithmetic every plain reference shares: the int8 control's quantised
# product
from benchmark.reference.dense_gqa import _mm as _mm8

#: query rows full attention handles at once (a sequence shorter than two
#: blocks, or no multiple of it, is one block)
QUERY_BLOCK = 128


def _mm(x, w, low):
    return _mm8(x, w, "int8" if low == "int8" else None)


def _silu(a):
    import jax
    return a * jax.nn.sigmoid(a)


def norm(x, w, eps, low=None):
    """The zero-centred RMS norm: x̂ ⊙ (1 + w)."""
    import jax.numpy as jnp
    g = w if low == "w_norm" else 1.0 + w
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(t, rotary: int, theta: float):
    """t (L, heads, hd) float32 at positions 0..L-1: the first ``rotary``
    features of every head turned half-split, the rest passed."""
    import jax.numpy as jnp
    half = rotary // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t.shape[0], dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    t1, t2 = t[..., :half], t[..., half:rotary]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin,
                            t[..., rotary:]], -1)


def delta_rule(q, k, v, alpha, beta, low=None):
    """The recurrence, a token at a time from an empty state: q, k (L, Hv,
    dk), v (L, Hv, dv), alpha, beta (L, Hv) -> o (L, Hv, dv)."""
    import jax
    import jax.numpy as jnp

    def step(s, x):
        q, k, v, a, b = x
        s = a[:, None, None] * s
        read = 0.0 if low == "no_correction" else jnp.einsum(
            "hkv,hk->hv", s, k)
        u = b[:, None] * (v - read)
        s = s + k[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q)

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(step, s0, (q, k, v, alpha, beta))[1]


def linear_mixer(h, w, hf, low=None):
    """h (S, L, d) -> the gated-delta-rule layer's output (S, L, d), W_out
    applied."""
    import jax
    import jax.numpy as jnp
    z = WG.sizes(hf)
    L = h.shape[1]
    hk, hv, dk, dv, taps = z["Hk"], z["Hv"], z["dk"], z["dv"], z["K"]
    eps = hf["rms_norm_eps"]

    def unit(t):                                       # (L, Hk, dk)
        if low == "no_l2":
            return t
        return t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    def one_seq(hs):                                   # (L, d)
        mixed = _mm(hs, w["gdn_in"], low)
        u, gate = mixed[:, :z["conv"]], mixed[:, z["conv"]:]
        ba = _mm(hs, w["gdn_ba"], low)
        window = jnp.concatenate(
            [jnp.zeros((taps - 1, z["conv"]), jnp.float32), u])
        u = _silu(sum(w["gdn_conv_w"][j] * window[j:j + L]
                      for j in range(taps)))
        q = unit(u[:, :z["key"]].reshape(L, hk, dk)) * dk ** -0.5
        k = unit(u[:, z["key"]:2 * z["key"]].reshape(L, hk, dk))
        v = u[:, 2 * z["key"]:].reshape(L, hv, dv)
        beta = jax.nn.sigmoid(ba[:, :hv])
        alpha = jnp.exp(-jnp.exp(w["gdn_A_log"])
                        * jax.nn.softplus(ba[:, hv:] + w["gdn_dt_bias"]))
        if low == "beta1":
            beta = jnp.ones_like(beta)
        if low == "alpha1":
            alpha = jnp.ones_like(alpha)
        rep = hv // hk                 # value head j reads key head j // rep
        o = delta_rule(jnp.repeat(q, rep, axis=1), jnp.repeat(k, rep, axis=1),
                       v, alpha, beta, low)
        y = (o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
             * w["gdn_norm"] * _silu(gate.reshape(L, hv, dv)))
        return _mm(y.reshape(L, hv * dv), w["gdn_out"], low)

    return jax.lax.map(one_seq, h)


def full_mixer(h, w, hf, low=None):
    """h (S, L, d) -> the gated attention layer's output (S, L, d), W_o
    applied."""
    import jax
    import jax.numpy as jnp
    z = WG.sizes(hf)
    L = h.shape[1]
    nh, nkv, hd = z["nh"], z["nkv"], z["hd"]
    g = nh // nkv
    eps = hf["rms_norm_eps"]
    rotary = hd if low == "rotary_all" else z["rotary"]
    qb = QUERY_BLOCK if L % QUERY_BLOCK == 0 and L > QUERY_BLOCK else L

    def one_seq(hs):                                   # (L, d)
        qg = _mm(hs, w["wq"], low).reshape(L, nh, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:].reshape(L, nh * hd)
        q = _rope(norm(q, w["q_norm"], eps, low), rotary,
                  z["theta"]).reshape(L, nkv, g, hd)
        k = _rope(norm(_mm(hs, w["wk"], low).reshape(L, nkv, hd),
                       w["k_norm"], eps, low), rotary, z["theta"])
        v = _mm(hs, w["wv"], low).reshape(L, nkv, hd)

        def block(t0):
            qs = jax.lax.dynamic_slice_in_dim(q, t0, qb)
            s = jnp.einsum("qngd,knd->ngqk", qs, k) * hd ** -0.5
            rows = t0 + jnp.arange(qb)[:, None]
            s = jnp.where(jnp.arange(L)[None, :] <= rows, s, -jnp.inf)
            return jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(s, axis=-1), v)

        a = jax.lax.map(block, jnp.arange(0, L, qb)).reshape(L, nh * hd)
        if low != "no_attn_gate":
            a = a * jax.nn.sigmoid(gate)
        return _mm(a, w["wo"], low)

    return jax.lax.map(one_seq, h)


def gated_mlp(h, w1, w3, w2, low=None):
    """W2 (silu(W1 h) * W3 h), one sequence at a time."""
    import jax
    return jax.lax.map(
        lambda hs: _mm(_silu(_mm(hs, w1, low)) * _mm(hs, w3, low), w2, low),
        h)


def routing(h, w, hf, low=None):
    """h (..., d) -> weight (..., E) float32 over ALL the routed experts, 0
    where not selected."""
    import jax
    import jax.numpy as jnp
    z = WG.sizes(hf)
    p = jax.nn.softmax(_mm(h, w["router"], low), axis=-1)
    _, sel = jax.lax.top_k(p, z["k"])
    chosen = jnp.any(sel[..., None] == jnp.arange(z["E"]), axis=-2)
    if low == "norm_held":
        e = jnp.arange(z["E"])
        chosen = chosen & (e >= z["offset"]) & (e < z["offset"] + z["held"])
    wt = jnp.where(chosen, p, 0.0)
    if hf.get("norm_topk_prob", True):
        # (the floor only keeps "norm_held" finite on a row none of whose
        # selected experts is held here; ten softmax scores never sum to 0)
        wt = wt / jnp.maximum(wt.sum(-1, keepdims=True), 1e-30)
    return wt


def moe(h, w, hf, expert_weights, low=None):
    """The plain expert layer of this share: every held expert on every row,
    weighted by the router's mask, and the shared expert under its gate.
    ``expert_weights(e)`` gives held expert e's (W1, W3, W2), e traced."""
    import jax
    import jax.numpy as jnp
    z = WG.sizes(hf)
    wt = routing(h, w, hf, low)

    def one(acc, e):
        f = gated_mlp(h, *expert_weights(e), low)
        return acc + jnp.take(wt, z["offset"] + e, axis=-1)[..., None] * f, \
            None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(z["held"]))
    shared = gated_mlp(h, w["shared_w_gate"], w["shared_w_up"],
                       w["shared_w_down"], low)
    if low != "no_shared_gate":
        shared = shared * jax.nn.sigmoid(_mm(h, w["shared_gate"], low))
    return out + shared


_KEYS = ("hidden_size", "vocab_size", "num_attention_heads",
         "num_key_value_heads", "head_dim", "partial_rotary_factor",
         "rope_theta", "full_attention_interval", "linear_conv_kernel_dim",
         "linear_key_head_dim", "linear_num_key_heads",
         "linear_num_value_heads", "linear_value_head_dim",
         "moe_intermediate_size", "shared_expert_intermediate_size",
         "num_experts", "num_experts_per_tok", "num_hidden_layers",
         "rms_norm_eps", "norm_topk_prob")
_GROUPS = ("expert_share",)


@functools.lru_cache(maxsize=None)
def _programs(hf_items: tuple, low):
    """(embed, {kind: layer}, head), jitted once per configuration and
    arithmetic; weights are generated inside from traced stream ids."""
    import jax
    import jax.numpy as jnp
    hf = {k: dict(v) if k in _GROUPS else v for k, v in hf_items}
    top, shapes = WG.top_shapes(hf), WG.layer_shapes(hf)
    eps = hf["rms_norm_eps"]

    def gen(base, name, shape, first=0):
        return WG.make_tensor(base, name, shape, first).astype(jnp.float32)

    def embed(base, tokens):
        return gen(base, "tok_embed", top["tok_embed"])[tokens]

    def layer_of(kind):
        leaves = WG.layer_leaves(kind)
        mixer = linear_mixer if kind == "linear" else full_mixer

        def layer(x, layer_bases):
            at = {leaf: layer_bases[j] for j, leaf in enumerate(leaves)}
            w = {leaf: gen(at[leaf], leaf, shapes[leaf]) for leaf in leaves
                 if leaf not in WG.STACKED}
            x = x + mixer(norm(x, w["attn_norm"], eps, low), w, hf, low)

            def expert_weights(e):       # one expert's slices, drawn alone
                def one(leaf):
                    n = shapes[leaf][1] * shapes[leaf][2]
                    return gen(at[leaf], leaf, shapes[leaf][1:],
                               e.astype(jnp.uint32) * jnp.uint32(n))
                return one("moe_w_gate"), one("moe_w_up"), one("moe_w_down")

            return x + moe(norm(x, w["mlp_norm"], eps, low), w, hf,
                           expert_weights, low)
        return jax.jit(layer, donate_argnums=(0,))

    def head(x, base_norm, base_head, at):
        xs = jnp.take_along_axis(x, at[:, :, None], axis=1)
        h = norm(xs, gen(base_norm, "final_norm", top["final_norm"]), eps,
                 low)
        return _mm(h, gen(base_head, "lm_head", top["lm_head"]), low)

    kinds = {WG.layer_kind(hf, i) for i in range(hf["num_hidden_layers"])}
    return (jax.jit(embed), {k: layer_of(k) for k in kinds}, jax.jit(head))


def logits_at(hf: dict, seed: int, tokens, at, low=None):
    """Reference logits (S, K, vocab) float32 at positions ``at`` (S, K) of
    the sequences ``tokens`` (S, L) int32 (causal: right padding is inert)."""
    import jax
    small = {k: hf[k] for k in _KEYS if hf.get(k) is not None}
    for g in _GROUPS:
        if hf.get(g):
            small[g] = tuple(sorted(hf[g].items()))
    embed, layers, head = _programs(tuple(sorted(small.items())), low)
    bs, idx = WG.bases(hf, seed), WG.layer_indices(hf)
    with jax.default_matmul_precision("highest"):
        x = embed(bs[idx["tok_embed"]], np.asarray(tokens, np.int32))
        for i in range(hf["num_hidden_layers"]):
            kind = WG.layer_kind(hf, i)
            lb = np.asarray([bs[idx[f"layers.{i}.{leaf}"]]
                             for leaf in WG.layer_leaves(kind)], np.uint32)
            x = layers[kind](x, lb)
        return head(x, bs[idx["final_norm"]], bs[idx["lm_head"]],
                    np.asarray(at, np.int32))

"""Plain reference of an LFM2-MoE decoder (``model_type`` lfm2_moe:
LFM2-24B-A2B's architecture), written from the published description — the
dense half of the family is ``transformers/models/lfm2`` — and importing
nothing of the program.  ``x`` (T, d); RMS norms at ``norm_eps``; no bias
anywhere:

    x = E[tokens]
    per layer i:   x += mixer_i(rmsnorm(x; operator_norm))
                   x += mlp_i(rmsnorm(x; ffn_norm))
    logits = rmsnorm(x; embedding_norm) Eᵀ                      (tied head)

    layer_types[i] == "conv" (Lfm2ShortConv.slow_forward):
        [B | C | u] = h W_in            d → 3 d, split in that order
        v   = B ⊙ u
        c_t = Σ_{j<K} w[:, j] ⊙ v_{t-K+1+j}     K = conv_L_cache taps, zeros
                                                before the sequence, no
                                                bias, no activation
        mixer = (C ⊙ c) W_out
    "full_attention":
        q, k, v = h Wq, h Wk, h Wv;  q and k RMS-normed PER HEAD over
        head_dim (q_layernorm, k_layernorm) BEFORE rotary (theta from
        rope_parameters, default type, half-split pairs as HF rotate_half);
        kv heads repeated to the query heads; causal softmax(q·k /
        √head_dim); then W_out.
    mlp, i < num_dense_layers:   W2 (silu(W1 h) ⊙ W3 h)  at intermediate_size
    mlp, otherwise (64 experts, no shared expert):
        s   = sigmoid(h W_g) in float32                       (E scores)
        sel = top-k of (s + b),  b = expert_bias: it chooses, does not weigh
        w   = s[sel] / (Σ s[sel] + 1e-6)            (norm_topk_prob)
              × routed_scaling_factor
        f   = Σ_{e ∈ sel} w_e · W2_e (silu(W1_e h) ⊙ W3_e h)
                                                at moe_intermediate_size

The expert layer is the PLAIN form: a loop over all the experts, each
computed on every row and kept where a mask says the row chose it — no
grouping, no sort, no capacity.  float32 throughout under
``jax.default_matmul_precision("highest")``, no cache, no kernels.  Weights
are drawn layer by layer (and, inside an expert layer, expert by expert: one
layer is 2.25 GiB in float32) from the benchmark's seeded generator
(``benchmark/weights_moe.py``), never taken from the program.  Departures
from the published model: none in the mathematics; the weights are random,
and the MoE block's tensor names are not needed here.

``low="int8"`` is the control's arithmetic, as in ``dense_gqa.py``: every
weight per output channel and every activation row quantised to int8 before
each matrix product (W8A8) — the nearest precision below bf16.  The router's
product is quantised with the rest."""

from __future__ import annotations

import functools

import numpy as np

from benchmark import weights_moe as WM
# the arithmetic every plain reference shares: RMS norm, HF's half-split
# rotary, and the int8 control's quantised product
from benchmark.reference.dense_gqa import _mm, _rmsnorm, _rope


def _silu(a):
    import jax
    return a * jax.nn.sigmoid(a)


def conv_mixer(h, w, hf, low=None):
    """h (S, L, d) → the short conv operator's output (S, L, d)."""
    import jax.numpy as jnp
    K, L = hf["conv_L_cache"], h.shape[1]
    b, c, u = jnp.split(_mm(h, w["conv_in"], low), 3, axis=-1)
    v = jnp.pad(b * u, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(w["conv_w"][j] * v[:, j:j + L] for j in range(K))
    return _mm(c * conv, w["conv_out"], low)


def attention_mixer(h, w, hf, low=None):
    import jax
    import jax.numpy as jnp
    S, L, d = h.shape
    nh, nkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or d // nh
    eps, theta = hf["norm_eps"], hf["rope_parameters"]["rope_theta"]
    q = _mm(h, w["wq"], low).reshape(S, L, nh, hd)
    k = _mm(h, w["wk"], low).reshape(S, L, nkv, hd)
    v = _mm(h, w["wv"], low).reshape(S, L, nkv, hd)
    q = _rope(_rmsnorm(q, w["q_norm"], eps), theta)
    k = _rope(_rmsnorm(k, w["k_norm"], eps), theta)
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)

    def one_seq(qkv):
        q1, k1, v1 = qkv
        sc = jnp.einsum("qhd,khd->hqk", q1, k1) / np.sqrt(hd)
        mask = jnp.tril(jnp.ones((L, L), bool))
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v1)

    a = jax.lax.map(one_seq, (q, k, v)).reshape(S, L, nh * hd)
    return _mm(a, w["wo"], low)


def routing(h, w, hf, low=None):
    """h (..., d) → (selected (..., E) bool, weight (..., E) float32, 0
    where not selected)."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(_mm(h, w["router"], low))
    choose = s + w["router_bias"] if hf["use_expert_bias"] else s
    _, sel = jax.lax.top_k(choose, hf["num_experts_per_tok"])
    chosen = jnp.any(sel[..., None] == jnp.arange(hf["num_experts"]), axis=-2)
    wt = jnp.where(chosen, s, 0.0)
    if hf["norm_topk_prob"]:
        wt = wt / (wt.sum(-1, keepdims=True) + 1e-6)
    return chosen, wt * hf["routed_scaling_factor"]


def expert_mlp(h, w, hf, expert_weights, low=None):
    """The plain expert layer: every expert on every row, masked.
    ``expert_weights(e)`` gives expert e's (W1, W3, W2), e traced."""
    import jax
    import jax.numpy as jnp
    _, wt = routing(h, w, hf, low)

    def one(acc, e):
        w1, w3, w2 = expert_weights(e)
        f = _mm(_silu(_mm(h, w1, low)) * _mm(h, w3, low), w2, low)
        return acc + jnp.take(wt, e, axis=-1)[..., None] * f, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          jnp.arange(hf["num_experts"]))
    return out


def dense_mlp(h, w, low=None):
    return _mm(_silu(_mm(h, w["w_gate"], low)) * _mm(h, w["w_up"], low),
               w["w_down"], low)


_KEYS = ("hidden_size", "vocab_size", "num_attention_heads",
         "num_key_value_heads", "head_dim", "intermediate_size",
         "moe_intermediate_size", "num_experts", "num_experts_per_tok",
         "num_dense_layers", "num_hidden_layers", "norm_eps", "conv_L_cache",
         "norm_topk_prob", "use_expert_bias", "routed_scaling_factor")


@functools.lru_cache(maxsize=None)
def _programs(hf_items: tuple, low):
    """(embed, {(mixer, mlp): layer}, head), jitted once per configuration
    and precision; weights are generated inside from traced stream ids."""
    import jax
    import jax.numpy as jnp
    hf = dict(hf_items)
    hf["layer_types"] = list(hf["layer_types"])
    hf["rope_parameters"] = dict(hf["rope_parameters"])
    z = WM.sizes(hf)
    shapes = WM.layer_shapes(hf)

    def gen(base, name, shape, first=0):
        return WM.make_tensor(base, name, shape, first).astype(jnp.float32)

    def embed(base, tokens):
        return gen(base, "tok_embed", (z["v"], z["d"]))[tokens]

    def layer_of(mixer, mlp):
        leaves = WM.layer_leaves(mixer, mlp)
        stacked = ("moe_w_gate", "moe_w_up", "moe_w_down")

        def layer(x, layer_bases):
            at = {leaf: layer_bases[j] for j, leaf in enumerate(leaves)}
            w = {leaf: gen(at[leaf], leaf, shapes[leaf]) for leaf in leaves
                 if leaf not in stacked}
            h = _rmsnorm(x, w["attn_norm"], hf["norm_eps"])
            x = x + (conv_mixer if mixer == "conv"
                     else attention_mixer)(h, w, hf, low)
            h = _rmsnorm(x, w["mlp_norm"], hf["norm_eps"])
            if mlp == "dense":
                return x + dense_mlp(h, w, low)

            def expert_weights(e):       # one expert's slices, drawn alone
                def one(leaf):
                    # the (E, in, out) tensor's scale comes from its own
                    # name and shape; the slice is (in, out) at e x in x out
                    n = shapes[leaf][1] * shapes[leaf][2]
                    return gen(at[leaf], leaf, shapes[leaf][1:],
                               e.astype(jnp.uint32) * jnp.uint32(n))
                return one("moe_w_gate"), one("moe_w_up"), one("moe_w_down")

            return x + expert_mlp(h, w, hf, expert_weights, low)
        return jax.jit(layer, donate_argnums=(0,))

    def head(x, base_norm, base_embed, at):
        xs = jnp.take_along_axis(x, at[:, :, None], axis=1)
        h = _rmsnorm(xs, gen(base_norm, "final_norm", (z["d"],)),
                     hf["norm_eps"])
        return _mm(h, gen(base_embed, "tok_embed", (z["v"], z["d"])).T, low)

    kinds = {WM.layer_kinds(hf, i) for i in range(hf["num_hidden_layers"])}
    return (jax.jit(embed), {k: layer_of(*k) for k in kinds}, jax.jit(head))


def logits_at(hf: dict, seed: int, tokens, at, low=None):
    """Reference logits (S, K, vocab) float32 at positions ``at`` (S, K) of
    the sequences ``tokens`` (S, L) int32 (causal: right padding is inert)."""
    import jax
    small = {k: hf[k] for k in _KEYS if hf.get(k) is not None}
    small["layer_types"] = tuple(hf["layer_types"])
    small["rope_parameters"] = tuple(sorted(hf["rope_parameters"].items()))
    embed, layers, head = _programs(tuple(sorted(small.items())), low)
    bs = WM.bases(hf, seed)
    idx = WM.layer_indices(hf)
    with jax.default_matmul_precision("highest"):
        x = embed(bs[idx["tok_embed"]], np.asarray(tokens, np.int32))
        for i in range(hf["num_hidden_layers"]):
            kind = WM.layer_kinds(hf, i)
            lb = np.asarray([bs[idx[f"layers.{i}.{leaf}"]]
                             for leaf in WM.layer_leaves(*kind)], np.uint32)
            x = layers[kind](x, lb)
        return head(x, bs[idx["final_norm"]], bs[idx["tok_embed"]],
                    np.asarray(at, np.int32))

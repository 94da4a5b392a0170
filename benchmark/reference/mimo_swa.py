"""Plain reference of a MiMo-V2-shaped decoder (``model_type`` mimo_v2:
MiMo-V2.5's language model), written from the catalog row's ``config`` and
``described_as`` and importing nothing of the program.  ``x`` (T, d); RMS
norms at ``layernorm_epsilon``; no bias anywhere; an untied head:

    x = E[tokens]
    per layer i:   x += attn_i(rmsnorm(x));  x += mlp_i(rmsnorm(x))
    logits = rmsnorm(x) W_head

    attention, h = rmsnorm(x); layer i is FULL where hybrid_layer_pattern[i]
    is 0 and a WINDOW layer where it is 1:
        q = h W_q  -> 64 heads x head_dim (192)
        k = h W_k  -> KV heads x 192      (4 full: num_key_value_heads;
                                           8 window: swa_num_key_value_heads)
        v = h W_v  -> KV heads x v_head_dim (128), times attention_value_scale
        rotary on the FIRST r = int(192 x partial_rotary_factor) = 64 features
        of every q and k head, half-split pairs (x[:r/2], x[r/2:r]), angle =
        position x theta^(-2j/r); the other 128 pass.  theta = rope_theta
        (1e7) in a full layer, swa_rope_theta (1e4) in a window layer
        scores z = q . k / sqrt(192); query head h reads KV head h // (64 /
        KV heads)
        full:    row i sees keys j <= i;            P = softmax(z)
        window:  row i sees keys i - 128 < j <= i, and a learned scalar s_h
                 per query head joins the softmax as one more column that
                 carries no value:
                 P_ij = exp(z_ij - m) / (sum_j' exp(z_ij' - m) + exp(s_h - m))
        out = concat_heads(P v) W_o                 (64 x 128 -> d)
    mlp, moe_layer_freq[i] == 0:   W2 (silu(W1 h) * W3 h) at intermediate_size
    mlp, otherwise:
        s   = sigmoid(h W_g) in float32             (E = 256 scores)
        sel = top-k of (s + e_score_correction_bias) (n_group 1, topk_group
              1: no group limit)
        w   = s[sel] / (sum s[sel] + 1e-20)         (norm_topk_prob;
              routed_scaling_factor null = 1)
        f   = sum_{e in sel} w_e expert_e(h), gated MLPs at
              moe_intermediate_size; no shared expert

The configuration's file is one chip's SHARE of a deployment
(``deployment``, ``expert_share``): the router keeps its E outputs and its
top-k, the weights are normalised over all k selected experts, and the sum
runs over the experts HELD here (``n_routed_experts`` of them from
``expert_share["offset"]``) — what the absent ones would add is left out,
here as in the program; the embedding and the head are the held slice of the
vocabulary.  The expert layer is the plain form: a loop over the held
experts, each computed on every row and weighted by a mask.

Assumed (the model ships its own modelling file, which could not be read:
no network): the window's edge (``i - j < 128``: 128 keys, the row's own
among them), the value scale's place (on v, before P v — the same numbers as
on the output), the sink's form (above; gpt-oss's), that
``attention_chunk_size`` 128 = the window is no mechanism of its own, and
``1e-20`` in the weights' sum (DeepSeek-V3's; the program's ``moe.route``
has ``1e-6``).  The MTP layers and the vision and audio towers are not part
of the row's ``config`` and are not here.

float32 throughout under ``jax.default_matmul_precision("highest")``, no
cache, no kernels.  Sequences go through a layer one at a time and attention
walks the query rows in blocks of 128 against ALL the keys under the layer's
mask (a window layer too: full scores, band mask), so that a replay of six
17,408-token sequences at the published widths fits one chip; weights are
drawn layer by layer (an expert layer expert by expert).

``low`` selects a control's arithmetic (``benchmark/tools/control_swa.py``):
"int8" quantises every weight per output channel and every activation row to
int8 before each matrix product (W8A8, as in ``dense_gqa.py``; the router's
product too); "no_window" lets a window layer see every key j <= i;
"no_sink" drops the sink column; "no_value_scale" leaves v unscaled;
"one_theta" rotates both kinds of layer at ``rope_theta``; "norm_held"
normalises the weights over the selected experts held here only."""

from __future__ import annotations

import functools

import numpy as np

from benchmark import weights_swa as WS
# the arithmetic every plain reference shares: RMS norm and the int8
# control's quantised product
from benchmark.reference.dense_gqa import _mm as _mm8
from benchmark.reference.dense_gqa import _rmsnorm

#: query rows attention handles at once (a sequence shorter than two blocks,
#: or no multiple of it, is one block)
QUERY_BLOCK = 128


def _mm(x, w, low):
    return _mm8(x, w, "int8" if low == "int8" else None)


def _silu(a):
    import jax
    return a * jax.nn.sigmoid(a)


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


def attention(h, w, hf, kind: str, low=None):
    """h (S, L, d) -> the attention's output (S, L, d), W_o applied;
    ``kind`` "full" or "window"."""
    import jax
    import jax.numpy as jnp
    z = WS.sizes(hf)
    L = h.shape[1]
    nh, hd, vd, nkv = z["nh"], z["hd"], z["vd"], z["nkv"][kind]
    g = nh // nkv
    theta = z["theta"]["full" if low == "one_theta" else kind]
    vscale = 1.0 if low == "no_value_scale" else hf["attention_value_scale"]
    band = kind == "window" and low != "no_window"
    sink = kind == "window" and low != "no_sink" \
        and hf["add_swa_attention_sink_bias"]
    qb = QUERY_BLOCK if L % QUERY_BLOCK == 0 and L > QUERY_BLOCK else L

    def one_seq(hs):                                   # (L, d)
        q = _rope(_mm(hs, w["wq"], low).reshape(L, nh, hd), z["rotary"],
                  theta).reshape(L, nkv, g, hd)
        k = _rope(_mm(hs, w["wk"], low).reshape(L, nkv, hd), z["rotary"],
                  theta)
        v = _mm(hs, w["wv"], low).reshape(L, nkv, vd) * vscale

        def block(t0):
            qs = jax.lax.dynamic_slice_in_dim(q, t0, qb)
            s = jnp.einsum("qngd,knd->ngqk", qs, k) * hd ** -0.5
            rows = t0 + jnp.arange(qb)[:, None]
            cols = jnp.arange(L)[None, :]
            seen = cols <= rows
            if band:
                seen = seen & (rows - cols < z["window"])
            s = jnp.where(seen, s, -jnp.inf)
            if sink:        # one more column, with no value behind it
                col = jnp.broadcast_to(
                    w["sink"].reshape(nkv, g, 1, 1), (nkv, g, qb, 1))
                p = jax.nn.softmax(jnp.concatenate([s, col], -1),
                                   axis=-1)[..., :L]
            else:
                p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("ngqk,knd->qngd", p, v)

        a = jax.lax.map(block, jnp.arange(0, L, qb)).reshape(L, nh * vd)
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
    z = WS.sizes(hf)
    s = jax.nn.sigmoid(_mm(h, w["router"], low))
    _, sel = jax.lax.top_k(s + w["router_bias"], z["k"])
    chosen = jnp.any(sel[..., None] == jnp.arange(z["E"]), axis=-2)
    if low == "norm_held":
        e = jnp.arange(z["E"])
        chosen = chosen & (e >= z["offset"]) & (e < z["offset"] + z["held"])
    wt = jnp.where(chosen, s, 0.0)
    if hf.get("norm_topk_prob", True):
        wt = wt / (wt.sum(-1, keepdims=True) + 1e-20)
    return wt * (hf.get("routed_scaling_factor") or 1.0)


def expert_mlp(h, w, hf, expert_weights, low=None):
    """The plain expert layer of this share: every held expert on every row,
    weighted by the router's mask.  ``expert_weights(e)`` gives held expert
    e's (W1, W3, W2), e traced."""
    import jax
    import jax.numpy as jnp
    z = WS.sizes(hf)
    wt = routing(h, w, hf, low)

    def one(acc, e):
        f = gated_mlp(h, *expert_weights(e), low)
        return acc + jnp.take(wt, z["offset"] + e, axis=-1)[..., None] * f, \
            None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(z["held"]))
    return out


_KEYS = ("hidden_size", "vocab_size", "num_attention_heads",
         "num_key_value_heads", "swa_num_key_value_heads", "head_dim",
         "v_head_dim", "partial_rotary_factor", "rope_theta",
         "swa_rope_theta", "sliding_window", "attention_value_scale",
         "add_swa_attention_sink_bias", "intermediate_size",
         "moe_intermediate_size", "n_routed_experts", "num_experts_per_tok",
         "num_hidden_layers", "layernorm_epsilon", "norm_topk_prob",
         "routed_scaling_factor")
_GROUPS = ("expert_share",)
_LISTS = ("hybrid_layer_pattern", "moe_layer_freq")


@functools.lru_cache(maxsize=None)
def _programs(hf_items: tuple, low):
    """(embed, {(attention kind, mlp kind): layer}, head), jitted once per
    configuration and arithmetic; weights are generated inside from traced
    stream ids."""
    import jax
    import jax.numpy as jnp
    hf = {k: dict(v) if k in _GROUPS else list(v) if k in _LISTS else v
          for k, v in hf_items}
    top = WS.top_shapes(hf)
    eps = hf["layernorm_epsilon"]

    def gen(base, name, shape, first=0):
        return WS.make_tensor(base, name, shape, first).astype(jnp.float32)

    def embed(base, tokens):
        return gen(base, "tok_embed", top["tok_embed"])[tokens]

    def layer_of(attn, mlp):
        leaves, shapes = WS.layer_leaves(attn, mlp), WS.layer_shapes(hf, attn)

        def layer(x, layer_bases):
            at = {leaf: layer_bases[j] for j, leaf in enumerate(leaves)}
            w = {leaf: gen(at[leaf], leaf, shapes[leaf]) for leaf in leaves
                 if leaf not in WS.STACKED}
            x = x + attention(_rmsnorm(x, w["attn_norm"], eps), w, hf, attn,
                              low)
            h = _rmsnorm(x, w["mlp_norm"], eps)
            if mlp == "dense":
                return x + gated_mlp(h, w["w_gate"], w["w_up"], w["w_down"],
                                     low)

            def expert_weights(e):       # one expert's slices, drawn alone
                def one(leaf):
                    n = shapes[leaf][1] * shapes[leaf][2]
                    return gen(at[leaf], leaf, shapes[leaf][1:],
                               e.astype(jnp.uint32) * jnp.uint32(n))
                return one("moe_w_gate"), one("moe_w_up"), one("moe_w_down")

            return x + expert_mlp(h, w, hf, expert_weights, low)
        return jax.jit(layer, donate_argnums=(0,))

    def head(x, base_norm, base_head, at):
        xs = jnp.take_along_axis(x, at[:, :, None], axis=1)
        h = _rmsnorm(xs, gen(base_norm, "final_norm", top["final_norm"]), eps)
        return _mm(h, gen(base_head, "lm_head", top["lm_head"]), low)

    kinds = {WS.layer_kinds(hf, i) for i in range(hf["num_hidden_layers"])}
    return (jax.jit(embed), {k: layer_of(*k) for k in kinds}, jax.jit(head))


def logits_at(hf: dict, seed: int, tokens, at, low=None):
    """Reference logits (S, K, vocab) float32 at positions ``at`` (S, K) of
    the sequences ``tokens`` (S, L) int32 (causal: right padding is inert)."""
    import jax
    small = {k: hf[k] for k in _KEYS if hf.get(k) is not None}
    for g in _GROUPS:
        if hf.get(g):
            small[g] = tuple(sorted(hf[g].items()))
    for g in _LISTS:
        small[g] = tuple(hf[g])
    embed, layers, head = _programs(tuple(sorted(small.items())), low)
    bs, idx = WS.bases(hf, seed), WS.layer_indices(hf)
    with jax.default_matmul_precision("highest"):
        x = embed(bs[idx["tok_embed"]], np.asarray(tokens, np.int32))
        for i in range(hf["num_hidden_layers"]):
            kind = WS.layer_kinds(hf, i)
            lb = np.asarray([bs[idx[f"layers.{i}.{leaf}"]]
                             for leaf in WS.layer_leaves(*kind)], np.uint32)
            x = layers[kind](x, lb)
        return head(x, bs[idx["final_norm"]], bs[idx["lm_head"]],
                    np.asarray(at, np.int32))

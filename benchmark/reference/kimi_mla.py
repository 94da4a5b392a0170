"""Plain reference of a DeepSeek-V3-shaped decoder (``model_type`` kimi_k2:
Kimi-K2.7-Code's language model), written from the published description —
``transformers/models/deepseek_v3`` is the same architecture — and importing
nothing of the program.  ``x`` (T, d); RMS norms at ``rms_norm_eps``; no bias
anywhere; an untied head:

    x = E[tokens]
    per layer i:   x += attn_i(rmsnorm(x));  x += mlp_i(rmsnorm(x))
    logits = rmsnorm(x) W_head

    attention (every layer; the EXPANDED form only), h = rmsnorm(x):
        c_q = rmsnorm(h W_qa)                          d -> q_lora_rank
        q   = c_q W_qb   -> heads x (nope | rope)
        [c_kv | k_pe] = h W_kva                        d -> kv_lora_rank + rope
        c_kv = rmsnorm(c_kv);  q_pe and k_pe rotated (k_pe is ONE head,
        shared by all the query heads);  [k_nope | v] = c_kv W_kvb
        scores = (q_nope . k_nope + q_pe . k_pe) * scale, causal softmax,
        out = (P v) W_o
        scale  = (nope + rope)^-0.5 * m^2,
                 m = 0.1 * mscale_all_dim * ln(factor) + 1   (1.4159, m^2 =
                 2.0047 at factor 64)
        YaRN (rope_scaling "yarn"): inv_freq_j blends theta^(-2j/rope) /
        factor and theta^(-2j/rope) by the linear ramp between
        find_correction_range(beta_fast, beta_slow, rope, theta, original
        max positions) (HF ``_compute_yarn_parameters``); cos and sin times
        yarn_get_mscale(factor, mscale) / yarn_get_mscale(factor,
        mscale_all_dim), 1 here.
    mlp, i < first_k_dense_replace:   W2 (silu(W1 h) * W3 h) at
                                      intermediate_size
    mlp, otherwise:
        s   = sigmoid(h W_g) in float32                (E = 384 scores)
        sel = top-k of (s + e_score_correction_bias)   (n_group 1,
              topk_group 1: no group limit)
        w   = s[sel] / (sum s[sel] + 1e-20) * routed_scaling_factor
        f   = sum_{e in sel} w_e expert_e(h)  +  shared(h)
              experts and the shared one gated MLPs at moe_intermediate_size

The configuration's file is one chip's SHARE of a deployment
(``deployment``, ``expert_share``): the router keeps its E outputs and its
top-k, the weights are normalised over all k selected experts, and the sum
runs over the experts HELD here (``n_routed_experts`` of them from
``expert_share["offset"]``) — what the absent ones would add is left out,
here as in the program; the embedding and the head are the held slice of the
vocabulary.  The expert layer is the plain form: a loop over the held
experts, each computed on every row and weighted by a mask.

Departures from the published model.  (1) The rotary pairs: HF
de-interleaves q_pe and k_pe ((rope/2, 2) -> (2, rope/2)) before its
half-split rotation, which is one fixed permutation of W_qb's and W_kva's
rotary output columns; a converted checkpoint carries it in the weights, so
with weights drawn in that layout the rotation here is the half-split one
alone.  (2) ``1e-20`` in the weights' sum is HF's; the program's
``moe.route`` has ``1e-6`` (a relative 3e-7 of a sum near 4).  (3) The
weights are random (``benchmark/weights_mla.py``); tensor names are not
needed here.

float32 throughout under ``jax.default_matmul_precision("highest")``, no
cache, no kernels.  Sequences go through a layer one at a time and
attention walks the query rows in blocks of 128, so that a replay of six
8,448-token sequences at the published widths fits one chip; weights are
drawn layer by layer (an expert layer expert by expert).

``low`` selects a control's arithmetic (``benchmark/tools/control_mla.py``):
"int8" quantises every weight per output channel and every activation row to
int8 before each matrix product (W8A8, as in ``dense_gqa.py``; the router's
product too); "no_mscale" drops m^2 from the scale; "plain_rope" rotates
with theta^(-2j/rope) and no YaRN blend; "no_shared" leaves the shared
expert out; "norm_held" normalises the weights over the selected experts
held here only."""

from __future__ import annotations

import functools
import math

import numpy as np

from benchmark import weights_mla as WM
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


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(hf: dict, low=None) -> np.ndarray:
    """The rotary frequencies (rope/2,) float32: YaRN's blend, or the plain
    theta^(-2j/rope) where the file has no ``rope_scaling`` (or the control
    says so)."""
    dim, theta = hf["qk_rope_head_dim"], float(hf["rope_theta"])
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float32) / dim)
    sc = hf.get("rope_scaling")
    if not sc or low == "plain_rope":
        return plain.astype(np.float32)
    factor, orig = float(sc["factor"]), sc["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(correction_dim(sc.get("beta_fast", 32))), 0)
    hi = min(math.ceil(correction_dim(sc.get("beta_slow", 1))), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - lo) / (hi - lo),
                   0, 1)
    keep = 1 - ramp                 # 1: the pair turns fast, left as it is
    return (plain / factor * (1 - keep) + plain * keep).astype(np.float32)


def softmax_scale(hf: dict, low=None) -> float:
    scale = (hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]) ** -0.5
    sc = hf.get("rope_scaling")
    if sc and sc.get("mscale_all_dim") and low != "no_mscale":
        scale *= yarn_mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return scale


def _rope(t, freqs, mult):
    """t (L, ..., rope) float32 at positions 0..L-1: half-split rotation."""
    import jax.numpy as jnp
    half = t.shape[-1] // 2
    ang = jnp.arange(t.shape[0], dtype=jnp.float32)[:, None] * freqs
    ang = ang.reshape((t.shape[0],) + (1,) * (t.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang) * mult, jnp.sin(ang) * mult
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)


def attention(h, w, hf, low=None):
    """h (S, L, d) -> the latent attention's output (S, L, d), W_o applied."""
    import jax
    import jax.numpy as jnp
    L = h.shape[1]
    nh, dc = hf["num_attention_heads"], hf["kv_lora_rank"]
    dn, dr, dv = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
                  hf["v_head_dim"])
    eps, scale = hf["rms_norm_eps"], softmax_scale(hf, low)
    freqs = jnp.asarray(inv_freq(hf, low))
    sc = hf.get("rope_scaling") or {}
    mult = 1.0
    if sc and low != "plain_rope":
        ms, ma = sc.get("mscale"), sc.get("mscale_all_dim")
        mult = (yarn_mscale(sc["factor"], ms) / yarn_mscale(sc["factor"], ma)
                if ms and ma else yarn_mscale(sc["factor"], 1.0))
    qb = QUERY_BLOCK if L % QUERY_BLOCK == 0 and L > QUERY_BLOCK else L

    def one_seq(hs):                                   # (L, d)
        cq = _rmsnorm(_mm(hs, w["wq_a"], low), w["q_a_norm"], eps)
        q = _mm(cq, w["wq_b"], low).reshape(L, nh, dn + dr)
        kv = _mm(hs, w["wkv_a"], low)
        c = _rmsnorm(kv[:, :dc], w["kv_a_norm"], eps)
        k_pe = _rope(kv[:, dc:], freqs, mult)          # one head
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], freqs, mult)],
                            axis=-1)
        kvb = _mm(c, w["wkv_b"], low).reshape(L, nh, dn + dv)
        k = jnp.concatenate(
            [kvb[..., :dn], jnp.broadcast_to(k_pe[:, None], (L, nh, dr))],
            axis=-1)
        v = kvb[..., dn:]

        def block(t0):
            qs = jax.lax.dynamic_slice_in_dim(q, t0, qb)
            s = jnp.einsum("qhd,khd->hqk", qs, k) * scale
            seen = (jnp.arange(L)[None, :]
                    <= t0 + jnp.arange(qb)[:, None])
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", p, v)

        a = jax.lax.map(block, jnp.arange(0, L, qb)).reshape(L, nh * dv)
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
    z = WM.sizes(hf)
    s = jax.nn.sigmoid(_mm(h, w["router"], low))
    _, sel = jax.lax.top_k(s + w["router_bias"], z["k"])
    chosen = jnp.any(sel[..., None] == jnp.arange(z["E"]), axis=-2)
    if low == "norm_held":
        e = jnp.arange(z["E"])
        chosen = chosen & (e >= z["offset"]) & (e < z["offset"] + z["held"])
    wt = jnp.where(chosen, s, 0.0)
    if hf.get("norm_topk_prob", True):
        wt = wt / (wt.sum(-1, keepdims=True) + 1e-20)
    return wt * hf["routed_scaling_factor"]


def expert_mlp(h, w, hf, expert_weights, low=None):
    """The plain expert layer of this share: every held expert on every row,
    weighted by the router's mask, plus the shared expert.
    ``expert_weights(e)`` gives held expert e's (W1, W3, W2), e traced."""
    import jax
    import jax.numpy as jnp
    z = WM.sizes(hf)
    wt = routing(h, w, hf, low)

    def one(acc, e):
        f = gated_mlp(h, *expert_weights(e), low)
        return acc + jnp.take(wt, z["offset"] + e, axis=-1)[..., None] * f, \
            None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(z["held"]))
    if low != "no_shared":
        out = out + gated_mlp(h, w["shared_w_gate"], w["shared_w_up"],
                              w["shared_w_down"], low)
    return out


_KEYS = ("hidden_size", "vocab_size", "num_attention_heads", "q_lora_rank",
         "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "intermediate_size", "moe_intermediate_size",
         "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
         "first_k_dense_replace", "num_hidden_layers", "rms_norm_eps",
         "rope_theta", "norm_topk_prob", "routed_scaling_factor")
_GROUPS = ("rope_scaling", "expert_share")


@functools.lru_cache(maxsize=None)
def _programs(hf_items: tuple, low):
    """(embed, {mlp kind: layer}, head), jitted once per configuration and
    arithmetic; weights are generated inside from traced stream ids."""
    import jax
    import jax.numpy as jnp
    hf = {k: dict(v) if k in _GROUPS else v for k, v in hf_items}
    shapes, top = WM.layer_shapes(hf), WM.top_shapes(hf)

    def gen(base, name, shape, first=0):
        return WM.make_tensor(base, name, shape, first).astype(jnp.float32)

    def embed(base, tokens):
        return gen(base, "tok_embed", top["tok_embed"])[tokens]

    def layer_of(mlp):
        leaves = WM.layer_leaves(mlp)

        def layer(x, layer_bases):
            at = {leaf: layer_bases[j] for j, leaf in enumerate(leaves)}
            w = {leaf: gen(at[leaf], leaf, shapes[leaf]) for leaf in leaves
                 if leaf not in WM.STACKED}
            eps = hf["rms_norm_eps"]
            x = x + attention(_rmsnorm(x, w["attn_norm"], eps), w, hf, low)
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
        h = _rmsnorm(xs, gen(base_norm, "final_norm", top["final_norm"]),
                     hf["rms_norm_eps"])
        return _mm(h, gen(base_head, "lm_head", top["lm_head"]), low)

    kinds = {WM.mlp_kind(hf, i) for i in range(hf["num_hidden_layers"])}
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
    bs, idx = WM.bases(hf, seed), WM.layer_indices(hf)
    with jax.default_matmul_precision("highest"):
        x = embed(bs[idx["tok_embed"]], np.asarray(tokens, np.int32))
        for i in range(hf["num_hidden_layers"]):
            kind = WM.mlp_kind(hf, i)
            lb = np.asarray([bs[idx[f"layers.{i}.{leaf}"]]
                             for leaf in WM.layer_leaves(kind)], np.uint32)
            x = layers[kind](x, lb)
        return head(x, bs[idx["final_norm"]], bs[idx["lm_head"]],
                    np.asarray(at, np.int32))

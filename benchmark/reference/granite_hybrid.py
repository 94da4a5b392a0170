"""Plain reference of a hybrid decoder (``model_type`` granitemoehybrid with
no routed experts: granite-4.0-h-micro's architecture), written from the
published description and importing nothing of the program:

    x = embedding_multiplier · E[tokens]
    per layer i of kind layer_types[i]:
        h = rmsnorm(x) · g_in ;   x += residual_multiplier · mixer(h)
        h = rmsnorm(x) · g_post;  x += residual_multiplier ·
                                       (silu(h Wgate) ⊙ (h Wup)) Wdown
    logits = (rmsnorm(x) · g_final) Eᵀ / logits_scaling          (tied head)

    attention:  q, k, v = h Wq, h Wk, h Wv — no bias, NO positional encoding
                (position_embedding_type "nope"); kv heads repeated to the
                query heads; causal softmax of (q·k) · attention_multiplier
                (not 1/√head_dim); then Wo.

    mamba (Mamba-2; H heads of P, state N, one B/C group, K conv taps):
        [z, u, dt] = split(h W_in)            inner | inner + 2N | H
        u_t  = silu(Σ_{j<K} w_conv[j] · u_{t-K+1+j} + b_conv)   zeros to the left
        [x, B, C] = split(u_t)
        Δ_t  = softplus(dt_t + dt_bias);  A = −exp(A_log)       per head
        S_t  = exp(Δ_t A) · S_{t−1} + Δ_t · x_t ⊗ B_t           S: (H, P, N)
        y_t  = S_t C_t + D ⊙ x_t
        out  = rmsnorm(y ⊙ silu(z)) · g_norm  W_out   (gate first, one group)

The recurrence runs TOKEN BY TOKEN in a ``lax.scan`` — not the chunked
algorithm of the program's kernel, which is what makes this independent of
it.  float32 throughout under ``jax.default_matmul_precision("highest")``,
no cache, no kernels, no batching tricks.  Weights are drawn layer by layer
from the benchmark's seeded generator (``benchmark/weights_hybrid.py``),
never taken from the program.  Departures from the published model: none in
the mathematics; the weights are random.

``low="int8"`` is the control's arithmetic, as in ``dense_gqa.py``: every
weight per output channel and every activation row quantised to int8 before
each matrix product (W8A8) — the nearest precision below bf16."""

from __future__ import annotations

import functools

import numpy as np

from benchmark import weights_hybrid as WH


def _rmsnorm(x, g, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * g


def _q8(a, axis):
    import jax.numpy as jnp
    scale = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True),
                        1e-30) / 127.0
    return jnp.clip(jnp.round(a / scale), -127, 127) * scale


def _mm(x, w, low):
    import jax.numpy as jnp
    if low == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w)


def _silu(a):
    import jax
    return a * jax.nn.sigmoid(a)


def _mlp(x, w, hf, low):
    h = _rmsnorm(x, w["mlp_norm"], hf["rms_norm_eps"])
    ff = _silu(_mm(h, w["w_gate"], low)) * _mm(h, w["w_up"], low)
    return x + hf["residual_multiplier"] * _mm(ff, w["w_down"], low)


def _attention_layer(x, w, hf, low):
    import jax
    import jax.numpy as jnp
    S, L, d = x.shape
    nh, nkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or d // nh
    h = _rmsnorm(x, w["attn_norm"], hf["rms_norm_eps"])
    q = _mm(h, w["wq"], low).reshape(S, L, nh, hd)
    k = jnp.repeat(_mm(h, w["wk"], low).reshape(S, L, nkv, hd),
                   nh // nkv, axis=2)
    v = jnp.repeat(_mm(h, w["wv"], low).reshape(S, L, nkv, hd),
                   nh // nkv, axis=2)

    def one_seq(qkv):
        q1, k1, v1 = qkv
        sc = jnp.einsum("qhd,khd->hqk", q1, k1) * hf["attention_multiplier"]
        mask = jnp.tril(jnp.ones((L, L), bool))
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v1)

    a = jax.lax.map(one_seq, (q, k, v)).reshape(S, L, nh * hd)
    x = x + hf["residual_multiplier"] * _mm(a, w["wo"], low)
    return _mlp(x, w, hf, low)


def mamba_mixer(h, w, hf, low=None):
    """h (S, L, d) → the mixer's output (S, L, d)."""
    import jax
    import jax.numpy as jnp
    z = WH.sizes(hf)
    S, L, _ = h.shape
    H, P, N, K, inner = z["H"], z["P"], z["N"], z["K"], z["inner"]
    zu = _mm(h, w["ssm_in"], low)
    gate, u, dt = (zu[..., :inner], zu[..., inner:inner + z["conv"]],
                   zu[..., inner + z["conv"]:])
    upad = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    u = sum(w["ssm_conv_w"][j] * upad[:, j:j + L] for j in range(K))
    u = _silu(u + w["ssm_conv_b"])
    xs = u[..., :inner].reshape(S, L, H, P)
    bs, cs = u[..., inner:inner + N], u[..., inner + N:]
    delta = jax.nn.softplus(dt + w["ssm_dt_bias"])             # (S, L, H)
    a = -jnp.exp(w["ssm_A_log"])

    def token(state, row):                     # one position, all sequences
        x_t, b_t, c_t, d_t = row
        state = (jnp.exp(d_t * a)[..., None, None] * state
                 + (d_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return state, jnp.einsum("shpn,sn->shp", state, c_t)

    rows = tuple(jnp.moveaxis(t, 1, 0) for t in (xs, bs, cs, delta))
    _, y = jax.lax.scan(token, jnp.zeros((S, H, P, N), jnp.float32), rows)
    y = jnp.moveaxis(y, 0, 1) + w["ssm_D"][:, None] * xs       # (S, L, H, P)
    y = _rmsnorm(y.reshape(S, L, inner) * _silu(gate), w["ssm_norm"],
                 hf["rms_norm_eps"])
    return _mm(y, w["ssm_out"], low)


def _mamba_layer(x, w, hf, low):
    h = _rmsnorm(x, w["attn_norm"], hf["rms_norm_eps"])
    x = x + hf["residual_multiplier"] * mamba_mixer(h, w, hf, low)
    return _mlp(x, w, hf, low)


_KEYS = ("hidden_size", "vocab_size", "num_attention_heads",
         "num_key_value_heads", "head_dim", "shared_intermediate_size",
         "rms_norm_eps", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
         "mamba_d_conv", "embedding_multiplier", "residual_multiplier",
         "logits_scaling", "attention_multiplier")


@functools.lru_cache(maxsize=None)
def _programs(hf_items: tuple, low):
    """(embed, {kind: layer}, head), jitted once per configuration and
    precision; weights are generated inside from traced stream ids."""
    import jax
    import jax.numpy as jnp
    hf = dict(hf_items)
    hf["layer_types"] = list(hf["layer_types"])
    d, v = hf["hidden_size"], hf["vocab_size"]

    def gen(base, name, shape):
        return WH.make_tensor(base, name, shape).astype(jnp.float32)

    def embed(base, tokens):
        return hf["embedding_multiplier"] * gen(base, "tok_embed",
                                                (v, d))[tokens]

    def layer_of(kind):
        leaves = WH.MAMBA_LEAVES if kind == "mamba" else WH.ATTN_LEAVES
        shapes = WH.layer_shapes(hf, kind)
        fn = _mamba_layer if kind == "mamba" else _attention_layer

        def layer(x, layer_bases):
            w = {leaf: gen(layer_bases[j], leaf, shapes[leaf])
                 for j, leaf in enumerate(leaves)}
            return fn(x, w, hf, low)
        return jax.jit(layer, donate_argnums=(0,))

    def head(x, base_norm, base_embed, at):
        xs = jnp.take_along_axis(x, at[:, :, None], axis=1)
        h = _rmsnorm(xs, gen(base_norm, "final_norm", (d,)),
                     hf["rms_norm_eps"])
        return _mm(h, gen(base_embed, "tok_embed", (v, d)).T,
                   low) / hf["logits_scaling"]

    return (jax.jit(embed), {k: layer_of(k) for k in ("mamba", "attention")},
            jax.jit(head))


def logits_at(hf: dict, seed: int, tokens, at, low=None):
    """Reference logits (S, K, vocab) float32 at positions ``at`` (S, K) of
    the sequences ``tokens`` (S, L) int32 (causal: right padding is inert)."""
    import jax
    small = {k: hf[k] for k in _KEYS if hf.get(k) is not None}
    small["layer_types"] = tuple(hf["layer_types"])
    embed, layers, head = _programs(tuple(sorted(small.items())), low)
    bs = WH.bases(hf, seed)
    idx = WH.layer_indices(hf)
    with jax.default_matmul_precision("highest"):
        x = embed(bs[idx["tok_embed"]], np.asarray(tokens, np.int32))
        for i, kind in enumerate(hf["layer_types"]):
            leaves = WH.MAMBA_LEAVES if kind == "mamba" else WH.ATTN_LEAVES
            lb = np.asarray([bs[idx[f"layers.{i}.{leaf}"]]
                             for leaf in leaves], np.uint32)
            x = layers[kind](x, lb)
        return head(x, bs[idx["final_norm"]], bs[idx["tok_embed"]],
                    np.asarray(at, np.int32))

"""Seeded weights of a Qwen3-Next-shaped decoder (``model_type`` qwen3_next:
gated-delta-rule layers and gated full GQA layers mixed by
``full_attention_interval``, every layer's MLP softmax-routed experts beside
one gated shared expert, an untied head), on ``benchmark/weights.py``'s
integer generator — imported, not copied, so a tensor is the same bits on the
TPU, on the CPU and in numpy:

    value = bfloat16(float32(irwin_hall4(mix(mix(i) ^ base)) + offset) * scale)

Names and layouts are the program's flat parameter dict
(``models/transformer.init_params``, ``models/ssm.init_gdn_params``,
``models/moe.init_moe_params``): matrices (in, out); ``gdn_in`` [q | k | v |
z] and ``gdn_ba`` [b | a] in the order of the equations
(``benchmark/reference/qwen3_next.py``), which is what the converter's
de-interleave leaves; ``wq`` a head at a time (q | gate); an expert layer's
three matrices stacked (experts held, in, out); ``shared_gate`` (d, 1).

The file this reads is one chip's SHARE of a deployment (its ``deployment``
key): ``num_experts`` counts the experts held here and ``vocab_size`` the
rows of the embedding and the head held here, while the router keeps its
published width, ``expert_share["routed"]``.

A tensor is drawn in the PUBLISHED form: a zero-centred norm's ``w`` is what
``make_tensor`` gives and what the reference adds to 1; ``make_params`` hands
the program ``1 + w`` in float32, as the converter does (``ZERO_CENTRED``).

Distributions (``assumed`` in the configuration's file).  Matrices are
N(0, 1/fan_in) — ``shared_gate`` (d, 1) among them, so that the shared
expert's gate spans (0, 1).  The zero-centred norms' ``w`` are N(0, 0.1^2);
``gdn_norm`` (not zero-centred) 1 + N(0, 0.1^2).  ``gdn_A_log`` N(1, 1) and
``gdn_dt_bias`` N(-4, 1.5^2) per value head, as ``weights_hybrid.py`` has
them for Δ: with the projection's own N(0, 1) on top a token's log-decay
−exp(A_log)·softplus(a + dt_bias) spans about −5 … −0.001 over a layer's 32
heads — heads that forget within a few tokens beside heads that keep
thousands, so both a dropped state and a state carried wrongly across a
chunk move the logits.  ``gdn_conv_w`` N(0, 0.5^2) over the 4 taps.
``tok_embed`` is N(0, (1/1024)^2) and the head N(0, 1/d), as kimi's.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import weights as W

LINEAR_LEAVES = ("attn_norm", "gdn_in", "gdn_ba", "gdn_conv_w",
                 "gdn_dt_bias", "gdn_A_log", "gdn_norm", "gdn_out")
FULL_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm")
EXPERT_LEAVES = ("mlp_norm", "router", "moe_w_gate", "moe_w_up",
                 "moe_w_down", "shared_w_gate", "shared_w_up",
                 "shared_w_down", "shared_gate")
STACKED = ("moe_w_gate", "moe_w_up", "moe_w_down")
#: the norms whose stored weight is added to 1 (every one but ``gdn_norm``)
ZERO_CENTRED = ("attn_norm", "mlp_norm", "final_norm", "q_norm", "k_norm")

#: leaf -> (mean, std) where it is not N(0, 1/fan_in)
_DIST = {"gdn_A_log": (1.0, 1.0), "gdn_dt_bias": (-4.0, 1.5),
         "gdn_conv_w": (0.0, 0.5), "gdn_norm": (1.0, 0.1),
         "tok_embed": (0.0, 1.0 / 1024),
         **{leaf: (0.0, 0.1) for leaf in ZERO_CENTRED}}


def sizes(hf: dict) -> dict:
    """The widths the layout is made of, from the file's keys."""
    share = hf.get("expert_share") or {}
    hk, hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    dk, dv = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    hd = hf["head_dim"]
    return {"d": hf["hidden_size"], "v": hf["vocab_size"],
            "Hk": hk, "Hv": hv, "dk": dk, "dv": dv,
            "K": hf["linear_conv_kernel_dim"],
            "key": hk * dk, "value": hv * dv, "conv": 2 * hk * dk + hv * dv,
            "nh": hf["num_attention_heads"], "nkv": hf["num_key_value_heads"],
            "hd": hd, "rotary": int(hd * hf["partial_rotary_factor"]),
            "theta": float(hf["rope_theta"]),
            "fe": hf["moe_intermediate_size"],
            "fs": hf["shared_expert_intermediate_size"],
            "held": hf["num_experts"],
            "E": share.get("routed", hf["num_experts"]),
            "offset": share.get("offset", 0),
            "k": hf["num_experts_per_tok"]}


def layer_kind(hf: dict, i: int) -> str:
    """"full" for every ``full_attention_interval``-th layer, else
    "linear"."""
    return "linear" if (i + 1) % hf["full_attention_interval"] else "full"


def layer_leaves(kind: str) -> tuple:
    return (LINEAR_LEAVES if kind == "linear" else FULL_LEAVES) \
        + EXPERT_LEAVES


def layer_shapes(hf: dict) -> dict:
    """{leaf: shape} of every leaf a layer can hold."""
    z = sizes(hf)
    d, nh, nkv, hd = z["d"], z["nh"], z["nkv"], z["hd"]
    fe, fs, held = z["fe"], z["fs"], z["held"]
    return {"attn_norm": (d,), "mlp_norm": (d,),
            "gdn_in": (d, z["conv"] + z["value"]), "gdn_ba": (d, 2 * z["Hv"]),
            "gdn_conv_w": (z["K"], z["conv"]), "gdn_dt_bias": (z["Hv"],),
            "gdn_A_log": (z["Hv"],), "gdn_norm": (z["dv"],),
            "gdn_out": (z["value"], d),
            "wq": (d, nh * 2 * hd), "wk": (d, nkv * hd), "wv": (d, nkv * hd),
            "wo": (nh * hd, d), "q_norm": (hd,), "k_norm": (hd,),
            "router": (d, z["E"]),
            "moe_w_gate": (held, d, fe), "moe_w_up": (held, d, fe),
            "moe_w_down": (held, fe, d),
            "shared_w_gate": (d, fs), "shared_w_up": (d, fs),
            "shared_w_down": (fs, d), "shared_gate": (d, 1)}


def top_shapes(hf: dict) -> dict:
    z = sizes(hf)
    return {"tok_embed": (z["v"], z["d"]), "final_norm": (z["d"],),
            "lm_head": (z["d"], z["v"])}


def tensor_specs(hf: dict) -> list:
    """[(name, shape)]: the index in this list keys the generator."""
    specs = list(top_shapes(hf).items())
    shapes = layer_shapes(hf)
    for i in range(hf["num_hidden_layers"]):
        specs += [(f"layers.{i}.{leaf}", shapes[leaf])
                  for leaf in layer_leaves(layer_kind(hf, i))]
    return specs


def layer_indices(hf: dict) -> dict:
    return {name: i for i, (name, _) in enumerate(tensor_specs(hf))}


def offset_scale(name: str, shape: tuple) -> tuple:
    leaf = name.rsplit(".", 1)[-1]
    if leaf in _DIST:
        mean, std = _DIST[leaf]
    else:                       # a matrix (in, out), or a stack of them
        mean, std = 0.0, float(shape[-2]) ** -0.5
    scale = np.float32(std / W._SIGMA)
    return int(round(mean / float(scale))) - W._MEAN, scale


def bases(hf: dict, seed: int) -> np.ndarray:
    return np.asarray([W._base(seed, i)
                       for i in range(len(tensor_specs(hf)))], np.uint32)


def make_tensor_np(seed: int, index: int, name: str, shape: tuple):
    """The tensor in plain numpy: the definition the tests pin."""
    import ml_dtypes
    n = int(np.prod(shape, dtype=np.int64))
    off, scale = offset_scale(name, shape)
    with np.errstate(over="ignore"):
        vals = W._values(np.arange(n, dtype=np.uint32),
                         np.uint32(W._base(seed, index)), off, scale, np)
    return vals.astype(ml_dtypes.bfloat16).reshape(shape)


def make_tensor(base, name: str, shape: tuple, first: int = 0):
    """The tensor, in its published form, as a traced jax value; ``base``
    its traced stream id.  ``first`` is the flat index of the value's first
    element: with ``shape`` one expert's (in, out) and ``first`` e x in x
    out this is expert e's slice of a stacked tensor, drawn alone."""
    import jax.numpy as jnp
    from jax import lax
    n = int(np.prod(shape, dtype=np.int64))
    off, scale = offset_scale(name, shape)
    i = lax.iota(jnp.uint32, n).reshape(shape) + jnp.uint32(first)
    return W._values(i, base, off, scale, jnp).astype(jnp.bfloat16)


def as_served(leaf: str, w):
    """A drawn tensor as the program's parameter dict holds it: a
    zero-centred norm's ``w`` as ``1 + w`` in float32 (the converter's
    rule), everything else as drawn."""
    import jax.numpy as jnp
    return 1.0 + w.astype(jnp.float32) if leaf in ZERO_CENTRED else w


@functools.lru_cache(maxsize=None)
def _draw(shapes: tuple):
    """One jitted program that draws the leaves ``shapes`` ((leaf, shape),
    ...) from a vector of stream ids, as the program holds them."""
    import jax
    return jax.jit(lambda b: {
        leaf: as_served(leaf, make_tensor(b[j], leaf, shape))
        for j, (leaf, shape) in enumerate(shapes)})


def make_params(hf: dict, seed: int, shardings=None) -> dict:
    """All weights on the device, drawn LAYER BY LAYER (one compiled program
    per kind of layer).  ``shardings`` is ``weights.make_params``' argument;
    this configuration is served on one device."""
    if shardings is not None:
        raise NotImplementedError("qwen3_next weights are made on one device")
    bs, idx = bases(hf, seed), layer_indices(hf)
    top = tuple(top_shapes(hf).items())
    params = dict(_draw(top)(np.asarray([bs[idx[n]] for n, _ in top])))
    shapes = layer_shapes(hf)
    for i in range(hf["num_hidden_layers"]):
        leaves = layer_leaves(layer_kind(hf, i))
        got = _draw(tuple((leaf, shapes[leaf]) for leaf in leaves))(
            np.asarray([bs[idx[f"layers.{i}.{leaf}"]] for leaf in leaves]))
        params.update({f"layers.{i}.{leaf}": a for leaf, a in got.items()})
    return params

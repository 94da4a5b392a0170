"""Seeded weights of a MiMo-V2-shaped decoder (``model_type`` mimo_v2: full
and window GQA layers mixed by ``hybrid_layer_pattern``, each kind with its
own KV-head count, keys ``head_dim`` and values ``v_head_dim`` wide, a learned
sink per query head in the window layers; a dense MLP where
``moe_layer_freq`` says 0 and sigmoid-routed experts — no shared one — where
it says 1; an untied head), on ``benchmark/weights.py``'s integer generator —
imported, not copied, so a tensor is the same bits on the TPU, on the CPU
and in numpy:

    value = bfloat16(float32(irwin_hall4(mix(mix(i) ^ base)) + offset) * scale)

Names and layouts are the program's flat parameter dict
(``models/transformer.init_params``, ``models/moe.init_moe_params``):
matrices (in, out), an expert layer's three matrices stacked (experts held,
in, out), ``sink`` (query heads,).

The file this reads is one chip's SHARE of a deployment (its ``deployment``
key): ``n_routed_experts`` counts the experts held here and ``vocab_size``
the rows of the embedding and the head held here, while the router keeps its
published width, ``expert_share["routed"]``.

Distributions (``assumed`` in the configuration's file).  Matrices are
N(0, 1/fan_in) and norms 1 + N(0, 0.1^2), as in ``weights.py``.  ``sink`` is
N(0, 1): a window row's scores are N(0, ~1) over at most 128 keys, so a sink
of that size takes a few percent of the row's mass and a sink that is
dropped moves every window layer's output — it cannot hide inside the
comparison's tolerance.  ``router_bias`` (HF's ``e_score_correction_bias``)
is N(0, 0.04^2) per expert, as for lfm2-24b-a2b and kimi-k2.7-code.
``tok_embed`` is N(0, (1/1024)^2) and the head N(0, 1/d), as kimi's.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import weights as W

ATTN_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo")
DENSE_LEAVES = ("mlp_norm", "w_gate", "w_up", "w_down")
EXPERT_LEAVES = ("mlp_norm", "router", "router_bias", "moe_w_gate",
                 "moe_w_up", "moe_w_down")
STACKED = ("moe_w_gate", "moe_w_up", "moe_w_down")

#: leaf -> (mean, std) where it is not N(0, 1/fan_in)
_DIST = {"sink": (0.0, 1.0), "router_bias": (0.0, 0.04),
         "tok_embed": (0.0, 1.0 / 1024)}


def sizes(hf: dict) -> dict:
    """The widths the layout is made of, from the file's keys."""
    share = hf.get("expert_share") or {}
    hd = hf["head_dim"]
    return {"d": hf["hidden_size"], "v": hf["vocab_size"],
            "nh": hf["num_attention_heads"], "hd": hd,
            "vd": hf["v_head_dim"],
            "nkv": {"full": hf["num_key_value_heads"],
                    "window": hf["swa_num_key_value_heads"]},
            "theta": {"full": float(hf["rope_theta"]),
                      "window": float(hf["swa_rope_theta"])},
            "rotary": int(hd * hf["partial_rotary_factor"]),
            "window": hf["sliding_window"],
            "ff": hf["intermediate_size"], "fe": hf["moe_intermediate_size"],
            "held": hf["n_routed_experts"],
            "E": share.get("routed", hf["n_routed_experts"]),
            "offset": share.get("offset", 0),
            "k": hf["num_experts_per_tok"]}


def layer_kinds(hf: dict, i: int) -> tuple:
    """(attention, mlp) of layer ``i``: ("full" | "window", "dense" |
    "experts")."""
    return ("window" if hf["hybrid_layer_pattern"][i] else "full",
            "experts" if hf["moe_layer_freq"][i] else "dense")


def layer_leaves(attn: str, mlp: str) -> tuple:
    return (ATTN_LEAVES + (("sink",) if attn == "window" else ())
            + (DENSE_LEAVES if mlp == "dense" else EXPERT_LEAVES))


def layer_shapes(hf: dict, attn: str) -> dict:
    """{leaf: shape} of every leaf a layer with ``attn`` attention can
    hold."""
    z = sizes(hf)
    d, nh, hd, vd, fe, held = (z["d"], z["nh"], z["hd"], z["vd"], z["fe"],
                               z["held"])
    nkv = z["nkv"][attn]
    return {"attn_norm": (d,), "mlp_norm": (d,),
            "wq": (d, nh * hd), "wk": (d, nkv * hd), "wv": (d, nkv * vd),
            "wo": (nh * vd, d), "sink": (nh,),
            "w_gate": (d, z["ff"]), "w_up": (d, z["ff"]),
            "w_down": (z["ff"], d),
            "router": (d, z["E"]), "router_bias": (z["E"],),
            "moe_w_gate": (held, d, fe), "moe_w_up": (held, d, fe),
            "moe_w_down": (held, fe, d)}


def top_shapes(hf: dict) -> dict:
    z = sizes(hf)
    return {"tok_embed": (z["v"], z["d"]), "final_norm": (z["d"],),
            "lm_head": (z["d"], z["v"])}


def tensor_specs(hf: dict) -> list:
    """[(name, shape)]: the index in this list keys the generator."""
    specs = list(top_shapes(hf).items())
    for i in range(hf["num_hidden_layers"]):
        attn, mlp = layer_kinds(hf, i)
        shapes = layer_shapes(hf, attn)
        specs += [(f"layers.{i}.{leaf}", shapes[leaf])
                  for leaf in layer_leaves(attn, mlp)]
    return specs


def layer_indices(hf: dict) -> dict:
    return {name: i for i, (name, _) in enumerate(tensor_specs(hf))}


def offset_scale(name: str, shape: tuple) -> tuple:
    leaf = name.rsplit(".", 1)[-1]
    if leaf in _DIST:
        mean, std = _DIST[leaf]
    elif leaf.endswith("norm"):
        mean, std = 1.0, 0.1
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
    """The tensor as a traced jax value; ``base`` its traced stream id.
    ``first`` is the flat index of the value's first element: with ``shape``
    one expert's (in, out) and ``first`` e x in x out this is expert e's
    slice of a stacked tensor, drawn alone."""
    import jax.numpy as jnp
    from jax import lax
    n = int(np.prod(shape, dtype=np.int64))
    off, scale = offset_scale(name, shape)
    i = lax.iota(jnp.uint32, n).reshape(shape) + jnp.uint32(first)
    return W._values(i, base, off, scale, jnp).astype(jnp.bfloat16)


@functools.lru_cache(maxsize=None)
def _draw(shapes: tuple):
    """One jitted program that draws the leaves ``shapes`` ((leaf, shape),
    ...) from a vector of stream ids."""
    import jax
    return jax.jit(lambda b: {leaf: make_tensor(b[j], leaf, shape)
                              for j, (leaf, shape) in enumerate(shapes)})


def make_params(hf: dict, seed: int, shardings=None) -> dict:
    """All weights on the device, drawn LAYER BY LAYER (one compiled program
    per kind of layer; an expert layer here is 0.93 GiB).  ``shardings`` is
    ``weights.make_params``' argument; this configuration is served on one
    device."""
    if shardings is not None:
        raise NotImplementedError("mimo_v2 weights are made on one device")
    bs, idx = bases(hf, seed), layer_indices(hf)
    top = tuple(top_shapes(hf).items())
    params = dict(_draw(top)(np.asarray([bs[idx[n]] for n, _ in top])))
    for i in range(hf["num_hidden_layers"]):
        attn, mlp = layer_kinds(hf, i)
        shapes, leaves = layer_shapes(hf, attn), layer_leaves(attn, mlp)
        got = _draw(tuple((leaf, shapes[leaf]) for leaf in leaves))(
            np.asarray([bs[idx[f"layers.{i}.{leaf}"]] for leaf in leaves]))
        params.update({f"layers.{i}.{leaf}": a for leaf, a in got.items()})
    return params

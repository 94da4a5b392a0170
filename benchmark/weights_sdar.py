"""Seeded weights of an SDAR-MoE-shaped decoder (``model_type`` sdar_moe: a
Qwen3-MoE decoder — GQA attention with a stated head width and q/k norms a
head at a time, softmax-routed experts in every layer, no shared expert, an
untied head — that generates by diffusion over blocks), on
``benchmark/weights.py``'s integer generator — imported, not copied, so a
tensor is the same bits on the TPU, on the CPU and in numpy:

    value = bfloat16(float32(irwin_hall4(mix(mix(i) ^ base)) + offset) * scale)

Names and layouts are the program's flat parameter dict
(``models/transformer.init_params``, ``models/moe.init_moe_params``):
matrices (in, out); an expert layer's three matrices stacked (experts, in,
out).

Distributions (``assumed`` in the configuration's file).  Matrices are
N(0, 1/fan_in) and norms (the per-head q/k norms too) 1 + N(0, 0.1^2), as in
``weights.py``; ``tok_embed`` N(0, (1/1024)^2) and the head N(0, 1/d), as
qwen3-next's.  The ROUTER is the one tensor with a shape of its own: its
column e is N(0, g_e^2 / d) with a gain g_e ~ N(1, 0.25^2) an expert
(``router_gain``, drawn as a tensor of its own and folded into the router as
it is handed over, ``served``: the program holds no such leaf).  A softmax
router has no selection bias to skew: with equal columns every expert's
logit is N(0, 1) on a normed row and the 128 loads differ by sampling noise
alone, which would flatter every grouped product.  A column of gain g is
chosen among the top 8 of 128 with probability Q(1.53 / g): 2.0x the mean
load at g = 1.35, 0.2x at g = 0.7 — at this spread the busiest expert of a
layer takes 2-3x the mean load (read on the chip: PERF.md section 4).
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import weights as W

LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
                "mlp_norm", "router", "router_gain", "moe_w_gate",
                "moe_w_up", "moe_w_down")
STACKED = ("moe_w_gate", "moe_w_up", "moe_w_down")

#: leaf -> (mean, std) where it is not N(0, 1/fan_in)
_DIST = {"tok_embed": (0.0, 1.0 / 1024), "router_gain": (1.0, 0.25)}


def sizes(hf: dict) -> dict:
    """The widths the layout is made of, from the published keys."""
    hd = hf["head_dim"]
    return {"d": hf["hidden_size"], "v": hf["vocab_size"], "hd": hd,
            "nh": hf["num_attention_heads"], "nkv": hf["num_key_value_heads"],
            "theta": float(hf["rope_theta"]),
            "fe": hf["moe_intermediate_size"], "E": hf["num_experts"],
            "k": hf["num_experts_per_tok"]}


def layer_shapes(hf: dict) -> dict:
    """{leaf: shape} of every leaf a layer holds."""
    z = sizes(hf)
    d, nh, nkv, hd, fe, E = (z[k] for k in ("d", "nh", "nkv", "hd", "fe",
                                            "E"))
    return {"attn_norm": (d,), "mlp_norm": (d,),
            "wq": (d, nh * hd), "wk": (d, nkv * hd), "wv": (d, nkv * hd),
            "wo": (nh * hd, d), "q_norm": (hd,), "k_norm": (hd,),
            "router": (d, E), "router_gain": (E,),
            "moe_w_gate": (E, d, fe), "moe_w_up": (E, d, fe),
            "moe_w_down": (E, fe, d)}


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
                  for leaf in LAYER_LEAVES]
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


def served(drawn: dict) -> dict:
    """A layer's drawn leaves as the program's parameter dict holds them:
    ``router_gain`` folded into the router's columns (one float32 multiply,
    rounded to bfloat16 once) and gone; the rest as drawn."""
    import jax.numpy as jnp
    out = {leaf: w for leaf, w in drawn.items() if leaf != "router_gain"}
    out["router"] = (drawn["router"].astype(jnp.float32)
                     * drawn["router_gain"].astype(jnp.float32)[None, :]
                     ).astype(jnp.bfloat16)
    return out


@functools.lru_cache(maxsize=None)
def _draw(shapes: tuple, layer: bool):
    """One jitted program that draws the leaves ``shapes`` ((leaf, shape),
    ...) from a vector of stream ids, as the program holds them."""
    import jax

    def draw(b):
        got = {leaf: make_tensor(b[j], leaf, shape)
               for j, (leaf, shape) in enumerate(shapes)}
        return served(got) if layer else got
    return jax.jit(draw)


def make_params(hf: dict, seed: int, shardings=None) -> dict:
    """All weights on the device, drawn LAYER BY LAYER (one compiled
    program for the six layers: a layer is 1.16 GiB in bf16).  ``shardings``
    is ``weights.make_params``' argument; this configuration is served on
    one device."""
    if shardings is not None:
        raise NotImplementedError("sdar_moe weights are made on one device")
    bs, idx = bases(hf, seed), layer_indices(hf)
    top = tuple(top_shapes(hf).items())
    params = dict(_draw(top, False)(np.asarray([bs[idx[n]] for n, _ in top])))
    shapes = layer_shapes(hf)
    layer = tuple((leaf, shapes[leaf]) for leaf in LAYER_LEAVES)
    for i in range(hf["num_hidden_layers"]):
        got = _draw(layer, True)(np.asarray(
            [bs[idx[f"layers.{i}.{leaf}"]] for leaf in LAYER_LEAVES]))
        params.update({f"layers.{i}.{leaf}": a for leaf, a in got.items()})
    return params

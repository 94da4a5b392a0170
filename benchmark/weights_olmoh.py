"""Seeded weights of an Olmo-Hybrid-shaped decoder (``model_type``
olmo_hybrid: gated-delta-rule layers and full attention layers mixed as
``layer_types`` says, Olmo 2/3's post-norm block, a dense MLP, an untied
head), on ``benchmark/weights.py``'s integer generator — imported, not
copied, so a tensor is the same bits on the TPU, on the CPU and in numpy:

    value = bfloat16(float32(irwin_hall4(mix(mix(i) ^ base)) + offset) * scale)

Names and layouts are the program's flat parameter dict
(``models/transformer.init_params``, ``models/ssm.init_gdn_params``):
matrices (in, out); ``gdn_in`` [q | k | v | g] and ``gdn_ba`` [b | a] — FLA's
six projections side by side, as the converter lays them — and
``gdn_conv_w`` (taps, q | k | v); ``attn_norm`` the norm AFTER the mixer and
``mlp_norm`` the norm AFTER the MLP; ``q_norm`` / ``k_norm`` over a whole
projection (n_heads x head_dim wide).  Every norm's weight is used as
stored (no 1 + w), so a tensor goes to the program as it is drawn.

Distributions (``assumed`` in the configuration's file).  Matrices are N(0,
1/fan_in).  Norm weights 1 + N(0, 0.1^2) (a post-norm's weight IS the scale
of what a sub-layer adds to the stream).  ``gdn_A_log`` N(1, 1) and
``gdn_dt_bias`` N(-4, 1.5^2) a head, as ``weights_gdn.py`` has them: a
token's log-decay spans about -5 ... -0.001 over a layer's 30 heads.
``gdn_conv_w`` N(0, 0.5^2) over the 4 taps.  ``tok_embed`` is N(0, 1): with
post-norm blocks nothing normalises what the first mixer takes, and a
trained model's stream is of the order of its norms' weights — an embedding
a thousandth of that would leave the first layers' q/k norms and L2 norms
dividing by their epsilons.  The head N(0, 1/d).
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import weights as W

LINEAR_LEAVES = ("gdn_in", "gdn_ba", "gdn_conv_w", "gdn_dt_bias",
                 "gdn_A_log", "gdn_norm", "gdn_out", "attn_norm")
FULL_LEAVES = ("wq", "wk", "wv", "wo", "q_norm", "k_norm", "attn_norm")
MLP_LEAVES = ("w_gate", "w_up", "w_down", "mlp_norm")
NORMS = ("attn_norm", "mlp_norm", "final_norm", "q_norm", "k_norm",
         "gdn_norm")

#: leaf -> (mean, std) where it is not N(0, 1/fan_in)
_DIST = {"gdn_A_log": (1.0, 1.0), "gdn_dt_bias": (-4.0, 1.5),
         "gdn_conv_w": (0.0, 0.5), "tok_embed": (0.0, 1.0),
         **{leaf: (1.0, 0.1) for leaf in NORMS}}


def sizes(hf: dict) -> dict:
    """The widths the layout is made of, from the file's keys."""
    hk, hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    dk, dv = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    nh = hf["num_attention_heads"]
    theta = (hf.get("rope_parameters") or {}).get("rope_theta")
    return {"d": hf["hidden_size"], "v": hf["vocab_size"],
            "ff": hf["intermediate_size"],
            "Hk": hk, "Hv": hv, "dk": dk, "dv": dv,
            "K": hf["linear_conv_kernel_dim"],
            "key": hk * dk, "value": hv * dv, "conv": 2 * hk * dk + hv * dv,
            "nh": nh, "nkv": hf["num_key_value_heads"],
            "hd": hf["hidden_size"] // nh,
            "theta": None if theta is None else float(theta),
            "beta_max": 2.0 if hf.get("linear_allow_neg_eigval") else 1.0}


def layer_kind(hf: dict, i: int) -> str:
    """"linear" or "full", as ``layer_types`` says."""
    return "linear" if hf["layer_types"][i] == "linear_attention" else "full"


def layer_leaves(kind: str) -> tuple:
    return (LINEAR_LEAVES if kind == "linear" else FULL_LEAVES) + MLP_LEAVES


def layer_shapes(hf: dict) -> dict:
    """{leaf: shape} of every leaf a layer can hold."""
    z = sizes(hf)
    d, nh, nkv, hd, ff = z["d"], z["nh"], z["nkv"], z["hd"], z["ff"]
    return {"attn_norm": (d,), "mlp_norm": (d,),
            "gdn_in": (d, z["conv"] + z["value"]), "gdn_ba": (d, 2 * z["Hv"]),
            "gdn_conv_w": (z["K"], z["conv"]), "gdn_dt_bias": (z["Hv"],),
            "gdn_A_log": (z["Hv"],), "gdn_norm": (z["dv"],),
            "gdn_out": (z["value"], d),
            "wq": (d, nh * hd), "wk": (d, nkv * hd), "wv": (d, nkv * hd),
            "wo": (nh * hd, d), "q_norm": (nh * hd,), "k_norm": (nkv * hd,),
            "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}


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
    else:                       # a matrix (in, out)
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


def make_tensor(base, name: str, shape: tuple):
    """The tensor as a traced jax value; ``base`` its traced stream id."""
    import jax.numpy as jnp
    from jax import lax
    n = int(np.prod(shape, dtype=np.int64))
    off, scale = offset_scale(name, shape)
    i = lax.iota(jnp.uint32, n).reshape(shape)
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
    per kind of layer).  ``shardings`` is ``weights.make_params``' argument;
    this configuration is served on one device."""
    if shardings is not None:
        raise NotImplementedError("olmo_hybrid weights are made on one "
                                  "device")
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

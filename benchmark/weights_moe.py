"""Seeded weights of an LFM2-MoE decoder (``model_type`` lfm2_moe: gated
short convolutions beside q/k-normed GQA attention, ``num_dense_layers``
dense MLPs and then sigmoid-routed experts, a tied head), on
``benchmark/weights.py``'s integer generator — imported, not copied, so a
tensor is the same bits on the TPU, on the CPU and in numpy:

    value = bfloat16(float32(irwin_hall4(mix(mix(i) ^ base)) + offset) * scale)

Names and layouts are the program's flat parameter dict
(``models/transformer.init_params``, ``models/ssm.init_conv_params``,
``models/moe.init_moe_params``): matrices (in, out), an expert layer's three
matrices stacked (experts, in, out).

Distributions (``assumed`` in the configuration's file).  Matrices are
N(0, 1/fan_in) and norms (the per-head q/k norms too) 1 + N(0, 0.1^2) as in
``weights.py``.  The rest is chosen so that a fault in a new mechanism
cannot hide inside the comparison's tolerance, and so that the expert layer
is loaded the way a trained router loads it:

* ``conv_w`` N(0, 0.5^2) over the 3 taps: the two carried rows weigh as much
  as the present one, so a tail that is dropped or shifted moves the logits.
* ``router_bias`` N(0, 0.04^2) per expert.  The router's scores are
  sigmoid(N(0, 1)), top-4 of 64 chosen by score + bias: at this spread the
  busiest expert of a layer takes 2-3x the mean load and the idlest under
  half (measured: PERF.md section 4).  A uniform router would flatter every
  grouped product; a bias of 0 would also make "the bias left out of the
  selection" a fault no comparison could see.
* ``tok_embed`` N(0, (1/1024)^2).  The head is the embedding, transposed:
  with N(0, 1) rows the token just read would out-vote the layers' work at
  its own logit and every served token would repeat the prompt's last.  At
  1/1024 layer 0's norm still sees the token, and what the head reads is
  the layers' work (as for granite-4.0-h-micro, ``weights_hybrid.py``).
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import weights as W

CONV_LEAVES = ("attn_norm", "conv_in", "conv_w", "conv_out")
ATTN_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm")
DENSE_LEAVES = ("mlp_norm", "w_gate", "w_up", "w_down")
EXPERT_LEAVES = ("mlp_norm", "router", "router_bias", "moe_w_gate",
                 "moe_w_up", "moe_w_down")

#: leaf -> (mean, std) where it is not N(0, 1/fan_in)
_DIST = {"conv_w": (0.0, 0.5), "router_bias": (0.0, 0.04),
         "tok_embed": (0.0, 1.0 / 1024)}


def sizes(hf: dict) -> dict:
    """The widths the layout is made of, from the published keys."""
    d = hf["hidden_size"]
    hd = hf.get("head_dim") or d // hf["num_attention_heads"]
    return {"d": d, "v": hf["vocab_size"], "hd": hd,
            "nq": hf["num_attention_heads"] * hd,
            "nkv": hf["num_key_value_heads"] * hd,
            "K": hf["conv_L_cache"], "ff": hf["intermediate_size"],
            "fe": hf["moe_intermediate_size"], "E": hf["num_experts"],
            "k": hf["num_experts_per_tok"]}


def layer_kinds(hf: dict, i: int) -> tuple:
    """(mixer, mlp) of layer ``i``: ("conv" | "attention", "dense" |
    "experts")."""
    mixer = "attention" if hf["layer_types"][i] == "full_attention" else "conv"
    return mixer, "dense" if i < hf["num_dense_layers"] else "experts"


def layer_leaves(mixer: str, mlp: str) -> tuple:
    return ((CONV_LEAVES if mixer == "conv" else ATTN_LEAVES)
            + (DENSE_LEAVES if mlp == "dense" else EXPERT_LEAVES))


def layer_shapes(hf: dict) -> dict:
    """{leaf: shape} of every leaf a layer of any kind can hold."""
    z = sizes(hf)
    d, ff, fe, E = z["d"], z["ff"], z["fe"], z["E"]
    return {"attn_norm": (d,), "mlp_norm": (d,),
            "conv_in": (d, 3 * d), "conv_w": (z["K"], d), "conv_out": (d, d),
            "wq": (d, z["nq"]), "wk": (d, z["nkv"]), "wv": (d, z["nkv"]),
            "wo": (z["nq"], d), "q_norm": (z["hd"],), "k_norm": (z["hd"],),
            "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d),
            "router": (d, E), "router_bias": (E,),
            "moe_w_gate": (E, d, fe), "moe_w_up": (E, d, fe),
            "moe_w_down": (E, fe, d)}


def tensor_specs(hf: dict) -> list:
    """[(name, shape)]: the index in this list keys the generator."""
    z = sizes(hf)
    shapes = layer_shapes(hf)
    specs = [("tok_embed", (z["v"], z["d"])), ("final_norm", (z["d"],))]
    for i in range(hf["num_hidden_layers"]):
        specs += [(f"layers.{i}.{leaf}", shapes[leaf])
                  for leaf in layer_leaves(*layer_kinds(hf, i))]
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
    ``first`` (a traced or plain integer) is the flat index of the value's
    first element: with ``shape`` one expert's (in, out) and ``first`` e x in
    x out this is expert e's slice of a stacked tensor, drawn alone."""
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
    per kind of layer): an expert layer is 1.125 GiB in bf16, and one call
    that held twelve layers' temporaries beside 12 GiB of results would not
    fit the chip.  ``shardings`` is ``weights.make_params``' argument; this
    configuration is served on one device."""
    if shardings is not None:
        raise NotImplementedError("lfm2_moe weights are made on one device")
    bs, idx = bases(hf, seed), layer_indices(hf)
    shapes = layer_shapes(hf)
    z = sizes(hf)
    top = (("tok_embed", (z["v"], z["d"])), ("final_norm", (z["d"],)))
    params = dict(_draw(top)(np.asarray([bs[idx[n]] for n, _ in top])))
    for i in range(hf["num_hidden_layers"]):
        leaves = layer_leaves(*layer_kinds(hf, i))
        got = _draw(tuple((leaf, shapes[leaf]) for leaf in leaves))(
            np.asarray([bs[idx[f"layers.{i}.{leaf}"]] for leaf in leaves]))
        params.update({f"layers.{i}.{leaf}": a for leaf, a in got.items()})
    return params

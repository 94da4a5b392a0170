"""Seeded weights of a DeepSeek-V3-shaped decoder (``model_type`` kimi_k2 /
deepseek_v3: latent attention in every layer, ``first_k_dense_replace`` dense
MLPs and then sigmoid-routed experts beside a shared expert, an untied
head), on ``benchmark/weights.py``'s integer generator — imported, not
copied, so a tensor is the same bits on the TPU, on the CPU and in numpy
(``weights_moe.make_tensor``):

    value = bfloat16(float32(irwin_hall4(mix(mix(i) ^ base)) + offset) * scale)

Names and layouts are the program's flat parameter dict
(``models/transformer.init_params``, ``models/mla.init_mla_params``,
``models/moe.init_moe_params``): matrices (in, out), an expert layer's
three matrices stacked (experts held, in, out).

The file this reads is one chip's SHARE of a deployment (its ``deployment``
key): ``n_routed_experts`` counts the experts held here and ``vocab_size``
the rows of the embedding and the head held here, while the router keeps its
published width, ``expert_share["routed"]``.  The experts drawn are the
held ones, as one tensor of their own.

Distributions (``assumed`` in the configuration's file).  Matrices are
N(0, 1/fan_in) and norms (the two inside the attention too) 1 + N(0,
0.1^2), as in ``weights.py``.  ``router_bias`` (HF's
``e_score_correction_bias``) is N(0, 0.04^2) per expert as for
lfm2-24b-a2b: sigmoid(N(0, 1)) scores, top-8 of 384 by score + bias, so that
the bias moves which experts are chosen and "the bias left out" is a fault
the comparison can see.  ``tok_embed`` is N(0, (1/1024)^2): at N(0, 1) the
residual stream would be the token's own row and five layers' work noise
beside it; the head is untied, N(0, 1/d).
"""

from __future__ import annotations

import numpy as np

from benchmark import weights as W
# one tensor from its stream id — in numpy, traced (an expert's slice of a
# stack drawn alone), or a layer's leaves in one jitted program: the same
# functions and the same table of distributions as lfm2's (``router_bias``
# N(0, 0.04^2), ``tok_embed`` N(0, 1/1024^2), norms 1 + N(0, 0.1^2),
# matrices N(0, 1/fan_in))
from benchmark.weights_moe import (_draw, make_tensor,          # noqa: F401
                                   make_tensor_np, offset_scale)

ATTN_LEAVES = ("attn_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a",
               "kv_a_norm", "wkv_b", "wo")
DENSE_LEAVES = ("mlp_norm", "w_gate", "w_up", "w_down")
EXPERT_LEAVES = ("mlp_norm", "router", "router_bias", "moe_w_gate",
                 "moe_w_up", "moe_w_down", "shared_w_gate", "shared_w_up",
                 "shared_w_down")
STACKED = ("moe_w_gate", "moe_w_up", "moe_w_down")


def sizes(hf: dict) -> dict:
    """The widths the layout is made of, from the file's keys."""
    share = hf.get("expert_share") or {}
    return {"d": hf["hidden_size"], "v": hf["vocab_size"],
            "nh": hf["num_attention_heads"],
            "dq": hf["q_lora_rank"], "dc": hf["kv_lora_rank"],
            "dn": hf["qk_nope_head_dim"], "dr": hf["qk_rope_head_dim"],
            "dv": hf["v_head_dim"], "ff": hf["intermediate_size"],
            "fe": hf["moe_intermediate_size"],
            "fs": hf["moe_intermediate_size"] * hf["n_shared_experts"],
            "held": hf["n_routed_experts"],
            "E": share.get("routed", hf["n_routed_experts"]),
            "offset": share.get("offset", 0),
            "k": hf["num_experts_per_tok"]}


def mlp_kind(hf: dict, i: int) -> str:
    """"dense" for the ``first_k_dense_replace`` leading layers, then
    "experts" (``moe_layer_freq`` is 1 in every published config of the
    family)."""
    return "dense" if i < hf["first_k_dense_replace"] else "experts"


def layer_leaves(mlp: str) -> tuple:
    return ATTN_LEAVES + (DENSE_LEAVES if mlp == "dense" else EXPERT_LEAVES)


def layer_shapes(hf: dict) -> dict:
    """{leaf: shape} of every leaf a layer of either kind can hold."""
    z = sizes(hf)
    d, nh, dc, fe, fs, held = (z["d"], z["nh"], z["dc"], z["fe"], z["fs"],
                               z["held"])
    return {"attn_norm": (d,), "mlp_norm": (d,),
            "wq_a": (d, z["dq"]), "q_a_norm": (z["dq"],),
            "wq_b": (z["dq"], nh * (z["dn"] + z["dr"])),
            "wkv_a": (d, dc + z["dr"]), "kv_a_norm": (dc,),
            "wkv_b": (dc, nh * (z["dn"] + z["dv"])),
            "wo": (nh * z["dv"], d),
            "w_gate": (d, z["ff"]), "w_up": (d, z["ff"]),
            "w_down": (z["ff"], d),
            "router": (d, z["E"]), "router_bias": (z["E"],),
            "moe_w_gate": (held, d, fe), "moe_w_up": (held, d, fe),
            "moe_w_down": (held, fe, d),
            "shared_w_gate": (d, fs), "shared_w_up": (d, fs),
            "shared_w_down": (fs, d)}


def top_shapes(hf: dict) -> dict:
    z = sizes(hf)
    return {"tok_embed": (z["v"], z["d"]), "final_norm": (z["d"],),
            "lm_head": (z["d"], z["v"])}


def tensor_specs(hf: dict) -> list:
    """[(name, shape)]: the index in this list keys the generator."""
    shapes = layer_shapes(hf)
    specs = list(top_shapes(hf).items())
    for i in range(hf["num_hidden_layers"]):
        specs += [(f"layers.{i}.{leaf}", shapes[leaf])
                  for leaf in layer_leaves(mlp_kind(hf, i))]
    return specs


def layer_indices(hf: dict) -> dict:
    return {name: i for i, (name, _) in enumerate(tensor_specs(hf))}


def bases(hf: dict, seed: int) -> np.ndarray:
    return np.asarray([W._base(seed, i)
                       for i in range(len(tensor_specs(hf)))], np.uint32)


def make_params(hf: dict, seed: int, shardings=None) -> dict:
    """All weights on the device, drawn LAYER BY LAYER (one compiled program
    per kind of layer; an expert layer here is 1.26 GiB).  ``shardings`` is
    ``weights.make_params``' argument; this configuration is served on one
    device."""
    if shardings is not None:
        raise NotImplementedError("kimi_k2 weights are made on one device")
    bs, idx = bases(hf, seed), layer_indices(hf)
    shapes = layer_shapes(hf)
    top = tuple(top_shapes(hf).items())
    params = dict(_draw(top)(np.asarray([bs[idx[n]] for n, _ in top])))
    for i in range(hf["num_hidden_layers"]):
        leaves = layer_leaves(mlp_kind(hf, i))
        got = _draw(tuple((leaf, shapes[leaf]) for leaf in leaves))(
            np.asarray([bs[idx[f"layers.{i}.{leaf}"]] for leaf in leaves]))
        params.update({f"layers.{i}.{leaf}": a for leaf, a in got.items()})
    return params

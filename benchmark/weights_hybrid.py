"""Seeded weights of a hybrid decoder (``model_type`` granitemoehybrid:
Mamba-2 layers beside GQA attention, a gated MLP after every mixer, a tied
head), on ``benchmark/weights.py``'s integer generator — imported, not
copied, so a tensor is the same bits on the TPU, on the CPU and in numpy:

    value = bfloat16(float32(irwin_hall4(mix(mix(i) ^ base)) + offset) * scale)

Names and (in, out) layouts are the program's flat parameter dict
(``models/transformer.init_params``, ``models/ssm.init_mamba_params``).

Distributions (mean, std of the near-normal sum of four bytes; ``assumed`` in
the configuration's file).  Matrices are N(0, 1/fan_in) and norms 1 +
N(0, 0.1^2) as in ``weights.py``.  The rest is chosen so that a fault in the
new mechanism cannot hide inside the comparison's tolerance:

* ``ssm_A_log`` N(1, 1) and ``ssm_dt_bias`` N(-4, 1.5^2) per head: with
  the projection's own N(0, 1) on top, a step's log-decay Δ·A spans about
  -5 … -0.001 over the 64 heads of a layer — heads that forget within a few
  tokens beside heads that remember hundreds, so both a dropped state and a
  state carried wrongly across a chunk move the logits.
* ``ssm_D`` 1 + N(0, 0.1^2) (the published initialisation is 1),
  ``ssm_conv_w`` N(0, 0.5^2) over the 4 taps, ``ssm_conv_b`` N(0, 0.1^2).
* ``tok_embed`` N(0, (1/1024)^2).  The head is the embedding, transposed:
  with N(0, 1) rows and ``embedding_multiplier`` 12 the token just read
  would out-vote the 40 layers' work at its own logit by orders of magnitude
  and every served token would be the prompt's last, whatever the mixers
  compute.  At 1/1024 the layers see the token (layer 0 normalises its
  input) and the residual the head reads is the layers' work.
"""

from __future__ import annotations

import numpy as np

from benchmark import weights as W

MAMBA_LEAVES = ("attn_norm", "ssm_in", "ssm_conv_w", "ssm_conv_b",
                "ssm_dt_bias", "ssm_A_log", "ssm_D", "ssm_norm", "ssm_out",
                "mlp_norm", "w_gate", "w_up", "w_down")
ATTN_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
               "w_up", "w_down")

#: leaf -> (mean, std) where it is not N(0, 1/fan_in)
_DIST = {"ssm_A_log": (1.0, 1.0), "ssm_dt_bias": (-4.0, 1.5),
         "ssm_D": (1.0, 0.1), "ssm_conv_w": (0.0, 0.5),
         "ssm_conv_b": (0.0, 0.1), "tok_embed": (0.0, 1.0 / 1024)}


def sizes(hf: dict) -> dict:
    """The widths the layout is made of, from the published keys."""
    d = hf["hidden_size"]
    heads, p, n = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"]
    hd = hf.get("head_dim") or d // hf["num_attention_heads"]
    inner = heads * p
    return {"d": d, "v": hf["vocab_size"], "H": heads, "P": p, "N": n,
            "K": hf["mamba_d_conv"], "inner": inner, "conv": inner + 2 * n,
            "in": 2 * inner + 2 * n + heads, "hd": hd,
            "nq": hf["num_attention_heads"] * hd,
            "nkv": hf["num_key_value_heads"] * hd,
            "ff": hf["shared_intermediate_size"]}


def layer_shapes(hf: dict, kind: str) -> dict:
    z = sizes(hf)
    d, ff = z["d"], z["ff"]
    mlp = {"mlp_norm": (d,), "w_gate": (d, ff), "w_up": (d, ff),
           "w_down": (ff, d)}
    if kind == "mamba":
        return {"attn_norm": (d,), "ssm_in": (d, z["in"]),
                "ssm_conv_w": (z["K"], z["conv"]), "ssm_conv_b": (z["conv"],),
                "ssm_dt_bias": (z["H"],), "ssm_A_log": (z["H"],),
                "ssm_D": (z["H"],), "ssm_norm": (z["inner"],),
                "ssm_out": (z["inner"], d), **mlp}
    return {"attn_norm": (d,), "wq": (d, z["nq"]), "wk": (d, z["nkv"]),
            "wv": (d, z["nkv"]), "wo": (z["nq"], d), **mlp}


def tensor_specs(hf: dict) -> list:
    """[(name, shape)]: the index in this list keys the generator."""
    z = sizes(hf)
    specs = [("tok_embed", (z["v"], z["d"])), ("final_norm", (z["d"],))]
    for i, kind in enumerate(hf["layer_types"]):
        leaves = MAMBA_LEAVES if kind == "mamba" else ATTN_LEAVES
        shapes = layer_shapes(hf, kind)
        specs += [(f"layers.{i}.{leaf}", shapes[leaf]) for leaf in leaves]
    return specs


def layer_indices(hf: dict) -> dict:
    return {name: i for i, (name, _) in enumerate(tensor_specs(hf))}


def _offset_scale(name: str, shape: tuple) -> tuple:
    leaf = name.rsplit(".", 1)[-1]
    if leaf in _DIST:
        mean, std = _DIST[leaf]
    elif leaf.endswith("norm"):
        mean, std = 1.0, 0.1
    else:
        mean, std = 0.0, float(shape[0]) ** -0.5
    scale = np.float32(std / W._SIGMA)
    return int(round(mean / float(scale))) - W._MEAN, scale


def bases(hf: dict, seed: int) -> np.ndarray:
    return np.asarray([W._base(seed, i)
                       for i in range(len(tensor_specs(hf)))], np.uint32)


def make_tensor_np(seed: int, index: int, name: str, shape: tuple):
    """The tensor in plain numpy: the definition the tests pin."""
    import ml_dtypes
    n = int(np.prod(shape, dtype=np.int64))
    off, scale = _offset_scale(name, shape)
    with np.errstate(over="ignore"):
        vals = W._values(np.arange(n, dtype=np.uint32),
                         np.uint32(W._base(seed, index)), off, scale, np)
    return vals.astype(ml_dtypes.bfloat16).reshape(shape)


def make_tensor(base, name: str, shape: tuple):
    """The tensor as a traced jax value; ``base`` its traced stream id."""
    import jax.numpy as jnp
    from jax import lax
    n = int(np.prod(shape, dtype=np.int64))
    off, scale = _offset_scale(name, shape)
    i = lax.iota(jnp.uint32, n).reshape(shape)
    return W._values(i, base, off, scale, jnp).astype(jnp.bfloat16)


def make_params(hf: dict, seed: int, shardings=None) -> dict:
    """All weights on the device in one jitted call (``shardings`` is
    ``weights.make_params``' argument; a hybrid is served on one device)."""
    import jax
    if shardings is not None:
        raise NotImplementedError("hybrid weights are made on one device")
    specs = tensor_specs(hf)

    def build(b):
        return {name: make_tensor(b[i], name, shape)
                for i, (name, shape) in enumerate(specs)}

    return jax.jit(build)(bases(hf, seed))

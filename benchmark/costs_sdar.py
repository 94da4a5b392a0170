"""Operations and bytes of an SDAR-MoE decoder's calls (q/k-normed GQA
attention and softmax-routed experts in every layer, an untied head;
generation by diffusion over blocks: a step forwards ``rows`` rows a slot),
from shapes and from what the router's load histogram says was touched: the
yardstick's side of ``bd_step_roofline``, ``block_attn_roofline`` and
``bd_prefill_mfu``.  An expert layer's grouped products are
``costs_moe.experts_cost`` — called, not copied."""

from __future__ import annotations

from benchmark import costs_moe
from benchmark.weights_sdar import sizes


def param_count(hf: dict) -> dict:
    """Parameters by part.  ``expert`` is ONE expert's three matrices;
    ``layer_rest`` what a layer holds beside its experts (attention with its
    two norms and the per-head q/k norms, the router, the MLP's norm)."""
    z = sizes(hf)
    d, nq, nkv = z["d"], z["nh"] * z["hd"], z["nkv"] * z["hd"]
    p = {"attn": d * nq + 2 * d * nkv + nq * d,
         "expert": 3 * d * z["fe"], "router": d * z["E"],
         "embed": z["v"] * d, "head": d * z["v"],
         "layers": hf["num_hidden_layers"]}
    p["layer_rest"] = p["attn"] + p["router"] + 2 * d + 2 * z["hd"]
    p["layer"] = p["layer_rest"] + z["E"] * p["expert"]
    p["total"] = p["layers"] * p["layer"] + p["embed"] + p["head"] + d
    return p


def kv_bytes_per_token(hf: dict, dtype_bytes: int = 2) -> int:
    """K and V of every layer."""
    z = sizes(hf)
    return 2 * hf["num_hidden_layers"] * z["nkv"] * z["hd"] * dtype_bytes


def attn_cost(hf: dict, slots: float, live_tokens: float,
              rows: int) -> tuple:
    """(bytes, operations) of ONE ``strom_paged_attn`` call (one layer, one
    forward of ``rows`` rows a slot): every live K and V row of the layer's
    KV heads read ONCE for all the slot's rows, the slots' queries in and
    outputs out; q.k and p.v over the live rows for every query head of
    every row."""
    z = sizes(hf)
    kv = 2 * z["nkv"] * z["hd"] * 2 * live_tokens
    io = 2 * slots * rows * z["nh"] * z["hd"] * 2
    return kv + io, 4.0 * rows * z["nh"] * z["hd"] * live_tokens


def step_bytes(hf: dict, slots: float, live_tokens: float, touched: float,
               rows: int) -> float:
    """Bytes one forward of ``rows`` rows a slot over ``slots`` sequences
    must move: everything outside the experts and the head once, ``touched``
    experts (summed over the layers, from the load histogram) once each with
    their pairs' activations (``costs_moe.experts_cost``), one embedding row
    a row, and the live keys and values of every layer (``live_tokens`` in
    total) once for all the rows of a slot.  The K/V rows written, the
    float32 logits and the other activations are left out."""
    z, p = sizes(hf), param_count(hf)
    pairs = slots * rows * z["k"] * p["layers"]
    experts, _ = costs_moe.experts_cost(hf, pairs, touched)
    outside = (p["layers"] * p["layer_rest"] + p["head"] + z["d"]
               + slots * rows * z["d"]) * 2
    return outside + experts + live_tokens * kv_bytes_per_token(hf)


def step_flops(hf: dict, slots: float, live_tokens: float,
               rows: int) -> float:
    """Multiply-adds x 2 of one such forward: the matrices outside the
    experts and the head on every row, k experts a row in every layer, and
    attention's q.k and p.v over the live positions for every row."""
    z, p = sizes(hf), param_count(hf)
    n = slots * rows
    _, experts = costs_moe.experts_cost(hf, n * z["k"] * p["layers"], 0)
    mats = 2.0 * n * (p["layers"] * (p["attn"] + p["router"]) + p["head"])
    attn = p["layers"] * attn_cost(hf, slots, live_tokens, rows)[1]
    return mats + experts + attn


def prefill_flops(hf: dict, rows: int) -> float:
    """Model operations of ONE prompt of ``rows`` tokens through the
    admission: every matrix on every row (the head on one), k experts a row,
    and q.k and p.v over the causal half counted once (the block-causal
    mask shows a row at most three rows more)."""
    z, p = sizes(hf), param_count(hf)
    _, experts = costs_moe.experts_cost(hf, rows * z["k"] * p["layers"], 0)
    mats = 2.0 * (rows * p["layers"] * (p["attn"] + p["router"])
                  + p["head"])
    attn = 4.0 * p["layers"] * z["nh"] * z["hd"] * rows * (rows + 1) / 2
    return mats + experts + attn

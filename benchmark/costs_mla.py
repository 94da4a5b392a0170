"""Operations and bytes of a DeepSeek-V3-shaped decoder's calls (latent
attention in every layer, a leading dense MLP, then routed experts — the
share of them held here — beside a shared expert, an untied head), from
shapes and from what the program's counters say was live and touched: the
yardstick's side of ``mla_attn_roofline``, ``mla_step_roofline`` and
``prefill_mfu``.  ``costs.py`` counts a dense decoder, ``costs_hybrid.py`` a
Mamba-2 hybrid, ``costs_moe.py`` LFM2-MoE; all stay as they are."""

from __future__ import annotations

from benchmark.weights_mla import mlp_kind, sizes


def param_count(hf: dict) -> dict:
    """Parameters by part.  ``attn`` is one layer's latent attention with
    its three norms; ``expert`` ONE expert's three matrices;
    ``expert_layer_rest`` what an expert layer holds beside its routed
    experts and its attention (router, bias, the MLP's norm, the shared
    expert)."""
    z = sizes(hf)
    d, nh = z["d"], z["nh"]
    kinds = [mlp_kind(hf, i) for i in range(hf["num_hidden_layers"])]
    p = {"attn": (d * z["dq"] + z["dq"] + z["dq"] * nh * (z["dn"] + z["dr"])
                  + d * (z["dc"] + z["dr"]) + z["dc"]
                  + z["dc"] * nh * (z["dn"] + z["dv"]) + nh * z["dv"] * d
                  + d),
         "dense_mlp": 3 * d * z["ff"] + d,
         "expert": 3 * d * z["fe"],
         "expert_layer_rest": d * z["E"] + z["E"] + d + 3 * d * z["fs"],
         "embed": z["v"] * d, "head": d * z["v"],
         "n_layers": len(kinds),
         "n_dense": sum(k == "dense" for k in kinds),
         "n_expert_layers": sum(k == "experts" for k in kinds)}
    p["outside_experts"] = (
        p["n_layers"] * p["attn"] + p["n_dense"] * p["dense_mlp"]
        + p["n_expert_layers"] * p["expert_layer_rest"] + p["head"] + d)
    p["total"] = (p["outside_experts"] + p["embed"]
                  + p["n_expert_layers"] * z["held"] * p["expert"])
    return p


def latent_bytes_per_token(hf: dict, dtype_bytes: int = 2) -> int:
    """What one token costs the pool: a latent row in every layer."""
    z = sizes(hf)
    return hf["num_hidden_layers"] * (z["dc"] + z["dr"]) * dtype_bytes


def mla_attn_cost(hf: dict, slots: int, live_tokens: float) -> tuple:
    """(bytes, operations) of ONE call of the absorbed-form kernel (one
    layer, every slot): each live latent row read once — it is key and value
    at once — the queries in and the outputs out; per live row and head a
    score over the row's whole width and a weighted sum of its latent
    part."""
    z = sizes(hf)
    width = z["dc"] + z["dr"]
    nbytes = (live_tokens * width + slots * z["nh"] * (width + z["dc"])) * 2
    return nbytes, 2.0 * z["nh"] * (width + z["dc"]) * live_tokens


def decode_step_bytes(hf: dict, slots: int, live_tokens: float,
                      touched: float) -> float:
    """Bytes one decode step over ``slots`` sequences must read: everything
    outside the routed experts once (the head among it), ``touched`` experts
    (summed over the expert layers, from the load histogram) once each, one
    embedding row per slot, and every live latent row of every layer.  The
    rows written and the activations are left out."""
    z, p = sizes(hf), param_count(hf)
    weights = (p["outside_experts"] + touched * p["expert"]
               + slots * z["d"]) * 2
    return weights + live_tokens * latent_bytes_per_token(hf)


def decode_step_flops(hf: dict, slots: int, live_tokens: float,
                      pairs: float) -> float:
    """Multiply-adds x 2 of one decode step: the matrices outside the routed
    experts on ``slots`` rows, ``pairs`` (row, expert) pairs computed here
    (summed over the expert layers), and the absorbed attention over the
    live rows of every layer."""
    p = param_count(hf)
    mats = 2.0 * (slots * p["outside_experts"] + pairs * p["expert"])
    return mats + hf["num_hidden_layers"] * mla_attn_cost(
        hf, slots, live_tokens)[1]


def prefill_flops(hf: dict, rows: int, pairs: float) -> float:
    """Model operations of ONE prompt of ``rows`` tokens through the prefill
    in its expanded form: every matrix outside the routed experts on every
    row (the head on one), ``pairs`` (row, expert) pairs computed here
    (summed over the expert layers), and attention's q.k (nope + rope wide)
    and p.v (v wide) over the causal half, counted once."""
    z, p = sizes(hf), param_count(hf)
    mats = 2.0 * (rows * (p["outside_experts"] - p["head"]) + p["head"]
                  + pairs * p["expert"])
    attn = (2.0 * hf["num_hidden_layers"] * z["nh"]
            * (z["dn"] + z["dr"] + z["dv"]) * rows * (rows + 1) / 2)
    return mats + attn

"""Operations and bytes of an LFM2-MoE decoder's calls (gated short convs
beside GQA attention, dense MLPs then routed experts, a tied head), from
shapes and from what the router's load histogram says was touched: the
yardstick's side of ``moe_step_roofline``, ``moe_experts_roofline`` and
``moe_prefill_experts_roofline``.  ``costs.py`` counts a dense decoder,
``costs_hybrid.py`` a Mamba-2 hybrid; both stay as they are."""

from __future__ import annotations

from benchmark.weights_moe import layer_kinds, sizes


def param_count(hf: dict) -> dict:
    """Parameters by part.  ``expert`` is ONE expert's three matrices;
    ``expert_layer_rest`` what an expert layer holds beside its experts
    (router, selection bias, the MLP's norm)."""
    z = sizes(hf)
    d = z["d"]
    kinds = [layer_kinds(hf, i) for i in range(hf["num_hidden_layers"])]
    p = {"conv_op": d * 3 * d + z["K"] * d + d * d + d,
         "attn_op": d * z["nq"] + 2 * d * z["nkv"] + z["nq"] * d
         + 2 * z["hd"] + d,
         "dense_mlp": 3 * d * z["ff"] + d,
         "expert": 3 * d * z["fe"],
         "expert_layer_rest": d * z["E"] + z["E"] + d,
         "embed": z["v"] * d,
         "n_conv": sum(m == "conv" for m, _ in kinds),
         "n_attn": sum(m == "attention" for m, _ in kinds),
         "n_dense": sum(f == "dense" for _, f in kinds),
         "n_expert_layers": sum(f == "experts" for _, f in kinds)}
    p["outside_experts"] = (
        p["n_conv"] * p["conv_op"] + p["n_attn"] * p["attn_op"]
        + p["n_dense"] * p["dense_mlp"]
        + p["n_expert_layers"] * p["expert_layer_rest"] + p["embed"] + d)
    p["total"] = (p["outside_experts"]
                  + p["n_expert_layers"] * z["E"] * p["expert"])
    return p


def kv_bytes_per_token(hf: dict, dtype_bytes: int = 2) -> int:
    """K and V of the attention layers only."""
    return 2 * param_count(hf)["n_attn"] * sizes(hf)["nkv"] * dtype_bytes


def state_bytes_per_slot(hf: dict, dtype_bytes: int = 2) -> int:
    """What one sequence's conv layers carry, whatever its length: the last
    K - 1 rows of B ⊙ u per layer."""
    z = sizes(hf)
    return param_count(hf)["n_conv"] * (z["K"] - 1) * z["d"] * dtype_bytes


def experts_cost(hf: dict, rows: float, touched: float) -> tuple:
    """(bytes, operations) of ONE expert layer's grouped products (gate, up
    and down) over ``rows`` (row, expert) pairs that fall on ``touched``
    experts: each touched expert's three matrices read once, the pairs'
    activations in and out, two operations per multiply-add of every pair
    (tile padding is the kernel's, not the algorithm's).  Needs the two
    widths only, under the keys every expert configuration publishes them
    by, so it counts a device that holds a share of the router's experts
    (``n_routed_experts`` files: the pairs and the touched experts are the
    held ones') as it counts one that holds all (``num_experts``)."""
    d, fe = hf["hidden_size"], hf["moe_intermediate_size"]
    expert = 3 * d * fe
    nbytes = (touched * expert + rows * (2 * d + 2 * fe)) * 2
    return nbytes, 2.0 * rows * expert


def decode_step_bytes(hf: dict, slots: int, live_tokens: float,
                      touched: float) -> float:
    """Bytes one decode step over ``slots`` sequences must move: everything
    outside the experts and the tied head once, ``touched`` experts (summed
    over the expert layers, from the load histogram) once each, one
    embedding row per slot, every slot's conv tails read and written, and
    the live keys and values of the attention layers (``live_tokens`` in
    total).  The K/V rows written and the activations are left out."""
    z, p = sizes(hf), param_count(hf)
    weights = (p["outside_experts"] + touched * p["expert"]
               + slots * z["d"]) * 2
    return (weights + 2 * slots * state_bytes_per_slot(hf)
            + live_tokens * kv_bytes_per_token(hf))


def decode_step_flops(hf: dict, slots: int, live_tokens: float) -> float:
    """Multiply-adds x 2 of one decode step: the matrices outside the
    experts on ``slots`` rows (the tied head among them; the embedding is
    counted once, as the head), k experts per row in every expert layer,
    and attention's q.k and p.v over the live positions."""
    z, p = sizes(hf), param_count(hf)
    mats = 2.0 * slots * (p["outside_experts"]
                          + p["n_expert_layers"] * z["k"] * p["expert"])
    attn = 4.0 * p["n_attn"] * z["nq"] * live_tokens
    return mats + attn

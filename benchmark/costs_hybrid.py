"""Operations and bytes of a hybrid decoder's calls (Mamba-2 layers beside
attention, a tied head), from shapes alone: the yardstick's side of
``hybrid_step_roofline``, ``ssm_update_roofline`` and ``ssm_scan_roofline``.
``benchmark/costs.py`` counts a dense decoder and stays as it is."""

from __future__ import annotations

from benchmark.weights_hybrid import sizes


def param_count(hf: dict) -> dict:
    z = sizes(hf)
    d, ff = z["d"], z["ff"]
    mlp = 3 * d * ff
    mamba = (d * z["in"] + z["inner"] * d + mlp        # in, out, MLP
             + (z["K"] + 1) * z["conv"]                # conv taps and bias
             + 3 * z["H"] + z["inner"] + 2 * d)        # dt_bias, A_log, D; norms
    attn = d * z["nq"] + 2 * d * z["nkv"] + z["nq"] * d + mlp + 2 * d
    kinds = hf["layer_types"]
    n_mamba = sum(k == "mamba" for k in kinds)
    embed = z["v"] * d
    return {"mamba_layer": mamba, "attn_layer": attn, "embed": embed,
            "n_mamba": n_mamba, "n_attn": len(kinds) - n_mamba,
            "total": (n_mamba * mamba + (len(kinds) - n_mamba) * attn
                      + embed + d)}


def state_bytes_per_slot(hf: dict, conv_bytes: int = 2) -> int:
    """What one sequence's recurrent layers carry, whatever its length: S in
    float32 and the conv tail (K-1 rows) per mamba layer."""
    z = sizes(hf)
    n_mamba = param_count(hf)["n_mamba"]
    return n_mamba * (z["H"] * z["P"] * z["N"] * 4
                      + (z["K"] - 1) * z["conv"] * conv_bytes)


def kv_bytes_per_token(hf: dict, dtype_bytes: int = 2) -> int:
    """K and V of the attention layers only."""
    z = sizes(hf)
    return 2 * param_count(hf)["n_attn"] * z["nkv"] * dtype_bytes


def ssm_update_cost(hf: dict, slots: int) -> tuple:
    """(bytes, operations) of ONE ``strom_ssm_update`` call (one layer, one
    token of every slot): the state read and written, plus the step's
    vectors; per state element decay, input, add, and C's multiply-add."""
    z = sizes(hf)
    elems = slots * z["H"] * z["P"] * z["N"]
    vectors = slots * (3 * z["H"] * z["P"] + 2 * z["N"]) * 4   # dA, Δx, y; B, C
    return 2 * elems * 4 + vectors, 5.0 * elems


def ssm_scan_cost(hf: dict, rows: int) -> tuple:
    """(bytes, operations) of ONE ``strom_ssm_scan`` call over ``rows``
    (padded) rows of one sequence (one layer).  Per chunk of Q rows: C Bᵀ
    once (2 Q² N), and per head (C Bᵀ ⊙ L)(Δx) (2 Q² P), the carried state's
    share and the chunk's own state (2 Q N P each); the exponentials and
    masks are not counted.  Bytes: Δx in and y out in bf16, B, C, the
    cumulative decay twice (column and row layout), the state in and out."""
    z = sizes(hf)
    q = min(hf["mamba_chunk_size"], rows)
    chunks = -(-rows // q)
    H, P, N = z["H"], z["P"], z["N"]
    flops = chunks * (2.0 * q * q * N + H * (2.0 * q * q * P + 4.0 * q * N * P))
    nbytes = (2 * rows * H * P * 2 + 2 * rows * N * 2 + 2 * rows * H * 4
              + 2 * H * P * N * 4)
    return nbytes, flops


def decode_step_bytes(hf: dict, slots: int, live_tokens: float) -> float:
    """Bytes one decode step over ``slots`` sequences must move: every
    layer's weights and the tied head once, one embedding row per slot, the
    recurrent state of every slot read AND written, and the live keys and
    values of the attention layers (``live_tokens`` in total).  The K/V rows
    written and the activations are left out (under 0.1 %)."""
    p = param_count(hf)
    weights = (p["total"] + slots * sizes(hf)["d"]) * 2
    return (weights + 2 * slots * state_bytes_per_slot(hf)
            + live_tokens * kv_bytes_per_token(hf))


def decode_step_flops(hf: dict, slots: int, live_tokens: float) -> float:
    """Multiply-adds x 2 of one decode step: the matrices on ``slots`` rows,
    the state updates, and attention's q.k and p.v over the live positions."""
    p, z = param_count(hf), sizes(hf)
    mats = 2.0 * slots * (p["total"] - z["d"])
    updates = p["n_mamba"] * ssm_update_cost(hf, slots)[1]
    attn = 4.0 * p["n_attn"] * z["nq"] * live_tokens
    return mats + updates + attn

"""Operations and bytes a call needs, from its shapes alone (the yardstick's
side of every roofline share)."""

from __future__ import annotations


def param_count(hf: dict) -> dict:
    d, v, ff = hf["hidden_size"], hf["vocab_size"], hf["intermediate_size"]
    hd = hf.get("head_dim") or d // hf["num_attention_heads"]
    nq, nkv = hf["num_attention_heads"] * hd, hf["num_key_value_heads"] * hd
    layer = d * nq + 2 * d * nkv + nq * d + 3 * d * ff + 2 * d
    return {"layer": layer, "embed": v * d, "head": d * v, "final_norm": d,
            "total": hf["num_hidden_layers"] * layer + 2 * v * d + d}


def kv_bytes_per_token(hf: dict, dtype_bytes: int = 2) -> int:
    hd = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    return (2 * hf["num_hidden_layers"] * hf["num_key_value_heads"] * hd
            * dtype_bytes)


def decode_step_bytes(hf: dict, slots: int, live_tokens: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step over ``slots`` sequences must read from HBM:
    every layer's weights and the output head once, one embedding row per
    slot, and the live keys and values of all slots (``live_tokens`` in
    total).  Writes (one KV row per slot) and activations are left out: they
    are under 0.1 % of this."""
    p = param_count(hf)
    weights = (hf["num_hidden_layers"] * p["layer"] + p["head"]
               + p["final_norm"] + slots * hf["hidden_size"]) * dtype_bytes
    return weights + live_tokens * kv_bytes_per_token(hf, dtype_bytes)


def decode_step_flops(hf: dict, slots: int, live_tokens: float) -> float:
    """Multiply-adds x 2 of one decode step: the matmuls on ``slots`` rows
    plus attention's q.k and p.v over the live positions."""
    p = param_count(hf)
    d = hf["hidden_size"]
    mats = 2.0 * slots * (hf["num_hidden_layers"] * (p["layer"] - 2 * d)
                          + p["head"])
    hd = hf.get("head_dim") or d // hf["num_attention_heads"]
    attn = (4.0 * hf["num_hidden_layers"] * hf["num_attention_heads"] * hd
            * live_tokens)
    return mats + attn

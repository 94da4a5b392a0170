"""Operations and bytes of a MiMo-V2-shaped decoder's calls (full and window
GQA layers with their own KV-head counts, keys wider than values; a leading
dense MLP, then routed experts — the share of them held here —, an untied
head), from shapes and from what the program's counters say was live and
touched: the yardstick's side of ``swa_step_roofline``, ``swa_prefill_mfu``,
``full_attn_roofline``, ``window_attn_roofline`` and ``kv_prefill_roofline``.
What the MODEL needs is counted, not what the program does: a window layer's
row reads its last ``sliding_window`` keys and no more, the causal half once,
no pad row, no masked half of a score block.  ``costs.py`` counts a dense
decoder, ``costs_hybrid.py`` a Mamba-2 hybrid, ``costs_moe.py`` LFM2-MoE,
``costs_mla.py`` a latent one; all stay as they are."""

from __future__ import annotations

from benchmark.weights_swa import layer_kinds, sizes


def _kinds(hf: dict) -> list:
    return [layer_kinds(hf, i) for i in range(hf["num_hidden_layers"])]


def param_count(hf: dict) -> dict:
    """Parameters by part.  ``attn`` is one layer's attention with its norm,
    by kind (a window layer's sinks among it); ``expert`` ONE expert's three
    matrices; ``expert_layer_rest`` what an expert layer holds beside its
    routed experts and its attention (router, bias, the MLP's norm)."""
    z = sizes(hf)
    d, nh, hd, vd = z["d"], z["nh"], z["hd"], z["vd"]
    kinds = _kinds(hf)
    attn = {kind: d * nh * hd + d * nkv * (hd + vd) + nh * vd * d + d
            + (nh if kind == "window" else 0)
            for kind, nkv in z["nkv"].items()}
    p = {"attn": attn, "dense_mlp": 3 * d * z["ff"] + d,
         "expert": 3 * d * z["fe"],
         "expert_layer_rest": d * z["E"] + z["E"] + d,
         "embed": z["v"] * d, "head": d * z["v"],
         "n_layers": len(kinds),
         "n_full": sum(a == "full" for a, _ in kinds),
         "n_window": sum(a == "window" for a, _ in kinds),
         "n_dense": sum(m == "dense" for _, m in kinds),
         "n_expert_layers": sum(m == "experts" for _, m in kinds)}
    p["outside_experts"] = (
        p["n_full"] * attn["full"] + p["n_window"] * attn["window"]
        + p["n_dense"] * p["dense_mlp"]
        + p["n_expert_layers"] * p["expert_layer_rest"] + p["head"] + d)
    p["total"] = (p["outside_experts"] + p["embed"]
                  + p["n_expert_layers"] * z["held"] * p["expert"])
    return p


def kv_bytes_per_token(hf: dict, dtype_bytes: int = 2) -> int:
    """What one token costs the pool: K and V of every FULL layer."""
    z, p = sizes(hf), param_count(hf)
    return p["n_full"] * z["nkv"]["full"] * (z["hd"] + z["vd"]) * dtype_bytes


def window_bytes_per_row(hf: dict, dtype_bytes: int = 2) -> int:
    """What one live row of a slot's rings holds: K and V of every WINDOW
    layer."""
    z, p = sizes(hf), param_count(hf)
    return (p["n_window"] * z["nkv"]["window"] * (z["hd"] + z["vd"])
            * dtype_bytes)


def attn_cost(hf: dict, kind: str, slots: int, live_rows: float) -> tuple:
    """(bytes, operations) of ONE call of a decode kernel (one layer of
    ``kind``, every slot): each live K and V row read once, the queries in
    and the outputs out; per live row and query head a score over the key's
    width and a weighted sum over the value's.  ``live_rows`` sums over the
    slots: every cached token for a full layer, at most ``sliding_window``
    a slot for a window layer."""
    z = sizes(hf)
    width = z["hd"] + z["vd"]
    nbytes = (live_rows * z["nkv"][kind] + slots * z["nh"]) * width * 2
    return nbytes, 2.0 * z["nh"] * width * live_rows


def decode_step_bytes(hf: dict, slots: int, live_tokens: float,
                      touched: float, window_rows: float) -> float:
    """Bytes one decode step over ``slots`` sequences must read: everything
    outside the routed experts once (the head among it), ``touched`` experts
    (summed over the expert layers, from the load histogram) once each, one
    embedding row per slot, every live K and V row of the full layers and
    the live rows of the window layers' rings (``window_rows`` summed over
    the slots).  The rows written and the activations are left out."""
    z, p = sizes(hf), param_count(hf)
    weights = (p["outside_experts"] + touched * p["expert"]
               + slots * z["d"]) * 2
    return (weights + live_tokens * kv_bytes_per_token(hf)
            + window_rows * window_bytes_per_row(hf))


def decode_step_flops(hf: dict, slots: int, live_tokens: float,
                      pairs: float, window_rows: float) -> float:
    """Multiply-adds x 2 of one decode step: the matrices outside the routed
    experts on ``slots`` rows, ``pairs`` (row, expert) pairs computed here
    (summed over the expert layers), and attention over the live rows of
    each kind of layer."""
    p = param_count(hf)
    mats = 2.0 * (slots * p["outside_experts"] + pairs * p["expert"])
    return (mats + p["n_full"] * attn_cost(hf, "full", slots, live_tokens)[1]
            + p["n_window"] * attn_cost(hf, "window", slots, window_rows)[1])


def prefill_attn_flops(hf: dict, rows: int) -> dict:
    """Attention's model operations of ONE prompt of ``rows`` tokens, by
    kind of layer, all its layers of that kind: q.k (key wide) and p.v
    (value wide) over the causal half counted once in a full layer, over the
    band — row i's last min(i + 1, window) keys — in a window layer."""
    z, p = sizes(hf), param_count(hf)
    w = min(z["window"], rows)
    pairs = {"full": rows * (rows + 1) / 2,
             "window": w * (w + 1) / 2 + (rows - w) * w}
    return {kind: 2.0 * p["n_" + kind] * z["nh"] * (z["hd"] + z["vd"]) * n
            for kind, n in pairs.items()}


def prefill_flops(hf: dict, rows: int, pairs: float) -> float:
    """Model operations of ONE prompt of ``rows`` tokens through the prefill:
    every matrix outside the routed experts on every row (the head on one),
    ``pairs`` (row, expert) pairs computed here (summed over the expert
    layers), and ``prefill_attn_flops``."""
    p = param_count(hf)
    mats = 2.0 * (rows * (p["outside_experts"] - p["head"]) + p["head"]
                  + pairs * p["expert"])
    return mats + sum(prefill_attn_flops(hf, rows).values())

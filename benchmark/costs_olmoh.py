"""Operations and bytes of an Olmo-Hybrid-shaped decoder's calls (gated-
delta-rule layers beside full attention layers in which every query head has
its own keys and values, post-norm blocks, a dense MLP, an untied head), from
shapes and from what the program's counters say was live: the yardstick's
side of ``delta_update_roofline``, ``delta_scan_roofline``,
``delta_step_roofline``, ``delta_prefill_mfu`` and ``mha_attn_roofline``.
What the MODEL needs is counted, not what the program does: the state
UNPADDED (96 x 192 float32 a head, whatever lanes a layout would round a row
up to); the recurrence's own operations a valid row (decay, Sᵀk, the rank-one
write, Sᵀq: 6 dk dv a head), never the chunked form's extra products; the
causal half once; no pad row.  ``costs.py`` counts a dense decoder,
``costs_hybrid.py`` a Mamba-2 hybrid, ``costs_moe.py`` LFM2-MoE,
``costs_mla.py`` a latent one, ``costs_swa.py`` a window one,
``costs_gdn.py`` Qwen3-Next; all stay as they are."""

from __future__ import annotations

from benchmark.weights_olmoh import layer_kind, sizes


def param_count(hf: dict) -> dict:
    """Parameters by part.  ``linear`` / ``full`` are one layer's mixer with
    its norms (the block's norm after it among them); ``mlp`` one layer's
    dense MLP with its norm."""
    z = sizes(hf)
    d, hd = z["d"], z["hd"]
    kinds = [layer_kind(hf, i) for i in range(hf["num_hidden_layers"])]
    p = {"linear": (d * (z["conv"] + z["value"]) + d * 2 * z["Hv"]
                    + z["K"] * z["conv"] + 2 * z["Hv"] + z["dv"]
                    + z["value"] * d + d),
         "full": (d * z["nh"] * hd + 2 * d * z["nkv"] * hd
                  + z["nh"] * hd * d + (z["nh"] + z["nkv"]) * hd + d),
         "mlp": 3 * d * z["ff"] + d,
         "embed": z["v"] * d, "head": d * z["v"],
         "n_layers": len(kinds),
         "n_linear": sum(k == "linear" for k in kinds),
         "n_full": sum(k == "full" for k in kinds)}
    p["read_a_step"] = (p["n_linear"] * p["linear"] + p["n_full"] * p["full"]
                        + p["n_layers"] * p["mlp"] + p["head"] + d)
    p["total"] = p["read_a_step"] + p["embed"]
    return p


def state_bytes_per_slot(hf: dict, conv_bytes: int = 2) -> int:
    """What one sequence's delta-rule layers carry, whatever its length: S
    in float32, unpadded, and the conv's last K - 1 rows, per layer."""
    z = sizes(hf)
    return param_count(hf)["n_linear"] * (
        z["Hv"] * z["dk"] * z["dv"] * 4 + (z["K"] - 1) * z["conv"]
        * conv_bytes)


def kv_bytes_per_token(hf: dict, dtype_bytes: int = 2) -> int:
    """What one token costs the pool: K and V of every full layer."""
    z = sizes(hf)
    return param_count(hf)["n_full"] * 2 * z["nkv"] * z["hd"] * dtype_bytes


def update_cost(hf: dict, slots: int) -> tuple:
    """(bytes, operations) of ONE ``strom_gdn_update`` call (one layer, one
    token of every slot): each slot's state read and written once, float32,
    and the step's operands as the kernel takes them — k, q and the decay a
    column, βv and β a row, the output, float32 a head; the recurrence's 6
    dk dv operations a head."""
    z = sizes(hf)
    elems = slots * z["Hv"] * z["dk"] * z["dv"]
    operands = slots * z["Hv"] * (3 * z["dk"] + 3 * z["dv"]) * 4
    return 2 * elems * 4 + operands, 6.0 * elems


def scan_cost(hf: dict, prompts: int, valid_rows: float) -> tuple:
    """(bytes, operations) of ONE ``strom_gdn_scan`` call (one layer) over
    ``prompts`` sequences holding ``valid_rows`` prompt rows between them:
    q, k and v in and o out in bfloat16, α and β a head in float32, a row;
    the state in and out, a prompt; the recurrence's own 6 dk dv operations a
    head a valid row."""
    z = sizes(hf)
    row = (2 * z["key"] + 2 * z["value"]) * 2 + 2 * z["Hv"] * 4
    state = 2 * z["Hv"] * z["dk"] * z["dv"] * 4
    return (valid_rows * row + prompts * state,
            6.0 * valid_rows * z["Hv"] * z["dk"] * z["dv"])


def attn_cost(hf: dict, slots: float, live_tokens: float) -> tuple:
    """(bytes, operations) of ONE ``strom_paged_attn`` call (one full layer,
    one token of every slot): every live K and V row of the layer's KV heads
    read once, the slots' queries in and outputs out; q.k and p.v over the
    live rows of every query head."""
    z = sizes(hf)
    rows = 2 * z["nkv"] * z["hd"] * 2 * live_tokens
    io = 2 * slots * z["nh"] * z["hd"] * 2
    return rows + io, 4.0 * z["nh"] * z["hd"] * live_tokens


def decode_step_bytes(hf: dict, slots: float, live_tokens: float) -> float:
    """Bytes one decode step over ``slots`` ACTIVE sequences must move: every
    weight a step reads once (the head among them), one embedding row per
    slot, every active slot's recurrent state read AND written (conv tails
    with it), and every live K and V row of the full layers.  The K/V rows
    written and the activations are left out."""
    z, p = sizes(hf), param_count(hf)
    weights = (p["read_a_step"] + slots * z["d"]) * 2
    return (weights + 2 * slots * state_bytes_per_slot(hf)
            + live_tokens * kv_bytes_per_token(hf))


def decode_step_flops(hf: dict, slots: float, live_tokens: float) -> float:
    """Multiply-adds x 2 of one decode step: the matrices on ``slots`` rows,
    the state updates, and the full layers' q.k and p.v over the live rows."""
    z, p = sizes(hf), param_count(hf)
    return (2.0 * slots * p["read_a_step"]
            + p["n_linear"] * update_cost(hf, slots)[1]
            + 4.0 * p["n_full"] * z["nh"] * z["hd"] * live_tokens)


def prefill_flops(hf: dict, rows: int) -> float:
    """Model operations of ONE prompt of ``rows`` tokens through the
    prefill: every matrix on every row (the head on one), the full layers'
    q.k and p.v over the causal half counted once, and the recurrence's own
    count in the delta-rule layers."""
    z, p = sizes(hf), param_count(hf)
    mats = 2.0 * (rows * (p["read_a_step"] - p["head"]) + p["head"])
    attn = 4.0 * p["n_full"] * z["nh"] * z["hd"] * rows * (rows + 1) / 2
    return mats + attn + p["n_linear"] * scan_cost(hf, 1, rows)[1]

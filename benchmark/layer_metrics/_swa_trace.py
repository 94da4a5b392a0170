"""What is the window-attention configuration's own: its kernels' fixed names
(``ops/paged_attention.py``: a full layer's decode kernel ``strom_paged_attn``,
a window layer's ``strom_window_attn``; ``ops/kv_prefill.py``: the blocked
prefill ``strom_kv_prefill`` and ``strom_window_prefill``), the window's
counters per decode step, the configuration test and a decode kernel's
roofline.  The walks are ``_kernel_trace``'s.  A program without the kernels
or the counters (an older commit), or a configuration of another family,
gives nothing, and the readers return ``None``."""

from __future__ import annotations

from benchmark.layer_metrics import _kernel_trace as K

FULL, WINDOW = "strom_paged_attn", "strom_window_attn"
PREFILL_KERNELS = ("strom_kv_prefill", "strom_window_prefill")


def is_swa(config: dict) -> bool:
    return bool(config.get("hybrid_layer_pattern")
                and any(config["hybrid_layer_pattern"]))


def per_step(facts: dict):
    """The window's means per decode step from the program's counters and
    the runner's: {"slots", "live" (cached tokens over the slots),
    "window_rows" (rows the window layers' rings are read for, over the
    slots), "touched" (experts, over the layers), "pairs"}; None where a
    counter is missing (an older program)."""
    t = facts.get("timings") or {}
    steps = t.get("steps")
    if (not steps or facts.get("live_tokens") is None
            or not t.get("window_rows_live")):
        return None
    return {"slots": facts["slots"], "live": facts["live_tokens"],
            "window_rows": t["window_rows_live"] / steps,
            "touched": t.get("moe_experts_touched", 0) / steps,
            "pairs": t.get("moe_pairs", 0) / steps}


def attn_roofline(ctx, kind: str, kernel: str):
    """Σ over the calls of ``kernel`` inside ``_paged_step`` of the least
    time their bytes and operations allow at the window's mean live rows,
    over Σ of their device time, in percent."""
    from benchmark import costs_swa
    mean = per_step(ctx.facts)
    _, spent, calls = K.totals(K.runs(ctx.trace, K.STEP, kernel))
    if not mean or not calls or not is_swa(ctx.config):
        return None
    rows = mean["live"] if kind == "full" else mean["window_rows"]
    least = K.least_seconds(costs_swa.attn_cost(
        ctx.config, kind, mean["slots"], rows), ctx.peaks)
    return 100.0 * least * calls / (spent / 1e9)

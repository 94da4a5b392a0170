"""What the window-attention configuration's readers share: the device
events of its kernels by their fixed names (``ops/paged_attention.py``: a
full layer's decode kernel ``strom_paged_attn``, a window layer's
``strom_window_attn``; ``ops/kv_prefill.py``: the blocked prefill
``strom_kv_prefill`` and ``strom_window_prefill``) inside the program that
ran them, the window's counters, and the configuration test.  A program
without the kernels or the counters (an older commit), or a configuration of
another family, gives nothing, and the readers return ``None``."""

from __future__ import annotations

import bisect

from benchmark.layer_metrics._ssm_trace import least_seconds  # noqa: F401

FULL, WINDOW = "strom_paged_attn", "strom_window_attn"
PREFILL_KERNELS = ("strom_kv_prefill", "strom_window_prefill")
STEP, PREFILL = "_paged_step", "_paged_prefill"


def is_kernel(event_name: str, kernels) -> bool:
    """Whether a device event IS a call of one of ``kernels``: its own name,
    left of the ``=``, says so (the operation that consumes the kernel's
    result names it among its operands)."""
    own = event_name.split("=", 1)[0]
    return any(k in own for k in kernels)


def is_swa(config: dict) -> bool:
    return bool(config.get("hybrid_layer_pattern")
                and any(config["hybrid_layer_pattern"]))


def runs(trace, program: str, kernels) -> list:
    """[(device ns of the execution, summed ns of the kernels' calls in it,
    the calls)] for every execution of ``program`` that ran one of
    ``kernels``, on the first device plane that did."""
    from benchmark import xplane
    if isinstance(kernels, str):
        kernels = (kernels,)
    for name, ops in (trace.ops.items() if trace else ()):
        hits = sorted((s, e) for n, s, e in ops if is_kernel(n, kernels))
        if not hits:
            continue
        starts = [s for s, _ in hits]
        out = []
        for mod, s, e in trace.modules.get(name, []):
            if xplane.program_name(mod) != program:
                continue
            inside = hits[bisect.bisect_left(starts, s):
                          bisect.bisect_left(starts, e)]
            if inside:
                out.append((e - s, sum(b - a for a, b in inside),
                            len(inside)))
        return out
    return []


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
    got = runs(ctx.trace, STEP, kernel)
    if not mean or not got or not is_swa(ctx.config):
        return None
    rows = mean["live"] if kind == "full" else mean["window_rows"]
    least = least_seconds(costs_swa.attn_cost(
        ctx.config, kind, mean["slots"], rows), ctx.peaks)
    return (100.0 * least * sum(n for _, _, n in got)
            / (sum(k for _, k, _ in got) / 1e9))


def attn_share(ctx, kernel: str):
    """The kernel's summed device time inside ``_paged_step`` over the
    summed device time of the steps that ran it, in percent."""
    got = runs(ctx.trace, STEP, kernel)
    if not got:
        return None
    return 100.0 * sum(k for _, k, _ in got) / sum(ns for ns, _, _ in got)

"""What is the expert layer's own: the grouped product's fixed name
(``strom_moe_gmm``, ``ops/moe.py``), the window's means per call of an expert
layer from the program's counters, and the kernel's roofline in one program.
The walks are ``_kernel_trace``'s.  A program without the kernel or the
counters (an older commit) gives nothing, and the readers return ``None``."""

from __future__ import annotations

from benchmark.layer_metrics import _kernel_trace as K

KERNEL = "strom_moe_gmm"


def kernel_seconds(got: list) -> tuple:
    """(calls of the kernel, their summed device seconds) in what
    ``K.runs(trace, program, KERNEL)`` gave."""
    hits = [(e - s) / 1e9 for _, calls in got for _, s, e in calls]
    return len(hits), sum(hits)


def per_call(facts: dict, suffix: str = ""):
    """(pairs, experts touched, layouts run) per call of an expert layer,
    the window's means from ``srv.timings`` (``suffix`` "_prefill": the
    admissions')."""
    t = facts.get("timings") or {}
    calls = t.get("moe_calls" + suffix)
    if not calls:
        return None
    return (t["moe_pairs" + suffix] / calls,
            t["moe_experts_touched" + suffix] / calls,
            t.get("moe_rounds" + suffix, calls) / calls)


def experts_roofline(ctx, program: str, suffix: str):
    """Σ over the expert layers' calls in ``program`` of the least time
    their bytes and operations allow, over Σ of the kernel's device time, in
    percent.  A layout is two calls of the kernel (gate and up; down) and a
    layer's call one layout, or more where its local pairs overflowed
    (``moe_rounds``): the layer's ``experts_cost`` is counted once."""
    from benchmark import costs_moe
    mean = per_call(ctx.facts, suffix)
    calls, seconds = kernel_seconds(K.runs(ctx.trace, program, KERNEL))
    if not mean or not calls or "moe_intermediate_size" not in ctx.config:
        return None
    pairs, touched, rounds = mean
    least = K.least_seconds(costs_moe.experts_cost(ctx.config, pairs, touched),
                            ctx.peaks)
    return 100.0 * least * (calls / 2 / rounds) / seconds

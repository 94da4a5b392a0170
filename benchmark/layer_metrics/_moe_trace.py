"""What the expert layer's readers share: the device events of the grouped
product (``strom_moe_gmm``, the fixed name ``ops/moe.py`` gives its kernel),
told apart by the program that ran them, and the window's means per call of
an expert layer from the program's counters.  A program without the kernel
or the counters (an older commit) gives nothing, and the readers return
``None``."""

from __future__ import annotations

import bisect

from benchmark.layer_metrics._ssm_trace import least_seconds  # noqa: F401

KERNEL = "strom_moe_gmm"
STEP, PREFILL = "_paged_step", "_paged_prefill"


def is_kernel(event_name: str) -> bool:
    """Whether a device event IS a call of the kernel: its own name, left of
    the ``=``, says so.  The rest of the text lists operands, and the
    operation that consumes the kernel's result names it there."""
    return KERNEL in event_name.split("=", 1)[0]


def _plane(trace):
    """(ops, modules) of the first device plane that ran the kernel."""
    for name, ops in (trace.ops.items() if trace else ()):
        if any(is_kernel(n) for n, _, _ in ops):
            return sorted(ops, key=lambda o: o[1]), trace.modules.get(name, [])
    return [], []


def runs(trace, program: str) -> list:
    """[(device ns of the execution, [(event name, start_ns, end_ns), ...])]:
    for every execution of ``program`` that ran the kernel, its device
    operations in order."""
    from benchmark import xplane
    ops, modules = _plane(trace)
    starts = [s for _, s, _ in ops]
    out = []
    for name, s, e in modules:
        if xplane.program_name(name) != program:
            continue
        inside = ops[bisect.bisect_left(starts, s):
                     bisect.bisect_left(starts, e)]
        if any(is_kernel(n) for n, _, _ in inside):
            out.append((e - s, inside))
    return out


def kernel_seconds(trace, program: str) -> tuple:
    """(calls of the kernel, their summed device seconds) in ``program``."""
    hits = [(e - s) / 1e9 for _, run in runs(trace, program)
            for n, s, e in run if is_kernel(n)]
    return len(hits), sum(hits)


def per_call(facts: dict, suffix: str = ""):
    """(pairs, experts touched) per call of an expert layer, the window's
    means from ``srv.timings`` (``suffix`` "_prefill": the admissions')."""
    t = facts.get("timings") or {}
    calls = t.get("moe_calls" + suffix)
    if not calls:
        return None
    return (t["moe_pairs" + suffix] / calls,
            t["moe_experts_touched" + suffix] / calls)


def experts_roofline(ctx, program: str, suffix: str):
    """Σ over the kernel's calls in ``program`` of the least time their
    bytes and operations allow, over Σ of their device time, in percent.  A
    layer's two calls (gate and up; down) share one ``experts_cost``."""
    from benchmark import costs_moe
    mean = per_call(ctx.facts, suffix)
    calls, seconds = kernel_seconds(ctx.trace, program)
    if not mean or not calls or "num_experts" not in ctx.config:
        return None
    least = least_seconds(costs_moe.experts_cost(ctx.config, *mean),
                          ctx.peaks)
    return 100.0 * least * (calls / 2) / seconds

"""What the latent attention's readers share: the device events of the
absorbed-form kernel (``strom_mla_attn``, the fixed name
``ops/mla_attention.py`` gives it) inside the decode step, and the
configuration test.  A program without the kernel or the counters (an older
commit), or a configuration of another family, gives nothing, and the
readers return ``None``."""

from __future__ import annotations

import bisect

from benchmark.layer_metrics._ssm_trace import least_seconds  # noqa: F401

KERNEL = "strom_mla_attn"
STEP, PREFILL = "_paged_step", "_paged_prefill"


def is_kernel(event_name: str) -> bool:
    """Whether a device event IS a call of the kernel: its own name, left of
    the ``=``, says so (the operation that consumes the kernel's result
    names it among its operands)."""
    return KERNEL in event_name.split("=", 1)[0]


def is_latent(config: dict) -> bool:
    return bool(config.get("kv_lora_rank"))


def step_runs(trace) -> list:
    """[(device ns of the execution, summed ns of the kernel's calls in it,
    the calls)] for every execution of ``_paged_step`` that ran the
    kernel, on the first device plane that did."""
    from benchmark import xplane
    for name, ops in (trace.ops.items() if trace else ()):
        hits = sorted((s, e) for n, s, e in ops if is_kernel(n))
        if not hits:
            continue
        starts = [s for s, _ in hits]
        out = []
        for mod, s, e in trace.modules.get(name, []):
            if xplane.program_name(mod) != STEP:
                continue
            inside = hits[bisect.bisect_left(starts, s):
                          bisect.bisect_left(starts, e)]
            if inside:
                out.append((e - s, sum(b - a for a, b in inside),
                            len(inside)))
        return out
    return []

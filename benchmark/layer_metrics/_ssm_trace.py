"""What the new readers share: the device events of one Pallas kernel, found
by the fixed name the program gives it (``ops/ssm.py``)."""

from __future__ import annotations

import re

_DIMS = re.compile(r"\[([0-9,]+)\]")


def kernel_events(trace, kernel: str) -> list:
    """[(event name, seconds)] of every call of ``kernel`` on the first
    device plane that ran it."""
    for ops in (trace.ops.values() if trace else ()):
        hits = [(n, (e - s) / 1e9) for n, s, e in ops if kernel in n]
        if hits:
            return hits
    return []


def first_result_dims(event_name: str):
    """``%k.3 = (bf16[1,64,1024,64]{..}, f32[..])`` -> (1, 64, 1024, 64)."""
    m = _DIMS.search(event_name.split("=", 1)[-1])
    return tuple(int(d) for d in m.group(1).split(",")) if m else None


def least_seconds(cost: tuple, peaks: dict) -> float:
    nbytes, flops = cost
    return max(nbytes / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])

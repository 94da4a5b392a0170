"""From the profiler's ``.xplane.pb`` to numbers: the benchmark's own
reduction (parser after ``nvme_strom_tpu/tools/profile_report.py``'s
``_load_profile_data``/``parse_trace``, which bucket a train step; here the
serving and restore reductions).

A ``Trace`` holds, per device plane, the ``XLA Ops`` events (name, start,
end, ns) and the ``XLA Modules`` events (one per executed program), and the
host's annotation events (``TraceAnnotation`` names).  Everything else is
arithmetic on those lists, tested on a small recorded file."""

from __future__ import annotations

import re
import statistics
from dataclasses import dataclass, field


@dataclass
class Trace:
    #: {device plane name: [(name, start_ns, end_ns)]}
    ops: dict = field(default_factory=dict)
    modules: dict = field(default_factory=dict)
    #: [(name, start_ns, end_ns)] from every host line
    host: list = field(default_factory=list)


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name.upper()


def load(path: str) -> Trace:
    """Parse with ``jax.profiler.ProfileData`` (nothing but JAX needed)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        if _is_device_plane(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
                elif line.name == "XLA Modules":
                    mods += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events]
            if ops or mods:
                tr.ops[plane.name] = ops
                tr.modules[plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
    return tr


def union_ns(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_seconds(tr: Trace) -> float:
    """Seconds in which an operation ran on the device: the union of the
    ``XLA Ops`` intervals (ops inside one program overlap their parents),
    averaged over the device planes that ran anything."""
    per_dev = [union_ns((s, e) for _, s, e in ops) / 1e9
               for ops in tr.ops.values() if ops]
    return sum(per_dev) / len(per_dev) if per_dev else 0.0


def idle_share(tr: Trace, window_s: float) -> float:
    """1 - busy/window, in percent."""
    return 100.0 * (1.0 - busy_seconds(tr) / window_s)


_PROGRAM = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def program_name(module_event_name: str) -> str:
    """``jit__paged_step(123456)`` -> ``_paged_step``."""
    return _PROGRAM.match(module_event_name).group(1)


def program_durations_ms(tr: Trace, program: str) -> list:
    """Device durations (ms) of every execution of one jitted program, from
    the first device plane's ``XLA Modules`` line."""
    for mods in tr.modules.values():
        out = [(e - s) / 1e6 for name, s, e in mods
               if program_name(name) == program]
        if out:
            return out
    return []


def median_program_ms(tr: Trace, program: str):
    d = program_durations_ms(tr, program)
    return statistics.median(d) if d else None


def host_seconds(tr: Trace, name: str) -> float:
    """Summed host duration of the annotations called ``name``."""
    return sum(e - s for n, s, e in tr.host if n == name) / 1e9


_OP = re.compile(r"^%?([A-Za-z_\-]+(?:\.[A-Za-z_\-]+)*?)(?:\.\d+)*\s*=\s*\(*"
                 r"([a-z0-9]+\[[0-9,]*\])?")


def op_key(event_name: str) -> str:
    """``%copy-start.12 = (bf16[4096,14336]{...}, ...`` -> ``copy-start
    bf16[4096,14336]``: the operation and its first result's type, without
    the instruction's number, so that equal work adds up."""
    m = _OP.match(event_name)
    if not m:
        return event_name[:48]
    return (m.group(1) + (" " + m.group(2) if m.group(2) else ""))[:64]


def top_device_ops(tr: Trace, k: int = 10) -> list:
    """[[name, seconds]]: the k kinds of operation with most summed device
    time on the first device plane (``XLA Ops`` line)."""
    for ops in tr.ops.values():
        by = {}
        for name, s, e in ops:
            key = op_key(name)
            by[key] = by.get(key, 0.0) + (e - s) / 1e9
        return [[n, t] for n, t in
                sorted(by.items(), key=lambda kv: -kv[1])[:k]]
    return []


def top_programs(tr: Trace, k: int = 10) -> list:
    """[[program, seconds]] from the ``XLA Modules`` line."""
    for mods in tr.modules.values():
        by = {}
        for name, s, e in mods:
            key = program_name(name)
            by[key] = by.get(key, 0.0) + (e - s) / 1e9
        return [[n, t] for n, t in
                sorted(by.items(), key=lambda kv: -kv[1])[:k]]
    return []


def idle_gaps(tr: Trace, phases: tuple, k: int = 10) -> list:
    """[[phase, seconds]]: the device's idle time inside the traced span,
    bucketed by which of the benchmark loop's annotations covered the gap's
    start (host and device share the xplane's clock).  ``phases`` is in order
    of precedence, innermost first; a gap under none counts as ``other``."""
    import bisect
    plane = next(iter(tr.ops), None)
    if plane is None:
        return []
    spans = {p: sorted((s, e) for n, s, e in tr.host if n == p)
             for p in phases}
    starts = {p: [s for s, _ in v] for p, v in spans.items()}
    by, cur = {}, None
    for s, e in sorted((s, e) for _, s, e in tr.ops[plane]):
        if cur is not None and s > cur:
            name = "other"
            for p in phases:
                j = bisect.bisect_right(starts[p], cur) - 1
                if j >= 0 and spans[p][j][1] > cur:
                    name = p
                    break
            by[name] = by.get(name, 0.0) + (s - cur) / 1e9
        cur = e if cur is None else max(cur, e)
    return [[n, t] for n, t in sorted(by.items(), key=lambda kv: -kv[1])[:k]]

"""Arithmetic on the PROGRAM's own spans (``nvme_strom_tpu/utils/trace.py``
``Tracer.span`` writes every ``strom.*`` span into the profiler's trace), over
``xplane.Trace.host``.  A program without these spans (an older commit) gives
``None`` everywhere, and the reader leaves its metric out."""

from __future__ import annotations

import bisect

from benchmark import xplane

#: one admission, innermost first: the order ``xplane.idle_gaps`` wants
ADMISSION = ("strom.serve.prefill", "strom.serve.scatter",
             "strom.serve.first_token", "strom.serve.admit")
#: what ``strom.restore.load`` is made of (``strom.restore.tensor`` is the
#: level between and has no time of its own to name)
RESTORE_PARTS = ("strom.restore.plan", "strom.restore.read_wait",
                 "strom.restore.slice", "strom.h2d", "strom.restore.retire",
                 "strom.restore.join")
LOAD = "strom.restore.load"


def has(tr, name: str) -> bool:
    return bool(tr) and any(n == name for n, _, _ in tr.host)


def share(tr, name: str, marker: str, window_s: float):
    """Summed host time of the spans called ``name`` over the traced window,
    in percent; 0 where the program has spans (``marker`` is there) and none
    of this name, ``None`` where it has no spans."""
    if not has(tr, marker) or not window_s:
        return None
    return 100.0 * xplane.host_seconds(tr, name) / window_s


def admission_idle(tr, window_s: float):
    """{span name: percent of the traced window in which the device idled
    with the gap's start under that span}, innermost span first; ``None``
    without the program's admission spans or without a device plane."""
    if not has(tr, ADMISSION[0]) or not tr.ops or not window_s:
        return None
    gaps = dict(map(tuple, xplane.idle_gaps(tr, ADMISSION,
                                            k=len(ADMISSION) + 1)))
    return {n: 100.0 * gaps.get(n, 0.0) / window_s for n in ADMISSION}


def longest_ms(tr, name: str):
    d = [e - s for n, s, e in (tr.host if tr else ()) if n == name]
    return max(d) / 1e6 if d else None


def inside(intervals, outers) -> list:
    """The ``intervals`` that start inside one of ``outers`` (both lists of
    (start, end); spans of one thread nest, so starting inside is lying
    inside)."""
    outers = sorted(outers)
    starts = [s for s, _ in outers]
    out = []
    for s, e in intervals:
        j = bisect.bisect_right(starts, s) - 1
        if j >= 0 and outers[j][1] > s:
            out.append((s, min(e, outers[j][1])))
    return out


def restore_self_seconds(tr):
    """Seconds inside ``strom.restore.load`` that none of RESTORE_PARTS
    covers: the loop's own host work (sharding lookups, index maps, Python
    between the spans)."""
    loads = [(s, e) for n, s, e in (tr.host if tr else ()) if n == LOAD]
    if not loads:
        return None
    parts = inside([(s, e) for n, s, e in tr.host if n in RESTORE_PARTS],
                   loads)
    return (xplane.union_ns(loads) - xplane.union_ns(parts)) / 1e9

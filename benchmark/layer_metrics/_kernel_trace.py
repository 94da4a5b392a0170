"""What every kernel's reader shares: the device events of a Pallas kernel,
found by the fixed name the program gives it (``name=`` of its
``pallas_call``), alone or inside the executions of one program.  A reader is
then a kernel's name, a cost function from ``benchmark/costs*.py`` and one of
these walks; a program without the kernel (an older commit, another family)
gives empty lists, and the reader returns ``None``.

A device event's text is the whole instruction: ``%strom_x.3 = bf16[..]
custom-call(bf16[..] %fusion.7, ...)``.  Only the part left of the ``=`` is
the operation's own name; the rest lists operands, and the operation that
consumes a kernel's result names the kernel there (read as a call, that was
132 % of a roofline in PR 30)."""

from __future__ import annotations

import bisect
import re

STEP, PREFILL = "_paged_step", "_paged_prefill"
_DIMS = re.compile(r"\[([0-9,]+)\]")


def _tuple(kernels) -> tuple:
    return (kernels,) if isinstance(kernels, str) else tuple(kernels)


def is_call(event_name: str, kernels) -> bool:
    """Whether a device event IS a call of one of ``kernels`` (a name or
    several): its own name, left of the ``=``, says so."""
    kernels = _tuple(kernels)
    if not any(k in event_name for k in kernels):     # most events: one scan
        return False
    own = event_name.split("=", 1)[0]
    return any(k in own for k in kernels)


def _plane(trace, kernels: tuple) -> tuple:
    """(the plane's name, its calls of ``kernels`` by start) for the first
    device plane that ran one; kept on the trace, which several readers of
    one run walk for the same kernel."""
    memo = vars(trace).setdefault("_kernel_calls", {})
    if kernels not in memo:
        memo[kernels] = (None, [])
        for plane, ops in trace.ops.items():
            calls = sorted((o for o in ops if is_call(o[0], kernels)),
                           key=lambda o: o[1])
            if calls:
                memo[kernels] = (plane, calls)
                break
    return memo[kernels]


def events(trace, kernels) -> list:
    """[(event name, seconds)] of every call of ``kernels``, in order of
    start, whatever program ran it."""
    if not trace:
        return []
    return [(n, (e - s) / 1e9) for n, s, e in _plane(trace, _tuple(kernels))[1]]


def runs(trace, program: str, kernels, every: bool = False) -> list:
    """[(device ns of the execution, [(event name, start_ns, end_ns), ...])]
    for every execution of ``program`` that ran one of ``kernels``: the
    kernels' calls inside it in order of start — with ``every``, all its
    device operations (what lies between two calls is then there to read)."""
    from benchmark import xplane
    if not trace:
        return []
    kernels = _tuple(kernels)
    plane, calls = _plane(trace, kernels)
    if every and calls:
        memo = vars(trace).setdefault("_ops_by_start", {})
        if plane not in memo:
            memo[plane] = sorted(trace.ops[plane], key=lambda o: o[1])
        inside_of = memo[plane]
    else:
        inside_of = calls
    starts = [s for _, s, _ in inside_of]
    call_starts = [s for _, s, _ in calls]
    out = []
    for name, s, e in trace.modules.get(plane, []):
        if xplane.program_name(name) != program:
            continue
        if bisect.bisect_left(call_starts, s) == bisect.bisect_left(
                call_starts, e):
            continue                                  # ran none of them
        out.append((e - s, inside_of[bisect.bisect_left(starts, s):
                                     bisect.bisect_left(starts, e)]))
    return out


def totals(got: list) -> tuple:
    """(Σ device ns of the executions, Σ ns of the kernels' calls in them,
    the number of those calls) of what ``runs`` gave (without ``every``)."""
    calls = [(s, e) for _, ops in got for _, s, e in ops]
    return (sum(ns for ns, _ in got), sum(e - s for s, e in calls),
            len(calls))


def share(trace, program: str, kernels):
    """The kernels' summed device time inside ``program`` over the summed
    device time of the executions that ran them, in percent."""
    total, spent, _ = totals(runs(trace, program, kernels))
    return 100.0 * spent / total if total else None


def first_result_dims(event_name: str):
    """``%k.3 = (bf16[1,64,1024,64]{..}, f32[..])`` -> (1, 64, 1024, 64)."""
    m = _DIMS.search(event_name.split("=", 1)[-1])
    return tuple(int(d) for d in m.group(1).split(",")) if m else None


def least_seconds(cost: tuple, peaks: dict) -> float:
    """The least time (bytes, operations) allow on a chip of ``peaks``."""
    nbytes, flops = cost
    return max(nbytes / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])

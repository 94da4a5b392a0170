"""Device time by the program's own names.  Every operation of the two serving
programs lies under a ``strom.*`` scope (``models/decode.MIXER_SCOPES``,
docs/OBSERVABILITY.md "Scopes on the device"), a prefill's under the label of
its compiled shape too, and the profiler keeps each operation's scope path:
not in the event's name, which ``xplane.load`` reads, but in the plane's
``event_metadata`` table, as the stat ``tf_op`` beside ``program_id`` and
``hlo_category``.  ``jax.profiler.ProfileData`` does not hand those tables
out, and a benchmark run imports no TensorFlow, so ``tables`` reads them off
the file's wire format: four messages of ``xplane.proto`` (XSpace, XPlane,
XEventMetadata, XStat and the map entries around them), each plane's
``lines`` — the millions of events — skipped by their length.

The join: an operation belongs to the execution (``XLA Modules`` event) whose
interval holds its start, and that module's name ends in ``(program_id)``;
(program_id, the event's name) finds the operation's record.  Two programs
that both hold a ``%fusion.3`` are so kept apart.

The time: operations nest on the ``XLA Ops`` line (a ``while`` holds its
body's, a fusion may hold its own), so every instant counts once, for the
INNERMOST operation running then — an operation's time is its own less its
children's.  Summed over a program that is the union ``xplane.busy_seconds``
takes, so families plus unscoped add up to the device's busy time inside the
program's executions; what is left of the executions' own durations is the
gaps between operations.

A label is XLA's: a fusion that spans two scopes carries ONE ``tf_op``
(PERF.md §5 names the ones found: a product fused with the next norm's sum
keeps the PRODUCT's), and an operation the compiler made itself carries none:
it takes its consumer's (``inherit``).  The persistent compile cache must key
on metadata (``utils/compile_cache``): a program fetched under a key that
ignores it brings the names of whoever compiled it first.  A program without
scopes (an older commit) gives ``None`` from every reader."""

from __future__ import annotations

import bisect
import mmap
import re

STEP, PREFILL = "_paged_step", "_paged_prefill"
#: ``hlo_category`` values that move data and compute nothing, as found on
#: the chip in the four serving cells (PR 37): ``data formatting`` (``copy``,
#: a transpose or a bitcast XLA materialises), the two halves of an
#: asynchronous copy, and ``async-start`` / ``async-done`` (``slice-start`` /
#: ``slice-done``: a weight fetched in pieces ahead of its product)
COPY_KINDS = ("data formatting", "copy-start", "copy-done", "async-start",
              "async-done")
#: scopes that only a program under the whole partition has
WHOLE = ("strom.embed", "strom.head", "strom.attn.proj", "strom.attn.out",
         "strom.ssm.proj", "strom.ssm.out")
BUCKET = re.compile(r"^strom\.prefill\.(\d+)x(\d+)x(\d+)$")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


# ------------------------------------------------------------ the wire format

def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, wire type, value) over one message's bytes; a
    length-delimited value comes as its (start, end) in ``buf``."""
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind == 1:
            value, i = bytes(buf[i:i + 8]), i + 8
        elif kind == 5:
            value, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield key >> 3, kind, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    """The value message's (start, end) of one map entry."""
    for no, kind, value in _fields(buf, *span):
        if no == 2 and kind == 2:
            return value
    return None


def _stat(buf, span) -> tuple:
    """(stat metadata id, value) of one XStat: a number, a string, or
    ("ref", id) for a string kept once in the stat-metadata table."""
    key = value = None
    for no, kind, v in _fields(buf, *span):
        if no == 1:
            key = v
        elif no in (3, 4, 7) and kind == 0:
            value = ("ref", v) if no == 7 else v
        elif no in (5, 6) and kind == 2:
            value = _text(buf, v)
    return key, value


def _plane(buf, span) -> tuple:
    """({stat id: stat name}, [{name, stats: {stat id: value}}] of a plane's
    event metadata).  Lines are stepped over."""
    stat_names, events = {}, []
    for no, kind, value in _fields(buf, *span):
        if no == 5 and kind == 2:                    # stat_metadata entry
            meta = _map_value(buf, value)
            sid = sname = None
            for n2, k2, v2 in _fields(buf, *meta) if meta else ():
                if n2 == 1:
                    sid = v2
                elif n2 == 2 and k2 == 2:
                    sname = _text(buf, v2)
            stat_names[sid] = sname
        elif no == 4 and kind == 2:                  # event_metadata entry
            meta = _map_value(buf, value)
            rec = {"name": "", "stats": {}}
            for n2, k2, v2 in _fields(buf, *meta) if meta else ():
                if n2 == 2 and k2 == 2:
                    rec["name"] = _text(buf, v2)
                elif n2 == 5 and k2 == 2:
                    sid, sval = _stat(buf, v2)
                    rec["stats"][sid] = sval
            events.append(rec)
    return stat_names, events


def tables(path: str) -> dict:
    """{(program_id, event name): {"tf_op", "scope" (``scope_of`` it, or its
    consumer's: ``inherit``), "via", "category", "source", "flops",
    "bytes"}} of the first device plane of an ``.xplane.pb`` that names a
    ``program_id`` on its operations ({} where none does)."""
    from benchmark import xplane
    with open(path, "rb") as f, \
            mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ) as buf:
        for no, kind, span in _fields(buf, 0, len(buf)):
            if no != 1 or kind != 2:
                continue
            # a plane's name is a short field before its lines: look at it
            # before reading the tables of a plane that is not a device's
            name = next((_text(buf, v) for n, k, v in _fields(buf, *span)
                         if n == 2 and k == 2), "")
            if not xplane._is_device_plane(name):
                continue
            stat_names, events = _plane(buf, span)
            out = {}
            for rec in events:
                st = {}
                for sid, value in rec["stats"].items():
                    if isinstance(value, tuple):          # ("ref", id)
                        value = stat_names.get(value[1], "")
                    st[stat_names.get(sid)] = value
                if "program_id" not in st:
                    continue
                tf_op = st.get("tf_op", "") or ""
                out[(int(st["program_id"]), rec["name"])] = {
                    "tf_op": tf_op, "scope": scope_of(tf_op), "via": None,
                    "category": st.get("hlo_category", "") or "",
                    "source": st.get("source", "") or "",
                    "flops": st.get("flops"),
                    "bytes": st.get("bytes_accessed")}
            if out:
                return inherit(out)
    return {}


_INSTRUCTION = re.compile(r"%[\w.\-]+")


def inherit(table: dict) -> dict:
    """An operation the COMPILER made (a staged copy, the start and done of
    an asynchronous copy or slice) carries no path of the program: its
    ``tf_op`` is empty, or names the parameter it copies
    (``params['layers.5.wq']``).  It serves the operation that consumes its
    result, so such a record takes the scope of the first consumer that has
    one — an event's name is its instruction's text, operands included —
    through chains (``slice-start`` → ``slice-done`` → the fusion), and
    ``via`` names that consumer.  An operation the program traced
    (``jit(...)/...``) with no ``strom.*`` scope on its path is a hole in
    the partition and stays one."""
    short = {}                  # (program_id, %name) -> key
    users = {}                  # (program_id, %operand) -> [consumer keys]
    for key in table:
        pid, text = key
        head, _, rest = text.partition(" = ")
        short[(pid, head.strip())] = key
        for operand in dict.fromkeys(_INSTRUCTION.findall(rest)):
            users.setdefault((pid, operand), []).append(key)
    for _ in range(4):                               # the longest chain seen
        changed = False
        for (pid, head), key in short.items():
            rec = table[key]
            if rec["scope"][1] or rec["tf_op"].startswith("jit("):
                continue
            for user in users.get((pid, head), ()):
                if table[user]["scope"][1]:
                    rec["scope"] = table[user]["scope"]
                    rec["via"] = table[user]["via"] or user[1].partition(
                        " = ")[0]
                    changed = True
                    break
        if not changed:
            break
    return table


# ------------------------------------------------------------------ the names

def scope_of(tf_op: str) -> tuple:
    """(bucket (width, suffix, cache) or None, family or None, the first
    ``strom.*`` scope past the bucket label or None) of a ``tf_op`` path: the
    family is the first ``strom.<family>`` component, the label of a
    prefill's compiled shape (``strom.prefill.<digits>x<digits>x<digits>``)
    apart."""
    bucket = None
    for part in tf_op.split("/"):
        m = BUCKET.match(part)
        if m:
            bucket = bucket or tuple(int(g) for g in m.groups())
        elif part.startswith("strom."):
            return bucket, part.split(".")[1].rstrip(":"), part.rstrip(":")
    return bucket, None, None


# ------------------------------------------------------------------- the join

def self_ns(ops: list) -> list:
    """[(name, start, own ns)] of ``ops`` [(name, start, end)], sorted by
    start: each operation's duration less its children's (the operations
    that lie inside it)."""
    out, stack = [], []          # stack: [index in out, end]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:                                   # a child: take its time
            e = min(e, stack[-1][1])                # ... off its parent's
            out[stack[-1][0]][2] -= e - s
        out.append([name, s, e - s])
        stack.append((len(out) - 1, e))
    return [tuple(o) for o in out]


class Scoped:
    """One traced run, read once and kept as sums: the executions of every
    program (the ``XLA Modules`` events of the first device plane that ran
    any) and, per (program, program_id, event name), the operations' own
    time — thousands of entries for the millions of events.  ``table`` is
    ``tables``' result."""

    def __init__(self, trace, table: dict):
        from benchmark import xplane
        self.table = table
        #: [(program, program_id, device ns)] in order of start
        self.execs = []
        #: {(program, program_id, event name): [own ns, calls]}
        self.ops = {}
        plane = next((p for p, ops in (trace.ops.items() if trace else ())
                      if ops and trace.modules.get(p)), None)
        if plane is None or not table:
            return
        ops = self_ns(trace.ops[plane])
        starts = [s for _, s, _ in ops]
        for name, s, e in sorted(trace.modules[plane], key=lambda m: m[1]):
            pid = _PROGRAM_ID.search(name)
            pid = int(pid.group(1)) if pid else None
            program = xplane.program_name(name)
            self.execs.append((program, pid, e - s))
            for n, _, own in ops[bisect.bisect_left(starts, s):
                                 bisect.bisect_left(starts, e)]:
                cur = self.ops.setdefault((program, pid, n), [0.0, 0])
                cur[0] += own
                cur[1] += 1

    def records(self, program: str):
        """(program_id, event name, own ns, calls, record or None) of the
        program's operations."""
        for (p, pid, name), (ns, calls) in self.ops.items():
            if p == program:
                yield pid, name, ns, calls, self.table.get((pid, name))

    def has_scopes(self, program: str) -> bool:
        """Whether the program carries the whole partition, told by what
        only it brings: a prefill's bucket label, a scope of ``WHOLE`` in
        the step (older commits scoped the kernels and the MLP alone: a
        share read off them would be of another partition)."""
        return any(rec and (rec["scope"][0] if program == PREFILL
                            else rec["scope"][2] in WHOLE)
                   for *_, rec in self.records(program))

    def program_ns(self, program: str) -> float:
        """Σ of the executions' own device durations."""
        return float(sum(ns for p, _, ns in self.execs if p == program))

    def by_family(self, program: str) -> dict:
        """{family or None (no ``strom.*`` scope): ns} over the program's
        executions."""
        out = {}
        for _, _, ns, _, rec in self.records(program):
            fam = rec["scope"][1] if rec else None
            out[fam] = out.get(fam, 0.0) + ns
        return out

    def copy_ns(self, program: str) -> dict:
        """{hlo_category: ns} of the data-movement operations."""
        out = {}
        for _, _, ns, _, rec in self.records(program):
            if rec and rec["category"] in COPY_KINDS:
                out[rec["category"]] = out.get(rec["category"], 0.0) + ns
        return out

    def buckets(self, program: str = PREFILL) -> dict:
        """{(width, suffix, cache): [device ns of each execution]}; a
        compiled shape whose operations name no bucket, or two, is under
        ``None``."""
        seen = {}
        for pid, _, _, _, rec in self.records(program):
            if rec and rec["scope"][0]:
                seen.setdefault(pid, set()).add(rec["scope"][0])
        out = {}
        for p, pid, ns in self.execs:
            if p == program:
                labels = seen.get(pid, ())
                out.setdefault(next(iter(labels)) if len(labels) == 1
                               else None, []).append(ns)
        return out


def scoped(ctx):
    """The run's ``Scoped``, made once a run (the readers share it); ``None``
    without a trace.  The harness keeps the parsed trace and not its file, so
    the file is found again where ``harness.TraceWindow`` wrote it."""
    if not getattr(ctx, "trace", None):
        return None
    if getattr(ctx, "_scoped", None) is None:
        from benchmark import harness
        path = harness.TraceWindow(False, ctx.workload).file()
        ctx._scoped = Scoped(ctx.trace, tables(path) if path else {})
    return ctx._scoped


def family_share(ctx, program: str, families):
    """Percent of ``program``'s device time in operations under one of
    ``families`` (``None``: under no ``strom.*`` scope); ``None`` where the
    program ran none or carries no scopes."""
    sc = scoped(ctx)
    if sc is None or not sc.has_scopes(program):
        return None
    total = sc.program_ns(program)
    by = sc.by_family(program)
    return 100.0 * sum(by.get(f, 0.0) for f in families) / total \
        if total else None

#!/usr/bin/env python
"""MFU attribution: trace a train step, break device time down by op class.

The raw TFLOP/s number says *how much* of the MXU we use; this
tool says *where the rest went*.  It runs the config-7 train-step
variant (same model/step as ``bench_suite.bench_train``, honoring
``STROM_TRAIN_CFG`` / batch / remat / attn flags) under
``jax.profiler.trace``, then parses the xplane protobuf with
``jax.profiler.ProfileData`` — no TensorBoard dependency — and emits ONE
JSON line:

  - per-category device-time shares over the "XLA Ops" timeline
    (matmul fusions vs elementwise fusions vs copies vs custom calls),
  - device busy-time vs step wall-time (the gap is host/dispatch stall),
  - the top-N individual ops by total device time, truncated names.

Categories are keyword classes over HLO fusion names — coarse by
design: the question the breakdown answers is "is the residual
(1 - MFU) matmul inefficiency, memory-bound elementwise, data movement,
or host stall", which these four buckets decide.

Usage:
    python -m nvme_strom_tpu.tools.profile_report [--batch 8]
        [--remat none|dots|full] [--attn dense|flash] [--seq 1024]
        [--dir DIR]   # parse an existing trace instead of capturing
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import sys
import tempfile
from collections import ChainMap


def _log(msg: str) -> None:
    print(f"profile: {msg}", file=sys.stderr, flush=True)


#: keyword → bucket, first match wins (order matters: a fusion named
#: "%convolution_reduce_fusion" is matmul work even though it is also a
#: fusion).  HLO spellings: dot/convolution for MXU work; Pallas/flash
#: kernels arrive as custom-call "tpu_custom_call".
_CLASSES = (
    ("matmul", ("convolution", "dot", "conv_", "%dot", "matmul",
                "gemm")),
    ("attention-kernel", ("tpu_custom_call", "custom-call", "custom_call",
                          "flash", "pallas")),
    ("copy", ("copy", "bitcast", "transpose", "reshape", "format")),
    ("reduce", ("reduce", "scatter", "gather", "sort", "select-and")),
    ("elementwise-fusion", ("add", "multiply", "subtract",
                            "divide", "exponential", "rsqrt", "tanh",
                            "elementwise", "loop")),
    # LAST, and deliberately its own bucket: a bare "%fusion.212" name
    # says nothing about its constituents — on this runtime's device
    # plane most dots hide inside such names (the 2026-07-31T19:00
    # d2048 parse put 0.75% in matmul at a measured 76 TFLOP/s, which
    # is impossible — the MXU work was inside unnamed fusions).
    # Claiming "elementwise" for them would be the same class of
    # misattribution the operand-text fix removed.
    ("unnamed-fusion", ("fusion",)),
)

#: "opcode(" right after the "= type[shape]{layout}" of an HLO line
_OPCODE = re.compile(r"=\s*[a-z0-9]+\[[^\]]*\][^\s]*\s+([a-z0-9_-]+)\(")


def _keyword_bucket(text: str):
    low = text.lower()
    for bucket, keys in _CLASSES:
        if any(k in low for k in keys):
            return bucket
    return None


def classify(name: str) -> str:
    """Bucket an op by its own identity, NEVER its operands.

    The 2026-07-31 window's headline-grade misattribution: TPU op
    events carry the FULL HLO line (operands included), so any matmul
    fusion consuming a ``%transpose`` operand keyword-matched "copy" —
    the ledgered profile read "69% copy" for a step that was really
    matmul-bound.  Classification now looks only at (in order) the
    opcode after the "=", then the lhs instruction name (XLA names
    fusions after their constituent ops), and for bare fusions falls
    through to the name's constituents."""
    lhs = name.split("=", 1)[0].strip()
    m = _OPCODE.search(name)
    if m and m.group(1) != "fusion":
        b = _keyword_bucket(m.group(1))
        if b is not None:
            return b
    return _keyword_bucket(lhs) or "other"


#: xprof's own per-op category stat (present on TPU device planes) —
#: authoritative when available; values like "convolution fusion",
#: "loop fusion", "copy", "all-reduce", "custom-call"
_CATEGORY_STAT_KEYS = ("hlo_category", "category")

#: computation header: "%name (params...) -> type {"
_HLO_COMP = re.compile(r"^(?:ENTRY\s+)?(%[\w.\-]+)\s*\([^)]*\)\s*->")

#: fusion instruction with its called computation
_HLO_FUSION = re.compile(r"(%[\w.\-]*fusion[\w.\-]*)\s*=.*?"
                         r"\bcalls=(%[\w.\-]+)")

#: fused-computation opcode → resolved bucket, first match wins (a
#: dot+bias+gelu output fusion is MXU work; a reduce+multiply fusion is
#: VPU reduction work)
_FUSED_BUCKETS = (
    ("matmul-fusion", ("dot", "convolution")),
    ("reduce-fusion", ("reduce", "reduce-window", "scatter", "sort",
                       "select-and-scatter")),
    ("gather-fusion", ("gather", "dynamic-slice", "dynamic-update-slice")),
    # data movement is its own answer, exactly as in _CLASSES — filing
    # a transpose/copy-only fusion under elementwise would inflate the
    # compute share with memory traffic
    ("copy-fusion", ("transpose", "copy", "bitcast", "reshape")),
)


#: generous per-op achieved-TFLOP/s ceiling (v5e bf16 peak is 197; a
#: mapped op "running" faster than this proves its FLOPs↔event mapping
#: wrong, not that the MXU broke physics)
_PLAUSIBLE_TFLOPS_CAP = 250.0

#: "type[d0,d1,...]" — first shape literal in a fragment
_SHAPE = re.compile(r"\b[a-z0-9]+\[([0-9,]*)\]")
_LHS_CONTRACT = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")


#: operand inside an op's parens: optional inline shape, then %name
_OPERAND = re.compile(r"((?:[a-z0-9]+\[[0-9,]*\]\S*\s+)?%[\w.\-]+)")
_DIM_LABELS = re.compile(r"dim_labels=(\w+)_(\w+)->(\w+)")
#: "name: type[dims]" parameter declarations in computation headers
_HEADER_PARAM = re.compile(r"([\w.\-]+)\s*:\s*[a-z0-9]+\[([0-9,]*)\]")


def _operand_dims(tok: str, defs: dict) -> list:
    """Dims of one operand token — inline shape if the dump carries
    operand shapes, else resolved via the module-wide ``defs``."""
    m = _SHAPE.match(tok)
    if m:
        return [int(d) for d in m.group(1).split(",") if d]
    return defs[tok.rsplit("%", 1)[-1]]


def _matmul_flops(line: str, opcode: str, defs: dict) -> int:
    """FLOPs of one optimized-HLO ``dot`` or matmul-as-``convolution``
    line: 2·|output|·K.

    The output shape already carries the batch and free dims, so
    multiplying by the contracted sizes is exact for batched dots too.
    XLA's optimized modules spell many matmuls as convolutions
    (``dim_labels=bf_io->bf`` and friends); there K is the lhs 'f'
    (feature) dim times any rhs spatial kernel dims.  0 on any parse
    miss — an unparsed op must read as "no efficiency estimate", never
    as a wrong one."""
    return _matmul_info(line, opcode, defs)[0]


#: the JAX source mapping XLA stamps on every instruction
_OP_NAME = re.compile(r'op_name="([^"]+)"')


def _matmul_info(line: str, opcode: str, defs: dict) -> tuple:
    """(FLOPs, source descriptor) for one dot/convolution line.

    The descriptor — "<out dims>@k<K> <op_name tail>" — is what lets a
    ledgered efficiency row name the slow matmul in MODEL terms (which
    projection, fwd or transpose(jvp) bwd) without the HLO dump, which
    is gone by the time anyone reads the row.  (0, "") on parse miss."""
    try:
        rhs = line.split("=", 1)[1]
        out = _SHAPE.search(rhs).group(1)
        elems = 1
        for d in out.split(","):
            if d:
                elems *= int(d)
        args = rhs[rhs.index(opcode + "(") + len(opcode) + 1:]
        toks = _OPERAND.findall(args)
        lhs = _operand_dims(toks[0], defs)
        if opcode == "dot":
            k = 1
            for i in (int(x) for x in
                      _LHS_CONTRACT.search(line).group(1).split(",") if x):
                k *= lhs[i]
        else:
            lhs_l, rhs_l, _ = _DIM_LABELS.search(line).groups()
            k = lhs[lhs_l.index("f")]
            rdims = _operand_dims(toks[1], defs)
            for ch, d in zip(rhs_l, rdims):
                if ch.isdigit():
                    k *= d
        m = _OP_NAME.search(line)
        desc = f"{out.replace(',', 'x')}@k{k}"
        if m:
            desc += " " + m.group(1)[-64:]
        return 2 * elems * k, desc
    except Exception:
        return 0, ""


def _load_hlo_maps(trace_dir: str) -> tuple:
    """ONE walk of the optimized-HLO dump → (bucket map, FLOPs map).

    Both public views come from the same line-walk so a dump-format
    change cannot silently diverge them: computation bodies yield the
    constituent-opcode sets (bucket classification) AND the dot/conv
    FLOPs; the fusion instructions then resolve each %fusion.NN to its
    called computation for both maps at once.  Keys are sigil-less
    ("fusion.212"): the TPU device plane names events "%fusion.212"
    but the CPU host plane logs "fusion.212" — lookups strip the sigil
    to match either."""
    path = os.path.join(trace_dir, "optimized_hlo.txt")
    if not os.path.exists(path):
        return {}, {}, {}
    with open(path) as f:
        lines = f.read().splitlines()

    # pass 1 — module-wide name → dims for INSTRUCTION names (those
    # really are unique module-wide, and operands routinely reference
    # names defined in OTHER computations, e.g. a fused conv consuming
    # an ENTRY-level fusion's output).  Computation-header PARAMETER
    # names (param_0, Arg_0.1) are NOT module-unique — every fused
    # computation reuses them — so they are scoped per computation and
    # consulted first, falling back to the module-wide map only for
    # instruction names; a flat map here let a later computation's
    # same-named param silently overwrite an earlier one and mis-size K
    # for operands without inline shapes.
    defs: dict[str, list] = {}
    comp_params: dict[str, dict] = {}
    cur_hdr = None
    for line in lines:
        stripped = line.strip()
        if stripped.endswith("{"):          # computation header params
            m = _HLO_COMP.match(stripped)
            # keyed exactly as pass 2's ``cur`` (sigil kept) so the
            # per-computation scope lookup matches
            cur_hdr = m.group(1) if m else None
            if cur_hdr is not None:
                scope = comp_params.setdefault(cur_hdr, {})
                for name, dims in _HEADER_PARAM.findall(stripped):
                    scope[name] = [int(d) for d in dims.split(",") if d]
            continue
        if stripped.startswith("}"):
            cur_hdr = None
            continue
        if "=" in stripped:
            name = stripped.removeprefix("ROOT ").split("=", 1)[0].strip()
            if name.startswith("%"):
                sh = _SHAPE.search(stripped.split("=", 1)[1])
                if sh:
                    dims = [int(d) for d in sh.group(1).split(",") if d]
                    # parameter instructions (%p0 = ... parameter(N))
                    # reuse names across computations just like header
                    # params — scope them; everything else is a real
                    # module-unique instruction name
                    if "parameter(" in stripped and cur_hdr is not None:
                        comp_params.setdefault(cur_hdr, {})[
                            name.lstrip("%")] = dims
                    else:
                        defs[name.lstrip("%")] = dims

    # pass 2 — per-computation opcode sets and dot/conv FLOPs, plus
    # FLOPs of un-fused matmul instructions (profiler events under
    # their own names)
    comp_ops: dict[str, set] = {}
    comp_flops: dict[str, int] = {}
    comp_descs: dict[str, list] = {}       # (flops, source desc) pairs
    inst_flops: dict[str, int] = {}
    inst_descs: dict[str, list] = {}
    cur = None
    for line in lines:
        m = _HLO_COMP.match(line.strip())
        if m and line.rstrip().endswith("{"):
            cur = m.group(1)
            comp_ops[cur] = set()
            continue
        if line.startswith("}"):
            cur = None
            continue
        op = _OPCODE.search(line)
        if not op:
            continue
        if cur is not None:
            comp_ops[cur].add(op.group(1))
        if op.group(1) in ("dot", "convolution"):
            # lookup order: this computation's own params, then
            # module-wide instruction names
            scope = (ChainMap(comp_params[cur], defs)
                     if cur is not None and cur in comp_params else defs)
            fl, desc = _matmul_info(line, op.group(1), scope)
            if not fl:
                continue
            if cur is not None:
                comp_flops[cur] = comp_flops.get(cur, 0) + fl
                comp_descs.setdefault(cur, []).append((fl, desc))
            name = line.strip().removeprefix("ROOT ").split("=", 1)[0]
            name = name.strip()
            if name.startswith("%"):
                inst_flops[name.lstrip("%")] = fl
                inst_descs[name.lstrip("%")] = [(fl, desc)]

    # pass 3 — resolve fusion instructions through their called
    # computations, for both maps at once
    fmap: dict[str, str] = {}
    for line in lines:
        m = _HLO_FUSION.search(line)
        if not m:
            continue
        key = m.group(1).lstrip("%")
        if m.group(2) in comp_flops:
            inst_flops[key] = comp_flops[m.group(2)]
            inst_descs[key] = comp_descs.get(m.group(2), [])
        ops = comp_ops.get(m.group(2), set())
        for bucket, keys in _FUSED_BUCKETS:
            if any(o in keys for o in ops):
                fmap[key] = bucket
                break
        else:
            if ops:
                fmap[key] = "elementwise-fusion"
    return fmap, inst_flops, inst_descs


def load_fusion_flops(trace_dir: str) -> dict:
    """{"fusion.NN" | "dot.NN": dot/conv FLOPs per execution} from the
    optimized-HLO dump — the per-op half of the MXU-efficiency table.

    The window-8 fusion-resolved parses settled WHERE the time goes
    (matmul-fusion ≈ 88% at busy_frac 1.0) but not WHY those fusions
    run at ~54% of bf16 peak.  Dividing each fusion's known dot FLOPs
    by its measured device time names the underperformers exactly —
    lm_head vs ffn vs attention projections — or shows the deficit is
    spread (a small-shape tax no single kernel fix recovers)."""
    return _load_hlo_maps(trace_dir)[1]


def load_fusion_map(trace_dir: str) -> dict:
    """{"fusion.NN": resolved bucket} from the post-optimization HLO
    dump the capture step writes next to the trace (optimized_hlo.txt).

    The profiler's device plane names most of a train step's time after
    bare "%fusion.NN" events — ~70% of device time in the valid
    window-7 parses, which attributes nothing.  The dumped module
    defines each %fused_computation body, so the fusion's constituent
    opcodes are known exactly; classification by real constituents
    replaces the "unnamed-fusion" bucket without re-introducing the
    operand-text guessing the c92ebd3 fix removed."""
    return _load_hlo_maps(trace_dir)[0]


def _fmap_bucket(ev, fmap: dict | None):
    """Resolved bucket for an event via the dumped-HLO fusion map, or
    None on a miss — split out so the tally can count how much device
    time actually resolved (a silent name-format mismatch must read as
    0 ms resolved, not as a successful attribution)."""
    if not fmap:
        return None
    return fmap.get(ev.name.split("=", 1)[0].strip().lstrip("%"))


def event_bucket(ev, fmap: dict | None = None) -> str:
    """Bucket for one xplane event: the dumped-HLO fusion resolution
    when available (exact constituents), else the profiler's
    hlo_category stat, else name-based :func:`classify`."""
    b = _fmap_bucket(ev, fmap)
    if b is not None:
        return b
    try:
        for k, v in ev.stats:
            if str(k) in _CATEGORY_STAT_KEYS:
                return _keyword_bucket(str(v)) or "other"
    except Exception:
        pass
    return classify(ev.name)


class _XStatView:
    """(key, value) pairs of one XEvent's stats — the iteration shape
    ``event_bucket`` expects from ``jax.profiler.ProfileData``."""

    __slots__ = ("_pairs",)

    def __init__(self, pairs):
        self._pairs = pairs

    def __iter__(self):
        return iter(self._pairs)


class _XEventView:
    __slots__ = ("name", "start_ns", "duration_ns", "stats")

    def __init__(self, name, start_ns, duration_ns, stats):
        self.name = name
        self.start_ns = start_ns
        self.duration_ns = duration_ns
        self.stats = stats


class _XLineView:
    __slots__ = ("name", "events")

    def __init__(self, name, events):
        self.name = name
        self.events = events


class _XPlaneView:
    __slots__ = ("name", "lines")

    def __init__(self, name, lines):
        self.name = name
        self.lines = lines


class _XSpaceView:
    __slots__ = ("planes",)

    def __init__(self, planes):
        self.planes = planes


def _stat_value(stat, stat_md):
    for f in ("double_value", "uint64_value", "int64_value", "str_value",
              "bytes_value"):
        if stat.HasField(f):
            return getattr(stat, f)
    if stat.HasField("ref_value"):
        md = stat_md.get(stat.ref_value)
        return md.name if md is not None else stat.ref_value
    return ""


def _xplane_pb2():
    """The XSpace protobuf module, wherever this install keeps it."""
    for mod in ("tensorflow.tsl.profiler.protobuf.xplane_pb2",
                "tsl.profiler.protobuf.xplane_pb2",
                "tensorflow.core.profiler.protobuf.xplane_pb2"):
        try:
            import importlib
            return importlib.import_module(mod)
        except Exception:
            continue
    return None


def _load_profile_data(path: str):
    """``jax.profiler.ProfileData``-shaped view of one xplane.pb.

    Newer jax ships ``ProfileData`` (no TensorBoard dependency); older
    runtimes (jax ≤ 0.4.x of this container) don't — there the raw
    XSpace protobuf is decoded into the same planes/lines/events shape,
    so ``parse_trace`` has exactly one consumption path.  Times follow
    ProfileData's convention: ps-resolution fields scaled to ns."""
    try:
        import jax
        pd = getattr(jax.profiler, "ProfileData", None)
        if pd is not None:
            return pd.from_file(path)
    except Exception:
        pass
    pb2 = _xplane_pb2()
    if pb2 is None:
        raise RuntimeError(
            "no xplane parser available: jax.profiler.ProfileData is "
            "missing and no xplane_pb2 protobuf module could be "
            "imported — upgrade jax or install tensorflow")
    with open(path, "rb") as f:
        space = pb2.XSpace.FromString(f.read())
    planes = []
    for plane in space.planes:
        ev_md = dict(plane.event_metadata)
        st_md = dict(plane.stat_metadata)
        lines = []
        for line in plane.lines:
            t0 = int(line.timestamp_ns)
            events = []
            for ev in line.events:
                md = ev_md.get(ev.metadata_id)
                name = ""
                if md is not None:
                    name = md.display_name or md.name
                stats = _XStatView([
                    ((st_md[s.metadata_id].name
                      if s.metadata_id in st_md else str(s.metadata_id)),
                     _stat_value(s, st_md))
                    for s in ev.stats])
                events.append(_XEventView(
                    name, t0 + ev.offset_ps / 1000.0,
                    ev.duration_ps / 1000.0, stats))
            lines.append(_XLineView(line.name, events))
        planes.append(_XPlaneView(plane.name, lines))
    return _XSpaceView(planes)


def parse_trace(trace_dir: str) -> dict:
    """Aggregate the device plane of the newest xplane.pb under
    ``trace_dir``.  Returns the breakdown dict (no I/O)."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    pdata = _load_profile_data(paths[-1])
    dev_plane = host_plane = None
    for p in pdata.planes:
        if "/device:" in p.name and "CUSTOM" not in p.name:
            dev_plane = p
            break
        if p.name == "/host:CPU":
            host_plane = p

    fmap, flops_map, descs_map = _load_hlo_maps(trace_dir)
    by_cat: dict[str, float] = {}
    by_op: dict[str, float] = {}
    # category → {op: ns}: names the time, not just buckets — the
    # 2026-07-31 69%-copy profile was unactionable without knowing
    # WHICH ops the bucket held
    by_cat_op: dict[str, dict] = {}
    module_ns = []          # per-step module durations (XLA Modules line)
    module_spans = []       # (start, end) to bound the traced window

    resolved_ns = [0.0]

    def _tally(ev) -> None:
        cat = event_bucket(ev, fmap)
        if _fmap_bucket(ev, fmap) is not None:
            resolved_ns[0] += ev.duration_ns
        by_cat[cat] = by_cat.get(cat, 0.0) + ev.duration_ns
        # strip the "= <type> op(...)" tail: the lhs name keys the op;
        # full HLO text would blow up the ledger line
        short = ev.name.split("=", 1)[0].strip()[:48] or ev.name[:48]
        by_op[short] = by_op.get(short, 0.0) + ev.duration_ns
        co = by_cat_op.setdefault(cat, {})
        co[short] = co.get(short, 0.0) + ev.duration_ns

    if dev_plane is not None:
        for line in dev_plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    module_ns.append(ev.duration_ns)
                    module_spans.append((ev.start_ns,
                                         ev.start_ns + ev.duration_ns))
            elif line.name == "XLA Ops":
                for ev in line.events:
                    _tally(ev)
    elif host_plane is not None:
        # a CPU trace (tests): the CPU PJRT client logs
        # ops on tf_XLAPjRtCpuClient/* thread lines, with paired
        # "end: <op>" markers and threadpool noise to skip.  Good
        # enough for parser coverage; the MFU story itself is TPU-only.
        for line in host_plane.lines:
            if not line.name.startswith("tf_"):
                continue
            for ev in line.events:
                if ev.name.startswith(("end:", "ThreadpoolListener",
                                       "ThunkExecutor")):
                    continue
                _tally(ev)
    else:
        raise RuntimeError(
            f"no device or host-CPU plane in {paths[-1]}; planes="
            f"{[p.name for p in pdata.planes]}")
    if not by_cat:
        raise RuntimeError("trace has no op events")

    busy_ns = sum(by_cat.values())
    # wall of the traced region on the device timeline: first module
    # start to last module end (covers inter-step gaps = host stall)
    wall_ns = (max(e for _, e in module_spans)
               - min(s for s, _ in module_spans)) if module_spans else busy_ns
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:8]

    # MXU-efficiency table: each op's dot FLOPs (from the HLO dump) over
    # its measured per-execution time.  An op's total ns spans all
    # traced steps; one HLO instruction executes once per step.
    matmul_eff = {}
    if flops_map and module_ns:
        steps = len(module_ns)
        ranked = sorted(((ns, op) for op, ns in by_op.items()
                         if flops_map.get(op.lstrip("%")) and ns > 0),
                        reverse=True)[:10]
        plausible_ns = plausible_fl = 0
        for ns, op in ranked:
            key = op.lstrip("%")
            fl = flops_map[key]
            tflops = fl * steps / ns / 1e3
            entry = {"ms": round(ns / 1e6, 3), "tflops": round(tflops, 1)}
            # an op "running" above device peak means the FLOPs↔event
            # mapping is wrong for it (the all-mapped aggregate once
            # ledgered 764 TFLOP/s at d2048 from exactly such tails) —
            # keep the entry visible but flagged, and out of the
            # aggregate
            if tflops > _PLAUSIBLE_TFLOPS_CAP:
                entry["suspect_mapping"] = True
            else:
                plausible_ns += ns
                plausible_fl += fl
            # top source descriptors: which model matmuls this fusion
            # holds ("8192x11008@k4096 ...transpose(jvp())/dot_general")
            descs = sorted(descs_map.get(key, ()), reverse=True)[:2]
            if descs:
                entry["ops"] = [d for _, d in descs]
            matmul_eff[op] = entry
        if plausible_ns:
            matmul_eff["_aggregate_plausible"] = {
                "ms": round(plausible_ns / 1e6, 3),
                "tflops": round(plausible_fl * steps / plausible_ns
                                / 1e3, 1)}
    return {
        "plane": (dev_plane or host_plane).name,
        "trace": os.path.basename(paths[-1]),
        "fusions_resolved": len(fmap),
        # how much device time the map ACTUALLY resolved: 0 despite a
        # populated map means the event-name format diverged from the
        # dump — the attribution did not happen, whatever map size says
        "fusion_resolved_ms": round(resolved_ns[0] / 1e6, 3),
        "steps_traced": len(module_ns),
        "device_busy_ms": round(busy_ns / 1e6, 3),
        "window_wall_ms": round(wall_ns / 1e6, 3),
        "busy_frac": round(busy_ns / wall_ns, 4) if wall_ns else None,
        "category_ms": {k: round(v / 1e6, 3)
                        for k, v in sorted(by_cat.items(),
                                           key=lambda kv: -kv[1])},
        "category_frac": {k: round(v / busy_ns, 4)
                          for k, v in sorted(by_cat.items(),
                                             key=lambda kv: -kv[1])},
        "top_ops_ms": {k: round(v / 1e6, 3) for k, v in top},
        # per-dot-op achieved TFLOP/s (present when the HLO dump parsed)
        **({"matmul_eff_tflops": matmul_eff} if matmul_eff else {}),
        "category_top_ops_ms": {
            cat: {k: round(v / 1e6, 3)
                  for k, v in sorted(ops.items(),
                                     key=lambda kv: -kv[1])[:4]}
            for cat, ops in sorted(by_cat_op.items(),
                                   key=lambda kv: -sum(kv[1].values()))},
    }


def capture(batch: int, seq: int, remat: str, attn: str,
            trace_dir: str) -> float:
    """Run the measured train variant with a 3-step trace; returns the
    median model-FLOP/s (same number config 7 reports)."""
    import dataclasses

    import jax

    import bench_suite
    from nvme_strom_tpu.utils.compile_cache import enable_compile_cache

    # a standalone capture bypasses bench_suite.run()'s cache enable;
    # the HLO-dump path AOT-compiles the step before executing it, and
    # only the persistent cache makes that one compile, not two
    from nvme_strom_tpu.utils.device import require_tpu
    require_tpu("profile_report")       # no TPU: exit non-zero
    enable_compile_cache()
    cfg = dataclasses.replace(bench_suite._bench_cfg(train_override=True),
                              remat_policy=(None if remat == "none"
                                            else remat),
                              remat=False)
    dev = jax.devices()[0]
    _log(f"tracing train step on {dev.platform}: d={cfg.d_model} "
         f"L={cfg.n_layers} b={batch} s={seq} remat={remat} attn={attn}")
    return bench_suite._train_variant(cfg, batch, seq, dev,
                                      profile_dir=trace_dir, attn=attn)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--remat", default="none",
                    choices=("none", "dots", "full"))
    ap.add_argument("--attn", default="dense", choices=("dense", "flash"))
    ap.add_argument("--dir", default=None,
                    help="parse an existing trace dir (skip capture)")
    args = ap.parse_args(argv)

    flops = None
    if args.dir:
        trace_dir = args.dir
    else:
        trace_dir = tempfile.mkdtemp(prefix="strom_profile_")
        try:
            flops = capture(args.batch, args.seq, args.remat, args.attn,
                            trace_dir)
        except Exception as e:  # noqa: BLE001 — report the failure mode
            _log(f"capture failed: {type(e).__name__}: {str(e)[:200]}")
            shutil.rmtree(trace_dir, ignore_errors=True)
            return 1

    try:
        rep = parse_trace(trace_dir)
    finally:
        if not args.dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    if args.dir:
        # parse-only mode: the trace came from an earlier capture (the
        # suite's STROM_PROFILE_DIR hook) — do NOT instantiate a
        # backend here: only the process that holds the chip can, and
        # parsing needs none.  The device identity is in the trace's
        # plane name.
        rep["device"] = rep["plane"]
        rep["variant"] = (f"(from {args.dir}) "
                          f"cfg={os.environ.get('STROM_TRAIN_CFG', 'default')}")
    else:
        import jax
        dev = jax.devices()[0]
        peak = __import__("bench_suite")._peak_flops(dev)
        if flops is not None:
            rep["tflops"] = round(flops / 1e12, 3)
            if peak:
                rep["mfu"] = round(flops / peak, 4)
        rep["device"] = f"{dev.platform} {dev.device_kind}"
        from nvme_strom_tpu.utils.device import device_info
        rep.update(device_info())
        rep["variant"] = (f"b={args.batch} s={args.seq} "
                          f"remat={args.remat} attn={args.attn} "
                          f"cfg={os.environ.get('STROM_TRAIN_CFG', 'default')}")
    print(json.dumps({"metric": "config7:profile-breakdown", **rep}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Device time by the program's own names, without a viewer:

    python3 benchmark/tools/scope_table.py <trace dir | .xplane.pb> [k [out]]

prints, for the newest ``.xplane.pb`` under the directory (a traced benchmark
run leaves one under ``benchmark/.trace/<cell>/``):

1. program x family: device seconds of every jitted program by the family of
   ``strom.*`` scopes its operations lie under, the seconds under none, the
   gaps between operations, and how far the rows add up to the program's own
   seconds (its ``XLA Modules`` events);
2. the copy-kind operations of each program, by ``hlo_category``;
3. program x bucket for ``_paged_prefill``: executions, median ms, rows
   (width x suffix) and us a row of each compiled shape, found by the label
   ``strom.prefill.<width>x<suffix>x<cache>`` its operations carry, beside the
   ``program=`` values of the host spans ``strom.serve.prefill`` of the same
   trace;
4. the k (default 10) kinds of operation with most device time: program,
   its first ``strom.*`` scope (for an operation the compiler made itself,
   its consumer's, and ``via`` which), the path's tail, ``hlo_category`` and
   source line (``out.json`` holds every kind, not the first k).

The reading is ``benchmark/layer_metrics/_scope_trace.py``'s, which the
per-layer metrics share."""

from __future__ import annotations

import glob
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def newest(root: str) -> str:
    if os.path.isfile(root):
        return root
    paths = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise SystemExit(f"scope_table: no .xplane.pb under {root}")
    return paths[-1]


def span_programs(path: str) -> dict:
    """{``program=`` value: count} of the host spans ``strom.serve.prefill``
    (the one thing here ``xplane.load`` does not keep: a span's arguments)."""
    import jax
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "strom.serve.prefill":
                    value = next((v for k, v in ev.stats
                                  if k == "program"), None)
                    out[value] = out.get(value, 0) + 1
    return out


def tail(tf_op: str, n: int = 3) -> str:
    return "/".join(tf_op.rstrip(":").split("/")[-n:])


def source_line(source: str) -> str:
    """``file:line`` from the repository's root, wherever the checkout was."""
    for top in ("/nvme_strom_tpu/", "/benchmark/", "/examples/"):
        if top in source:
            return top[1:] + source.split(top, 1)[1]
    return source


def report(path: str, k: int = 10) -> dict:
    """Everything the tool prints, as one object."""
    from benchmark import xplane
    from benchmark.layer_metrics import _scope_trace as S
    tr = xplane.load(path)
    sc = S.Scoped(tr, S.tables(path))
    programs = {}
    for name in dict.fromkeys(p for p, *_ in sc.execs):
        total = sc.program_ns(name)
        by = sc.by_family(name)
        inside = sum(by.values())
        programs[name] = {
            "executions": sum(1 for p, *_ in sc.execs if p == name),
            "seconds": total / 1e9,
            "families": {str(f): t / 1e9 for f, t in
                         sorted(by.items(), key=lambda kv: -kv[1])},
            "gaps_s": (total - inside) / 1e9,
            "ops_over_program": inside / total if total else None,
            "copies": {c: t / 1e9 for c, t in sc.copy_ns(name).items()}}
    buckets = {}
    for label, runs in sc.buckets().items():
        rows = label[0] * label[1] if label else None
        buckets["x".join(map(str, label)) if label else "None"] = {
            "executions": len(runs),
            "median_ms": statistics.median(runs) / 1e6,
            "seconds": sum(runs) / 1e9, "rows": rows,
            "us_per_row": sum(runs) / 1e3 / (rows * len(runs))
            if rows else None}
    by_op = {}      # equal work of one program under one path and family
    for prog in programs:
        for _, name, ns, calls, rec in sc.records(prog):
            key = (prog, xplane.op_key(name), rec["tf_op"] if rec else None,
                   rec["scope"][1:] if rec else (None, None))
            cur = by_op.setdefault(key, [0.0, 0, rec])
            cur[0] += ns
            cur[1] += calls
    ops = []
    for (prog, key, tf_op, (family, scope)), (ns, calls, rec) in sorted(
            by_op.items(), key=lambda kv: -kv[1][0]):
        ops.append({"program": prog, "op": key, "seconds": ns / 1e9,
                    "calls": calls, "family": family, "scope": scope,
                    # a compiler-made operation under its consumer's scope
                    "via": xplane.op_key(rec["via"]) if rec and rec["via"]
                    else None,
                    "tf_op": tail(tf_op or ""),
                    "category": rec["category"] if rec else None,
                    "source": source_line(rec["source"]) if rec else None})
    return {"file": path, "busy_s": xplane.busy_seconds(tr),
            "table_records": len(sc.table), "programs": programs,
            "buckets": buckets, "span_programs": span_programs(path),
            "top": ops[:k], "ops": ops}


def show(rep: dict) -> None:
    print(f"{rep['file']}: busy {rep['busy_s']:.3f} s, "
          f"{rep['table_records']} operation records")
    print("\n1. program x family (device s)")
    for name, p in rep["programs"].items():
        fam = "  ".join(f"{f}={t:.3f}" for f, t in p["families"].items())
        print(f"  {name}: {p['executions']} executions, {p['seconds']:.3f} s"
              f" = {fam}  gaps={p['gaps_s']:.3f}"
              f"  (operations / program = {p['ops_over_program']:.4f})")
    print("\n2. copy-kind operations (device s)")
    for name, p in rep["programs"].items():
        if p["copies"]:
            print(f"  {name}: " + "  ".join(
                f"{c}={t:.3f}" for c, t in sorted(p["copies"].items())))
    print("\n3. _paged_prefill by bucket (width x suffix x cache)")
    for label, b in sorted(rep["buckets"].items(),
                           key=lambda kv: -kv[1]["seconds"]):
        per = f"{b['us_per_row']:.2f}" if b["us_per_row"] else "-"
        print(f"  {label}: {b['executions']} executions, median "
              f"{b['median_ms']:.2f} ms, {b['seconds']:.3f} s, rows "
              f"{b['rows']}, {per} us a row")
    spans = rep["span_programs"]
    print(f"  host spans' program=: {spans}")
    print(f"  labels equal the spans' values: "
          f"{set(rep['buckets']) == set(map(str, spans))}")
    print(f"\n4. the {len(rep['top'])} largest kinds of operation")
    for t in rep["top"]:
        via = f" via {t['via']}" if t["via"] else ""
        print(f"  {t['seconds']:.3f} s x{t['calls']}  {t['program']}  "
              f"{t['op']}  [{t['scope']}{via}] {t['tf_op']}  "
              f"({t['category']}; {t['source']})")


def main() -> int:
    import json
    rep = report(newest(sys.argv[1]),
                 int(sys.argv[2]) if len(sys.argv) > 2 else 10)
    show(rep)
    if len(sys.argv) > 3:                   # the same, for a machine
        with open(sys.argv[3], "w") as f:
            json.dump(rep, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

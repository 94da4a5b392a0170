"""One recorded run read by two trees' readers: what a PR that moves, folds or
rewrites per-layer readers owes (PR 41) — the parent's readers and the
change's over the SAME trace and the same counters, value for value.

    python3 benchmark/tools/reread.py run W SEED SECONDS
        one traced run of cell W on the chip (``run.execute``); beside the
        profiler's file under ``benchmark/.trace/W/`` it leaves ``ctx.pkl``
        (the run's facts, configuration, traffic, peaks and clocks: what a
        reader takes from ``ctx``) and prints the result line
    python3 benchmark/tools/reread.py read DIR [TREE]
        every per-layer entry TREE's ``BENCHMARK.json`` lists for that cell
        (TREE: a checkout, this one by default), read by TREE's readers from
        DIR's trace and ``ctx.pkl``; prints {reader: [entry, value]} — keyed
        by the reader, which a fold leaves alone where it renames entries
    python3 benchmark/tools/reread.py diff A.json B.json
        the readers whose values differ between two such outputs

Reading needs no chip (``JAX_PLATFORMS=cpu`` will do)."""

from __future__ import annotations

import json
import os
import pickle
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KEPT = ("workload", "seed", "seconds", "cell", "config", "traffic", "facts",
        "peaks", "window_s", "trace_window_s", "setup_s", "memory_peak_bytes")


def run(workload: str, seed: str, seconds: str) -> int:
    sys.path.insert(0, ROOT)
    from benchmark import harness, run as bench_run
    out, ctx = bench_run.execute(["--workload", workload, "--seed", seed,
                                  "--seconds", seconds, "--trace", "1"])
    where = harness.TraceWindow(False, workload).dir
    with open(os.path.join(where, "ctx.pkl"), "wb") as f:
        pickle.dump({k: getattr(ctx, k) for k in KEPT}, f)
    harness.emit(out)
    return 0


def read(where: str, tree: str = ROOT) -> int:
    sys.path.insert(0, os.path.abspath(tree))
    from benchmark import harness, run as bench_run, xplane
    from benchmark.layer_metrics import _scope_trace as S
    with open(os.path.join(where, "ctx.pkl"), "rb") as f:
        ctx = types.SimpleNamespace(**pickle.load(f))
    window = harness.TraceWindow(False, ctx.workload)
    window.dir = where
    path = window.file()
    ctx.trace = xplane.load(path)
    ctx._scoped = S.Scoped(ctx.trace, S.tables(path))
    _, per_layer = bench_run.cell_metrics(harness.load_json("BENCHMARK.json"),
                                          ctx.workload)
    out = {}
    for m in per_layer:
        value = harness.plugin("layer_metrics", m["name"]).read(ctx)
        out[m["name"].split(".", 1)[0]] = [
            m["name"], None if value is None else float(value)]
    print(json.dumps(out))
    return 0


def diff(a: str, b: str) -> int:
    with open(a) as fa, open(b) as fb:
        one, two = json.load(fa), json.load(fb)
    differing = 0
    for reader in sorted(set(one) | set(two)):
        x, y = one.get(reader), two.get(reader)
        if x is None or y is None:
            print(f"only in {'B' if x is None else 'A'}: {(x or y)[0]} = "
                  f"{(x or y)[1]!r}")
        elif x[1] != y[1]:
            differing += 1
            print(f"DIFFER {x[0]} = {x[1]!r}  |  {y[0]} = {y[1]!r}")
    print(f"{len(set(one) & set(two))} readers in both, {differing} differ")
    return 1 if differing else 0


if __name__ == "__main__":
    sys.exit({"run": run, "read": read, "diff": diff}[sys.argv[1]](
        *sys.argv[2:]))

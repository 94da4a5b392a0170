"""The benchmark's one command:

    python3 benchmark/run.py --workload W --seed N --seconds S --trace 0|1

One run of one cell of ``BENCHMARK.json`` in this process, on the machine it
is started on.  Prints the device first and one JSON object last."""

from __future__ import annotations

import time

T_START = time.monotonic()          # set-up is counted from here

import argparse                     # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402
import types                        # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: the benchmark loop's host annotations, innermost first (idle gaps are
#: bucketed by them)
PHASES = ("admit", "submit", "wait", "restore", "step")


def cell_metrics(bench: dict, cell: str) -> tuple:
    """(end-to-end, per-layer) entries this cell reports: an end-to-end metric
    with no ``workloads`` is every cell's; a per-layer metric with none is
    reported wherever the metric it moves is."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if (cell in m["workloads"] if "workloads" in m
               else m["moves"] in names)]
    return e2e, per


def execute(argv=None, test: dict | None = None) -> tuple:
    """One run; returns (the result object, the run's context).  ``test`` is
    for the benchmark's own tests and tools (never reachable from the command
    line): ``allow_cpu``, ``config``/``traffic`` overrides merged over the
    files', and hooks the runners call."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    test = test or {}

    from benchmark import harness
    spec = harness.load_cell(args.workload)
    cell, bench = spec["cell"], spec["bench"]
    config = dict(spec["config"], **test.get("config", {}))
    traffic = dict(spec["traffic"], **test.get("traffic", {}))
    try:
        import nvme_strom_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        raise SystemExit(f"benchmark: the program is not in this checkout "
                         f"({e})")
    info = harness.require_chips(cell["chips"], test.get("allow_cpu", False))
    cache = harness.enable_compile_cache()
    print(f"compile cache: {cache}", flush=True)
    import jax
    devices = jax.devices()[:cell["chips"]]

    ctx = types.SimpleNamespace(
        workload=args.workload, seed=args.seed, seconds=args.seconds,
        cell=cell, config=config, traffic=traffic, test=test,
        t_start=test.get("t_start", T_START),
        compiles=harness.CompileCounter(),
        trace=harness.TraceWindow(bool(args.trace), args.workload))
    res = harness.plugin("runners", traffic["runner"]).run(ctx)
    correct = harness.print_checks(res["checks"])

    ctx.facts, ctx.window_s = res["facts"], res["window_s"]
    ctx.setup_s, ctx.memory_peak_bytes = res["setup_s"], res["memory_peak_bytes"]
    ctx.trace_window_s = None
    device = {"platform": info["platform"], "kind": info["kind"],
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": bool(correct), "attempted": res["attempted"],
           "failed": res["failed"], "metrics": {}, "device": device}
    ctx.peaks = harness.peaks_for(info["kind"]) if info["platform"] == "tpu" \
        else test.get("peaks", {})
    e2e, per_layer = cell_metrics(bench, cell["name"])
    if args.trace:
        from benchmark import xplane
        path = ctx.trace.file()
        if path is None:
            raise SystemExit("benchmark: the profiler wrote no trace")
        tr = xplane.load(path)
        ctx.trace_window_s = ctx.trace.t1 - ctx.trace.t0
        ctx.trace = tr
        device["busy_s"] = xplane.busy_seconds(tr)
        device["window_s"] = ctx.trace_window_s
        out["breakdown"] = {"device_ops": xplane.top_device_ops(tr),
                            "idle_gaps": xplane.idle_gaps(tr, PHASES)}
        out["programs"] = xplane.top_programs(tr, 5)   # beside the contract's keys
        group, entries = "layer_metrics", per_layer
    else:
        ctx.trace = None
        group, entries = "end_to_end", e2e
    for m in entries:
        value = harness.plugin(group, m["name"]).read(ctx)
        if value is not None:
            out["metrics"][m["name"]] = {"value": float(value),
                                         "unit": m["unit"]}
    # what ``correct`` was decided from, last in the line: each number
    # compared beside its limit
    out["checks"] = {name: {"value": value, "limit": limit}
                     for name, value, limit in res["checks"]}
    return out, ctx


def main(argv=None, test: dict | None = None) -> int:
    from benchmark import harness
    out, _ = execute(argv, test)
    harness.emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())

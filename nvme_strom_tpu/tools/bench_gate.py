"""bench-gate — per-metric regression gate over the bench trajectory.

Compares a fresh ``bench.py`` JSON against the latest recorded
``BENCH_*.json`` datapoint with per-metric tolerances, so a PR that
quietly costs 30% of stream bandwidth (or blows the observability
overhead bound) fails CI instead of landing:

    python bench.py > /tmp/new.json
    bench-gate /tmp/new.json                  # vs newest BENCH_*.json
    bench-gate /tmp/new.json --baseline BENCH_x.json --json

Baselines may be RAW bench.py output or a wrapper
(``{"tail": "...last line is the JSON..."}``).  No baseline ships with
the repository.  Platforms must match (``tpu`` vs ``cpu``): numbers from
a JAX_PLATFORMS=cpu run are not comparable to the chip's and the gate
refuses to pretend otherwise — a mismatch is reported and exits 0
unless ``--strict``.

Tolerances are deliberately wide (dev boxes are noisy VMs; the gate
exists to catch step-function regressions, not 3% drift).  A metric
missing from either side is reported and skipped — scenario knobs
(``STROM_BENCH_*=0``) must not fail the gate.

Exit codes: 0 pass / 1 regression / 2 usage or unreadable input.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import List, Optional, Tuple

#: (dotted metric path, mode, tolerance)
#:   higher      regress when new < base * (1 - tol)
#:   lower       regress when new > base * (1 + tol)
#:   lower_abs   regress when new > base + tol  (absolute points —
#:               overhead percentages, where a ratio of a near-zero
#:               baseline is meaningless)
GATES: Tuple[Tuple[str, str, float], ...] = (
    ("value", "higher", 0.35),
    ("verify_overhead_pct", "lower_abs", 15.0),
    ("submit_syscalls_per_gib", "lower", 0.50),
    ("mixed.multi_ring.decode_p99_ms", "lower", 0.60),
    ("hostcache.repeat_read_speedup", "higher", 0.50),
    ("kvserve.on.ttft_avg_ms", "lower", 0.60),
    ("overlap.overlapped_gib_s", "higher", 0.35),
    # the observability bound (docs/OBSERVABILITY.md): the always-on
    # layers must stay cheap — measured, gated, never asserted
    ("observability.flight_overhead_pct", "lower_abs", 3.0),
    ("observability.traced_overhead_pct", "lower_abs", 3.0),
    ("observability.attrib_overhead_pct", "lower_abs", 3.0),
    # elastic cold-start (docs/RESILIENCE.md): serve-while-restoring
    # must keep its boot-elasticity step function — a TTFT-from-boot
    # speedup collapsing toward 1x means the demand-fault lane started
    # paying for the warm payload again
    ("coldstart.ttft_boot_speedup", "higher", 0.50),
    ("coldstart.on.ttft_boot_s", "lower", 0.60),
    # drain & warm handoff (docs/RESILIENCE.md): a rolling replacement
    # must keep its warm-boot TTFT win, and the zero-drop invariant is
    # absolute — one dropped session is a protocol break, not noise
    ("handoff.ttft_boot_speedup", "higher", 0.50),
    ("handoff.dropped_requests", "lower_abs", 0.0),
)


def _dig(doc: dict, path: str):
    cur = doc
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur if isinstance(cur, (int, float)) \
        and not isinstance(cur, bool) else None


def load_bench_json(path: str) -> dict:
    """A bench datapoint: raw ``bench.py`` stdout JSON, or the run
    driver's wrapper whose ``tail`` text ends with that JSON line."""
    with open(path) as f:
        doc = json.load(f)
    if "metric" in doc:
        return doc
    tail = doc.get("tail")
    if isinstance(tail, str):
        for line in reversed(tail.splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    inner = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if "metric" in inner:
                    return inner
    raise ValueError(f"{path}: no bench JSON found (neither raw "
                     f"bench.py output nor a wrapper with one in tail)")


def latest_baseline(root: str) -> Optional[str]:
    """Newest ``BENCH_*.json`` (by name order — r01 < r02 < ...) that
    actually parses to a bench datapoint."""
    for path in sorted(glob.glob(os.path.join(root, "BENCH_*.json")),
                       reverse=True):
        try:
            load_bench_json(path)
            return path
        except (OSError, ValueError, json.JSONDecodeError):
            continue
    return None


def compare(base: dict, new: dict) -> Tuple[List[dict], List[dict]]:
    """(results, regressions): one result row per gate, regressions
    the failing subset."""
    results: List[dict] = []
    regressions: List[dict] = []
    for path, mode, tol in GATES:
        b, n = _dig(base, path), _dig(new, path)
        row = {"metric": path, "mode": mode, "tolerance": tol,
               "baseline": b, "new": n}
        if b is None or n is None:
            row["verdict"] = "skipped (missing)"
            results.append(row)
            continue
        if mode == "higher":
            ok = n >= b * (1.0 - tol)
        elif mode == "lower":
            ok = b <= 0 or n <= b * (1.0 + tol)
        elif mode == "lower_abs":
            ok = n <= b + tol
        else:
            raise ValueError(f"unknown gate mode {mode!r}")
        row["verdict"] = "ok" if ok else "REGRESSION"
        results.append(row)
        if not ok:
            regressions.append(row)
    return results, regressions


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench-gate",
        description="per-metric regression gate: fresh bench.py JSON "
                    "vs the latest BENCH_*.json datapoint")
    ap.add_argument("new", help="fresh bench.py JSON output")
    ap.add_argument("--baseline", default=None,
                    help="baseline datapoint (default: newest "
                         "BENCH_*.json next to bench.py)")
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))),
        help="directory holding BENCH_*.json (default: repo root)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable result document")
    ap.add_argument("--strict", action="store_true",
                    help="platform mismatch fails instead of skipping")
    args = ap.parse_args(argv)

    try:
        new = load_bench_json(args.new)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"bench-gate: cannot read {args.new}: {e}",
              file=sys.stderr)
        return 2
    bpath = args.baseline or latest_baseline(args.root)
    if bpath is None:
        print("bench-gate: no BENCH_*.json baseline found — record one "
              "(python bench.py > BENCH_rNN.json) to arm the gate",
              file=sys.stderr)
        return 2
    try:
        base = load_bench_json(bpath)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"bench-gate: cannot read baseline {bpath}: {e}",
              file=sys.stderr)
        return 2

    bplat = base.get("platform", "unknown")
    nplat = new.get("platform", "unknown")
    if bplat != nplat:
        msg = (f"bench-gate: platform mismatch (baseline={bplat}, "
               f"new={nplat}) — datapoints are not comparable")
        print(msg, file=sys.stderr)
        return 1 if args.strict else 0

    results, regressions = compare(base, new)
    doc = {"baseline": bpath, "platform": nplat,
           "results": results,
           "regressions": len(regressions),
           "pass": not regressions}
    if args.as_json:
        print(json.dumps(doc, indent=2))
    else:
        print(f"bench-gate: {args.new} vs {bpath} [{nplat}]")
        for row in results:
            b, n = row["baseline"], row["new"]
            shown = (f"{b:.3f} -> {n:.3f}"
                     if b is not None and n is not None else "-")
            print(f"  {row['verdict']:<20} {row['metric']:<42} {shown}")
        print(f"bench-gate: {'PASS' if doc['pass'] else 'FAIL'} "
              f"({len(regressions)} regression(s))")
    return 0 if doc["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())

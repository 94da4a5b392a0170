"""The chat cell's rate sweep, once, on the chip: the same jittered grid at
each of a few rates in one process; for each the queue in the middle and at
the end of the window, TTFT and token gap.  The knee is the highest rate at
which the queue is no longer at the end than in the middle; the cell's rate
is 0.8 of it, rounded down to 0.05, written into ``traffic/chat.json``.

    python3 benchmark/tools/sweep_chat.py 0.3 0.4 ... [--seconds 40]"""

from __future__ import annotations

import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    from benchmark import run
    args = sys.argv[1:]
    seconds = 40.0
    if "--seconds" in args:
        i = args.index("--seconds")
        seconds = float(args[i + 1])
        del args[i:i + 2]
    rows = []
    for k, rate in enumerate(float(a) for a in args):
        out, ctx = run.execute(
            ["--workload", "m7b.chat", "--seed", str(7000 + k),
             "--seconds", str(seconds), "--trace", "0"],
            test={"traffic": {"rate": rate}, "skip_reference": True})
        q = ctx.facts["queue_len"]

        def mean_q(lo, hi):
            v = [n for t, n in q if lo <= t < hi]
            return statistics.mean(v) if v else float("nan")

        reqs = ctx.facts["requests"]
        row = {"rate": rate, "sampled": len(reqs), "failed": out["failed"],
               "queue_mid": mean_q(seconds / 2 - 5, seconds / 2 + 5),
               "queue_end": mean_q(seconds - 10, seconds),
               "queue_max": max((n for _, n in q), default=0),
               "admit_share": 100 * ctx.facts["timings"]["admit_s"]
               / ctx.window_s,
               **{m: v["value"] for m, v in out["metrics"].items()}}
        rows.append(row)
        print("SWEEP " + json.dumps(row), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "sweep_chat.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

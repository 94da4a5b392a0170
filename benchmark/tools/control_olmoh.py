"""The controls of the Olmo-Hybrid cell's comparison, on the chip at the
cell's own size (run by hand; PERF.md section 2 holds the readings).  One PATH
per process (a broken path is traced into the compiled programs, and a
process keeps its traces), any number of seeds:

    python3 benchmark/tools/control_olmoh.py olmoh.flood-cot 20 sound 101 102

* ``sound``    the program as it is: its own numbers (mean and widest gap of
               a served token below the reference's best) and, on the very
               sample the run compares, five controls computed in the
               program's place by the reference in another arithmetic — the
               gaps of the token each puts first:
               ``int8`` (W8A8), ``beta1`` (β = sigmoid(b), without the 2),
               ``pre_norm`` (both norms of a block before their sub-layers),
               ``head_norm`` (q and k normalised a head at a time),
               ``rotary`` (q and k turned at theta 500,000);
* ``nostate``  the state a prefill wrote into the admitted slot's rows of
               the ``"s"`` pools is zeroed before its first decode step: the
               delta-rule layers carry no state from prefill into decode
               (conv tails and K/V pages stay);
* ``notail``   likewise the slot's rows of the ``"conv"`` pools: the conv's
               last three rows are not carried.

Each control must fail at least one of the limits in the configuration's
file, or be named in it as one the comparison cannot see; the limits go
above ``sound`` and below the others.  ``CONTROLS`` may be cut by the
environment (``CONTROL_OLMOH=int8,beta1``) where a run's time is short: each
is one more replay of the sample."""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

PATHS = ("sound", "nostate", "notail")
CONTROLS = ("int8", "beta1", "pre_norm", "head_norm", "rotary")


from benchmark.tools.control_gdn import drop_rows  # noqa: E402


def main() -> int:
    from benchmark import harness, run
    from benchmark.runners import serve
    workload, seconds, path = sys.argv[1], sys.argv[2], sys.argv[3]
    if path not in PATHS:
        raise SystemExit(f"control_olmoh: path {path!r} is not one of {PATHS}")
    controls = tuple(c for c in os.environ.get(
        "CONTROL_OLMOH", ",".join(CONTROLS)).split(",") if c)
    rows, read = [], {}
    print_checks = harness.print_checks

    def keep_checks(checks):        # the program's own numbers, as compared
        read.update({name: value for name, value, _ in checks})
        return print_checks(checks)
    harness.print_checks = keep_checks
    for seed in sys.argv[4:]:
        got = {}

        def after(ctx, sample, got=got):
            hf, ref = ctx.config, ctx.config["reference"]
            got["sample_lengths"] = [len(r["prompt"]) + len(r["tokens"])
                                     for r in sample]
            if path != "sound":
                return
            for low in controls:
                t0 = time.monotonic()
                got["control_" + low] = serve.control_gaps(
                    hf, ctx.seed, sample, ref, low=low)
                got["control_" + low]["replay_s"] = time.monotonic() - t0

        def built(srv):
            if path != "sound":
                drop_rows(srv, "s" if path == "nostate" else "conv")

        t0 = time.monotonic()
        out, ctx = run.execute(
            ["--workload", workload, "--seed", seed, "--seconds", seconds,
             "--trace", "0"], test={"after_window": after,
                                    "server_built": built})
        row = {"workload": workload, "seed": int(seed), "path": path,
               "correct": out["correct"], "failed": out["failed"],
               "tok_s": out["metrics"].get("tok_s", {}).get("value"),
               "memory_peak_bytes": out["device"]["memory_peak_bytes"],
               "run_s": time.monotonic() - t0,
               "program": dict(read), **got}
        rows.append(row)
        print("CONTROL " + json.dumps(row), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"control_{workload}_{path}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

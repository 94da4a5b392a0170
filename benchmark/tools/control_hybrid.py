"""The controls of a hybrid cell's comparison, on the chip at the cell's own
size (``tools/control.py`` knows the dense layout only).  For each seed, one
short window of the cell at its own load, then on the very sample the run
compares

* the program's numbers (mean and widest gap of a served token below the
  reference's best),
* the int8 control's: the reference computed in W8A8 in the program's
  place, the gaps of the token it puts first;

and a second window with the timed path BROKEN where the new mechanism
lives — what the prefill left in the admitted slot's recurrent state and
conv tail is zeroed before the first decode step — whose gaps must fail the
limits too.  The limits in the configuration's file go above the first and
below the other two (PERF.md §2 holds the readings).

    python3 benchmark/tools/control_hybrid.py g4hm.flood 20 101 102 103"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def drop_state(srv) -> None:
    """``server_built`` hook: admission forgets the state it computed."""
    import jax

    zero_row = jax.jit(
        lambda state, slot: jax.tree_util.tree_map(
            lambda a: a.at[slot].set(0), state), donate_argnums=(0,))
    inner = srv._admit_finish

    def admit(plan, restored):
        inner(plan, restored)
        srv.state = zero_row(srv.state, plan["slot"])
    srv._admit_finish = admit


def main() -> int:
    from benchmark import run
    from benchmark.runners import serve
    workload, seconds = sys.argv[1], sys.argv[2]
    rows = []
    for seed in sys.argv[3:]:
        for broken in (False, True):
            got = {}

            def hook(ctx, sample, got=got, broken=broken):
                hf, ref = ctx.config, ctx.config["reference"]
                got["program"] = serve.served_gaps(hf, ctx.seed, sample, ref)
                if not broken:
                    got["control_int8_ref"] = serve.control_gaps(
                        hf, ctx.seed, sample, ref)

            test = {"after_window": hook}
            if broken:
                test["server_built"] = drop_state
            out, _ = run.execute(["--workload", workload, "--seed", seed,
                                  "--seconds", seconds, "--trace", "0"],
                                 test=test)
            row = {"workload": workload, "seed": int(seed),
                   "path": "state dropped" if broken else "sound",
                   "correct": out["correct"], "failed": out["failed"],
                   "tok_s": out["metrics"].get("tok_s", {}).get("value"),
                   **got}
            rows.append(row)
            print("CONTROL " + json.dumps(row), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"control_{workload}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

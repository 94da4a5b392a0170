"""The controls of the SDAR cell's comparison, on the chip at the cell's own
size (run by hand; PERF.md section 2 holds the readings), any number of
seeds:

    python3 benchmark/tools/control_sdar.py sdar.flood-bd 20 101 102

One short window of the cell as it is — its own three numbers (the mean and
the widest gap of a served token below the reference's best at its position
and denoising step; the widest gap of a committed position's log-confidence
below the best position left masked) — and, on the very sample the run
compares, what a program with each FAULT would have done at the served
requests' own states, computed in the program's place by the reference
(``reference/sdar_bd.control_choice``: at every (block, step) as many
positions as were committed there, the most confident by the faulty
forward's confidences, with the faulty forward's arg-max tokens), held
against the sound reference like the program:

* ``int8``          every product W8A8 (the nearest precision below bf16);
* ``bf16``          every product's operands rounded to bfloat16 (the
                    configuration's own precision: reported, not a fault);
* ``causal_block``  a causal mask inside the block, in every forward of it;
* ``causal_prompt`` the admission's mask causal over the prompt;
* ``stale_pages``   a finished block's K/V left as its last denoising
                    forward wrote them (no clean forward);
* ``equal_rope``    the rotary positions of a block's rows all equal;
* ``lowest_first``  the lowest masked positions committed in place of the
                    most confident.

Each fault must fail at least one of the limits in the configuration's file,
or be named in PERF.md section 2 with the float32 test that holds the
mechanism instead; the limits go above the program's band and below the
faults'.  ``CONTROL_SDAR=int8,stale_pages`` picks some: each is a replay or
two more of the sample."""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CONTROLS = ("int8", "bf16", "causal_block", "causal_prompt", "stale_pages",
            "equal_rope", "lowest_first")


def control_gaps(hf: dict, seed: int, sample: list, ref_name: str,
                 mask_id: int, control: str) -> dict:
    """The three numbers of a program with the fault ``control``."""
    from benchmark import harness
    ref = harness.plugin("reference", ref_name)
    bl = hf["serving"]["diffusion"]["block_length"]
    served = ref.served_choice(sample, bl)
    low = control if control in ("int8", "bf16") else None
    variant = control if control in ref.VARIANTS[:-1] else None
    faulty = ref.replay(hf, seed, sample, bl, mask_id, low=low,
                        variant=variant)
    choice = ref.control_choice(served, faulty,
                                lowest=control == "lowest_first")
    sound = ref.replay(hf, seed, sample, bl, mask_id,
                       tokens=choice["tokens"])
    return ref.gaps(sound, choice)


def main() -> int:
    from benchmark import harness, run
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    workload, seconds = sys.argv[1], sys.argv[2]
    controls = tuple(c for c in os.environ.get(
        "CONTROL_SDAR", ",".join(CONTROLS)).split(",") if c)
    rows, read = [], {}
    print_checks = harness.print_checks

    def keep_checks(checks):        # the program's own numbers, as compared
        read.update({name: value for name, value, _ in checks})
        return print_checks(checks)
    harness.print_checks = keep_checks
    for seed in sys.argv[3:]:
        got = {}

        def after(ctx, sample, got=got):
            hf = ctx.config
            mask_id = config_from_hf(hf).mask_token_id
            got["sample_lengths"] = [len(r["prompt"]) + len(r["tokens"])
                                     for r in sample]
            for control in controls:
                t0 = time.monotonic()
                got["control_" + control] = dict(
                    control_gaps(hf, ctx.seed, sample, hf["reference"],
                                 mask_id, control),
                    replay_s=time.monotonic() - t0)
                print("CONTROL_ROW " + json.dumps(
                    {control: got["control_" + control]}), flush=True)

        t0 = time.monotonic()
        out, ctx = run.execute(
            ["--workload", workload, "--seed", seed, "--seconds", seconds,
             "--trace", "0"], test={"after_window": after})
        row = {"workload": workload, "seed": int(seed),
               "correct": out["correct"], "failed": out["failed"],
               "tok_s": out["metrics"].get("tok_s", {}).get("value"),
               "memory_peak_bytes": out["device"]["memory_peak_bytes"],
               "run_s": time.monotonic() - t0,
               "program": dict(read), **got}
        rows.append(row)
        print("CONTROL " + json.dumps(row), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"control_{workload}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

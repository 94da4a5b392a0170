"""The controls of a window-attention cell's comparison, on the chip at the
cell's own size (run by hand; PERF.md section 2 holds the readings).  One
PATH per process (a broken path is traced into the compiled programs, and a
process keeps its traces), any number of seeds:

    python3 benchmark/tools/control_swa.py mimo.flood16k 20 sound 101 102

* ``sound``    the program as it is: its own numbers (mean and widest gap of
               a served token below the reference's best) and, on the very
               sample the run compares, six controls computed in the
               program's place by the reference in another arithmetic — the
               gaps of the token each puts first:
               ``int8`` (W8A8), ``no_window`` (a window layer attends every
               key behind its row), ``no_sink`` (the sink column dropped),
               ``no_value_scale`` (v unscaled), ``one_theta`` (both kinds of
               layer rotate at ``rope_theta``), ``norm_held`` (the routing
               weights normalised over the held experts only);
* ``nocarry``  the rows a prefill wrote into the admitted slot's rings are
               zeroed before its first decode step: the window layers carry
               nothing from prefill into decode (the full layers' pages
               stay).

Each control must fail at least one of the limits in the configuration's
file; the limits go above ``sound`` and below the others.  ``CONTROLS`` may
be cut by the environment (``CONTROL_SWA=int8,no_sink``) where a run's time
is short: each is one more replay of the sample."""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

PATHS = ("sound", "nocarry")
CONTROLS = ("int8", "no_window", "no_sink", "no_value_scale", "one_theta",
            "norm_held")


def drop_rings(srv) -> None:
    """``server_built`` hook: admission forgets what it wrote into the
    admitted slot's rings."""
    import jax
    import jax.numpy as jnp

    from nvme_strom_tpu.models.serving import ring_blocks

    ring = ring_blocks(srv.cfg, srv.block_len)
    zero = jax.jit(lambda pool, blks: pool.at[:, blks].set(0),
                   donate_argnums=(0,))
    inner = srv._admit_finish

    def admit(plan, restored):
        inner(plan, restored)
        blks = jnp.asarray(plan["slot"] * ring + jnp.arange(ring), jnp.int32)
        srv.state = dict(srv.state, wk=zero(srv.state["wk"], blks),
                         wv=zero(srv.state["wv"], blks))
    srv._admit_finish = admit


def main() -> int:
    from benchmark import harness, run
    from benchmark.runners import serve
    workload, seconds, path = sys.argv[1], sys.argv[2], sys.argv[3]
    if path not in PATHS:
        raise SystemExit(f"control_swa: path {path!r} is not one of {PATHS}")
    controls = tuple(c for c in os.environ.get(
        "CONTROL_SWA", ",".join(CONTROLS)).split(",") if c)
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
            if path == "nocarry":
                drop_rings(srv)

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

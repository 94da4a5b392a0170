"""The controls of an LFM2-MoE cell's comparison, on the chip at the cell's
own size (run by hand; PERF.md section 2 holds the readings).  One PATH per
process (a broken path is traced into the compiled programs, and a process
keeps its traces), any number of seeds:

    python3 benchmark/tools/control_moe.py lfm2.flood 20 sound 101 102 103

* ``sound``     the program as it is: its own numbers (mean and widest gap of
                a served token below the reference's best) and, on the very
                sample the run compares, the int8 control's — the reference
                computed in W8A8 in the program's place, the gaps of the token
                it puts first;
* ``capacity``  the capacity-dropping rule of ``models/moe.moe_mlp`` in the
                exact layer's place: pairs past ceil(1.25 x rows x k /
                experts) of their expert, first choices before second,
                contribute nothing (pad rows of a prefill contest the slots,
                as they did there);
* ``nobias``    the selection bias left out of the top-k;
* ``notail``    what the prefill left in the admitted slot's conv tails is
                zeroed before the first decode step.

Each broken path must fail at least one of the limits in the configuration's
file; the limits go above ``sound`` and below the other four."""

from __future__ import annotations

import dataclasses
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

PATHS = ("sound", "capacity", "nobias", "notail")


def drop_over_capacity(factor: float = 1.25) -> None:
    """``moe.route`` with GShard's capacity: a pair whose place in its
    expert's queue (first choices of every row, then second choices, ...) is
    past the capacity keeps its expert and loses its weight."""
    import jax.numpy as jnp

    from nvme_strom_tpu.models import moe
    inner = moe.route

    def route(x, p, prefix, cfg):
        sel, w = inner(x, p, prefix, cfg)
        T, k = sel.shape
        cap = max(1, math.ceil(T * k / cfg.n_experts * factor))
        hot = (sel.T.reshape(T * k, 1)
               == jnp.arange(cfg.n_experts)).astype(jnp.int32)   # k-major
        place = jnp.sum((jnp.cumsum(hot, axis=0) - hot) * hot, axis=1)
        keep = (place < cap).reshape(k, T).T
        return sel, jnp.where(keep, w, 0.0)
    moe.route = route


def leave_bias_out() -> None:
    from nvme_strom_tpu.models import moe
    inner = moe.route
    moe.route = lambda x, p, prefix, cfg: inner(
        x, p, prefix, dataclasses.replace(cfg, router_bias=False))


def drop_tail(srv) -> None:
    """``server_built`` hook: admission forgets the conv tails it computed."""
    import jax

    zero_row = jax.jit(
        lambda tails, slot: tuple(a.at[slot].set(0) for a in tails),
        donate_argnums=(0,))
    inner = srv._admit_finish

    def admit(plan, restored):
        inner(plan, restored)
        srv.state = dict(srv.state, conv=zero_row(srv.state["conv"],
                                                  plan["slot"]))
    srv._admit_finish = admit


def load_summary(srv_box: list) -> dict:
    """max / mean / min load of an expert, per expert layer, over the run's
    decode steps (``DecodeServer.moe_load``)."""
    load = srv_box[0].moe_load
    if load is None:
        return {}
    mean = load.mean(axis=1)
    return {"load_max_over_mean": (load.max(axis=1) / mean).round(2).tolist(),
            "load_min_over_mean": (load.min(axis=1) / mean).round(2).tolist(),
            "experts_never_touched": (load == 0).sum(axis=1).tolist()}


def main() -> int:
    from benchmark import run
    from benchmark.runners import serve
    workload, seconds, path = sys.argv[1], sys.argv[2], sys.argv[3]
    if path not in PATHS:
        raise SystemExit(f"control_moe: path {path!r} is not one of {PATHS}")
    if path == "capacity":
        drop_over_capacity()
    elif path == "nobias":
        leave_bias_out()
    rows = []
    for seed in sys.argv[4:]:
        got, box = {}, []

        def after(ctx, sample, got=got, box=box):
            got.update(load_summary(box))
            box.clear()             # the server goes before the reference
            hf, ref = ctx.config, ctx.config["reference"]
            got["program"] = serve.served_gaps(hf, ctx.seed, sample, ref)
            if path == "sound":
                got["control_int8_ref"] = serve.control_gaps(
                    hf, ctx.seed, sample, ref)

        def built(srv, box=box):
            box.append(srv)
            if path == "notail":
                drop_tail(srv)

        out, ctx = run.execute(
            ["--workload", workload, "--seed", seed, "--seconds", seconds,
             "--trace", "0"], test={"after_window": after,
                                    "server_built": built})
        t = ctx.facts["timings"]
        row = {"workload": workload, "seed": int(seed), "path": path,
               "correct": out["correct"], "failed": out["failed"],
               "tok_s": out["metrics"].get("tok_s", {}).get("value"),
               "moe": {k: v for k, v in t.items() if k.startswith("moe_")},
               **got}
        rows.append(row)
        print("CONTROL " + json.dumps(row), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"control_{workload}_{path}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

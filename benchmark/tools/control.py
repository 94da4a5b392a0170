"""The control of the serving cells' comparison, on the chip at the cell's own
size: for a few seeds, one short window of the cell at its own load, then on
the very sample the run compares

* the program's number (widest gap of a served token below the reference's
  best),
* the control's: the reference computed in int8 (W8A8) in the program's
  place, the gap of the token it puts first, and
* the program's own lower-precision path (``models/quant`` int8 weights
  through ``models/transformer.forward``), the same reading.

The limit in the configuration's file goes above the first and below the
other two (PERF.md §2 holds the readings).

    python3 benchmark/tools/control.py m7b.chat 25 101 102 103"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def program_int8_tokens(hf: dict, seed: int, sample: list):
    """argmax tokens of the program's dense forward with its int8 weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import weights as W
    from nvme_strom_tpu.models.quant import quantize_weights_int8
    from nvme_strom_tpu.models.transformer import forward
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    cfg = config_from_hf(hf)
    params = W.make_params(hf, seed)
    q = {}
    for name in list(params):            # leaf by leaf: frees the bf16 copy
        q.update(quantize_weights_int8({name: params.pop(name)}))
    fwd = jax.jit(forward, static_argnums=(2,))
    rows = []
    for r in sample:
        seq = list(r["prompt"]) + list(r["tokens"])
        pad = -(-len(seq) // 128) * 128
        toks = np.zeros((1, pad), np.int32)
        toks[0, :len(seq)] = seq
        logits = fwd(q, jnp.asarray(toks), cfg)[0]
        n, p = len(r["tokens"]), len(r["prompt"])
        rows.append(np.asarray(jnp.argmax(logits[p - 1:p - 1 + n], -1)))
    del q
    return rows


def main() -> int:
    import numpy as np

    from benchmark import run
    from benchmark.runners import serve
    workload, seconds = sys.argv[1], sys.argv[2]
    rows = []
    for seed in sys.argv[3:]:
        got = {}

        def hook(ctx, sample, got=got):
            hf, ref = ctx.config, ctx.config["reference"]
            got["control_int8_ref"] = serve.control_gaps(hf, ctx.seed,
                                                         sample, ref)
            toks = program_int8_tokens(hf, ctx.seed, sample)
            logits, served, valid = serve.reference_logits(hf, ctx.seed,
                                                           sample, ref)
            alt = np.zeros_like(served)
            for i, t in enumerate(toks):
                alt[i, :len(t)] = t
            got["control_program_int8"] = serve.gaps_of(logits, alt, valid)
            got["program"] = serve.gaps_of(logits, served, valid)

        out, _ = run.execute(["--workload", workload, "--seed", seed,
                              "--seconds", seconds, "--trace", "0"],
                             test={"after_window": hook})
        row = {"workload": workload, "seed": int(seed),
               "correct": out["correct"], **got}
        rows.append(row)
        print("CONTROL " + json.dumps(row), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out",
                        f"control_{workload}.json")
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

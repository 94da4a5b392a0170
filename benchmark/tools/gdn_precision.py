"""Where a delta-rule cell's served tokens part from the reference's: the
program's full forward pass (``decode.block_step``, the chunked scan among
it) over ONE prompt at the cell's widths, against the plain reference, in
three arithmetics (run by hand on the chip; PERF.md section 2 holds what it
read):

* ``served``    the serving types: bfloat16 weights and activations, the
                scan's products on bfloat16 operands;
* ``scan_f32``  the same, but the scan kernel's q, k and v handed over in
                float32 (its products then take float32 operands): what the
                scan's operand type costs;
* ``float32``   weights and activations float32 throughout, under the
                highest matmul precision: the program's mathematics at the
                published widths, with no rounding to hide behind.

For each, at every 8th position: the mean and widest gap of the token it
puts first under the reference's logits (the quantity ``correct`` limits),
how many tokens are off the reference's best, and the root mean square of
the logits' difference.

Off the chip (``JAX_PLATFORMS=cpu``) only ``float32`` runs — a comparison of
results, no measurement —, and ``layers`` cuts the depth so that it ends:
16 layers x 512 rows take two minutes there (PR 43: rms 1.0e-5, every argmax
equal).

    python3 benchmark/tools/gdn_precision.py [rows] [seed] [layers]"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import harness
    from benchmark import weights_gdn as WG
    from benchmark.reference import qwen3_next as ref
    from benchmark.runners.serve import gaps_of
    from benchmark.traffic import prompt_tokens
    from nvme_strom_tpu.models import decode, ssm
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 2048
    seed = int(sys.argv[2]) if len(sys.argv) > 2 else 2147483777
    hf = harness.load_json("benchmark", "configs", "qwen3-next-80b-a3b.json")
    if len(sys.argv) > 3:
        hf = dict(hf, num_hidden_layers=int(sys.argv[3]))
    on_chip = jax.default_backend() == "tpu"
    cfg = config_from_hf(hf)
    toks = np.asarray([prompt_tokens(seed, 0, rows, hf["vocab_size"])],
                      np.int32)
    at = np.arange(7, rows, 8)[None]
    want = ref.logits_at(hf, seed, toks, at)[0]
    valid = np.ones(want.shape[:1], bool)

    def forward(params, cfg):
        cache = decode.init_cache(cfg, 1, rows)
        logits, _ = jax.jit(decode.block_step, static_argnums=(2,))(
            params, jnp.asarray(toks), cfg, cache)
        return logits[0, at[0]]

    def read(name, logits):
        tokens = np.asarray(jnp.argmax(logits, axis=-1))
        row = gaps_of(want, tokens, valid)
        row["rms_diff"] = float(jnp.sqrt(jnp.mean((logits - want) ** 2)))
        print("PRECISION " + json.dumps({"arithmetic": name, **row}),
              flush=True)
        return row

    out = {"rows": rows, "seed": seed, "positions": int(valid.sum()),
           "layers": hf["num_hidden_layers"],
           "platform": jax.default_backend()}
    params = WG.make_params(hf, seed)
    if on_chip:
        out["served"] = read("served", forward(params, cfg))
        scan = ssm.gdn_scan
        ssm.gdn_scan = lambda q, k, v, *rest, **kw: scan(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), *rest, **kw)
        try:
            # (another config object: the jitted program is traced anew)
            out["scan_f32"] = read("scan_f32", forward(
                params, dataclasses.replace(cfg, max_seq=cfg.max_seq + 1)))
        finally:
            ssm.gdn_scan = scan
    params = {k: v.astype(jnp.float32) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        out["float32"] = read("float32", forward(
            params, dataclasses.replace(cfg, dtype=jnp.float32)))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "gdn_precision.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

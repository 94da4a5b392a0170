"""The two gated-delta-rule kernels alone, on the chip at the cell's own
shapes (run by hand; PERF.md section 6 holds what it read): 128 slots of 32
value heads against a pool of 129 rows of (32, 128, 128) float32, and
prompts of 512 rows by four and of 4,096 rows alone.

* ``strom_gdn_update`` (``ops/gdn.py``), checked against the token-by-token
  recurrence in float32 before it is timed; its bytes are the pool's rows in
  and out and the operands' (``costs_gdn.update_cost``);
* ``strom_gdn_scan`` over both prompt shapes in bfloat16, its first 256 rows
  checked against the same recurrence; its least time is the larger of its
  bytes' and of the recurrence's own operations' (``costs_gdn.scan_cost``).

The time is the host's clock around ``calls`` calls ending in
``block_until_ready``.

    python3 benchmark/tools/gdn_probe.py [calls]"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def recurrence(q, k, v, alpha, beta, s0):
    """The rule a token at a time, float32: q, k (b, m, H, dk), v (b, m, H,
    dv), log alpha and beta (b, m, H), s0 (b, H, dk, dv) -> (o (b, m, H,
    dv), S)."""
    import jax
    import jax.numpy as jnp

    def step(s, x):
        q, k, v, a, b = x
        s = jnp.exp(a)[..., None, None] * s
        u = b[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", s, k))
        s = s + k[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q)

    xs = tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0)
               for t in (q, k, v, alpha, beta))
    s, o = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(o, 0, 1), s


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import costs_gdn, harness
    from nvme_strom_tpu.ops.gdn import gdn_scan, gdn_update
    calls = int(sys.argv[1]) if len(sys.argv) > 1 else 20
    hf = harness.load_json("benchmark", "configs", "qwen3-next-80b-a3b.json")
    info = harness.require_chips(1)
    peaks = harness.peaks_for(info["kind"])
    B = hf["serving"]["slots"]
    H, dk, dv = (hf["linear_num_value_heads"], hf["linear_key_head_dim"],
                 hf["linear_value_head_dim"])
    f32, bf = jnp.float32, jnp.bfloat16

    def draw(key, bsz, m, dtype):
        ks = jax.random.split(jax.random.key(key), 6)
        q = jax.random.normal(ks[0], (bsz, m, H, dk), f32)
        k = jax.random.normal(ks[1], (bsz, m, H, dk), f32)
        q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / dk ** 0.5
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        v = jax.random.normal(ks[2], (bsz, m, H, dv), f32)
        # decays from a few tokens to thousands, as the cell's weights give
        alpha = -jnp.exp(
            2.0 * jax.random.normal(ks[3], (bsz, m, H), f32) - 4.0)
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (bsz, m, H), f32))
        return (q.astype(dtype), k.astype(dtype), v.astype(dtype), alpha,
                beta)

    out = {"slots": B}
    # ---- the update: one token of every slot
    q, k, v, alpha, beta = (t[:, 0] for t in draw(1, B, 1, f32))
    pool = jax.random.normal(jax.random.key(2), (B + 1, H, dk, dv), f32)
    sidx = jnp.asarray(np.random.default_rng(3).permutation(B), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = jax.jit(recurrence)(
            q[:, None], k[:, None], v[:, None], alpha[:, None],
            beta[:, None], pool[sidx])
    want_o, want_s = np.asarray(want_o[:, 0]), np.asarray(want_s)
    step = jax.jit(gdn_update, donate_argnums=(0,))
    o, pool = step(pool, sidx, q, k, v, alpha, beta)
    err_o = float(np.abs(np.asarray(o) - want_o).max())
    err_s = float(np.abs(np.asarray(pool[sidx]) - want_s).max())
    t0 = time.monotonic()
    for _ in range(calls):
        o, pool = step(pool, sidx, q, k, v, alpha, beta)
    o.block_until_ready()
    ms = 1e3 * (time.monotonic() - t0) / calls
    nbytes, _ = costs_gdn.update_cost(hf, B)
    out["update"] = {"ms": ms, "max_err_o": err_o, "max_err_s": err_s,
                     "gb_per_s": nbytes / ms / 1e6, "roofline_pct":
                     100 * 1e3 * nbytes / peaks["hbm_bytes_per_s"] / ms}
    del pool
    # ---- the scan: the prompt shapes of the cell's admissions
    out["scan"] = []
    for bsz, m in ((4, 512), (2, 2048), (1, 4096)):
        q, k, v, alpha, beta = draw(10 + bsz, bsz, m, bf)
        s0 = jnp.zeros((bsz, H, dk, dv), f32)
        fn = jax.jit(gdn_scan)
        o, s = fn(q, k, v, alpha, beta, s0)
        with jax.default_matmul_precision("highest"):
            want, _ = jax.jit(recurrence)(
                q[:, :256], k[:, :256], v[:, :256], alpha[:, :256],
                beta[:, :256], s0)
        err = float(jnp.abs(o[:, :256].astype(f32) - want).max()
                    / jnp.abs(want).max())
        t0 = time.monotonic()
        for _ in range(calls):
            o, s = fn(q, k, v, alpha, beta, s0)
        o.block_until_ready()
        ms = 1e3 * (time.monotonic() - t0) / calls
        nbytes, flops = costs_gdn.scan_cost(hf, bsz, bsz * m)
        least = max(nbytes / peaks["hbm_bytes_per_s"],
                    flops / peaks["bf16_flops_per_s"])
        out["scan"].append({"prompts": bsz, "rows": m, "ms": ms,
                            "us_per_row": 1e3 * ms / (bsz * m),
                            "rel_err_256": err,
                            "roofline_pct": 100 * 1e3 * least / ms})
    print("PROBE " + json.dumps(out), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "gdn_probe.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

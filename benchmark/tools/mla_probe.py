"""The latent-attention decode kernel alone, on the chip at the cell's own
shapes (run by hand; PERF.md section 6 holds what it read): 64 slots of 64
heads against a pool of 4,225 blocks of 128 latent rows 576 wide, the slots'
lengths spread over 1,024 .. 8,448 as the cell's are.

* ``strom_mla_attn`` (``ops/mla_attention.py``) at 1, 2, 4 and 8 table
  entries a grid step, checked against a dense float32 computation over the
  gathered rows before it is timed;
* ``strom_latent_write`` for the 64 slots' new rows, checked element for
  element;
* ``strom_mla_prefill`` over one prompt of 1,024 and of 8,192 rows at 64
  heads (its causal half's operations over the time and the peak).

The time is the host's clock around ``calls`` calls ending in
``block_until_ready``; the bytes are the live rows' (``costs_mla``).

    python3 benchmark/tools/mla_probe.py [calls]"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import costs_mla, harness
    from nvme_strom_tpu.ops.mla_attention import latent_write, mla_attention
    calls = int(sys.argv[1]) if len(sys.argv) > 1 else 20
    hf = harness.load_json("benchmark", "configs", "kimi-k2.7-code.json")
    info = harness.require_chips(1)
    peaks = harness.peaks_for(info["kind"])
    sv = hf["serving"]
    B, bk, blocks = sv["slots"], sv["block_len"], sv["total_blocks"]
    nh, dc = hf["num_attention_heads"], hf["kv_lora_rank"]
    width = dc + hf["qk_rope_head_dim"]
    max_blocks = -(-sv["max_len"] // bk)
    rng = np.random.default_rng(7)
    pool = jax.jit(lambda k: (jax.random.normal(
        k, (2, blocks + 1, width, bk), jnp.float32) * 0.5).astype(
            jnp.bfloat16))(jax.random.key(1))
    pos = rng.integers(1024, sv["max_len"] - 1, B).astype(np.int32)
    table = np.zeros((B, max_blocks), np.int32)
    free = rng.permutation(blocks)
    at = 0
    for b in range(B):
        n = pos[b] // bk + 1
        table[b, :n] = free[at:at + n]
        at += n
    q = (jax.random.normal(jax.random.key(2), (B, nh, width), jnp.float32)
         * 0.05).astype(jnp.bfloat16)
    table_d, pos_d = jnp.asarray(table), jnp.asarray(pos)

    @jax.jit
    def dense(q, pool, table, pos):
        def one(qb, row, p):
            rows = pool[1, row].transpose(0, 2, 1).reshape(-1, width)
            rows = rows.astype(jnp.float32)
            s = qb.astype(jnp.float32) @ rows.T
            s = jnp.where(jnp.arange(rows.shape[0])[None] <= p, s, -1e30)
            return jax.nn.softmax(s, axis=-1) @ rows[:, :dc]
        return jax.lax.map(lambda a: one(*a), (q, table, pos))

    with jax.default_matmul_precision("highest"):
        want = np.asarray(dense(q, pool, table_d, pos_d))
    live = float(pos.sum() + B)
    nbytes, flops = costs_mla.mla_attn_cost(hf, B, live)
    least = max(nbytes / peaks["hbm_bytes_per_s"],
                flops / peaks["bf16_flops_per_s"])
    out = {"slots": B, "live_rows": live, "least_ms": 1e3 * least,
           "attn": []}
    for group in (1, 2, 4, 8):
        fn = jax.jit(functools.partial(mla_attention, layer=1, dc=dc,
                                       group=group))
        got = np.asarray(fn(q, pool, table_d, pos_d).astype(jnp.float32))
        err = float(np.abs(got - want).max() / np.abs(want).max())
        t0 = time.monotonic()
        for _ in range(calls):
            r = fn(q, pool, table_d, pos_d)
        r.block_until_ready()
        ms = 1e3 * (time.monotonic() - t0) / calls
        out["attn"].append({"group": group, "ms": ms, "rel_err": err,
                            "roofline_pct": 100 * 1e3 * least / ms})
    rows = (jax.random.normal(jax.random.key(3), (B, width), jnp.float32)
            ).astype(jnp.bfloat16)
    blk = jnp.asarray(table[np.arange(B), pos // bk])
    off = jnp.asarray(pos % bk)
    write = jax.jit(functools.partial(latent_write, layer=1),
                    donate_argnums=(0,))
    before = np.array(pool[1, blk].astype(jnp.float32))
    pool = write(pool, rows, blk, off)
    after = np.asarray(pool[1, blk].astype(jnp.float32))
    before[np.arange(B), :, np.asarray(off)] = np.asarray(
        rows.astype(jnp.float32))
    out["write_exact"] = bool((before == after).all())
    t0 = time.monotonic()
    for _ in range(calls):
        pool = write(pool, rows, blk, off)
    pool.block_until_ready()
    out["write_ms"] = 1e3 * (time.monotonic() - t0) / calls
    # the prefill's kernel: one prompt of each length the cell offers
    from nvme_strom_tpu.ops.mla_attention import mla_prefill_attention
    dq = hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]
    dv = hf["v_head_dim"]
    out["prefill"] = []
    for rows_n in (1024, 8192):
        qkv = [(jax.random.normal(jax.random.key(10 + i),
                                  (1, nh, rows_n, w), jnp.float32) * 0.5
                ).astype(jnp.bfloat16) for i, w in enumerate((dq, dq, dv))]
        fn = jax.jit(functools.partial(mla_prefill_attention, scale=0.1447))
        fn(*qkv, jnp.int32(0)).block_until_ready()
        t0 = time.monotonic()
        for _ in range(calls):
            r = fn(*qkv, jnp.int32(0))
        r.block_until_ready()
        ms = 1e3 * (time.monotonic() - t0) / calls
        flops = 2.0 * nh * (dq + dv) * rows_n * (rows_n + 1) / 2
        out["prefill"].append({
            "rows": rows_n, "ms": ms, "mfu_pct":
            100 * flops / (ms / 1e3) / peaks["bf16_flops_per_s"]})
    print("PROBE " + json.dumps(out), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "mla_probe.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

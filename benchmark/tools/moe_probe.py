"""The three ways to compute one expert layer's products, on the chip at the
configuration's own widths (run by hand; PERF.md section 6 holds what it
read).  For each row count (128: a decode step of the cell; 1,024: its
longest prefill) under a router as uneven as the cell's:

* ``gmm``     the program's own layer, ``models/moe.expert_mlp`` (kernel
              ``strom_moe_gmm``): rows grouped by expert on tile boundaries,
              an expert with no rows skipped;
* ``ragged``  ``jax.lax.ragged_dot`` over the rows sorted by expert;
* ``masked``  every expert on every row, kept where a mask says so (the plain
              reference's form, in bf16).

Each is checked against the masked form in float32 before it is timed; the
time is the host's clock around ``calls`` calls ending in
``block_until_ready``.

    python3 benchmark/tools/moe_probe.py [calls]"""

from __future__ import annotations

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

    from benchmark import harness
    from benchmark import weights_moe as WM
    from nvme_strom_tpu.models import moe
    from nvme_strom_tpu.ops import moe as ops
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    calls = int(sys.argv[1]) if len(sys.argv) > 1 else 20
    hf = harness.load_json("benchmark", "configs", "lfm2-24b-a2b.json")
    harness.require_chips(1)
    cfg = config_from_hf(hf)
    z = WM.sizes(hf)
    E, k, d = z["E"], z["k"], z["d"]
    shapes = WM.layer_shapes(hf)
    leaves = ("router", "router_bias", "moe_w_gate", "moe_w_up",
              "moe_w_down")
    w = WM._draw(tuple((n, shapes[n]) for n in leaves))(
        np.arange(1, 6, dtype=np.uint32) * np.uint32(2654435761))
    rows_out = []

    def routed(x, w):
        return moe.route(x, w, "", cfg)

    def by_gmm(x, w):
        return moe.expert_mlp(x[None], w, "", cfg)[0][0].astype(jnp.float32)

    def by_ragged(x, w):
        T = x.shape[0]
        sel, wt = routed(x, w)
        flat = sel.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
        xs = x[order // k]
        g = jax.lax.ragged_dot(xs, w["moe_w_gate"], sizes)
        u = jax.lax.ragged_dot(xs, w["moe_w_up"], sizes)
        y = jax.lax.ragged_dot((jax.nn.silu(g) * u).astype(x.dtype),
                               w["moe_w_down"], sizes)
        back = jnp.zeros((T * k, d), jnp.float32).at[order].set(
            y.astype(jnp.float32))
        return jnp.sum(wt[..., None] * back.reshape(T, k, d), axis=1)

    def by_mask(x, w, dtype=jnp.bfloat16):
        sel, wt = routed(x, w)
        dense = jnp.zeros((x.shape[0], E), jnp.float32).at[
            jnp.arange(x.shape[0])[:, None], sel].set(wt)
        xd = x.astype(dtype)

        def one(acc, e):
            g = xd @ w["moe_w_gate"][e].astype(dtype)
            u = xd @ w["moe_w_up"][e].astype(dtype)
            y = (jax.nn.silu(g) * u) @ w["moe_w_down"][e].astype(dtype)
            return acc + dense[:, e, None] * y.astype(jnp.float32), None
        return jax.lax.scan(one, jnp.zeros((x.shape[0], d), jnp.float32),
                            jnp.arange(E))[0]

    def forms(T, x):
        # the weights go in as arguments: closed over, every compiled
        # program would carry its own 2.7 GB copy of them on the host
        sel, _ = jax.jit(routed)(x, w)
        counts = np.bincount(np.asarray(sel).reshape(-1), minlength=E)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(
                lambda v, ws: by_mask(v, ws, jnp.float32))(x, w))
        row = {"rows": T, "touched": int((counts > 0).sum()),
               "load_max_over_mean": float(counts.max() / counts.mean()),
               "load_min": int(counts.min())}
        for name, fn in (("gmm", by_gmm), ("masked", by_mask),
                         ("ragged", by_ragged)):
            if name == "ragged":    # what is known so far, should it die
                print("PROBE " + json.dumps(row), flush=True)
            print(f"probe: {T} rows, {name}", flush=True)
            f = jax.jit(fn)
            try:
                got = np.asarray(f(x, w))
            except Exception as e:      # a form this JAX cannot lower
                row[name] = {"error": repr(e)[:200]}
                continue
            err = float(np.abs(got - want).max() / np.abs(want).max())
            t0 = time.monotonic()
            for _ in range(calls):
                out = f(x, w)
            out.block_until_ready()
            row[name] = {"ms": 1e3 * (time.monotonic() - t0) / calls,
                         "rel_err": err}
        return row

    def products_alone(T, x, row):
        """The two grouped products alone, on rows already laid out."""
        tm = ops.tile_rows(T * k, E)
        dest, te, nt, _ = jax.jit(lambda v, ws: ops.group_rows(
            routed(v, ws)[0].reshape(-1), E, tm))(x, w)
        xs = jnp.zeros((ops.padded_rows(T * k, E, tm), d), jnp.bfloat16)
        prod = jax.jit(lambda a, b, c, ws: ops.gmm(
            ops.gmm(a, (ws["moe_w_gate"], ws["moe_w_up"]), b, c, tm=tm),
            (ws["moe_w_down"],), b, c, tm=tm))
        prod(xs, te, nt, w).block_until_ready()
        t0 = time.monotonic()
        for _ in range(calls):
            out = prod(xs, te, nt, w)
        out.block_until_ready()
        row["gmm_products_only_ms"] = 1e3 * (time.monotonic() - t0) / calls
        row["tile_rows"], row["tiles_used"] = tm, int(nt)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    for T in (128, 1024):
        x = jax.random.normal(jax.random.key(T), (T, d), jnp.bfloat16)
        row = {"rows": T}
        products_alone(T, x, row)
        print("PROBE " + json.dumps(row), flush=True)
        row.update(forms(T, x))
        rows_out.append(row)
        print("PROBE " + json.dumps(row), flush=True)
        with open(os.path.join(ROOT, "chiprun_out", "moe_probe.json"),
                  "w") as f:
            json.dump(rows_out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

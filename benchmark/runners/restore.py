"""Runner of the restore cells: whole-checkpoint ``load_sharded`` back to back
for the window; afterwards every tensor of the last restore is compared, bit
for bit, with the seeded generator's."""

from __future__ import annotations

import os
import time

from benchmark import harness
from benchmark import weights as W


def data_root() -> str:
    """Where the checkpoint lives: inside the checkout (see PERF.md §5 for
    what that directory is on the chip machine)."""
    return os.path.join(harness.BENCH_DIR, ".data")


def _shardings(hf, cfg, mesh_axes, devices):
    import jax
    if not mesh_axes:
        one = jax.sharding.SingleDeviceSharding(devices[0])
        return {name: one for name, _ in W.tensor_specs(hf)}
    from nvme_strom_tpu.parallel.mesh import make_mesh
    from nvme_strom_tpu.parallel.shardings import param_shardings
    return param_shardings(cfg, make_mesh(dict(mesh_axes), devices))


def compare_with_generator(params: dict, hf: dict, seed: int, shardings: dict,
                           n_devices: int) -> list:
    """[(name, value, limit)]: elements that differ from the generator's
    (limit 0: an exact comparison), tensors missing or of another shape or
    type, and sharded tensors not laid 1/n on each of n distinct devices."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def differ(a, b):
        return jnp.sum(lax.bitcast_convert_type(a, jnp.uint16)
                       != lax.bitcast_convert_type(b, jnp.uint16))

    specs = W.tensor_specs(hf)
    bs = W.bases(hf, seed)
    wrong_set = len(set(params) ^ {n for n, _ in specs})
    wrong_layout, counts = 0, []
    for i, (name, shape) in enumerate(specs):
        arr = params.get(name)
        if arr is None or tuple(arr.shape) != tuple(shape) \
                or arr.dtype != jnp.bfloat16:
            wrong_set += 1
            continue
        want = W.one_tensor(bs[i], name, shape, sharding=arr.sharding)
        counts.append(differ(arr, want))
        shards = arr.addressable_shards
        split = any(ax is not None for ax in
                    getattr(shardings[name], "spec", ()))
        per = arr.nbytes // n_devices if split else arr.nbytes
        if len({s.device for s in shards}) != n_devices or any(
                int(s.data.nbytes) != per for s in shards):
            wrong_layout += 1
    wrong_elems = int(sum(int(c) for c in jax.device_get(counts)))
    return [("restore.elements_differing", wrong_elems, 0),
            ("restore.tensors_missing_or_misshapen", wrong_set, 0),
            ("restore.tensors_mislaid", wrong_layout, 0)]


def run(ctx) -> dict:
    import jax

    from nvme_strom_tpu.io import StromEngine
    from nvme_strom_tpu.parallel.weights import LazyCheckpoint
    from nvme_strom_tpu.tools.convert_llama import config_from_hf

    hf = ctx.config
    plan = harness.plugin("traffic.kinds", ctx.traffic["kind"]).schedule(
        ctx.traffic, ctx.seed, ctx.seconds)
    devices = jax.devices()[:ctx.cell["chips"]]
    cfg = config_from_hf(hf)
    shardings = _shardings(hf, cfg, plan["mesh"], devices)
    engine = StromEngine()
    print(f"engine: backend={engine.backend}", flush=True)
    ck = W.ensure_checkpoint(data_root(), hf, ctx.seed, ctx.cell["config"])
    print(f"checkpoint: {ck['bytes'] / 2**30:.3f} GiB under {ck['dir']} "
          f"(written in {ck['written_s']:.1f}s; 0 = this seed was there)",
          flush=True)

    def restore():
        t0 = time.monotonic()
        params = LazyCheckpoint(ck["dir"]).load_sharded(shardings,
                                                        engine=engine)
        jax.block_until_ready(params)
        return params, t0, time.monotonic()

    for _ in range(plan["warm_restores"]):
        params, _, _ = restore()
        del params
    engine.sync_stats()
    stats0 = engine.stats.snapshot()
    ctx.compiles.mark()
    setup_s = time.monotonic() - ctx.t_start

    restores, params = [], None
    t_open = time.monotonic()
    while time.monotonic() - t_open < ctx.seconds:
        ctx.trace.tick(time.monotonic() - t_open, ctx.seconds)
        if not plan["hold_previous"]:
            params = None                   # drop before the next lands
        with ctx.trace.annotate("restore"):
            new, t0, t1 = restore()
        params = new                        # the held copy goes now
        del new
        if t1 - t_open <= ctx.seconds or not restores:
            restores.append((t0 - t_open, t1 - t_open,
                             sum(int(a.nbytes) for a in params.values())))
    t_close = time.monotonic()
    ctx.trace.stop()
    compiles = ctx.compiles.count
    engine.sync_stats()
    stats1 = engine.stats.snapshot()
    peak = harness.memory_peak_bytes(devices)

    checks = compare_with_generator(params, hf, ctx.seed, shardings,
                                    len(devices))
    if ctx.test and ctx.test.get("after_window"):
        checks = ctx.test["after_window"](ctx, params, checks) or checks
    del params
    engine.close_all()
    return {"setup_s": setup_s, "window_s": t_close - t_open,
            "attempted": len(restores), "failed": 0, "checks": checks,
            "memory_peak_bytes": peak,
            "facts": {"restores": restores,
                      "engine": {k: stats1.get(k, 0) - stats0.get(k, 0)
                                 for k in stats1
                                 if isinstance(stats1.get(k), (int, float))},
                      "compiles_in_window": compiles}}

"""Records the small xplane file the kernel readers' shared walk is tested on
(``benchmark/tests/fixture_kernels.xplane.pb``), on the chip, as
``record_scope_fixture.py`` records the scopes' file: a toy ``_paged_step``
that calls three toy Pallas kernels under the program's fixed names —
``strom_paged_attn`` and ``strom_window_attn`` (one result each, so the
operation that consumes it names the kernel among its operands) and
``strom_ssm_update`` (two results: its consumers name a
``get-tuple-element``) — between plain products, three executions; and a
toy ``_paged_prefill`` that calls ``strom_kv_prefill``, two executions.
Prints what ``test_kernel_trace.py`` then asserts."""

from __future__ import annotations

import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

D = 512
KERNELS = ("strom_paged_attn", "strom_window_attn", "strom_ssm_update",
           "strom_kv_prefill")


def build(interpret: bool = False):
    """(step, prefill): the two jitted toy programs."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def toy(name: str, results: int = 1):
        def kernel(x_ref, *out_refs):
            for o in out_refs:
                o[...] = x_ref[...] * 2
        def call(x):
            shape = jax.ShapeDtypeStruct(x.shape, x.dtype)
            return pl.pallas_call(
                kernel, name=name, interpret=interpret,
                out_shape=shape if results == 1 else (shape,) * results)(x)
        return call

    paged, window = toy("strom_paged_attn"), toy("strom_window_attn")
    update, kv_prefill = toy("strom_ssm_update", 2), toy("strom_kv_prefill")

    def _paged_step(x, w):
        h = x @ w
        h = jnp.tanh(paged(h)) @ w          # the consumer names the kernel
        h = h + window(h)
        y, state = update(h.astype(jnp.float32))
        return (y + state).astype(x.dtype) @ w

    def _paged_prefill(x, w):
        return jnp.tanh(kv_prefill(x @ w)) @ w

    return jax.jit(_paged_step), jax.jit(_paged_prefill)


def main() -> int:
    import jax.numpy as jnp

    from benchmark import harness, xplane
    from benchmark.layer_metrics import _kernel_trace as K
    harness.require_chips(1)
    step, prefill = build()
    w = jnp.full((D, D), 0.01, jnp.bfloat16)
    x = jnp.ones((256, D), jnp.bfloat16)
    step(x, w).block_until_ready()
    prefill(x, w).block_until_ready()
    out = os.path.join(ROOT, "chiprun_out", "fixture_kernels")
    shutil.rmtree(out, ignore_errors=True)
    tw = harness.TraceWindow(True, "fixture_kernels")
    tw.dir = out
    tw.start()
    for _ in range(3):
        with tw.annotate("step"):
            step(x, w).block_until_ready()
    for _ in range(2):
        with tw.annotate("admit"):
            prefill(x, w).block_until_ready()
    tw.stop()
    path = tw.file()
    shutil.copy(path, os.path.join(ROOT, "chiprun_out",
                                   "fixture_kernels.xplane.pb"))
    tr = xplane.load(path)
    print("size", os.path.getsize(path))
    for plane, ops in tr.ops.items():
        for name, s, e in sorted(ops, key=lambda o: o[1]):
            print(plane, e - s, name[:200])
        print("modules", [(n, e - s) for n, s, e in tr.modules[plane]])
    for k in KERNELS:
        anywhere = [n for ops in tr.ops.values() for n, _, _ in ops if k in n]
        print(k, "calls", len(K.events(tr, k)), "seconds",
              sum(t for _, t in K.events(tr, k)), "named anywhere in",
              len(anywhere), "events; share of the step",
              K.share(tr, K.STEP, k), "of the prefill",
              K.share(tr, K.PREFILL, k))
    shutil.rmtree(out, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

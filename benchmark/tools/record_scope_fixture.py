"""Records the small xplane file the scope readers are tested on
(``benchmark/tests/fixture_scopes.xplane.pb``), on the chip, as
``record_fixture.py`` records the other: a toy ``_paged_step`` whose
operations lie under ``strom.embed`` / ``strom.attn.proj`` / ``strom.mlp``
(a ``fori_loop`` among them: operations inside a ``while``) /
``strom.head`` and one product under no scope, and a toy ``_paged_prefill``
in two shapes, each under its bucket label, three and two executions, every
one inside a host span ``strom.serve.prefill`` that carries the label as
``program=``.  Prints what ``test_scope_metrics.py`` then asserts."""

from __future__ import annotations

import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

D = 512
SHAPES = ((2, 128), (4, 256))          # (width, suffix); cache = suffix
RUNS = {SHAPES[0]: 3, SHAPES[1]: 2}


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmark import harness
    from benchmark.tools import scope_table
    harness.require_chips(1)

    def _paged_step(x, w):
        with jax.named_scope("strom.embed"):
            h = jnp.take(x, jnp.arange(x.shape[0])[::-1], axis=0)
        with jax.named_scope("strom.attn.proj"):
            h = h @ w
        with jax.named_scope("strom.mlp"):
            h = jax.lax.fori_loop(0, 3, lambda i, a: jnp.tanh(a @ w), h)
        h = jnp.sin(h) @ w                          # under no scope
        with jax.named_scope("strom.head"):
            return jnp.argmax(h, -1)

    def _paged_prefill(x, w):
        b, m, _ = x.shape
        with jax.named_scope(f"strom.prefill.{b}x{m}x{m}"):
            with jax.named_scope("strom.prefill.gather"):
                h = jnp.concatenate([x, x], axis=1)[:, :m]
            with jax.named_scope("strom.attn.proj"):
                h = h @ w
            with jax.named_scope("strom.ssm.scan"):
                h = jnp.cumsum(h.astype(jnp.float32), axis=1).astype(h.dtype)
            with jax.named_scope("strom.mlp"):
                h = jnp.tanh(h @ w) @ w
            with jax.named_scope("strom.prefill.scatter"):
                return h.transpose(1, 0, 2) + 1

    step, prefill = jax.jit(_paged_step), jax.jit(_paged_prefill)
    w = jnp.full((D, D), 0.01, jnp.bfloat16)
    xs = {s: jnp.ones(s + (D,), jnp.bfloat16) for s in SHAPES}
    x = jnp.ones((1024, D), jnp.bfloat16)
    step(x, w).block_until_ready()
    for s in SHAPES:
        prefill(xs[s], w).block_until_ready()
    out = os.path.join(ROOT, "chiprun_out", "fixture_scopes")
    shutil.rmtree(out, ignore_errors=True)
    tw = harness.TraceWindow(True, "fixture_scopes")
    tw.dir = out
    tw.start()
    for _ in range(3):
        with tw.annotate("step"):
            step(x, w).block_until_ready()
    for s in SHAPES:
        for _ in range(RUNS[s]):
            with jax.profiler.TraceAnnotation(
                    "strom.serve.prefill", program=f"{s[0]}x{s[1]}x{s[1]}"):
                prefill(xs[s], w).block_until_ready()
    tw.stop()
    path = tw.file()
    shutil.copy(path, os.path.join(ROOT, "chiprun_out",
                                   "fixture_scopes.xplane.pb"))
    print("size", os.path.getsize(path))
    scope_table.show(scope_table.report(path, 40))
    shutil.rmtree(out, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

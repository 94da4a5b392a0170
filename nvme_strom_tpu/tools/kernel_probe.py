#!/usr/bin/env python
"""Kernel probes: a kernel of the serving cells alone on the chip, one
JSON line per measurement, each naming ``platform``, ``device_kind`` and
``device_count``.  Three modes; called with none of them it names them and
exits non-zero.

``python -m nvme_strom_tpu.tools.kernel_probe paged`` times the paged
decode kernels (``ops/paged_attention.py``) alone, at the serving
cells' own shapes and slot mixes (``PAGED_CASES``): a call's time with the
table entries it reads and the grid steps it issues beside it, so that
what a grid step costs without a block can be told from what it costs
with one.

``python -m nvme_strom_tpu.tools.kernel_probe ssm`` times the two state
updates alone, each checked against one step of its recurrence first:
``strom_ssm_update`` (``ops/ssm.py``) at ``g4hm.flood``'s shape and
``strom_gdn_update`` (``ops/gdn.py``) at ``q3n.flood4k``'s, the yardstick
the first is read against — the same walk over a float32 pool of 2 MiB
rows, with more arithmetic an element.  What it read when the Mamba-2 pool
went state-major (the old form, the new one, the yardstick): PERF.md §6,
PR 44.

``python -m nvme_strom_tpu.tools.kernel_probe gdn_scan`` times
``strom_gdn_scan`` (``ops/gdn.py``) alone at the shapes of the two cells
whose admissions run it (``GDN_SCAN_CASES``: ``q3n.flood4k``'s 32 heads of
128 x 128 over four prompts of 512 rows and one of 4,096, ``olmoh.flood-cot``'s
30 heads of 96 x 192 over one of 1,024 with β drawn in (0, 2)), the first
256 rows checked against the recurrence a token at a time in float32 before
it is timed, with the operands as served and in float32, and the chunk's
solve alone against a float64 solve (``solve_rel_err``): the CPU's
interpret mode multiplies exactly and cannot see how many passes the chip
gives a float32 product, and on the chip the whole scan's error cannot
either.  What it read when the solve went from rows to blocks of rows:
PERF.md §6, PR 48.

One process, on the chip: without a TPU the probe exits non-zero unless
the caller set ``JAX_PLATFORMS=cpu`` (mechanics only, tiny shapes; every
line then says ``"platform": "cpu"``).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _log(msg: str) -> None:
    print(f"kernel_probe: {msg}", file=sys.stderr, flush=True)


_DEVICE: dict = {}     # platform / device_kind / device_count, set by main


def _emit(obj: dict) -> None:
    print(json.dumps({**obj, **_DEVICE}), flush=True)


#: name -> (slots, query heads, KV heads, K width, V width, pool blocks,
#: table width, calls a step, window, each slot's ``pos`` cycled over the
#: slots): the shapes of the benchmark's K/V cells, block 128, bf16.  The
#: first two read the same number of blocks to within 2 % on walks of 63
#: and 133; ``-1`` is a free slot (handed ``pos`` 0 as the step hands it).
PAGED_CASES = {
    "mimo.even": (64, 64, 4, 192, 128, 8705, 136, 2, 0, (8063,)),
    "mimo.ragged": (64, 64, 4, 192, 128, 8705, 136, 2, 0,
                    (2100, 4200, 8300, 17000)),
    "mimo.window": (64, 64, 8, 192, 128, 130, 2, 5, 128,
                    (2100, 4200, 8300, 17000)),
    "m7b.flood": (16, 32, 8, 128, 128, 256, 32, 24, 0,
                  (200, 330, 460, 640)),
    "m7b.chat": (16, 32, 8, 128, 128, 256, 32, 24, 0, (520,) + (-1,) * 15),
    "g4hm.flood": (64, 32, 8, 64, 64, 640, 10, 4, 0,
                   (200, 390, 700, 1150)),
    "lfm2.flood": (128, 32, 8, 64, 64, 1280, 10, 3, 0,
                   (200, 390, 700, 1150)),
}


def probe_paged(case: str, repeats: int = 5) -> None:
    """One line for ``PAGED_CASES[case]``: microseconds a call of
    ``paged_attention`` — ``calls`` calls of one jitted program on one
    table and ``pos``, as a decode step makes them on its layers, chained
    through q and looped on the device so that no dispatch is in the time
    — with the table entries the call reads (``blocks_live``) and the grid
    steps of the (slots x longest slot) walk (``steps_rect``)."""
    import statistics

    import jax
    import jax.numpy as jnp
    import numpy as np

    from nvme_strom_tpu.ops.paged_attention import paged_attention
    (b, nh, nkv, d, dv, blocks, width, calls, window,
     cycle) = PAGED_CASES[case]
    bk, layers = 128, 2
    on_cpu = jax.default_backend() != "tpu"
    if on_cpu:                             # mechanics only
        b, nh, nkv, d, dv, bk, calls = min(b, 4), 4, 2, 16, 8, 8, 2
        window = window and 8
        width = 2 if window else 6
        blocks = b * width
        cycle = tuple(-1 if p < 0 else p % (width * bk) for p in cycle)
    pos = np.array([cycle[i % len(cycle)] for i in range(b)], np.int32)
    free = pos < 0
    pos = np.where(free, 0, pos)
    last = pos // bk
    first = np.maximum(pos - window + 1, 0) // bk if window else 0 * last
    n = np.where(free, 1, last - first + 1)
    table = np.zeros((b, width), np.int32)
    if window:              # a ring a slot, as the server lays them out
        table[:] = np.arange(b * width).reshape(b, width)
    else:
        ids, at = np.random.default_rng(0).permutation(blocks), 0
        for i in np.flatnonzero(~free):
            table[i, :n[i]] = ids[at:at + n[i]]
            at += n[i]
    draw = jax.jit(lambda k, shape: (jax.random.normal(
        k, shape, jnp.float32) * 0.5).astype(jnp.bfloat16), static_argnums=1)
    keys = jax.random.split(jax.random.key(1), 4)
    k_pool = draw(keys[0], (layers, blocks + 1, nkv, bk, d))
    v_pool = draw(keys[1], (layers, blocks + 1, nkv, bk, dv))
    q = draw(keys[2], (b, nh, 1, d))
    sink = draw(keys[3], (nh,)) if window else None
    # ~0.3 s a timed run: a call is milliseconds on a long table
    rounds = 2 if on_cpu else max(
        2, int(0.3 / (2e-3 if width > 32 else 1e-4) / calls))

    @jax.jit
    def run(q, k_pool, v_pool, table, pos):
        def step(_, carry):
            # table and pos are the step's own, as a server's are: what
            # is worked out from them is worked out every round
            q, table, pos = jax.lax.optimization_barrier(carry)
            for i in range(calls):
                out = paged_attention(q, k_pool, v_pool, table, pos,
                                      layer=i % layers, window=window,
                                      sink=sink)
                # the next call waits for this one; the values stay q's
                q = q + (jnp.max(out) * 1e-30).astype(q.dtype)
            return q, table, pos
        return jax.lax.fori_loop(0, rounds, step, (q, table, pos))[0]

    args = (q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(pos))
    out = run(*args).block_until_ready()
    ts = []
    for _ in range(repeats):
        t0 = time.monotonic()
        run(*args).block_until_ready()
        ts.append((time.monotonic() - t0) / (rounds * calls))
    live = int(n.sum())
    _emit({"probe": "paged_attention", "case": case, "window": window,
           "slots": b, "heads": [nh, nkv], "widths": [d, dv],
           "table": [b, width], "calls_a_step": calls,
           "blocks_live": live, "steps_rect": int(b * n.max()),
           "mib_live": round(live * nkv * bk * (d + dv) * 2 / 2 ** 20, 1),
           "us_a_call": round(statistics.median(ts) * 1e6, 2),
           "us_a_call_min": round(min(ts) * 1e6, 2),
           "finite": bool(jnp.isfinite(out.astype(jnp.float32)).all()),
           "timing": f"{rounds} rounds of {calls} calls on the device, "
                     f"median of {repeats}"})


#: HBM bytes/s by ``device_kind`` (``benchmark/peaks.json``'s number)
HBM_GB_S = {"TPU v5 lite": 819.0}

#: (slots, heads, head width, state width) of ``g4hm.flood``'s 36 Mamba-2
#: layers and (slots, value heads, dk, dv) of ``q3n.flood4k``'s 12
#: delta-rule layers: pools of 65 and 129 rows of 2 MiB
SSM_SHAPE, SSM_CALLS = (64, 64, 64, 128), 36
GDN_SHAPE, GDN_CALLS = (128, 32, 128, 128), 12


def _emit_update(kernel, shape, pool, nbytes, errs, update, calls, repeats):
    """Time ``update(pool) -> (out, pool)`` — ``calls`` calls a round as a
    step's layers make them, looped on the device over the donated pool so
    that no dispatch is in the time — and print the kernel's line."""
    import statistics

    import jax
    import jax.numpy as jnp

    on_cpu = jax.default_backend() != "tpu"
    rounds = 2 if on_cpu else max(2, int(0.4 / 5e-4 / calls))

    @functools.partial(jax.jit, donate_argnums=0)
    def run(pool):
        def body(_, carry):
            pool, out = carry
            for _ in range(calls):
                out, pool = update(pool)
            return pool, out
        out = jax.eval_shape(update, pool)[0]
        return jax.lax.fori_loop(
            0, rounds, body, (pool, jnp.zeros(out.shape, out.dtype)))

    pool, out = run(pool)
    out.block_until_ready()
    ts = []
    for _ in range(repeats):
        t0 = time.monotonic()
        pool, out = run(pool)
        out.block_until_ready()
        ts.append((time.monotonic() - t0) / (rounds * calls))
    t = statistics.median(ts)
    rec = {"probe": "state_update", "kernel": kernel, "shape": list(shape),
           "pool": list(pool.shape), "calls_a_step": calls,
           "mib_a_call": round(nbytes / 2 ** 20, 1),
           "us_a_call": round(t * 1e6, 2),
           "us_a_call_min": round(min(ts) * 1e6, 2),
           "gb_s": round(nbytes / t / 1e9, 1), **errs,
           "timing": f"{rounds} rounds of {calls} calls on the device, "
                     f"median of {repeats}"}
    peak = HBM_GB_S.get(_DEVICE.get("device_kind"))
    if peak:
        rec["bytes_roofline_pct"] = round(100 * nbytes / t / 1e9 / peak, 2)
    _emit(rec)


def probe_ssm(repeats: int = 5) -> None:
    """Two lines: ``strom_ssm_update`` and ``strom_gdn_update`` alone, every
    slot on a row of its own (``sidx`` a permutation), bytes as
    ``benchmark/costs_hybrid.ssm_update_cost`` / ``costs_gdn.update_cost``
    count them: the state in and out and the call's vectors."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nvme_strom_tpu.ops import gdn, ssm
    on_cpu = jax.default_backend() != "tpu"
    f32, bf = jnp.float32, jnp.bfloat16
    hi = jax.lax.Precision.HIGHEST

    def rel(got, want):
        return float(jnp.abs(got - want).max() / jnp.abs(want).max())

    # ---- Mamba-2
    B, H, P, N = (4, 4, 16, 16) if on_cpu else SSM_SHAPE
    ks = jax.random.split(jax.random.key(1), 6)
    sidx = jnp.asarray(np.random.default_rng(3).permutation(B), jnp.int32)
    x = jax.random.normal(ks[0], (B, H, P), f32).astype(bf)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H), f32) * 1.5 - 4.0)
    a = -jnp.exp(jax.random.normal(ks[2], (H,), f32) + 1.0)
    b, c = (jax.random.normal(k, (B, N), f32).astype(bf) for k in ks[3:5])
    pool = ssm.pack_state(jax.random.normal(ks[5], (B + 1, H, P, N), f32))
    s0 = ssm.unpack_state(pool[sidx], H)
    want_s = (s0 * jnp.exp(dt * a)[..., None, None]
              + (dt[..., None] * x.astype(f32))[..., None]
              * b.astype(f32)[:, None, None, :])
    want_y = jnp.einsum("bhpn,bn->bhp", want_s, c.astype(f32), precision=hi)
    step = jax.jit(ssm.ssm_update, donate_argnums=0)
    y, pool = step(pool, sidx, x, dt, a, b, c)
    errs = {"rel_err_y": rel(y, want_y),
            "rel_err_s": rel(ssm.unpack_state(pool[sidx], H), want_s)}
    nbytes = 2 * B * H * P * N * 4 + B * (3 * H * P + 2 * N) * 4
    _emit_update("strom_ssm_update", (B, H, P, N), pool, nbytes, errs,
                 lambda pool: ssm.ssm_update(pool, sidx, x, dt, a, b, c),
                 2 if on_cpu else SSM_CALLS, repeats)
    del pool, s0, want_s

    # ---- the gated delta rule
    B, H, dk, dv = (4, 4, 16, 16) if on_cpu else GDN_SHAPE
    ks = jax.random.split(jax.random.key(2), 6)
    sidx = jnp.asarray(np.random.default_rng(4).permutation(B), jnp.int32)
    q, k = (jax.random.normal(key, (B, H, dk), f32) for key in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / dk ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, H, dv), f32)
    g = -jnp.exp(2.0 * jax.random.normal(ks[3], (B, H), f32) - 4.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, H), f32))
    pool = jax.random.normal(ks[5], (B + 1, H, dk, dv), f32)
    s = jnp.exp(g)[..., None, None] * pool[sidx]
    u = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", s, k,
                                          precision=hi))
    want_s = s + k[..., :, None] * u[..., None, :]
    want_o = jnp.einsum("bhkv,bhk->bhv", want_s, q, precision=hi)
    step = jax.jit(gdn.gdn_update, donate_argnums=0)
    o, pool = step(pool, sidx, q, k, v, g, beta)
    errs = {"rel_err_y": rel(o, want_o), "rel_err_s": rel(pool[sidx], want_s)}
    nbytes = 2 * B * H * dk * dv * 4 + B * H * (3 * dk + 3 * dv) * 4
    _emit_update("strom_gdn_update", (B, H, dk, dv), pool, nbytes, errs,
                 lambda pool: gdn.gdn_update(pool, sidx, q, k, v, g, beta),
                 2 if on_cpu else GDN_CALLS, repeats)


#: name -> (value heads, dk, dv, β's range, (prompts, rows) ...): the scan
#: at the shapes of ``q3n.flood4k``'s and ``olmoh.flood-cot``'s admissions
GDN_SCAN_CASES = {
    "q3n": (32, 128, 128, 1.0, ((4, 512), (1, 4096))),
    "olmoh": (30, 96, 192, 2.0, ((1, 1024),)),
}


def probe_gdn_scan(case: str, repeats: int = 5) -> None:
    """One line a prompt shape of ``GDN_SCAN_CASES[case]``: milliseconds a
    call of ``gdn_scan`` in bfloat16 — the host's clock around ``calls``
    calls of one jitted program ending in ``block_until_ready``; a call is
    milliseconds — with the (head, chunk) solves it makes and the bytes it
    moves as the kernel takes them (q, k a VALUE head, v in and o out in
    bfloat16, g and β a row in float32, the state in and out a prompt), and
    the error of its first rows against the recurrence."""
    import statistics

    import jax
    import jax.numpy as jnp

    from nvme_strom_tpu.ops.gdn import gdn_scan
    H, dk, dv, beta_max, shapes = GDN_SCAN_CASES[case]
    on_cpu = jax.default_backend() != "tpu"
    if on_cpu:                  # mechanics only: an eighth of everything
        H, dk, dv = H // 8, dk // 8, dv // 8
        shapes = tuple((b, rows // 8 + 8) for b, rows in shapes)
    f32, bf = jnp.float32, jnp.bfloat16
    check = 32 if on_cpu else 256          # the first rows, checked
    calls = 2 if on_cpu else 20

    def draw(seed, bsz, m):                # float32
        ks = jax.random.split(jax.random.key(seed), 5)
        q, k = (jax.random.normal(ki, (bsz, m, H, dk), f32) for ki in ks[:2])
        q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / dk ** 0.5
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        v = jax.random.normal(ks[2], (bsz, m, H, dv), f32)
        # decays from a few tokens to thousands, as the cells' weights give
        g = -jnp.exp(2.0 * jax.random.normal(ks[3], (bsz, m, H), f32) - 4.0)
        beta = beta_max * jax.nn.sigmoid(
            jax.random.normal(ks[4], (bsz, m, H), f32))
        return q, k, v, g, beta

    @jax.jit
    def recurrence(q, k, v, g, beta, s0):
        def step(s, x):
            q, k, v, g, b = x
            s = jnp.exp(g)[..., None, None] * s
            u = b[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", s, k))
            s = s + k[..., :, None] * u[..., None, :]
            return s, jnp.einsum("bhkv,bhk->bhv", s, q)
        xs = tuple(jnp.moveaxis(t[:, :check].astype(f32), 1, 0)
                   for t in (q, k, v, g, beta))
        return jnp.moveaxis(jax.lax.scan(step, s0, xs)[1], 0, 1)

    solve_err = _solve_error(dk, dv, beta_max, interpret=on_cpu)
    scan = jax.jit(gdn_scan)
    for bsz, m in shapes:
        s0 = jnp.zeros((bsz, H, dk, dv), f32)
        exact = draw(10 + bsz, bsz, m)
        args = tuple(t.astype(bf) for t in exact[:3]) + exact[3:]  # served
        errs = {}
        for tag, operands in (("rel_err_rows", args),
                              ("rel_err_rows_f32", exact)):
            with jax.default_matmul_precision("highest"):
                want = recurrence(*operands, s0)
            got = scan(*operands, s0)[0][:, :check].astype(f32)
            errs[tag] = float(jnp.abs(got - want).max()
                              / jnp.abs(want).max())
        ts = []
        for _ in range(repeats):
            t0 = time.monotonic()
            for _ in range(calls):
                o, _ = scan(*args, s0)
            o.block_until_ready()
            ts.append((time.monotonic() - t0) / calls)
        t = statistics.median(ts)
        solves = bsz * H * -(-m // 64)     # gdn_scan's chunk is 64 rows
        nbytes = (bsz * m * H * ((2 * dk + 2 * dv) * 2 + 2 * 4)
                  + 2 * bsz * H * dk * dv * 4)
        rec = {"probe": "gdn_scan", "kernel": "strom_gdn_scan", "case": case,
               "heads": H, "widths": [dk, dv], "beta_max": beta_max,
               "prompts": bsz, "rows": m, "rows_checked": check,
               "head_chunks": solves, "mib_a_call": round(nbytes / 2 ** 20, 2),
               "ms_a_call": round(t * 1e3, 4),
               "ms_a_call_min": round(min(ts) * 1e3, 4),
               "us_a_row": round(t * 1e6 / (bsz * m), 4),
               "us_a_head_chunk": round(t * 1e6 / solves, 4), **errs,
               "solve_rel_err": solve_err,
               "timing": f"host clock around {calls} calls, "
                         f"median of {repeats}"}
        peak = HBM_GB_S.get(_DEVICE.get("device_kind"))
        if peak:
            rec["bytes_roofline_pct"] = round(
                100 * nbytes / t / 1e9 / peak, 2)
        _emit(rec)


def _solve_error(dk: int, dv: int, beta_max: float, interpret: bool) -> float:
    """The chunk's solve ALONE, in a kernel of its own: ``(I + A) X = B`` for
    two drawn chunks of 64 rows side by side, as a grid step holds its heads
    — ``A = tril(β (K Kᵀ ⊙ Γ), −1)`` from unit keys ``dk`` wide, ``B`` ``dk +
    dv`` wide — against numpy's float64 solve, largest error over largest
    entry.  The whole scan's error cannot show it on the chip: the products
    around the solve take one bfloat16 pass there whatever their operands
    (PERF.md §6, PR 48: 4e-3 with the coupling at either precision); this
    reads 1e-6 only while the coupling's product keeps float32's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    from nvme_strom_tpu.ops import gdn
    h, c = 2, 64
    rng = np.random.default_rng(5)
    k = rng.normal(size=(h, c, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = np.cumsum(-np.exp(2.0 * rng.normal(size=(h, c)) - 4.0), axis=1)
    a = np.tril(rng.uniform(0, beta_max, (h, c, 1)) * (k @ k.swapaxes(1, 2))
                * np.exp(g[:, :, None] - g[:, None]), -1).astype(np.float32)
    b = rng.normal(size=(h, c, dk + dv)).astype(np.float32)

    def kernel(a_ref, b_ref, x_ref):
        x_ref[...] = gdn.solve_unit_lower(a_ref[...], b_ref[...],
                                          gdn.SCAN_SOLVE_ROWS)

    got = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(b.shape, jnp.float32),
        interpret=interpret)(jnp.asarray(a), jnp.asarray(b))
    want = np.linalg.solve(np.eye(c) + a.astype(np.float64),
                           b.astype(np.float64))
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


MODES = ("paged", "ssm", "gdn_scan")


def main() -> int:
    mode = sys.argv[1] if len(sys.argv) > 1 else None
    if mode not in MODES:
        print("usage: python -m nvme_strom_tpu.tools.kernel_probe "
              "paged [case ...] | ssm | gdn_scan [case ...]",
              file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)   # direct-script mode: repo root first
    from nvme_strom_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from nvme_strom_tpu.utils.device import require_tpu
    _DEVICE.update(require_tpu("kernel_probe"))
    import jax
    _log(f"device = {jax.devices()[0]}")
    if mode == "paged":
        for case in sys.argv[2:] or PAGED_CASES:
            probe_paged(case)
    elif mode == "ssm":
        probe_ssm()
    else:
        for case in sys.argv[2:] or GDN_SCAN_CASES:
            probe_gdn_scan(case)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Pallas kernel autotune probe: flash-attention block sizes on the chip.

This probe measures, on the chip, the fused flash-attention kernel's fwd
and fwd+bwd step time across (block_q, block_k) tilings — against the
XLA dense-attention baseline — at the train bench's shape and at a
long-context shape where the O(s²) dense path stops being competitive.
One JSON line per measurement, each naming ``platform``,
``device_kind`` and ``device_count``.

``python -m nvme_strom_tpu.tools.kernel_probe paged`` times the paged
decode kernels (``ops/paged_attention.py``) alone instead, at the serving
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


def _time_step(fn, q, k, v, chain: int = 8, repeats: int = 3) -> float:
    """Seconds per call: MEDIAN over ``repeats`` CHAINED windows of
    ``chain`` data-dependent calls, each bracketed by host reads:
    call ``i+1`` consumes call ``i``'s output, the pre-clock
    float() pins the timeline start, and the final float() cannot
    produce bytes until the whole chain has executed — the
    bench_suite._train_variant discipline applied to kernels.  The
    median across windows keeps one mid-chain link stall from
    mis-ranking a tiling (the suspect gate only catches impossibly
    FAST rates, never slow outliers)."""
    import statistics

    import jax.numpy as jnp

    def head(out):
        x = out[0] if isinstance(out, tuple) else out
        return x.astype(q.dtype) if x.dtype != q.dtype else x

    x = head(fn(q, k, v))              # compile
    float(jnp.sum(x[..., :1, :1]))
    ts = []
    for _ in range(repeats):
        x = q
        float(jnp.sum(x[..., :1, :1]))  # host round-trip: window start
        t0 = time.monotonic()
        for _ in range(chain):
            x = head(fn(x, k, v))
        float(jnp.sum(x[..., :1, :1]))
        ts.append((time.monotonic() - t0) / chain)
    return statistics.median(ts)


def probe_shape(b: int, h: int, s: int, d: int, dev) -> tuple[int, int]:
    """Sweep one shape; returns (honest, suspect) timed-row counts so
    the caller can void an all-lying step."""
    import jax
    import jax.numpy as jnp
    from nvme_strom_tpu.models.transformer import dense_causal_attention
    from nvme_strom_tpu.ops.flash_attention import flash_attention

    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.device_put(jax.random.normal(kq, (b, h, s, d), jnp.bfloat16),
                       dev)
    k = jax.device_put(jax.random.normal(kk, (b, h, s, d), jnp.bfloat16),
                       dev)
    v = jax.device_put(jax.random.normal(kv, (b, h, s, d), jnp.bfloat16),
                       dev)

    # the baseline is the MODEL's dense path (bf16 matmuls, f32 score
    # accumulation) — a hand-rolled f32 version would inflate dense
    # times and steer the flash-vs-dense choice wrong
    dense = dense_causal_attention

    def bwd_of(fn):
        def loss(q, k, v):
            return fn(q, k, v).astype(jnp.float32).sum()
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    shape = f"b{b}h{h}s{s}d{d}"
    # causal attention FLOPs (half the score matrix), fwd+bwd ≈ 3.5x
    # the QK+PV forward pair — the sanity denominator for the lying-
    # runtime gate below
    flops_fwdbwd = 3.5 * 4 * b * h * s * s * d * 0.5

    counts = [0, 0]          # [honest, suspect] timed rows

    def row(impl, t_fwd, t_bwd):
        tf = flops_fwdbwd / max(t_bwd, 1e-9) / 1e12
        rec = {"probe": "attn", "shape": shape, "impl": impl,
               "fwd_ms": round(t_fwd * 1e3, 3),
               "fwdbwd_ms": round(t_bwd * 1e3, 3),
               "tflops": round(tf, 1), "timing": "chained"}
        if tf > 300:           # v5e peak 197: physically impossible
            rec["suspect"] = "rate above device peak"
        counts[1 if "suspect" in rec else 0] += 1
        _emit(rec)
        _log(f"{shape} {impl} fwd={t_fwd * 1e3:.2f}ms "
             f"fwd+bwd={t_bwd * 1e3:.2f}ms ({tf:.0f} TF/s"
             f"{' SUSPECT' if 'suspect' in rec else ''})")
        return rec

    try:
        t_fwd = _time_step(jax.jit(dense), q, k, v)
        t_bwd = _time_step(bwd_of(dense), q, k, v)
        row("dense-xla", t_fwd, t_bwd)
    except Exception as e:  # noqa: BLE001 — OOM at long s is expected
        _emit({"probe": "attn", "shape": shape, "impl": "dense-xla",
               "error": f"{type(e).__name__}: {str(e)[:120]}"})

    best = None
    for bq in (128, 256, 512):
        for bk in (128, 256, 512):
            if bq > s or bk > s:
                continue
            fl = jax.jit(lambda q, k, v, bq=bq, bk=bk: flash_attention(
                q, k, v, block_q=bq, block_k=bk))
            fb = bwd_of(lambda q, k, v, bq=bq, bk=bk: flash_attention(
                q, k, v, block_q=bq, block_k=bk))
            try:
                t_fwd = _time_step(fl, q, k, v)
                t_bwd = _time_step(fb, q, k, v)
            except Exception as e:  # noqa: BLE001
                _emit({"probe": "attn", "shape": shape,
                       "impl": f"flash-{bq}x{bk}",
                       "error": f"{type(e).__name__}: {str(e)[:120]}"})
                continue
            rec = row(f"flash-{bq}x{bk}", t_fwd, t_bwd)
            # a suspect point must not become the adopted tiling
            if "suspect" not in rec and (best is None or t_bwd < best[0]):
                best = (t_bwd, bq, bk)
    if best is not None:
        _emit({"probe": "attn_best", "shape": shape,
               "block_q": best[1], "block_k": best[2],
               "fwdbwd_ms": round(best[0] * 1e3, 3),
               "timing": "chained"})
    return counts[0], counts[1]


def probe_matmul_roof(dev) -> None:
    """Pure bf16 matmul chain — the chip's ACHIEVABLE matmul rate as
    this runtime exposes it, i.e. the honest MFU denominator.

    If a train step's big matmul fusions sit far below the nominal
    197 TFLOP/s and a bare square-matmul chain caps at the same rate,
    the ceiling is the device as exposed; if the chain runs well above
    them, the program leaves real headroom.  Same chained
    data-dependent timing as the attention rows."""
    import statistics

    import jax
    import jax.numpy as jnp

    sizes = (4096, 8192) if dev.platform == "tpu" else (256,)
    for n in sizes:
        kx, kw = jax.random.split(jax.random.key(1))
        x = jax.device_put(jax.random.normal(kx, (n, n), jnp.bfloat16),
                           dev)
        w = jax.device_put(jax.random.normal(kw, (n, n), jnp.bfloat16),
                           dev)

        @jax.jit
        def step(x, w, n=n):
            # 1/sqrt(n) keeps the chain's variance at 1 so bf16 never
            # saturates; the scale fuses into the matmul epilogue
            return (x @ w) * (1.0 / float(n) ** 0.5)

        chain, repeats = 8, 3
        y = step(x, w)
        float(jnp.sum(y[:1, :1]))          # compile + settle
        ts = []
        for _ in range(repeats):
            y = x
            float(jnp.sum(y[:1, :1]))      # host round-trip: win start
            t0 = time.monotonic()
            for _ in range(chain):
                y = step(y, w)
            float(jnp.sum(y[:1, :1]))
            ts.append((time.monotonic() - t0) / chain)
        t = statistics.median(ts)
        tf = 2 * n ** 3 / t / 1e12
        rec = {"probe": "matmul_roof", "n": n,
               "ms": round(t * 1e3, 3), "tflops": round(tf, 1),
               "timing": "chained"}
        reasons = []
        if tf > 300:                       # v5e peak 197
            reasons.append("rate above device peak")
        if not bool(jnp.isfinite(y).all()):
            reasons.append("non-finite chain output")
        if reasons:
            rec["suspect"] = "; ".join(reasons)
        _emit(rec)
        _log(f"matmul_roof n={n}: {t * 1e3:.2f} ms = {tf:.0f} TF/s"
             f"{' SUSPECT' if 'suspect' in rec else ''}")


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


def main() -> int:
    sys.path.insert(0, REPO)   # direct-script mode: repo root first
    from nvme_strom_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from nvme_strom_tpu.utils.device import require_tpu
    _DEVICE.update(require_tpu("kernel_probe"))
    on_cpu = _DEVICE["platform"] != "tpu"
    import jax
    dev = jax.devices()[0]
    _log(f"device = {dev}")
    if sys.argv[1:2] == ["paged"]:
        for case in sys.argv[2:] or PAGED_CASES:
            probe_paged(case)
        return 0
    if sys.argv[1:2] == ["ssm"]:
        probe_ssm()
        return 0

    def roof_guarded():
        # the roof probe must never cost the step its PRIMARY output
        # (the attn tiling rows that feed best_attn_blocks adoption) —
        # exception-guarded AND ordered LAST, so a hang in it burns
        # only the tail of the step budget, never the tiling rows
        try:
            probe_matmul_roof(dev)
        except Exception as e:  # noqa: BLE001 — device/alloc flake
            _emit({"probe": "matmul_roof",
                   "error": f"{type(e).__name__}: {str(e)[:120]}"})

    if on_cpu:
        roof_guarded()                        # tiny-n mechanics
        probe_shape(1, 2, 256, 64, dev)       # mechanics only
        return 0
    h1, s1 = probe_shape(8, 16, 1024, 128, dev)   # config-7 train shape
    h2, s2 = probe_shape(2, 16, 4096, 128, dev)   # long context
    roof_guarded()                            # MFU denominator
    if (s1 + s2) and not (h1 + h2):
        # every timed row was impossibly fast: say so in a marker row
        # rather than let a reader cite a step the probe disbelieved
        _emit({"metric": "kernel_probe: SUSPECT-TIMING "
                         "(every tiling above device peak)"})
    return 0


if __name__ == "__main__":
    sys.exit(main())

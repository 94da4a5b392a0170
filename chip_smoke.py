#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a user
calls, at the published widths of Llama-3.1-8B with depth cut to fit one
16 GB chip and random weights drawn from ``--seed``:

    NVMe file → C engine → staging → bridge → HBM → jitted decode step
    with Pallas attention

Phases (any failure is fatal; nothing is caught and passed over):

  build    the C engine builds from csrc/ and reports its backend
  data     a converted-checkpoint directory (``examples/serve.py --weights``
           layout) is written under the checkout and evicted from the
           page cache
  stream   ``DeviceStream.stream_file`` over one >= 1 GiB shard, cold; the
           bytes that landed in HBM are compared with the file's
  weights  ``examples/serve.py``'s ``load_weights``; tensors compared
           bit-for-bit with what was generated
  serve    ``examples/serve.py``'s ``build_server``: DecodeServer (Pallas
           paged attention over a block pool) answers mixed requests; one
           decode step's logits are compared with ``models/decode``'s
           plain-XLA attention path

``--chips 4`` runs instead, and only: data, ``load_sharded`` under a tp=4
mesh with a per-device share check, one forward compared with the same
forward on one device, and ``IciExchange.all_gather`` over the four chips.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: generated checkpoint (git-ignored, rewritten by every run)
DATA_DIR = os.path.join(ROOT, ".chip_smoke")

#: meta-llama/Llama-3.1-8B config.json — the keys that give the shape
LLAMA31_8B = {
    "hidden_act": "silu",
    "hidden_size": 4096,
    "intermediate_size": 14336,
    "max_position_embeddings": 131072,
    "num_attention_heads": 32,
    "num_hidden_layers": 32,
    "num_key_value_heads": 8,
    "rms_norm_eps": 1e-05,
    "rope_scaling": {"factor": 8.0, "low_freq_factor": 1.0,
                     "high_freq_factor": 4.0,
                     "original_max_position_embeddings": 8192,
                     "rope_type": "llama3"},
    "rope_theta": 500000.0,
    "tie_word_embeddings": False,
    "vocab_size": 128256,
}

#: depth one 16 GB chip holds beside the KV pools and the logits
#: comparison's caches (32 published): ~7 GB of blocks + 2.1 GB of
#: embedding/lm_head in bf16; peak HBM 11.5 GiB on the v5e
SMOKE_LAYERS = 16
#: serving --max-len (the published 131072 would size every slot's cache
#: to it)
SMOKE_MAX_LEN = 4096
SLOTS = 4
POOL_BLOCKS, BLOCK_LEN = 96, 128
#: (prompt tokens, new-token budget) of the served requests
REQUESTS = ((7, 24), (12, 16), (100, 32), (60, 48), (300, 16), (400, 24),
            (33, 40), (128, 8))
#: bf16 keeps 8 significand bits (2^-8 ≈ 0.4 % per rounding); the kernels
#: accumulate in f32 where the XLA path rounds probabilities to bf16, and
#: the difference compounds over the layers' residual adds.  A wrong mask
#: or block table moves logits by O(1) of their range.
LOGITS_TOL = 0.03
#: seconds each phase may take before the process ends itself (non-zero,
#: every thread's stack on stderr) — also when the main thread is stuck
#: inside a device call.  Each path's budgets sum to under the driver's
#: 1200 s, compilation included.
BUDGET_S = {"build": 90, "data": 240, "stream": 120, "weights": 180,
            "serve": 480, "sharded": 420, "forward": 240, "exchange": 90}

_BLOCK_ELEMS = 1 << 24       # elements per generator task


def say(msg: str) -> None:
    print(msg, flush=True)


def require(ok, what) -> None:
    """A check of the smoke (not an ``assert``: it must hold under -O)."""
    if not ok:
        raise AssertionError(f"chip_smoke: check failed: {what}")


def on_tpu() -> bool:
    import jax
    return jax.devices()[0].platform == "tpu"


def phase(name: str) -> None:
    """Start ``name``'s clock (replaces the previous phase's)."""
    faulthandler.dump_traceback_later(BUDGET_S[name], exit=True)


# ----------------------------- data writer -----------------------------
# Host only: numpy generators keyed by (seed, tensor index, block), so any
# tensor can be drawn again later for the bit-for-bit checks.

def hf_config(n_layers: int, base: dict = LLAMA31_8B) -> dict:
    return dict(base, num_hidden_layers=n_layers)


def tensor_specs(cfg) -> list:
    """[(name, shape)] under ``init_params``' own names, sorted."""
    import jax

    from nvme_strom_tpu.models.transformer import init_params
    shapes = jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg))
    return [(name, tuple(s.shape)) for name, s in shapes.items()]


def _mean_std(name: str, shape: tuple) -> tuple:
    """``dense_init``'s scale (normal/√fan_in; (in, out) layout) for the
    matmul weights, N(0,1) for the embedding, 1+N(0,0.1²) for norms."""
    if name.endswith("norm"):
        return 1.0, 0.1
    if name == "tok_embed":
        return 0.0, 1.0
    return 0.0, float(shape[0]) ** -0.5


def make_tensor(seed: int, index: int, name: str, shape: tuple,
                pool: ThreadPoolExecutor | None = None) -> np.ndarray:
    """Tensor ``index`` of the checkpoint, bf16, drawn from ``seed``."""
    import ml_dtypes
    n = int(np.prod(shape, dtype=np.int64))
    out = np.empty(n, dtype=ml_dtypes.bfloat16)
    mean, std = _mean_std(name, shape)

    def fill(block: int) -> None:
        lo = block * _BLOCK_ELEMS
        hi = min(n, lo + _BLOCK_ELEMS)
        rng = np.random.default_rng([seed, index, block])
        vals = rng.standard_normal(hi - lo, dtype=np.float32)
        vals *= np.float32(std)
        if mean:
            vals += np.float32(mean)
        out[lo:hi] = vals.astype(ml_dtypes.bfloat16)

    blocks = range(-(-n // _BLOCK_ELEMS))
    if pool is None:
        for b in blocks:
            fill(b)
    else:
        list(pool.map(fill, blocks))     # list(): re-raise any failure
    return out.reshape(shape)


def write_checkpoint(out_dir: str, hf_cfg: dict, seed: int,
                     shard_bytes: int = 1 << 30) -> dict:
    """A converted checkpoint as ``tools/convert_llama.convert`` lays it
    out (``strom-NNNNN.safetensors`` shards flushed once they reach
    ``shard_bytes``, plus ``strom_config.json``), evicted from the page
    cache.  Returns {"cfg", "specs", "shards", "bytes"}."""
    from bench import evict_file
    from nvme_strom_tpu.formats.safetensors import write_safetensors
    from nvme_strom_tpu.tools.convert_llama import (config_from_hf,
                                                    strom_config_dict)

    cfg = config_from_hf(hf_cfg)
    os.makedirs(out_dir, exist_ok=True)
    for stale in os.listdir(out_dir):
        if stale.endswith(".safetensors"):
            os.unlink(os.path.join(out_dir, stale))
    specs = tensor_specs(cfg)
    shards, pending, pending_bytes, total = [], {}, 0, 0

    def flush():
        nonlocal pending, pending_bytes
        if not pending:
            return
        path = os.path.join(out_dir,
                            f"strom-{len(shards):05d}.safetensors")
        write_safetensors(path, pending)
        evict_file(path)
        shards.append(path)
        pending, pending_bytes = {}, 0

    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) \
            as pool:
        for index, (name, shape) in enumerate(specs):
            arr = make_tensor(seed, index, name, shape, pool)
            pending[name] = arr
            pending_bytes += arr.nbytes
            total += arr.nbytes
            if pending_bytes >= shard_bytes:
                flush()
        flush()
    with open(os.path.join(out_dir, "strom_config.json"), "w") as f:
        json.dump(strom_config_dict(cfg), f, indent=1)
    return {"cfg": cfg, "specs": specs, "shards": shards, "bytes": total}


# ------------------------------- phases -------------------------------

class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, summed from its
    own monitoring events, so a phase can report compile time apart from
    the time it served."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


def phase_build():
    """The engine, built from csrc/ on first use when the checkout has no
    ``libstrom_io.so`` (it is git-ignored, so the driver's never has)."""
    from nvme_strom_tpu.io import StromEngine
    from nvme_strom_tpu.io.engine import _LIB_PATH
    had = _LIB_PATH.exists()
    t0 = time.monotonic()
    engine = StromEngine()
    say(f"build: {_LIB_PATH.name} "
        f"{'present' if had else 'built from csrc/'} in "
        f"{time.monotonic() - t0:.1f}s; engine backend={engine.backend} "
        f"rings={engine.n_rings} chunk={engine.config.chunk_bytes >> 20}MiB "
        f"pool={engine.config.buffer_pool_bytes >> 20}MiB")
    return engine


def phase_data(hf_cfg: dict, seed: int, out_dir: str = DATA_DIR) -> dict:
    t0 = time.monotonic()
    ckpt = write_checkpoint(out_dir, hf_cfg, seed)
    free = os.statvfs(out_dir)
    say(f"data: {len(ckpt['specs'])} tensors, "
        f"{ckpt['bytes'] / 2**30:.2f} GiB bf16 in {len(ckpt['shards'])} "
        f"shards under {out_dir} in {time.monotonic() - t0:.1f}s "
        f"(disk free {free.f_bavail * free.f_frsize / 2**30:.0f} GiB)")
    return ckpt


def phase_stream(engine, path: str) -> None:
    """One shard, cold, at default settings; every byte that landed on
    the device is compared with the file."""
    import jax

    from bench import evict_file
    from nvme_strom_tpu.io import check_file
    from nvme_strom_tpu.ops import bridge

    info = check_file(path)
    say(f"stream: {os.path.basename(path)} "
        f"{os.path.getsize(path) / 2**30:.3f} GiB, O_DIRECT "
        f"open {'granted' if info.supports_direct else 'REFUSED'} "
        f"(check_file), fs_magic={info.fs_magic:#x}")
    engine.sync_stats()
    before = engine.stats.snapshot()
    stream = bridge.DeviceStream(engine)
    t0 = time.monotonic()
    parts = list(stream.stream_file(path))
    jax.block_until_ready(parts)
    dt = time.monotonic() - t0
    engine.sync_stats()
    d = {k: v - before.get(k, 0)
         for k, v in engine.stats.snapshot().items()
         if isinstance(v, int)}
    moved = sum(int(p.nbytes) for p in parts)

    dev = jax.devices()[0]
    require(all(p.devices() == {dev} for p in parts), "chunk off device 0")
    with open(path, "rb") as f:
        want = np.frombuffer(f.read(), dtype=np.uint8)
    evict_file(path)            # the weights phase reads it cold again
    require(moved == want.nbytes, (moved, want.nbytes))
    pos = 0
    for p in parts:
        got = np.asarray(p)
        if not np.array_equal(got, want[pos:pos + got.nbytes]):
            raise AssertionError(f"stream: bytes differ at offset {pos}")
        pos += got.nbytes

    staged = d.get("overlap_chunks", 0)
    tpu = on_tpu()
    dma_programs = bridge._pallas_h2d(dev)._cache_size() if tpu else 0
    say(f"stream: {moved / 2**30:.3f} GiB in {dt:.2f}s wall "
        f"({len(parts)} chunks), all bytes equal to the file's; "
        f"bytes_direct={d.get('bytes_direct', 0)} "
        f"bytes_fallback={d.get('bytes_fallback', 0)} (buffered reads; "
        f"{d.get('bytes_resident', 0)} chosen because page-cache "
        f"resident, {d.get('retries', 0)} direct reads retried buffered) "
        f"bounce_bytes={d.get('bounce_bytes', 0)}")
    say(f"stream: transfer backend: {staged} chunks via the overlap "
        f"stage's {'Pallas pinned_host→HBM DMA' if tpu else 'device_put'}"
        f", {len(parts) - staged} via plain device_put; "
        f"DMA kernel programs compiled={dma_programs}")
    if tpu:
        # auto-engaged on a TPU, and no other transfer exists there
        require(staged == len(parts) and dma_programs >= 1,
                f"every chunk through the DMA kernel: {staged} of "
                f"{len(parts)}, {dma_programs} programs")


def phase_weights(engine, ckpt: dict, seed: int,
                  weights_dir: str = DATA_DIR):
    """``examples/serve.py``'s own load; a few tensors bit-for-bit."""
    import jax

    from examples.serve import load_weights, read_config

    cfg = read_config(weights_dir)
    require(cfg == ckpt["cfg"], (cfg, ckpt["cfg"]))
    t0 = time.monotonic()
    params = load_weights(weights_dir, engine)
    jax.block_until_ready(params)
    dt = time.monotonic() - t0
    nbytes = sum(int(a.nbytes) for a in params.values())
    index = {name: (i, shape)
             for i, (name, shape) in enumerate(ckpt["specs"])}
    require(set(params) == set(index), set(params) ^ set(index))
    for name, (_, shape) in index.items():
        require(params[name].shape == shape, (name, params[name].shape))
        require(params[name].dtype == jax.numpy.bfloat16, name)
    last = cfg.n_layers - 1
    checked = ["final_norm", "layers.0.wk", f"layers.{last}.w_down",
               "lm_head"]
    for name in checked:
        i, shape = index[name]
        want = make_tensor(seed, i, name, shape)
        got = np.asarray(params[name])
        if not np.array_equal(got.view(np.uint16), want.view(np.uint16)):
            raise AssertionError(f"weights: {name} differs from what "
                                 "was generated")
    say(f"weights: {len(params)} tensors, {nbytes / 2**30:.2f} GiB in "
        f"{dt:.2f}s; bit-for-bit equal to the generator: "
        f"{', '.join(checked)}")
    return cfg, params


def _requests(cfg, seed: int) -> list:
    rng = np.random.default_rng([seed, 1 << 20])
    return [(f"r{i}", rng.integers(0, cfg.vocab, size=s).tolist(), new)
            for i, (s, new) in enumerate(REQUESTS)]


def _serve(label: str, srv, reqs: list, clock: CompileClock) -> None:
    import jax
    c0, t0 = clock.seconds, time.monotonic()
    for rid, ids, new in reqs:
        srv.submit(rid, ids, new)
    results = srv.run()
    jax.block_until_ready(srv.tok)
    wall = time.monotonic() - t0
    compile_s = clock.seconds - c0
    for rid, ids, new in reqs:
        out = results[rid]
        require(len(out) == new, (label, rid, len(out), new))
        require(all(0 <= t < srv.cfg.vocab for t in out), (label, rid))
    total = sum(len(v) for v in results.values())
    say(f"serve[{label}]: {len(reqs)} requests on {srv.B} slots, every "
        f"budget returned in full, {total} tokens; compile "
        f"{compile_s:.1f}s, serve {max(wall - compile_s, 0.0):.1f}s "
        f"(wall {wall:.1f}s)")


def _compare_logits(params, cfg, seed: int, max_len: int) -> None:
    """One decode step of all slots through the paged-attention kernel on
    a block pool, against ``models/decode.decode_step`` (plain XLA
    attention) of each slot alone over the same params and the same cache
    contents, gathered dense."""
    import jax

    from nvme_strom_tpu.models.decode import decode_step
    from nvme_strom_tpu.models.serving import paged_logits

    B, bk = SLOTS, BLOCK_LEN
    max_blocks = max_len // bk
    n_pool = B * max_blocks
    L, nkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    kk, kv = jax.random.split(jax.random.key(seed))
    pool_shape = (L, n_pool + 1, nkv, bk, hd)
    k_pool = jax.random.normal(kk, pool_shape, cfg.dtype)
    v_pool = jax.random.normal(kv, pool_shape, cfg.dtype)
    rng = np.random.default_rng([seed, 2 << 20])
    # each slot owns a shuffled set of pool blocks and sits at its own
    # position (one short, one near the end of the cache)
    table = rng.permutation(n_pool).astype(np.int32).reshape(
        B, max_blocks)
    pos = np.array([5, max_len - 2] + rng.integers(
        bk, max_len - 1, size=B - 2).tolist(), np.int32)[:B]
    blk = table[np.arange(B), pos // bk]
    off = pos % bk
    tok = rng.integers(0, cfg.vocab, size=B).astype(np.int32)

    @jax.jit
    def alone(params, tok, k_pool, v_pool, row, pos):
        def dense(pool):    # one slot's blocks → (L, 1, nkv, max_len, hd)
            return pool[:, row].transpose(0, 2, 1, 3, 4).reshape(
                L, 1, nkv, max_blocks * bk, hd)
        return decode_step(params, tok[None], cfg,
                           {"k": dense(k_pool), "v": dense(v_pool),
                            "pos": pos})[0][0]

    ref = np.stack([np.asarray(alone(params, tok[b], k_pool, v_pool,
                                     table[b], pos[b])) for b in range(B)])
    lowered = jax.jit(paged_logits, static_argnums=(1,)).lower(
        params, cfg, tok, k_pool, v_pool, blk, off, table, pos)
    kernel = "tpu_custom_call" in lowered.as_text()
    if on_tpu():
        # interpret=False: the kernels lowered to Mosaic custom calls
        require(kernel, "the paged step lowered no Pallas kernel")
    paged = np.asarray(lowered.compile()(
        params, tok, k_pool, v_pool, blk, off, table, pos)[0])
    scale = float(np.max(np.abs(ref)))
    require(np.isfinite(ref).all() and scale > 0,
            "finite, non-zero reference logits")
    require(paged.shape == (B, cfg.vocab) and np.isfinite(paged).all(),
            f"finite ({B}, vocab) logits from the paged step")
    err = float(np.max(np.abs(paged - ref))) / scale
    say(f"logits: one decode step, {B} slots at positions {pos.tolist()}"
        f", vs plain XLA attention: max|diff|/max|ref| {err:.2e} "
        f"(tolerance {LOGITS_TOL:g}, bf16); kernels compiled "
        f"(interpret=False): {kernel}")
    require(err <= LOGITS_TOL, err)


def phase_serve(params, cfg, seed: int, max_len: int,
                clock: CompileClock) -> None:
    from examples.serve import build_server

    reqs = _requests(cfg, seed)
    srv = build_server(params, cfg, slots=SLOTS, max_len=max_len,
                       paged=POOL_BLOCKS, block_len=BLOCK_LEN)
    _serve("DecodeServer, Pallas paged attention", srv, reqs, clock)
    del srv
    _compare_logits(params, cfg, seed, max_len)


def phase_four_chips(engine, ckpt: dict, seed: int, devices,
                     weights_dir: str = DATA_DIR,
                     tick=lambda name: None) -> None:
    """The sharded restore users depend on, across ``devices`` (tp).
    ``tick(name)`` is told when each of its three parts starts."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from nvme_strom_tpu.models.transformer import forward
    from nvme_strom_tpu.ops.ici import IciExchange
    from nvme_strom_tpu.parallel.mesh import exchange_mesh, make_mesh
    from nvme_strom_tpu.parallel.shardings import param_shardings
    from nvme_strom_tpu.parallel.weights import LazyCheckpoint

    cfg = ckpt["cfg"]
    n = len(devices)
    tick("sharded")
    mesh = make_mesh({"tp": n}, devices)
    shardings = param_shardings(cfg, mesh)
    t0 = time.monotonic()
    params = LazyCheckpoint(weights_dir).load_sharded(shardings,
                                                      engine=engine)
    jax.block_until_ready(params)
    dt = time.monotonic() - t0

    per_dev = {d: 0 for d in devices}
    for name, arr in params.items():
        shards = arr.addressable_shards
        require({s.device for s in shards} == set(devices), name)
        split = any(ax is not None for ax in shardings[name].spec)
        for s in shards:
            per_dev[s.device] += int(s.data.nbytes)
            want = arr.nbytes // n if split else arr.nbytes
            require(int(s.data.nbytes) == want, (name, s.device))
    total = sum(int(a.nbytes) for a in params.values())
    say(f"sharded: load_sharded under tp={n} in {dt:.2f}s; every sharded "
        f"tensor has 1/{n} on each of {n} distinct devices; per-device "
        "bytes " + ", ".join(f"{d.id}:{b}" for d, b in per_dev.items())
        + f" (whole model {total})")
    require(max(per_dev.values()) < total, per_dev)
    index = {nm: i for i, (nm, _) in enumerate(ckpt["specs"])}
    for name in ("layers.0.wq", f"layers.{cfg.n_layers - 1}.w_down"):
        want = make_tensor(seed, index[name], name, params[name].shape)
        require(np.array_equal(np.asarray(params[name]).view(np.uint16),
                               want.view(np.uint16)), name)

    tick("forward")
    rng = np.random.default_rng([seed, 3 << 20])
    tokens = rng.integers(0, cfg.vocab, size=(2, 256)).astype(np.int32)
    fwd = jax.jit(forward, static_argnums=(2,))
    sharded = np.asarray(fwd(
        params, jax.device_put(tokens, NamedSharding(mesh, P())), cfg))
    one = SingleDeviceSharding(devices[0])
    single = np.asarray(fwd(
        {k: jax.device_put(v, one) for k, v in params.items()},
        jax.device_put(tokens, one), cfg))
    require(sharded.shape == (2, 256, cfg.vocab), sharded.shape)
    require(np.isfinite(single).all() and np.isfinite(sharded).all(),
            "finite logits from both forwards")
    err = float(np.max(np.abs(sharded - single))
                / np.max(np.abs(single)))
    say(f"forward: tokens {tokens.shape} under tp={n} vs device "
        f"{devices[0].id} alone: max|diff|/max|ref|={err:.2e} "
        f"(tolerance {LOGITS_TOL:g}, bf16)")
    require(err <= LOGITS_TOL, err)
    del params

    tick("exchange")
    ex = IciExchange(exchange_mesh(n, devices), stats=engine.stats)
    sizes = (4096, (1 << 20) + 123, 8 << 20)
    t0 = time.monotonic()
    for _ in range(3):
        for nbytes in sizes:
            rows = rng.integers(0, 256, size=(n, nbytes), dtype=np.uint8)
            if not np.array_equal(ex.all_gather(rows), rows):
                raise AssertionError(
                    f"exchange: gathered rows differ at {nbytes} bytes")
    say(f"exchange: IciExchange.all_gather backend={ex.backend} over "
        f"{n} devices, rows of {', '.join(map(str, sizes))} bytes x 3 "
        f"rounds in {time.monotonic() - t0:.2f}s (compile included); "
        "every gather byte for byte equal to the local rows")


# -------------------------------- main --------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the sharded-restore path on four chips and "
                         "what it is compared with, no other phase")
    args = ap.parse_args(argv)
    phase("build")

    import jax

    from nvme_strom_tpu.utils.compile_cache import (cache_entries,
                                                    enable_compile_cache)
    from nvme_strom_tpu.utils.device import device_line

    say(device_line())
    devs = jax.devices()
    if devs[0].platform != "tpu":
        say(f"chip_smoke: needs a TPU, found platform "
            f"{devs[0].platform!r} — nothing was run")
        return 1
    if len(devs) < args.chips:
        say(f"chip_smoke: --chips {args.chips} needs {args.chips} "
            f"devices, found {len(devs)} — nothing was run")
        return 1
    cache_dir = enable_compile_cache()
    entries0 = cache_entries(cache_dir)
    say(f"compile cache: {cache_dir} ({entries0} entries; "
        "JAX_COMPILATION_CACHE_DIR "
        + ("set" if os.environ.get("JAX_COMPILATION_CACHE_DIR")
           else "unset") + ")")
    clock = CompileClock()
    hf_cfg = hf_config(SMOKE_LAYERS)
    say(f"reduced: Llama-3.1-8B widths as published (hidden "
        f"{hf_cfg['hidden_size']}, {hf_cfg['num_attention_heads']} heads, "
        f"{hf_cfg['num_key_value_heads']} KV heads, intermediate "
        f"{hf_cfg['intermediate_size']}, vocab {hf_cfg['vocab_size']}, "
        f"rope_theta {hf_cfg['rope_theta']:g}, llama3 rope scaling, "
        f"untied lm_head); num_hidden_layers "
        f"{LLAMA31_8B['num_hidden_layers']} -> {SMOKE_LAYERS}; serving "
        f"--max-len {LLAMA31_8B['max_position_embeddings']} -> "
        f"{SMOKE_MAX_LEN}; weights random from --seed {args.seed}")

    engine = phase_build()
    phase("data")
    ckpt = phase_data(hf_cfg, args.seed)
    if args.chips == 4:
        phase_four_chips(engine, ckpt, args.seed, devs[:4], tick=phase)
    else:
        phase("stream")
        phase_stream(engine, ckpt["shards"][0])
        phase("weights")
        cfg, params = phase_weights(engine, ckpt, args.seed)
        phase("serve")
        phase_serve(params, cfg, args.seed, SMOKE_MAX_LEN, clock)
        del params
    engine.sync_stats()
    s = engine.stats
    say(f"engine stats: direct={s.bytes_direct} "
        f"fallback={s.bytes_fallback} bounce={s.bounce_bytes}")
    engine.close_all()
    peak = devs[0].memory_stats()["peak_bytes_in_use"]
    say(f"peak HBM on device 0: {peak} bytes ({peak / 2**30:.2f} GiB); "
        f"compile {clock.seconds:.1f}s in all; compile cache entries "
        f"{entries0} -> {cache_entries(cache_dir)}")
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

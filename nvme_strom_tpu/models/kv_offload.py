"""SSD-backed KV cache: decode beyond HBM via the strom-io engine.

The reference moves file bytes into accelerator memory so consumers can
work on data larger than the device (SURVEY.md §3.5 — PG-Strom scans
tables bigger than GPU RAM).  This module applies the same move to the
inference KV cache: a decode session whose attention history exceeds the
device budget keeps only a recent window in HBM and spills full pages to
NVMe through the engine's write path (the checkpoint/inverse direction,
SURVEY.md §5), streaming them back through DeviceStream for attention.

TPU-first structure:

- the HBM working set is two static-shape arrays
  ``(n_layers, batch, n_kv_heads, window, head_dim)`` — page eviction is
  an on-device shift, never a reallocation, so every jitted step reuses
  one compiled program regardless of total history length;
- attention over history is **online-softmax accumulation** (the
  flash-attention recipe) at kv-head width: each NVMe page contributes a
  partial ``(m, l, acc)`` that combines associatively with the window's
  partial, so pages stream through one at a time and the full history
  never co-resides in HBM;
- GQA queries are grouped to their kv head inside the partial
  (``(b, n_kv, group, hd)``) — no expanded cache copies anywhere;
- the page file layout is stride-regular (k block then v block per
  page, layer-major inside) so a layer's page reads are two contiguous
  spans the engine can pipeline at queue depth.

Honest accounting: evicted pages ride ``submit_write`` (O_DIRECT when
aligned, bounced+counted otherwise); streamed pages ride the zero-copy
read path and count ``bytes_to_device``, exactly like every other
consumer of the engine.

Durability + integrity (docs/RESILIENCE.md): eviction writes adopt the
resilient write mirror when the engine carries it (each page slot is an
exclusively-owned range, so retries are idempotent), and under
``STROM_VERIFY`` every evicted section stamps a per-layer CRC32C that
the read tier re-checks in the staging window before the device
transfer — a flipped bit in cold history fails attention loudly instead
of skewing the softmax silently.
"""

from __future__ import annotations

import functools
import threading
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from nvme_strom_tpu.io.engine import StromEngine
from nvme_strom_tpu.models.decode import mlp_block as _mlp_block
from nvme_strom_tpu.models.transformer import (
    TransformerConfig, qkv_project, rms_norm, wmat)
from nvme_strom_tpu.ops.bridge import DeviceStream
from nvme_strom_tpu.utils.lockwitness import make_condition, make_lock


@dataclass(frozen=True)
class OffloadConfig:
    """Shape of the HBM window and its NVMe backing file.

    window = ``page_len * window_pages`` recent positions stay in HBM;
    older history lives in ``path`` in ``page_len``-position pages.

    ``quantize="int8"`` stores cold pages as int8 with one f32
    absmax scale per (position, kv head) — the NVMe stream per token
    shrinks ~2x (bf16) / ~4x (f32) at a bounded attention error; the
    window and all compute stay full precision, dequantization happens
    on device after the read.

    ``host_cache_pages``: a host-DRAM middle tier.  The newest N
    evicted pages keep their (already materialized) host copies in an
    LRU; attention serves those pages straight from RAM — no NVMe
    read — and falls through to the page file past the LRU.  Three
    tiers total: HBM window / host RAM / NVMe, each overflowing into
    the next.
    """
    path: str
    page_len: int = 256
    window_pages: int = 4
    quantize: Optional[str] = None      # None | "int8"
    host_cache_pages: int = 0

    def __post_init__(self):
        if self.quantize not in (None, "int8"):
            raise ValueError(f"quantize must be None or 'int8', "
                             f"got {self.quantize!r}")

    @property
    def window(self) -> int:
        return self.page_len * self.window_pages


# ---------------------------------------------------------------------------
# jitted pieces (cached per shape)

@functools.partial(jax.jit, donate_argnums=(0, 1))
def _append_block(k_win, v_win, k_new, v_new, count):
    """Write (L,b,nkv,s,hd) new positions at window slot ``count``."""
    k_win = lax.dynamic_update_slice(k_win, k_new, (0, 0, 0, count, 0))
    v_win = lax.dynamic_update_slice(v_win, v_new, (0, 0, 0, count, 0))
    return k_win, v_win


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _append_layer(k_win, v_win, k_new, v_new, layer, count):
    """Write one layer's (1,b,nkv,1,hd) position at (layer, count)."""
    k_win = lax.dynamic_update_slice(k_win, k_new, (layer, 0, 0, count, 0))
    v_win = lax.dynamic_update_slice(v_win, v_new, (layer, 0, 0, count, 0))
    return k_win, v_win


@functools.partial(jax.jit, static_argnums=(2,), donate_argnums=())
def _evict_pages(k_win, v_win, page_slots: int):
    """Split off the oldest ``page_slots`` positions; shift the rest down.

    Returns (k_page, v_page, k_win', v_win') — the page arrays are the
    evicted history (device-resident until the engine write drains them).
    """
    L, b, nkv, W, hd = k_win.shape
    k_page = lax.slice_in_dim(k_win, 0, page_slots, axis=3)
    v_page = lax.slice_in_dim(v_win, 0, page_slots, axis=3)
    pad = jnp.zeros((L, b, nkv, page_slots, hd), k_win.dtype)
    k_win = jnp.concatenate(
        [lax.slice_in_dim(k_win, page_slots, W, axis=3), pad], axis=3)
    v_win = jnp.concatenate(
        [lax.slice_in_dim(v_win, page_slots, W, axis=3), pad], axis=3)
    return k_page, v_page, k_win, v_win


def _grouped(q, n_kv: int):
    """(b, nh, s, hd) queries → (b, n_kv, g*s, hd) grouped to kv heads."""
    b, nh, s, hd = q.shape
    g = nh // n_kv
    return q.reshape(b, n_kv, g * s, hd)


def _partial_impl(q, k, v, mask=None):
    """Online-softmax partial of grouped queries against one key block.

    q (b, nkv, rows, hd); k/v (b, nkv, S, hd); optional ``mask``
    broadcastable to the (b, nkv, rows, S) score shape (False = hidden,
    -1e30 sentinel) → m (b,nkv,rows,1), l, acc.  The ONE softmax-
    partial recipe every attention path here shares."""
    hd = q.shape[-1]
    s = jnp.einsum("bkgd,bksd->bkgs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(hd))
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bkgs,bksd->bkgd", p, v.astype(jnp.float32))
    return m, l, acc


_page_partial = jax.jit(_partial_impl)


@jax.jit
def _page_partial_q(q, k_q, k_s, v_q, v_s):
    """int8 page variant: dequant INSIDE the jit so XLA fuses it into
    the einsum input — no eager f32 page materializes in HBM."""
    return _partial_impl(q, k_q.astype(jnp.float32) * k_s,
                         v_q.astype(jnp.float32) * v_s)


@jax.jit
def _window_partial(q, k_win_l, v_win_l, count):
    """Partial over the window's first ``count`` valid positions."""
    W = k_win_l.shape[2]
    valid = (jnp.arange(W) < count)[None, None, None, :]
    return _partial_impl(q, k_win_l, v_win_l, mask=valid)


@jax.jit
def _quantize_page(x):
    """(…, P, hd) page → (int8 data, f32 absmax scale over hd)."""
    xf = x.astype(jnp.float32)
    m = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(m > 0, m / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


@jax.jit
def _combine(m1, l1, a1, m2, l2, a2):
    """Associative online-softmax merge of two partials."""
    m = jnp.maximum(m1, m2)
    w1 = jnp.exp(m1 - m)
    w2 = jnp.exp(m2 - m)
    return m, l1 * w1 + l2 * w2, a1 * w1 + a2 * w2


@functools.partial(jax.jit, static_argnums=(3,))
def _chunk_causal_partial(q, k, v, s_len: int):
    """Causal partial of a prefill chunk against its OWN k/v.

    q (b, nkv, g*s, hd) grouped rows (row j*s+t ↔ head j, position t);
    k/v (b, nkv, s, hd).  Row t sees keys 0..t — the intra-chunk half
    of chunked prefill (history pages/window are the other half)."""
    rows = q.shape[2]
    t = jnp.arange(rows) % s_len
    causal = (t[:, None] >= jnp.arange(s_len)[None, :])[None, None]
    return _partial_impl(q, k, v, mask=causal)


@jax.jit
def _finish(m, l, acc):
    """(b, nkv, rows, hd) partials → normalized attention rows.

    Row index kv*(g*s)+j*s+t equals (kv*g+j)*s+t — i.e. flattened
    (head, position) row-major — so the caller's reshape to
    (b, n_heads, s, hd) is exact for any s."""
    return acc / l


class PagedKVCache:
    """Mutable decode-session KV cache: HBM window + NVMe page tiers.

    The host orchestrates the tier boundary (append/evict/stream) while
    every tensor op runs jitted on device with static shapes.  Not
    thread-safe; one instance per decode session.
    """

    def __init__(self, cfg: TransformerConfig, ocfg: OffloadConfig,
                 engine: StromEngine, batch: int, device=None):
        cfg.require_kv_pages("PagedKVCache (models/kv_offload.py)")
        cfg.require_pre_norm("PagedKVCache (models/kv_offload.py)")
        self.cfg = cfg
        self.ocfg = ocfg
        self.engine = engine
        self.batch = batch
        self.device = device or jax.local_devices()[0]
        L, nkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        W = ocfg.window
        shape = (L, batch, nkv, W, hd)
        self.k_win = jnp.zeros(shape, cfg.dtype)
        self.v_win = jnp.zeros(shape, cfg.dtype)
        self.count = 0            # valid positions in the window (host int)
        self.n_cold = 0           # pages already written to NVMe
        self._quant = ocfg.quantize == "int8"
        self._itemsize = (1 if self._quant
                          else jnp.zeros((), cfg.dtype).dtype.itemsize)
        # per-layer bytes of one page of one of k/v (data, then scales)
        self._pb_layer = (batch * nkv * ocfg.page_len * hd * self._itemsize)
        self._pb_block = self._pb_layer * L     # all layers of k (or v)
        self._sb_layer = (batch * nkv * ocfg.page_len * 4 if self._quant
                          else 0)               # f32 absmax scales
        self._sb_block = self._sb_layer * L
        # page file stride: [k data][k scales][v data][v scales]
        self._page_stride = 2 * (self._pb_block + self._sb_block)
        self._fh = engine.open(ocfg.path, writable=True)
        self._stream = DeviceStream(engine, device=self.device,
                                    klass="decode",
                                    depth=engine.config.queue_depth)
        # in-flight eviction writes (PendingWrite keeps the host buffer
        # alive); drained before any read and bounded by _MAX_PENDING
        self._pending_writes: list = []
        # host-DRAM tier: page index → section host arrays (LRU; the
        # newest evictions — decode re-reads every cold page per step,
        # so RAM hits replace NVMe reads wholesale)
        self._host_cache: "dict" = {}
        self.host_cache_hits = 0
        self.host_cache_misses = 0
        # read-side integrity (STROM_VERIFY): per-(page, section, layer)
        # CRC32C stamped at eviction time, verified when the layer slice
        # streams back for attention.  Session-scoped and in-memory —
        # the page file's lifetime IS the cache's, so unlike checkpoint
        # tiles there is no durable sidecar to keep in sync.
        from nvme_strom_tpu.utils.checksum import VerifyPolicy
        self._verify = VerifyPolicy()
        self._page_crc: Dict[tuple, int] = {}

    _MAX_PENDING_PAGES = 4

    # -- lifecycle --------------------------------------------------------

    def _drain_writes(self, keep: int = 0) -> None:
        """Complete in-flight eviction writes (oldest first), leaving at
        most ``keep`` page-writes outstanding.

        Exception-safe: every popped PendingWrite is waited even when an
        earlier one fails — each holds the only reference keeping its
        source buffer alive while the engine works from a raw pointer,
        so dropping one mid-flight would let the engine read freed
        memory.  The first error re-raises after the batch settles."""
        first_err: Optional[OSError] = None
        while len(self._pending_writes) > keep:
            for p in self._pending_writes.pop(0):
                try:
                    p.wait()
                except OSError as e:
                    if first_err is None:
                        first_err = e
        if first_err is not None:
            raise first_err

    def flush(self) -> None:
        """Block until every evicted page's write has completed, so the
        backing file is fully visible to same-host readers (size
        checks, handoff to another process).  Completion is not crash
        durability — no fsync is issued, and non-conformant
        (unaligned/buffered-fallback) writes may still sit in the page
        cache; use the checkpoint manager for durable state."""
        self._drain_writes()

    def close(self) -> None:
        if self._fh is not None:
            try:
                self._drain_writes()   # writes target this fh
            finally:
                self.engine.close(self._fh)
                self._fh = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    @property
    def pos(self) -> int:
        """Total cached positions (cold + window)."""
        return self.n_cold * self.ocfg.page_len + self.count

    # -- write tier -------------------------------------------------------

    def _section_offsets(self, page: int) -> Tuple[int, int, int, int]:
        """(k_data, k_scales, v_data, v_scales) offsets of a page.

        Scale sections have zero size in the unquantized layout, so the
        k/v data offsets degrade to the two-block stride."""
        base = page * self._page_stride
        return (base,
                base + self._pb_block,
                base + self._pb_block + self._sb_block,
                base + 2 * self._pb_block + self._sb_block)

    def _write_page(self, k_page, v_page) -> None:
        """Evicted (L,b,nkv,P,hd) pair → contiguous engine writes
        (int8 data + f32 scale sections when quantizing).

        Asynchronous: the writes overlap whatever compute follows the
        eviction (bulk prefill seeding writes pages back-to-back);
        every read path drains first, so a just-evicted page can never
        be streamed back stale."""
        self._drain_writes(keep=self._MAX_PENDING_PAGES - 1)
        kd, ks, vd, vs = self._section_offsets(self.n_cold)
        if self._quant:
            k_q, k_s = _quantize_page(k_page)
            v_q, v_s = _quantize_page(v_page)
            sections = ((k_q, kd), (k_s, ks), (v_q, vd), (v_s, vs))
        else:
            sections = ((k_page, kd), (v_page, vd))
        pend = []
        hosts = []
        sec_lens = (self._pb_layer, self._sb_layer,
                    self._pb_layer, self._sb_layer)
        for sec_idx, (arr, off) in enumerate(sections):
            host = np.ascontiguousarray(
                np.asarray(arr)).view(np.uint8).reshape(-1)
            hosts.append(host)
            if self._verify.enabled:
                # stamp per LAYER slice — exactly the spans the read
                # tier streams back (one layer's k/v/scales per page).
                # The sampling policy gates HERE, at stamp time: in
                # ``sample`` mode only every Nth span pays the CRC on
                # this hot eviction path, and the read tier verifies
                # precisely the spans that carry a stamp — one gate,
                # not two multiplying into 1/N².
                from nvme_strom_tpu.utils.checksum import crc32c
                ln = (sec_lens[sec_idx] if self._quant
                      else self._pb_layer)
                L = self.k_win.shape[0]
                for layer in range(L):
                    if self._verify.want():
                        self._page_crc[(self.n_cold, sec_idx, layer)] = \
                            crc32c(host[layer * ln:(layer + 1) * ln])
            chunk = self.engine.config.chunk_bytes
            for p0 in range(0, host.nbytes, chunk):
                part = host[p0:p0 + chunk]
                pend.append(
                    self.engine.submit_write(self._fh, off + p0, part))
        self._pending_writes.append(pend)
        if self.ocfg.host_cache_pages > 0:
            # RAM tier: the section buffers already exist host-side —
            # retaining them costs nothing extra (they double as the
            # write keepalives) and spares the NVMe round trip
            self._host_cache[self.n_cold] = hosts
            while len(self._host_cache) > self.ocfg.host_cache_pages:
                self._host_cache.pop(next(iter(self._host_cache)))
        self.n_cold += 1

    def _evict_one(self) -> None:
        k_page, v_page, self.k_win, self.v_win = _evict_pages(
            self.k_win, self.v_win, self.ocfg.page_len)
        self._write_page(k_page, v_page)
        self.count -= self.ocfg.page_len

    def append(self, k_new, v_new) -> None:
        """Push (L, b, nkv, s, hd) new positions; evict pages as needed.

        Post-condition: ``count < window`` — at least one free slot, the
        invariant the per-step append_layer/commit_step cycle relies on.
        """
        W = self.ocfg.window
        s = k_new.shape[3]
        done = 0
        while done < s:
            take = min(W - self.count, s - done)
            if take > 0:
                blk_k = lax.slice_in_dim(k_new, done, done + take, axis=3)
                blk_v = lax.slice_in_dim(v_new, done, done + take, axis=3)
                self.k_win, self.v_win = _append_block(
                    self.k_win, self.v_win, blk_k.astype(self.cfg.dtype),
                    blk_v.astype(self.cfg.dtype),
                    jnp.asarray(self.count, jnp.int32))
                self.count += take
                done += take
            if self.count == W:
                self._evict_one()

    def append_layer(self, layer: int, k, v) -> None:
        """Stage one layer's (b, nkv, s, hd) positions at slot ``count``
        WITHOUT advancing it — every layer of a step/chunk writes the
        same slots; :meth:`commit_step` / :meth:`commit_block` advance.
        Requires count + s <= window (decode: guaranteed by the
        commit post-conditions; chunks: call :meth:`ensure_room`)."""
        self.k_win, self.v_win = _append_layer(
            self.k_win, self.v_win, k[None].astype(self.cfg.dtype),
            v[None].astype(self.cfg.dtype),
            jnp.asarray(layer, jnp.int32),
            jnp.asarray(self.count, jnp.int32))

    def commit_step(self) -> None:
        """Advance past the slot all layers just staged; evict if full."""
        self.commit_block(1)

    def commit_block(self, s: int) -> None:
        """Advance past ``s`` slots all layers just staged; evict until
        the invariant count < window holds again."""
        self.count += s
        if self.count > self.ocfg.window:
            raise RuntimeError(
                f"commit_block({s}) overran the window "
                f"({self.count} > {self.ocfg.window})")
        while self.count >= self.ocfg.window:
            self._evict_one()

    def ensure_room(self, s: int) -> None:
        """Evict until ``s`` more positions fit in the window.  The
        evicted slots are pure history (they pre-date the block being
        staged), so this is always causally safe."""
        P, W = self.ocfg.page_len, self.ocfg.window
        if s > W:
            raise ValueError(f"block of {s} exceeds window {W}")
        while self.count + s > W:
            if self.count < P:
                raise RuntimeError(
                    f"cannot make room: count={self.count} < page "
                    f"{P} but {s} more positions requested")
            self._evict_one()

    # -- session persistence ----------------------------------------------

    def save_session(self, directory) -> None:
        """Persist the session next to its page file: the HBM window
        (through the engine's write path) + counters.  With the page
        file (already on NVMe, flushed here) this is the WHOLE decode
        state — a generation can suspend and resume in another process
        (the inference analogue of checkpoint/resume, SURVEY.md §5)."""
        import json
        import os
        from nvme_strom_tpu.ops.bridge import write_from_device
        os.makedirs(directory, exist_ok=True)
        self.flush()
        for name, arr in (("k_win.bin", self.k_win),
                          ("v_win.bin", self.v_win)):
            path = os.path.join(directory, name)
            # truncate first: the engine writer opens without O_TRUNC,
            # and a smaller re-save over a reused directory would
            # otherwise leave stale trailing bytes that break the load
            open(path, "wb").close()
            write_from_device(self.engine, arr, path)
        meta = {"count": self.count, "n_cold": self.n_cold,
                "batch": self.batch, "page_len": self.ocfg.page_len,
                "window_pages": self.ocfg.window_pages,
                "quantize": self.ocfg.quantize,
                "host_cache_pages": self.ocfg.host_cache_pages,
                "page_file": os.path.abspath(self.ocfg.path),
                # loud mismatch beats a silent same-itemsize bitcast
                "dtype": jnp.dtype(self.cfg.dtype).name,
                "window_shape": list(self.k_win.shape)}
        tmp = os.path.join(directory, "session.json.tmp")
        with open(tmp, "w") as f:
            json.dump(meta, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(directory, "session.json"))

    @classmethod
    def load_session(cls, cfg: TransformerConfig, engine: StromEngine,
                     directory, device=None) -> "PagedKVCache":
        """Rebuild a saved session: window streams back through the
        engine, the page file reattaches in place."""
        import json
        import os
        with open(os.path.join(directory, "session.json")) as f:
            meta = json.load(f)
        ocfg = OffloadConfig(
            path=meta["page_file"], page_len=meta["page_len"],
            window_pages=meta["window_pages"],
            quantize=meta["quantize"],
            host_cache_pages=meta.get("host_cache_pages", 0))
        if meta.get("dtype") != jnp.dtype(cfg.dtype).name:
            raise ValueError(
                f"session saved with dtype {meta.get('dtype')}, "
                f"cfg has {jnp.dtype(cfg.dtype).name} — a bitcast "
                f"would silently corrupt the cache")
        self = cls(cfg, ocfg, engine, meta["batch"], device=device)
        try:
            shape = self.k_win.shape
            if list(shape) != meta.get("window_shape"):
                raise ValueError(
                    f"session window shape {meta.get('window_shape')} "
                    f"does not match cfg's {list(shape)}")
            # free the constructor's zero windows before streaming the
            # saved ones — no transient double footprint
            self.k_win = self.v_win = None
            for attr, name in (("k_win", "k_win.bin"),
                               ("v_win", "v_win.bin")):
                arr = self._stream.read_to_device(
                    os.path.join(directory, name),
                    dtype=self.cfg.dtype, shape=shape)
                setattr(self, attr, arr)
            self.count = meta["count"]
            self.n_cold = meta["n_cold"]
        except BaseException:
            self.close()     # don't leak the page-file engine handle
            raise
        return self

    # -- read tier --------------------------------------------------------

    def _make_verify_cb(self, layer: int, span_meta, n_sub):
        """Staging-view CRC32C check for the page stream — hooks
        ``DeviceStream.stream_ranges``'s host-visible window (the only
        point on this path where payload bytes exist host-side).  A
        span split across several chunk ranges accumulates its CRC
        incrementally; the final chunk compares against the eviction-
        time stamp.  Sampling happened at STAMP time (the eviction
        path), so every span that carries a stamp is verified — an
        unstamped span (not sampled, or evicted before verification
        was enabled) is skipped.  A mismatch raises ChecksumError —
        corrupt KV history must never reach attention silently (there
        is no older intact copy to fall back to; the session aborts
        loudly)."""
        from nvme_strom_tpu.utils.checksum import ChecksumError, crc32c
        # range index → (span index, is_last_chunk_of_span)
        range_span = []
        for si, cnt in enumerate(n_sub):
            for j in range(cnt):
                range_span.append((si, j == cnt - 1))
        running: Dict[int, int] = {}
        stats = self.engine.stats

        def verify(ri: int, view) -> None:
            si, last = range_span[ri]
            page, sec = span_meta[si]
            expected = self._page_crc.get((page, sec, layer))
            if expected is None:
                return      # unstamped: not sampled at eviction
            running[si] = crc32c(view, running.get(si, 0))
            stats.add(bytes_verified=int(view.nbytes))
            if not last:
                return
            got = running.pop(si)
            if got != expected:
                stats.add(checksum_failures=1)
                raise ChecksumError(
                    f"KV page {page} section {sec} layer {layer} of "
                    f"{self.ocfg.path} fails its eviction-time CRC32C "
                    f"({got:#010x} != {expected:#010x}) — corrupt "
                    f"history must not reach attention")

        return verify

    def _iter_layer_pages(self, layer: int):
        """Stream (k_page, v_page) device pairs for one layer's cold
        history, pipelined at queue depth across all pages.  Spans
        larger than the engine's staging buffers split into chunk-sized
        sub-ranges (mirroring the write side); the on-device concat
        reassembles each page."""
        from nvme_strom_tpu.ops.bridge import host_to_device, split_ranges
        self._drain_writes()   # a just-evicted page must not read stale
        P = self.ocfg.page_len
        L, b, nkv, _, hd = self.k_win.shape
        sec_lens = tuple(ln for ln in (self._pb_layer, self._sb_layer,
                                       self._pb_layer, self._sb_layer)
                         if ln)
        spans = []          # per UNCACHED page: k data[, sc], v data[, sc]
        span_meta = []      # parallel: (page, write-section index)
        for page in range(self.n_cold):
            if page in self._host_cache:
                continue     # served from the RAM tier, no NVMe read
            kd, ks, vd, vs = self._section_offsets(page)
            for sec_idx, (base, ln) in enumerate(
                    ((kd, self._pb_layer), (ks, self._sb_layer),
                     (vd, self._pb_layer), (vs, self._sb_layer))):
                if ln:
                    spans.append((base + layer * ln, ln))
                    # write-side stamps key by the FILTERED order the
                    # eviction path enumerated (k,v unquantized;
                    # k,ks,v,vs quantized) — recover it here
                    span_meta.append(
                        (page, sec_idx if self._quant else sec_idx // 2))
        ranges, n_sub = split_ranges(spans,
                                     self.engine.config.chunk_bytes)
        verify_cb = (self._make_verify_cb(layer, span_meta, n_sub)
                     if self._verify.enabled else None)
        it = self._stream.stream_ranges(self._fh, ranges,
                                        verify=verify_cb)
        counts = iter(n_sub)

        def stream_flat():
            parts = [next(it) for _ in range(next(counts))]
            return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

        def read_kv(take):
            if self._quant:
                # (data, scale) stay separate: attend feeds them to the
                # quantized partial, which dequantizes inside its jit
                data = take().view(jnp.int8).reshape(b, nkv, P, hd)
                scale = take().view(jnp.float32).reshape(b, nkv, P, 1)
                return data, scale
            return take().view(self.cfg.dtype).reshape(b, nkv, P, hd)

        for page in range(self.n_cold):
            hosts = self._host_cache.get(page)
            if hosts is not None:
                self.host_cache_hits += 1
                flats = iter([
                    host_to_device(
                        self.engine,
                        sec[layer * ln:(layer + 1) * ln], self.device,
                        alias_safe=True)   # immutable long-lived buffer
                    for sec, ln in zip(hosts, sec_lens)])
                take = lambda: next(flats)     # noqa: E731
            else:
                self.host_cache_misses += 1
                take = stream_flat
            yield read_kv(take), read_kv(take)

    def _history_partials(self, layer: int, qf, valid: int):
        """(m, l, acc) of grouped queries over cold pages + ``valid``
        window slots — the shared-history half of any attention here."""
        m, l, acc = _window_partial(
            qf, self.k_win[layer], self.v_win[layer],
            jnp.asarray(valid, jnp.int32))
        for k_item, v_item in self._iter_layer_pages(layer):
            if self._quant:
                pm, pl, pacc = _page_partial_q(qf, *k_item, *v_item)
            else:
                pm, pl, pacc = _page_partial(qf, k_item, v_item)
            m, l, acc = _combine(m, l, acc, pm, pl, pacc)
        return m, l, acc

    def attend(self, layer: int, q,
               valid: Optional[int] = None) -> jax.Array:
        """Full-history attention for one layer's query block.

        q (b, n_heads, s, hd) — every query row attends to the entire
        cached history (cold pages + ``valid`` window slots, default
        ``count``), so use this only when all ``s`` queries share that
        same visible history (s == 1 decode; pass ``valid=count+1``
        after append_layer so a step's own position is visible to its
        own query).  Returns (b, n_heads, s, hd).
        """
        b, nh, s_q, hd = q.shape
        qf = _grouped(q, self.cfg.n_kv_heads)
        m, l, acc = self._history_partials(
            layer, qf, self.count if valid is None else valid)
        out = _finish(m, l, acc)
        return out.reshape(b, nh, s_q, hd).astype(self.cfg.dtype)

    def attend_chunk(self, layer: int, q, k_chunk, v_chunk) -> jax.Array:
        """Chunked-prefill attention: every query row sees the full
        cached history (shared) PLUS its own chunk causally.

        q (b, n_heads, s, hd); k_chunk/v_chunk (b, nkv, s, hd) are the
        chunk's OWN projections, not yet appended to the window.
        Returns (b, n_heads, s, hd)."""
        b, nh, s_q, hd = q.shape
        qf = _grouped(q, self.cfg.n_kv_heads)
        m, l, acc = self._history_partials(layer, qf, self.count)
        cm, cl, cacc = _chunk_causal_partial(
            qf, k_chunk.astype(self.cfg.dtype),
            v_chunk.astype(self.cfg.dtype), s_q)
        m, l, acc = _combine(m, l, acc, cm, cl, cacc)
        out = _finish(m, l, acc)
        return out.reshape(b, nh, s_q, hd).astype(self.cfg.dtype)


# ---------------------------------------------------------------------------
# generation on top of the paged cache


def _layer_forward(params: Dict, i: int, x, cfg: TransformerConfig,
                   positions, attend):
    """One transformer layer against the paged cache — the ONE copy of
    the layer wiring (norms, qkv, wo residual, mlp residual) both the
    decode step and chunked prefill run.  ``attend(i, q, k, v)`` owns
    the append/attend ordering and returns (b, nh, s, hd)."""
    b, s, _ = x.shape
    Lk = f"layers.{i}."
    h = rms_norm(x, params[Lk + "attn_norm"], cfg.norm_eps)
    q, k, v = qkv_project(h, params, Lk, cfg, positions=positions)
    a = attend(i, q, k, v)
    a = a.transpose(0, 2, 1, 3).reshape(b, s, -1)
    x = x + a @ wmat(params, Lk + "wo", a.dtype)
    h = rms_norm(x, params[Lk + "mlp_norm"], cfg.norm_eps)
    return (x + _mlp_block(h, params, Lk, cfg)).astype(cfg.dtype)


def _final_logits(params: Dict, x_last, cfg: TransformerConfig):
    x_last = rms_norm(x_last, params["final_norm"], cfg.norm_eps)
    return (x_last @ wmat(params, "lm_head", x_last.dtype)
            ).astype(jnp.float32)


def offload_decode_step(params: Dict, token, cfg: TransformerConfig,
                        cache: PagedKVCache):
    """One decode step against the paged cache (mirrors
    models/decode.decode_step, with append_layer+attend replacing the
    dense cache update).  The per-layer host loop is the tier boundary:
    NVMe streaming happens between jitted segments.  token (b,) int32 →
    next-token logits (b, vocab) f32."""
    pos = cache.pos
    x = params["tok_embed"].astype(cfg.dtype)[token[:, None]]
    positions = jnp.asarray([pos], jnp.float32)

    def attend(i, q, k, v):
        # layer i's kv lands in the window BEFORE its attention so the
        # new position is visible to its own query (valid=count+1);
        # count itself advances once per step in commit_step
        cache.append_layer(i, k, v)
        return cache.attend(i, q, valid=cache.count + 1)

    for i in range(cfg.n_layers):
        x = _layer_forward(params, i, x, cfg, positions, attend)
    cache.commit_step()
    return _final_logits(params, x[:, 0], cfg)


def offloaded_prefill(params: Dict, tokens, cfg: TransformerConfig,
                      cache: PagedKVCache):
    """Prefill an arbitrary-length prompt with BOUNDED HBM.

    The prompt processes in ``page_len``-sized chunks: each chunk's
    queries attend to the full cached history (cold pages + window,
    shared) plus the chunk itself causally, then the chunk's KV joins
    the window (evicting as needed).  Activation memory is
    O(batch × page_len × d) regardless of prompt length — the missing
    half of "decode beyond HBM".  Requires ``window_pages >= 2`` (a
    chunk and at least one page of history must coexist).
    Returns last-position logits (b, vocab) f32.
    """
    if cache.ocfg.window_pages < 2:
        raise ValueError("chunked prefill needs window_pages >= 2")
    b, total = tokens.shape
    P = cache.ocfg.page_len

    def attend(i, q, k, v):
        # the chunk attends to history (shared) + itself (causal)
        # BEFORE its kv joins the window
        a = cache.attend_chunk(i, q, k, v)
        cache.append_layer(i, k, v)
        return a

    x_last = None
    for c0 in range(0, total, P):
        chunk = tokens[:, c0:c0 + P]
        s = chunk.shape[1]
        cache.ensure_room(s)
        pos0 = cache.pos
        x = params["tok_embed"].astype(cfg.dtype)[chunk]
        positions = jnp.arange(pos0, pos0 + s, dtype=jnp.float32)
        for i in range(cfg.n_layers):
            x = _layer_forward(params, i, x, cfg, positions, attend)
        cache.commit_block(s)
        x_last = x[:, -1]
    return _final_logits(params, x_last, cfg)


# ---------------------------------------------------------------------------
# serving prefix store: content-addressed cross-request KV pages on NVMe
# ---------------------------------------------------------------------------
#
# PagedKVCache above is a PER-SESSION offload: one decode session's own
# history spills to its own page file.  Production serving is
# CROSS-request: thousands of sessions share system prompts and few-shot
# prefixes whose aggregate KV far exceeds HBM+DRAM (ROADMAP open item 2;
# Tutti, PAPERS.md).  PrefixStore is that tier — prompt KV pages keyed
# by a rolling hash of their TOKEN CHAIN (per model identity), written
# once however many sessions compute them, restored through the
# decode-class batched read path (io/plan.py + io/sched.py) and pinned
# hot in the host-DRAM tier (io/hostcache.py) so a popular prefix costs
# one prefill fleet-wide and one NVMe read per cold restore.
# models/serving.py's DecodeServer drives it at
# admission; docs/PERF.md §5 documents knobs, counters, and policy.


class SloGovernor:
    """Decode-path p99 SLO: turn a restore-latency target into policy.

    ``STROM_KV_P99_MS`` names the restore p99 the serving path promises
    (the existing log2-histogram machinery measures it).  On violation
    the governor raises the ``decode`` class's concurrent-hedge budget
    (io/resilient.py, the PR-7 per-class tokens) and its fair-share
    weight (io/sched.py) one notch — stragglers get hedged away and the
    scheduler leans harder toward decode; once the p99 recovers below
    half the target the boost decays back a notch toward the baseline.
    Bounded (``_MAX_BOOST`` doublings) and rate-limited, so a noisy
    histogram can never ratchet the budgets to infinity or flap them
    per-request.  With no target (0, the default), or an engine without
    the matching lever, it is inert."""

    _MAX_BOOST = 3
    _MIN_INTERVAL_S = 0.5

    def __init__(self, target_ms: float, klass: str = "decode"):
        self.target_ms = float(target_ms)
        self.klass = klass
        self.boost = 0
        self._base_budget: Optional[int] = None
        self._base_weight: Optional[float] = None
        self._last = 0.0
        # per-tenant rate-limit clocks (observe_tenant)
        self._tenant_last: Dict[str, float] = {}

    def observe(self, engine, p99_ms: Optional[float], stats=None) -> None:
        """Feed one restore-p99 sample; applies/decays the boost."""
        import time
        if self.target_ms <= 0 or not p99_ms:
            return
        now = time.monotonic()
        if now - self._last < self._MIN_INTERVAL_S:
            return
        step = 0
        if p99_ms > self.target_ms and self.boost < self._MAX_BOOST:
            step = 1
        elif p99_ms < 0.5 * self.target_ms and self.boost > 0:
            step = -1
        if step == 0:
            return
        sup = getattr(engine, "supervisor", None)
        if step > 0 and sup is not None and sup.unhealthy():
            # failure-domain gate (docs/RESILIENCE.md): a p99 violation
            # caused by a tripped ring / degraded device is not a
            # scheduling problem — boosting the hedge budget would
            # DOUBLE the I/O pressed into the sick domain exactly when
            # the breaker is trying to drain it.  Decay still runs.
            return
        self._last = now
        self.boost += step
        set_budget = getattr(engine, "set_hedge_budget", None)
        if set_budget is not None:
            if self._base_budget is None:
                self._base_budget = int(getattr(engine, "hedge_budgets",
                                                {}).get(self.klass, 8))
            set_budget(self.klass,
                       self._base_budget * (2 ** self.boost))
        sched = getattr(engine, "scheduler", None)
        if sched is not None:
            try:
                if self._base_weight is None:
                    self._base_weight = sched.policies[self.klass].weight
                sched.set_weight(self.klass,
                                 self._base_weight * (1 + self.boost))
            except (KeyError, AttributeError):
                pass
        if step > 0 and stats is not None:
            stats.add(kv_slo_boosts=1)
        if step > 0:
            # SLO violation: capture the op ring NOW — the post-mortem
            # wants the reads that blew the p99, not the recovered
            # steady state an hour later (io/flightrec.py)
            flight = getattr(engine, "flight", None)
            if flight is not None:
                flight.dump("slo_violation",
                            extra={"p99_ms": p99_ms,
                                   "target_ms": self.target_ms,
                                   "boost": self.boost})

    def observe_tenant(self, engine, tenant, p99_ms, stats=None) -> None:
        """Per-tenant SLO lane (multi-tenant isolation): feed one
        tenant's decode-latency p99 against ITS declared target
        (``Tenant.slo_p99_ms``).  A violation boosts only that tenant's
        fair-share weight (``share_boost`` notches, read live by the
        scheduler's hierarchical pick) — NEVER the device-global hedge
        budget: hedges double real I/O on a device every tenant
        shares, so one tenant's bad p99 must not buy it the right to
        press more load into everyone's SSD.  Same bound, decay, and
        rate limit as the device-level lane; same supervisor gate."""
        import time
        if tenant is None or tenant.slo_p99_ms <= 0 or not p99_ms:
            return
        now = time.monotonic()
        if now - self._tenant_last.get(tenant.id, 0.0) \
                < self._MIN_INTERVAL_S:
            return
        step = 0
        if (p99_ms > tenant.slo_p99_ms
                and tenant.share_boost < self._MAX_BOOST):
            step = 1
        elif p99_ms < 0.5 * tenant.slo_p99_ms and tenant.share_boost > 0:
            step = -1
        if step == 0:
            return
        sup = getattr(engine, "supervisor", None)
        if step > 0 and sup is not None and sup.unhealthy():
            # a sick device, not a scheduling problem (see observe)
            return
        self._tenant_last[tenant.id] = now
        tenant.share_boost += step
        if step > 0:
            if stats is not None:
                stats.add(tenant_slo_boosts=1)
                stats.add_tenant_stat(tenant.id, slo_boosts=1)
            flight = getattr(engine, "flight", None)
            if flight is not None:
                flight.dump("slo_violation",
                            extra={"tenant": tenant.id,
                                   "p99_ms": p99_ms,
                                   "target_ms": tenant.slo_p99_ms,
                                   "share_boost": tenant.share_boost})


class PrefixStore:
    """Content-addressed NVMe store of prompt KV pages, shared across
    decode sessions/servers (thread-safe; one instance per page file).

    A page holds ``page_tokens`` positions of a SINGLE sequence at
    kv-head width — layout ``[k block][v block]``, each
    ``(L, nkv, page_tokens, hd)`` of the model dtype — keyed by the
    rolling hash of the full token chain up to and including the page
    (seeded with the model identity, so two models or dtypes can never
    alias).  ``put`` writes a page once (a resident key counts
    ``kv_pages_deduped``/``kv_bytes_saved`` instead of re-writing);
    ``restore_many`` gathers EVERY requesting slot's due pages into ONE
    ``plan_and_submit`` batch under the ``decode`` QoS class with
    ``hot=True`` — cross-request locality for the extent-coalescing
    planner and the multi-ring scheduler, and sticky host-tier lines
    under the decode quota.  Every page carries a write-time CRC32C
    stamp (PR-5 machinery) persisted in a ``.kvman.json`` manifest
    sidecar, verified on restore behind ``STROM_VERIFY`` and offline by
    ``strom-scrub``.

    Eviction (capacity pressure) reclaims the lowest BENEFIT score —
    reuse frequency x the histogram-estimated per-page restore cost —
    so the hottest prefixes stay SSD-resident (docs/PERF.md §5); pages
    pinned by an in-flight restore are never reclaimed.  Restore
    failures (I/O or CRC) drop the damaged entry and heal through the
    server's normal prefill — the store accelerates, it never fails a
    request.
    """

    #: async page writes kept in flight before put() drains (mirrors
    #: PagedKVCache's bounded write pipeline)
    _MAX_PENDING = 4

    def __init__(self, cfg: TransformerConfig, engine: StromEngine,
                 path: str, page_tokens: int, capacity_bytes: int,
                 p99_target_ms: float = 0.0):
        import hashlib
        import threading
        cfg.require_kv_pages("PrefixStore (models/kv_offload.py)")
        if page_tokens < 1:
            raise ValueError(f"page_tokens must be >= 1, "
                             f"got {page_tokens}")
        self.cfg = cfg
        self.engine = engine
        self.path = str(path)
        self.page_tokens = page_tokens
        L, nkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        self._np_dtype = jnp.dtype(cfg.dtype)
        self._kv_shape = (L, nkv, page_tokens, hd)
        self.page_bytes = (2 * L * nkv * page_tokens * hd
                          * self._np_dtype.itemsize)
        if capacity_bytes < self.page_bytes:
            capacity_bytes = self.page_bytes   # a non-zero budget means
            #                                    the user wants the tier
        self.capacity_pages = max(1, capacity_bytes // self.page_bytes)
        #: chain-hash seed: the model identity — every field that
        #: changes the KV bytes a token chain produces
        self._seed = hashlib.sha1(repr((
            "kvprefix-v1", cfg.vocab, cfg.d_model, cfg.n_layers,
            cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.rope_theta,
            cfg.rope_scaling, cfg.norm_eps, self._np_dtype.name,
            cfg.n_experts, cfg.expert_top_k, cfg.moe_every,
            page_tokens)).encode()).digest()
        import os
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._fh = engine.open(self.path, writable=True)
        self.stats = getattr(engine, "stats", None)
        self._lock = make_lock("kv_offload.PrefixStore._lock")
        self._wlock = make_lock("kv_offload.PrefixStore._wlock")
        #: set by close() BEFORE its final flush: put()/restore_many()
        #: refuse new work once closing, so the bounded drain converges
        #: (no new appends) and the engine fh is never closed under a
        #: storm's in-flight I/O.  _io_inflight counts put() writes AND
        #: restore_many() reads past the gate; close() waits for it to
        #: hit zero before touching the fh, so an op that won the gate
        #: race can never submit against a closed (or None) handle.
        self._closed = False
        self._io_inflight = 0
        #: notified whenever _io_inflight hits zero (shares _lock);
        #: close() waits on it instead of busy-polling
        self._io_cv = make_condition("kv_offload.PrefixStore._io_cv",
                                     self._lock)
        #: thread id of the active drainer (_drain_mu holder): a put()
        #: re-entered from one of the drain's own waits must SKIP the
        #: backpressure acquire below, not self-deadlock on it
        self._drain_owner: Optional[int] = None
        # serializes DRAINERS only (flush semantics: on return, every
        # batch beyond `keep` is COMPLETE, even when popped by a
        # concurrent drainer); put()'s bounded maintenance drain only
        # TRY-acquires it — when a drain is already running the
        # submitter skips (the active drainer enforces the bound), so
        # put() never blocks behind another thread's I/O waits while
        # the backlog is within 2x the soft bound (past that it blocks
        # for backpressure: memory stays bounded under a wedged drain)
        self._drain_mu = make_lock("kv_offload.PrefixStore._drain_mu")
        #: key -> {"page": slot, "hits": n, "seq": lru-tick, "crc": int,
        #:         "pins": in-flight restores}
        self._entries: Dict[bytes, dict] = {}
        # reversed so pop() hands out slot 0 first: the page file grows
        # from the front instead of starting capacity-sized-sparse
        self._free = list(range(self.capacity_pages - 1, -1, -1))
        self._seq = 0
        self._pending_writes: list = []
        #: restore-latency log2 histogram in µs (the same bucketing as
        #: the engine's native histogram; utils/stats percentile walk)
        self._restore_hist = [0] * 40
        self._man_last = 0.0          # throttled manifest-save clock
        #: tenant id -> declared residency quota fraction, registered
        #: as puts run inside tenant scopes (multi-tenant isolation;
        #: empty — and eviction tenant-blind — until one does)
        self._tenant_quota_frac: Dict[str, float] = {}
        self.slo = SloGovernor(p99_target_ms)
        from nvme_strom_tpu.utils.checksum import VerifyPolicy
        self._verify = VerifyPolicy()
        self._load_manifest()

    # -- identity / lookup -------------------------------------------------

    def chain_keys(self, tokens) -> list:
        """One key per FULL page of the token chain, capped at
        ``(len-1)//page_tokens`` — at least one token always prefills
        live (the first-token logits need a real forward; the cap also
        matches the serving block cache's rule, so the two tiers index
        the same boundaries)."""
        import hashlib
        P = self.page_tokens
        n = max(0, (len(tokens) - 1) // P)
        keys, h = [], self._seed
        for i in range(n):
            chunk = np.asarray(tokens[i * P:(i + 1) * P],
                               np.int32).tobytes()
            h = hashlib.sha1(h + chunk).digest()
            keys.append(h)
        return keys

    def match(self, keys) -> int:
        """Length of the longest resident chain prefix (pages whose
        write is fully SUBMITTED — a restore drains pending writes
        before reading, so ready pages can never serve torn bytes)."""
        with self._lock:
            n = 0
            for kx in keys:
                e = self._entries.get(kx)
                if e is None or not e["ready"]:
                    break
                n += 1
            return n

    def pages_resident(self) -> int:
        with self._lock:
            return len(self._entries)

    # -- restore (the decode-class batched read path) ----------------------

    def restore_many(self, wants: Dict[object, tuple]) -> Dict[object, Dict[int, tuple]]:
        """Restore every requesting slot's due pages in ONE batch.

        ``wants``: slot -> (first_chain_index, [chain keys]).  Returns
        slot -> {chain_index: (k, v)} numpy ``(L, nkv, P, hd)`` pairs
        for the pages that restored cleanly (duplicate pages across
        slots — two sessions admitting the same prompt in one step —
        submit once: the planner dedupes the overlapping extents into
        one span and hands each slot a view).  A failed page drops its
        store entry (healed by recompute) and is simply absent from the
        result; the caller prefills it like any miss."""
        # same close() gate as put(): this path submits against
        # self._fh, and an empty result just means the caller
        # recomputes — refuse work, never fail it
        with self._lock:
            if self._closed:
                return {}
            self._io_inflight += 1
        try:
            return self._restore_many_gated(wants)
        finally:
            with self._io_cv:
                self._io_inflight -= 1
                if self._io_inflight == 0:
                    self._io_cv.notify_all()

    def _restore_many_gated(self, wants) -> Dict[object, Dict[int, tuple]]:
        import time as _time
        plan: list = []            # (slot, chain_index, key, entry)
        with self._lock:
            for slot, (start, keys) in wants.items():
                for j, kx in enumerate(keys):
                    e = self._entries.get(kx)
                    if e is None or not e["ready"]:
                        continue   # evicted since match(), or a put
                        #            still submitting; recompute
                    e["pins"] += 1
                    e["hits"] += 1
                    self._seq += 1
                    e["seq"] = self._seq
                    plan.append((slot, start + j, kx, e))
        if not plan:
            return {}
        from nvme_strom_tpu.io.plan import plan_and_submit
        out: Dict[object, Dict[int, tuple]] = {}
        failed: list = []
        tracer = getattr(self.engine, "tracer", None)
        if tracer is not None and not tracer.enabled:
            tracer = None
        t0_ns = _time.monotonic_ns()
        t0 = _time.monotonic()
        try:
            # a failed eviction WRITE surfacing here must degrade to
            # recompute, not fail the serving step (and must not leak
            # the pins just taken)
            self._drain_writes()
            extents = [(self._fh, e["page"] * self.page_bytes,
                        self.page_bytes) for (_s, _i, _k, e) in plan]
            planned = plan_and_submit(self.engine, extents,
                                      klass="decode", hot=True)
        except OSError:
            with self._lock:
                for (_s, _i, _k, e) in plan:
                    self._unpin_locked(e)
            if self.stats is not None:
                self.stats.add(kv_restore_failures=len(plan))
            return {}
        try:
            for (slot, idx, kx, e), pieces in zip(plan, planned):
                buf = np.empty(self.page_bytes, np.uint8)
                pos = 0
                bad = None
                for p in pieces:
                    try:
                        v = p.wait()
                    except OSError as err:
                        bad = err
                        break
                    buf[pos:pos + v.nbytes] = v.reshape(-1).view(np.uint8)
                    pos += v.nbytes
                if bad is None and pos != self.page_bytes:
                    bad = OSError(f"short page: {pos} of "
                                  f"{self.page_bytes} bytes")
                if bad is None and self._verify.enabled \
                        and self._verify.want():
                    from nvme_strom_tpu.utils.checksum import crc32c
                    got = crc32c(buf)
                    if self.stats is not None:
                        self.stats.add(bytes_verified=int(buf.nbytes))
                    if got != e["crc"]:
                        if self.stats is not None:
                            self.stats.add(checksum_failures=1)
                        bad = OSError(
                            f"KV prefix page {e['page']} fails its "
                            f"write-time CRC32C ({got:#010x} != "
                            f"{e['crc']:#010x})")
                if bad is not None:
                    failed.append((kx, e))
                    continue
                half = self.page_bytes // 2
                k = buf[:half].view(self._np_dtype).reshape(self._kv_shape)
                v = buf[half:].view(self._np_dtype).reshape(self._kv_shape)
                out.setdefault(slot, {})[idx] = (k, v)
        finally:
            for pieces in planned:
                for p in pieces:
                    p.release()
            with self._lock:
                for (_s, _i, _k, e) in plan:
                    self._unpin_locked(e)
        elapsed_us = max(1, int((_time.monotonic() - t0) * 1e6))
        n_ok = sum(len(v) for v in out.values())
        if tracer is not None:
            # the store's own restore span (NVMe read + page assembly +
            # verify), a child of the serving kv_restore scope
            tracer.add_span("strom.kv.restore", t0_ns,
                            _time.monotonic_ns(), category="strom.kv",
                            pages=len(plan), ok=n_ok,
                            failed=len(failed),
                            bytes=len(plan) * self.page_bytes)
        with self._lock:
            # hist[i] counts [2^i, 2^(i+1)) — the same convention as
            # percentiles_from_log2_hist and the engine's histogram.
            # Aged by halving past 512 samples (exponential forgetting)
            # so the SLO governor reacts to CURRENT latency, not a
            # lifetime average a cold start poisoned for hours.
            self._restore_hist[min(elapsed_us.bit_length() - 1,
                                   len(self._restore_hist) - 1)] += 1
            if sum(self._restore_hist) >= 512:
                self._restore_hist = [c // 2
                                      for c in self._restore_hist]
        if failed:
            # damaged/vanished pages heal through recompute: drop the
            # entries so the NEXT admission re-writes fresh bytes
            with self._lock:
                for kx, e in failed:
                    if self._entries.get(kx) is e and e["pins"] == 0:
                        del self._entries[kx]
                        self._free.append(e["page"])
            self._save_manifest()
        if self.stats is not None:
            self.stats.add(kv_pages_restored=n_ok, kv_prefix_hits=n_ok,
                           **({"kv_restore_failures": len(failed)}
                              if failed else {}))
            self.stats.set_gauges(
                kv_restore_p99_ms=self.restore_p99_ms() or 0.0,
                kv_store_pages_resident=self.pages_resident())
        self.slo.observe(self.engine, self.restore_p99_ms(), self.stats)
        return out

    def restore_p99_ms(self) -> Optional[float]:
        """p99 of the restore-batch latency from the log2 histogram
        (µs buckets; the percentile walk shared with the engine's own
        histogram rendering)."""
        from nvme_strom_tpu.utils.stats import percentiles_from_log2_hist
        with self._lock:
            hist = list(self._restore_hist)
        p = percentiles_from_log2_hist(hist, ps=(99,))[99]
        return p / 1000.0 if p else None

    def _restore_cost_ms(self) -> float:
        """Median restore cost estimate (the benefit-score factor).
        Called from ``_evict_locked`` with the store lock HELD — reads
        the histogram without re-acquiring (a snapshot of monotonic
        counters; the non-reentrant lock would deadlock)."""
        from nvme_strom_tpu.utils.stats import percentiles_from_log2_hist
        p = percentiles_from_log2_hist(list(self._restore_hist),
                                       ps=(50,))[50]
        return max(p / 1000.0, 1e-3)

    # -- write tier --------------------------------------------------------

    def _drain_writes(self, keep: int = 0) -> None:
        """Complete pending page writes (oldest first).  A FAILED write
        never raises: the store is a cache, so the affected page simply
        drops (the next admission recomputes and re-writes it) — the
        never-fail-a-request contract, write side."""
        bad: list = []
        # strom-lint lock-blocking fix (PR 13): the pre-PR shape waited
        # the whole backlog UNDER _wlock, stalling every concurrent
        # put() behind this thread's I/O.  Now _wlock covers only the
        # pop; the waits run outside it, serialized by _drain_mu.  A
        # MAINTENANCE drain (keep > 0, put()'s backlog bound) only
        # try-acquires: if another thread is already draining, it will
        # observe our append and enforce the bound itself, so the
        # submitter returns without ever blocking on foreign I/O.
        # flush (keep == 0) blocks — its contract is completion.
        me = threading.get_ident()
        if keep > 0:
            if not self._drain_mu.acquire(blocking=False):
                # a drainer is already active; skip — UNLESS the
                # backlog has outrun it past the hard cap, where the
                # submitter must block for backpressure (the pre-PR
                # memory bound: each pending batch pins a page of
                # write buffers, and a wedged drain must not let
                # every subsequent put() grow the backlog forever).
                # A put() RE-ENTERED from the active drain's own
                # wait() is that drainer — blocking here would
                # self-deadlock on our own non-reentrant mu
                if self._drain_owner == me:
                    return
                with self._wlock:
                    backlog = len(self._pending_writes)
                if backlog <= 2 * self._MAX_PENDING:
                    return
                self._drain_mu.acquire()
            self._drain_owner = me
            try:
                self._drain_loop(keep, bad)
            finally:
                self._drain_owner = None
                self._drain_mu.release()
        else:
            if self._drain_owner == me:
                # restore_many()/flush() re-entered from the active
                # drain's own wait(): the outer drainer IS doing the
                # work — blocking would self-deadlock on our own mu
                return
            with self._drain_mu:
                self._drain_owner = me
                try:
                    self._drain_loop(0, bad)
                finally:
                    self._drain_owner = None
        if bad:
            self._drop_pages_at(bad)

    def _drain_all_and_snapshot(self) -> Optional[set]:
        """flush()'s drain: returns the set of keys PROVEN drained, for
        the ``clean=True`` manifest stamp.  Each round snapshots the
        ready key set FIRST, then runs the snapshot drain; the stamp is
        the final round's pre-drain snapshot.  Why that is safe:
        (a) put() appends an entry's batch BEFORE flipping it ready, so
        a snapshotted entry's batch predates the drain that follows;
        (b) ``_drain_loop`` pops FIFO at least every batch pending at
        its entry, and waits them; (c) a batch popped by an EARLIER
        drainer is complete, because drainers finish their waits before
        releasing ``_drain_mu`` and we hold it.  An entry that flips
        ready after the snapshot (a put() racing the flush) is simply
        not stamped — a crash costs that cache entry, never serves torn
        bytes (snapshotting AFTER the drain instead would TOCTOU: the
        racing entry lands in the stamp with its writes in flight).
        Rounds are BOUNDED: sustained put() traffic appends faster than
        one round drains, and an unbounded chase would pin
        flush()/close() forever — the leftover tail batches stay
        pending (and unstamped) for the next drain."""
        bad: list = []
        stamped: set = set()
        if self._drain_owner == threading.get_ident():
            # flush() re-entered from our own drain's wait(): None =
            # "do not save a manifest at all" — the outer flush
            # finishes the job (an empty SET here would stamp an
            # empty clean manifest over every persisted page)
            return None
        with self._drain_mu:
            self._drain_owner = threading.get_ident()
            try:
                for _ in range(8):
                    with self._lock:
                        stamped = {kx for kx, e in self._entries.items()
                                   if e["ready"]}
                    self._drain_loop(0, bad)
                    with self._wlock:
                        if not self._pending_writes:
                            break
            finally:
                self._drain_owner = None
        if bad:
            # dropped entries leave _entries, and _save_manifest
            # re-filters against the live map — a failed write's page
            # can't be stamped through the stale snapshot
            self._drop_pages_at(bad)
        return stamped

    def _drain_loop(self, keep: int, bad: list) -> None:
        # caller holds _drain_mu (waived in the lock-order manifest:
        # these waits are the drain, and only drainers contend the mu).
        # Drain a SNAPSHOT of the backlog: _wlock is released during
        # each batch's waits, so batches appended meanwhile belong to
        # the NEXT drain — chasing the moving tail would let sustained
        # put() traffic pin flush()/restore_many() forever.
        with self._wlock:
            excess = len(self._pending_writes) - keep
        while excess > 0:
            excess -= 1
            with self._wlock:
                if len(self._pending_writes) <= keep:
                    break
                batch = self._pending_writes.pop(0)
            for p in batch:
                try:
                    p.wait()
                except OSError:
                    bad.append(getattr(p, "offset", None))

    def _drop_pages_at(self, offsets) -> None:
        """Drop entries whose backing page overlaps a failed write —
        ALWAYS removed from the map (no future match/restore can serve
        them); a pinned entry's slot is reclaimed by the in-flight
        restore's unpin instead of here, so it is never reused under
        an outstanding read."""
        slots = {off // self.page_bytes for off in offsets
                 if off is not None}
        dropped = 0
        with self._lock:
            for kx, e in list(self._entries.items()):
                if e["page"] in slots:
                    del self._entries[kx]
                    if e["pins"] == 0:
                        self._free.append(e["page"])
                    else:
                        e["dropped"] = True   # unpin frees the slot
                    dropped += 1
        if dropped and self.stats is not None:
            self.stats.add(kv_restore_failures=dropped)

    def _unpin_locked(self, e: dict) -> None:
        """Release one restore pin (lock held); hands a dropped
        entry's slot back on the LAST unpin."""
        e["pins"] -= 1
        if e["pins"] == 0 and e.pop("dropped", False):
            self._free.append(e["page"])

    def put(self, pages) -> int:
        """Persist computed pages: ``pages`` is a list of
        ``(chain_key, k, v)`` with k/v numpy/JAX ``(L, nkv, P, hd)`` of
        the model dtype.  A key already resident dedupes (counted) —
        identical system prompts across sessions are written exactly
        once.  Returns the number of pages actually written.  Writes
        are async (bounded pipeline) and ride the engine's resilient
        write mirror when it carries one; ``flush()`` drains.

        Ordering contract: the entry is registered not-ready first (so
        a racing put of the same key dedupes instead of double-writing)
        and flips ready only AFTER its writes are submitted — a restore
        that sees a ready page and then drains pending writes can never
        read bytes the device hasn't been handed."""
        from nvme_strom_tpu.utils.checksum import crc32c
        with self._lock:
            if self._closed:
                # closing/closed: a cache may refuse work, never fail
                # it — the caller's recompute path serves
                return 0
            self._io_inflight += 1
        try:
            return self._put_gated(pages, crc32c)
        finally:
            with self._io_cv:
                self._io_inflight -= 1
                if self._io_inflight == 0:
                    self._io_cv.notify_all()

    def _put_gated(self, pages, crc32c) -> int:
        # body of put(); the caller holds an _io_inflight reference,
        # so close() cannot close the engine fh under these submits
        written = 0
        deduped = 0
        for kx, k, v in pages:
            # membership FIRST: the common dedupe case (two slots of
            # one batch, or two servers, computing the same prompt)
            # must not pay the page copy + CRC it is about to discard
            with self._lock:
                if self._closed:
                    break
                if kx in self._entries:
                    deduped += 1
                    continue
                if self._free:
                    slot = self._free.pop()
                else:
                    slot = self._evict_locked()
                    if slot is None:
                        continue   # everything pinned: skip, not fail
                self._seq += 1
                from nvme_strom_tpu.io.tenants import current_tenant
                t = current_tenant()
                if t is not None:
                    self._tenant_quota_frac[t.id] = t.quota_frac
                # pages are charged to the tenant whose admission
                # computed them (pins included — an in-flight restore
                # still counts against its owner)
                self._entries[kx] = {"page": slot, "hits": 0,
                                     "seq": self._seq, "crc": None,
                                     "pins": 0, "ready": False,
                                     "tenant": (t.id if t is not None
                                                else None)}
            host = np.empty(self.page_bytes, np.uint8)
            half = self.page_bytes // 2
            host[:half] = np.ascontiguousarray(
                np.asarray(k)).view(np.uint8).reshape(-1)
            host[half:] = np.ascontiguousarray(
                np.asarray(v)).view(np.uint8).reshape(-1)
            crc = crc32c(host)
            off = slot * self.page_bytes
            chunk = self.engine.config.chunk_bytes
            pend: list = []
            try:
                self._drain_writes(keep=self._MAX_PENDING - 1)
                for p0 in range(0, self.page_bytes, chunk):
                    pend.append(self.engine.submit_write(
                        self._fh, off + p0, host[p0:p0 + chunk]))
            except OSError:
                # a submit failure mid-page must not leak the slot (a
                # never-ready entry is invisible to match AND eviction)
                # nor strand in-flight chunks' buffers: settle them,
                # then reclaim
                for p in pend:
                    try:
                        p.wait()
                    except OSError:
                        pass
                with self._lock:
                    e = self._entries.get(kx)
                    if (e is not None and e["page"] == slot
                            and not e["ready"]):
                        del self._entries[kx]
                        self._free.append(slot)
                break
            with self._wlock:
                self._pending_writes.append(pend)
            with self._lock:
                e = self._entries.get(kx)
                if e is not None and e["page"] == slot:
                    e["crc"] = crc
                    e["ready"] = True
            written += 1
        if self.stats is not None and (written or deduped):
            self.stats.add(kv_pages_written=written,
                           kv_pages_deduped=deduped,
                           kv_bytes_saved=deduped * self.page_bytes)
            self.stats.set_gauges(
                kv_store_pages_resident=self.pages_resident())
        if written:
            self._save_manifest(throttle=True)
        return written

    def _evict_locked(self) -> Optional[int]:
        """Reclaim the lowest-benefit unpinned page (lock held): score =
        reuse frequency x estimated restore cost (docs/PERF.md §5) with
        LRU tiebreak — equal-size pages make the cost a common factor,
        but the formula stays literal so variable-size layouts inherit
        the right policy."""
        cost = self._restore_cost_ms()
        # tenant-quota pre-pass (multi-tenant isolation): when any
        # tenant holds more pages than its quota fraction allows, the
        # victim scan restricts to THOSE tenants' pages first — one
        # tenant's prompt storm reclaims its own borrowing before it
        # can touch another tenant's hot prefixes.  Pinned pages count
        # against their owner but are never reclaimed.
        over = self._tenant_over_locked() if self._tenant_quota_frac \
            else None
        for restrict in ((over, None) if over else (None,)):
            victim_key = None
            victim_score = None
            for kx, e in self._entries.items():
                if e["pins"] > 0 or not e["ready"]:
                    continue   # in-flight restore or a put still writing
                if restrict is not None \
                        and e.get("tenant") not in restrict:
                    continue
                score = (e["hits"] * cost, e["seq"])
                if victim_score is None or score < victim_score:
                    victim_score = score
                    victim_key = kx
            if victim_key is None:
                continue
            e = self._entries.pop(victim_key)
            if self.stats is not None:
                self.stats.add(kv_store_evictions=1)
                if restrict is not None:
                    self.stats.add(tenant_quota_evictions=1)
                    self.stats.add_tenant_stat(e.get("tenant"),
                                               quota_evictions=1)
            return e["page"]
        return None

    def _tenant_over_locked(self) -> set:
        """Tenant ids holding more resident pages than their quota
        fraction of the store allows (lock held; fraction 0 = fair
        share, 1/N of the tenants resident)."""
        counts: Dict[str, int] = {}
        for e in self._entries.values():
            tid = e.get("tenant")
            if tid is not None:
                counts[tid] = counts.get(tid, 0) + 1
        over = set()
        for tid, n in counts.items():
            frac = self._tenant_quota_frac.get(tid, 0.0)
            if frac <= 0.0:
                frac = 1.0 / max(1, len(counts))
            if n > frac * self.capacity_pages:
                over.add(tid)
        return over

    # -- cold-start warmup (docs/RESILIENCE.md "Elastic cold-start") -------

    def warm_pages(self, budget_pages: int = 256) -> int:
        """Re-read the top-benefit resident pages at ``prefetch`` class
        with ``hot=True`` — the cold-start warming thunk.  A replica
        that just reattached a manifest has every page on NVMe but
        nothing in the pinned-DRAM tier; replaying the highest
        ``hits``-weighted pages fills (and hot-pins) their cache lines
        behind live traffic, so the first real restore of a popular
        prefix is a DRAM hit instead of an NVMe read.  Best-effort:
        failures warm less, never error; returns pages warmed."""
        if budget_pages <= 0:
            return 0
        with self._lock:
            if self._closed:
                return 0
            ranked = sorted(
                ((e["hits"], e["seq"], kx, e)
                 for kx, e in self._entries.items() if e["ready"]),
                reverse=True)[:budget_pages]
            for _h, _s, _k, e in ranked:
                e["pins"] += 1
            self._io_inflight += 1
        warmed = 0
        try:
            from nvme_strom_tpu.io.plan import plan_and_submit
            self._drain_writes()
            extents = [(self._fh, e["page"] * self.page_bytes,
                        self.page_bytes) for _h, _s, _k, e in ranked]
            if extents:
                planned = plan_and_submit(self.engine, extents,
                                          klass="prefetch", hot=True)
                for pieces in planned:
                    ok = bool(pieces)
                    for p in pieces:
                        try:
                            p.wait()
                        except OSError:
                            ok = False
                        finally:
                            p.release()
                    if ok:
                        warmed += 1
        except OSError:
            pass
        finally:
            with self._lock:
                for _h, _s, _k, e in ranked:
                    self._unpin_locked(e)
            with self._io_cv:
                self._io_inflight -= 1
                if self._io_inflight == 0:
                    self._io_cv.notify_all()
        if warmed and self.stats is not None:
            self.stats.add(coldstart_warm_pages=warmed)
        return warmed

    # -- durable manifest (the scrub contract) -----------------------------

    @property
    def manifest_path(self) -> str:
        return self.path + ".kvman.json"

    def _save_manifest(self, throttle: bool = False,
                       clean: bool = False, keys=None) -> None:
        """Atomically persist {page slot -> (key hex, crc)} so
        ``strom-scrub`` can verify the store offline with no model or
        server around (the PR-5 at-rest integrity contract).

        ``throttle`` (the per-put call) rewrites at most once per
        second: the dump is O(resident pages) and must not ride every
        admission of a large store.  ``clean`` is set ONLY by
        ``flush()``/``close()`` — after the write pipeline drained —
        and is what :meth:`_load_manifest` requires to reattach: a
        mid-run manifest may stamp pages whose async writes never
        completed (or whose slot was re-used inside the throttle
        window), so a crash must cost cache entries, never serve torn
        bytes to a restarted server.  ``keys`` (set by ``flush()``)
        restricts the stamp to entries whose writes were PROVEN
        drained (:meth:`_drain_all_and_snapshot`): a put() racing the
        flush can flip an entry ready after the drain, and a clean
        manifest must not cover it."""
        import json
        import os
        import time as _time
        if throttle:
            now = _time.monotonic()
            if now - self._man_last < 1.0:
                return
            self._man_last = now
        with self._lock:
            pages = {str(e["page"]): {"key": kx.hex(), "crc": e["crc"]}
                     for kx, e in self._entries.items()
                     if e["ready"] and (keys is None or kx in keys)}
        man = {"version": 1, "page_bytes": self.page_bytes,
               "page_tokens": self.page_tokens, "clean": clean,
               "pages": pages}
        tmp = self.manifest_path + f".tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump(man, f, sort_keys=True)
            os.replace(tmp, self.manifest_path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass

    def _load_manifest(self) -> None:
        """Reattach a previous process's store: resident pages (and
        their stamps) survive a server restart — the cross-SESSION half
        of cross-request reuse.  Chain keys are content hashes, so a
        manifest from another model/page size simply never matches;
        only a CLEAN manifest (written after the write pipeline
        drained) reattaches, and ANY malformed content starts the
        store cold instead of failing construction — a cache's
        manifest must never be able to crash a serving deployment."""
        import json
        try:
            with open(self.manifest_path) as f:
                man = json.load(f)
            if (man.get("version") != 1
                    or man.get("page_bytes") != self.page_bytes
                    or man.get("page_tokens") != self.page_tokens
                    or not man.get("clean")):
                return
            with self._lock:
                for slot_s, row in man.get("pages", {}).items():
                    slot = int(slot_s)
                    if slot >= self.capacity_pages:
                        continue
                    self._entries[bytes.fromhex(row["key"])] = {
                        "page": slot, "hits": 0, "seq": 0,
                        "crc": int(row["crc"]), "pins": 0,
                        "ready": True}
                    if slot in self._free:
                        self._free.remove(slot)
        except (OSError, ValueError, TypeError, KeyError,
                AttributeError):
            with self._lock:
                self._entries.clear()
                self._free = list(range(self.capacity_pages - 1, -1,
                                        -1))

    # -- lifecycle ---------------------------------------------------------

    def flush(self) -> None:
        stamped = self._drain_all_and_snapshot()
        if stamped is None:
            # re-entered from our own drain's wait(): the OUTER flush
            # saves — stamping now would atomically install an EMPTY
            # clean manifest, wiping every persisted page on a crash
            return
        self._save_manifest(clean=True, keys=stamped)

    def flush_for_handoff(self) -> list:
        """The drain-time flush the handoff path MUST use: exactly
        :meth:`flush`'s proven-drained stamping — drain all in-flight
        writes, stamp the clean manifest from the drained snapshot —
        but returning the stamped key set (hex) so the bundle can be
        audited to never reference a page whose write was not proven
        complete.  A re-entrant call returns ``[]`` (the outer flush
        owns the stamping; shipping keys it hasn't proven would defeat
        the audit)."""
        stamped = self._drain_all_and_snapshot()
        if stamped is None:
            return []
        self._save_manifest(clean=True, keys=stamped)
        return sorted(k.hex() for k in stamped)

    def ready_keys(self) -> list:
        """Hex keys of pages currently proven complete (ready, crc
        stamped) — the audit surface tests pin handoff bundles
        against."""
        with self._lock:
            return sorted(k.hex() for k, e in self._entries.items()
                          if e.get("ready"))

    def close(self) -> None:
        if self._fh is not None:
            # gate BEFORE the flush: put() refuses new work once
            # closing, so the bounded drain converges.  Then WAIT for
            # puts already past the gate — their submits target
            # self._fh, and closing (or None-ing) it under them would
            # surface a ctypes/OS error into the serving path a cache
            # must never fail.  put() holds no lock across its I/O,
            # so the in-flight count drains promptly.
            with self._lock:
                self._closed = True
            with self._io_cv:
                while self._io_inflight:
                    self._io_cv.wait(timeout=1.0)
            try:
                self.flush()
            finally:
                self.engine.close(self._fh)
                self._fh = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def build_prefix_store(cfg: TransformerConfig, engine: StromEngine,
                       path: str, page_tokens: int,
                       kvcfg=None) -> Optional[PrefixStore]:
    """The env-gated factory serving deployments use: None when
    ``STROM_KV_PREFIX`` is unset/0 OR the budget is 0 — the servers
    then run today's per-session path bit-for-bit
    (tests/test_kvserve.py proves it).  A zero budget must disable
    rather than clamp: a one-page store would thrash every multi-page
    prefix while paying full write/manifest/restore overhead."""
    from nvme_strom_tpu.utils.config import KVServeConfig
    kvcfg = kvcfg or KVServeConfig()
    if not kvcfg.prefix_enabled or kvcfg.store_mb <= 0:
        return None
    return PrefixStore(cfg, engine, path,
                       page_tokens=kvcfg.page_tokens or page_tokens,
                       capacity_bytes=kvcfg.store_mb << 20,
                       p99_target_ms=kvcfg.p99_target_ms)


def offloaded_generate(params: Dict, prompt, cfg: TransformerConfig,
                       ocfg: OffloadConfig, engine: StromEngine,
                       max_new_tokens: int,
                       eos_id: Optional[int] = None,
                       pad_id: int = 0,
                       chunked_prefill: bool = False):
    """Greedy generation with the SSD-backed cache.

    prompt (b, s) int32 → (b, max_new_tokens) int32.  By default the
    prompt prefills through the standard dense path (it must fit in
    HBM once) and its KV blocks seed the paged cache;
    ``chunked_prefill=True`` instead runs :func:`offloaded_prefill`,
    bounding HBM for the prompt too — decode proceeds with a bounded
    window no matter how long the sequence.
    """
    from nvme_strom_tpu.models import decode as _dec
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, "
                         f"got {max_new_tokens}")
    b, s = prompt.shape
    with PagedKVCache(cfg, ocfg, engine, b) as cache:
        if chunked_prefill:
            logits = offloaded_prefill(params, prompt, cfg, cache)
        else:
            dense = _dec.init_cache(cfg, b, s)
            logits, dense = _dec.prefill(params, prompt, cfg, dense)
            cache.append(dense["k"], dense["v"])
            del dense
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        done = (jnp.zeros((b,), bool) if eos_id is None else tok == eos_id)
        out = [tok]
        for _ in range(max_new_tokens - 1):
            logits = offload_decode_step(params, tok, cfg, cache)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if eos_id is not None:
                nxt = jnp.where(done, pad_id, nxt)
                done = done | (nxt == eos_id)
            out.append(nxt)
            tok = nxt
        return jnp.stack(out, axis=1)

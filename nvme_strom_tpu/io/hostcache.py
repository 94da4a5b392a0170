"""Tiered pinned-host DRAM cache between NVMe and HBM.

Repeat traffic — hot weight shards re-streamed per serving replica, hot
KV prefixes, hot SQL partitions — used to re-pay full SSD latency on
every read even though the engine already probes page-cache residency.
This module promotes that probe into a MANAGED tier (the LMB
CXL-linked-buffer pattern, PAPERS.md): an mlock'd host-DRAM arena of
fixed-size cache lines serving repeat reads at link speed instead of
SSD speed, shared by every consumer as ONE memory budget
(``STROM_HOSTCACHE_MB``; 0 — the default — disables the tier and the
submit path is bit-for-bit the pre-cache code).

  lines      fixed-size, keyed by ``(file_key, aligned_offset)`` where
             ``file_key`` is the file's (dev, inode, mtime_ns, size)
             identity captured at ``StromEngine.open`` — a file
             modified between opens gets a NEW key, so stale lines can
             never serve (they age out of the budget instead).  The
             line size is the ``chunk_bytes`` of the first engine
             that touches the tier, rounded down to a power of two,
             unless pinned by ``STROM_HOSTCACHE_LINE_BYTES``.  A line
             may hold a VALID PREFIX shorter than the line (EOF tails,
             partial fills) — hits are served only inside the valid
             prefix.
  admission  frequency-based, via a ghost list (second-chance sketch):
             a line key is admitted only when it was ALREADY missed
             recently — one-shot streaming scans never pollute the
             tier, while the second touch of a hot span promotes it.
             Fill happens on the miss read's completion (``wait``),
             copying the staging view into the line via the native
             ``strom_hostcache_copy`` helper so the staging buffer
             recycles immediately.
  quotas     class-aware: each QoS class (io/sched.py) owns a
             weight-derived share of the budget
             (``STROM_HOSTCACHE_CLASS_QUOTAS``, defaulting to the
             scheduler's stock class weights).  Borrowing free space is
             allowed (work-conserving); under pressure, eviction
             reclaims from OVER-QUOTA classes first with the same
             deficit-round-robin machinery as ``io/sched.py`` —
             inverse-weight credits, one round of banking, lowest
             priority served first — then a second-chance clock inside
             the chosen class.  Pinned lines (outstanding views) are
             never reclaimed.
  integrity  every fill stamps the line's CRC32C (PR 5 machinery,
             ``utils/checksum.py``); hits verify behind the same
             ``STROM_VERIFY`` gate, and a mismatched line drops itself
             and heals through the normal miss path — host-DRAM
             corruption of a resident line can never serve silently.

Integration lives at the ``io/plan.py`` boundary (``plan_and_submit``
splits extents into hit spans served here and miss spans submitted
through the QoS scheduler as today; ``submit_spans_tiered`` does the
whole-span equivalent for ``DeviceStream.stream_ranges``), so all five
read consumers get the tier transparently.  Hit spans NEVER enter
``FaultyEngine``/``ResilientEngine`` — a DRAM read needs no retry or
hedge budget.  Every decision is counted (``StromStats.cache_*``,
``bytes_served_cache``, per-class hit rates in ``class_stats``) and
rendered by ``strom_stat``'s "host cache" block and watchdog dumps.
"""

from __future__ import annotations

import ctypes
import os
import threading
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Tuple

import numpy as np

from nvme_strom_tpu.utils.lockwitness import make_lock, make_rlock
from nvme_strom_tpu.io.tenants import current_tenant
from nvme_strom_tpu.io.sched import CLASS_ORDER, DEFAULT_CLASS, \
    default_policies
from nvme_strom_tpu.utils.config import HostCacheConfig

#: line-key type: ((dev, ino, mtime_ns, size), line_offset)
LineKey = Tuple[tuple, int]


_hc_lib = None        # bound private CDLL handle (None until first bind)
_hc_lib_lock = make_lock("hostcache._hc_lib_lock")


def _hostcache_lib():
    """The module's ONE owning bind site for the ``strom_hostcache_*``
    symbols (strom-lint abi: single-bind ownership — the pre-PR-13
    shape bound ``strom_hostcache_copy`` at two sites).  Private CDLL
    handle: ctypes caches one function object per CDLL instance, so
    sharing ``_load_lib()``'s handle would let another module's
    ``argtypes`` assignment silently retype ours.  None when the
    library cannot build (trimmed installs) — NOT cached, so a later
    arena retries once the build becomes possible (the pre-PR-13
    per-arena cadence)."""
    global _hc_lib
    with _hc_lib_lock:
        if _hc_lib is None:
            try:
                from nvme_strom_tpu.io.engine import _load_lib
                lib = ctypes.CDLL(_load_lib()._name)
                lib.strom_hostcache_arena_create.restype = ctypes.c_void_p
                lib.strom_hostcache_arena_create.argtypes = [
                    ctypes.c_uint64, ctypes.c_int,
                    ctypes.POINTER(ctypes.c_int32)]
                lib.strom_hostcache_arena_destroy.restype = None
                lib.strom_hostcache_arena_destroy.argtypes = [
                    ctypes.c_void_p, ctypes.c_uint64]
                lib.strom_hostcache_copy.restype = None
                lib.strom_hostcache_copy.argtypes = [
                    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64]
                _hc_lib = lib
            except Exception:
                return None
        return _hc_lib


def _scheduler_weights() -> Dict[str, float]:
    """The QoS scheduler's EFFECTIVE class weights — including a user's
    ``STROM_CLASS_WEIGHTS`` override — so 'quota default = scheduler
    weights' holds by construction, not only for the stock values."""
    weights = os.environ.get("STROM_CLASS_WEIGHTS", "")
    try:
        policies = default_policies(weights)
    except ValueError:
        policies = default_policies()
    return {k: p.weight for k, p in policies.items()}


class _Arena:
    """The pinned backing store: one anonymous mapping, pre-faulted and
    (best-effort) mlock'd by the native helper
    (``strom_hostcache_arena_create``); a plain numpy buffer when the
    library cannot build (trimmed installs) — unpinned but functional."""

    def __init__(self, nbytes: int, lock_pages: bool):
        self.nbytes = nbytes
        self.locked = False
        self._base: Optional[int] = None
        self._lib = None
        self._slab = None
        # Unified arena first (io/arena.py, docs/PERF.md §6): cache
        # lines share ONE reservation with staging pools and bridge
        # slabs instead of owning a second mapping.  The carve is
        # mlock'd (pages fault in then) under the same STROM_MLOCK
        # policy; carve refused/arena off → the private pre-arena
        # mapping below, bit-for-bit.
        try:
            from nvme_strom_tpu.io import arena as _arena
            from nvme_strom_tpu.utils.stats import global_stats
            # the tier is built engine-agnostically, so a refused carve
            # lands in the process-global block — starvation of the
            # LARGEST intended arena consumer must not be silent
            slab = _arena.carve_or_none(nbytes, "hostcache",
                                        stats=global_stats,
                                        lock=lock_pages)
        except Exception:
            slab = None
        if slab is not None:
            self._slab = slab
            self._base = slab.addr
            self.view = slab.view
            self.locked = bool(slab.locked)   # THIS carve's mlock verdict
            # numpy-backed fallback when the lib can't build: copy_in's
            # _lib-is-None branch serves fills — unpinned but
            # functional, the documented degradation
            self._lib = _hostcache_lib()
            return
        try:
            lib = _hostcache_lib()
            if lib is None:
                raise OSError("libstrom_io unavailable")
            locked = ctypes.c_int32(0)
            base = lib.strom_hostcache_arena_create(
                nbytes, 1 if lock_pages else 0, ctypes.byref(locked))
            if base:
                self._base = int(base)
                self._lib = lib
                self.locked = bool(locked.value)
                self.view = np.ctypeslib.as_array(
                    ctypes.cast(base, ctypes.POINTER(ctypes.c_uint8)),
                    shape=(nbytes,))
        except Exception:
            self._base = None
        if self._base is None:
            self.view = np.zeros(nbytes, dtype=np.uint8)

    def copy_in(self, dst_off: int, src: np.ndarray) -> None:
        """Fill primitive: staging view → line bytes.  The native path
        memcpys with the GIL dropped; either way the source buffer is
        free to recycle the moment this returns."""
        n = src.nbytes
        if n == 0:
            return
        if self._lib is not None:
            src = np.ascontiguousarray(src)
            self._lib.strom_hostcache_copy(
                self._base + dst_off, src.ctypes.data, n)
        else:
            self.view[dst_off:dst_off + n] = src.reshape(-1)

    def close(self) -> None:
        if self._slab is not None:
            self.view = None
            self._base = None
            self._slab.release()   # the carve recycles; the arena lives
            self._slab = None
            return
        if self._base is not None:
            self.view = None
            self._lib.strom_hostcache_arena_destroy(self._base,
                                                    self.nbytes)
            self._base = None


class _Line:
    """One resident cache line (a valid PREFIX of ``line_bytes``)."""

    __slots__ = ("key", "slot", "valid", "klass", "crc", "pins", "ref",
                 "dead", "sticky", "hits", "tenant")

    def __init__(self, key: LineKey, slot: int, klass: str):
        self.key = key
        self.slot = slot
        self.valid = 0        # valid bytes from the line start
        self.klass = klass
        t = current_tenant()
        #: owning tenant ID, stamped from the fill thread's tenant
        #: scope (None outside any scope — the whole per-tenant quota
        #: layer stays inert then); the line counts against this
        #: owner's residency quota until it leaves the map
        self.tenant = t.id if t is not None else None
        self.crc: Optional[int] = None
        self.pins = 0         # outstanding hit views
        self.ref = False      # second-chance bit
        self.hits = 0         # lifetime hit count: a line evicted at 0
        #                       was filled from NVMe for nothing — the
        #                       ledger's evicted-before-reuse waste class
        self.dead = False     # invalidated while pinned: slot freed on
        #                       last unpin, mapping already gone
        self.sticky = False   # hot-pinned (docs/PERF.md §5): eviction
        #                       skips it while its class is WITHIN quota
        #                       — a KV-prefix page stays resident through
        #                       the decode quota instead of rotating out
        #                       under bulk pressure; over-quota sticky
        #                       lines pay like everyone else, and writes
        #                       still invalidate them


class CacheHitRead:
    """Pending-/SpanView-shaped zero-copy view over a resident line.

    ``wait()`` returns a numpy slice of the pinned arena (no copy, no
    I/O, no engine, no retry/hedge); the line stays pinned — ineligible
    for eviction — until ``release()``."""

    __slots__ = ("_cache", "_line", "_lo", "_hi", "fh", "offset",
                 "_released")

    was_fallback = False

    def __init__(self, cache: "HostCache", line: _Line, lo: int, hi: int,
                 fh: int, offset: int):
        self._cache = cache
        self._line = line
        self._lo = lo
        self._hi = hi
        self.fh = fh
        self.offset = offset
        self._released = False

    @property
    def length(self) -> int:
        return self._hi - self._lo

    def wait(self, timeout: Optional[float] = None) -> np.ndarray:
        del timeout   # always ready: the bytes are resident by contract
        return self._cache.line_view(self._line, self._lo, self._hi)

    def is_ready(self) -> bool:
        return True

    def release(self) -> None:
        if self._released:
            return
        self._released = True
        self._cache.unpin(self._line)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.release()


class _FillOnWait:
    """Wrap a miss span's pending read: on the first successful
    ``wait()``, copy the admitted line-aligned portions of the completed
    view into the cache (the fill-on-miss half of the tier), then hand
    the view through untouched.  A cache failure never fails the read."""

    __slots__ = ("_pending", "_cache", "_fkey", "_off", "_keys",
                 "_klass", "_stats", "_filled", "_sticky", "_tracer",
                 "_ctx")

    def __init__(self, pending, cache: "HostCache", fkey: tuple,
                 span_off: int, keys: Dict[LineKey, int], klass, stats,
                 sticky: bool = False, tracer=None):
        self._pending = pending
        self._cache = cache
        self._fkey = fkey
        self._off = span_off
        self._keys = keys
        self._klass = klass
        self._stats = stats
        self._filled = False
        self._sticky = sticky
        #: fill-span sink + causal identity, captured at construction —
        #: the fill runs at wait() time, possibly on another thread
        self._tracer = tracer if (tracer is not None
                                  and tracer.enabled) else None
        self._ctx = None
        if self._tracer is not None:
            from nvme_strom_tpu.utils.trace import attach_context
            self._ctx = attach_context()

    @property
    def length(self) -> int:
        return self._pending.length

    @property
    def fh(self) -> int:
        return self._pending.fh

    @property
    def offset(self) -> int:
        return self._pending.offset

    @property
    def was_fallback(self) -> bool:
        return bool(getattr(self._pending, "was_fallback", False))

    def wait(self, timeout: Optional[float] = None) -> np.ndarray:
        view = self._pending.wait(timeout)
        if not self._filled:
            self._filled = True
            import time as _time
            t0 = _time.monotonic_ns()
            try:
                self._cache.fill_from_view(self._fkey, self._off, view,
                                           self._keys, self._klass,
                                           self._stats,
                                           sticky=self._sticky)
            except Exception:
                pass   # the tier is an accelerator, never a failure mode
            if self._tracer is not None:
                self._tracer.add_span(
                    "strom.cache.fill", t0, _time.monotonic_ns(),
                    category="strom.cache", ctx=self._ctx,
                    lines=len(self._keys), bytes=int(view.nbytes),
                    klass=self._klass)
        return view

    def is_ready(self) -> bool:
        return self._pending.is_ready()

    def release(self) -> None:
        self._pending.release()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.release()


class HostCache:
    """The managed tier: line map + ghost-list admission + class quotas
    over one pinned arena.  Thread-safe; one instance per process
    (module singleton via :func:`get_cache`), shared by every engine —
    the ONE memory budget ROADMAP item 5 asks for."""

    def __init__(self, line_bytes: int, budget_bytes: int,
                 quotas: Optional[Dict[str, float]] = None,
                 ghost_factor: int = 4, lock_arena: bool = True,
                 verify=None):
        if line_bytes <= 0:
            raise ValueError("line_bytes must be > 0")
        self.line_bytes = line_bytes
        self.capacity = max(1, budget_bytes // line_bytes)
        self.arena = _Arena(self.capacity * line_bytes, lock_arena)
        if quotas is None:
            quotas = _scheduler_weights()
        total_w = sum(quotas.values()) or 1.0
        #: soft per-class residency quota in SLOTS (borrowing free space
        #: is allowed; pressure reclaims over-quota classes first)
        self.quota_slots: Dict[str, float] = {
            k: self.capacity * w / total_w for k, w in quotas.items()}
        # eviction DRR credits mirror io/sched.py's deficit machinery
        # with INVERSE weights: the class the scheduler protects most
        # (decode) pays for pressure last
        max_w = max(quotas.values()) or 1.0
        self._evict_w = {k: max_w / w if w > 0 else max_w * 2
                         for k, w in quotas.items()}
        self._evict_deficit = {k: 0.0 for k in quotas}
        self._rev_order = [k for k in reversed(CLASS_ORDER) if k in quotas]
        for k in quotas:
            if k not in self._rev_order:
                self._rev_order.insert(0, k)
        self._lock = make_rlock("hostcache.HostCache._lock")
        self._lines: Dict[LineKey, _Line] = {}
        self._free: List[int] = list(range(self.capacity))
        self._ghost: "OrderedDict[LineKey, None]" = OrderedDict()
        self._ghost_cap = max(self.capacity * ghost_factor, 16)
        self._clock: Dict[str, deque] = {k: deque() for k in quotas}
        self._class_slots: Dict[str, int] = {k: 0 for k in quotas}
        # per-tenant residency (multi-tenant isolation, orthogonal to
        # the class axis): resident slots per owning tenant id, and
        # each tenant's declared quota fraction (0 = fair share, 1/N of
        # the tenants seen).  Both stay empty — and every tenant branch
        # below short-circuits — until a fill runs inside a tenant
        # scope (STROM_TENANTS=1 serving traffic).
        self._tenant_slots: Dict[str, int] = {}
        self._tenant_quota_frac: Dict[str, float] = {}
        # per-LINE invalidation epoch: a fill whose admission verdict
        # predates a write OVERLAPPING THAT LINE is refused, so a read
        # racing a write can never install pre-write bytes — while
        # writes to other offsets of the same file (kv_offload pages
        # out one slot while decode reads another back) leave in-flight
        # fills untouched.  LRU-bounded WITH A FLOOR: keys absent from
        # the map read as ``_epoch_floor``, which rises to the largest
        # epoch ever evicted from the map — so losing a write's entry
        # can only REFUSE fills (floor > admission epoch), never let a
        # pre-write fill slip back in as epoch 0.
        self._key_epoch: "OrderedDict[LineKey, int]" = OrderedDict()
        self._key_epoch_cap = max(4 * self.capacity, 4096)
        self._epoch_floor = 0
        self._write_seq = 0
        self.bytes_resident = 0    # sum of resident lines' valid bytes
        if verify is None:
            from nvme_strom_tpu.utils.checksum import VerifyPolicy
            verify = VerifyPolicy()
        self._verify = verify

    # -- introspection -----------------------------------------------------

    def counters(self) -> dict:
        with self._lock:
            return {
                "lines_resident": len(self._lines),
                "bytes_resident": self.bytes_resident,
                "capacity_lines": self.capacity,
                "line_bytes": self.line_bytes,
                "arena_locked": self.arena.locked,
                "class_slots": dict(self._class_slots),
                "tenant_slots": dict(self._tenant_slots),
            }

    def resident_spans(self, fkey: tuple) -> list:
        """Merged ``(offset, length)`` spans of one file currently
        resident in the cache, largest-first — the raw material for a
        ``.warmhints.json`` warmup manifest (io/warmup.py): the next
        boot prefetches exactly these byte ranges at ``prefetch`` class
        and lands at today's hit rate instead of re-learning it."""
        with self._lock:
            raw = sorted((key[1], line.valid)
                         for key, line in self._lines.items()
                         if key[0] == fkey and line.valid > 0
                         and not line.dead)
        merged: list = []
        for off, ln in raw:
            if merged and off <= merged[-1][0] + merged[-1][1]:
                last_off, last_len = merged[-1]
                merged[-1] = (last_off,
                              max(last_len, off + ln - last_off))
            else:
                merged.append((off, ln))
        merged.sort(key=lambda s: (-s[1], s[0]))
        return merged

    def _klass(self, klass: Optional[str]) -> str:
        return klass if klass in self.quota_slots else DEFAULT_CLASS

    def _epoch_of(self, key: LineKey) -> int:
        """A line key's invalidation epoch (lock held): its map entry,
        or the fail-closed floor for keys the bounded map has dropped."""
        return self._key_epoch.get(key, self._epoch_floor)

    # -- hit serving -------------------------------------------------------

    def line_view(self, line: _Line, lo: int, hi: int) -> np.ndarray:
        base = line.slot * self.line_bytes
        view = self.arena.view[base + lo:base + hi]
        # engine staging views are private to one request; a line is
        # SHARED persistent state serving every future hit — hand out
        # read-only slices so an in-place consumer mutation (harmless
        # on the engine path) cannot silently corrupt the resident copy
        view.flags.writeable = False
        return view

    def unpin(self, line: _Line) -> None:
        with self._lock:
            self.unpin_locked(line)

    def unpin_locked(self, line: _Line) -> None:
        line.pins -= 1
        if line.pins <= 0 and line.dead:
            self._free.append(line.slot)
            line.dead = False       # slot handed back exactly once

    def _verify_ok(self, line: _Line, stats) -> bool:
        """STROM_VERIFY gate over the resident prefix; a mismatched line
        drops itself (heals through the miss path) and counts
        checksum_failures — corruption never serves silently.

        The CRC pass runs under the cache lock (the probe loops hold
        it): with ``STROM_VERIFY=full`` and large lines this serializes
        concurrent probes behind line-sized checksum work — the same
        deliberate throughput-for-integrity trade ``full`` makes on the
        engine read path; ``sample`` (every Nth span) amortizes it to
        noise and is the recommended steady-state mode."""
        if line.crc is None or not self._verify.want():
            return True
        from nvme_strom_tpu.utils.checksum import crc32c
        got = crc32c(self.line_view(line, 0, line.valid))
        if stats is not None:
            stats.add(bytes_verified=int(line.valid))
        if got == line.crc:
            return True
        if stats is not None:
            stats.add(checksum_failures=1)
        self._drop_line(line, stats, counter="cache_invalidations")
        return False

    # -- probe (the planner boundary) --------------------------------------

    def probe_range(self, fkey: tuple, off: int, length: int,
                    klass: Optional[str], stats=None, hot: bool = False
                    ) -> Tuple[List[tuple], Dict[LineKey, int]]:
        """Split ``[off, off+length)`` into hit and miss segments.

        Returns ``(segments, admitted)``: segments are ordered
        ``("hit", abs_off, ln, line)`` — the line PINNED, one segment
        per line so every hit view is a zero-copy arena slice — and
        ``("miss", abs_off, ln)`` runs (contiguous missed bytes merged);
        ``admitted`` maps each line key the caller should fill from the
        miss reads' completions (the ghost-list verdict) to the file's
        invalidation epoch at verdict time — a fill is refused if a
        write bumps the epoch in between.

        ``hot`` marks the range latency-critical repeat traffic (KV
        prefix pages): missed lines are admitted on FIRST touch (the
        ghost gate exists to filter one-shot scans, which a declared-hot
        range is not) and resident lines turn sticky — protected from
        eviction while their class stays within quota."""
        kl = self._klass(klass)
        lb = self.line_bytes
        segments: List[tuple] = []
        admitted: Dict[LineKey, int] = {}
        hits = misses = served = 0
        with self._lock:
            pos, end = off, off + length
            m_lo: Optional[int] = None     # open miss RUN (segments
            #                                merge; misses count lines)
            while pos < end:
                lo = pos - pos % lb
                take_end = min(end, lo + lb)
                line = self._lines.get((fkey, lo))
                ok = (line is not None and take_end - lo <= line.valid
                      and self._verify_ok(line, stats))
                if ok:
                    if m_lo is not None:
                        segments.append(("miss", m_lo, pos - m_lo))
                        m_lo = None
                    line.pins += 1
                    line.ref = True
                    line.hits += 1
                    if hot:
                        line.sticky = True
                    segments.append(("hit", pos, take_end - pos, line))
                    hits += 1
                    served += take_end - pos
                else:
                    # count misses PER LINE, the same unit as hits, so
                    # hit rate = hits/(hits+misses) is a line fraction
                    misses += 1
                    if m_lo is None:
                        m_lo = pos
                    if pos == lo:
                        if (fkey, lo) in self._lines:
                            # resident but too short for this request:
                            # the line already proved hot — admit the
                            # fill directly so the longer read EXTENDS
                            # the prefix instead of missing forever
                            admitted[(fkey, lo)] = \
                                self._epoch_of((fkey, lo))
                        else:
                            self._admit_or_note((fkey, lo), admitted,
                                                stats, hot=hot)
                pos = take_end
            if m_lo is not None:
                segments.append(("miss", m_lo, end - m_lo))
        if stats is not None and (hits or misses):
            stats.add(cache_hits=hits, cache_misses=misses,
                      bytes_served_cache=served)
            stats.add_class_stat(kl, cache_hits=hits, cache_misses=misses,
                                 bytes_served_cache=served)
        return segments, admitted

    def probe_span(self, fkey: tuple, off: int, length: int,
                   klass: Optional[str], stats=None, hot: bool = False
                   ) -> Tuple[Optional[_Line], Dict[LineKey, int]]:
        """Whole-span variant for vectored refill paths
        (``DeviceStream.stream_ranges``): a span is a hit only when it
        fits inside ONE line's valid prefix (anything else would need a
        concatenating copy to serve — against the zero-copy contract);
        otherwise the fillable line starts inside the span are run
        through admission and the span submits as a normal miss."""
        kl = self._klass(klass)
        lb = self.line_bytes
        admitted: Dict[LineKey, int] = {}
        with self._lock:
            lo = off - off % lb
            line = self._lines.get((fkey, lo))
            if (line is not None and off + length <= lo + line.valid
                    and self._verify_ok(line, stats)):
                line.pins += 1
                line.ref = True
                line.hits += 1
                if hot:
                    line.sticky = True
                if stats is not None:
                    stats.add(cache_hits=1, bytes_served_cache=length)
                    stats.add_class_stat(kl, cache_hits=1,
                                         bytes_served_cache=length)
                return line, admitted
            # admission only when a future IDENTICAL read could hit:
            # a stream-path hit must fit in ONE line and fills cover a
            # line from its start, so only a line-aligned span within
            # one line earns fills — a cross-line or mid-line span
            # passes through untouched (filling its lines would squat
            # the budget serving nothing; the PLANNER path's partial-
            # hit splitting is where unaligned repeat traffic caches)
            if off % lb == 0 and length <= lb:
                key = (fkey, off)
                if key in self._lines:
                    # too-short resident prefix: admit the extension
                    admitted[key] = self._epoch_of(key)
                else:
                    self._admit_or_note(key, admitted, stats, hot=hot)
        if stats is not None:
            # per-line units, matching probe_range's hits
            n_lines = (off + length - 1) // lb - lo // lb + 1
            stats.add(cache_misses=n_lines)
            stats.add_class_stat(kl, cache_misses=n_lines)
        return None, admitted

    def _admit_or_note(self, key: LineKey, admitted: Dict[LineKey, int],
                       stats, hot: bool = False) -> None:
        """The ghost-list second-chance verdict (lock held): admit a
        missed line only if it was ALREADY missed recently — the first
        touch of a streaming scan is refused (counted) and remembered.
        An admitted key carries the file's current invalidation epoch,
        so a write landing between verdict and fill voids the fill.
        ``hot`` skips the ghost gate entirely: a declared-hot range
        (KV prefix restore) is repeat traffic by contract, so the first
        touch admits."""
        if hot or key in self._ghost:
            self._ghost.pop(key, None)
            admitted[key] = self._epoch_of(key)
            return
        self._ghost[key] = None
        while len(self._ghost) > self._ghost_cap:
            self._ghost.popitem(last=False)
        if stats is not None:
            stats.add(cache_admission_rejections=1)

    # -- fill (miss completions) -------------------------------------------

    def fill_from_view(self, fkey: tuple, span_off: int,
                       view: np.ndarray, keys: Dict[LineKey, int],
                       klass: Optional[str], stats=None,
                       sticky: bool = False) -> None:
        """Copy the admitted line-aligned portions of a completed span
        read into lines.  ``view`` may be short (EOF) — each line holds
        whatever prefix the read actually covered.  ``keys`` carries
        each key's admission-time epoch (see :meth:`probe_range`)."""
        n = view.nbytes
        for key, epoch in keys.items():
            line_off = key[1]
            rel = line_off - span_off
            if rel < 0 or rel >= n:
                continue   # admitted under another span of the batch
            self.fill(fkey, line_off,
                      view[rel:rel + min(self.line_bytes, n - rel)],
                      klass, stats, epoch=epoch, sticky=sticky)

    def fill(self, fkey: tuple, line_off: int, payload: np.ndarray,
             klass: Optional[str], stats=None,
             epoch: Optional[int] = None, sticky: bool = False) -> bool:
        """Install ``payload`` (a prefix of the line at ``line_off``) —
        allocating a slot, evicting under the class-quota policy when
        the arena is full.  False when the fill was skipped (already
        resident with as much data, pinned, nothing evictable, or the
        file was written since the admission verdict — ``epoch``).

        The line-sized memcpy (and CRC pass when verification is on)
        runs OUTSIDE the cache lock: the line is reserved under the
        lock with ``valid = 0`` and a pin, so concurrent probes miss
        it, eviction skips it, and an invalidation racing the copy
        marks it dead (abandoned below) — fills from N miss threads
        overlap instead of serializing behind one memcpy."""
        kl = self._klass(klass)
        valid = int(payload.nbytes)
        if valid <= 0 or valid > self.line_bytes:
            return False
        with self._lock:
            key = (fkey, line_off)
            if (epoch is not None
                    and self._epoch_of((fkey, line_off)) != epoch):
                if stats is not None:   # written since admission:
                    stats.add(cache_fill_failures=1)   # stale payload
                return False
            line = self._lines.get(key)
            if line is not None:
                if line.valid >= valid or line.pins > 0:
                    return False
                self.bytes_resident -= line.valid
                line.valid = 0          # probes miss while we rewrite
            else:
                if self._free:
                    slot = self._free.pop()
                else:
                    slot = self._evict_one(kl, stats)
                    if slot is None:
                        if stats is not None:
                            stats.add(cache_fill_failures=1)
                        return False
                line = _Line(key, slot, kl)
                self._lines[key] = line
                self._ghost.pop(key, None)
                self._class_slots[kl] = self._class_slots.get(kl, 0) + 1
                self._clock.setdefault(kl, deque()).append(key)
                if line.tenant is not None:
                    self._note_tenant_fill_locked(line, stats)
            if sticky:
                line.sticky = True
            line.pins += 1              # copy in progress: unevictable
        try:
            self.arena.copy_in(line.slot * self.line_bytes, payload)
            crc = None
            if self._verify.enabled:
                from nvme_strom_tpu.utils.checksum import crc32c
                crc = crc32c(payload)
        except BaseException:
            with self._lock:
                self.unpin_locked(line)
            raise
        with self._lock:
            self.unpin_locked(line)
            if line.dead or self._lines.get(key) is not line:
                return False            # invalidated mid-copy: abandon
            line.valid = valid
            line.crc = crc
            self.bytes_resident += valid
            if stats is not None:
                stats.add(cache_admissions=1)
                stats.set_gauges(cache_bytes_resident=self.bytes_resident,
                                 cache_lines_resident=len(self._lines))
        return True

    # -- eviction (class quotas, DRR + second chance) ----------------------

    def _over_quota(self, klass: str) -> bool:
        return self._class_slots.get(klass, 0) > \
            self.quota_slots.get(klass, 0.0)

    # -- per-tenant residency quotas (multi-tenant isolation) --------------

    def _note_tenant_fill_locked(self, line: _Line, stats) -> None:
        """Charge a new line to its owner's residency count; landing
        past the quota while free space existed is BORROWING (allowed,
        counted — pressure reclaims it first)."""
        tid = line.tenant
        t = current_tenant()
        if t is not None and t.id == tid:
            self._tenant_quota_frac[tid] = t.quota_frac
        else:
            self._tenant_quota_frac.setdefault(tid, 0.0)
        self._tenant_slots[tid] = self._tenant_slots.get(tid, 0) + 1
        if self._tenant_over(tid) and stats is not None:
            stats.add(tenant_borrows=1)
            stats.add_tenant_stat(tid, borrows=1)

    def _tenant_quota_slots(self, tid: str) -> float:
        """One tenant's residency quota in slots: its declared fraction
        of the arena, or — fraction 0 — a fair share (1/N of the
        tenants currently resident)."""
        frac = self._tenant_quota_frac.get(tid, 0.0)
        if frac <= 0.0:
            frac = 1.0 / max(1, len(self._tenant_slots))
        return frac * self.capacity

    def _tenant_over(self, tid: Optional[str]) -> bool:
        if tid is None or not self._tenant_slots:
            return False
        return self._tenant_slots.get(tid, 0) > \
            self._tenant_quota_slots(tid)

    def _tenant_drop_locked(self, line: _Line) -> None:
        """Refund a departing line's residency charge (lock held)."""
        tid = line.tenant
        if tid is None:
            return
        n = self._tenant_slots.get(tid, 0) - 1
        if n > 0:
            self._tenant_slots[tid] = n
        else:
            # last resident line gone: forget the tenant entirely so
            # fair-share fractions track tenants actually resident
            self._tenant_slots.pop(tid, None)
            self._tenant_quota_frac.pop(tid, None)

    def _tenant_evict_locked(self, stats) -> Optional[int]:
        """Quota pre-pass: before any class pays, reclaim from the MOST
        over-quota tenant (largest slot excess) — the borrowing that
        storm bought is the first residency pressure takes back, so one
        tenant's storm cannot evict another's hot set.  Prefers lines
        the second-chance bit marks cold; sticky does not protect an
        over-quota tenant's lines (mirroring the over-quota class
        rule).  None when no tenant is over quota."""
        over = [tid for tid in self._tenant_slots
                if self._tenant_over(tid)]
        if not over:
            return None
        over.sort(key=lambda tid: (self._tenant_slots.get(tid, 0)
                                   - self._tenant_quota_slots(tid)),
                  reverse=True)
        for tid in over:
            best = None
            for line in self._lines.values():
                if line.tenant != tid or line.pins > 0:
                    continue
                if not line.ref:
                    best = line
                    break
                if best is None:
                    best = line
            if best is None:
                continue                    # everything pinned: next
            del self._lines[best.key]
            self._class_slots[best.klass] -= 1
            self.bytes_resident -= best.valid
            self._tenant_drop_locked(best)
            if stats is not None:
                stats.add(cache_evictions=1, tenant_quota_evictions=1)
                stats.add_tenant_stat(tid, quota_evictions=1)
                if best.hits == 0 and best.valid:
                    from nvme_strom_tpu.obs.ledger import charge_waste
                    charge_waste(stats, "evicted_unused", best.valid)
                stats.set_gauges(
                    cache_bytes_resident=self.bytes_resident,
                    cache_lines_resident=len(self._lines))
            return best.slot
        return None

    def _evict_one(self, incoming: str, stats) -> Optional[int]:
        """Reclaim one slot (lock held).  Candidate classes: over-quota
        first; then — when none is over quota OR every over-quota line
        turned out pinned — every class with resident lines (the
        fallback must not be skipped just because the over-quota class
        is momentarily unevictable).  Among candidates the
        deficit-round-robin credits (inverse scheduler weights, one
        round of banking, lowest priority first) pick the payer; a
        second-chance clock inside the class picks the line, skipping
        pinned and recently-referenced lines."""
        if self._tenant_slots:
            # tenant-quota pre-pass: over-quota tenants' borrowing pays
            # for pressure before any class-level candidate does
            slot = self._tenant_evict_locked(stats)
            if slot is not None:
                return slot
        over = [k for k in self._rev_order
                if self._over_quota(k) and self._clock.get(k)]
        every = [k for k in self._rev_order if self._clock.get(k)]
        for cands in (over, every):
            cands = list(cands)
            while cands:
                for k in cands:
                    w = self._evict_w.get(k, 1.0)
                    self._evict_deficit[k] = min(
                        self._evict_deficit[k] + w, 2 * w)
                cands.sort(key=lambda k: -self._evict_deficit[k])
                for k in list(cands):
                    if self._evict_deficit[k] < 1.0:
                        continue
                    slot = self._clock_evict(k, stats)
                    if slot is not None:
                        self._evict_deficit[k] -= 1.0
                        return slot
                    cands.remove(k)   # nothing evictable here right now
        return None

    def _clock_evict(self, klass: str, stats) -> Optional[int]:
        """Second-chance sweep of one class's clock (lock held)."""
        q = self._clock.get(klass)
        if not q:
            return None
        for _ in range(2 * len(q)):
            key = q[0]
            line = self._lines.get(key)
            if line is None or line.klass != klass:
                q.popleft()            # stale clock entry
                if not q:
                    return None
                continue
            if line.pins > 0:
                q.rotate(-1)
                continue
            if line.sticky and not self._over_quota(klass) \
                    and not self._tenant_over(line.tenant):
                # hot-pinned within quota (docs/PERF.md §5): the decode
                # class's KV-prefix residency survives bulk churn; an
                # over-quota class's — or over-quota TENANT's — sticky
                # lines pay normally, so the pin can never wedge the
                # shared budget
                q.rotate(-1)
                continue
            if line.ref:
                line.ref = False       # second chance
                q.rotate(-1)
                continue
            q.popleft()
            del self._lines[key]
            self._class_slots[klass] -= 1
            self.bytes_resident -= line.valid
            self._tenant_drop_locked(line)
            if stats is not None:
                stats.add(cache_evictions=1)
                if line.hits == 0 and line.valid:
                    # filled from NVMe, never served a hit: the fill's
                    # bandwidth bought nothing (ledger waste class —
                    # growth means the ghost gate or quotas are wrong)
                    from nvme_strom_tpu.obs.ledger import charge_waste
                    charge_waste(stats, "evicted_unused", line.valid)
                stats.set_gauges(cache_bytes_resident=self.bytes_resident,
                                 cache_lines_resident=len(self._lines))
            return line.slot
        return None

    # -- invalidation (engine writes) --------------------------------------

    def _drop_line(self, line: _Line, stats,
                   counter: str = "cache_invalidations") -> None:
        """Remove a line from the map NOW (no new hits); its slot frees
        immediately when unpinned, else on the last unpin (outstanding
        views keep serving the old bytes — same contract as a read
        racing a write on the file itself).  Lock held."""
        if self._lines.get(line.key) is not line:
            return
        del self._lines[line.key]
        self._class_slots[line.klass] -= 1
        self.bytes_resident -= line.valid
        self._tenant_drop_locked(line)
        if line.pins > 0:
            line.dead = True
        else:
            self._free.append(line.slot)
        # stale clock entries are normally reaped lazily by eviction
        # sweeps; a rewrite-heavy workload with no eviction pressure
        # would grow the deque forever, so compact when it runs well
        # past the class's resident population
        q = self._clock.get(line.klass)
        if q is not None and len(q) > \
                2 * max(1, self._class_slots.get(line.klass, 0)) + 16:
            self._clock[line.klass] = deque(
                k for k in q
                if self._lines.get(k) is not None
                and self._lines[k].klass == line.klass)
        if stats is not None:
            stats.add(**{counter: 1})
            stats.set_gauges(cache_bytes_resident=self.bytes_resident,
                             cache_lines_resident=len(self._lines))

    def invalidate(self, fkey: tuple, offset: int, length: int,
                   stats=None) -> int:
        """Drop every line overlapping a written range (the staleness
        guard ``StromEngine.submit_write`` calls); returns lines
        dropped."""
        if length <= 0:
            return 0
        lb = self.line_bytes
        first = offset - offset % lb
        n = 0
        with self._lock:
            self._write_seq += 1
            for line_off in range(first, offset + length, lb):
                key = (fkey, line_off)
                # epoch bump: any fill admitted before this write —
                # even one whose read is still in flight — is now
                # void; fills of OTHER lines are untouched
                self._key_epoch[key] = self._write_seq
                self._key_epoch.move_to_end(key)
                line = self._lines.get(key)
                if line is not None:
                    self._drop_line(line, stats)
                    n += 1
                self._ghost.pop(key, None)
            while len(self._key_epoch) > self._key_epoch_cap:
                _k, ev = self._key_epoch.popitem(last=False)
                # fail CLOSED: an evicted entry's epoch becomes the
                # floor every absent key reads, so a fill admitted
                # before the evicted write can never pass as epoch 0
                self._epoch_floor = max(self._epoch_floor, ev)
        return n

    def clear(self) -> None:
        """Drop every unpinned line (tests/bench)."""
        with self._lock:
            for line in list(self._lines.values()):
                self._drop_line(line, None)
            self._ghost.clear()

    def close(self) -> None:
        """Unmap the arena.  The hit-view contract mirrors the engine's
        staging views: a view is valid until ITS release and no longer
        after the tier is torn down — callers release before
        reset()/configure(), exactly as they release before
        ``close_all()``."""
        with self._lock:
            self._lines.clear()
            self._ghost.clear()
            self._tenant_slots.clear()
            self._tenant_quota_frac.clear()
            self.bytes_resident = 0
        self.arena.close()


# --------------------------------------------------------------------------
# module singleton — the ONE shared budget
# --------------------------------------------------------------------------

_singleton_lock = make_lock("hostcache._singleton_lock")
_cache: Optional[HostCache] = None
_cache_init = False


def parse_class_quotas(spec: str) -> Optional[Dict[str, float]]:
    """Parse/validate ``STROM_HOSTCACHE_CLASS_QUOTAS`` — THE one
    implementation of the ``decode=8,restore=4`` grammar
    (``HostCacheConfig.__post_init__`` validates through it too, so
    a malformed value fails loudly at construction)."""
    if not spec:
        return None
    out: Dict[str, float] = {}
    for part in filter(None, (s.strip() for s in spec.split(","))):
        name, eq, val = part.partition("=")
        name = name.strip()
        try:
            weight = float(val)
        except ValueError:
            weight = -1.0
        if not eq or name not in CLASS_ORDER or weight < 0:
            raise ValueError(
                f"STROM_HOSTCACHE_CLASS_QUOTAS entry {part!r}: expected "
                f"<class>=<non-negative weight> with class in "
                f"{CLASS_ORDER}")
        out[name] = weight
    # unnamed classes keep the scheduler's effective relative weights
    # (STROM_CLASS_WEIGHTS included) so every class retains SOME quota
    # (a zero-quota class could never cache at all)
    for k, w in _scheduler_weights().items():
        out.setdefault(k, w)
    return out


def _default_line_bytes(engine) -> int:
    """Auto line size: the ``chunk_bytes`` of the first engine that
    touches the tier (4 MiB when the tier is built without one),
    rounded down to a power of two (cheap aligned arithmetic), floored
    at 64 KiB so a tiny probe engine cannot shred the arena into
    confetti lines."""
    config = getattr(engine, "config", None)
    ck = int(getattr(config, "chunk_bytes", 4 << 20))
    p = 4096
    while p * 2 <= ck:
        p *= 2
    return max(p, 64 << 10)


def _build_locked(cfg: HostCacheConfig, engine) -> None:
    """Swap the singleton in (``_singleton_lock`` held).  On a build
    error nothing is marked initialized, so every later caller raises
    the SAME loud error instead of one crash followed by a silently
    tier-off process."""
    global _cache, _cache_init
    if _cache is not None:
        _cache.close()
        _cache = None
    new = None
    if cfg.budget_mb > 0:
        line = cfg.line_bytes or _default_line_bytes(engine)
        budget = cfg.budget_mb << 20
        if budget < line:
            # a non-zero budget means the user WANTS the tier: shrink
            # the line to fit (largest power of two ≤ budget; the
            # config floor keeps budgets ≥ 1 MiB ≥ the 4 KiB minimum)
            # instead of silently disabling
            line = 4096
            while line * 2 <= budget:
                line *= 2
        new = HostCache(
            line_bytes=line, budget_bytes=budget,
            quotas=parse_class_quotas(cfg.class_quotas),
            ghost_factor=cfg.ghost_factor,
            lock_arena=cfg.lock_arena)
    _cache = new
    _cache_init = True


def configure(config: Optional[HostCacheConfig] = None,
              engine=None) -> Optional[HostCache]:
    """(Re)build the process-wide tier from ``config`` (default: the
    env-derived :class:`HostCacheConfig`).  Returns the cache, or None
    when the budget disables the tier."""
    with _singleton_lock:
        _build_locked(config or HostCacheConfig(), engine)
        return _cache


def reset() -> None:
    """Tear the singleton down; the next :func:`get_cache` re-reads the
    environment (tests and bench toggle the tier this way)."""
    global _cache, _cache_init
    with _singleton_lock:
        if _cache is not None:
            _cache.close()
        _cache = None
        _cache_init = False


def get_cache(engine=None) -> Optional[HostCache]:
    """The process-wide tier, built lazily from the environment on first
    use; None when ``STROM_HOSTCACHE_MB`` is unset/0 (the default) —
    callers then take their exact pre-cache path.  Double-checked under
    the lock: two racing first callers must not build twice (the loser
    would munmap an arena the winner is serving hits from)."""
    if _cache_init:
        return _cache
    with _singleton_lock:
        if not _cache_init:
            _build_locked(HostCacheConfig(), engine)
        return _cache


def file_key_of(engine, fh: int) -> Optional[tuple]:
    """The engine's stable file identity for ``fh`` (None for engines
    without the mapping — stub/foreign wrappers simply skip the tier)."""
    fn = getattr(engine, "file_key", None)
    if fn is None:
        return None
    try:
        return fn(fh)
    except Exception:
        return None


def notify_write(fkey: Optional[tuple], offset: int, length: int,
                 stats=None) -> None:
    """Write-path staleness guard: drop cached lines overlapping an
    engine write (``StromEngine.submit_write`` calls this for every
    write on a mapped fh).  No-op while the tier is off."""
    c = _cache
    if c is not None and fkey is not None:
        c.invalidate(fkey, offset, length, stats=stats)


def spoil_span(engine, fh: int, offset: int, length: int,
               stats=None) -> None:
    """Heal-path hook: a consumer-level checksum just failed on this
    span, so any line filled from that (possibly transiently corrupt)
    read must not serve the re-read — or any future read.  The PR 5
    're-read once, then the damage path' protocol calls this before its
    re-read; without it a corrupt FILL would satisfy the heal from DRAM
    and convert a transient flip into a permanent-looking corruption
    (or, under sampled verification, serve it silently).  No-op while
    the tier is off."""
    c = _cache
    if c is None:
        return
    fkey = file_key_of(engine, fh)
    if fkey is not None:
        c.invalidate(fkey, offset, length, stats=stats)


def spoil_path(path, offset: int, length: int, stats=None) -> None:
    """:func:`spoil_span` for callers holding a path instead of an open
    engine fh (checkpoint tile heals): the stat-derived identity equals
    the engine's fstat key while the file is unmodified — exactly the
    window in which a stale line could exist."""
    c = _cache
    if c is None:
        return
    try:
        st = os.stat(path)
    except OSError:
        return
    c.invalidate((st.st_dev, st.st_ino, st.st_mtime_ns, st.st_size),
                 offset, length, stats=stats)

"""Extent-coalescing I/O planner — the shared read-plan layer between
consumers and the engine's vectored submit.

The reference amortizes per-request overhead by carrying MANY chunks in
one MEMCPY_SSD2GPU command (SURVEY.md §3.1); before this module every
consumer crossed Python→ctypes→``io_uring_enter`` once per extent and
hand-rolled its own chunk-split loop.  The planner is the one place
both problems are solved:

  coalesce   extents that are adjacent — or separated by at most
             ``STROM_COALESCE_GAP`` bytes (default one 4 KiB block) —
             on the SAME file merge into one larger O_DIRECT read.
             Consumers get zero-copy SUB-VIEWS of the completed span
             buffer (legal because the engine already returns offset
             views instead of memcpy'ing: slicing a numpy view costs
             nothing).  Overlapping/duplicate extents dedupe into one
             read the same way.  Cross-file extents never coalesce.
  split      extents larger than the split size (the engine's
             ``chunk_bytes``, its staging-buffer capacity, unless the
             caller pins a smaller one) break into pieces —
             replacing the near-identical hard-coded loops each
             consumer carried.  ``split_unit`` keeps piece boundaries
             on record boundaries (fixedrec) — pieces of one extent
             are always multiples of the unit from the extent's start.
  batch      the resulting spans submit through the engine's
             ``submit_readv`` (ONE C call, ONE ``io_uring_enter``
             doorbell) when available, falling back to per-span
             ``submit_read`` for engine wrappers that predate it.

Accounting: every merged extent counts ``StromStats.spans_coalesced``;
the C engine counts ``submit_batches`` / ``submit_syscalls_saved`` at
the vectored boundary.  Thresholds and semantics are documented in
docs/PERF.md.

The planner composes with the resilience stack unchanged: a
``ResilientEngine`` submits the batch through the wrapped engine and
wraps EACH span in its own recovery loop (a failed span retries alone,
never the whole batch), and ``FaultyEngine`` injects per-span faults
into the vectored path (docs/RESILIENCE.md).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

#: default coalesce-gap: one O_DIRECT logical block — reading one
#: wasted block is cheaper than a second NVMe round trip, and tar's
#: 512 B inter-member headers / the offload file's slot padding both
#: fall under it
DEFAULT_COALESCE_GAP = 4096


def coalesce_gap() -> int:
    """Gap threshold in bytes (env ``STROM_COALESCE_GAP``; default one
    4 KiB block).  0 disables coalescing across gaps (adjacent and
    overlapping extents still merge); negative values clamp to 0."""
    try:
        return max(0, int(os.environ.get("STROM_COALESCE_GAP",
                                         DEFAULT_COALESCE_GAP)))
    except ValueError:
        return DEFAULT_COALESCE_GAP


def split_spans(spans, chunk: int):
    """(offset, length) spans → (flat sub-ranges ≤ ``chunk``, per-span
    sub-range counts).  The one splitting rule every chunk-bound
    consumer shares (engine reads are capped at chunk_bytes);
    zero-length spans contribute zero sub-ranges but keep their count
    entry so group boundaries stay aligned.  (Formerly
    ``ops.bridge.split_ranges``, which now delegates here.)"""
    flat, counts = [], []
    for off, ln in spans:
        before = len(flat)
        while ln > 0:
            take = min(chunk, ln)
            flat.append((off, take))
            off += take
            ln -= take
        counts.append(len(flat) - before)
    return flat, counts


@dataclass(frozen=True)
class ExtentPlan:
    """The pure (side-effect-free) plan: which engine reads to submit
    and where each input extent's bytes land in them.

    ``spans``       (fh, offset, length) engine reads, each ≤ the split
                    size, in submission order.
    ``placements``  per input extent (input order), the ordered pieces
                    covering it: (span_index, lo, hi) byte ranges
                    RELATIVE to that span's completed view.  Zero-
                    length extents get an empty piece list.
    ``spans_coalesced``  input extents that merged into a span opened
                    by an earlier extent (k-extent merge counts k-1).
    ``gap_bytes``   dead bytes deliberately read through when merging
                    near-adjacent extents (the coalesce-gap waste class
                    of obs/ledger.py: cheaper than extra NVMe round
                    trips, but bandwidth nonetheless — honestly
                    accounted as ``waste_coalesce_gap_bytes``).
    """

    spans: List[Tuple[int, int, int]]
    placements: List[List[Tuple[int, int, int]]]
    spans_coalesced: int
    n_extents: int
    gap_bytes: int = 0

    @property
    def submits_saved(self) -> int:
        """Engine submissions a per-extent caller would have made minus
        what this plan makes (coalescing net of splitting)."""
        return self.n_extents - len(self.spans)


def plan_extents(extents: Sequence[Tuple[int, int, int]], *,
                 chunk_bytes: int, gap: Optional[int] = None,
                 split_unit: int = 1) -> ExtentPlan:
    """Sort + coalesce + split ``(fh, offset, length)`` extents.

    ``chunk_bytes``: max bytes of one engine read (≤ the engine's
    staging-buffer capacity).  ``gap``: max bytes of dead space to read
    through when merging (None = env/default via :func:`coalesce_gap`).
    ``split_unit``: piece boundaries of a SPLIT extent stay multiples
    of this from the extent's start (record size for fixedrec); a
    merged span is never split, so sub-views inside it keep exact
    byte placement regardless of the unit.
    """
    if gap is None:
        gap = coalesce_gap()
    if split_unit <= 0:
        raise ValueError(f"split_unit must be >= 1, got {split_unit}")
    split = (chunk_bytes // split_unit) * split_unit
    if split <= 0:
        raise ValueError(
            f"split_unit ({split_unit}) exceeds chunk_bytes "
            f"({chunk_bytes}); raise EngineConfig.chunk_bytes")
    n = len(extents)
    placements: List[List[Tuple[int, int, int]]] = [[] for _ in range(n)]
    spans: List[Tuple[int, int, int]] = []
    coalesced = 0

    for i in range(n):
        if extents[i][2] < 0:
            raise ValueError(f"extent {i}: negative length "
                             f"{extents[i][2]}")
    order = sorted((i for i in range(n) if extents[i][2] > 0),
                   key=lambda i: (extents[i][0], extents[i][1],
                                  extents[i][2]))

    def emit(group: list) -> None:
        """One coalesced group → spans + placements.  Multi-extent
        groups fit one span by construction; a lone oversized extent
        splits at unit-aligned piece boundaries."""
        nonlocal coalesced
        fh = extents[group[0]][0]
        start = extents[group[0]][1]
        end = max(extents[i][1] + extents[i][2] for i in group)
        length = end - start
        if length <= split:
            si = len(spans)
            spans.append((fh, start, length))
            for i in group:
                off, ln = extents[i][1], extents[i][2]
                placements[i].append((si, off - start, off - start + ln))
            coalesced += len(group) - 1
            return
        # lone oversized extent: piece k covers [start + k*split, ...)
        assert len(group) == 1
        i = group[0]
        pos = 0
        while pos < length:
            take = min(split, length - pos)
            si = len(spans)
            spans.append((fh, start + pos, take))
            placements[i].append((si, 0, take))
            pos += take

    group: list = []
    g_fh = g_start = g_end = 0
    gap_bytes = 0
    for i in order:
        fh, off, ln = extents[i]
        if group and fh == g_fh and off <= g_end + gap \
                and max(g_end, off + ln) - g_start <= split:
            if off > g_end:
                # dead bytes read through to merge (ledger waste class)
                gap_bytes += off - g_end
            group.append(i)
            g_end = max(g_end, off + ln)
            continue
        if group:
            emit(group)
        group = [i]
        g_fh, g_start, g_end = fh, off, off + ln
    if group:
        emit(group)
    return ExtentPlan(spans=spans, placements=placements,
                      spans_coalesced=coalesced, n_extents=n,
                      gap_bytes=gap_bytes)


class _SharedSpan:
    """One submitted span read, shared by every sub-view cut from it.
    The underlying request releases when the LAST view releases."""

    __slots__ = ("pending", "_refs")

    def __init__(self, pending, refs: int):
        self.pending = pending
        self._refs = refs

    def release_one(self) -> None:
        self._refs -= 1
        if self._refs <= 0:
            self.pending.release()


_EMPTY = np.empty(0, dtype=np.uint8)


class SpanView:
    """PendingRead-shaped zero-copy sub-view of a (possibly coalesced)
    span read.

    ``wait()`` returns ``span_view[lo:hi]`` — a numpy slice of the
    engine's staging buffer, no copy; validity follows the span's
    buffer (until every view of the span releases).  ``length``/
    ``fh``/``offset`` describe THIS piece, so ``wait_exact`` reports
    name the exact range.  A span completing short (EOF/device short
    read) surfaces here as a short sub-view, which ``wait_exact``
    turns into the loud OSError.  Piece of a zero-length extent:
    ``lo == hi``, waits to an empty view without any I/O dependency
    beyond its span.
    """

    __slots__ = ("_span", "_lo", "_hi", "fh", "offset", "_released")

    def __init__(self, span: _SharedSpan, lo: int, hi: int,
                 fh: int, offset: int):
        self._span = span
        self._lo = lo
        self._hi = hi
        self.fh = fh
        self.offset = offset
        self._released = False

    @property
    def length(self) -> int:
        return self._hi - self._lo

    @property
    def was_fallback(self) -> bool:
        return bool(getattr(self._span.pending, "was_fallback", False))

    def wait(self, timeout: Optional[float] = None) -> np.ndarray:
        view = self._span.pending.wait(timeout)
        lo = min(self._lo, view.nbytes)
        return view[lo:min(self._hi, view.nbytes)]

    def is_ready(self) -> bool:
        return self._span.pending.is_ready()

    def release(self) -> None:
        """Idempotent; the shared span's request frees once every view
        cut from it has released (refcounted — the engine's
        release-waits-if-live contract applies to the last one)."""
        if self._released:
            return
        self._released = True
        self._span.release_one()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.release()


class JoinedPieces:
    """Pending-shaped join of one extent's MULTIPLE pieces.

    Pre-tier, an extent ≤ the split size always came back as exactly
    one piece; the host tier's hit/miss splitting (docs/PERF.md §4) can
    return several (one per cache line plus miss runs).  Consumers
    whose shape logic needs ONE view per extent (weight row chunks)
    join them here: ``wait()`` assembles the pieces into one host
    buffer — a host copy, honestly counted as ``bounce_bytes`` — and
    ``release()`` releases every piece.  :func:`join_pieces` returns
    the piece ITSELF when there is only one, so the common case stays
    zero-copy."""

    __slots__ = ("_pieces", "_stats", "_buf", "fh", "offset", "length")

    def __init__(self, pieces, stats=None):
        self._pieces = list(pieces)
        self._stats = stats
        self._buf: Optional[np.ndarray] = None
        first = self._pieces[0]
        self.fh = first.fh
        self.offset = first.offset
        self.length = sum(p.length for p in self._pieces)

    @property
    def was_fallback(self) -> bool:
        return any(getattr(p, "was_fallback", False)
                   for p in self._pieces)

    def wait(self, timeout: Optional[float] = None) -> np.ndarray:
        if self._buf is None:
            views = [p.wait(timeout).reshape(-1).view(np.uint8)
                     for p in self._pieces]
            self._buf = np.concatenate(views)
            if self._stats is not None:
                self._stats.add(bounce_bytes=int(self._buf.nbytes))
        return self._buf

    def is_ready(self) -> bool:
        return all(p.is_ready() for p in self._pieces)

    def release(self) -> None:
        for p in self._pieces:
            p.release()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.release()


def join_pieces(pieces, stats=None):
    """One pending-shaped object for an extent's ordered pieces: the
    single piece itself (zero-copy) or a :class:`JoinedPieces` host
    assembly.  ``pieces`` must be non-empty."""
    if len(pieces) == 1:
        return pieces[0]
    return JoinedPieces(pieces, stats)


#: per-engine-class cache: does this engine's submit_readv accept the
#: ``klass`` keyword?  In-repo engines all do; a foreign/stub wrapper
#: without it still works (the class tag is dropped, traffic rides the
#: scheduler's default class if one sits below).
_READV_KLASS: dict = {}


def _readv_accepts_klass(engine) -> bool:
    t = type(engine)
    ok = _READV_KLASS.get(t)
    if ok is None:
        import inspect
        try:
            params = inspect.signature(engine.submit_readv).parameters
            ok = "klass" in params or any(
                p.kind is inspect.Parameter.VAR_KEYWORD
                for p in params.values())
        except (TypeError, ValueError):
            ok = False
        _READV_KLASS[t] = ok
    return ok


def submit_spans(engine, spans: Sequence[Tuple[int, int, int]],
                 klass: Optional[str] = None) -> list:
    """Submit planned spans through the engine's vectored path when it
    has one (StromEngine/Resilient/Faulty all do), else per-span —
    returns pending reads aligned with ``spans``.  All-or-nothing
    either way: the C path validates atomically, and the per-span
    fallback releases already-submitted reads before re-raising, so a
    mid-list failure never strands staging buffers.

    ``klass`` tags the batch's latency class (io/sched.py: ``decode`` >
    ``restore`` > ``prefetch`` > ``scan`` > ``scrub``); on a sharded
    engine the QoS
    scheduler dispatches accordingly, and the resilience layer applies
    that class's hedge/retry budgets.  None rides the default class.

    Failure-domain fallback (io/health.py, docs/RESILIENCE.md): when
    the engine's supervisor reports the DEVICE degraded (every ring
    breaker open, or the error budget blown across domains), the batch
    is served as plain synchronous buffered preads instead — bypassing
    the engine, the scheduler, AND any Faulty/Resilient wrapper above
    it, exactly like host-cache hits do — so serving browns out at
    reduced bandwidth instead of blacking out.  One half-open probe
    per interval rides the real path; its success restores the fast
    path for the very batch that probed."""
    sup = getattr(engine, "supervisor", None)
    if sup is not None:
        sup.tick()
        if sup.degraded():
            out = sup.serve_degraded(engine, spans)
            if out is not None:
                return out      # still degraded (None = probe healed)
    readv = getattr(engine, "submit_readv", None)
    if readv is not None:
        if klass is not None and _readv_accepts_klass(engine):
            return readv(spans, klass=klass)
        return readv(spans)
    out: list = []
    try:
        for fh, off, ln in spans:
            out.append(engine.submit_read(fh, off, ln))
    except BaseException:
        for p in out:
            p.release()
        raise
    return out


def plan_and_submit(engine, extents: Sequence[Tuple[int, int, int]], *,
                    gap: Optional[int] = None, split_unit: int = 1,
                    chunk_bytes: Optional[int] = None,
                    klass: Optional[str] = None, hot: bool = False
                    ) -> List[List[SpanView]]:
    """Plan ``(fh, offset, length)`` extents, submit the spans as ONE
    batch, and return — aligned with the input — each extent's ordered
    list of :class:`SpanView` pieces (one piece unless the extent was
    split; empty list for zero-length extents).

    The split size defaults to the engine's ``chunk_bytes`` (its
    staging capacity); pass ``chunk_bytes`` to pin a smaller one.
    Coalescing counts into ``StromStats.spans_coalesced``.

    ``klass`` is the batch's latency class (see :func:`submit_spans`) —
    the one knob consumers use to tag their traffic for the QoS
    scheduler and the per-class resilience budgets.

    When the pinned-host tier is on (``STROM_HOSTCACHE_MB``,
    io/hostcache.py) each extent is first split into HIT spans — served
    as zero-copy views over resident cache lines, bypassing the engine
    (and any Faulty/Resilient wrapper) entirely — and MISS spans, which
    ride the planner/scheduler exactly as below and fill the cache on
    completion behind the admission gate.  Record-unit-pinned plans
    (``split_unit > 1``) bypass the tier: line boundaries cannot
    guarantee unit-aligned pieces.

    ``hot`` declares the batch latency-critical REPEAT traffic (the KV
    prefix store's page restores): tier lines it touches are admitted
    on first miss (no ghost round) and pinned sticky under the class's
    residency quota — hot prefix pages ride DRAM on the next restore
    instead of rotating out behind a bulk scan (docs/PERF.md §5).  With
    the tier off it changes nothing.
    """
    if chunk_bytes is None:
        chunk_bytes = engine.config.chunk_bytes
    if split_unit == 1:
        from nvme_strom_tpu.io import hostcache
        cache = hostcache.get_cache(engine)
        if cache is not None:
            return _plan_and_submit_tiered(cache, engine, extents,
                                           gap=gap,
                                           chunk_bytes=chunk_bytes,
                                           klass=klass, hot=hot)
    plan = plan_extents(extents, chunk_bytes=chunk_bytes, gap=gap,
                        split_unit=split_unit)
    pendings = submit_spans(engine, plan.spans, klass=klass)
    shared = _share_spans(pendings, plan.placements)
    out = [_views_for(shared, pieces, fh, off)
           for (fh, off, _ln), pieces in zip(extents, plan.placements)]
    stats = getattr(engine, "stats", None)
    if stats is not None and plan.spans_coalesced:
        stats.add(spans_coalesced=plan.spans_coalesced)
    if stats is not None and plan.gap_bytes:
        from nvme_strom_tpu.obs.ledger import charge_waste
        charge_waste(stats, "coalesce_gap", plan.gap_bytes)
    return out


def _fill_keys_for_span(cache, fkey, admitted: dict, s_off: int,
                        s_ln: int) -> dict:
    """Admitted line keys (→ admission epoch) whose fill data this
    span's completion can provide (line starts covered from their
    beginning)."""
    lb = cache.line_bytes
    start = s_off if s_off % lb == 0 else s_off - s_off % lb + lb
    return {(fkey, lo): admitted[(fkey, lo)]
            for lo in range(start, s_off + s_ln, lb)
            if (fkey, lo) in admitted}


def _share_spans(pendings, placements) -> list:
    """Refcount each submitted span by the pieces cut from it — the
    release unit both submit paths share (the span's request frees when
    the LAST view does)."""
    refs = [0] * len(pendings)
    for pieces in placements:
        for si, _, _ in pieces:
            refs[si] += 1
    return [_SharedSpan(p, max(1, r)) for p, r in zip(pendings, refs)]


def _views_for(shared, pieces, fh: int, start_off: int) -> list:
    """One placement's ordered pieces → SpanViews (offsets advance from
    ``start_off`` piece by piece)."""
    views = []
    pos = 0
    for si, lo, hi in pieces:
        views.append(SpanView(shared[si], lo, hi, fh, start_off + pos))
        pos += hi - lo
    return views


def _plan_and_submit_tiered(cache, engine, extents, *, gap, chunk_bytes,
                            klass, hot: bool = False
                            ) -> List[List[SpanView]]:
    """The host-tier path of :func:`plan_and_submit`: probe each extent
    against the cache, serve hit spans as pinned zero-copy line views,
    plan+submit only the miss spans (which fill admitted lines when
    they complete)."""
    from nvme_strom_tpu.io.hostcache import (CacheHitRead, _FillOnWait,
                                             file_key_of)
    stats = getattr(engine, "stats", None)
    tracer = getattr(engine, "tracer", None)
    if tracer is not None and not tracer.enabled:
        tracer = None
    for i, (_fh, _off, ln) in enumerate(extents):
        if ln < 0:   # validate BEFORE probing: probes pin cache lines
            raise ValueError(f"extent {i}: negative length {ln}")
    fkeys: dict = {}
    segs_all: List[list] = []
    miss_exts: List[Tuple[int, int, int]] = []
    admitted: dict = {}      # line key → admission-time epoch
    for fh, off, ln in extents:
        if ln == 0:
            segs_all.append([])
            continue
        if fh not in fkeys:
            fkeys[fh] = file_key_of(engine, fh)
        fkey = fkeys[fh]
        if fkey is None:
            segs = [("miss", off, ln)]
        else:
            segs, adm = cache.probe_range(fkey, off, ln, klass, stats,
                                          hot=hot)
            admitted.update(adm)
        segs_all.append(segs)
        for s in segs:
            if s[0] == "miss":
                miss_exts.append((fh, s[1], s[2]))
    try:
        plan = plan_extents(miss_exts, chunk_bytes=chunk_bytes, gap=gap)
        pendings = submit_spans(engine, plan.spans, klass=klass)
    except BaseException:
        for segs in segs_all:       # pinned hits must not leak
            for s in segs:
                if s[0] == "hit":
                    cache.unpin(s[3])
        raise
    wrapped = []
    for (fh, s_off, s_ln), p in zip(plan.spans, pendings):
        fkey = fkeys.get(fh)
        keys = (_fill_keys_for_span(cache, fkey, admitted, s_off, s_ln)
                if fkey is not None and admitted else {})
        wrapped.append(_FillOnWait(p, cache, fkey, s_off, keys, klass,
                                   stats, sticky=hot, tracer=tracer)
                       if keys else p)
    shared = _share_spans(wrapped, plan.placements)
    out: List[List[SpanView]] = []
    hit_bytes = hit_count = 0
    mi = 0
    for (fh, _off, ln), segs in zip(extents, segs_all):
        pieces_out: list = []
        for s in segs:
            if s[0] == "hit":
                _, a, sl, line = s
                rel = a - line.key[1]
                pieces_out.append(CacheHitRead(cache, line, rel,
                                               rel + sl, fh, a))
                hit_bytes += sl
                hit_count += 1
            else:
                _, a, _sl = s
                pieces_out.extend(_views_for(shared,
                                             plan.placements[mi], fh, a))
                mi += 1
        out.append(pieces_out)
    if tracer is not None and hit_count:
        # one aggregate span per probed batch (per-line spans would
        # dominate the trace on a hot run): the DRAM-served portion of
        # this batch, causally under the requester
        import time as _time
        now = _time.monotonic_ns()
        tracer.add_span("strom.cache.hit", now, now,
                        category="strom.cache", klass=klass,
                        hits=hit_count, bytes=hit_bytes)
    if stats is not None and plan.spans_coalesced:
        stats.add(spans_coalesced=plan.spans_coalesced)
    if stats is not None and plan.gap_bytes:
        from nvme_strom_tpu.obs.ledger import charge_waste
        charge_waste(stats, "coalesce_gap", plan.gap_bytes)
    return out


def submit_spans_tiered(engine, spans: Sequence[Tuple[int, int, int]],
                        klass: Optional[str] = None) -> list:
    """:func:`submit_spans` with the pinned-host tier in front: spans
    fully resident in ONE cache line return as ready zero-copy cache
    views (no engine submission, no retry/hedge), the rest submit as
    one vectored batch exactly like :func:`submit_spans` — and fill
    admitted lines when they complete.  This is the refill primitive of
    ``DeviceStream.stream_ranges``, which is how kv_offload/opt_offload/
    pq_direct streams get the tier; with the tier off it IS
    ``submit_spans``."""
    from nvme_strom_tpu.io import hostcache
    cache = hostcache.get_cache(engine)
    if cache is None:
        return submit_spans(engine, spans, klass=klass)
    from nvme_strom_tpu.io.hostcache import (CacheHitRead, _FillOnWait,
                                             file_key_of)
    stats = getattr(engine, "stats", None)
    tracer = getattr(engine, "tracer", None)
    if tracer is not None and not tracer.enabled:
        tracer = None
    spans = list(spans)
    out: list = [None] * len(spans)
    miss: list = []
    meta: list = []    # (out index, fkey, admitted keys)
    fkeys: dict = {}
    hit_bytes = hit_count = 0
    for i, (fh, off, ln) in enumerate(spans):
        if fh not in fkeys:
            fkeys[fh] = file_key_of(engine, fh)
        fkey = fkeys[fh]
        line = None
        adm: dict = {}
        if fkey is not None and ln > 0:
            line, adm = cache.probe_span(fkey, off, ln, klass, stats)
        if line is not None:
            rel = off - line.key[1]
            out[i] = CacheHitRead(cache, line, rel, rel + ln, fh, off)
            hit_bytes += ln
            hit_count += 1
        else:
            miss.append((fh, off, ln))
            meta.append((i, fkey, adm))
    if tracer is not None and hit_count:
        import time as _time
        now = _time.monotonic_ns()
        tracer.add_span("strom.cache.hit", now, now,
                        category="strom.cache", klass=klass,
                        hits=hit_count, bytes=hit_bytes)
    try:
        pendings = submit_spans(engine, miss, klass=klass)
    except BaseException:
        for p in out:
            if p is not None:
                p.release()
        raise
    for (i, fkey, adm), p in zip(meta, pendings):
        fh, off, ln = spans[i]
        keys = (_fill_keys_for_span(cache, fkey, adm, off, ln)
                if fkey is not None and adm else {})
        out[i] = _FillOnWait(p, cache, fkey, off, keys, klass,
                             stats, tracer=tracer) if keys else p
    return out

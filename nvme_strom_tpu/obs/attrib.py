"""Critical-path attribution — where every microsecond of a request
went (docs/OBSERVABILITY.md §4).

PR 11's causal tracer answers "what happened" one Perfetto load at a
time; production triage needs the folded form: *this* decode request
spent 1.2 ms queued at the scheduler, 3.4 ms on NVMe, 0.8 ms in retry
backoff, and the remainder in compute.  This module is that fold:

  collect    an :class:`AttributionCollector` attaches to a
             :class:`~nvme_strom_tpu.utils.trace.Tracer` as a span
             SINK (``Tracer.add_sink``) and buffers each trace's spans
             — bounded per trace and across traces, with drops counted
             (``attrib_spans_dropped``).  Sink delivery works with NO
             export path, so ``STROM_ATTRIB=1`` prices only the span
             emit + a dict append, never a trace file.
  fold       at request retire (models/serving.py calls
             :meth:`AttributionCollector.request_retired`) the trace's
             spans fold into the FIXED component breakdown below.
             Per-component intervals are clipped to the request window
             and interval-UNIONED, so N parallel reads charge their
             covered wall time once; ``unattributed`` is the wall time
             no component covers (compute, host work, scheduling gaps)
             — by construction ``coverage + unattributed == wall``,
             the conservation invariant tests pin within 1%.
  aggregate  folds land in rolling per-QoS-class profiles: one
             :class:`~nvme_strom_tpu.utils.stats.Log2Histogram` (µs)
             per (class, component) yields p50/p99 per component, the
             view ``/attrib`` serves and ``strom-top`` renders.

Components (span-name mapping in ``NAME_TO_COMPONENT``):

  ``sched_queue``    QoS-scheduler queue wait (``strom.sched.queue``)
  ``hostcache``      pinned-host tier hits + fills (``strom.cache.*``)
  ``nvme_read``      engine device time (``strom.read[.fallback]``,
                     ``strom.write``)
  ``retry_backoff``  resilient retry + backoff (``strom.resilient.retry``)
  ``hedge``          hedge submissions/races (``strom.resilient.hedge*``)
  ``degraded``       buffered brown-out service (``strom.read.degraded``,
                     ``strom.health.*``)
  ``bridge``         host→HBM hop (``strom.bridge.hop``, ``strom.h2d*``)
  ``ici_scatter``    read-once restore shard exchange over the
                     interconnect (``strom.ici.*`` — ops/ici.py)
  ``prefill``        the admission's prefill call (``strom.serve.prefill``
                     — models/serving.py), so a store-less request no
                     longer folds whole into ``unattributed``
  ``unattributed``   wall time outside every component (decode steps,
                     host work, scheduling gaps)

Activation: ``STROM_ATTRIB=1`` (default off) builds the process-wide
collector; every engine attaches it to its tracer, serving folds at
retire.  ``STROM_ATTRIB=0``/unset is the exact pre-attribution stack.
"""

from __future__ import annotations

import os
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Tuple

from nvme_strom_tpu.utils.lockwitness import make_lock

#: the fixed breakdown, in render order (``unattributed`` is derived,
#: always last)
COMPONENTS = ("sched_queue", "hostcache", "nvme_read", "retry_backoff",
              "hedge", "degraded", "bridge", "ici_scatter", "prefill")

#: span name → component.  Prefix matching (see :func:`component_of`)
#: keeps future ``strom.resilient.*`` names in the right bucket.
NAME_TO_COMPONENT = {
    "strom.sched.queue": "sched_queue",
    "strom.cache.hit": "hostcache",
    "strom.cache.fill": "hostcache",
    "strom.read": "nvme_read",
    "strom.read.fallback": "nvme_read",
    "strom.write": "nvme_read",
    "strom.read.degraded": "degraded",
    "strom.health.probe": "degraded",
    "strom.health.ring_restart": "degraded",
    "strom.resilient.retry": "retry_backoff",
    "strom.resilient.write_retry": "retry_backoff",
    "strom.resilient.hedge": "hedge",
    "strom.resilient.hedge_won": "hedge",
    "strom.bridge.hop": "bridge",
    "strom.serve.prefill": "prefill",
    "strom.ici.exchange": "ici_scatter",
    "strom.ici.scatter": "ici_scatter",
}

#: serving/root spans: structure, not a cost component — excluded from
#: the fold so the admission span (which CONTAINS prefill + engine I/O)
#: cannot shadow the whole window as one component.  An exact entry of
#: ``NAME_TO_COMPONENT`` (``strom.serve.prefill``) is looked up first.
_STRUCTURAL = ("strom.serve.",)


def component_of(name: str) -> Optional[str]:
    """The attribution component of a span name (None = structural or
    unknown — contributes to ``unattributed`` only)."""
    c = NAME_TO_COMPONENT.get(name)
    if c is not None:
        return c
    for prefix in _STRUCTURAL:
        if name.startswith(prefix):
            return None
    if name.startswith("strom.resilient."):
        return "retry_backoff"
    if name.startswith("strom.h2d"):
        return "bridge"
    if name.startswith("strom.ici."):
        return "ici_scatter"
    return None


def _merge_intervals(ivals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Sorted interval union (the double-count guard: two parallel
    reads of one request charge their covered wall time once)."""
    if not ivals:
        return []
    ivals.sort()
    out = [list(ivals[0])]
    for b, e in ivals[1:]:
        if b <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([b, e])
    return [(b, e) for b, e in out]


def _union_ns(ivals: List[Tuple[int, int]]) -> int:
    return sum(e - b for b, e in _merge_intervals(list(ivals)))


def fold_events(spans, t0_ns: int, t1_ns: int) -> dict:
    """Fold one request's spans — ``(name, begin_ns, end_ns)`` tuples —
    over the request window ``[t0_ns, t1_ns)`` into the component
    breakdown (all values µs).

    Per-component times are interval unions clipped to the window;
    ``coverage_us`` is the union ACROSS components, ``unattributed_us``
    the uncovered remainder — so ``coverage + unattributed == wall``
    exactly, and with no cross-component overlap (sequential
    deterministic runs) the per-component sum equals the coverage.
    ``overlap_us`` reports cross-component parallelism (per-component
    sum minus coverage) so the conservation check can tell parallel
    I/O from accounting error."""
    wall = max(0, t1_ns - t0_ns)
    per: Dict[str, List[Tuple[int, int]]] = {}
    everything: List[Tuple[int, int]] = []
    for name, b, e in spans:
        comp = component_of(name)
        if comp is None:
            continue
        b, e = max(b, t0_ns), min(e, t1_ns)
        if e <= b:
            continue
        per.setdefault(comp, []).append((b, e))
        everything.append((b, e))
    comps = {c: _union_ns(iv) / 1000.0 for c, iv in per.items()}
    coverage = _union_ns(everything) / 1000.0
    comp_sum = sum(comps.values())
    return {
        "wall_us": wall / 1000.0,
        "components": {c: round(comps.get(c, 0.0), 3)
                       for c in COMPONENTS},
        "coverage_us": round(coverage, 3),
        "unattributed_us": round(wall / 1000.0 - coverage, 3),
        "overlap_us": round(max(0.0, comp_sum - coverage), 3),
        "spans": len(spans),
    }


class AttributionCollector:
    """Bounded span buffer + per-class rolling attribution profiles.

    ``sink`` is the :meth:`Tracer.add_sink` callable: one dict per
    completed span, buffered under the span's trace id.  Traces are
    LRU-bounded (``max_traces``) — a request that never retires (a
    crash, an abandoned trace) ages out instead of leaking — and each
    trace keeps at most ``max_spans`` spans (drops counted).
    """

    #: retired folds kept for the flight recorder's dump summary and
    #: the ``/attrib`` recent view
    _RECENT = 64

    def __init__(self, max_traces: int = 256, max_spans: int = 1024,
                 stats=None):
        self._lock = make_lock("attrib.AttributionCollector._lock")
        self.max_traces = max_traces
        self.max_spans = max_spans
        #: trace id (hex string, as stamped in span args) → span tuples
        self._traces: "OrderedDict[str, list]" = OrderedDict()
        self.stats = stats
        self.dropped = 0
        self.requests = 0
        #: (klass, component) → Log2Histogram in µs — the Log2Histogram
        #: reuse the per-component p50/p99 rides on
        self._hists: Dict[Tuple[str, str], object] = {}
        #: (klass, component) → cumulative µs (exact totals next to the
        #: bucketed percentiles)
        self._totals: Dict[Tuple[str, str], float] = {}
        self._class_n: Dict[str, int] = {}
        self._recent: deque = deque(maxlen=self._RECENT)

    # -- collection (the Tracer sink) --------------------------------------

    def sink(self, ev: dict) -> None:
        """One completed span event (hot-ish path: one dict lookup, one
        list append under the lock; spans without a trace id — the
        flat, request-less majority of a bulk run — return in two
        lookups)."""
        if ev.get("ph") == "C":
            return
        args = ev.get("args")
        if not args:
            return
        tid = args.get("trace")
        if tid is None:
            return
        b_ns = int(ev["ts"] * 1000.0)
        e_ns = b_ns + int(ev.get("dur", 0.0) * 1000.0)
        dropped = 0
        with self._lock:
            spans = self._traces.get(tid)
            if spans is None:
                while len(self._traces) >= self.max_traces:
                    self._traces.popitem(last=False)
                spans = self._traces[tid] = []
            else:
                # true LRU: an actively-emitting long-lived request
                # must outlive abandoned traces created after it, or
                # its retire fold reads as all-unattributed
                self._traces.move_to_end(tid)
            if len(spans) >= self.max_spans:
                self.dropped += 1
                dropped = 1
            else:
                spans.append((ev["name"], b_ns, e_ns))
        if dropped and self.stats is not None:
            self.stats.add(attrib_spans_dropped=dropped)

    # -- the retire-time fold ----------------------------------------------

    def request_retired(self, trace_id, t0_ns: int, t1_ns: int,
                        klass: str = "decode",
                        extra: Optional[dict] = None) -> dict:
        """Fold the retired request's span tree and roll it into the
        ``klass`` profile.  ``trace_id``: the root TraceContext's id
        (int) or the hex string its spans were stamped with.  Returns
        the fold (tests and the caller's own logging use it)."""
        tid = trace_id if isinstance(trace_id, str) else f"{trace_id:x}"
        with self._lock:
            spans = self._traces.pop(tid, [])
        fold = fold_events(spans, t0_ns, t1_ns)
        fold["klass"] = klass
        if extra:
            fold.update(extra)
        with self._lock:
            self.requests += 1
            self._class_n[klass] = self._class_n.get(klass, 0) + 1
            for comp in list(fold["components"]) + ["unattributed"]:
                us = (fold["unattributed_us"] if comp == "unattributed"
                      else fold["components"][comp])
                key = (klass, comp)
                self._totals[key] = self._totals.get(key, 0.0) + us
                if us > 0:
                    h = self._hists.get(key)
                    if h is None:
                        from nvme_strom_tpu.utils.stats import \
                            Log2Histogram
                        h = self._hists[key] = Log2Histogram(
                            f"strom_attrib_{klass}_{comp}_us",
                            "per-request component time (µs)")
                    h.observe(us)
            key = (klass, "wall")
            self._totals[key] = self._totals.get(key, 0.0) \
                + fold["wall_us"]
            h = self._hists.get(key)
            if h is None:
                from nvme_strom_tpu.utils.stats import Log2Histogram
                h = self._hists[key] = Log2Histogram(
                    f"strom_attrib_{klass}_wall_us",
                    "per-request wall time (µs)")
            h.observe(max(fold["wall_us"], 0))
            self._recent.append(fold)
        if self.stats is not None:
            self.stats.add(attrib_requests=1)
        return fold

    # -- views --------------------------------------------------------------

    def profiles(self) -> dict:
        """The rolling per-class attribution profiles: per component
        p50/p99 (µs), cumulative µs, mean share of wall — what
        ``/attrib`` serves and ``strom-top`` renders."""
        with self._lock:
            classes = sorted(self._class_n)
            out: dict = {"requests": self.requests,
                         "spans_dropped": self.dropped,
                         "classes": {}}
            for kl in classes:
                n = self._class_n[kl]
                wall_total = max(self._totals.get((kl, "wall"), 0.0),
                                 1e-9)
                comps = {}
                for comp in list(COMPONENTS) + ["unattributed"]:
                    key = (kl, comp)
                    total = self._totals.get(key, 0.0)
                    h = self._hists.get(key)
                    comps[comp] = {
                        "p50_us": h.percentile(50) if h is not None else 0,
                        "p99_us": h.percentile(99) if h is not None else 0,
                        "total_us": round(total, 1),
                        "share": round(total / wall_total, 4),
                    }
                wh = self._hists.get((kl, "wall"))
                out["classes"][kl] = {
                    "n": n,
                    "wall_p50_us": wh.percentile(50) if wh else 0,
                    "wall_p99_us": wh.percentile(99) if wh else 0,
                    "wall_total_us": round(wall_total, 1),
                    "components": comps,
                }
            return out

    def summary(self) -> dict:
        """Compact recent-request summary for flight-recorder dumps:
        the last few folds plus per-class mean component shares."""
        with self._lock:
            recent = list(self._recent)[-8:]
        prof = self.profiles()
        shares = {kl: {c: v["share"]
                       for c, v in blk["components"].items()}
                  for kl, blk in prof["classes"].items()}
        return {"requests": prof["requests"], "shares": shares,
                "recent": recent}

    def clear(self) -> None:
        with self._lock:
            self._traces.clear()
            self._hists.clear()
            self._totals.clear()
            self._class_n.clear()
            self._recent.clear()
            self.requests = 0
            self.dropped = 0


# ---------------------------------------------------------------------------
# process-wide collector (STROM_ATTRIB)
# ---------------------------------------------------------------------------

_singleton_lock = make_lock("attrib._singleton_lock")
_collector: Optional[AttributionCollector] = None
_collector_init = False


def get_collector() -> Optional[AttributionCollector]:
    """The process-wide collector when ``STROM_ATTRIB=1`` (default off:
    None, zero overhead — the exact pre-attribution stack).  Engines
    attach it to their tracer at construction; serving folds at
    retire."""
    global _collector, _collector_init
    if _collector_init:
        return _collector
    with _singleton_lock:
        if not _collector_init:
            if os.environ.get("STROM_ATTRIB", "0") == "1":
                _collector = AttributionCollector()
            _collector_init = True
        return _collector


def reset() -> None:
    """Drop the singleton; the next :func:`get_collector` re-reads the
    environment (tests toggle attribution this way).  Sinks already
    attached to tracers keep feeding the old collector — tests that
    reset should also detach (``tracer.remove_sink``)."""
    global _collector, _collector_init
    with _singleton_lock:
        _collector = None
        _collector_init = False


def attach(tracer, stats=None) -> Optional[AttributionCollector]:
    """Wire the process collector (if enabled) into ``tracer`` as a
    span sink — idempotent; the engine-construction hook."""
    col = get_collector()
    if col is None or tracer is None:
        return None
    if stats is not None and col.stats is None:
        col.stats = stats
    tracer.add_sink(col.sink)
    return col

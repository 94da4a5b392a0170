"""QoS-aware I/O scheduler — latency classes over the multi-ring engine.

Under production mixed traffic every consumer used to funnel through ONE
io_uring ring: a scrub or bulk-prefetch storm queued ahead of
decode-critical KV reads and the p99 the serving path promised was gone.
The engine now shards into N rings over one global staging pool
(``strom_engine_create_rings``, ``EngineConfig.n_rings``); this module
decides WHICH planned batch goes to WHICH ring, and WHEN:

  classes     every planned batch carries a latency class —
              ``decode`` > ``restore`` > ``prefetch`` > ``scan`` >
              ``scrub`` (priority order).  Consumers tag their traffic at the
              ``io/plan.py`` boundary (``plan_and_submit(...,
              klass=...)``); untagged batches ride the default
              ``prefetch`` class so the fair-share always sees the
              whole load.
  fair-share  each dispatch round credits every backlogged class its
              WEIGHT in batches (deficit round-robin, at most one
              round of banking), then serves classes in priority
              order — under contention class shares converge to the
              weight ratio, while an idle system dispatches everything
              immediately.
  aging       a batch stuck longer than ``aging_rounds`` dispatch
              rounds is promoted ahead of every weight/priority
              consideration: the starvation bound.  Even a weight-0
              class completes within K rounds of queueing
              (tests/test_sched.py proves it).
  admission   a ring accepts a batch while its in-flight I/O
              (submitted - COMPLETED, lock-free C counters) is under
              the per-ring budget; batches pick the least-loaded
              eligible ring.  Completion — not release — frees
              capacity, so a consumer sitting on completed views can
              never wedge admission (deadlock-free by construction).

Dispatch is split grant/execute: the scheduler lock covers only the
ADMISSION DECISION (which batch, which ring, when), and each owner
thread performs its own engine submission outside the lock — concurrent
submitters overlap exactly as they would with no scheduler, so the QoS
layer adds ordering, never serialization.  ``submit()`` blocks until
the caller's batch is granted, and the blocked thread helps run grant
rounds, so higher-priority batches queued by other threads are granted
first — exactly the admission control that keeps a scrub storm out of
the decode class's way.  Per-class hedge/retry budgets live in
``io/resilient.py`` (``ResilientEngine(class_configs=...)``) keyed by
the same class names.

Every decision is accounted: ``StromStats.sched_*`` counters, per-class
dispatch/queue-wait tallies (``class_stats`` in the export), and
per-ring depth gauges — rendered by ``strom_stat``'s scheduler block
and watchdog dumps.

Failure domains (io/health.py, docs/RESILIENCE.md): the ``ring_free``
callback the engine binds here is supervision-aware — a ring whose
circuit breaker is OPEN reports zero admission headroom, so every
queued batch routes to healthy rings until the hot restart brings the
ring back half-open; the admission poll doubles as the supervision
heartbeat (time-gated ``tick`` inside the callback).  The scheduler
itself never sees an all-masked ring set: the device-level breaker
(whose open state diverts traffic to the degraded buffered path at the
planner boundary, above this layer) is decided atomically with the
last ring trip.
"""

from __future__ import annotations

import errno
import threading
import time
from collections import deque
from dataclasses import dataclass, replace
from typing import Callable, Dict, List, Optional, Sequence

from nvme_strom_tpu.io.tenants import current_tenant
from nvme_strom_tpu.utils.lockwitness import make_condition, make_lock

#: priority order, highest first — the serving decode path outranks
#: checkpoint/weight restore, which outranks loader prefetch, which
#: outranks analytics scans (sql/), which outrank background scrub
CLASS_ORDER = ("decode", "restore", "prefetch", "scan", "scrub")

#: class every untagged batch rides (bulk by assumption)
DEFAULT_CLASS = "prefetch"


@dataclass(frozen=True)
class ClassPolicy:
    """One latency class's scheduling + resilience-budget policy.

    ``weight``: fair-share credits per dispatch round (batches).
    ``hedge_budget``: max CONCURRENT hedged duplicate reads this class
    may hold (io/resilient.py enforces it — a scrub storm exhausting
    its own budget can never eat the decode class's hedges).
    ``max_retries``: per-class override of ResilientConfig.max_retries
    (None = inherit the engine-wide value).
    """

    name: str
    priority: int          # position in CLASS_ORDER; lower serves first
    weight: float = 1.0
    hedge_budget: int = 4
    max_retries: Optional[int] = None

    def __post_init__(self):
        if self.weight < 0:
            raise ValueError(f"weight ({self.weight}) must be >= 0")
        if self.hedge_budget < 0:
            raise ValueError("hedge_budget must be >= 0")


def default_policies(weights: str = "") -> Dict[str, ClassPolicy]:
    """The five stock policies; ``weights`` ("decode=8,scrub=1")
    overrides weights per class (SchedConfig.class_weights).

    ``scan`` is the analytics class (sql/ Direct SQL scans — partition-
    parallel workers all submit here): same weight as prefetch so a
    table scan and a loader share bulk bandwidth evenly, but BELOW it
    in priority — an aggressor scan drains after serving-adjacent
    prefetch, and far after decode (tests/test_sql_scan.py proves the
    decode-under-scan-storm bound)."""
    pol = {
        "decode": ClassPolicy("decode", 0, weight=8.0, hedge_budget=8),
        "restore": ClassPolicy("restore", 1, weight=4.0, hedge_budget=4),
        "prefetch": ClassPolicy("prefetch", 2, weight=2.0, hedge_budget=2),
        "scan": ClassPolicy("scan", 3, weight=2.0, hedge_budget=2),
        "scrub": ClassPolicy("scrub", 4, weight=1.0, hedge_budget=1),
    }
    for part in filter(None, (s.strip() for s in weights.split(","))):
        name, eq, val = part.partition("=")
        name = name.strip()
        if not eq or name not in pol:
            raise ValueError(
                f"STROM_CLASS_WEIGHTS entry {part!r}: expected "
                f"<class>=<weight> with class in {CLASS_ORDER}")
        pol[name] = replace(pol[name], weight=float(val))
    return pol


class _Batch:
    """One planned batch queued for a dispatch grant."""

    __slots__ = ("spans", "klass", "rounds", "granted", "ring",
                 "promoted", "t_enq", "t_enq_ns", "ctx", "tenant")

    def __init__(self, spans, klass: str, ctx=None):
        self.spans = spans
        self.klass = klass
        self.rounds = 0          # dispatch rounds survived ungranted
        self.granted = False     # admission decision made
        self.ring: Optional[int] = None
        self.promoted = False    # granted via the aging bound
        self.t_enq = time.monotonic()
        self.t_enq_ns = time.monotonic_ns()
        #: requester's TraceContext, captured at enqueue — the grant may
        #: run on ANOTHER thread's dispatch round, so the queue-wait
        #: span carries its causal identity explicitly
        self.ctx = ctx
        #: owning Tenant, captured from the tenant contextvar exactly
        #: like the trace context (None outside any tenant scope — the
        #: whole hierarchical layer below then stays inert)
        self.tenant = current_tenant()


class QoSScheduler:
    """Weighted fair-share + aging dispatcher over N rings.

    ``submit_ring(spans, ring) -> pendings`` performs the actual engine
    submission (StromEngine binds its ring-pinned vectored submit);
    ``ring_free() -> [free slots per ring]`` reports admission headroom.
    Both are injectable, so the dispatch logic is testable with no
    hardware and no engine (tests/test_sched.py drives ``step()``
    directly).
    """

    #: helper-drain poll slice while waiting for ring capacity — I/O
    #: completion frees capacity asynchronously and is not signalled
    _POLL_S = 0.002

    def __init__(self, submit_ring: Callable[[Sequence, int], list],
                 ring_free: Callable[[], List[int]],
                 policies: Optional[Dict[str, ClassPolicy]] = None,
                 aging_rounds: int = 16, stats=None,
                 ring_cap: Optional[int] = None, tracer=None):
        if aging_rounds < 1:
            raise ValueError("aging_rounds must be >= 1")
        self._submit_ring = submit_ring
        self._ring_free = ring_free
        self.policies = policies or default_policies()
        self.aging_rounds = aging_rounds
        self.stats = stats
        #: span sink for queue-wait attribution (strom.sched.queue);
        #: None = no tracing overhead on dispatch
        self.tracer = tracer
        #: per-ring admission budget (what a fully idle ring reports
        #: free) — lets the urgent-ring rule tell "ring 0 is idle" from
        #: "every ring is equally saturated"
        self.ring_cap = ring_cap
        self._order = sorted(self.policies,
                             key=lambda k: self.policies[k].priority)
        self._queues: Dict[str, deque] = {k: deque() for k in self._order}
        self._deficit: Dict[str, float] = {k: 0.0 for k in self._order}
        # hierarchical fair-share inner level (class × tenant): per
        # class, each tenant's accumulated grant cost (1/effective
        # weight per grant — lowest bank serves next).  Empty, and the
        # pick short-circuits to exact FIFO, until the first batch that
        # actually carries a tenant flips _tenant_seen.
        self._tenant_credit: Dict[str, Dict] = {}
        self._tenant_seen = False
        self._granted_out: Dict[int, int] = {}  # ring -> spans granted,
        #                                         not yet engine-submitted
        self._closed = False
        self._lock = make_lock("sched.QoSScheduler._lock")
        self._cv = make_condition("sched.QoSScheduler._cv", self._lock)
        # counters mirrored into StromStats when one is attached
        self.dispatches = 0
        self.promotions = 0
        self.enqueued = 0
        # Perfetto counter-track sampling gate (docs/OBSERVABILITY.md):
        # per-class queue depth lands on the trace timeline at most
        # every 20 ms, so a hot dispatch loop never floods the file
        self._next_counter_t = 0.0

    # -- public API --------------------------------------------------------

    def enqueue(self, spans: Sequence, klass: Optional[str] = None
                ) -> _Batch:
        """Queue one planned batch for a grant WITHOUT waiting (tests
        drive ``step()`` against this; ``submit()`` is the blocking
        production path)."""
        if klass not in self.policies:
            klass = DEFAULT_CLASS
        # NO_CONTEXT, not None, when untraced/out-of-scope: the grant
        # may run on ANOTHER request's thread, and ctx=None at emit
        # would auto-adopt that request's context (mis-attribution)
        from nvme_strom_tpu.utils.trace import NO_CONTEXT, attach_context
        ctx = NO_CONTEXT
        if self.tracer is not None and self.tracer.enabled:
            ctx = attach_context()
        b = _Batch(list(spans), klass, ctx=ctx)
        with self._cv:
            if self._closed:
                raise OSError(errno.ECANCELED,
                              "engine closing: scheduler shut down")
            self._queues[klass].append(b)
            if b.tenant is not None:
                self._tenant_seen = True
            self.enqueued += 1
            if self.stats is not None:
                self.stats.add(sched_enqueued=1)
        return b

    def submit(self, spans: Sequence, klass: Optional[str] = None) -> list:
        """Queue one planned batch under ``klass``, block until the
        scheduler GRANTS it a ring, then perform the engine submission
        — outside the scheduler lock, so concurrent submitters overlap
        exactly as they would with no scheduler (the lock covers only
        the admission decision).  Returns the engine pendings aligned
        with ``spans``; raises whatever the engine submission raised."""
        b = self.enqueue(spans, klass)
        with self._cv:
            while not b.granted:
                if self._closed:
                    # engine teardown: wake OUT of the grant loop before
                    # the C handle dies under the capacity poll
                    try:
                        self._queues[b.klass].remove(b)
                    except ValueError:
                        pass
                    raise OSError(errno.ECANCELED,
                                  "engine closing: batch never granted")
                self._drain_locked()
                if b.granted:
                    break
                # capacity frees when in-flight I/O completes (lock-free
                # C counters, not signalled): poll in short slices; a
                # grant by another thread's round notifies immediately
                self._cv.wait(timeout=self._POLL_S)
        try:
            out = self._submit_ring(b.spans, b.ring)
            for p in out:
                try:
                    p.op_klass = b.klass   # flight-recorder attribution
                except AttributeError:
                    break   # injected test double without a __dict__
            return out
        finally:
            self.ack_submitted(b)

    def ack_submitted(self, b: _Batch) -> None:
        """Hand a granted batch's capacity charge over to the engine's
        own in-flight counters (call once the engine submission landed
        — ``submit()`` does; tests driving ``enqueue``/``step`` call it
        explicitly)."""
        with self._cv:
            if b.ring is not None:
                self._granted_out[b.ring] = \
                    self._granted_out.get(b.ring, 0) - max(1, len(b.spans))
            self._cv.notify_all()

    def close(self) -> None:
        """Quiesce before engine teardown: every thread blocked in
        ``submit()``'s grant loop wakes and raises ECANCELED instead of
        polling ring state on a handle about to be destroyed.  Further
        submissions are refused.  StromEngine.close_all calls this
        first."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    def step(self) -> bool:
        """Run ONE dispatch round (test/diagnostic hook); True if any
        batch was granted a ring."""
        with self._cv:
            return self._dispatch_round_locked()

    def queued(self) -> Dict[str, int]:
        """Per-class queued batch counts (diagnostics)."""
        with self._lock:
            return {k: len(q) for k, q in self._queues.items()}

    def backlog(self) -> Dict[str, Dict[str, float]]:
        """Per-class queue depth with span counts and oldest wait — the
        richer sibling of queued(), built for post-mortem payloads (the
        coldstart_stall flight dump records which lane was starving)."""
        now = time.monotonic()
        out: Dict[str, Dict[str, float]] = {}
        with self._lock:
            for k, q in self._queues.items():
                if not q:
                    continue
                out[k] = {
                    "batches": len(q),
                    "spans": sum(len(b.spans) for b in q),
                    "oldest_wait_s": round(now - q[0].t_enq, 6),
                }
        return out

    def set_weight(self, klass: str, weight: float) -> None:
        """Adjust one class's fair-share weight at runtime — the SLO
        governor's scheduler lever (docs/PERF.md §5): a decode-path p99
        violation temporarily raises the decode class's share, and the
        governor lowers it back when the target is met again.  Priority
        order and the aging bound are untouched, so the starvation
        guarantee survives any weight setting (weight 0 included)."""
        with self._lock:
            p = self.policies.get(klass)
            if p is None:
                raise KeyError(f"unknown class {klass!r} "
                               f"(have {sorted(self.policies)})")
            self.policies[klass] = replace(p, weight=float(weight))

    # -- dispatch core -----------------------------------------------------

    def _pick_index_locked(self, klass: str, q: deque) -> int:
        """Hierarchical DRR, inner (tenant) level: among ONE class's
        queued batches, grant the tenant with the lowest accumulated
        cost bank next (each grant costs 1/effective_weight, so under
        contention tenants split the class's grants by weight ratio —
        the same deficit discipline the outer class level uses).  Ties
        break FIFO; batches outside any tenant scope ride a pseudo
        tenant of weight 1.  Returns the queue index to grant.  With no
        tenant ever seen (STROM_TENANTS=0) this is index 0 — the exact
        pre-tenant FIFO.  The aging pass never calls this: a batch past
        the starvation bound outranks tenant fairness too, which is
        precisely what keeps the proven bound intact at any weight."""
        if not self._tenant_seen or len(q) <= 1:
            return 0
        credits = self._tenant_credit.setdefault(klass, {})
        first: Dict = {}           # tenant id -> its oldest batch index
        for i, b in enumerate(q):
            tid = b.tenant.id if b.tenant is not None else None
            if tid not in first:
                first[tid] = i
        for tid in list(credits):
            if tid not in first:   # departed: a returning tenant must
                del credits[tid]   # not owe (or own) history-old bank
        pick = min(first,
                   key=lambda tid: (credits.get(tid, 0.0), first[tid]))
        return first[pick]

    def _charge_tenant_locked(self, b: _Batch) -> None:
        """Bank one grant's cost against the batch's tenant (called for
        EVERY grant, aged promotions included, so the banks stay an
        honest record of service consumed)."""
        if not self._tenant_seen:
            return
        credits = self._tenant_credit.setdefault(b.klass, {})
        tid = b.tenant.id if b.tenant is not None else None
        w = b.tenant.effective_weight if b.tenant is not None else 1.0
        credits[tid] = credits.get(tid, 0.0) + 1.0 / max(w, 1e-9)
        if len(credits) > 1:
            # floor-normalize so banks measure RELATIVE debt and never
            # grow without bound over a long run
            base = min(credits.values())
            if base > 0:
                for t in credits:
                    credits[t] -= base

    def _drain_locked(self) -> None:
        while any(self._queues.values()):
            if not self._dispatch_round_locked():
                break

    def _dispatch_round_locked(self) -> bool:
        """One dispatch round: aging promotions first, then weighted
        fair-share in priority order, against the rings' current
        admission headroom.  Ages every still-queued batch.  Returns
        True if anything was granted (a False round does NOT age — a
        zero-capacity poll must not burn the starvation budget)."""
        try:
            slots = list(self._ring_free())
        except Exception:
            slots = []
        if not slots:
            return False
        for r, g in self._granted_out.items():
            # granted-but-not-yet-submitted batches already own slots
            if 0 <= r < len(slots):
                slots[r] -= g
        progress = False
        # 0) the TOP class is latency-critical and never admission-
        #    queued: admission control exists to bound BULK traffic
        #    ahead of it, so decode grants immediately to the least-
        #    loaded ring whatever the depths (strict priority over the
        #    fair-shared classes below; its only queueing is the C
        #    ring itself, which the bulk caps keep shallow)
        top_q = self._queues[self._order[0]]
        while top_q:
            # tenant-fair grant ORDER (the ring each batch lands on and
            # the class's unconditional admission are unchanged)
            i = self._pick_index_locked(self._order[0], top_q)
            b = top_q[i]
            # prefer the urgent ring (bulk avoids it, so it is almost
            # always shallow — landing decode anywhere else risks
            # queueing its small reads behind a bulk batch's service
            # tail); spill to the least-loaded ring only when ring 0
            # itself is backed up
            if slots[0] > 0:
                r = 0
            else:
                r = max(range(len(slots)), key=lambda j: slots[j])
            slots[r] -= max(1, len(b.spans))
            del top_q[i]
            self._dispatch_one(b, r)
            progress = True
        if not any(s > 0 for s in slots):
            return progress

        cap = self.ring_cap if self.ring_cap is not None \
            else (max(slots) if slots else 0)
        # Bulk headroom reserve only exists when a ring HAS more than one
        # slot: with cap == 1 (qd_ring=1 topologies, STROM_SCHED_INFLIGHT=1)
        # a reserve of 1 would make every bulk class ungrantable except
        # via aging — the work-conserving guarantee must hold at any cap.
        bulk_reserve = 1 if cap > 1 else 0

        def pick_ring(n_spans: int, reserve: int = 0) -> Optional[int]:
            # least-loaded eligible ring; a whole batch lands on ONE
            # ring (one doorbell), so charge its span count there.
            # ``reserve``: slots a LOWER-priority class must leave free
            # on every ring — the headroom that keeps a bulk storm from
            # filling all admission slots ahead of a decode burst (only
            # the top class and aged promotions may consume it).
            # Ring 0 is the URGENT ring (NVMe WRR-with-urgent-class
            # arbitration): bulk classes treat it as a LAST RESORT —
            # eligible only when no other ring has headroom AND ring 0
            # is completely idle (work-conserving: an engine with no
            # latency-critical traffic still uses every ring) — so an
            # active decode stream owns a ring's worth of service
            # capacity instead of intermittently queueing behind a
            # bulk batch that grabbed the idle urgent ring first.
            lo = 0 if (reserve == 0 or len(slots) == 1) else 1
            r = max(range(lo, len(slots)), key=lambda i: slots[i])
            if slots[r] <= reserve:
                if lo == 1 and slots[0] >= cap and cap > reserve:
                    r = 0       # bulk's last resort: the idle urgent ring
                else:
                    return None
            slots[r] -= max(1, n_spans)
            return r

        # 1) aging: a batch past the starvation bound outranks all
        #    weights, priorities, and the reserve
        for klass in self._order:
            q = self._queues[klass]
            while q and q[0].rounds >= self.aging_rounds:
                r = pick_ring(len(q[0].spans))
                if r is None:
                    break
                self._dispatch_one(q.popleft(), r, promoted=True)
                progress = True
        # 2) weighted fair-share: credit each backlogged class its
        #    weight (one round of banking max), serve in priority order
        for klass in self._order:
            if self._queues[klass]:
                w = self.policies[klass].weight
                self._deficit[klass] = min(self._deficit[klass] + w, 2 * w)
        top = self._order[0]
        for klass in self._order:
            q = self._queues[klass]
            reserve = 0 if klass == top else bulk_reserve
            while q and self._deficit[klass] >= 1.0:
                i = self._pick_index_locked(klass, q)
                b = q[i]
                r = pick_ring(len(b.spans), reserve)
                if r is None:
                    break
                del q[i]
                self._dispatch_one(b, r)
                self._deficit[klass] -= 1.0
                progress = True
            if not q:
                self._deficit[klass] = 0.0  # no banking while idle
        # 3) age the survivors of a round that had capacity
        for q in self._queues.values():
            for b in q:
                b.rounds += 1
        if self.tracer is not None and self.tracer.exports:
            now = time.monotonic()
            if now >= self._next_counter_t:
                # per-class queue depth as a Perfetto counter track:
                # the sched spans' queue waits get their denominator on
                # the same timeline (docs/OBSERVABILITY.md)
                self._next_counter_t = now + 0.02
                self.tracer.add_counter(
                    "strom.sched.queue_depth",
                    {k: len(q) for k, q in self._queues.items()})
        return progress

    def _dispatch_one(self, b: _Batch, ring: int,
                      promoted: bool = False) -> None:
        """Grant ``b`` ring admission (the owner thread performs the
        actual engine submission outside the lock)."""
        b.ring = ring
        b.promoted = promoted
        b.granted = True
        self._charge_tenant_locked(b)
        self._granted_out[ring] = (self._granted_out.get(ring, 0)
                                   + max(1, len(b.spans)))
        self.dispatches += 1
        if promoted:
            self.promotions += 1
        if self.tracer is not None and self.tracer.enabled:
            # the scheduler-queue wait this batch paid, causally under
            # the requester's span (b.ctx captured at enqueue)
            self.tracer.add_span(
                "strom.sched.queue", b.t_enq_ns, time.monotonic_ns(),
                category="strom.sched", ctx=b.ctx, klass=b.klass,
                ring=ring, spans=len(b.spans), promoted=promoted)
        if self.stats is not None:
            wait_s = time.monotonic() - b.t_enq
            self.stats.add(sched_dispatches=1,
                           **({"sched_promotions": 1} if promoted else {}))
            self.stats.add_class_stat(
                b.klass, dispatches=1, spans=len(b.spans),
                **({"promotions": 1} if promoted else {}))
            self.stats.class_stat_gauges(b.klass, queue_wait_s=wait_s)
            if b.tenant is not None:
                self.stats.add_tenant_stat(
                    b.tenant.id, dispatches=1, spans=len(b.spans),
                    **({"promotions": 1} if promoted else {}))
        self._cv.notify_all()

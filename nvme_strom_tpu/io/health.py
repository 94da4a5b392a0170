"""Failure-domain supervision — ring health, circuit breakers, hot ring
restart, and the degraded buffered mode (docs/RESILIENCE.md "Failure
domains").

PRs 1/5 made a single bad read or write survivable; this layer makes a
whole FAILURE DOMAIN survivable: a wedged io_uring ring, an NVMe device
throwing an EIO storm, a hung kernel worker.  Below the per-request
retry loop nothing used to notice those — every consumer pinned to the
sick ring just stalled.  The supervisor watches the domains and applies
escalating policy:

  health      per-ring rolling error windows, fed from BOTH sides of
              the stack: the engine's lock-free ring counters
              (``strom_ring_info.failed``, polled) and the resilience
              layer's per-attempt failures (``note_error`` — a
              Python-level fault plan never touches the C counters,
              yet must trip the same breakers).  A reap-side stall
              detector reads ``oldest_inflight_ns``: a completion that
              never arrives shows up as an age that only grows.
  breaker     one circuit breaker per ring (closed → open → half-open
              → closed) plus one device-level breaker.  A tripped ring
              reports zero admission headroom to the QoS scheduler
              (io/sched.py) — new batches route to healthy rings — and
              scalar submissions (retries, hedges) round-robin over the
              healthy set only.
  restart     a tripped ring is HOT-RESTARTED (``strom_ring_restart``):
              stall-parked requests cancel ``-ECANCELED`` — their
              waiters' retry (ResilientRead) resubmits them onto
              healthy rings, so consumers see one longer wait, never
              an error — dispatched I/O drains bounded, and the uring
              is rebuilt.  The restarted ring serves half-open until a
              clean interval closes its breaker.
  degraded    when every ring (or the device behind them) is unhealthy
              the engine browns out instead of blacking out:
              ``plan_and_submit``/``submit_spans`` serve plain
              synchronous ``pread``s (``strom_read_buffered`` — no
              O_DIRECT, no uring, no staging pool) at reduced
              bandwidth, while one half-open PROBE per interval rides
              the real path; a probe success restores it.  Serving
              (models/serving.py) sheds new prefill admissions while
              degraded, and the SLO governor stops boosting hedges
              into the sick device.

Everything is deterministic and hardware-free to drive: the C stall
injection (``STROM_FAULT_RING_STALL_*`` / ``strom_set_ring_stall``)
wedges a ring on demand, the Python fault plan's ``estorm`` kind
(io/faults.py) models a bounded EIO storm, and ``tick(force=True)``
runs a supervision round on the caller's thread — no background
threads anywhere (tests/test_health.py, ``-m chaos``).

Every action is accounted: ``breaker_trips`` / ``ring_restarts`` /
``extents_requeued`` / ``degraded_reads`` / ``degraded_bytes`` /
``degraded_probes`` counters and the ``ring_health`` /
``engine_degraded`` gauges flow through StromStats → ``strom_stat``'s
health block → watchdog dumps.
"""

from __future__ import annotations

import errno
import threading
import time
from collections import deque
from typing import List, Optional, Sequence

import numpy as np

from nvme_strom_tpu.utils.config import BreakerConfig
from nvme_strom_tpu.utils.lockwitness import make_rlock

#: breaker states (the ``ring_health`` gauge renders these)
CLOSED, OPEN, HALF_OPEN = "closed", "open", "half-open"

#: max bytes of one half-open probe read — enough to prove the path,
#: cheap enough to lose
_PROBE_BYTES = 64 << 10

#: min interval between polled supervision rounds (C counter reads);
#: ``tick(force=True)`` bypasses it (tests, explicit supervision)
_TICK_S = 0.25


class _Window:
    """Rolling event counter: ``add`` stamps now, ``count`` forgets
    everything older than ``span_s``.  Tiny (error paths only — the
    hot path never touches it)."""

    __slots__ = ("span_s", "_events")

    def __init__(self, span_s: float):
        self.span_s = span_s
        self._events: deque = deque()

    def add(self, n: int = 1, now: Optional[float] = None) -> None:
        now = time.monotonic() if now is None else now
        # prune on ADD too: while a breaker is open/degraded nothing
        # evaluates count(), yet note_error keeps appending — a days-
        # long outage with a retrying writer must not grow this without
        # bound
        horizon = now - self.span_s
        ev = self._events
        while ev and ev[0][0] < horizon:
            ev.popleft()
        ev.append((now, n))

    def count(self, now: Optional[float] = None) -> int:
        now = time.monotonic() if now is None else now
        horizon = now - self.span_s
        ev = self._events
        while ev and ev[0][0] < horizon:
            ev.popleft()
        return sum(n for _, n in ev)

    def clear(self) -> None:
        self._events.clear()


class _RingBreaker:
    """One ring's breaker + health window."""

    __slots__ = ("state", "window", "opened_at", "half_open_at",
                 "last_restart", "last_failed")

    def __init__(self, window_s: float):
        self.state = CLOSED
        self.window = _Window(window_s)
        self.opened_at = 0.0
        self.half_open_at = 0.0
        self.last_restart = -1e9   # first restart is never backoff-gated
        self.last_failed = 0       # C failed-counter watermark


class DegradedRead:
    """Pending-shaped degraded-mode read: one plain synchronous
    ``pread`` (``strom_read_buffered``) on ``wait()`` — no ring, no
    uring, no staging buffer, no retry/hedge machinery.  This is the
    brown-out path: reduced bandwidth, but alive while every fast
    domain is sick.  EOF tails surface as a short view, exactly like
    an engine read (``wait_exact`` raises identically)."""

    __slots__ = ("_engine", "fh", "offset", "_length", "_stats",
                 "_view", "_released", "_ctx")

    #: the payload rode the page cache — fallback semantics, honestly
    was_fallback = True

    def __init__(self, base_engine, fh: int, offset: int, length: int,
                 stats=None):
        self._engine = base_engine
        self.fh = fh
        self.offset = offset
        self._length = length
        self._stats = stats
        self._view: Optional[np.ndarray] = None
        self._released = False
        #: causal identity, captured at construction (the pread runs at
        #: wait() time, possibly on another thread) — degraded service
        #: must stay visible in a request's trace tree, and an
        #: out-of-scope read must stay OUT of whatever request happens
        #: to be current on the waiting thread (NO_CONTEXT default)
        from nvme_strom_tpu.utils.trace import NO_CONTEXT, attach_context
        self._ctx = NO_CONTEXT
        tracer = getattr(base_engine, "tracer", None)
        if tracer is not None and tracer.enabled:
            self._ctx = attach_context()

    @property
    def length(self) -> int:
        return self._length

    def wait(self, timeout: Optional[float] = None) -> np.ndarray:
        del timeout   # synchronous: the pread happens here, bounded by I/O
        if self._view is None:
            t0 = time.monotonic_ns()
            self._view = self._engine.read_buffered(
                self.fh, self.offset, self._length)
            t1 = time.monotonic_ns()
            if self._stats is not None:
                self._stats.add(degraded_bytes=int(self._view.nbytes))
                # delivered, but through the page-cache brown-out on a
                # condemned device — the ledger's degraded waste class
                from nvme_strom_tpu.obs.ledger import charge_waste
                charge_waste(self._stats, "degraded",
                             int(self._view.nbytes))
            tracer = getattr(self._engine, "tracer", None)
            if tracer is not None and tracer.enabled:
                tracer.add_span("strom.read.degraded", t0, t1,
                                category="strom.health", ctx=self._ctx,
                                bytes=int(self._view.nbytes))
            flight = getattr(self._engine, "flight", None)
            if flight is not None:
                flight.record("read", None, -1, self.fh, self.offset,
                              int(self._view.nbytes),
                              max(0, t1 - t0) // 1000, "degraded")
        return self._view

    def is_ready(self) -> bool:
        return True

    def release(self) -> None:
        self._released = True
        self._view = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.release()


class EngineSupervisor:
    """The failure-domain supervisor of one StromEngine.

    No background threads: supervision rounds (``tick``) run on caller
    threads — time-gated from the QoS scheduler's admission poll, the
    planner's submit boundary, and the resilient wait loop — so every
    decision is deterministic and test-drivable (``tick(force=True)``).
    """

    def __init__(self, engine, config: Optional[BreakerConfig] = None):
        self._engine = engine          # the BASE StromEngine
        self.cfg = config or BreakerConfig()
        n = getattr(engine, "n_rings", 1)
        self.rings = [_RingBreaker(self.cfg.window_s) for _ in range(n)]
        self.device_window = _Window(self.cfg.window_s)
        self._degraded = False         # device breaker open
        self._lock = make_rlock("health.EngineSupervisor._lock")
        self._next_tick = 0.0
        self._next_probe = 0.0
        self._rr = 0                   # healthy-ring round-robin cursor
        self._probe_zombies: list = []
        #: (engine, fh, offset, length) of the last degraded-served
        #: span: lets tick() keep probing for recovery even when the
        #: brown-out (plus serving's load shedding) has stopped all
        #: batch traffic — otherwise an idle degraded engine could
        #: never close its device breaker
        self._probe_hint: Optional[tuple] = None
        self._closed = False
        #: degraded-transition observers, called OUTSIDE the lock with
        #: the new state (True = brown-out entered, False = recovered).
        #: The cold-start coordinator registers here to count brown-outs
        #: that land mid-restore (io/coldstart.py); listeners must be
        #: cheap and must not raise into the breaker path.
        self._degraded_listeners: list = []

    # -- cheap queries (hot paths read these without the lock) -------------

    def degraded(self) -> bool:
        """Device breaker open: serve the buffered brown-out path."""
        return self._degraded

    def unhealthy(self) -> bool:
        """Any domain currently not fully trusted (degraded, or any
        ring breaker open/half-open) — what the SLO governor checks
        before boosting hedges into the device."""
        return self._degraded or any(r.state != CLOSED
                                     for r in self.rings)

    def ring_states(self) -> List[str]:
        return [r.state for r in self.rings]

    def mask_free_slots(self, free: List[int]) -> List[int]:
        """The QoS scheduler's admission filter: a ring with an OPEN
        breaker reports zero headroom, so new batches route to healthy
        rings.  Half-open rings admit (that is how they prove
        themselves).  While degraded nothing is masked — the planner
        already bypasses the engine, and any straggler batch must not
        starve in the grant loop."""
        if self._degraded:
            return free
        if all(r.state != OPEN for r in self.rings):
            return free
        return [0 if self.rings[i].state == OPEN else f
                for i, f in enumerate(free[:len(self.rings)])]

    def pick_ring(self) -> Optional[int]:
        """Healthy ring for scalar submissions (retries, hedges,
        probes): None when every ring is trusted (keep the C
        round-robin) or none is (the caller can't do better)."""
        states = [r.state for r in self.rings]
        if OPEN not in states:
            return None
        healthy = [i for i, s in enumerate(states) if s != OPEN]
        if not healthy:
            return None
        self._rr += 1
        return healthy[self._rr % len(healthy)]

    # -- ingestion (error paths only) --------------------------------------

    def note_error(self, ring: int = -1, err: Optional[int] = None,
                   engine_counted: bool = False) -> None:
        """One failed read/write attempt observed ABOVE the C engine
        (ResilientEngine feeds this per attempt) — how Python-level
        fault plans and consumer-visible errors reach the breakers.
        Cancellations are requeues, never device damage, and errors the
        C engine already counted (``engine_counted`` — real completion
        failures, which tick() ingests from the per-ring counters) are
        skipped here so one error never burns the budget twice."""
        if err == errno.ECANCELED or engine_counted or self._closed:
            return
        now = time.monotonic()
        stats = getattr(self._engine, "stats", None)
        with self._lock:
            self.device_window.add(now=now)
            if 0 <= ring < len(self.rings):
                rb = self.rings[ring]
                rb.window.add(now=now)
                if (rb.state in (CLOSED, HALF_OPEN)
                        and rb.window.count(now) >= self.cfg.ring_errors):
                    self._trip_ring(ring, now, stats)
            self._check_device(now, stats)

    # -- the supervision round ---------------------------------------------

    def tick(self, force: bool = False) -> None:
        """One supervision round: poll the C ring counters, detect
        stalls and error budgets crossed below Python, restart tripped
        rings (backoff-gated), close clean half-open breakers.  Time-
        gated (``_TICK_S``) and contention-free: a round already
        running absorbs this call."""
        now = time.monotonic()
        if not force and now < self._next_tick:
            return
        if not self._lock.acquire(blocking=force):
            return
        probe_hint = None
        try:
            if self._closed:
                return
            self._next_tick = now + _TICK_S
            stats = getattr(self._engine, "stats", None)
            self._reap_probe_zombies()
            for i, rb in enumerate(self.rings):
                try:
                    info = self._engine.ring_info(i)
                except (OSError, AttributeError):
                    continue
                failed = int(info.get("failed", 0))
                delta = failed - rb.last_failed
                rb.last_failed = failed
                if delta > 0:
                    rb.window.add(delta, now=now)
                    self.device_window.add(delta, now=now)
                stalled = (int(info.get("oldest_inflight_ns", 0))
                           > self.cfg.stall_s * 1e9)
                if rb.state in (CLOSED, HALF_OPEN) and (
                        stalled
                        or rb.window.count(now) >= self.cfg.ring_errors):
                    self._trip_ring(i, now, stats)
                if rb.state == OPEN and (
                        now - rb.last_restart
                        >= self.cfg.restart_backoff_s):
                    self._restart_ring(i, now, stats)
                if rb.state == HALF_OPEN and (
                        now - rb.half_open_at >= self.cfg.half_open_s
                        and rb.window.count(now) == 0):
                    rb.state = CLOSED
            self._check_device(now, stats)
            self._export_gauges(stats)
            if self._degraded:
                probe_hint = self._probe_hint
        finally:
            self._lock.release()
        if probe_hint is not None:
            # outside the lock: a probe waits on real I/O and must not
            # block note_error/mask queries behind it
            eng, fh, off, ln = probe_hint
            self._maybe_probe(eng, [(fh, off, ln)],
                              getattr(eng, "stats", None))

    def _flight_dump(self, reason: str, **extra) -> None:
        """Post-mortem trigger (io/flightrec.py): capture the recent-op
        ring at the moment a failure-domain verdict lands."""
        flight = getattr(self._engine, "flight", None)
        if flight is not None:
            flight.dump(reason, extra=extra or None)

    def _trip_ring(self, ring: int, now: float, stats) -> None:
        rb = self.rings[ring]
        rb.state = OPEN
        rb.opened_at = now
        if stats is not None:
            stats.add(breaker_trips=1)
        self._flight_dump("breaker_trip", ring=ring,
                          window_errors=rb.window.count(now))
        # all rings open == no healthy failure domain left: that IS the
        # device verdict, decided here atomically so the scheduler can
        # never face an all-masked ring set outside degraded mode
        if all(r.state == OPEN for r in self.rings):
            self._enter_degraded(now, stats)

    def _restart_ring(self, ring: int, now: float, stats) -> None:
        """Hot restart (strom_ring_restart): cancelled extents requeue
        through their waiters' retry loop; -ETIMEDOUT keeps the breaker
        open (an undrainable ring is the degraded path's problem)."""
        rb = self.rings[ring]
        rb.last_restart = now
        t0 = time.monotonic_ns()
        try:
            cancelled = self._engine.ring_restart(ring, self.cfg.drain_s)
        except TimeoutError:
            return        # undrainable in-flight I/O: breaker stays
            #               open, the degraded path is the fallback
        except (OSError, AttributeError):
            return        # EBUSY (concurrent restart) / teardown race
        if stats is not None:
            stats.add(ring_restarts=1,
                      **({"extents_requeued": cancelled}
                         if cancelled else {}))
        tracer = getattr(self._engine, "tracer", None)
        if tracer is not None and tracer.enabled:
            tracer.add_span("strom.health.ring_restart", t0,
                            time.monotonic_ns(),
                            category="strom.health", ring=ring,
                            cancelled=cancelled)
        self._flight_dump("ring_restart", ring=ring,
                          cancelled=cancelled)
        rb.window.clear()
        rb.state = HALF_OPEN
        rb.half_open_at = time.monotonic()

    def _check_device(self, now: float, stats) -> None:
        if self._degraded:
            return
        if self.device_window.count(now) >= self.cfg.device_errors:
            self._enter_degraded(now, stats)

    def _enter_degraded(self, now: float, stats) -> None:
        if not self._degraded:
            self._degraded = True
            self._next_probe = now + self.cfg.probe_s
            if stats is not None:
                stats.add(breaker_trips=1)   # the device breaker's trip
            # a multi-tenant box's degraded-device post-mortem wants
            # WHOSE traffic was on the device when it went sick — embed
            # the per-tenant ledger when one exists (empty = old dump)
            tenants = (stats.tenant_stats
                       if stats is not None else {})
            self._flight_dump("device_degraded",
                              device_errors=self.device_window.count(now),
                              **({"tenant_stats": tenants}
                                 if tenants else {}))
            self._export_gauges(stats)
            self._notify_degraded(True)

    def _recover(self, stats) -> None:
        """A half-open probe succeeded: restore the fast path.  Open
        ring breakers move to half-open (they close after a clean
        interval; fresh errors re-trip them immediately)."""
        with self._lock:
            self._degraded = False
            self._probe_hint = None   # episode over: a later one must
            #                           re-learn a live (fh, span)
            self.device_window.clear()
            now = time.monotonic()
            for rb in self.rings:
                if rb.state == OPEN:
                    rb.state = HALF_OPEN
                    rb.half_open_at = now
                rb.window.clear()
            self._export_gauges(stats)
        self._notify_degraded(False)

    def add_degraded_listener(self, fn) -> None:
        """Register an observer of device-breaker transitions (called
        with True on brown-out entry, False on recovery)."""
        self._degraded_listeners.append(fn)

    def _notify_degraded(self, on: bool) -> None:
        for fn in list(self._degraded_listeners):
            try:
                fn(on)
            except Exception:
                pass   # an observer must never wedge the breaker

    def _export_gauges(self, stats) -> None:
        if stats is not None:
            stats.set_gauges(ring_health=self.ring_states(),
                             engine_degraded=int(self._degraded))

    # -- degraded service ---------------------------------------------------

    def serve_degraded(self, engine, spans: Sequence,
                       stats=None) -> Optional[list]:
        """Serve ``(fh, offset, length)`` spans as :class:`DegradedRead`
        buffered preads — the brown-out.  First runs the half-open
        probe (one real-path read per ``probe_s``, through ``engine``,
        the TOP of the wrapper stack, so a Python-level fault plan
        gates recovery exactly like a device fault); a probe success
        recovers and returns None — the caller re-takes the fast path
        for this very batch."""
        if stats is None:
            stats = getattr(engine, "stats", None)
        if spans:
            fh, off, ln = next(
                ((f, o, n) for f, o, n in spans if n > 0), spans[0])
            self._probe_hint = (engine, fh, off, ln)
            if self._maybe_probe(engine, spans, stats):
                return None
        out = [DegradedRead(self._engine, fh, off, ln, stats)
               for fh, off, ln in spans]
        if stats is not None and out:
            stats.add(degraded_reads=len(out))
        return out

    def degraded_pending(self, fh: int, offset: int, length: int,
                         stats=None, probe_engine=None) -> DegradedRead:
        """One degraded read (counted) — the resilient retry loop's
        fallback for a read already mid-recovery when the device
        breaker opens: its next attempt browns out instead of burning
        the rest of its retry budget against a sick device.

        ``probe_engine``: the engine the recovery probe should ride —
        the layer BELOW the resilient wrapper (fault injection
        included), so a Python-level storm gates recovery exactly like
        a device fault; defaults to the base engine."""
        if stats is None:
            stats = getattr(self._engine, "stats", None)
        if stats is not None:
            stats.add(degraded_reads=1)
        # refresh the recovery hint with the MOST RECENT live span: a
        # device that degraded mid-read and then went idle is probed by
        # tick() from here, and an older hint may name an fh the
        # consumer has since closed
        self._probe_hint = (probe_engine or self._engine, fh, offset,
                            length)
        return DegradedRead(self._engine, fh, offset, length, stats)

    def _maybe_probe(self, engine, spans, stats) -> bool:
        """True when the probe ran AND succeeded (fast path restored)."""
        now = time.monotonic()
        if self.cfg.probe_s > 0 and now < self._next_probe:
            return False
        with self._lock:
            if now < self._next_probe and self.cfg.probe_s > 0:
                return False           # another thread probed first
            self._next_probe = now + max(self.cfg.probe_s, 1e-9)
        fh, off, ln = next(
            ((f, o, n) for f, o, n in spans if n > 0), spans[0])
        # the probe must ride the RAW path: a ResilientEngine on top
        # would retry the probe into the degraded fallback and mask the
        # very failure being probed (recovery would flap) — step below
        # it; a fault layer (FaultyEngine) stays, so Python-level
        # storms gate recovery exactly like device faults
        from nvme_strom_tpu.io.resilient import ResilientEngine
        while isinstance(engine, ResilientEngine):
            engine = engine._engine
        ok = False
        pending = None
        t0 = time.monotonic_ns()
        try:
            pending = engine.submit_read(fh, off,
                                         min(ln, _PROBE_BYTES))
            pending.wait(timeout=self.cfg.probe_timeout_s)
            ok = True
        except TimeoutError:
            # still in flight: park it (release would block on the very
            # wedge being probed); reaped on later ticks/probes.  Under
            # the lock — an unsynchronized append can lose the race
            # against _reap_probe_zombies' list swap and leak the
            # probe's staging-pool slot for the life of the engine.
            with self._lock:
                self._probe_zombies.append(pending)
            pending = None
        except OSError:
            ok = False                 # wait released the request
            if pending is None:
                # the SUBMIT itself failed (closed fh, teardown): this
                # span can never probe again — drop a hint naming it so
                # tick() doesn't re-probe a dead fh forever
                with self._lock:
                    if (self._probe_hint is not None
                            and self._probe_hint[1] == fh):
                        self._probe_hint = None
            pending = None
        finally:
            if pending is not None:
                try:
                    pending.release()
                except OSError:
                    pass
        if stats is not None:
            stats.add(degraded_probes=1)
        tracer = getattr(self._engine, "tracer", None)
        if tracer is not None and tracer.enabled:
            tracer.add_span("strom.health.probe", t0,
                            time.monotonic_ns(),
                            category="strom.health", fh=fh, offset=off,
                            ok=ok)
        if ok:
            self._recover(stats)
        return ok

    def _reap_probe_zombies(self) -> None:
        survivors = []
        for p in self._probe_zombies:
            try:
                if p.is_ready():
                    p.release()
                else:
                    survivors.append(p)
            except OSError:
                pass
        self._probe_zombies = survivors

    # -- lifecycle ----------------------------------------------------------

    def close(self) -> None:
        """Engine teardown: release landed probe zombies and stop
        supervising.  Still-in-flight zombies are left to the engine's
        own drain (which must wait for the kernel regardless)."""
        with self._lock:
            self._closed = True
            self._reap_probe_zombies()
            self._probe_zombies = []

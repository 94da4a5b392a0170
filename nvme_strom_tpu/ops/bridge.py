"""JAX bridge: engine staging buffers → device-resident arrays.

This is the consumer half of the reference's hot path (SURVEY.md §3.1): where
the reference DMAs NVMe blocks into pre-pinned CUDA BAR1 pages and userspace
then launches kernels on them, we hand the engine's locked staging buffer
*by pointer* to JAX — ``np.ctypeslib`` views cost zero copies — and let PJRT
run the host→device PCIe transfer straight out of that buffer.  With
``depth > 1`` the next chunk's NVMe read overlaps the current chunk's PCIe
transfer, so the SSD and the PCIe link stay concurrently busy — the same
pipelining the reference gets from N in-flight DMA requests (SURVEY.md §3.4).

The staging buffer is released back to the pool only after
``block_until_ready`` confirms the device transfer consumed it.

``PutStage`` is the caller-side stage of a weight restore: the reading
thread hands each chunk over and a worker a device gathers and puts it,
so reading and transferring overlap without changing what a put is.
A column shard, which the host has to gather anyway, is gathered into a
``HostAssembly`` — one reused host buffer a (tensor, device) — and
crosses to its device in one put.
"""

from __future__ import annotations

import collections
import functools
import os
import queue
import threading
from typing import Callable, Iterator, Optional, Sequence

import numpy as np

from nvme_strom_tpu.io.engine import StromEngine, PendingRead
from nvme_strom_tpu.io.plan import split_spans, submit_spans_tiered
from nvme_strom_tpu.utils.config import EngineConfig


def _default_device():
    import jax
    return jax.local_devices()[0]


def overlap_env_enabled() -> bool:
    """Global kill switch of the double-buffered host→HBM stage:
    ``STROM_BRIDGE_OVERLAP=0`` restores today's wait→device_put path
    bit-for-bit, even for streams constructed with ``overlap=True``
    (an off-switch that explicit call sites could override would not
    be an off-switch)."""
    return os.environ.get("STROM_BRIDGE_OVERLAP", "1") != "0"


#: per-device cache of the jitted Pallas host→HBM DMA callable
_H2D_DMA_CACHE: dict = {}


def _pallas_h2d(dev):
    """Jitted Pallas kernel DMA'ing a pinned-host array into device HBM
    (SNIPPETS.md [2]'s pinned-host→HBM ``pltpu.async_copy`` pattern).
    The copy runs on the device's DMA engines, asynchronously to the
    Python thread — which is what lets the NVMe read of chunk K+1
    overlap the host→HBM hop of chunk K.

    The operand ref is declared in ``pltpu.HOST``: Mosaic refuses a
    ``pinned_host`` operand behind ``pl.ANY`` ("Failed to convert a
    memory space to MLIR"; tests/test_chip_compile_kernels.py holds the
    compile for v5e)."""
    fn = _H2D_DMA_CACHE.get(dev)
    if fn is not None:
        return fn
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def _dma_kernel(x_ref, y_ref):
        def body(sem):
            copy = pltpu.make_async_copy(x_ref, y_ref, sem)
            copy.start()
            copy.wait()

        pl.run_scoped(body, pltpu.SemaphoreType.DMA)

    @functools.partial(
        jax.jit, out_shardings=jax.sharding.SingleDeviceSharding(dev))
    def _call(x):
        return pl.pallas_call(
            _dma_kernel,
            in_specs=[pl.BlockSpec(memory_space=pltpu.HOST)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            name="strom_h2d_dma",
        )(x)

    _H2D_DMA_CACHE[dev] = _call
    return _call


class OverlapStage:
    """Double-buffered host→HBM stage of ``DeviceStream.stream_ranges``
    (docs/PERF.md §6).

    Two ping-pong slabs carved from the unified pinned arena
    (io/arena.py, tag ``bridge``; private buffers when the arena is
    off/full).  Per chunk: the completed — and verified — staging view
    is memcpy'd into the next slab, the STAGING buffer releases
    immediately (the NVMe read of chunk K+1 can start while chunk K is
    still in flight to the device), and the device transfer launches
    asynchronously off the slab.  A slab is never overwritten before
    the transfer it sources reports ready — the rotation invariant
    tests/test_bridge.py pins with a fake transfer.

    ``transfer(host_view, dtype, shape) -> device_array`` is injectable
    (tests, exotic transports); the default is the Pallas
    pinned-host→HBM DMA on a TPU device and the alias-safe
    ``host_to_device`` everywhere else — chosen by the device's
    platform, never by a failure at run time.
    """

    def __init__(self, engine: StromEngine, dev, chunk_bytes: int,
                 transfer: Optional[Callable] = None):
        from nvme_strom_tpu.io import arena as _arena
        self.engine = engine
        self.dev = dev
        self.chunk_bytes = chunk_bytes
        self._slabs: list = []       # numpy views, one per ping-pong slot
        self._carves: list = []      # arena Slab objects (None = private)
        for _ in range(2):
            slab = _arena.carve_or_none(chunk_bytes, "bridge",
                                        stats=engine.stats)
            if slab is not None:
                self._carves.append(slab)
                self._slabs.append(slab.view)
            else:
                self._carves.append(None)
                self._slabs.append(np.empty(chunk_bytes, dtype=np.uint8))
        self._busy: list = [None, None]   # device array sourcing slot k
        self._k = 0
        self._transfer = transfer

    # -- transfer backends -------------------------------------------------

    def _default_transfer(self, host: np.ndarray, dtype, shape):
        import jax
        arr = host if dtype is None else host.view(dtype)
        if shape is not None:
            arr = arr.reshape(shape)
        if self.dev.platform != "tpu" or arr.size < 2:
            # (a one-element pinned_host operand aborts the TPU
            # compiler — "Unsupported operand memory space" — so such a
            # view takes the plain put by its size, not by a failure)
            return host_to_device(self.engine, arr, self.dev)
        # pinned-host residency first (one host copy at DRAM speed),
        # then the Pallas DMA moves it to HBM on the device's own
        # engines — fully async to this thread.  A refusal (compile or
        # run time) raises: on a TPU a kernel failure is an error, not
        # a reason to take another path silently.
        sharding = jax.sharding.SingleDeviceSharding(
            self.dev, memory_kind="pinned_host")
        pinned = jax.device_put(arr, sharding)
        out = _pallas_h2d(self.dev)(pinned)
        self.engine.stats.add(bytes_to_device=int(host.nbytes))
        return out

    # -- the ping-pong rotation --------------------------------------------

    def put(self, view: np.ndarray, dtype, shape):
        """Stage one completed chunk view and launch its device
        transfer; returns the device array, or None for a view larger
        than the slabs (an oversized cache-line hit, say) — the CALLER
        must then take the non-overlapped path and hold the source
        until the transfer is ready (transferring straight off the
        view here and letting the caller release it immediately would
        let the buffer recycle under a live DMA).  Blocks only when
        BOTH slabs still source in-flight transfers (depth-2
        backpressure — by then the link, not the host, is the
        bottleneck)."""
        n = view.nbytes
        if n > self.chunk_bytes:
            return None
        k = self._k
        self._k ^= 1
        prev = self._busy[k]
        if prev is not None:
            # slab-reuse gate: the transfer sourced from this slab must
            # be done with the bytes before they are overwritten
            prev.block_until_ready()
            self._busy[k] = None
        import time as _time
        t0 = _time.monotonic_ns()
        slab_view = self._slabs[k][:n]
        slab_view[:] = view.reshape(-1).view(np.uint8)
        arr = (self._transfer or self._default_transfer)(
            slab_view, dtype, shape)
        self._busy[k] = arr
        self.engine.stats.add(overlap_chunks=1, overlap_bytes=int(n))
        tracer = getattr(self.engine, "tracer", None)
        if tracer is not None and tracer.enabled:
            # the host→HBM hop of this chunk (slab copy + async launch)
            # — the `bridge` component of obs/attrib.py's breakdown
            tracer.add_span("strom.bridge.hop", t0, _time.monotonic_ns(),
                            category="strom.bridge", bytes=int(n),
                            slab=k)
        return arr

    def close(self) -> None:
        """Block out the in-flight transfers, then recycle the slabs
        (a carve returned while a DMA still sources it would let the
        next consumer overwrite live transfer bytes)."""
        for i, arr in enumerate(self._busy):
            if arr is not None:
                try:
                    arr.block_until_ready()
                except Exception:
                    pass
                self._busy[i] = None
        self._slabs = []
        for slab in self._carves:
            if slab is not None:
                slab.release()
        self._carves = []


def split_ranges(spans, chunk: int):
    """(offset, length) spans → (flat sub-ranges ≤ ``chunk``, per-span
    sub-range counts).  Delegates to the planner's shared splitting
    rule (``io.plan.split_spans``) — kept under its historical name for
    the format readers that import it from here."""
    return split_spans(spans, chunk)


def host_to_device(engine: StromEngine, host: np.ndarray, dev,
                   alias_safe: bool = False):
    """``device_put`` with the staging-alias rule and byte accounting.

    On a host-backed device, ``jax.device_put`` may ALIAS the numpy buffer;
    staging memory is recycled after release(), so a copy is forced (and
    counted as a bounce). On an accelerator the PCIe transfer itself moves
    the bytes and no host copy exists.  Single source of truth for every
    consumer that puts staging-backed views on device.

    ``alias_safe=True``: the source is a long-lived immutable host
    array (e.g. the KV host-cache tier), never recycled staging memory
    — aliasing is fine, so no protective copy and no bounce count.

    Span ``strom.h2d``: the dispatch, through ``Tracer.span`` — on the
    JAX profiler's timeline always (the benchmark's
    ``h2d_dispatch_share`` reads it there) and in the strom tracer when
    that is enabled.  The two clocks tick alike, a constant offset
    apart (the profiler's xplane counts from its session's start; the
    check and its numbers: utils/trace.py's docstring).
    """
    import jax
    if dev.platform == "cpu" and not alias_safe:
        host = np.array(host)
        engine.stats.add(bounce_bytes=int(host.nbytes))
    nbytes = int(host.nbytes)
    with engine.tracer.span("strom.h2d", bytes=nbytes):
        arr = jax.device_put(host, dev)
    engine.stats.add(bytes_to_device=nbytes)
    return arr


class StagingRetirePool:
    """Deferred staging release for read→host-decode→device pipelines.

    ``DeviceStream`` owns the raw-range case; format readers that must
    touch the bytes on host BETWEEN the engine read and the device put
    (Arrow IPC decode, safetensors slicing) can't use it — and the
    conservative alternative they shipped with (block on every batch's
    transfers before releasing its staging buffer) costs one
    stop-and-wait link round trip per batch, the same disease the
    round-3 verdict called on the SQL scan.  This pool is
    ``DeviceStream``'s drain discipline, factored out: push each
    batch's (release, device_arrays); completed heads retire
    opportunistically (``is_ready``), and only when more than ``depth``
    batches' staging is outstanding does it block on the OLDEST — by
    which time ``depth-1`` younger transfers are overlapping it.

    Correctness rule unchanged: a staging buffer is released only
    after every device array transferred out of it reports ready.

    ``depth`` counts outstanding entries; 0 degrades to the old
    block-per-batch behavior — the safe fallback when the engine's
    staging pool is too small to also hold deferred entries (callers
    must budget: reads in flight + deferred entries < pool buffers, or
    a deferred submit can wait on a buffer only this pool can free).

    Who owns one: the stream that pushes to it, and only that stream —
    the pool takes no lock.  The format readers, the SQL scans and the
    loader each keep a pool on their one consuming thread; a weight
    restore's pool belongs to its ``PutStage`` (below), which pushes
    from whichever worker ends a chunk last, under the stage's lock."""

    def __init__(self, depth: int = 3):
        self.depth = max(0, depth)
        self._q: list = []          # (release_cb, [device arrays])

    def push(self, release, arrays) -> None:
        """``release``: the staging release callback (None = nothing to
        retire, e.g. a host-owned buffer); ``arrays``: device arrays
        whose transfers consume that staging."""
        if release is None:
            return
        self._q.append((release, list(arrays)))
        self._drain_ready()
        while len(self._q) > self.depth:
            self._block_oldest()

    def drain_ready(self) -> None:
        """Retire every completed head entry without blocking."""
        while self._q and all(a.is_ready() for a in self._q[0][1]):
            rel, _ = self._q.pop(0)
            rel()

    _drain_ready = drain_ready

    def retire_oldest(self) -> bool:
        """Blocking-retire the oldest entry; False when none remain.
        Callers under staging-pool pressure loop on this — it always
        makes progress (the device finishes transfers on its own)."""
        if not self._q:
            return False
        self._block_oldest()
        return True

    def _block_oldest(self) -> None:
        rel, arrs = self._q.pop(0)
        for a in arrs:
            a.block_until_ready()
        rel()

    def flush(self) -> None:
        """Retire everything (end of stream, or error-path cleanup)."""
        while self._q:
            self._block_oldest()


class Once:
    """A value that the first of several threads to ask computes and
    the others wait for (a chunk's column gather that several of
    ``PutStage``'s workers need)."""

    __slots__ = ("_lock", "_value")

    def __init__(self):
        self._lock = threading.Lock()
        self._value = None

    def get(self, compute: Callable):
        with self._lock:
            if self._value is None:
                self._value = compute()
            return self._value


class PutStage:
    """Bounded transfer stage between a thread that reads and the
    devices it feeds (``LazyCheckpoint.load_sharded``; PERF.md §3).

    One FIFO worker a device.  The reading thread hands each completed
    chunk to :meth:`put` as ``(release, [(dev, job)])`` and goes on to
    its next read; device ``dev``'s worker calls ``job()`` — the host
    gather and the ``host_to_device`` calls of that device's share —
    and collects the device arrays it returns.  Both leave Python
    (numpy's copy loop, PJRT's ``BufferFromHostBuffer``), so they run
    beside the reader's planning and waiting.  :meth:`then` queues a
    callable behind a device's puts (a tensor's ``jnp.concatenate``).

    What it holds:

    * a chunk's ``release`` fires only after EVERY array put out of the
      chunk reports ready: the worker that finishes a chunk last pushes
      it, with all devices' arrays, to the stage's one
      ``StagingRetirePool`` (under a lock; ``StagingRetirePool``'s rule,
      across threads);
    * one worker a device, so a device's jobs run in the order they
      were handed in;
    * at most ``depth`` chunks are in the stage (handed in, not yet
      pushed to the pool): :meth:`put` waits for room, under the span
      ``strom.restore.put_wait``.  The caller budgets ``depth`` and
      ``retire_depth`` against the engine's staging pool;
    * ``depth`` 0 starts no thread: :meth:`put` and :meth:`then` run
      their jobs on the calling thread — the synchronous path of a pool
      too small for a queue, and of a single tensor's load;
    * a chunk nothing was put out of — every device's job gathered its
      share into a :class:`HostAssembly` (:meth:`assemble`) — is
      released when its last job ends: it waits behind no transfer;
    * an exception in a job stops the stage: the jobs still queued are
      dropped (their chunks released once what was put out of them is
      ready), a worker waiting for a host buffer wakes, and the next
      :meth:`put`, :meth:`then` or :meth:`close` raises it on the
      calling thread.  :meth:`close` always leaves every buffer, staging
      and host, released and no worker alive.

    ``engine.stats`` counts the arrays put out of staging views by a
    worker (``restore_puts_staged``) and on the calling thread
    (``restore_puts_inline``), and the puts of assembled host buffers,
    whichever thread made them (``restore_puts_assembled``).
    """

    def __init__(self, engine: StromEngine, depth: int, retire_depth: int):
        self.engine = engine
        self.depth = max(0, depth)
        self._retire = StagingRetirePool(retire_depth)
        self._retire_lock = threading.Lock()
        self._error: Optional[BaseException] = None
        self._room = threading.Semaphore(self.depth)
        self._queues: dict = {}      # dev -> its worker's queue
        self._workers: list = []
        # host buffers of the assemblies: a ring a group of devices, and
        # the condition its hand-overs (and a failure) are told under
        self._rings: dict = {}       # devs -> the group's last segments
        self._handover = threading.Condition()

    def _queue(self, dev):
        """``dev``'s queue; its worker starts with its first job (only
        the calling thread asks, so no two start one)."""
        q = self._queues.get(dev)
        if q is None:
            q = self._queues[dev] = queue.SimpleQueue()
            t = threading.Thread(target=self._work, args=(q,),
                                 name=f"strom-put-{len(self._workers)}",
                                 daemon=True)
            self._workers.append(t)
            t.start()
        return q

    # -- the calling thread ------------------------------------------------

    def put(self, release, jobs: Sequence[tuple]) -> None:
        """Hand one chunk over: ``release`` its staging release (None:
        host-owned memory), ``jobs`` a ``(dev, job)`` for each device
        with a share of it, ``job() -> [device arrays]`` put out of the
        chunk's view."""
        chunk = _Chunk(release, len(jobs))
        if not self.depth:
            try:
                for _, job in jobs:
                    chunk.arrays.extend(job())
            finally:
                self._retire_chunk(chunk, "restore_puts_inline")
            return
        with self.engine.tracer.span("strom.restore.put_wait",
                                     "strom.restore"):
            self._room.acquire()
        if self._error is not None:
            self._room.release()
            if release is not None:
                release()               # nothing was put out of it
            raise self._error
        for dev, job in jobs:
            self._queue(dev).put((chunk, job))

    def assemble(self, devs: Sequence, rows: int, row_shape: tuple,
                 dtype) -> "HostAssembly":
        """The assembly of one column shard, ``rows`` rows of
        ``row_shape``, that every device of ``devs`` takes; its host
        buffers come out of that group's ring.  Called on the thread
        that calls :meth:`put`, before it hands in the shard's first
        chunk: the order of these calls is the order the ring's buffers
        pass from one shard to the next in."""
        ring = self._rings.setdefault(
            tuple(devs), collections.deque(maxlen=ASSEMBLY_SLOTS))
        return HostAssembly(self, ring, tuple(devs), rows, row_shape, dtype)

    def then(self, dev, fn: Callable) -> None:
        """Run ``fn()`` after everything handed in for ``dev`` so far."""
        if not self.depth:
            fn()
        elif self._error is not None:
            raise self._error
        else:
            self._queue(dev).put((None, fn))

    def close(self) -> None:
        """Wait for every job handed in, release every buffer, end the
        workers; raises what a job raised."""
        for q in self._queues.values():
            q.put(None)
        for t in self._workers:
            t.join()
        self._workers = []
        self._queues = {}
        with self.engine.tracer.span("strom.restore.retire",
                                     "strom.restore"):
            self._retire.flush()
            self._keep_host_buffers()
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    # -- a worker ------------------------------------------------------------

    def _work(self, q) -> None:
        while True:
            item = q.get()
            if item is None:
                return
            chunk, job = item
            try:
                if self._error is None:
                    if chunk is None:
                        job()
                    else:
                        chunk.arrays.extend(job())
            except BaseException as e:
                self._fail(e)
            if chunk is not None and chunk.done_one():
                try:
                    self._retire_chunk(chunk, "restore_puts_staged")
                except BaseException as e:  # a transfer that failed
                    self._fail(e)
                self._room.release()
            del item, chunk, job    # hold no array while the queue is idle

    def _keep_host_buffers(self) -> None:
        """Leave the rings' buffers with the engine for its next load,
        each once what was put out of it has landed; one whose shard
        was not put by every device, or whose transfer failed, is
        dropped (the array on its way holds it: nothing is rewritten)."""
        rings, self._rings = self._rings, {}
        for ring in rings.values():
            for seg in ring:
                if seg.raw is None or seg.left \
                        or seg.raw.nbytes != ASSEMBLY_BYTES:
                    continue
                try:
                    for arr in seg.arrays:
                        arr.block_until_ready()
                except Exception:
                    continue
                self.engine.spare_host_buffers.append(seg.raw)

    def _fail(self, e: BaseException) -> None:
        """Keep the first failure and wake whoever waits for a host
        buffer: the put it waits for may never come."""
        with self._handover:
            self._error = self._error or e
            self._handover.notify_all()

    def _retire_chunk(self, chunk, counter: str) -> None:
        """Every job of ``chunk`` has ended: count its puts and hand its
        release to the pool, behind the arrays put out of it — or call
        it here, where nothing was put out of the chunk."""
        if not chunk.arrays:
            if chunk.release is not None:
                chunk.release()
            return
        self.engine.stats.add(**{counter: len(chunk.arrays)})
        with self.engine.tracer.span("strom.restore.retire",
                                     "strom.restore"):
            with self._retire_lock:
                self._retire.push(chunk.release, chunk.arrays)


class _Chunk:
    """One chunk in a ``PutStage``: its release, the arrays put out of
    it so far, and how many devices' jobs are still to end."""

    __slots__ = ("release", "arrays", "_left", "_lock")

    def __init__(self, release, n_jobs: int):
        self.release = release
        self.arrays: list = []
        self._left = n_jobs
        self._lock = threading.Lock()

    def done_one(self) -> bool:
        """One job ended; True for the one that ends last."""
        with self._lock:
            self._left -= 1
            return self._left == 0


#: the most bytes one assembled put carries, and the size of every host
#: buffer of the assemblies (its pages are touched only as far as a
#: shard reaches).  A put's per-call cost is what a restore pays for
#: (PERF.md §5: ~0.3–0.5 ms whatever its size, serialised across
#: threads); at 64 MiB it is a twentieth of the put's time on the link,
#: and mistral-7b's largest column shard under tp=4 (``tok_embed``,
#: ``lm_head``) is one put.  A larger shard crosses in several such
#: puts, joined on the device.
ASSEMBLY_BYTES = 64 << 20
#: host buffers a group of devices gathers into in turn: one being
#: filled while the one before it is on its way to the devices
ASSEMBLY_SLOTS = 2


class _Segment:
    """The rows ``[start, end)`` of a ``HostAssembly`` that cross in one
    put a device, and the host buffer they are gathered into.  ``prev``
    is the segment that had the buffer ``ASSEMBLY_SLOTS`` segments
    earlier in the same ring: this one takes the buffer over once every
    device of the group has put ``prev`` and those arrays are ready."""

    __slots__ = ("start", "end", "prev", "raw", "arrays", "left", "_host")

    def __init__(self, start: int, end: int, prev, n_devs: int):
        self.start = start
        self.end = end
        self.prev = prev
        self.raw = None             # the uint8 buffer, once taken
        self.arrays: list = []      # device arrays put out of it
        self.left = n_devs          # devices still to put it
        self._host = Once()


class HostAssembly:
    """One column shard on its way to the devices that take it, gathered
    on the host chunk by chunk into a reused buffer and put whole — one
    ``device_put`` a device in place of one a chunk and a concatenate
    (``PutStage.assemble`` makes one; PERF.md §3, §5).

    A shard of more than ``ASSEMBLY_BYTES`` crosses in segments of that
    size.  Each segment's buffer comes out of the group's ring of
    ``ASSEMBLY_SLOTS``: while the ring is young it is one the engine
    kept from an earlier load (``spare_host_buffers``) or a new one,
    afterwards the buffer of the segment ``ASSEMBLY_SLOTS`` earlier —
    never rewritten before every array put out of it reports ready
    (``StagingRetirePool``'s rule, for host buffers); the wait lies
    under the span ``strom.restore.retire``.  ``PutStage.close`` leaves
    the buffers with the engine.

    :meth:`gather` and :meth:`put` run inside the stage's jobs: a device's
    worker gathers its chunk (or waits for the worker that does, where
    several devices take the same shard) and then puts whatever became
    complete.  A device's jobs run in order, so after its gather of rows
    up to ``r`` every row below ``r`` is in place.
    """

    def __init__(self, stage: "PutStage", ring, devs: tuple, rows: int,
                 row_shape: tuple, dtype):
        self.stage = stage
        self.rows = rows
        self.row_shape = tuple(row_shape)
        self.dtype = np.dtype(dtype)
        row_bytes = int(np.prod(self.row_shape, dtype=np.int64)) \
            * self.dtype.itemsize
        self.row_bytes = row_bytes
        self.seg_rows = max(1, ASSEMBLY_BYTES // row_bytes if row_bytes
                            else rows)
        self.segments = []
        for start in range(0, rows, self.seg_rows):
            prev = ring[0] if len(ring) == ring.maxlen else None
            seg = _Segment(start, min(rows, start + self.seg_rows), prev,
                           len(devs))
            ring.append(seg)
            self.segments.append(seg)
        self._next = {dev: 0 for dev in devs}   # dev -> segment to put

    def _host(self, seg: _Segment) -> np.ndarray:
        """``seg``'s host array; the first thread to ask takes the
        buffer, the others wait for it."""
        return seg._host.get(functools.partial(self._take_buffer, seg))

    def _take_buffer(self, seg: _Segment) -> np.ndarray:
        """``seg``'s host array, on the buffer of the segment that had
        it before (once that one has been put and has landed) or on a
        new one."""
        stage = self.stage
        prev, seg.prev = seg.prev, None
        need = (seg.end - seg.start) * self.row_bytes
        raw = None
        if prev is not None:
            with stage.engine.tracer.span("strom.restore.retire",
                                          "strom.restore"):
                with stage._handover:
                    stage._handover.wait_for(
                        lambda: prev.left == 0 or stage._error is not None)
                    if prev.left:
                        raise stage._error
                for arr in prev.arrays:
                    arr.block_until_ready()
            prev.arrays = []
            raw, prev.raw = prev.raw, None
        if raw is None or raw.nbytes < need:
            spares = stage.engine.spare_host_buffers
            if raw is not None:
                spares.append(raw)
            if need > ASSEMBLY_BYTES:   # one row over the cap: its own
                raw = np.empty(need, dtype=np.uint8)
            else:
                try:
                    raw = spares.pop()
                except IndexError:
                    raw = np.empty(ASSEMBLY_BYTES, dtype=np.uint8)
        seg.raw = raw
        return raw[:need].view(self.dtype).reshape(
            (seg.end - seg.start,) + self.row_shape)

    def gather(self, row0: int, cut: np.ndarray) -> bool:
        """Copy ``cut`` — the shard's columns of one chunk, strided in
        the chunk's view — into rows ``[row0, row0 + len(cut))``."""
        eng = self.stage.engine
        done, n = 0, cut.shape[0]
        while done < n:
            seg = self.segments[(row0 + done) // self.seg_rows]
            host = self._host(seg)
            at = row0 + done - seg.start
            take = min(n - done, seg.end - seg.start - at)
            piece = cut[done:done + take]
            with eng.tracer.span("strom.restore.slice", "strom.restore",
                                 bytes=int(piece.nbytes)):
                np.copyto(host[at:at + take], piece)
            done += take
        eng.stats.add(bounce_bytes=int(cut.nbytes))
        return True

    def put(self, dev, rows_done: int) -> list:
        """On ``dev``'s thread, its gather of the rows below
        ``rows_done`` behind it: put every segment that became complete
        and return the arrays."""
        stage = self.stage
        out = []
        while self._next[dev] < len(self.segments) \
                and self.segments[self._next[dev]].end <= rows_done:
            seg = self.segments[self._next[dev]]
            self._next[dev] += 1
            host = self._host(seg)
            arr = host_to_device(stage.engine, host, dev)
            with stage._handover:
                seg.arrays.append(arr)
                seg.left -= 1
                stage._handover.notify_all()
            stage.engine.stats.add(restore_puts_assembled=1)
            out.append(arr)
        return out


class DeviceStream:
    """Pipelined NVMe→HBM chunk stream over one engine.

    ``depth`` chunks are kept in flight: while chunk *k* rides PCIe to the
    device, chunks *k+1 … k+depth* are being DMA'd from NVMe into staging
    buffers.  Yields device-resident arrays.

    ``drain``: "blocking" waits on the OLDEST transfer once ``depth``
    are in flight (the round-2 behavior); "ready" additionally retires
    any already-completed head transfers opportunistically
    (``jax.Array.is_ready``) after every dispatch, so staging buffers
    recycle the moment the device is done with them instead of waiting
    for the pipeline to fill — on a high-latency link this keeps the
    NVMe side of the pipe fed (round-2 verdict: the 0.69 stream
    efficiency investigation, task #2).
    """

    def __init__(self, engine: StromEngine, device=None, depth: int = 3,
                 drain: str = "blocking", klass: Optional[str] = None,
                 overlap: Optional[bool] = None,
                 overlap_transfer: Optional[Callable] = None):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        if drain not in ("blocking", "ready"):
            raise ValueError(f"bad drain={drain!r}")
        self.engine = engine
        self.device = device
        self.depth = depth
        self.drain = drain
        #: latency class every batch of this stream submits under
        #: (io/sched.py; the per-stream default — stream_ranges can
        #: override per call)
        self.klass = klass
        #: double-buffered host→HBM stage (docs/PERF.md §6).  None =
        #: auto: engage on a TPU device when STROM_BRIDGE_OVERLAP
        #: allows (a CPU device keeps the plain device_put path
        #: bit-for-bit — an extra slab copy would only cost there).
        #: True forces the stage on any device (tests, measurements);
        #: False disables for this stream; STROM_BRIDGE_OVERLAP=0
        #: overrides everything.
        self.overlap = overlap
        #: injectable transfer callable for the stage (tests)
        self.overlap_transfer = overlap_transfer

    def _overlap_active(self, dev) -> bool:
        if not overlap_env_enabled():
            return False
        if self.overlap is not None:
            return self.overlap
        return dev.platform == "tpu"

    def _put(self, view: np.ndarray, dtype, shape):
        dev = self.device or _default_device()
        arr = view if dtype is None else view.view(dtype)
        if shape is not None:
            arr = arr.reshape(shape)
        return host_to_device(self.engine, arr, dev)

    def stream_file(self, path, chunk_bytes: Optional[int] = None,
                    dtype=None) -> Iterator:
        """Yield device arrays of consecutive file chunks (uint8 unless
        ``dtype`` given; chunk_bytes must then be dtype-size aligned)."""
        chunk = chunk_bytes or self.engine.config.chunk_bytes
        if chunk > self.engine.config.chunk_bytes:
            raise ValueError("chunk_bytes exceeds engine buffer capacity")
        fh = self.engine.open(path)
        try:
            size = self.engine.file_size(fh)
            offsets = list(range(0, size, chunk))
            yield from self.stream_ranges(
                fh, [(o, min(chunk, size - o)) for o in offsets], dtype=dtype)
        finally:
            self.engine.close(fh)

    def stream_ranges(self, fh: int, ranges: Sequence[tuple[int, int]],
                      dtype=None, shapes: Optional[Sequence] = None,
                      verify: Optional[Callable] = None,
                      klass: Optional[str] = None) -> Iterator:
        """Yield device arrays for arbitrary (offset, length) ranges of an
        open file — the planner-facing API used by the format readers.

        ``verify``: optional ``fn(range_index, host_view)`` invoked on
        the completed staging view BEFORE the device transfer — the one
        window where payload bytes are host-visible on this path, so
        read-side integrity checks (STROM_VERIFY, utils/checksum.py)
        hook here; raising aborts the stream loudly.

        ``klass``: latency class of this stream's batches (defaults to
        the stream's own ``klass``) — the QoS tag consumers set so the
        scheduler can rank their traffic (io/sched.py)."""
        if klass is None:
            klass = self.klass
        pending: list = []   # (PendingRead, shape, range_index)
        inflight: list = []  # (device_array, PendingRead-or-None)
        dev = self.device or _default_device()
        # double-buffered host→HBM stage (docs/PERF.md §6): the staging
        # buffer releases the moment its bytes land in a ping-pong slab,
        # so the NVMe read of chunk K+1 overlaps the host→HBM DMA of
        # chunk K instead of queueing behind it.  Inactive (None) =
        # today's wait→device_put path, bit-for-bit.
        stage = (OverlapStage(self.engine, dev,
                              self.engine.config.chunk_bytes,
                              transfer=self.overlap_transfer)
                 if self._overlap_active(dev) else None)

        def drain_one():
            arr, pr = inflight.pop(0)
            with self.engine.tracer.span("strom.h2d.sync",
                                         bytes=int(arr.nbytes)):
                arr.block_until_ready()  # device owns the bytes now
            if pr is not None:
                pr.release()
            return arr

        def drain_ready():
            # retire completed head transfers without blocking: their
            # staging buffers go back to the pool NOW, so the engine
            # can keep reading ahead instead of stalling on buffers
            # still pinned under long-done transfers
            while inflight and inflight[0][0].is_ready():
                yield drain_one()

        def start_transfer():
            # oldest pending read → verified staging view → device;
            # the entry leaves ``pending`` first, so on a verify
            # failure the finally can't see it — release here, no
            # buffer leak
            pr, shp, ri = pending.pop(0)
            view = pr.wait()
            if verify is not None:
                # ordering contract (docs/PERF.md §6): the verify hook
                # (and the host-tier fill inside pr.wait()) runs on the
                # completed view BEFORE any slab copy/reuse — a corrupt
                # chunk never reaches a DMA slab, let alone the device
                try:
                    verify(ri, view)
                except BaseException:
                    # a corrupt read may have been FILLED into the
                    # pinned tier before this check ran: spoil the
                    # overlapping lines so no retry/future read is
                    # served the same bytes from DRAM
                    from nvme_strom_tpu.io.hostcache import spoil_span
                    try:
                        spoil_span(self.engine, pr.fh, pr.offset,
                                   pr.length, self.engine.stats)
                    except Exception:
                        pass
                    pr.release()
                    raise
            try:
                arr = (stage.put(view, dtype, shp)
                       if stage is not None else None)
                if arr is None:
                    # no stage, or the view outgrew the slabs: the
                    # classic path, source held until its transfer
                    # drains ready
                    inflight.append((self._put(view, dtype, shp), pr))
                    return
            except BaseException:
                # a refused transfer raises (no other path is tried);
                # the entry already left ``pending``, so release here
                pr.release()
                raise
            pr.release()   # staging recycles NOW — the overlap win
            inflight.append((arr, None))

        ranges = list(ranges)
        shapes_l = list(shapes) if shapes is not None else None
        try:
            i = 0
            while i < len(ranges):
                # vectored refill: up to ``depth`` ranges enter the
                # engine as ONE batched submission (single
                # io_uring_enter via submit_readv) instead of one
                # boundary crossing per chunk
                take = ranges[i:i + self.depth]
                # tiered refill: ranges resident in the pinned host
                # cache come back as ready zero-copy views (no engine
                # I/O); the rest enter as ONE batched submission
                prs = submit_spans_tiered(
                    self.engine, [(fh, off, ln) for off, ln in take],
                    klass=klass)
                for j, pr in enumerate(prs):
                    shape = (shapes_l[i + j] if shapes_l is not None
                             else None)
                    pending.append((pr, shape, i + j))
                i += len(take)
                # keep `depth` reads in flight before starting transfers
                while len(pending) > self.depth:
                    start_transfer()
                    if self.drain == "ready":
                        yield from drain_ready()
                    while len(inflight) > self.depth:
                        yield drain_one()
            while pending:
                start_transfer()
            while inflight:
                yield drain_one()
        finally:
            for pr, _, _ in pending:
                try:
                    pr.wait()
                except OSError:
                    pass
                pr.release()
            for _, pr in inflight:
                if pr is not None:
                    pr.release()
            if stage is not None:
                stage.close()

    def read_to_device(self, path, dtype=None, shape=None):
        """Whole file → one device array (concatenated on device, not host).

        Chunks stream independently to the device and are joined with a
        jitted concatenate there, so no host-side assembly buffer exists.
        """
        import jax.numpy as jnp
        parts = list(self.stream_file(path))  # uint8 chunks on device
        if not parts:
            out = jnp.zeros((0,), dtype=jnp.uint8)
        elif len(parts) == 1:
            out = parts[0]
        else:
            out = jnp.concatenate(parts)
        if dtype is not None:
            out = out.view(dtype)  # on-device bitcast, no transfer
        if shape is not None:
            out = out.reshape(shape)
        return out


def submit_chunked_writes(engine: StromEngine, fh: int, offset: int,
                          host: np.ndarray, pend: list) -> int:
    """Chunk-split pipelined writes of ``host`` bytes at ``offset`` into
    an open fh.  In-flight submissions live in the CALLER-OWNED ``pend``
    list (bounded at the engine's queue depth here) so several calls can
    share one pipeline and drain together — the one write-side pattern
    every consumer (checkpointing, KV eviction, optimizer offload)
    shares, mirroring ``split_ranges`` on the read side.

    The caller must drain ``pend`` (``.wait()`` each) before closing the
    fh: in-flight writes target it, and closing first would EBADF them —
    or hit a recycled descriptor.  Returns the bytes confirmed by waits
    done HERE (depth-bound drains); bytes still in ``pend`` are the
    caller's to count."""
    chunk = engine.config.chunk_bytes
    depth = engine.config.queue_depth
    drained = 0
    for pos in range(0, host.nbytes, chunk):
        pend.append(engine.submit_write(fh, offset + pos,
                                        host[pos:pos + chunk]))
        while len(pend) >= depth:
            drained += pend.pop(0).wait()
    return drained


def write_from_device(engine: StromEngine, array, path,
                      offset: int = 0) -> int:
    """Device array → NVMe (the checkpoint/inverse path, SURVEY.md §5).

    The device→host transfer lands in one numpy buffer; chunks of it are
    then submitted as pipelined engine writes (O_DIRECT zero-copy when the
    chunk is alignment-conformant, bounced + counted otherwise).
    """
    host = np.ascontiguousarray(np.asarray(array)).view(np.uint8).reshape(-1)
    fh = engine.open(path, writable=True)
    total = 0
    pend: list = []
    try:
        total += submit_chunked_writes(engine, fh, offset, host, pend)
        while pend:
            total += pend.pop(0).wait()
    finally:
        for p in pend:
            try:
                p.wait()
            except OSError:
                pass
        engine.close(fh)
    return total

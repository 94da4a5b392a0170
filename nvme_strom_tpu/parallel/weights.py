"""Lazy sharded weight loading: safetensors on NVMe → per-device HBM shards.

Benchmark config 4 (BASELINE.md: "Llama-3 8B safetensors weight shards on
NVMe → lazy HBM param load").  The key property: a host reads ONLY the byte
ranges its addressable devices actually need — a tensor sharded 8-ways over
rows costs each host 1/8th of the I/O, and a replicated tensor is read once
per host (not once per device).  Reads are planned with
``SafetensorsFile.slice_plan`` (rows along axis 0 are contiguous on disk) and
flow through the direct engine; assembly uses
``jax.make_array_from_single_device_arrays`` so no host-side concatenation
of the global tensor ever exists.

This is the read side of the reference's inverse (checkpoint) path noted in
SURVEY.md §5; the write side is ``ops.bridge.write_from_device`` /
``save_checkpoint`` below.
"""

from __future__ import annotations

import collections
import json
import os
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from nvme_strom_tpu.formats.safetensors import (
    SafetensorsFile,
    _np_dtype,
)
from nvme_strom_tpu.io.engine import StromEngine, wait_exact
from nvme_strom_tpu.io.plan import join_pieces, plan_and_submit
from nvme_strom_tpu.utils.config import EngineConfig

#: category of the restore path's spans (names: docs/OBSERVABILITY.md)
_CAT = "strom.restore"


def _normalize_index(idx, shape):
    """Device index (tuple of slices) → ((r0, r1), tail_slices)."""
    idx = tuple(idx)
    full = tuple(slice(0, s) for s in shape)
    idx = idx + full[len(idx):]
    if not shape:
        return (0, 1), ()
    s0 = idx[0]
    r0 = 0 if s0.start is None else s0.start
    r1 = shape[0] if s0.stop is None else s0.stop
    if s0.step not in (None, 1):
        raise ValueError("strided axis-0 sharding is not supported")
    tail = []
    for d, s in zip(shape[1:], idx[1:]):
        start = 0 if s.start is None else s.start
        stop = d if s.stop is None else s.stop
        if s.step not in (None, 1):
            raise ValueError("strided sharding is not supported")
        tail.append(slice(start, stop))
    return (r0, r1), tuple(tail)


def _stage_depths(eng, staged: bool) -> tuple:
    """(chunks a ``PutStage`` may hold, entries its retire pool defers)
    for a load over ``eng``, budgeted against the engine's staging pool:
    ``_stream_span`` keeps up to ``stream_depth`` reads in flight, the
    stage holds chunks handed over and the pool holds retired-pending
    entries, and their sum must leave a free buffer or a deferred
    submit could wait on memory only this consumer can release
    (deadlock).  A pool with no room for a queue gives stage depth 0 —
    the puts run on the reading thread — and a tiny one retire depth 0,
    the old block-per-chunk behavior; ``staged=False`` asks for that
    synchronous path whatever the pool."""
    half = eng.config.queue_depth // 2
    spare = eng.n_buffers - max(2, half) - 1
    stage = max(0, min(half, spare // 2)) if staged else 0
    return stage, max(0, min(half, spare - stage))


class _IssuedTensor:
    """A tensor whose chunks are with a ``PutStage``: ``arrays[dev]`` is
    set by ``dev``'s worker when it has joined that device's parts, or
    put the column shard it assembled."""

    def __init__(self, gshape: tuple, sharding, devices: list):
        self.gshape = gshape
        self.sharding = sharding
        self.devices = devices
        self.arrays: Dict[object, object] = {}

    def ready(self) -> bool:
        return len(self.arrays) == len(self.devices)

    def assemble(self, eng):
        import jax
        arrays = [self.arrays[d] for d in self.devices]
        with eng.tracer.span("strom.restore.join", _CAT,
                             parts=len(arrays)):
            return jax.make_array_from_single_device_arrays(
                self.gshape, self.sharding, arrays)


class LazyCheckpoint:
    """Union view over one or more safetensors shard files.

    Accepts a list of ``.safetensors`` paths, a directory containing them,
    or a HuggingFace-style ``*.index.json``.
    """

    def __init__(self, source: Union[str, os.PathLike, Sequence]):
        paths: list[str] = []
        if isinstance(source, (str, os.PathLike)):
            src = str(source)
            if src.endswith(".json"):
                with open(src) as f:
                    index = json.load(f)
                base = os.path.dirname(src)
                paths = sorted({os.path.join(base, v)
                                for v in index["weight_map"].values()})
            elif os.path.isdir(src):
                paths = sorted(
                    os.path.join(src, n) for n in os.listdir(src)
                    if n.endswith(".safetensors"))
            elif not os.path.exists(src) and any(c in src for c in "*?["):
                # glob pattern — only when no file literally has this
                # name (a real path like "run[1]/model.safetensors" must
                # never be re-interpreted as a character class)
                import glob
                paths = sorted(glob.glob(src))
            else:
                paths = [src]
        else:
            paths = [str(p) for p in source]
        if not paths:
            raise ValueError(f"no safetensors files in {source!r}")
        self.files = [SafetensorsFile(p) for p in paths]
        self._by_name: Dict[str, SafetensorsFile] = {}
        for sf in self.files:
            for name in sf.keys():
                if name in self._by_name:
                    raise ValueError(f"duplicate tensor {name}")
                self._by_name[name] = sf

    def keys(self):
        return self._by_name.keys()

    def shape(self, name) -> tuple:
        return self._by_name[name].tensors[name]["shape"]

    def dtype(self, name) -> str:
        return self._by_name[name].tensors[name]["dtype"]

    # ------------------------------------------------------------------

    def load_sharded(self, shardings: Union[Dict, Callable],
                     engine: Optional[StromEngine] = None,
                     dtype=None, ici_mesh=None) -> Dict[str, object]:
        """Load every tensor as a global jax.Array under its sharding.

        ``shardings``: {name: Sharding} or fn(name, shape) -> Sharding.
        ``dtype``: optional on-device cast applied after placement (the
        disk bytes stay in the stored dtype; the cast runs on device).

        Read-once/scatter mode (``STROM_ICI_SCATTER=1``, docs/PERF.md
        §7): the shard files partition into per-host contiguous byte
        shares, each host reads only its 1/N from NVMe (``restore``
        class) and the mesh all-gathers the shares over ICI; every span
        read below is then served from the gathered bytes — so a
        replicated tensor costs the MESH one read instead of one per
        host.  ``ici_mesh`` pins the exchange mesh; any scatter failure
        browns out to the per-host read path (``ici_fallbacks``).  Off
        (the default) touches zero code paths.
        """
        import jax

        own = engine is None
        if engine is None:
            from nvme_strom_tpu.io.faults import build_engine
            engine = build_engine(EngineConfig())
        eng = engine
        from nvme_strom_tpu.ops.ici import ici_scatter_enabled
        if ici_scatter_enabled():
            from nvme_strom_tpu.ops.ici import scatter_engine
            served = scatter_engine(
                engine, [sf.path for sf in self.files], mesh=ici_mesh,
                klass="restore")
            if served is not None:
                eng = served
        from nvme_strom_tpu.ops.bridge import PutStage
        out: Dict[str, object] = {}
        # the one transfer stage of this load (ops/bridge.PutStage): the
        # workers live from here to the finally below, never longer
        stage = PutStage(eng, *_stage_depths(eng, staged=True))
        try:
            with eng.tracer.span("strom.restore.load", _CAT,
                                 tensors=len(self._by_name)):
                issued: collections.deque = collections.deque()
                for name in self.keys():
                    get = (shardings.get if isinstance(shardings, dict)
                           else None)
                    sh = (get(name) if get
                          else shardings(name, self.shape(name)))
                    if sh is None:
                        raise KeyError(f"no sharding for tensor {name}")
                    issued.append((name, self._issue_tensor(
                        eng, name, sh, "restore", stage)))
                    # assemble what the workers have joined meanwhile
                    while issued and issued[0][1].ready():
                        done, tensor = issued.popleft()
                        out[done] = tensor.assemble(eng)
                stage.close()
                for done, tensor in issued:
                    out[done] = tensor.assemble(eng)
                if dtype is not None:
                    cast = jax.jit(lambda x: x.astype(dtype),
                                   out_shardings=None)
                    out = {n: cast(a) for n, a in out.items()}
            return out
        finally:
            stage.close()       # a no-op after the close above
            if own:
                eng.close_all()

    def _load_tensor(self, eng: StromEngine, name: str, sharding,
                     klass: str = "restore"):
        """One tensor → its global array, its puts on the calling thread
        (a ``PutStage`` of depth 0: the cold-start lanes call this from
        two threads at once, a tensor at a time, and a demand fault
        waits for nothing but its own transfers)."""
        from nvme_strom_tpu.ops.bridge import PutStage
        stage = PutStage(eng, *_stage_depths(eng, staged=False))
        try:
            tensor = self._issue_tensor(eng, name, sharding, klass, stage)
        finally:
            stage.close()
        return tensor.assemble(eng)

    def _issue_tensor(self, eng: StromEngine, name: str, sharding,
                      klass: str, stage) -> "_IssuedTensor":
        """Read one tensor and hand its chunks to ``stage``, under
        ``strom.restore.tensor`` (the reading thread's spans of
        docs/OBSERVABILITY.md's restore rows nest in it).  The result
        assembles the global array once the stage has run what this
        call handed it."""
        sf = self._by_name[name]
        info = sf.tensors[name]
        gshape = tuple(info["shape"])
        np_dt = _np_dtype(info["dtype"])
        nbytes = int(np.prod(gshape, dtype=np.int64)) * np_dt.itemsize
        with eng.tracer.span("strom.restore.tensor", _CAT, tensor=name,
                             bytes=nbytes):
            return self._issue_tensor_inner(eng, sf, name, gshape, np_dt,
                                            sharding, klass, stage)

    def _issue_tensor_inner(self, eng: StromEngine, sf, name: str,
                            gshape: tuple, np_dt, sharding, klass: str,
                            stage) -> "_IssuedTensor":
        """Read each row span of the tensor once and hand every chunk of
        it to ``stage`` with one job a device.  What a device's job does
        with the chunk follows from the sharding alone: a device that
        takes whole rows gets them put out of the staging view, a put a
        chunk, joined on the device behind the span's last chunk; a
        device that takes a column shard gets its columns gathered into
        the shard's ``HostAssembly`` (``stage.assemble``: one reused
        host buffer for the devices that take that shard) and the
        buffer put whole behind the gather of its last rows — that
        array IS the device's part of the tensor."""
        idx_map = sharding.addressable_devices_indices_map(gshape)

        # Group devices by ROW SPAN only: rows are contiguous on disk, so a
        # span is read sequentially once regardless of how many column
        # groups cut it up afterwards — the whole tensor is read at most
        # once per host (replicated shards included).  Spans larger than
        # one staging buffer are split into row-aligned chunks, streamed
        # with several reads in flight, and re-joined ON DEVICE (no host
        # assembly buffer for the row-sharded/replicated case).
        import jax.numpy as jnp

        spans: Dict[tuple, list] = {}
        for dev, idx in idx_map.items():
            (r0, r1), tail = _normalize_index(
                idx if idx is not None else (), gshape)
            if not any((s.start, s.stop) != (0, d)
                       for s, d in zip(tail, gshape[1:])):
                tail = ()           # whole rows: nothing to gather
            # hashable key: slice objects only hash on 3.12+
            tkey = tuple((s.start, s.stop) for s in tail)
            spans.setdefault((r0, r1), []).append((dev, tail, tkey))

        from nvme_strom_tpu.ops.bridge import Once, host_to_device
        from nvme_strom_tpu.utils.checksum import (ChecksumError,
                                                   VerifyPolicy, crc32c)
        # read-side integrity (STROM_VERIFY): a span covering the WHOLE
        # tensor accumulates a CRC32C over its streamed chunks and
        # compares against the write-time stamp (formats/safetensors).
        # Row-sharded spans read sub-ranges the whole-tensor stamp
        # cannot cover — the offline scrubber owns those (strom-scrub).
        # Detection is loud-by-raise: the views were already in flight
        # to devices, but the load fails before the params are returned,
        # so corruption never reaches training silently.
        policy = getattr(self, "_verify", None)
        if policy is None:
            policy = self._verify = VerifyPolicy()
        stamp = None
        if policy.enabled:
            from nvme_strom_tpu.formats.safetensors import \
                tensor_checksums
            stamps = getattr(sf, "_strom_crcs", None)
            if stamps is None:
                stamps = sf._strom_crcs = tensor_checksums(sf)
            stamp = stamps.get(name)
        span = eng.tracer.span
        tensor = _IssuedTensor(gshape, sharding, list(idx_map))

        def put_share(view, dev, tail, parts, asm, row0, gathered):
            """Device ``dev``'s share of one chunk, on its worker.  Whole
            rows are put out of the staging view as they lie.  A column
            shard is strided there and the host has to gather it: into
            the shard's assembly, which crosses in one put behind the
            gather of its last rows — nothing is put out of the view."""
            if not tail:
                arr = host_to_device(eng, view, dev)
                parts.append(arr)
                return (arr,)
            cut = view[(slice(None),) + tail]
            if gathered is None:
                asm.gather(row0, cut)
            else:       # several devices' columns: the first one gathers
                gathered.get(partial(asm.gather, row0, cut))
            end = row0 + cut.shape[0]
            parts.extend(asm.put(dev, end))
            if end == asm.rows:
                if len(parts) == 1:
                    tensor.arrays[dev] = parts.pop()
                else:           # a shard of several assembled puts
                    join(dev, parts)
            return ()

        def join(dev, parts):
            """Behind ``dev``'s last put of a span, on its worker."""
            with span("strom.restore.join", _CAT, parts=len(parts)):
                tensor.arrays[dev] = (parts[0] if len(parts) == 1
                                      else jnp.concatenate(parts))
            parts.clear()

        # The staging buffers are the stage's from the hand-over on: it
        # releases a chunk's once every array put out of it is ready
        # (its one StagingRetirePool) — at once where every device
        # gathered its share.  This thread only reads: plan, wait, the
        # CRC pass, and the hand-over (strom.restore.put_wait is its
        # wait for room in the stage).
        fh = eng.open(sf.path)
        try:
            for (r0, r1), devs in spans.items():
                full_span = (r0, r1) == (0, gshape[0] if gshape else 1)
                check = (stamp is not None and full_span
                         and policy.want())
                crc = 0
                parts: Dict[object, list] = {dev: [] for dev, _, _ in devs}
                # a column shard is assembled on the host, once for the
                # devices that take the same one
                takers: Dict[tuple, list] = {}
                for dev, tail, tkey in devs:
                    if tail:
                        takers.setdefault(tkey, []).append((dev, tail))
                asms = {
                    tkey: stage.assemble(
                        [dev for dev, _ in group], r1 - r0,
                        tuple(s.stop - s.start for s in group[0][1]), np_dt)
                    for tkey, group in takers.items()}
                shared = [k for k, group in takers.items() if len(group) > 1]
                row0 = 0
                for view, release in self._stream_span(
                        eng, fh, sf, name, r0, r1, np_dt, gshape,
                        klass=klass):
                    if check:
                        with span("strom.restore.slice", _CAT,
                                  bytes=int(view.nbytes)):
                            crc = crc32c(view, crc)
                        eng.stats.add(bytes_verified=int(view.nbytes))
                    # devs sharing a column shard share the gathered
                    # sub-array: the first worker to want it makes it
                    gathers = {tkey: Once() for tkey in shared}
                    stage.put(release, [
                        (dev, partial(put_share, view, dev, tail,
                                      parts[dev], asms.get(tkey), row0,
                                      gathers.get(tkey)))
                        for dev, tail, tkey in devs])
                    row0 += view.shape[0] if view.ndim else 1
                if check and crc != stamp:
                    eng.stats.add(checksum_failures=1)
                    raise ChecksumError(
                        f"tensor {name} of {sf.path} fails its stamped "
                        f"CRC32C ({crc:#010x} != {stamp:#010x}) — "
                        f"corrupt weights must not reach the model")
                for dev, tail, _ in devs:
                    if not tail:
                        stage.then(dev, partial(join, dev, parts[dev]))
        finally:
            eng.close(fh)
        return tensor

    def _stream_span(self, eng, fh, sf, name, r0, r1, np_dt, gshape,
                     klass: str = "restore"):
        """Yield (host view, release_cb | None) per row-chunk of rows
        [r0, r1), each at most one staging buffer; pipelined (several
        reads in flight).  The view is valid until ``release_cb()`` —
        the CONSUMER calls it (via a StagingRetirePool) once transfers
        out of the view complete; None means host-owned memory with
        nothing to retire.  release is idempotent, so generator cleanup
        can double as a backstop.

        ``klass`` is the QoS class every read of this span rides —
        ``restore`` for bulk loads (the default, today's behavior);
        the cold-start demand-fault lane (FaultingCheckpoint) passes
        ``decode`` so a request-blocking tensor overtakes the bulk
        stream in the scheduler."""
        span = eng.tracer.span
        if not gshape:
            with span("strom.restore.plan", _CAT, slices=1):
                ent = sf.plan([name]).entries[0]
                (pieces,) = plan_and_submit(eng, [(fh, ent.offset,
                                                   ent.length)],
                                            klass=klass)
                # one piece pre-tier; the host tier's hit/miss split can
                # return several — join_pieces keeps one view either way
                p = join_pieces(pieces, eng.stats)
            done = False
            try:
                # ownership transfers at the yield: the consumer's
                # retire pool releases once transfers finish.  NO
                # with-block — its __exit__ fired on generator resume,
                # BEFORE deferred transfers completed (a recycled
                # buffer under an in-flight H2D read = wrong bytes on
                # device).  The finally only covers never-yielded
                # abandonment; release() is idempotent either way.
                with span("strom.restore.read_wait", _CAT,
                          bytes=ent.length):
                    view = p.wait()
                yield view.view(np_dt).reshape(()), p.release
                done = True
            finally:
                if not done:
                    p.release()
            return
        info = sf.tensors[name]
        row_elems = (int(np.prod(gshape[1:], dtype=np.int64))
                     if len(gshape) > 1 else 1)
        row_bytes = row_elems * np_dt.itemsize
        chunk_rows = max(1, eng.config.chunk_bytes // max(1, row_bytes))
        if row_bytes > eng.config.chunk_bytes:
            # One row exceeds the staging buffer: assemble rows on host
            # (counted as bounce — resize the pool to avoid this).  The
            # planner owns the oversized-extent split.
            for r in range(r0, r1):
                with span("strom.restore.plan", _CAT, slices=1):
                    ent = sf.slice_plan(name, r, 1)
                    (pend,) = plan_and_submit(
                        eng, [(fh, ent.offset, ent.length)],
                        chunk_bytes=eng.config.chunk_bytes, klass=klass)
                buf = np.empty(ent.length, dtype=np.uint8)
                pos = 0
                for p in pend:
                    # cumulative assembly: a silently short view would
                    # leave a garbage tail that reshapes cleanly
                    with span("strom.restore.read_wait", _CAT,
                              bytes=p.length):
                        v = wait_exact(p)
                    buf[pos:pos + v.nbytes] = v
                    pos += v.nbytes
                    p.release()
                eng.stats.add(bounce_bytes=int(ent.length))
                # host-owned buffer: nothing to retire
                yield buf.view(np_dt).reshape((1,) + tuple(gshape[1:])), \
                    None
            return
        # One planned, vectored submission for the whole row span: row
        # chunks are contiguous on disk, so small tensors coalesce into
        # fewer reads (each slice keeps its own zero-copy sub-view) and
        # every span crosses Python→C→io_uring_enter once, not once per
        # chunk.  The engine defers reads past its pool without
        # blocking, so submitting the span up front cannot deadlock —
        # buffers recycle oldest-first as the consumer retires views.
        with span("strom.restore.plan", _CAT,
                  slices=len(range(r0, r1, chunk_rows))):
            slices = []
            for r in range(r0, r1, chunk_rows):
                n = min(chunk_rows, r1 - r)
                ent = sf.slice_plan(name, r, n)
                slices.append(((fh, ent.offset, ent.length), ent.shape))
            planned = plan_and_submit(eng, [s for s, _ in slices],
                                      chunk_bytes=eng.config.chunk_bytes,
                                      klass=klass)
            pend = []
            for ((_, _, ln), shp), pieces in zip(slices, planned):
                if not pieces:    # zero-element slice: no I/O to wait on
                    pend.append((None, shp, 0))
                    continue
                # a nonzero slice fits one buffer, so pre-tier this is
                # one zero-copy piece; a host-tier hit/miss split joins
                # on host
                pend.append((join_pieces(pieces, eng.stats), shp, ln))
        try:
            while pend:
                p, shp, ln = pend.pop(0)
                if p is None:
                    yield np.empty(0, np.uint8).view(np_dt).reshape(shp), \
                        None
                    continue
                with span("strom.restore.read_wait", _CAT, bytes=ln):
                    view = p.wait()
                yield view.view(np_dt).reshape(shp), p.release
        finally:
            for p, _, _ in pend:  # abandoned mid-span: drain + free
                if p is not None:
                    p.release()


class FaultingCheckpoint:
    """Demand-faulting front-end over :class:`LazyCheckpoint` — the
    weights half of elastic cold-start (``STROM_COLDSTART=1``,
    docs/RESILIENCE.md "Elastic cold-start").

    The serving stack constructs one of these instead of calling
    ``load_sharded`` and starts taking traffic immediately.  Two lanes
    then race, on purpose:

    * **demand faults** — :meth:`get`/:meth:`materialize` load any
      tensor a request needs *now* at ``decode`` class, so the QoS
      scheduler dispatches it ahead of everything else;
    * **bulk restore** — :meth:`start_bulk` streams the remaining
      tensors in a background thread at ``restore`` class, riding the
      read-once/ICI-scatter path when enabled, exactly like
      ``load_sharded``.

    Both lanes share one claim table: each tensor is read from NVMe at
    most once, whichever lane gets there first, and waiters block on
    the claimant's event instead of re-reading.  A FAILED claim (the
    bulk lane's ring tripped mid-restore) wakes the waiters and clears
    the claim so a demand-faulting waiter re-claims and loads the
    tensor itself at ``decode`` class — this is what lets the PR-10
    breakers brown out the restore stream with zero consumer errors.

    Locking: ``coldstart.FaultingCheckpoint._lock`` guards only the
    claim/array tables (group ``coldstart`` in lock_order.conf); all
    engine I/O runs outside it.
    """

    def __init__(self, source, shardings: Union[Dict, Callable],
                 engine: Optional[StromEngine] = None, dtype=None,
                 ici_mesh=None, coordinator=None):
        import threading

        from nvme_strom_tpu.utils.lockwitness import make_lock
        self.ckpt = (source if isinstance(source, LazyCheckpoint)
                     else LazyCheckpoint(source))
        self._shardings = shardings
        self._dtype = dtype
        self._ici_mesh = ici_mesh
        self.coordinator = coordinator
        self._own = engine is None
        if engine is None:
            from nvme_strom_tpu.io.faults import build_engine
            engine = build_engine(EngineConfig())
        self.engine = engine
        self._lock = make_lock("coldstart.FaultingCheckpoint._lock")
        self._arrays: Dict[str, object] = {}
        self._claims: Dict[str, object] = {}   # name -> threading.Event
        # claim-table residue (io/handoff.py): tensors requests could
        # not wait for — demand-faulted at decode class, in fault
        # order.  A handoff bundle ships this measured hot set so the
        # replacement pre-faults them ahead of its bulk stream.
        self._fault_names: List[str] = []
        self._resident_ev = threading.Event()
        self._bulk_thread: Optional[object] = None
        self._cast = None
        if dtype is not None:
            import jax
            self._cast = jax.jit(lambda x: x.astype(dtype),
                                 out_shardings=None)

    # -- introspection ------------------------------------------------------

    def keys(self):
        return self.ckpt.keys()

    def resident(self) -> bool:
        """True once every tensor is device-resident."""
        return self._resident_ev.is_set()

    def wait_resident(self, timeout: Optional[float] = None) -> bool:
        return self._resident_ev.wait(timeout)

    def fault_names(self) -> List[str]:
        """Tensors demand-faulted at decode class so far, in fault
        order — this replica's measured hot set (shipped in handoff
        bundles as the claim-table residue)."""
        with self._lock:
            return list(self._fault_names)

    def _sharding_for(self, name: str):
        get = (self._shardings.get
               if isinstance(self._shardings, dict) else None)
        sh = (get(name) if get
              else self._shardings(name, self.ckpt.shape(name)))
        if sh is None:
            raise KeyError(f"no sharding for tensor {name}")
        return sh

    # -- the claim protocol -------------------------------------------------

    def _acquire(self, name: str, eng, klass: str):
        """Load ``name`` under the claim table.  Returns
        ``(array, loaded_by_me)``; every tensor hits NVMe at most once
        across both lanes, and a failed claim is re-claimable."""
        import threading

        while True:
            with self._lock:
                arr = self._arrays.get(name)
                if arr is not None:
                    return arr, False
                ev = self._claims.get(name)
                if ev is None:
                    ev = self._claims[name] = threading.Event()
                    mine = True
                else:
                    mine = False
            if not mine:
                ev.wait()
                continue   # loaded (return above) or failed (re-claim)
            try:
                arr = self.ckpt._load_tensor(eng, name,
                                             self._sharding_for(name),
                                             klass=klass)
                if self._cast is not None:
                    arr = self._cast(arr)
            except BaseException:
                with self._lock:
                    self._claims.pop(name, None)
                ev.set()
                raise
            with self._lock:
                self._arrays[name] = arr
                self._claims.pop(name, None)
                done = len(self._arrays) == len(self.ckpt._by_name)
            ev.set()
            if done:
                self._resident_ev.set()
                if self.coordinator is not None:
                    self.coordinator.note_weights_resident()
            return arr, True

    def get(self, name: str, klass: str = "decode"):
        """Return ``name``'s global array, demand-faulting it at
        ``klass`` (default ``decode``) if not yet resident."""
        import time

        t0 = time.monotonic()
        arr, loaded = self._acquire(name, self.engine, klass)
        if loaded and klass == "decode":
            ms = (time.monotonic() - t0) * 1e3
            with self._lock:
                self._fault_names.append(name)
            stats = getattr(self.engine, "stats", None)
            if stats is not None:
                nbytes = 0
                for shard in getattr(arr, "addressable_shards", []):
                    nbytes += int(
                        getattr(shard.data, "nbytes", 0))
                stats.add(coldstart_faults=1,
                          coldstart_fault_bytes=nbytes)
            if self.coordinator is not None:
                self.coordinator.note_fault_ms(ms)
        return arr

    def materialize(self, klass: str = "decode") -> Dict[str, object]:
        """Fault every missing tensor at ``klass`` and return the full
        params dict — the serving stack's first-step hook (jit flattens
        the whole dict at trace time, so residency must be total before
        the first dispatch)."""
        for name in self.ckpt.keys():
            self.get(name, klass=klass)
        with self._lock:
            return dict(self._arrays)

    # -- the bulk lane ------------------------------------------------------

    def start_bulk(self):
        """Start the background bulk-restore thread (``restore`` class,
        read-once/ICI-scatter when enabled).  Idempotent; returns the
        thread."""
        import threading

        with self._lock:
            if self._bulk_thread is not None:
                return self._bulk_thread
            t = threading.Thread(target=self._bulk_run,
                                 name="strom-coldstart-bulk",
                                 daemon=True)
            self._bulk_thread = t
        t.start()
        return t

    def _bulk_run(self):
        eng = self.engine
        from nvme_strom_tpu.ops.ici import ici_scatter_enabled
        if ici_scatter_enabled():
            from nvme_strom_tpu.ops.ici import scatter_engine
            try:
                served = scatter_engine(
                    eng, [sf.path for sf in self.ckpt.files],
                    mesh=self._ici_mesh, klass="restore")
                if served is not None:
                    eng = served
            except Exception:
                eng = self.engine   # brown out to per-host reads
        stats = getattr(self.engine, "stats", None)
        for name in self.ckpt.keys():
            try:
                _, loaded = self._acquire(name, eng, "restore")
            except Exception:
                # ring tripped / transient failure: leave the tensor to
                # the demand-fault lane (or a later pass) — the bulk
                # thread must never take the replica down
                loaded = False
            if loaded and stats is not None:
                stats.add(coldstart_bulk_tensors=1)

    def join_bulk(self, timeout: Optional[float] = None) -> None:
        with self._lock:
            t = self._bulk_thread
        if t is not None:
            t.join(timeout)

    def close(self) -> None:
        """Release the owned engine (no-op for a borrowed one).  Call
        only after residency — in-flight lanes need the engine."""
        if self._own:
            self.engine.close_all()


def save_checkpoint(path, params: Dict[str, object],
                    engine: Optional[StromEngine] = None) -> None:
    """Global (possibly sharded) arrays → one safetensors file.

    Each array is gathered to host (the D2H transfer) and its payload is
    written through the engine's O_DIRECT writer in pipelined chunks —
    the HBM→NVMe inverse path (SURVEY.md §5 "Checkpoint/resume").  With
    ``engine=None`` a temporary engine is created.  For multi-host use,
    gather to one process first (``jax.experimental.multihost_utils``).
    """
    import jax
    from nvme_strom_tpu.formats.safetensors import write_safetensors_engine

    host = {}
    for name, arr in params.items():
        if isinstance(arr, jax.Array) and len(arr.sharding.device_set) > 1:
            arr = jax.device_get(arr)  # gathers addressable shards
        host[name] = np.asarray(arr)

    own = engine is None
    if engine is None:
        from nvme_strom_tpu.io.faults import build_engine
        engine = build_engine(EngineConfig())
    eng = engine
    try:
        write_safetensors_engine(path, host, eng)
    finally:
        if own:
            eng.close_all()

"""Sharded dataloader: local-NVMe shards → globally-sharded device batches.

The consumer-facing equivalent of the reference's PG-Strom integration
(SURVEY.md §3.5): where PG-Strom pulls table blocks through the DMA ioctls
into GPU scan kernels, this loader pulls WebDataset/TFRecord samples through
the strom-io engine and assembles them into ``jax.Array``s sharded over a
``Mesh`` data axis — benchmark config 3 (BASELINE.md).

Pipeline per batch (prefetched in a background thread):

    index shard (headers only) → planned payload ranges → engine direct
    reads → decode (user fn; raw view for fixed-size records) → host batch
    → make_array_from_process_local_data → global device array

Every process touches only its own shards (data/sharding.py); the global
array is assembled without bulk cross-host traffic.
"""

from __future__ import annotations

import logging
import os
import queue
import threading
import time
from typing import Callable, Iterator, List, Optional, Sequence

import numpy as np

from nvme_strom_tpu.data.sharding import assign_shards, shuffled_indices
from nvme_strom_tpu.formats.tfrecord import TFRecordIndex
from nvme_strom_tpu.formats.wds import WdsShardIndex
from nvme_strom_tpu.io.engine import StromEngine, wait_exact
from nvme_strom_tpu.io.plan import plan_and_submit
from nvme_strom_tpu.parallel.mesh import batch_sharding
from nvme_strom_tpu.utils.config import EngineConfig, LoaderConfig

_SENTINEL = object()
_log = logging.getLogger(__name__)


class ShardReadError(RuntimeError):
    """A shard failed (index/read/decode) and could not be quarantined.

    Always names the originating shard (``path``); the underlying
    exception rides along as ``__cause__``."""

    def __init__(self, path: str, exc: BaseException, detail: str = ""):
        self.path = str(path)
        super().__init__(
            f"shard {self.path}: {type(exc).__name__}: {exc}"
            + (f" ({detail})" if detail else ""))


class LoaderErrors(RuntimeError):
    """Several producer-side errors queued before the consumer saw any.

    3.10-compatible stand-in for ExceptionGroup: every queued error is
    in ``errors`` (oldest first) and in the message; the first is also
    the ``__cause__`` chain root."""

    def __init__(self, errors):
        self.errors = list(errors)
        super().__init__(
            f"{len(self.errors)} loader errors: "
            + "; ".join(f"{type(e).__name__}: {e}" for e in self.errors))


def _process_span(sharding, global_shape, dim: int, proc: int):
    """Contiguous [lo, hi) index range this process's addressable devices
    cover along ``dim`` of the global array.

    The sp mesh axis may span processes (multi-host long context); each
    process must then hand make_array_from_process_local_data only its
    own sequence slice.  Raises if the process's shards are
    non-contiguous along ``dim`` (an sp axis interleaved across hosts —
    a mesh layout the loader does not support)."""
    spans = set()
    size = global_shape[dim]
    for d, idx in sharding.devices_indices_map(tuple(global_shape)).items():
        if d.process_index != proc:
            continue
        sl = idx[dim]
        spans.add((sl.start or 0,
                   size if sl.stop is None else sl.stop))
    lo = min(s for s, _ in spans)
    hi = max(e for _, e in spans)
    covered = sorted(spans)
    # contiguity: the union of spans must tile [lo, hi) without holes
    reach = lo
    for s, e in covered:
        if s > reach:
            raise ValueError(
                f"process {proc} holds non-contiguous spans {covered} "
                f"along dim {dim}; lay out the mesh so the seq axis is "
                "contiguous per process")
        reach = max(reach, e)
    return lo, hi


def _group_blocks(blocks: dict, n_blk: int, pi: int,
                  axis: str) -> tuple:
    """Validate and index the process→batch-block map.

    ``blocks`` maps process_index → set of batch-axis block starts that
    process's devices cover.  Groups must partition the blocks into
    equal tiles: overlapping or unequal coverage would assign disjoint
    shard lists to processes that feed the SAME global rows (silent
    data corruption), or break local_batch = global/n_groups."""
    groups = sorted({frozenset(b) for b in blocks.values()}, key=min)
    all_blocks = [b for g in groups for b in g]
    if (len(all_blocks) != len(set(all_blocks))
            or set(all_blocks) != set(range(n_blk))
            or len({len(g) for g in groups}) != 1):
        raise ValueError(
            f"batch axis {axis!r}: process groups do not tile the "
            f"axis blocks equally ({[sorted(g) for g in groups]}) — "
            "unsupported mesh layout")
    return groups.index(frozenset(blocks[pi])), len(groups)


def _settle(arrays) -> None:
    """Best-effort block on dispatched device transfers before their
    staging is released (the release-after-ready rule's error path):
    a failed batch may have younger puts still reading the buffers."""
    for a in arrays:
        try:
            a.block_until_ready()
        except Exception:
            pass


def _default_decode(parts: dict) -> np.ndarray:
    """Single-part raw samples → uint8 array (copy: counted by caller)."""
    if len(parts) != 1:
        raise ValueError(
            f"sample has parts {sorted(parts)}; pass decode= to combine")
    (payload,) = parts.values()
    return np.frombuffer(payload, dtype=np.uint8)


class ShardedLoader:
    """Iterate globally-sharded batches from per-host local shards.

    Args:
      shard_paths: ALL shard files of the dataset (same list on all hosts).
      mesh: jax Mesh; batches are sharded over `axis` (default "dp").
      global_batch: global batch size (divided across processes).
      fmt: "wds", "tfrecord", or "fixedrec" (the zero-copy contiguous-
        batch fast path, formats/fixedrec.py — no decode, no seq_axis).
      decode: fn(parts: dict[ext, bytes]) -> np.ndarray | dict of arrays.
        For tfrecord, parts is {"": payload}.
      engine: shared StromEngine (one is created if omitted).
      exts: for wds, restrict to these extensions.
      seq_axis: also shard dim 1 of every RANK-2 batch leaf — (batch,
        seq) token arrays — over this mesh axis: the input layout for
        ring/Ulysses sequence parallelism.  Leaves of any other rank
        (per-sample scalars, images, ...) keep the batch-only sharding;
        a rank-2 leaf whose dim 1 the axis cannot divide raises.
    """

    def __init__(self, shard_paths: Sequence, mesh, global_batch: int,
                 fmt: str = "wds",
                 decode: Optional[Callable] = None,
                 engine: Optional[StromEngine] = None,
                 exts: Optional[List[str]] = None,
                 config: Optional[LoaderConfig] = None,
                 axis: str = "dp",
                 seq_axis: Optional[str] = None,
                 process_index: Optional[int] = None,
                 process_count: Optional[int] = None):
        import jax
        if fmt not in ("wds", "wds_raw", "tfrecord", "fixedrec"):
            raise ValueError(f"unknown fmt {fmt!r}")
        if fmt in ("fixedrec", "wds_raw"):
            if decode is not None:
                raise ValueError(
                    f"{fmt} is a zero-copy raw path: payload goes "
                    "staging→device untouched; decode on device instead")
            if seq_axis is not None:
                raise ValueError(
                    f"{fmt} cannot seq-shard: a device's seq slice of "
                    "every row is not a contiguous file span")
            if config is not None and config.shard_error_budget > 0:
                raise ValueError(
                    f"{fmt} does not support shard_error_budget: its "
                    "batch spans coalesce across shards, so per-shard "
                    "quarantine isolation does not exist — zero-copy "
                    "paths fail fast (docs/RESILIENCE.md)")
        self.mesh = mesh
        self.axis = axis
        self.seq_axis = seq_axis
        batch_sharding(mesh, axis, seq_axis)   # validate axes early
        self.fmt = fmt
        self.decode = decode or _default_decode
        self.exts = exts
        self.config = config or LoaderConfig(batch_size=global_batch)
        self.global_batch = global_batch
        pi = jax.process_index() if process_index is None else process_index
        pc = jax.process_count() if process_count is None else process_count
        if global_batch % mesh.shape[axis]:
            raise ValueError(
                f"global_batch {global_batch} not divisible by mesh axis "
                f"{axis}={mesh.shape[axis]}")
        # Shard assignment must follow the BATCH-AXIS group, not the
        # process: when seq_axis spans processes (multi-host long
        # context), several processes hold seq slices of the SAME global
        # batch rows — they must read the same shards in the same order,
        # each slicing its own sequence span at assembly time.  With a
        # batch axis that spans processes (the common case) every group
        # is one process and this reduces to plain per-process
        # round-robin.  Explicit process_index/process_count overrides
        # (single-process multi-host simulation in tests) keep the plain
        # behavior — there is no real device→process map to group by.
        if (seq_axis is not None and process_index is None
                and process_count is None and pc > 1):
            group_idx, n_groups = self._batch_groups(mesh, axis, pi)
        else:
            group_idx, n_groups = pi, pc
        if global_batch % n_groups:
            raise ValueError(
                f"global_batch {global_batch} not divisible by "
                f"{n_groups} batch-axis groups")
        self.local_batch = global_batch // n_groups
        self.local_shards = assign_shards(shard_paths, group_idx, n_groups)
        if engine is None:
            from nvme_strom_tpu.io.faults import build_engine
            engine, self._owns_engine = build_engine(EngineConfig()), True
        else:
            self._owns_engine = False
        self._engine = engine
        self.epoch = 0
        #: shards skipped under config.shard_error_budget, in failure
        #: order — public so a training loop can alert on degradation
        self.quarantined: List[str] = []
        self._quarantined_set: set = set()
        # shard files are immutable for the loader's lifetime: index
        # each once, not once per epoch — the per-epoch re-walk was a
        # whole extra pass of I/O per epoch.  LRU-bounded by
        # config.index_cache_samples so web-scale shard lists don't
        # grow host RSS without limit.
        from collections import OrderedDict
        self._shard_index: "OrderedDict[str, list]" = OrderedDict()
        self._shard_index_total = 0    # cached samples, LRU accounting
        # read-side integrity (STROM_VERIFY, utils/checksum.py): sample
        # payloads verify against each shard's offset-keyed .crc.json
        # sidecar when one exists.  A mismatch is treated like a failed
        # read — re-read once, then the shard takes the normal
        # quarantine-or-raise path.  Applies to the per-sample formats
        # (wds, tfrecord); the zero-copy paths (fixedrec, wds_raw) never
        # touch payload bytes on the host, so their integrity lives in
        # the offline scrubber (tools/strom_scrub.py).
        from nvme_strom_tpu.utils.checksum import VerifyPolicy
        self._verify = VerifyPolicy()
        self._sidecars: dict = {}      # shard path → Sidecar | None

    def _sidecar(self, path):
        key = str(path)
        if key not in self._sidecars:
            from nvme_strom_tpu.utils.checksum import load_sidecar
            self._sidecars[key] = load_sidecar(key)
        return self._sidecars[key]

    @staticmethod
    def _batch_groups(mesh, axis: str, pi: int) -> tuple[int, int]:
        """(my group index, group count) where a 'group' is the set of
        processes whose devices cover the same batch-axis blocks.

        sp-peers (processes sharing batch rows, differing only in their
        sequence slice) land in one group; dp-separated processes land in
        different groups.  Block membership comes from the mesh's actual
        device→process map, so any axis order works."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        n_blk = mesh.shape[axis]
        sh = NamedSharding(mesh, P(axis))
        blocks: dict[int, set] = {}
        for d, idx in sh.devices_indices_map((n_blk,)).items():
            blocks.setdefault(d.process_index, set()).add(
                idx[0].start or 0)
        return _group_blocks(blocks, n_blk, pi, axis)

    # -- sample iteration (host side) -------------------------------------

    def _index_shard(self, path):
        key = str(path)
        cached = self._shard_index.get(key)
        if cached is not None:
            self._shard_index.move_to_end(key)
            return cached
        if self.fmt in ("wds", "wds_raw"):
            idx = WdsShardIndex(path)
            out = [
                {ext: rng for ext, rng in idx.samples[k].items()
                 if self.exts is None or ext in self.exts}
                for k in idx.order
            ]
        else:
            idx = TFRecordIndex(path)
            out = [{"": (idx.offsets[i], idx.lengths[i])}
                   for i in range(len(idx))]
            if self.config.drop_index_pollution:
                # the Python record walk faulted the file resident; a
                # resident span flips the engine's residency planner to
                # the buffered path for every record read that follows
                try:
                    fd = os.open(key, os.O_RDONLY)
                    try:
                        os.posix_fadvise(fd, 0, 0,
                                         os.POSIX_FADV_DONTNEED)
                    finally:
                        os.close(fd)
                except (OSError, AttributeError):
                    pass
        cap = self.config.index_cache_samples
        if cap > 0:
            self._shard_index[key] = out
            self._shard_index_total += len(out)
            while (self._shard_index_total > cap
                   and len(self._shard_index) > 1):
                _, old = self._shard_index.popitem(last=False)
                self._shard_index_total -= len(old)
        return out

    def _iter_local_samples(self) -> Iterator[np.ndarray]:
        order = list(self.local_shards)
        if self.config.shuffle_buffer:
            perm = shuffled_indices(len(order), self.config.seed, self.epoch)
            order = [order[i] for i in perm]
        for path in order:
            if str(path) in self._quarantined_set:
                continue   # failed a previous epoch; still out
            try:
                yield from self._shard_samples(path)
            except Exception as e:   # GeneratorExit/KeyboardInterrupt pass
                self._quarantine_or_raise(path, e)

    def _quarantine_or_raise(self, path, e: Exception) -> None:
        """The shard-quarantine policy (docs/RESILIENCE.md): under the
        error budget the failing shard is skipped-and-logged (counted,
        traced, excluded from later epochs); at budget the failure is
        loud and carries the full quarantine list."""
        budget = self.config.shard_error_budget
        if budget <= 0:
            raise ShardReadError(path, e) from e
        if len(self.quarantined) >= budget:
            raise ShardReadError(
                path, e,
                f"shard error budget ({budget}) exhausted; already "
                f"quarantined: {self.quarantined}") from e
        self.quarantined.append(str(path))
        self._quarantined_set.add(str(path))
        self._engine.stats.add(shards_quarantined=1)
        tracer = getattr(self._engine, "tracer", None)
        if tracer is not None and tracer.enabled:
            now = time.monotonic_ns()
            tracer.add_span("strom.loader.quarantine", now, now,
                            category="strom.resilient", shard=str(path),
                            error=f"{type(e).__name__}: {e}")
        _log.warning(
            "quarantining shard %s after %s: %s (%d/%d of error budget "
            "used)", path, type(e).__name__, e, len(self.quarantined),
            budget)

    def _shard_samples(self, path) -> Iterator[np.ndarray]:
        """Index → pipelined reads → decode for ONE shard (the unit the
        quarantine policy skips)."""
        eng = self._engine
        samples = self._index_shard(path)
        sample_order = range(len(samples))
        if self.config.shuffle_buffer:
            sample_order = shuffled_indices(
                len(samples), self.config.seed + 1, self.epoch)
        fh = eng.open(path)
        pend: list = []
        policy = self._verify
        sidecar = self._sidecar(path) if policy.enabled else None
        try:
            depth = max(2, eng.config.queue_depth // 2)

            def verify_part(ext, off, ln, payload: bytes) -> bytes:
                """CRC32C the part against the shard sidecar (when the
                span is stamped and the policy samples it), via the
                shared retry-once protocol (utils/checksum.py): a
                mismatch re-reads once — transient in-flight corruption
                heals, counted — and a persistent one raises
                ChecksumError, which the caller's quarantine-or-raise
                policy treats exactly like any other shard failure."""
                expected = sidecar.lookup(off, ln)
                if expected is None or not policy.want():
                    return payload
                from nvme_strom_tpu.io.hostcache import spoil_span
                return policy.check_with_reread(
                    payload, expected,
                    lambda: eng.read(fh, off, ln).tobytes(),
                    eng.stats,
                    where=f"sample part {ext!r} at [{off}:+{ln}] "
                          f"of {path}",
                    spoil=lambda: spoil_span(eng, fh, off, ln,
                                             eng.stats))

            def finish(entry):
                idx_parts, reads = entry
                parts = {}
                try:
                    for ext, pieces in reads.items():
                        # the index promised the bytes inside the shard:
                        # a short read means truncation — loud
                        # (quarantine-able), never a silently short
                        # training sample
                        parts[ext] = b"".join(
                            wait_exact(p).tobytes()  # host copy, decode
                            for p in pieces)
                        for p in pieces:
                            p.release()
                        if sidecar is not None:
                            off, ln = idx_parts[ext]
                            parts[ext] = verify_part(ext, off, ln,
                                                     parts[ext])
                finally:
                    # a mid-sample failure must hand the sample's OTHER
                    # reads back too — the entry already left pend, so
                    # the outer drain cannot see them (release is
                    # idempotent for the ones that got there)
                    for pieces in reads.values():
                        for p in pieces:
                            p.release()
                eng.stats.add(bounce_bytes=sum(
                    len(v) for v in parts.values()))
                return self.decode(parts)

            for si in sample_order:
                # one planned batch per sample: a sample's members are
                # adjacent tar/record ranges, so they coalesce into
                # fewer, larger reads and submit under ONE doorbell
                items = list(samples[si].items())
                planned = plan_and_submit(
                    eng, [(fh, off, ln) for _, (off, ln) in items],
                    klass="prefetch")
                reads = {ext: pieces
                         for (ext, _), pieces in zip(items, planned)}
                pend.append((samples[si], reads))
                if len(pend) >= depth:
                    yield finish(pend.pop(0))
            while pend:
                yield finish(pend.pop(0))
        finally:
            # Drain before close: in-flight reads DMA into pool buffers
            # and must be waited + released, or the pool leaks and the
            # engine teardown would race the I/O.
            for _, reads in pend:
                for pieces in reads.values():
                    for p in pieces:
                        p.release()  # waits if still in flight
            eng.close(fh)

    # -- batching + device placement ---------------------------------------

    def _host_batches(self) -> Iterator:
        import jax
        batch: list = []
        for sample in self._iter_local_samples():
            batch.append(sample)
            if len(batch) == self.local_batch:
                yield jax.tree.map(lambda *xs: np.stack(xs), *batch)
                batch = []
        if batch and not self.config.drop_remainder:
            raise ValueError(
                "partial final batch with drop_remainder=False is not "
                "representable as a fixed global shape; pad your dataset "
                "or use drop_remainder=True")

    def __iter__(self) -> Iterator:
        """Yield pytrees of global jax.Arrays sharded over the mesh axis."""
        import jax
        if self.fmt == "fixedrec":
            yield from self._iter_fixedrec()
            return
        if self.fmt == "wds_raw":
            yield from self._iter_wds_raw()
            return
        sharding = batch_sharding(self.mesh, self.axis)
        if self.seq_axis is not None:
            # long-context batches: samples over `axis`, the sequence dim
            # over `seq_axis` (ring/Ulysses consume this layout); rank-1
            # leaves (per-sample scalars) keep the batch-only sharding
            seq_sharding = batch_sharding(self.mesh, self.axis,
                                          self.seq_axis)
        q: queue.Queue = queue.Queue(maxsize=self.config.prefetch)
        err: list = []
        stop = threading.Event()

        def put_checked(item) -> bool:
            """Blocking put that aborts when the consumer went away."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def producer():
            gen = self._host_batches()
            try:
                for hb in gen:
                    if not put_checked(hb):
                        break
            except BaseException as e:  # surfaced in the consumer
                err.append(e)
            finally:
                try:
                    gen.close()  # runs the sample iterator's drain/close
                except BaseException as e:
                    # a drain/close failure is a SECOND error — queue it
                    # too, never shadow (or be shadowed by) the first
                    err.append(e)
                put_checked(_SENTINEL)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                hb = q.get()
                if hb is _SENTINEL:
                    if len(err) == 1:
                        raise err[0]
                    if err:   # every queued error propagates, not just
                        raise LoaderErrors(err) from err[0]   # err[0]
                    break
                global_shape_of = (
                    lambda x: (self.global_batch,) + x.shape[1:])
                span_cache: dict = {}
                def put(x):
                    sh = sharding
                    gshape = global_shape_of(x)
                    # exactly rank 2 == (batch, seq): images and other
                    # higher-rank leaves are NOT sequences — batch-only
                    if self.seq_axis is not None and x.ndim == 2:
                        n_sp = self.mesh.shape[self.seq_axis]
                        if x.shape[1] % n_sp:
                            raise ValueError(
                                f"seq_axis={self.seq_axis!r} (size "
                                f"{n_sp}) cannot shard batch leaf of "
                                f"shape {x.shape}: dim 1 not divisible")
                        sh = seq_sharding
                        # Multi-host sp: each process generated the FULL
                        # sequence locally, but make_array_from_process_
                        # local_data wants only this process's addressable
                        # span along dim 1 — slice it out.  The global
                        # shape keeps the full extent; the span depends
                        # only on (sharding, shape) so it is computed once
                        # per leaf shape, not per batch.
                        if gshape not in span_cache:
                            span_cache[gshape] = _process_span(
                                sh, gshape, dim=1,
                                proc=jax.process_index())
                        lo, hi = span_cache[gshape]
                        if (hi - lo) != x.shape[1]:
                            x = x[:, lo:hi]
                    return jax.make_array_from_process_local_data(
                        sh, x, gshape)
                yield jax.tree.map(put, hb)
        finally:
            # Abandoned iterator: unblock and stop the producer, then wait
            # for it — close() must never race a thread still submitting.
            stop.set()
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            t.join(timeout=30)
        self.epoch += 1

    # -- fixedrec: the zero-copy contiguous-batch fast path -----------------

    def _iter_fixedrec(self) -> Iterator:
        """One epoch of fixedrec batches (VERDICT round 1 #2).

        Per batch, per local device: the device's rows are a CONTIGUOUS
        span of one shard file (split only at shard/buffer boundaries),
        so the plan is engine read → staging view (`.view().reshape()`,
        zero copies) → ``device_put`` of the view → assemble with
        ``make_array_from_single_device_arrays``.  No Python-side byte
        copy exists on the accelerator path; record-level shuffling is
        traded away (shuffle shard order per epoch; randomize record
        order at dataset-prep time, the ffcv/ArrayRecord recipe).

        Multi-host note: every process must hold the same local record
        count (equal shards per process) or epochs desynchronize.
        """
        import jax.numpy as jnp
        from nvme_strom_tpu.formats.fixedrec import FixedRecIndex
        from nvme_strom_tpu.ops.bridge import host_to_device

        eng = self._engine
        sharding = batch_sharding(self.mesh, self.axis)
        order = self._epoch_shard_order()
        idxs = [FixedRecIndex(p) for p in order]
        if not idxs:
            self.epoch += 1
            return
        rec_bytes, dtype = idxs[0].record_bytes, idxs[0].dtype
        rshape = idxs[0].shape
        for ix in idxs[1:]:
            if (ix.record_bytes, ix.dtype, ix.shape) != (rec_bytes, dtype,
                                                         rshape):
                raise ValueError(
                    f"{ix.path}: record layout differs from {idxs[0].path}")
        # split size: the engine's chunk (the planner's default),
        # floored to whole records so every piece reshapes cleanly
        max_read = (eng.config.chunk_bytes // rec_bytes) * rec_bytes
        if max_read == 0:
            raise ValueError(
                f"record ({rec_bytes}B) exceeds engine chunk_bytes "
                f"({eng.config.chunk_bytes}B); raise EngineConfig."
                "chunk_bytes")

        gshape = (self.global_batch,) + rshape
        dev_spans, lo = self._device_row_spans(sharding, gshape)

        # local record r lives in shard s at record r - base[s]
        base, total = [], 0
        for ix in idxs:
            base.append(total)
            total += ix.count
        n_batches = self._count_batches(total)

        def row_spans(r0, r1):
            """Local records [r0, r1) → [(shard_i, offset, nbytes), ...]
            contiguous per-shard extents (split only at shard bounds —
            the planner owns the buffer-bound split)."""
            out = []
            si = 0
            while r0 < r1:
                while base[si] + idxs[si].count <= r0:
                    si += 1
                take = min(r1, base[si] + idxs[si].count) - r0
                out.append((si, (r0 - base[si]) * rec_bytes,
                            take * rec_bytes))
                r0 += take
            return out

        def span_pieces(r0, r1) -> int:
            """Worst-case staging pieces the planner produces for these
            rows (per-shard extents never coalesce across files, so the
            per-extent ceil is exact-or-over — safe for pool-fit)."""
            return sum(-(-nb // max_read)
                       for _, _, nb in row_spans(r0, r1))

        fhs = [eng.open(p) for p in order]

        def plan_reads(r0, r1):
            """One planned, vectored submission for the rows: pieces
            stay record-aligned (split_unit=rec_bytes) so each staging
            view reshapes to whole records."""
            exts = [(fhs[si], off, nb)
                    for si, off, nb in row_spans(r0, r1)]
            parts = plan_and_submit(eng, exts, split_unit=rec_bytes,
                                    klass="prefetch")
            return [p for pieces in parts for p in pieces]

        def to_device(dev, prs):
            parts = []
            try:
                for pr in prs:
                    # the plan never crosses EOF, so a short read ==
                    # truncation; the silent alternative is dropped
                    # records and an opaque shape mismatch at assembly
                    v = wait_exact(pr)
                    n = v.nbytes // rec_bytes
                    parts.append(host_to_device(
                        eng, v.view(dtype).reshape((n,) + rshape), dev))
                return (parts[0] if len(parts) == 1
                        else jnp.concatenate(parts))
            except BaseException:
                # a mid-piece failure leaves younger puts in flight;
                # they must retire before the caller releases staging
                _settle(parts)
                raise

        span_list = sorted({sp for sp in dev_spans.values()})
        batch_pieces = sum(
            span_pieces((g0 - lo), (g1 - lo)) for g0, g1 in span_list)
        yield from self._zero_copy_batches(
            sharding, gshape, dev_spans, lo, n_batches, batch_pieces,
            plan_reads, to_device, fhs)

    # -- shared scaffolding of the zero-copy batch paths --------------------

    def _epoch_shard_order(self) -> List:
        """Per-epoch shard order: shuffled at SHARD granularity only —
        both zero-copy paths trade record-level shuffling away (shuffle
        record order at dataset-prep time, the ffcv/ArrayRecord
        recipe)."""
        order = list(self.local_shards)
        if self.config.shuffle_buffer:
            perm = shuffled_indices(len(order), self.config.seed,
                                    self.epoch)
            order = [order[i] for i in perm]
        return order

    def _count_batches(self, total: int) -> int:
        n_batches = total // self.local_batch
        if total % self.local_batch and not self.config.drop_remainder:
            raise ValueError(
                f"{total} local records do not fill batches of "
                f"{self.local_batch}; pad the dataset or set "
                "drop_remainder=True")
        return n_batches

    def _device_row_spans(self, sharding, gshape):
        """device → its contiguous global row span [g0, g1), plus the
        process's own row base ``lo`` (local record = global row − lo)."""
        import jax
        dev_spans = {}
        for d, idx in sharding.devices_indices_map(gshape).items():
            if d.process_index != jax.process_index():
                continue
            s0 = tuple(idx)[0]
            dev_spans[d] = (0 if s0.start is None else int(s0.start),
                            gshape[0] if s0.stop is None
                            else int(s0.stop))
        lo, hi = _process_span(sharding, gshape, dim=0,
                               proc=jax.process_index())
        if (hi - lo) != self.local_batch:
            raise ValueError(
                f"process rows [{lo},{hi}) != local_batch "
                f"{self.local_batch}")
        return dev_spans, lo

    def _zero_copy_batches(self, sharding, gshape, dev_spans, lo,
                           n_batches, batch_pieces, plan_reads,
                           to_device, fhs) -> Iterator:
        """Prefetch/backpressure engine shared by fixedrec and wds_raw.

        ``plan_reads(r0, r1)`` submits engine reads for local rows
        [r0, r1) and returns them as an arbitrarily nested list with
        PendingReads at the leaves; it is called once per DISTINCT
        device row span per batch (replicas along non-batch mesh axes
        share the reads).  ``to_device(dev, reads)`` turns one device's
        read structure into that device's array (calling ``wait()`` —
        idempotent — on each read).  Rules enforced here:

        - the pool is finite and the engine defers (never errors) reads
          past it; releases happen after transfer, so in-flight pieces
          are bounded by the pool or submission would deadlock;
        - staging buffers release even when a wait/transfer throws;
        - ``config.prefetch`` batches are kept in flight.

        Closes ``fhs`` and bumps the epoch on exit."""
        import jax
        eng = self._engine
        if batch_pieces > eng.n_buffers:
            raise ValueError(
                f"one batch needs {batch_pieces} staging buffers but "
                f"the pool has {eng.n_buffers}; raise EngineConfig."
                "chunk_bytes or lower the batch size")

        def entry_reads(entry):
            reads = {}   # id → PendingRead (replicas share the reads)

            def walk(x):
                if isinstance(x, list):
                    for y in x:
                        walk(y)
                else:
                    reads[id(x)] = x
            for _, rs in entry:
                walk(rs)
            return list(reads.values())

        from nvme_strom_tpu.ops.bridge import StagingRetirePool
        depth = max(1, self.config.prefetch)
        # Deferred staging release (round-4): the per-batch
        # block_until_ready finish() used to pay was one link round
        # trip per batch — the same stop-and-wait disease the round-3
        # verdict called on the SQL scan.  ``held`` counts staging
        # buffers from submission until RETIREMENT (not until yield):
        # the submission-side pressure loops below retire completed
        # transfers first and block on the oldest only when the pool
        # is genuinely full.
        retire = StagingRetirePool(depth)
        held = [0]

        def finish(entry):
            per_dev = []
            reads = entry_reads(entry)
            try:
                for dev, rs in entry:
                    per_dev.append(to_device(dev, rs))
            except BaseException:
                # a failed wait/transfer must still hand every staging
                # buffer of this entry back to the pool — but transfers
                # already dispatched out of it must retire FIRST, or
                # the recycled buffer is overwritten under an in-flight
                # H2D read (the module's release-after-ready rule)
                _settle(per_dev)
                for pr in reads:
                    pr.release()
                held[0] -= len(reads)
                raise

            def release_all():
                for pr in reads:
                    pr.release()
                held[0] -= len(reads)

            retire.push(release_all, per_dev)
            return jax.make_array_from_single_device_arrays(
                gshape, sharding, per_dev)

        # Eager dispatch (window-8 diagnosis): finishing an entry only
        # at yield time meant the consumer's per-batch
        # ``block_until_ready`` had NO younger transfers overlapping it
        # — the link ran stop-and-wait at batch granularity (config 3
        # ledgered 0.35 GiB/s on a 1.44 GiB/s link from exactly this).
        # Two stages now run ahead of the consumer, ``depth`` entries
        # across both: ``pending`` holds planned batches whose engine
        # READS are in flight; a batch whose reads all report ready is
        # promoted (``finish`` — transfers dispatch) into ``ready``,
        # opportunistically so younger reads keep the NVMe queue full
        # while promoted transfers ride the link.  The consumer then
        # receives arrays whose successors are already on the wire.
        # Staging-pool pressure is relieved by retiring the oldest
        # TRANSFERS after force-promoting any read-stage entries
        # (retire pool + pending cover all held staging between them).
        pending: list = []      # planned: reads in flight
        ready: list = []        # finished: transfers dispatched
        try:
            for b in range(n_batches):
                b0 = b * self.local_batch
                retire.drain_ready()
                while held[0] + batch_pieces > eng.n_buffers:
                    if pending:
                        ready.append(finish(pending.pop(0)))
                    elif not retire.retire_oldest():
                        break
                span_reads = {}
                entry = []
                for dev, (g0, g1) in dev_spans.items():
                    key = (g0, g1)
                    if key not in span_reads:
                        span_reads[key] = plan_reads(b0 + (g0 - lo),
                                                     b0 + (g1 - lo))
                    entry.append((dev, span_reads[key]))
                pending.append(entry)
                held[0] += len(entry_reads(entry))
                while pending and all(pr.is_ready()
                                      for pr in entry_reads(pending[0])):
                    ready.append(finish(pending.pop(0)))
                if len(pending) + len(ready) > depth:
                    if not ready:
                        ready.append(finish(pending.pop(0)))
                    yield ready.pop(0)
            while pending:
                ready.append(finish(pending.pop(0)))
            while ready:
                yield ready.pop(0)
        finally:
            retire.flush()
            for entry in pending:
                for pr in entry_reads(entry):
                    pr.release()
            for fh in fhs:
                eng.close(fh)
        self.epoch += 1

    # -- wds_raw: batch-coalesced zero-copy WebDataset path -----------------

    def _iter_wds_raw(self) -> Iterator:
        """One epoch of raw-member WebDataset batches (VERDICT r2 #6).

        The standard wds path copies every payload to host
        (``view.tobytes()`` per member) because ``decode`` is arbitrary
        Python.  But config 3's shards — and any raw-tensor wds dataset
        — need no host decode at all: each member's bytes go staging →
        device untouched.  Per batch, per local device: the device's
        rows' member ranges are engine-read as ONE pipelined sequence
        (tar headers between members are never read), each staging view
        is ``device_put`` directly, members concat/stack ON DEVICE, and
        the global array assembles with
        ``make_array_from_single_device_arrays`` — the fixedrec recipe
        applied to tar shards.  Members that need host decode (JPEG…)
        belong on the standard path; this one requires single-part
        samples of one common byte length (uint8 output, reshape/cast
        on device downstream).  Like fixedrec, record-level shuffling
        is traded away: ``shuffle_buffer`` permutes SHARD order only —
        randomize record order at dataset-prep time.
        """
        import jax.numpy as jnp
        from nvme_strom_tpu.ops.bridge import host_to_device

        eng = self._engine
        sharding = batch_sharding(self.mesh, self.axis)
        order = self._epoch_shard_order()
        recs: list = []          # (shard_i, offset, length) per record
        mlen = None
        for si, path in enumerate(order):
            for parts in self._index_shard(path):
                if len(parts) != 1:
                    raise ValueError(
                        f"{path}: wds_raw needs single-part samples "
                        f"(got {sorted(parts)}); restrict with exts= or "
                        "use the standard wds path")
                ((off, ln),) = parts.values()
                if mlen is None:
                    mlen = ln
                elif ln != mlen:
                    raise ValueError(
                        f"{path}: member length {ln} != {mlen}; wds_raw "
                        "stacks fixed-size members — variable-size "
                        "samples need the standard wds path")
                recs.append((si, off, ln))
        if mlen is None or not recs:
            self.epoch += 1
            return
        gshape = (self.global_batch, mlen)
        dev_spans, lo = self._device_row_spans(sharding, gshape)
        n_batches = self._count_batches(len(recs))
        chunk = eng.config.chunk_bytes   # the planner's split size
        fhs = [eng.open(p) for p in order]

        # Span coalescing (window-9): tar members of one fixed payload
        # size sit at a CONSTANT stride (512 B header + padded
        # payload), so a run of consecutive members is ONE strided
        # read and ONE device put — the batch then materializes as
        # reshape(k, stride)[:, :mlen] on device, a single fused
        # program with the SAME shape every batch (no per-batch
        # recompiles).  That moves the loader from 8 × 1 MiB puts per
        # batch to bench's own chunk regime, whose stream rides ≥0.9
        # of ceiling.  The ~512 B/member of header bytes transferred
        # along is 0.05% overhead; reading one header-gap past the
        # last payload is covered by tar's mandatory ≥1024 B
        # end-of-archive zero blocks (checked against file size below).
        stride = None
        uniform = True
        prev = None
        for si, off, _ in recs:
            if prev is not None and prev[0] == si:
                d = off - prev[1]
                if stride is None:
                    stride = d
                elif d != stride:
                    uniform = False
                    break
            prev = (si, off)
        uniform = uniform and stride is not None and stride >= mlen
        if uniform:
            last = {}
            for si, off, _ in recs:
                last[si] = off
            uniform = all(off + stride <= os.path.getsize(order[si])
                          for si, off in last.items())
        if uniform:
            # ONE encoding of the grouping rule, shared by the read
            # planner (span_groups) and the pool-fit piece count
            # (range_pieces below): record r continues a group iff it
            # stays in the same shard at exactly one stride past its
            # predecessor.  brk[r] marks the group STARTS.
            sis = np.fromiter((r[0] for r in recs), np.int64, len(recs))
            offs = np.fromiter((r[1] for r in recs), np.int64,
                               len(recs))
            brk = np.ones(len(recs), bool)
            brk[1:] = (sis[1:] != sis[:-1]) | (offs[1:] != offs[:-1]
                                               + stride)

        class _Span(list):
            """PendingReads of one strided span + its member count
            (a list subclass so _zero_copy_batches' read-walker still
            finds the leaves)."""
            __slots__ = ("k",)

        def span_groups(r0, r1):
            """Runs of stride-consecutive records in one shard, read
            straight off the shared ``brk`` array — the read planner
            and the pool-fit count (range_pieces) consume the SAME
            group boundaries by construction."""
            groups = []
            for r in range(r0, r1):
                si, off, _ = recs[r]
                if groups and not brk[r]:
                    groups[-1][2] += 1
                else:
                    groups.append([si, off, 1])
            return groups

        # BOTH read plans (strided spans and per-member) route through
        # the shared planner: one place owns the chunk-split rule (the
        # two hand-rolled loops here used to drift), near-adjacent
        # ranges coalesce (consecutive tar members sit one 512 B header
        # apart — under the default gap), and the whole range submits
        # as ONE vectored batch.

        def plan_reads_span(r0, r1):
            groups = span_groups(r0, r1)
            planned = plan_and_submit(
                eng, [(fhs[si], off0, k * stride)
                      for si, off0, k in groups],
                chunk_bytes=chunk, klass="prefetch")
            out = []
            for (si, off0, k), pieces in zip(groups, planned):
                prs = _Span(pieces)
                prs.k = k
                out.append(prs)
            return out

        def plan_reads(r0, r1):
            return plan_and_submit(
                eng, [(fhs[recs[r][0]], recs[r][1], recs[r][2])
                      for r in range(r0, r1)],
                chunk_bytes=chunk, klass="prefetch")

        def dispatch_groups(dev, groups, group_block):
            """One batch's groups → device blocks: wait each read, put
            its staging view, concat a multi-chunk group, finish with
            ``group_block``.  On ANY failure, dispatched puts settle
            before the caller releases staging (release-after-ready) —
            one copy of the hazard path for both read plans."""
            blocks = []
            dispatched = []
            try:
                for prs in groups:
                    parts = []
                    for pr in prs:
                        parts.append(host_to_device(
                            eng, wait_exact(pr), dev))
                        dispatched.append(parts[-1])
                    big = (parts[0] if len(parts) == 1
                           else jnp.concatenate(parts))
                    blocks.append(group_block(big, prs))
                return blocks
            except BaseException:
                _settle(dispatched)
                raise

        def to_device_span(dev, groups):
            blocks = dispatch_groups(
                dev, groups,
                lambda big, prs: big.reshape(prs.k, stride)[:, :mlen])
            return (blocks[0] if len(blocks) == 1
                    else jnp.concatenate(blocks))

        def to_device(dev, groups):
            return jnp.stack(dispatch_groups(dev, groups,
                                             lambda big, prs: big))

        if uniform:
            plan_reads, to_device = plan_reads_span, to_device_span
            # EXACT worst-case staging pieces per batch: a "+margin"
            # guess here underestimates datasets of many tiny shards
            # (each shard boundary opens a new group), and an entry
            # needing more buffers than the pool deadlocks finish() —
            # the engine defers the excess reads and only this entry's
            # own transfers could free buffers.  Walk every batch's
            # distinct device spans and take the max — via the shared
            # ``brk`` array (round-4 advisor: re-running the
            # pure-Python span_groups walk per batch cost O(total
            # records) of list-building at every epoch start): a
            # sub-range's groups are its forced start plus the breaks
            # inside it, and the piece count follows from
            # consecutive-start diffs.
            def range_pieces(a, b):
                starts = np.flatnonzero(brk[a:b])
                if starts.size == 0 or starts[0] != 0:
                    starts = np.concatenate(([0], starts))
                k = np.diff(np.append(starts, b - a))
                return int(np.sum(-(-(k * stride) // chunk)))

            span_list = sorted({sp for sp in dev_spans.values()})
            batch_pieces = 1
            for b in range(n_batches):
                b0 = b * self.local_batch
                tot = sum(range_pieces(b0 + (g0 - lo), b0 + (g1 - lo))
                          for g0, g1 in span_list)
                batch_pieces = max(batch_pieces, tot)
        else:
            batch_pieces = self.local_batch * -(-mlen // chunk)

        yield from self._zero_copy_batches(
            sharding, gshape, dev_spans, lo, n_batches, batch_pieces,
            plan_reads, to_device, fhs)

    def close(self) -> None:
        if self._owns_engine:
            self._engine.close_all()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

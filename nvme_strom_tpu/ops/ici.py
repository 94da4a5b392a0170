"""Read-once shard exchange over the mesh interconnect (docs/PERF.md §7).

The paper's restore bottleneck is per-host SSD bandwidth: every host in a
mesh re-reads the ENTIRE weight/checkpoint payload from its own NVMe, so
an N-host restore moves N·T bytes off flash to deliver T useful bytes per
host.  ICI is an order of magnitude faster than any SSD, so the right
shape is read-once/scatter: each host NVMe-reads only its 1/N byte share
(through the ordinary ``plan_and_submit`` → staging → bridge path at
``restore`` class, governed by the scheduler, breakers and ledger like
any other consumer) and the mesh all-gathers the shares — restore becomes
mesh-aggregate-bound instead of per-host-SSD-bound.

Two layers live here:

:class:`IciExchange`
    ``shard_map``-compatible all-gather of per-host byte rows.  On an
    all-TPU mesh the exchange is a Pallas ring collective built on
    ``pltpu.make_async_remote_copy`` (one-hop neighbour pushes around the
    ring, DMA'd HBM→HBM on the device's own engines); every other mesh
    (the CPU/emulated mesh the tests pin) runs ``jax.lax.all_gather``.
    The mesh's platform selects the backend once, at construction; a
    kernel that fails to build or run raises — nothing degrades at run
    time.

:func:`scatter_engine`
    The consumer-facing orchestrator: partition a file set into per-host
    contiguous byte shares, read the local share(s), exchange, and return
    a :class:`~nvme_strom_tpu.io.scatter.ScatterServeEngine` that serves
    every subsequent read of those files from the gathered bytes.  Any
    failure returns None (counted ``ici_fallbacks``) and the caller keeps
    its plain engine — scatter can only ever brown out to the read-all
    path, never black out a restore.

Knobs: ``STROM_ICI_SCATTER`` (default off — ``=0`` is bit-for-bit the
read-all stack), ``STROM_ICI_HOSTS``, ``STROM_ICI_UNIT_BYTES``.
Counters: ``ici_bytes_read``, ``ici_bytes_received``, ``ici_fallbacks``.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Optional, Sequence

import numpy as np

from nvme_strom_tpu.parallel.mesh import exchange_mesh

_log = logging.getLogger("nvme_strom_tpu.ici")

#: lane-friendly padding of a host's share row: rows exchange as
#: (tiles, 128) int32 words and TPU tiles want multiples of a full
#: (8, 128) tile
_ROW_ALIGN = 4096
_LANES = 128

#: default partition unit — share boundaries stay on O_DIRECT-friendly
#: 1 MiB lines so each host's span submits as large aligned reads
DEFAULT_UNIT_BYTES = 1 << 20


def ici_scatter_enabled() -> bool:
    """``STROM_ICI_SCATTER=1`` turns the read-once/scatter restore mode
    on; unset/``0`` (the default) is the exact read-all stack — the
    gate sits at the consumer so OFF touches zero code paths."""
    return os.environ.get("STROM_ICI_SCATTER", "0") not in ("", "0")


def ici_unit_bytes() -> int:
    """Partition unit for per-host byte shares (``STROM_ICI_UNIT_BYTES``,
    default 1 MiB; clamped to >= 4 KiB so shares stay O_DIRECT-aligned)."""
    try:
        v = int(os.environ.get("STROM_ICI_UNIT_BYTES", DEFAULT_UNIT_BYTES))
    except ValueError:
        return DEFAULT_UNIT_BYTES
    return max(4096, v)


def ici_hosts() -> Optional[int]:
    """Pinned exchange width (``STROM_ICI_HOSTS``); None = every host
    (one per process, or every local device when single-process)."""
    v = os.environ.get("STROM_ICI_HOSTS")
    if not v:
        return None
    try:
        return max(1, int(v))
    except ValueError:
        return None


class IciExchange:
    """All-gather of per-host byte rows over the mesh interconnect.

    ``all_gather(rows)`` takes a ``(n_hosts, row_bytes)`` uint8 array
    whose row h is host h's share (single-process emulation holds every
    row; multi-process runs only need their own rows populated) and
    returns the fully-gathered array on this host.

    TPU: Pallas ring all-gather — each device copies its own row into
    its output slot, then ``n-1`` lockstep steps push the freshest slot
    to the right neighbour via ``make_async_remote_copy`` so every chunk
    DMAs straight into its final HBM location.  Non-TPU meshes run
    ``jax.lax.all_gather`` — the only path a CPU-emulated mesh ever
    compiles.  ``backend`` names which; it is fixed by the mesh's
    platform and a failure of either raises.
    """

    def __init__(self, mesh=None, axis: str = "hosts", stats=None,
                 tracer=None):
        if mesh is None:
            mesh = exchange_mesh(ici_hosts())
        if axis not in mesh.shape:
            raise ValueError(f"mesh has no {axis!r} axis: {mesh.shape}")
        self.mesh = mesh
        self.axis = axis
        self.n = int(mesh.shape[axis])
        self.stats = stats
        self.tracer = tracer
        devs = list(mesh.devices.flat)
        #: "pallas_ring" on an all-TPU mesh, else "lax_all_gather"
        self.backend = ("pallas_ring" if devs and all(
            d.platform == "tpu" for d in devs) else "lax_all_gather")
        self._fns: dict = {}    # tiles -> jitted gather

    # -- the two exchange backends ------------------------------------
    # Both take the (n, tiles, 128) int32 view of the rows, sharded over
    # the axis, and return it whole on every device.

    def _shard_map(self, fn):
        import jax
        from jax.sharding import PartitionSpec as P
        return jax.jit(jax.shard_map(
            fn, mesh=self.mesh, in_specs=P(self.axis, None, None),
            out_specs=P(None, None, None), check_vma=False))

    def _lax_gather_fn(self):
        import jax

        axis = self.axis

        def gather(block):          # (1, tiles, 128) int32 per device
            return jax.lax.all_gather(block, axis, axis=0, tiled=True)

        return self._shard_map(gather)

    def _pallas_gather_fn(self, tiles: int):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        n, axis = self.n, self.axis
        logical = pltpu.DeviceIdType.LOGICAL

        def kernel(local_ref, out_ref, copy_sem, send_sem, recv_sem):
            my_id = lax.axis_index(axis)
            right = lax.rem(my_id + 1, n)
            left = lax.rem(my_id + n - 1, n)
            # both neighbours must have entered the kernel (their output
            # buffers and semaphores live) before any remote DMA lands
            barrier = pltpu.get_barrier_semaphore()
            pltpu.semaphore_signal(barrier, inc=1, device_id=left,
                                   device_id_type=logical)
            pltpu.semaphore_signal(barrier, inc=1, device_id=right,
                                   device_id_type=logical)
            # the refs live in HBM: the local row moves by DMA too (a
            # load/store on an ANY-space ref does not lower).  Rows are
            # (tiles, 128) so a slot is a slice of the untiled leading
            # dim — Mosaic refuses a 1-row slice of a tiled dim.
            own = pltpu.make_async_copy(
                local_ref, out_ref.at[pl.ds(my_id, 1)], copy_sem)
            own.start()
            own.wait()
            pltpu.semaphore_wait(barrier, 2)
            # lockstep ring: at step k every device pushes the chunk
            # that originated k hops to its left straight into the
            # right neighbour's matching output slot — no staging
            # buffer, each chunk DMAs once into its final location.
            # One receive semaphore per step: a left neighbour running
            # ahead must not satisfy this step's wait with a later
            # step's arrival.
            for step in range(n - 1):
                src = lax.rem(my_id + n - step, n) if step else my_id
                rdma = pltpu.make_async_remote_copy(
                    src_ref=out_ref.at[pl.ds(src, 1)],
                    dst_ref=out_ref.at[pl.ds(src, 1)],
                    send_sem=send_sem,
                    recv_sem=recv_sem.at[step],
                    device_id=right,
                    device_id_type=logical,
                )
                rdma.start()
                rdma.wait()

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA((n - 1,))],
        )

        def ring(block):            # (1, tiles, 128) int32 per device
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct((n, tiles, _LANES),
                                               jnp.int32),
                grid_spec=grid_spec,
                compiler_params=pltpu.CompilerParams(
                    has_side_effects=True, collective_id=0),
                name="strom_ici_ring_all_gather",
            )(block)

        return self._shard_map(ring)

    def _gather_fn(self, tiles: int):
        fn = self._fns.get(tiles)
        if fn is None:
            fn = (self._pallas_gather_fn(tiles)
                  if self.backend == "pallas_ring"
                  else self._lax_gather_fn())
            self._fns[tiles] = fn
        return fn

    # -- the host-facing exchange -------------------------------------

    def all_gather(self, rows: np.ndarray) -> np.ndarray:
        """``rows`` (n_hosts, row_bytes) uint8 → the gathered array on
        this host.  Row length pads to an int32-word multiple
        internally; callers see exact bytes back."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if rows.ndim != 2 or rows.shape[0] != self.n:
            raise ValueError(
                f"rows {rows.shape} != ({self.n}, row_bytes)")
        nbytes = rows.shape[1]
        pad = (-nbytes) % _ROW_ALIGN
        if pad:
            rows = np.pad(rows, ((0, 0), (0, pad)))
        tiles = rows.shape[1] // (4 * _LANES)
        t0 = time.monotonic_ns()
        sharding = NamedSharding(self.mesh, P(self.axis, None, None))
        wrows = np.ascontiguousarray(rows).view(np.int32).reshape(
            self.n, tiles, _LANES)
        if jax.process_count() > 1:
            # wrows is the FULL (n, tiles, 128) array with only this
            # process's row(s) populated, so global_shape must say so
            # explicitly: with it, each process's addressable row is
            # sliced from its local copy (row p belongs to process p —
            # exchange_mesh pins axis index == process index).  Without
            # it JAX treats the n local rows as this process's SHARD,
            # infers an (n·n_proc, ...) global array, and the gather
            # silently returns zeros for every peer row instead of
            # raising.
            arr = jax.make_array_from_process_local_data(
                sharding, wrows, global_shape=wrows.shape)
        else:
            arr = jax.device_put(wrows, sharding)
        out = self._gather_fn(tiles)(arr)
        out.block_until_ready()
        got = np.asarray(jax.device_get(out)).view(np.uint8).reshape(
            self.n, -1)
        if got.shape != rows.shape:
            # multi-process-semantics guard: a shape drift here means
            # the gather's global view disagrees with the exchange
            # contract — fail loudly so scatter_engine browns out to
            # the read-all path instead of serving corrupt bytes
            raise RuntimeError(
                f"ici: gather returned {got.shape}, expected "
                f"{rows.shape}")
        got = got[:, :nbytes]
        if self.tracer is not None and getattr(self.tracer, "enabled",
                                               False):
            self.tracer.add_span(
                "strom.ici.exchange", t0, time.monotonic_ns(),
                category="strom.ici", hosts=self.n,
                bytes=int(self.n * nbytes),
                backend=self.backend)
        return got


def _read_share(engine, paths: Sequence[str], fhs: Sequence[int],
                units, row_bytes: int, klass: str) -> np.ndarray:
    """One host's share row: its assigned ``(file_idx, offset, length)``
    units read through the ordinary planner path (coalesced, split at
    the engine's chunk, ``restore``-class — scheduler, breakers and
    hostcache all apply) and packed in unit order."""
    from nvme_strom_tpu.io.engine import wait_exact
    from nvme_strom_tpu.io.plan import plan_and_submit

    row = np.zeros(row_bytes, dtype=np.uint8)
    extents = [(fhs[fi], off, ln) for fi, off, ln in units]
    pos = 0
    per_extent = plan_and_submit(engine, extents, klass=klass)
    flat = [p for pieces in per_extent for p in pieces]
    try:
        for pieces in per_extent:
            for p in pieces:
                v = wait_exact(p)           # short read must fail HERE
                row[pos:pos + v.nbytes] = v
                pos += v.nbytes
                flat.remove(p)
                p.release()
    finally:
        for p in flat:
            p.release()
    return row


def scatter_engine(engine, paths: Sequence[str], mesh=None,
                   klass: str = "restore",
                   unit_bytes: Optional[int] = None, manifest=None):
    """Read-once/scatter front-end over ``engine`` for ``paths``.

    Partitions the files into per-host contiguous byte shares, reads the
    local share(s) through ``plan_and_submit`` at ``klass``, exchanges
    the shares over :class:`IciExchange`, and returns a
    :class:`~nvme_strom_tpu.io.scatter.ScatterServeEngine` serving every
    later read of those files from the gathered bytes — so the consumer
    above (checkpoint restore, weight streaming) runs unchanged and
    bit-identical while each byte leaves flash exactly once per mesh.

    Single-process meshes emulate every virtual host (reading each
    host's share once, attributed per host in the store); multi-process
    runs read only this process's rows.  Returns None — and counts
    ``ici_fallbacks`` — on ANY failure or on a degraded (breaker-open)
    engine, leaving the caller on the plain read-all path with zero
    consumer-visible errors."""
    from nvme_strom_tpu.io.scatter import (
        ScatterServeEngine, ScatterStore, partition_files)

    stats = getattr(engine, "stats", None)
    tracer = getattr(engine, "tracer", None)

    def fall_back(why: str) -> None:
        _log.warning("ici scatter disabled for this restore: %s "
                     "(falling back to local full reads)", why)
        if stats is not None:
            stats.add(ici_fallbacks=1)

    sup = getattr(engine, "supervisor", None)
    if sup is not None:
        try:
            sup.tick()
            if sup.degraded():
                # a browned-out device must serve the work it already
                # owes, not take on the whole mesh's share traffic
                fall_back("engine degraded (breaker open)")
                return None
        except Exception:
            pass

    t0 = time.monotonic_ns()
    try:
        exchange = IciExchange(mesh, stats=stats, tracer=tracer)
        if exchange.n < 2:
            fall_back(f"exchange mesh has {exchange.n} host(s)")
            return None
        if manifest is None:
            sizes = [os.path.getsize(p) for p in paths]
            manifest = partition_files(
                sizes, exchange.n,
                unit_bytes if unit_bytes is not None else ici_unit_bytes())
        elif manifest.n_hosts != exchange.n:
            fall_back(f"manifest built for {manifest.n_hosts} hosts, "
                      f"exchange mesh has {exchange.n}")
            return None
        row_bytes = max(manifest.host_bytes) if manifest.host_bytes else 0
        if row_bytes == 0:
            fall_back("empty file set")
            return None

        import jax
        multi = jax.process_count() > 1
        my_hosts = ([jax.process_index()] if multi
                    else list(range(exchange.n)))
        fhs = [engine.open(p) for p in paths]
        rows = np.zeros((exchange.n, row_bytes), dtype=np.uint8)
        read_by_host = {}
        try:
            for h in my_hosts:
                units = manifest.units_for(h)
                rows[h] = _read_share(engine, paths, fhs, units,
                                      row_bytes, klass)
                read_by_host[h] = manifest.host_bytes[h]
        finally:
            for fh in fhs:
                engine.close(fh)
        gathered = exchange.all_gather(rows)
        for h in my_hosts:
            # cross-row checksum before trusting the store: the rows
            # this process read itself must round-trip bit-identically
            # through the exchange; a mismatch means the gather's
            # process/row mapping drifted, and the same corruption
            # would hit every peer row we CANNOT check locally
            if not np.array_equal(gathered[h], rows[h]):
                raise RuntimeError(
                    f"ici: exchange corrupted host {h}'s own share row")
        store = ScatterStore(paths, manifest, gathered,
                             host_bytes_read=read_by_host)
        local = sum(read_by_host.values())
        if stats is not None:
            # received = payload obtained from peers over ICI instead
            # of local NVMe.  Single-process emulation has no peers —
            # every byte came off this host's own flash — so it reports
            # 0 rather than crediting phantom interconnect savings to
            # the ledger/dashboards
            received = (manifest.total_bytes - local) if multi else 0
            stats.add(ici_bytes_read=int(local),
                      ici_bytes_received=int(received))
        if tracer is not None and getattr(tracer, "enabled", False):
            tracer.add_span(
                "strom.ici.scatter", t0, time.monotonic_ns(),
                category="strom.ici", hosts=exchange.n,
                files=len(paths), bytes_read=int(local),
                total_bytes=int(manifest.total_bytes))
        return ScatterServeEngine(engine, store)
    except Exception as e:
        fall_back(f"{type(e).__name__}: {e}")
        return None

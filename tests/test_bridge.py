"""Bridge tests: NVMe→device streaming correctness on the CPU backend.

The content-verification discipline mirrors the reference's ssd2gpu_test
(DMA bytes vs pread of the same range — SURVEY.md §4), with the device leg
included.
"""

import numpy as np
import pytest

from nvme_strom_tpu.io import StromEngine
from nvme_strom_tpu.ops import DeviceStream, write_from_device
from nvme_strom_tpu.utils.config import EngineConfig
from nvme_strom_tpu.utils.stats import StromStats


@pytest.fixture()
def engine():
    cfg = EngineConfig(chunk_bytes=1 << 20, queue_depth=8,
                       buffer_pool_bytes=16 << 20)
    with StromEngine(cfg, stats=StromStats()) as e:
        yield e


def test_stream_file_roundtrip(engine, tmp_data_file):
    path, payload = tmp_data_file
    ds = DeviceStream(engine, depth=3)
    got = b"".join(np.asarray(c).tobytes() for c in ds.stream_file(path))
    assert got == payload


def test_stream_file_device_resident(engine, tmp_data_file):
    import jax
    path, _ = tmp_data_file
    ds = DeviceStream(engine, depth=2)
    chunk = next(iter(ds.stream_file(path)))
    assert isinstance(chunk, jax.Array)
    assert chunk.dtype == np.uint8


def test_stream_ranges_ordering_and_shapes(engine, tmp_data_file):
    path, payload = tmp_data_file
    fh = engine.open(path)
    ranges = [(0, 1000), (500000, 2048), (7, 4096), (1 << 20, 128)]
    shapes = [None, (2, 1024), None, (128,)]
    ds = DeviceStream(engine, depth=2)
    outs = list(ds.stream_ranges(fh, ranges, shapes=shapes))
    engine.close(fh)
    assert len(outs) == 4
    for (off, ln), shp, out in zip(ranges, shapes, outs):
        arr = np.asarray(out)
        if shp:
            assert arr.shape == tuple(shp)
        assert arr.reshape(-1).tobytes() == payload[off:off + ln]


def test_read_to_device_whole_file(engine, tmp_data_file):
    path, payload = tmp_data_file
    ds = DeviceStream(engine, depth=2)
    arr = ds.read_to_device(path)
    assert np.asarray(arr).tobytes() == payload


def test_read_to_device_dtype_view(engine, tmp_data_file):
    path, payload = tmp_data_file
    ds = DeviceStream(engine)
    arr = ds.read_to_device(path, dtype=np.float32)
    expect = np.frombuffer(payload, dtype=np.float32)
    np.testing.assert_array_equal(np.asarray(arr), expect)


def test_bytes_to_device_accounted(engine, tmp_data_file):
    path, payload = tmp_data_file
    ds = DeviceStream(engine)
    for _ in ds.stream_file(path):
        pass
    assert engine.stats.bytes_to_device == len(payload)


def test_early_close_releases_buffers(engine, tmp_data_file):
    """Abandoning a stream mid-way must return staging buffers to the pool."""
    path, payload = tmp_data_file
    ds = DeviceStream(engine, depth=4)
    it = ds.stream_file(path)
    next(it)
    it.close()  # triggers the generator's finally
    # all buffers must be free again: a full second pass succeeds
    got = b"".join(np.asarray(c).tobytes() for c in ds.stream_file(path))
    assert got == payload


def test_read_to_device_empty_file(engine, tmp_path):
    path = tmp_path / "empty.bin"
    path.write_bytes(b"")
    arr = DeviceStream(engine).read_to_device(path)
    assert arr.shape == (0,) and arr.dtype == np.uint8


def test_write_from_device_roundtrip(engine, tmp_path):
    import jax.numpy as jnp
    data = jnp.arange(1 << 18, dtype=jnp.int32)
    path = tmp_path / "dev.bin"
    n = write_from_device(engine, data, path)
    assert n == (1 << 18) * 4
    back = DeviceStream(engine).read_to_device(path, dtype=np.int32)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(data))


def test_write_from_device_larger_than_chunk(engine, tmp_path):
    """Arrays bigger than one staging buffer must be written chunked.
    Regression: 16 MiB write vs 1 MiB chunk_bytes raised EINVAL."""
    import jax.numpy as jnp
    data = jnp.arange(5 << 20, dtype=jnp.uint8).reshape(5, 1 << 20) % 251
    path = tmp_path / "big.bin"
    n = write_from_device(engine, data, path)
    assert n == 5 << 20
    assert path.read_bytes() == np.asarray(data).tobytes()


def test_stream_ready_drain_matches_blocking(engine, tmp_data_file):
    """drain='ready' (opportunistic is_ready retirement) must yield the
    identical ordered byte stream as the blocking policy — it only
    changes WHEN staging buffers recycle, never what comes out."""
    path, payload = tmp_data_file
    for depth in (1, 2, 5):
        ds = DeviceStream(engine, depth=depth, drain="ready")
        got = b"".join(np.asarray(c).tobytes()
                       for c in ds.stream_file(path))
        assert got == payload
    # arbitrary ranges keep order too
    fh = engine.open(path)
    ranges = [(4096, 8192), (0, 100), (1 << 20, 65536), (77, 4000)]
    ds = DeviceStream(engine, depth=3, drain="ready")
    outs = list(ds.stream_ranges(fh, ranges))
    engine.close(fh)
    for (off, ln), out in zip(ranges, outs):
        assert np.asarray(out).tobytes() == payload[off:off + ln]
    with pytest.raises(ValueError, match="drain"):
        DeviceStream(engine, drain="bogus")


def test_pjrt_cpu_alias_semantics():
    """The measured facts behind host_to_device's protective CPU copy
    (round-2 verdict #2: "a written answer on what PJRT does with the
    buffer" — the full answer is in ARCHITECTURE.md, this pins the
    observable half on the CPU client):

      - device_put of a >=64-byte-aligned numpy source ALIASES it
        (zero-copy): the jax.Array's buffer pointer equals the source's;
      - the alias is LIVE — mutating the numpy buffer mutates the
        "device" array, which is exactly why staging views (recycled on
        release()) must be copied before device_put on host-backed
        devices;
      - a misaligned source is copied (no alias), so the behavior is
        alignment-gated, and the engine pool's 4096-byte alignment
        always qualifies on the zero-copy side.
    """
    import jax

    buf = np.zeros(1 << 16, dtype=np.uint8)
    off = (-buf.ctypes.data) % 4096
    aligned = buf[off:off + 4096]
    arr = jax.device_put(aligned)
    ptr = arr.addressable_shards[0].data.unsafe_buffer_pointer()
    assert ptr == aligned.ctypes.data, "aligned source must alias"
    aligned[:] = 7                      # the hazard host_to_device guards
    assert int(np.asarray(arr)[0]) == 7, "alias is live"

    misaligned = buf[off + 3:off + 3 + 4096]
    arr2 = jax.device_put(misaligned)
    ptr2 = arr2.addressable_shards[0].data.unsafe_buffer_pointer()
    assert ptr2 != misaligned.ctypes.data, "misaligned source must copy"


def test_host_to_device_cpu_copy_is_alias_proof(engine, tmp_data_file):
    """host_to_device's CPU bounce copy makes the yielded array IMMUNE to
    staging recycling: stream a file, then scribble over the whole
    engine pool — every yielded array must still hash to the original
    payload.  (Without the copy, the aliased buffers would show the
    scribble — see test_pjrt_cpu_alias_semantics.)"""
    path, payload = tmp_data_file
    ds = DeviceStream(engine, depth=2)
    parts = list(ds.stream_file(path))
    # scribble: read DIFFERENT content through the same pool slots
    other = str(path) + ".other"
    with open(other, "wb") as f:
        f.write(bytes(len(payload)))
    list(DeviceStream(engine, depth=2).stream_file(other))
    got = b"".join(np.asarray(c).tobytes() for c in parts)
    assert got == payload


def test_staging_retire_pool_orders_and_bounds():
    """StagingRetirePool (deferred staging release, round-4): releases
    fire exactly once each, oldest-first, and pushing past ``depth``
    blocks on the oldest instead of growing without bound."""
    import jax.numpy as jnp
    from nvme_strom_tpu.ops.bridge import StagingRetirePool
    released = []
    pool = StagingRetirePool(depth=2)
    arrs = [jnp.arange(4) + i for i in range(4)]
    for i in range(4):
        pool.push(lambda i=i: released.append(i), [arrs[i]])
    # depth=2: at most 2 entries outstanding, so >= 2 retired already
    assert released == sorted(released) and len(released) >= 2
    pool.flush()
    assert released == [0, 1, 2, 3]
    pool.flush()                    # idempotent, nothing double-fires
    assert released == [0, 1, 2, 3]
    # None release: nothing tracked
    pool.push(None, [arrs[0]])
    pool.flush()
    assert released == [0, 1, 2, 3]


# ---------------------------------------------------------------------------
# Double-buffered host→HBM overlap stage (docs/PERF.md §6)
# ---------------------------------------------------------------------------

class _FakeTransfer:
    """Injectable transfer that records WHEN each slab's bytes are read
    vs when the slab is overwritten — the rotation-invariant probe.
    Returned arrays complete only when the test releases them."""

    def __init__(self):
        self.launched = []          # _FakeArray in launch order

    def __call__(self, host_view, dtype, shape):
        arr = _FakeArray(host_view)
        self.launched.append(arr)
        return arr


class _FakeArray:
    def __init__(self, host_view):
        self._src = host_view              # the slab slice it sources
        self.snapshot = host_view.copy()   # bytes at launch time
        self.nbytes = host_view.nbytes
        self.ready = False
        self.blocked = 0

    def block_until_ready(self):
        # the FIRST block is the completion moment: the slab must still
        # hold the launch-time bytes RIGHT NOW — an overwrite before
        # this is exactly the corruption the ping-pong gate prevents.
        # (Later blocks are after completion; the slab may legitimately
        # have been recycled by then.)
        if not self.ready:
            assert np.array_equal(self._src, self.snapshot), \
                "slab overwritten before its transfer completed"
            self.ready = True
        self.blocked += 1
        return self

    def is_ready(self):
        return self.ready


@pytest.mark.perf
def test_overlap_pingpong_slab_rotation(engine, tmp_data_file):
    """Slab k's next reuse blocks on the transfer it sourced; every
    chunk's device bytes equal the file bytes."""
    path, payload = tmp_data_file
    fake = _FakeTransfer()
    ds = DeviceStream(engine, depth=3, overlap=True,
                      overlap_transfer=fake)
    fh = engine.open(path)
    try:
        ranges = [(i << 20, 1 << 20) for i in range(6)]
        out = list(ds.stream_ranges(fh, ranges))
    finally:
        engine.close(fh)
    assert len(out) == 6
    for i, arr in enumerate(out):
        assert bytes(arr.snapshot) == payload[i << 20:(i + 1) << 20]
    # with two slabs and 6 chunks, chunks 2..5 each had to wait on the
    # transfer two slots earlier — every launched transfer was blocked
    # on before its slab was reused (the assertion inside _FakeArray
    # is the real check; this pins that it actually exercised)
    assert all(a.blocked >= 1 for a in fake.launched)
    assert engine.stats.overlap_chunks == 6
    assert engine.stats.overlap_bytes == 6 << 20


@pytest.mark.perf
def test_overlap_odd_tail_chunk(engine, tmp_data_file):
    """A tail shorter than the slab transfers exactly its bytes."""
    path, payload = tmp_data_file
    fake = _FakeTransfer()
    ds = DeviceStream(engine, depth=2, overlap=True,
                      overlap_transfer=fake)
    fh = engine.open(path)
    try:
        tail = 12_345
        ranges = [(0, 1 << 20), (1 << 20, tail)]
        out = list(ds.stream_ranges(fh, ranges))
    finally:
        engine.close(fh)
    assert out[1].nbytes == tail
    assert bytes(out[1].snapshot) == payload[1 << 20:(1 << 20) + tail]


@pytest.mark.perf
def test_overlap_verify_hook_runs_before_slab_copy(engine,
                                                   tmp_data_file):
    """Ordering contract: verify sees the staging view BEFORE the chunk
    touches a slab (a corrupt chunk never reaches a DMA slab), and a
    verify failure aborts the stream without leaking buffers."""
    path, _payload = tmp_data_file
    events = []

    def verify(ri, view):
        events.append(("verify", ri))
        if ri == 2:
            raise ValueError("synthetic corruption")

    def transfer(host_view, dtype, shape):
        events.append(("transfer", host_view.nbytes))
        a = _FakeArray(host_view)
        a.ready = True
        return a

    ds = DeviceStream(engine, depth=2, overlap=True,
                      overlap_transfer=transfer)
    fh = engine.open(path)
    try:
        with pytest.raises(ValueError, match="synthetic corruption"):
            list(ds.stream_ranges(fh, [(i << 20, 1 << 20)
                                       for i in range(4)],
                                  verify=verify))
    finally:
        engine.close(fh)
    # chunk 2 was verified but never transferred; order is strictly
    # verify-then-transfer per chunk
    assert ("verify", 2) in events
    transfers = [e for e in events if e[0] == "transfer"]
    assert len(transfers) == 2
    vi = [i for i, e in enumerate(events) if e[0] == "verify"]
    ti = [i for i, e in enumerate(events) if e[0] == "transfer"]
    assert all(v < t for v, t in zip(vi, ti))
    # no staging leak: the pool refills completely
    info = engine.pool_info()
    assert info["free_buffers"] == info["n_buffers"]


@pytest.mark.perf
def test_overlap_off_switch_bit_for_bit(engine, tmp_data_file,
                                        monkeypatch):
    """STROM_BRIDGE_OVERLAP=0 reproduces today's path exactly — same
    bytes, zero overlap counters — even on a stream built with
    overlap=True."""
    path, payload = tmp_data_file
    ranges = [(i << 20, 1 << 20) for i in range(4)]
    fh = engine.open(path)
    try:
        monkeypatch.setenv("STROM_BRIDGE_OVERLAP", "0")
        ds = DeviceStream(engine, depth=2, overlap=True)
        off = b"".join(np.asarray(a).tobytes()
                       for a in ds.stream_ranges(fh, ranges))
        assert engine.stats.overlap_chunks == 0
        assert engine.stats.overlap_bytes == 0
        monkeypatch.delenv("STROM_BRIDGE_OVERLAP")
        ds2 = DeviceStream(engine, depth=2, overlap=True)
        on = b"".join(np.asarray(a).tobytes()
                      for a in ds2.stream_ranges(fh, ranges))
        assert engine.stats.overlap_chunks == 4
    finally:
        engine.close(fh)
    assert off == on == payload[:4 << 20]


@pytest.mark.perf
def test_overlap_auto_gate_stays_off_on_cpu(engine, tmp_data_file):
    """overlap=None (auto) keeps a CPU device on the plain device_put
    path — the overlap stage is a TPU-platform engagement."""
    path, payload = tmp_data_file
    ds = DeviceStream(engine, depth=2)          # overlap=None
    got = b"".join(np.asarray(a).tobytes()
                   for a in ds.stream_file(path))
    assert got == payload
    assert engine.stats.overlap_chunks == 0


class _FakeTpu:
    """Stands in for a device whose platform is ``tpu``."""
    platform = "tpu"


@pytest.mark.perf
def test_tpu_transfer_failure_raises_instead_of_degrading(
        engine, tmp_data_file, monkeypatch):
    """On a TPU the overlap stage auto-engages and its one transfer is
    the Pallas DMA from the pinned slab.  When that kernel is refused
    the stream raises: no second attempt, no other transfer path, no
    byte counted as delivered, no staging buffer leaked."""
    import jax
    from nvme_strom_tpu.ops import bridge
    path, _ = tmp_data_file
    attempts = []

    def refusing_kernel(dev):
        def call(pinned):
            attempts.append(pinned)
            raise RuntimeError("Mosaic failed to compile TPU kernel")
        return call

    monkeypatch.setattr(bridge, "_pallas_h2d", refusing_kernel)
    # the pinned_host residency step succeeds, as would any plain put
    monkeypatch.setattr(jax.sharding, "SingleDeviceSharding",
                        lambda dev, memory_kind=None: (dev, memory_kind))
    monkeypatch.setattr(jax, "device_put", lambda arr, where: arr)
    ds = DeviceStream(engine, device=_FakeTpu(), depth=2)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        list(ds.stream_file(path))
    assert len(attempts) == 1
    assert engine.stats.bytes_to_device == 0
    info = engine.pool_info()
    assert info["free_buffers"] == info["n_buffers"]


# ---------------------------------------------------------------------------
# PutStage: the transfer stage between load_sharded's reading thread and
# the devices (PERF.md §3, §6 PR 45)
# ---------------------------------------------------------------------------

from conftest import within as _within     # noqa: E402


def _stage_threads():
    import threading
    return sorted(t.name for t in threading.enumerate()
                  if t.name.startswith("strom-put"))


class _GatedArray:
    """A transfer whose readiness the test controls."""

    def __init__(self):
        import threading
        self.gate = threading.Event()

    def is_ready(self):
        return self.gate.is_set()

    def block_until_ready(self):
        assert self.gate.wait(20), "the test never made this array ready"
        return self


class _Buffers:
    """Staging buffers of a fake reader: ``release(i)`` records the
    release and what the arrays put out of buffer ``i`` said then."""

    def __init__(self):
        self.arrays = {}            # chunk -> [its _GatedArray]
        self.released = []          # chunk ids, in release order
        self.live_at_release = []   # chunks released under a live array

    def job(self, i, ready=False, log=None, dev=None, delay=0.0):
        def put():
            import time
            time.sleep(delay)
            arr = _GatedArray()
            if ready:
                arr.gate.set()
            self.arrays.setdefault(i, []).append(arr)
            if log is not None:
                log[dev].append(i)
            return [arr]
        return put

    def release(self, i):
        def rel():
            if not all(a.is_ready() for a in self.arrays.get(i, ())):
                self.live_at_release.append(i)
            self.released.append(i)
        return rel


@pytest.mark.parametrize("n_dev", [1, 4])
@_within(60)
def test_put_stage_releases_only_after_every_array_is_ready(engine, n_dev):
    """Rule 1: a staging buffer is never recycled under a live
    transfer — with one device and with four putting out of it."""
    import time
    from nvme_strom_tpu.ops.bridge import PutStage
    before = _stage_threads()
    bufs = _Buffers()
    stage = PutStage(engine, depth=2, retire_depth=0)
    devs = [f"dev{k}" for k in range(n_dev)]
    stage.put(bufs.release(0), [(d, bufs.job(0)) for d in devs])
    while len(bufs.arrays.get(0, ())) < n_dev:      # every share was put
        time.sleep(0.001)
    assert bufs.released == []
    for arr in bufs.arrays[0][:-1]:                 # all but one ready
        arr.gate.set()
    time.sleep(0.05)
    assert bufs.released == []
    bufs.arrays[0][-1].gate.set()
    stage.close()
    assert bufs.released == [0] and not bufs.live_at_release
    assert _stage_threads() == before
    assert engine.stats.restore_puts_staged == n_dev
    assert engine.stats.restore_puts_inline == 0


@_within(60)
def test_put_stage_keeps_chunk_order_under_uneven_workers(engine):
    """Rule 2: each device's jobs run in the order they were handed in,
    whatever the other workers do, and ``then`` runs behind them; rule
    3's bound: never more than ``depth`` chunks in the stage."""
    from nvme_strom_tpu.ops.bridge import PutStage
    bufs = _Buffers()
    devs = [f"dev{k}" for k in range(4)]
    log = {d: [] for d in devs}
    stage = PutStage(engine, depth=3, retire_depth=2)
    most = 0
    for i in range(24):
        # worker k sleeps on chunks i % 4 == k: they finish out of order
        stage.put(bufs.release(i), [
            (d, bufs.job(i, ready=True, log=log, dev=d,
                         delay=0.004 if i % 4 == k else 0.0))
            for k, d in enumerate(devs)])
        handed, out = i + 1, len(bufs.released)
        most = max(most, handed - out)
        if i % 8 == 7:
            for d in devs:
                stage.then(d, lambda d=d, i=i: log[d].append(("join", i)))
    stage.close()
    want = []
    for i in range(24):
        want.append(i)
        if i % 8 == 7:
            want.append(("join", i))
    assert all(log[d] == want for d in devs)
    assert sorted(bufs.released) == list(range(24))
    assert not bufs.live_at_release
    assert most <= 3 + 2 + 1        # depth + retire depth + the one in hand
    assert _stage_threads() == []


@pytest.mark.parametrize("who", ["worker", "reader"])
@_within(60)
def test_put_stage_failure_reaches_the_caller_and_frees_everything(
        engine, who):
    """Rule 4: a worker's exception and the reader's both reach the
    caller, every buffer handed in is released, no worker is left."""
    from nvme_strom_tpu.ops.bridge import PutStage
    before = _stage_threads()
    bufs = _Buffers()
    devs = ["dev0", "dev1"]

    def boom():
        raise RuntimeError("put failed")

    handed = []
    with pytest.raises(RuntimeError, match="put failed|reader failed"):
        stage = PutStage(engine, depth=2, retire_depth=2)
        try:
            for i in range(40):
                jobs = [(d, bufs.job(i, ready=True)) for d in devs]
                if who == "worker" and i == 5:
                    jobs[1] = ("dev1", boom)
                if who == "reader" and i == 7:
                    raise RuntimeError("reader failed")
                handed.append(i)
                stage.put(bufs.release(i), jobs)
        finally:
            stage.close()
    assert sorted(bufs.released) == handed and handed
    assert not bufs.live_at_release
    assert _stage_threads() == before
    if who == "worker":
        assert len(handed) < 40         # the reader was stopped early


@_within(60)
def test_put_stage_depth_zero_runs_on_the_calling_thread(engine):
    import threading
    from nvme_strom_tpu.ops.bridge import PutStage
    bufs = _Buffers()
    ran_on = []
    stage = PutStage(engine, depth=0, retire_depth=0)

    def job():
        ran_on.append(threading.current_thread())
        return bufs.job(0, ready=True)()

    stage.put(bufs.release(0), [("dev0", job), ("dev1", job)])
    stage.then("dev0", lambda: ran_on.append(threading.current_thread()))
    assert bufs.released == [0]         # retire depth 0: block per chunk
    stage.close()
    assert set(ran_on) == {threading.current_thread()}
    assert _stage_threads() == []
    assert engine.stats.restore_puts_inline == 2
    assert engine.stats.restore_puts_staged == 0


def _column_checkpoint(tmp_path, rows=96, cols=256):
    from nvme_strom_tpu.formats import write_safetensors
    rng = np.random.default_rng(45)
    tensors = {f"w{i}": rng.standard_normal((rows, cols)).astype(np.float32)
               for i in range(3)}
    tensors["bias"] = rng.standard_normal((cols,)).astype(np.float32)
    path = tmp_path / "stage.safetensors"
    write_safetensors(path, tensors)
    return path, tensors


def _tp4_shardings(tensors):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:4]), ("tp",))
    return {n: NamedSharding(mesh, P(None, "tp") if t.ndim == 2 else P())
            for n, t in tensors.items()}


@pytest.mark.parametrize("pool", ["smallest", "default"])
@_within(120)
def test_load_sharded_stages_by_the_pools_size(tmp_path, pool):
    """Rule 3: a pool too small for any queue (the smallest the engine
    accepts: two buffers) takes the synchronous path and counts
    ``restore_puts_inline``; the default pool counts only
    ``restore_puts_staged``.  Either way the bytes are the file's."""
    from nvme_strom_tpu.parallel.weights import (LazyCheckpoint,
                                                 _stage_depths)
    path, tensors = _column_checkpoint(tmp_path)
    cfg = (EngineConfig(chunk_bytes=1 << 16, queue_depth=4,
                        buffer_pool_bytes=1 << 16)
           if pool == "smallest" else EngineConfig())
    with StromEngine(cfg, stats=StromStats()) as eng:
        stage_depth, retire_depth = _stage_depths(eng, staged=True)
        if pool == "smallest":
            assert eng.n_buffers == 2 and (stage_depth, retire_depth) == (0, 0)
        else:
            assert (eng.n_buffers, stage_depth, retire_depth) == (64, 8, 8)
        half = eng.config.queue_depth // 2
        assert max(2, half) + stage_depth + retire_depth < eng.n_buffers \
            or stage_depth == retire_depth == 0
        params = LazyCheckpoint(path).load_sharded(_tp4_shardings(tensors),
                                                   engine=eng)
        for name, ref in tensors.items():
            np.testing.assert_array_equal(np.asarray(params[name]), ref)
        staged = eng.stats.restore_puts_staged
        inline = eng.stats.restore_puts_inline
        info = eng.pool_info()
        assert info["free_buffers"] == info["n_buffers"]
    assert staged + inline > 0
    assert (staged == 0) if pool == "smallest" else (inline == 0)
    assert _stage_threads() == []


@_within(240)
def test_two_hundred_loads_back_to_back_do_not_deadlock(tmp_path):
    from nvme_strom_tpu.parallel.weights import LazyCheckpoint
    path, tensors = _column_checkpoint(tmp_path)
    shardings = _tp4_shardings(tensors)
    cfg = EngineConfig(chunk_bytes=1 << 14, queue_depth=8,
                       buffer_pool_bytes=16 << 14)
    with StromEngine(cfg, stats=StromStats()) as eng:
        ck = LazyCheckpoint(path)
        for i in range(200):
            params = ck.load_sharded(shardings, engine=eng)
            if i % 50 == 0:
                for name, ref in tensors.items():
                    np.testing.assert_array_equal(
                        np.asarray(params[name]), ref)
        assert eng.stats.restore_puts_inline == 0
        # a load: ``bias`` whole onto four devices out of its one chunk,
        # three column-sharded tensors in one assembled put a device
        assert eng.stats.restore_puts_staged == 200 * 4
        assert eng.stats.restore_puts_assembled == 200 * 3 * 4
    assert _stage_threads() == []


# ---------------------------------------------------------------------------
# HostAssembly: a column shard gathered into a reused host buffer and put
# whole (PERF.md §3, §6 PR 49)
# ---------------------------------------------------------------------------

class _HostPuts:
    """Stands in for ``host_to_device``: every put keeps the host array
    it was given, a copy of what that held then, and a gate."""

    def __init__(self, ready=True):
        self.ready = ready
        self.puts = []

    def __call__(self, engine, host, dev, alias_safe=False):
        arr = _GatedArray()
        arr.dev, arr.host, arr.seen = dev, host, host.copy()
        if self.ready:
            arr.gate.set()
        self.puts.append(arr)
        return arr


def _assembly_job(asm, dev, row0, cut, gathered=None):
    """What ``_issue_tensor_inner`` hands the stage for a column shard:
    gather, then put what became complete; nothing out of the chunk."""
    def job():
        if gathered is None:
            asm.gather(row0, cut)
        else:
            gathered.get(lambda: asm.gather(row0, cut))
        asm.put(dev, row0 + len(cut))
        return ()
    return job


def _shard(t, rows=8, cols=4):
    return (np.arange(rows * cols, dtype=np.float32).reshape(rows, cols)
            + 1000 * t)


@pytest.mark.parametrize("depth", [0, 2])
@_within(60)
def test_assembly_buffer_is_not_rewritten_under_a_live_put(
        engine, monkeypatch, depth):
    """More shards than ring buffers: the third waits for the first's
    array — held back here — before it writes a byte over it."""
    import threading
    import time
    from nvme_strom_tpu.ops import bridge
    h2d = _HostPuts(ready=depth == 0)
    monkeypatch.setattr(bridge, "host_to_device", h2d)
    bufs = _Buffers()
    stage = bridge.PutStage(engine, depth=depth, retire_depth=2)

    def hand_in():
        for t in range(5):
            asm = stage.assemble(["dev0"], 8, (4,), np.float32)
            for c in range(2):
                stage.put(bufs.release(2 * t + c), [("dev0", _assembly_job(
                    asm, "dev0", 4 * c, _shard(t)[4 * c:4 * c + 4]))])
        stage.close()

    feeder = threading.Thread(target=hand_in, daemon=True)
    feeder.start()
    if depth:
        while len(h2d.puts) < bridge.ASSEMBLY_SLOTS:
            time.sleep(0.001)
        time.sleep(0.1)
        # both buffers are on their way; the third shard's gather waits
        assert len(h2d.puts) == bridge.ASSEMBLY_SLOTS
        for t, arr in enumerate(h2d.puts):
            np.testing.assert_array_equal(arr.host, _shard(t))
        # ... with its chunk in hand, and the reader behind it
        assert len(bufs.released) <= 2 * bridge.ASSEMBLY_SLOTS
        h2d.ready = True
        for arr in list(h2d.puts):
            arr.gate.set()
    feeder.join(30)
    assert not feeder.is_alive()
    assert [a.dev for a in h2d.puts] == ["dev0"] * 5
    for t, arr in enumerate(h2d.puts):
        np.testing.assert_array_equal(arr.seen, _shard(t))
    # two buffers, used in turn
    for t, arr in enumerate(h2d.puts[bridge.ASSEMBLY_SLOTS:]):
        assert np.shares_memory(arr.host, h2d.puts[t].host)
    assert not np.shares_memory(h2d.puts[0].host, h2d.puts[1].host)
    assert sorted(bufs.released) == list(range(10))
    assert engine.stats.restore_puts_assembled == 5
    assert engine.stats.restore_puts_staged == 0
    assert engine.stats.restore_puts_inline == 0
    assert _stage_threads() == []


@_within(60)
def test_a_gathered_chunk_is_released_behind_no_transfer(engine,
                                                         monkeypatch):
    """Nothing is put out of a gathered chunk's view: its buffer goes
    back when its last device has gathered it, whatever the retire pool
    still holds and whether or not the assembled put has landed."""
    import time
    from nvme_strom_tpu.ops import bridge
    h2d = _HostPuts(ready=False)
    monkeypatch.setattr(bridge, "host_to_device", h2d)
    bufs = _Buffers()
    stage = bridge.PutStage(engine, depth=2, retire_depth=4)
    devs = ["dev0", "dev1"]
    stage.put(bufs.release("rows"), [(d, bufs.job("rows")) for d in devs])
    asms = {d: stage.assemble([d], 8, (4,), np.float32) for d in devs}
    for c in range(2):
        stage.put(bufs.release(c), [
            (d, _assembly_job(asms[d], d, 4 * c, _shard(k)[4 * c:4 * c + 4]))
            for k, d in enumerate(devs)])
    while len(h2d.puts) < 2:
        time.sleep(0.001)
    time.sleep(0.05)
    assert bufs.released == [0, 1]      # not "rows": its arrays are live
    for arr in bufs.arrays["rows"] + h2d.puts:
        arr.gate.set()
    stage.close()
    assert bufs.released == [0, 1, "rows"] and not bufs.live_at_release
    assert engine.stats.restore_puts_staged == 2
    assert engine.stats.restore_puts_assembled == 2


@_within(60)
def test_devices_that_take_the_same_shard_share_its_gather_and_buffer(
        engine, monkeypatch):
    """dp x tp: one gather a chunk for the group, a put a device, and
    the buffer waits for every device's array."""
    import time
    from nvme_strom_tpu.ops import bridge
    h2d = _HostPuts(ready=False)
    monkeypatch.setattr(bridge, "host_to_device", h2d)
    gathers = []
    real = bridge.HostAssembly.gather
    monkeypatch.setattr(
        bridge.HostAssembly, "gather",
        lambda self, row0, cut: gathers.append(row0) or real(self, row0, cut))
    bufs = _Buffers()
    stage = bridge.PutStage(engine, depth=4, retire_depth=2)
    devs = ["dev0", "dev4"]
    n = bridge.ASSEMBLY_SLOTS + 1
    for t in range(n):
        asm = stage.assemble(devs, 8, (4,), np.float32)
        for c in range(2):
            once = bridge.Once()
            stage.put(bufs.release(2 * t + c), [
                (d, _assembly_job(asm, d, 4 * c, _shard(t)[4 * c:4 * c + 4],
                                  once)) for d in devs])
    while len(h2d.puts) < 2 * bridge.ASSEMBLY_SLOTS:
        time.sleep(0.001)
    # the first shard's buffer: ready on one device, live on the other
    first = [a for a in h2d.puts if a.seen[0, 0] == 0]
    assert sorted(a.dev for a in first) == devs
    assert first[0].host is first[1].host
    first[0].gate.set()
    time.sleep(0.1)
    assert len(h2d.puts) == 2 * bridge.ASSEMBLY_SLOTS
    np.testing.assert_array_equal(first[1].host, _shard(0))
    h2d.ready = True
    for arr in list(h2d.puts):
        arr.gate.set()
    stage.close()
    assert len(gathers) == 2 * n            # once a chunk, not a device
    assert len(h2d.puts) == 2 * n
    for arr in h2d.puts:
        np.testing.assert_array_equal(arr.seen, _shard(arr.seen[0, 0] // 1000))
    assert engine.stats.restore_puts_assembled == 2 * n
    assert sorted(bufs.released) == list(range(2 * n))


@_within(60)
def test_a_shard_over_the_cap_crosses_in_segments(engine, monkeypatch):
    """A chunk may straddle two segments; each is put when its last row
    is in place, in order."""
    from nvme_strom_tpu.ops import bridge
    monkeypatch.setattr(bridge, "ASSEMBLY_BYTES", 5 * 16)   # 5 rows
    h2d = _HostPuts()
    monkeypatch.setattr(bridge, "host_to_device", h2d)
    stage = bridge.PutStage(engine, depth=2, retire_depth=2)
    asm = stage.assemble(["dev0"], 12, (4,), np.float32)
    data = _shard(3, rows=12)
    got = []
    for r in range(0, 12, 4):
        stage.put(None, [("dev0", lambda r=r: (
            asm.gather(r, data[r:r + 4]),
            got.append(len(asm.put("dev0", r + 4))))[:0])])
    stage.close()
    assert got == [0, 1, 2]                 # rows 0-4, 5-9, 10-11
    assert [a.seen.shape for a in h2d.puts] == [(5, 4), (5, 4), (2, 4)]
    np.testing.assert_array_equal(
        np.concatenate([a.seen for a in h2d.puts]), data)
    assert np.shares_memory(h2d.puts[2].host, h2d.puts[0].host)
    assert engine.stats.restore_puts_assembled == 3


@pytest.mark.parametrize("failing", ["gather", "put"])
@_within(60)
def test_assembly_failure_wakes_the_worker_that_waits_for_a_buffer(
        engine, monkeypatch, failing):
    """Two devices take the same shards; one's job fails while the other
    waits for a buffer that only the failed one's put would free: the
    waiter wakes, the caller gets the failure, every staging buffer is
    released and no worker is left."""
    import threading
    import time
    from nvme_strom_tpu.ops import bridge
    h2d = _HostPuts()
    monkeypatch.setattr(bridge, "host_to_device", h2d)
    bufs = _Buffers()
    go = threading.Event()
    devs = ["dev0", "dev4"]

    def failing_job(asm):
        def job():
            assert go.wait(20)
            if failing == "gather":     # rows of another width
                asm.gather(0, np.zeros((8, 3), np.float32))
            raise RuntimeError("put failed")
        return job

    before = _stage_threads()
    handed = []
    with pytest.raises((RuntimeError, ValueError)):
        stage = bridge.PutStage(engine, depth=8, retire_depth=2)
        try:
            for t in range(bridge.ASSEMBLY_SLOTS + 1):
                asm = stage.assemble(devs, 8, (4,), np.float32)
                jobs = [(d, _assembly_job(asm, d, 0, _shard(t)))
                        for d in devs]
                if t == 0:
                    jobs[1] = ("dev4", failing_job(asm))
                handed.append(t)
                stage.put(bufs.release(t), jobs)
            # dev0 has put two shards and waits for the first's buffer
            while len(h2d.puts) < bridge.ASSEMBLY_SLOTS:
                time.sleep(0.001)
            time.sleep(0.05)
            assert len(h2d.puts) == bridge.ASSEMBLY_SLOTS
            go.set()
        finally:
            stage.close()
    assert sorted(bufs.released) == handed
    assert _stage_threads() == before
    assert stage._rings == {}


@_within(240)
def test_shared_assemblies_under_a_short_switch_interval(tmp_path):
    """Stress: eight workers (dp x tp: every column shard shared by two
    devices, its gathers raced for chunk by chunk), tiny chunks, a
    thread switch every 10 us — every load equals the file."""
    import sys
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from nvme_strom_tpu.parallel.weights import LazyCheckpoint
    path, tensors = _column_checkpoint(tmp_path)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "tp"))
    shardings = {n: NamedSharding(mesh, P(None, "tp") if t.ndim == 2 else P())
                 for n, t in tensors.items()}
    cfg = EngineConfig(chunk_bytes=1 << 13, queue_depth=8,
                       buffer_pool_bytes=16 << 13)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with StromEngine(cfg, stats=StromStats()) as eng:
            ck = LazyCheckpoint(path)
            for i in range(40):
                params = ck.load_sharded(shardings, engine=eng)
                for name, ref in tensors.items():
                    assert np.asarray(params[name]).tobytes() == ref.tobytes()
                    for shard in params[name].addressable_shards:
                        assert np.asarray(shard.data).tobytes() \
                            == ref[shard.index].tobytes()
            assert eng.stats.restore_puts_assembled == 40 * 3 * 8
            assert eng.stats.restore_puts_inline == 0
            info = eng.pool_info()
            assert info["free_buffers"] == info["n_buffers"]
    finally:
        sys.setswitchinterval(old)
    assert _stage_threads() == []


@_within(60)
def test_host_buffers_pass_to_the_next_load_once_their_puts_have_landed(
        engine, monkeypatch):
    """``close`` leaves a stage's host buffers with the engine — after
    the arrays put out of them are ready, so the next stage may write
    into them at once — and the next stage takes them in place of new
    ones; one whose shard a device never put is dropped."""
    import threading
    import time
    from nvme_strom_tpu.ops import bridge
    h2d = _HostPuts(ready=False)
    monkeypatch.setattr(bridge, "host_to_device", h2d)
    stage = bridge.PutStage(engine, depth=2, retire_depth=2)
    for t in range(2):
        asm = stage.assemble(["dev0"], 8, (4,), np.float32)
        stage.put(None, [("dev0", _assembly_job(asm, "dev0", 0, _shard(t)))])
    half = stage.assemble(["dev1", "dev5"], 8, (4,), np.float32)
    stage.put(None, [("dev1", _assembly_job(half, "dev1", 0, _shard(9)))])
    closer = threading.Thread(target=stage.close, daemon=True)
    closer.start()
    while len(h2d.puts) < 3:
        time.sleep(0.001)
    time.sleep(0.05)
    assert closer.is_alive() and engine.spare_host_buffers == []
    for arr in h2d.puts:
        arr.gate.set()
    closer.join(20)
    assert not closer.is_alive()
    kept = list(engine.spare_host_buffers)
    assert len(kept) == 2 and all(b.nbytes == bridge.ASSEMBLY_BYTES
                                  for b in kept)
    for arr in h2d.puts:    # dev1's shard still waits for dev5's put
        assert any(np.shares_memory(b, arr.host) for b in kept) \
            == (arr.dev == "dev0")
    # the next load gathers into the same memory
    h2d.ready = True
    stage = bridge.PutStage(engine, depth=0, retire_depth=0)
    asm = stage.assemble(["dev0"], 8, (4,), np.float32)
    stage.put(None, [("dev0", _assembly_job(asm, "dev0", 0, _shard(3)))])
    assert len(engine.spare_host_buffers) == 1
    assert any(np.shares_memory(b, h2d.puts[-1].host) for b in kept)
    stage.close()
    assert len(engine.spare_host_buffers) == 2
    engine.close_all()
    assert engine.spare_host_buffers == []

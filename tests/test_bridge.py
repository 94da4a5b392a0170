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

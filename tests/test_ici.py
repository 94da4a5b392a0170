"""Read-once/ICI-scatter restore (ops/ici.py, io/scatter.py; docs/PERF.md
§7) on the virtual 8-host CPU mesh.

The pins the issue asked for: per-host NVMe traffic is <= 1/N of the
payload plus unit slack (the counters prove it), the served bytes are
bit-identical to the files, scatter-off is the untouched read-all stack,
and every failure mode — degraded engine, exchange error — browns out to
local full reads with zero consumer-visible errors (``ici_fallbacks``
counts each brown-out).
"""

import os

import jax
import numpy as np
import pytest

from nvme_strom_tpu.checkpoint import CheckpointManager, build_restore_manifest
from nvme_strom_tpu.io import StromEngine, wait_exact
from nvme_strom_tpu.io.scatter import ScatterStore, partition_files
from nvme_strom_tpu.ops import ici as ici_mod
from nvme_strom_tpu.ops.ici import IciExchange, scatter_engine
from nvme_strom_tpu.parallel.mesh import exchange_mesh
from nvme_strom_tpu.utils.config import EngineConfig
from nvme_strom_tpu.utils.stats import StromStats

UNIT = 1 << 16          # small partition unit so 8 hosts all get shares
N = 8


@pytest.fixture()
def engine():
    cfg = EngineConfig(chunk_bytes=1 << 20, queue_depth=8,
                       buffer_pool_bytes=8 << 20)
    with StromEngine(cfg, stats=StromStats()) as e:
        yield e


def _write_files(tmp_path, sizes, seed=0):
    rng = np.random.default_rng(seed)
    paths, datas = [], []
    for i, sz in enumerate(sizes):
        p = tmp_path / f"w{i}.safetensors"
        data = rng.integers(0, 256, size=sz, dtype=np.uint8)
        p.write_bytes(data.tobytes())
        paths.append(str(p))
        datas.append(data)
    return paths, datas


# -- partitioning ------------------------------------------------------


def test_partition_covers_every_byte_exactly_once():
    sizes = [1_000_000, 3_000, UNIT, 1, 5 * UNIT + 17]
    man = partition_files(sizes, N, UNIT)
    assert man.total_bytes == sum(sizes)
    assert sum(man.host_bytes) == sum(sizes)
    cover = [np.zeros(sz, np.int32) for sz in sizes]
    for h in range(N):
        for fi, off, ln in man.units_for(h):
            assert ln > 0 and off >= 0 and off + ln <= sizes[fi]
            assert off % UNIT == 0          # shares stay unit-aligned
            cover[fi][off:off + ln] += 1
    for c in cover:
        assert (c == 1).all()               # no gap, no overlap

def test_partition_balance_within_unit_slack():
    sizes = [1_000_000, 3_000, UNIT, 1, 5 * UNIT + 17]
    man = partition_files(sizes, N, UNIT)
    # each file hands out contiguous unit runs differing by at most one
    # unit between hosts, so the worst host carries at most one extra
    # unit per file over the even split
    assert max(man.host_bytes) <= sum(sizes) / N + len(sizes) * UNIT
    for h in range(N):
        assert sum(ln for _, _, ln in man.units_for(h)) \
            == man.host_bytes[h]


# -- the exchange ------------------------------------------------------


def test_exchange_roundtrip_unaligned_rows():
    ex = IciExchange(exchange_mesh(N))
    assert ex.n == N
    assert ex.backend == "lax_all_gather"   # a CPU mesh's one path
    rng = np.random.default_rng(1)
    for row_bytes in (1, 4096, 12_345):
        rows = rng.integers(0, 256, size=(N, row_bytes), dtype=np.uint8)
        got = ex.all_gather(rows)
        assert got.shape == rows.shape
        assert np.array_equal(got, rows)


class _ClaimsTpu:
    """A CPU mesh whose devices report ``platform == "tpu"``."""

    class _Dev:
        platform = "tpu"

    def __init__(self, mesh):
        self.shape = mesh.shape
        self.devices = np.array([self._Dev() for _ in mesh.devices.flat])


def test_exchange_kernel_failure_on_tpu_mesh_raises(monkeypatch):
    """An all-TPU mesh selects the Pallas ring, once, by platform.  When
    the kernel cannot be built (here: a compiled Pallas TPU kernel on
    the CPU backend) the exchange raises — it never degrades to
    ``lax.all_gather``."""
    mesh = exchange_mesh(N)
    ex = IciExchange(_ClaimsTpu(mesh))
    assert ex.backend == "pallas_ring"
    ex.mesh = mesh                          # real devices to shard over
    monkeypatch.setattr(
        IciExchange, "_lax_gather_fn",
        lambda self: pytest.fail("degraded to lax.all_gather"))
    rows = np.zeros((N, 4096), np.uint8)
    with pytest.raises(Exception) as e:
        ex.all_gather(rows)
    assert not isinstance(e.value, pytest.fail.Exception)
    assert ex.backend == "pallas_ring"      # and stays selected


def test_exchange_rejects_bad_shape():
    ex = IciExchange(exchange_mesh(N))
    with pytest.raises(ValueError):
        ex.all_gather(np.zeros((N - 1, 64), np.uint8))


# -- scatter_engine: read-once + bit-identical serving -----------------


def test_scatter_serves_bit_identical_and_reads_one_nth(tmp_path,
                                                        engine):
    sizes = [1_000_000, 3_000, 7 * UNIT + 123]
    paths, datas = _write_files(tmp_path, sizes)
    served = scatter_engine(engine, paths, unit_bytes=UNIT)
    assert served is not None
    store = served.scatter_store

    # per-host flash traffic: <= 1/N of the payload + unit slack, and
    # the whole mesh reads each byte exactly once
    total = sum(sizes)
    assert sum(store.host_bytes_read.values()) == total
    for h, got in store.host_bytes_read.items():
        assert got <= total / N + len(sizes) * UNIT
    assert engine.stats.ici_bytes_read == total
    # single-process emulation has no peers: every byte came off this
    # host's own flash, so no interconnect savings are credited
    assert engine.stats.ici_bytes_received == 0
    assert engine.stats.ici_fallbacks == 0

    # reads crossing unit AND host-share boundaries serve bit-identical
    for fi, (off, ln) in [(0, (0, sizes[0])), (0, (UNIT - 9, 3 * UNIT)),
                          (1, (17, 2_000)), (2, (6 * UNIT, UNIT + 123))]:
        fh = served.open(paths[fi])
        with served.submit_read(fh, off, ln) as pend:
            got = np.asarray(pend.wait(10.0)).view(np.uint8).ravel()[:ln]
            assert np.array_equal(got, datas[fi][off:off + ln])
        served.close(fh)


def test_scatter_readv_mixes_store_hits_and_misses(tmp_path, engine):
    paths, datas = _write_files(tmp_path, [3 * UNIT, 2 * UNIT + 77])
    other = tmp_path / "outside.bin"
    other.write_bytes(bytes(range(256)) * 64)
    served = scatter_engine(engine, paths, unit_bytes=UNIT)
    assert served is not None
    fh0 = served.open(paths[0])
    fho = served.open(str(other))           # NOT in the scattered set
    reads = [(fh0, 0, 1000), (fho, 256, 512), (fh0, UNIT - 5, 100)]
    pends = served.submit_readv(reads, klass="restore")
    want = [datas[0][0:1000].tobytes(),
            other.read_bytes()[256:768],
            datas[0][UNIT - 5:UNIT + 95].tobytes()]
    for p, w in zip(pends, want):
        got = np.asarray(wait_exact(p)).view(np.uint8).tobytes()
        assert got == w
        p.release()
    served.close(fh0)
    served.close(fho)


def test_serve_engine_close_all_clears_handle_tracking(tmp_path,
                                                       engine):
    """``close_all`` must drop the fh→path map with the handles: a
    recycled fh integer naming a DIFFERENT file must never be served
    stale scattered-file bytes."""
    paths, _ = _write_files(tmp_path, [2 * UNIT])
    served = scatter_engine(engine, paths, unit_bytes=UNIT)
    served.open(paths[0])
    assert served._paths
    served.close_all()
    assert served._paths == {}


def test_scatter_store_view_outside_files_is_none(tmp_path, engine):
    paths, datas = _write_files(tmp_path, [2 * UNIT])
    served = scatter_engine(engine, paths, unit_bytes=UNIT)
    store = served.scatter_store
    assert store.view(paths[0], 0, 2 * UNIT + 1) is None   # past EOF
    assert store.view(str(tmp_path / "nope"), 0, 10) is None
    assert np.array_equal(store.view(paths[0], 5, 100), datas[0][5:105])


# -- brown-outs: every failure keeps the caller on read-all ------------


class _DegradedWrap:
    """Engine proxy whose supervisor reports an open breaker (and
    serves the brown-out path with buffered preads, like the real
    EngineSupervisor would)."""

    class _Sup:
        def __init__(self, inner):
            self._inner = inner

        def tick(self):
            pass

        def degraded(self):
            return True

        def serve_degraded(self, engine, spans, stats=None):
            from nvme_strom_tpu.io.health import DegradedRead
            return [DegradedRead(self._inner, fh, off, ln,
                                 getattr(engine, "stats", None))
                    for fh, off, ln in spans]

    def __init__(self, inner):
        self._inner = inner
        self.supervisor = self._Sup(inner)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_scatter_declines_on_degraded_engine(tmp_path, engine):
    paths, _ = _write_files(tmp_path, [2 * UNIT])
    served = scatter_engine(_DegradedWrap(engine), paths,
                            unit_bytes=UNIT)
    assert served is None                   # caller keeps plain engine
    assert engine.stats.ici_fallbacks == 1
    assert engine.stats.ici_bytes_read == 0


def test_scatter_rejects_corrupted_exchange(tmp_path, engine,
                                            monkeypatch):
    """A gather whose process/row mapping drifted (a locally-read row
    comes back altered) must brown out to read-all, never build a
    store that serves corrupt bytes."""
    paths, _ = _write_files(tmp_path, [2 * UNIT])
    real = ici_mod.IciExchange.all_gather

    def corrupt(self, rows):
        got = np.array(real(self, rows))
        got[0, 0] ^= 1
        return got

    monkeypatch.setattr(ici_mod.IciExchange, "all_gather", corrupt)
    served = scatter_engine(engine, paths, unit_bytes=UNIT)
    assert served is None
    assert engine.stats.ici_fallbacks == 1


def test_scatter_falls_back_on_exchange_failure(tmp_path, engine,
                                                monkeypatch):
    paths, _ = _write_files(tmp_path, [2 * UNIT])

    def boom(self, rows):
        raise RuntimeError("ici link down")

    monkeypatch.setattr(ici_mod.IciExchange, "all_gather", boom)
    served = scatter_engine(engine, paths, unit_bytes=UNIT)
    assert served is None
    assert engine.stats.ici_fallbacks == 1


# -- checkpoint restore under the env knob -----------------------------


def _state():
    rng = np.random.default_rng(7)
    return {"params": {
        "w": rng.standard_normal((64, 64)).astype(np.float32),
        "b": rng.standard_normal((4096,)).astype(np.float32)},
        "step": 3}


def _target():
    return {"params": {"w": np.zeros((64, 64), np.float32),
                       "b": np.zeros((4096,), np.float32)}, "step": 0}


def _assert_bitwise(got, want):
    for k in ("w", "b"):
        g = np.asarray(got["params"][k])
        assert g.dtype == want["params"][k].dtype
        assert np.array_equal(g, want["params"][k])  # bit-for-bit


def test_restore_scatter_on_is_bit_identical(tmp_path, engine,
                                             monkeypatch):
    state = _state()
    mgr = CheckpointManager(tmp_path / "ckpt", engine=engine)
    mgr.save(3, state)

    off = mgr.restore(_target())            # knob unset: read-all stack
    assert engine.stats.ici_bytes_read == 0
    assert engine.stats.ici_bytes_received == 0

    monkeypatch.setenv("STROM_ICI_SCATTER", "1")
    monkeypatch.setenv("STROM_ICI_UNIT_BYTES", str(UNIT))
    on = mgr.restore(_target())
    _assert_bitwise(on, state)
    _assert_bitwise(off, state)
    assert on["step"] == off["step"] == 3

    # the counters prove read-once: the mesh read the payload bytes
    # exactly once (vs N·total under read-all); received stays 0 in
    # single-process emulation — there are no peers to receive from
    man = build_restore_manifest(str(mgr.step_dir(3)), N, UNIT)
    assert engine.stats.ici_bytes_read == man.total_bytes
    assert engine.stats.ici_bytes_received == 0
    assert engine.stats.ici_fallbacks == 0
    for hb in man.host_bytes:
        assert hb <= man.total_bytes / N + len(man.paths) * UNIT


def test_restore_scatter_survives_exchange_failure(tmp_path, engine,
                                                   monkeypatch):
    """Breaker-open / link-down mid-restore: the consumer sees ZERO
    errors — restore browns out to local full reads and stays exact."""
    state = _state()
    mgr = CheckpointManager(tmp_path / "ckpt", engine=engine)
    mgr.save(3, state)
    monkeypatch.setenv("STROM_ICI_SCATTER", "1")

    def boom(self, rows):
        raise RuntimeError("ici link down")

    monkeypatch.setattr(ici_mod.IciExchange, "all_gather", boom)
    got = mgr.restore(_target())
    _assert_bitwise(got, state)
    assert engine.stats.ici_fallbacks >= 1


def test_restore_scatter_declines_on_degraded_engine(tmp_path,
                                                     monkeypatch):
    state = _state()
    cfg = EngineConfig(chunk_bytes=1 << 20, queue_depth=8,
                       buffer_pool_bytes=8 << 20)
    with StromEngine(cfg, stats=StromStats()) as inner:
        CheckpointManager(tmp_path / "ckpt", engine=inner).save(3, state)
        wrapped = _DegradedWrap(inner)
        mgr = CheckpointManager(tmp_path / "ckpt", engine=wrapped)
        monkeypatch.setenv("STROM_ICI_SCATTER", "1")
        got = mgr.restore(_target())
        _assert_bitwise(got, state)
        assert inner.stats.ici_fallbacks >= 1
        assert inner.stats.ici_bytes_read == 0   # local full read path


def test_restore_sharded_state_scatter_on(tmp_path, mesh8, engine,
                                          monkeypatch):
    """Sharded restore target (the real trainer shape) under scatter:
    device placement still follows the shardings, values stay exact."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    state = _state()
    mgr = CheckpointManager(tmp_path / "ckpt", engine=engine)
    mgr.save(3, state)
    monkeypatch.setenv("STROM_ICI_SCATTER", "1")
    monkeypatch.setenv("STROM_ICI_UNIT_BYTES", str(UNIT))
    sh_w = NamedSharding(mesh8, P("dp", None))
    sh_b = NamedSharding(mesh8, P())
    # restore honors the target leaves' own shardings
    target = {"params": {
        "w": jax.device_put(np.zeros((64, 64), np.float32), sh_w),
        "b": jax.device_put(np.zeros((4096,), np.float32), sh_b)},
        "step": 0}
    got = mgr.restore(target)
    _assert_bitwise(got, state)
    assert got["params"]["w"].sharding.is_equivalent_to(sh_w, 2)
    assert engine.stats.ici_bytes_read > 0


# -- weight streaming under the env knob -------------------------------


def test_weights_load_sharded_scatter_on(tmp_path, mesh8, engine,
                                         monkeypatch):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from nvme_strom_tpu.formats import write_safetensors
    from nvme_strom_tpu.parallel.weights import LazyCheckpoint

    rng = np.random.default_rng(3)
    tensors = {"wte": rng.standard_normal((64, 32)).astype(np.float32),
               "bias": rng.standard_normal((32,)).astype(np.float32)}
    write_safetensors(tmp_path / "model.safetensors", tensors)
    sh = {"wte": NamedSharding(mesh8, P("dp", None)),
          "bias": NamedSharding(mesh8, P())}

    off = LazyCheckpoint(tmp_path).load_sharded(sh, engine=engine)
    monkeypatch.setenv("STROM_ICI_SCATTER", "1")
    monkeypatch.setenv("STROM_ICI_UNIT_BYTES", str(UNIT))
    on = LazyCheckpoint(tmp_path).load_sharded(sh, engine=engine)
    for k in tensors:
        assert np.array_equal(np.asarray(on[k]), tensors[k])
        assert np.array_equal(np.asarray(off[k]), np.asarray(on[k]))
    assert engine.stats.ici_bytes_read > 0

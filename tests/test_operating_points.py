"""The operating points every consumer runs on, where each is written:
the planner's split size, the host tier's line, the flash kernel's blocks,
the scan's worker count and its streams' depth.
"""

import types

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from nvme_strom_tpu.io import StromEngine, plan_and_submit
from nvme_strom_tpu.utils.config import EngineConfig
from nvme_strom_tpu.utils.stats import StromStats


# -- the planner's split: the engine's chunk_bytes --------------------------

@pytest.mark.parametrize("chunk", [256 << 10, 1 << 20])
def test_planner_splits_an_oversized_extent_at_the_engines_chunk(tmp_path,
                                                                 chunk):
    payload = np.random.default_rng(3).integers(
        0, 256, (5 << 19) + 4096, dtype=np.uint8).tobytes()   # 2.5 MiB + 4 KiB
    path = tmp_path / "extent.bin"
    path.write_bytes(payload)
    cfg = EngineConfig(chunk_bytes=chunk, queue_depth=8,
                       buffer_pool_bytes=16 << 20)
    with StromEngine(cfg, stats=StromStats()) as eng:
        fh = eng.open(str(path))
        (pieces,) = plan_and_submit(eng, [(fh, 0, len(payload))])
        lengths = [len(v.wait()) for v in pieces]
        got = b"".join(bytes(v.wait()) for v in pieces)
        for v in pieces:
            v.release()
        eng.close(fh)
    n_full, tail = divmod(len(payload), chunk)
    assert lengths == [chunk] * n_full + [tail]
    assert got == payload


def test_planner_takes_a_pinned_chunk_over_the_engines(tmp_path):
    path = tmp_path / "extent.bin"
    path.write_bytes(bytes(1 << 20))
    cfg = EngineConfig(chunk_bytes=1 << 20, queue_depth=8,
                       buffer_pool_bytes=16 << 20)
    with StromEngine(cfg, stats=StromStats()) as eng:
        fh = eng.open(str(path))
        (pieces,) = plan_and_submit(eng, [(fh, 0, 1 << 20)],
                                    chunk_bytes=256 << 10)
        assert [len(v.wait()) for v in pieces] == [256 << 10] * 4
        for v in pieces:
            v.release()
        eng.close(fh)


# -- the host tier's line: the first engine's chunk, a power of two ---------

def _engine_like(**config):
    return types.SimpleNamespace(config=types.SimpleNamespace(**config))


@pytest.mark.parametrize("engine,line", [
    (_engine_like(chunk_bytes=4 << 20), 4 << 20),
    (_engine_like(chunk_bytes=3 << 20), 2 << 20),     # rounded DOWN
    (_engine_like(chunk_bytes=8 << 10), 64 << 10),    # floored at 64 KiB
    (None, 4 << 20)],                                 # built without one
    ids=["4MiB", "3MiB", "8KiB", "no_engine"])
def test_hostcache_line_is_the_engines_chunk_as_a_power_of_two(engine, line):
    from nvme_strom_tpu.io import hostcache
    assert hostcache._default_line_bytes(engine) == line


# -- the flash kernel's blocks: 128 x 128 through _pick_block ---------------

@pytest.mark.parametrize("seq,block", [
    (64, 64),       # shorter than a block: the sequence itself
    (128, 128), (512, 128),
    (192, 96),      # no multiple of 128: the largest divisor under it
    (200, 100)])
def test_flash_attention_default_blocks(seq, block):
    from nvme_strom_tpu.ops import flash_attention as fa
    q = jnp.zeros((1, 2, seq, 16), jnp.bfloat16)
    _, bq, bk, causal, _ = fa._prep(q, q, True, None, None, None, None)
    assert (bq, bk, causal) == (block, block, True)


def test_flash_attention_blocks_given_win_and_axes_are_independent():
    from nvme_strom_tpu.ops import flash_attention as fa
    q = jnp.zeros((1, 2, 256, 16), jnp.bfloat16)
    kv = jnp.zeros((1, 2, 384, 16), jnp.bfloat16)
    # block_q given, block_k defaulted — and sized by the KV length
    assert fa._prep(q, kv, False, None, 64, None, None)[1:3] == (64, 128)
    assert fa._prep(q, kv, False, None, None, 192, None)[1:3] == (128, 192)


# -- the scan's width: half the CPUs, one to four ---------------------------

@pytest.mark.parametrize("cpus,workers", [
    (None, 1), (1, 1), (2, 1), (8, 4), (64, 4)])
def test_sql_workers_auto_is_half_the_cpus_up_to_four(monkeypatch, cpus,
                                                      workers):
    from nvme_strom_tpu.sql import scan_plan
    monkeypatch.delenv("STROM_SQL_WORKERS", raising=False)
    monkeypatch.setattr(scan_plan.os, "cpu_count", lambda: cpus)
    assert scan_plan.sql_workers() == workers
    monkeypatch.setenv("STROM_SQL_WORKERS", "0")       # 0 says auto too
    assert scan_plan.sql_workers() == workers


def test_sql_workers_env_overrides_the_cpu_count(monkeypatch):
    from nvme_strom_tpu.sql import scan_plan
    monkeypatch.setattr(scan_plan.os, "cpu_count", lambda: 64)
    monkeypatch.setenv("STROM_SQL_WORKERS", "7")
    assert scan_plan.sql_workers() == 7
    monkeypatch.setenv("STROM_SQL_WORKERS", "1")
    assert scan_plan.sql_workers() == 1


# -- the scan's streams: the engine's queue depth, never under two ----------

class _Stream:
    """Stands where ``ops.bridge.DeviceStream`` is built: keeps how."""
    built = []

    def __init__(self, engine, **kw):
        self.built.append(kw)


@pytest.fixture
def streams(monkeypatch):
    from nvme_strom_tpu.ops import bridge
    monkeypatch.setattr(bridge, "DeviceStream", _Stream)
    _Stream.built.clear()
    return _Stream.built


@pytest.mark.parametrize("queue_depth,n_buffers,workers,depth", [
    (1, 16, 1, 2),          # the floor
    (16, 16, 1, 16),        # one worker: the engine's own depth
    (16, 34, 2, 8),         # (34 - 2) // (2 x 2): the pool's share
    (16, 18, 4, 2),         # ...which is floored too
    (4, 64, 2, 4)])         # a pool with room leaves the depth alone
def test_scan_worker_stream_depth(streams, queue_depth, n_buffers, workers,
                                  depth):
    from nvme_strom_tpu.sql import scan_plan
    from nvme_strom_tpu.sql.pq_direct import SCAN_CLASS
    scanner = types.SimpleNamespace(engine=types.SimpleNamespace(
        config=types.SimpleNamespace(queue_depth=queue_depth),
        n_buffers=n_buffers))
    scan_plan._worker_stream(scanner, "dev", workers)
    assert streams == [dict(device="dev", depth=depth, klass=SCAN_CLASS,
                            drain="ready")]


@pytest.mark.parametrize("queue_depth,depth", [(1, 2), (6, 6)])
@pytest.mark.parametrize("entry", ["read_plain_columns_to_device",
                                   "iter_plain_row_groups_to_device"])
def test_direct_scan_stream_depth(tmp_path, streams, entry, queue_depth,
                                  depth):
    """Both serial entry points of the direct scan open their stream at
    ``(max(2, queue_depth), "ready")``; the stand-in stream has no
    methods, so the scan stops right after building it."""
    from nvme_strom_tpu.sql import pq_direct
    from nvme_strom_tpu.sql.parquet import ParquetScanner
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"a": pa.array(np.arange(100, dtype=np.int32))}),
                   path, compression="none", use_dictionary=False)
    cfg = EngineConfig(queue_depth=queue_depth)
    with StromEngine(cfg, stats=StromStats()) as eng:
        with pytest.raises(AttributeError, match="_Stream"):
            scan = getattr(pq_direct, entry)(ParquetScanner(path, eng), ["a"])
            next(scan)       # the second entry point is a generator
    assert [(kw["depth"], kw["drain"]) for kw in streams] == [(depth, "ready")]


# -- the offloaded optimizer's moment reads: split at the engine's chunk ----

def test_offloaded_adam_splits_moment_ranges_at_the_engines_chunk():
    from nvme_strom_tpu.parallel.opt_offload import OffloadedAdam
    adam = types.SimpleNamespace(
        engine=_engine_like(chunk_bytes=1024),
        _slots=lambda name: [(0, 4096, 2500, (625,))])
    ranges, counts = OffloadedAdam._group_ranges(adam, ["w"])
    assert ranges == [(0, 1024), (1024, 1024), (2048, 452),
                      (4096, 1024), (5120, 1024), (6144, 452)]
    assert counts == [3, 3]

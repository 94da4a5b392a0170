"""Dataloader tests on the 8-device virtual CPU mesh."""

import numpy as np
import pytest

from nvme_strom_tpu.data import ShardedLoader, assign_shards, shuffled_indices
from nvme_strom_tpu.formats import write_tfrecords, write_wds_shard
from nvme_strom_tpu.parallel import make_mesh, local_batch_slice
from nvme_strom_tpu.utils.config import LoaderConfig


def test_assign_shards_partition():
    paths = [f"s{i:03d}.tar" for i in range(10)]
    a = assign_shards(paths, 0, 3)
    b = assign_shards(paths, 1, 3)
    c = assign_shards(paths, 2, 3)
    assert sorted(a + b + c) == sorted(paths)
    assert not (set(a) & set(b) | set(a) & set(c) | set(b) & set(c))
    with pytest.raises(ValueError):
        assign_shards(["one.tar"], 0, 2)


def test_shuffled_indices_deterministic():
    p1 = shuffled_indices(100, seed=7, epoch=3)
    p2 = shuffled_indices(100, seed=7, epoch=3)
    np.testing.assert_array_equal(p1, p2)
    assert not np.array_equal(p1, shuffled_indices(100, seed=7, epoch=4))


def test_make_mesh_wildcard(mesh8):
    m = make_mesh({"dp": 2, "tp": -1})
    assert m.shape == {"dp": 2, "tp": 4}
    with pytest.raises(ValueError):
        make_mesh({"dp": 16})


def test_local_batch_slice():
    assert local_batch_slice(32, 1, 4) == slice(8, 16)
    with pytest.raises(ValueError):
        local_batch_slice(33, 0, 4)


def _make_wds_shards(tmp_path, n_shards=2, per_shard=16, item=64):
    paths = []
    expected = {}
    for s in range(n_shards):
        samples = []
        for i in range(per_shard):
            payload = np.full(item, s * 100 + i, dtype=np.uint8).tobytes()
            samples.append({"bin": payload})
            expected[f"{s}/{i}"] = payload
        p = tmp_path / f"shard-{s:05d}.tar"
        write_wds_shard(p, samples)
        paths.append(str(p))
    return paths, expected


def test_wds_loader_batches(mesh8, tmp_path):
    paths, expected = _make_wds_shards(tmp_path)
    with ShardedLoader(paths, mesh8, global_batch=8, fmt="wds") as dl:
        batches = list(dl)
    assert len(batches) == 4  # 32 samples / batch 8
    seen = set()
    for b in batches:
        assert b.shape == (8, 64)
        assert b.sharding.spec == __import__("jax").sharding.PartitionSpec("dp")
        for row in np.asarray(b):
            seen.add(bytes(row.tobytes()))
    assert seen == set(expected.values())


def test_tfrecord_loader(mesh8, tmp_path):
    recs = [np.full(32, i, dtype=np.uint8).tobytes() for i in range(24)]
    p = tmp_path / "d.tfrecord"
    write_tfrecords(p, recs)
    with ShardedLoader([str(p)], mesh8, global_batch=8,
                       fmt="tfrecord") as dl:
        rows = [bytes(r.tobytes()) for b in dl for r in np.asarray(b)]
    assert sorted(rows) == sorted(recs)


def test_loader_custom_decode(mesh8, tmp_path):
    samples = [{"x": np.float32(i).tobytes(),
                "y": np.int32(i * 2).tobytes()} for i in range(16)]
    p = tmp_path / "s.tar"
    write_wds_shard(p, samples)

    def decode(parts):
        return {
            "x": np.frombuffer(parts["x"], dtype=np.float32),
            "y": np.frombuffer(parts["y"], dtype=np.int32),
        }

    with ShardedLoader([str(p)], mesh8, global_batch=8, fmt="wds",
                       decode=decode) as dl:
        b = next(iter(dl))
    assert set(b) == {"x", "y"}
    assert b["x"].shape == (8, 1)
    np.testing.assert_array_equal(np.asarray(b["y"]).ravel(),
                                  np.asarray(b["x"]).ravel() * 2)


def test_loader_shuffle_determinism(mesh8, tmp_path):
    paths, _ = _make_wds_shards(tmp_path, n_shards=1, per_shard=32)
    cfg = LoaderConfig(batch_size=8, shuffle_buffer=1, seed=5)

    def collect():
        with ShardedLoader(paths, mesh8, global_batch=8, fmt="wds",
                           config=cfg) as dl:
            return [np.asarray(b).copy() for b in dl]

    a, b = collect(), collect()
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    # shuffled order differs from natural order
    flat = np.concatenate([x[:, 0] for x in a])
    assert not np.array_equal(flat, np.sort(flat))


def test_loader_abandoned_iterator(mesh8, tmp_path):
    """Breaking out of a batch loop must stop the producer thread and leave
    the engine reusable (no leaked staging buffers / no use-after-free on
    close). Regression: producer blocked forever on a full queue."""
    paths, expected = _make_wds_shards(tmp_path, n_shards=2, per_shard=32)
    with ShardedLoader(paths, mesh8, global_batch=4, fmt="wds") as dl:
        for b in dl:
            break  # abandon mid-epoch with batches still queued
        # a fresh full epoch on the same loader must see every sample
        rows = {bytes(r.tobytes()) for b in dl for r in np.asarray(b)}
    assert rows == set(expected.values())


def test_loader_validation(mesh8, tmp_path):
    paths, _ = _make_wds_shards(tmp_path, n_shards=1)
    with pytest.raises(ValueError):
        ShardedLoader(paths, mesh8, global_batch=7, fmt="wds")  # not div dp=2
    with pytest.raises(ValueError):
        ShardedLoader(paths, mesh8, global_batch=8, fmt="nope")


def test_loader_simulated_two_processes(mesh8, tmp_path):
    """Multi-host simulation: two 'processes' each load their own shards;
    their local halves together cover the dataset exactly once."""
    paths, expected = _make_wds_shards(tmp_path, n_shards=4, per_shard=8)
    rows = []
    for pi in range(2):
        with ShardedLoader(paths, mesh8, global_batch=16, fmt="wds",
                           process_index=pi, process_count=2) as dl:
            assert dl.local_batch == 8
            for _ in dl._host_batches():
                pass
            # use the host-batch iterator directly: local rows only
        with ShardedLoader(paths, mesh8, global_batch=16, fmt="wds",
                           process_index=pi, process_count=2) as dl:
            for hb in dl._host_batches():
                rows.extend(bytes(r.tobytes()) for r in hb)
    assert sorted(rows) == sorted(expected.values())


def test_loader_seq_sharded_batches(tmp_path):
    """seq_axis shards dim 1 for ring/Ulysses consumers."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from nvme_strom_tpu.data.loader import ShardedLoader

    paths, expected = _make_wds_shards(tmp_path, n_shards=2, per_shard=8,
                                       item=64)
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 devices")
    mesh = Mesh(np.array(devs[:4]).reshape(2, 2), ("dp", "sp"))
    seen = []
    with ShardedLoader(paths, mesh, global_batch=4, fmt="wds",
                       seq_axis="sp") as loader:
        for batch in loader:
            assert batch.shape == (4, 64)
            spec = batch.sharding.spec
            assert tuple(spec) == ("dp", "sp")
            seen.append(np.asarray(batch))
    got = {bytes(row) for b in seen for row in b}
    assert got <= {bytes(v) for v in expected.values()}
    assert len(got) == 16

    with pytest.raises(ValueError, match="no 'sp'"):
        ShardedLoader(paths, Mesh(np.array(devs[:2]).reshape(2), ("dp",)),
                      global_batch=4, fmt="wds", seq_axis="sp")


def test_process_span_single_host_full_extent():
    """Single-process: every sharding covers the full seq extent, and the
    contiguity check accepts it (multi-host slicing is a no-op here)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from nvme_strom_tpu.data.loader import _process_span

    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 devices")
    mesh = Mesh(np.array(devs[:4]).reshape(2, 2), ("dp", "sp"))
    sh = NamedSharding(mesh, P("dp", "sp"))
    lo, hi = _process_span(sh, (4, 64), dim=1, proc=jax.process_index())
    assert (lo, hi) == (0, 64)
    # batch dim too
    lo, hi = _process_span(sh, (4, 64), dim=0, proc=jax.process_index())
    assert (lo, hi) == (0, 4)


def _make_fixedrec_shards(tmp_path, n_shards, per_shard, shape=(8, 8),
                          dtype=np.uint8):
    from nvme_strom_tpu.formats.fixedrec import write_fixedrec

    rng = np.random.default_rng(7)
    paths, rows = [], []
    for s in range(n_shards):
        rec = rng.integers(0, 255, size=(per_shard,) + shape).astype(dtype)
        p = tmp_path / f"shard-{s:03d}.sfr"
        write_fixedrec(p, rec)
        paths.append(str(p))
        rows.extend(np.asarray(r) for r in rec)
    return paths, rows


def test_fixedrec_loader_zero_copy_batches(tmp_path, monkeypatch):
    """The VERDICT#2 path: batches come straight from staging views —
    correct content, correct sharding, and zero Python-side copies (on
    the CPU backend the only counted bounce is the forced device_put
    alias-protection copy, exactly one batch's bytes per batch).

    The residency probe is disabled: the just-written shards are cache
    resident, and a planned page-cache read (counted as bounce, by
    design) would obscure the property under test — that the DIRECT path
    adds no Python-side copies."""
    import jax
    from jax.sharding import Mesh
    from nvme_strom_tpu.data.loader import ShardedLoader
    from nvme_strom_tpu.io.engine import StromEngine
    from nvme_strom_tpu.utils.config import EngineConfig
    from nvme_strom_tpu.utils.stats import StromStats

    monkeypatch.setenv("STROM_NO_RESIDENCY_PROBE", "1")
    paths, rows = _make_fixedrec_shards(tmp_path, n_shards=2, per_shard=8)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("dp",))
    stats = StromStats()
    eng = StromEngine(EngineConfig(), stats=stats)
    seen = 0
    with ShardedLoader(paths, mesh, global_batch=4, fmt="fixedrec",
                       engine=eng) as loader:
        for batch in loader:
            assert batch.shape == (4, 8, 8) and batch.dtype == np.uint8
            assert tuple(batch.sharding.spec) == ("dp",)
            np.testing.assert_array_equal(
                np.asarray(batch),
                np.stack(rows[seen:seen + 4]))
            seen += 4
    assert seen == 16
    eng.sync_stats()
    payload = 16 * 64  # every record byte, moved once
    assert stats.bytes_to_device == payload
    # CPU backend: host_to_device forces+counts one copy per batch —
    # nothing else copies (no tobytes, no np.stack). On TPU this is 0.
    assert stats.bounce_bytes == payload
    eng.close_all()


def test_fixedrec_loader_replicated_and_remainder(tmp_path):
    import jax
    from jax.sharding import Mesh
    from nvme_strom_tpu.data.loader import ShardedLoader

    paths, rows = _make_fixedrec_shards(tmp_path, n_shards=1, per_shard=6)
    # batch axis dp=2, tp axis replicates: one read per span, one
    # transfer per device
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    from nvme_strom_tpu.utils.config import LoaderConfig
    with ShardedLoader(paths, mesh, global_batch=4, fmt="fixedrec",
                       config=LoaderConfig(batch_size=4,
                                           drop_remainder=False)) as ld:
        with pytest.raises(ValueError, match="drop_remainder"):
            list(ld)
    with ShardedLoader(paths, mesh, global_batch=4, fmt="fixedrec") as ld:
        batches = list(ld)
    assert len(batches) == 1
    np.testing.assert_array_equal(np.asarray(batches[0]),
                                  np.stack(rows[:4]))


def test_fixedrec_loader_rejects_decode_and_seq(tmp_path):
    import jax
    from jax.sharding import Mesh
    from nvme_strom_tpu.data.loader import ShardedLoader

    paths, _ = _make_fixedrec_shards(tmp_path, 1, 4)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("dp",))
    with pytest.raises(ValueError, match="zero-copy raw path"):
        ShardedLoader(paths, mesh, 2, fmt="fixedrec",
                      decode=lambda p: p)
    mesh2 = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "sp"))
    with pytest.raises(ValueError, match="seq-shard"):
        ShardedLoader(paths, mesh2, 2, fmt="fixedrec", seq_axis="sp")


# -- negative paths: documented mesh-layout refusals (VERDICT r2 weak #7) --


class _StubDev:
    def __init__(self, proc):
        self.process_index = proc


class _StubSharding:
    """Minimal stand-in for NamedSharding: _process_span only calls
    devices_indices_map(shape) and reads .process_index — a stub lets a
    single-process test exercise the multi-host layouts that can never
    arise on the in-process CPU mesh."""

    def __init__(self, mapping):
        self._mapping = mapping

    def devices_indices_map(self, shape):
        return self._mapping


def test_process_span_rejects_non_contiguous():
    """An sp axis interleaved across hosts: process 0 holds seq spans
    [0,16) and [32,48) with a hole — the loader must refuse, not
    silently feed the wrong tokens (loader._process_span)."""
    from nvme_strom_tpu.data.loader import _process_span

    mapping = {}
    for proc, sl in [(0, (0, 16)), (1, (16, 32)), (0, (32, 48)),
                     (1, (48, 64))]:
        mapping[_StubDev(proc)] = (slice(0, 4), slice(*sl))
    sh = _StubSharding(mapping)
    with pytest.raises(ValueError, match="non-contiguous"):
        _process_span(sh, (4, 64), dim=1, proc=0)
    # the contiguous peer layout passes and returns its own span
    mapping2 = {}
    for proc, sl in [(0, (0, 16)), (0, (16, 32)), (1, (32, 48)),
                     (1, (48, 64))]:
        mapping2[_StubDev(proc)] = (slice(0, 4), slice(*sl))
    lo, hi = _process_span(_StubSharding(mapping2), (4, 64), dim=1, proc=0)
    assert (lo, hi) == (0, 32)


def test_group_blocks_rejects_unequal_tiling():
    """Process groups that overlap, leave holes, or tile the batch axis
    unequally must raise (silent data corruption otherwise): the
    validation core behind ShardedLoader._batch_groups."""
    from nvme_strom_tpu.data.loader import _group_blocks

    # the good case: two sp-peer pairs -> two groups, equal tiles
    ok = {0: {0}, 1: {0}, 2: {1}, 3: {1}}
    assert _group_blocks(ok, 2, 0, "dp") == (0, 2)
    assert _group_blocks(ok, 2, 3, "dp") == (1, 2)

    # overlapping coverage: procs 0+1 cover {0,1} but proc 2 covers {1}
    with pytest.raises(ValueError, match="tile"):
        _group_blocks({0: {0, 1}, 1: {1}}, 2, 0, "dp")

    # hole: block 2 covered by nobody
    with pytest.raises(ValueError, match="tile"):
        _group_blocks({0: {0}, 1: {1}}, 3, 0, "dp")

    # unequal group sizes: {0,1} vs {2}
    with pytest.raises(ValueError, match="tile"):
        _group_blocks({0: {0, 1}, 1: {2}}, 3, 0, "dp")


# -- wds_raw: the batch-coalesced zero-copy WebDataset path (VERDICT r2 #6) --


def _make_raw_wds_shards(tmp_path, n_shards=2, per_shard=8, mlen=4096):
    from nvme_strom_tpu.formats.wds import write_wds_shard
    rng = np.random.default_rng(3)
    paths, rows = [], []
    for s in range(n_shards):
        samples = []
        for i in range(per_shard):
            payload = rng.integers(0, 256, mlen, dtype=np.uint8)
            samples.append({"bin": payload.tobytes()})
            rows.append(payload)
        p = str(tmp_path / f"raw-{s:03d}.tar")
        write_wds_shard(p, samples)
        paths.append(p)
    return paths, rows


def test_wds_raw_batches_match_standard_path(tmp_path):
    """wds_raw yields the same rows as the standard wds path, assembled
    device-side with no host payload copy."""
    import jax
    from jax.sharding import Mesh

    paths, rows = _make_raw_wds_shards(tmp_path)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("dp",))
    with ShardedLoader(paths, mesh, global_batch=4,
                       fmt="wds_raw") as loader:
        got = [np.asarray(b) for b in loader]
    assert len(got) == 4
    flat = np.concatenate(got)
    np.testing.assert_array_equal(flat, np.stack(rows))
    # second epoch works (file handles reopened per epoch)
    with ShardedLoader(paths, mesh, global_batch=4,
                       fmt="wds_raw") as loader:
        assert len(list(loader)) == 4


def test_wds_raw_nonuniform_stride_falls_back(tmp_path):
    """A shard whose members are NOT at constant stride (here: one
    member carries a GNU long-name extension header, adding blocks
    between payloads) must take the per-member read path and still
    yield identical rows — span coalescing is an optimization, never a
    correctness condition."""
    import io as _io
    import tarfile
    import jax
    from jax.sharding import Mesh

    rng = np.random.default_rng(7)
    mlen = 4096
    rows = []
    p = str(tmp_path / "odd.tar")
    with tarfile.open(p, "w", format=tarfile.GNU_FORMAT) as tf:
        for i in range(8):
            payload = rng.integers(0, 256, mlen, dtype=np.uint8)
            rows.append(payload)
            name = (("x" * 120) if i == 3 else f"{i:05d}") + ".bin"
            ti = tarfile.TarInfo(name)
            ti.size = mlen
            tf.addfile(ti, _io.BytesIO(payload.tobytes()))
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("dp",))
    with ShardedLoader([p], mesh, global_batch=4,
                       fmt="wds_raw") as loader:
        got = [np.asarray(b) for b in loader]
    np.testing.assert_array_equal(np.concatenate(got), np.stack(rows))


def test_wds_raw_many_tiny_shards(tmp_path):
    """A batch spanning MANY shards opens one span group per shard —
    the exact shape whose staging-piece count a fixed '+margin'
    estimate underplans (the pool-fit guard must count real groups, or
    an entry needing more buffers than the pool deadlocks finish())."""
    import jax
    from jax.sharding import Mesh

    rng = np.random.default_rng(11)
    paths, rows = [], []
    for s in range(16):
        samples = []
        for i in range(2):
            p = rng.integers(0, 256, 4096, dtype=np.uint8)
            samples.append({"bin": p.tobytes()})
            rows.append(p)
        sp = str(tmp_path / f"tiny-{s:03d}.tar")
        from nvme_strom_tpu.formats.wds import write_wds_shard
        write_wds_shard(sp, samples)
        paths.append(sp)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("dp",))
    with ShardedLoader(paths, mesh, global_batch=16,
                       fmt="wds_raw") as loader:
        got = [np.asarray(b) for b in loader]
    np.testing.assert_array_equal(np.concatenate(got), np.stack(rows))


def test_wds_index_cached_and_no_cache_poisoning(tmp_path, monkeypatch):
    """(a) shards are indexed once per loader, not once per epoch — the
    re-walk was a whole extra end-to-end file read per epoch; (b) the
    index walk leaves no page-cache residue: with the residency probe
    ON, an evicted epoch's member reads must not be planned resident
    (the window-7 wds_raw rows bounced their full payload because the
    walk's 4 MiB windows flipped every member read to the buffered
    path)."""
    from conftest import evict_file
    import jax
    from jax.sharding import Mesh
    from nvme_strom_tpu.io.engine import StromEngine
    from nvme_strom_tpu.utils.stats import StromStats
    import nvme_strom_tpu.data.loader as loader_mod

    paths, _ = _make_raw_wds_shards(tmp_path, n_shards=2, per_shard=8,
                                    mlen=8192)
    built = []
    orig = loader_mod.WdsShardIndex

    class Counting(orig):
        def __init__(self, path):
            built.append(str(path))
            super().__init__(path)

    monkeypatch.setattr(loader_mod, "WdsShardIndex", Counting)
    stats = StromStats()
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("dp",))
    with StromEngine(stats=stats) as eng:
        with ShardedLoader(paths, mesh, global_batch=8, fmt="wds_raw",
                           engine=eng) as loader:
            for _ in range(2):
                for p in paths:
                    evict_file(p)
                assert len(list(loader)) == 2
        eng.sync_stats()
    assert sorted(built) == sorted(str(p) for p in paths)
    assert stats.bytes_resident == 0, (
        f"index walk poisoned the residency planner: "
        f"{stats.bytes_resident} bytes planned resident")


def test_wds_raw_bounce_accounting(tmp_path, monkeypatch):
    """No host-side payload copy: the only bounce on the CPU test device
    is device_put's alias-protection copy — exactly payload bytes, not
    the tobytes()-per-member copy of the standard path (which pays
    payload twice: tobytes + alias copy)."""
    monkeypatch.setenv("STROM_NO_RESIDENCY_PROBE", "1")
    import jax
    from jax.sharding import Mesh
    from nvme_strom_tpu.utils.stats import StromStats
    from nvme_strom_tpu.io.engine import StromEngine

    paths, rows = _make_raw_wds_shards(tmp_path, n_shards=1,
                                       per_shard=8, mlen=8192)
    payload = 8 * 8192
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("dp",))

    def run(fmt):
        stats = StromStats()
        with StromEngine(stats=stats) as eng:
            fh = eng.open(paths[0])
            direct = eng.file_is_direct(fh)
            eng.close(fh)
            with ShardedLoader(paths, mesh, global_batch=8, fmt=fmt,
                               engine=eng) as loader:
                out = [np.asarray(b).reshape(8, -1) for b in loader]
            eng.sync_stats()
        return out, stats.bounce_bytes, direct

    raw_out, raw_bounce, direct = run("wds_raw")
    std_out, std_bounce, _ = run("wds")
    np.testing.assert_array_equal(raw_out[0], std_out[0])
    if not direct:
        pytest.skip("fs rejects O_DIRECT")
    # On the CPU test device both paths count payload once, but from
    # DIFFERENT copies: wds_raw's term is host_to_device's CPU-only
    # alias-protection copy (vanishes on an accelerator -> bounce 0,
    # the config-3 claim); the standard path's is the per-member
    # tobytes() handoff, which an accelerator still pays.  The span-
    # coalesced read carries each member's tar header along (one
    # strided put per batch instead of one per member), so its
    # transfer counts stride = header + payload bytes per member —
    # derived from the shard's own index (round-4 advisor: a literal
    # 512+8192 would silently go stale if the helper's item size
    # changed), as the gap between consecutive member data offsets.
    from nvme_strom_tpu.io.engine import tar_index
    members = tar_index(paths[0])
    stride = members[1][1] - members[0][1]
    assert stride >= 8192 + 512               # payload + >=1 header blk
    assert raw_bounce == 8 * stride
    assert std_bounce == payload


def test_wds_raw_validation(tmp_path):
    import jax
    from jax.sharding import Mesh
    from nvme_strom_tpu.formats.wds import write_wds_shard

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("dp",))
    # multi-part samples are refused
    p = str(tmp_path / "multi.tar")
    write_wds_shard(p, [{"a": b"x" * 512, "b": b"y" * 512}])
    with ShardedLoader([p], mesh, global_batch=2,
                       fmt="wds_raw") as loader:
        with pytest.raises(ValueError, match="single-part"):
            list(loader)
    # unequal member lengths are refused
    p2 = str(tmp_path / "uneq.tar")
    write_wds_shard(p2, [{"bin": b"x" * 512}, {"bin": b"y" * 1024}])
    with ShardedLoader([p2], mesh, global_batch=2,
                       fmt="wds_raw") as loader:
        with pytest.raises(ValueError, match="length"):
            list(loader)
    # decode/seq_axis are refused up front
    with pytest.raises(ValueError, match="zero-copy"):
        ShardedLoader([p2], mesh, 2, fmt="wds_raw", decode=lambda x: x)

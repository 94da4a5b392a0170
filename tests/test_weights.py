"""Lazy sharded weight loading on the 8-device CPU mesh."""

import numpy as np
import pytest

from nvme_strom_tpu.formats import write_safetensors
from nvme_strom_tpu.io import StromEngine
from nvme_strom_tpu.parallel.weights import LazyCheckpoint, save_checkpoint
from nvme_strom_tpu.utils.config import EngineConfig
from nvme_strom_tpu.utils.stats import StromStats


@pytest.fixture()
def engine():
    cfg = EngineConfig(chunk_bytes=1 << 20, queue_depth=8,
                       buffer_pool_bytes=8 << 20)
    with StromEngine(cfg, stats=StromStats()) as e:
        yield e


@pytest.fixture()
def ckpt(tmp_path):
    rng = np.random.default_rng(0)
    tensors = {
        "wte": rng.standard_normal((64, 32)).astype(np.float32),
        "w_col": rng.standard_normal((16, 64)).astype(np.float32),
        "bias": rng.standard_normal((32,)).astype(np.float32),
        "scalar": np.float32(3.5).reshape(()),
    }
    # two shard files, HF-style
    write_safetensors(tmp_path / "model-00001-of-00002.safetensors",
                      {"wte": tensors["wte"], "scalar": tensors["scalar"]})
    write_safetensors(tmp_path / "model-00002-of-00002.safetensors",
                      {"w_col": tensors["w_col"], "bias": tensors["bias"]})
    return tmp_path, tensors


def _shardings(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return {
        "wte": NamedSharding(mesh, P("dp", None)),     # row-sharded
        "w_col": NamedSharding(mesh, P(None, "tp")),   # column-sharded
        "bias": NamedSharding(mesh, P()),              # replicated
        "scalar": NamedSharding(mesh, P()),
    }


def test_lazy_load_all_shardings(mesh8, ckpt, engine):
    import jax
    tmp_path, tensors = ckpt
    lc = LazyCheckpoint(tmp_path)
    assert set(lc.keys()) == set(tensors)
    params = lc.load_sharded(_shardings(mesh8), engine=engine)
    for name, ref in tensors.items():
        got = params[name]
        assert isinstance(got, jax.Array)
        assert got.shape == ref.shape
        np.testing.assert_array_equal(np.asarray(got), ref)
    # row-sharded tensor: each unique slice read once -> exactly one full
    # pass over wte; replicated bias read once per host, not per device
    snap = engine.engine_stats()
    expected = sum(t.nbytes for t in tensors.values())
    assert snap["bytes_direct"] + snap["bytes_fallback"] == expected


def test_lazy_load_sharding_fn(mesh8, ckpt, engine):
    from jax.sharding import NamedSharding, PartitionSpec as P
    tmp_path, tensors = ckpt
    lc = LazyCheckpoint(tmp_path)
    params = lc.load_sharded(
        lambda name, shape: NamedSharding(mesh8, P()), engine=engine)
    np.testing.assert_array_equal(np.asarray(params["wte"]), tensors["wte"])


def test_lazy_load_dtype_cast(mesh8, ckpt, engine):
    import jax.numpy as jnp
    tmp_path, tensors = ckpt
    params = LazyCheckpoint(tmp_path).load_sharded(
        _shardings(mesh8), engine=engine, dtype=jnp.bfloat16)
    assert params["wte"].dtype == jnp.bfloat16


def test_hf_index_json(mesh8, ckpt, engine):
    import json
    tmp_path, tensors = ckpt
    index = {"weight_map": {
        "wte": "model-00001-of-00002.safetensors",
        "scalar": "model-00001-of-00002.safetensors",
        "w_col": "model-00002-of-00002.safetensors",
        "bias": "model-00002-of-00002.safetensors",
    }}
    ipath = tmp_path / "model.safetensors.index.json"
    ipath.write_text(json.dumps(index))
    lc = LazyCheckpoint(ipath)
    assert set(lc.keys()) == set(tensors)


def test_save_then_lazy_load_roundtrip(mesh8, ckpt, engine, tmp_path):
    tmp, tensors = ckpt
    params = LazyCheckpoint(tmp).load_sharded(_shardings(mesh8),
                                              engine=engine)
    out = tmp_path / "resaved.safetensors"
    save_checkpoint(out, params)
    back = LazyCheckpoint(out).load_sharded(_shardings(mesh8), engine=engine)
    for name, ref in tensors.items():
        np.testing.assert_array_equal(np.asarray(back[name]), ref)


def test_lazy_load_tensor_larger_than_chunk(mesh8, engine, tmp_path):
    """Spans bigger than one staging buffer stream in row chunks.
    Regression: 4 MiB tensor with 1 MiB chunk_bytes raised ValueError."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    rng = np.random.default_rng(9)
    big = rng.standard_normal((1024, 1024)).astype(np.float32)  # 4 MiB
    write_safetensors(tmp_path / "big.safetensors", {"big": big})
    lc = LazyCheckpoint(tmp_path / "big.safetensors")
    for spec in (P("dp", None), P(None, "tp"), P()):
        params = lc.load_sharded({"big": NamedSharding(mesh8, spec)},
                                 engine=engine)
        np.testing.assert_array_equal(np.asarray(params["big"]), big)


def test_save_checkpoint_uses_engine_write_path(mesh8, engine, tmp_path):
    """save_checkpoint must route payload through the engine writer."""
    params = {"w": np.arange(1 << 16, dtype=np.float32)}
    out = tmp_path / "ck.safetensors"
    save_checkpoint(out, params, engine=engine)
    snap = engine.engine_stats()
    assert snap["bytes_written_direct"] + snap["bounce_bytes"] > 0
    from nvme_strom_tpu.formats import SafetensorsFile
    sf = SafetensorsFile(out)
    raw = open(out, "rb").read()
    t = sf.tensors["w"]
    np.testing.assert_array_equal(
        np.frombuffer(raw[t["offset"]:t["offset"] + t["nbytes"]],
                      dtype=np.float32), params["w"])


def test_missing_sharding_raises(mesh8, ckpt, engine):
    tmp_path, _ = ckpt
    with pytest.raises(KeyError):
        LazyCheckpoint(tmp_path).load_sharded({"wte": None}, engine=engine)


def test_duplicate_tensor_rejected(tmp_path):
    write_safetensors(tmp_path / "a.safetensors",
                      {"x": np.zeros(4, dtype=np.float32)})
    write_safetensors(tmp_path / "b.safetensors",
                      {"x": np.zeros(4, dtype=np.float32)})
    with pytest.raises(ValueError, match="duplicate"):
        LazyCheckpoint(tmp_path)


def test_glob_source(ckpt):
    """A glob pattern resolves to every matching shard (the documented
    --init-weights form in examples/train_lm.py)."""
    import os
    from nvme_strom_tpu.parallel.weights import LazyCheckpoint
    tmp_path, tensors = ckpt
    lc = LazyCheckpoint(os.path.join(str(tmp_path), "model-*.safetensors"))
    assert set(lc.keys()) == set(tensors)


def test_header_parse_no_residency_pollution(mesh8, engine, tmp_path):
    """The safetensors header parse must not leave the file head
    resident: its readahead would flip the engine's residency planner
    to the buffered path for every small early tensor (the wds index
    walk measured 100% fallback+bounce from the same class of
    pollution)."""
    from conftest import evict_file
    from jax.sharding import NamedSharding, PartitionSpec as P
    rng = np.random.default_rng(3)
    # many small tensors early in the file: a buffered header parse's
    # readahead marks them fully resident (verified: the old
    # open().read() parse leaves 16 KiB planned resident under exactly
    # this ordering).  The partial-page DONTNEED defect is pinned
    # separately by test_formats.test_pread_nopollute_drops_pages,
    # which asserts residency directly via mincore.
    tensors = {f"t{i:03d}": rng.standard_normal((64,)).astype(np.float32)
               for i in range(64)}
    path = tmp_path / "small.safetensors"
    write_safetensors(path, tensors)
    # evict BEFORE construction: headers parse in LazyCheckpoint's
    # __init__, and the assertion must see their pollution, not a
    # pre-evicted cache (verified: the old buffered parse leaves
    # 16 KiB planned resident under exactly this ordering)
    evict_file(path)
    ckpt = LazyCheckpoint([path])
    sh = NamedSharding(mesh8, P())
    params = ckpt.load_sharded(lambda name, shape: sh, engine=engine)
    for name, v in tensors.items():
        np.testing.assert_array_equal(np.asarray(params[name]), v)
    engine.sync_stats()
    assert engine.stats.snapshot()["bytes_resident"] == 0


def test_lazy_load_zero_size_tensor(mesh8, engine, tmp_path):
    """Zero-element tensors are legal safetensors payloads; the planner
    gives their zero-length extents an empty piece list, and the weight
    streamer must yield the empty view instead of unpacking it.
    Regression: (4, 0) tensor raised ValueError at load."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    empty = np.zeros((4, 0), dtype=np.float32)
    write_safetensors(tmp_path / "empty.safetensors",
                      {"empty": empty,
                       "real": np.ones((4, 4), np.float32)})
    lc = LazyCheckpoint(tmp_path / "empty.safetensors")
    params = lc.load_sharded(
        {"empty": NamedSharding(mesh8, P()),
         "real": NamedSharding(mesh8, P())}, engine=engine)
    assert np.asarray(params["empty"]).shape == (4, 0)
    np.testing.assert_array_equal(np.asarray(params["real"]),
                                  np.ones((4, 4), np.float32))


# ---------------------------------------------------------------------------
# load_sharded over the transfer stage (ops/bridge.PutStage, PR 45)
# ---------------------------------------------------------------------------

def _staged_engine():
    """Sixteen buffers of 64 KiB: room for a queue, so the loads below run
    their gathers and puts on the stage's workers, several chunks a
    tensor."""
    return StromEngine(EngineConfig(chunk_bytes=1 << 16, queue_depth=8,
                                    buffer_pool_bytes=16 << 16),
                       stats=StromStats())


def _stage_threads():
    import threading
    return [t.name for t in threading.enumerate()
            if t.name.startswith("strom-put")]


def _staged_sharding(layout):
    import jax
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)
    if layout == "single":
        return SingleDeviceSharding(jax.devices()[0])
    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    return NamedSharding(mesh, {"rows": P("x", None), "cols": P(None, "x"),
                                "replicated": P()}[layout])


@pytest.mark.parametrize("verify", ["off", "full"])
@pytest.mark.parametrize("layout", ["single", "rows", "cols", "replicated"])
def test_staged_load_equals_the_file_bit_for_bit(tmp_path, monkeypatch,
                                                 layout, verify):
    monkeypatch.setenv("STROM_VERIFY", verify)
    rng = np.random.default_rng(7)
    tensors = {"big": rng.standard_normal((512, 256)).astype(np.float32),
               "small": rng.standard_normal((8, 64)).astype(np.float32)}
    path = tmp_path / "m.safetensors"
    write_safetensors(path, tensors)
    sh = _staged_sharding(layout)
    with _staged_engine() as eng:
        params = LazyCheckpoint(path).load_sharded(
            {name: sh for name in tensors}, engine=eng)
        for name, ref in tensors.items():
            assert params[name].sharding.is_equivalent_to(sh, ref.ndim)
            assert np.asarray(params[name]).tobytes() == ref.tobytes()
            for shard in params[name].addressable_shards:
                assert np.asarray(shard.data).tobytes() \
                    == ref[shard.index].tobytes()
        # big: 512 KiB in 64 KiB chunks, every device a share of each
        # chunk it reads; the strided shares are host gathers, into one
        # buffer a (tensor, device) that crosses in one put
        assert eng.stats.restore_puts_inline == 0
        assert eng.stats.restore_puts_staged == \
            {"single": 9, "rows": 12, "cols": 0, "replicated": 36}[layout]
        assert eng.stats.restore_puts_assembled == \
            (8 if layout == "cols" else 0)
        gathered = eng.stats.snapshot()["bounce_bytes"]
        if layout == "cols":        # (the CPU platform's copies besides)
            assert gathered >= sum(t.nbytes for t in tensors.values())
        # a row shard's span is not the whole tensor: no stamp covers it
        assert eng.stats.bytes_verified == (
            sum(t.nbytes for t in tensors.values())
            if verify == "full" and layout != "rows" else 0)
        info = eng.pool_info()
        assert info["free_buffers"] == info["n_buffers"]
    assert not _stage_threads()


@pytest.mark.parametrize("layout", ["single", "cols"])
def test_corrupted_stamp_still_raises_before_anything_is_returned(
        tmp_path, monkeypatch, layout):
    from nvme_strom_tpu.utils.checksum import ChecksumError
    monkeypatch.setenv("STROM_VERIFY", "full")
    rng = np.random.default_rng(8)
    tensors = {"first": rng.standard_normal((64, 64)).astype(np.float32),
               "big": rng.standard_normal((512, 256)).astype(np.float32),
               "last": rng.standard_normal((64, 64)).astype(np.float32)}
    path = tmp_path / "m.safetensors"
    write_safetensors(path, tensors)
    sh = _staged_sharding(layout)
    lc = LazyCheckpoint(path)
    off = lc.files[0].tensors["big"]["offset"] + 300_000
    with open(path, "r+b") as f:    # one flipped bit inside ``big``
        f.seek(off)
        b = f.read(1)
        f.seek(off)
        f.write(bytes([b[0] ^ 0x10]))
    with _staged_engine() as eng:
        got = None
        with pytest.raises(ChecksumError, match="corrupt weights"):
            got = LazyCheckpoint(path).load_sharded(
                {name: sh for name in tensors}, engine=eng)
        assert got is None
        assert eng.stats.checksum_failures == 1
        info = eng.pool_info()
        assert info["free_buffers"] == info["n_buffers"]
        # every staging buffer came back: the same engine loads again
        monkeypatch.setenv("STROM_VERIFY", "off")
        again = LazyCheckpoint(path).load_sharded(
            {name: sh for name in tensors}, engine=eng)
        assert np.asarray(again["last"]).tobytes() \
            == tensors["last"].tobytes()
        info = eng.pool_info()
        assert info["free_buffers"] == info["n_buffers"]
    assert not _stage_threads()


# ---------------------------------------------------------------------------
# a column shard crosses once a tensor (ops/bridge.HostAssembly, PR 49)
# ---------------------------------------------------------------------------

def _span_names(eng):
    """Every span the load ends, by name, as the tracer's sinks see them."""
    seen = []
    eng.tracer.add_sink(lambda ev: seen.append((ev["name"],
                                                ev.get("args", {}))))
    return seen


def _assert_equals_file(params, tensors, shardings):
    for name, ref in tensors.items():
        got = params[name]
        assert got.sharding.is_equivalent_to(shardings[name], ref.ndim)
        assert got.shape == ref.shape and got.dtype == ref.dtype
        assert np.asarray(got).tobytes() == ref.tobytes()
        for shard in got.addressable_shards:
            assert np.asarray(shard.data).tobytes() \
                == ref[shard.index].tobytes()


def test_column_shard_crosses_in_one_put_a_device_and_no_concatenate(
        tmp_path):
    rng = np.random.default_rng(49)
    tensors = {"w": rng.standard_normal((512, 256)).astype(np.float32)}
    path = tmp_path / "m.safetensors"
    write_safetensors(path, tensors)
    sh = {"w": _staged_sharding("cols")}
    with _staged_engine() as eng:
        seen = _span_names(eng)
        params = LazyCheckpoint(path).load_sharded(sh, engine=eng)
        _assert_equals_file(params, tensors, sh)
        # 512 KiB in eight chunks, four devices: four puts, of 128 KiB
        assert eng.stats.restore_puts_assembled == 4
        assert eng.stats.restore_puts_staged == 0
        assert eng.stats.restore_puts_inline == 0
        puts = [a for n, a in seen if n == "strom.h2d"]
        assert sorted(a["bytes"] for a in puts) == [128 << 10] * 4
        # the only join is the assembly of the four devices' arrays
        joins = [a for n, a in seen if n == "strom.restore.join"]
        assert joins == [{"parts": 4}]
        gathers = [a for n, a in seen if n == "strom.restore.slice"]
        assert len(gathers) == 8 * 4
        assert sum(a["bytes"] for a in gathers) == tensors["w"].nbytes
        info = eng.pool_info()
        assert info["free_buffers"] == info["n_buffers"]
    assert not _stage_threads()


def _mixed_checkpoint(tmp_path):
    rng = np.random.default_rng(50)
    f32 = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    tensors = {"embed": f32(384, 128), "wq": f32(128, 128),
               "wo": f32(128, 128), "w_up": f32(128, 448),
               "w_down": f32(448, 128), "norm": f32(128),
               "cube": f32(96, 8, 64), "scalar": np.float32(2.5).reshape(()),
               "none_wide": np.zeros((4, 0), np.float32),
               "lm_head": f32(128, 384)}
    path = tmp_path / "mixed.safetensors"
    write_safetensors(path, tensors)
    return path, tensors


def _mixed_shardings(mesh, tp="x"):
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = {"embed": P(None, tp), "wq": P(None, tp), "wo": P(tp, None),
            "w_up": P(None, tp), "w_down": P(tp, None), "norm": P(),
            "cube": P(None, None, tp), "scalar": P(), "none_wide": P(),
            "lm_head": P(None, tp)}
    return {n: NamedSharding(mesh, s) for n, s in spec.items()}


@pytest.mark.parametrize("verify", ["off", "full"])
def test_a_mix_of_layouts_equals_the_file(tmp_path, monkeypatch, verify):
    """Column-sharded (2-D and 3-D), row-sharded, replicated, scalar and
    zero-size tensors in one load, more of them than ring buffers."""
    import jax
    from jax.sharding import Mesh
    monkeypatch.setenv("STROM_VERIFY", verify)
    path, tensors = _mixed_checkpoint(tmp_path)
    sh = _mixed_shardings(Mesh(np.array(jax.devices()[:4]), ("x",)))
    with _staged_engine() as eng:
        kept = []
        for _ in range(2):
            params = LazyCheckpoint(path).load_sharded(sh, engine=eng)
            _assert_equals_file(params, tensors, sh)
            # two host buffers a device, the engine's between loads
            kept.append(sorted(id(b) for b in eng.spare_host_buffers))
        assert len(kept[0]) == 8 and kept[0] == kept[1]
        # five column-sharded tensors, a put a device each, a load
        assert eng.stats.restore_puts_assembled == 2 * 5 * 4
        assert eng.stats.restore_puts_inline == 0
        info = eng.pool_info()
        assert info["free_buffers"] == info["n_buffers"]
    assert not _stage_threads()


def test_dp_by_tp_shares_a_column_shard_between_devices(tmp_path):
    """Columns cut over ``tp`` and replicated over ``dp``: two devices
    take each shard — one gather a chunk, a put a device."""
    import jax
    from jax.sharding import Mesh
    path, tensors = _mixed_checkpoint(tmp_path)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "tp"))
    sh = _mixed_shardings(mesh, tp="tp")
    with _staged_engine() as eng:
        seen = _span_names(eng)
        params = LazyCheckpoint(path).load_sharded(sh, engine=eng)
        _assert_equals_file(params, tensors, sh)
        assert eng.stats.restore_puts_assembled == 5 * 8
        column = [t for n, t in tensors.items()
                  if n in ("embed", "wq", "w_up", "cube", "lm_head")]
        gathered = sum(a["bytes"] for n, a in seen
                       if n == "strom.restore.slice")
        assert gathered == sum(t.nbytes for t in column)    # once, not twice
    assert not _stage_threads()


@pytest.mark.parametrize("layout", ["cols", "rows", "replicated"])
def test_load_tensor_at_depth_zero_gives_the_same_array(tmp_path, layout):
    """The cold-start lanes' path: one tensor a call, its gathers and
    puts on the calling thread, two lanes at once."""
    import threading
    rng = np.random.default_rng(51)
    tensors = {f"w{i}": rng.standard_normal((512, 256)).astype(np.float32)
               for i in range(4)}
    path = tmp_path / "m.safetensors"
    write_safetensors(path, tensors)
    sh = _staged_sharding(layout)
    with _staged_engine() as eng:
        ck = LazyCheckpoint(path)
        staged = ck.load_sharded({n: sh for n in tensors}, engine=eng)
        before = eng.stats.snapshot()
        got, errs = {}, []

        def lane(names):
            try:
                for n in names:
                    got[n] = ck._load_tensor(eng, n, sh)
            except BaseException as e:
                errs.append(e)

        lanes = [threading.Thread(target=lane, args=(names,))
                 for names in (["w0", "w2"], ["w1", "w3"])]
        for t in lanes:
            t.start()
        for t in lanes:
            t.join(60)
        assert not errs and not any(t.is_alive() for t in lanes)
        _assert_equals_file(got, tensors, {n: sh for n in tensors})
        for n in tensors:
            assert np.asarray(got[n]).tobytes() \
                == np.asarray(staged[n]).tobytes()
        after = eng.stats.snapshot()
        assert after["restore_puts_staged"] == before["restore_puts_staged"]
        assert (after["restore_puts_assembled"]
                - before["restore_puts_assembled"]) \
            == (16 if layout == "cols" else 0)
        assert (after["restore_puts_inline"] > 0) == (layout != "cols")
        info = eng.pool_info()
        assert info["free_buffers"] == info["n_buffers"]
    assert not _stage_threads()


def test_a_gather_that_raises_reaches_the_caller_and_frees_everything(
        tmp_path, monkeypatch):
    from nvme_strom_tpu.ops import bridge
    path, tensors = _mixed_checkpoint(tmp_path)
    import jax
    from jax.sharding import Mesh
    sh = _mixed_shardings(Mesh(np.array(jax.devices()[:4]), ("x",)))
    real = bridge.HostAssembly.gather
    calls = []

    def failing(self, row0, cut):
        calls.append(row0)
        if len(calls) == 11:
            raise MemoryError("gather failed")
        return real(self, row0, cut)

    with _staged_engine() as eng:
        monkeypatch.setattr(bridge.HostAssembly, "gather", failing)
        got = None
        with pytest.raises(MemoryError, match="gather failed"):
            got = LazyCheckpoint(path).load_sharded(sh, engine=eng)
        assert got is None
        info = eng.pool_info()
        assert info["free_buffers"] == info["n_buffers"]
        assert not _stage_threads()
        # every buffer came back: the same engine loads again
        monkeypatch.setattr(bridge.HostAssembly, "gather", real)
        again = LazyCheckpoint(path).load_sharded(sh, engine=eng)
        _assert_equals_file(again, tensors, sh)
    assert not _stage_threads()


def test_a_shard_over_the_cap_is_joined_from_a_few_large_puts(
        tmp_path, monkeypatch):
    from nvme_strom_tpu.ops import bridge
    monkeypatch.setattr(bridge, "ASSEMBLY_BYTES", 40 << 10)
    rng = np.random.default_rng(52)
    tensors = {"w": rng.standard_normal((512, 256)).astype(np.float32)}
    path = tmp_path / "m.safetensors"
    write_safetensors(path, tensors)
    sh = {"w": _staged_sharding("cols")}
    with _staged_engine() as eng:
        seen = _span_names(eng)
        params = LazyCheckpoint(path).load_sharded(sh, engine=eng)
        _assert_equals_file(params, tensors, sh)
        # a device's 128 KiB in segments of 40 KiB (160 rows): 4 puts
        assert eng.stats.restore_puts_assembled == 4 * 4
        joins = sorted(a["parts"] for n, a in seen
                       if n == "strom.restore.join")
        assert joins == [4] * 5     # a device's four, and the assembly
    assert not _stage_threads()

"""Format reader tests: plans must address exactly the payload bytes, and
payloads read via the planned ranges through the direct engine must equal
the format's own decode (content-verification discipline, SURVEY.md §4)."""

import numpy as np
import pytest

from nvme_strom_tpu.formats import (
    ArrowFileReader,
    SafetensorsFile,
    TFRecordIndex,
    WdsShardIndex,
    crc32c,
    masked_crc,
    read_records,
    write_safetensors,
    write_tfrecords,
    write_wds_shard,
)
from nvme_strom_tpu.io import StromEngine
from nvme_strom_tpu.utils.config import EngineConfig
from nvme_strom_tpu.utils.stats import StromStats


@pytest.fixture()
def engine():
    cfg = EngineConfig(chunk_bytes=1 << 20, queue_depth=8,
                       buffer_pool_bytes=8 << 20)
    with StromEngine(cfg, stats=StromStats()) as e:
        yield e


def _read_planned(engine, plan):
    fh = engine.open(plan.path)
    out = {}
    for e in plan.entries:
        with engine.submit_read(fh, e.offset, e.length) as p:
            out[e.key] = p.wait().tobytes()
    engine.close(fh)
    return out


# ---------------- safetensors ----------------

def test_safetensors_roundtrip(engine, tmp_path):
    rng = np.random.default_rng(0)
    tensors = {
        "wte": rng.standard_normal((128, 64)).astype(np.float32),
        "bias": rng.standard_normal((64,)).astype(np.float16),
        "ids": np.arange(100, dtype=np.int64),
    }
    path = tmp_path / "m.safetensors"
    write_safetensors(path, tensors, metadata={"fmt": "test"})
    sf = SafetensorsFile(path)
    assert set(sf.keys()) == set(tensors)
    assert sf.metadata == {"fmt": "test"}
    got = _read_planned(engine, sf.plan())
    for name, arr in tensors.items():
        t = sf.tensors[name]
        assert t["shape"] == arr.shape
        back = np.frombuffer(got[name], dtype=arr.dtype).reshape(arr.shape)
        np.testing.assert_array_equal(back, arr)


def test_safetensors_bf16(tmp_path):
    import ml_dtypes
    arr = np.arange(32, dtype=np.float32).astype(ml_dtypes.bfloat16)
    path = tmp_path / "b.safetensors"
    write_safetensors(path, {"x": arr})
    sf = SafetensorsFile(path)
    assert sf.tensors["x"]["dtype"] == "bfloat16"
    raw = open(path, "rb").read()
    t = sf.tensors["x"]
    back = np.frombuffer(
        raw[t["offset"]:t["offset"] + t["nbytes"]],
        dtype=ml_dtypes.bfloat16)
    np.testing.assert_array_equal(back, arr)


def test_safetensors_row_slice(engine, tmp_path):
    rng = np.random.default_rng(1)
    w = rng.standard_normal((64, 32)).astype(np.float32)
    path = tmp_path / "w.safetensors"
    write_safetensors(path, {"w": w})
    sf = SafetensorsFile(path)
    ent = sf.slice_plan("w", 16, 8)
    assert ent.shape == (8, 32)
    fh = engine.open(path)
    with engine.submit_read(fh, ent.offset, ent.length) as p:
        back = np.frombuffer(p.wait().tobytes(), dtype=np.float32
                             ).reshape(8, 32)
    engine.close(fh)
    np.testing.assert_array_equal(back, w[16:24])


def test_safetensors_slice_bounds(tmp_path):
    w = np.zeros((4, 4), dtype=np.float32)
    path = tmp_path / "s.safetensors"
    write_safetensors(path, {"w": w})
    sf = SafetensorsFile(path)
    with pytest.raises(ValueError):
        sf.slice_plan("w", 2, 3)


# ---------------- tfrecord ----------------

def test_crc32c_known_vectors():
    # RFC 3720 test vector: 32 bytes of zeros -> 0x8A9136AA
    assert crc32c(b"\x00" * 32) == 0x8A9136AA
    assert crc32c(b"123456789") == 0xE3069283


def test_tfrecord_roundtrip(engine, tmp_path):
    rng = np.random.default_rng(2)
    payloads = [rng.integers(0, 256, size=int(n), dtype=np.uint8).tobytes()
                for n in rng.integers(1, 5000, size=20)]
    path = tmp_path / "d.tfrecord"
    write_tfrecords(path, payloads)
    # full decode with crc verification
    assert list(read_records(path, verify=True)) == payloads
    # planned ranges through the engine
    idx = TFRecordIndex(path, verify_framing_crc=True)
    assert len(idx) == 20
    got = _read_planned(engine, idx.plan())
    for i, p in enumerate(payloads):
        assert got[str(i)] == p


def test_tfrecord_partial_plan(tmp_path):
    write_tfrecords(tmp_path / "x.tfrecord", [b"a" * 10, b"b" * 20, b"c"])
    idx = TFRecordIndex(tmp_path / "x.tfrecord")
    plan = idx.plan([2, 0])
    assert [e.length for e in plan.entries] == [1, 10]


def test_tfrecord_corrupt_crc(tmp_path):
    path = tmp_path / "bad.tfrecord"
    write_tfrecords(path, [b"hello world"])
    raw = bytearray(path.read_bytes())
    raw[14] ^= 0xFF  # flip a payload byte
    path.write_bytes(bytes(raw))
    with pytest.raises(ValueError, match="payload crc"):
        list(read_records(path, verify=True))


# ---------------- webdataset ----------------

def test_wds_roundtrip(engine, tmp_path):
    rng = np.random.default_rng(3)
    samples = [{"jpg": rng.bytes(1000 + i * 37), "cls": str(i).encode()}
               for i in range(12)]
    path = tmp_path / "shard-000000.tar"
    write_wds_shard(path, samples)
    idx = WdsShardIndex(path)
    assert len(idx) == 12
    got = _read_planned(engine, idx.plan())
    for i, s in enumerate(samples):
        key = f"{i:08d}"
        assert got[f"{key}.jpg"] == s["jpg"]
        assert got[f"{key}.cls"] == s["cls"]


def test_wds_ext_filter(tmp_path):
    write_wds_shard(tmp_path / "s.tar", [{"jpg": b"x", "cls": b"0"}])
    idx = WdsShardIndex(tmp_path / "s.tar")
    plan = idx.plan(exts=["cls"])
    assert [e.key for e in plan.entries] == ["00000000.cls"]


def test_wds_key_with_dots(tmp_path):
    """webdataset keys split at the FIRST dot: a.b.c -> key=a ext=b.c"""
    write_wds_shard(tmp_path / "s.tar", [{"seg.png": b"mask"}], keys=["img1"])
    idx = WdsShardIndex(tmp_path / "s.tar")
    assert idx.samples["img1"]["seg.png"] == idx.samples["img1"]["seg.png"]
    plan = idx.plan()
    assert plan.entries[0].key == "img1.seg.png"


# ---------------- arrow ----------------

def test_arrow_footer_blocks_match_pyarrow(tmp_path):
    import pyarrow as pa
    rng = np.random.default_rng(4)
    path = tmp_path / "t.arrow"
    batches = [
        pa.record_batch({
            "a": rng.standard_normal(1000).astype(np.float32),
            "b": rng.integers(0, 1 << 30, 1000, dtype=np.int64),
        }) for _ in range(3)
    ]
    with pa.OSFile(str(path), "wb") as f:
        with pa.ipc.new_file(f, batches[0].schema) as w:
            for b in batches:
                w.write_batch(b)
    r = ArrowFileReader(path)
    assert r.num_batches == 3
    assert {f.name for f in r.schema} == {"a", "b"}
    # planned ranges must decode to the original batches
    raw = path.read_bytes()
    for i, e in enumerate(r.plan().entries):
        view = np.frombuffer(raw, dtype=np.uint8,
                             count=e.length, offset=e.offset)
        batch = r.decode_batch(view)
        assert batch.num_rows == 1000
        np.testing.assert_array_equal(batch.column("a").to_numpy(),
                                      batches[i].column("a").to_numpy())


def test_arrow_columns_to_device(engine, tmp_path):
    import pyarrow as pa
    rng = np.random.default_rng(5)
    path = tmp_path / "c.arrow"
    a = rng.standard_normal(5000).astype(np.float32)
    b = rng.integers(0, 100, 5000, dtype=np.int32)
    batch = pa.record_batch({"a": a, "b": b})
    with pa.OSFile(str(path), "wb") as f:
        with pa.ipc.new_file(f, batch.schema) as w:
            for lo in range(0, 5000, 1250):
                w.write_batch(batch.slice(lo, 1250))
    r = ArrowFileReader(path)
    cols = r.read_columns_to_device(engine, columns=["a", "b"])
    np.testing.assert_array_equal(np.asarray(cols["a"]), a)
    np.testing.assert_array_equal(np.asarray(cols["b"]), b)


def test_pread_nopollute_drops_pages(tmp_path):
    """pread_nopollute must leave NO touched page resident — including
    the final PARTIAL page: the kernel drops only pages wholly inside
    a DONTNEED range, so an un-rounded end silently keeps the last
    page (verified with mincore; a resident page flips the engine's
    residency planner to the buffered path for any span inside it)."""
    import ctypes
    import mmap
    import os
    from nvme_strom_tpu.formats.base import pread_nopollute

    p = tmp_path / "f.bin"
    payload = os.urandom(32768)
    p.write_bytes(payload)
    from conftest import evict_file
    evict_file(p)

    def resident_pages() -> int:
        size = os.path.getsize(p)
        # writable mapping only so ctypes.from_buffer can take the
        # address; nothing is written and mapping populates no pages
        with open(p, "r+b") as f, \
                mmap.mmap(f.fileno(), size) as m:
            npg = (size + 4095) // 4096
            vec = (ctypes.c_ubyte * npg)()
            addr = ctypes.addressof(ctypes.c_char.from_buffer(m))
            libc = ctypes.CDLL("libc.so.6", use_errno=True)
            assert libc.mincore(ctypes.c_void_p(addr),
                                ctypes.c_size_t(size), vec) == 0
            return sum(v & 1 for v in vec)

    # partial-page read in the middle of the file
    got = pread_nopollute(str(p), 3700, 8)
    assert got == payload[8:8 + 3700]
    assert resident_pages() == 0
    # tiny head read (the wds gzip sniff shape)
    assert pread_nopollute(str(p), 2) == payload[:2]
    assert resident_pages() == 0


def test_arrow_multichunk_device_assembly(engine, tmp_path):
    """An IPC message larger than one staging buffer assembles ON
    DEVICE: the metadata decodes against a zeros body for the buffer
    layout, payload pieces put straight from staging and concatenate
    there.  On the CPU test device the alias-protection copy is the
    only bounce — the old path ALSO host-assembled the whole message,
    doubling it (and on a real accelerator leaving payload-sized
    bounce where the claim is zero)."""
    import pyarrow as pa
    rng = np.random.default_rng(7)
    path = tmp_path / "big.arrow"
    n = 400_000               # 2 x 1.6 MB columns > 1 MiB chunks
    a = rng.standard_normal(n).astype(np.float32)
    b = rng.integers(-5, 5, n).astype(np.int32)
    batch = pa.record_batch({"a": a, "b": b})
    with pa.OSFile(str(path), "wb") as f:
        with pa.ipc.new_file(f, batch.schema) as w:
            w.write_batch(batch)
    from conftest import evict_file
    r = ArrowFileReader(path)        # footer read while file is warm
    evict_file(path)                 # cold payload: direct reads, so
    engine.sync_stats()              # bounce is alias copies alone
    pre = engine.stats.snapshot()["bounce_bytes"]
    cols = r.read_columns_to_device(engine, columns=["a", "b"])
    np.testing.assert_array_equal(np.asarray(cols["a"]), a)
    np.testing.assert_array_equal(np.asarray(cols["b"]), b)
    engine.sync_stats()
    bounce = engine.stats.snapshot()["bounce_bytes"] - pre
    payload = a.nbytes + b.nbytes
    assert bounce <= payload, (bounce, payload)


# ------------------------- fixedrec (zero-copy path) -------------------------

def test_fixedrec_roundtrip_array(tmp_path):
    from nvme_strom_tpu.formats.fixedrec import FixedRecIndex, write_fixedrec

    rec = np.arange(6 * 4 * 4, dtype=np.int16).reshape(6, 4, 4)
    p = tmp_path / "a.sfr"
    assert write_fixedrec(p, rec) == 6
    ix = FixedRecIndex(p)
    assert (ix.count, ix.dtype, ix.shape) == (6, np.dtype(np.int16), (4, 4))
    assert ix.record_bytes == 32
    off, ln = ix.span(2, 3)
    with open(p, "rb") as f:
        f.seek(off)
        got = np.frombuffer(f.read(ln), np.int16).reshape(3, 4, 4)
    np.testing.assert_array_equal(got, rec[2:5])


def test_fixedrec_bytes_records_and_errors(tmp_path):
    from nvme_strom_tpu.formats.fixedrec import FixedRecIndex, write_fixedrec

    p = tmp_path / "b.sfr"
    write_fixedrec(p, [b"abcd", b"efgh"])
    ix = FixedRecIndex(p)
    assert ix.record_bytes == 4 and ix.dtype == np.uint8
    with pytest.raises(IndexError):
        ix.span(1, 2)
    with pytest.raises(ValueError, match="fixed size"):
        write_fixedrec(tmp_path / "c.sfr", [b"ab", b"abc"])
    (tmp_path / "d.sfr").write_bytes(b"not a fixedrec file....")
    with pytest.raises(ValueError, match="magic"):
        FixedRecIndex(tmp_path / "d.sfr")


def test_safetensors_engine_buffered_fs_roundtrip():
    """tmpfs rejects O_DIRECT → the writer's single (tail) path carries
    the whole data section buffered; the file must round-trip
    bit-exactly and stay standard safetensors."""
    import os
    import shutil
    import tempfile

    from nvme_strom_tpu.formats.safetensors import write_safetensors_engine

    if not os.path.isdir("/dev/shm"):
        pytest.skip("no tmpfs mount")
    d = tempfile.mkdtemp(dir="/dev/shm")
    try:
        path = os.path.join(d, "t.safetensors")
        rng = np.random.default_rng(9)
        tensors = {
            "a": rng.standard_normal((1000, 33)).astype(np.float32),
            "b": rng.integers(0, 1000, 7777, dtype=np.int64),
            "scalar": np.float32(3.5).reshape(()),
        }
        stats = StromStats()
        with StromEngine(stats=stats) as eng:
            write_safetensors_engine(path, tensors, eng)
            eng.sync_stats()
        assert stats.bytes_written_direct == 0  # all buffered
        sf = SafetensorsFile(path)
        with open(path, "rb") as f:
            for name, ref in tensors.items():
                t = sf.tensors[name]
                f.seek(t["offset"])
                got = np.frombuffer(f.read(t["nbytes"]),
                                    dtype=ref.dtype).reshape(t["shape"])
                np.testing.assert_array_equal(got, ref.reshape(t["shape"]))
    finally:
        shutil.rmtree(d, ignore_errors=True)


class TestNpy:
    """npy/npz planning: payload spans exact, device arrays bit-match."""

    def test_npy_roundtrip_dtypes(self, tmp_path):
        from nvme_strom_tpu.formats.npy import (plan_npy,
                                                read_npy_to_device)
        from nvme_strom_tpu.io.engine import StromEngine
        rng = np.random.default_rng(0)
        arrays = {
            "f32": rng.standard_normal((33, 7)).astype(np.float32),
            "i32": rng.integers(-2**30, 2**30, (5, 4, 3)).astype(np.int32),
            "u8": rng.integers(0, 255, 1000, dtype=np.uint8),
            "scalar0d": np.ones((), np.float32) * np.float32(3.5),
        }
        with StromEngine() as eng:
            for name, arr in arrays.items():
                p = str(tmp_path / f"{name}.npy")
                np.save(p, arr)
                entry = plan_npy(p)
                assert entry.length == arr.nbytes
                assert tuple(entry.shape) == arr.shape
                got = np.asarray(read_npy_to_device(eng, p))
                np.testing.assert_array_equal(got, arr)
            # 8-byte dtypes refuse without x64 (bitcast would truncate);
            # planning still answers
            p64 = str(tmp_path / "i64.npy")
            np.save(p64, rng.integers(-2**40, 2**40, (6,)))
            assert plan_npy(p64).length == 48
            with pytest.raises(ValueError, match="x64"):
                read_npy_to_device(eng, p64)

    def test_npy_rejects_fortran_and_object(self, tmp_path):
        from nvme_strom_tpu.formats.npy import plan_npy
        f = np.asfortranarray(np.arange(12.0).reshape(3, 4))
        pf = str(tmp_path / "f.npy")
        np.save(pf, f)
        with pytest.raises(ValueError, match="fortran"):
            plan_npy(pf)
        po = str(tmp_path / "o.npy")
        np.save(po, np.array([{"a": 1}], dtype=object),
                allow_pickle=True)
        with pytest.raises(ValueError, match="object"):
            plan_npy(po)

    def test_npz_members_to_device(self, tmp_path):
        from nvme_strom_tpu.formats.npy import plan_npz, read_npz_to_device
        from nvme_strom_tpu.io.engine import StromEngine
        rng = np.random.default_rng(1)
        a = rng.standard_normal((16, 16)).astype(np.float32)
        b = rng.integers(0, 99, 64, dtype=np.int32)
        p = str(tmp_path / "pack.npz")
        np.savez(p, weights=a, ids=b)
        plan = plan_npz(p)
        assert {e.key for e in plan.entries} == {"weights", "ids"}
        with StromEngine() as eng:
            out = read_npz_to_device(eng, p)
            np.testing.assert_array_equal(np.asarray(out["weights"]), a)
            np.testing.assert_array_equal(np.asarray(out["ids"]), b)
            only = read_npz_to_device(eng, p, keys=["ids"])
            assert set(only) == {"ids"}

    def test_npz_rejects_compressed(self, tmp_path):
        from nvme_strom_tpu.formats.npy import plan_npz
        p = str(tmp_path / "c.npz")
        np.savez_compressed(p, x=np.arange(1000.0))
        with pytest.raises(ValueError, match="compressed"):
            plan_npz(p)

    def test_npy_rejects_big_endian_and_structured(self, tmp_path):
        from nvme_strom_tpu.formats.npy import plan_npy
        pb = str(tmp_path / "be.npy")
        np.save(pb, np.arange(10, dtype=np.float32).astype(">f4"))
        with pytest.raises(ValueError, match="big-endian"):
            plan_npy(pb)
        ps = str(tmp_path / "rec.npy")
        np.save(ps, np.zeros(4, dtype=[("a", "<i4"), ("b", "<f4")]))
        with pytest.raises(ValueError, match="structured"):
            plan_npy(ps)

    def test_npy_header_larger_than_window(self, tmp_path):
        """Huge-descr headers (> 4 KiB) re-read with the right size."""
        import struct
        from nvme_strom_tpu.formats.npy import plan_npy
        arr = np.zeros((2, 3), np.float32)
        p = str(tmp_path / "bighdr.npy")
        np.save(p, arr)
        raw = open(p, "rb").read()
        # rebuild with a v1 header padded to 8 KiB of trailing spaces
        hdr_end = 10 + struct.unpack_from("<H", raw, 8)[0]
        header = raw[10:hdr_end].rstrip(b"\n").rstrip()
        pad = 8192 - (10 + len(header) + 1)
        big = (raw[:8] + struct.pack("<H", len(header) + pad + 1)
               + header + b" " * pad + b"\n" + raw[hdr_end:])
        open(p, "wb").write(big)
        np.testing.assert_array_equal(np.load(p), arr)  # still valid
        entry = plan_npy(p)
        assert entry.offset == 8192       # 10-byte preamble + 8182 header
        assert entry.length == arr.nbytes

    def test_npy_header_fuzz(self, tmp_path):
        """Corrupt/truncated headers raise ValueError — never hang or
        crash the planner (the thrift-fuzz discipline for npy)."""
        from nvme_strom_tpu.formats.npy import plan_npy
        rng = np.random.default_rng(9)
        good = str(tmp_path / "good.npy")
        np.save(good, np.zeros(8, np.float32))
        raw = bytearray(open(good, "rb").read())
        p = str(tmp_path / "fuzz.npy")
        for _ in range(300):
            buf = bytearray(raw)
            for _ in range(rng.integers(1, 6)):
                buf[rng.integers(0, len(buf))] = rng.integers(0, 256)
            open(p, "wb").write(bytes(buf))
            try:
                entry = plan_npy(p)
                assert entry.length >= 0
            except (ValueError, SyntaxError, KeyError, TypeError,
                    OverflowError):
                # NOT MemoryError: a corrupt length field must never
                # drive an allocation bomb (the planner clamps)
                pass


def test_compressed_shards_fail_loudly(tmp_path):
    """gzip'd TFRecord/tar shards have no random access: the index must
    refuse with a message naming the fix, not die parsing garbage."""
    import gzip

    import pytest

    from nvme_strom_tpu.formats.tfrecord import TFRecordIndex
    from nvme_strom_tpu.formats.wds import WdsShardIndex

    gz = tmp_path / "d.tfrecord.gz"
    gz.write_bytes(gzip.compress(b"payload" * 100))
    with pytest.raises(ValueError, match="gzip-compressed TFRecord"):
        TFRecordIndex(gz)
    tgz = tmp_path / "s.tar.gz"
    tgz.write_bytes(gzip.compress(b"tarball" * 100))
    with pytest.raises(ValueError, match="gzip-compressed shard"):
        WdsShardIndex(tgz)

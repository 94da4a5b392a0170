"""pq_direct: on-device Parquet decode (PLAIN + dictionary) vs pyarrow.

The fast path must (a) bit-match pyarrow on every supported physical
type, encoding and nullability shape, (b) refuse anything it can't
decode with a reason, and (c) never touch payload bytes on host
(accounting tests) — dictionary chunks touch only the index stream.
"""

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from nvme_strom_tpu.io.engine import StromEngine
from nvme_strom_tpu.sql import pq_direct
from nvme_strom_tpu.sql.parquet import ParquetScanner
from nvme_strom_tpu.utils.stats import StromStats


def _write(path, table, **kw):
    kw.setdefault("compression", "none")
    kw.setdefault("use_dictionary", False)
    pq.write_table(table, path, **kw)


@pytest.fixture
def engine():
    with StromEngine(stats=StromStats()) as eng:
        yield eng


def _mixed_table(rows=5000, seed=0):
    rng = np.random.default_rng(seed)
    return pa.table({
        "i32": pa.array(rng.integers(-2**31, 2**31 - 1, rows,
                                     dtype=np.int64).astype(np.int32)),
        "i64": pa.array(rng.integers(-2**62, 2**62, rows, dtype=np.int64)),
        "f32": pa.array(rng.standard_normal(rows).astype(np.float32)),
        "f64": pa.array(rng.standard_normal(rows)),
    })


def test_direct_matches_pyarrow_32bit(tmp_path, engine):
    path = str(tmp_path / "t.parquet")
    tbl = _mixed_table()
    _write(path, tbl, row_group_size=1200)   # several row groups
    sc = ParquetScanner(path, engine)
    assert sc.metadata.num_row_groups > 1
    cols = ["i32", "f32"]
    assert all(r is None for r in sc.direct_reasons(cols).values())
    # 64-bit types are ineligible without x64 (bitcast would truncate)
    r64 = sc.direct_reasons(["i64", "f64"])
    assert all("x64" in v for v in r64.values())
    out = sc.read_columns_to_device(cols, direct="always")
    for c in cols:
        np.testing.assert_array_equal(np.asarray(out[c]),
                                      tbl.column(c).to_numpy())


def test_direct_matches_pyarrow_64bit_x64_mode(tmp_path):
    """i64/f64 decode correctly when jax runs in x64 mode (subprocess:
    the flag must be set before jax initialises)."""
    import subprocess
    import sys
    path = str(tmp_path / "t64.parquet")
    tbl = _mixed_table(rows=3000, seed=7)
    _write(path, tbl, row_group_size=1024)
    code = f"""
import sys; sys.path.insert(0, {repr(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))})
import numpy as np
from nvme_strom_tpu.io.engine import StromEngine
from nvme_strom_tpu.sql.parquet import ParquetScanner
import pyarrow.parquet as pq
with StromEngine() as eng:
    sc = ParquetScanner({repr(path)}, eng)
    out = sc.read_columns_to_device(["i64", "f64"], direct="always")
    ref = pq.read_table({repr(path)})
    np.testing.assert_array_equal(np.asarray(out["i64"]),
                                  ref.column("i64").to_numpy())
    np.testing.assert_array_equal(np.asarray(out["f64"]),
                                  ref.column("f64").to_numpy())
print("ok64")
"""
    env = dict(os.environ, JAX_ENABLE_X64="1", JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ok64" in r.stdout


def test_direct_required_fields_no_def_levels(tmp_path, engine):
    """nullable=False columns carry no definition levels — the span
    starts right after the page header."""
    rng = np.random.default_rng(1)
    schema = pa.schema([pa.field("v", pa.float32(), nullable=False)])
    vals = rng.standard_normal(3000).astype(np.float32)
    tbl = pa.table({"v": pa.array(vals)}, schema=schema)
    path = str(tmp_path / "req.parquet")
    _write(path, tbl)
    sc = ParquetScanner(path, engine)
    assert sc.metadata.schema.column(0).max_definition_level == 0
    out = sc.read_columns_to_device(["v"], direct="always")
    np.testing.assert_array_equal(np.asarray(out["v"]), vals)


def test_direct_rejects_with_reasons(tmp_path, engine):
    rng = np.random.default_rng(2)
    rows = 2000

    # delta-encoded (no on-device decode)
    p1 = str(tmp_path / "delta.parquet")
    pq.write_table(pa.table({"v": pa.array(
        rng.integers(0, 10**6, rows, dtype=np.int32))}), p1,
        compression="none", use_dictionary=False,
        column_encoding={"v": "DELTA_BINARY_PACKED"})
    r = ParquetScanner(p1, engine).direct_reasons(["v"])
    assert r["v"] is not None and "encodings" in r["v"]

    # compressed chunks are now direct-eligible (host decompress leg)
    p2 = str(tmp_path / "snappy.parquet")
    pq.write_table(pa.table({"v": pa.array(
        rng.standard_normal(rows).astype(np.float32))}), p2,
        compression="snappy", use_dictionary=False)
    r = ParquetScanner(p2, engine).direct_reasons(["v"])
    assert r["v"] is None

    # nulls present (a real Arrow null — NaN would NOT count): rejected
    # unless the caller opts into nulls="mask"
    p3 = str(tmp_path / "nulls.parquet")
    vals = [float(x) for x in rng.standard_normal(rows)]
    vals[7] = None
    _write(p3, pa.table({"v": pa.array(vals, type=pa.float32())}))
    r = ParquetScanner(p3, engine).direct_reasons(["v"])
    assert r["v"] is not None and "null" in r["v"]

    # unsupported physical type (strings)
    p4 = str(tmp_path / "str.parquet")
    _write(p4, pa.table({"v": pa.array(["a"] * rows)}))
    r = ParquetScanner(p4, engine).direct_reasons(["v"])
    assert r["v"] is not None

    # direct="always" raises; "auto" still answers correctly
    sc = ParquetScanner(p3, engine)
    with pytest.raises(ValueError, match="not direct-eligible"):
        sc.read_columns_to_device(["v"], direct="always")


def test_groupby_direct_equals_pyarrow_path(tmp_path, engine):
    from nvme_strom_tpu.sql.groupby import sql_groupby
    rng = np.random.default_rng(3)
    rows, groups = 20000, 32
    tbl = pa.table({
        "k": pa.array(rng.integers(0, groups, rows, dtype=np.int32)),
        "v": pa.array(rng.standard_normal(rows).astype(np.float32))})
    path = str(tmp_path / "g.parquet")
    _write(path, tbl, row_group_size=4096)
    sc = ParquetScanner(path, engine)
    assert all(r is None for r in sc.direct_reasons(["k", "v"]).values())
    out = sql_groupby(sc, "k", "v", groups, aggs=("count", "sum", "mean"))

    keys = tbl.column("k").to_numpy()
    vals = tbl.column("v").to_numpy()
    exp_count = np.bincount(keys, minlength=groups)
    exp_sum = np.bincount(keys, weights=vals.astype(np.float64),
                          minlength=groups)
    np.testing.assert_array_equal(np.asarray(out["count"]), exp_count)
    np.testing.assert_allclose(np.asarray(out["sum"]), exp_sum,
                               rtol=2e-4)
    np.testing.assert_allclose(
        np.asarray(out["mean"]), exp_sum / np.maximum(exp_count, 1),
        rtol=2e-4)


def test_direct_payload_bytes_never_bounce(tmp_path, monkeypatch):
    """Direct scan accounting: payload goes engine→device with no
    Python-side copy; the only counted bounce is the CPU device_put
    alias-protection copy (zero on an accelerator)."""
    monkeypatch.setenv("STROM_NO_RESIDENCY_PROBE", "1")
    rng = np.random.default_rng(4)
    rows = 8192
    tbl = pa.table({"v": pa.array(rng.standard_normal(rows)
                                  .astype(np.float32))})
    path = str(tmp_path / "acct.parquet")
    _write(path, tbl)

    stats = StromStats()
    with StromEngine(stats=stats) as eng:
        fh = eng.open(path)
        is_direct = eng.file_is_direct(fh)
        eng.close(fh)
        if not is_direct:
            pytest.skip("fs rejects O_DIRECT")
        sc = ParquetScanner(path, eng)
        out = sc.read_columns_to_device(["v"], direct="always")
        np.testing.assert_array_equal(np.asarray(out["v"]),
                                      tbl.column("v").to_numpy())
        eng.sync_stats()
    payload = rows * 4
    assert stats.bytes_to_device == payload
    import jax
    expected_bounce = (payload if jax.devices()[0].platform == "cpu"
                       else 0)
    assert stats.bounce_bytes == expected_bounce


def test_direct_v2_data_pages(tmp_path, engine):
    """DataPageHeaderV2 states level lengths in the header; the direct
    scan must decode v2 files identically (and not crash 'auto')."""
    rng = np.random.default_rng(6)
    vals = rng.standard_normal(6000).astype(np.float32)
    keys = rng.integers(0, 9, 6000, dtype=np.int32)
    tbl = pa.table({"k": pa.array(keys), "v": pa.array(vals)})
    path = str(tmp_path / "v2.parquet")
    _write(path, tbl, row_group_size=2048, data_page_version="2.0")
    sc = ParquetScanner(path, engine)
    out = sc.read_columns_to_device(["k", "v"], direct="always")
    np.testing.assert_array_equal(np.asarray(out["k"]), keys)
    np.testing.assert_array_equal(np.asarray(out["v"]), vals)


def test_direct_span_larger_than_chunk(tmp_path):
    """Pages bigger than the engine's staging buffers split into
    chunk-sized sub-ranges (on-device concat reassembles)."""
    from nvme_strom_tpu.utils.config import EngineConfig
    rng = np.random.default_rng(8)
    vals = rng.standard_normal(100_000).astype(np.float32)  # 400 KB
    tbl = pa.table({"v": pa.array(vals)})
    path = str(tmp_path / "big.parquet")
    _write(path, tbl, data_page_size=1 << 20)   # one big page
    cfg = EngineConfig(chunk_bytes=64 << 10)    # 64 KiB staging buffers
    with StromEngine(cfg) as eng:
        sc = ParquetScanner(path, eng)
        out = sc.read_columns_to_device(["v"], direct="always")
        np.testing.assert_array_equal(np.asarray(out["v"]), vals)


def test_page_header_parser_roundtrip(tmp_path, engine):
    """plan_chunk's spans exactly tile the values: total span bytes ==
    num_values * width for every chunk, and spans are in-file order."""
    path = str(tmp_path / "p.parquet")
    tbl = _mixed_table(rows=10000, seed=5)
    _write(path, tbl, row_group_size=2048, data_page_size=4096)
    sc = ParquetScanner(path, engine)
    plans = pq_direct.plan_columns(sc, ["i32", "f32"])
    meta = sc.metadata
    for c, per_rg in plans.items():
        assert len(per_rg) == meta.num_row_groups
        for rg, plan in enumerate(per_rg):
            width = pq_direct._WIDTHS[plan.physical_type]
            assert sum(ln for _, ln in plan.spans) \
                == plan.num_values * width
            assert len(plan.spans) > 1   # data_page_size forced paging
            offs = [o for o, _ in plan.spans]
            assert offs == sorted(offs)


def test_rle_hybrid_decoder_unit():
    """Hand-crafted RLE/bit-packed hybrid streams decode exactly."""
    # RLE run: header = count << 1 (low bit 0), then ceil(bw/8)-byte value
    out = pq_direct.decode_rle_hybrid(bytes([10 << 1, 7]), 3, 10)
    np.testing.assert_array_equal(out, np.full(10, 7))

    # bit-packed run, bit_width 3: one group of 8 values 0..7
    # packed LSB-first: 0,1,2,...,7 → 3 bytes 0b10001000 0b11000110 0b11111010
    vals = np.arange(8)
    bits = np.zeros(24, np.uint8)
    for i, v in enumerate(vals):
        for b in range(3):
            bits[i * 3 + b] = (v >> b) & 1
    packed = np.packbits(bits, bitorder="little").tobytes()
    out = pq_direct.decode_rle_hybrid(bytes([1 << 1 | 1]) + packed, 3, 8)
    np.testing.assert_array_equal(out, vals)

    # mixed: RLE run of 4 fives, then the bit-packed 0..7, truncated to 10
    stream = bytes([4 << 1, 5]) + bytes([1 << 1 | 1]) + packed
    out = pq_direct.decode_rle_hybrid(stream, 3, 10)
    np.testing.assert_array_equal(out, [5, 5, 5, 5, 0, 1, 2, 3, 4, 5])

    # bit_width 0: single-entry dictionary, indices all zero, no bytes
    np.testing.assert_array_equal(
        pq_direct.decode_rle_hybrid(b"", 0, 6), np.zeros(6))

    # wide value: bit_width 17 RLE run uses a 3-byte little-endian value
    v = 0x1ABCD
    out = pq_direct.decode_rle_hybrid(
        bytes([3 << 1]) + v.to_bytes(3, "little"), 17, 3)
    np.testing.assert_array_equal(out, np.full(3, v))

    # truncation raises, never hangs
    with pytest.raises(ValueError):
        pq_direct.decode_rle_hybrid(b"", 3, 5)
    with pytest.raises(ValueError):
        pq_direct.decode_rle_hybrid(bytes([1 << 1 | 1]), 3, 8)


def test_batched_device_decode_parity():
    """The one-program batched device decoder (ops/bitunpack) matches
    the host reference across bit widths 1..24, mixed RLE/packed runs,
    and multi-page batches — the shape the round-4 change ships (three
    device ops per chunk instead of one put per run).  Streams come
    from test_bitunpack's reference encoder, independent of both
    decoders."""
    import jax
    from test_bitunpack import encode_hybrid
    from nvme_strom_tpu.ops.bitunpack import (rle_hybrid_batch_to_device,
                                              rle_hybrid_to_device)
    rng = np.random.default_rng(11)
    dev = jax.devices()[0]
    for bw in (1, 3, 6, 12, 17, 24):
        parts, expect = [], []
        for _ in range(3):
            runs, vals_all = [], []
            for _ in range(int(rng.integers(1, 6))):
                if rng.random() < 0.5:
                    n = int(rng.integers(1, 40))
                    v = int(rng.integers(0, 1 << bw))
                    runs.append(("rle", n, v))
                    vals_all += [v] * n
                else:
                    vs = rng.integers(
                        0, 1 << bw, int(rng.integers(1, 5)) * 8).tolist()
                    runs.append(("packed", vs))
                    vals_all += vs
            buf = encode_hybrid(runs, bw)
            parts.append((buf, bw, len(vals_all)))
            expect += vals_all
            one = np.asarray(rle_hybrid_to_device(
                buf, bw, len(vals_all), dev))
            np.testing.assert_array_equal(
                one, pq_direct.decode_rle_hybrid(buf, bw, len(vals_all)))
        got = np.asarray(rle_hybrid_batch_to_device(parts, dev))
        np.testing.assert_array_equal(got, np.array(expect, np.int32))


def test_dict_decode_matches_pyarrow(tmp_path, engine):
    """Dictionary-encoded chunks decode on device (gather) and bit-match
    pyarrow across row groups and page boundaries."""
    rng = np.random.default_rng(21)
    rows = 20000
    ki = rng.integers(0, 37, rows)
    kf = rng.integers(0, 11, rows)
    fvals = rng.standard_normal(11).astype(np.float32)
    tbl = pa.table({
        "i32": pa.array(ki.astype(np.int32)),
        "f32": pa.array(fvals[kf]),
    })
    path = str(tmp_path / "dict.parquet")
    pq.write_table(tbl, path, compression="none", use_dictionary=True,
                   row_group_size=6000, data_page_size=4096)
    sc = ParquetScanner(path, engine)
    assert all(r is None for r in sc.direct_reasons(["i32", "f32"]).values())
    plans = pq_direct.plan_columns(sc, ["i32", "f32"])
    assert any(p.kind == "dict" for plan in plans["i32"]
               for p in plan.parts)
    assert all(plan.dict_span is not None for plan in plans["i32"])
    out = sc.read_columns_to_device(["i32", "f32"], direct="always")
    np.testing.assert_array_equal(np.asarray(out["i32"]),
                                  tbl.column("i32").to_numpy())
    np.testing.assert_array_equal(np.asarray(out["f32"]),
                                  tbl.column("f32").to_numpy())


def test_dict_whole_column_batched_path(tmp_path, engine, monkeypatch):
    """The multi-row-group dict scan takes the WHOLE-COLUMN batched
    path (one decode + one combine + one sync, per-chunk dictionary
    base offsets — the round-4 suite_13 row priced the per-row-group
    walk at 179 s of dispatches), and the per-chunk fallback produces
    bit-identical values when the batched decode declines."""
    rng = np.random.default_rng(33)
    rows = 24000
    # per-row-group dictionaries DIFFER (encounter order of a random
    # stream), so the base-offset math is really exercised
    vals = rng.integers(0, 97, rows).astype(np.int32)
    tbl = pa.table({"v": pa.array(vals)})
    path = str(tmp_path / "dict_batched.parquet")
    pq.write_table(tbl, path, compression="none", use_dictionary=True,
                   row_group_size=5000, data_page_size=4096)
    sc = ParquetScanner(path, engine)
    plans = pq_direct.plan_columns(sc, ["v"])
    assert len(plans["v"]) > 1
    assert pq_direct._raw_dict_only(plans["v"])

    taken = {"batched": 0}
    real = pq_direct._read_dict_column_batched

    def spy(*a, **kw):
        out = real(*a, **kw)
        if out is not None:
            taken["batched"] += 1
        return out

    monkeypatch.setattr(pq_direct, "_read_dict_column_batched", spy)
    out = sc.read_columns_to_device(["v"], direct="always")
    assert taken["batched"] == 1
    np.testing.assert_array_equal(np.asarray(out["v"]), vals)

    # declined decode → per-chunk _assemble_chunk walk, same bytes
    monkeypatch.setattr(pq_direct, "_read_dict_column_batched",
                        lambda *a, **kw: None)
    out2 = sc.read_columns_to_device(["v"], direct="always")
    np.testing.assert_array_equal(np.asarray(out2["v"]), vals)
    monkeypatch.undo()

    # whole-batch decline → per-CHUNK retry on the SAME buffers (fresh
    # segment budget per chunk, device decode per chunk, no re-read)
    from nvme_strom_tpu.ops import bitunpack
    calls = {"n": 0}
    real_batch = bitunpack.rle_hybrid_batch_to_device

    def decline_first(parts, dev, engine=None):
        calls["n"] += 1
        if calls["n"] == 1:        # the whole-column attempt
            return None
        return real_batch(parts, dev, engine=engine)

    monkeypatch.setattr(bitunpack, "rle_hybrid_batch_to_device",
                        decline_first)
    out3 = sc.read_columns_to_device(["v"], direct="always")
    np.testing.assert_array_equal(np.asarray(out3["v"]), vals)
    assert calls["n"] == 1 + len(plans["v"])   # one retry per chunk


def test_dict_single_entry_bit_width_zero(tmp_path, engine):
    """A constant column gets a 1-entry dictionary and bit_width 0."""
    rows = 3000
    tbl = pa.table({"v": pa.array(np.full(rows, 42, np.int32))})
    path = str(tmp_path / "const.parquet")
    pq.write_table(tbl, path, compression="none", use_dictionary=True)
    sc = ParquetScanner(path, engine)
    out = sc.read_columns_to_device(["v"], direct="always")
    np.testing.assert_array_equal(np.asarray(out["v"]),
                                  np.full(rows, 42, np.int32))


def test_dict_overflow_mixed_plain_pages(tmp_path, engine):
    """When the writer's dictionary overflows it falls back to PLAIN data
    pages mid-chunk; the plan carries both kinds and assembly preserves
    page order."""
    rng = np.random.default_rng(22)
    rows = 30000
    vals = rng.integers(0, 2**30, rows).astype(np.int32)  # high cardinality
    tbl = pa.table({"v": pa.array(vals)})
    path = str(tmp_path / "overflow.parquet")
    pq.write_table(tbl, path, compression="none", use_dictionary=True,
                   dictionary_pagesize_limit=4096, data_page_size=8192)
    sc = ParquetScanner(path, engine)
    plans = pq_direct.plan_columns(sc, ["v"])
    kinds = {p.kind for plan in plans["v"] for p in plan.parts}
    assert kinds == {"dict", "plain"}, f"writer did not mix pages: {kinds}"
    out = sc.read_columns_to_device(["v"], direct="always")
    np.testing.assert_array_equal(np.asarray(out["v"]), vals)


def test_dict_accounting(tmp_path, monkeypatch):
    """Dictionary scan accounting with the on-device bit-unpack: the
    device receives dict values + the RAW (pow2-padded) bit-packed
    stream — never a 4-bytes-per-row expanded index array.  Host-touched
    payload (bounce) is the raw index stream the engine read (plus
    CPU-only device_put alias copies)."""
    monkeypatch.setenv("STROM_NO_RESIDENCY_PROBE", "1")
    rng = np.random.default_rng(23)
    rows = 16384
    tbl = pa.table({"v": pa.array(rng.integers(0, 50, rows)
                                  .astype(np.int32))})
    path = str(tmp_path / "acct_dict.parquet")
    pq.write_table(tbl, path, compression="none", use_dictionary=True)

    from nvme_strom_tpu.ops.bitunpack import split_rle_hybrid, _pow2_pad
    stats = StromStats()
    with StromEngine(stats=stats) as eng:
        fh = eng.open(path)
        is_direct = eng.file_is_direct(fh)
        eng.close(fh)
        if not is_direct:
            pytest.skip("fs rejects O_DIRECT")
        sc = ParquetScanner(path, eng)
        plans = pq_direct.plan_columns(sc, ["v"])
        idx_raw = 0        # raw index-stream bytes (engine-read, host)
        put_bytes = 0      # batched-decoder puts: padded raw stream
        #                    (+4 gather slack) + the (5, Rpad) run table
        with open(path, "rb") as f:
            for plan in plans["v"]:
                nruns = rawlen = 0
                for p in plan.parts:
                    assert p.kind == "dict"
                    idx_raw += p.span[1]
                    f.seek(p.span[0])
                    buf = f.read(p.span[1])
                    segs = split_rle_hybrid(buf, p.bit_width,
                                            p.valid_count)
                    assert segs is not None   # device path must engage
                    nruns += len(segs)
                    if any(s[0] == "packed" for s in segs):
                        rawlen += len(buf)
                put_bytes += (max(8, _pow2_pad(rawlen + 4))
                              + 5 * _pow2_pad(nruns) * 4)
        dict_bytes = sum(plan.dict_span[1] for plan in plans["v"])
        out = sc.read_columns_to_device(["v"], direct="always")
        np.testing.assert_array_equal(np.asarray(out["v"]),
                                      tbl.column("v").to_numpy())
        eng.sync_stats()
    assert idx_raw > 0 and dict_bytes > 0
    # device saw the dictionary values plus the padded packed stream —
    # NOT 4 bytes per row (the round-2 contract this replaces)
    assert stats.bytes_to_device == dict_bytes + put_bytes
    assert put_bytes < 4 * rows / 3     # bw=6: ~6x smaller than int32
    import jax
    alias = (dict_bytes + put_bytes
             if jax.devices()[0].platform == "cpu" else 0)
    assert stats.bounce_bytes == idx_raw + alias


def test_groupby_on_dict_file(tmp_path, engine):
    """sql_groupby consumes the dict fast path transparently."""
    from nvme_strom_tpu.sql.groupby import sql_groupby
    rng = np.random.default_rng(24)
    rows, groups = 20000, 16
    keys = rng.integers(0, groups, rows).astype(np.int32)
    vals = rng.integers(0, 9, rows).astype(np.float32)  # low cardinality
    tbl = pa.table({"k": pa.array(keys), "v": pa.array(vals)})
    path = str(tmp_path / "gdict.parquet")
    pq.write_table(tbl, path, compression="none", use_dictionary=True,
                   row_group_size=8192)
    sc = ParquetScanner(path, engine)
    assert all(r is None for r in sc.direct_reasons(["k", "v"]).values())
    out = sql_groupby(sc, "k", "v", groups, aggs=("count", "sum"))
    exp_count = np.bincount(keys, minlength=groups)
    exp_sum = np.bincount(keys, weights=vals.astype(np.float64),
                          minlength=groups)
    np.testing.assert_array_equal(np.asarray(out["count"]), exp_count)
    np.testing.assert_allclose(np.asarray(out["sum"]), exp_sum, rtol=2e-4)


def test_byte_stream_split_matches_pyarrow(tmp_path, engine):
    """BYTE_STREAM_SPLIT columns decode on device (reshape/transpose/
    bitcast — zero host-touched payload) and bit-match pyarrow."""
    rng = np.random.default_rng(31)
    rows = 20000
    f32 = rng.standard_normal(rows).astype(np.float32)
    i32 = rng.integers(-2**30, 2**30, rows).astype(np.int32)
    tbl = pa.table({"f32": pa.array(f32), "i32": pa.array(i32)})
    path = str(tmp_path / "bss.parquet")
    try:
        pq.write_table(tbl, path, compression="none", use_dictionary=False,
                       column_encoding={"f32": "BYTE_STREAM_SPLIT",
                                        "i32": "BYTE_STREAM_SPLIT"},
                       row_group_size=8192, data_page_size=4096)
    except pa.lib.ArrowNotImplementedError as e:
        pytest.skip(f"pyarrow cannot write BSS here: {e}")
    sc = ParquetScanner(path, engine)
    assert all(r is None
               for r in sc.direct_reasons(["f32", "i32"]).values())
    plans = pq_direct.plan_columns(sc, ["f32", "i32"])
    assert all(p.kind == "bss" for plan in plans["f32"]
               for p in plan.parts)
    out = sc.read_columns_to_device(["f32", "i32"], direct="always")
    np.testing.assert_array_equal(np.asarray(out["f32"]), f32)
    np.testing.assert_array_equal(np.asarray(out["i32"]), i32)


def test_byte_stream_split_payload_never_bounce(tmp_path, monkeypatch):
    """BSS accounting matches PLAIN: payload engine→device only (the
    decode permutation runs on device)."""
    monkeypatch.setenv("STROM_NO_RESIDENCY_PROBE", "1")
    rng = np.random.default_rng(32)
    rows = 8192
    vals = rng.standard_normal(rows).astype(np.float32)
    path = str(tmp_path / "bss_acct.parquet")
    pq.write_table(pa.table({"v": pa.array(vals)}), path,
                   compression="none", use_dictionary=False,
                   column_encoding={"v": "BYTE_STREAM_SPLIT"})
    stats = StromStats()
    with StromEngine(stats=stats) as eng:
        fh = eng.open(path)
        is_direct = eng.file_is_direct(fh)
        eng.close(fh)
        if not is_direct:
            pytest.skip("fs rejects O_DIRECT")
        sc = ParquetScanner(path, eng)
        out = sc.read_columns_to_device(["v"], direct="always")
        np.testing.assert_array_equal(np.asarray(out["v"]), vals)
        eng.sync_stats()
    payload = rows * 4
    assert stats.bytes_to_device == payload
    import jax
    expected_bounce = (payload if jax.devices()[0].platform == "cpu"
                       else 0)
    assert stats.bounce_bytes == expected_bounce


def test_empty_table_direct_scan(tmp_path, engine):
    """Zero-row files return empty typed columns, not a concat crash —
    both the 1-row-group/0-rows shape write_table emits and the
    0-row-group shape an unused ParquetWriter emits."""
    schema = pa.schema([pa.field("v", pa.float32(), nullable=False)])
    tbl = pa.table({"v": pa.array([], type=pa.float32())}, schema=schema)
    path = str(tmp_path / "empty.parquet")
    _write(path, tbl)
    sc = ParquetScanner(path, engine)
    out = sc.read_columns_to_device(["v"], direct="auto")
    arr = np.asarray(out["v"])
    assert arr.shape == (0,) and arr.dtype == np.float32

    path0 = str(tmp_path / "empty0.parquet")
    pq.ParquetWriter(path0, schema, compression="none",
                     use_dictionary=False).close()
    sc0 = ParquetScanner(path0, engine)
    assert sc0.metadata.num_row_groups == 0
    out0 = sc0.read_columns_to_device(["v"], direct="auto")
    arr0 = np.asarray(out0["v"])
    assert arr0.shape == (0,) and arr0.dtype == np.float32


def test_string_dict_codes_groupby(tmp_path, engine):
    """GROUP BY over a dictionary-encoded string key: the device groups
    by int32 codes, labels come back from the host-side dictionary —
    matches a host groupby including labels only seen in later row
    groups (global remap)."""
    from nvme_strom_tpu.sql.groupby import sql_groupby_str
    rng = np.random.default_rng(41)
    # row group 1 sees only cities A-C; row group 2 adds D, E —
    # per-rg dictionaries differ, so the global remap must do real work
    rg1 = [b"amsterdam", b"boston", b"cairo"]
    rg2 = [b"cairo", b"dakar", b"edinburgh", b"amsterdam"]
    n1, n2 = 6000, 6000
    k1 = rng.integers(0, len(rg1), n1)
    k2 = rng.integers(0, len(rg2), n2)
    keys = [rg1[i] for i in k1] + [rg2[i] for i in k2]
    vals = rng.standard_normal(n1 + n2).astype(np.float32)
    tbl = pa.table({"city": pa.array([k.decode() for k in keys]),
                    "v": pa.array(vals)})
    path = str(tmp_path / "cities.parquet")
    pq.write_table(tbl, path, compression="none", use_dictionary=True,
                   row_group_size=n1)
    sc = ParquetScanner(path, engine)
    out = sql_groupby_str(sc, "city", "v", aggs=("count", "sum"))
    labels = out["labels"]
    assert set(labels) == set(rg1) | set(rg2)
    # host ground truth
    import collections
    want_count = collections.Counter(keys)
    want_sum = collections.defaultdict(float)
    for k, v in zip(keys, vals):
        want_sum[k] += float(v)
    for g, lab in enumerate(labels):
        assert int(np.asarray(out["count"])[g]) == want_count[lab]
        np.testing.assert_allclose(np.asarray(out["sum"])[g],
                                   want_sum[lab], rtol=2e-4)


def test_string_dict_codes_where_pushdown(tmp_path, engine):
    """WHERE runs on device against codes + value columns."""
    from nvme_strom_tpu.sql.groupby import sql_groupby_str
    rng = np.random.default_rng(42)
    rows = 8000
    cities = [b"x", b"y", b"z"]
    ki = rng.integers(0, 3, rows)
    vals = rng.standard_normal(rows).astype(np.float32)
    tbl = pa.table({"city": pa.array([cities[i].decode() for i in ki]),
                    "v": pa.array(vals)})
    path = str(tmp_path / "wh.parquet")
    pq.write_table(tbl, path, compression="none", use_dictionary=True)
    sc = ParquetScanner(path, engine)
    out = sql_groupby_str(sc, "city", "v", aggs=("count",),
                          where=lambda c: c["v"] > 0)
    total = sum(int(x) for x in np.asarray(out["count"]))
    assert total == int((vals > 0).sum())


def test_string_dict_rejects_plain(tmp_path, engine):
    """A non-dictionary string column refuses with a reason."""
    tbl = pa.table({"s": pa.array(["a", "b", "c"] * 100)})
    path = str(tmp_path / "plain_str.parquet")
    pq.write_table(tbl, path, compression="none", use_dictionary=False)
    sc = ParquetScanner(path, engine)
    with pytest.raises(ValueError, match="dict-code-eligible"):
        pq_direct.read_dict_key_column(sc, "s")


def test_page_header_parser_fuzz():
    """Malformed/truncated header bytes must raise ThriftError (or parse
    to a header the walker then validates) — never hang or crash."""
    rng = np.random.default_rng(12)
    for ln in (0, 1, 3, 7, 17, 64, 256):
        for _ in range(200):
            buf = rng.integers(0, 256, ln, dtype=np.uint8).tobytes()
            try:
                ph = pq_direct.parse_page_header(buf)
                assert ph.header_len <= len(buf)
            except pq_direct.ThriftError:
                pass


# -- compressed chunks + null masks on the direct path (VERDICT r2 #4) ------


@pytest.mark.parametrize("comp", ["snappy", "zstd", "gzip"])
@pytest.mark.parametrize("ver", ["1.0", "2.0"])
@pytest.mark.parametrize("use_dict", [False, True])
def test_compressed_direct_matches_pyarrow(tmp_path, engine, comp, ver,
                                           use_dict):
    """Compressed chunks stay on the direct path (engine-read compressed
    spans, host decompress, on-device decode) and bit-match pyarrow for
    plain and dictionary encodings, v1 and v2 data pages."""
    rng = np.random.default_rng(11)
    rows = 9000
    i32 = rng.integers(0, 50, rows).astype(np.int32)   # dict-friendly
    f32 = rng.standard_normal(rows).astype(np.float32)
    path = str(tmp_path / "c.parquet")
    pq.write_table(pa.table({"i32": pa.array(i32), "f32": pa.array(f32)}),
                   path, compression=comp, use_dictionary=use_dict,
                   data_page_version=ver, row_group_size=4000)
    sc = ParquetScanner(path, engine)
    assert sc.direct_reasons(["i32", "f32"]) == {"i32": None, "f32": None}
    out = sc.read_columns_to_device(["i32", "f32"], direct="always")
    np.testing.assert_array_equal(np.asarray(out["i32"]), i32)
    np.testing.assert_array_equal(np.asarray(out["f32"]), f32)


@pytest.mark.parametrize("comp", ["none", "zstd"])
@pytest.mark.parametrize("ver", ["1.0", "2.0"])
@pytest.mark.parametrize("use_dict", [False, True])
def test_null_mask_direct_matches_pyarrow(tmp_path, engine, comp, ver,
                                          use_dict):
    """nulls='mask': definition levels decode to a validity mask, dense
    values scatter on device, null slots zero-fill — across page
    versions, codecs, and encodings."""
    rng = np.random.default_rng(12)
    rows = 7000
    base = rng.integers(0, 40, rows).astype(np.int32)
    nm = rng.random(rows) < 0.2
    vals = base.astype(object)
    vals[nm] = None
    path = str(tmp_path / "n.parquet")
    pq.write_table(pa.table({"v": pa.array(list(vals), pa.int32())}),
                   path, compression=comp, use_dictionary=use_dict,
                   data_page_version=ver, row_group_size=3000)
    sc = ParquetScanner(path, engine)
    v, m = sc.read_columns_to_device(["v"], direct="always",
                                     nulls="mask")["v"]
    v, m = np.asarray(v), np.asarray(m)
    np.testing.assert_array_equal(m, ~nm)
    np.testing.assert_array_equal(v[m], base[~nm])
    assert (v[~m] == 0).all()
    # default mode refuses the same column with a pointer to the fix
    with pytest.raises(ValueError, match="null"):
        sc.read_columns_to_device(["v"], direct="always")


def test_null_mask_pyarrow_fallback_parity(tmp_path, engine):
    """The pyarrow fallback honours the same (values, mask) contract so
    consumers never care which path served them."""
    rng = np.random.default_rng(13)
    rows = 3000
    base = rng.standard_normal(rows).astype(np.float32)
    nm = rng.random(rows) < 0.15
    vals = base.astype(object)
    vals[nm] = None
    path = str(tmp_path / "fb.parquet")
    _write(path, pa.table({"v": pa.array(list(vals), pa.float32())}))
    sc = ParquetScanner(path, engine)
    direct = sc.read_columns_to_device(["v"], direct="always",
                                       nulls="mask")["v"]
    fallb = sc.read_columns_to_device(["v"], direct="never",
                                      nulls="mask")["v"]
    for v, m in (direct, fallb):
        v, m = np.asarray(v), np.asarray(m)
        np.testing.assert_array_equal(m, ~nm)
        np.testing.assert_array_equal(v[m], base[~nm])
        assert (v[~m] == 0).all()


def test_all_null_and_leading_null_pages(tmp_path, engine):
    """Degenerate shapes: a column that is entirely null, and pages that
    START with nulls (exercises the clip(pos,0) guard in the on-device
    scatter)."""
    rows = 2000
    alln = pa.array([None] * rows, pa.int32())
    lead = pa.array([None] * 100 + list(range(rows - 100)), pa.int32())
    path = str(tmp_path / "d.parquet")
    _write(path, pa.table({"alln": alln, "lead": lead}))
    sc = ParquetScanner(path, engine)
    out = sc.read_columns_to_device(["alln", "lead"], direct="always",
                                    nulls="mask")
    v, m = (np.asarray(x) for x in out["alln"])
    assert not m.any() and (v == 0).all() and v.shape == (rows,)
    v, m = (np.asarray(x) for x in out["lead"])
    assert not m[:100].any() and m[100:].all()
    np.testing.assert_array_equal(v[100:], np.arange(rows - 100))


def test_compressed_bounce_is_bounded(tmp_path, engine):
    """Accounting: the compressed direct path may bounce (decompression
    is host work) but the bounce must stay within ~compressed+payload
    bytes — not the pyarrow path's whole-table materializations."""
    rng = np.random.default_rng(14)
    rows = 50000
    f32 = rng.standard_normal(rows).astype(np.float32)
    path = str(tmp_path / "b.parquet")
    pq.write_table(pa.table({"v": pa.array(f32)}), path,
                   compression="zstd", use_dictionary=False)
    sc = ParquetScanner(path, engine)
    pre = engine.stats.snapshot()["bounce_bytes"]
    out = sc.read_columns_to_device(["v"], direct="always")
    np.testing.assert_array_equal(np.asarray(out["v"]), f32)
    dbounce = engine.stats.snapshot()["bounce_bytes"] - pre
    payload = rows * 4
    # CPU test device: engine-read compressed bytes + decompressed body
    # + host_to_device protective copy — bound it at 3x payload
    assert 0 < dbounce <= 3 * payload + (1 << 16)


def test_direct_fuzz_random_layouts(tmp_path, engine):
    """Randomized layout fuzz: tiny data pages (multi-page chunks),
    random row-group sizes, codecs, page versions, dict-vs-plain,
    nullability — every combination must either bit-match pyarrow via
    the direct path or be rejected up front (never silently wrong)."""
    rng = np.random.default_rng(99)
    for trial in range(12):
        rows = int(rng.integers(500, 6000))
        comp = ["none", "snappy", "zstd"][trial % 3]
        ver = ["1.0", "2.0"][trial % 2]
        use_dict = bool(trial % 4 < 2)
        cardinality = int(rng.choice([3, 50, 1 << 20]))  # incl. overflow
        has_null = trial % 5 == 0
        base = rng.integers(0, cardinality, rows).astype(np.int32)
        if has_null:
            nm = rng.random(rows) < 0.1
            arr = base.astype(object)
            arr[nm] = None
            col = pa.array(list(arr), pa.int32())
        else:
            nm = np.zeros(rows, bool)
            col = pa.array(base)
        path = str(tmp_path / f"fuzz{trial}.parquet")
        pq.write_table(
            pa.table({"v": col}), path,
            compression=comp, use_dictionary=use_dict,
            data_page_version=ver,
            data_page_size=int(rng.integers(512, 8192)),  # tiny pages
            row_group_size=int(rng.integers(300, rows + 1)))
        sc = ParquetScanner(path, engine)
        ref = pq.read_table(path).column("v")
        if has_null:
            v, m = sc.read_columns_to_device(["v"], direct="always",
                                             nulls="mask")["v"]
            v, m = np.asarray(v), np.asarray(m)
            np.testing.assert_array_equal(m, ~nm, err_msg=str(trial))
            np.testing.assert_array_equal(v[m], base[~nm],
                                          err_msg=str(trial))
        else:
            out = sc.read_columns_to_device(["v"], direct="always")
            np.testing.assert_array_equal(
                np.asarray(out["v"]), ref.to_numpy(),
                err_msg=f"trial {trial} comp={comp} ver={ver} "
                        f"dict={use_dict} card={cardinality}")


def test_pipelined_iter_boundaries_and_pruning(tmp_path, engine):
    """The all-PLAIN scan streams as ONE pipelined range sequence
    (round-3 verdict #2); row-group boundaries are reassembled from
    chunk counts, so each yielded group must carry exactly its own
    rows — including under a pruned, non-contiguous row_groups subset
    and a column whose spans split across engine chunks."""
    import jax
    rows = 40_000
    rng = np.random.default_rng(7)
    data = {
        "k": rng.integers(0, 9, rows).astype(np.int32),
        "v": rng.standard_normal(rows).astype(np.float32),
    }
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table(data), path, row_group_size=4096)
    sc = ParquetScanner(path, engine)
    n_rg = sc.metadata.num_row_groups
    assert n_rg == 10
    dev = jax.local_devices()[0]
    subset = [7, 2, 9]              # pruned AND out of order
    got = list(pq_direct.iter_plain_row_groups_to_device(
        sc, ["k", "v"], device=dev, row_groups=subset))
    assert len(got) == len(subset)
    for rg, cols in zip(subset, got):
        lo, hi = rg * 4096, min((rg + 1) * 4096, rows)
        for c in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(cols[c]),
                                          data[c][lo:hi])


def test_windowed_iter_coalesces_and_matches(tmp_path, engine):
    """window_bytes batches consecutive row groups into fewer yields
    (the dispatch-latency lever for fold consumers) without changing
    the concatenated data or its order — including under a pruned
    subset, and degenerating to per-group yields when smaller than one
    group."""
    import jax
    rows = 40_000
    rng = np.random.default_rng(11)
    data = {
        "k": rng.integers(0, 9, rows).astype(np.int32),
        "v": rng.standard_normal(rows).astype(np.float32),
    }
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table(data), path, row_group_size=4096,
                   use_dictionary=False, compression="none")
    sc = ParquetScanner(path, engine)
    dev = jax.local_devices()[0]
    per_rg = list(pq_direct.iter_plain_row_groups_to_device(
        sc, ["k", "v"], device=dev))
    # ~2 groups of payload per window → fewer yields, same bytes
    win = list(pq_direct.iter_plain_row_groups_to_device(
        sc, ["k", "v"], device=dev, window_bytes=2 * 4096 * 8))
    assert 1 < len(win) < len(per_rg)
    for c in ("k", "v"):
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(g[c]) for g in win]), data[c])
    # pruned, out-of-order subset keeps submission order within windows
    subset = [7, 2, 9]
    winp = list(pq_direct.iter_plain_row_groups_to_device(
        sc, ["k", "v"], device=dev, row_groups=subset,
        window_bytes=1 << 30))
    assert len(winp) == 1
    want = np.concatenate([data["v"][rg * 4096:(rg + 1) * 4096]
                           for rg in subset])
    np.testing.assert_array_equal(np.asarray(winp[0]["v"]), want)
    # a window smaller than one group degenerates to per-group yields
    tiny = list(pq_direct.iter_plain_row_groups_to_device(
        sc, ["k", "v"], device=dev, window_bytes=1))
    assert len(tiny) == len(per_rg)


def test_groupby_windowing_invariant(tmp_path, engine, monkeypatch):
    """sql_groupby's result must not depend on the coalescing window
    (the fold is associative); pin window-on == window-off."""
    from nvme_strom_tpu.sql.groupby import sql_groupby
    rows = 50_000
    rng = np.random.default_rng(3)
    data = {
        "k": rng.integers(0, 16, rows).astype(np.int64),
        "v": rng.standard_normal(rows).astype(np.float64),
    }
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table(data), path, row_group_size=4096,
                   use_dictionary=False, compression="none")
    sc = ParquetScanner(path, engine)
    monkeypatch.setenv("STROM_SQL_WINDOW_BYTES", "0")
    off = sql_groupby(sc, "k", "v", 16, aggs=("count", "sum", "min",
                                              "max"))
    monkeypatch.setenv("STROM_SQL_WINDOW_BYTES", str(64 << 20))
    on = sql_groupby(sc, "k", "v", 16, aggs=("count", "sum", "min",
                                             "max"))
    for a in off:
        np.testing.assert_allclose(np.asarray(off[a]), np.asarray(on[a]),
                                   rtol=1e-12, err_msg=a)


def test_coalesced_multipage_chunks_bitmatch(tmp_path, engine):
    """Multi-page column chunks stream as ONE enclosing range (page
    headers ride along) and a jitted static-slice program drops the
    gaps on device — values must bit-match pyarrow, and the degap path
    must actually have engaged (page spans are per ~page; verbatim
    submission costs ~8x more device puts per byte than the merged
    range — the window-7 on-silicon gap)."""
    import jax
    from nvme_strom_tpu.sql.pq_direct import _coalesce_spans, _degap
    rows = 60_000
    rng = np.random.default_rng(21)
    data = {
        "k": rng.integers(0, 9, rows).astype(np.int32),
        "v": rng.standard_normal(rows).astype(np.float32),
    }
    path = str(tmp_path / "mp.parquet")
    # 4 KiB pages → ~15 pages per 15k-row group chunk: real gaps
    pq.write_table(pa.table(data), path, row_group_size=15_000,
                   use_dictionary=False, compression="none",
                   data_page_size=4096)
    sc = ParquetScanner(path, engine)
    plans = pq_direct.plan_columns(sc, ["k", "v"])
    assert any(len(plans[c][rg].spans) > 1
               for c in ("k", "v") for rg in range(4)), \
        "layout did not produce multi-page chunks"
    assert _coalesce_spans(plans["v"][0].spans) is not None
    before = _degap.cache_info().misses + _degap.cache_info().hits
    dev = jax.local_devices()[0]
    for wb in (None, 1 << 30):       # per-rg and windowed
        got = list(pq_direct.iter_plain_row_groups_to_device(
            sc, ["k", "v"], device=dev, window_bytes=wb))
        for c in ("k", "v"):
            np.testing.assert_array_equal(
                np.concatenate([np.asarray(g[c]) for g in got]),
                data[c], err_msg=f"wb={wb} col={c}")
    assert _degap.cache_info().misses + _degap.cache_info().hits \
        > before, "degap compaction never engaged"
    # end-to-end through the fold too
    from nvme_strom_tpu.sql.groupby import sql_groupby
    out = sql_groupby(sc, "k", "v", 9, aggs=("count", "sum"))
    np.testing.assert_array_equal(np.asarray(out["count"]),
                                  np.bincount(data["k"], minlength=9))
    np.testing.assert_allclose(
        np.asarray(out["sum"]),
        np.bincount(data["k"], weights=data["v"].astype(np.float64),
                    minlength=9), rtol=1e-3, atol=0.05)  # f32 cancel


def test_pipelined_iter_abandoned_mid_scan(tmp_path, engine):
    """Breaking out of the pipelined scan (the topk elimination path)
    must release every in-flight staging buffer — a second full scan
    through the same engine would otherwise starve on the pool."""
    import jax
    rows = 40_000
    data = {"v": np.arange(rows, dtype=np.int32)}
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table(data), path, row_group_size=4096)
    sc = ParquetScanner(path, engine)
    dev = jax.local_devices()[0]
    it = pq_direct.iter_plain_row_groups_to_device(sc, ["v"], device=dev)
    next(it)
    it.close()                      # abandon after one group
    # engine still serviceable: a full scan completes and is correct
    full = list(pq_direct.iter_plain_row_groups_to_device(
        sc, ["v"], device=dev))
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(c["v"]) for c in full]), data["v"])

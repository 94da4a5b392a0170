"""Direct Parquet column decode: NVMe pages → device, no pyarrow on the hot
path.

PG-Strom's distinguishing move is decoding table blocks ON the accelerator
(SURVEY.md §3.5) — the CPU plans, the device decodes.  The Parquet analogue
for uncompressed, fixed-width columns, two page shapes:

- **PLAIN** data pages: host (metadata-class I/O, tiny) parses the footer
  (already held by the scanner) and each data-page header — a minimal
  Thrift compact-protocol reader, ~40 bytes per page — to compute the
  exact byte spans of raw little-endian values inside the file; the spans
  stream through the O_DIRECT engine and DeviceStream (staging → HBM, zero
  host-side payload copies), and the 'decode' is an on-device bitcast +
  concatenate.  Optional columns with no nulls carry an RLE
  definition-level block per page; its length is read host-side (8 bytes)
  and the span simply starts after it.
- **Dictionary-encoded** (PLAIN_DICTIONARY / RLE_DICTIONARY) chunks, the
  PG-Strom dictionary pattern: the dictionary page's PLAIN values stream
  O_DIRECT → device exactly like a plain span, the data pages'
  RLE/bit-packed index stream is read through the engine and expanded
  host-side with a vectorized numpy decoder (runs are sequential
  bitstream control flow — host work by nature; the decoded index array
  is honestly counted as bounce), and the final decode is an on-device
  ``take(dictionary, indices)`` gather.  Chunks where the writer fell
  back to PLAIN mid-stream (dictionary overflow) assemble both kinds in
  page order.

- **Compressed** chunks (SNAPPY / ZSTD / GZIP / BROTLI / LZ4_RAW) stay on
  the direct path: the compressed page spans ride O_DIRECT through the
  engine exactly like plain spans (less disk traffic — compressed size),
  the host decompresses each page body (pyarrow's codec library; the
  decompressed bytes are honestly counted as bounce — codecs are
  sequential bitstream control flow, host work by nature), and the value
  decode (bitcast / dictionary gather) still happens on device.  v2 data
  pages keep their level blocks uncompressed ahead of the values region
  (and may mark individual pages ``is_compressed=false``); v1 pages
  compress levels+values together, so their levels parse from the
  decompressed body.
- **Nulls** (``nulls="mask"``): definition levels decode host-side
  (plan time when raw, decode time inside compressed v1 bodies) into a
  per-page validity mask; dense non-null values take their normal path
  (zero-copy stream when uncompressed!) and a cumsum-gather ON DEVICE
  scatters them to full page length, null slots zero-filled.  Consumers
  get ``(values, mask)`` pairs.

Everything else — exotic codecs (legacy framed LZ4), strings outside the
dict-code scan, nested/repeated schemas — falls back to the pyarrow path
in :mod:`.parquet`, which decodes on host and honestly counts the
handoff copy as bounce.

Why not decode the index bitstream on device too?  RLE runs are
variable-length sequential control flow; a Pallas cursor over them would
serialize (one varint at a time) — exactly what the MXU/VPU are worst
at.  The expensive expansion (indices → values) IS on device: the gather
reads only index ints host-side, never payload values.
"""

from __future__ import annotations

import functools
import struct
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

#: the QoS latency class every sql/ payload read rides (io/sched.py):
#: analytics scans dispatch below serving decode/restore/prefetch and
#: above scrub, so a partition-parallel table scan is governed by the
#: scheduler's fair-share instead of competing as anonymous bulk
SCAN_CLASS = "scan"

# Parquet physical types that are raw fixed-width little-endian under PLAIN
_WIDTHS = {"INT32": 4, "INT64": 8, "FLOAT": 4, "DOUBLE": 8}
_NP_DTYPES = {"INT32": "<i4", "INT64": "<i8", "FLOAT": "<f4",
              "DOUBLE": "<f8"}

# Thrift compact-protocol wire types
_CT_STOP = 0
_CT_BOOL_TRUE = 1
_CT_BOOL_FALSE = 2
_CT_BYTE = 3
_CT_I16 = 4
_CT_I32 = 5
_CT_I64 = 6
_CT_DOUBLE = 7
_CT_BINARY = 8
_CT_LIST = 9
_CT_SET = 10
_CT_MAP = 11
_CT_STRUCT = 12

# parquet-format enums
_PAGE_DATA = 0
_PAGE_DICTIONARY = 2
_PAGE_DATA_V2 = 3
_ENC_PLAIN = 0
_ENC_PLAIN_DICTIONARY = 2
_ENC_RLE = 3
_ENC_RLE_DICTIONARY = 8
_ENC_BYTE_STREAM_SPLIT = 9
_DICT_ENCODINGS = (_ENC_PLAIN_DICTIONARY, _ENC_RLE_DICTIONARY)


class ThriftError(ValueError):
    """Malformed/truncated Thrift compact data (or not enough bytes read —
    callers retry with a bigger window before giving up)."""


class _Compact:
    """Just enough of the Thrift compact protocol to read a Parquet
    PageHeader: varints, zigzag, field headers, and recursive skip.
    parquet-format/src/main/thrift/parquet.thrift defines the schema; the
    reference consumes the same metadata via its SQL host code."""

    def __init__(self, buf: bytes, pos: int = 0):
        self.buf = buf
        self.pos = pos

    def _byte(self) -> int:
        if self.pos >= len(self.buf):
            raise ThriftError("truncated")
        b = self.buf[self.pos]
        self.pos += 1
        return b

    def varint(self) -> int:
        out = shift = 0
        while True:
            b = self._byte()
            out |= (b & 0x7F) << shift
            if not b & 0x80:
                return out
            shift += 7
            if shift > 63:
                raise ThriftError("varint overflow")

    def zigzag(self) -> int:
        v = self.varint()
        return (v >> 1) ^ -(v & 1)

    def read_field_header(self, last_id: int) -> Tuple[int, int]:
        """→ (wire_type, field_id); wire_type 0 = stop."""
        b = self._byte()
        if b == _CT_STOP:
            return 0, 0
        delta, ctype = b >> 4, b & 0x0F
        fid = last_id + delta if delta else self.zigzag()
        return ctype, fid

    def skip(self, ctype: int) -> None:
        if ctype in (_CT_BOOL_TRUE, _CT_BOOL_FALSE):
            return
        if ctype == _CT_BYTE:
            self._byte()
        elif ctype in (_CT_I16, _CT_I32, _CT_I64):
            self.varint()
        elif ctype == _CT_DOUBLE:
            self.pos += 8
            if self.pos > len(self.buf):
                raise ThriftError("truncated")
        elif ctype == _CT_BINARY:
            n = self.varint()
            self.pos += n
            if self.pos > len(self.buf):
                raise ThriftError("truncated")
        elif ctype in (_CT_LIST, _CT_SET):
            b = self._byte()
            n, et = b >> 4, b & 0x0F
            if n == 15:
                n = self.varint()
            # bool elements consume ZERO bytes per skip — an unbounded
            # count from malformed input would spin forever; any honest
            # collection needs at least... well, bools need nothing, so
            # bound by what the buffer could possibly hold
            if n > len(self.buf) - self.pos:
                raise ThriftError(f"collection count {n} exceeds buffer")
            for _ in range(n):
                self.skip(et)
        elif ctype == _CT_MAP:
            n = self.varint()
            if n > len(self.buf) - self.pos:
                raise ThriftError(f"map count {n} exceeds buffer")
            if n:
                b = self._byte()
                kt, vt = b >> 4, b & 0x0F
                for _ in range(n):
                    self.skip(kt)
                    self.skip(vt)
        elif ctype == _CT_STRUCT:
            last = 0
            while True:
                t, fid = self.read_field_header(last)
                if t == 0:
                    return
                last = fid
                self.skip(t)
        else:
            raise ThriftError(f"bad compact type {ctype}")


@dataclass(frozen=True)
class PageHeader:
    type: int
    compressed_size: int
    uncompressed_size: int
    num_values: int          # data/dictionary pages (0 otherwise)
    encoding: int            # data/dictionary pages (-1 otherwise)
    header_len: int          # bytes the Thrift header itself occupies
    # DataPageHeaderV2 states the level-block lengths explicitly (a v1
    # reader must instead parse RLE length prefixes from the page body)
    def_levels_len: int = 0
    rep_levels_len: int = 0
    # DataPageHeaderV2 field 7: false = the values region is stored RAW
    # even though the chunk declares a codec (writers skip codecs that
    # don't pay — pyarrow does this routinely for dict index streams)
    v2_is_compressed: bool = True


def parse_page_header(buf: bytes) -> PageHeader:
    """Parse a PageHeader at buf[0].  Raises ThriftError if ``buf`` is too
    short (callers re-read with a larger window)."""
    c = _Compact(buf)
    ptype = comp = uncomp = -1
    num_values, encoding = 0, -1
    def_len = rep_len = 0
    v2_compressed = True
    last = 0
    while True:
        t, fid = c.read_field_header(last)
        if t == 0:
            break
        last = fid
        if fid == 1 and t == _CT_I32:
            ptype = c.zigzag()
        elif fid == 2 and t == _CT_I32:
            uncomp = c.zigzag()
        elif fid == 3 and t == _CT_I32:
            comp = c.zigzag()
        elif fid in (5, 7, 8) and t == _CT_STRUCT:
            # DataPageHeader (v1) / DictionaryPageHeader / DataPageHeaderV2
            inner_last = 0
            while True:
                it, ifid = c.read_field_header(inner_last)
                if it == 0:
                    break
                inner_last = ifid
                if ifid == 1 and it == _CT_I32:
                    num_values = c.zigzag()
                elif ifid == 2 and it == _CT_I32 and fid in (5, 7):
                    encoding = c.zigzag()
                elif ifid == 4 and it == _CT_I32 and fid == 8:
                    encoding = c.zigzag()
                elif ifid == 5 and it == _CT_I32 and fid == 8:
                    def_len = c.zigzag()
                elif ifid == 6 and it == _CT_I32 and fid == 8:
                    rep_len = c.zigzag()
                elif (ifid == 7 and fid == 8
                      and it in (_CT_BOOL_TRUE, _CT_BOOL_FALSE)):
                    # bool struct fields carry the value in the type nibble
                    v2_compressed = it == _CT_BOOL_TRUE
                else:
                    c.skip(it)
        else:
            c.skip(t)
    if ptype < 0 or comp < 0:
        raise ThriftError("missing required PageHeader fields")
    return PageHeader(ptype, comp, uncomp, num_values, encoding, c.pos,
                      def_len, rep_len, v2_compressed)


@dataclass(frozen=True)
class PagePart:
    """One data page's decodable payload within a column chunk.

    kind "plain": ``span`` covers raw little-endian values (on-device
    bitcast).  kind "dict": ``span`` covers the RLE/bit-packed index
    stream (host-expanded, then on-device gather against the chunk's
    dictionary); ``bit_width`` is the stream's index width.  kind
    "bss": BYTE_STREAM_SPLIT — ``span`` covers the byte-transposed
    values (decode is an on-device reshape/transpose/bitcast, zero
    host-touched payload like plain).

    ``codec`` != None: ``span`` covers COMPRESSED bytes — the engine
    still reads them O_DIRECT, but the host must decompress before the
    on-device decode (counted as bounce; see module docstring).  v1
    pages compress levels+values together, so a compressed v1 page with
    definition levels sets ``inline_levels`` and its levels are parsed
    from the decompressed body; every other layout resolves its levels
    at PLAN time into ``mask``/``n_valid``.  ``mask`` (len num_values,
    True = non-null) is None when every value is present; masked pages
    scatter their dense values on device.
    """
    kind: str                              # "plain" | "dict" | "bss"
    span: Tuple[int, int]                  # (offset, length) into the file
    num_values: int                        # values INCLUDING nulls
    bit_width: int = 0                     # dict parts (-1 = in codec body)
    codec: Optional[str] = None            # Parquet codec name
    uncompressed_len: int = 0              # decompressed span length
    inline_levels: bool = False            # v1+codec: levels in the body
    max_def: int = 0                       # schema max definition level
    n_valid: int = -1                      # -1 = num_values (no nulls)
    mask: Optional[object] = None          # np.bool_ mask, plan-time known

    @property
    def valid_count(self) -> int:
        return self.num_values if self.n_valid < 0 else self.n_valid

    @property
    def is_raw(self) -> bool:
        """Payload can ride staging→device untouched (no host decode)."""
        return self.codec is None and self.mask is None


@dataclass(frozen=True)
class ColumnPlan:
    """Decodable page layout of one column chunk (one row group)."""
    parts: Tuple[PagePart, ...]            # in file/page order
    num_values: int
    physical_type: str
    dict_span: Optional[Tuple[int, int]] = None   # PLAIN dictionary values
    dict_count: int = 0
    dict_codec: Optional[str] = None       # dictionary page's codec
    dict_uncompressed_len: int = 0

    @property
    def spans(self) -> Tuple[Tuple[int, int], ...]:
        """Plain value-byte spans (the pre-dictionary API surface)."""
        return tuple(p.span for p in self.parts if p.kind == "plain")


# Parquet codec name → pyarrow codec name.  pyarrow here is a CODEC
# LIBRARY only (snappy/zstd/... C++ decompressors) — the page walk,
# span planning, and value decode stay this module's own.  Legacy
# hadoop-framed "LZ4" is intentionally absent (ambiguous framing);
# it falls back to the pyarrow reader path.
_CODECS = {"SNAPPY": "snappy", "GZIP": "gzip", "ZSTD": "zstd",
           "BROTLI": "brotli", "LZ4_RAW": "lz4_raw"}


def _codec_of(col) -> Optional[str]:
    """Column chunk's codec name, None when uncompressed."""
    name = col.compression or "UNCOMPRESSED"
    return None if name == "UNCOMPRESSED" else name


def _codec_available(name: str) -> bool:
    if name not in _CODECS:
        return False
    import pyarrow as pa
    return pa.Codec.is_available(_CODECS[name])


def _decompress(codec: str, buf, out_len: int) -> memoryview:
    """Host page decompression via the pyarrow codec library.  Returns a
    memoryview over the codec's output buffer (no extra copy)."""
    import pyarrow as pa
    out = pa.Codec(_CODECS[codec]).decompress(bytes(buf), out_len)
    mv = memoryview(out)
    if mv.nbytes != out_len:
        raise ValueError(
            f"codec {codec}: decompressed {mv.nbytes} bytes, header "
            f"promised {out_len}")
    return mv


def eligible_chunk(meta, rg: int, ci: int,
                   allow_nulls: bool = False) -> Optional[str]:
    """None if the (row group, column) chunk can decode on device, else a
    human-readable reason for the pyarrow fallback (surfaced in stats).

    ``allow_nulls``: chunks with (possible) nulls are eligible — the
    plan decodes definition levels and decode scatters on device; the
    caller must consume (values, mask) pairs."""
    col = meta.row_group(rg).column(ci)
    sc = meta.schema.column(ci)
    if col.physical_type not in _WIDTHS:
        return f"physical type {col.physical_type}"
    if _WIDTHS[col.physical_type] == 8:
        import jax
        if not jax.config.jax_enable_x64:
            # the on-device bitcast would silently truncate i64/f64
            return (f"{col.physical_type} needs jax_enable_x64 "
                    f"(bitcast would truncate)")
    codec = _codec_of(col)
    if codec is not None and not _codec_available(codec):
        return f"compression {col.compression}"
    encs = set(col.encodings)
    if not encs <= {"PLAIN", "RLE", "PLAIN_DICTIONARY", "RLE_DICTIONARY",
                    "BYTE_STREAM_SPLIT"}:
        return f"encodings {sorted(encs)}"
    if sc.max_repetition_level != 0:
        return "repeated field"
    if sc.max_definition_level > 0 and not allow_nulls:
        st = col.statistics
        if st is None or st.null_count is None:
            return "no null statistics"
        if st.null_count != 0:
            return f"{st.null_count} nulls (pass nulls='mask')"
    return None


def _walk_pages(col, raw_read):
    """Yield (pos, PageHeader) for every page of a column chunk, until
    the data pages' value counts cover ``col.num_values``.

    ``raw_read(offset, length) -> bytes`` serves page headers —
    metadata-class reads (≤ ~1 KiB per page, via buffered I/O like the
    footer), never payload."""
    pos = col.data_page_offset
    if (col.dictionary_page_offset or 0) > 0:
        # the dictionary page precedes the data pages in the chunk
        pos = min(pos, col.dictionary_page_offset)
    end = pos + col.total_compressed_size
    remaining = col.num_values
    window = 1 << 10
    while remaining > 0:
        if pos >= end:
            raise ValueError(f"page walk ran past chunk end at {pos}")
        buf = raw_read(pos, min(window, end - pos))
        while True:
            try:
                ph = parse_page_header(buf)
                break
            except ThriftError:
                if len(buf) >= end - pos:
                    raise
                buf = raw_read(pos, min(len(buf) * 2, end - pos))
        if ph.type in (_PAGE_DATA, _PAGE_DATA_V2):
            if ph.num_values > remaining:
                # RLE can legally pack huge claimed counts into a few
                # bytes — an unbounded count would drive a huge host
                # allocation in the index decoder (and silently
                # over-long plain output)
                raise ValueError(
                    f"page at {pos}: {ph.num_values} values exceeds "
                    f"chunk remainder {remaining}")
            remaining -= ph.num_values
        yield pos, ph
        pos += ph.header_len + ph.compressed_size


def _plan_levels(pos, ph, max_def: int, raw_read, may_null: bool):
    """Levels of an UNCOMPRESSED-levels page → (level_bytes, mask|None).

    v2 stores levels uncompressed regardless of the chunk codec; v1
    callers must only pass pages whose body is raw (a compressed v1
    page parses its levels from the decompressed body instead —
    ``inline_levels``).  ``may_null`` False skips the decode (statistics
    already proved every value present).  mask is None when all valid.
    """
    import numpy as np
    bw = max_def.bit_length()
    if ph.type == _PAGE_DATA_V2:
        lb = ph.def_levels_len + ph.rep_levels_len
        if not (may_null and ph.def_levels_len):
            return lb, None
        buf = raw_read(pos + ph.header_len + ph.rep_levels_len,
                       ph.def_levels_len)
        lev = decode_rle_hybrid(buf, bw, ph.num_values)
    else:
        if max_def == 0:
            return 0, None
        (n,) = struct.unpack("<I", raw_read(pos + ph.header_len, 4))
        lb = 4 + n
        if not may_null:
            return lb, None
        lev = decode_rle_hybrid(raw_read(pos + ph.header_len + 4, n),
                                bw, ph.num_values)
    mask = lev == max_def
    return lb, (None if mask.all() else np.asarray(mask))


def _index_stream_part(pos, ph, level_bytes: int, raw_read,
                       max_def: int = 0, n_valid: int = -1,
                       mask=None) -> PagePart:
    """Dict-encoded data-page body → index-stream PagePart.

    Body after levels: ``<bit_width: 1 byte><RLE-hybrid runs>`` — the
    one layout rule both the numeric and byte-array walks share.  Only
    valid for RAW bodies (compressed pages read their bit-width from
    the decompressed body at decode time)."""
    val_off = pos + ph.header_len + level_bytes
    (bw,) = raw_read(val_off, 1)
    if bw > 32:
        raise ValueError(f"page at {pos}: bit width {bw} > 32")
    idx_len = ph.compressed_size - level_bytes - 1
    if idx_len < 0:
        raise ValueError(f"page at {pos}: negative index span")
    return PagePart("dict", (val_off + 1, idx_len), ph.num_values,
                    bit_width=bw, max_def=max_def, n_valid=n_valid,
                    mask=mask)


def _check_dict_page(pos, ph, already_seen: bool) -> None:
    """Shared dictionary-page validity rules (one per chunk, PLAIN)."""
    if already_seen:
        raise ValueError(f"second dictionary page at {pos}")
    if ph.encoding not in (_ENC_PLAIN, _ENC_PLAIN_DICTIONARY):
        raise ValueError(
            f"dictionary page encoding {ph.encoding} not PLAIN")


def plan_chunk(meta, rg: int, ci: int, raw_read,
               allow_nulls: bool = False) -> ColumnPlan:
    """Walk the chunk's data pages, returning exact value-byte spans.

    ``raw_read`` as in :func:`_walk_pages`; it additionally serves the
    v1 RLE level-length prefixes and — when nulls are possible and
    allowed — the (always-uncompressed-accessible) level blocks, which
    decode to per-page masks at plan time.  Compressed chunks emit
    codec-tagged parts whose spans cover the compressed bytes; a
    compressed v1 page with definition levels defers its level parse to
    decode time (``inline_levels`` — v1 compresses levels and values
    together)."""
    col = meta.row_group(rg).column(ci)
    sc = meta.schema.column(ci)
    width = _WIDTHS[col.physical_type]
    max_def = sc.max_definition_level
    codec = _codec_of(col)
    st = col.statistics
    # statistics can PROVE the chunk null-free; anything else (nulls
    # recorded, or no stats at all) must consult the levels
    may_null = (max_def > 0
                and (st is None or st.null_count is None
                     or st.null_count != 0))
    if may_null and not allow_nulls:
        raise ValueError(
            f"rg{rg} col{ci}: possible nulls (pass nulls='mask')")
    parts: List[PagePart] = []
    dict_span: Optional[Tuple[int, int]] = None
    dict_count = 0
    dict_codec: Optional[str] = None
    dict_ulen = 0
    for pos, ph in _walk_pages(col, raw_read):
        if ph.type in (_PAGE_DATA, _PAGE_DATA_V2):
            v2 = ph.type == _PAGE_DATA_V2
            page_codec = codec
            if v2 and not ph.v2_is_compressed:
                page_codec = None
            if ph.encoding not in (_ENC_PLAIN, _ENC_BYTE_STREAM_SPLIT,
                                   *_DICT_ENCODINGS):
                raise ValueError(
                    f"page at {pos}: unsupported encoding {ph.encoding}")
            kind = {_ENC_PLAIN: "plain",
                    _ENC_BYTE_STREAM_SPLIT: "bss"}.get(ph.encoding, "dict")
            if kind == "dict" and dict_span is None:
                raise ValueError(
                    f"page at {pos}: dict-encoded data page before "
                    f"any dictionary page")
            if page_codec is not None and not v2:
                # v1: levels+values compressed as one body — the span is
                # the whole body, levels resolve after decompression
                # inline_levels whenever the schema has def levels: even
                # a proven null-free page carries the level block and the
                # decoder must parse past it (mask collapses to None)
                parts.append(PagePart(
                    kind, (pos + ph.header_len, ph.compressed_size),
                    ph.num_values, bit_width=-1, codec=page_codec,
                    uncompressed_len=ph.uncompressed_size,
                    inline_levels=max_def > 0, max_def=max_def))
                continue
            # levels are addressable raw: v1-uncompressed in the body,
            # v2 always uncompressed ahead of the values region
            lb, mask = _plan_levels(pos, ph, max_def, raw_read, may_null)
            n_valid = int(mask.sum()) if mask is not None else -1
            vc = ph.num_values if n_valid < 0 else n_valid
            val_off = pos + ph.header_len + lb
            val_len = ph.compressed_size - lb
            if page_codec is not None:      # compressed v2 values region
                parts.append(PagePart(
                    kind, (val_off, val_len), ph.num_values,
                    bit_width=-1, codec=page_codec,
                    uncompressed_len=ph.uncompressed_size - lb,
                    max_def=max_def, n_valid=n_valid, mask=mask))
                continue
            if kind in ("plain", "bss"):
                want = vc * width
                if want + lb > ph.compressed_size:
                    raise ValueError(
                        f"page at {pos}: {vc} values x {width} + {lb} "
                        f"level bytes > page size {ph.compressed_size}")
                parts.append(PagePart(kind, (val_off, want),
                                      ph.num_values, max_def=max_def,
                                      n_valid=n_valid, mask=mask))
            else:
                parts.append(_index_stream_part(
                    pos, ph, lb, raw_read, max_def=max_def,
                    n_valid=n_valid, mask=mask))
        elif ph.type == _PAGE_DICTIONARY:
            _check_dict_page(pos, ph, dict_span is not None)
            if codec is not None:
                dict_span = (pos + ph.header_len, ph.compressed_size)
                dict_codec = codec
                dict_ulen = ph.uncompressed_size
                if ph.num_values * width > ph.uncompressed_size:
                    raise ValueError(
                        f"dictionary page at {pos}: {ph.num_values} "
                        f"values x {width} > uncompressed size "
                        f"{ph.uncompressed_size}")
            else:
                val_len = ph.num_values * width
                if val_len > ph.compressed_size:
                    raise ValueError(
                        f"dictionary page at {pos}: {ph.num_values} "
                        f"values x {width} > page size "
                        f"{ph.compressed_size}")
                dict_span = (pos + ph.header_len, val_len)
            dict_count = ph.num_values
        # INDEX pages are skipped silently
    return ColumnPlan(tuple(parts), col.num_values, col.physical_type,
                      dict_span=dict_span, dict_count=dict_count,
                      dict_codec=dict_codec,
                      dict_uncompressed_len=dict_ulen)


def decode_rle_hybrid(buf: bytes, bit_width: int, count: int):
    """Parquet RLE/bit-packed hybrid stream → int32 index array (host).

    The stream is a sequence of runs, each headed by a varint: low bit 1
    → bit-packed run of ``(header >> 1) * 8`` values (``bit_width`` bits
    each, LSB-first little-endian — decoded vectorized via
    ``np.unpackbits``); low bit 0 → RLE run of ``header >> 1`` copies of
    one ``ceil(bit_width / 8)``-byte value.  The final run may carry
    padding values past ``count``; they are discarded per the spec.
    """
    import numpy as np
    out = np.empty(count, np.int32)
    if bit_width == 0:
        # zero-width indices: a single-entry dictionary, all index 0
        out[:] = 0
        return out
    byte_w = (bit_width + 7) // 8
    weights = (np.int64(1) << np.arange(bit_width, dtype=np.int64))
    pos, filled, n = 0, 0, len(buf)
    while filled < count:
        header = shift = 0
        while True:
            if pos >= n:
                raise ValueError("truncated RLE stream header")
            b = buf[pos]
            pos += 1
            header |= (b & 0x7F) << shift
            if not b & 0x80:
                break
            shift += 7
            if shift > 35:
                raise ValueError("RLE header varint overflow")
        if header & 1:                       # bit-packed run
            groups = header >> 1
            nbytes = groups * bit_width      # groups of 8 values
            if pos + nbytes > n:
                raise ValueError("truncated bit-packed run")
            bits = np.unpackbits(
                np.frombuffer(buf, np.uint8, nbytes, pos),
                bitorder="little")
            vals = bits.reshape(-1, bit_width).astype(np.int64) @ weights
            take = min(groups * 8, count - filled)
            out[filled:filled + take] = vals[:take]
            filled += take
            pos += nbytes
        else:                                # RLE run
            run = header >> 1
            if run == 0:
                raise ValueError("zero-length RLE run")
            if pos + byte_w > n:
                raise ValueError("truncated RLE run value")
            v = int.from_bytes(buf[pos:pos + byte_w], "little")
            pos += byte_w
            take = min(run, count - filled)
            out[filled:filled + take] = v
            filled += take
    return out


def plan_columns(scanner, columns: Sequence[str],
                 allow_nulls: bool = False
                 ) -> Dict[str, List[ColumnPlan]]:
    """Page-walk every (row group, column) chunk → value spans.  Raises
    ValueError naming the first non-eligible chunk — callers wanting a
    soft answer use :func:`eligible_chunk` first."""
    import os
    meta = scanner.metadata
    name_to_ci = {meta.schema.column(i).name: i
                  for i in range(meta.num_columns)}
    with open(scanner.path, "rb") as f:
        def raw_read(off: int, ln: int) -> bytes:
            return os.pread(f.fileno(), ln, off)

        plans: Dict[str, List[ColumnPlan]] = {c: [] for c in columns}
        for rg in range(meta.num_row_groups):
            for c in columns:
                ci = name_to_ci[c]
                why = eligible_chunk(meta, rg, ci,
                                     allow_nulls=allow_nulls)
                if why is not None:
                    raise ValueError(
                        f"rg{rg}.{c} not direct-eligible: {why}")
                plans[c].append(plan_chunk(meta, rg, ci, raw_read,
                                           allow_nulls=allow_nulls))
    return plans


def _stream_spans(scanner, ds, fh, spans, physical_type):
    """spans → one device array (on-device concat + bitcast).

    Spans larger than the engine's staging-buffer size are split into
    chunk-sized sub-ranges first (writers like parquet-mr can emit pages
    bigger than chunk_bytes; the on-device concat makes the split
    invisible)."""
    import jax.numpy as jnp
    import numpy as np
    from nvme_strom_tpu.ops.bridge import split_ranges
    ranges, _ = split_ranges(spans, scanner.engine.config.chunk_bytes)
    parts = list(ds.stream_ranges(fh, ranges))
    if not parts:    # zero-row chunk: no spans to stream
        return jnp.zeros((0,), dtype=np.dtype(_NP_DTYPES[physical_type]))
    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return flat.view(np.dtype(_NP_DTYPES[physical_type]))


def _stream_raw_groups(scanner, ds, fh, spans):
    """spans → one uint8 device array PER SPAN, all spans streamed as a
    single pipelined range sequence (sub-chunk split like
    :func:`_stream_spans`, but span boundaries preserved — BSS pages
    decode per page)."""
    import jax.numpy as jnp
    import numpy as np
    from nvme_strom_tpu.ops.bridge import split_ranges
    flat, counts = split_ranges(spans, scanner.engine.config.chunk_bytes)
    it = ds.stream_ranges(fh, flat)
    outs = []
    for n in counts:
        group = [next(it) for _ in range(n)]
        if not group:            # zero-length span (0-value page)
            outs.append(jnp.zeros((0,), dtype=np.uint8))
        else:
            outs.append(group[0] if n == 1 else jnp.concatenate(group))
    return outs


def _index_from_body(body, count: int):
    """Dict index stream after levels: ``<bit_width byte><RLE runs>`` —
    the one decode rule every compressed-body consumer shares."""
    bw = body[0]
    if bw > 32:
        raise ValueError(f"bit width {bw} > 32")
    return decode_rle_hybrid(bytes(body[1:]), bw, count)


def _decode_one_index_stream(eng, fh, p: PagePart, dev):
    """One dict-kind PagePart → int32 host index array, handling raw
    spans (bit_width known at plan time) and compressed bodies
    (decompress, parse the v1 inline level block, read bit_width from
    the body).  Nulls are rejected — callers on this path planned the
    chunk null-free (masked dict parts go through
    :func:`_decode_special_part`)."""
    buf = _read_span_bytes(eng, fh, *p.span)
    if p.codec is None:
        return decode_rle_hybrid(buf, p.bit_width, p.valid_count)
    body = _decompress(p.codec, buf, p.uncompressed_len)
    if dev.platform != "cpu":
        eng.stats.add(bounce_bytes=p.uncompressed_len)
    n_valid = p.valid_count
    if p.inline_levels:
        body, mask, n_valid = _inline_levels(body, p)
        if mask is not None:
            raise ValueError(
                "unexpected nulls in a chunk planned null-free")
    return _index_from_body(body, n_valid)


def _indices_to_device(eng, fh, parts, dict_count: int, dev):
    """Dict-kind PageParts → one validated int32 DEVICE index array.

    Prefers the on-device bit-unpack (ops/bitunpack.py — round-2
    verdict #5): the host parses only run headers, bit-packed bytes
    unpack with shifts/masks on the VPU, RLE runs are ``jnp.full`` —
    no expanded index array ever exists host-side, so the only
    payload-class host traffic is the engine read of the raw stream.
    Each span is read ONCE: pages the device path declines
    (pathological run counts, bw > 24) host-decode from the same
    buffer; compressed bodies go through
    :func:`_decode_one_index_stream`.  Host-expanded arrays keep the
    module's accounting policy (bounce on non-CPU; the CPU device_put
    alias copy counts it there).  The range check (corrupt-stream
    honesty — ``jnp.take`` would silently clip into wrong rows) costs
    one scalar sync per chunk."""
    import jax.numpy as jnp
    import numpy as np
    from nvme_strom_tpu.ops.bitunpack import rle_hybrid_batch_to_device
    from nvme_strom_tpu.ops.bridge import host_to_device

    def put_host_idx(idx):
        if dev.platform != "cpu":
            eng.stats.add(bounce_bytes=int(idx.nbytes))
        return host_to_device(eng, idx, dev)

    dev_parts = []
    raw_batch = []     # consecutive raw pages decode as ONE program

    def flush_raw():
        # three device ops for the whole run of adjacent raw pages,
        # instead of puts per run — a chunk that mixes raw and
        # compressed pages still batches each raw stretch
        if not raw_batch:
            return
        d = rle_hybrid_batch_to_device(raw_batch, dev, engine=eng)
        if d is not None:
            dev_parts.append(d)
        else:              # declined: host decode the same buffers
            dev_parts.extend(put_host_idx(decode_rle_hybrid(b, bw, c))
                             for b, bw, c in raw_batch)
        raw_batch.clear()

    for p in parts:
        if p.is_raw:
            raw_batch.append((_read_span_bytes(eng, fh, *p.span),
                              p.bit_width, p.valid_count))
        else:
            flush_raw()
            dev_parts.append(put_host_idx(
                _decode_one_index_stream(eng, fh, p, dev)))
    flush_raw()
    if not dev_parts:          # zero-row chunk
        return jnp.zeros((0,), jnp.int32)
    idx = (dev_parts[0] if len(dev_parts) == 1
           else jnp.concatenate(dev_parts))
    if idx.shape[0]:
        lo, hi = np.asarray(jnp.stack([idx.min(), idx.max()]))
        if lo < 0 or hi >= dict_count:
            raise ValueError(
                f"dictionary index {lo if lo < 0 else hi} out of range "
                f"[0, {dict_count})")
    return idx


def _read_span_bytes(engine, fh, off: int, ln: int) -> bytes:
    """Direct-engine read of a small control-stream span → host bytes.

    ``engine.read`` counts the staging→host copy as bounce — same rule
    as the pyarrow handoff (`parquet.EngineFile.readinto`): payload-class
    bytes a host decoder must touch.  Index streams are the small side of
    a dictionary chunk (≤ ~bit_width/8 bytes per value vs the full value
    width for the gathered output, which never exists host-side).
    """
    eng_chunk = engine.config.chunk_bytes
    parts = [engine.read(fh, pos, min(eng_chunk, off + ln - pos)).tobytes()
             for pos in range(off, off + ln, eng_chunk)]
    return parts[0] if len(parts) == 1 else b"".join(parts)


def _put_control(eng, arr, dev):
    """Host-decoded control data (masks, index arrays) → device, with
    the module's accounting policy: payload-derived host-materialized
    bytes count as bounce (on CPU ``host_to_device``'s protective copy
    counts the same buffer, so only non-CPU adds it here)."""
    from nvme_strom_tpu.ops.bridge import host_to_device
    if dev.platform != "cpu":
        eng.stats.add(bounce_bytes=int(arr.nbytes))
    return host_to_device(eng, arr, dev)


def _scatter_masked(vals_dev, mask_np, eng, dev):
    """Dense non-null values → full-length page output, ON DEVICE.

    positions = cumsum(mask)-1 maps each output slot to its dense
    source index; null slots read a garbage lane and are zeroed by the
    where().  Returns (full_values, device_mask)."""
    import jax.numpy as jnp
    m = _put_control(eng, mask_np, dev)
    pos = jnp.cumsum(m) - 1
    pad = mask_np.shape[0] - vals_dev.shape[0]
    vp = jnp.pad(vals_dev, (0, pad)) if pad > 0 else vals_dev
    return jnp.where(m, vp[jnp.clip(pos, 0)], 0), m


def _inline_levels(body, p: PagePart):
    """Parse a compressed v1 page's level block from its decompressed
    body → (values_view, mask|None, n_valid).  ``<u32 len><RLE def
    levels>``; all-valid masks collapse to None (stats may have proved
    it, or the writer padded an optional column with zero nulls)."""
    import numpy as np
    (n,) = struct.unpack_from("<I", body, 0)
    if 4 + n > len(body):
        raise ValueError("level block overruns decompressed page body")
    lev = decode_rle_hybrid(bytes(body[4:4 + n]),
                            p.max_def.bit_length(), p.num_values)
    mask = np.asarray(lev == p.max_def)
    vals = body[4 + n:]
    if mask.all():
        return vals, None, p.num_values
    return vals, mask, int(mask.sum())


def _decode_special_part(scanner, ds, fh, p: PagePart, plan, dict_dev,
                         dev):
    """One non-raw page (codec and/or mask) → (device values, mask).

    Compressed bytes ride the O_DIRECT engine, decompress on host
    (counted — see module docstring), and decode on device; raw-but-
    masked pages keep the zero-copy value stream and only the mask is
    host-decoded.  Returns full-page-length values when masked."""
    import numpy as np
    import jax.numpy as jnp
    eng = scanner.engine
    width = _WIDTHS[plan.physical_type]
    np_dtype = np.dtype(_NP_DTYPES[plan.physical_type])
    mask, n_valid = p.mask, p.valid_count

    if p.codec is not None:
        raw = _read_span_bytes(eng, fh, *p.span)
        body = _decompress(p.codec, raw, p.uncompressed_len)
        if dev.platform != "cpu":
            eng.stats.add(bounce_bytes=p.uncompressed_len)
        if p.inline_levels:
            body, mask, n_valid = _inline_levels(body, p)
        if p.kind == "dict":
            idx = _index_from_body(body, n_valid)
            _check_index_range(idx, plan.dict_count)
            vals = jnp.take(dict_dev, _put_control(eng, idx, dev))
        elif p.kind == "bss":
            u8 = _put_control(eng, np.frombuffer(body, np.uint8,
                                                 n_valid * width), dev)
            vals = (u8.reshape(width, n_valid).T.reshape(-1)
                    .view(np_dtype))
        else:
            arr = np.frombuffer(body, np_dtype, n_valid)
            from nvme_strom_tpu.ops.bridge import host_to_device
            # decompressed bytes were already counted above; the CPU
            # protective copy inside host_to_device re-counts there
            vals = host_to_device(eng, arr, dev)
    else:
        # raw values, masked: payload still streams zero-copy
        if p.kind == "dict":
            buf = _read_span_bytes(eng, fh, *p.span)
            idx = decode_rle_hybrid(buf, p.bit_width, n_valid)
            _check_index_range(idx, plan.dict_count)
            vals = jnp.take(dict_dev, _put_control(eng, idx, dev))
        elif p.kind == "bss":
            (raw,) = _stream_raw_groups(scanner, ds, fh, [p.span])
            vals = (raw.reshape(width, n_valid).T.reshape(-1)
                    .view(np_dtype))
        else:
            vals = _stream_spans(scanner, ds, fh, [p.span],
                                 plan.physical_type)
    if mask is not None:
        return _scatter_masked(vals, mask, eng, dev)
    return vals, None


def _check_index_range(idx, dict_count: int) -> None:
    if idx.size:
        lo, hi = int(idx.min()), int(idx.max())
        if lo < 0 or hi >= dict_count:
            raise ValueError(
                f"dictionary index {lo if lo < 0 else hi} out of range "
                f"[0, {dict_count})")


def _raw_dict_only(plans: Sequence[ColumnPlan]) -> bool:
    """Every row group a raw (uncompressed, null-free) dictionary-
    encoded chunk with a raw PLAIN dictionary page — the shape the
    whole-column batched path handles."""
    return all(
        plan.parts and plan.dict_span is not None
        and plan.dict_codec is None
        and all(p.kind == "dict" and p.is_raw for p in plan.parts)
        for plan in plans)


@functools.lru_cache(maxsize=1)
def _dict_combine_fn():
    """Jitted whole-column dict materialization: (concatenated dicts,
    concatenated per-chunk indices, per-chunk dict bases/sizes,
    per-chunk row counts) → (values, any-index-out-of-range).

    ONE program per (shape set): the per-chunk dictionary-base offset
    and the validity bound broadcast to rows via ``jnp.repeat`` with a
    static total, the gather reads the big dictionary once, and the
    range check collapses to a single boolean — so the whole column
    costs one decode + one combine + ONE host sync, independent of row
    group count."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def combine(big_dict, idx, bases, counts, rows_per_chunk):
        n = idx.shape[0]
        off = jnp.repeat(bases, rows_per_chunk, total_repeat_length=n)
        cnt = jnp.repeat(counts, rows_per_chunk, total_repeat_length=n)
        bad = ((idx < 0) | (idx >= cnt)).any()
        return jnp.take(big_dict, idx + off), bad

    return combine


def _read_dict_column_batched(scanner, ds, fh,
                              plans: Sequence[ColumnPlan], dev):
    """ALL row groups of a raw dictionary-encoded column as one device
    program set.  When the batched device decode declines, the SAME
    already-read buffers host-expand (counted as bounce, read once)
    and feed the identical combine — the per-chunk `_assemble_chunk`
    walk remains only as the caller's safety net.

    The per-chunk path costs, PER ROW GROUP: a dictionary put, a
    3-op batched index decode, a gather, and a BLOCKING min/max
    range-check sync — so a scan's time goes to per-row-group
    dispatches and syncs (the same dispatch-window disease config 5's
    ``sql_window_bytes`` lever fixed for the groupby scan).  Here the
    whole column is: one
    pipelined stream of every chunk's dictionary page (device concat),
    ONE batched RLE/bit-packed decode across every chunk's index runs,
    and one jitted combine that adds each chunk's dictionary base
    offset, range-checks, and gathers — one sync per COLUMN, not per
    row group."""
    import jax.numpy as jnp
    import numpy as np
    from nvme_strom_tpu.ops.bitunpack import rle_hybrid_batch_to_device

    eng = scanner.engine
    raw_parts = []
    rows_per_chunk = []
    for plan in plans:
        raw_parts.extend(
            (_read_span_bytes(eng, fh, *p.span), p.bit_width,
             p.valid_count) for p in plan.parts)
        rows_per_chunk.append(sum(p.valid_count for p in plan.parts))
    idx = rle_hybrid_batch_to_device(raw_parts, dev, engine=eng)
    if idx is None:
        # whole-batch decode declined (one bw>24 part, the int32
        # bit-offset cap on the concatenated stream, or the shared
        # segment budget — all scale with COLUMN size once batched):
        # retry per CHUNK with the same already-read buffers.  Each
        # chunk gets a fresh budget and its own device decode, and
        # only chunks that individually decline host-expand — the
        # per-chunk walk's behavior, minus the re-read (returning None
        # to the caller would re-read every index stream and double
        # the bounce claim suite_13 exists to verify).
        pieces, base = [], 0
        for plan in plans:
            chunk_parts = raw_parts[base:base + len(plan.parts)]
            base += len(plan.parts)
            d = rle_hybrid_batch_to_device(chunk_parts, dev, engine=eng)
            if d is None:
                host = [decode_rle_hybrid(b, bw, c)
                        for b, bw, c in chunk_parts]
                d = _put_control(
                    eng,
                    host[0] if len(host) == 1 else np.concatenate(host),
                    dev)
            pieces.append(d)
        idx = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
    # every chunk's dictionary values in one pipelined stream (device
    # concat inside _stream_spans); per-chunk bases index into it
    big_dict = _stream_spans(scanner, ds, fh,
                             [plan.dict_span for plan in plans],
                             plans[0].physical_type)
    counts = np.fromiter((plan.dict_count for plan in plans), np.int64)
    bases = np.zeros_like(counts)
    np.cumsum(counts[:-1], out=bases[1:])
    vals, bad = _dict_combine_fn()(
        big_dict, idx, jnp.asarray(bases, jnp.int32),
        jnp.asarray(counts, jnp.int32),
        jnp.asarray(np.asarray(rows_per_chunk, np.int64), jnp.int32))
    if bool(bad):              # the column's ONE host sync
        raise ValueError(
            f"dictionary index out of range (column of "
            f"{len(plans)} row groups)")
    return vals


def _assemble_chunk(scanner, ds, fh, plan: ColumnPlan, dev):
    """One column chunk → (device array, device mask | None), pages
    assembled in order.

    Raw plain pages stream O_DIRECT→device and bitcast there.  Raw
    dict-encoded pages: the dictionary's PLAIN values stream the same
    zero-copy path, index streams are host-expanded
    (:func:`decode_rle_hybrid`) and the decode is an on-device ``take``
    — values never materialize on host; adjacent dict pages share one
    gather.  Compressed and/or null-masked pages go through
    :func:`_decode_special_part` (host decompress / mask scatter).  The
    mask is None when every value in the chunk is present.
    """
    import jax.numpy as jnp
    import numpy as np
    from nvme_strom_tpu.ops.bridge import host_to_device

    eng = scanner.engine
    dict_dev = None
    if any(p.kind == "dict" for p in plan.parts):
        if plan.dict_codec is not None:
            raw = _read_span_bytes(eng, fh, *plan.dict_span)
            body = _decompress(plan.dict_codec, raw,
                               plan.dict_uncompressed_len)
            if dev.platform != "cpu":
                eng.stats.add(bounce_bytes=plan.dict_uncompressed_len)
            arr = np.frombuffer(body,
                                np.dtype(_NP_DTYPES[plan.physical_type]),
                                plan.dict_count)
            dict_dev = host_to_device(eng, arr, dev)
        else:
            dict_dev = _stream_spans(scanner, ds, fh, [plan.dict_span],
                                     plan.physical_type)
    segs = []            # (device array, mask | None) in page order
    pending_dict = []    # adjacent RAW dict pages' index-stream parts
    pending_plain = []   # value spans of adjacent RAW plain pages
    pending_bss = []     # value spans of adjacent RAW bss pages

    def flush_dict():
        if pending_dict:
            idx = _indices_to_device(eng, fh, pending_dict,
                                     plan.dict_count, dev)
            segs.append((jnp.take(dict_dev, idx), None))
            pending_dict.clear()

    def flush_plain():
        if pending_plain:
            # one pipelined stream over the adjacent spans — per-page
            # calls would collapse the queue to depth 1
            segs.append((_stream_spans(scanner, ds, fh,
                                       list(pending_plain),
                                       plan.physical_type), None))
            pending_plain.clear()

    def flush_bss():
        if pending_bss:
            width = _WIDTHS[plan.physical_type]
            np_dtype = np.dtype(_NP_DTYPES[plan.physical_type])
            for raw in _stream_raw_groups(scanner, ds, fh,
                                          list(pending_bss)):
                # BYTE_STREAM_SPLIT: page bytes are transposed
                # (width, n) — undo ON DEVICE, then bitcast
                n = raw.shape[0] // width
                segs.append((raw.reshape(width, n).T.reshape(-1)
                             .view(np_dtype), None))
            pending_bss.clear()

    def flush_all():
        flush_dict()
        flush_plain()
        flush_bss()

    flushes = {"plain": (flush_dict, flush_bss),
               "dict": (flush_plain, flush_bss),
               "bss": (flush_dict, flush_plain)}
    for p in plan.parts:
        if not p.is_raw:
            flush_all()          # page order is the output order
            segs.append(_decode_special_part(scanner, ds, fh, p, plan,
                                             dict_dev, dev))
            continue
        for fl in flushes[p.kind]:   # close the other kinds' runs
            fl()
        if p.kind == "plain":
            pending_plain.append(p.span)
        elif p.kind == "bss":
            pending_bss.append(p.span)
        else:
            pending_dict.append(p)
    flush_all()
    np_dtype = np.dtype(_NP_DTYPES[plan.physical_type])
    if not segs:     # zero-row chunk
        return jnp.zeros((0,), dtype=np_dtype), None
    vals = (segs[0][0] if len(segs) == 1
            else jnp.concatenate([s[0] for s in segs]))
    if all(m is None for _, m in segs):
        return vals, None
    mask = jnp.concatenate([
        m if m is not None else jnp.ones((a.shape[0],), bool)
        for a, m in segs])
    return vals, mask


def _plain_only(plans: Sequence[ColumnPlan]) -> bool:
    return all(p.kind == "plain" and p.is_raw
               for plan in plans for p in plan.parts)


def try_plan(scanner, columns: Sequence[str], allow_nulls: bool = False):
    """plan_columns, or None when the scanner/file isn't direct-eligible
    — THE fallback rule, shared by every consumer that degrades to the
    pyarrow path (groupby's iter_device_columns, topk) so the two can
    never diverge on the same scanner."""
    if not hasattr(scanner, "direct_reasons"):
        return None
    try:
        return plan_columns(scanner, columns, allow_nulls=allow_nulls)
    except ValueError:
        return None


def _compressed_plain_only(plans: Sequence[ColumnPlan]) -> bool:
    """Every page a codec-tagged null-free PLAIN body — the shape a
    zstd/snappy analytics table presents."""
    return all(p.kind == "plain" and p.codec is not None
               and p.mask is None
               for plan in plans for p in plan.parts)


#: phase breakdown of the most recent _read_compressed_plain_pipelined
#: call — read_stall (blocked in engine waits), decompress, device put —
#: so the bench row can ATTRIBUTE a compressed scan instead of shipping
#: one opaque number (round-3 verdict #5)
LAST_COMPRESSED_PHASES: Dict[str, float] = {}


def _iter_span_bytes_pipelined(eng, fh, spans, stall_box):
    """Yield ``bytes`` per span with the engine queue kept full ACROSS
    spans: sub-chunk splits of every span are submitted ahead (up to
    the configured queue depth) while earlier spans decompress on the
    host.  Reading each page span with a blocking ``engine.read``
    would cost one stop-and-wait round trip per page.
    ``stall_box[0]`` accumulates the time
    actually blocked in waits — the read-stall phase of the breakdown."""
    from collections import deque
    from nvme_strom_tpu.ops.bridge import split_ranges
    flat, n_chunks = split_ranges(spans, eng.config.chunk_bytes)
    span_of = [i for i, n in enumerate(n_chunks) for _ in range(n)]
    pend = deque()                  # (span_idx, PendingRead)
    parts: Dict[int, list] = {}
    emit_next = 0

    def drain_one():
        i, pr = pend.popleft()
        t0 = time.monotonic()
        view = pr.wait()
        stall_box[0] += time.monotonic() - t0
        b = bytes(view)             # copy out of recycled staging
        eng.stats.add(bounce_bytes=len(b))   # host-touched payload,
        pr.release()                         # same rule as engine.read
        parts.setdefault(i, []).append(b)

    try:
        for si, (off, n) in zip(span_of, flat):
            pend.append((si, eng.submit_read(fh, off, n,
                                             klass=SCAN_CLASS)))
            while len(pend) > eng.config.queue_depth:
                drain_one()
            # FIFO completion: span k's chunks all land before k+1's
            while (emit_next < len(spans)
                   and len(parts.get(emit_next, ())) ==
                   n_chunks[emit_next]):
                chunks = parts.pop(emit_next, [])
                yield (chunks[0] if len(chunks) == 1
                       else b"".join(chunks))
                emit_next += 1
        while pend:
            drain_one()
        while emit_next < len(spans):
            chunks = parts.pop(emit_next, [])
            yield (chunks[0] if len(chunks) == 1 else b"".join(chunks))
            emit_next += 1
    finally:
        for _, pr in pend:
            try:
                pr.wait()
            except OSError:
                pass
            pr.release()


def _read_compressed_plain_pipelined(scanner, fh, columns, plans, dev):
    """All-compressed-PLAIN scan: pipelined O_DIRECT page reads, host
    decompression overlapped with the in-flight reads, and one bulk
    device transfer per (column, row group).

    Contrast with the page-at-a-time path (`_decode_special_part`):
    that pays a blocking engine read AND a small ``device_put`` per
    page — ~2 round trips x pages, which on a high-latency link
    dominates everything (the 0.24x-of-pyarrow ledger rows).  Here the
    engine queue stays full across pages and the link sees a few
    column-sized transfers — the same shape pyarrow's fallback enjoys,
    so the comparison becomes an honest read+decode race."""
    import jax.numpy as jnp
    import numpy as np
    from nvme_strom_tpu.ops.bridge import host_to_device

    eng = scanner.engine
    # (column, row-group ordinal, part): host memory is bounded at one
    # row group's decompressed pages — each (c, rg)'s bodies join and
    # ship to device the moment its last page lands, so a table larger
    # than host RAM still scans (the whole-table join this replaced
    # peaked at ~2x decompressed size)
    work = [(c, gi, p) for c in columns
            for gi, plan in enumerate(plans[c])
            for p in plan.parts]
    widths = {c: _WIDTHS[plans[c][0].physical_type] for c in columns}
    stall = [0.0]
    t_decomp = 0.0
    t_put = 0.0
    comp_bytes = 0
    decomp_bytes = 0
    dev_parts: Dict[str, list] = {c: [] for c in columns}
    group_bodies: list = []

    def flush_group(c):
        nonlocal t_put, decomp_bytes
        if not group_bodies:
            return
        joined = (group_bodies[0] if len(group_bodies) == 1
                  else b"".join(group_bodies))
        group_bodies.clear()
        arr = np.frombuffer(joined, np.dtype(_NP_DTYPES[
            plans[c][0].physical_type]))
        decomp_bytes += arr.nbytes
        t0 = time.monotonic()
        dev_parts[c].append(host_to_device(eng, arr, dev))
        t_put += time.monotonic() - t0

    it = _iter_span_bytes_pipelined(eng, fh,
                                    [p.span for _, _, p in work], stall)
    prev = None                     # (column, row-group) being filled
    for (c, gi, p), raw in zip(work, it):
        if prev is not None and prev != (c, gi):
            flush_group(prev[0])
        prev = (c, gi)
        comp_bytes += len(raw)
        t0 = time.monotonic()
        body = _decompress(p.codec, raw, p.uncompressed_len)
        t_decomp += time.monotonic() - t0
        if dev.platform != "cpu":
            eng.stats.add(bounce_bytes=p.uncompressed_len)
        n_valid = p.valid_count
        if p.inline_levels:
            body, mask, n_valid = _inline_levels(body, p)
            if mask is not None:
                raise ValueError(
                    "unexpected nulls in a chunk planned null-free")
        group_bodies.append(bytes(body[:n_valid * widths[c]]))
    if prev is not None:
        flush_group(prev[0])
    out = {}
    for c in columns:
        parts = dev_parts[c]
        if not parts:
            out[c] = jnp.zeros((0,), dtype=np.dtype(_NP_DTYPES[
                plans[c][0].physical_type]))
        else:
            out[c] = (parts[0] if len(parts) == 1
                      else jnp.concatenate(parts))
    LAST_COMPRESSED_PHASES.clear()
    LAST_COMPRESSED_PHASES.update(
        read_stall_s=round(stall[0], 4), decomp_s=round(t_decomp, 4),
        put_s=round(t_put, 4), compressed_bytes=comp_bytes,
        decompressed_bytes=decomp_bytes, pages=len(work))
    return out


def _join_chunks(chunks, nulls: str, column: str):
    """[(values, mask|None)] per row group → column output per the
    ``nulls`` policy: "forbid" raises on any real mask (statistics lied
    or the caller forgot to opt in), "mask" returns (values, mask) with
    all-valid chunks contributing ones."""
    import jax.numpy as jnp
    vals = (chunks[0][0] if len(chunks) == 1
            else jnp.concatenate([c[0] for c in chunks]))
    if nulls == "forbid":
        if any(m is not None for _, m in chunks):
            raise ValueError(
                f"column {column!r} has nulls; pass nulls='mask'")
        return vals
    mask = (jnp.ones((vals.shape[0],), bool)
            if all(m is None for _, m in chunks)
            else jnp.concatenate([
                m if m is not None else jnp.ones((a.shape[0],), bool)
                for a, m in chunks]))
    return vals, mask


def read_plain_columns_to_device(scanner, columns: Sequence[str],
                                 device=None, plans=None,
                                 nulls: str = "forbid"
                                 ) -> Dict[str, "object"]:
    """Direct scan of the whole file: {name: device array}, row groups
    concatenated ON DEVICE.  Payload bytes (PLAIN values and dictionary
    values) ride O_DIRECT → staging → device; the host reads only
    headers, dict index streams, level blocks, and — for compressed
    chunks — the page bodies it must decompress (counted as bounce).
    ``plans`` lets callers reuse a prior :func:`plan_columns` walk.

    ``nulls``: "forbid" (default) raises if any chunk holds nulls;
    "mask" returns ``(values, valid_mask)`` pairs — null slots are
    zero-filled, the mask is the truth."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from nvme_strom_tpu.ops.bridge import DeviceStream

    if nulls not in ("forbid", "mask"):
        raise ValueError(f"bad nulls={nulls!r}")
    dev = device or jax.local_devices()[0]
    plans = plans or plan_columns(scanner, columns,
                                  allow_nulls=nulls == "mask")
    ds = DeviceStream(scanner.engine, device=dev,
                      depth=max(2, scanner.engine.config.queue_depth),
                      klass=SCAN_CLASS, drain="ready")
    out = {}
    meta = scanner.metadata
    name_to_ci = {meta.schema.column(i).name: i
                  for i in range(meta.num_columns)}
    fh = scanner.engine.open(scanner.path)
    try:
        if (nulls == "forbid" and columns
                and all(plans[c] and _plain_only(plans[c])
                        for c in columns)):
            # the whole read is ONE pipelined range sequence across
            # every (row group, column) chunk — no boundary drains
            # (same rationale as iter_plain_row_groups_to_device)
            per_col = {c: [] for c in columns}
            for rg_out in _iter_plain_pipelined(
                    scanner, ds, fh, columns, plans,
                    range(meta.num_row_groups)):
                for c, v in rg_out.items():
                    per_col[c].append(v)
            return {c: (parts[0] if len(parts) == 1
                        else jnp.concatenate(parts))
                    for c, parts in per_col.items()}
        if (nulls == "forbid" and columns
                and all(plans[c] and _compressed_plain_only(plans[c])
                        for c in columns)):
            return _read_compressed_plain_pipelined(scanner, fh,
                                                    columns, plans, dev)
        for c in columns:
            if not plans[c]:   # zero row groups: empty typed column
                pt = meta.schema.column(name_to_ci[c]).physical_type
                empty = jnp.zeros((0,), dtype=np.dtype(_NP_DTYPES[pt]))
                out[c] = (empty if nulls == "forbid"
                          else (empty, jnp.zeros((0,), bool)))
            elif _plain_only(plans[c]) and nulls == "forbid":
                # one pipelined stream across every row group's spans
                out[c] = _stream_spans(
                    scanner, ds, fh,
                    (s for p in plans[c] for s in p.spans),
                    plans[c][0].physical_type)
            else:
                v = None
                if nulls == "forbid" and _raw_dict_only(plans[c]):
                    # whole-column batched dict path: one decode + one
                    # combine + one sync for ALL row groups.  It always
                    # returns the column (a declined device decode is
                    # retried per-chunk and then host-expanded INSIDE),
                    # so the per-chunk walk below runs only for columns
                    # that failed the _raw_dict_only gate above.
                    v = _read_dict_column_batched(scanner, ds, fh,
                                                  plans[c], dev)
                if v is None:
                    chunks = [_assemble_chunk(scanner, ds, fh, plan,
                                              dev)
                              for plan in plans[c]]
                    v = _join_chunks(chunks, nulls, c)
                out[c] = v
    finally:
        scanner.engine.close(fh)
    return out


# ---------------------------------------------------------------------------
# dictionary-code scans of BYTE_ARRAY (string) columns
#
# PG-Strom's trick for GROUP BY over strings: never materialize the
# strings on the accelerator — group by the dictionary CODE (an int32)
# and map codes back to labels on the host, where the dictionary page
# (tiny, one per chunk) already lives.  Payload economics: the device
# sees 4 bytes per row regardless of string length.


@dataclass(frozen=True)
class DictCodeChunk:
    """One chunk of a dictionary-coded BYTE_ARRAY column."""
    parts: Tuple[PagePart, ...]            # all kind "dict"
    num_values: int
    dict_span: Tuple[int, int]             # raw dictionary page body
    dict_count: int
    dict_codec: Optional[str] = None
    dict_uncompressed_len: int = 0


def dict_code_eligible(meta, rg: int, ci: int) -> Optional[str]:
    """None if the chunk can scan as dictionary codes, else the reason.

    A footer-level check only — a chunk whose writer overflowed to
    PLAIN BYTE_ARRAY data pages (undetectable from the footer) fails
    later in :func:`plan_dict_code_chunk`."""
    col = meta.row_group(rg).column(ci)
    sc = meta.schema.column(ci)
    if col.physical_type != "BYTE_ARRAY":
        return f"physical type {col.physical_type} (need BYTE_ARRAY)"
    codec = _codec_of(col)
    if codec is not None and not _codec_available(codec):
        return f"compression {col.compression}"
    encs = set(col.encodings)
    if not encs <= {"PLAIN", "RLE", "PLAIN_DICTIONARY", "RLE_DICTIONARY"}:
        return f"encodings {sorted(encs)}"
    if (col.dictionary_page_offset or 0) <= 0:
        return "no dictionary page"
    if sc.max_repetition_level != 0:
        return "repeated field"
    if sc.max_definition_level > 0:
        st = col.statistics
        if st is None or st.null_count is None:
            return "no null statistics"
        if st.null_count != 0:
            return f"{st.null_count} nulls"
    return None


def plan_dict_code_chunk(meta, rg: int, ci: int, raw_read) -> DictCodeChunk:
    """Page-walk a BYTE_ARRAY chunk: dictionary page body span + index
    stream spans (codec-tagged when the chunk is compressed).  Raises
    ValueError on any PLAIN data page (dictionary overflow) — string
    bytes cannot decode on device."""
    col = meta.row_group(rg).column(ci)
    sc = meta.schema.column(ci)
    max_def = sc.max_definition_level
    codec = _codec_of(col)
    parts: List[PagePart] = []
    dict_span = None
    dict_count = 0
    dict_codec: Optional[str] = None
    dict_ulen = 0
    for pos, ph in _walk_pages(col, raw_read):
        if ph.type in (_PAGE_DATA, _PAGE_DATA_V2):
            if ph.encoding not in _DICT_ENCODINGS:
                raise ValueError(
                    f"page at {pos}: encoding {ph.encoding} — string "
                    f"chunk fell back from dictionary (overflow?)")
            if dict_span is None:
                raise ValueError(
                    f"page at {pos}: dict-encoded data page before "
                    f"any dictionary page")
            v2 = ph.type == _PAGE_DATA_V2
            page_codec = codec
            if v2 and not ph.v2_is_compressed:
                page_codec = None
            if page_codec is not None and not v2:
                # v1: levels+values in one compressed body
                parts.append(PagePart(
                    "dict", (pos + ph.header_len, ph.compressed_size),
                    ph.num_values, bit_width=-1, codec=page_codec,
                    uncompressed_len=ph.uncompressed_size,
                    inline_levels=max_def > 0, max_def=max_def))
                continue
            # eligibility proved the chunk null-free → no masks
            lb, _ = _plan_levels(pos, ph, max_def, raw_read, False)
            if page_codec is not None:      # compressed v2 values
                parts.append(PagePart(
                    "dict",
                    (pos + ph.header_len + lb, ph.compressed_size - lb),
                    ph.num_values, bit_width=-1, codec=page_codec,
                    uncompressed_len=ph.uncompressed_size - lb,
                    max_def=max_def))
            else:
                parts.append(_index_stream_part(pos, ph, lb, raw_read,
                                                max_def=max_def))
        elif ph.type == _PAGE_DICTIONARY:
            _check_dict_page(pos, ph, dict_span is not None)
            # var-len strings: the span is the whole page body; entry
            # lengths are parsed from it host-side
            dict_span = (pos + ph.header_len, ph.compressed_size)
            dict_count = ph.num_values
            if codec is not None:
                dict_codec = codec
                dict_ulen = ph.uncompressed_size
    if dict_span is None:
        raise ValueError(f"rg{rg} col{ci}: no dictionary page")
    return DictCodeChunk(tuple(parts), col.num_values, dict_span,
                         dict_count, dict_codec=dict_codec,
                         dict_uncompressed_len=dict_ulen)


def parse_byte_array_dict(buf: bytes, count: int) -> List[bytes]:
    """PLAIN BYTE_ARRAY dictionary page body → label list
    (``<u32 len><bytes>`` repeated ``count`` times)."""
    out: List[bytes] = []
    pos = 0
    for _ in range(count):
        if pos + 4 > len(buf):
            raise ValueError("truncated dictionary page (length prefix)")
        (n,) = struct.unpack_from("<I", buf, pos)
        pos += 4
        if pos + n > len(buf):
            raise ValueError("truncated dictionary page (entry bytes)")
        out.append(bytes(buf[pos:pos + n]))
        pos += n
    return out


def read_dict_key_column(scanner, column: str, device=None,
                         row_groups=None):
    """Prepare a BYTE_ARRAY column for on-device GROUP BY by code.

    Returns ``(labels, iter_codes)``: ``labels`` is the GLOBAL label
    list (union of EVERY row group's dictionary, first-seen order;
    bytes objects — stable across pruned and unpruned queries),
    ``iter_codes()`` yields one int32 device array of global codes per
    row group in ``row_groups`` (default: all).

    Two-pass: dictionary pages are read first (through the engine,
    host-touched by design → counted as bounce) so the global label
    space is known before any data streams — per-row-group dictionaries
    are remapped to global codes ON DEVICE via a gather.
    """
    import jax
    from nvme_strom_tpu.ops.bridge import host_to_device

    meta = scanner.metadata
    name_to_ci = {meta.schema.column(i).name: i
                  for i in range(meta.num_columns)}
    if column not in name_to_ci:
        raise KeyError(f"column {column!r} not in schema")
    ci = name_to_ci[column]
    import os
    with open(scanner.path, "rb") as f:
        def raw_read(off: int, ln: int) -> bytes:
            return os.pread(f.fileno(), ln, off)

        chunks = []
        for rg in range(meta.num_row_groups):
            why = dict_code_eligible(meta, rg, ci)
            if why is not None:
                raise ValueError(
                    f"rg{rg}.{column} not dict-code-eligible: {why}")
            chunks.append(plan_dict_code_chunk(meta, rg, ci, raw_read))

    dev = device or jax.local_devices()[0]
    eng = scanner.engine
    labels: List[bytes] = []
    gid: Dict[bytes, int] = {}
    remaps: List["object"] = []       # per-rg int32 device remap arrays
    import numpy as np
    fh = eng.open(scanner.path)
    try:
        for ch in chunks:
            body = _read_span_bytes(eng, fh, *ch.dict_span)
            if ch.dict_codec is not None:
                body = _decompress(ch.dict_codec, body,
                                   ch.dict_uncompressed_len)
                eng.stats.add(bounce_bytes=ch.dict_uncompressed_len)
            local = parse_byte_array_dict(body, ch.dict_count)
            remap = np.empty(max(ch.dict_count, 1), np.int32)
            for i, lab in enumerate(local):
                if lab not in gid:
                    gid[lab] = len(labels)
                    labels.append(lab)
                remap[i] = gid[lab]
            remaps.append(host_to_device(eng, remap, dev))
    finally:
        eng.close(fh)

    selected = (range(len(chunks)) if row_groups is None
                else list(row_groups))

    def iter_codes():
        import jax.numpy as jnp
        fh = eng.open(scanner.path)
        try:
            for rg in selected:
                ch, remap_dev = chunks[rg], remaps[rg]
                idx = _indices_to_device(eng, fh, ch.parts,
                                         ch.dict_count, dev)
                # local code → global code, on device
                yield jnp.take(remap_dev, idx)
        finally:
            eng.close(fh)

    return labels, iter_codes


def iter_plain_row_groups_to_device(scanner, columns: Sequence[str],
                                    device=None, plans=None,
                                    row_groups=None,
                                    nulls: str = "forbid",
                                    window_bytes: int | None = None):
    """Yield {name: device array} per (selected) row group — the
    incremental form sql_groupby folds over, so device memory holds one
    row group of columns at a time regardless of table size.  ``plans``
    lets callers reuse a prior :func:`plan_columns` walk;
    ``row_groups`` restricts to a pruned subset (statistics-based scan
    elimination — skipped chunks never leave the SSD).  ``nulls`` as in
    :func:`read_plain_columns_to_device` ("mask" yields (values, mask)
    pairs per column).

    ``window_bytes`` batches consecutive row groups into one yielded
    dict holding ~that many payload bytes (all-PLAIN ``forbid`` path
    only).  For FOLD consumers exclusively: on a high-latency link the
    per-row-group consumer ops (concat/view/fold dispatches) price the
    scan, not bandwidth — the 2026-07-31T18:04 on-silicon row ledgered
    the config-5 stream at 0.186 GiB/s under a 1.35 GiB/s link, ~20 ms
    per dispatch across ~70 of them.  Windowing divides the dispatch
    count by the window's group count.  Default None = one yield per
    row group — POSITIONAL consumers (topk zips yields against row-
    group ids; LIMIT scans early-exit per group) must keep that.

    When every selected chunk is raw-PLAIN (the common analytics case),
    the WHOLE scan is one pipelined range sequence — row-group
    boundaries are just chunk counts on the consumer side.  The per-
    row-group form (one drained ``stream_ranges`` call per column per
    group) collapsed the engine queue at every boundary: each drain is
    a ``block_until_ready`` round-trip with the device link idle, and a
    64-group × 2-column scan paid ~128 of them."""
    import jax
    from nvme_strom_tpu.ops.bridge import DeviceStream

    if nulls not in ("forbid", "mask"):
        raise ValueError(f"bad nulls={nulls!r}")
    dev = device or jax.local_devices()[0]
    plans = plans or plan_columns(scanner, columns,
                                  allow_nulls=nulls == "mask")
    ds = DeviceStream(scanner.engine, device=dev,
                      depth=max(2, scanner.engine.config.queue_depth),
                      klass=SCAN_CLASS, drain="ready")
    fh = scanner.engine.open(scanner.path)
    try:
        groups = (range(scanner.metadata.num_row_groups)
                  if row_groups is None else row_groups)
        groups = list(groups)
        if nulls == "forbid" and all(
                _plain_only([plans[c][rg]])
                for rg in groups for c in columns):
            yield from _iter_plain_pipelined(scanner, ds, fh, columns,
                                             plans, groups,
                                             window_bytes=window_bytes)
            return
        for rg in groups:
            out = {}
            for c in columns:
                plan = plans[c][rg]
                if _plain_only([plan]) and nulls == "forbid":
                    out[c] = _stream_spans(scanner, ds, fh, plan.spans,
                                           plan.physical_type)
                else:
                    out[c] = _join_chunks(
                        [_assemble_chunk(scanner, ds, fh, plan, dev)],
                        nulls, c)
            yield out
    finally:
        scanner.engine.close(fh)


def _iter_plain_pipelined(scanner, ds, fh, columns, plans, groups,
                          window_bytes: int | None = None):
    """All-raw-PLAIN scan as ONE pipelined range sequence.

    Every (row group, column) chunk's spans are flattened into a single
    ``stream_ranges`` submission — the engine keeps ``depth`` reads in
    flight across row-group boundaries, and the only blocking wait is
    backpressure (pipe full), never a boundary drain.  The consumer
    side reassembles boundaries from chunk counts: submission order is
    yield order.  The fold's device compute overlaps the stream for
    free — JAX dispatch is async, so by the time the consumer asks for
    the next group's chunks, its aggregation is already queued behind
    the transfers.

    ``window_bytes`` (see :func:`iter_plain_row_groups_to_device`)
    coalesces consecutive row groups into one yield of ~that size, so
    each consumer-side concat/view/fold dispatch covers a window of
    payload instead of one group — the dispatch-latency lever.

    Transfer-side coalescing: PLAIN value spans are PER PAGE (~1 MiB
    each — page headers interleave them), so submitting them verbatim
    costs ~8x more device puts per byte than the north-star stream's
    8 MiB chunks; the same-minute window-7 ledger showed the scan's
    put path at 0.20 GiB/s while bench rode the identical link at
    1.15 (ratio 0.953).  When a column chunk's header gap is small,
    the ENCLOSING byte range streams as chunk-sized reads
    (header bytes ride along) and one jitted static-slice program per
    (window, column) drops the gaps ON DEVICE — one put per 8 MiB and
    ~3 device dispatches per window-column, independent of page
    count."""
    flat, counts, windows = [], [], _split_windows(columns, plans,
                                                   groups, window_bytes)
    for w in windows:
        f, cn = _plan_window_ranges(scanner, columns, plans, w)
        flat.extend(f)
        counts.extend(cn)
    it = ds.stream_ranges(fh, flat)
    ci = iter(counts)
    try:
        for w in windows:
            yield _assemble_window(columns, plans, w, ci, it)
    finally:
        it.close()                 # abandoned scan: release staging now


def _split_windows(columns, plans, groups,
                   window_bytes: int | None) -> list:
    """Row-group ids → consecutive windows of ~``window_bytes`` payload
    each (one group per window when None/0).  The ONE windowing rule
    shared by the serial pipelined scan above and the partition-parallel
    scan (sql/scan_plan.py) — identical windows are what make the
    parallel merge bit-identical to the serial stream."""
    if window_bytes:
        windows, cur, cur_b = [], [], 0
        for rg in groups:
            b = sum(ln for c in columns for _, ln in plans[c][rg].spans)
            if cur and cur_b + b > window_bytes:
                windows.append(cur)
                cur, cur_b = [], 0
            cur.append(rg)
            cur_b += b
        if cur:
            windows.append(cur)
        return windows
    return [[rg] for rg in groups]


def _plan_window_ranges(scanner, columns, plans, w):
    """One window's submission plan: ``(flat, counts)`` — every
    chunk-sized sub-range in submission order, plus the
    ``(rg, column, n_chunks, spec)`` reassembly records
    :func:`_assemble_window` consumes.  Pure function of the window:
    the serial path streams all windows' ranges as one sequence, the
    parallel path streams each worker's windows independently, and
    both assemble the same per-window buffers."""
    from nvme_strom_tpu.ops.bridge import split_ranges

    chunk_bytes = scanner.engine.config.chunk_bytes
    flat = []                      # every sub-range, submission order
    counts = []                    # (rg, column, n_chunks, spec)
    # merge decision per (window, column): the degap program holds
    # one lax.slice per value span ACROSS the window, so a
    # small-page layout (4 KiB pages → thousands of spans per
    # 64 MiB window) would compile a pathological program — cap
    # the slice count and fall back to exact per-span reads
    allow = {c: sum(len([s for s in plans[c][rg].spans if s[1]])
                    for rg in w) <= _COALESCE_MAX_SLICES
             for c in columns}
    for rg in w:
        for c in columns:
            spans = plans[c][rg].spans
            merged = _coalesce_spans(spans) if allow[c] else None
            if merged is not None:
                ranges, _ = split_ranges([merged], chunk_bytes)
                # value spans relative to the merged buffer: the
                # on-device degap spec
                spec = tuple((off - merged[0], ln)
                             for off, ln in spans if ln)
            else:
                ranges, _ = split_ranges(spans, chunk_bytes)
                spec = None
            flat.extend(ranges)
            counts.append((rg, c, len(ranges), spec))
    return flat, counts


def _assemble_window(columns, plans, w, ci, it):
    """Reassemble one window's {column: device array} dict from its
    ``counts`` records (``ci``) and streamed buffers (``it``) — the
    consumer half of :func:`_plan_window_ranges`, shared by the serial
    and parallel scans."""
    import jax.numpy as jnp
    import numpy as np

    parts: dict = {c: [] for c in columns}
    specs: dict = {c: [] for c in columns}
    merged_any = {c: False for c in columns}
    sizes = {c: 0 for c in columns}     # buffer bytes so far
    for rg in w:
        for c in columns:
            _, _, n, spec = next(ci)
            got = [next(it) for _ in range(n)]
            base = sizes[c]
            if spec is not None:
                merged_any[c] = True
                specs[c].extend((base + o, ln)
                                for o, ln in spec)
            else:
                # unmerged chunks are pure value bytes: they
                # enter the buffer verbatim, and the spec keeps
                # them in case a SIBLING row group merged
                pos = 0
                for p in got:
                    specs[c].append((base + pos,
                                     int(p.shape[0])))
                    pos += int(p.shape[0])
            parts[c].extend(got)
            sizes[c] += sum(int(p.shape[0]) for p in got)
    out = {}
    for c in columns:
        np_dtype = np.dtype(
            _NP_DTYPES[plans[c][w[0]].physical_type])
        ps = parts[c]
        if not ps:         # zero-row window
            out[c] = jnp.zeros((0,), dtype=np_dtype)
            continue
        buf = ps[0] if len(ps) == 1 else jnp.concatenate(ps)
        if merged_any[c]:
            buf = _degap(tuple(specs[c]), int(buf.shape[0]))(buf)
        out[c] = buf.view(np_dtype)
    return out


#: tolerated header/gap overhead when streaming a column chunk's
#: enclosing range: page headers are ~30-60 B per ~1 MiB page (<0.01%),
#: so anything beyond a few percent means an unexpected layout — fall
#: back to exact per-span reads rather than wasting link on holes
_COALESCE_GAP_FRAC = 0.05

#: max lax.slice ops in one window-column degap program (compile cost
#: grows with operand count; 1 MiB default pages put a 64 MiB window at
#: ~64-128 slices, comfortably under; 4 KiB-page layouts blow past and
#: take the exact per-span path instead)
_COALESCE_MAX_SLICES = 256


def _coalesce_spans(spans):
    """Enclosing (offset, length) of the span list when the interior
    gaps (page headers) are a negligible fraction — else None."""
    spans = [s for s in spans if s[1]]
    if len(spans) < 2:
        return None
    lo = spans[0][0]
    hi = spans[-1][0] + spans[-1][1]
    payload = sum(ln for _, ln in spans)
    if hi - lo - payload > _COALESCE_GAP_FRAC * payload:
        return None
    # spans must be ascending and disjoint for the relative spec to be
    # meaningful (the page walk emits them in file order)
    pos = lo
    for off, ln in spans:
        if off < pos:
            return None
        pos = off + ln
    return (lo, hi - lo)


@functools.lru_cache(maxsize=256)
def _degap(spec: tuple, total: int):
    """Jitted static-slice compaction: uint8 buffer of ``total`` bytes
    → the concatenation of the ``spec`` (offset, length) value spans.
    Page layouts repeat across row groups and windows, so the lru
    cache (plus the persistent compile cache) makes this one compile
    per distinct layout, ONE device dispatch per application."""
    import jax
    import jax.numpy as jnp

    def f(a):
        pieces = [jax.lax.slice(a, (o,), (o + ln,)) for o, ln in spec]
        return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)

    return jax.jit(f)

"""On-device RLE/bit-packed hybrid index decode (round-2 verdict #5).

The Parquet dictionary index stream is a sequence of runs: RLE runs
(``count × one value``) and bit-packed runs (``groups × 8`` values of
``bit_width`` bits, LSB-first).  Round 2 expanded the WHOLE stream on
host (``pq_direct.decode_rle_hybrid``) and counted the expanded int32
array as bounce — 4 bytes/value of host-touched payload.  But only the
run HEADERS are sequential control flow; the run bodies are not:

- an RLE run is two scalars — ``jnp.full(count, value)`` materializes
  it on DEVICE, zero host bytes;
- a bit-packed run is a fixed-width bitstream — exactly the shape the
  VPU unpacks with shifts/masks: ship the RAW bytes (bit_width/8 per
  value instead of 4) and decode there.

So the host walk shrinks to varint header parsing (~2 bytes per run),
and payload-class host traffic drops from ``4·count`` bytes to the raw
index-stream bytes the engine read anyway.

Decode shape (round-4): the WHOLE stream — all pages of a column
chunk, every run — decodes in ONE fused device program.  The host
parse emits a (5, runs) int32 table (output offset, absolute bit
offset, RLE value, bit width, kind); on device each output row finds
its run by ``searchsorted`` over the offsets, packed rows bit-extract
through a 4-byte gather window (value v of a run starts at stream bit
``bit_base + v·bw``; shift ≤ 7 plus bw ≤ 24 keeps the window
sufficient), RLE rows select the literal.  Three device ops total — a
per-run design would dispatch one put + one unpack per run.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import numpy as np

#: give up on streams with more runs than this.  Runs are pure
#: metadata rows in the batched decoder (20 bytes each), so the cap is
#: generous — it only bounds the metadata put; beyond it the stream is
#: so fragmented that host decode's bounce (the stream itself is tiny
#: per value) is the better trade.
MAX_SEGMENTS = 1 << 18

#: bit widths above this leave the device path: a packed value is read
#: through a 4-byte little-endian gather window, so shift (≤7) plus
#: bit_width must fit in 32 bits — bw 25 at shift 7 would truncate high
#: bits into silently wrong indices.  (A >16M-entry dictionary has no
#: business being gathered anyway.)
MAX_BIT_WIDTH = 24


def split_rle_hybrid(buf, bit_width: int, count: int,
                     max_segments: int = MAX_SEGMENTS
                     ) -> Optional[List[Tuple]]:
    """Parse run headers only → segment list, or None when the device
    path shouldn't be used (too many runs / oversized bit width).

    Segments: ``("rle", take, value)`` or ``("packed", start, nbytes,
    groups, take)`` with ``take`` = values this run contributes after
    discarding the final run's spec-legal padding."""
    if bit_width == 0:
        # single-entry dictionary: every index is 0, no stream to parse
        # — the device answer is one free jnp.zeros
        return [("rle", count, 0)] if count else []
    if bit_width > MAX_BIT_WIDTH:
        return None
    byte_w = (bit_width + 7) // 8
    segs: List[Tuple] = []
    pos, filled, n = 0, 0, len(buf)
    while filled < count:
        if len(segs) >= max_segments:
            return None
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
            nbytes = groups * bit_width
            if pos + nbytes > n:
                raise ValueError("truncated bit-packed run")
            take = min(groups * 8, count - filled)
            segs.append(("packed", pos, nbytes, groups, take))
            pos += nbytes
            filled += take
        else:                                # RLE run
            run = header >> 1
            if run == 0:
                raise ValueError("zero-length RLE run")
            if pos + byte_w > n:
                raise ValueError("truncated RLE run value")
            v = int.from_bytes(buf[pos:pos + byte_w], "little")
            pos += byte_w
            take = min(run, count - filled)
            segs.append(("rle", take, v))
            filled += take
    return segs


def _pow2_pad(groups: int) -> int:
    p = 1
    while p < groups:
        p *= 2
    return p


@functools.lru_cache(maxsize=1)
def _batch_decode():
    """Jitted whole-stream decode: (u8 buffer, (5, R) run table) →
    int32 indices.  ONE fused program regardless of run count.

    Row → run by ``searchsorted`` over the run table's output-offset
    row (pad entries are int32 max so they are never selected); packed
    values bit-extract with a 4-byte little-endian gather window
    (shift ≤ 7 + bit_width ≤ 24 → 31 bits, so the window always
    covers the value); RLE rows select the run's literal value.
    Retraces per (pow2 buffer, pow2 runs, pow2 rows) triple — bounded,
    and served by the persistent compile cache."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("cpad",))
    def decode(u8, meta, cpad: int):
        out_start, bit_base, val, bw, kind = meta
        i = jnp.arange(cpad, dtype=jnp.int32)
        rid = jnp.searchsorted(out_start, i, side="right") - 1
        rel = i - out_start[rid]
        rbw = bw[rid]
        bb = bit_base[rid] + rel * rbw
        byte0 = jnp.minimum(bb >> 3, u8.shape[0] - 4)
        word = (u8[byte0].astype(jnp.uint32)
                | (u8[byte0 + 1].astype(jnp.uint32) << 8)
                | (u8[byte0 + 2].astype(jnp.uint32) << 16)
                | (u8[byte0 + 3].astype(jnp.uint32) << 24))
        mask = (jnp.uint32(1) << rbw.astype(jnp.uint32)) - jnp.uint32(1)
        pv = ((word >> (bb & 7).astype(jnp.uint32)) & mask)
        return jnp.where(kind[rid] == 1, pv.astype(jnp.int32), val[rid])

    return decode


def rle_hybrid_batch_to_device(parts, dev, engine=None
                               ) -> Optional["object"]:
    """``[(buf, bit_width, count), ...]`` (page order) → ONE int32
    device array of the concatenated decoded indices, or None → caller
    host-decodes.

    Exactly three device ops regardless of run count: one put of the
    concatenated raw streams (pow2(+4 window slack) padded), one put
    of the (5, Rpad) int32 run table, one fused decode program.  A
    per-run design would dispatch one put + one unpack PER RUN —
    16,784 device puts per scan pass for a 256 MiB dictionary
    column.  Host work is unchanged in kind: varint
    header parsing only; no expanded index array ever exists host-side.
    """
    import jax.numpy as jnp
    from nvme_strom_tpu.ops.bridge import host_to_device

    rows = []            # (out_start, bit_base, val, bw, kind)
    out_base = 0
    buf_chunks = []
    buf_base = 0
    budget = MAX_SEGMENTS
    for buf, bit_width, count in parts:
        segs = split_rle_hybrid(buf, bit_width, count,
                                max_segments=budget)
        if segs is None:
            return None
        budget -= len(segs)
        need_payload = any(s[0] == "packed" for s in segs)
        for s in segs:
            if s[0] == "rle":
                _, take, v = s
                rows.append((out_base, 0, v, 0, 0))
            else:
                _, start, nbytes, groups, take = s
                rows.append((out_base, (buf_base + start) * 8, 0,
                             bit_width, 1))
            out_base += take
        if need_payload:
            buf_chunks.append(bytes(buf))
            buf_base += len(buf)
    total = out_base
    if total == 0:
        return jnp.zeros((0,), jnp.int32)
    if not buf_chunks and len(rows) == 1:
        # pure single-RLE stream (whole page one run, or bit_width 0):
        # one jnp.full beats two puts + a program
        return jnp.full((total,), rows[0][2], jnp.int32)
    # bit offsets must stay inside int32 (the decode math is int32 on
    # both CPU and TPU): cap the concatenated stream at 128 MiB
    if buf_base * 8 + 64 > np.iinfo(np.int32).max:
        return None
    rpad = _pow2_pad(len(rows))
    meta = np.zeros((5, rpad), np.int32)
    meta[0, len(rows):] = np.iinfo(np.int32).max
    meta[:, :len(rows)] = np.array(rows, np.int32).T
    raw = b"".join(buf_chunks)
    bpad = max(8, _pow2_pad(len(raw) + 4))
    u8 = np.zeros(bpad, np.uint8)
    u8[:len(raw)] = np.frombuffer(raw, np.uint8)
    if engine is not None:
        u8_dev = host_to_device(engine, u8, dev)
        meta_dev = host_to_device(engine, meta, dev)
    else:
        u8_dev = jnp.asarray(u8)
        meta_dev = jnp.asarray(meta)
    out = _batch_decode()(u8_dev, meta_dev, _pow2_pad(total))
    return out[:total]


def rle_hybrid_to_device(buf, bit_width: int, count: int, dev,
                         engine=None) -> Optional["object"]:
    """Single-stream form of :func:`rle_hybrid_batch_to_device`."""
    return rle_hybrid_batch_to_device([(buf, bit_width, count)], dev,
                                      engine=engine)

"""ctypes wrapper over the strom-io C++ engine (csrc/strom_io.{h,cc}).

This is the userspace library layer of the stack — the analogue of the thin
wrappers PG-Strom keeps around the reference's ioctl ABI (SURVEY.md §1 L2/L4).
Python never touches payload bytes: reads complete into engine-owned locked
buffers, exposed here as zero-copy numpy views via ``np.ctypeslib.as_array``.
"""

from __future__ import annotations

import bisect
import ctypes
import errno
import os
import subprocess
import threading
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

import numpy as np

from nvme_strom_tpu.utils.config import EngineConfig
from nvme_strom_tpu.utils.lockwitness import make_lock
from nvme_strom_tpu.utils.stats import StromStats, global_stats
from nvme_strom_tpu.utils.trace import NO_CONTEXT

_CSRC = Path(__file__).resolve().parents[2] / "csrc"
_LIB_PATH = _CSRC / "libstrom_io.so"
_lib_lock = make_lock("engine._lib_lock")
_lib: Optional[ctypes.CDLL] = None


class _FileInfo(ctypes.Structure):
    _fields_ = [
        ("size", ctypes.c_int64),
        ("supports_direct", ctypes.c_int32),
        ("block_size", ctypes.c_int32),
        ("fs_magic", ctypes.c_uint64),
    ]


_MAX_RAID_MEMBERS = 16


class _DeviceInfo(ctypes.Structure):
    _fields_ = [
        ("device", ctypes.c_char * 64),
        ("is_nvme", ctypes.c_int32),
        ("is_raid", ctypes.c_int32),
        ("raid_level", ctypes.c_int32),
        ("n_members", ctypes.c_int32),
        ("rotational", ctypes.c_int32),
        ("nvme_backed", ctypes.c_int32),
        ("members", (ctypes.c_char * 64) * _MAX_RAID_MEMBERS),
    ]


class _Extent(ctypes.Structure):
    _fields_ = [
        ("logical", ctypes.c_uint64),
        ("physical", ctypes.c_uint64),
        ("length", ctypes.c_uint64),
        ("flags", ctypes.c_uint32),
        ("pad", ctypes.c_uint32),
    ]


class _PoolInfo(ctypes.Structure):
    _fields_ = [
        ("n_buffers", ctypes.c_uint32),
        ("free_buffers", ctypes.c_uint32),
        ("buf_bytes", ctypes.c_uint64),
        ("pool_bytes", ctypes.c_uint64),
        ("locked", ctypes.c_int32),
        ("queue_depth", ctypes.c_int32),
        ("in_flight", ctypes.c_uint32),
        ("deferred", ctypes.c_uint32),
        ("fixed_bufs", ctypes.c_int32),
        ("pad", ctypes.c_uint32),
        ("pool_base", ctypes.c_uint64),
    ]


class _StatsBlk(ctypes.Structure):
    _fields_ = [(n, ctypes.c_uint64) for n in (
        "bytes_direct", "bytes_fallback", "bounce_bytes",
        "bytes_written_direct", "requests_submitted", "requests_completed",
        "requests_failed", "retries", "bytes_resident",
        "submit_batches", "submit_syscalls_saved", "submit_enters")]


class _RdExt(ctypes.Structure):
    _fields_ = [
        ("fh", ctypes.c_int32),
        ("pad", ctypes.c_uint32),
        ("offset", ctypes.c_uint64),
        ("length", ctypes.c_uint64),
    ]


class _Completion(ctypes.Structure):
    _fields_ = [
        ("data", ctypes.POINTER(ctypes.c_uint8)),
        ("len", ctypes.c_uint64),
        ("status", ctypes.c_int32),
        ("was_fallback", ctypes.c_int32),
        ("submit_ns", ctypes.c_uint64),
        ("complete_ns", ctypes.c_uint64),
    ]


_LAT_BUCKETS = 64
_MAX_RINGS = 64    # STROM_MAX_RINGS: request ids carry 6 ring bits


class _RingInfo(ctypes.Structure):
    _fields_ = [
        ("ring_id", ctypes.c_uint32),
        ("n_buffers", ctypes.c_uint32),
        ("free_buffers", ctypes.c_uint32),
        ("deferred", ctypes.c_uint32),
        ("submitted", ctypes.c_uint64),
        ("completed", ctypes.c_uint64),
        ("inflight_io", ctypes.c_uint32),
        ("backend_uring", ctypes.c_int32),
        # failure-domain health (io/health.py): real-error completions
        # (cancels excluded), hot restarts survived, parked backlog,
        # stall-injection state, and the age of the oldest completion
        # a backend still owes — the reap-side stall signal
        ("failed", ctypes.c_uint64),
        ("restarts", ctypes.c_uint64),
        ("parked", ctypes.c_uint32),
        ("stalled", ctypes.c_int32),
        ("oldest_inflight_ns", ctypes.c_uint64),
        # zero-copy submission state (PR 12): fixed-buffer registration,
        # registered-file slot table, SQPOLL mode — per-ring gauges so a
        # silently-unregistered pool is visible instead of just slow
        ("fixed_bufs", ctypes.c_int32),
        ("reg_files", ctypes.c_int32),
        ("sqpoll", ctypes.c_int32),
    ]


def _nvme_hw_queues() -> int:
    """Largest hardware-queue count across visible NVMe namespaces
    (/sys/block/nvme*/mq has one directory per hw queue); 0 unknown."""
    best = 0
    try:
        for d in os.listdir("/sys/block"):
            if d.startswith("nvme"):
                try:
                    best = max(best, len(os.listdir(f"/sys/block/{d}/mq")))
                except OSError:
                    pass
    except OSError:
        pass
    return best


def auto_ring_count() -> int:
    """Default ring count: CPU topology capped by the NVMe device's
    hardware queue count, rounded down to a power of two (divides the
    default queue depths/pools evenly), ceiling 8.  The caller further
    caps by what the configured pool/queue depth can feed."""
    cpus = os.cpu_count() or 1
    n = max(1, min(8, cpus // 4))
    mq = _nvme_hw_queues()
    if mq:
        n = max(1, min(n, mq))
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def _load_lib() -> ctypes.CDLL:
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if not _CSRC.is_dir():
            raise ImportError(
                f"C++ engine sources not found at {_CSRC} — "
                "nvme_strom_tpu must run from a source checkout "
                "(`pip install -e .` or sys.path), not a plain wheel: "
                "the engine builds csrc/ against the running kernel's "
                "io_uring support on first import")
        src_mtime = max((_CSRC / n).stat().st_mtime
                        for n in ("strom_io.cc", "strom_io.h"))
        if not _LIB_PATH.exists() or _LIB_PATH.stat().st_mtime < src_mtime:
            # the one way the library is ever built (it is git-ignored)
            make = subprocess.run(["make", "-C", str(_CSRC)],
                                  capture_output=True, text=True)
            if make.returncode != 0:
                raise ImportError(
                    f"building {_LIB_PATH} failed (make exit "
                    f"{make.returncode}):\n{make.stdout}{make.stderr}")
        lib = ctypes.CDLL(str(_LIB_PATH), use_errno=True)
        lib.strom_engine_create.restype = ctypes.c_void_p
        lib.strom_engine_create.argtypes = [
            ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint64,
            ctypes.c_uint32, ctypes.c_int, ctypes.c_int]
        lib.strom_engine_create_rings.restype = ctypes.c_void_p
        lib.strom_engine_create_rings.argtypes = [
            ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint32,
            ctypes.c_uint64, ctypes.c_uint32, ctypes.c_int, ctypes.c_int]
        lib.strom_engine_create_prealloc.restype = ctypes.c_void_p
        lib.strom_engine_create_prealloc.argtypes = [
            ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint32,
            ctypes.c_uint64, ctypes.c_uint32, ctypes.c_int, ctypes.c_int,
            ctypes.c_void_p, ctypes.c_uint64]
        lib.strom_engine_pool_bytes.restype = ctypes.c_uint64
        lib.strom_engine_pool_bytes.argtypes = [
            ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint64,
            ctypes.c_uint32]
        # strom_arena_* is OWNED by io/arena.py (its private handle) —
        # binding it here too was exactly the double-bind shape
        # strom-lint's abi pass forbids (one owning site per symbol)
        lib.strom_ring_count.restype = ctypes.c_int
        lib.strom_ring_count.argtypes = [ctypes.c_void_p]
        lib.strom_get_ring_info.restype = ctypes.c_int
        lib.strom_get_ring_info.argtypes = [ctypes.c_void_p,
                                            ctypes.c_uint32,
                                            ctypes.POINTER(_RingInfo)]
        lib.strom_ring_inflight.restype = ctypes.c_int64
        lib.strom_ring_inflight.argtypes = [ctypes.c_void_p,
                                            ctypes.c_uint32]
        lib.strom_ring_restart.restype = ctypes.c_int64
        lib.strom_ring_restart.argtypes = [ctypes.c_void_p,
                                           ctypes.c_uint32,
                                           ctypes.c_uint64]
        lib.strom_set_ring_stall.restype = ctypes.c_int
        lib.strom_set_ring_stall.argtypes = [ctypes.c_void_p,
                                             ctypes.c_uint32,
                                             ctypes.c_int]
        lib.strom_read_buffered.restype = ctypes.c_int64
        lib.strom_read_buffered.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_uint64,
            ctypes.c_uint64, ctypes.c_void_p]
        lib.strom_submit_read_ring.restype = ctypes.c_int64
        lib.strom_submit_read_ring.argtypes = [
            ctypes.c_void_p, ctypes.c_uint32, ctypes.c_int,
            ctypes.c_uint64, ctypes.c_uint64]
        lib.strom_submit_readv_ring.restype = ctypes.c_int
        lib.strom_submit_readv_ring.argtypes = [
            ctypes.c_void_p, ctypes.c_uint32, ctypes.POINTER(_RdExt),
            ctypes.c_uint32, ctypes.POINTER(ctypes.c_int64)]
        lib.strom_engine_destroy.restype = None
        lib.strom_engine_destroy.argtypes = [ctypes.c_void_p]
        lib.strom_check_file.restype = ctypes.c_int
        lib.strom_check_file.argtypes = [ctypes.c_char_p,
                                         ctypes.POINTER(_FileInfo)]
        lib.strom_resolve_device.restype = ctypes.c_int
        lib.strom_resolve_device.argtypes = [ctypes.c_char_p,
                                             ctypes.POINTER(_DeviceInfo)]
        lib.strom_file_extents.restype = ctypes.c_int
        lib.strom_file_extents.argtypes = [ctypes.c_char_p,
                                           ctypes.POINTER(_Extent),
                                           ctypes.c_uint32]
        lib.strom_stripe_attr.argtypes = [
            ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64,
            ctypes.c_uint32, ctypes.POINTER(ctypes.c_uint64)]
        lib.strom_stripe_attr.restype = None
        lib.strom_get_pool_info.restype = None
        lib.strom_get_pool_info.argtypes = [ctypes.c_void_p,
                                            ctypes.POINTER(_PoolInfo)]
        lib.strom_get_latency.restype = None
        lib.strom_get_latency.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64)]
        lib.strom_open.restype = ctypes.c_int
        lib.strom_open.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_int]
        lib.strom_close.restype = ctypes.c_int
        lib.strom_close.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.strom_file_size.restype = ctypes.c_int64
        lib.strom_file_size.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.strom_file_is_direct.restype = ctypes.c_int
        lib.strom_file_is_direct.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.strom_file_ident.restype = ctypes.c_int
        lib.strom_file_ident.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                         ctypes.POINTER(ctypes.c_uint64)]
        lib.strom_submit_read.restype = ctypes.c_int64
        lib.strom_submit_read.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                          ctypes.c_uint64, ctypes.c_uint64]
        lib.strom_submit_readv.restype = ctypes.c_int
        lib.strom_submit_readv.argtypes = [ctypes.c_void_p,
                                           ctypes.POINTER(_RdExt),
                                           ctypes.c_uint32,
                                           ctypes.POINTER(ctypes.c_int64)]
        lib.strom_submit_write.restype = ctypes.c_int64
        lib.strom_submit_write.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                           ctypes.c_uint64, ctypes.c_void_p,
                                           ctypes.c_uint64]
        lib.strom_submit_write_ring.restype = ctypes.c_int64
        lib.strom_submit_write_ring.argtypes = [
            ctypes.c_void_p, ctypes.c_uint32, ctypes.c_int,
            ctypes.c_uint64, ctypes.c_void_p, ctypes.c_uint64]
        lib.strom_wait.restype = ctypes.c_int
        lib.strom_wait.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                   ctypes.POINTER(_Completion)]
        lib.strom_wait_timeout.restype = ctypes.c_int
        lib.strom_wait_timeout.argtypes = [ctypes.c_void_p,
                                           ctypes.c_int64,
                                           ctypes.POINTER(_Completion),
                                           ctypes.c_uint64]
        lib.strom_release.restype = ctypes.c_int
        lib.strom_release.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.strom_get_stats.restype = None
        lib.strom_get_stats.argtypes = [ctypes.c_void_p,
                                        ctypes.POINTER(_StatsBlk)]
        lib.strom_drain_stats.restype = None
        lib.strom_drain_stats.argtypes = [ctypes.c_void_p,
                                          ctypes.POINTER(_StatsBlk)]
        lib.strom_reset_stats.restype = None
        lib.strom_reset_stats.argtypes = [ctypes.c_void_p]
        lib.strom_backend_is_uring.restype = ctypes.c_int
        lib.strom_backend_is_uring.argtypes = [ctypes.c_void_p]
        lib.strom_tar_index.restype = ctypes.c_int64
        lib.strom_tar_index.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_uint64)]
        lib.strom_tar_index_free.restype = None
        lib.strom_tar_index_free.argtypes = [
            ctypes.POINTER(ctypes.c_uint8)]
        _lib = lib
        return lib


@dataclass(frozen=True)
class FileInfo:
    """Result of the CHECK_FILE-analogue eligibility probe (SURVEY.md §3.3)."""
    size: int
    supports_direct: bool
    block_size: int
    fs_magic: int


@dataclass(frozen=True)
class DeviceInfo:
    """Backing block-device topology — the blockdev half of the reference's
    CHECK_FILE verdict (SURVEY.md §3.3: fs must sit on NVMe, or md-raid0
    whose members are all NVMe). ``device == ""`` means no backing blockdev
    is visible (overlayfs/tmpfs/network fs)."""
    device: str
    is_nvme: bool
    is_raid: bool
    raid_level: int       # numeric md level (0 == raid0); -1 unknown
    rotational: int       # -1 unknown
    nvme_backed: bool     # NVMe, or raid0 striped over all-NVMe members
    members: tuple[str, ...]


def check_file(path: os.PathLike | str) -> FileInfo:
    lib = _load_lib()
    info = _FileInfo()
    rc = lib.strom_check_file(str(path).encode(), ctypes.byref(info))
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc), str(path))
    return FileInfo(size=info.size, supports_direct=bool(info.supports_direct),
                    block_size=info.block_size, fs_magic=info.fs_magic)


def resolve_device(path: os.PathLike | str) -> DeviceInfo:
    """sysfs walk: st_dev → /sys/dev/block → partition→parent → md members."""
    lib = _load_lib()
    info = _DeviceInfo()
    rc = lib.strom_resolve_device(str(path).encode(), ctypes.byref(info))
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc), str(path))
    members = tuple(info.members[i].value.decode()
                    for i in range(min(info.n_members, _MAX_RAID_MEMBERS)))
    return DeviceInfo(device=info.device.decode(),
                      is_nvme=bool(info.is_nvme), is_raid=bool(info.is_raid),
                      raid_level=info.raid_level, rotational=info.rotational,
                      nvme_backed=bool(info.nvme_backed), members=members)


def tar_index(path: os.PathLike | str) -> list:
    """Native tar header walk: [(member name str, data offset, size)]
    for every regular file, in archive order.

    The C side (strom_tar_index) understands ustar name+prefix, GNU
    longname and pax path=/size= overrides — the formats Python's
    tarfile emits — and validates header checksums, failing loudly
    (ValueError) on malformed archives instead of returning a partial
    index.  Valid-but-unimplemented features (global pax path=/size=
    overrides, names past the 4096 cap) raise NotImplementedError so
    formats/wds.py can fall back to tarfile for those archives only.  ~5x the Python-loop indexing rate (measured: 20k members
    in ~100ms vs ~490ms warm-cache); formats/wds.py uses it when the
    library is built and falls back to tarfile otherwise."""
    lib = _load_lib()
    buf = ctypes.POINTER(ctypes.c_uint8)()
    nbytes = ctypes.c_uint64()
    n = lib.strom_tar_index(os.fsencode(path), ctypes.byref(buf),
                            ctypes.byref(nbytes))
    if n < 0:
        import errno as _errno
        if -n == _errno.ENOTSUP:
            # valid archive, feature this walker doesn't implement
            # (global pax path=/size= overrides, names beyond the 4096
            # cap): a DIFFERENT type so callers can fall back to
            # tarfile, while genuine corruption stays a loud ValueError
            raise NotImplementedError(
                f"{path}: tar feature unsupported by the native walker")
        raise ValueError(f"{path}: tar index failed "
                         f"({_errno.errorcode.get(-n, -n)})")
    try:
        raw = ctypes.string_at(buf, nbytes.value) if nbytes.value else b""
    finally:
        if buf:
            lib.strom_tar_index_free(buf)
    out = []
    pos = 0
    import struct as _struct
    for _ in range(n):
        off, size, nl = _struct.unpack_from("<QQI", raw, pos)
        pos += 20
        name = raw[pos:pos + nl].decode("utf-8", errors="surrogateescape")
        pos += nl
        out.append((name, off, size))
    return out


def stripe_attr(phys_off: int, length: int, chunk: int,
                n_members: int) -> list:
    """Per-member byte attribution of physical span [phys_off,
    phys_off+length) on an md-raid0 of ``n_members`` devices with
    stripe ``chunk`` (C closed-form; see strom_stripe_attr)."""
    lib = _load_lib()
    out = (ctypes.c_uint64 * n_members)()
    lib.strom_stripe_attr(phys_off, length, chunk, n_members, out)
    return list(out)


def md_chunk_bytes(device: str) -> int:
    """Stripe chunk of an md device from sysfs (bytes); 0 if unknown."""
    try:
        with open(f"/sys/block/{device}/md/chunk_size") as f:
            return int(f.read().strip())
    except (OSError, ValueError):
        return 0


EXTENT_SYNTHETIC = 0x80000000


@dataclass(frozen=True)
class Extent:
    """One file extent — the analogue of the reference's extent-walk output
    (file offsets resolved toward physical LBAs, SURVEY.md §3.1).
    ``synthetic`` extents come from filesystems without FIEMAP: the range is
    readable but not physically addressable."""
    logical: int
    physical: int
    length: int
    flags: int

    @property
    def synthetic(self) -> bool:
        return bool(self.flags & EXTENT_SYNTHETIC)


def file_extents(path: os.PathLike | str, max_extents: int = 1024
                 ) -> list[Extent]:
    """Complete extent map of `path`. Grows the buffer on -E2BIG so a
    heavily fragmented file never yields a silently truncated map."""
    lib = _load_lib()
    while True:
        arr = (_Extent * max_extents)()
        n = lib.strom_file_extents(str(path).encode(), arr, max_extents)
        if n == -errno.E2BIG and max_extents < (1 << 22):
            max_extents *= 4
            continue
        if n < 0:
            raise OSError(-n, os.strerror(-n), str(path))
        return [Extent(logical=e.logical, physical=e.physical,
                       length=e.length, flags=e.flags) for e in arr[:n]]


def file_eligible(path: os.PathLike | str) -> tuple[bool, FileInfo, DeviceInfo]:
    """The complete CHECK_FILE analogue: O_DIRECT works AND the file sits on
    NVMe (or md-raid0 over all-NVMe). Consumers use a False verdict the way
    the reference's callers use EINVAL/ENOTSUP — fall back to buffered
    reads (SURVEY.md §3.3)."""
    fi = check_file(path)
    di = resolve_device(path)
    return bool(fi.supports_direct and di.nvme_backed), fi, di


class PendingRead:
    """An in-flight read — MEMCPY_SSD2GPU's async DMA task id (SURVEY §3.1).

    ``wait()`` returns a zero-copy numpy view into the engine buffer; the
    view is valid until ``release()``.
    """

    def __init__(self, engine: "StromEngine", req_id: int, length: int,
                 fh: int = -1, offset: int = -1):
        self._engine = engine
        self._req_id = req_id
        self._length = length
        #: submit-time identity, carried so short-read/error reports can
        #: name the exact range (wait_exact, ReadError history)
        self.fh = fh
        self.offset = offset
        self._released = False
        self._view: Optional[np.ndarray] = None
        self._error: Optional[OSError] = None
        self.was_fallback = False

    @property
    def length(self) -> int:
        """Bytes REQUESTED at submit (the completed view may be shorter
        only at EOF — consumers whose plans never cross EOF treat a
        shorter view as a short read and recover or raise)."""
        return self._length

    @property
    def ring(self) -> int:
        """The submission ring this request rode (request ids carry
        their ring in the low STROM_RING_ID_BITS bits) — how the
        supervision layer (io/health.py) attributes a failed attempt
        to its failure domain."""
        return int(self._req_id) & (_MAX_RINGS - 1)

    def wait(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block for the completed staging view.

        ``timeout`` (seconds): bounded wait — raises TimeoutError if
        the request is still in flight after the deadline, WITHOUT
        releasing it (hang detection: the caller can diagnose, retry
        the wait, or ``release()`` to abort; the buffer stays a live
        DMA target until then).

        The still-live contract after a TimeoutError, explicitly:

        - retrying ``wait()`` on the same request is always valid and
          returns the completed payload once the I/O lands;
        - ``release()`` is the CANCEL path: it blocks until the request
          is out of flight (the staging buffer is a live DMA target and
          cannot be recycled under the kernel), then frees it — after
          which a fresh ``submit_read`` of the same range is the
          cancel-then-retry recovery ``io/resilient.py`` builds on
          (tested in tests/test_engine.py
          ``test_wait_timeout_cancel_then_retry``).
        """
        if self._view is not None:
            return self._view
        if self._error is not None:     # error found by an is_ready probe
            raise self._error
        comp = _Completion()
        rc = _wait_for_completion(self._engine, self._req_id, comp,
                                  timeout, "read")
        if rc < 0:
            self.release()
            e = OSError(-rc, os.strerror(-rc))
            # the C engine already counted this completion in its
            # per-ring failed counter: the supervision layer must not
            # count it a second time via note_error (io/health.py —
            # the breaker budgets would silently halve for exactly the
            # real device errors they are calibrated against)
            e.engine_counted = True
            flight = self._engine.flight
            if flight is not None:
                flight.record("read", getattr(self, "op_klass", None),
                              self.ring, self.fh, self.offset, 0, 0,
                              "error", err=-rc)
            raise e
        self.was_fallback = bool(comp.was_fallback)
        tracer = self._engine.tracer
        if tracer is not None and tracer.enabled:
            tracer.add_span(
                "strom.read.fallback" if comp.was_fallback else "strom.read",
                int(comp.submit_ns), int(comp.complete_ns),
                ctx=getattr(self, "trace_ctx", NO_CONTEXT),
                bytes=int(comp.len))
        flight = self._engine.flight
        if flight is not None:
            flight.record(
                "read", getattr(self, "op_klass", None), self.ring,
                self.fh, self.offset, int(comp.len),
                max(0, int(comp.complete_ns - comp.submit_ns)) // 1000,
                "fallback" if comp.was_fallback else "ok")
        # completion reaping doubles as the ring time-in-state sampling
        # point (obs/ledger.py; time-gated inside — one monotonic read
        # per completed op on the fast path)
        self._engine._sample_ring_states()
        n = int(comp.len)
        if n == 0:
            self._view = np.empty(0, dtype=np.uint8)
        else:
            self._view = np.ctypeslib.as_array(comp.data, shape=(n,))
        return self._view

    def is_ready(self) -> bool:
        """Non-blocking completion probe: True once ``wait()`` would
        return without blocking — including completed-with-error reads,
        whose OSError is cached here and raised by the caller's
        ``wait()`` (a bool probe must not throw or release as a side
        effect).  Pipelines use this to promote read-complete batches
        to the transfer stage while younger reads stay in flight (the
        read-side analogue of ``DeviceStream``'s ``drain="ready"``)."""
        if (self._view is not None or self._error is not None
                or self._released):
            return True
        try:
            self.wait(timeout=0.0)
            return True
        except TimeoutError:
            return False
        except OSError as e:
            self._error = e
            return True

    def release(self) -> None:
        if self._released:
            return
        rc = self._engine._lib.strom_release(self._engine._h, self._req_id)
        if rc == -errno.EBUSY:
            # Still in flight: the staging buffer is a live DMA target and
            # must not be recycled yet — wait for completion, then free.
            self._engine._lib.strom_wait(self._engine._h, self._req_id, None)
            self._engine._lib.strom_release(self._engine._h, self._req_id)
        self._released = True
        self._view = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.release()


def wait_exact(pending, timeout: Optional[float] = None) -> np.ndarray:
    """``pending.wait(timeout)`` + strict length verification.

    For consumers whose read plans never cross EOF (index-derived
    ranges: the loader's sample/record plans, checkpoint tiles, weight
    slices, offload slots) a completed view shorter than the submit
    request can only mean file truncation or a device short read — and
    accepting it silently yields garbage-tailed tensors.  One helper so
    every consumer enforces the invariant identically instead of
    hand-rolling the check (works on PendingRead, FaultyRead, and
    ResilientRead alike via their ``length`` property).  TimeoutError
    passes through with the request still live (the ``wait`` contract);
    the short-read OSError releases the request first.
    """
    view = pending.wait(timeout)
    if view.nbytes != pending.length:
        pending.release()
        fh = getattr(pending, "fh", None)
        offset = getattr(pending, "offset", None)
        where = ("" if fh is None or fh < 0
                 else f" (fh={fh} offset={offset})")
        raise OSError(errno.EIO,
                      f"short read: got {view.nbytes} of "
                      f"{pending.length} expected bytes{where}")
    return view


def _wait_for_completion(engine: "StromEngine", req_id: int,
                         comp, timeout: Optional[float],
                         what: str) -> int:
    """strom_wait / strom_wait_timeout dispatch shared by reads and
    writes.  Raises TimeoutError with the request STILL LIVE (retry the
    wait or release() to abort)."""
    if timeout is None:
        return engine._lib.strom_wait(engine._h, req_id,
                                      ctypes.byref(comp))
    if timeout < 0:
        raise ValueError(f"timeout must be >= 0, got {timeout}")
    # cap at chrono's int64 nanoseconds — anything longer is forever
    ns = min(int(timeout * 1e9), (1 << 63) - 1)
    rc = engine._lib.strom_wait_timeout(engine._h, req_id,
                                        ctypes.byref(comp), ns)
    if rc == -errno.ETIMEDOUT:
        raise TimeoutError(f"{what} {req_id} still in flight after "
                           f"{timeout}s")
    return rc


class PendingWrite:
    def __init__(self, engine: "StromEngine", req_id: int,
                 keepalive: Optional[np.ndarray],
                 fh: int = -1, offset: int = -1):
        self._engine = engine
        self._req_id = req_id
        self._keepalive = keepalive  # zero-copy source must outlive the I/O
        #: submit-time identity + size, carried so short-write/error
        #: reports (and the resilient write-retry mirror) can name the
        #: exact range without re-deriving it
        self.fh = fh
        self.offset = offset
        self.length = keepalive.nbytes if keepalive is not None else 0
        self._released = False

    @property
    def ring(self) -> int:
        """Submission ring (failure-domain attribution, PendingRead
        parity)."""
        return int(self._req_id) & (_MAX_RINGS - 1)

    def release(self) -> None:
        """Abort/free path (e.g. after a wait timeout): blocks until
        the write is out of flight, then frees the request — the
        source buffer and any bounce staging return to the pool."""
        if self._released:
            return
        rc = self._engine._lib.strom_release(self._engine._h,
                                             self._req_id)
        if rc == -errno.EBUSY:
            self._engine._lib.strom_wait(self._engine._h, self._req_id,
                                         None)
            self._engine._lib.strom_release(self._engine._h,
                                            self._req_id)
        self._released = True
        self._keepalive = None
        # the abandoned write may still have (partially) landed
        self._engine._hostcache_write_done(self.fh, self.offset,
                                           self.length)

    def wait(self, timeout: Optional[float] = None) -> int:
        comp = _Completion()
        rc = _wait_for_completion(self._engine, self._req_id, comp,
                                  timeout, "write")
        n = int(comp.len)
        self._engine._lib.strom_release(self._engine._h, self._req_id)
        self._released = True
        self._keepalive = None
        # completion-side staleness guard (the submit-side bump alone
        # leaves a hole: a read admitted AFTER submit can complete with
        # pre-write bytes while the write is still in flight, and would
        # otherwise install them as a resident line)
        self._engine._hostcache_write_done(self.fh, self.offset,
                                           self.length)
        flight = self._engine.flight
        if rc < 0:
            e = OSError(-rc, os.strerror(-rc))
            e.engine_counted = True   # see PendingRead.wait: the C
            #                           ring counter has this failure
            if flight is not None:
                flight.record("write", getattr(self, "op_klass", None),
                              self.ring, self.fh, self.offset, 0, 0,
                              "error", err=-rc)
            raise e
        tracer = self._engine.tracer
        if tracer is not None and tracer.enabled:
            tracer.add_span("strom.write", int(comp.submit_ns),
                            int(comp.complete_ns),
                            ctx=getattr(self, "trace_ctx", NO_CONTEXT),
                            bytes=n)
        if flight is not None:
            flight.record(
                "write", getattr(self, "op_klass", None), self.ring,
                self.fh, self.offset, n,
                max(0, int(comp.complete_ns - comp.submit_ns)) // 1000,
                "ok")
        return n


class StromEngine:
    """The userspace handle to the strom-io engine.

    One engine owns N submission rings (``EngineConfig.n_rings``; each
    an io_uring or worker pool reaping its own completions) over ONE
    locked staging pool — the MAP_GPU_MEMORY analogue, created once and
    reused for every transfer, deliberately global: buffers freed on
    any ring recycle to the oldest deferred request engine-wide, so
    ring pinning can never deadlock on pool pressure.  A sharded engine
    also owns the QoS scheduler that maps latency classes onto its
    rings (io/sched.py).  ``n_rings=1`` is exactly the pre-sharding
    engine: no scheduler, one ring, one pool.
    """

    def __init__(self, config: Optional[EngineConfig] = None,
                 stats: Optional[StromStats] = None,
                 tracer: Optional["Tracer"] = None):
        from nvme_strom_tpu.utils.trace import global_tracer
        self.config = config or EngineConfig()
        self.stats = stats if stats is not None else global_stats
        self.tracer = tracer if tracer is not None else global_tracer
        if self.tracer is not None and self.tracer.stats is None:
            # drop accounting must land in the block THIS engine
            # exports, or trace_spans_dropped can never reach the
            # strom_stat/watchdog warnings for private-stats engines
            # (first engine wins on a shared tracer)
            self.tracer.stats = self.stats
        #: host buffers a weight restore assembled column shards in
        #: (ops/bridge.HostAssembly), kept between loads: every one is
        #: ``ASSEMBLY_BYTES`` long and on its way to no device.  A fresh
        #: buffer's pages cost more to touch than to copy into (PERF.md
        #: §5), so a load leaves them for the next; ``close_all`` drops
        #: them.
        self.spare_host_buffers: list = []
        self._lib = _load_lib()
        c = self.config
        n_buffers = max(
            2, min(64, c.buffer_pool_bytes // max(1, c.chunk_bytes)))
        # Ring count: explicit n_rings, or auto from CPU/NVMe topology —
        # capped by what the CONFIGURED engine can feed (each ring needs
        # >= 2 staging buffers and >= 1 queue slot, so a deliberately
        # tiny engine stays single-ring and keeps its exact pre-sharding
        # deferral behavior).
        n_rings = c.n_rings if c.n_rings > 0 else auto_ring_count()
        n_rings = max(1, min(n_rings, _MAX_RINGS, n_buffers // 2,
                             c.queue_depth))
        qd_ring = max(1, c.queue_depth // n_rings)
        bufs_ring = max(2, n_buffers // n_rings)
        # Unified pinned arena (io/arena.py, docs/PERF.md §6): carve the
        # staging pool out of the ONE process reservation so staging,
        # cache lines and bridge slabs share a single mapping + lock
        # policy.  Arena off/exhausted → the engine maps its own pool,
        # the exact pre-arena path (arena_fallbacks counts exhaustion).
        self._pool_slab = None
        from nvme_strom_tpu.io import arena as _arena
        pool_bytes = int(self._lib.strom_engine_pool_bytes(
            n_rings, bufs_ring, c.chunk_bytes, c.alignment))
        slab = (_arena.carve_or_none(pool_bytes, "staging",
                                     stats=self.stats,
                                     lock=c.lock_buffers)
                if pool_bytes else None)
        if slab is not None:
            self._h = self._lib.strom_engine_create_prealloc(
                n_rings, qd_ring, bufs_ring, c.chunk_bytes, c.alignment,
                1 if c.use_io_uring else 0, 1 if c.lock_buffers else 0,
                slab.addr, slab.nbytes)
            if not self._h:
                slab.release()
                slab = None
        if slab is None:
            self._h = self._lib.strom_engine_create_rings(
                n_rings, qd_ring, bufs_ring, c.chunk_bytes, c.alignment,
                1 if c.use_io_uring else 0, 1 if c.lock_buffers else 0)
        self._pool_slab = slab
        if not self._h:
            raise OSError(ctypes.get_errno(),
                          "strom_engine_create failed: "
                          + os.strerror(ctypes.get_errno()))
        self.n_rings = n_rings
        self.n_buffers = bufs_ring * n_rings
        self._qd_ring = qd_ring
        self._open_fhs: set[int] = set()
        self._last_lat_read: list[int] = [0] * _LAT_BUCKETS
        self._stripe: dict = {}   # fh → (chunk, members, extents)
        # fh → (dev, ino, mtime_ns, size): the stable file identity the
        # pinned-host tier keys its lines by (io/hostcache.py) — a file
        # modified between opens gets a new key, so stale lines never hit
        self._file_keys: dict = {}
        self._closed = False
        # failure-domain supervision (io/health.py): per-ring breakers,
        # hot restart, degraded buffered fallback.  STROM_BREAKER=0
        # removes the layer entirely (None = the exact pre-supervision
        # engine; every hook below is a cheap None check).
        self.supervisor = None
        from nvme_strom_tpu.utils.config import BreakerConfig
        bcfg = BreakerConfig()
        if bcfg.enabled:
            from nvme_strom_tpu.io.health import EngineSupervisor
            self.supervisor = EngineSupervisor(self, bcfg)
        # flight recorder (io/flightrec.py, docs/OBSERVABILITY.md):
        # always-on bounded ring of recent op records, dumped by the
        # health/SLO/watchdog triggers.  STROM_FLIGHT=0 removes it
        # (None = the exact pre-recorder wait path).
        self.flight = None
        from nvme_strom_tpu.utils.config import FlightConfig
        fcfg = FlightConfig()
        if fcfg.enabled:
            from nvme_strom_tpu.io.flightrec import FlightRecorder
            self.flight = FlightRecorder(fcfg, self.stats)
        # critical-path attribution (obs/attrib.py, STROM_ATTRIB=1):
        # the process collector rides this engine's tracer as a span
        # sink — span emission turns on (sink-only: nothing accumulates
        # in memory) and serving folds per-request trees at retire.
        # None (the default) is the exact pre-attribution engine.
        from nvme_strom_tpu.obs.attrib import attach as _attach_attrib
        self._attrib = _attach_attrib(self.tracer, self.stats)
        if self._attrib is not None and self.flight is not None:
            # every post-mortem dump opens with where recent requests'
            # time went
            self.flight.attrib = self._attrib
        # per-ring time-in-state ledger (obs/ledger.py): cumulative
        # busy/idle/stalled/restarting seconds, sampled at completion
        # reaping (time-gated below) and exported at every stats sync
        from nvme_strom_tpu.obs.ledger import RingTimeLedger
        self.ring_ledger = RingTimeLedger(n_rings)
        self._ring_sample_next = 0.0
        self._ring_counter_live = False
        # live debug endpoint (obs/debugsrv.py, STROM_DEBUG_PORT): one
        # loopback HTTP server per process serving /metrics /attrib
        # /ledger /flight /health /locks; off by default (None)
        from nvme_strom_tpu.obs.debugsrv import maybe_start_debug_server
        self._debug_srv = maybe_start_debug_server(self.stats,
                                                   engine=self)
        # opt-in OpenMetrics textfile writer (STROM_METRICS_FILE):
        # started once per process with the first engine's stats block.
        # When the writer observes THIS engine's block, its periodic
        # snapshots drain the C counters through sync_stats (detached
        # at close_all so a snapshot can never race engine teardown).
        from nvme_strom_tpu.utils.stats import maybe_start_metrics_writer
        self._metrics_writer = maybe_start_metrics_writer(self.stats)
        if (self._metrics_writer is not None
                and self._metrics_writer.stats is self.stats):
            self._metrics_writer.set_sync(self.sync_stats)
        else:
            self._metrics_writer = None
        # per-ring registration/SQPOLL gauge cache (refreshed only at
        # create and ring restart; sync_stats exports it without the
        # per-sync ring_info walk)
        self._zc_gauges = None
        self._refresh_zc_gauges()
        self.scheduler = None
        if n_rings > 1:
            from nvme_strom_tpu.utils.config import SchedConfig
            scfg = SchedConfig()
            if scfg.enabled:
                from nvme_strom_tpu.io.sched import (QoSScheduler,
                                                     default_policies)
                cap = scfg.max_inflight_per_ring or qd_ring
                self._ring_cap = max(1, cap)
                self.scheduler = QoSScheduler(
                    submit_ring=self._submit_readv_ring,
                    ring_free=self._ring_free_slots,
                    policies=default_policies(scfg.class_weights),
                    aging_rounds=scfg.aging_rounds,
                    stats=self.stats,
                    ring_cap=self._ring_cap,
                    tracer=self.tracer)

    # -- file handles ------------------------------------------------------

    def open(self, path: os.PathLike | str, writable: bool = False,
             force_buffered: bool = False) -> int:
        flags = (1 if writable else 0) | (2 if force_buffered else 0)
        fh = self._lib.strom_open(self._h, str(path).encode(), flags)
        if fh < 0:
            raise OSError(-fh, os.strerror(-fh), str(path))
        self._open_fhs.add(fh)
        # identity via fstat on the engine's OWN descriptor, never the
        # path: a rename racing the open (the checkpoint commit window)
        # could otherwise key one inode's cached bytes under another
        # file's identity
        ident = (ctypes.c_uint64 * 4)()
        if self._lib.strom_file_ident(self._h, fh, ident) == 0:
            self._file_keys[fh] = tuple(int(x) for x in ident)
        if self.config.stripe_accounting:
            self._setup_stripe(fh, path, writable=writable)
        return fh

    def file_key(self, fh: int) -> Optional[tuple]:
        """Stable identity of the file behind ``fh`` — what the
        pinned-host tier (io/hostcache.py) keys cache lines by; None
        when unknown (the tier then skips this fh)."""
        return self._file_keys.get(fh)

    def _setup_stripe(self, fh: int, path, writable: bool = False) -> None:
        """Per-member attribution geometry for this file (SURVEY.md §6:
        the reference's striped claim implies knowing which member
        served which byte).  Real geometry comes from the backing
        md-raid0 (sysfs chunk + member walk); STROM_STRIPE_SIM=
        "<chunk_kib>:<n>" imposes synthetic geometry on any device so
        the attribution path is exercisable without raid hardware.
        Synthetic (FIEMAP-less) extents attribute by logical offset —
        best effort, flagged by the extent itself."""
        sim = os.environ.get("STROM_STRIPE_SIM")
        if sim:
            try:
                chunk_kib, n = sim.split(":")
                chunk = int(chunk_kib) << 10
                members = tuple(f"sim{i}" for i in range(int(n)))
                if chunk <= 0 or not members:
                    raise ValueError
            except ValueError:
                raise ValueError(
                    f"STROM_STRIPE_SIM={sim!r}: expected "
                    "'<chunk_kib>:<n_members>' with positive integers")
            # simulated geometry attributes by LOGICAL offset (one
            # unbounded pseudo extent with physical == logical):
            # deterministic regardless of fs placement, and valid for
            # GROWING files too (the write path)
            extents = [Extent(0, 0, 1 << 62, 0)]
            self._stripe[fh] = (chunk, members, extents, [0])
            return
        if writable:
            # a real-raid extent map of a growing file is a moving
            # target — write attribution is sim-geometry only
            return
        else:
            info = resolve_device(path)
            if not (info.is_raid and info.raid_level == 0
                    and len(info.members) > 1):
                return
            chunk = md_chunk_bytes(info.device)
            if chunk <= 0:
                return
            members = info.members
            extents = sorted(file_extents(path),
                             key=lambda e: e.logical)
        self._stripe[fh] = (chunk, members, extents,
                            [e.logical for e in extents])

    def _attr_stripe(self, fh: int, offset: int, length: int) -> None:
        st = self._stripe.get(fh)
        if st is None:
            return
        chunk, members, extents, logicals = st
        lib = self._lib
        buf = (ctypes.c_uint64 * len(members))()
        # extents are sorted by logical: bisect to the first overlap and
        # stop past the range (fragmented files can map to thousands of
        # extents; a full scan per submit would dominate the hot path)
        i = bisect.bisect_right(logicals, offset) - 1
        for e in extents[max(i, 0):]:
            if e.logical >= offset + length:
                break
            lo = max(offset, e.logical)
            hi = min(offset + length, e.logical + e.length)
            if lo >= hi:
                continue
            phys = e.physical + (lo - e.logical)
            lib.strom_stripe_attr(phys, hi - lo, chunk, len(members),
                                  buf)
        self.stats.add_member_bytes(members, list(buf))

    def close(self, fh: int) -> None:
        self._lib.strom_close(self._h, fh)
        self._open_fhs.discard(fh)
        self._stripe.pop(fh, None)
        self._file_keys.pop(fh, None)

    def file_size(self, fh: int) -> int:
        n = self._lib.strom_file_size(self._h, fh)
        if n < 0:
            raise OSError(-n, os.strerror(-n))
        return n

    def file_is_direct(self, fh: int) -> bool:
        return self._lib.strom_file_is_direct(self._h, fh) == 1

    # -- rings -------------------------------------------------------------

    def ring_info(self, ring: int) -> dict:
        """One ring's occupancy/counters (strom_get_ring_info)."""
        info = _RingInfo()
        rc = self._lib.strom_get_ring_info(self._h, ring,
                                           ctypes.byref(info))
        if rc < 0:
            raise OSError(-rc, os.strerror(-rc))
        return {n: int(getattr(info, n)) for n, _ in _RingInfo._fields_}

    def ring_depths(self) -> list:
        """Per-ring in-flight I/O (submitted - completed) via the
        lock-free depth-only C path — the scheduler's admission polls
        this at dispatch frequency, so it must never contend with the
        pool mutex the data path is hammering (strom_ring_inflight, not
        the full strom_get_ring_info)."""
        return [max(0, int(self._lib.strom_ring_inflight(self._h, r)))
                for r in range(self.n_rings)]

    def _ring_free_slots(self) -> list:
        cap = getattr(self, "_ring_cap", self._qd_ring)
        free = [max(0, cap - d) for d in self.ring_depths()]
        if self.supervisor is not None:
            # the scheduler's admission poll doubles as the supervision
            # heartbeat (time-gated inside), and tripped rings report
            # zero headroom so new batches route around them
            self.supervisor.tick()
            free = self.supervisor.mask_free_slots(free)
        return free

    def _sample_ring_states(self) -> None:
        """Time-gated per-ring time-in-state sample (obs/ledger.py):
        charges the elapsed interval to each ring's current state
        (busy/idle/stalled) from the lock-free depth counters and the
        supervisor's breaker verdicts.  Called from completion reaping
        and stat syncs; ~10 Hz cap keeps it off the hot path."""
        now = time.monotonic()
        if now < self._ring_sample_next or self._closed:
            return
        self._ring_sample_next = now + 0.1
        states = (self.supervisor.ring_states()
                  if self.supervisor is not None else None)
        try:
            self.ring_ledger.sample(self.ring_depths(), states, now=now)
        except OSError:
            pass

    def _refresh_zc_gauges(self) -> None:
        """Snapshot the per-ring registration/SQPOLL state (changes only
        at engine create and ring restart — the two callers)."""
        try:
            ri = [self.ring_info(r) for r in range(self.n_rings)]
            self._zc_gauges = dict(
                ring_fixed_bufs=[r["fixed_bufs"] for r in ri],
                ring_reg_files=[r["reg_files"] for r in ri],
                ring_sqpoll=[r["sqpoll"] for r in ri],
                pool_arena=1 if self._pool_slab is not None else 0)
        except OSError:
            self._zc_gauges = None

    def ring_restart(self, ring: int, drain_timeout_s: float = 0.5) -> int:
        """Hot-restart one ring (``strom_ring_restart``): cancel its
        stall-parked backlog (-ECANCELED — the waiters' retry loop is
        the requeue path), drain dispatched I/O bounded, rebuild the
        uring, resume.  Returns the number of requests cancelled for
        requeue; raises TimeoutError when in-flight I/O would not
        drain (the ring resumes untouched — fall back to degraded
        reads), OSError otherwise."""
        ns = max(1, int(drain_timeout_s * 1e9))
        t0 = time.monotonic()
        rc = self._lib.strom_ring_restart(self._h, ring, ns)
        # the restart window is charged explicitly: it is a rare,
        # bounded interval the ~10 Hz state sampler would mostly miss
        self.ring_ledger.note_restart(ring, time.monotonic() - t0)
        if rc == -errno.ETIMEDOUT:
            raise TimeoutError(
                f"ring {ring}: in-flight I/O did not drain within "
                f"{drain_timeout_s}s; restart aborted")
        if rc < 0:
            raise OSError(-rc, os.strerror(-rc))
        # the rebuilt uring re-registered buffers/files and re-armed
        # SQPOLL (or fell back to the worker pool): refresh the cached
        # registration gauges sync_stats exports
        self._refresh_zc_gauges()
        return int(rc)

    def set_ring_stall(self, ring: int, on: bool = True) -> None:
        """Arm/disarm the C-level ring-stall injection (chaos/tests):
        while armed the ring parks every dispatch — completions never
        arrive, exactly what a wedged uring looks like.  Disarm
        dispatches the backlog; ``ring_restart`` cancels it instead."""
        rc = self._lib.strom_set_ring_stall(self._h, ring,
                                            1 if on else 0)
        if rc < 0:
            raise OSError(-rc, os.strerror(-rc))

    def read_buffered(self, fh: int, offset: int, length: int
                      ) -> np.ndarray:
        """Degraded-mode primitive: one synchronous buffered ``pread``
        into a caller-owned array — no ring, no staging pool (counted
        fallback + bounce).  Returns the bytes actually read (short
        only at EOF)."""
        arr = np.empty(max(0, length), dtype=np.uint8)
        if length <= 0:
            return arr
        n = self._lib.strom_read_buffered(
            self._h, fh, offset, length,
            arr.ctypes.data_as(ctypes.c_void_p))
        if n < 0:
            raise OSError(-n, os.strerror(-n))
        return arr[:int(n)]

    # -- reads -------------------------------------------------------------

    def submit_read(self, fh: int, offset: int, length: int,
                    klass: Optional[str] = None,
                    ring: Optional[int] = None) -> PendingRead:
        """Scalar read.  Scalar submissions route round-robin across
        rings (``ring`` pins one) and never queue at the scheduler:
        they are the retry/hedge/probe path, where added queueing delay
        would fight the recovery that issued them.  ``klass`` is
        accepted for API symmetry (wrappers use it for per-class
        budgets) and stamped onto the pending for flight-recorder
        attribution; it does not affect scalar routing."""
        if length > self.config.chunk_bytes:
            raise ValueError(
                f"read length {length} exceeds chunk_bytes "
                f"{self.config.chunk_bytes}; split the range")
        if ring is None and self.supervisor is not None:
            # route around rings with an open breaker (None = all
            # trusted, keep the C round-robin): this is what lands a
            # requeued extent's resubmission on a HEALTHY ring
            ring = self.supervisor.pick_ring()
        if ring is None:
            rid = self._lib.strom_submit_read(self._h, fh, offset, length)
        else:
            rid = self._lib.strom_submit_read_ring(self._h, ring, fh,
                                                   offset, length)
        if rid < 0:
            raise OSError(-rid, os.strerror(-rid))
        if self._stripe:
            self._attr_stripe(fh, offset, length)
        pending = PendingRead(self, rid, length, fh=fh, offset=offset)
        if klass is not None:
            pending.op_klass = klass
        if self.tracer is not None and self.tracer.enabled:
            # causal attachment (docs/OBSERVABILITY.md): the completion
            # span may be waited on another thread — carry the child
            # context explicitly instead of relying on the contextvar
            from nvme_strom_tpu.utils.trace import attach_context
            pending.trace_ctx = attach_context()
        return pending

    def _submit_readv_ring(self, reads, ring: Optional[int]) -> list:
        """Raw vectored submission to one ring (or C round-robin when
        ``ring`` is None) — the scheduler's dispatch callback; no
        scheduler re-entry."""
        reads = list(reads)
        n = len(reads)
        exts = (_RdExt * n)()
        for i, (fh, offset, length) in enumerate(reads):
            exts[i].fh = fh
            exts[i].offset = offset
            exts[i].length = length
        rids = (ctypes.c_int64 * n)()
        if ring is None and self.supervisor is not None:
            # scheduler-less batches (single ring, STROM_SCHED=0) still
            # avoid rings with an open breaker
            ring = self.supervisor.pick_ring()
        if ring is None:
            rc = self._lib.strom_submit_readv(self._h, exts, n, rids)
        else:
            rc = self._lib.strom_submit_readv_ring(self._h, ring, exts,
                                                   n, rids)
        if rc < 0:
            raise OSError(-rc, os.strerror(-rc))
        if self._stripe:
            for fh, offset, length in reads:
                self._attr_stripe(fh, offset, length)
        out = [PendingRead(self, int(rids[i]), reads[i][2],
                           fh=reads[i][0], offset=reads[i][1])
               for i in range(n)]
        if self.tracer is not None and self.tracer.enabled:
            from nvme_strom_tpu.utils.trace import attach_context
            for p in out:
                p.trace_ctx = attach_context()
        return out

    def submit_readv(self, reads, klass: Optional[str] = None,
                     ring: Optional[int] = None) -> list:
        """Vectored submission: one C call, one io_uring doorbell for the
        whole batch (``strom_submit_readv``).

        ``reads``: sequence of ``(fh, offset, length)``.  Returns one
        PendingRead per input extent, in order — each waits/releases
        exactly like a ``submit_read`` result.  Validation is atomic:
        on ValueError/OSError nothing was submitted.  This is the L2
        boundary the extent-coalescing planner (io/plan.py) submits
        through; calling it directly is fine for pre-split ranges.

        ``klass``: the batch's latency class.  On a sharded engine the
        QoS scheduler (io/sched.py) gates dispatch — the call may block
        behind higher classes under contention, exactly the admission
        control that protects decode-critical reads.  ``ring`` pins a
        ring and bypasses the scheduler (the scheduler's own dispatch
        path; also handy in tests).  Single-ring engines have no
        scheduler: every batch submits immediately, the pre-sharding
        behavior.
        """
        reads = list(reads)
        if not reads:
            return []
        chunk = self.config.chunk_bytes
        for fh, offset, length in reads:
            if length > chunk:
                raise ValueError(
                    f"read length {length} exceeds chunk_bytes "
                    f"{chunk}; split the range (io/plan.py does)")
        if self.scheduler is not None and ring is None:
            return self.scheduler.submit(reads, klass)
        out = self._submit_readv_ring(reads, ring)
        if klass is not None:
            for p in out:
                p.op_klass = klass   # flight-recorder attribution
        return out

    def read(self, fh: int, offset: int, length: int) -> np.ndarray:
        """Synchronous convenience read returning an *owning* array.

        The copy out of the staging buffer is counted as bounce bytes — use
        ``submit_read`` + the JAX bridge for the zero-copy path.
        """
        with self.submit_read(fh, offset, length) as p:
            out = p.wait().copy()
        self.stats.add(bounce_bytes=int(out.nbytes))
        return out

    # -- writes ------------------------------------------------------------

    def submit_write(self, fh: int, offset: int,
                     data: np.ndarray) -> PendingWrite:
        arr = np.ascontiguousarray(data).view(np.uint8).reshape(-1)
        ptr = arr.ctypes.data_as(ctypes.c_void_p)
        ring = (self.supervisor.pick_ring()
                if self.supervisor is not None else None)
        if ring is None:
            rid = self._lib.strom_submit_write(self._h, fh, offset, ptr,
                                               arr.nbytes)
        else:
            # checkpoint/KV writes route around rings with an open
            # breaker exactly like scalar reads do — a ResilientWrite
            # retry must never resubmit into the condemned domain
            rid = self._lib.strom_submit_write_ring(
                self._h, ring, fh, offset, ptr, arr.nbytes)
        if rid < 0:
            raise OSError(-rid, os.strerror(-rid))
        if self._stripe:
            self._attr_stripe(fh, offset, arr.nbytes)
        # staleness guard, submit side: a cached line overlapping a
        # write must never serve the pre-write bytes (kv/optimizer slot
        # rewrites read their pages back through the same planner);
        # PendingWrite invalidates AGAIN at completion — see wait()
        self._hostcache_write_done(fh, offset, arr.nbytes)
        pending = PendingWrite(self, rid, arr, fh=fh, offset=offset)
        if self.tracer is not None and self.tracer.enabled:
            from nvme_strom_tpu.utils.trace import attach_context
            pending.trace_ctx = attach_context()
        return pending

    def _hostcache_write_done(self, fh: int, offset: int,
                              length: int) -> None:
        """Drop host-tier lines overlapping a write and bump the file's
        invalidation epoch (voiding in-flight admitted fills) — called
        at write submit AND completion so no read/write interleaving
        can persist pre-write bytes in the tier."""
        fkey = self._file_keys.get(fh)
        if fkey is not None and length > 0:
            from nvme_strom_tpu.io.hostcache import notify_write
            notify_write(fkey, offset, length, stats=self.stats)

    # -- stats / lifecycle -------------------------------------------------

    def latency_histogram(self) -> dict:
        """Per-request submit→complete latency, log2-ns buckets: entry i of
        each list counts SUCCESSFUL requests whose latency fell in
        [2^i, 2^(i+1)) ns (failures are excluded — they complete near-
        instantly and would drag the percentiles down; count them via
        requests_failed).  The per-request upgrade over the reference's
        aggregate-only STAT_INFO counters (SURVEY.md §5 Tracing)."""
        rd = (ctypes.c_uint64 * _LAT_BUCKETS)()
        wr = (ctypes.c_uint64 * _LAT_BUCKETS)()
        self._lib.strom_get_latency(self._h, rd, wr)
        return {"read": [int(x) for x in rd], "write": [int(x) for x in wr]}

    def latency_percentiles(self, kind: str = "read",
                            ps=(50, 90, 99)) -> dict:
        """Approximate percentiles (ns) from the log2 histogram."""
        from nvme_strom_tpu.utils.stats import percentiles_from_log2_hist
        return percentiles_from_log2_hist(self.latency_histogram()[kind], ps)

    def pool_info(self) -> dict:
        """Staging-pool occupancy — LIST/INFO_GPU_MEMORY analogue
        (SURVEY.md §2 "GPU memory mapper")."""
        info = _PoolInfo()
        self._lib.strom_get_pool_info(self._h, ctypes.byref(info))
        return {n: int(getattr(info, n)) for n, _ in _PoolInfo._fields_}

    def engine_stats(self) -> dict:
        blk = _StatsBlk()
        self._lib.strom_get_stats(self._h, ctypes.byref(blk))
        return {n: int(getattr(blk, n)) for n, _ in _StatsBlk._fields_}

    def sync_stats(self) -> dict:
        """Atomically drain engine counters into the Python StromStats block
        (per-counter exchange in C — no increment can fall between read and
        reset)."""
        blk = _StatsBlk()
        self._lib.strom_drain_stats(self._h, ctypes.byref(blk))
        snap = {n: int(getattr(blk, n)) for n, _ in _StatsBlk._fields_}
        self.stats.merge_engine(snap)
        # Interval percentiles (diff vs the previous sync), matching the
        # per-interval semantics of the drained counters — a cumulative
        # histogram would bury a fresh latency regression under hours of
        # old samples.
        from nvme_strom_tpu.utils.stats import percentiles_from_log2_hist
        cur = self.latency_histogram()["read"]
        interval = [max(0, c - p)  # a reset_stats between syncs clamps to 0
                    for c, p in zip(cur, self._last_lat_read)]
        self._last_lat_read = cur
        pct = percentiles_from_log2_hist(interval, ps=(50, 99))
        if any(pct.values()):
            self.stats.set_gauges(lat_read_p50_us=pct[50] / 1000.0,
                                  lat_read_p99_us=pct[99] / 1000.0)
        if self.n_rings > 1:
            # instantaneous per-ring queue depth: the scheduler block in
            # strom_stat/watchdog reads these next to the sched counters
            self.stats.set_gauges(ring_depths=self.ring_depths())
        # ring time-in-state accounting (obs/ledger.py): sample at the
        # sync boundary too (an idle engine still accumulates idle
        # time), then publish the ring_state_s gauge every exporter
        # rides — and a Perfetto counter track when a trace is live, so
        # per-ring in-flight lands on the spans' own timeline
        self._sample_ring_states()
        self.ring_ledger.export(self.stats)
        if (self.n_rings > 1 and self.tracer is not None
                and getattr(self.tracer, "exports", False)):
            try:
                depths = self.ring_depths()
                # emit while I/O is in flight, plus ONE trailing all-
                # zero sample so the Perfetto track returns to zero —
                # and an idle engine's stat syncs add no events at all
                # (tests pin exact span counts around idle syncs)
                live = any(depths)
                if live or self._ring_counter_live:
                    self.tracer.add_counter(
                        "strom.ring.inflight",
                        {str(i): d for i, d in enumerate(depths)})
                self._ring_counter_live = live
            except OSError:
                pass
        # zero-copy submission state (docs/PERF.md §6): per-ring
        # fixed-buffer / registered-file / SQPOLL gauges, so a try_register
        # that silently soft-failed (old kernel, RLIMIT_MEMLOCK) shows in
        # strom_stat's engine block instead of only as missing throughput.
        # Served from the cache refreshed at create/restart — this state
        # only changes then, and the full strom_get_ring_info walk holds
        # each ring mutex over its request map, too heavy for a path the
        # watchdog and metrics writer hit at stat frequency.
        if self._zc_gauges is not None:
            self.stats.set_gauges(**self._zc_gauges)
        if self.supervisor is not None:
            # a stat sync is a natural supervision heartbeat, and the
            # health gauges (ring_health / engine_degraded) ride the
            # same export the counters do
            self.supervisor.tick()
        self.stats.maybe_export()  # keep strom_stat --watch observers live
        return snap

    @property
    def backend(self) -> str:
        return "io_uring" if self._lib.strom_backend_is_uring(self._h) \
            else "threadpool"

    def close_all(self) -> None:
        if self._closed:
            return
        if self._metrics_writer is not None:
            # detach BEFORE teardown (blocks on any in-flight periodic
            # drain, so no snapshot can touch the dying handle) — but
            # compare-and-clear: a later engine on the same shared
            # stats block may have installed ITS hook over ours
            self._metrics_writer.detach_sync(self.sync_stats)
            self._metrics_writer = None
        if self._debug_srv is not None:
            # the debug server outlives engines (process-wide); just
            # stop routing live-engine queries at this dying handle
            self._debug_srv.detach_engine(self)
            self._debug_srv = None
        if self.supervisor is not None:
            # release landed probe zombies and stop supervising before
            # the C handle dies under a tick's ring poll
            self.supervisor.close()
        if self.scheduler is not None:
            # wake any thread still blocked in a grant loop BEFORE the C
            # handle dies under its capacity poll (it raises ECANCELED)
            self.scheduler.close()
        self.sync_stats()  # drains counters and exports the final snapshot
        self._lib.strom_engine_destroy(self._h)
        self._closed = True
        self.spare_host_buffers.clear()
        if self._pool_slab is not None:
            # the staging carve returns to the arena only AFTER destroy
            # drained every in-flight DMA targeting it
            self._pool_slab.release()
            self._pool_slab = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close_all()

    def __del__(self):
        try:
            if not getattr(self, "_closed", True):
                self._lib.strom_engine_destroy(self._h)
                self._closed = True
                slab = getattr(self, "_pool_slab", None)
                if slab is not None:
                    slab.release()
                    self._pool_slab = None
        except Exception:
            pass

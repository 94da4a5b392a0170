"""Storage placement, once: for the checkout and a few other directories of
the machine, the file system's type, whether an O_DIRECT read succeeds,
whether fsync + POSIX_FADV_DONTNEED evicts, and how fast 1 GiB writes and
reads back; also whether the kernel offers io_uring.  Writes the table to
``chiprun_out/storage_probe.json``.  (PERF.md §5 holds the table.)"""

from __future__ import annotations

import ctypes
import json
import mmap
import os
import sys
import time

SIZE = 1 << 30


def _resident_share(path: str) -> float:
    libc = ctypes.CDLL(None, use_errno=True)
    size = os.path.getsize(path)
    with open(path, "rb") as f:
        m = mmap.mmap(f.fileno(), size, prot=mmap.PROT_READ)
    pages = (size + 4095) // 4096
    vec = (ctypes.c_ubyte * pages)()
    import numpy as np                   # the mapping's address, via a view
    arr = np.frombuffer(m, dtype=np.uint8)
    rc = libc.mincore(ctypes.c_void_p(arr.ctypes.data), ctypes.c_size_t(size),
                      vec)
    share = (sum(b & 1 for b in vec) / pages) if rc == 0 else float("nan")
    del arr
    m.close()
    return share


def probe(directory: str) -> dict:
    out = {"dir": directory}
    try:
        os.makedirs(directory, exist_ok=True)
        st = os.statvfs(directory)
        out["free_gib"] = st.f_bavail * st.f_frsize / 2**30
        with open("/proc/mounts") as f:
            best = ("", "?")
            for line in f:
                _, mnt, fstype = line.split()[:3]
                if os.path.realpath(directory).startswith(mnt) \
                        and len(mnt) > len(best[0]):
                    best = (mnt, fstype)
        out["mount"], out["fstype"] = best
        path = os.path.join(directory, "probe.bin")
        block = os.urandom(1 << 20) * 64
        t0 = time.monotonic()
        with open(path, "wb") as f:
            for _ in range(SIZE // len(block)):
                f.write(block)
        out["write_gib_s"] = 1.0 / (time.monotonic() - t0)
        t0 = time.monotonic()
        fd = os.open(path, os.O_RDONLY)
        os.fsync(fd)
        os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
        os.close(fd)
        out["fsync_s"] = time.monotonic() - t0
        try:
            out["resident_after_evict"] = _resident_share(path)
        except Exception as e:          # noqa: BLE001 (a probe reports)
            out["resident_after_evict"] = repr(e)
        t0 = time.monotonic()
        with open(path, "rb", buffering=0) as f:
            while f.read(1 << 24):
                pass
        out["read_after_evict_gib_s"] = 1.0 / (time.monotonic() - t0)
        t0 = time.monotonic()
        with open(path, "rb", buffering=0) as f:
            while f.read(1 << 24):
                pass
        out["read_again_gib_s"] = 1.0 / (time.monotonic() - t0)
        try:
            fd = os.open(path, os.O_RDONLY | os.O_DIRECT)
            m = mmap.mmap(-1, 1 << 20)
            n = os.readv(fd, [m])
            os.close(fd)
            out["o_direct_read"] = n == 1 << 20
        except OSError as e:
            out["o_direct_read"] = f"refused: {e}"
        os.unlink(path)
    except OSError as e:
        out["error"] = repr(e)
    return out


def main() -> int:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    dirs = [os.path.join(root, "benchmark", ".data"), "/tmp/strom_probe",
            "/var/tmp/strom_probe", "/dev/shm/strom_probe",
            os.path.join(os.environ.get("TMPDIR", "/tmp"), "strom_probe2")]
    table = [probe(d) for d in dirs]
    from nvme_strom_tpu.io import StromEngine
    eng = StromEngine()
    uring = {"engine_backend": eng.backend}
    eng.close_all()
    res = {"dirs": table, "io_uring": uring,
           "env": {k: os.environ.get(k) for k in
                   ("TMPDIR", "HOME", "XDG_CACHE_HOME",
                    "JAX_COMPILATION_CACHE_DIR")}}
    os.makedirs(os.path.join(root, "chiprun_out"), exist_ok=True)
    with open(os.path.join(root, "chiprun_out", "storage_probe.json"),
              "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

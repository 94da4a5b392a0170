"""strom-io engine tests: content verification of every transfer path.

The reference validates its DMA path by comparing SSD2GPU-read bytes against
pread() of the same range (SURVEY.md §4) — we do the same, for both the
io_uring and thread-pool backends, aligned and unaligned ranges, EOF edges,
and the write path.
"""

import hashlib
import os

import numpy as np
import pytest

from nvme_strom_tpu.io import (StromEngine, check_file, file_eligible,
                               file_extents, resolve_device)
from nvme_strom_tpu.utils.config import EngineConfig
from nvme_strom_tpu.utils.stats import StromStats


def _cfg(**kw):
    base = dict(chunk_bytes=1 << 20, queue_depth=8,
                buffer_pool_bytes=16 << 20)
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(params=["io_uring", "threadpool"])
def engine(request):
    cfg = _cfg(use_io_uring=request.param == "io_uring")
    with StromEngine(cfg, stats=StromStats()) as e:
        if request.param == "io_uring" and e.backend != "io_uring":
            pytest.skip("io_uring unavailable in this sandbox")
        yield e


def test_failed_build_raises_with_compiler_output(tmp_path, monkeypatch):
    """A checkout whose csrc/ does not compile fails at first use with
    the compiler's own message, not a bare CalledProcessError."""
    import shutil
    from nvme_strom_tpu.io import engine as eng
    src = tmp_path / "csrc"
    src.mkdir()
    shutil.copy(eng._CSRC / "Makefile", src)
    (src / "strom_io.h").write_text("")
    (src / "strom_io.cc").write_text(
        "#error strom build is broken on purpose\n")
    monkeypatch.setattr(eng, "_CSRC", src)
    monkeypatch.setattr(eng, "_LIB_PATH", src / "libstrom_io.so")
    monkeypatch.setattr(eng, "_lib", None)
    with pytest.raises(ImportError, match="broken on purpose"):
        eng._load_lib()


def test_check_file(tmp_data_file):
    path, payload = tmp_data_file
    info = check_file(path)
    assert info.size == len(payload)
    assert info.block_size > 0


def test_check_file_missing():
    with pytest.raises(OSError):
        check_file("/no/such/file")


def test_resolve_device(tmp_data_file):
    path, _ = tmp_data_file
    dev = resolve_device(path)
    # On a visible blockdev (ext4/xfs) the whole-disk name resolves; on
    # overlay/tmpfs it is empty — both are valid, but fields must be
    # internally consistent either way.
    if dev.device:
        assert "/" not in dev.device
        assert dev.rotational in (-1, 0, 1)
    else:
        assert not dev.nvme_backed and not dev.is_raid
    if dev.is_raid:
        assert len(dev.members) > 0
    else:
        assert dev.members == ()
        # plain device: verdict must equal the NVMe test
        if dev.device:
            assert dev.nvme_backed == dev.is_nvme
    if dev.nvme_backed and dev.is_raid:
        assert dev.raid_level == 0
        assert all(m.startswith("nvme") for m in dev.members)


def test_resolve_device_missing():
    with pytest.raises(OSError):
        resolve_device("/no/such/file")


def test_file_extents(tmp_data_file):
    path, payload = tmp_data_file
    exts = file_extents(path)
    assert len(exts) >= 1
    # extents cover the whole file (FIEMAP rounds up to fs blocks)
    assert sum(e.length for e in exts) >= len(payload)
    assert exts[0].logical == 0
    logicals = [e.logical for e in exts]
    assert logicals == sorted(logicals)
    if not exts[0].synthetic:
        # physically mapped extents carry device addresses
        assert all(e.physical > 0 for e in exts)


def test_file_extents_sparse_no_truncation(tmp_path):
    """A multi-extent (sparse) file must yield its COMPLETE map even when
    the initial buffer is too small — the C side returns -E2BIG and the
    wrapper grows, never silently truncating (reference never drops the
    extent tail either, SURVEY.md §3.1)."""
    p = tmp_path / "frag.bin"
    with open(p, "wb") as f:
        for i in range(6):
            f.seek(i * 65536)
            f.write(b"x" * 4096)
        f.flush()
        os.fsync(f.fileno())
    exts = file_extents(p, max_extents=1)
    if exts and exts[0].synthetic:
        pytest.skip("no FIEMAP on this filesystem")
    assert len(exts) == 6
    assert [e.logical for e in exts] == [i * 65536 for i in range(6)]


def test_file_extents_empty(tmp_path):
    p = tmp_path / "empty.bin"
    p.write_bytes(b"")
    assert file_extents(p) == []


def test_file_extents_missing():
    with pytest.raises(OSError):
        file_extents("/no/such/file")


def test_pool_info(engine, tmp_data_file):
    path, _ = tmp_data_file
    info = engine.pool_info()
    assert info["n_buffers"] == engine.n_buffers
    assert info["free_buffers"] == info["n_buffers"]
    assert info["pool_bytes"] >= info["n_buffers"] * info["buf_bytes"]
    fh = engine.open(path)
    p = engine.submit_read(fh, 0, 4096)
    p.wait()
    held = engine.pool_info()
    # one buffer is held by the un-released request
    assert held["free_buffers"] == info["n_buffers"] - 1
    assert held["in_flight"] == 1
    p.release()
    assert engine.pool_info()["free_buffers"] == info["n_buffers"]
    engine.close(fh)
    # fixed-buffer registration is reported (1 on io_uring backends with
    # kernel support; reads above verified content either way)
    assert info["fixed_bufs"] in (0, 1)
    if engine.backend != "io_uring":
        assert info["fixed_bufs"] == 0


def test_file_eligible_verdict(tmp_data_file):
    path, _ = tmp_data_file
    ok, fi, di = file_eligible(path)
    # the verdict is the AND of the two probes, like the reference's
    # CHECK_FILE (fs check + blockdev check, SURVEY.md §3.3)
    assert ok == bool(fi.supports_direct and di.nvme_backed)


def test_full_read_matches(engine, tmp_data_file):
    path, payload = tmp_data_file
    fh = engine.open(path)
    assert engine.file_size(fh) == len(payload)
    got = bytearray()
    step = engine.config.chunk_bytes
    for off in range(0, len(payload), step):
        n = min(step, len(payload) - off)
        with engine.submit_read(fh, off, n) as p:
            view = p.wait()
            assert view.nbytes == n
            got += view.tobytes()
    assert hashlib.sha256(got).hexdigest() == \
        hashlib.sha256(payload).hexdigest()
    engine.close(fh)


@pytest.mark.parametrize("off,ln", [
    (0, 4096),          # aligned
    (1, 4095),          # unaligned head
    (4095, 2),          # straddles a block boundary
    (123457, 99991),    # arbitrary unaligned
    (0, 1),             # single byte
])
def test_unaligned_ranges(engine, tmp_data_file, off, ln):
    path, payload = tmp_data_file
    fh = engine.open(path)
    with engine.submit_read(fh, off, ln) as p:
        assert p.wait().tobytes() == payload[off:off + ln]
    engine.close(fh)


def test_read_past_eof(engine, tmp_data_file):
    path, payload = tmp_data_file
    fh = engine.open(path)
    tail = len(payload) - 100
    with engine.submit_read(fh, tail, 1 << 20) as p:
        view = p.wait()
        assert view.tobytes() == payload[tail:]
    with engine.submit_read(fh, len(payload) + 4096, 4096) as p:
        assert p.wait().nbytes == 0
    engine.close(fh)


def test_many_inflight(engine, tmp_data_file):
    """Queue-depth stress: more requests than buffers, interleaved waits."""
    path, payload = tmp_data_file
    fh = engine.open(path)
    chunk = 128 << 10
    pend = [(off, engine.submit_read(fh, off, chunk))
            for off in range(0, 4 << 20, chunk)]
    for off, p in pend:
        assert p.wait().tobytes() == payload[off:off + chunk]
        p.release()
    engine.close(fh)


def test_stats_accounting(tmp_data_file):
    path, payload = tmp_data_file
    st = StromStats()
    with StromEngine(_cfg(), stats=st) as e:
        fh = e.open(path)
        total = 2 << 20
        for off in range(0, total, 1 << 20):
            with e.submit_read(fh, off, 1 << 20) as p:
                p.wait()
        e.close(fh)
        snap = e.engine_stats()
        assert snap["bytes_direct"] + snap["bytes_fallback"] == total
        assert snap["requests_submitted"] == 2
        assert snap["requests_completed"] == 2
        # direct path must contribute zero bounce bytes
        assert snap["bounce_bytes"] == snap["bytes_fallback"]
    assert st.total_payload_bytes == total


def test_copy_read_counts_bounce(tmp_data_file):
    path, payload = tmp_data_file
    st = StromStats()
    with StromEngine(_cfg(), stats=st) as e:
        fh = e.open(path)
        out = e.read(fh, 0, 4096)
        assert out.tobytes() == payload[:4096]
        assert st.bounce_bytes >= 4096
        e.close(fh)


def test_fallback_path_no_retry_storm(engine, tmp_data_file):
    """Buffered-mode files (fs rejects O_DIRECT, or the force_buffered debug
    knob): unaligned reads must take the buffered path exactly once — no
    rescue double-I/O, no retry counting.  Regression for the reaper
    success-check including alignment head on buffered submissions."""
    path, payload = tmp_data_file
    fh = engine.open(path, force_buffered=True)
    assert not engine.file_is_direct(fh)
    for off, ln in [(1, 4095), (4095, 100000), (0, 1 << 20)]:
        with engine.submit_read(fh, off, ln) as p:
            assert p.wait().tobytes() == payload[off:off + ln]
            assert p.was_fallback
    engine.close(fh)
    snap = engine.engine_stats()
    assert snap["retries"] == 0
    assert snap["bytes_fallback"] == snap["bounce_bytes"] > 0


def test_write_roundtrip(engine, tmp_path):
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=1 << 20, dtype=np.uint8)
    path = tmp_path / "out.bin"
    fh = engine.open(path, writable=True)
    # aligned zero-copy write
    n = engine.submit_write(fh, 0, data).wait()
    assert n == data.nbytes
    # unaligned bounce write
    tail = rng.integers(0, 256, size=1000, dtype=np.uint8)
    n = engine.submit_write(fh, data.nbytes, tail).wait()
    assert n == 1000
    engine.close(fh)
    on_disk = path.read_bytes()
    assert on_disk[:data.nbytes] == data.tobytes()
    assert on_disk[data.nbytes:] == tail.tobytes()


def test_write_then_read_same_engine(engine, tmp_path):
    data = np.arange(256 * 1024, dtype=np.uint8) % 251
    path = tmp_path / "rt.bin"
    fh = engine.open(path, writable=True)
    engine.submit_write(fh, 0, data).wait()
    with engine.submit_read(fh, 0, data.nbytes) as p:
        assert np.array_equal(p.wait(), data)
    engine.close(fh)


def test_oversized_read_rejected(engine, tmp_data_file):
    path, _ = tmp_data_file
    fh = engine.open(path)
    with pytest.raises(ValueError):
        engine.submit_read(fh, 0, engine.config.chunk_bytes + 1)
    engine.close(fh)


def test_release_before_wait_returns_buffer(engine, tmp_data_file):
    """release() on an in-flight request must wait then free — not leak.
    Regression: -EBUSY from strom_release was silently dropped."""
    path, payload = tmp_data_file
    fh = engine.open(path)
    n_cycles = 3 * engine.n_buffers
    for i in range(n_cycles):
        p = engine.submit_read(fh, 0, 64 << 10)
        p.release()  # no wait()
    # pool must still be fully usable
    with engine.submit_read(fh, 0, 4096) as p:
        assert p.wait().tobytes() == payload[:4096]
    engine.close(fh)


def test_destroy_with_inflight_requests(tmp_data_file):
    """Engine teardown must drain in-flight DMA before unmapping the pool."""
    path, _ = tmp_data_file
    for uring in (True, False):
        e = StromEngine(_cfg(use_io_uring=uring), stats=StromStats())
        fh = e.open(path)
        for i in range(8):
            e.submit_read(fh, i << 20, 1 << 20)  # never waited
        e.close_all()  # must not crash or hang


def test_write_bounce_counted_once(engine, tmp_path):
    """A staged (unaligned) write counts its payload as bounce exactly once."""
    path = tmp_path / "w.bin"
    fh = engine.open(path, writable=True)
    data = np.arange(1000, dtype=np.uint8)
    engine.submit_write(fh, 0, data).wait()  # unaligned len -> staged
    engine.close(fh)
    snap = engine.engine_stats()
    assert snap["bounce_bytes"] == 1000


def test_bad_handles(engine):
    with pytest.raises(OSError):
        engine.open("/no/such/file")
    with pytest.raises(OSError):
        engine.submit_read(9999, 0, 4096)


def test_residency_planned_reads(tmp_path):
    """VERDICT#4: a warm span is CHOSEN from the page cache (counted as
    bytes_resident, not a rescue); an evicted span goes O_DIRECT."""
    import os

    from conftest import evict_file
    from nvme_strom_tpu.io.engine import StromEngine
    from nvme_strom_tpu.utils.config import EngineConfig
    from nvme_strom_tpu.utils.stats import StromStats

    data = os.urandom(1 << 20)
    path = tmp_path / "resident.bin"
    path.write_bytes(data)          # buffered write: pages are in cache

    stats = StromStats()
    with StromEngine(EngineConfig(), stats=stats) as eng:
        fh = eng.open(str(path))
        if not eng.file_is_direct(fh):
            eng.close(fh)
            pytest.skip("fs rejects O_DIRECT; no plan to make")
        p = eng.submit_read(fh, 0, len(data))
        v = p.wait()
        assert bytes(v) == data
        p.release()
        eng.sync_stats()
        warm_resident = stats.bytes_resident
        warm_retries = stats.retries
        assert warm_resident == len(data)   # planned, full span
        assert warm_retries == 0            # ...and NOT an error-rescue

        # Evict (clean, synced pages) and read again: the probe must now
        # say non-resident and the read go O_DIRECT.
        evict_file(path)
        p = eng.submit_read(fh, 0, len(data))
        v = p.wait()
        assert bytes(v) == data
        p.release()
        eng.close(fh)
        eng.sync_stats()
        if stats.bytes_resident > warm_resident:
            pytest.skip("page cache not evictable in this environment")
        assert stats.bytes_direct >= len(data)


def test_concurrent_streams_one_engine(engine, tmp_path):
    """Config-8 requirement: N threads streaming distinct files through
    ONE engine — content-correct, no failures, all bytes accounted."""
    import threading

    import numpy as np

    n_streams, per = 4, 1 << 20
    rng = np.random.default_rng(11)
    payloads, paths = [], []
    for s in range(n_streams):
        data = rng.integers(0, 256, per, dtype=np.uint8).tobytes()
        p = tmp_path / f"s{s}.bin"
        p.write_bytes(data)
        payloads.append(data)
        paths.append(str(p))

    errors = []

    def stream(idx: int) -> None:
        try:
            fh = engine.open(paths[idx])
            got = bytearray()
            chunk = 256 << 10
            pend = []
            for off in range(0, per, chunk):
                pend.append(engine.submit_read(fh, off, chunk))
            for p in pend:
                v = p.wait()
                got.extend(bytes(v))
                p.release()
            engine.close(fh)
            if bytes(got) != payloads[idx]:
                errors.append(f"stream {idx}: payload mismatch")
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append(f"stream {idx}: {e!r}")

    threads = [threading.Thread(target=stream, args=(i,))
               for i in range(n_streams)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    engine.sync_stats()
    assert engine.stats.requests_failed == 0
    assert engine.stats.total_payload_bytes >= n_streams * per


def test_wait_timeout_detects_stalled_request(tmp_path):
    """Bounded wait (failure DETECTION): a request that cannot start —
    staging pool exhausted by unreleased peers — times out with the
    request still live, and completes once buffers free."""
    from nvme_strom_tpu.utils.config import EngineConfig
    path = str(tmp_path / "t.bin")
    data = np.random.default_rng(0).integers(
        0, 255, 64 << 10, dtype=np.uint8)
    with open(path, "wb") as f:
        f.write(data.tobytes())
    # pool of exactly 2 staging buffers
    cfg = EngineConfig(chunk_bytes=16 << 10, queue_depth=2,
                       buffer_pool_bytes=32 << 10)
    with StromEngine(cfg) as eng:
        fh = eng.open(path)
        hold = [eng.submit_read(fh, 0, 16 << 10),
                eng.submit_read(fh, 16 << 10, 16 << 10)]
        for p in hold:
            p.wait()          # both buffers now owned and NOT released
        starved = eng.submit_read(fh, 32 << 10, 16 << 10)
        with pytest.raises(TimeoutError, match="in flight"):
            starved.wait(timeout=0.25)
        # request stayed live: freeing a buffer lets it finish
        hold[0].release()
        view = starved.wait(timeout=10.0)
        np.testing.assert_array_equal(
            np.asarray(view), data[32 << 10:48 << 10])
        starved.release()
        hold[1].release()
        eng.close(fh)


# -- per-member stripe attribution (VERDICT r2 #8) --------------------------


def test_stripe_attr_matches_reference():
    """The C closed-form attribution equals a chunk-walk reference over
    random (phys, len, chunk, members) cases, and conserves bytes."""
    import numpy as np
    from nvme_strom_tpu.io.engine import stripe_attr

    def ref(phys, ln, chunk, n):
        out = [0] * n
        off, left = phys, ln
        while left:
            take = min(left, chunk - off % chunk)
            out[(off // chunk) % n] += take
            off += take
            left -= take
        return out

    rng = np.random.default_rng(0)
    for _ in range(200):
        chunk = int(rng.choice([4096, 65536, 524288]))
        n = int(rng.integers(1, 9))
        phys = int(rng.integers(0, 1 << 30))
        ln = int(rng.integers(0, 1 << 24))
        got = stripe_attr(phys, ln, chunk, n)
        assert got == ref(phys, ln, chunk, n)
        assert sum(got) == ln
    # degenerate inputs do nothing
    assert stripe_attr(0, 0, 4096, 4) == [0] * 4


def test_engine_stripe_accounting_sim(tmp_path, monkeypatch):
    """STROM_STRIPE_ACCT + simulated geometry: every submitted read's
    payload lands in per-member counters; an 8 MiB sequential scan over
    4 simulated members at 256 KiB chunks attributes exactly 2 MiB
    each (and the counters survive into snapshot()/strom_stat)."""
    import numpy as np
    from nvme_strom_tpu.io.engine import StromEngine
    from nvme_strom_tpu.utils.config import EngineConfig
    from nvme_strom_tpu.utils.stats import StromStats

    monkeypatch.setenv("STROM_STRIPE_ACCT", "1")
    monkeypatch.setenv("STROM_STRIPE_SIM", "256:4")
    path = tmp_path / "stripe.bin"
    path.write_bytes(np.random.default_rng(0).integers(
        0, 256, 8 << 20, dtype=np.uint8).tobytes())
    stats = StromStats()
    with StromEngine(EngineConfig(), stats=stats) as eng:
        fh = eng.open(path)
        prs = [eng.submit_read(fh, o, 1 << 20)
               for o in range(0, 8 << 20, 1 << 20)]
        for p in prs:
            p.wait()
            p.release()
        eng.close(fh)
    mb = stats.member_bytes
    assert set(mb) == {f"sim{i}" for i in range(4)}
    assert all(v == 2 << 20 for v in mb.values()), mb
    assert stats.snapshot()["member_bytes"] == mb
    # off by default: a fresh engine without the env attributes nothing
    monkeypatch.delenv("STROM_STRIPE_ACCT")
    stats2 = StromStats()
    with StromEngine(EngineConfig(), stats=stats2) as eng:
        fh = eng.open(path)
        with eng.submit_read(fh, 0, 4096) as p:
            p.wait()
        eng.close(fh)
    assert stats2.member_bytes == {}


def test_engine_stripe_accounting_writes(tmp_path, monkeypatch):
    """Write-path attribution (checkpoint inverse path on a striped
    rig): simulated geometry attributes written payload per member by
    logical offset, valid for growing files."""
    import numpy as np
    from nvme_strom_tpu.io.engine import StromEngine
    from nvme_strom_tpu.utils.config import EngineConfig
    from nvme_strom_tpu.utils.stats import StromStats

    monkeypatch.setenv("STROM_STRIPE_ACCT", "1")
    monkeypatch.setenv("STROM_STRIPE_SIM", "128:2")
    stats = StromStats()
    payload = np.random.default_rng(1).integers(
        0, 256, 1 << 20, dtype=np.uint8)
    path = tmp_path / "w.bin"
    with StromEngine(EngineConfig(), stats=stats) as eng:
        fh = eng.open(path, writable=True)
        eng.submit_write(fh, 0, payload).wait()
        eng.submit_write(fh, 1 << 20, payload).wait()
        eng.close(fh)
    mb = stats.member_bytes
    assert sum(mb.values()) == 2 << 20
    assert mb["sim0"] == mb["sim1"] == 1 << 20   # even 128KiB stripes


def test_wait_timeout_cancel_then_retry(tmp_data_file, monkeypatch):
    """The wait(timeout=...) contract, end to end against the C engine:
    after a TimeoutError the request is STILL LIVE — (a) retrying the
    wait returns the payload, and (b) release() cancels cleanly so a
    fresh submit of the same range succeeds (the cancel-then-retry
    recovery io/resilient.py builds on).  The C-level
    STROM_FAULT_READ_DELAY_MS hook holds every completion 150 ms so the
    timeout genuinely fires below Python."""
    path, payload = tmp_data_file
    monkeypatch.setenv("STROM_FAULT_READ_DELAY_MS", "150")
    with StromEngine(_cfg(), stats=StromStats()) as eng:
        fh = eng.open(path)
        # (a) timeout, then retry the wait on the SAME request
        p = eng.submit_read(fh, 0, 4096)
        with pytest.raises(TimeoutError, match="still in flight"):
            p.wait(timeout=0.01)
        assert p.wait().tobytes() == payload[:4096]
        p.release()
        # (b) timeout, cancel, resubmit the same range
        p2 = eng.submit_read(fh, 4096, 4096)
        with pytest.raises(TimeoutError):
            p2.wait(timeout=0.01)
        p2.release()     # blocks until out of flight, then frees
        p3 = eng.submit_read(fh, 4096, 4096)
        assert p3.wait().tobytes() == payload[4096:8192]
        p3.release()
        eng.close(fh)


# ---------------------------------------------------------------------------
# Zero-copy submission modes (PR 12): SQPOLL, registered files, gauges
# ---------------------------------------------------------------------------

@pytest.mark.perf
def test_sqpoll_elides_submission_doorbells(tmp_data_file, monkeypatch):
    """STROM_SQPOLL=1: steady-state submissions skip the dispatch
    doorbell (io_uring_enter on a uring ring; the wakeup notify on the
    worker-pool analogue) — counted in submit_syscalls_saved while
    submit_enters stays near zero."""
    path, payload = tmp_data_file
    monkeypatch.setenv("STROM_SQPOLL", "1")
    monkeypatch.setenv("STROM_SQPOLL_IDLE_MS", "200")
    stats = StromStats()
    n = 16
    with StromEngine(_cfg(queue_depth=4, n_rings=1), stats=stats) as e:
        assert e.ring_info(0)["sqpoll"] == 1
        fh = e.open(path)
        for i in range(n):
            with e.submit_read(fh, i * 4096, 4096) as p:
                assert p.wait().tobytes() == \
                    payload[i * 4096:(i + 1) * 4096]
        e.close(fh)
        blk = e.engine_stats()
        # the poller consumed (nearly) every submission without a
        # doorbell; allow a few wakeups for pollers that idled out
        assert blk["submit_syscalls_saved"] >= n // 2
        assert blk["submit_enters"] < n
        assert blk["submit_enters"] + blk["submit_syscalls_saved"] >= n


@pytest.mark.perf
def test_sqpoll_off_switch_bit_for_bit(tmp_data_file, monkeypatch):
    """STROM_SQPOLL unset/0 is today's engine exactly: every dispatch
    rings its doorbell (enters == reads on the worker pool), zero
    elisions, same bytes."""
    path, payload = tmp_data_file

    def read_all(n, want_sqpoll=0):
        stats = StromStats()
        out = []
        with StromEngine(_cfg(queue_depth=4, n_rings=1),
                         stats=stats) as e:
            assert e.ring_info(0)["sqpoll"] == want_sqpoll
            fh = e.open(path)
            for i in range(n):
                with e.submit_read(fh, i * 8192, 8192) as p:
                    out.append(p.wait().tobytes())
            e.close(fh)
            blk = e.engine_stats()
        return out, blk

    monkeypatch.setenv("STROM_SQPOLL", "0")
    off_bytes, off_blk = read_all(8)
    assert off_bytes == [payload[i * 8192:(i + 1) * 8192]
                         for i in range(8)]
    if not off_blk["submit_batches"]:
        # scalar worker-pool reads: one doorbell each, none saved
        assert off_blk["submit_syscalls_saved"] == 0
    monkeypatch.setenv("STROM_SQPOLL", "1")
    on_bytes, _on_blk = read_all(8, want_sqpoll=1)
    assert on_bytes == off_bytes


@pytest.mark.perf
def test_reg_files_off_switch_bit_for_bit(tmp_data_file, monkeypatch):
    """STROM_REG_FILES=0 disables the slot table; reads are identical
    and the per-ring gauge reports unregistered."""
    path, payload = tmp_data_file

    def read_some():
        with StromEngine(_cfg(queue_depth=4, n_rings=1),
                         stats=StromStats()) as e:
            fh = e.open(path)
            prs = e.submit_readv([(fh, i * 65536, 65536)
                                  for i in range(4)])
            got = [p.wait().tobytes() for p in prs]
            for p in prs:
                p.release()
            info = e.ring_info(0)
            e.close(fh)
        return got, info

    monkeypatch.setenv("STROM_REG_FILES", "0")
    off_got, off_info = read_some()
    assert off_info["reg_files"] == 0
    monkeypatch.delenv("STROM_REG_FILES")
    on_got, on_info = read_some()
    assert on_got == off_got == [payload[i * 65536:(i + 1) * 65536]
                                 for i in range(4)]
    # threadpool backend has no slot table either way; a uring backend
    # must register when enabled (soft-fail tolerated on old kernels)
    assert on_info["reg_files"] in (0, 1)


@pytest.mark.perf
def test_sync_stats_exports_zero_copy_gauges(tmp_data_file):
    stats = StromStats()
    with StromEngine(_cfg(queue_depth=4), stats=stats) as e:
        fh = e.open(tmp_data_file[0])
        with e.submit_read(fh, 0, 4096) as p:
            p.wait()
        e.close(fh)
        e.sync_stats()
        snap = stats.snapshot()
    for key in ("ring_fixed_bufs", "ring_reg_files", "ring_sqpoll"):
        assert key in snap and len(snap[key]) == e.n_rings
        assert all(v in (0, 1) for v in snap[key])
    assert snap.get("pool_arena") in (0, 1)
    assert "submit_enters" in snap


@pytest.mark.perf
def test_ring_restart_under_sqpoll(tmp_data_file, monkeypatch):
    """PR-10 contract under SQPOLL: stall → park → hot restart cancels
    the backlog (-ECANCELED requeue), and the rebuilt ring serves —
    with SQPOLL still active after the rebuild."""
    path, payload = tmp_data_file
    monkeypatch.setenv("STROM_SQPOLL", "1")
    monkeypatch.setenv("STROM_BREAKER", "0")   # drive the C layer bare
    with StromEngine(_cfg(queue_depth=4, n_rings=1),
                     stats=StromStats()) as e:
        fh = e.open(path)
        e.set_ring_stall(0, True)
        p = e.submit_read(fh, 0, 4096)
        cancelled = e.ring_restart(0, drain_timeout_s=2.0)
        assert cancelled == 1
        with pytest.raises(OSError):
            p.wait()
        p.release()
        assert e.ring_info(0)["sqpoll"] == 1   # mode survived the rebuild
        with e.submit_read(fh, 4096, 4096) as p2:
            assert p2.wait().tobytes() == payload[4096:8192]
        e.close(fh)

"""Tests for the L3 CLI utilities (ssd2tpu_test, strom_stat) —
the analogues of the reference's benchmark + stat tools (SURVEY.md §2/§3.4).
"""

import json
import os

import numpy as np
import pytest

from nvme_strom_tpu.tools import ssd2tpu_test, strom_stat


@pytest.fixture
def data_file(tmp_path):
    path = tmp_path / "payload.bin"
    rng = np.random.default_rng(7)
    path.write_bytes(rng.integers(0, 256, 3 * (1 << 20) + 777,
                                  dtype=np.uint8).tobytes())
    return path


def _run(capsys, argv):
    rc = ssd2tpu_test.main(argv)
    out = capsys.readouterr().out.strip().splitlines()[-1]
    return rc, json.loads(out)


def test_ssd2tpu_host_verify(capsys, data_file):
    rc, res = _run(capsys, [str(data_file), "--chunk-bytes", str(1 << 20),
                            "--depth", "3", "--verify"])
    assert rc == 0
    assert res["verify"] == "ok"
    assert res["bytes"] == data_file.stat().st_size
    assert res["gib_per_s"] > 0
    assert res["stats"]["requests_failed"] == 0


def test_ssd2tpu_chunk_byte_exact(capsys, data_file):
    rc, res = _run(capsys, [str(data_file), "--chunk-bytes", str(1 << 20),
                            "--verify-pread", "--depth", "2"])
    assert rc == 0
    assert res["verify"] == "ok"


def test_ssd2tpu_device_dest(capsys, data_file):
    rc, res = _run(capsys, [str(data_file), "--dest", "device",
                            "--chunk-bytes", str(1 << 20), "--verify"])
    assert rc == 0
    assert res["verify"] == "ok"
    assert res["stats"]["bytes_to_device"] >= data_file.stat().st_size


def test_ssd2tpu_total_bytes_cap(capsys, data_file):
    rc, res = _run(capsys, [str(data_file), "--total-bytes", str(1 << 20),
                            "--chunk-bytes", str(256 << 10)])
    assert rc == 0
    assert res["bytes"] == 1 << 20


def test_ssd2tpu_generates_file(capsys, tmp_path):
    rc, res = _run(capsys, ["--make-bytes", str(1 << 20), "--tmpdir",
                            str(tmp_path), "--verify"])
    assert rc == 0
    assert res["verify"] == "ok"
    assert not os.path.exists(res["file"])  # cleaned up without --keep


def test_stats_export_and_strom_stat(capsys, data_file, tmp_path,
                                     monkeypatch):
    export = tmp_path / "strom_stats.json"
    monkeypatch.setenv("STROM_STATS_EXPORT", str(export))

    from nvme_strom_tpu.io.engine import StromEngine
    from nvme_strom_tpu.utils.stats import StromStats

    with StromEngine(stats=StromStats()) as eng:
        fh = eng.open(data_file)
        with eng.submit_read(fh, 0, 4096) as p:
            assert p.wait().nbytes == 4096
        eng.close(fh)
    assert export.exists()

    rc = strom_stat.main([str(export)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "requests_completed" in out

    rc = strom_stat.main([str(export), "--json"])
    out = capsys.readouterr().out
    assert rc == 0
    snap = json.loads(out)
    assert snap["requests_completed"] >= 1
    # North star in the residency-planning regime: every host copy is a
    # PLANNED page-cache read (the data_file fixture is freshly written,
    # hence warm) — unplanned bounce stays zero.
    assert snap["bounce_bytes"] == snap["bytes_resident"]
    assert snap["retries"] == 0


def test_strom_stat_missing_file(capsys, tmp_path, monkeypatch):
    monkeypatch.delenv("STROM_STATS_EXPORT", raising=False)
    assert strom_stat.main([]) == 2
    assert strom_stat.main([str(tmp_path / "absent.json")]) == 2


def test_strom_stat_device_topology(capsys, tmp_path):
    """--device prints the backing blockdev walk (raid members when
    striped) — the observable form of the reference's md-raid0 check."""
    rc = strom_stat.main(["--device", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "device topology" in out
    # Either a real blockdev (with the DMA-eligibility verdict) or an
    # honest no-blockdev report on overlay/tmpfs.
    assert ("direct-DMA eligible" in out
            or "no visible backing blockdev" in out)


def test_transfer_diag_alias_proof(capsys):
    """The zero-copy claim's evidence: a wait() view's data pointer lies
    inside the mlock'd staging pool, 4 KiB-aligned (VERDICT weak #3 —
    instrumentation for the device boundary)."""
    from nvme_strom_tpu.tools import transfer_diag
    res = transfer_diag.run(1 << 20, repeats=2)
    assert res["view_in_pool"] is True
    assert res["view_aligned"] is True
    assert res["verdict"] == "zero-copy to PJRT boundary"
    assert res["t_staging_s"] > 0 and res["t_copy_heap_s"] > 0


def test_transfer_diag_sweep_json_lines(capsys):
    """``--sizes`` / ``--threads`` / ``--devices``: one JSON line a
    (size, thread count) after the alias line, each with the put's
    time to return, time to ready and GiB/s to ready, sources in the
    staging pool (the host→HBM ceiling of PERF.md §5; here the CPU
    platform, mechanics only)."""
    import json
    from nvme_strom_tpu.tools import transfer_diag
    rc = transfer_diag.main(["--bytes", "65536", "--repeats", "2",
                             "--sizes", "65536,100000", "--threads", "1,2",
                             "--devices", "2", "--gil-seconds", "0.05"])
    assert rc == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert lines[0]["view_in_pool"] is True and "sweep" not in lines[0]
    rows = lines[1:]
    # 100000 rounds up to the engine's alignment
    assert [(r["bytes"], r["threads"]) for r in rows] == [
        (65536, 1), (65536, 2), (102400, 1), (102400, 2)]
    for r in rows:
        assert r["sweep"] is True and r["platform"] == "cpu"
        assert r["devices"] == 2 and r["repeats"] == 2
        assert 0 < r["return_us"] <= r["ready_us"]
        assert r["gib_s"] > 0 and r["gib_s_blocking"] > 0
        assert r["puts_per_s"] > 0 and 0 <= r["gil_held_share"] <= 1
    # without --gil-seconds the probe's keys are left out, not zero
    (row,) = transfer_diag.sweep([65536], threads=(1,), repeats=2)
    assert "gil_held_share" not in row and "puts_per_s" not in row


def test_transfer_diag_sweep_reads_heap_and_batched_sources():
    """``--sources``: the same pairs out of a reused numpy buffer a
    thread (what an assembled put reads) and as one batched call over
    all the devices; the default is staging views alone."""
    from nvme_strom_tpu.tools import transfer_diag
    rows = transfer_diag.sweep([65536], threads=(1, 2), n_devices=2,
                               repeats=2,
                               sources=("staging", "heap", "batched"))
    assert [(r["source"], r["threads"]) for r in rows] == [
        ("staging", 1), ("staging", 2), ("heap", 1), ("heap", 2),
        ("batched", 1)]
    for r in rows:
        assert r["bytes"] == 65536 and r["devices"] == 2
        assert 0 < r["return_us"] <= r["ready_us"] and r["gib_s"] > 0
        assert ("first_touch_us" in r) == (r["source"] == "heap")
    (row,) = transfer_diag.sweep([65536], threads=(1,), repeats=2)
    assert row["source"] == "staging"


def test_strom_stat_renders_a_restores_puts_by_kind():
    """The engine block lists a weight restore's ``device_put``s: out of
    staging views by a worker / on the reading thread, and of assembled
    column shards (ops/bridge.HostAssembly)."""
    from nvme_strom_tpu.tools.strom_stat import render
    from nvme_strom_tpu.utils.stats import StromStats
    stats = StromStats()
    stats.add(restore_puts_staged=1412, restore_puts_assembled=648)
    out = render(stats.snapshot())
    for name, value in (("restore_puts_staged", "1412"),
                        ("restore_puts_inline", "0"),
                        ("restore_puts_assembled", "648")):
        (line,) = [ln for ln in out.splitlines() if name in ln]
        assert line.split()[-1] == value


def test_strom_stat_renders_member_bytes(capsys):
    """Per-member attribution shows up in the CLI render with shares."""
    from nvme_strom_tpu.tools.strom_stat import render
    out = render({"bytes_direct": 4096, "bounce_bytes": 0,
                  "member_bytes": {"nvme0n1": 3 << 20, "nvme1n1": 1 << 20}})
    assert "per-member payload" in out
    assert "nvme0n1" in out and "75.0%" in out
    assert "nvme1n1" in out and "25.0%" in out


def test_strom_stat_renders_kv_serving_block():
    """The serving prefix-store counters get their own block: hit
    rate, dedupe savings, restore p99 — and stay invisible on a run
    with no store traffic."""
    from nvme_strom_tpu.tools.strom_stat import render
    out = render({"bytes_direct": 4096, "bounce_bytes": 0,
                  "kv_prefix_hits": 30, "kv_prefix_misses": 10,
                  "kv_pages_deduped": 12, "kv_bytes_saved": 3 << 20,
                  "kv_pages_written": 4, "kv_pages_restored": 30,
                  "kv_store_pages_resident": 4,
                  "kv_restore_p99_ms": 12.5})
    assert "kv serving" in out
    assert "kv_pages_deduped" in out and "12" in out
    assert "3.00 MiB" in out                  # kv_bytes_saved humanized
    assert "0.750" in out                     # prefix hit rate
    assert "12.50 ms" in out                  # restore p99
    quiet = render({"bytes_direct": 4096, "bounce_bytes": 0})
    assert "kv serving" not in quiet


def test_strom_stat_json_carries_kv_counters(capsys, tmp_path,
                                             monkeypatch):
    """--json round-trips the kv_* counters an exporting engine
    wrote (the fleet-tooling contract of the satellite)."""
    import json as _json
    from nvme_strom_tpu.utils.stats import StromStats
    export = tmp_path / "stats.json"
    monkeypatch.setenv("STROM_STATS_EXPORT", str(export))
    st = StromStats()
    st.add(kv_prefix_hits=5, kv_pages_deduped=2, kv_bytes_saved=1024)
    st.set_gauges(kv_restore_p99_ms=7.25)
    st.maybe_export()
    rc = strom_stat.main([str(export), "--json"])
    out = capsys.readouterr().out
    assert rc == 0
    snap = _json.loads(out)
    assert snap["kv_prefix_hits"] == 5
    assert snap["kv_pages_deduped"] == 2
    assert snap["kv_restore_p99_ms"] == 7.25


def test_watchdog_dump_carries_kv_serving_line():
    """A watchdog timeout dump includes the kv-serving line when the
    store saw traffic (and omits it otherwise)."""
    import io as _io
    import time as _time
    from nvme_strom_tpu.utils.stats import StromStats
    from nvme_strom_tpu.utils.watchdog import StepWatchdog

    class Eng:
        def __init__(self, stats):
            self.stats = stats

        def sync_stats(self):
            return {}

    for traffic, expect in ((True, True), (False, False)):
        st = StromStats()
        if traffic:
            st.add(kv_prefix_hits=3, kv_pages_restored=3,
                   kv_pages_written=2)
        stream = _io.StringIO()
        wd = StepWatchdog(deadline_s=0.05, engine=Eng(st),
                          stream=stream, max_reports=1)
        with wd.step("kv"):
            _time.sleep(0.2)
        wd.close()
        dump = stream.getvalue()
        assert "watchdog" in dump
        assert ("kv serving:" in dump) is expect, dump



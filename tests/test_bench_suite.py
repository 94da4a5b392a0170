"""bench_suite.py: every BASELINE config runs end-to-end and emits a
well-formed result (tiny sizes, CPU backend)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def test_suite_all_configs(tmp_path):
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               STROM_SUITE_BYTES=str(8 << 20),
               STROM_SUITE_TINY_COMPUTE="1",
               STROM_BENCH_DIR=str(tmp_path))
    r = subprocess.run(
        [sys.executable, str(REPO / "bench_suite.py"), "--all"],
        capture_output=True, text=True, timeout=540, env=env,
        cwd=str(REPO))
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 25, r.stdout
    units = {1: "GiB/s", 2: "GiB/s", 3: "GiB/s", 4: "GiB/s", 5: "GiB/s",
             6: "tok/s", 7: "TFLOP/s", 8: "GiB/s", 9: "GiB/s",
             10: "tok/s", 11: "tok/s", 12: "GiB/s", 13: "GiB/s",
             14: "GiB/s", 15: "GiB/s", 16: "Mmembers/s",
             17: "TFLOP/s", 18: "GiB/s", 19: "tok/s", 20: "GiB/s",
             21: "GiB/s", 22: "x", 23: "GiB/s", 24: "x", 25: "x"}
    for i, ln in enumerate(lines, start=1):
        rec = json.loads(ln)
        assert set(rec) == {"metric", "value", "unit", "vs_baseline",
                            "platform", "device_kind", "device_count"}
        # JAX_PLATFORMS=cpu was asked for by name: every row says so
        assert rec["platform"] == "cpu" and rec["device_count"] >= 1
        assert rec["metric"].startswith(f"config{i}:")
        assert rec["value"] > 0
        assert rec["unit"] == units[i]
        # CPU-pinned run: vs_baseline must be null on I/O rows (the north
        # star is only measurable on a real TPU — round-1 verdict honesty
        # fix); compute rows (6–7) have no baseline target at all.
        assert rec["vs_baseline"] is None
    # scratch data landed in the requested dir, not the repo
    assert (tmp_path / ".bench_suite").is_dir()


def test_per_pass_link_pairing(tmp_path, monkeypatch):
    """On a live device the suite ratios every _steady pass against its
    own interleaved link burst (a shared link drifts within a step, so
    a step-start ceiling pairs a pass with the wrong minute); the
    metric tag carries the per-pass pairs.  Simulated here by reporting
    the CPU backend's device as a TPU."""
    import bench_suite
    from nvme_strom_tpu.utils import device
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setenv("STROM_SUITE_BYTES", str(4 << 20))
    monkeypatch.setenv("STROM_BENCH_DIR", str(tmp_path))
    monkeypatch.setattr(device, "device_info", lambda: {
        "platform": "tpu", "device_kind": "faked", "device_count": 1})
    rows = bench_suite.run([2])
    rec = rows[0]
    assert rec["platform"] == "tpu" and rec["device_kind"] == "faked"
    assert rec["vs_baseline"] is not None
    assert "per-pass rate@link=" in rec["metric"]
    pairs = bench_suite._PASS_LINK["last"]
    assert pairs and all(l > 0 for _, l in pairs)
    assert bench_suite._PASS_LINK["probe"] is None   # cleared by run()


@pytest.mark.parametrize("command", ["bench", "bench_suite"])
def test_measuring_command_without_tpu_exits_nonzero(tmp_path,
                                                     monkeypatch,
                                                     command):
    """No TPU and no JAX_PLATFORMS=cpu from the caller: a measuring
    command refuses to run (non-zero exit, no result row) instead of
    measuring another backend under the chip's name."""
    import bench
    import bench_suite
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setenv("STROM_BENCH_DIR", str(tmp_path))
    run = bench.main if command == "bench" else \
        (lambda: bench_suite.run([1]))
    with pytest.raises(SystemExit) as e:
        run()
    assert e.value.code not in (0, None)
    assert "no TPU found" in str(e.value.code)
    assert not list(tmp_path.iterdir())     # refused before any work

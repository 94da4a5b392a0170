"""Concurrency stress + race detection (SURVEY.md §5 "Race detection").

Runs the C++ stress harness (csrc/stress_test.cc): many reader threads
with payload verification, a writer, an open/close churn thread and a
stats observer all hammering one engine.  The TSAN build turns any data
race into a hard failure.
"""

import shutil
import subprocess
from pathlib import Path

import pytest

CSRC = Path(__file__).resolve().parents[1] / "csrc"


def _build(target: str) -> Path:
    # Missing toolchain -> skip; a COMPILE error must FAIL, or a refactor
    # that breaks the harness silently disables race coverage.
    if shutil.which("g++") is None or shutil.which("make") is None:
        pytest.skip("no C++ toolchain")
    r = subprocess.run(["make", "-C", str(CSRC), target],
                       capture_output=True, text=True)
    assert r.returncode == 0, f"build of {target} failed:\n{r.stderr[-2000:]}"
    return CSRC / target


def test_stress_plain(tmp_path):
    binary = _build("stress_test")
    r = subprocess.run([str(binary), "150", "4", str(tmp_path)],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "errors=0" in r.stderr


def test_stress_tsan(tmp_path):
    binary = _build("stress_test_tsan")
    # a healthy run takes 1.5-3 s, on a loaded machine too; a hang costs
    # a worker two minutes, not ten
    r = subprocess.run([str(binary), "60", "3", str(tmp_path)],
                       capture_output=True, text=True, timeout=120,
                       env={"PATH": "/usr/bin:/bin",
                            "TSAN_OPTIONS": "halt_on_error=0 exitcode=66"})
    assert r.returncode == 0, r.stderr[-3000:]
    assert "WARNING: ThreadSanitizer" not in r.stderr

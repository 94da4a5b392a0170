"""The repository has one benchmark, ``benchmark/``: nothing names the
measuring stack of PRs 1-20 (deleted in PR 46) — no import, no command in a
document, none of its environment variables."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GONE = ["bench.py", "bench_suite.py", "chip_smoke.py",
        "nvme_strom_tpu/tools/profile_report.py",
        "nvme_strom_tpu/tools/bench_gate.py",
        "nvme_strom_tpu/tools/stream_probe.py",
        "nvme_strom_tpu/utils/tuning.py"]

#: a module of the old stack imported or named, or one of its variables
NAMES = re.compile(
    r"import bench\b|bench_suite|chip_smoke|profile_report|bench_gate|"
    r"bench-gate|stream_probe|utils\.tuning|utils/tuning|STROM_BENCH_")

#: where nothing may name them (``CHANGES.md``, ``ROADMAP.md`` and
#: ``PERF.md`` tell the history; ``benchmark/`` is the driver's)
ROOTS = ["nvme_strom_tpu", "csrc", "examples", "tests", "docs",
         "README.md", "ARCHITECTURE.md", "pyproject.toml",
         "__graft_entry__.py", ".gitignore"]


def _files(root):
    path = os.path.join(REPO, root)
    if os.path.isfile(path):
        yield path
        return
    for folder, dirs, names in os.walk(path):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in names:
            if name.endswith((".py", ".md", ".cc", ".h", ".toml", ".json",
                              ".txt", ".cfg")) or name == "Makefile":
                yield os.path.join(folder, name)


@pytest.mark.parametrize("root", ROOTS)
def test_nothing_names_the_deleted_stack(root):
    found = []
    for path in _files(root):
        if os.path.abspath(path) == os.path.abspath(__file__):
            continue
        with open(path, encoding="utf-8", errors="replace") as f:
            found += [f"{os.path.relpath(path, REPO)}:{i}: {line.strip()}"
                      for i, line in enumerate(f, 1) if NAMES.search(line)]
    assert not found, "\n".join(found[:20])


@pytest.mark.parametrize("path", GONE)
def test_the_file_is_gone(path):
    assert not os.path.exists(os.path.join(REPO, path))

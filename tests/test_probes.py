"""The measuring commands that stay — ``kernel_probe paged``, ``kernel_probe
ssm``, ``kernel_probe gdn_scan`` and ``transfer_diag`` — measure on the chip
or not at all: without a TPU they exit non-zero and print no result, unless
the caller asked for the CPU by name (``utils/device.require_tpu``), and then
every line they print says which platform it was.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBES = {
    "paged": ["nvme_strom_tpu.tools.kernel_probe", "paged", "m7b.chat"],
    "ssm": ["nvme_strom_tpu.tools.kernel_probe", "ssm"],
    "gdn_scan": ["nvme_strom_tpu.tools.kernel_probe", "gdn_scan", "olmoh"],
    "transfer_diag": ["nvme_strom_tpu.tools.transfer_diag",
                      "--bytes", "65536", "--repeats", "2",
                      "--sizes", "65536", "--threads", "1,2",
                      "--devices", "2"],
}


def _main(argv, monkeypatch):
    """The command's ``main`` in this process, as ``python -m`` calls it."""
    import importlib
    monkeypatch.setattr(sys, "argv", argv)
    return importlib.import_module(argv[0]).main()


@pytest.mark.parametrize("probe", sorted(PROBES))
def test_probe_without_a_tpu_exits_nonzero_and_prints_no_result(probe):
    """No TPU here and no ``JAX_PLATFORMS`` from the caller: JAX falls back
    to the CPU by itself, and a probe that went on would print a CPU's times
    under the chip's commands."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    r = subprocess.run([sys.executable, "-m", *PROBES[probe]], cwd=REPO,
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU found" in r.stderr


@pytest.mark.parametrize("probe", sorted(PROBES))
def test_probe_on_the_cpu_by_name_says_so_on_every_line(probe, monkeypatch,
                                                        capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")      # as conftest.py set it
    assert _main(PROBES[probe], monkeypatch) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert lines
    for line in lines:
        assert line["platform"] == "cpu"
        assert line["device_kind"] and line["device_count"] >= 1


@pytest.mark.parametrize("argv", [[], ["attn"], ["roof"]],
                         ids=["no_mode", "attn", "roof"])
def test_kernel_probe_names_its_modes(argv, monkeypatch, capsys):
    """Called with no mode, or with one it does not have, it says what it
    has and exits non-zero before it touches a device."""
    rc = _main(["nvme_strom_tpu.tools.kernel_probe", *argv], monkeypatch)
    out, err = capsys.readouterr()
    assert rc == 2 and out == ""
    assert "paged" in err and "ssm" in err and "gdn_scan" in err

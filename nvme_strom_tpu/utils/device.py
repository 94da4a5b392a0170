"""Which device this process runs on — named on every line that reports
a measurement, and required to be a TPU by every measuring command.

``JAX_PLATFORMS`` from the environment is the only selector: nothing in
the repo re-pins the platform in code, probes the device in a child
process, or falls back to another backend when the chip is missing.
"""

from __future__ import annotations

import os


def device_info() -> dict:
    """``platform`` / ``device_kind`` / ``device_count`` as JAX reports
    them (initialises the backend)."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def device_line() -> str:
    """The first line an entry point prints."""
    d = device_info()
    return (f"device: platform={d['platform']} "
            f"device_kind={d['device_kind']!r} count={d['device_count']}")


def require_tpu(what: str) -> dict:
    """``device_info()`` for a measuring command: exits non-zero when no
    TPU is found, unless the caller asked for the CPU by name
    (``JAX_PLATFORMS=cpu`` — the functional path tests drive; every
    line such a run prints then says ``platform: cpu``)."""
    info = device_info()
    if info["platform"] != "tpu" and \
            os.environ.get("JAX_PLATFORMS", "").lower() != "cpu":
        raise SystemExit(
            f"{what}: no TPU found (jax.devices()[0].platform="
            f"{info['platform']!r}); measurements run on the chip only — "
            "set JAX_PLATFORMS=cpu to drive the functional path on the "
            "CPU")
    return info

"""Test harness: run everything on a virtual 8-device CPU mesh.

Tests never take the chip: ``JAX_PLATFORMS=cpu`` (the environment is the
only platform selector in this repo), multi-chip sharding is validated on
``--xla_force_host_platform_device_count=8`` CPU devices, and Pallas
kernels run in interpret mode.  Must be set before jax is imported
anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never take the chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _lock_witness_armed(request):
    """Arm the runtime lock-order witness (utils/lockwitness.py,
    docs/ANALYSIS.md) for every chaos/stress/analysis test: locks built
    during the test record real acquisition edges, and a cycle — an
    inversion that WOULD deadlock under another interleaving — fails the
    test even though this run survived it.  Other suites run disarmed
    (plain threading primitives, zero overhead)."""
    wanted = {"chaos", "analysis"}
    marked = {m.name for m in request.node.iter_markers()}
    if not (marked & wanted) and "test_stress" not in request.node.nodeid:
        yield None
        return
    from nvme_strom_tpu.utils import lockwitness
    with lockwitness.armed_scope() as w:
        yield w
    assert not w.violations, (
        f"lock-order witness recorded violations: {w.violations}")


@pytest.fixture(scope="session")
def mesh8():
    import jax
    from jax.sharding import Mesh
    import numpy as np

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip(f"needs 8 devices, have {len(devs)} "
                    "(XLA_FLAGS was pre-set or platform override)")
    return Mesh(np.array(devs[:8]).reshape(2, 4), ("dp", "tp"))


@pytest.fixture()
def tmp_data_file(tmp_path):
    """A 16 MiB file of deterministic bytes on local disk."""
    import numpy as np

    path = tmp_path / "data.bin"
    rng = np.random.default_rng(0)
    payload = rng.integers(0, 256, size=16 << 20, dtype=np.uint8).tobytes()
    path.write_bytes(payload)
    return path, payload


def evict_file(path) -> None:
    """Drop the file's pages from the page cache, as far as the kernel
    will: fsync first (only clean pages can be evicted), then
    POSIX_FADV_DONTNEED.  A failed eviction shows as ``bytes_resident``
    in the engine's stats."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
        os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
    finally:
        os.close(fd)


def mesh_for(axes):
    """Mesh from ((name, size), ...), skipping when devices are short.
    Shared helper for the parallelism suites (pipeline, ulysses, ...)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    sizes = [s for _, s in axes]
    need = int(np.prod(sizes))
    if len(devs) < need:
        pytest.skip(f"needs {need} devices")
    return Mesh(np.array(devs[:need]).reshape(sizes),
                tuple(n for n, _ in axes))


def dot_census(lowered):
    """(all_dots, non_bf16_dots) operand-dtype census of a lowered
    computation's StableHLO — shared by the bf16 dot-census tests
    (test_model, test_ring_attention) so the regex and filter cannot
    drift when the StableHLO text format moves."""
    import re

    dots = re.findall(
        r"dot_general.*?:\s*\(tensor<([^>]*)>,\s*tensor<([^>]*)>\)",
        lowered.as_text())
    assert dots, "census regex matched nothing — StableHLO format moved"
    bad = [(a, b) for a, b in dots
           if not (a.endswith("bf16") and b.endswith("bf16"))]
    return dots, bad


def within(seconds):
    """Per-test timeout (no pytest-timeout here): the body runs on a
    thread of its own; one that has not ended in ``seconds`` fails the
    test instead of hanging the run."""
    import functools
    import threading

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            box = []

            def body():
                try:
                    fn(*args, **kwargs)
                except BaseException as e:
                    box.append(e)

            t = threading.Thread(target=body, daemon=True,
                                 name="test-body")
            t.start()
            t.join(seconds)
            assert not t.is_alive(), f"{fn.__name__}: over {seconds}s"
            if box:
                raise box[0]
        return wrapper
    return deco

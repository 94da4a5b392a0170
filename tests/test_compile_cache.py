"""Persistent compilation cache: placed from outside the program.

``JAX_COMPILATION_CACHE_DIR`` set → JAX reads it, the code sets no
directory; unset → the fixed ``<checkout>/.jax_cache``, the same in every
process (the path is part of the cache key, so it must never move).
"""

import os
import subprocess
import sys

import pytest

from nvme_strom_tpu.utils import compile_cache as cc
from nvme_strom_tpu.utils.compile_cache import (cache_entries,
                                                enable_compile_cache)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT = os.path.join(REPO, ".jax_cache")
#: the one setting made wherever the cache lives: a program fetched under a
#: key that ignores metadata brings the scope names of whoever compiled it
#: first, and the profiler's readers go by those names
KEY_ON_METADATA = ("jax_compilation_cache_include_metadata_in_key", True)


@pytest.fixture()
def config_updates(monkeypatch):
    """Every ``jax.config.update`` made while the test runs, recorded
    and NOT applied — the process-wide cache setting stays as it was."""
    import jax
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: calls.append((name, val)))
    return calls


@pytest.mark.parametrize("where", ["/some/dir", "relative/dir"])
def test_env_var_set_sets_no_directory_in_code(monkeypatch,
                                               config_updates, where):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", where)
    assert enable_compile_cache() == where
    assert config_updates == [KEY_ON_METADATA]      # no directory
    assert not os.path.exists(where)       # JAX creates it, not us


def test_unset_uses_fixed_path_inside_checkout(monkeypatch,
                                               config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == DEFAULT == cc._DEFAULT_DIR
    assert config_updates == [KEY_ON_METADATA,
                              ("jax_compilation_cache_dir", DEFAULT)]
    # nothing between the checkout and the entries: no sub-directory
    # named after a backend, a host, a pid or a time
    assert os.path.dirname(DEFAULT) == REPO
    assert os.path.isdir(DEFAULT)


def test_env_disable(monkeypatch, config_updates):
    monkeypatch.setenv("STROM_NO_COMPILE_CACHE", "1")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert enable_compile_cache() is None
    assert config_updates == []


def test_cache_entries_counts_executables_only(tmp_path):
    assert cache_entries(None) == 0
    assert cache_entries(str(tmp_path / "missing")) == 0
    (tmp_path / "jit_f-abc-cache").write_bytes(b"x")
    (tmp_path / "jit_f-abc-atime").write_bytes(b"x")
    assert cache_entries(str(tmp_path)) == 1


def _child(code: str, cwd, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    for k, v in env.items():
        if v is None:
            full.pop(k)
    r = subprocess.run([sys.executable, "-c", code], env=full,
                       cwd=str(cwd), capture_output=True, text=True,
                       timeout=180)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.strip().splitlines()[-1]


def test_unset_path_identical_in_another_process(tmp_path, monkeypatch,
                                                 config_updates):
    """A child started from another directory lands on the same path
    this process does."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    code = f"""
import sys; sys.path.insert(0, {REPO!r})
from nvme_strom_tpu.utils.compile_cache import enable_compile_cache
import jax
d = enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == d
print(d)
"""
    theirs = _child(code, tmp_path, JAX_COMPILATION_CACHE_DIR=None)
    assert theirs == enable_compile_cache() == DEFAULT


def test_env_dir_receives_executables_and_second_process_hits(tmp_path):
    """Two fresh processes compile the same program under
    ``JAX_COMPILATION_CACHE_DIR``: the first persists a serialized
    executable THERE, the second HITS it (no new entries — wall-time
    deltas are too jittery on CPU to pin)."""
    d = str(tmp_path / "cc")
    code = f"""
import sys; sys.path.insert(0, {REPO!r})
from nvme_strom_tpu.utils.compile_cache import enable_compile_cache
import jax
assert enable_compile_cache() == {d!r}
assert jax.config.jax_compilation_cache_dir == {d!r}   # JAX's own read
import jax.numpy as jnp
jax.jit(lambda x: jnp.tanh(x) @ x.T)(jnp.ones((256, 256))).block_until_ready()
print("done")
"""
    # JAX's own knob: persist even a compile this small
    env = {"JAX_COMPILATION_CACHE_DIR": d,
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    assert _child(code, tmp_path, **env) == "done"
    first = cache_entries(d)
    assert first >= 1, os.listdir(d) if os.path.isdir(d) else "no dir"
    assert _child(code, tmp_path, **env) == "done"
    assert cache_entries(d) == first
    assert not os.path.exists(tmp_path / ".jax_cache")

"""Persistent XLA compilation cache, placed from outside the program.

Every process that compiles (entry points, the benchmark, the probes)
calls ``enable_compile_cache()`` before its first compile so a later
process loads the serialized executable instead of compiling again.

Where the directory comes from:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself; this
  module sets NO directory in code and returns the variable's value.
- unset: the fixed ``<checkout>/.jax_cache`` (git-ignored).  The path is
  part of JAX's cache key, so it never moves: no platform or host
  sub-directory (JAX's key already separates backends), nothing derived
  from a temporary name, a pid or the clock.

The cache's key includes the programs' metadata
(``jax_compilation_cache_include_metadata_in_key``): the serving programs
name every operation by ``jax.named_scope``s that the profiler's readers go
by (docs/OBSERVABILITY.md "Scopes on the device"), and under JAX's default
key a program fetched from the cache carries the names of whichever commit
compiled it first — ``m7b.flood``'s ``_paged_prefill``, which holds no
kernel, came back with its parent's scopes (PERF.md §6, PR 37).  The price:
an edit that shifts a traced line compiles that program again, once.

``STROM_NO_COMPILE_CACHE=1`` disables.  A backend whose PJRT client
cannot serialize executables logs a warning and skips caching, so
enabling is always safe.
"""

from __future__ import annotations

import os

_DEFAULT_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".jax_cache"))


def enable_compile_cache() -> str | None:
    """Turn on JAX's disk compilation cache (idempotent).  Returns the
    cache directory, or None when disabled via env."""
    if os.environ.get("STROM_NO_COMPILE_CACHE") == "1":
        return None
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    os.makedirs(_DEFAULT_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return _DEFAULT_DIR


def cache_entries(path: str | None) -> int:
    """Number of executables in the cache directory (0 for None or a
    directory that does not exist yet)."""
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for n in os.listdir(path) if n.endswith("-cache"))

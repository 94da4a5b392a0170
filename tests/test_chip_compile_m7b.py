"""The dense decoder's serving programs compile for a TPU v5e and fit it:
the whole decode step and the admission programs at Llama-3.1-8B's widths
(m7b's head geometry) and the serving sizes of ``tests/chip_compile.py``,
the forward under tp=4 across four chips, and m7b's projections read in
place.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chip_compile import (  # noqa: F401 — the first two are fixtures
    topo, _no_compile_cache,
    BLOCK_LEN, HBM_BYTES, HD, NKV, POOL_BLOCKS, SLOTS, SMOKE_MAX_LEN,
    _one, _param_specs, _smoke_cfg, _spec,
    check_projection_weights_read_in_place, pool_sized_ops)


def test_decode_step_fits_one_chip(topo, monkeypatch):
    """The server's whole jitted decode step at Llama-3.1-8B's widths and
    the depth and pool size of ``tests/chip_compile.py``, handed the described device and
    ``jax.eval_shape`` shapes by the test."""
    from nvme_strom_tpu.models import serving
    # the kernels pick interpret mode from the default backend, which is
    # the CPU here: steer them to the compiled form
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _smoke_cfg()
    sh = _one(topo)
    B, L, max_len = SLOTS, cfg.n_layers, SMOKE_MAX_LEN
    params = _param_specs(cfg, lambda name: sh)
    vec = lambda dt: _spec((B,), dt, sh)                    # noqa: E731
    sampling = (vec(jnp.float32), vec(jnp.float32), vec(jnp.uint32))
    pool = _spec((L, POOL_BLOCKS + 1, NKV, BLOCK_LEN, HD),
                 jnp.bfloat16, sh)
    table = _spec((B, max_len // BLOCK_LEN), jnp.int32, sh)
    compiled = serving._paged_step.lower(
        params, cfg, vec(jnp.int32), pool, pool, vec(jnp.int32),
        vec(jnp.int32), table, vec(jnp.int32), *sampling).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert not pool_sized_ops(compiled.as_text(), pool.shape)
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert need < HBM_BYTES, m


@pytest.mark.parametrize("name,program", [
    ("m7b", "step"), ("m7b", "prefill")])
def test_projection_weights_read_in_place(topo, monkeypatch, name, program):
    check_projection_weights_read_in_place(topo, monkeypatch, name, program)


@pytest.mark.parametrize("width,suffix_blocks,blocks", [
    (1, 4, 4), (1, 1, 4), (2, 1, 1)],
    ids=["no_hit", "prefix_hit", "group_of_two"])
def test_prefill_program_fits_one_chip(topo, width, suffix_blocks, blocks):
    """The server's admission program at the same widths, depth and pool, for one prompt and for a group: it compiles, fits, and
    writes the donated pools in place (no second copy of a pool is ever
    live)."""
    from nvme_strom_tpu.models import serving
    cfg = _smoke_cfg()
    sh = _one(topo)
    bk = BLOCK_LEN
    params = _param_specs(cfg, lambda name: sh)
    pool = _spec((cfg.n_layers, POOL_BLOCKS + 1, NKV, bk, HD),
                 jnp.bfloat16, sh)
    compiled = serving._paged_prefill.lower(
        params, cfg, pool, pool,
        _spec((width, suffix_blocks * bk), jnp.int32, sh),
        _spec((width, blocks), jnp.int32, sh),
        _spec((width,), jnp.int32, sh)).compile()
    m = compiled.memory_analysis()
    pools = 2 * np.prod(pool.shape) * 2
    assert m.alias_size_in_bytes >= pools, m
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert need < HBM_BYTES, m


def test_sharded_forward_compiles_for_four_chips(topo):
    """The forward under the tp=4 shardings of
    ``load_sharded`` is one program across the four chips."""
    from nvme_strom_tpu.models.transformer import forward
    from nvme_strom_tpu.parallel.shardings import param_shardings
    cfg = _smoke_cfg()
    mesh = Mesh(np.array(topo.devices), ("tp",))
    shardings = param_shardings(cfg, mesh)
    params = _param_specs(cfg, shardings.__getitem__)
    tokens = _spec((2, 256), jnp.int32, NamedSharding(mesh, P()))
    compiled = jax.jit(forward, static_argnums=(2,)).lower(
        params, tokens, cfg).compile()
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes) < HBM_BYTES, m
    assert "all-reduce" in compiled.as_text()


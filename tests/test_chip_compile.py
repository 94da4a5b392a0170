"""Every kernel on chip_smoke.py's path compiles for a TPU v5e.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (``topologies.get_topology_desc``), so what
Mosaic or XLA would refuse on the machine with the chip is refused here
first, at no chip time.  Shapes are Llama-3.1-8B's head geometry (32 query
/ 8 KV heads, head_dim 128) and chip_smoke.py's own serving sizes; every
kernel is compiled with ``interpret=False``.  Nothing runs: a compile
that passes is not a chip run.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

NH, NKV, HD = 32, 8, 128
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu / unknown topology name
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A described-device compile can be written to the persistent cache
    but not read back without a chip (the next one warns and compiles
    again), so the cache is off around this module."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _one(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs, **jit_kw):
    compiled = jax.jit(fn, **jit_kw).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "no Mosaic kernel in the compiled program"
    return compiled


def _paged(topo):
    from nvme_strom_tpu.ops.paged_attention import paged_attention
    sh, b, blocks, bk = _one(topo), 8, 96, 128
    return _compile(
        functools.partial(paged_attention, interpret=False),
        _spec((b, NH, 1, HD), jnp.bfloat16, sh),
        _spec((blocks + 1, NKV, bk, HD), jnp.bfloat16, sh),
        _spec((blocks + 1, NKV, bk, HD), jnp.bfloat16, sh),
        _spec((b, 4096 // bk), jnp.int32, sh),
        _spec((b,), jnp.int32, sh))


def _decode(topo):
    from nvme_strom_tpu.ops.decode_attention import decode_attention
    sh, b, S = _one(topo), 4, 4096
    return _compile(
        functools.partial(decode_attention, interpret=False),
        _spec((b, NH, 1, HD), jnp.bfloat16, sh),
        _spec((b, NKV, S, HD), jnp.bfloat16, sh),
        _spec((b, NKV, S, HD), jnp.bfloat16, sh),
        _spec((b,), jnp.int32, sh))


def _flash_specs(topo):
    return [_spec((1, NH, 2048, HD), jnp.bfloat16, _one(topo))] * 3


def _flash_fwd(topo):
    from nvme_strom_tpu.ops.flash_attention import flash_attention
    return _compile(functools.partial(flash_attention, interpret=False),
                    *_flash_specs(topo))


def _flash_bwd(topo):
    from nvme_strom_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, interpret=False).astype(
            jnp.float32).sum()

    return _compile(jax.grad(loss, argnums=(0, 1, 2)),
                    *_flash_specs(topo))


def _bridge(topo):
    """The overlap stage's transfer program, from the only operand it is
    ever given: a chunk resident in ``pinned_host``."""
    from nvme_strom_tpu.ops.bridge import _pallas_h2d
    dev = topo.devices[0]
    pinned = SingleDeviceSharding(dev, memory_kind="pinned_host")
    fn = _pallas_h2d(dev)
    compiled = fn.lower(_spec((8 << 20,), jnp.uint8, pinned)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _ici(topo):
    """The ring exchange over the four chips, 1 MiB rows."""
    from nvme_strom_tpu.ops.ici import IciExchange
    mesh = Mesh(np.array(topo.devices), ("hosts",))
    ex = IciExchange(mesh)
    assert ex.backend == "pallas_ring" and ex.n == 4
    tiles = (1 << 20) // (4 * 128)
    rows = _spec((4, tiles, 128), jnp.int32,
                 NamedSharding(mesh, P("hosts", None, None)))
    compiled = ex._gather_fn(tiles).lower(rows).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" not in text
    return compiled


@pytest.mark.parametrize("build", [_paged, _decode, _flash_fwd,
                                   _flash_bwd, _bridge, _ici],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_kernel_compiles_for_v5e(topo, build):
    assert build(topo) is not None


def _smoke_cfg():
    import chip_smoke
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    return chip_smoke, config_from_hf(
        chip_smoke.hf_config(chip_smoke.SMOKE_LAYERS))


def _param_specs(cfg, sharding_of):
    import chip_smoke
    return {name: _spec(shape, jnp.bfloat16, sharding_of(name))
            for name, shape in chip_smoke.tensor_specs(cfg)}


@pytest.mark.parametrize("server", ["paged", "pallas"])
def test_decode_step_fits_one_chip(topo, monkeypatch, server):
    """The servers' whole jitted decode step at chip_smoke.py's widths,
    depth and cache sizes, handed the described device and
    ``jax.eval_shape`` shapes by the test."""
    from nvme_strom_tpu.models import serving
    from nvme_strom_tpu.ops.decode_attention import make_decode_attn
    # the kernels pick interpret mode from the default backend, which is
    # the CPU here: steer them to the compiled form
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    smoke, cfg = _smoke_cfg()
    sh = _one(topo)
    B, L, max_len = smoke.SLOTS, cfg.n_layers, smoke.SMOKE_MAX_LEN
    params = _param_specs(cfg, lambda name: sh)
    vec = lambda dt: _spec((B,), dt, sh)                    # noqa: E731
    sampling = (vec(jnp.float32), vec(jnp.float32), vec(jnp.uint32))
    if server == "paged":
        pool = _spec((L, smoke.POOL_BLOCKS + 1, NKV, smoke.BLOCK_LEN, HD),
                     jnp.bfloat16, sh)
        table = _spec((B, max_len // smoke.BLOCK_LEN), jnp.int32, sh)
        lowered = serving._paged_step.lower(
            params, cfg, vec(jnp.int32), pool, pool, vec(jnp.int32),
            vec(jnp.int32), table, vec(jnp.int32), *sampling)
    else:
        cache = _spec((L, B, NKV, max_len, HD), jnp.bfloat16, sh)
        lowered = serving._serve_step.lower(
            params, cfg, vec(jnp.int32), cache, cache, vec(jnp.int32),
            *sampling, make_decode_attn())
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert need < HBM_BYTES, m


@pytest.mark.parametrize("suffix_blocks,blocks", [(4, 4), (1, 4)],
                         ids=["no_hit", "prefix_hit"])
def test_prefill_program_fits_one_chip(topo, suffix_blocks, blocks):
    """The paged server's admission program at chip_smoke.py's widths,
    depth and pool: it compiles, fits, and writes the donated pools in
    place (no second copy of a pool is ever live)."""
    from nvme_strom_tpu.models import serving
    smoke, cfg = _smoke_cfg()
    sh = _one(topo)
    bk = smoke.BLOCK_LEN
    params = _param_specs(cfg, lambda name: sh)
    pool = _spec((cfg.n_layers, smoke.POOL_BLOCKS + 1, NKV, bk, HD),
                 jnp.bfloat16, sh)
    compiled = serving._paged_prefill.lower(
        params, cfg, pool, pool, _spec((1, suffix_blocks * bk), jnp.int32, sh),
        _spec((blocks,), jnp.int32, sh), _spec((), jnp.int32, sh)).compile()
    m = compiled.memory_analysis()
    pools = 2 * np.prod(pool.shape) * 2
    assert m.alias_size_in_bytes >= pools, m
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert need < HBM_BYTES, m


def test_sharded_forward_compiles_for_four_chips(topo):
    """chip_smoke.py --chips 4: the forward under the tp=4 shardings of
    ``load_sharded`` is one program across the four chips."""
    from nvme_strom_tpu.models.transformer import forward
    from nvme_strom_tpu.parallel.shardings import param_shardings
    _, cfg = _smoke_cfg()
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

"""SDAR-30B-A3B-Chat's serving programs compile for a TPU v5e at the cell's
pools: the step at EIGHT rows a slot, the block a slot finished last beside
its current one — ``strom_kv_write`` placing each half's four rows in its
tile (a call a half), ``strom_paged_attn`` running them as 64 query rows a
KV head with a limit a row (``lag`` 4), 1,024 rows through 128 experts,
confidence and selection over the current blocks' 512 x 151,936 float32
logits — with the pages updated in place, and the block-causal admission
beside them (``tests/chip_compile.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_compile import (  # noqa: F401 — the first two are fixtures
    topo, _no_compile_cache, _one, _spec, hf_config_of, pool_sized_ops)

SDAR_SLOTS, SDAR_BLOCKS, SDAR_LAYERS = 128, 1536, 2     # sdar.flood-bd's


def _sdar_layers(topo):
    """SDAR's widths as the cell serves them, cut to TWO of its six alike
    layers for the compiler's sake, as shapes on one described chip: (cfg,
    sharding, params, the K and V pools, the carried counters)."""
    from nvme_strom_tpu.models import serving
    from nvme_strom_tpu.models.transformer import init_params
    cfg = hf_config_of("sdar-30b-a3b-chat", layers=SDAR_LAYERS)
    assert (cfg.diffusion_block, cfg.diffusion_steps, cfg.head_dim,
            cfg.n_experts, cfg.vocab) == (4, 2, 128, 128, 151936)
    sh = _one(topo)
    params = {k: _spec(v.shape, jnp.bfloat16, sh) for k, v in jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)).items()}
    pools = [_spec((SDAR_LAYERS, SDAR_BLOCKS + 1, 4, 128, 128),
                   jnp.bfloat16, sh)] * 2
    spec_of = lambda a: _spec(a.shape, a.dtype, sh)         # noqa: E731
    state = jax.tree_util.tree_map(spec_of, jax.eval_shape(
        lambda: serving.init_carried(cfg, SDAR_SLOTS + 1)))
    bd = jax.tree_util.tree_map(spec_of, jax.eval_shape(
        lambda: serving.bd_state(cfg, SDAR_SLOTS)))
    return cfg, sh, params, pools, state, bd


def test_sdar_step_forwards_eight_rows_a_slot_in_place(topo, monkeypatch):
    """The server's step at the cell's widths, 128 slots and eight rows a
    slot: both kernels — the writer twice a layer — and the grouped expert
    product are there by name, the pages are aliased input to output,
    nothing of the pool's size is copied, and the step's temporaries — the
    head sees the current blocks' 512 rows only: 512 x 151,936 float32
    logits and what the selection makes of them — stay under the 1.5 GiB
    the four-row step was held to."""
    from nvme_strom_tpu.models import serving
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, sh, params, pools, state, bd = _sdar_layers(topo)
    B = SDAR_SLOTS
    vec = lambda dt: _spec((B,), dt, sh)                    # noqa: E731
    compiled = serving._paged_step.lower(
        params, cfg, _spec((B, 4), jnp.int32, sh), *pools, vec(jnp.int32),
        vec(jnp.int32), _spec((B, 12), jnp.int32, sh), vec(jnp.int32),
        vec(jnp.float32), vec(jnp.float32), vec(jnp.uint32), state,
        vec(jnp.int32), bd=bd).compile()
    text = compiled.as_text()
    for name, n in (("strom_kv_write", 2 * SDAR_LAYERS),
                    ("strom_paged_attn", SDAR_LAYERS),
                    ("strom_moe_gmm", 2 * SDAR_LAYERS)):
        assert text.count(name) >= n, name
    assert not pool_sized_ops(text, pools[0].shape)
    m = compiled.memory_analysis()
    nbytes = 2 * int(np.prod(pools[0].shape)) * 2
    assert m.alias_size_in_bytes >= nbytes
    assert m.temp_size_in_bytes < 1.5 * 2 ** 30, m


@pytest.mark.parametrize("width,rows", [(2, 1024), (4, 128)])
def test_sdar_prefill_fits_beside_the_cells_pools(topo, monkeypatch, width,
                                                  rows):
    """An admission of two of the cell's longest prompts, and of four of its
    shortest, under the block-causal mask: the pages are aliased and the
    program's temporaries stay under the 3 GiB the six-layer cell has to
    spare (weights 8.12 + pages 2.25 GiB of the chip's 15.75; the layers run
    one after another, so six need what two do)."""
    from nvme_strom_tpu.models import serving
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, sh, params, pools, state, _ = _sdar_layers(topo)
    compiled = serving._paged_prefill.lower(
        params, cfg, *pools, _spec((width, rows), jnp.int32, sh),
        _spec((width, rows // 128), jnp.int32, sh),
        _spec((width,), jnp.int32, sh), state,
        _spec((width,), jnp.int32, sh)).compile()
    assert "strom_moe_gmm" in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 2 * int(np.prod(pools[0].shape)) * 2
    assert m.temp_size_in_bytes < 3 * 2 ** 30, m

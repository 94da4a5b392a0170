"""LFM2-24B-A2B's serving programs compile for a TPU v5e: a group's
admission and the decode step update every pool in place, and the
attention layer's projections are read where they lie
(``tests/chip_compile.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_compile import (  # noqa: F401 — the first two are fixtures
    topo, _no_compile_cache,
    _one, _spec, check_projection_weights_read_in_place, hf_config_of,
    pool_sized_ops)


def _lfm2_five_layers(topo):
    """LFM2-24B-A2B's widths, its first period and one more conv layer
    (conv, conv, attention, conv, conv: 2 dense MLPs, 3 expert layers of 64
    experts) as the cell serves it — 128 slots, 1,280 blocks of 128 —, as
    shapes on one described chip: (cfg, sharding, params, one K/V pool,
    the carried state, the bytes of what a serving program is donated)."""
    from nvme_strom_tpu.models import serving
    from nvme_strom_tpu.models.transformer import init_params
    cfg = hf_config_of("lfm2-24b-a2b", layers=5)
    sh = _one(topo)
    params = {k: _spec(v.shape, jnp.bfloat16, sh) for k, v in jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)).items()}
    pool = _spec((1, 1280 + 1, cfg.n_kv_heads, 128, cfg.head_dim),
                 jnp.bfloat16, sh)
    state = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype, sh),
        jax.eval_shape(lambda: serving.init_carried(cfg, 128 + 1)))
    donated = (2 * np.prod(pool.shape) * 2
               + sum(np.prod(a.shape) * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(state)))
    return cfg, sh, params, pool, state, donated


@pytest.mark.parametrize("width,rows", [(2, 1024), (4, 512), (4, 128)])
def test_lfm2_group_prefill_updates_every_pool_in_place(topo, monkeypatch,
                                                        width, rows):
    """The admission program of a GROUP at LFM2-24B-A2B's widths (the same
    five layers as the step below) at the programs the grouping rule gives
    its lengths there — two prompts of 1,024 rows, four of 512, four of
    128: it compiles for a v5e, the K/V pool, every conv layer's
    tail pool and the load counters are aliased input to output, each expert
    layer is two calls of the grouped product over ALL the group's rows,
    and what it needs beside the 12-layer model's 12.11 GiB fits the chip."""
    from nvme_strom_tpu.models import serving
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, sh, params, pool, state, donated = _lfm2_five_layers(topo)
    bk = 128
    vec = _spec((width,), jnp.int32, sh)
    compiled = serving._paged_prefill.lower(
        params, cfg, pool, pool, _spec((width, rows), jnp.int32, sh),
        _spec((width, rows // bk), jnp.int32, sh), vec, state, vec).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3 * 2       # gmm only
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= donated, m
    weights = sum(np.prod(a.shape) * 2 for a in params.values())
    scratch = (m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes - weights)
    # beside the cell's 13.13 GiB (weights, pools, the step's own scratch)
    assert scratch < (15.75 - 13.13) * 2 ** 30, (scratch / 2 ** 30, m)


def test_lfm2_step_updates_every_pool_in_place(topo, monkeypatch):
    """The server's decode step at LFM2-24B-A2B's widths, its first period
    and one more conv layer (conv, conv, attention, conv, conv: 2 dense
    MLPs, 3 expert layers of 64 experts), 128 slots: every conv layer's tail
    pool, the K/V pool and the load counters are aliased input to output,
    and each expert layer is two calls of the grouped product."""
    from nvme_strom_tpu.models import serving
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, sh, params, pool, state, donated = _lfm2_five_layers(topo)
    B, bk = 128, 128
    assert len(state["conv"]) == 4 and not state["s"]
    vec = lambda dt: _spec((B,), dt, sh)                    # noqa: E731
    compiled = serving._paged_step.lower(
        params, cfg, vec(jnp.int32), pool, pool, vec(jnp.int32),
        vec(jnp.int32), _spec((B, 1280 // bk), jnp.int32, sh),
        vec(jnp.int32), vec(jnp.float32), vec(jnp.float32),
        vec(jnp.uint32), state, vec(jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 + 3 * 2  # write, attend; gmm
    assert not pool_sized_ops(text, pool.shape)
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= donated, m
    # the router's scores are the one f32[slots, experts] array of the step:
    # benchmark/layer_metrics/moe_route_share.py finds routing by it
    assert "f32[128,64]" in text


@pytest.mark.parametrize("name,program", [
    ("lfm2", "step")])
def test_projection_weights_read_in_place(topo, monkeypatch, name, program):
    check_projection_weights_read_in_place(topo, monkeypatch, name, program)

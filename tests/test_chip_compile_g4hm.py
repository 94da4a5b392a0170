"""granite-4.0-h-micro's serving programs compile for a TPU v5e: the hybrid
decode step updates both caches in place, and the attention layers'
projections are read where they lie (``tests/chip_compile.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_compile import (  # noqa: F401 — the first two are fixtures
    topo, _no_compile_cache,
    _one, _spec, check_projection_weights_read_in_place, hf_config_of,
    pool_sized_ops)


def test_hybrid_step_updates_both_caches_in_place(topo, monkeypatch):
    """The server's decode step for a hybrid at granite-4.0-h-micro's
    widths, one period of its layer pattern (9 mamba + 1 attention), 64
    slots: every recurrent layer's state pool, conv tail and the K/V pool
    are aliased input to output — nothing pool-sized is copied."""
    from nvme_strom_tpu.models import serving, ssm
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = hf_config_of("granite-4.0-h-micro", layers=10)
    sh = _one(topo)
    B, blocks, bk = 64, 640, 128
    from nvme_strom_tpu.models.transformer import init_params
    params = {k: _spec(v.shape, jnp.bfloat16, sh) for k, v in jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)).items()}
    pool = _spec((1, blocks + 1, cfg.n_kv_heads, bk, cfg.head_dim),
                 jnp.bfloat16, sh)
    state = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype, sh),
        jax.eval_shape(lambda: ssm.init_state(cfg, B + 1)))
    vec = lambda dt: _spec((B,), dt, sh)                    # noqa: E731
    compiled = serving._paged_step.lower(
        params, cfg, vec(jnp.int32), pool, pool, vec(jnp.int32),
        vec(jnp.int32), _spec((B, 1280 // bk), jnp.int32, sh),
        vec(jnp.int32), vec(jnp.float32), vec(jnp.float32),
        vec(jnp.uint32), state, vec(jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 11   # 9 updates, write, attend
    assert not pool_sized_ops(text, pool.shape)
    donated = (2 * np.prod(pool.shape) * 2
               + sum(np.prod(a.shape) * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(state)))
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= donated, m
    # and no operation of the step copies a state array (65 x 2 MiB)
    assert not [line for line in text.splitlines()
                if " copy(" in line and "= f32[65,32,128,128]" in line]


@pytest.mark.parametrize("name,program", [
    ("g4hm", "step")])
def test_projection_weights_read_in_place(topo, monkeypatch, name, program):
    check_projection_weights_read_in_place(topo, monkeypatch, name, program)

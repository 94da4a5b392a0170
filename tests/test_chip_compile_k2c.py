"""Kimi-K2.7-Code's serving programs compile for a TPU v5e: the decode step
updates the one latent pool in place and a long prefill holds no score
tensor (``tests/chip_compile.py``).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np

from chip_compile import (  # noqa: F401 — the first two are fixtures
    topo, _no_compile_cache,
    K2C_POOL, _one, _spec, hf_config_of, pool_sized_ops)


def _k2c_two_layers(topo):
    """Kimi-K2.7-Code's widths as the cell serves them, cut to its dense
    layer and one expert layer (12 experts held of 384) for the compiler's
    sake, as shapes on one described chip: (cfg, sharding, params, the
    latent pool of those two layers, the carried state)."""
    from nvme_strom_tpu.models import serving
    from nvme_strom_tpu.models.transformer import init_params
    cfg = hf_config_of("kimi-k2.7-code", layers=2)
    sh = _one(topo)
    params = {k: _spec(v.shape, jnp.bfloat16, sh) for k, v in jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)).items()}
    pool = _spec((2,) + K2C_POOL[1:], jnp.bfloat16, sh)
    state = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype, sh),
        jax.eval_shape(lambda: serving.init_carried(cfg, 64 + 1)))
    return cfg, sh, params, pool, state


def test_k2c_step_updates_the_latent_pool_in_place(topo, monkeypatch):
    """The server's decode step of a latent configuration at the cell's
    widths and 64 slots: the ONE latent pool is aliased input to output
    beside no second pool, nothing of its size is copied or transposed, a
    layer is the row writer and the absorbed-form kernel, the expert layer
    two calls of the grouped product over the 12 experts held."""
    from nvme_strom_tpu.models import serving
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, sh, params, pool, state = _k2c_two_layers(topo)
    B = 64
    vec = lambda dt: _spec((B,), dt, sh)                    # noqa: E731
    compiled = serving._paged_step.lower(
        params, cfg, vec(jnp.int32), pool, None, vec(jnp.int32),
        vec(jnp.int32), _spec((B, 66), jnp.int32, sh), vec(jnp.int32),
        vec(jnp.float32), vec(jnp.float32), vec(jnp.uint32), state,
        vec(jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 * 2 + 2   # write, attend; gmm
    assert "strom_mla_attn" in text and "strom_latent_write" in text
    assert not pool_sized_ops(text, pool.shape)
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= np.prod(pool.shape) * 2


def test_k2c_long_prefill_holds_no_score_tensor_over_a_gib(topo,
                                                            monkeypatch):
    """The admission program of one 8,192-row prompt at the cell's widths
    (the same two layers): it compiles for a v5e, the latent pool is
    aliased through, a layer's attention is the blocked kernel
    ``strom_mla_prefill`` so that no array of the program is larger than
    1 GiB — the (64, 8192, 8192) float32 score tensor would be 17 GB — and
    its temporaries fit beside the five-layer model's 9.41 GiB of weights
    and pool."""
    from nvme_strom_tpu.models import serving
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, sh, params, pool, state = _k2c_two_layers(topo)
    rows, bk = 8192, 128
    vec = _spec((1,), jnp.int32, sh)
    compiled = serving._paged_prefill.lower(
        params, cfg, pool, None, _spec((1, rows), jnp.int32, sh),
        _spec((1, rows // bk), jnp.int32, sh), vec, state, vec).compile()
    text = compiled.as_text()
    assert text.count("strom_mla_prefill") >= 2
    # the expert layer's one layout is the held share's (4,096 of 65,536
    # pairs in tiles of 128), not a 16,384-pair chunk's 17,152 rows
    assert "bf16[5632,7168]" in text and "bf16[5632,2048]" in text
    assert "[17152," not in text
    size = {"f32": 4, "bf16": 2, "s32": 4}
    largest = max(size[t] * int(np.prod([int(n) for n in dims.split(",")]))
                  for t, dims in re.findall(r"\b(f32|bf16|s32)\[([\d,]+)\]",
                                            text)
                  if dims != ",".join(map(str, pool.shape)))
    assert largest <= 2 ** 30, largest / 2 ** 30
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= np.prod(pool.shape) * 2
    assert m.temp_size_in_bytes < (15.75 - 9.41 - 1.0) * 2 ** 30, m


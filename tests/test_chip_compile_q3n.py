"""Qwen3-Next-80B-A3B's serving programs compile for a TPU v5e: the delta
rule's state pools beside K/V pages, updated in place by the step, and
the longest prompt's admission fits beside the cell's pools
(``tests/chip_compile.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_compile import (  # noqa: F401 — the first two are fixtures
    topo, _no_compile_cache,
    Q3N_BLOCKS, Q3N_SLOTS, _array_ops, _one, _spec, hf_config_of,
    pool_sized_ops)


def _q3n_period(topo):
    """Qwen3-Next's widths as the cell serves them, cut to ONE period (three
    delta-rule layers and a full one, 32 experts held of 512) for the
    compiler's sake, as shapes on one described chip: (cfg, sharding,
    params, the full layer's K and V pools, the carried state)."""
    from nvme_strom_tpu.models import serving
    from nvme_strom_tpu.models.transformer import init_params
    cfg = hf_config_of("qwen3-next-80b-a3b", layers=4)
    assert cfg.layer_kinds == ("gdn", "gdn", "gdn", "attention")
    sh = _one(topo)
    params = {k: _spec(v.shape, jnp.bfloat16, sh) for k, v in jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)).items()}
    pools = [_spec((1, Q3N_BLOCKS + 1, 2, 128, 256), jnp.bfloat16, sh)] * 2
    state = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype, sh),
        jax.eval_shape(lambda: serving.init_carried(cfg, Q3N_SLOTS + 1)))
    assert [a.shape for a in state["s"]] == [(129, 32, 128, 128)] * 3
    assert [a.shape for a in state["conv"]] == [(129, 3, 8192)] * 3
    return cfg, sh, params, pools, state


def _q3n_caches(pools, state):
    """(bytes of every array the programs carry, their distinct shapes)."""
    arrays = list(pools) + list(state["s"]) + list(state["conv"])
    return (sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in arrays),
            {a.shape for a in arrays})


def test_q3n_step_updates_pages_and_state_pools_in_place(topo, monkeypatch):
    """The server's decode step of a delta-rule configuration at the cell's
    widths and 128 slots: the full layer's pages AND the three delta-rule
    layers' state pools and conv tails are aliased input to output; nothing
    of a state pool's size is copied (a step that copied one would move its
    264 MiB twice more); a delta-rule layer is ``strom_gdn_update``, the
    full layer ``strom_kv_write`` and ``strom_paged_attn``."""
    from nvme_strom_tpu.models import serving
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, sh, params, pools, state = _q3n_period(topo)
    B = Q3N_SLOTS
    vec = lambda dt: _spec((B,), dt, sh)                    # noqa: E731
    compiled = serving._paged_step.lower(
        params, cfg, vec(jnp.int32), *pools, vec(jnp.int32),
        vec(jnp.int32), _spec((B, 40), jnp.int32, sh), vec(jnp.int32),
        vec(jnp.float32), vec(jnp.float32), vec(jnp.uint32), state,
        vec(jnp.int32)).compile()
    text = compiled.as_text()
    for name, n in (("strom_gdn_update", 3), ("strom_kv_write", 1),
                    ("strom_paged_attn", 1), ("strom_moe_gmm", 8)):
        assert text.count(name) >= n, name
    nbytes, shapes = _q3n_caches(pools, state)
    assert not pool_sized_ops(text, pools[0].shape)
    # (a slot's share of a state pool is 128 x 4,096 elements, which
    # activations are too: only what is as large as the WHOLE pool counts)
    whole = int(np.prod(state["s"][0].shape))
    assert not [f"{op} {shape}" for op, shape, n in _array_ops(text)
                if n == whole and op not in ("parameter", "custom-call",
                                             "get-tuple-element", "bitcast")]
    assert compiled.memory_analysis().alias_size_in_bytes >= nbytes


@pytest.mark.parametrize("width,rows", [(1, 4096)])
def test_q3n_prefill_fits_beside_the_cells_pools(topo, monkeypatch, width,
                                                 rows):
    """An admission of the cell's longest prompt through one period: the scan kernel is there by name, every carried array is
    aliased, and the program's temporaries stay under the 1.5 GiB the
    sixteen-layer cell has to spare (weights 4.23 + state 3.09 + pages 5.0
    GiB of the chip's 15.75; the layers run one after another, so sixteen
    need what four do)."""
    from nvme_strom_tpu.models import serving
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, sh, params, pools, state = _q3n_period(topo)
    compiled = serving._paged_prefill.lower(
        params, cfg, *pools, _spec((width, rows), jnp.int32, sh),
        _spec((width, rows // 128), jnp.int32, sh),
        _spec((width,), jnp.int32, sh), state,
        _spec((width,), jnp.int32, sh)).compile()
    text = compiled.as_text()
    assert "strom_gdn_scan" in text and "strom_kv_prefill" in text
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= _q3n_caches(pools, state)[0]
    assert m.temp_size_in_bytes < 1.5 * 2 ** 30, m

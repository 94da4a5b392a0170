"""Olmo-Hybrid-7B's serving programs compile for a TPU v5e: the delta rule's
state pools — two heads of 96 x 192 side by side on the lanes, unpadded —
beside the pages of 30 KV heads, updated in place by the step, and the
longest prompt's admission fits beside the cell's pools
(``tests/chip_compile.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_compile import (  # noqa: F401 — the first two are fixtures
    topo, _no_compile_cache,
    _array_ops, _one, _spec, hf_config_of, pool_sized_ops)

OLMOH_SLOTS, OLMOH_BLOCKS = 32, 512     # the cell olmoh.flood-cot's server


def _olmoh_period(topo):
    """Olmo-Hybrid's widths as the cell serves them, cut to ONE period (three
    delta-rule layers and a full one) for the compiler's sake, as shapes on
    one described chip: (cfg, sharding, params, the full layer's K and V
    pools, the carried state)."""
    from nvme_strom_tpu.models import serving
    from nvme_strom_tpu.models.transformer import init_params
    cfg = hf_config_of("olmo-hybrid-7b", layers=4)
    assert cfg.layer_kinds == ("gdn", "gdn", "gdn", "attention")
    assert cfg.post_norm and cfg.qk_norm_whole and cfg.gdn_neg_eigval
    sh = _one(topo)
    params = {k: _spec(v.shape, jnp.bfloat16, sh) for k, v in jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)).items()}
    pools = [_spec((1, OLMOH_BLOCKS + 1, 30, 128, 128), jnp.bfloat16, sh)] * 2
    state = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype, sh),
        jax.eval_shape(lambda: serving.init_carried(cfg, OLMOH_SLOTS + 1)))
    assert [a.shape for a in state["s"]] == [(33, 15, 96, 384)] * 3
    assert [a.shape for a in state["conv"]] == [(33, 3, 11520)] * 3
    return cfg, sh, params, pools, state


def _caches(pools, state):
    """(bytes of every array the programs carry, their distinct shapes)."""
    arrays = list(pools) + list(state["s"]) + list(state["conv"])
    return (sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in arrays),
            {a.shape for a in arrays})


def test_olmoh_step_updates_pages_and_packed_state_pools_in_place(
        topo, monkeypatch):
    """The server's decode step at the cell's widths and 32 slots: the full
    layer's pages AND the three delta-rule layers' state pools and conv
    tails are aliased input to output; nothing of a state pool's size is
    copied, and a pool is its 73 MB — (33, 15, 96, 384) float32 — with no
    lane of padding; a delta-rule layer is ``strom_gdn_update``, the full
    layer ``strom_kv_write`` and ``strom_paged_attn`` at 30 KV heads."""
    from nvme_strom_tpu.models import serving
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, sh, params, pools, state = _olmoh_period(topo)
    B = OLMOH_SLOTS
    vec = lambda dt: _spec((B,), dt, sh)                    # noqa: E731
    compiled = serving._paged_step.lower(
        params, cfg, vec(jnp.int32), *pools, vec(jnp.int32),
        vec(jnp.int32), _spec((B, 16), jnp.int32, sh), vec(jnp.int32),
        vec(jnp.float32), vec(jnp.float32), vec(jnp.uint32), state,
        vec(jnp.int32)).compile()
    text = compiled.as_text()
    for name, n in (("strom_gdn_update", 3), ("strom_kv_write", 1),
                    ("strom_paged_attn", 1)):
        assert text.count(name) >= n, name
    nbytes, shapes = _caches(pools, state)
    assert not pool_sized_ops(text, pools[0].shape)
    whole = int(np.prod(state["s"][0].shape))
    assert whole * 4 == 33 * 30 * 96 * 192 * 4
    assert not [f"{op} {shape}" for op, shape, n in _array_ops(text)
                if n == whole and op not in ("parameter", "custom-call",
                                             "get-tuple-element", "bitcast")]
    assert compiled.memory_analysis().alias_size_in_bytes >= nbytes


@pytest.mark.parametrize("width,rows", [(1, 1024), (2, 128)])
def test_olmoh_prefill_fits_beside_the_cells_pools(topo, monkeypatch, width,
                                                   rows):
    """An admission of the cell's longest prompt, and of two of its shortest
    in one program, through one period: the scan kernel is there by name,
    every carried array is aliased, and the program's temporaries stay under
    the 2.5 GiB the sixteen-layer cell has to spare (weights 7.64 + state
    0.84 + pages 3.76 GiB of the chip's 15.75; the layers run one after
    another, so sixteen need what four do)."""
    from nvme_strom_tpu.models import serving
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, sh, params, pools, state = _olmoh_period(topo)
    compiled = serving._paged_prefill.lower(
        params, cfg, *pools, _spec((width, rows), jnp.int32, sh),
        _spec((width, rows // 128), jnp.int32, sh),
        _spec((width,), jnp.int32, sh), state,
        _spec((width,), jnp.int32, sh)).compile()
    text = compiled.as_text()
    assert "strom_gdn_scan" in text
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= _caches(pools, state)[0]
    assert m.temp_size_in_bytes < 2.5 * 2 ** 30, m

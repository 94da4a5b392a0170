"""MiMo-V2.5's serving programs compile for a TPU v5e: the decode step
updates pages and rings in place and builds each walk list once, a long
prefill holds no score tensor, and both kinds of layer read their
projections where they lie (``tests/chip_compile.py``).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_compile import (  # noqa: F401 — the first two are fixtures
    topo, _no_compile_cache,
    MIMO_K, MIMO_SLOTS, MIMO_V, MIMO_WK, _array_ops, _one, _spec,
    check_projection_weights_read_in_place, hf_config_of, pool_sized_ops)


def _mimo_layers(topo, pattern=(0, 1, 1), experts=(0, 1, 1), **keys):
    """MiMo-V2.5's widths as the cell serves them, cut to its dense full
    layer and two window expert layers (16 experts held of 256) for the
    compiler's sake — or to another ``pattern`` of full (0) and window (1)
    layers, ``experts`` saying which hold experts, ``keys`` replacing keys
    of the configuration's file —, as shapes on one described chip: (cfg,
    sharding, params, the full layers' K and V pools, the carried state with
    the window layers' rings)."""
    from nvme_strom_tpu.models import serving
    from nvme_strom_tpu.models.transformer import init_params
    cfg = hf_config_of(
        "mimo-v2.5", layers=len(pattern), hybrid_layer_pattern=list(pattern),
        moe_layer_freq=list(experts), **keys)
    assert cfg.layer_kinds == tuple(
        "window" if kind else "attention" for kind in pattern)
    sh = _one(topo)
    params = {k: _spec(v.shape, jnp.bfloat16, sh) for k, v in jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)).items()}
    pools = [_spec((pattern.count(0),) + shape[1:], jnp.bfloat16, sh)
             for shape in (MIMO_K, MIMO_V)]
    state = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype, sh),
        jax.eval_shape(lambda: serving.init_carried(cfg, MIMO_SLOTS + 1)))
    assert state["wk"].shape == (sum(pattern),) + MIMO_WK[1:]
    return cfg, sh, params, pools, state


def _mimo_caches(pools, state):
    return [p.shape for p in pools] + [state["wk"].shape, state["wv"].shape]


def test_mimo_step_updates_both_kinds_of_cache_in_place(topo, monkeypatch):
    """The server's decode step of a window configuration at the cell's
    widths and 64 slots: the full layer's pages AND the window layers' rings
    are aliased input to output, nothing of the size of any of the four
    arrays is copied or transposed (K is 192 wide and lies tokens-on-lanes,
    V 128 wide and does not), a full layer is ``strom_kv_write`` and
    ``strom_paged_attn``, a window layer ``strom_window_write`` and
    ``strom_window_attn`` — a device trace tells them apart by name."""
    from nvme_strom_tpu.models import serving
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, sh, params, pools, state = _mimo_layers(topo)
    B = MIMO_SLOTS
    vec = lambda dt: _spec((B,), dt, sh)                    # noqa: E731
    compiled = serving._paged_step.lower(
        params, cfg, vec(jnp.int32), *pools, vec(jnp.int32),
        vec(jnp.int32), _spec((B, 136), jnp.int32, sh), vec(jnp.int32),
        vec(jnp.float32), vec(jnp.float32), vec(jnp.uint32), state,
        vec(jnp.int32)).compile()
    text = compiled.as_text()
    for name, n in (("strom_kv_write", 1), ("strom_paged_attn", 1),
                    ("strom_window_write", 2), ("strom_window_attn", 2)):
        assert text.count(name) >= n, name
    assert text.count("tpu_custom_call") == 2 * 3 + 2 * 2   # + the gmm's
    shapes = _mimo_caches(pools, state)
    for shape in shapes:
        assert not pool_sized_ops(text, shape), shape
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= sum(np.prod(shape) for shape in shapes) * 2


def test_mimo_step_builds_each_walk_list_once_a_step(topo, monkeypatch):
    """The decode step at the cell's attention shapes — 64 slots, a table
    136 wide, 64 query heads over 4 (full) and 8 (window) KV heads, keys
    192 and values 128 wide — with TWO full and TWO window layers (dense
    MLPs cut to 512 for the compiler's sake): ``walk_list``'s lists of the
    live table entries are built once a step for each kind of cache, not
    once a layer — one gather of 64 x 136 table entries for both full
    layers, and for both window layers one of the rings' 64 x 2 and one of
    the slots' first blocks — ; the four int32 operands the kernel takes on
    scalar prefetch (two lists of 8,704, the slots' 65 bounds, their 64
    positions: 70 KiB) fit, or Mosaic would have refused the kernel;
    nothing of a cache's size is copied."""
    from nvme_strom_tpu.models import serving
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, sh, params, pools, state = _mimo_layers(
        topo, pattern=(0, 1, 0, 1), experts=(0, 0, 0, 0),
        intermediate_size=512)
    B = MIMO_SLOTS
    vec = lambda dt: _spec((B,), dt, sh)                    # noqa: E731
    compiled = serving._paged_step.lower(
        params, cfg, vec(jnp.int32), *pools, vec(jnp.int32),
        vec(jnp.int32), _spec((B, 136), jnp.int32, sh), vec(jnp.int32),
        vec(jnp.float32), vec(jnp.float32), vec(jnp.uint32), state,
        vec(jnp.int32)).compile()
    text = compiled.as_text()
    kernels = re.findall(r"= \S+ custom-call\(([^)]*)\), custom_call_target="
                         r'"tpu_custom_call"[^\n]*strom_(paged|window)_attn',
                         text)
    assert sorted(kind for _, kind in kernels) == ["paged"] * 2 + ["window"] * 2
    gathers = [shape for op, shape, _ in _array_ops(text) if op == "gather"]
    assert gathers.count(f"s32[{B * 136}]") == 1, gathers
    assert gathers.count(f"s32[{B * 2}]") == 2, gathers
    for shape in _mimo_caches(pools, state):
        assert not pool_sized_ops(text, shape), shape


def test_mimo_long_prefill_holds_no_score_tensor_over_a_gib(topo,
                                                             monkeypatch):
    """The admission program of one 16,384-row prompt at the cell's widths
    (the same three layers): it compiles for a v5e, pages and rings are
    aliased through, a full layer's attention is the blocked kernel
    ``strom_kv_prefill`` and a window layer's ``strom_window_prefill``, so
    that no array of the program is larger than 1 GiB — the (64, 16384,
    16384) float32 score tensor ``cache_attention`` would build is 64 GiB —
    and its temporaries fit beside the seven-layer model's 12.10 GiB of
    weights, pages and rings (the seven-layer program's whole need is 14.8
    of the chip's 15.75 GiB by the compiler's buffer assignment, which the
    chip bears out — PERF.md section 4)."""
    from nvme_strom_tpu.models import serving
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, sh, params, pools, state = _mimo_layers(topo)
    rows, bk = 16384, 128
    vec = _spec((1,), jnp.int32, sh)
    compiled = serving._paged_prefill.lower(
        params, cfg, *pools, _spec((1, rows), jnp.int32, sh),
        _spec((1, rows // bk), jnp.int32, sh), vec, state, vec).compile()
    text = compiled.as_text()
    assert "strom_kv_prefill" in text and "strom_window_prefill" in text
    shapes = _mimo_caches(pools, state)
    own = {",".join(map(str, shape[skip:])) for shape in shapes
           for skip in (0, 1)}
    size = {"f32": 4, "bf16": 2, "s32": 4}
    largest = max(size[t] * int(np.prod([int(n) for n in dims.split(",")]))
                  for t, dims in re.findall(r"\b(f32|bf16|s32)\[([\d,]+)\]",
                                            text) if dims not in own)
    assert largest <= 2 ** 30, largest / 2 ** 30
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= sum(np.prod(s) for s in shapes) * 2
    assert m.temp_size_in_bytes < (15.75 - 12.10 - 0.4) * 2 ** 30, m


@pytest.mark.parametrize("name,program", [
    ("mimo", "step"), ("mimo", "prefill")])
def test_projection_weights_read_in_place(topo, monkeypatch, name, program):
    check_projection_weights_read_in_place(topo, monkeypatch, name, program)

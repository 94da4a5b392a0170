"""Every kernel on chip_smoke.py's path compiles for a TPU v5e.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (``topologies.get_topology_desc``), so what
Mosaic or XLA would refuse on the machine with the chip is refused here
first, at no chip time.  Shapes are Llama-3.1-8B's head geometry (32 query
/ 8 KV heads, head_dim 128) and chip_smoke.py's own serving sizes; every
kernel is compiled with ``interpret=False``.  Nothing runs: a compile
that passes is not a chip run.

Run as a script on the machine with the chip (``python
tests/test_chip_compile.py``) it compiles the paged decode step for the
ATTACHED device and applies the same guards as
``test_step_moves_nothing_pool_sized`` and, at m7b's widths for the step
and a prefill, ``test_projection_weights_read_in_place``: that run, with
the layouts the device really gives its arrays, is the one that means
something.
"""

import functools
import json
import os
import re
import sys

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


def _array_ops(hlo_text: str):
    """(opcode, "type[dims]", elements) of every operation of an HLO module
    whose result is one array."""
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\w+\[([\d,]+)\])\S* "
                     r"([\w\-]+)\(", line)
        if m:
            yield m.group(3), m.group(1), int(np.prod(
                [int(n) for n in m.group(2).split(",")]))


def pool_sized_ops(hlo_text: str, pool_shape) -> list:
    """Operations of an optimised HLO module whose result has as many
    elements as the K/V pool or as one layer of it, as "opcode shape".
    Parameters, tuple plumbing, bitcasts (no bytes move) and the kernels
    themselves (which alias the pool through) do not count; a copy, a
    transpose, a slice, a scatter or a fusion of them does."""
    sizes = {int(np.prod(pool_shape)), int(np.prod(pool_shape[1:]))}
    free = {"parameter", "get-tuple-element", "tuple", "bitcast",
            "custom-call"}
    return [f"{op} {shape}" for op, shape, n in _array_ops(hlo_text)
            if op not in free and n in sizes]


def weight_sized_copies(hlo_text: str, shapes) -> list:
    """Operations of an optimised HLO module that lay a projection weight
    out again: a ``copy`` or a ``transpose`` whose result has the element
    count of one of ``shapes`` (a config's ``wq``, ``wk``, ``wv``), as
    "opcode shape".  A product that reads the parameter where it lies has
    none.  An asynchronous ``slice-start`` of a weight is no such
    operation by itself: the compiler prefetches many a weight in pieces
    straight into its product (``wo`` and ``w_down`` of lfm2's step), moved
    once; m7b's four ``bf16[1024,1024]`` pieces of ``wk`` a layer cost what
    they did because they were joined for a ``copy``, which this finds."""
    sizes = {int(np.prod(shape)) for shape in shapes}
    return [f"{op} {shape}" for op, shape, n in _array_ops(hlo_text)
            if op in ("copy", "transpose") and n in sizes]


def _dense_programs(cfg, slots, blocks, sharding=None, prefill_blocks=4):
    """The two serving programs of a plain decoder ``cfg`` over a pool of
    ``blocks`` + 1 blocks of 128 rows, as lowerings nothing has compiled
    yet: ({"step": ``_paged_step`` of ``slots`` slots, "prefill":
    ``_paged_prefill`` of one prompt of ``prefill_blocks`` x 128 rows (the
    bucket ``1x512x512``)}, the pool's shape, the shapes of one layer's ``wq``,
    ``wk``, ``wv``)."""
    from nvme_strom_tpu.models import serving
    from nvme_strom_tpu.models.transformer import init_params
    spec = functools.partial(_spec, sharding=sharding)
    params = {k: spec(v.shape, jnp.bfloat16) for k, v in jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)).items()}
    bk = 128
    pool = spec((len(cfg.attn_layers), blocks + 1, cfg.n_kv_heads, bk,
                 cfg.head_dim), jnp.bfloat16)
    v_pool = spec(pool.shape[:-1] + (cfg.v_dim,), jnp.bfloat16)
    vec = lambda dt, n=slots: spec((n,), dt)                # noqa: E731
    # what a config with window layers carries beside the pools: its rings
    state = jax.tree_util.tree_map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda: serving.init_carried(cfg, slots + 1, bk)))
    lowered = {
        "step": lambda: serving._paged_step.lower(
            params, cfg, vec(jnp.int32), pool, v_pool, vec(jnp.int32),
            vec(jnp.int32), spec((slots, cfg.max_seq // bk), jnp.int32),
            vec(jnp.int32), vec(jnp.float32), vec(jnp.float32),
            vec(jnp.uint32), *(() if state is None
                               else (state, vec(jnp.int32)))),
        "prefill": lambda: serving._paged_prefill.lower(
            params, cfg, pool, v_pool,
            spec((1, prefill_blocks * bk), jnp.int32),
            spec((1, prefill_blocks), jnp.int32), vec(jnp.int32, 1),
            *(() if state is None else (state, vec(jnp.int32, 1))))}
    return lowered, pool.shape, [
        params[f"layers.{i}.{w}"].shape for w in ("wq", "wk", "wv")
        for i in ((0, 1) if cfg.window_layers else (0,))]


def _small_step(hd, sharding=None):
    """``_paged_step`` of a two-layer decoder with 8 heads of ``hd`` over a
    pool of 257 blocks of 128 rows (64 MiB a layer at 128: too large for
    the compiler to stage through the chip's fast memory, as it does with
    a pool of a megabyte), 8 slots: (compiled, pool shape)."""
    from nvme_strom_tpu.models.transformer import TransformerConfig
    cfg = TransformerConfig(vocab=512, d_model=8 * hd, n_layers=2, n_heads=8,
                            n_kv_heads=8, d_ff=512, max_seq=512)
    lowered, pool_shape, _ = _dense_programs(cfg, 8, 256, sharding)
    return lowered["step"]().compile(), pool_shape


def _paged(topo, slots=16, layers=24, blocks=256, max_len=4096, nh=NH,
           hd=HD, **kw):
    """Paged attention at the shapes of the benchmark's ``m7b`` cells: 16
    slots, every layer's pool in one array and the last layer read, a
    table 32 entries wide.  All 8 KV heads of a block come in one grid
    step (256 KiB of K, as much of V, double-buffered) and the block axis
    of the grid is data."""
    from nvme_strom_tpu.ops.paged_attention import paged_attention
    sh, bk = _one(topo), 128
    pool = _spec((layers, blocks + 1, NKV, bk, hd), jnp.bfloat16, sh)
    compiled = _compile(
        functools.partial(paged_attention, layer=layers - 1,
                          interpret=False, **kw),
        _spec((slots, nh, 1, hd), jnp.bfloat16, sh), pool, pool,
        _spec((slots, max_len // bk), jnp.int32, sh),
        _spec((slots,), jnp.int32, sh))
    assert not pool_sized_ops(compiled.as_text(), pool.shape)
    return compiled


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


# granite-4.0-h-micro's recurrent layer: 64 heads of 64, state 128, and the
# benchmark's 64 slots
SSM_B, SSM_H, SSM_P, SSM_N = 64, 64, 64, 128


def _paged_hd64(topo):
    """...and of ``g4hm.flood``: head_dim 64 (half a lane row: the pool is
    read through its swapped view), 4 queries a KV head, 64 slots over the
    4 attention layers' pool, a table 10 entries wide, with a scale that
    is passed in."""
    return _paged(topo, slots=SSM_B, layers=4, blocks=640, max_len=1280,
                  nh=32, hd=64, scale=1 / 64)


def _kv_write(topo, hd=HD, slots=16, blocks=256, layers=2):
    """The row writer on the benchmark's pools: both pools aliased through
    the call, nothing else of their size in the program."""
    from nvme_strom_tpu.ops.paged_attention import write_rows
    sh = _one(topo)
    pool = _spec((layers, blocks + 1, 8, 128, hd), jnp.bfloat16, sh)
    new = _spec((slots, 8, hd), jnp.bfloat16, sh)
    compiled = _compile(
        functools.partial(write_rows, layer=1, interpret=False), pool, pool,
        new, new, _spec((slots,), jnp.int32, sh),
        _spec((slots,), jnp.int32, sh), donate_argnums=(0, 1))
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 2 * np.prod(pool.shape) * 2, m
    assert not pool_sized_ops(compiled.as_text(), pool.shape)
    return compiled


def _kv_write_hd64(topo):
    """...and on a pool the device keeps with the tokens on the lanes."""
    return _kv_write(topo, hd=64, slots=SSM_B, blocks=640)


def _ssm_update(topo):
    """The state update: the pool (65 rows of 2 MiB) is aliased through the
    call — no second copy of it is ever live."""
    from nvme_strom_tpu.ops.ssm import pool_shape, ssm_update
    sh = _one(topo)
    # state-major, two heads of 64 on a lane row: (65, 32, 128, 128)
    pool = _spec(pool_shape(SSM_B + 1, SSM_H, SSM_P, SSM_N), jnp.float32, sh)
    compiled = _compile(
        functools.partial(ssm_update, interpret=False), pool,
        _spec((SSM_B,), jnp.int32, sh),
        _spec((SSM_B, SSM_H, SSM_P), jnp.bfloat16, sh),
        _spec((SSM_B, SSM_H), jnp.float32, sh),
        _spec((SSM_H,), jnp.float32, sh),
        _spec((SSM_B, SSM_N), jnp.bfloat16, sh),
        _spec((SSM_B, SSM_N), jnp.bfloat16, sh), donate_argnums=(0,))
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= np.prod(pool.shape) * 4, m
    return compiled


def _ssm_scan(topo, rows=1024):
    from nvme_strom_tpu.ops.ssm import pool_shape, ssm_scan
    sh = _one(topo)
    return _compile(
        functools.partial(ssm_scan, chunk=256, interpret=False),
        _spec((1, rows, SSM_H, SSM_P), jnp.bfloat16, sh),
        _spec((1, rows, SSM_H), jnp.float32, sh),
        _spec((SSM_H,), jnp.float32, sh),
        _spec((1, rows, SSM_N), jnp.bfloat16, sh),
        _spec((1, rows, SSM_N), jnp.bfloat16, sh),
        _spec(pool_shape(1, SSM_H, SSM_P, SSM_N), jnp.float32, sh),
        _spec((1, rows), jnp.bool_, sh))


def _ssm_scan_128(topo):
    return _ssm_scan(topo, rows=128)       # a chunk shorter than 256


GDN_B, GDN_H, GDN_D = 128, 32, 128      # the cell q3n.flood4k's state pool


def _gdn_update(topo):
    """The delta rule's state update at the cell ``q3n.flood4k``'s shapes:
    the pool (129 rows of 2 MiB a layer) is aliased through the call — no
    second copy of it is ever live."""
    from nvme_strom_tpu.ops.gdn import gdn_update
    sh = _one(topo)
    pool = _spec((GDN_B + 1, GDN_H, GDN_D, GDN_D), jnp.float32, sh)
    vec = _spec((GDN_B, GDN_H, GDN_D), jnp.float32, sh)
    gate = _spec((GDN_B, GDN_H), jnp.float32, sh)
    compiled = _compile(
        functools.partial(gdn_update, interpret=False), pool,
        _spec((GDN_B,), jnp.int32, sh), vec, vec, vec, gate, gate,
        donate_argnums=(0,))
    assert "strom_gdn_update" in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= np.prod(pool.shape) * 4, m
    return compiled


def _gdn_scan(topo, prompts=1, rows=4096):
    """The chunked scan over the cell's longest prompt: chunks of 64 rows,
    the forward substitution unrolled, bfloat16 operands."""
    from nvme_strom_tpu.ops.gdn import gdn_scan
    sh = _one(topo)
    vec = _spec((prompts, rows, GDN_H, GDN_D), jnp.bfloat16, sh)
    gate = _spec((prompts, rows, GDN_H), jnp.float32, sh)
    compiled = _compile(
        functools.partial(gdn_scan, chunk=64, interpret=False),
        vec, vec, vec, gate, gate,
        _spec((prompts, GDN_H, GDN_D, GDN_D), jnp.float32, sh),
        _spec((prompts, rows), jnp.bool_, sh))
    assert "strom_gdn_scan" in compiled.as_text()
    return compiled


def _gdn_scan_group(topo):
    return _gdn_scan(topo, prompts=4, rows=512)


def _moe_gmm(topo, rows=128, k=4, gated=True):
    """The grouped product of an expert layer at LFM2-24B-A2B's widths (64
    experts of 2048 x 1536): gate and up fused, or down."""
    from nvme_strom_tpu.ops import moe as ops
    sh = _one(topo)
    E, d, fe = 64, 2048, 1536
    tm = ops.tile_rows(rows * k, E)
    padded = ops.padded_rows(rows * k, E, tm)
    kdim, n = (d, fe) if gated else (fe, d)
    w = _spec((E, kdim, n), jnp.bfloat16, sh)
    return _compile(
        lambda x, te, nt, *ws: ops.gmm(x, ws, te, nt, tm=tm,
                                       interpret=False),
        _spec((padded, kdim), jnp.bfloat16, sh),
        _spec((padded // tm,), jnp.int32, sh), _spec((), jnp.int32, sh),
        *([w, w] if gated else [w]))


def _moe_gmm_down(topo):
    return _moe_gmm(topo, gated=False)


def _moe_gmm_1024(topo):
    return _moe_gmm(topo, rows=1024)


def _moe_gmm_down_1024(topo):
    return _moe_gmm(topo, rows=1024, gated=False)


def _moe_gmm_7168(topo, gated=True):
    """The grouped product at Kimi-K2's widths (12 experts held of 7168 x
    2048) over the bounded layout of an 8,192-row prompt (4,096 of its
    65,536 pairs, ``models/moe.pair_bound``: 5,632 rows in tiles of 128):
    a gated contraction 7168 deep, whose column tile narrows to 256 so that
    the weight blocks fit VMEM, and the down product back."""
    from nvme_strom_tpu.ops import moe as ops
    sh = _one(topo)
    E, d, fe = 12, 7168, 2048
    tm = ops.tile_rows(8192 * 8, 384)
    padded = ops.padded_rows(4096, E, tm)
    assert (tm, padded) == (128, 5632)
    kdim, n = (d, fe) if gated else (fe, d)
    w = _spec((E, kdim, n), jnp.bfloat16, sh)
    return _compile(
        lambda x, te, nt, *ws: ops.gmm(x, ws, te, nt, tm=tm,
                                       interpret=False),
        _spec((padded, kdim), jnp.bfloat16, sh),
        _spec((padded // tm,), jnp.int32, sh), _spec((), jnp.int32, sh),
        *([w, w] if gated else [w]))


def _moe_gmm_down_7168(topo):
    return _moe_gmm_7168(topo, gated=False)


K2C_POOL = (5, 4224 + 1, 576, 128)      # the cell's latent pool


def _mla_attn(topo):
    """The absorbed-form decode kernel at the cell ``k2c.flood8k``'s shapes:
    64 slots of 64 heads against 576-wide latent rows, a table 66 entries
    wide, the last layer of the five-layer pool read in place."""
    from nvme_strom_tpu.ops.mla_attention import mla_attention
    sh = _one(topo)
    pool = _spec(K2C_POOL, jnp.bfloat16, sh)
    compiled = _compile(
        functools.partial(mla_attention, layer=4, dc=512, interpret=False),
        _spec((64, 64, 576), jnp.bfloat16, sh), pool,
        _spec((64, 66), jnp.int32, sh), _spec((64,), jnp.int32, sh))
    assert not pool_sized_ops(compiled.as_text(), pool.shape)
    return compiled


def _latent_write(topo):
    from nvme_strom_tpu.ops.mla_attention import latent_write
    sh = _one(topo)
    pool = _spec(K2C_POOL, jnp.bfloat16, sh)
    vec = _spec((64,), jnp.int32, sh)
    compiled = _compile(
        functools.partial(latent_write, layer=4, interpret=False),
        pool, _spec((64, 576), jnp.bfloat16, sh), vec, vec,
        donate_argnums=(0,))
    assert not pool_sized_ops(compiled.as_text(), pool.shape)
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= np.prod(pool.shape) * 2
    return compiled


MIMO_SLOTS, MIMO_BLOCKS = 64, 8704      # the cell mimo.flood16k's server
#: its pools: the full layers' pages (K 192 wide, which the device keeps
#: tokens-on-lanes, V 128 wide, which it does not) and the window layers'
#: rings, two blocks a slot and the sacrificial slot's
MIMO_K, MIMO_V = (2, MIMO_BLOCKS + 1, 4, 128, 192), (2, MIMO_BLOCKS + 1, 4,
                                                     128, 128)
MIMO_WK, MIMO_WV = (5, 2 * 65, 8, 128, 192), (5, 2 * 65, 8, 128, 128)


def _paged_k192_v128(topo):
    """A full layer's decode kernel at the cell ``mimo.flood16k``'s shapes:
    64 slots of 64 query heads over 4 KV heads, keys 192 and values 128
    wide — each pool read in the layout the device keeps it in —, a table
    136 entries wide, the last layer read in place."""
    from nvme_strom_tpu.ops.paged_attention import paged_attention
    sh = _one(topo)
    k, v = (_spec(shape, jnp.bfloat16, sh) for shape in (MIMO_K, MIMO_V))
    compiled = _compile(
        functools.partial(paged_attention, layer=1, interpret=False),
        _spec((MIMO_SLOTS, 64, 1, 192), jnp.bfloat16, sh), k, v,
        _spec((MIMO_SLOTS, 136), jnp.int32, sh),
        _spec((MIMO_SLOTS,), jnp.int32, sh))
    text = compiled.as_text()
    assert "strom_paged_attn" in text
    assert not pool_sized_ops(text, k.shape) + pool_sized_ops(text, v.shape)
    return compiled


def _window_attn(topo):
    """A window layer's: 8 KV heads, the slot's ring of two blocks walked
    from the block of its oldest visible row, a sink per query head; the
    kernel's name tells it from a full layer's."""
    from nvme_strom_tpu.ops.paged_attention import paged_attention
    sh = _one(topo)
    k, v = (_spec(shape, jnp.bfloat16, sh) for shape in (MIMO_WK, MIMO_WV))
    compiled = _compile(
        lambda q, k, v, table, pos, sink: paged_attention(
            q, k, v, table, pos, layer=4, window=128, sink=sink,
            interpret=False),
        _spec((MIMO_SLOTS, 64, 1, 192), jnp.bfloat16, sh), k, v,
        _spec((MIMO_SLOTS, 2), jnp.int32, sh),
        _spec((MIMO_SLOTS,), jnp.int32, sh), _spec((64,), jnp.bfloat16, sh))
    text = compiled.as_text()
    assert "strom_window_attn" in text and "strom_paged_attn" not in text
    assert not pool_sized_ops(text, k.shape) + pool_sized_ops(text, v.shape)
    return compiled


def _window_write(topo):
    """The row writer on pools of unequal widths and layouts: both rings
    aliased through the call, nothing else of their size in the program."""
    from nvme_strom_tpu.ops.paged_attention import write_rows
    sh = _one(topo)
    k, v = (_spec(shape, jnp.bfloat16, sh) for shape in (MIMO_WK, MIMO_WV))
    vec = _spec((MIMO_SLOTS,), jnp.int32, sh)
    compiled = _compile(
        functools.partial(write_rows, layer=4, name="strom_window_write",
                          interpret=False), k, v,
        _spec((MIMO_SLOTS, 8, 192), jnp.bfloat16, sh),
        _spec((MIMO_SLOTS, 8, 128), jnp.bfloat16, sh), vec, vec,
        donate_argnums=(0, 1))
    text = compiled.as_text()
    assert "strom_window_write" in text
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= (np.prod(k.shape) + np.prod(v.shape)) * 2
    assert not pool_sized_ops(text, k.shape) + pool_sized_ops(text, v.shape)
    return compiled


def _kv_prefill(topo, nkv=4, window=0):
    """The blocked prefill kernel over a 16,384-row prompt at the cell's
    widths: a full layer's causal walk (16 query heads a KV head in one
    grid step)..."""
    from nvme_strom_tpu.ops.kv_prefill import kv_prefill_attention
    sh, rows = _one(topo), 16384
    sink = [_spec((64,), jnp.bfloat16, sh)] if window else []
    compiled = _compile(
        lambda q, k, v, pos, *s: kv_prefill_attention(
            q, k, v, pos, scale=192 ** -0.5, window=window,
            sink=s[0] if s else None, interpret=False),
        _spec((1, 64, rows, 192), jnp.bfloat16, sh),
        _spec((1, nkv, rows, 192), jnp.bfloat16, sh),
        _spec((1, nkv, rows, 128), jnp.bfloat16, sh),
        _spec((), jnp.int32, sh), *sink)
    assert ("strom_window_prefill" if window else "strom_kv_prefill") \
        in compiled.as_text()
    return compiled


def _window_prefill(topo):
    """...and a window layer's band with the sink column."""
    return _kv_prefill(topo, nkv=8, window=128)


@pytest.mark.parametrize("build", [_paged, _decode, _flash_fwd,
                                   _flash_bwd, _bridge, _ici, _paged_hd64,
                                   _ssm_update, _ssm_scan, _ssm_scan_128,
                                   _kv_write, _kv_write_hd64, _moe_gmm,
                                   _moe_gmm_down, _moe_gmm_1024,
                                   _moe_gmm_down_1024, _moe_gmm_7168,
                                   _moe_gmm_down_7168, _mla_attn,
                                   _latent_write, _paged_k192_v128,
                                   _window_attn, _window_write, _kv_prefill,
                                   _window_prefill, _gdn_update, _gdn_scan,
                                   _gdn_scan_group],
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


def test_decode_step_fits_one_chip(topo, monkeypatch):
    """The server's whole jitted decode step at chip_smoke.py's widths,
    depth and pool size, handed the described device and
    ``jax.eval_shape`` shapes by the test."""
    from nvme_strom_tpu.models import serving
    # the kernels pick interpret mode from the default backend, which is
    # the CPU here: steer them to the compiled form
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    smoke, cfg = _smoke_cfg()
    sh = _one(topo)
    B, L, max_len = smoke.SLOTS, cfg.n_layers, smoke.SMOKE_MAX_LEN
    params = _param_specs(cfg, lambda name: sh)
    vec = lambda dt: _spec((B,), dt, sh)                    # noqa: E731
    sampling = (vec(jnp.float32), vec(jnp.float32), vec(jnp.uint32))
    pool = _spec((L, smoke.POOL_BLOCKS + 1, NKV, smoke.BLOCK_LEN, HD),
                 jnp.bfloat16, sh)
    table = _spec((B, max_len // smoke.BLOCK_LEN), jnp.int32, sh)
    compiled = serving._paged_step.lower(
        params, cfg, vec(jnp.int32), pool, pool, vec(jnp.int32),
        vec(jnp.int32), table, vec(jnp.int32), *sampling).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert not pool_sized_ops(compiled.as_text(), pool.shape)
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert need < HBM_BYTES, m


@pytest.mark.parametrize("hd", [128, 64])
def test_step_moves_nothing_pool_sized(topo, monkeypatch, hd):
    """The paged decode step compiled for a v5e holds no operation whose
    result is the K/V pool or one layer of it: the new rows are written
    into the donated pool and the kernel reads the pool where it lies, at
    head_dim 128 and at 64 (which the device keeps with the tokens on the
    lanes).  The same guard against the ATTACHED chip's own compile:
    ``python tests/test_chip_compile.py`` on the machine with the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, pool_shape = _small_step(hd, _one(topo))
    assert compiled.as_text().count("tpu_custom_call") == 4
    assert not pool_sized_ops(compiled.as_text(), pool_shape)
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 2 * np.prod(pool_shape) * 2, m


# the attention widths of the benchmark's dense-attention configs — two
# layers each, because the compiler treats the first layer's weights apart
# (they are not prefetched) — under an MLP and a head of the config's own
# widths: (config, slots, pool blocks)
PROJECTION_CFGS = {
    "m7b": (dict(vocab=32768, d_model=4096, n_heads=32, n_kv_heads=8,
                 d_ff=14336, max_seq=4096, rope_theta=1e6), 16, 256),
    # granite-4.0-h-micro's attention layers: no positional encoding, a
    # score scale of its own
    "g4hm": (dict(vocab=100352, d_model=2048, n_heads=32, n_kv_heads=8,
                  d_ff=8192, max_seq=1280, rope=False, attn_scale=1 / 64,
                  tie_embed=True), 64, 640),
    # lfm2-24b-a2b's: per-head q/k norms before the rotation
    "lfm2": (dict(vocab=65536, d_model=2048, n_heads=32, n_kv_heads=8,
                  d_ff=11776, max_seq=1280, rope_theta=1e6, qk_norm=True,
                  tie_embed=True), 128, 1280),
    # mimo-v2.5's: a full layer (4 KV heads) and a window layer (8, a sink),
    # heads 192 / 128 wide, rotary on the first 64, values scaled
    "mimo": (dict(vocab=19072, d_model=4096, n_heads=64, n_kv_heads=4,
                  d_ff=16384, max_seq=17408, rope_theta=1e7,
                  layer_kinds=("attention", "window"), qk_head_dim=192,
                  v_head_dim=128, rotary_dim=64, value_scale=0.707,
                  window=128, window_kv_heads=8, window_rope_theta=1e4,
                  window_sink=True), 64, 8704)}


def _projection_programs(name, sharding=None):
    from nvme_strom_tpu.models.transformer import TransformerConfig
    kw, slots, blocks = PROJECTION_CFGS[name]
    # mimo's prompt is 640 rows: at 512 its activations have the element
    # counts of its window layer's wk and wv (512 x 12288 = 4096 x 1536)
    lowered, _, shapes = _dense_programs(
        TransformerConfig(n_layers=2, **kw), slots, blocks, sharding,
        prefill_blocks=5 if name == "mimo" else 4)
    return lowered, shapes


@pytest.mark.parametrize("name,program", [
    ("m7b", "step"), ("m7b", "prefill"), ("g4hm", "step"), ("lfm2", "step"),
    ("mimo", "step"), ("mimo", "prefill")])
def test_projection_weights_read_in_place(topo, monkeypatch, name, program):
    """``qkv_project``'s three products read ``wq``, ``wk`` and ``wv`` in
    the layout they are stored in: the serving program compiled for a v5e
    holds no copy or transpose of a weight's size — before PR 38 ``wq`` was
    copied into a head-major layout on every decode step and every prefill,
    and ``wk`` fetched in four square pieces for the same (14 % of
    ``m7b.flood``'s step)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lowered, shapes = _projection_programs(name, _one(topo))
    found = weight_sized_copies(lowered[program]().compile().as_text(),
                                shapes)
    assert not found, found


@pytest.mark.parametrize("width,suffix_blocks,blocks", [
    (1, 4, 4), (1, 1, 4), (2, 1, 1)],
    ids=["no_hit", "prefix_hit", "group_of_two"])
def test_prefill_program_fits_one_chip(topo, width, suffix_blocks, blocks):
    """The server's admission program at chip_smoke.py's widths,
    depth and pool, for one prompt and for a group: it compiles, fits, and
    writes the donated pools in place (no second copy of a pool is ever
    live)."""
    from nvme_strom_tpu.models import serving
    smoke, cfg = _smoke_cfg()
    sh = _one(topo)
    bk = smoke.BLOCK_LEN
    params = _param_specs(cfg, lambda name: sh)
    pool = _spec((cfg.n_layers, smoke.POOL_BLOCKS + 1, NKV, bk, HD),
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


def test_hybrid_step_updates_both_caches_in_place(topo, monkeypatch):
    """The server's decode step for a hybrid at granite-4.0-h-micro's
    widths, one period of its layer pattern (9 mamba + 1 attention), 64
    slots: every recurrent layer's state pool, conv tail and the K/V pool
    are aliased input to output — nothing pool-sized is copied."""
    import json
    from nvme_strom_tpu.models import serving, ssm
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        hf = json.load(f)
    hf = dict(hf, num_hidden_layers=10, layer_types=hf["layer_types"][:10])
    cfg = config_from_hf(hf)
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


def _lfm2_five_layers(topo):
    """LFM2-24B-A2B's widths, its first period and one more conv layer
    (conv, conv, attention, conv, conv: 2 dense MLPs, 3 expert layers of 64
    experts) as the cell serves it — 128 slots, 1,280 blocks of 128 —, as
    shapes on one described chip: (cfg, sharding, params, one K/V pool,
    the carried state, the bytes of what a serving program is donated)."""
    import json
    from nvme_strom_tpu.models import serving
    from nvme_strom_tpu.models.transformer import init_params
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "lfm2-24b-a2b.json")) as f:
        hf = json.load(f)
    hf = dict(hf, num_hidden_layers=5, layer_types=hf["layer_types"][:5])
    cfg = config_from_hf(hf)
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


def _k2c_two_layers(topo):
    """Kimi-K2.7-Code's widths as the cell serves them, cut to its dense
    layer and one expert layer (12 experts held of 384) for the compiler's
    sake, as shapes on one described chip: (cfg, sharding, params, the
    latent pool of those two layers, the carried state)."""
    import json
    from nvme_strom_tpu.models import serving
    from nvme_strom_tpu.models.transformer import init_params
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "kimi-k2.7-code.json")) as f:
        hf = json.load(f)
    cfg = config_from_hf(dict(hf, num_hidden_layers=2))
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
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "mimo-v2.5.json")) as f:
        hf = json.load(f)
    cfg = config_from_hf(dict(
        hf, num_hidden_layers=len(pattern),
        hybrid_layer_pattern=list(pattern), moe_layer_freq=list(experts),
        **keys))
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


if __name__ == "__main__":
    # on the machine with the chip: the attached device's own compile
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    found = {}
    for head_dim in (128, 64):
        step, shape = _small_step(head_dim)
        found[f"hd{head_dim}"] = {
            "pool": list(shape),
            "pool_sized_ops": pool_sized_ops(step.as_text(), shape),
            "kernels": step.as_text().count("tpu_custom_call")}
    lowered, shapes = _projection_programs("m7b")
    for program, lower in lowered.items():
        found[f"m7b_{program}"] = {"weight_sized_copies": weight_sized_copies(
            lower().compile().as_text(), shapes)}
    ok = (jax.default_backend() == "tpu"
          and all(not f["pool_sized_ops"] and f["kernels"] == 4
                  for f in (found["hd128"], found["hd64"]))
          and not any(found[f"m7b_{program}"]["weight_sized_copies"]
                      for program in lowered))
    print(json.dumps({"guard": "paged step moves nothing pool-sized; step "
                               "and prefill lay no projection weight out "
                               "again",
                      "platform": jax.default_backend(),
                      "device": jax.devices()[0].device_kind, "ok": ok,
                      **found}))
    sys.exit(0 if ok else 1)


# -- qwen3-next-80b-a3b: the delta rule's state pools beside K/V pages -------

Q3N_SLOTS, Q3N_BLOCKS = 128, 5120       # the cell q3n.flood4k's server


def _q3n_period(topo):
    """Qwen3-Next's widths as the cell serves them, cut to ONE period (three
    delta-rule layers and a full one, 32 experts held of 512) for the
    compiler's sake, as shapes on one described chip: (cfg, sharding,
    params, the full layer's K and V pools, the carried state)."""
    from nvme_strom_tpu.models import serving
    from nvme_strom_tpu.models.transformer import init_params
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        hf = json.load(f)
    cfg = config_from_hf(dict(hf, num_hidden_layers=4))
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

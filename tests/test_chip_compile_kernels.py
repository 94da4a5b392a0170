"""Every Pallas kernel on the benchmark's cells compiles for a TPU v5e, alone
at its cell's shapes (``tests/chip_compile.py`` says how), and the paged
decode step of a small decoder moves nothing of its pool's size.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from chip_compile import (  # noqa: F401 — the first two are fixtures
    topo, _no_compile_cache,
    GDN_B, GDN_D, GDN_H, HD, K2C_POOL, MIMO_K, MIMO_SLOTS, MIMO_V, MIMO_WK,
    MIMO_WV, NH, NKV, SSM_B, SSM_H, SSM_N, SSM_P, _compile, _one, _small_step,
    _spec, pool_sized_ops)


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


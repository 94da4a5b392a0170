"""What the ``tests/test_chip_compile_*.py`` files share: every kernel
and serving program of the benchmark's cells compiles for a TPU v5e.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (``topologies.get_topology_desc``), so what
Mosaic or XLA would refuse on the machine with the chip is refused here
first, at no chip time.  Every kernel is compiled with
``interpret=False``.  Nothing runs: a compile that passes is not a chip
run.  One file a family (``_kernels``, ``_m7b``, ``_g4hm``, ``_lfm2``,
``_k2c``, ``_mimo``, ``_q3n``), because the driver's run keeps a file on
one worker and the longest file bounds it.

Run as a script on the machine with the chip (``python
tests/chip_compile.py``) it compiles the paged decode step for the
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
from jax.sharding import SingleDeviceSharding

NH, NKV, HD = 32, 8, 128
HBM_BYTES = 16 * 10**9

#: the serving sizes of the dense programs compiled whole: Llama-3.1-8B's
#: published widths (meta-llama/Llama-3.1-8B config.json — m7b's head
#: geometry, 32 query / 8 KV heads of 128) at the depth one 16 GB chip
#: holds beside the pools, 4 slots over 96 blocks of 128 rows
LLAMA31_8B = {
    "hidden_act": "silu",
    "hidden_size": 4096,
    "intermediate_size": 14336,
    "max_position_embeddings": 131072,
    "num_attention_heads": 32,
    "num_hidden_layers": 32,
    "num_key_value_heads": 8,
    "rms_norm_eps": 1e-05,
    "rope_scaling": {"factor": 8.0, "low_freq_factor": 1.0,
                     "high_freq_factor": 4.0,
                     "original_max_position_embeddings": 8192,
                     "rope_type": "llama3"},
    "rope_theta": 500000.0,
    "tie_word_embeddings": False,
    "vocab_size": 128256,
}
SMOKE_LAYERS = 16
SMOKE_MAX_LEN = 4096
SLOTS = 4
POOL_BLOCKS, BLOCK_LEN = 96, 128

# granite-4.0-h-micro's recurrent layer: 64 heads of 64, state 128, and the
# benchmark's 64 slots
SSM_B, SSM_H, SSM_P, SSM_N = 64, 64, 64, 128
GDN_B, GDN_H, GDN_D = 128, 32, 128      # the cell q3n.flood4k's state pool
K2C_POOL = (5, 4224 + 1, 576, 128)      # the cell k2c.flood8k's latent pool
MIMO_SLOTS, MIMO_BLOCKS = 64, 8704      # the cell mimo.flood16k's server
#: its pools: the full layers' pages (K 192 wide, which the device keeps
#: tokens-on-lanes, V 128 wide, which it does not) and the window layers'
#: rings, two blocks a slot and the sacrificial slot's
MIMO_K, MIMO_V = (2, MIMO_BLOCKS + 1, 4, 128, 192), (2, MIMO_BLOCKS + 1, 4,
                                                     128, 128)
MIMO_WK, MIMO_WV = (5, 2 * 65, 8, 128, 192), (5, 2 * 65, 8, 128, 128)
Q3N_SLOTS, Q3N_BLOCKS = 128, 5120       # the cell q3n.flood4k's server


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


def hf_config_of(name, layers, **keys):
    """``benchmark/configs/<name>.json`` cut to its first ``layers`` layers
    (``layer_types`` with them, where the file lists them), as a config;
    ``keys`` replace keys of the file."""
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           f"{name}.json")) as f:
        hf = json.load(f)
    if "layer_types" in hf:
        keys.setdefault("layer_types", hf["layer_types"][:layers])
    return config_from_hf(dict(hf, num_hidden_layers=layers, **keys))


def _smoke_cfg():
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    return config_from_hf(dict(LLAMA31_8B, num_hidden_layers=SMOKE_LAYERS))


def _param_specs(cfg, sharding_of):
    """``init_params``' own names and shapes, as bfloat16 specs."""
    from nvme_strom_tpu.models.transformer import init_params
    shapes = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    return {name: _spec(tuple(s.shape), jnp.bfloat16, sharding_of(name))
            for name, s in shapes.items()}


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


def check_projection_weights_read_in_place(topo, monkeypatch, name, program):
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

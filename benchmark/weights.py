"""Seeded bf16 weights: the benchmark's own generator.

Every tensor is a pure function of (seed, tensor index, element index), made
from integer arithmetic alone plus ONE float32 multiply, so the bits are the
same on the TPU, on the CPU, in numpy, inside one big jit or leaf by leaf:

    h      = mix(mix(i) ^ base(seed, index))          # uint32, lowbias32
    s      = byte0 + byte1 + byte2 + byte3 (+ offset)  # Irwin-Hall(4), int32
    value  = bfloat16(float32(s) * scale)

The serving cells make all weights on the device in one jitted call; the
restore cells write them as safetensors shards; the plain reference and the
bit-for-bit checks draw any tensor again from (seed, index).

Names and (in, out) layouts are those of the program's flat parameter dict
(``models/transformer.init_params``), which is also what a converted
checkpoint holds (``tools/convert_llama``)."""

from __future__ import annotations

import json
import os
import struct

import numpy as np

_SIGMA = (4 * (256 ** 2 - 1) / 12.0) ** 0.5      # std of a sum of 4 bytes
_MEAN = 510                                       # 4 * 127.5


def tensor_specs(hf: dict) -> list:
    """[(name, shape)] of the decoder described by an HF-style config."""
    d, v = hf["hidden_size"], hf["vocab_size"]
    hd = hf.get("head_dim") or d // hf["num_attention_heads"]
    nq, nkv = hf["num_attention_heads"] * hd, hf["num_key_value_heads"] * hd
    ff = hf["intermediate_size"]
    specs = [("tok_embed", (v, d)), ("final_norm", (d,)), ("lm_head", (d, v))]
    for i in range(hf["num_hidden_layers"]):
        p = f"layers.{i}."
        specs += [(p + "attn_norm", (d,)), (p + "wq", (d, nq)),
                  (p + "wk", (d, nkv)), (p + "wv", (d, nkv)),
                  (p + "wo", (nq, d)), (p + "mlp_norm", (d,)),
                  (p + "w_gate", (d, ff)), (p + "w_up", (d, ff)),
                  (p + "w_down", (ff, d))]
    return specs


def layer_indices(hf: dict) -> dict:
    """{name: index in tensor_specs} (the index keys the generator)."""
    return {name: i for i, (name, _) in enumerate(tensor_specs(hf))}


def _offset_scale(name: str, shape: tuple) -> tuple:
    """Integer offset and float32 scale: normal/sqrt(fan_in) for matmul
    weights, N(0,1) for the embedding, 1 + N(0, 0.1^2) for the norms."""
    if name.endswith("norm"):
        std, mean = 0.1, 1.0
    elif name == "tok_embed":
        std, mean = 1.0, 0.0
    else:
        std, mean = float(shape[0]) ** -0.5, 0.0
    scale = np.float32(std / _SIGMA)
    return int(round(mean / float(scale))) - _MEAN, scale


def _base(seed: int, index: int) -> int:
    """uint32 stream id of one tensor (host integers)."""
    x = (int(seed) * 0x9E3779B1 + int(index) * 0x85EBCA77 + 0x27D4EB2F)
    x &= 0xFFFFFFFF
    x ^= x >> 16
    x = (x * 0x7FEB352D) & 0xFFFFFFFF
    x ^= x >> 15
    x = (x * 0x846CA68B) & 0xFFFFFFFF
    x ^= x >> 16
    return x


def _mix(x, xp):
    x = x ^ (x >> 16)
    x = x * xp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * xp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _values(i, base, offset, scale, xp):
    h = _mix(_mix(i, xp) ^ base, xp)
    s = ((h & xp.uint32(0xFF)) + ((h >> 8) & xp.uint32(0xFF))
         + ((h >> 16) & xp.uint32(0xFF)) + (h >> 24)).astype(xp.int32)
    return (s + xp.int32(offset)).astype(xp.float32) * xp.float32(scale)


def bases(hf: dict, seed: int) -> np.ndarray:
    """uint32 stream id of every tensor of ``tensor_specs(hf)``: the only
    place the seed enters, and plain data to the jitted generators, so one
    compiled program serves every seed."""
    return np.asarray([_base(seed, i) for i in range(len(tensor_specs(hf)))],
                      dtype=np.uint32)


def make_tensor_np(seed: int, index: int, name: str, shape: tuple):
    """The tensor on the host in plain numpy — the generator's definition,
    used by the tests to pin the bits every backend must give."""
    import ml_dtypes
    n = int(np.prod(shape, dtype=np.int64))
    off, scale = _offset_scale(name, shape)
    with np.errstate(over="ignore"):
        vals = _values(np.arange(n, dtype=np.uint32),
                       np.uint32(_base(seed, index)), off, scale, np)
    return vals.astype(ml_dtypes.bfloat16).reshape(shape)


def make_tensor(base, name: str, shape: tuple):
    """The tensor as a traced jax value (call under jit); ``base`` is the
    tensor's traced uint32 stream id."""
    import jax.numpy as jnp
    from jax import lax
    n = int(np.prod(shape, dtype=np.int64))
    off, scale = _offset_scale(name, shape)
    i = lax.iota(jnp.uint32, n).reshape(shape)
    return _values(i, base, off, scale, jnp).astype(jnp.bfloat16)


def make_params(hf: dict, seed: int, shardings=None) -> dict:
    """All weights on the device(s) in one jitted call.  ``shardings``:
    None (default device), one sharding for every leaf, or {name: sharding}."""
    import jax
    specs = tensor_specs(hf)
    out_sh = None
    if shardings is not None:
        out_sh = {name: (shardings[name] if isinstance(shardings, dict)
                         else shardings) for name, _ in specs}

    def build(b):
        return {name: make_tensor(b[i], name, shape)
                for i, (name, shape) in enumerate(specs)}

    return jax.jit(build, out_shardings=out_sh)(bases(hf, seed))


_ONE = {}


def one_tensor(base, name: str, shape: tuple, sharding=None):
    """One tensor drawn again on the device (restore checks, checkpoint
    writer): one compiled program per (kind, shape, sharding)."""
    import jax
    kind = "norm" if name.endswith("norm") else name.rsplit(".", 1)[-1]
    key = (kind, tuple(shape), sharding)
    fn = _ONE.get(key)
    if fn is None:
        fn = _ONE[key] = jax.jit(
            lambda b: make_tensor(b, name, tuple(shape)),
            out_shardings=sharding)
    return fn(np.uint32(base))


# ---------------------------------------------------------------- checkpoint

def strom_config(hf: dict) -> dict:
    """``strom_config.json`` as ``tools/convert_llama.strom_config_dict``
    writes it for this config (what ``examples/serve.read_config`` reads)."""
    return {"vocab": hf["vocab_size"], "d_model": hf["hidden_size"],
            "n_layers": hf["num_hidden_layers"],
            "n_heads": hf["num_attention_heads"],
            "n_kv_heads": hf["num_key_value_heads"],
            "d_ff": hf["intermediate_size"],
            "max_seq": hf["max_position_embeddings"],
            "rope_theta": float(hf["rope_theta"]),
            "norm_eps": float(hf["rms_norm_eps"])}


def _write_shard(path: str, tensors: list) -> None:
    """safetensors: 8-byte header length, JSON header, row-major payloads.
    No CRC stamps: the restore path verifies none unless STROM_VERIFY is set."""
    header, pos = {}, 0
    for name, arr in tensors:
        header[name] = {"dtype": "BF16", "shape": list(arr.shape),
                        "data_offsets": [pos, pos + arr.nbytes]}
        pos += arr.nbytes
    hjson = json.dumps(header, separators=(",", ":")).encode()
    hjson += b" " * ((-(8 + len(hjson))) % 4096)
    with open(path, "wb") as f:
        f.write(struct.pack("<Q", len(hjson)) + hjson)
        for _, arr in tensors:
            f.write(np.ascontiguousarray(arr).view(np.uint16).data)


def ensure_checkpoint(root: str, hf: dict, seed: int, tag: str,
                      shard_bytes: int = 1 << 30) -> dict:
    """The checkpoint of (config ``tag``, seed) under ``root``/``tag``,
    written only if the directory does not already hold exactly that one.
    One seed is kept per configuration: a new seed replaces the old files, so
    the directory never grows.  Tensors are made by the same jitted generator
    on JAX's CPU backend (the copy back from the chip runs at 0.66 GiB/s,
    XLA:CPU makes 1 GiB/s and more: PERF.md §5), a few ahead of the shard
    being written.  Returns {"dir", "bytes", "written_s"}."""
    import time
    from concurrent.futures import ThreadPoolExecutor

    import jax
    out_dir = os.path.join(root, tag)
    stamp_path = os.path.join(out_dir, "stamp.json")
    specs = tensor_specs(hf)
    total = sum(2 * int(np.prod(s, dtype=np.int64)) for _, s in specs)
    stamp = {"seed": int(seed), "config": strom_config(hf), "bytes": total,
             "generator": 1}
    try:
        with open(stamp_path) as f:
            if json.load(f) == stamp:
                return {"dir": out_dir, "bytes": total, "written_s": 0.0}
    except (OSError, ValueError):
        pass
    t0 = time.monotonic()
    os.makedirs(out_dir, exist_ok=True)
    for stale in os.listdir(out_dir):
        os.unlink(os.path.join(out_dir, stale))
    host = jax.sharding.SingleDeviceSharding(jax.devices("cpu")[0])
    bs = bases(hf, seed)
    ahead = 4
    made = [one_tensor(bs[i], *specs[i], sharding=host)
            for i in range(min(ahead, len(specs)))]
    with ThreadPoolExecutor(1) as writer:
        pending, pending_bytes, writes, n_shards = [], 0, [], 0
        for i, (name, _) in enumerate(specs):
            arr = np.asarray(made.pop(0))
            if i + ahead < len(specs):
                made.append(one_tensor(bs[i + ahead], *specs[i + ahead],
                                       sharding=host))
            pending.append((name, arr))
            pending_bytes += arr.nbytes
            if pending_bytes >= shard_bytes or i + 1 == len(specs):
                if len(writes) >= 2:             # at most two shards queued
                    writes.pop(0).result()
                writes.append(writer.submit(_write_shard, os.path.join(
                    out_dir, f"strom-{n_shards:05d}.safetensors"), pending))
                n_shards += 1
                pending, pending_bytes = [], 0
        for w in writes:
            w.result()
    with open(os.path.join(out_dir, "strom_config.json"), "w") as f:
        json.dump(strom_config(hf), f, indent=1)
    with open(stamp_path, "w") as f:
        json.dump(stamp, f)
    return {"dir": out_dir, "bytes": total,
            "written_s": time.monotonic() - t0}

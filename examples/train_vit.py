"""End-to-end config-3 consumer: fixedrec image shards → ViT training.

BASELINE.json's headline config is "ImageNet-1k WebDataset shards →
infeed dataloader"; this example runs that loop on the framework's
FASTEST input path: fixed-size records stream NVMe → staging → device
with zero Python-side copies (data/loader.py fixedrec path, VERDICT
round-1 #2), and ALL decoding happens on device inside the jitted train
step — each record is ``C*H*W image bytes ++ 4 label bytes``, unpacked
with an on-device slice + bitcast (the same decode-on-the-accelerator
move as sql/pq_direct.py).

    python examples/train_vit.py --steps 20 --global-batch 32 --tp 2

For real WebDataset `.tar` image shards use examples/train_lm.py's
loader pattern with ``fmt="wds"`` and a host-side decode (counted as
bounce); this example sticks to fixedrec because it demonstrates the
bounce-free path.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--data-dir", default=None,
                    help="dir of .sfr fixedrec shards (synthesized if "
                         "omitted)")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--image-size", type=int, default=64)
    ap.add_argument("--classes", type=int, default=100)
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args(argv)

    import jax

    from nvme_strom_tpu.utils.compile_cache import enable_compile_cache
    from nvme_strom_tpu.utils.device import device_line
    print(device_line(), flush=True)
    enable_compile_cache()
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from nvme_strom_tpu.data.loader import ShardedLoader
    from nvme_strom_tpu.io import StromEngine
    from nvme_strom_tpu.models.vit import (
        ViTConfig, init_vit_params, make_vit_train_step,
        vit_param_shardings)
    from nvme_strom_tpu.parallel.mesh import make_mesh
    from nvme_strom_tpu.parallel.shardings import (
        prune_spec, replicate_scalars)

    cfg = ViTConfig(image_size=args.image_size, patch_size=8,
                    d_model=192, n_layers=4, n_heads=4, d_ff=768,
                    n_classes=args.classes)
    img_bytes = cfg.channels * cfg.image_size ** 2
    rec_bytes = img_bytes + 4                      # ++ int32 label
    mesh = make_mesh({"dp": -1, "tp": args.tp})
    print(f"mesh: {dict(mesh.shape)} model: d={cfg.d_model} "
          f"L={cfg.n_layers} img={cfg.image_size} rec={rec_bytes}B")

    engine = StromEngine()
    tmp = None
    data_dir = args.data_dir
    if data_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="strom_vit_")
        data_dir = tmp.name
        _synthesize_shards(data_dir, rec_bytes, img_bytes, args.classes,
                           n_shards=4, per_shard=4 * args.global_batch)
        print(f"data: synthesized 4 shards under {data_dir}")
    shards = sorted(os.path.join(data_dir, f)
                    for f in os.listdir(data_dir) if f.endswith(".sfr"))
    if not shards:
        ap.error(f"no .sfr shards found under {data_dir}")

    params = init_vit_params(jax.random.key(0), cfg)
    p_sh = vit_param_shardings(cfg, mesh)
    params = {k: jax.device_put(v, p_sh[k]) for k, v in params.items()}
    optimizer = optax.adamw(args.lr)
    opt_state = replicate_scalars(optimizer.init(params), mesh)
    b_sh = NamedSharding(mesh, prune_spec(P("dp"), mesh))

    vit_step = make_vit_train_step(cfg, optimizer)

    def step_raw(params, opt_state, records):
        """records (B, rec_bytes) uint8 → on-device unpack + train step.
        The slice/bitcast/normalize all run inside the jit — no host
        byte is ever touched (the PG-Strom decode-on-device pattern)."""
        imgs = records[:, :img_bytes].reshape(
            -1, cfg.image_size, cfg.image_size, cfg.channels)
        imgs = imgs.astype(cfg.dtype) / 255.0
        # (B, 4) uint8 → (B,) int32: bitcast folds the trailing dim
        labels = jax.lax.bitcast_convert_type(
            records[:, img_bytes:], jnp.int32)
        labels = jnp.clip(labels, 0, cfg.n_classes - 1)
        return vit_step(params, opt_state, imgs, labels)

    step_fn = jax.jit(step_raw,
                      in_shardings=(p_sh, None, b_sh),
                      out_shardings=(p_sh, None, None),
                      donate_argnums=(0, 1))

    t0 = time.monotonic()
    loss = None
    it = 0
    while it < args.steps:
        n_epoch = 0
        with ShardedLoader(shards, mesh, args.global_batch,
                           fmt="fixedrec", engine=engine) as loader:
            for rec in loader:
                params, opt_state, loss = step_fn(params, opt_state, rec)
                it += 1
                n_epoch += 1
                if it % 5 == 0 or it == args.steps:
                    print(f"step {it}: loss={float(loss):.4f}")
                if it >= args.steps:
                    break
        if n_epoch == 0:
            raise RuntimeError(
                f"shards under {data_dir} yield zero full batches of "
                f"{args.global_batch}")
    jax.block_until_ready(loss)
    dt = time.monotonic() - t0
    print(f"{args.steps} steps in {dt:.2f}s "
          f"({args.steps * args.global_batch / dt:.1f} img/s)")

    engine.sync_stats()
    s = engine.stats
    print(f"engine stats: direct={s.bytes_direct} "
          f"fallback={s.bytes_fallback} bounce={s.bounce_bytes} "
          f"to_device={s.bytes_to_device}")
    engine.close_all()
    if tmp:
        tmp.cleanup()
    return 0


def _synthesize_shards(dirpath: str, rec_bytes: int, img_bytes: int,
                       n_classes: int, n_shards: int,
                       per_shard: int) -> None:
    import numpy as np
    from nvme_strom_tpu.formats.fixedrec import write_fixedrec
    rng = np.random.default_rng(0)
    for s in range(n_shards):
        rec = np.empty((per_shard, rec_bytes), np.uint8)
        rec[:, :img_bytes] = rng.integers(
            0, 256, size=(per_shard, img_bytes), dtype=np.uint8)
        labels = rng.integers(0, n_classes, size=per_shard,
                              dtype=np.int32)
        rec[:, img_bytes:] = labels[:, None].view(np.uint8).reshape(
            per_shard, 4)
        write_fixedrec(os.path.join(dirpath, f"shard-{s:04d}.sfr"), rec)


if __name__ == "__main__":
    sys.exit(main())

"""Evaluation example: NVMe tokens → perplexity.

Completes the train/eval/generate/serve quartet: weights lazy-load
through the engine, evaluation tokens stream from either WebDataset
shards (the training layout) or a single ``.npy`` of shape
``(n_sequences, seq_len)`` int32 (the ``formats/npy.py`` direct
reader — payload bytes go NVMe→device untouched), and the metric is
token-mean cross-entropy / perplexity.

    python examples/eval_ppl.py --weights conv/ --npy heldout.npy
    python examples/eval_ppl.py --weights conv/ --data-dir shards/ \
        --batches 50
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--weights", required=True,
                    help="converted checkpoint dir (strom_config.json)")
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--npy", default=None,
                     help=".npy of (n, seq) int32 token sequences")
    src.add_argument("--data-dir", default=None,
                     help="dir of WebDataset .tar token shards")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--batches", type=int, default=0,
                    help="cap on evaluated batches (0 = everything)")
    ap.add_argument("--xent-chunks", type=int, default=0,
                    help="evaluate the cross-entropy in N sequence "
                         "slices — (b, s, vocab) logits never "
                         "materialize (the 100k+-vocab memory lever)")
    ap.add_argument("--int4", action="store_true",
                    help="weight-only int4 (lm_head stays fp; combine "
                         "with --int8 for the int8-lm_head mixed "
                         "recipe); the ppl delta vs fp is the cost")
    ap.add_argument("--int8", action="store_true",
                    help="weight-only int8 quantization after load "
                         "(models/quant.py) - also measures the "
                         "quantization's perplexity cost")
    args = ap.parse_args(argv)

    import jax

    from nvme_strom_tpu.utils.compile_cache import enable_compile_cache
    from nvme_strom_tpu.utils.device import device_line
    print(device_line(), flush=True)
    enable_compile_cache()
    import jax.numpy as jnp
    import numpy as np

    from nvme_strom_tpu.io import StromEngine
    from nvme_strom_tpu.models.transformer import TransformerConfig
    from nvme_strom_tpu.parallel.weights import LazyCheckpoint

    cfg_path = os.path.join(args.weights, "strom_config.json")
    if not os.path.exists(cfg_path):
        ap.error(f"{cfg_path} not found")
    with open(cfg_path) as f:
        cfg = TransformerConfig(**json.load(f))

    engine = StromEngine()
    params = LazyCheckpoint(args.weights).load_sharded(
        lambda name, shape: jax.sharding.SingleDeviceSharding(
            jax.devices()[0]),
        engine=engine)
    if args.int8:
        from nvme_strom_tpu.models.quant import quantize_weights_int8
        # with --int4 too: int8 ONLY the lm_head (the mixed recipe) —
        # int4 then converts the rest and passes dict leaves through
        sfx = ("lm_head",) if args.int4 else None
        params = quantize_weights_int8(params, suffixes=sfx)
        what = "lm_head (mixed recipe)" if args.int4 else "matmul weights"
        print(f"int8: {what} quantized "
              "(ppl delta vs fp measures the cost)", flush=True)
    if args.int4:
        from nvme_strom_tpu.models.quant import quantize_weights_int4
        params = quantize_weights_int4(params)
        print("int4: matmul weights packed 2/byte "
              "(ppl delta vs fp measures the cost)", flush=True)

    @jax.jit
    def eval_loss(params, tokens):
        # PURE token cross-entropy — loss_fn would fold in the MoE
        # router aux penalty and inflate the metric on expert configs
        if args.xent_chunks > 1:
            import dataclasses
            from nvme_strom_tpu.models.transformer import loss_fn
            # aux coef zeroed == pure token CE through the library's
            # own chunked path (no drift if its convention changes)
            return loss_fn(params, tokens, dataclasses.replace(
                cfg, xent_chunks=args.xent_chunks,
                router_aux_coef=0.0))
        from nvme_strom_tpu.models.transformer import forward
        logits = forward(params, tokens, cfg)
        lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
        ll = jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1)
        return -jnp.mean(ll)

    def batches():
        if args.npy:
            from nvme_strom_tpu.formats.npy import plan_npy
            from nvme_strom_tpu.ops.bridge import (DeviceStream,
                                                   split_ranges)
            entry = plan_npy(args.npy)
            if len(entry.shape) != 2:
                ap.error(f"--npy must be (n, seq), got {entry.shape}")
            if entry.dtype != "<i4":
                ap.error(f"--npy must be int32 token ids, "
                         f"got {entry.dtype}")
            n, seq = entry.shape
            if seq < 2:
                ap.error(f"--npy seq length {seq} < 2: nothing to "
                         "predict")
            # stream one batch of contiguous rows at a time — the file
            # need not fit in device memory, and --batches caps I/O
            row = seq * 4
            ds = DeviceStream(engine,
                              depth=engine.config.queue_depth)
            fh = engine.open(args.npy)
            try:
                for i in range(0, n - args.batch + 1, args.batch):
                    ranges, _ = split_ranges(
                        [(entry.offset + i * row, args.batch * row)],
                        engine.config.chunk_bytes)
                    parts = list(ds.stream_ranges(fh, ranges))
                    flat = (parts[0] if len(parts) == 1
                            else jnp.concatenate(parts))
                    toks = flat.view(jnp.int32).reshape(args.batch, seq)
                    if int(jnp.max(toks)) >= cfg.vocab or \
                            int(jnp.min(toks)) < 0:
                        ap.error(f"--npy holds ids outside "
                                 f"[0, {cfg.vocab}) at batch {i}")
                    yield toks
            finally:
                engine.close(fh)
            return
        import glob
        shards = sorted(glob.glob(os.path.join(args.data_dir, "*.tar")))
        if not shards:
            ap.error(f"no .tar shards under {args.data_dir}")
        from nvme_strom_tpu.data.loader import ShardedLoader
        from nvme_strom_tpu.parallel.mesh import make_mesh
        mesh = make_mesh({"dp": 1})

        def decode(parts):
            (payload,) = parts.values()
            return np.frombuffer(payload, dtype=np.int32) % cfg.vocab
        with ShardedLoader(shards, mesh, args.batch, fmt="wds",
                           decode=decode, engine=engine) as loader:
            yield from loader

    t0 = time.monotonic()
    total_loss, total_tok, n = 0.0, 0, 0
    for tokens in batches():
        if args.batches and n >= args.batches:
            break
        loss = float(eval_loss(params, tokens))   # token-mean CE
        ntok = tokens.shape[0] * (tokens.shape[1] - 1)
        total_loss += loss * ntok
        total_tok += ntok
        n += 1
    if n == 0:
        ap.error("no full batches to evaluate")
    dt = time.monotonic() - t0
    ce = total_loss / total_tok
    print(f"evaluated {n} batches / {total_tok} predicted tokens "
          f"in {dt:.2f}s")
    print(f"cross-entropy: {ce:.4f} nats/token   "
          f"perplexity: {float(np.exp(ce)):.2f}")

    engine.sync_stats()
    s = engine.stats
    print(f"engine stats: direct={s.bytes_direct} "
          f"fallback={s.bytes_fallback} bounce={s.bounce_bytes}")
    engine.close_all()
    return 0


if __name__ == "__main__":
    sys.exit(main())

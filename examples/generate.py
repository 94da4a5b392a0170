"""Generation example: NVMe weight shards → KV-cache decode.

Completes the inference story end to end: weights lazy-load through the
O_DIRECT engine (per-tensor ranged reads, parallel/weights.py), the
whole generation loop is one jitted ``lax.scan`` (models/decode.py), and
long prompts automatically use the Pallas decode-attention kernel
(measured ~1.7x over the XLA einsum at S≈1856 on a v5e,
ops/decode_attention.py).

    # from a converted checkpoint dir (tools/convert_llama or
    # parallel.weights.save_checkpoint)
    python examples/generate.py --weights conv/ --prompt 1,2,3 --new 32

    # straight from a HuggingFace Llama checkpoint dir
    python examples/generate.py --from-hf Meta-Llama-3.1-8B/ \
        --out-dir conv/ --prompt 1,2,3 --new 32

Token-id in, token-id out — tokenizers are out of scope for a storage
framework; feed ids from whatever tokenizer matches the checkpoint.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--weights", default=None,
                     help="converted checkpoint dir (must contain "
                          "strom_config.json)")
    src.add_argument("--from-hf", default=None, metavar="HF_DIR",
                     help="HF Llama checkpoint dir; converted into "
                          "--out-dir first (reused when already there)")
    ap.add_argument("--out-dir", default=None,
                    help="conversion output dir for --from-hf")
    ap.add_argument("--prompt", default="1,2,3,4",
                    help="comma-separated token ids")
    ap.add_argument("--new", type=int, default=32,
                    help="tokens to generate")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--int8", action="store_true",
                    help="weight-only int8 quantization after load "
                         "(halved weight streaming; models/quant.py)")
    ap.add_argument("--int4", action="store_true",
                    help="packed int4 (with --int8: the mixed recipe — "
                         "int8 lm_head, int4 everything else)")
    ap.add_argument("--offload", default=None, metavar="PAGEFILE",
                    help="decode with the SSD-backed KV cache spilling "
                         "pages to this path (greedy only; HBM holds a "
                         "bounded window, history streams from NVMe)")
    ap.add_argument("--offload-window", type=int, default=1024,
                    help="HBM window positions for --offload")
    ap.add_argument("--offload-quant", choices=["int8"], default=None,
                    help="quantize cold pages (halves the NVMe stream)")
    ap.add_argument("--offload-chunked-prefill", action="store_true",
                    help="prefill the prompt in page-sized chunks too "
                         "(bounded HBM for arbitrary prompt lengths)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from nvme_strom_tpu.utils.compile_cache import enable_compile_cache
    from nvme_strom_tpu.utils.device import device_line

    print(device_line(), flush=True)
    enable_compile_cache()

    from nvme_strom_tpu.io import StromEngine
    from nvme_strom_tpu.models.decode import generate
    from nvme_strom_tpu.models.transformer import TransformerConfig
    from nvme_strom_tpu.ops.decode_attention import make_decode_attn
    from nvme_strom_tpu.parallel.weights import LazyCheckpoint

    weights_dir = args.weights
    if args.from_hf:
        if not args.out_dir:
            ap.error("--from-hf needs --out-dir for the conversion")
        from nvme_strom_tpu.tools.convert_llama import convert
        if not os.path.exists(os.path.join(args.out_dir,
                                           "strom_config.json")):
            summary = convert(args.from_hf, args.out_dir)
            print(f"converted {summary['tensors']} tensors", flush=True)
        weights_dir = args.out_dir

    cfg_path = os.path.join(weights_dir, "strom_config.json")
    if not os.path.exists(cfg_path):
        ap.error(f"{cfg_path} not found — convert with "
                 "tools/convert_llama or pass a converted dir")
    with open(cfg_path) as f:
        cfg = TransformerConfig(**json.load(f))

    if args.new < 1:
        ap.error("--new must be >= 1")
    prompt_ids = [int(t) for t in args.prompt.split(",") if t.strip()]
    if not prompt_ids:
        ap.error("empty prompt")
    if max(prompt_ids) >= cfg.vocab or min(prompt_ids) < 0:
        ap.error(f"prompt ids must be in [0, {cfg.vocab})")
    total = len(prompt_ids) + args.new
    if total > cfg.max_seq:
        ap.error(f"prompt+new = {total} exceeds max_seq {cfg.max_seq}")

    engine = StromEngine()
    t0 = time.monotonic()
    params = LazyCheckpoint(weights_dir).load_sharded(
        lambda name, shape: jax.sharding.SingleDeviceSharding(
            jax.devices()[0]),
        engine=engine)
    print(f"weights: {len(params)} tensors in "
          f"{time.monotonic() - t0:.2f}s", flush=True)
    if args.int8:
        from nvme_strom_tpu.models.quant import (quantize_weights_int8,
                                                 quantized_nbytes)
        sfx = ("lm_head",) if args.int4 else None
        params = quantize_weights_int8(params, suffixes=sfx)
        q, fp = quantized_nbytes(params)
        what = "lm_head only (mixed recipe)" if args.int4 \
            else "matmul weights"
        print(f"int8: {what} {q >> 20} MiB "
              f"(vs {fp >> 20} MiB fp32)", flush=True)
    if args.int4:
        from nvme_strom_tpu.models.quant import (quantize_weights_int4,
                                                 quantized_nbytes)
        params = quantize_weights_int4(params)
        q, fp = quantized_nbytes(params)
        print(f"int4: all quantized leaves now {q >> 20} MiB "
              f"(vs {fp >> 20} MiB fp32; incl. any int8 lm_head)",
              flush=True)

    prompt = jnp.asarray([prompt_ids], jnp.int32)
    rng = jax.random.key(args.seed)
    if args.offload:
        # bounded-HBM decode: history beyond the window lives on NVMe
        if args.temperature != 0.0:
            ap.error("--offload decode is greedy (temperature 0)")
        from nvme_strom_tpu.models.kv_offload import (
            OffloadConfig, offloaded_generate)
        page_len = max(4, args.offload_window // 4)
        window_pages = max(1, args.offload_window // page_len)
        if args.offload_chunked_prefill and window_pages < 2:
            ap.error("--offload-chunked-prefill needs --offload-window "
                     ">= 8 (at least two pages)")
        ocfg = OffloadConfig(
            path=args.offload, page_len=page_len,
            window_pages=window_pages, quantize=args.offload_quant)
        t0 = time.monotonic()
        out = offloaded_generate(
            params, prompt, cfg, ocfg, engine, args.new,
            eos_id=args.eos_id,
            chunked_prefill=args.offload_chunked_prefill)
        dt = time.monotonic() - t0
        # single cold run: the time INCLUDES XLA compilation of the
        # prefill and per-layer segments — not comparable to the dense
        # branch's warm number
        print(f"offloaded decode: window={ocfg.window} "
              f"quant={args.offload_quant or 'off'} "
              f"(cold timing, includes compile)")
    else:
        # long live-cache decodes win with the fused Pallas kernel;
        # short ones with XLA's einsum (measured crossover ~1k
        # positions)
        cache_attn = make_decode_attn() if total >= 1024 else None
        gen = jax.jit(functools.partial(
            generate, cfg=cfg, max_new_tokens=args.new,
            temperature=args.temperature, eos_id=args.eos_id,
            cache_attn=cache_attn))
        out = gen(params, prompt, rng=rng)
        out.block_until_ready()                  # compile (discarded)
        t0 = time.monotonic()
        out = gen(params, prompt, rng=rng)
        out.block_until_ready()
        dt = time.monotonic() - t0
    ids = [int(t) for t in out[0]]
    print(f"generated {args.new} tokens in {dt:.3f}s "
          f"({args.new / dt:.1f} tok/s)")
    print("output ids:", ",".join(map(str, ids)))

    engine.sync_stats()
    s = engine.stats
    print(f"engine stats: direct={s.bytes_direct} "
          f"fallback={s.bytes_fallback} bounce={s.bounce_bytes}")
    engine.close_all()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Serving example: NVMe weight shards → continuous-batching decode.

The inference-serving walkthrough: weights lazy-load through the
O_DIRECT engine (parallel/weights.py), requests with different prompts
and budgets share the slots and the paged K/V pool of one decode server
(models/serving.py), and every step advances all active requests — freed
slots admit queued work immediately.

    python examples/serve.py --weights conv/ \
        --request 1,2,3:16 --request 7,8:32 --request 5:8

Each --request is ``comma-separated-prompt-ids:max_new``.  Token-id in,
token-id out — tokenizers are out of scope for a storage framework.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def read_config(weights_dir: str):
    """``strom_config.json`` of a converted checkpoint dir."""
    from nvme_strom_tpu.models.transformer import TransformerConfig

    with open(os.path.join(weights_dir, "strom_config.json")) as f:
        return TransformerConfig(**json.load(f))


def load_weights(weights_dir: str, engine):
    """Converted checkpoint dir → params on the first device, streamed
    through ``engine`` (parallel/weights.py)."""
    import jax

    from nvme_strom_tpu.parallel.weights import LazyCheckpoint

    return LazyCheckpoint(weights_dir).load_sharded(
        lambda name, shape: jax.sharding.SingleDeviceSharding(
            jax.devices()[0]),
        engine=engine)


def build_server(params, cfg, *, slots: int, max_len: int,
                 paged: int = 0, block_len: int = 128):
    """The decode server ``main`` serves from: ``slots`` slots over a
    shared KV pool of ``paged`` blocks of ``block_len`` positions; 0 is the
    pool the server works out (every slot's worst case)."""
    from nvme_strom_tpu.models.serving import DecodeServer
    return DecodeServer(params, cfg, max_batch=slots, max_len=max_len,
                        total_blocks=paged or None, block_len=block_len)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--weights", required=True,
                    help="converted checkpoint dir (must contain "
                         "strom_config.json; see tools/convert_llama)")
    ap.add_argument("--request", action="append", default=[],
                    metavar="IDS:MAX_NEW",
                    help="prompt token ids and budget, e.g. 1,2,3:16 "
                         "(repeatable)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=None,
                    help="per-slot sequence capacity (default: model "
                         "max_seq)")
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature for every request "
                         "(0 = greedy)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus truncation (with --temperature > 0)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base sampling seed; request i uses seed+i")
    ap.add_argument("--paged", type=int, default=0, metavar="BLOCKS",
                    help="size of the shared KV pool in blocks (capacity "
                         "= total live tokens; default: every slot's "
                         "worst case, slots × ceil(max-len / block-len))")
    ap.add_argument("--block-len", type=int, default=128,
                    help="positions per pool block")
    ap.add_argument("--lookahead", type=int, default=1,
                    help="decode steps per host readback (8-16 "
                         "amortizes a high-latency host<->device link; "
                         "token-identical to 1)")
    args = ap.parse_args(argv)
    if not args.request:
        ap.error("at least one --request")
    if args.slots < 1:
        ap.error(f"--slots must be >= 1, got {args.slots}")
    # pure-argument conditions fail BEFORE the expensive weight load
    if args.paged < 0 or args.block_len < 1:
        ap.error("--paged must be >= 0 and --block-len >= 1")

    from nvme_strom_tpu.io import StromEngine
    from nvme_strom_tpu.utils.compile_cache import enable_compile_cache
    from nvme_strom_tpu.utils.device import device_line

    print(device_line(), flush=True)
    enable_compile_cache()

    cfg_path = os.path.join(args.weights, "strom_config.json")
    if not os.path.exists(cfg_path):
        ap.error(f"{cfg_path} not found — convert with "
                 "tools/convert_llama first")
    cfg = read_config(args.weights)
    max_len = args.max_len or cfg.max_seq

    reqs = []
    for i, spec in enumerate(args.request):
        ids_part, _, new_part = spec.partition(":")
        try:
            ids = [int(t) for t in ids_part.split(",") if t.strip()]
            max_new = int(new_part or 16)
        except ValueError:
            ap.error(f"bad --request {spec!r} (want IDS:MAX_NEW)")
        if not ids:
            ap.error(f"empty prompt in --request {spec!r}")
        if max(ids) >= cfg.vocab or min(ids) < 0:
            ap.error(f"--request {spec!r}: ids must be in "
                     f"[0, {cfg.vocab})")
        # validate bounds BEFORE the expensive weight load — the same
        # checks DecodeServer.submit enforces, surfaced as ap.error
        if max_new < 1:
            ap.error(f"--request {spec!r}: MAX_NEW must be >= 1")
        if len(ids) + max_new > max_len:
            ap.error(f"--request {spec!r}: prompt {len(ids)} + "
                     f"{max_new} exceeds max_len {max_len}")
        if args.paged and (len(ids) + max_new
                           > args.paged * args.block_len):
            ap.error(f"--request {spec!r}: worst case "
                     f"{len(ids) + max_new} tokens can never fit the "
                     f"{args.paged}x{args.block_len} pool")
        reqs.append((f"r{i}", ids, max_new))

    engine = StromEngine()
    t0 = time.monotonic()
    params = load_weights(args.weights, engine)
    print(f"weights: {len(params)} tensors in "
          f"{time.monotonic() - t0:.2f}s", flush=True)

    srv = build_server(params, cfg, slots=args.slots, max_len=max_len,
                       paged=args.paged, block_len=args.block_len)
    for i, (rid, ids, max_new) in enumerate(reqs):
        srv.submit(rid, ids, max_new, eos_id=args.eos_id,
                   temperature=args.temperature, top_p=args.top_p,
                   seed=args.seed + i)

    t0 = time.monotonic()
    results = srv.run(lookahead=args.lookahead)
    dt = time.monotonic() - t0
    total = sum(len(v) for v in results.values())
    for rid, ids, _ in reqs:
        print(f"{rid}: {','.join(map(str, results[rid]))}")
    print(f"served {len(reqs)} requests / {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s aggregate, {args.slots} slots)")

    engine.sync_stats()
    s = engine.stats
    print(f"engine stats: direct={s.bytes_direct} "
          f"fallback={s.bytes_fallback} bounce={s.bounce_bytes}")
    engine.close_all()
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""End-to-end example: train the flagship LM with every framework layer.

This is the "switching user" walkthrough — the full consumer path the
reference serves for PG-Strom (SURVEY.md §3.5), assembled from this
framework's pieces:

  strom-io engine ── WebDataset shards ──► ShardedLoader ──► device batches
        │                                                      │
        ├─ safetensors shards ──► LazyCheckpoint ──► sharded params
        │                                                      │
        │                     jit(make_train_step) over a dp×tp Mesh
        │                                                      │
        └──◄── CheckpointManager (direct writes) ◄── step state ┘

Run on any backend (CPU works: JAX_PLATFORMS=cpu python examples/train_lm.py
--steps 5 --tiny).  Every byte of input and weights moves through the
engine; stats print at the end (bounce_bytes == 0 on the direct path to an
accelerator).
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _make_lr_schedule(args):
    """LR as a float (pure constant) or an optax schedule.

    Warmup is linear 0→lr over --warmup-steps; after that either flat
    (``constant``) or cosine-decayed to 10% of peak over the remaining
    --steps (``cosine``).  Returned as a plain float when neither knob
    is set so the offloaded-optimizer path (which takes float-or-
    callable) keeps its simplest form.  On resume the schedule position
    comes from the optimizer's own step count (optax count / Offloaded-
    Adam .step), not wall progress, so a resumed run continues the
    decay where it left off.
    """
    import optax
    if args.lr_schedule == "constant" and args.warmup_steps <= 0:
        return args.lr
    decay_steps = max(args.steps, args.warmup_steps + 1)
    if args.lr_schedule == "cosine":
        return optax.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=args.lr,
            warmup_steps=args.warmup_steps,
            decay_steps=decay_steps, end_value=args.lr * 0.1)
    return optax.join_schedules(
        [optax.linear_schedule(0.0, args.lr, args.warmup_steps),
         optax.constant_schedule(args.lr)],
        boundaries=[args.warmup_steps])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mix", default=None, metavar="DIR:W,DIR:W",
                    help="train on a weighted MIXTURE of shard dirs "
                         "(seeded per-step source draws, identical on "
                         "every host) instead of one --data-dir")
    ap.add_argument("--data-dir", default=None,
                    help="dir of WebDataset .tar shards of token arrays "
                         "(int32, seq_len per sample); synthesized if "
                         "omitted")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--init-weights", default=None,
                    help="glob of safetensors shards to warm-start from "
                         "(lazy NVMe->HBM load)")
    ap.add_argument("--from-hf", default=None, metavar="HF_DIR",
                    help="warm-start from a HuggingFace Llama checkpoint "
                         "dir: converted once (tools/convert_llama) into "
                         "--ckpt-dir/hf_converted, model config taken "
                         "from its config.json")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=2048,
                    help="training sequence length (--from-hf caps the "
                         "HF max_position_embeddings to this)")
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--lr-schedule", choices=("constant", "cosine"),
                    default="constant",
                    help="learning-rate shape after warmup: constant, or "
                         "cosine decay to 10%% of --lr over --steps")
    ap.add_argument("--warmup-steps", type=int, default=0,
                    help="linear LR warmup from 0 to --lr over N steps")
    ap.add_argument("--grad-clip", type=float, default=0.0,
                    metavar="NORM",
                    help="clip gradients to this global L2 norm before "
                         "the optimizer update (0 = off)")
    ap.add_argument("--xent-chunks", type=int, default=0,
                    help="cross-entropy over N sequence slices so the "
                         "(b, s, vocab) logits never materialize — the "
                         "memory lever for 100k+ vocabs (0 = off)")
    ap.add_argument("--accum-steps", type=int, default=1,
                    help="gradient-accumulation microbatches per step "
                         "(activation memory of global-batch/N)")
    ap.add_argument("--watchdog", type=float, default=0.0,
                    metavar="SECONDS",
                    help="per-step deadline: a hung step dumps all "
                         "thread stacks + engine counters to stderr "
                         "(0 = off)")
    ap.add_argument("--tiny", action="store_true",
                    help="tiny config (CI/demo) instead of the flagship")
    ap.add_argument("--save-every", type=int, default=10)
    ap.add_argument("--lora", type=int, default=0, metavar="RANK",
                    help="train rank-RANK LoRA adapters instead of full "
                         "weights (base stays frozen; checkpoints hold "
                         "only adapters + their optimizer state)")
    ap.add_argument("--lora-alpha", type=float, default=None,
                    help="LoRA scale numerator (default: RANK)")
    ap.add_argument("--remat", default="none",
                    choices=("none", "dots", "full", "nvme"),
                    help="rematerialization policy: 'dots' saves matmul "
                         "outputs and recomputes elementwise ops (most "
                         "of full remat's memory win at a fraction of "
                         "its recompute); 'full' recomputes whole "
                         "layers; 'nvme' additionally moves the "
                         "layer-boundary activations to NVMe "
                         "(--offload-acts DIR) — O(1)-layers HBM "
                         "activations")
    ap.add_argument("--offload-acts", default=None, metavar="DIR",
                    help="backing dir for --remat nvme "
                         "(parallel/act_offload ActivationStore)")
    ap.add_argument("--flash", action="store_true",
                    help="use the Pallas fused flash-attention kernel "
                         "(O(seq) memory) instead of XLA dense "
                         "attention")
    ap.add_argument("--offload-opt", default=None, metavar="DIR",
                    help="keep Adam moments on NVMe under DIR instead of "
                         "HBM (parallel/opt_offload): HBM holds one "
                         "group of moments at a time, so optimizer "
                         "state no longer bounds trainable model size")
    args = ap.parse_args(argv)
    if args.offload_opt and args.lora:
        ap.error("--offload-opt is for full fine-tunes; LoRA optimizer "
                 "state is adapter-sized and lives happily in HBM")
    if (args.remat == "nvme") != bool(args.offload_acts):
        ap.error("--remat nvme and --offload-acts DIR go together")
    if args.remat == "nvme" and args.lora:
        ap.error("--remat nvme is for full fine-tunes; LoRA's frozen "
                 "base already skips most activation memory")

    import jax
    import numpy as np
    import optax
    from nvme_strom_tpu.checkpoint.manager import CheckpointManager
    from nvme_strom_tpu.data.loader import ShardedLoader
    from nvme_strom_tpu.io import StromEngine
    from nvme_strom_tpu.models.transformer import (
        flagship_config, init_params, make_train_step, tiny_config)
    from nvme_strom_tpu.parallel.mesh import make_mesh
    from nvme_strom_tpu.parallel.shardings import (
        batch_shardings, param_shardings, replicate_scalars)
    from nvme_strom_tpu.parallel.weights import LazyCheckpoint
    from nvme_strom_tpu.utils.compile_cache import enable_compile_cache
    from nvme_strom_tpu.utils.device import device_line

    print(device_line(), flush=True)
    enable_compile_cache()

    if args.from_hf:
        # Convert ONCE (skipped when a prior run already converted into
        # this ckpt-dir) and adopt the HF architecture as the config.
        if args.init_weights or args.tiny:
            ap.error("--from-hf is mutually exclusive with "
                     "--init-weights/--tiny (it supplies both weights "
                     "and model config)")
        if not args.ckpt_dir:
            ap.error("--from-hf needs --ckpt-dir: the converted shards "
                     "are a durable multi-GB artifact")
        import json as _json
        from nvme_strom_tpu.tools.convert_llama import convert
        from nvme_strom_tpu.models.transformer import TransformerConfig
        import hashlib
        conv_dir = os.path.join(args.ckpt_dir, "hf_converted")
        marker = os.path.join(conv_dir, "strom_config.json")
        src_marker = os.path.join(conv_dir, "source.json")
        reusable = False
        if os.path.exists(marker) and os.path.exists(src_marker):
            with open(src_marker) as f:
                src = _json.load(f)
            with open(os.path.join(args.from_hf, "config.json"),
                      "rb") as f:
                sha = hashlib.sha256(f.read()).hexdigest()
            reusable = src.get("config_sha256") == sha
            if not reusable:
                ap.error(
                    f"{conv_dir} holds a conversion of a DIFFERENT "
                    f"checkpoint ({src.get('hf_dir')}); refusing to mix "
                    "— use a fresh --ckpt-dir or delete hf_converted/")
        if reusable:
            print(f"from-hf: reusing converted shards under {conv_dir}")
        else:
            summary = convert(args.from_hf, conv_dir)
            print(f"from-hf: converted {summary['tensors']} tensors "
                  f"into {summary['shards']} shard(s) under {conv_dir}")
        with open(marker) as f:
            cfg = TransformerConfig(**_json.load(f))
        if cfg.max_seq > args.seq_len:
            # HF configs carry max_position_embeddings up to 128k; the
            # training seq length is a run choice, not the model ceiling
            import dataclasses
            cfg = dataclasses.replace(cfg, max_seq=args.seq_len)
        args.init_weights = conv_dir  # dir form: every shard inside
    else:
        cfg = tiny_config() if args.tiny else flagship_config()
    if args.remat != "none":
        import dataclasses
        cfg = dataclasses.replace(cfg, remat_policy=args.remat)
    if args.xent_chunks > 1:
        import dataclasses
        cfg = dataclasses.replace(cfg, xent_chunks=args.xent_chunks)
    attn_fn = None
    if args.flash:
        from nvme_strom_tpu.ops.flash_attention import make_flash_attn
        attn_fn = make_flash_attn()
    mesh = make_mesh({"dp": -1, "tp": args.tp})
    print(f"mesh: {dict(mesh.shape)} devices={len(jax.devices())} "
          f"model: d={cfg.d_model} L={cfg.n_layers} vocab={cfg.vocab}")

    engine = StromEngine()
    tmp = None
    mix_specs = None           # [(shard list, weight)] when --mix
    if args.mix:
        if args.data_dir:
            ap.error("--mix and --data-dir conflict: list every corpus "
                     "in --mix (DIR:W,DIR:W)")
        mix_specs = []
        for part in args.mix.split(","):
            d, _, w = part.rpartition(":")
            try:
                weight = float(w)
            except ValueError:
                weight = -1.0
            if not d or weight <= 0:
                ap.error(f"--mix entry {part!r}: want DIR:WEIGHT "
                         "with a positive weight")
            entry = sorted(os.path.join(d, f) for f in os.listdir(d)
                           if f.endswith(".tar"))
            if not entry:
                ap.error(f"--mix: no .tar shards under {d}")
            mix_specs.append((entry, weight))
        data_dir = None
    else:
        data_dir = args.data_dir
        if data_dir is None:
            tmp = tempfile.TemporaryDirectory(prefix="strom_lm_")
            data_dir = tmp.name
            _synthesize_shards(data_dir, cfg, n_shards=4,
                               per_shard=8 * args.global_batch)
            print(f"data: synthesized 4 shards under {data_dir}")
        shards = sorted(
            os.path.join(data_dir, f) for f in os.listdir(data_dir)
            if f.endswith(".tar"))
        if not shards:
            ap.error(f"no .tar shards found under {data_dir}")

    ckpt_dir = args.ckpt_dir or os.path.join(
        tmp.name if tmp else ".", "ckpt")
    mgr = CheckpointManager(ckpt_dir, engine=engine)
    start = mgr.latest_step()

    p_sh = param_shardings(cfg, mesh)
    # Full fine-tune resumes overwrite params from the checkpoint, so
    # the warm start only matters on a fresh run — but a LoRA resume
    # restores ONLY adapters, so its frozen base must reload every time.
    if args.init_weights and (start is None or args.lora):
        params = LazyCheckpoint(args.init_weights).load_sharded(
            p_sh, engine=engine)
        print(f"params: lazy-loaded {len(params)} tensors from "
              f"{args.init_weights}")
    else:
        # fixed seed: the re-initialized base is identical across runs,
        # so a LoRA resume without a warm start is still coherent
        params = init_params(jax.random.key(0), cfg)
        params = {k: jax.device_put(v, p_sh[k]) for k, v in params.items()}

    lr_sched = _make_lr_schedule(args)
    optimizer = optax.adamw(lr_sched)
    if args.grad_clip > 0:
        optimizer = optax.chain(
            optax.clip_by_global_norm(args.grad_clip), optimizer)
    b_sh = batch_shardings(mesh)
    act_store = None
    if args.offload_acts:
        if len(jax.devices()) > 1:
            raise SystemExit(
                "--remat nvme is single-device: the store's ordered "
                "io_callbacks cannot lower inside a multi-device "
                "computation — use --remat full/dots on meshes")
        from nvme_strom_tpu.parallel.act_offload import ActivationStore
        act_store = ActivationStore(
            os.path.join(args.offload_acts, "acts.bin"),
            cfg.n_layers, engine=engine)
        print(f"offload-acts: {cfg.n_layers} layer slots under "
              f"{args.offload_acts} (O(1)-layers HBM activations)")
    if args.lora:
        # frozen streamed base + tiny trainable adapters: the
        # checkpoint/optimizer state shrinks to adapter size
        from nvme_strom_tpu.models.lora import (
            count_params, lora_init, make_lora_train_step)
        from jax.sharding import NamedSharding, PartitionSpec
        alpha = (args.lora_alpha if args.lora_alpha is not None
                 else float(args.lora))
        base = params
        rep = NamedSharding(mesh, PartitionSpec())   # adapters are tiny
        trainable = jax.device_put(
            lora_init(jax.random.key(1), base, args.lora), rep)
        opt_state = jax.device_put(optimizer.init(trainable), rep)
        _lora_step = jax.jit(
            make_lora_train_step(cfg, optimizer, alpha=alpha,
                                 accum_steps=args.accum_steps),
            donate_argnums=(0, 1))

        def step_fn(tr, ost, tokens):
            return _lora_step(tr, ost, base, tokens)
        print(f"lora: rank {args.lora} alpha {alpha:g} — "
              f"{count_params(trainable)} trainable of "
              f"{count_params(base)} base params")
    elif args.offload_opt:
        # grads on device, moments on NVMe: the jitted step stops at the
        # gradient; OffloadedAdam streams each moment group through the
        # engine around a per-group update
        from nvme_strom_tpu.models.transformer import (
            accumulate_grads, loss_fn)
        from nvme_strom_tpu.parallel.opt_offload import OffloadedAdam

        trainable = params
        opt_state = ()          # NVMe-resident; manifest is the state
        offl = OffloadedAdam(args.offload_opt, params, lr=lr_sched,
                             weight_decay=1e-4,  # = optax.adamw default
                             engine=engine)

        def gstep(p, tokens):
            loss, grads = accumulate_grads(
                lambda mb: jax.value_and_grad(
                    lambda q: loss_fn(q, mb, cfg, attn_fn,
                                      act_store=act_store))(p),
                p, tokens, args.accum_steps)
            if args.grad_clip > 0:
                grads, _ = optax.clip_by_global_norm(
                    args.grad_clip).update(grads, optax.EmptyState())
            return loss, grads

        grad_fn = jax.jit(gstep, in_shardings=(p_sh, b_sh))

        def step_fn(tr, ost, tokens):
            loss, grads = grad_fn(tr, tokens)
            return offl.update(tr, grads), ost, loss

        print(f"offload-opt: {offl.moment_bytes() >> 20} MiB of moments "
              f"on NVMe, peak {offl.peak_group_bytes() >> 20} MiB in "
              f"HBM, {offl.num_groups()} groups, resumed at step "
              f"{offl.step}")
    else:
        trainable = params
        opt_state = replicate_scalars(optimizer.init(params), mesh)
        step_fn = jax.jit(make_train_step(cfg, optimizer,
                                          attn_fn=attn_fn,
                                          accum_steps=args.accum_steps,
                                          act_store=act_store),
                          in_shardings=(p_sh, None, b_sh),
                          out_shardings=(p_sh, None, None),
                          donate_argnums=(0, 1))

    if start is not None:
        trainable, opt_state = mgr.restore((trainable, opt_state))
        if args.lora:
            # restore commits to single-device placements; the adapters
            # must live replicated beside the tp-sharded base
            trainable = jax.device_put(trainable, rep)
            opt_state = jax.device_put(opt_state, rep)
        print(f"resumed from step {start}")
    start = (start or 0)
    if args.offload_opt and offl.step != start:
        # A crash between --save-every checkpoints leaves the moment
        # manifest ahead of the params checkpoint; pairing step-M params
        # with step-N moments (and t=N+1 bias correction) diverges
        # SILENTLY, so refuse instead.
        raise SystemExit(
            f"offload-opt: moment manifest is at step {offl.step} but "
            f"params resume at step {start} — Adam would run a "
            "divergent trajectory.  Restore the params checkpoint "
            f"matching step {offl.step}, or start a fresh moment dir "
            "(the moments update in place every step; only "
            "checkpoint-aligned pairs are coherent)")

    def decode(parts):
        (payload,) = parts.values()
        return np.frombuffer(payload, dtype=np.int32) % cfg.vocab

    def batches():
        if mix_specs is not None:
            from contextlib import ExitStack
            from nvme_strom_tpu.data import MixtureLoader
            with ExitStack() as stack:
                loaders = [
                    (stack.enter_context(
                        ShardedLoader(e, mesh, args.global_batch,
                                      fmt="wds", decode=decode,
                                      engine=engine)), w)
                    for e, w in mix_specs]
                mix = MixtureLoader(loaders, seed=0)
                for b, _src in mix:     # unbounded: sources restart
                    yield b
            return
        while True:
            n = 0
            with ShardedLoader(shards, mesh, args.global_batch, fmt="wds",
                               decode=decode, engine=engine) as loader:
                for b in loader:
                    n += 1
                    yield b
            if n == 0:
                raise RuntimeError(
                    f"shards under {data_dir} yield zero full batches of "
                    f"{args.global_batch}")

    from contextlib import nullcontext
    from nvme_strom_tpu.data.prefetch import prefetch_to_device
    from nvme_strom_tpu.utils.watchdog import StepWatchdog
    it = prefetch_to_device(batches(), size=2)
    wd = (StepWatchdog(args.watchdog, engine=engine)
          if args.watchdog > 0 else None)
    t0 = time.monotonic()
    loss = None
    for step in range(start, args.steps):
        # the armed region covers the HOST SYNC POINTS too
        # (block_until_ready/float(loss)/save) — async dispatch means a
        # wedged collective usually hangs there, not in step_fn
        with wd.step(f"step {step}") if wd else nullcontext():
            tokens = next(it)
            trainable, opt_state, loss = step_fn(trainable, opt_state,
                                                 tokens)
            if (step + 1) % args.save_every == 0 or step + 1 == args.steps:
                jax.block_until_ready(loss)
                if jax.process_count() == 1:
                    # snapshot now (donation-safe numpy copies), NVMe
                    # write overlaps the next steps; errors surface at
                    # the next save/restore/wait
                    mgr.save_async(step + 1, (trainable, opt_state))
                else:
                    mgr.save(step + 1, (trainable, opt_state))
                print(f"step {step + 1}: loss={float(loss):.4f} "
                      f"(checkpointed)")
            elif (step + 1) % 5 == 0:
                print(f"step {step + 1}: loss={float(loss):.4f}")
    jax.block_until_ready(loss)
    dt = time.monotonic() - t0
    print(f"{args.steps - start} steps in {dt:.2f}s "
          f"({(args.steps - start) / max(dt, 1e-9):.2f} steps/s)")

    if wd:
        wd.close()
    it.close()  # drain the loader's prefetch thread BEFORE engine teardown
    mgr.wait_pending()  # last async save durable (or raising) before exit
    engine.sync_stats()
    s = engine.stats
    print(f"engine stats: direct={s.bytes_direct} "
          f"fallback={s.bytes_fallback} bounce={s.bounce_bytes} "
          f"to_device={s.bytes_to_device}")
    engine.close_all()
    if tmp:
        tmp.cleanup()
    return 0


def _synthesize_shards(dirpath: str, cfg, n_shards: int,
                       per_shard: int) -> None:
    """Tar shards of int32 token arrays (one .bin per sample)."""
    import io
    import tarfile
    import numpy as np
    rng = np.random.default_rng(0)
    for s in range(n_shards):
        with tarfile.open(os.path.join(dirpath, f"lm-{s:04d}.tar"),
                          "w") as tf:
            for i in range(per_shard):
                toks = rng.integers(0, cfg.vocab, cfg.max_seq,
                                    dtype=np.int32).tobytes()
                ti = tarfile.TarInfo(f"{s:04d}{i:05d}.bin")
                ti.size = len(toks)
                tf.addfile(ti, io.BytesIO(toks))


if __name__ == "__main__":
    sys.exit(main())

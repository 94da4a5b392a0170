"""Runner of the serving cells of an SDAR-MoE configuration (generation by
diffusion over blocks): THE timed loop of ``runners/serve.py`` — called, not
copied — with the weights drawn by ``benchmark/weights_sdar.py`` and a
comparison of its own.

``serve.run`` reaches its generator and its comparison through its module
globals ``W`` and ``served_gaps``; this binds both for the call (a shim until
``serve.py`` takes them from the configuration: PERF.md section 7).  A served
token was made at a denoising step whose input held masks, so the causal
teacher-forced replay does not apply: the sample is replayed through
``reference/sdar_bd.py``'s two-stream forward at every (block, denoising
step) with the step at which each token was committed, which the server
hands on at retirement (``request_metrics[rid]["commit_steps"]``: collected
here as requests finish, through ``ctx.test``'s ``server_built`` hook).
Compared are the mean and the widest gap by which a served token's reference
logit lies below the reference's best at its position and step, and — two
more checks, added to the result's ``checks`` — the mean and the widest gap
by which the reference's log-confidence of a committed position lies below
that of the best position of its block left masked at that step (the widest
swings with one near-tie of a window and parts nothing; the mean is what
tells a program that does not choose by confidence)."""

from __future__ import annotations

from benchmark import harness, weights_sdar
from benchmark.runners import serve


def gaps_of_sample(hf: dict, seed: int, sample: list, ref_name: str,
                   mask_id: int) -> dict:
    """The served sample ([{"prompt", "tokens", "steps"}]) against the sound
    reference: ``reference/sdar_bd.gaps``."""
    ref = harness.plugin("reference", ref_name)
    bl = hf["serving"]["diffusion"]["block_length"]
    sound = ref.replay(hf, seed, sample, bl, mask_id)
    return ref.gaps(sound, ref.served_choice(sample, bl))


def run(ctx) -> dict:
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    try:
        cfg = config_from_hf(ctx.config)
    except Exception as e:          # a checkout that cannot read the file
        raise SystemExit(f"benchmark: this checkout's program cannot read "
                         f"an sdar_moe configuration ({e})")
    if not getattr(cfg, "diffusion_block", 0):
        raise SystemExit("benchmark: this checkout's program does not "
                         "generate by diffusion over blocks (config_from_hf "
                         "gives no diffusion_block)")
    steps, seen = {}, {}
    hooks = dict(ctx.test or {})

    def server_built(srv):
        inner = srv.step_many

        def step_many(k):           # the commit steps, as requests finish
            finished = inner(k)
            for rid in finished:
                steps[rid] = srv.request_metrics[rid]["commit_steps"]
            return finished
        srv.step_many = step_many
        if hooks.get("server_built"):
            hooks["server_built"](srv)

    def after_window(ctx, sample):  # the sample, with its commit steps
        for r in sample:
            r["steps"] = list(steps[r["rid"]])
        if hooks.get("after_window"):
            hooks["after_window"](ctx, sample)

    def served_gaps(hf, seed, sample, ref_name):
        seen.update(gaps_of_sample(hf, seed, sample, ref_name,
                                   cfg.mask_token_id))
        return seen

    dense, causal = serve.W, serve.served_gaps
    serve.W, serve.served_gaps = weights_sdar, served_gaps
    ctx.test = dict(hooks, server_built=server_built,
                    after_window=after_window)
    try:
        res = serve.run(ctx)
    finally:
        serve.W, serve.served_gaps = dense, causal
        ctx.test = hooks
    limits = ctx.config["correct"]
    t = res["facts"]["timings"]
    if t.get("moe_pairs"):
        print(f"experts: a layer's busiest takes "
              f"{t['moe_load_max'] * cfg.n_experts / t['moe_pairs']:.2f}x "
              f"the mean load of a forward's call", flush=True)
    if seen:
        print(f"reference: {seen['conf_off']} of {seen['choices']} choices "
              f"of a (block, step) left a more confident position masked",
              flush=True)
    for name, key in (("mean", "conf_mean_gap"), ("max", "conf_gap")):
        value = 0.0 if hooks.get("skip_reference") else seen.get(key)
        res["checks"].insert(-1, (f"served.{name}_confidence_gap", value,
                                  limits[f"served_{key}_limit"]))
    return res

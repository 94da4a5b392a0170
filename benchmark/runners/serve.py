"""Runner of the serving cells: the program's paged decode server
(``examples/serve.build_server``) under a closed queue or an open-loop
schedule, timed by the benchmark's own clock.

After the window the server and its weights are freed, and a seeded sample of
the requests it finished (the longest among them) is replayed through the
plain reference: for every served token, how far its reference logit lies
below the reference's best at that position."""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import harness
from benchmark import weights as W
from benchmark.traffic import prompt_tokens

#: requests replayed through the reference after the window
SAMPLE = 6


def reference_logits(hf: dict, seed: int, sample: list, ref_name: str,
                     low=None):
    """Replay ``sample`` ([{"prompt": ids, "tokens": ids}]) through the plain
    reference, teacher-forced on the served tokens.  Returns (logits (S, K,
    vocab) at the positions that predict each served token, served tokens
    (S, K), valid (S, K))."""
    ref = harness.plugin("reference", ref_name)
    width = max(len(r["prompt"]) + len(r["tokens"]) for r in sample)
    width = -(-width // 128) * 128
    k = max(len(r["tokens"]) for r in sample)
    toks = np.zeros((len(sample), width), np.int32)
    at = np.zeros((len(sample), k), np.int32)
    served = np.zeros((len(sample), k), np.int32)
    valid = np.zeros((len(sample), k), bool)
    for i, r in enumerate(sample):
        seq = list(r["prompt"]) + list(r["tokens"])
        toks[i, :len(seq)] = seq
        n = len(r["tokens"])
        # token j of the answer is predicted from position prompt + j - 1
        at[i, :n] = len(r["prompt"]) - 1 + np.arange(n)
        served[i, :n] = r["tokens"]
        valid[i, :n] = True
    return ref.logits_at(hf, seed, toks, at, low=low), served, valid


def gaps_of(logits, tokens, valid) -> dict:
    """How far each of ``tokens`` lies below the best logit at its position:
    the widest and the mean gap, and how many are off the best."""
    import jax.numpy as jnp
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, jnp.asarray(tokens)[..., None],
                              axis=-1)[..., 0]
    gaps = np.asarray(best - got)[valid]
    return {"max_gap": float(gaps.max()), "mean_gap": float(gaps.mean()),
            "tokens": int(valid.sum()), "off_best": int((gaps > 0).sum()),
            "scale": float(jnp.max(jnp.abs(logits)))}


def served_gaps(hf: dict, seed: int, sample: list, ref_name: str) -> dict:
    """The program's number: gaps of the tokens it served."""
    logits, served, valid = reference_logits(hf, seed, sample, ref_name)
    return gaps_of(logits, served, valid)


def control_gaps(hf: dict, seed: int, sample: list, ref_name: str,
                 low: str = "int8") -> dict:
    """The control's number: at the same positions of the same prompts and
    tokens, the gap of the token that the reference computed in the lower
    precision puts first."""
    import jax.numpy as jnp
    logits, _, valid = reference_logits(hf, seed, sample, ref_name)
    lowl, _, _ = reference_logits(hf, seed, sample, ref_name, low=low)
    return gaps_of(logits, np.asarray(jnp.argmax(lowl, axis=-1)), valid)


def _pick_sample(finished: list, seed: int) -> list:
    """The longest finished request and SAMPLE-1 others drawn from the seed."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (r["prompt_len"] + len(r["tokens"]),
                                           -r["rid"]))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng([int(seed), 0x5A3F])
    picks = [rest[j] for j in rng.permutation(len(rest))[:SAMPLE - 1]]
    return [longest] + picks


def _warm(srv, sched, seed, vocab, slots, lookahead) -> None:
    """Every shape the window will use, and no other: the decode step at the
    server's slot count, each prompt length of the schedule once per slot
    round, and the token stacks of 1..lookahead sub-steps."""
    import jax.numpy as jnp
    lens = sorted({r["prompt_len"] for r in sched["requests"]})
    for i in range(max(slots, len(lens))):
        srv.submit(("warm", i), prompt_tokens(seed, 10**9 + i,
                                              lens[i % len(lens)], vocab),
                   lookahead + 2 + i % 3)
    srv.run(lookahead=lookahead)
    for k in range(1, lookahead + 1):
        jnp.stack([srv.tok] * k).block_until_ready()


def run(ctx) -> dict:
    import jax

    from examples.serve import build_server
    from nvme_strom_tpu.tools.convert_llama import config_from_hf

    hf, serving = ctx.config, ctx.config["serving"]
    sched = harness.plugin("traffic.kinds", ctx.traffic["kind"]).schedule(
        ctx.traffic, ctx.seed, ctx.seconds)
    lookahead = sched["lookahead"]
    open_loop = sched["requests"][0]["due"] is not None
    cfg = config_from_hf(hf)
    device = jax.devices()[0]
    params = W.make_params(hf, ctx.seed)
    jax.block_until_ready(params)
    srv = build_server(params, cfg, slots=serving["slots"],
                       max_len=serving["max_len"],
                       paged=serving["total_blocks"],
                       block_len=serving["block_len"])
    if ctx.test and ctx.test.get("server_built"):
        ctx.test["server_built"](srv)
    _warm(srv, sched, ctx.seed, hf["vocab_size"], serving["slots"],
          lookahead)
    print(f"set-up: {ctx.compiles.count} programs compiled or fetched from "
          f"the cache in {ctx.compiles.seconds:.1f}s", flush=True)

    reqs = sched["requests"]
    for r in reqs:
        r.update(tokens=None, n=0, t_submit=None, t_admit=None,
                 t_first=None, t_last=None, error=None,
                 prompt=prompt_tokens(ctx.seed, r["rid"], r["prompt_len"],
                                      hf["vocab_size"]))
    by_rid = {r["rid"]: r for r in reqs}
    clock = time.monotonic
    if ctx.trace.on:
        # spans of the traced run only: the program's admission, seen from
        # outside (an instance attribute; the class is not touched)
        inner = srv._finish_traced

        def admit(plan, restored):
            with ctx.trace.annotate("admit"):
                return inner(plan, restored)
        srv._finish_traced = admit

    live = [0.0, 0]                      # sum of live tokens, observations

    def account(finished: dict, t: float) -> None:
        """Deliveries seen after one ``step_many``: ``t`` on our clock."""
        live[0] += sum(len(q.prompt) + len(q.out) for q in srv.slots if q)
        live[1] += 1
        for req in srv.slots:
            if req is None or not req.out:
                continue
            r = by_rid[req.rid]
            if r["t_first"] is None:
                r["t_first"] = t
                r["t_admit"] = req.t_admit      # the program's clock
            r["n"] = len(req.out)
        for rid, toks in finished.items():
            r = by_rid[rid]
            if r["t_first"] is None:
                r["t_first"] = t
            r["n"], r["tokens"], r["t_last"] = len(toks), list(toks), t

    def delivered() -> int:
        return sum(r["n"] for r in reqs)

    queue_len = []                       # (t, waiting) for the rate sweep
    if open_loop:
        lead = -min(r["due"] for r in reqs)
        origin = clock() + lead          # window opens at origin
        setup_s = origin - ctx.t_start   # the lead-in is not set-up, but it
        # is before the window: counted, so that a longer one shows
        nxt, t_close = 0, None
        timings0 = tokens0 = None
        while True:
            now = clock() - origin
            if timings0 is None and now >= 0:
                timings0, tokens0 = dict(srv.timings), delivered()
                ctx.compiles.mark()
            if timings0 is not None and t_close is None:
                ctx.trace.tick(now, ctx.seconds)
            if t_close is None and now >= ctx.seconds:
                t_close = now
                timings1, tokens1 = dict(srv.timings), delivered()
                compiles = ctx.compiles.count
            with ctx.trace.annotate("submit"):
                while nxt < len(reqs) and reqs[nxt]["due"] <= now:
                    r = reqs[nxt]
                    srv.submit(r["rid"], r["prompt"], r["budget"])
                    r["t_submit"] = clock() - origin
                    nxt += 1
            open_sampled = [r for r in reqs
                            if r["sampled"] and r["t_last"] is None]
            if t_close is not None and (not open_sampled or now
                                        > ctx.seconds + sched["drain_limit_s"]):
                break
            if srv.idle:
                with ctx.trace.annotate("wait"):
                    wait = (reqs[nxt]["due"] - now) if nxt < len(reqs) else 0.05
                    time.sleep(max(0.0, min(wait, 0.05)))
                continue
            with ctx.trace.annotate("step"):
                finished = srv.step_many(lookahead)
            t = clock() - origin
            account(finished, t)
            queue_len.append((t, len(srv.queue)))
        # the profiler takes tens of seconds to stop after 20 s of eager
        # prefills: stopping at the window's close would stall the drain, so
        # the traced span runs on through it (same traffic: steady state)
        ctx.trace.stop()
        window_s = t_close
        sampled = [r for r in reqs if r["sampled"]]
        for r in sampled:
            if r["t_last"] is None:
                r["error"] = "unfinished at the drain limit"
    else:
        for r in reqs:
            srv.submit(r["rid"], r["prompt"], r["budget"])
            r["t_submit"] = 0.0
        while any(s is None for s in srv.slots):
            account(srv.step_many(lookahead), -1.0)
        origin = clock()
        setup_s = origin - ctx.t_start
        timings0, tokens0 = dict(srv.timings), delivered()
        ctx.compiles.mark()
        while clock() - origin < ctx.seconds:
            ctx.trace.tick(clock() - origin, ctx.seconds)
            with ctx.trace.annotate("step"):
                finished = srv.step_many(lookahead)
            t = clock() - origin
            account(finished, t)
        window_s = clock() - origin
        ctx.trace.stop()
        timings1, tokens1 = dict(srv.timings), delivered()
        compiles = ctx.compiles.count
        sampled = [r for r in reqs if r["t_last"] is not None
                   and r["t_last"] >= 0]

    peak = harness.memory_peak_bytes([device])
    for r in reqs:                       # monotonic -> window time
        if r["t_admit"] is not None:
            r["t_admit"] -= origin
    for r in sampled:
        if r["error"] is None and r["n"] != r["budget"]:
            r["error"] = f"{r['n']} tokens for a budget of {r['budget']}"
    failed = [r for r in sampled if r["error"]]
    for r in failed[:5]:
        print(f"failed: request {r['rid']} (prompt {r['prompt_len']}, "
              f"budget {r['budget']}, due {r['due']}): {r['error']}",
              flush=True)
    live_tokens = live[0] / max(1, live[1])   # keys and values a step reads

    # free the program's state, then the reference (memory stays the
    # program's peak: it was read above)
    srv.params = None
    del srv, params
    gc.collect()
    finished_ok = [r for r in sampled if not r["error"]]
    sample = _pick_sample(finished_ok, ctx.seed)
    if ctx.test and ctx.test.get("after_window"):
        ctx.test["after_window"](ctx, sample)
    limits = ctx.config["correct"]
    t_ref = clock()
    gap = mean_gap = None
    if ctx.test and ctx.test.get("skip_reference"):
        gap = mean_gap = 0.0             # the rate sweep only
    elif sample:
        g = served_gaps(hf, ctx.seed, sample, ctx.config["reference"])
        print(f"reference: {g['tokens']} served tokens of {len(sample)} "
              f"requests replayed in {clock() - t_ref:.1f}s; "
              f"{g['off_best']} off the reference's best, mean gap "
              f"{g['mean_gap']:.4g}, max|logit| {g['scale']:.4g}",
              flush=True)
        gap, mean_gap = g["max_gap"], g["mean_gap"]
    wrong = sum(1 for r in sampled
                if r["t_last"] is not None and r["n"] != r["budget"])
    checks = [("served.mean_logit_gap", mean_gap,
               limits["served_mean_gap_limit"]),
              ("served.max_logit_gap", gap, limits["served_max_gap_limit"]),
              ("served.requests_with_wrong_token_count", wrong, 0)]
    return {"setup_s": setup_s, "window_s": window_s,
            "attempted": len(sampled), "failed": len(failed),
            "checks": checks, "memory_peak_bytes": peak,
            "facts": {"requests": sampled, "open_loop": open_loop,
                      "tokens_in_window": tokens1 - tokens0,
                      "timings": {k: timings1[k] - timings0[k]
                                  for k in timings1},
                      "compiles_in_window": compiles,
                      "live_tokens": live_tokens,
                      "slots": serving["slots"],
                      "queue_len": queue_len}}

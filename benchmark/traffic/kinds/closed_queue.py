"""Offline batch generation: a closed queue, everything queued before the
window.  Request i has budget ``budgets[i % len(budgets)]`` — the same for
every seed, so requests finish, and slots are refilled, at the same points of
every run — and a prompt length from ``prompts``: each run of
``len(prompts)`` consecutive requests holds every length once, in an order
drawn from the seed.  Any stretch of the queue a window reaches therefore
holds the same multiset of lengths for every seed (runs of the first version,
which permuted whole (prompt, budget) cycles, spread by 5 % between seeds and
1 % within one: PERF.md)."""

import numpy as np


def schedule(traffic: dict, seed: int, seconds: float) -> dict:
    prompts, budgets = traffic["prompts"], traffic["budgets"]
    rng = np.random.default_rng([int(seed), 0xC105ED])
    reqs = []
    while len(reqs) < traffic["requests"]:
        for j in rng.permutation(len(prompts)):
            if len(reqs) < traffic["requests"]:
                reqs.append({"rid": len(reqs), "due": None,
                             "prompt_len": int(prompts[j]),
                             "budget": int(budgets[len(reqs) % len(budgets)]),
                             "sampled": True})
    return {"requests": reqs, "lookahead": int(traffic["lookahead"])}

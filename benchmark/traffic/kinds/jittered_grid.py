"""Open loop, independent users, built to repeat.

* Fixed count, jittered grid: with rate r and window T exactly
  N = round(r*T) sampled requests; request i is due at (i + u_i)/r, u_i
  uniform in [0, 1) from the seed.
* Fixed multiset, seeded order: the (prompt, budget) pairs are consecutive
  entries of the cyclic list ``pairs``; the seed only permutes the order
  inside the lead-in, the sampled and the drain segment.
* Steady state at both ends: the same grid runs for ``lead_in_s`` before the
  window (negative due times) and goes on for ``drain_limit_s`` after it; only
  requests due inside the window are sampled."""

import math

import numpy as np


def schedule(traffic: dict, seed: int, seconds: float) -> dict:
    rate = float(traffic["rate"])
    pairs = [tuple(p) for p in traffic["pairs"]]
    n = int(round(rate * seconds))
    n_lead = int(math.ceil(traffic["lead_in_s"] * rate))
    n_post = int(math.ceil(traffic["drain_limit_s"] * rate))
    rng = np.random.default_rng([int(seed), 0x9E1D])
    jitter = rng.random(n_lead + n + n_post)

    def segment(first: int, count: int) -> list:
        seg = [pairs[(first + j) % len(pairs)] for j in range(count)]
        return [seg[j] for j in rng.permutation(count)]

    # sampled first, so that its multiset is the head of the list for any N
    body = segment(0, n)
    lead = segment(n, n_lead)
    post = segment(n + n_lead, n_post)
    reqs = []
    for slot, (prompt, budget) in enumerate(lead + body + post):
        i = slot - n_lead
        reqs.append({"rid": slot, "due": (i + float(jitter[slot])) / rate,
                     "prompt_len": int(prompt), "budget": int(budget),
                     "sampled": 0 <= i < n})
    return {"requests": reqs, "lookahead": int(traffic["lookahead"]),
            "drain_limit_s": float(traffic["drain_limit_s"])}

"""The three traffic kinds: the same seed gives the same schedule; every seed
gives the same count and the same multiset of lengths."""

from collections import Counter

import pytest

from benchmark import harness
from benchmark.traffic import prompt_tokens

SEEDS = (0, 7, 2**31 + 12345)
MIXES = ("restore", "restore4", "flood", "chat")


def _schedule(mix, seed, seconds=45.0):
    traffic = harness.load_json("benchmark", "traffic", mix + ".json")
    kind = harness.plugin("traffic.kinds", traffic["kind"])
    return kind.schedule(traffic, seed, seconds)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_schedule(mix):
    assert _schedule(mix, 5) == _schedule(mix, 5)


def _multiset(sched, key=lambda r: True):
    return Counter((r["prompt_len"], r["budget"])
                   for r in sched["requests"] if key(r))


@pytest.mark.parametrize("mix", ("flood", "chat"))
def test_every_seed_same_count_and_multiset(mix):
    scheds = [_schedule(mix, s) for s in SEEDS]
    assert len({len(s["requests"]) for s in scheds}) == 1
    count = (lambda r: (r["prompt_len"], r["budget"])) if mix == "chat" \
        else (lambda r: r["prompt_len"])     # flood: marginals are fixed
    for key in (lambda r: r["sampled"], lambda r: not r["sampled"]):
        assert len({frozenset(Counter(count(r) for r in s["requests"]
                                      if key(r)).items())
                    for s in scheds}) == 1
    orders = {tuple((r["prompt_len"], r["budget"]) for r in s["requests"])
              for s in scheds}
    assert len(orders) == len(SEEDS)        # the seed permutes the order


def test_flood_any_stretch_holds_the_same_lengths_and_budgets():
    a, b = _schedule("flood", 1), _schedule("flood", 2)
    assert ([r["budget"] for r in a["requests"]]
            == [r["budget"] for r in b["requests"]])
    for lo in (0, 12, 48, 236):
        cut = slice(lo, lo + 4)
        assert (sorted(r["prompt_len"] for r in a["requests"][cut])
                == sorted(r["prompt_len"] for r in b["requests"][cut])
                == [128, 256, 384, 512])
    assert Counter(r["budget"] for r in a["requests"]) == {
        128: 80, 192: 80, 256: 80}


def test_chat_grid():
    traffic = harness.load_json("benchmark", "traffic", "chat.json")
    rate = traffic["rate"]
    for seed in SEEDS:
        s = _schedule("chat", seed, 45.0)
        sampled = [r for r in s["requests"] if r["sampled"]]
        assert len(sampled) == round(rate * 45.0)
        dues = [r["due"] for r in s["requests"]]
        assert dues == sorted(dues)
        assert all(0 <= r["due"] < 45.0 + 1 / rate for r in sampled)
        gaps = [b - a for a, b in zip(dues, dues[1:])]
        assert max(gaps) < 2 / rate and min(gaps) >= 0
        # steady state at both ends: arrivals before 0 and after the window
        assert min(dues) <= -traffic["lead_in_s"] + 1 / rate
        assert max(dues) >= 45.0 + traffic["drain_limit_s"] - 2 / rate


def test_chat_shares():
    pairs = harness.load_json("benchmark", "traffic", "chat.json")["pairs"]
    assert Counter(p for p, _ in pairs) == {96: 12, 224: 9, 480: 6, 992: 3}
    assert Counter(b for _, b in pairs) == {64: 10, 96: 10, 128: 10}


def test_prompts_differ_and_repeat():
    a = prompt_tokens(2**31 + 5, 3, 96, 32768)
    assert a == prompt_tokens(2**31 + 5, 3, 96, 32768)
    assert a != prompt_tokens(2**31 + 5, 4, 96, 32768)
    assert len(a) == 96 and 0 <= min(a) and max(a) < 32768

"""Continuous batching (models/serving.py): per-request outputs are
token-identical to isolated decode.generate, under slot contention and
staggered admission."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nvme_strom_tpu.models import decode as dec
from nvme_strom_tpu.models.serving import DecodeServer
from nvme_strom_tpu.models.transformer import (
    TransformerConfig, init_params, tiny_config)


@pytest.fixture(scope="module")
def setup():
    cfg = TransformerConfig(**{**tiny_config().__dict__,
                               "dtype": jnp.float32})
    params = init_params(jax.random.key(0), cfg)
    return cfg, params


def _solo(params, cfg, prompt_ids, max_new, eos_id=None):
    """Reference: the request run alone through generate()."""
    out = np.asarray(dec.generate(
        params, jnp.asarray([prompt_ids], jnp.int32), cfg, max_new,
        eos_id=eos_id))[0].tolist()
    if eos_id is not None and eos_id in out:
        out = out[:out.index(eos_id) + 1]   # serving returns up to eos
    return out


def _server(pool, params, cfg, **kw):
    """Two slots of 64 positions over the pool the server works out
    ("slots": every slot's worst case, in one block of the default 128) or
    over an explicit, smaller one ("shared": 16 blocks of 8)."""
    if pool == "shared":
        kw = dict(total_blocks=16, block_len=8, **kw)
    return DecodeServer(params, cfg, max_batch=2, max_len=64, **kw)


def test_mixed_lengths_match_solo(setup):
    """Three requests with different prompt lengths and budgets, all
    admitted together, each matches its solo run."""
    cfg, params = setup
    rng = np.random.default_rng(0)
    reqs = {f"r{i}": (rng.integers(0, cfg.vocab, n).tolist(), m)
            for i, (n, m) in enumerate([(5, 12), (9, 7), (3, 15)])}
    srv = DecodeServer(params, cfg, max_batch=3, max_len=64)
    for rid, (p, m) in reqs.items():
        srv.submit(rid, p, m)
    got = srv.run()
    assert set(got) == set(reqs)
    for rid, (p, m) in reqs.items():
        assert got[rid] == _solo(params, cfg, p, m), rid


def test_max_new_one_and_first_token_eos(setup):
    """Admission-time completion under the DEFERRED first-token
    readback: a max_new=1 request and a request whose FIRST token is
    eos both retire at the batch readback (never having decoded a
    counted surplus token into their output), their slots recycle, and
    every result still matches solo.  This is the edge the round-5
    dispatch-only admission moved: retirement used to happen inside
    _admit, synchronously."""
    cfg, params = setup
    rng = np.random.default_rng(7)
    p1 = rng.integers(0, cfg.vocab, 6).tolist()
    # find a prompt whose first generated token can serve as eos
    first = _solo(params, cfg, p1, 1)[0]

    srv = DecodeServer(params, cfg, max_batch=2, max_len=64)
    srv.submit("one", p1, 1)                      # max_new == 1
    srv.submit("eos", p1, 10, eos_id=first)       # instant eos
    p3 = rng.integers(0, cfg.vocab, 4).tolist()
    srv.submit("tail", p3, 5)                     # queued behind both
    got = srv.run()
    assert got["one"] == [first]
    assert got["eos"] == [first]                  # stopped AT the eos
    assert got["tail"] == _solo(params, cfg, p3, 5)
    assert srv.idle
    # lookahead > 1 (surplus sub-steps decode past the retired slots)
    srv2 = DecodeServer(params, cfg, max_batch=2, max_len=64)
    srv2.submit("one", p1, 1)
    srv2.submit("eos", p1, 10, eos_id=first)
    got2 = srv2.run(lookahead=8)
    assert got2 == {"one": [first], "eos": [first]}


def test_slot_recycling_and_staggered_admission(setup):
    """More requests than slots: later requests admit into recycled
    slots mid-flight and still match their solo runs."""
    cfg, params = setup
    rng = np.random.default_rng(1)
    reqs = {f"q{i}": (rng.integers(0, cfg.vocab, 4 + i).tolist(), 5 + i)
            for i in range(5)}
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64)
    it = iter(reqs.items())
    # seed two, then drip the rest in while stepping
    for _ in range(2):
        rid, (p, m) = next(it)
        srv.submit(rid, p, m)
    got = {}
    steps = 0
    while not srv.idle or got.keys() != reqs.keys():
        got.update(srv.step())
        steps += 1
        if steps in (3, 6, 9):   # staggered arrivals mid-decode
            try:
                rid, (p, m) = next(it)
                srv.submit(rid, p, m)
            except StopIteration:
                pass
        assert steps < 200
    for rid, (p, m) in reqs.items():
        assert got[rid] == _solo(params, cfg, p, m), rid


def test_eos_stops_request_early(setup):
    cfg, params = setup
    rng = np.random.default_rng(2)
    p = rng.integers(0, cfg.vocab, 6).tolist()
    probe = _solo(params, cfg, p, 10)
    eos = probe[3]              # force an early stop
    want = _solo(params, cfg, p, 10, eos_id=eos)
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64)
    srv.submit("e", p, 10, eos_id=eos)
    got = srv.run()
    assert got["e"] == want
    assert got["e"][-1] == eos and len(got["e"]) <= 10


def test_validation(setup):
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=1, max_len=16)
    with pytest.raises(ValueError, match="empty"):
        srv.submit("x", [], 4)
    with pytest.raises(ValueError, match="max_new"):
        srv.submit("x", [1, 2], 0)
    with pytest.raises(ValueError, match="exceeds"):
        srv.submit("x", [1] * 10, 10)
    srv.submit("dup", [1, 2], 4)
    with pytest.raises(ValueError, match="already in flight"):
        srv.submit("dup", [3, 4], 4)


def test_paged_server_matches_solo(setup):
    """Block-pool serving (paged-attention kernel) is token-identical
    to solo generate, with a pool FAR smaller than slots×max_len."""
    cfg, params = setup
    rng = np.random.default_rng(7)
    reqs = {f"b{i}": (rng.integers(0, cfg.vocab, n).tolist(), m)
            for i, (n, m) in enumerate([(5, 9), (11, 6), (3, 12)])}
    # worst cases: 14, 17, 15 tokens → 4+5+4 = 13 blocks of 4;
    # dense reservation would be 3 slots × 64 rows = 48 blocks
    srv = DecodeServer(params, cfg, max_batch=3, max_len=64,
                       total_blocks=13, block_len=4)
    for rid, (p, m) in reqs.items():
        srv.submit(rid, p, m)
    got = srv.run()
    for rid, (p, m) in reqs.items():
        assert got[rid] == _solo(params, cfg, p, m), rid
    # every block is either free or resident in the (fully evictable)
    # prefix cache — none leaked, none still referenced
    cached = [e["blk"] for e in srv._pc.values()]
    assert sorted(srv.free + cached) == list(range(13))
    assert srv.stats()["prefix_evictable"] == len(cached)


def test_paged_server_queues_on_pool_exhaustion(setup):
    """Admission control: requests wait for blocks, recycled blocks
    admit them, everything still matches solo."""
    cfg, params = setup
    rng = np.random.default_rng(8)
    reqs = {f"q{i}": (rng.integers(0, cfg.vocab, 6).tolist(), 6)
            for i in range(4)}
    # each request needs ceil(12/4)=3 blocks; pool of 4 → strictly one
    # in flight even though 2 slots exist
    srv = DecodeServer(params, cfg, max_batch=2, max_len=32,
                       total_blocks=4, block_len=4)
    for rid, (p, m) in reqs.items():
        srv.submit(rid, p, m)
    steps = 0
    got = {}
    while not srv.idle:
        got.update(srv.step())
        active = sum(r is not None for r in srv.slots)
        assert active <= 1       # pool admits one 3-block request
        steps += 1
        assert steps < 200
    for rid, (p, m) in reqs.items():
        assert got[rid] == _solo(params, cfg, p, m), rid


def test_paged_server_rejects_oversized(setup):
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=1, max_len=16,
                       total_blocks=8, block_len=4)
    srv.submit("big", [1] * 8, 8)     # needs 4 blocks == max_blocks: ok
    with pytest.raises(ValueError, match="exceeds"):
        srv.submit("huge", [1] * 10, 7)   # 17 > max_len
    with pytest.raises(ValueError, match=">= 1"):
        DecodeServer(params, cfg, 1, 16, total_blocks=0)
    srv.run()


def test_moe_model_serves():
    """Expert-routed models run through the server over both pools (the
    dense-or-MoE dispatch is shared with decode), matching solo generate."""
    from nvme_strom_tpu.models.transformer import (
        TransformerConfig, init_params, tiny_moe_config)
    mcfg = TransformerConfig(**{**tiny_moe_config().__dict__,
                                "dtype": jnp.float32})
    mparams = init_params(jax.random.key(3), mcfg)
    rng = np.random.default_rng(9)
    p = rng.integers(0, mcfg.vocab, 6).tolist()
    want = _solo(mparams, mcfg, p, 6)
    for make in (lambda: DecodeServer(mparams, mcfg, 2, 32),
                 lambda: DecodeServer(mparams, mcfg, 2, 32,
                                      total_blocks=8,
                                      block_len=4)):
        srv = make()
        srv.submit("m", p, 6)
        assert srv.run()["m"] == want


def test_a_call_admits_at_most_admit_rows(setup):
    """Beside decoding slots ``ADMIT_ROWS`` bounds the padded prompt rows one
    call admits alone in their programs (a long burst must not hold them up;
    prompts that share a program are not counted): the queue fills the
    free slots over successive calls, always at least one prompt a call; an
    EMPTY server fills at once; and every request's tokens are those of an
    unbounded server."""
    cfg, params = setup
    prompts = {f"r{i}": [1 + i, 2, 3, 4, 5] for i in range(4)}   # 8 rows each

    def run(cap, first_alone, group_rows=0):
        srv = DecodeServer(params, cfg, max_batch=5, max_len=32,
                           total_blocks=20, block_len=4)
        if cap:
            srv.ADMIT_ROWS = cap
        srv._group_rows = group_rows         # 0: every prompt goes alone
        out = {}
        if first_alone:
            srv.submit("first", [9, 8, 7], 12)
            out.update(srv.step())           # one slot now decodes
        for rid, p in prompts.items():
            srv.submit(rid, p, 6)
        out.update(srv.step())
        busy = srv.stats()["slots_busy"]
        out.update(srv.run())
        return busy, out

    busy, out = run(12, True)       # one prompt of 8 rows fits, two do not
    assert busy == 1 + 1
    busy2, out2 = run(16, True)
    assert busy2 == 1 + 2
    busy_all, want = run(None, True)
    assert busy_all == 1 + 4 and out == out2 == want
    assert run(12, False)[0] == 4   # nobody decoding: nobody to hold up
    # prompts that share a program (two of 8 rows within 16) are not
    # counted: together is what makes them cheap
    busy_g, out_g = run(12, True, group_rows=16)
    assert busy_g == 1 + 4 and out_g == want


def test_server_stats_gauges(setup):
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=2, max_len=32,
                       total_blocks=6, block_len=4)
    srv.submit("a", [1, 2, 3], 5)      # needs 2 blocks
    srv.submit("b", [4, 5], 5)         # needs 2 blocks
    s0 = srv.stats()
    want0 = {"slots_total": 2, "slots_busy": 0, "queued": 2,
             "inflight_tokens": 0, "blocks_total": 6,
             "blocks_free": 6, "prefix_cached_blocks": 0,
             "prefix_evictable": 0, "prefix_hits": 0,
             "prefix_shared_blocks": 0, "requests_finished": 0,
             "ttft_ms_avg": 0.0, "ttft_ms_max": 0.0,
             "admit_wait_ms_avg": 0.0, "admit_wait_ms_max": 0.0,
             "admissions_shed": 0, "prefill_programs": 0,
             # the two kinds of cache: every layer keeps K/V here, and no
             # layer carries a recurrent state (tests/test_hybrid.py)
             "kv_layers": cfg.n_layers, "state_bytes": 0, "state_slots": 0,
             "state_bytes_per_slot": 0, "state_layers": 0,
             "state_heads_per_lane_row": 1,
             # no decode step yet: paged attention has walked nothing
             "attn_blocks_live": 0, "attn_blocks_table": 0,
             "attn_grid_steps": 0,
             # no layer's MLP is the exact expert layer here, so nothing is
             # routed (tests/test_lfm2.py has a model that does)
             "moe_layers": 0, "moe_calls": 0, "moe_pairs": 0,
             "moe_pairs_routed": 0, "moe_rows_computed": 0,
             "moe_experts_touched": 0, "moe_load_max": 0,
             "moe_rounds": 0, "moe_calls_prefill": 0,
             "moe_rounds_prefill": 0,
             # nor a share of a deployment's experts, nor a latent pool
             # (tests/test_mla.py has both)
             "experts_held": 0, "latent_bytes_per_token": 0,
             # K and V of every layer's kv heads, float32 here; no window
             # layer, so no ring
             "kv_bytes_per_token": 2 * cfg.n_layers * cfg.n_kv_heads
             * cfg.head_dim * 4,
             "window_layers": 0, "window_bytes_per_slot": 0,
             "window_rows_live": 0,
             # a token a step: no diffusion blocks, no forwards counted
             "diffusion_block": 0, "bd_tokens_per_forward": 0.0,
             "bd_writes_fused": 0}
    assert s0 == want0
    srv.step()
    s1 = srv.stats()
    assert s1["slots_busy"] == 2 and s1["queued"] == 0
    # one step, both slots inside their first block of a table 8 wide
    assert (s1["attn_blocks_live"], s1["attn_blocks_table"]) == (2, 16)
    # and the kernel's grid was those two entries, a step each
    assert s1["attn_grid_steps"] == 2
    # both prompts, one block each, went through ONE call of the bucket's
    # one program (four wide at four rows a prompt: two rows dead)
    assert s1["prefill_programs"] == 1
    assert (srv.timings["admits"], srv.timings["prefill_calls"],
            srv.timings["prefill_rows_dead"]) == (2, 1, 2)
    assert s1["blocks_free"] == 2 and s1["inflight_tokens"] >= 2
    srv.run()
    s2 = srv.stats()
    assert s2["slots_busy"] == 0 and s2["blocks_free"] == 6


def test_server_ttft_and_admission_wait_metrics(setup):
    """The SLO satellite: every retired request carries TTFT (submit →
    first token delivered at a readback) and admission wait (submit →
    slot), per-request in ``request_metrics`` and aggregated in
    stats().  A request queued behind a full batch must show a LONGER
    admission wait than one admitted immediately, and TTFT is always
    >= its admission wait."""
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=1, max_len=64)
    srv.submit("first", [1, 2, 3], 4)
    srv.submit("queued", [4, 5, 6], 4)    # waits for the slot
    srv.run()
    m = srv.request_metrics
    assert set(m) == {"first", "queued"}
    for rid in m:
        assert m[rid]["ttft_ms"] >= m[rid]["admit_wait_ms"] >= 0.0
    # "queued" sat through "first"'s whole generation before admission
    assert m["queued"]["admit_wait_ms"] > m["first"]["admit_wait_ms"]
    st = srv.stats()
    assert st["requests_finished"] == 2
    assert st["ttft_ms_max"] >= st["ttft_ms_avg"] > 0.0
    assert st["admit_wait_ms_max"] == max(v["admit_wait_ms"]
                                          for v in m.values())


def test_sampled_requests_reproducible_and_mixed_with_greedy(setup):
    """Per-request sampling: a sampled request is reproducible given its
    seed, differs across seeds, stays in-vocab — and a greedy request
    sharing the batch is token-identical to running alone (sampling
    params are per-slot data, not program shape)."""
    cfg, params = setup
    prompts = {"g": [5, 6, 7], "s1": [9, 10, 11], "s2": [9, 10, 11]}

    def run(seed1, seed2):
        srv = DecodeServer(params, cfg, max_batch=3, max_len=64)
        srv.submit("g", prompts["g"], max_new=8)
        srv.submit("s1", prompts["s1"], max_new=8, temperature=0.8,
                   top_p=0.9, seed=seed1)
        srv.submit("s2", prompts["s2"], max_new=8, temperature=0.8,
                   top_p=0.9, seed=seed2)
        return srv.run()

    a = run(123, 456)
    b = run(123, 456)
    assert a["s1"] == b["s1"] and a["s2"] == b["s2"]  # reproducible
    assert a["g"] == _solo(params, cfg, prompts["g"], 8)  # greedy exact
    # identical prompts, different seeds -> (overwhelmingly) different
    # tokens; all tokens valid
    assert a["s1"] != a["s2"]
    for toks in a.values():
        assert all(0 <= t < cfg.vocab for t in toks)
    # temperature ~0 degenerates to greedy even via the sampling path
    srv = DecodeServer(params, cfg, max_batch=1, max_len=64)
    srv.submit("t0", prompts["g"], max_new=8, temperature=0.0,
               top_p=0.5, seed=7)
    assert srv.run()["t0"] == a["g"]


def test_paged_server_sampling(setup):
    """Sampling does not depend on the pool: same (seed, prompt) gives the
    same sampled tokens from the derived pool (every slot's worst case) and
    from a smaller shared one of other blocks."""
    cfg, params = setup
    prompt = [3, 4, 5, 6]

    def run(**kw):
        srv = DecodeServer(params, cfg, max_batch=2, max_len=64, **kw)
        srv.submit("r", prompt, max_new=8, temperature=0.7, seed=99)
        return srv.run()["r"]

    assert run() == run(total_blocks=8, block_len=16)


def test_submit_sampling_validation(setup):
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=1, max_len=32)
    with pytest.raises(ValueError, match="temperature"):
        srv.submit("a", [1], 2, temperature=-0.5)
    with pytest.raises(ValueError, match="top_p"):
        srv.submit("b", [1], 2, top_p=0.0)
    with pytest.raises(ValueError, match="top_p"):
        srv.submit("c", [1], 2, top_p=1.5)


# -- automatic prefix caching (DecodeServer) ---------------------------


def test_prefix_cache_reuses_blocks_and_stays_exact(setup):
    """Two sequential requests sharing a long prompt prefix: the second
    admission reuses the cached blocks (stats prove it) and both
    outputs stay token-identical to solo generate."""
    cfg, params = setup
    rng = np.random.default_rng(21)
    sys_prompt = rng.integers(0, cfg.vocab, 12).tolist()  # 3 full blocks
    a = sys_prompt + [7, 8]
    b = sys_prompt + [9]
    srv = DecodeServer(params, cfg, max_batch=1, max_len=64,
                       total_blocks=16, block_len=4)
    srv.submit("a", a, 6)
    out_a = srv.run()["a"]
    st = srv.stats()
    assert st["prefix_hits"] == 0          # nothing cached yet
    assert st["prefix_cached_blocks"] == 3  # a's full blocks registered
    srv.submit("b", b, 6)
    out_b = srv.run()["b"]
    st = srv.stats()
    assert st["prefix_hits"] == 1
    assert st["prefix_shared_blocks"] == 3  # whole shared prefix reused
    assert out_a == _solo(params, cfg, a, 6)
    assert out_b == _solo(params, cfg, b, 6)


def test_prefix_cache_block_aligned_prompt(setup):
    """A prompt that is an exact multiple of block_len: the last full
    block is deliberately NOT shared (suffix >= 1 token must prefill
    live; decode's first write must never hit a shared block)."""
    cfg, params = setup
    rng = np.random.default_rng(22)
    prompt = rng.integers(0, cfg.vocab, 12).tolist()   # exactly 3 blocks
    srv = DecodeServer(params, cfg, max_batch=1, max_len=64,
                       total_blocks=12, block_len=4)
    srv.submit("a", prompt, 5)
    out_a = srv.run()["a"]
    assert srv.stats()["prefix_cached_blocks"] == 2    # (s-1)//bk cap
    srv.submit("b", prompt, 5)
    out_b = srv.run()["b"]
    assert srv.stats()["prefix_shared_blocks"] == 2
    assert out_a == out_b == _solo(params, cfg, prompt, 5)


def test_prefix_cache_eviction_under_pressure(setup):
    """Pool pressure reclaims refs==0 cached blocks (LRU) before
    refusing admission; distinct prompts still serve correctly."""
    cfg, params = setup
    rng = np.random.default_rng(23)
    srv = DecodeServer(params, cfg, max_batch=1, max_len=64,
                       total_blocks=6, block_len=4)
    outs, refs = {}, {}
    for i in range(3):        # each needs ceil((9+6)/4)=4 of 6 blocks
        p = rng.integers(0, cfg.vocab, 9).tolist()
        srv.submit(f"r{i}", p, 6)
        outs[f"r{i}"] = srv.run()[f"r{i}"]
        refs[f"r{i}"] = _solo(params, cfg, p, 6)
    assert outs == refs
    st = srv.stats()
    assert st["prefix_cached_blocks"] <= 6   # eviction kept it bounded
    assert st["blocks_free"] + st["prefix_cached_blocks"] == 6


def test_prefix_cache_off_switch(setup):
    """prefix_cache=False restores the round-2 behavior: no registry,
    every block returns to the free list at retirement."""
    cfg, params = setup
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    srv = DecodeServer(params, cfg, max_batch=1, max_len=64,
                       total_blocks=8, block_len=4,
                       prefix_cache=False)
    srv.submit("a", prompt, 5)
    out = srv.run()["a"]
    assert out == _solo(params, cfg, prompt, 5)
    assert srv.stats()["prefix_cached_blocks"] == 0
    assert sorted(srv.free) == list(range(8))


def test_serving_randomized_soak(setup):
    """Randomized end-to-end soak of the paged serving stack: many
    requests with random lengths/budgets/sampling params, a third
    sharing a system prompt, under a deliberately tight pool — every
    greedy request must match solo generate exactly, every run must be
    reproducible, and the pool must account every block at drain."""
    cfg, params = setup
    rng = np.random.default_rng(77)
    system = rng.integers(0, cfg.vocab, 9).tolist()
    reqs = []
    for i in range(12):
        prompt = rng.integers(0, cfg.vocab,
                              int(rng.integers(2, 14))).tolist()
        if i % 3 == 0:
            prompt = system + prompt
        max_new = int(rng.integers(2, 9))
        temp = 0.0 if i % 2 == 0 else float(rng.uniform(0.5, 1.2))
        reqs.append((f"q{i}", prompt, max_new, temp, int(i * 131)))

    def run_all():
        srv = DecodeServer(params, cfg, max_batch=3, max_len=64,
                           total_blocks=14, block_len=4)
        for rid, prompt, max_new, temp, seed in reqs:
            srv.submit(rid, prompt, max_new, temperature=temp,
                       top_p=0.9 if temp else 1.0, seed=seed)
        out = srv.run()
        return out, srv

    out1, srv = run_all()
    out2, _ = run_all()
    assert out1 == out2                        # fully reproducible
    for rid, prompt, max_new, temp, _ in reqs:
        assert len(out1[rid]) == max_new
        assert all(0 <= t < cfg.vocab for t in out1[rid])
        if temp == 0.0:                        # greedy: exact vs solo
            assert out1[rid] == _solo(params, cfg, prompt, max_new), rid
    st = srv.stats()
    # the tight pool may evict a cached chain between shared requests;
    # at least one reuse must still have happened
    assert st["prefix_hits"] >= 1
    cached = [e["blk"] for e in srv._pc.values()]
    assert sorted(srv.free + cached) == list(range(14))  # no leaks


@pytest.mark.parametrize("pool", ["slots", "shared"])
def test_lookahead_token_identical(setup, pool):
    """step_many(k) (k decode sub-steps per host readback — the
    high-latency-link amortization, round-3 verdict #6) must return
    exactly what per-token stepping returns: same requests, same
    tokens, same EOS truncation — surplus sub-step tokens after a
    mid-batch EOS are discarded, never surfaced.  More requests than
    slots forces slot recycling through the lookahead path too."""
    cfg, params = setup
    rng = np.random.default_rng(3)
    # eos_id chosen so some requests stop early and some run out
    # max_new; staggered budgets make sub-step exhaustion heterogeneous
    reqs = {f"r{i}": (rng.integers(0, cfg.vocab, n).tolist(), m)
            for i, (n, m) in enumerate(
                [(5, 12), (9, 3), (3, 15), (7, 1), (4, 9)])}

    results = {}
    for k in (1, 4, 16):
        srv = _server(pool, params, cfg)
        for rid, (p, m) in reqs.items():
            srv.submit(rid, p, m, eos_id=7)
        results[k] = srv.run(lookahead=k)
    assert results[1] == results[4] == results[16]
    # and the lookahead path still matches isolated generate()
    for rid, (p, m) in reqs.items():
        assert results[16][rid] == _solo(params, cfg, p, m,
                                         eos_id=7), rid


def test_pending_first_drained_on_step_exception(setup):
    """An exception between admission and the batch readback must not
    leak ``_pending_first`` into the next call (the first token would
    replay a full batch LATE, after newer tokens): the except path
    drains the deferred first tokens in generation order, retirements
    completed during the drain surface on the next call, and every
    request's output stays token-identical to its solo run."""
    cfg, params = setup
    rng = np.random.default_rng(5)
    p0 = rng.integers(0, cfg.vocab, 4).tolist()
    p1 = rng.integers(0, cfg.vocab, 6).tolist()
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64)
    srv.submit("one", p0, 1)        # retires during the drain itself
    srv.submit("more", p1, 6)
    real_run_step = srv._run_step

    def boom():
        raise RuntimeError("device fault mid-dispatch")

    srv._run_step = boom
    with pytest.raises(RuntimeError, match="mid-dispatch"):
        srv.step_many(4)
    # both admissions' first tokens were drained, none leaked
    assert srv._pending_first == []
    assert "one" in srv._finished_carry      # max_new=1: drained full
    live = [r for r in srv.slots if r is not None]
    assert len(live) == 1 and len(live[0].out) == 1

    srv._run_step = real_run_step
    got = {}
    while not srv.idle:
        got.update(srv.step_many(4))
    assert got["one"] == _solo(params, cfg, p0, 1)
    assert got["more"] == _solo(params, cfg, p1, 6)


def test_pending_first_restored_on_readback_failure(setup, monkeypatch):
    """The batch readback failing AFTER step_many swapped
    ``_pending_first`` out must not drop the deferred first tokens:
    they are re-stashed before the drain runs, the drain's own failed
    readback RESTORES them (its documented contract), and once the
    device recovers the replay delivers them — late beats lost.
    max_new=1 requests keep the failed batch dispatch-free (their
    budget is consumed by the deferred first), so recovery is exact."""
    cfg, params = setup
    rng = np.random.default_rng(11)
    p0 = rng.integers(0, cfg.vocab, 4).tolist()
    p1 = rng.integers(0, cfg.vocab, 6).tolist()
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64)
    srv.submit("one", p0, 1)
    srv.submit("more", p1, 1)

    def boom(x):
        raise RuntimeError("link wedged at readback")

    with monkeypatch.context() as m:
        m.setattr(jax, "device_get", boom)
        with pytest.raises(RuntimeError, match="wedged"):
            srv.step_many(4)
    # both admissions' deferred first tokens survived the failed
    # readback — nothing was silently dropped
    assert sorted(s for s, _ in srv._pending_first) == [0, 1]

    got = {}
    while not srv.idle:
        got.update(srv.step_many(4))
    assert got["one"] == _solo(params, cfg, p0, 1)
    assert got["more"] == _solo(params, cfg, p1, 1)

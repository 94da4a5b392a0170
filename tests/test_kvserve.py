"""Serving KV prefix store (models/kv_offload.py PrefixStore +
models/serving.py wiring — docs/PERF.md §5): cross-session dedupe,
token-equivalence with the store on vs off, benefit-scored eviction,
the STROM_KV_PREFIX=0 bit-for-bit off switch, the SLO governor's
hedge/weight levers, and the host-tier hot pin.  Hardware-free
(``-m perf``, like the planner/scheduler/hostcache suites)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nvme_strom_tpu.io.engine import StromEngine
from nvme_strom_tpu.models import decode as dec
from nvme_strom_tpu.models.kv_offload import (PrefixStore, SloGovernor,
                                              build_prefix_store)
from nvme_strom_tpu.models.serving import DecodeServer
from nvme_strom_tpu.models.transformer import (TransformerConfig,
                                               init_params, tiny_config)
from nvme_strom_tpu.utils.config import EngineConfig
from nvme_strom_tpu.utils.stats import StromStats

pytestmark = pytest.mark.perf

PAGE = 4


@pytest.fixture(scope="module")
def setup():
    cfg = TransformerConfig(**{**tiny_config().__dict__,
                               "dtype": jnp.float32})
    params = init_params(jax.random.key(0), cfg)
    return cfg, params


@pytest.fixture
def engine():
    stats = StromStats()
    eng = StromEngine(EngineConfig(chunk_bytes=1 << 20,
                                   buffer_pool_bytes=16 << 20),
                      stats=stats)
    yield eng
    eng.close_all()


def _store(cfg, eng, tmp_path, name="prefix.kvstore", pages=64,
           **kw):
    return PrefixStore(cfg, eng, str(tmp_path / name),
                       page_tokens=PAGE,
                       capacity_bytes=pages * _page_bytes(cfg), **kw)


def _page_bytes(cfg):
    return (2 * cfg.n_layers * cfg.n_kv_heads * PAGE * cfg.head_dim
            * jnp.dtype(cfg.dtype).itemsize)


def _solo(params, cfg, prompt, max_new):
    return np.asarray(dec.generate(
        params, jnp.asarray([prompt], jnp.int32), cfg,
        max_new))[0].tolist()


def test_cross_session_dedupe_same_prefix_written_once(setup, engine,
                                                       tmp_path):
    """The tentpole claim: N sessions sharing a system prompt write its
    pages ONCE; later admissions (same server or another server over
    the same store) restore instead of re-prefilling, and a re-put of
    resident pages dedupes."""
    cfg, params = setup
    stats = engine.stats
    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(0, cfg.vocab, 3 * PAGE).tolist()
    store = _store(cfg, engine, tmp_path)
    # no HBM block cache: it would serve "b" before the store is asked
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64,
                       kv_store=store, prefix_cache=False)
    srv.submit("a", sys_prompt + [7, 8], 5)
    out_a = srv.run()["a"]
    assert stats.kv_pages_written == 3          # the shared pages
    assert stats.kv_prefix_hits == 0            # nothing to reuse yet
    # SECOND session, same server process: restores, writes nothing new
    srv.submit("b", sys_prompt + [9], 5)
    out_b = srv.run()["b"]
    assert stats.kv_pages_written == 3          # written exactly once
    assert stats.kv_prefix_hits == 3
    assert stats.kv_pages_restored == 3
    # THIRD session, a DIFFERENT server (a named pool) over the same store
    srv2 = DecodeServer(params, cfg, max_batch=2, max_len=64,
                        total_blocks=16, block_len=PAGE,
                        kv_store=store)
    srv2.submit("c", sys_prompt + [11, 12], 5)
    out_c = srv2.run()["c"]
    assert stats.kv_pages_written == 3          # still once, fleet-wide
    assert stats.kv_prefix_hits == 6
    # correctness everywhere
    assert out_a == _solo(params, cfg, sys_prompt + [7, 8], 5)
    assert out_b == _solo(params, cfg, sys_prompt + [9], 5)
    assert out_c == _solo(params, cfg, sys_prompt + [11, 12], 5)
    store.close()


def test_dedupe_counts_on_explicit_double_put(setup, engine, tmp_path):
    cfg, params = setup
    store = _store(cfg, engine, tmp_path)
    k = np.zeros((cfg.n_layers, cfg.n_kv_heads, PAGE, cfg.head_dim),
                 np.float32)
    keys = store.chain_keys(list(range(PAGE + 1)))
    assert store.put([(keys[0], k, k)]) == 1
    assert store.put([(keys[0], k, k)]) == 0    # deduped
    assert engine.stats.kv_pages_deduped == 1
    assert engine.stats.kv_bytes_saved == store.page_bytes
    store.close()


@pytest.mark.parametrize("pool", ["slots", "shared"])
def test_token_equivalence_store_on_vs_off(setup, engine, tmp_path,
                                           pool):
    """Greedy outputs with the prefix store attached are token-identical
    to the store-less server — restored pages are bit-for-bit the KV
    the prefill would have computed."""
    cfg, params = setup
    rng = np.random.default_rng(4)
    sys_prompt = rng.integers(0, cfg.vocab, 3 * PAGE).tolist()
    reqs = [(f"r{i}",
             sys_prompt + rng.integers(0, cfg.vocab,
                                       1 + i % 3).tolist(), 6)
            for i in range(4)]

    def make(store):
        # "slots": the pool the server works out; blocks of the store's
        # page with a store, so PAGE for the store-less twin as well
        kw = {"total_blocks": 16} if pool == "shared" else {}
        return DecodeServer(params, cfg, max_batch=2, max_len=64,
                            block_len=PAGE, kv_store=store, **kw)

    srv_off = make(None)
    for rid, p, m in reqs:
        srv_off.submit(rid, p, m)
    out_off = srv_off.run()

    store = _store(cfg, engine, tmp_path)
    # two batches: the first computes+writes, the second RESTORES —
    # both must match the store-less run
    srv_on = make(store)
    for rid, p, m in reqs:
        srv_on.submit(rid, p, m)
    out_on = srv_on.run()
    assert out_on == out_off
    # a fresh server over the now-warm store: its cheaper tiers are
    # cold, so admissions RESTORE from NVMe (the first server may have
    # served later batches from its own in-HBM blocks)
    srv_on2 = make(store)
    for rid, p, m in reqs:
        srv_on2.submit(rid, p, m)
    assert srv_on2.run() == out_off
    assert engine.stats.kv_pages_restored > 0   # the path actually ran
    store.close()


def test_paged_store_with_hbm_prefix_cache_disabled(setup, engine,
                                                    tmp_path):
    """prefix_cache=False (no in-HBM registry) + a kv_store: NVMe
    restores still serve every same-prefix admission, with exact
    tokens — the store does not depend on the HBM tier existing."""
    cfg, params = setup
    rng = np.random.default_rng(17)
    sys_prompt = rng.integers(0, cfg.vocab, 2 * PAGE).tolist()
    store = _store(cfg, engine, tmp_path)

    def make():
        return DecodeServer(params, cfg, max_batch=1, max_len=64,
                            total_blocks=12, block_len=PAGE,
                            prefix_cache=False, kv_store=store)

    srv = make()
    srv.submit("a", sys_prompt + [1], 4)
    out_a = srv.run()["a"]
    srv.submit("b", sys_prompt + [2], 4)   # same server: must RESTORE
    out_b = srv.run()["b"]                 # (no HBM cache to hit)
    assert engine.stats.kv_pages_restored >= 2
    assert out_a == _solo(params, cfg, sys_prompt + [1], 4)
    assert out_b == _solo(params, cfg, sys_prompt + [2], 4)
    assert srv.stats()["prefix_cached_blocks"] == 0
    store.close()


def test_eviction_under_pressure_keeps_hottest_prefix(setup, engine,
                                                      tmp_path):
    """Capacity pressure evicts the lowest benefit score (reuse
    frequency x restore cost): the repeatedly-restored prefix survives,
    the one-shot ones rotate out."""
    cfg, params = setup
    store = _store(cfg, engine, tmp_path, pages=2)
    assert store.capacity_pages == 2
    shape = (cfg.n_layers, cfg.n_kv_heads, PAGE, cfg.head_dim)
    k = np.zeros(shape, np.float32)
    key_a = store.chain_keys([1] * (PAGE + 1))[0]
    key_b = store.chain_keys([2] * (PAGE + 1))[0]
    key_c = store.chain_keys([3] * (PAGE + 1))[0]
    store.put([(key_a, k, k), (key_b, k, k)])
    store.flush()
    # A is hot: three restores bump its reuse count
    for _ in range(3):
        assert 0 in store.restore_many({0: (0, [key_a])})[0]
    # C arrives: the full store must evict B (hits 0), never A
    store.put([(key_c, k, k)])
    assert engine.stats.kv_store_evictions == 1
    assert store.match([key_a]) == 1            # hottest survived
    assert store.match([key_b]) == 0            # cold one paid
    assert store.match([key_c]) == 1
    store.close()


def test_kv_prefix_env_off_is_bit_for_bit_per_session(setup, engine,
                                                      tmp_path,
                                                      monkeypatch):
    """STROM_KV_PREFIX unset/0 → build_prefix_store returns None, the
    server runs today's per-session path (no store I/O, no counters),
    and tokens are identical to a plain server."""
    cfg, params = setup
    monkeypatch.delenv("STROM_KV_PREFIX", raising=False)
    assert build_prefix_store(cfg, engine, str(tmp_path / "x.kvstore"),
                              page_tokens=PAGE) is None
    monkeypatch.setenv("STROM_KV_PREFIX", "0")
    assert build_prefix_store(cfg, engine, str(tmp_path / "x.kvstore"),
                              page_tokens=PAGE) is None
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab, 11).tolist()
    srv = DecodeServer(params, cfg, max_batch=1, max_len=64,
                       kv_store=None)
    srv.submit("p", prompt, 6)
    out = srv.run()["p"]
    assert out == _solo(params, cfg, prompt, 6)
    snap = engine.stats.snapshot()
    assert all(v == 0 for kx, v in snap.items()
               if kx.startswith("kv_"))
    assert not os.path.exists(tmp_path / "x.kvstore")
    # =1 builds a live store honoring the env capacity/page knobs
    monkeypatch.setenv("STROM_KV_PREFIX", "1")
    st = build_prefix_store(cfg, engine, str(tmp_path / "y.kvstore"),
                            page_tokens=PAGE)
    assert st is not None and st.page_tokens == PAGE
    st.close()


def test_batched_multi_request_restore_single_step(setup, engine,
                                                   tmp_path):
    """Two same-prefix requests admitted in ONE serve step: their due
    pages go down as one decode-class batch (duplicate extents dedupe
    in the planner), both slots get served, outputs stay exact."""
    cfg, params = setup
    stats = engine.stats
    rng = np.random.default_rng(13)
    sys_prompt = rng.integers(0, cfg.vocab, 2 * PAGE).tolist()
    store = _store(cfg, engine, tmp_path)
    seed = DecodeServer(params, cfg, max_batch=1, max_len=64,
                        kv_store=store)
    seed.submit("seed", sys_prompt + [5], 2)
    seed.run()
    assert stats.kv_pages_written == 2
    submits0 = stats.requests_submitted
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64,
                       kv_store=store)
    reqs = {"x": sys_prompt + [6, 7], "y": sys_prompt + [8]}
    for rid, p in reqs.items():
        srv.submit(rid, p, 5)
    out = srv.run()
    # both slots restored in the same admission batch
    assert stats.kv_pages_restored == 4
    # the planner collapsed the two slots' identical extents: at most
    # one engine read per page went down (cross-request locality)
    assert stats.requests_submitted - submits0 <= 2
    assert stats.spans_coalesced >= 1
    for rid, p in reqs.items():
        assert out[rid] == _solo(params, cfg, p, 5), rid
    store.close()


def test_restore_heals_through_recompute_on_corruption(setup, engine,
                                                       tmp_path,
                                                       monkeypatch):
    """A corrupted store page under STROM_VERIFY drops its entry and
    the admission recomputes — corruption can never reach attention,
    and the request still serves exact tokens."""
    cfg, params = setup
    monkeypatch.setenv("STROM_VERIFY", "full")
    rng = np.random.default_rng(21)
    sys_prompt = rng.integers(0, cfg.vocab, 2 * PAGE).tolist()
    store = _store(cfg, engine, tmp_path)
    # no HBM block cache: "b" has to ask the store for the damaged page
    srv = DecodeServer(params, cfg, max_batch=1, max_len=64,
                       kv_store=store, prefix_cache=False)
    srv.submit("a", sys_prompt + [3], 4)
    srv.run()
    store.flush()
    # flip a byte in page 0
    with open(store.path, "r+b") as f:
        f.seek(17)
        b = f.read(1)
        f.seek(17)
        f.write(bytes([b[0] ^ 0xFF]))
    srv.submit("b", sys_prompt + [4], 4)
    out = srv.run()["b"]
    assert out == _solo(params, cfg, sys_prompt + [4], 4)
    assert engine.stats.checksum_failures >= 1
    assert engine.stats.kv_restore_failures >= 1
    # the damaged page healed: it was re-put by the recomputing
    # admission and the next restore serves it cleanly
    srv.submit("c", sys_prompt + [5], 4)
    assert srv.run()["c"] == _solo(params, cfg, sys_prompt + [5], 4)
    store.close()


def test_slo_governor_boosts_and_decays():
    """A p99 above target raises the decode hedge budget and scheduler
    weight (bounded); recovery decays them back toward baseline."""
    class FakeSched:
        def __init__(self):
            from nvme_strom_tpu.io.sched import default_policies
            self.policies = default_policies()

        def set_weight(self, klass, weight):
            from dataclasses import replace
            self.policies[klass] = replace(self.policies[klass],
                                           weight=weight)

    class FakeEngine:
        def __init__(self):
            self.hedge_budgets = {"decode": 8}
            self.scheduler = FakeSched()

        def set_hedge_budget(self, klass, budget):
            self.hedge_budgets[klass] = budget

    eng = FakeEngine()
    stats = StromStats()
    gov = SloGovernor(target_ms=10.0)
    gov._MIN_INTERVAL_S = 0.0               # no rate limit in the test
    base_w = eng.scheduler.policies["decode"].weight
    gov.observe(eng, 50.0, stats)           # violation
    assert eng.hedge_budgets["decode"] == 16
    assert eng.scheduler.policies["decode"].weight == 2 * base_w
    assert stats.kv_slo_boosts == 1
    gov.observe(eng, 50.0, stats)
    gov.observe(eng, 50.0, stats)
    gov.observe(eng, 50.0, stats)           # capped at _MAX_BOOST
    assert eng.hedge_budgets["decode"] == 8 * (2 ** gov._MAX_BOOST) / 2 \
        or eng.hedge_budgets["decode"] == 8 * (2 ** gov._MAX_BOOST)
    assert gov.boost == gov._MAX_BOOST
    while gov.boost:
        gov.observe(eng, 1.0, stats)        # healthy: decay back
    assert eng.hedge_budgets["decode"] == 8
    assert eng.scheduler.policies["decode"].weight == base_w
    # no target → inert
    gov2 = SloGovernor(target_ms=0.0)
    gov2.observe(eng, 1e9, stats)
    assert gov2.boost == 0


def test_slo_governor_wired_through_restore(setup, engine, tmp_path):
    """End-to-end: a store with an impossible p99 target boosts the
    decode budgets off its own restore histogram."""
    from nvme_strom_tpu.io.resilient import ResilientEngine
    cfg, params = setup
    reng = ResilientEngine(engine)
    store = PrefixStore(cfg, reng, str(tmp_path / "slo.kvstore"),
                        page_tokens=PAGE,
                        capacity_bytes=8 * _page_bytes(cfg),
                        p99_target_ms=1e-6)
    store.slo._MIN_INTERVAL_S = 0.0
    base = store.slo._base_budget
    k = np.zeros((cfg.n_layers, cfg.n_kv_heads, PAGE, cfg.head_dim),
                 np.float32)
    key = store.chain_keys([1] * (PAGE + 1))[0]
    store.put([(key, k, k)])
    store.restore_many({0: (0, [key])})
    assert engine.stats.kv_slo_boosts >= 1
    assert reng.hedge_budgets["decode"] > 8
    store.close()


def test_sched_set_weight_validates():
    from nvme_strom_tpu.io.sched import QoSScheduler
    sched = QoSScheduler(lambda spans, ring: [], lambda: [1])
    w0 = sched.policies["decode"].weight
    sched.set_weight("decode", w0 * 3)
    assert sched.policies["decode"].weight == w0 * 3
    with pytest.raises(KeyError):
        sched.set_weight("nope", 1.0)
    with pytest.raises(ValueError):
        sched.set_weight("decode", -1.0)


def test_resilient_set_hedge_budget_validates(engine):
    from nvme_strom_tpu.io.resilient import ResilientEngine
    reng = ResilientEngine(engine)
    reng.set_hedge_budget("decode", 32)
    assert reng.hedge_budgets["decode"] == 32
    with pytest.raises(ValueError):
        reng.set_hedge_budget("decode", -1)


def test_hostcache_hot_pin_first_touch_and_quota(tmp_path):
    """The plan.py hot path: hot ranges admit on FIRST touch (no ghost
    round), turn sticky, and sticky lines within their class quota
    survive eviction pressure that reclaims cold lines."""
    from nvme_strom_tpu.io.hostcache import HostCache
    line = 4096
    cache = HostCache(line_bytes=line, budget_bytes=4 * line,
                      ghost_factor=4, lock_arena=False)
    fkey = (1, 2, 3, 4)
    stats = StromStats()
    # hot probe: admitted immediately (a cold probe would be ghosted)
    segs, adm = cache.probe_range(fkey, 0, line, "decode", stats,
                                  hot=True)
    assert segs[0][0] == "miss" and (fkey, 0) in adm
    assert stats.cache_admission_rejections == 0
    assert cache.fill(fkey, 0, np.ones(line, np.uint8), "decode",
                      stats, epoch=adm[(fkey, 0)], sticky=True)
    # fill the rest of the arena with cold prefetch lines (two touches
    # each to clear the ghost gate)
    for i in range(1, 6):
        off = i * line
        for _ in range(2):
            _segs, a = cache.probe_range(fkey, off, line, "prefetch",
                                         stats)
        cache.fill(fkey, off, np.ones(line, np.uint8), "prefetch",
                   stats, epoch=a.get((fkey, off)))
    # pressure reclaimed SOMETHING, but never the sticky decode line
    assert stats.cache_evictions >= 1
    segs, _ = cache.probe_range(fkey, 0, line, "decode", stats)
    assert segs[0][0] == "hit"
    cache.close()


def test_prefix_store_survives_process_restart(setup, engine,
                                               tmp_path):
    """The manifest reattaches resident pages in a new store instance
    (a server restart): the next session restores instead of
    recomputing — cross-SESSION reuse, not just cross-request."""
    cfg, params = setup
    rng = np.random.default_rng(31)
    sys_prompt = rng.integers(0, cfg.vocab, 2 * PAGE).tolist()
    store = _store(cfg, engine, tmp_path)
    srv = DecodeServer(params, cfg, max_batch=1, max_len=64,
                       kv_store=store)
    srv.submit("a", sys_prompt + [1], 4)
    srv.run()
    store.close()                      # flush + manifest
    written = engine.stats.kv_pages_written
    store2 = _store(cfg, engine, tmp_path)      # same path: reattach
    srv2 = DecodeServer(params, cfg, max_batch=1, max_len=64,
                        kv_store=store2)
    srv2.submit("b", sys_prompt + [2], 4)
    out = srv2.run()["b"]
    assert out == _solo(params, cfg, sys_prompt + [2], 4)
    assert engine.stats.kv_pages_written == written   # restored, not
    assert engine.stats.kv_pages_restored >= 2        # rewritten
    store2.close()


def test_flush_clean_manifest_covers_racing_put(setup, engine,
                                               tmp_path):
    """flush()'s clean=True manifest must never stamp a page whose
    async writes are still in flight: a put() racing the drain appends
    its batch (and flips its entry ready) while the drainer is blocked
    in an earlier batch's wait.  The drain must loop until the
    pipeline is OBSERVED empty — a single snapshot drain would return
    with the racing batch pending and stamp clean anyway (the PR-13
    review fix, kv_offload._drain_all_and_snapshot)."""
    cfg, params = setup
    store = _store(cfg, engine, tmp_path)
    shape = (cfg.n_layers, cfg.n_kv_heads, PAGE, cfg.head_dim)
    k = np.zeros(shape, np.float32)
    key_a = store.chain_keys([1] * (PAGE + 1))[0]
    key_b = store.chain_keys([2] * (PAGE + 1))[0]
    store.put([(key_a, k, k)])

    class _RacingPend:
        # a pending write whose wait() performs the racing put: by the
        # time the drainer unblocks, put(B)'s batch is appended and
        # its entry ready — exactly the mid-drain window.  put()'s own
        # maintenance drain try-acquires _drain_mu (held), stays
        # within the backlog cap, and returns without blocking.
        def __init__(self, inner):
            self._inner = inner

        def wait(self):
            if not getattr(self, "fired", False):
                self.fired = True
                store.put([(key_b, k, k)])
            return self._inner.wait()

    with store._wlock:
        store._pending_writes[0] = [
            _RacingPend(p) for p in store._pending_writes[0]]
    store.flush()
    with store._wlock:
        assert store._pending_writes == []     # drained to empty
    import json
    with open(store.manifest_path) as f:
        man = json.load(f)
    assert man["clean"]
    stamped = {v["key"] for v in man["pages"].values()}
    # both pages were proven drained before the stamp, so both appear
    assert key_a.hex() in stamped and key_b.hex() in stamped
    store.close()


def test_flush_bounded_rounds_terminate_under_sustained_puts(
        setup, engine, tmp_path):
    """A put() storm that re-fills the pipeline every drain round must
    not pin flush() forever: the drain is bounded, and when it exits by
    bound the clean manifest stamps only the final round's PRE-drain
    ready snapshot — a key that flipped ready after that snapshot
    (writes possibly in flight) is left out, never stamped torn."""
    cfg, params = setup
    store = _store(cfg, engine, tmp_path)
    shape = (cfg.n_layers, cfg.n_kv_heads, PAGE, cfg.head_dim)
    k = np.zeros(shape, np.float32)
    keys = [store.chain_keys([t] * (PAGE + 1))[0] for t in range(1, 12)]
    store.put([(keys[0], k, k)])
    fired = []

    class _Refill:
        # every round's wait() appends ANOTHER batch: the pipeline is
        # never observed empty, so flush must exit by round bound
        def __init__(self, inner):
            self._inner = inner

        def wait(self):
            if not getattr(self, "done", False):
                self.done = True
                if len(fired) + 1 < len(keys):
                    nxt = keys[len(fired) + 1]
                    # put() refuses new work once close() set the
                    # gate (returns 0, appends nothing) — that is how
                    # close's own drain converges
                    if store.put([(nxt, k, k)]):
                        with store._wlock:
                            store._pending_writes[-1] = [
                                _Refill(p)
                                for p in store._pending_writes[-1]]
                        fired.append(nxt)
            return self._inner.wait()

    with store._wlock:
        store._pending_writes[0] = [
            _Refill(p) for p in store._pending_writes[0]]
    store.flush()                      # terminates despite the refills
    import json
    with open(store.manifest_path) as f:
        man = json.load(f)
    assert man["clean"]
    stamped = {v["key"] for v in man["pages"].values()}
    assert keys[0].hex() in stamped
    # the refill chain outran the 8-round bound: the tail key readied
    # after the final pre-drain snapshot must NOT be stamped
    assert len(fired) >= 8
    assert fired[-1].hex() not in stamped
    store.close()                      # gate stops refills, tail drains
    with store._wlock:
        assert store._pending_writes == []
    assert store.put([(keys[-1], k, k)]) == 0   # closed store refuses


def test_close_waits_for_inflight_put(setup, engine, tmp_path):
    """A put() that won the _closed gate race must finish before
    close() touches the engine fh: closing (or None-ing) the handle
    under the put's submit would raise into the serving path — a
    cache may refuse work, never fail it."""
    import threading
    import time
    cfg, params = setup
    store = _store(cfg, engine, tmp_path)
    shape = (cfg.n_layers, cfg.n_kv_heads, PAGE, cfg.head_dim)
    k = np.zeros(shape, np.float32)
    key = store.chain_keys([1] * (PAGE + 1))[0]
    gate = threading.Event()
    real = store.engine.submit_write

    def slow_submit(*a, **kw):
        gate.wait(5)                   # put is now inside its I/O,
        return real(*a, **kw)          # past the _closed gate check

    store.engine.submit_write = slow_submit
    errs = []

    def putter():
        try:
            store.put([(key, k, k)])
        except Exception as e:         # the bug: ctypes ArgumentError
            errs.append(repr(e))

    t = threading.Thread(target=putter)
    t.start()
    time.sleep(0.05)
    closer = threading.Thread(target=store.close)
    closer.start()
    time.sleep(0.1)
    assert closer.is_alive()           # close waits on the in-flight put
    gate.set()
    t.join(5)
    closer.join(5)
    store.engine.submit_write = real
    assert not errs, errs
    assert not t.is_alive() and not closer.is_alive()
    assert store._fh is None           # closed cleanly afterwards


def test_reentrant_put_during_drain_skips_backpressure(setup, engine,
                                                       tmp_path):
    """A put() re-entered from the active drain's own wait() IS the
    drainer: with the backlog past the 2x hard cap it must skip the
    backpressure acquire (it would self-deadlock on the drainer's own
    non-reentrant _drain_mu) instead of blocking forever."""
    import threading
    cfg, params = setup
    store = _store(cfg, engine, tmp_path)
    shape = (cfg.n_layers, cfg.n_kv_heads, PAGE, cfg.head_dim)
    k = np.zeros(shape, np.float32)

    class _ReentrantPend:
        def __init__(self, t):
            self.t = t

        def wait(self):
            key = store.chain_keys([self.t] * (PAGE + 1))[0]
            store.put([(key, k, k)])   # re-enters mid-drain

    # backlog far past 2 * _MAX_PENDING so the re-entered put's
    # maintenance drain takes the backpressure branch
    with store._wlock:
        for t in range(3 * store._MAX_PENDING):
            store._pending_writes.append([_ReentrantPend(100 + t)])
    done = threading.Event()

    def flusher():
        store.flush()
        done.set()

    th = threading.Thread(target=flusher, daemon=True)
    th.start()
    assert done.wait(30), "flush deadlocked on its own _drain_mu"
    th.join(5)
    store.close()


def test_close_gates_restore_many(setup, engine, tmp_path):
    """restore_many() on a closing/closed store returns {} (the caller
    recomputes) instead of submitting reads against a dead fh."""
    cfg, params = setup
    store = _store(cfg, engine, tmp_path)
    shape = (cfg.n_layers, cfg.n_kv_heads, PAGE, cfg.head_dim)
    k = np.zeros(shape, np.float32)
    key = store.chain_keys([1] * (PAGE + 1))[0]
    store.put([(key, k, k)])
    store.close()
    assert store.restore_many({0: (0, [key])}) == {}


@pytest.mark.parametrize("pool", ["slots", "shared"])
def test_store_restored_admission_runs_the_prefill_program(
        setup, engine, tmp_path, pool, monkeypatch):
    """An admission whose prefix comes back from the store prefills its
    suffix through the same compiled program as any other (a (suffix,
    cache) shape with suffix < cache), serves ``generate()``'s tokens,
    and pulls from the device once: ``_store_put``'s new pages, K and V
    (nothing at all when it has no new full page to put)."""
    cfg, params = setup
    rng = np.random.default_rng(23)
    sys_prompt = rng.integers(0, cfg.vocab, 3 * PAGE).tolist()
    store = _store(cfg, engine, tmp_path)

    def make():
        # "slots": block_len and the pool worked out from the store's page
        kw = ({"total_blocks": 16, "block_len": PAGE} if pool == "shared"
              else {})
        return DecodeServer(params, cfg, max_batch=1, max_len=64,
                            kv_store=store, **kw)

    pulls = []
    to_numpy = np.asarray

    def asarray(a, *args, **kw):
        if isinstance(a, jax.Array):
            pulls.append(a.shape)
        return to_numpy(a, *args, **kw)

    def admit(srv, rid, prompt):
        srv.submit(rid, prompt, 5)
        plans = srv._plan_admissions()
        restored = srv._restore_prefixes(plans)
        del pulls[:]
        with monkeypatch.context() as m:
            m.setattr(np, "asarray", asarray)
            for group in srv._form_groups(plans, restored):
                srv._finish_traced(group, restored.get(group[0]["slot"], {}))
        return srv.run()[rid]

    a = sys_prompt + [7, 8, 9, 10, 11]        # 4 full pages + 1 token
    assert admit(make(), "a", a) == _solo(params, cfg, a, 5)
    L, nkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    assert pulls == [(L, nkv, 4 * PAGE, hd)] * 2      # K and V, once
    store.flush()
    # a fresh server (cold HBM tiers): 3 pages restore, the suffix of
    # 5 tokens pads to 2 pages against the 5-page cache, and the one
    # new full page is the whole pull
    srv = make()
    b = sys_prompt + [1, 2, 3, 4, 5]
    assert admit(srv, "b", b) == _solo(params, cfg, b, 5)
    assert engine.stats.kv_pages_restored == 3
    assert {shape[1:] for shape in srv._prefill_shapes} == {
        (2 * PAGE, 5 * PAGE)}
    assert pulls == [(L, nkv, PAGE, hd)] * 2
    store.close()

"""Admission's prefill as one compiled program and the pools the server
works out (models/serving.py), against ``decode.generate``; and
``examples/serve.py`` end to end over both."""

import jax
import numpy as np
import pytest

from nvme_strom_tpu.models.serving import DecodeServer
from nvme_strom_tpu.models.transformer import init_params, tiny_config
from test_serving import _server, _solo, setup  # noqa: F401 — a fixture


# -- admission's prefill as one compiled program ---------------------------

@pytest.mark.parametrize("pool,prompt_lens,shared,programs", [
    # no hit: one block of 128 (slots) / two blocks of 8 (shared); 128 rows
    # are past this model's break-even on the CPU (35), so width 1; the
    # program of 16 rows holds two prompts
    ("slots", (9, 16), 0, {(1, 128, 128)}),
    ("shared", (9, 16), 0, {(2, 16, 16)}),
    # HBM prefix-cache hit: the second prompt shares two full blocks and
    # prefills its last block only, against the same 24-row cache
    ("shared", (20, 19), 16, {(1, 24, 24), (4, 8, 24)}),
])
def test_served_tokens_match_generate_through_the_prefill_program(
        setup, pool, prompt_lens, shared, programs):
    """Greedy tokens out of the compiled admission are ``generate()``'s,
    and the program is keyed on (width, padded suffix, cache) alone, the
    width following from the suffix: prompts of different lengths inside
    one bucket build ONE program — the true last row, the slot and the
    block ids do not retrace."""
    cfg, params = setup
    rng = np.random.default_rng(31)
    head = rng.integers(0, cfg.vocab, shared).tolist()
    prompts = [head + rng.integers(0, cfg.vocab, n - shared).tolist()
               for n in prompt_lens]
    from nvme_strom_tpu.models import serving
    fn = serving._paged_prefill
    fn.clear_cache()
    srv = _server(pool, params, cfg)
    for i, p in enumerate(prompts):
        srv.submit(i, p, 5)
        assert srv.run()[i] == _solo(params, cfg, p, 5)
    assert srv._prefill_shapes == programs
    assert srv.timings["prefill_programs"] == len(programs)
    assert fn._cache_size() == len(programs)
    if shared:
        assert srv.stats()["prefix_hits"] == 1
    # a second server of the same shapes compiles nothing new
    srv = _server(pool, params, cfg)
    srv.submit("again", prompts[0], 2)
    srv.run()
    assert fn._cache_size() == len(programs)
    assert srv.timings["prefill_programs"] == 1


@pytest.mark.parametrize("pool", ["slots", "shared"])
def test_admission_reads_nothing_back(setup, pool, monkeypatch):
    """A store-less admission is dispatches only: no ``device_get`` and
    no host conversion of any device array (the logits stay on the
    device; the first token rides ``step_many``'s one readback)."""
    cfg, params = setup
    srv = _server(pool, params, cfg)
    srv.submit("warm", [1, 2, 3], 2)        # compile outside the guard
    srv.run()
    pulled = []

    def pull(*a, **k):
        pulled.append(a)
        raise AssertionError("admission read back from the device")

    arr, to_numpy = type(srv.pos), np.asarray

    def asarray(a, *args, **kw):
        # numpy reads a CPU device array through the buffer protocol,
        # past every attribute a test can patch
        return (pull(a) if isinstance(a, jax.Array)
                else to_numpy(a, *args, **kw))

    srv.submit("r", [5, 6, 7, 8, 9], 4)
    with monkeypatch.context() as m:
        m.setattr(jax, "device_get", pull)
        m.setattr(np, "asarray", asarray)
        m.setattr(arr, "_value", property(pull))  # int(), .tolist(), ...
        for plan in srv._plan_admissions():
            srv._finish_traced([plan], {})
    assert not pulled and len(srv._pending_first) == 1
    assert srv.run()["r"] == _solo(params, cfg, [5, 6, 7, 8, 9], 4)


# -- one server: the pool it works out (PR 28) -------------------------------

def _Page(page_tokens):
    """A prefix store as far as the constructor looks."""
    import types
    return types.SimpleNamespace(page_tokens=page_tokens)


@pytest.mark.parametrize("max_len,kw,page,block_len,total_blocks", [
    (64, {}, None, 128, 3 * 1),                 # no store: blocks of 128
    (100, {"block_len": 16}, None, 16, 3 * 7),  # ceil(100 / 16) a slot
    (64, {}, 8, 8, 3 * 8),                      # the store's page is the block
    (64, {"total_blocks": 5}, 8, 8, 5),         # a named pool stays as named
    (64, {"block_len": 8}, 8, 8, 3 * 8),
], ids=["default", "block_len", "store_page", "named_pool", "page_agrees"])
def test_pool_sizes_are_worked_out(setup, max_len, kw, page, block_len,
                                   total_blocks):
    """``block_len`` and ``total_blocks`` left out are worked out, not
    options: the store's page (else 128), and every slot's worst case — the
    capacity fixed slots had."""
    cfg, params = setup
    store = None if page is None else _Page(page)
    srv = DecodeServer(params, cfg, max_batch=3, max_len=max_len,
                       kv_store=store, **kw)
    assert (srv.block_len, srv.total_blocks) == (block_len, total_blocks)
    assert srv.max_blocks == -(-max_len // block_len)
    assert srv.k_pool.shape[1:4:2] == (total_blocks + 1, block_len)
    st = srv.stats()
    assert (st["blocks_total"], st["blocks_free"]) == (total_blocks,) * 2


def test_pool_sizes_that_cannot_hold_refuse(setup):
    cfg, params = setup
    with pytest.raises(ValueError, match="must equal block_len"):
        DecodeServer(params, cfg, 2, 64, block_len=16, kv_store=_Page(8))
    with pytest.raises(ValueError, match=">= 1"):
        DecodeServer(params, cfg, 2, 64, total_blocks=0)
    with pytest.raises(ValueError, match=">= 1"):
        DecodeServer(params, cfg, 2, 64, block_len=0)


@pytest.mark.parametrize("shared_head", [0, 16], ids=["distinct", "shared"])
def test_derived_pool_never_defers_for_blocks(setup, shared_head):
    """A server that was given no pool admits ``max_batch`` worst-case
    requests (prompt + budget = max_len) at once, and every later step
    admits as many queued requests as it has free slots: admission never
    waits for a block, with prompts that share cached blocks or not."""
    cfg, params = setup
    rng = np.random.default_rng(28)
    head = rng.integers(0, cfg.vocab, shared_head).tolist()
    reqs = {i: head + rng.integers(0, cfg.vocab, 30 - shared_head).tolist()
            for i in range(7)}
    srv = DecodeServer(params, cfg, max_batch=3, max_len=40, block_len=8)
    assert srv.total_blocks == 3 * 5
    for i, p in reqs.items():
        srv.submit(i, p, 10 if i % 2 else 3)     # 30 + 10 = max_len
    got, steps = {}, 0
    while not srv.idle:
        due = min(len(srv.queue), sum(r is None for r in srv.slots))
        before = srv.timings["admits"]
        got.update(srv.step_many(2))
        assert srv.timings["admits"] - before == due
        steps += 1
        assert steps < 100
    assert srv.timings["admits"] == len(reqs)
    for i, p in reqs.items():
        assert got[i] == _solo(params, cfg, p, 10 if i % 2 else 3), i
    cached = [e["blk"] for e in srv._pc.values()]
    assert sorted(srv.free + cached) == list(range(15))     # none leaked


def test_build_server_without_a_pool_serves_generates_tokens(setup):
    """``examples/serve.build_server(paged=0)``: the one class over the
    pool it works out, serving ``generate()``'s greedy tokens."""
    from examples.serve import build_server
    cfg, params = setup
    srv = build_server(params, cfg, slots=2, max_len=48, paged=0,
                       block_len=16)
    assert type(srv) is DecodeServer
    assert (srv.block_len, srv.total_blocks) == (16, 2 * 3)
    named = build_server(params, cfg, slots=2, max_len=48, paged=4,
                         block_len=16)
    assert (named.block_len, named.total_blocks) == (16, 4)
    rng = np.random.default_rng(5)
    reqs = {f"p{i}": (rng.integers(0, cfg.vocab, 4 + 3 * i).tolist(), 5)
            for i in range(3)}
    for rid, (p, m) in reqs.items():
        srv.submit(rid, p, m)
    got = srv.run(lookahead=2)
    for rid, (p, m) in reqs.items():
        assert got[rid] == _solo(params, cfg, p, m), rid


def test_serve_example_runs_without_paged(tmp_path, capsys):
    """``examples/serve.py`` with no ``--paged`` end to end, from a
    converted checkpoint directory: the tokens are ``generate()``'s on the
    weights as loaded, and an explicit pool serves the same."""
    import json

    from examples import serve
    from nvme_strom_tpu.formats.safetensors import write_safetensors
    from nvme_strom_tpu.tools.convert_llama import strom_config_dict
    cfg = tiny_config()
    params = init_params(jax.random.key(4), cfg)
    write_safetensors(str(tmp_path / "model.safetensors"),
                      {k: np.asarray(v) for k, v in params.items()})
    with open(tmp_path / "strom_config.json", "w") as f:
        json.dump(strom_config_dict(cfg), f)
    argv = ["--weights", str(tmp_path), "--slots", "2", "--max-len", "32",
            "--request", "5,6,7:8", "--request", "9,1:5",
            "--request", "3:4"]

    def served(extra):
        assert serve.main(argv + extra) == 0
        out = capsys.readouterr().out
        assert "served 3 requests / 17 tokens" in out
        return {ln.split(":")[0]: [int(t) for t in
                                   ln.split(":")[1].split(",")]
                for ln in out.splitlines() if ln[:1] == "r"}

    got = served([])
    cfg = serve.read_config(str(tmp_path))
    for rid, p, m in (("r0", [5, 6, 7], 8), ("r1", [9, 1], 5),
                      ("r2", [3], 4)):
        assert got[rid] == _solo(params, cfg, p, m), rid
    assert served(["--paged", "3", "--block-len", "16"]) == got
    with pytest.raises(SystemExit):
        serve.main(argv + ["--pallas"])         # the flag is gone

"""Admission in groups: the prompts one ``step_many`` call admits go through
ONE prefill program where ``models/admission.form_groups`` says they may
share one.  For a dense, a hybrid and an expert config at test size (CPU,
float32): a group of mixed lengths, a dead row beside it, against the same
prompts admitted one by one — tokens, K/V blocks, state rows, conv tails,
and what the group must not touch; the grouping rule as a pure function at
the benchmark configurations' published widths; no program compiled after a
warm-up shaped like the benchmark runner's; and the runner's instance-level
wrapper of ``_finish_traced``."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import weights_hybrid as WH                      # noqa: E402
from benchmark import weights_moe as WM                         # noqa: E402
from nvme_strom_tpu.models import admission, serving            # noqa: E402
from nvme_strom_tpu.models.serving import DecodeServer          # noqa: E402
from nvme_strom_tpu.models.transformer import (                 # noqa: E402
    TransformerConfig, init_params, tiny_config)
from nvme_strom_tpu.tools.convert_llama import config_from_hf   # noqa: E402
from test_hybrid import HF as HF_HYBRID                         # noqa: E402
from test_lfm2 import HF as HF_MOE                              # noqa: E402

BLOCK = 8
KINDS = ("dense", "hybrid", "experts")
V5E = "TPU v5 lite"


@pytest.fixture(scope="module")
def models():
    dense = TransformerConfig(**{**tiny_config().__dict__,
                                 "dtype": jnp.float32})
    out = {"dense": (dense, init_params(jax.random.key(0), dense))}
    for kind, hf, gen in (("hybrid", HF_HYBRID, WH), ("experts", HF_MOE, WM)):
        cfg = dataclasses.replace(config_from_hf(hf), dtype=jnp.float32)
        out[kind] = (cfg, {k: v.astype(jnp.float32)
                           for k, v in gen.make_params(hf, 5).items()})
    return out


def _server(model, slots=5):
    cfg, params = model
    return DecodeServer(params, cfg, max_batch=slots, max_len=64,
                        total_blocks=40, block_len=BLOCK,
                        prefix_cache=False)


def _prompt(cfg, n, salt=0):
    return np.random.default_rng([n, salt]).integers(0, cfg.vocab,
                                                     n).tolist()


def _admit(srv, grouped: bool):
    """Admit everything queued: as ONE group, or one request a program."""
    plans = srv._plan_admissions()
    for group in ([plans] if grouped else [[p] for p in plans]):
        srv._finish_traced(group, {})
    return plans


def _own(srv, plan):
    """What an admission leaves on the device for its request: the valid
    rows of its prompt's K/V blocks, its state row and its conv tails."""
    n_tok = len(plan["req"].prompt)
    blks = np.asarray(plan["blks"][:-(-n_tok // BLOCK)])
    out = {}
    for name, pool in (("k", srv.k_pool), ("v", srv.v_pool)):
        rows = np.asarray(pool)[:, blks]          # (L, n, nkv, bk, hd)
        L, n, nkv, bk, hd = rows.shape
        out[name] = rows.transpose(0, 2, 1, 3, 4).reshape(
            L, nkv, n * bk, hd)[:, :, :n_tok]
    for key in ("s", "conv") if srv.state else ():
        for i, pool in enumerate(srv.state[key]):
            out[f"{key}{i}"] = np.asarray(pool)[plan["slot"]]
    return out


#: a group of three (width 4: one dead row) whose lengths pad to 8, 24, 16
LENS = (5, 19, 12)


@pytest.fixture(scope="module")
def pair(models):
    """{kind: (grouped server, one-by-one server, their plans)} after the
    admission, nothing decoded yet; slot 0 of both holds an older request
    that has decoded a few tokens."""
    out = {}
    for kind in KINDS:
        cfg = models[kind][0]
        both = []
        for grouped in (True, False):
            srv = _server(models[kind])
            # a model whose break-even is 4 x 24 rows: the program of a
            # 24-row prompt is four wide (steered here, in the test: the
            # program has no option for it)
            srv._group_rows = 4 * 24
            srv.submit("old", _prompt(cfg, 9, 9), 40)
            for _ in range(3):
                srv.step()
            before = jax.tree_util.tree_map(
                np.asarray, (srv.k_pool, srv.v_pool, srv.state))
            old_blocks = list(srv.blocks[0])
            for i, n in enumerate(LENS):
                srv.submit(f"r{i}", _prompt(cfg, n, i), 36)
            plans = _admit(srv, grouped)
            both.append((srv, plans, before, old_blocks))
        out[kind] = both
    return out


@pytest.mark.parametrize("kind", KINDS)
def test_a_group_leaves_what_each_admission_alone_leaves(pair, kind):
    """Each row's K/V blocks (the prompt's own positions), state row and
    conv tails after ONE program of (4, 24) — prompts of 5, 19 and 12
    tokens and a dead row — equal what three programs of one prompt (and
    three dead rows) leave."""
    (grp, g_plans, _, _), (one, o_plans, _, _) = pair[kind]
    assert grp.timings["prefill_calls"] == 2      # "old", then the group
    assert one.timings["prefill_calls"] == 4
    # every program of these lengths is four wide: the group leaves one
    # row dead, a prompt alone three
    assert grp.timings["prefill_rows_dead"] == 3 + 1
    assert one.timings["prefill_rows_dead"] == 3 * 4
    # width x longest, against each prompt padded to its own blocks
    assert grp.timings["prefill_tokens"] == 4 * (16 + 24)
    assert one.timings["prefill_tokens"] == 4 * (16 + 8 + 24 + 16)
    assert grp.timings["prompt_tokens"] == one.timings["prompt_tokens"] \
        == 9 + sum(LENS)
    assert grp._prefill_shapes == {(4, 16, 16), (4, 24, 24)}
    assert one._prefill_shapes == {(4, 8, 8), (4, 16, 16), (4, 24, 24)}
    for gp, op in zip(g_plans, o_plans):
        assert gp["req"].rid == op["req"].rid
        got, want = _own(grp, gp), _own(one, op)
        assert got.keys() == want.keys()
        for key in want:
            # float32: equal to the rounding of another batch shape
            np.testing.assert_allclose(got[key], want[key], rtol=1e-4,
                                       atol=1e-5, err_msg=key)


@pytest.mark.parametrize("kind", KINDS)
def test_a_group_touches_nothing_it_does_not_own(pair, kind):
    """The dead row, the rows a shorter prompt has no block for and the pad
    rows go to the trash block and the sacrificial state row: the older
    request's blocks and state row, every free block and every other
    slot's state row are bit for bit what they were."""
    srv, plans, (k0, v0, state0), old_blocks = pair[kind][0]
    mine = sorted({b for p in plans
                   for b in p["blks"][:-(-len(p["req"].prompt) // BLOCK)]})
    kept = [b for b in range(srv.total_blocks) if b not in mine]
    assert set(old_blocks) <= set(kept) and srv._trash not in kept
    for before, now in ((k0, srv.k_pool), (v0, srv.v_pool)):
        np.testing.assert_array_equal(np.asarray(now)[:, kept],
                                      before[:, kept])
    rows = [r for r in range(srv.B) if r not in {p["slot"] for p in plans}]
    assert 0 in rows
    for key in ("s", "conv") if srv.state else ():
        for before, now in zip(state0[key], srv.state[key]):
            np.testing.assert_array_equal(np.asarray(now)[rows],
                                          before[rows])


@pytest.mark.parametrize("kind", KINDS)
def test_a_group_serves_the_tokens_of_single_admissions(pair, kind):
    """36 tokens a request (the first and 35 decode steps) out of the
    grouped admission are those of the one-by-one admission, the older
    request's too; and an expert layer routed the valid rows only."""
    (grp, plans, _, _), (one, _, _, _) = pair[kind]
    got, want = grp.run(lookahead=4), one.run(lookahead=4)
    assert got.keys() == want.keys() == {"old", "r0", "r1", "r2"}
    for rid in want:
        assert len(want[rid]) == (40 if rid == "old" else 36)
        assert got[rid] == want[rid], rid
    n_exp, k = len(grp.cfg.expert_layers), grp.cfg.expert_top_k
    pairs = (9 + sum(LENS)) * k * n_exp if n_exp else 0
    for srv, programs in ((grp, 2), (one, 4)):
        assert srv.timings["moe_pairs_prefill"] == pairs
        assert srv.timings["moe_calls_prefill"] == programs * n_exp
    assert grp.timings["admits"] == one.timings["admits"] == 4


# -- the rule ---------------------------------------------------------------

def _bench_cfg(name, **over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        hf = dict(json.load(f), **over)
    return config_from_hf(hf)


#: the configurations as their cells run them, and at their published depth
CONFIGS = {
    "m7b": lambda: _bench_cfg("mistral-7b-v0.3"),
    "m7b-32": lambda: _bench_cfg("mistral-7b-v0.3-tp4"),
    "granite": lambda: _bench_cfg("granite-4.0-h-micro"),
    "lfm2": lambda: _bench_cfg("lfm2-24b-a2b"),
    "lfm2-40": lambda: _bench_cfg(
        "lfm2-24b-a2b", num_hidden_layers=40,
        layer_types=["conv", "conv", "full_attention", "conv"] * 10),
}


@pytest.mark.parametrize("name,rows", [
    ("m7b", 246), ("m7b-32", 245), ("granite", 250), ("lfm2", 2215),
    ("lfm2-40", 2616)])
def test_breakeven_rows_follow_from_the_configs_counts(name, rows):
    """Bytes of weights a prefill reads x 240.5 operations a byte over the
    operations a row costs: a dense or hybrid bf16 decoder near the ratio
    itself, a sparse one E/k times later."""
    cfg = CONFIGS[name]()
    assert admission.breakeven_rows(cfg, V5E) == rows
    read, rowops = admission.prefill_counts(cfg)
    assert rows == int(read * 2 * (197e12 / 819e9) / (2 * rowops))
    # float32 weights are twice the bytes for the same operations
    wide = dataclasses.replace(cfg, dtype=jnp.float32)
    assert abs(admission.breakeven_rows(wide, V5E) - 2 * rows) <= 1
    # nothing is known of a device that is not listed: no group
    assert admission.breakeven_rows(cfg, "some other chip") == 0


@pytest.mark.parametrize("name,lengths,groups", [
    # a dense and a hybrid model at 128-token blocks: two of the shortest
    # prompts are past the break-even, every admission is a group of one
    ("m7b", [128, 256, 128, 512, 384], [[3], [4], [1], [0], [2]]),
    ("granite", [128, 128, 1024, 256], [[2], [3], [0], [1]]),
    # the sparse model: the issue's call of five, two programs
    ("lfm2", [1024, 512, 256, 128, 128], [[0, 1], [2, 3, 4]]),
    ("lfm2", [128, 256, 512, 1024, 128, 256], [[3, 2], [1, 5, 0, 4]]),
    # a warm-up's burst of equal lengths: the widest groups only
    ("lfm2", [1024] * 4 + [512] * 4, [[0, 1], [2, 3], [4, 5, 6, 7]]),
    ("lfm2", [128] * 9, [[0, 1, 2, 3], [4, 5, 6, 7], [8]]),
    # 4 x 640 rows are past the break-even: such prompts pair
    ("lfm2", [640, 640, 640, 128], [[0, 1], [2, 3]]),
    ("lfm2", [1024], [[0]]),
    ("lfm2", [], []),
])
def test_groups_are_a_pure_function_of_lengths_and_config(name, lengths,
                                                          groups):
    limit = admission.breakeven_rows(CONFIGS[name](), V5E)
    got = admission.form_groups(lengths, limit)
    assert got == groups
    assert sorted(i for g in got for i in g) == list(range(len(lengths)))
    for g in got:
        # one program a length: the widest of the ladder under the limit
        width = admission.width_for(lengths[g[0]], limit)
        assert width in admission.WIDTHS and len(g) <= width
        assert width == 1 or width * lengths[g[0]] <= limit
        assert lengths[g[0]] == max(lengths[i] for i in g)


@pytest.mark.parametrize("name,widths", [
    ("m7b", (1, 1, 1, 1)), ("granite", (1, 1, 1, 1)),
    ("lfm2", (4, 4, 4, 2)), ("lfm2-40", (4, 4, 4, 2))])
def test_a_length_has_one_program(name, widths):
    """The width is a function of the padded length: at 128, 256, 512 and
    1,024 rows."""
    limit = admission.breakeven_rows(CONFIGS[name](), V5E)
    assert tuple(admission.width_for(m, limit)
                 for m in (128, 256, 512, 1024)) == widths
    assert admission.width_for(128, 0) == 1


def test_the_server_reads_the_rule_from_its_config_and_device(models):
    srv = _server(models["experts"])
    kind = jax.devices()[0].device_kind
    assert kind in admission.DEVICE_OPS_PER_BYTE
    assert srv._group_rows == admission.breakeven_rows(srv.cfg, kind) > 0
    # nothing else decides: no argument of the constructor, no variable of
    # the environment
    import inspect
    assert "group" not in " ".join(
        inspect.signature(DecodeServer.__init__).parameters)
    src = inspect.getsource(admission)
    assert "environ" not in src and "getenv" not in src


# -- every program exists before it is needed --------------------------------

@pytest.mark.parametrize("kind", ["dense", "experts"])
def test_no_program_is_built_after_a_warm_up_of_equal_lengths(models, kind):
    """The benchmark runner's ``_warm`` submits ``max(slots, lengths)``
    requests at once, so the server only ever sees its widest groups of
    equal length; what follows — narrower groups, mixed ones, a request
    alone — finds its program built: no entry joins a jit cache."""
    from benchmark.runners import serve
    cfg = models[kind][0]
    programs = (serving._paged_prefill, serving._admit_slots,
                serving._paged_step, serving._sample_slots)
    for fn in programs:
        fn.clear_cache()
    srv = _server(models[kind], slots=8)
    lens = (8, 16, 24, 32)
    sched = {"requests": [{"prompt_len": n} for n in lens]}
    serve._warm(srv, sched, 3, cfg.vocab, 8, 4)
    assert srv.idle
    built = [fn._cache_size() for fn in programs]
    shapes = set(srv._prefill_shapes)
    # one program a length, at the width its rows allow
    assert shapes == {(admission.width_for(m, srv._group_rows), m, m)
                      for m in lens}
    assert {b for b, _, _ in shapes} == {1, 2, 4}
    rng = np.random.default_rng(0)
    rid = 0
    for burst in ((8,), (16, 8), (32, 8, 8, 8), (24, 16, 8), (8, 8, 8),
                  (32, 24, 16, 8, 8, 16, 24, 32), (24, 24), (16, 16, 16)):
        for n in burst:
            srv.submit(rid, rng.integers(0, cfg.vocab, n - rid % 3).tolist(),
                       3 + rid % 4)
            rid += 1
        while not srv.idle:
            srv.step_many(4)
    assert srv.timings["admits"] == 8 + rid
    assert srv.timings["prefill_calls"] < srv.timings["admits"]
    assert set(srv._prefill_shapes) == shapes
    assert [fn._cache_size() for fn in programs] == built


# -- the one door ------------------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_the_runners_wrapper_sees_every_admission(models, kind):
    """``benchmark/runners/serve.py`` replaces ``_finish_traced`` on the
    instance with a wrapper of (plan, restored) in a traced run: every
    admission, grouped or alone, goes through it exactly once."""
    cfg = models[kind][0]
    srv = _server(models[kind], slots=4)
    inner, seen = srv._finish_traced, []

    def admit(plan, restored):
        seen.append(([p["req"].rid for p in plan], restored))
        return inner(plan, restored)
    srv._finish_traced = admit
    for i, n in enumerate((8, 8, 20, 5, 13, 8, 30)):
        srv.submit(i, _prompt(cfg, n, i), 4)
    out = srv.run(lookahead=2)
    assert sorted(out) == list(range(7))
    assert sorted(rid for rids, _ in seen for rid in rids) == list(range(7))
    assert all(restored == {} for _, restored in seen)
    assert len(seen) == srv.timings["prefill_calls"] < 7
    assert srv.timings["admits"] == 7


def test_a_tenants_request_is_a_group_of_one(models):
    """What is keyed on one request stays with it: a tenant's scope, store
    pages to scatter, another count of cached prefix blocks."""
    from nvme_strom_tpu.models.serving import _Request
    srv = _server(models["dense"])

    def plan(slot, n, c=0, tenant=None):
        req = _Request(slot, list(range(1, n + 1)), 4, None, tenant=tenant)
        return {"slot": slot, "req": req, "keys": [], "c": c,
                "blks": list(range(8))}
    plans = [plan(0, 8), plan(1, 8, tenant=object()), plan(2, 8),
             plan(3, 16, c=1), plan(4, 8)]
    groups = srv._form_groups(plans, {4: {0: "pages"}})
    assert sorted(sorted(p["slot"] for p in g) for g in groups) == [
        [0, 2], [1], [3], [4]]

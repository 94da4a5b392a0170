"""Hybrid decoders (Mamba-2 layers beside GQA attention) through the paged
server: the two kernels of ``ops/ssm.py`` against the per-token recurrence,
and the server's logits — compiled prefill, then decode through both kinds
of cache — against the benchmark's plain reference
(``benchmark/reference/granite_hybrid.py``, which imports nothing of the
program) on the benchmark's seeded weights, at a tiny size on the CPU."""

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
from benchmark.reference import granite_hybrid as ref           # noqa: E402
from nvme_strom_tpu.models import admission, serving            # noqa: E402
from nvme_strom_tpu.models.serving import DecodeServer        # noqa: E402
from nvme_strom_tpu.ops.ssm import (heads_per_lane_row,         # noqa: E402
                                    pack_state, pool_shape, ssm_scan,
                                    ssm_update, unpack_state)
from nvme_strom_tpu.tools.convert_llama import config_from_hf   # noqa: E402

#: granite-4.0-h-micro's keys at a tiny size: kinds m, m, a, m
HF = dict(
    model_type="granitemoehybrid", hidden_size=64, vocab_size=96,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=128, shared_intermediate_size=128,
    max_position_embeddings=64, rms_norm_eps=1e-5, hidden_act="silu",
    layer_types=["mamba", "mamba", "attention", "mamba"],
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4,
    mamba_chunk_size=8, mamba_expand=2, mamba_n_groups=1,
    mamba_conv_bias=True, mamba_proj_bias=False,
    position_embedding_type="nope", embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=8, attention_multiplier=1 / 16,
    tie_word_embeddings=True, num_local_experts=0, num_experts_per_tok=0)
SEED = 7
BLOCK = 8


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(config_from_hf(HF), dtype=jnp.float32)
    params = {k: v.astype(jnp.float32)
              for k, v in WH.make_params(HF, SEED).items()}
    return cfg, params


def _server(model, slots=4, **kw):
    cfg, params = model
    return DecodeServer(params, cfg, max_batch=slots, max_len=64,
                        total_blocks=32, block_len=BLOCK, **kw)


def _prompt(n, salt=0):
    return np.random.default_rng([n, salt]).integers(
        0, HF["vocab_size"], n).tolist()


def _reference(prompt, tokens, hf=HF):
    """Reference logits (len(tokens), vocab) at the positions that predict
    each served token, teacher-forced on them."""
    seq = np.asarray([prompt + tokens], np.int32)
    at = len(prompt) - 1 + np.arange(len(tokens))[None]
    return np.asarray(ref.logits_at(hf, SEED, seq, at)[0])


@pytest.fixture
def spy(monkeypatch):
    """Record the logits every token of every request was sampled from: the
    prefill's (``_admit_first``, a group's rows), then each decode step's — ``paged_logits``
    compiled as the step compiles it, minus the donation.  Returns
    ``run(srv, lookahead) -> {rid: (tokens, logits (n, vocab))}``."""
    rows = {}
    step_logits = jax.jit(serving.paged_logits, static_argnums=(1,))

    def run(srv, lookahead=1):
        first = srv._admit_first

        def first_spy(group, logits):
            for i, plan in enumerate(group):
                rows.setdefault(plan["req"].rid, []).append(
                    np.asarray(logits[i]))
            return first(group, logits)

        def step_spy(params, cfg, tok, k_pool, v_pool, blk, off, table,
                     pos, temps, top_ps, seeds, *recur):
            logits, k_pool, v_pool, *state = step_logits(
                params, cfg, tok, k_pool, v_pool, blk, off, table, pos,
                *recur)
            for b, req in enumerate(srv.slots):
                if req is not None:
                    rows[req.rid].append(np.asarray(logits[b]))
            nxt = serving._sample_slots(logits, temps, top_ps, seeds, pos)
            return nxt, k_pool, v_pool, (state[0] if state else None)

        if srv._admit_first.__name__ != "first_spy":   # once per server
            srv._admit_first = first_spy
        monkeypatch.setattr(serving, "_paged_step", step_spy)
        out = srv.run(lookahead=lookahead)
        return {rid: (toks, np.stack(rows[rid][:len(toks)]))
                for rid, toks in out.items()}
    return run


def _close(got, want):
    """float32 end to end: equal to rounding at the logits' own scale."""
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4 * scale)


# -- (1) the server against the reference ----------------------------------

@pytest.mark.parametrize("n_prompt,lookahead", [(13, 1), (21, 2), (8, 1)])
def test_server_logits_match_the_reference(model, spy, n_prompt, lookahead):
    """Prefill in the compiled admission program (right-padded to a block
    multiple: 13 → 16, 21 → 24), then decode through the KV pool and the
    state pool; no length is a multiple of block and chunk but the last."""
    srv = _server(model)
    prompt = _prompt(n_prompt)
    srv.submit("r", prompt, 7)
    tokens, logits = spy(srv, lookahead)["r"]
    assert len(tokens) == 7
    want = _reference(prompt, tokens)
    _close(logits, want)
    assert tokens == np.argmax(want, -1).tolist()
    assert srv.timings["scan_tokens"] == n_prompt
    # ... as handed to the length's one program: padded to blocks, times
    # the program's width (two prompts of 8 rows are under this model's
    # break-even on the CPU, so a lone one has a dead row beside it)
    padded = -(-n_prompt // BLOCK) * BLOCK
    width = admission.width_for(padded, srv._group_rows)
    assert width == (2 if padded == 8 else 1)
    assert srv.timings["prefill_tokens"] == width * padded
    assert srv.timings["prefill_rows_dead"] == width - 1


# -- (2) the scan kernel ----------------------------------------------------

def _recurrence(x, dt, a, b, c, s0):
    """S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t ⊗ B_t ; y_t = S_t C_t, in float64,
    one token at a time."""
    s = np.asarray(s0, np.float64)
    ys = []
    for t in range(x.shape[1]):
        s = (s * np.exp(dt[:, t] * a)[:, :, None, None]
             + (dt[:, t, :, None] * x[:, t])[..., None]
             * b[:, t][:, None, None, :])
        ys.append(np.einsum("bhpn,bn->bhp", s, c[:, t]))
    return np.stack(ys, 1), s


def _scan_inputs(rng, bsz, m, H=4, P=16, N=16):
    f = np.float32
    return dict(
        x=rng.normal(size=(bsz, m, H, P)).astype(f),
        dt=np.log1p(np.exp(rng.normal(size=(bsz, m, H)) - 2)).astype(f),
        a=-np.exp(rng.normal(size=(H,))).astype(f),
        b=rng.normal(size=(bsz, m, N)).astype(f),
        c=rng.normal(size=(bsz, m, N)).astype(f),
        s0=rng.normal(size=(bsz, H, P, N)).astype(f))


@pytest.mark.parametrize("m,n_valid,chunk", [
    (8, 8, 8),        # one chunk, nothing padded
    (24, 19, 8),      # three chunks, the last one part padding
    (40, 33, 16),     # state carried over two chunk boundaries
    (13, 13, 8),      # a length the wrapper pads to the chunk itself
    (16, 5, 8),       # a whole chunk of padding
])
def test_ssm_scan_matches_the_recurrence(m, n_valid, chunk):
    """Interpret mode against the per-token recurrence: several chunk
    counts, a non-zero state coming in, and pad rows that leave it as the
    last valid row did."""
    t = _scan_inputs(np.random.default_rng(m), 2, m)
    valid = np.broadcast_to(np.arange(m) < n_valid, (2, m))
    y, s = ssm_scan(*(jnp.asarray(t[k]) for k in "x dt a b c".split()),
                    pack_state(jnp.asarray(t["s0"])), jnp.asarray(valid),
                    chunk=chunk)
    s = unpack_state(s, t["x"].shape[2])
    cut = {k: (v[:, :n_valid] if k in ("x", "dt", "b", "c") else v)
           for k, v in t.items()}
    y_ref, s_ref = _recurrence(**cut)
    np.testing.assert_allclose(np.asarray(y)[:, :n_valid], y_ref,
                               rtol=0, atol=3e-5 * np.abs(y_ref).max())
    np.testing.assert_allclose(np.asarray(s), s_ref, rtol=0,
                               atol=3e-5 * np.abs(s_ref).max())


def test_pad_rows_leave_state_and_conv_tail_untouched(model):
    """The mixer over a right-padded block hands on exactly what it hands
    on over the unpadded one: the same state and the same last K-1 rows of
    the conv's input, whatever the pad rows hold."""
    from nvme_strom_tpu.models.ssm import mamba_block
    cfg, params = model
    rng = np.random.default_rng(3)
    h = rng.normal(size=(1, 16, cfg.d_model)).astype(np.float32)
    junk = h.copy()
    junk[:, 11:] = 1e3 * rng.normal(size=junk[:, 11:].shape)
    s0 = np.asarray(pack_state(jnp.asarray(rng.normal(size=(
        1, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)), jnp.float32)))
    tail = rng.normal(size=(1, cfg.ssm_conv - 1,
                            cfg.ssm_conv_dim)).astype(np.float32)
    out, s, t = mamba_block(jnp.asarray(h[:, :11]), params, "layers.0.",
                            cfg, jnp.asarray(s0), jnp.asarray(tail))
    out_p, s_p, t_p = mamba_block(jnp.asarray(junk), params, "layers.0.",
                                  cfg, jnp.asarray(s0), jnp.asarray(tail),
                                  n_valid=11)
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s), rtol=0,
                               atol=1e-5 * float(jnp.abs(s).max()))
    np.testing.assert_array_equal(np.asarray(t_p), np.asarray(t))
    np.testing.assert_allclose(np.asarray(out_p)[:, :11], np.asarray(out),
                               rtol=0, atol=1e-5)
    # and a prompt shorter than the conv: the old tail shifts, not vanishes
    _, _, t2 = mamba_block(jnp.asarray(junk), params, "layers.0.", cfg,
                           jnp.asarray(s0), jnp.asarray(tail), n_valid=2)
    np.testing.assert_array_equal(np.asarray(t2)[:, 0], tail[:, 2])


# -- (3) the update kernel --------------------------------------------------

#: (H, P) → g, the heads on a lane row: 128 // P of them where that divides H
PACKINGS = [(4, 16, 4), (6, 64, 2), (3, 64, 1), (2, 128, 1)]


@pytest.mark.parametrize("H,P,g", PACKINGS)
def test_ssm_update_is_one_step_in_place(H, P, g):
    """One step of the recurrence for the slots named, on the pool as it is
    kept (state-major, g heads on a lane row); every other row of the pool
    bit for bit as it was; a free slot writes the sacrificial row alone."""
    rng = np.random.default_rng(5)
    B, N = 3, 16
    t = _scan_inputs(rng, B, 1, H, P, N)
    pool = rng.normal(size=(B + 2, H, P, N)).astype(np.float32)
    trash = B + 1
    sidx = np.asarray([2, trash, 0], np.int32)     # slot 1 is free
    packed = pack_state(jnp.asarray(pool))
    assert packed.shape == pool_shape(B + 2, H, P, N) == (B + 2, H // g, N,
                                                          g * P)
    y, new = ssm_update(packed, sidx, jnp.asarray(t["x"][:, 0]),
                        jnp.asarray(t["dt"][:, 0]), jnp.asarray(t["a"]),
                        jnp.asarray(t["b"][:, 0]), jnp.asarray(t["c"][:, 0]))
    assert new.shape == packed.shape
    new = np.asarray(unpack_state(new, H))
    for b, row in enumerate(sidx):
        y_ref, s_ref = _recurrence(t["x"][b:b + 1], t["dt"][b:b + 1], t["a"],
                                   t["b"][b:b + 1], t["c"][b:b + 1],
                                   pool[row:row + 1])
        np.testing.assert_allclose(np.asarray(y)[b], y_ref[0, 0], rtol=0,
                                   atol=1e-5 * np.abs(y_ref).max())
        np.testing.assert_allclose(new[row], s_ref[0], rtol=0,
                                   atol=1e-5 * np.abs(s_ref).max())
    for row in (1, 3):                             # nobody's rows
        np.testing.assert_array_equal(new[row], pool[row])


@pytest.mark.parametrize("H,P,g", PACKINGS + [(64, 64, 2), (5, 32, 1)])
def test_pack_and_unpack_are_each_other_s_inverse(H, P, g):
    """Lane j·P + p of packed head hp is element p of head hp·g + j, and
    the way back is the identity: nothing is rounded, nothing is lost."""
    assert heads_per_lane_row(H, P) == g
    s = np.random.default_rng(H * P).normal(size=(2, H, P, 8)).astype(
        np.float32)
    packed = np.asarray(pack_state(jnp.asarray(s)))
    assert packed.shape == pool_shape(2, H, P, 8)
    for hp, j, p in [(0, 0, 0), (H // g - 1, g - 1, P - 1), (0, g - 1, 3)]:
        np.testing.assert_array_equal(packed[:, hp, :, j * P + p],
                                      s[:, hp * g + j, p, :])
    np.testing.assert_array_equal(
        np.asarray(unpack_state(jnp.asarray(packed), H)), s)


@pytest.mark.parametrize("H,P,g", PACKINGS)
def test_scan_hands_the_update_the_state_it_keeps(H, P, g):
    """A prompt through the scan, its state written to a pool row as the
    server writes it, then two tokens through the update: the recurrence
    run straight through — the hand-over's layout is the pool's."""
    rng = np.random.default_rng(11)
    B, m, N = 2, 11, 16
    t = _scan_inputs(rng, B, m + 2, H, P, N)
    ins = [jnp.asarray(t[k][:, :m]) for k in "x dt".split()] + [
        jnp.asarray(t["a"])] + [jnp.asarray(t[k][:, :m]) for k in "b c".split()]
    _, s = ssm_scan(*ins, pack_state(jnp.asarray(t["s0"])), chunk=8)
    pool = jnp.zeros(pool_shape(B + 1, H, P, N), jnp.float32)
    sidx = np.asarray([1, 0], np.int32)
    for b, row in enumerate(sidx):
        pool = pool.at[row].set(s[b])              # serving's scatter
    ys = []
    for i in (m, m + 1):
        y, pool = ssm_update(pool, sidx, jnp.asarray(t["x"][:, i]),
                             jnp.asarray(t["dt"][:, i]), jnp.asarray(t["a"]),
                             jnp.asarray(t["b"][:, i]),
                             jnp.asarray(t["c"][:, i]))
        ys.append(np.asarray(y))
    y_ref, s_ref = _recurrence(**t)
    np.testing.assert_allclose(np.stack(ys, 1), y_ref[:, m:], rtol=0,
                               atol=3e-5 * np.abs(y_ref).max())
    np.testing.assert_allclose(np.asarray(unpack_state(pool, H))[sidx], s_ref,
                               rtol=0, atol=3e-5 * np.abs(s_ref).max())


def test_the_kernel_probe_checks_both_updates_before_it_times_them(capsys):
    """``kernel_probe ssm`` at its CPU size (mechanics only: no time it
    prints here is a device's): one line a kernel, Mamba-2's on the pool as
    it is kept and the delta rule's beside it, each within rounding of one
    step of its recurrence."""
    from nvme_strom_tpu.tools import kernel_probe
    kernel_probe.probe_ssm(repeats=1)
    lines = [json.loads(line)
             for line in capsys.readouterr().out.splitlines()]
    assert [line["kernel"] for line in lines] == ["strom_ssm_update",
                                                  "strom_gdn_update"]
    b, h, p, n = lines[0]["shape"]
    assert tuple(lines[0]["pool"]) == pool_shape(b + 1, h, p, n)
    for line in lines:
        assert line["rel_err_y"] < 1e-5 and line["rel_err_s"] < 1e-5
        assert line["us_a_call"] > 0 and "bytes_roofline_pct" not in line


def test_free_slots_step_into_the_sacrificial_row_only(model):
    """Through the server: while one request decodes, the three free slots
    compute too — the state rows of slots 1..3 stay as they were."""
    srv = _server(model)
    before = [np.asarray(a) for a in srv.state["s"]]
    srv.submit("r", _prompt(13), 4)
    srv.run()
    for a, b in zip(srv.state["s"], before):
        np.testing.assert_array_equal(np.asarray(a)[1:4], b[1:4])
        assert np.abs(np.asarray(a)[0]).max() > 0       # slot 0 was used
    cfg = model[0]
    for a in srv.state["s"]:                   # the kept form: 8 heads of 16
        assert a.shape == pool_shape(5, cfg.ssm_heads, cfg.ssm_head_dim,
                                     cfg.ssm_state) == (5, 1, 16, 128)
    st = srv.stats()
    assert st["state_slots"] == 5 and st["kv_layers"] == 1
    assert st["state_heads_per_lane_row"] == 8
    assert st["state_bytes"] == sum(
        a.nbytes for a in srv.state["s"] + srv.state["conv"])


# -- (4)-(6) what the slot's history must not leak into ---------------------

def test_a_released_slot_answers_as_a_fresh_server(model):
    """Admission overwrites state and tail: release clears nothing, and
    the next request in the slot is not told about the last one."""
    srv = _server(model, slots=1)
    srv.submit("old", _prompt(21, salt=1), 9)
    srv.run()
    srv.submit("new", _prompt(13), 6)
    again = srv.run()["new"]
    fresh = _server(model, slots=1)
    fresh.submit("new", _prompt(13), 6)
    assert again == fresh.run()["new"]


def test_alone_and_among_three_others_gives_the_same_logits(model, spy):
    prompt = _prompt(13)
    alone = _server(model)
    alone.submit("r", prompt, 6)
    t_alone, l_alone = spy(alone)["r"]
    crowd = _server(model)
    for i, n in enumerate((9, 20, 5)):
        crowd.submit(i, _prompt(n, salt=2), 4 + i)
    crowd.submit("r", prompt, 6)
    t_crowd, l_crowd = spy(crowd, lookahead=2)["r"]
    assert t_crowd == t_alone
    _close(l_crowd, l_alone)


def test_shared_prefix_is_not_reused_without_its_state(model, spy):
    """``prefix_cache=True`` and two prompts sharing two full blocks: both
    match the reference, and no page was shared — a page without the state
    at its boundary is not a prefix."""
    srv = _server(model, prefix_cache=True)
    head = _prompt(16, salt=3)
    prompts = {"a": head + _prompt(5, salt=4), "b": head + _prompt(3, salt=5)}
    for rid, p in prompts.items():
        srv.submit(rid, p, 5)
        tokens, logits = spy(srv)[rid]
        _close(logits, _reference(p, tokens))
    st = srv.stats()
    assert st["prefix_hits"] == 0 and st["prefix_cached_blocks"] == 0


# -- (7) the way in ---------------------------------------------------------

def _catalog_config():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        with open(path) as f:
            rows = [json.loads(line) for line in f]
    except OSError:
        return None
    return next(r["config"] for r in rows
                if r["name"] == "granite-4.0-h-micro")


def test_config_from_hf_takes_the_published_config():
    """The catalog row's ``config`` where the catalog is, else the
    benchmark's copy of it (the same keys)."""
    hf = _catalog_config()
    if hf is None:
        with open(os.path.join(ROOT, "benchmark", "configs",
                               "granite-4.0-h-micro.json")) as f:
            hf = json.load(f)
    cfg = config_from_hf(hf)
    assert cfg.layer_kinds.count("mamba") == 36
    assert cfg.attn_layers == (5, 15, 25, 35)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state) == (64, 64, 128)
    assert cfg.ssm_inner == 4096 and cfg.ssm_conv_dim == 4352
    assert (cfg.embed_mult, cfg.residual_mult, cfg.logits_div,
            cfg.attn_scale) == (12.0, 0.22, 8.0, 1 / 64)
    assert not cfg.rope and cfg.tie_embed and cfg.head_dim == 64


@pytest.mark.parametrize("key,value", [
    ("num_local_experts", 8), ("mamba_n_groups", 2),
    ("position_embedding_type", "rope"), ("mamba_proj_bias", True),
    ("attention_bias", True)])
def test_config_from_hf_raises_on_what_is_not_implemented(key, value):
    with pytest.raises(ValueError, match=key):
        config_from_hf(dict(HF, **{key: value}))


# -- (8) the four multipliers -----------------------------------------------

@pytest.mark.parametrize("key", ["embedding_multiplier",
                                 "residual_multiplier", "logits_scaling",
                                 "attention_multiplier"])
def test_no_multiplier_is_silently_dropped(model, spy, key):
    """The server's logits follow the reference of THIS config and leave
    the reference of the same config with one multiplier set to 1."""
    srv = _server(model)
    prompt = _prompt(13)
    srv.submit("r", prompt, 3)
    tokens, logits = spy(srv)["r"]
    without = _reference(prompt, tokens, dict(HF, **{key: 1}))
    scale = float(np.abs(logits).max())
    assert np.abs(logits - without).max() > 1e-2 * scale
    _close(logits, _reference(prompt, tokens))


# -- (9) what refuses rather than mis-serves --------------------------------

def test_what_cannot_hold_the_state_refuses(model):
    cfg, params = model

    class Store:
        page_tokens = BLOCK

    with pytest.raises(NotImplementedError, match="kv_store"):
        _server(model, kv_store=Store())
    srv = _server(model)
    with pytest.raises(NotImplementedError, match="export_sessions"):
        srv.export_sessions()
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    sharded = dict(params)
    sharded["layers.0.w_gate"] = jax.device_put(
        params["layers.0.w_gate"], NamedSharding(mesh, P(None, "tp")))
    with pytest.raises(NotImplementedError, match="mesh"):
        DecodeServer(sharded, cfg, max_batch=2, max_len=64,
                     total_blocks=8, block_len=BLOCK)
    from nvme_strom_tpu.parallel.shardings import param_specs
    with pytest.raises(NotImplementedError, match="mesh"):
        param_specs(cfg)

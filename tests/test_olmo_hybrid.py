"""Olmo-Hybrid's mechanisms through the program at a tiny size on the CPU in
float32: post-norm blocks, delta-rule layers whose state is 96 x 192 a head
(two heads side by side on the pool's lanes) under β in (0, 2), attention in
which every query head has its own keys and values with q/k norms over the
whole projection and no rotary.  The two kernels in interpret mode at the
cell's head geometry against the recurrence a token at a time (transformers'
own loop a second witness); the paged server — compiled prefill through the
chunked scan, then decode through BOTH caches — against the benchmark's plain
reference (``benchmark/reference/olmo_hybrid.py``, which imports nothing of
the program) on the benchmark's seeded weights, in logits; each mechanism
against the reference with it switched off; the block and the q/k norm against
transformers' own Olmo3; the converter's layout and the config's keys."""

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

from benchmark import weights_olmoh as WO                       # noqa: E402
from delta_rule_helpers import (                                # noqa: E402,F401
    recurrence as _recurrence, spy)
from benchmark.reference import olmo_hybrid as ref              # noqa: E402
from nvme_strom_tpu.models import admission, decode, serving    # noqa: E402
from nvme_strom_tpu.models.serving import DecodeServer          # noqa: E402
from nvme_strom_tpu.models.transformer import init_params       # noqa: E402
from nvme_strom_tpu.ops import gdn                              # noqa: E402
from nvme_strom_tpu.tools import convert_llama                  # noqa: E402
from nvme_strom_tpu.tools.convert_llama import config_from_hf   # noqa: E402

#: Olmo-Hybrid's keys at a tiny size: one period of 3 delta-rule layers and a
#: full one; 6 heads of 12 / 24 (dv no multiple of 128: two heads a pack);
#: 6 query heads with their own keys and values, 16 wide
HF = dict(
    model_type="olmo_hybrid", hidden_size=96, vocab_size=96,
    num_hidden_layers=4, num_attention_heads=6, num_key_value_heads=6,
    intermediate_size=160, hidden_act="silu", attention_bias=False,
    layer_types=["linear_attention"] * 3 + ["full_attention"],
    linear_conv_kernel_dim=4, linear_key_head_dim=12,
    linear_value_head_dim=24, linear_num_key_heads=6,
    linear_num_value_heads=6, linear_allow_neg_eigval=True,
    rope_parameters={"rope_theta": None}, rms_norm_eps=1e-6,
    tie_word_embeddings=False, max_position_embeddings=256)
SEED = 47
BLOCK = 8
#: float32 on both sides; what is left is the order of the sums (the chunked
#: scan's products against the recurrence's, the paged softmax) through 4
#: layers: a few 1e-5 on logits of size ~4.  bfloat16 misses it by 100x
#: (``test_bfloat16_fails_the_tolerance``).
ATOL = 3e-4


def _model(hf=HF, dtype=jnp.float32):
    cfg = dataclasses.replace(config_from_hf(hf), dtype=dtype, gdn_chunk=16)
    params = {k: v.astype(dtype) for k, v in WO.make_params(hf, SEED).items()}
    return cfg, params


@pytest.fixture(scope="module")
def model():
    return _model()


def _server(model, slots=3, **kw):
    cfg, params = model
    return DecodeServer(params, cfg, max_batch=slots, max_len=128,
                        total_blocks=48, block_len=BLOCK, **kw)


def _prompt(n, salt=0):
    return np.random.default_rng([n, salt]).integers(
        0, HF["vocab_size"], n).tolist()


def _reference(prompt, tokens, hf=HF, low=None):
    """Reference logits (len(tokens), vocab) at the positions that predict
    each served token, teacher-forced on them."""
    seq = np.asarray([prompt + tokens], np.int32)
    at = len(prompt) - 1 + np.arange(len(tokens))[None]
    return np.asarray(ref.logits_at(hf, SEED, seq, at, low=low)[0])


# -- (1) the kernels at 30 heads of 96 x 192 and β up to 2 ------------------

def _draw(b, m, H=30, dk=96, dv=192, seed=0):
    rng = np.random.default_rng([seed, b, m])
    q = rng.normal(size=(b, m, H, dk))
    k = rng.normal(size=(b, m, H, dk))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    # log-decays from a few tokens to thousands, head 0 forgetting at once a
    # third of the way in (α = 0 in float32), and β over all of (0, 2)
    alpha = -np.exp(2.0 * rng.normal(size=(b, m, H)) - 3.0)
    alpha[:, m // 3, 0] = -200.0
    beta = 2 / (1 + np.exp(-2.0 * rng.normal(size=(b, m, H))))
    beta[:, :, 1] = 1.999
    return tuple(jnp.asarray(t, jnp.float32) for t in (
        q, k, rng.normal(size=(b, m, H, dv)), alpha, beta,
        rng.normal(size=(b, H, dk, dv))))


def test_the_pool_keeps_two_heads_of_192_side_by_side():
    assert gdn.heads_per_lane_row(30, 192) == 2
    assert gdn.heads_per_lane_row(32, 128) == 1      # qwen3-next: as it was
    assert gdn.heads_per_lane_row(6, 24) == 1        # no count makes 128
    assert gdn.heads_per_lane_row(4, 64) == 2
    assert gdn.heads_per_lane_row(15, 192) == 1      # an odd count: unpacked
    assert gdn.pool_shape(33, 30, 96, 192) == (33, 15, 96, 384)
    assert gdn.pool_shape(129, 32, 128, 128) == (129, 32, 128, 128)
    s = jnp.asarray(np.random.default_rng(0).normal(size=(3, 30, 96, 192)),
                    jnp.float32)
    packed = gdn.pack_state(s)
    assert packed.shape == (3, 15, 96, 384)
    # head 2p on lanes 0..191 of pack p, head 2p + 1 on lanes 192..383
    np.testing.assert_array_equal(packed[1, 4, :, :192], s[1, 8])
    np.testing.assert_array_equal(packed[1, 4, :, 192:], s[1, 9])
    np.testing.assert_array_equal(gdn.unpack_state(packed, 30), s)
    one = jnp.ones((2, 32, 128, 128))
    assert gdn.pack_state(one) is one and gdn.unpack_state(one, 32) is one


def test_gdn_update_at_the_cells_heads_is_one_step_in_place():
    """One token of five slots against a packed pool of seven rows at 30
    heads of 96 x 192 with β up to 2: the slots' rows move as the recurrence
    says, free slots (row 6 named twice) touch the sacrificial row only, and
    nobody else's row changes."""
    q, k, v, alpha, beta, s0 = _draw(5, 1, seed=7)
    pool = jnp.concatenate([s0, 1.0 + jnp.zeros((2,) + s0.shape[1:])])
    sidx = jnp.asarray([3, 0, 6, 1, 6], jnp.int32)
    o, new = jax.jit(gdn.gdn_update, donate_argnums=(0,))(
        gdn.pack_state(pool), sidx, q[:, 0], k[:, 0], v[:, 0], alpha[:, 0],
        beta[:, 0])
    assert new.shape == (7, 15, 96, 384)
    new = gdn.unpack_state(new, 30)
    want_o, want_s = _recurrence(q, k, v, alpha, beta, pool[sidx])
    np.testing.assert_allclose(o, want_o[:, 0], atol=1e-5)
    live = np.asarray([0, 1, 3])                  # slots 0, 1, 3 of sidx
    np.testing.assert_allclose(new[sidx[live]], want_s[live], atol=1e-5)
    for untouched in (2, 4, 5):
        np.testing.assert_array_equal(new[untouched], pool[untouched])


@pytest.mark.parametrize("m,n_valid,chunk", [
    (64, None, 64), (70, None, 32), (40, (40, 17), 16)])
def test_gdn_scan_at_the_cells_heads_matches_the_recurrence(m, n_valid,
                                                            chunk):
    b = 1 if n_valid is None else len(n_valid)
    q, k, v, alpha, beta, s0 = _draw(b, m, seed=3)
    valid = None if n_valid is None else (
        jnp.arange(m)[None] < jnp.asarray(n_valid)[:, None])
    o, s = gdn.gdn_scan(q, k, v, alpha, beta, s0, valid, chunk=chunk)
    want_o, want_s = _recurrence(q, k, v, alpha, beta, s0, valid)
    if valid is not None:
        o, want_o = o * valid[..., None, None], want_o * valid[..., None,
                                                               None]
    np.testing.assert_allclose(o, want_o, atol=3e-5)
    np.testing.assert_allclose(s, want_s, atol=3e-5)


@pytest.mark.parametrize("m", [64, 24])
def test_gdn_scan_with_keys_that_repeat_under_beta_two(m):
    """Every row of a chunk with the SAME key and β = 2: each write turns the
    key's slot over (I − 2 k kᵀ has the eigenvalue −1 there), the matrix the
    chunk solves is 2 everywhere under its diagonal, whose powers grow like
    2^r binomials before they cancel — the substitution does not care, in
    four blocks of 16 rows (m = 64) or in one and a half (m = 24); a head
    with α = 0 beside it."""
    q, k, v, alpha, beta, s0 = _draw(1, m, H=4, seed=5)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    beta = jnp.full_like(beta, 2.0)
    alpha = jnp.full_like(alpha, -0.001).at[:, :, 2].set(-300.0)
    o, s = gdn.gdn_scan(q, k, v, alpha, beta, s0)
    want_o, want_s = _recurrence(q, k, v, alpha, beta, s0)
    # (64 turns of a slot of size ~16: the kernel is 2.4e-4 and the float32
    # recurrence 4e-5 from the same loop in float64, 1.5e-5 of the size)
    np.testing.assert_allclose(o, want_o, atol=1e-4)
    np.testing.assert_allclose(s, want_s, atol=5e-4)
    # the head that forgets at once holds its last write alone
    np.testing.assert_allclose(
        s[0, 2], 2.0 * k[0, -1, 2][:, None] * v[0, -1, 2][None], atol=1e-5)


def test_transformers_own_recurrence_with_beta_doubled_agrees():
    """A second witness of the recurrence at β in (0, 2): transformers'
    ``torch_recurrent_gated_delta_rule`` (qwen3_next's loop, which scales q
    itself) given β doubled, against both kernels."""
    torch = pytest.importorskip("torch")
    m = pytest.importorskip(
        "transformers.models.qwen3_next.modeling_qwen3_next")
    q, k, v, alpha, beta, s0 = _draw(1, 24, H=6, seed=11)
    t = lambda a: torch.from_numpy(np.asarray(a))               # noqa: E731
    half = beta / 2                                   # sigmoid's range
    with torch.no_grad():
        want_o, want_s = m.torch_recurrent_gated_delta_rule(
            t(q) * 96 ** 0.5, t(k), t(v), t(alpha), 2 * t(half), t(s0), True)
    o, s = gdn.gdn_scan(q, k, v, alpha, beta, s0, chunk=8)
    np.testing.assert_allclose(o, want_o.numpy(), atol=3e-5)
    np.testing.assert_allclose(s, want_s.numpy(), atol=3e-5)
    pool = gdn.pack_state(s0)
    for i in range(3):
        o1, pool = gdn.gdn_update(pool, jnp.zeros((1,), jnp.int32), q[:, i],
                                  k[:, i], v[:, i], alpha[:, i], beta[:, i])
        np.testing.assert_allclose(o1, want_o.numpy()[:, i], atol=3e-5)


# -- (2) the program against the reference -------------------------------------

@pytest.mark.parametrize("lookahead", [1, 3])
def test_prefill_then_decode_through_both_caches(model, spy, lookahead):
    """Mixed prompt lengths — under a chunk of 16, over several, no multiple
    of chunk or block — and more requests than slots so that slots free and
    refill: every token's logits are the reference's full forward pass,
    prefill's and decode's alike."""
    srv = _server(model, slots=3)
    prompts = {"a": _prompt(10), "b": _prompt(37), "c": _prompt(3),
               "d": _prompt(64), "e": _prompt(50)}
    budgets = {"a": 9, "b": 7, "c": 14, "d": 11, "e": 6}
    for rid, p in prompts.items():
        srv.submit(rid, p, budgets[rid])
    out = spy(srv, lookahead)
    assert set(out) == set(prompts)
    for rid, (toks, logits) in out.items():
        assert len(toks) == budgets[rid]
        np.testing.assert_allclose(logits, _reference(prompts[rid], toks),
                                   atol=ATOL, err_msg=rid)
    st = srv.stats()
    # 3 delta-rule layers: S (6, 12, 24) float32 and 3 rows of 2·72 + 144
    # conv channels; 1 full layer: K and V of 6 KV heads of 16
    assert st["state_layers"] == 3 and st["kv_layers"] == 1
    assert st["state_bytes_per_slot"] == 3 * (6 * 12 * 24 + 3 * 288) * 4
    assert st["state_slots"] == 4
    assert st["kv_bytes_per_token"] == 2 * 6 * 16 * 4
    assert srv.state["s"][0].shape == (4, 6, 12, 24)
    assert srv.state["conv"][2].shape == (4, 3, 288)
    assert srv.timings["scan_tokens"] == sum(len(p)
                                             for p in prompts.values())
    assert st["blocks_free"] == st["blocks_total"]


def test_a_packed_pool_serves_the_same_logits(spy):
    """Heads of 64: the pools keep two side by side (4, 3, 12, 128), the
    step's kernel spreads each head's k, q and α over its own lanes, and the
    logits are still the reference's."""
    hf = dict(HF, linear_value_head_dim=64)
    model = _model(hf)
    srv = _server(model, slots=2)
    assert srv.state["s"][0].shape == (3, 3, 12, 128)
    assert srv.stats()["state_heads_per_lane_row"] == 2
    prompts = {"a": _prompt(21), "b": _prompt(40), "c": _prompt(5)}
    for rid, p in prompts.items():
        srv.submit(rid, p, 8)
    for rid, (toks, logits) in spy(srv, 2).items():
        np.testing.assert_allclose(
            logits, _reference(prompts[rid], toks, hf), atol=ATOL,
            err_msg=rid)


def test_bfloat16_fails_the_tolerance():
    """The same comparison with the program in bfloat16, its stated
    precision on the chip: far outside the float32 tolerance, which is
    therefore tight enough to tell the precisions apart."""
    cfg, params = _model(dtype=jnp.bfloat16)
    prompt = _prompt(37)
    logits, _ = decode.prefill(params, jnp.asarray([prompt], jnp.int32), cfg,
                               decode.init_cache(cfg, 1, 48))
    want = np.asarray(ref.logits_at(HF, SEED, np.asarray([prompt]),
                                    np.asarray([[36]])))[0, 0]
    assert np.abs(np.asarray(logits[0], np.float32) - want).max() > 30 * ATOL


def test_generate_is_the_servers_tokens(model):
    """``decode.generate`` (dense caches, every step a block of one row
    through the scan kernel) and the server (pages and state pools, the
    update kernel) produce the same greedy tokens."""
    cfg, params = model
    prompt = _prompt(29)
    want = np.asarray(decode.generate(
        params, jnp.asarray([prompt], jnp.int32), cfg, 12))[0]
    srv = _server(model, slots=1)
    srv.submit("g", prompt, 12)
    assert srv.run()["g"] == want.tolist()


# -- (3) each mechanism, with it switched off ----------------------------------

@pytest.mark.parametrize("low", ["beta1", "pre_norm", "head_norm", "rotary"])
def test_each_mechanism_switched_off_is_another_model(model, low):
    """The program's float32 logits against the reference WITHOUT one
    mechanism — β = sigmoid(b), pre-norm in the post-norm's place, q/k norm a
    head at a time, rotary on: each is 30 tolerances and more away, so a
    program that lost it would fail ``test_prefill_then_decode``."""
    cfg, params = model
    prompt = _prompt(45, salt=3)
    logits, _ = decode.block_step(
        params, jnp.asarray([prompt], jnp.int32), cfg,
        decode.init_cache(cfg, 1, 48))
    at = np.arange(45)[None]
    sound = np.asarray(ref.logits_at(HF, SEED, np.asarray([prompt]), at))[0]
    other = np.asarray(ref.logits_at(HF, SEED, np.asarray([prompt]), at,
                                     low=low))[0]
    np.testing.assert_allclose(np.asarray(logits[0]), sound, atol=ATOL)
    assert np.abs(np.asarray(logits[0]) - other).max() > 30 * ATOL


@pytest.mark.parametrize("key", ["s", "conv"])
def test_state_and_tail_are_carried_from_prefill_into_decode(model, spy,
                                                             key):
    """With the admitted slot's rows of one pool zeroed after its prefill the
    decode steps' logits leave the reference's by 30 tolerances and more:
    the carry is what the float32 comparison holds."""
    srv = _server(model, slots=1)
    inner = srv._admit_finish

    def admit(plan, restored):
        inner(plan, restored)
        srv.state = dict(srv.state, **{key: tuple(
            p.at[plan["slot"]].set(0) for p in srv.state[key])})
    srv._admit_finish = admit
    prompt = _prompt(33, salt=5)
    srv.submit("r", prompt, 6)
    toks, logits = spy(srv)["r"]
    want = _reference(prompt, toks)
    np.testing.assert_allclose(logits[0], want[0], atol=ATOL)   # prefill's
    assert np.abs(logits[1:] - want[1:]).max() > 30 * ATOL


# -- (4) the block and the q/k norm against transformers' own Olmo3 ----------

@pytest.fixture(scope="module")
def hf_olmo3(tmp_path_factory):
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    if not hasattr(transformers, "Olmo3ForCausalLM"):
        pytest.skip("this transformers has no Olmo3")
    d = tmp_path_factory.mktemp("hf_olmo3")
    cfg = transformers.Olmo3Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=128, rms_norm_eps=1e-6, rope_theta=10000.0,
        layer_types=["full_attention"] * 3, sliding_window=4096,
        attention_bias=False, tie_word_embeddings=False)
    torch.manual_seed(0)
    model = transformers.Olmo3ForCausalLM(cfg).eval()
    with torch.no_grad():                   # every norm off its init
        for name, p in model.named_parameters():
            if name.endswith(("norm.weight", "layernorm.weight")):
                p.add_(0.3 * torch.randn_like(p))
    model.save_pretrained(d, safe_serialization=True)
    # the same tensors under an olmo_hybrid config whose every layer is full
    # attention and whose rotary is ON, as Olmo3's is: the block's order and
    # the whole-projection norm are what is compared
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(dict(
            model_type="olmo_hybrid", vocab_size=256, hidden_size=64,
            intermediate_size=128, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=4, hidden_act="silu",
            max_position_embeddings=128, rms_norm_eps=1e-6,
            layer_types=["full_attention"] * 3, attention_bias=False,
            tie_word_embeddings=False, linear_num_key_heads=4,
            linear_num_value_heads=4, linear_key_head_dim=8,
            linear_value_head_dim=16, linear_conv_kernel_dim=4,
            linear_allow_neg_eigval=True,
            rope_parameters={"rope_theta": 10000.0}), f)
    return str(d), model


def test_post_norm_block_and_whole_qk_norm_match_hf_olmo3(hf_olmo3,
                                                          tmp_path):
    """Converted Olmo3 weights through ``transformer.forward`` and through
    prefill + decode: ``post_attention_layernorm`` lands on the mixer's
    output and ``post_feedforward_layernorm`` on the MLP's, q_norm / k_norm
    run over all 64 features before the split into heads, and the logits are
    transformers' own."""
    torch = pytest.importorskip("torch")
    from nvme_strom_tpu.models.transformer import (TransformerConfig,
                                                   forward)
    from nvme_strom_tpu.parallel.weights import LazyCheckpoint
    hf_dir, model = hf_olmo3
    out = str(tmp_path / "converted")
    summary = convert_llama.convert(hf_dir, out)
    assert summary["skipped"] == []
    with open(os.path.join(out, "strom_config.json")) as f:
        cfg = TransformerConfig(dtype=jnp.float32, **json.load(f))
    params = LazyCheckpoint(out).load_sharded(
        lambda name, shape: jax.sharding.SingleDeviceSharding(
            jax.devices()[0]))
    assert cfg.post_norm and cfg.qk_norm_whole and cfg.rope
    assert params["layers.0.q_norm"].shape == (64,)
    sd = model.state_dict()
    np.testing.assert_array_equal(
        np.asarray(params["layers.1.attn_norm"]),
        sd["model.layers.1.post_attention_layernorm.weight"].numpy())
    np.testing.assert_array_equal(
        np.asarray(params["layers.1.mlp_norm"]),
        sd["model.layers.1.post_feedforward_layernorm.weight"].numpy())
    toks = np.random.default_rng(0).integers(0, 256, (2, 40))
    with torch.no_grad():
        want = model(torch.from_numpy(toks)).logits.float().numpy()
    with jax.default_matmul_precision("highest"):
        ours = forward(params, jnp.asarray(toks, jnp.int32), cfg)
        np.testing.assert_allclose(np.asarray(ours), want, atol=3e-4,
                                   rtol=3e-4)
        cache = decode.init_cache(cfg, 2, 48)
        lg, cache = decode.prefill(params, jnp.asarray(toks[:, :32]), cfg,
                                   cache)
        np.testing.assert_allclose(np.asarray(lg), want[:, 31], atol=3e-4,
                                   rtol=3e-4)
        for t in range(32, 36):
            lg, cache = decode.decode_step(params, jnp.asarray(toks[:, t]),
                                           cfg, cache)
            np.testing.assert_allclose(np.asarray(lg), want[:, t],
                                       atol=3e-4, rtol=3e-4)


# -- (5) the config's keys, the converter's layout, the admission rule -------

def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        return next(r for r in map(json.loads, f)
                    if r["name"] == "Olmo-Hybrid-7B")["config"]


def test_config_from_the_catalog_rows_keys():
    cfg = config_from_hf(_catalog_row())
    assert cfg.layer_kinds == ("gdn", "gdn", "gdn", "attention") * 8
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.vocab) == (3840, 30, 30, 128, 11008, 100352)
    assert (cfg.gdn_k_heads, cfg.gdn_v_heads, cfg.gdn_k_dim, cfg.gdn_v_dim,
            cfg.gdn_conv) == (30, 30, 96, 192, 4)
    assert cfg.post_norm and cfg.qk_norm and cfg.qk_norm_whole
    assert cfg.gdn_neg_eigval and not cfg.rope and not cfg.tie_embed
    assert not cfg.stated_kv and not cfg.attn_gate and not cfg.mlp_kinds
    assert cfg.norm_eps == 1e-6 and cfg.max_seq == 65536
    # layer_types is read as given, never as an interval
    odd = dict(_catalog_row(), layer_types=(
        ["full_attention"] + ["linear_attention"] * 31))
    assert config_from_hf(odd).layer_kinds[:2] == ("attention", "gdn")


@pytest.mark.parametrize("key,value,msg", [
    ("layer_types", ["linear_attention"] * 3, "layer_types"),
    ("layer_types", ["sliding_attention"] * 32, "layer_types"),
    ("rope_parameters", {"rope_type": "yarn", "rope_theta": 1e4},
     "rope_type"),
    ("linear_num_value_heads", 45, "no multiple"),
    ("hidden_act", "gelu", "hidden_act")])
def test_config_raises_on_what_is_not_implemented(key, value, msg):
    with pytest.raises(ValueError, match=msg):
        config_from_hf(dict(_catalog_row(), **{key: value}))


def test_converter_lays_flas_projections_side_by_side(tmp_path):
    """A hand-built checkpoint under FLA's and Olmo3's names: q, k, v and
    the gate become ``gdn_in`` [q | k | v | g], b and a ``gdn_ba``, the three
    convs one ``gdn_conv_w`` (taps, q | k | v), and the block's two norms
    the mixer's and the MLP's."""
    from nvme_strom_tpu.formats.safetensors import write_safetensors
    from nvme_strom_tpu.parallel.weights import LazyCheckpoint
    hf = dict(HF, num_hidden_layers=1, layer_types=["linear_attention"])
    d, key, value, H = 96, 72, 144, 6
    rng = np.random.default_rng(0)
    t = {"model.embed_tokens.weight": rng.normal(size=(96, d)),
         "model.norm.weight": rng.normal(size=(d,)),
         "lm_head.weight": rng.normal(size=(96, d))}
    L = "model.layers.0."
    for name, shape in (
            ("linear_attn.q_proj", (key, d)), ("linear_attn.k_proj", (key, d)),
            ("linear_attn.v_proj", (value, d)),
            ("linear_attn.g_proj", (value, d)),
            ("linear_attn.b_proj", (H, d)), ("linear_attn.a_proj", (H, d)),
            ("linear_attn.q_conv1d", (key, 1, 4)),
            ("linear_attn.k_conv1d", (key, 1, 4)),
            ("linear_attn.v_conv1d", (value, 1, 4)),
            ("linear_attn.o_norm", (24,)), ("linear_attn.o_proj", (d, value)),
            ("post_attention_layernorm", (d,)),
            ("post_feedforward_layernorm", (d,)),
            ("mlp.gate_proj", (160, d)), ("mlp.up_proj", (160, d)),
            ("mlp.down_proj", (d, 160))):
        t[L + name + ".weight"] = rng.normal(size=shape)
    t[L + "linear_attn.A_log"] = rng.normal(size=(H,))
    t[L + "linear_attn.dt_bias"] = rng.normal(size=(H,))
    t = {k: v.astype(np.float32) for k, v in t.items()}
    src = tmp_path / "hf"
    src.mkdir()
    write_safetensors(str(src / "model.safetensors"), t)
    with open(src / "config.json", "w") as f:
        json.dump(hf, f)
    out = str(tmp_path / "out")
    summary = convert_llama.convert(str(src), out)
    assert summary["skipped"] == []
    got = LazyCheckpoint(out).load_sharded(
        lambda name, shape: jax.sharding.SingleDeviceSharding(
            jax.devices()[0]))
    w = lambda n: t[L + n + ".weight"]                          # noqa: E731
    np.testing.assert_array_equal(got["layers.0.gdn_in"], np.concatenate(
        [w("linear_attn.q_proj").T, w("linear_attn.k_proj").T,
         w("linear_attn.v_proj").T, w("linear_attn.g_proj").T], 1))
    np.testing.assert_array_equal(got["layers.0.gdn_ba"], np.concatenate(
        [w("linear_attn.b_proj").T, w("linear_attn.a_proj").T], 1))
    np.testing.assert_array_equal(got["layers.0.gdn_conv_w"], np.concatenate(
        [w("linear_attn.q_conv1d")[:, 0].T, w("linear_attn.k_conv1d")[:, 0].T,
         w("linear_attn.v_conv1d")[:, 0].T], 1))
    np.testing.assert_array_equal(got["layers.0.attn_norm"],
                                  w("post_attention_layernorm"))
    np.testing.assert_array_equal(got["layers.0.mlp_norm"],
                                  w("post_feedforward_layernorm"))
    np.testing.assert_array_equal(got["layers.0.gdn_norm"],
                                  w("linear_attn.o_norm"))
    cfg = config_from_hf(hf)
    want = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    assert {k: tuple(v.shape) for k, v in got.items()} == {
        k: tuple(v.shape) for k, v in want.items()}
    # a checkpoint that lacks one of the six projections is refused
    del t[L + "linear_attn.g_proj.weight"]
    write_safetensors(str(src / "model.safetensors"), t)
    with pytest.raises(ValueError, match="lack parts"):
        convert_llama.convert(str(src), str(tmp_path / "out2"))


def test_grouped_admissions_break_even_for_this_layer_mix():
    """A dense 7B decoder reads ~3.7 G parameters a program and multiplies
    ~3.4 G a row (the delta rule's scan among them): on a v5e its admissions
    are compute-bound from ~265 rows, so two 128-row prompts share a program
    and a 256-row prompt runs alone."""
    cfg = config_from_hf(dict(_catalog_row(), num_hidden_layers=16,
                              layer_types=_catalog_row()["layer_types"][:16]))
    read, rowops = admission.prefill_counts(cfg)
    mix_l = 3840 * (11520 + 5760 + 60) + 5760 * 3840
    mix_f = 4 * 3840 * 3840
    mlp = 3 * 3840 * 11008
    assert read == 12 * mix_l + 4 * mix_f + 16 * mlp + 3840 * 100352
    scan = 30 * (64 * (2 * 96 + 192) + 3 * 96 * 192)
    assert rowops == read - 3840 * 100352 + 12 * scan
    rows = admission.breakeven_rows(cfg, "TPU v5 lite")
    assert 256 <= rows < 300
    assert admission.width_for(128, rows) == 2
    assert admission.width_for(256, rows) == 1


def test_the_loops_with_their_own_pre_norm_block_refuse_it():
    """A mesh, the pipeline and the offloaded cache keep their own copy of
    the pre-norm block (and a mesh would split the q/k norm's features over
    devices): each refuses a post-norm config in one sentence, and serves
    the others as before."""
    from nvme_strom_tpu.models.transformer import tiny_config
    from nvme_strom_tpu.parallel import shardings
    full = config_from_hf(dict(HF, layer_types=["full_attention"] * 4,
                               rope_parameters={"rope_theta": 1e4}))
    assert not full.recurrent_layers and not full.stated_kv
    with pytest.raises(NotImplementedError, match="post-norm blocks"):
        shardings.param_specs(full)
    with pytest.raises(NotImplementedError, match="post-norm blocks"):
        full.require_pre_norm("the pipeline")
    tiny_config().require_pre_norm("anything")       # pre-norm: no refusal
    # the hybrid itself is turned away earlier, as every recurrent one is
    with pytest.raises(NotImplementedError, match="recurrent state"):
        shardings.param_specs(config_from_hf(HF))

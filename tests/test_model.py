"""Flagship transformer tests: correctness, sharded training, end-to-end
integration with the lazy weight loader and the dataloader."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nvme_strom_tpu.models.transformer import (
    TransformerConfig,
    forward,
    init_params,
    loss_fn,
    make_train_step,
    tiny_config,
)
from nvme_strom_tpu.parallel.shardings import (
    batch_shardings,
    param_shardings,
)


@pytest.fixture(scope="module")
def cfg():
    return tiny_config()


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(jax.random.key(0), cfg)


def test_forward_shapes_and_finite(cfg, params):
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab)
    logits = jax.jit(lambda p, t: forward(p, t, cfg))(params, tokens)
    assert logits.shape == (2, 16, cfg.vocab)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())


def test_causality(cfg, params):
    """Changing a future token must not affect earlier logits."""
    t1 = jax.random.randint(jax.random.key(2), (1, 16), 0, cfg.vocab)
    t2 = t1.at[0, 10].set((t1[0, 10] + 1) % cfg.vocab)
    l1 = forward(params, t1, cfg)
    l2 = forward(params, t2, cfg)
    np.testing.assert_allclose(np.asarray(l1[0, :10]),
                               np.asarray(l2[0, :10]), rtol=1e-5)
    assert not np.allclose(np.asarray(l1[0, 10:]), np.asarray(l2[0, 10:]))


def test_initial_loss_near_uniform(cfg, params):
    tokens = jax.random.randint(jax.random.key(3), (4, 32), 0, cfg.vocab)
    loss = float(loss_fn(params, tokens, cfg))
    assert abs(loss - np.log(cfg.vocab)) < 1.0


def test_training_reduces_loss(cfg):
    import optax
    params = init_params(jax.random.key(4), cfg)
    opt = optax.adamw(3e-3)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(cfg, opt))
    tokens = jax.random.randint(jax.random.key(5), (8, 32), 0, cfg.vocab)
    first = None
    for _ in range(20):
        params, opt_state, loss = step(params, opt_state, tokens)
        first = first if first is not None else float(loss)
    assert float(loss) < first - 0.5, (first, float(loss))


def test_sharded_train_step_matches_single_device(cfg, mesh8):
    """dp×tp sharded step must compute the same loss as unsharded.
    Probed at f32: the pin is sharded ≡ local, and the tp-split
    contractions round apart under honest-bf16 activations."""
    import dataclasses
    import optax
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = init_params(jax.random.key(6), cfg)
    opt = optax.sgd(1e-2)
    tokens = jax.random.randint(jax.random.key(7), (4, 32), 0, cfg.vocab)

    # single-device reference
    s_params = jax.tree.map(np.array, params)
    step1 = jax.jit(make_train_step(cfg, opt))
    _, _, loss_ref = step1(params, opt.init(params), tokens)

    p_sh = param_shardings(cfg, mesh8)
    b_sh = batch_shardings(mesh8)
    sharded = {k: jax.device_put(np.asarray(s_params[k]), p_sh[k])
               for k in s_params}
    opt_state = opt.init(sharded)
    stepN = jax.jit(make_train_step(cfg, opt),
                    in_shardings=(p_sh, None, b_sh),
                    out_shardings=(p_sh, None, None))
    new_params, _, loss_sh = stepN(sharded, opt_state,
                                   jax.device_put(tokens, b_sh))
    np.testing.assert_allclose(float(loss_ref), float(loss_sh), rtol=1e-4)
    # updated params remain correctly sharded
    assert new_params["layers.0.wq"].sharding.spec == p_sh[
        "layers.0.wq"].spec


def test_weights_roundtrip_through_lazy_loader(cfg, mesh8, tmp_path):
    """init → save safetensors → lazy shard-aware reload → same logits."""
    from nvme_strom_tpu.io import StromEngine
    from nvme_strom_tpu.parallel.weights import (
        LazyCheckpoint, save_checkpoint)
    from nvme_strom_tpu.utils.config import EngineConfig
    from nvme_strom_tpu.utils.stats import StromStats

    import dataclasses
    # f32 probe: the pin is storage fidelity (bytes identical); the
    # forward only witnesses it, and sharded-vs-local reduction orders
    # round apart under honest-bf16 activations
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = init_params(jax.random.key(8), cfg)
    path = tmp_path / "model.safetensors"
    save_checkpoint(path, params)
    p_sh = param_shardings(cfg, mesh8)
    with StromEngine(EngineConfig(chunk_bytes=1 << 20, queue_depth=8,
                                  buffer_pool_bytes=8 << 20),
                     stats=StromStats()) as eng:
        loaded = LazyCheckpoint(path).load_sharded(p_sh, engine=eng)
    tokens = jax.random.randint(jax.random.key(9), (2, 16), 0, cfg.vocab)
    ref = forward(params, tokens, cfg)
    got = forward(loaded, tokens, cfg)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-5, atol=1e-5)


def test_remat_matches_dense_grads():
    """cfg.remat trades FLOPs for memory; math must be identical."""
    import numpy as np
    from nvme_strom_tpu.models.transformer import (
        TransformerConfig, init_params, loss_fn, tiny_config)

    cfg = tiny_config()
    rcfg = TransformerConfig(**{**cfg.__dict__, "remat": True})
    params = init_params(jax.random.key(0), cfg)
    tok = jax.random.randint(jax.random.key(1), (4, cfg.max_seq),
                             0, cfg.vocab)
    assert float(loss_fn(params, tok, rcfg)) == pytest.approx(
        float(loss_fn(params, tok, cfg)), rel=1e-5)
    g1 = jax.grad(lambda p: loss_fn(p, tok, cfg))(params)
    g2 = jax.grad(lambda p: loss_fn(p, tok, rcfg))(params)
    for k in g1:
        np.testing.assert_allclose(np.asarray(g1[k], np.float32),
                                   np.asarray(g2[k], np.float32),
                                   atol=1e-5, rtol=1e-3)


def test_gradient_accumulation_matches_full_batch():
    """accum_steps microbatching produces the same update as the
    full-batch step (mean-of-means == full mean at equal micro sizes)."""
    import optax
    from nvme_strom_tpu.models.transformer import (
        TransformerConfig, init_params, make_train_step, tiny_config)
    cfg = TransformerConfig(**{**tiny_config().__dict__,
                               "dtype": jnp.float32})
    params = init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab)
    opt = optax.adamw(1e-3)

    def run(accum):
        p = jax.tree_util.tree_map(jnp.copy, params)
        st = opt.init(p)
        step = jax.jit(make_train_step(cfg, opt, accum_steps=accum))
        for _ in range(3):
            p, st, loss = step(p, st, tokens)
        return p, float(loss)

    p1, l1 = run(1)
    p4, l4 = run(4)
    np.testing.assert_allclose(l1, l4, rtol=1e-5)
    for k in p1:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p4[k]),
                                   atol=1e-5, rtol=1e-5)

    with pytest.raises(ValueError, match="divisible"):
        jax.jit(make_train_step(cfg, opt, accum_steps=3))(
            params, opt.init(params), tokens)


def test_remat_policies_same_loss_and_grads():
    """remat_policy none/full/dots are pure memory/recompute trades:
    loss and gradients must be bit-comparable (same program, same
    math); bogus policies fail loudly."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from nvme_strom_tpu.models.transformer import (
        TransformerConfig, init_params, loss_fn)

    cfg = TransformerConfig(vocab=128, d_model=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, d_ff=64, max_seq=32,
                            dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab,
                              dtype=jnp.int32)

    outs = {}
    for pol in ("none", "full", "dots"):
        c = dataclasses.replace(cfg, remat_policy=pol)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, toks, c)))(params)
        outs[pol] = (float(loss), grads)
    assert outs["none"][0] == outs["full"][0] == outs["dots"][0]
    for pol in ("full", "dots"):
        jax.tree.map(
            lambda a, b: None if (abs(a - b) < 1e-5).all() else
            (_ for _ in ()).throw(AssertionError(pol)),
            outs["none"][1], outs[pol][1])
    # legacy remat=True == policy "full"
    c = dataclasses.replace(cfg, remat=True)
    loss, _ = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, toks, c)))(params)
    assert float(loss) == outs["full"][0]
    import pytest
    c = dataclasses.replace(cfg, remat_policy="bogus")
    with pytest.raises(ValueError, match="remat_policy"):
        loss_fn(params, toks, c)


def test_grouped_default_matches_expanded_attention(cfg, params):
    """The default (projection-layout, grouped-GQA, no-transpose)
    attention path must be numerically identical to the explicit
    expand_gqa + dense_causal_attention path — the copy-elimination
    rewrite (2026-07-31 profile: 69% of device time in copies) is a
    layout change, not a math change.  Probed at f32: the pin is
    path-A ≡ path-B, and bf16 rounds the two contraction orders
    differently (the rms_norm dtype fix made activations HONESTLY
    bf16 — they used to ride a hidden f32 promotion)."""
    import dataclasses
    from nvme_strom_tpu.models.transformer import dense_causal_attention
    assert cfg.n_kv_heads != cfg.n_heads      # the fixture must be GQA
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(3), (2, 32), 0,
                                cfg.vocab, dtype=jnp.int32)
    default_logits = forward(params, tokens, cfg)
    explicit_logits = forward(params, tokens, cfg,
                              attn_fn=dense_causal_attention)
    np.testing.assert_allclose(np.asarray(default_logits),
                               np.asarray(explicit_logits),
                               rtol=2e-4, atol=2e-4)

    # gradients agree too (the bwd pass is where the transposes lived)
    g_def = jax.grad(lambda p: loss_fn(p, tokens, cfg, None))(params)
    g_exp = jax.grad(lambda p: loss_fn(
        p, tokens, cfg, dense_causal_attention))(params)
    for k in g_def:
        np.testing.assert_allclose(np.asarray(g_def[k]),
                                   np.asarray(g_exp[k]),
                                   rtol=2e-3, atol=2e-4, err_msg=k)


def test_grouped_vs_expanded_bf16_within_noise_floor(cfg, params):
    """bf16 regression guard (round-4 advisor: every equivalence test
    moved to f32 after the rms_norm dtype fix, leaving bf16 numerics
    unexercised).  The two attention paths cannot be bitwise equal in
    bf16 — they round different contraction orders — but both are
    round-offs of the same f32 math, so their distance must stay
    within a small multiple of the bf16 quantization floor measured
    ON THIS model/input (|default_bf16 − default_f32|).  A real bf16
    regression (flash/dense drift, a stray promotion re-widening a
    matmul) blows past that by orders of magnitude."""
    import dataclasses
    from nvme_strom_tpu.models.transformer import dense_causal_attention
    assert cfg.dtype == jnp.bfloat16          # the fixture default
    tokens = jax.random.randint(jax.random.key(3), (2, 32), 0,
                                cfg.vocab, dtype=jnp.int32)
    default = np.asarray(forward(params, tokens, cfg), np.float32)
    explicit = np.asarray(forward(params, tokens, cfg,
                                  attn_fn=dense_causal_attention),
                          np.float32)
    ref32 = np.asarray(forward(
        params, tokens, dataclasses.replace(cfg, dtype=jnp.float32)))
    floor = np.abs(default - ref32).max()
    assert floor > 0                          # bf16 path really is bf16
    # explicit is its own valid bf16 rounding of the same math: within
    # 2x the floor of the f32 truth; the pairwise bound then follows by
    # triangle inequality (<= floor + 2x floor), so the two asserts can
    # never contradict each other across backends
    assert np.abs(explicit - ref32).max() <= 2.0 * floor
    assert np.abs(default - explicit).max() <= 3.0 * floor


def test_every_train_step_dot_is_bf16(cfg, params):
    """StableHLO dot census: with cfg.dtype=bf16 every dot_general in
    the train step must take bf16×bf16 operands (f32 accumulation via
    preferred_element_type is fine — it's the OPERAND dtype that
    decides MXU rate).  History: the rms_norm promotion bug (round 4)
    silently ran ALL dots f32×f32; its fix left 4 — the attention
    backward's dq/dk, fed by the f32 scores cotangent — until the
    grouped path's custom VJP (round 5) downcast dS.  This census
    makes the next silent promotion a test failure, not a
    profile-archaeology project."""
    import optax
    from conftest import dot_census as census
    from nvme_strom_tpu.models.transformer import make_train_step
    assert cfg.dtype == jnp.bfloat16
    opt = optax.adamw(1e-3)

    dots, bad = census(jax.jit(make_train_step(cfg, opt)).lower(
        params, opt.init(params),
        jnp.zeros((2, cfg.max_seq), jnp.int32)))
    assert not bad, (
        f"{len(bad)}/{len(dots)} dots with non-bf16 operands: "
        f"{bad[:4]}")

    # MoE: the ONLY allowed f32-operand dots are the router matmul and
    # its two backward dots — router math is f32 by design (the
    # GShard/Switch convention; d_model x n_experts is negligible
    # FLOPs).  Identity is pinned, not just count: every allowed dot
    # must touch the n_experts dimension.  Dispatch/combine einsums
    # must stay bf16.
    from nvme_strom_tpu.models.transformer import tiny_moe_config
    mcfg = tiny_moe_config()
    assert mcfg.dtype == jnp.bfloat16
    assert mcfg.n_experts not in (mcfg.d_model, mcfg.d_ff,
                                  mcfg.max_seq, 2)   # dim is unambiguous
    mparams = init_params(jax.random.key(0), mcfg)
    _, mbad = census(jax.jit(make_train_step(mcfg, opt)).lower(
        mparams, opt.init(mparams),
        jnp.zeros((2, mcfg.max_seq), jnp.int32)))
    assert len(mbad) == 3, (
        f"MoE step: expected exactly the 3 f32 router dots, got "
        f"{len(mbad)}: {mbad[:6]}")
    for a, b in mbad:
        dims = a.split("x")[:-1] + b.split("x")[:-1]
        assert str(mcfg.n_experts) in dims, (
            f"non-bf16 dot is NOT a router dot (no n_experts dim): "
            f"({a}, {b})")

    # ViT (config 3's consumer): zero non-bf16 dots
    from nvme_strom_tpu.models.vit import (init_vit_params,
                                           make_vit_train_step,
                                           tiny_vit_config)
    vcfg = tiny_vit_config()
    assert vcfg.dtype == jnp.bfloat16
    vp = init_vit_params(jax.random.key(0), vcfg)
    _, vbad = census(jax.jit(make_vit_train_step(vcfg, opt)).lower(
        vp, opt.init(vp),
        jnp.zeros((2, vcfg.image_size, vcfg.image_size, 3),
                  jnp.float32),
        jnp.zeros((2,), jnp.int32)))
    assert not vbad, f"ViT step non-bf16 dots: {vbad[:4]}"


def test_chunked_xent_matches_full_path(cfg):
    """cfg.xent_chunks slices the lm_head+softmax; loss AND grads must
    match the full-logits path (it's a memory layout, not new math)."""
    import dataclasses
    from nvme_strom_tpu.models.transformer import loss_fn as lf
    # f32 probe: the pin is chunked ≡ full (same math, different
    # slicing); honest-bf16 activations round the two orders apart
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = init_params(jax.random.key(5), cfg)
    tokens = jax.random.randint(jax.random.key(6), (2, 32), 0,
                                cfg.vocab, dtype=jnp.int32)
    ccfg = dataclasses.replace(cfg, xent_chunks=4)   # 32 positions / 4
    l_full, g_full = jax.value_and_grad(
        lambda p: lf(p, tokens, cfg))(params)
    l_chunk, g_chunk = jax.value_and_grad(
        lambda p: lf(p, tokens, ccfg))(params)
    np.testing.assert_allclose(float(l_full), float(l_chunk),
                               rtol=1e-5, atol=1e-6)
    for k in g_full:
        np.testing.assert_allclose(np.asarray(g_full[k]),
                                   np.asarray(g_chunk[k]),
                                   rtol=2e-4, atol=2e-5, err_msg=k)
    # indivisible chunking refuses instead of silently truncating
    bad = dataclasses.replace(cfg, xent_chunks=5)
    with pytest.raises(ValueError, match="divide"):
        lf(params, tokens, bad)


def _plain_qkv(x, p, prefix, cfg, positions=None):
    """``qkv_project`` as the plain formula: three products, head split,
    per-head norms, (b, h, s, d), rotation — what it computed before the
    products were fenced off from the head split (PR 38), and the
    reference for it since."""
    from nvme_strom_tpu.models import transformer as T
    b, s, _ = x.shape
    hd = cfg.head_dim
    q, k, v = ((x @ p[prefix + w]).reshape(b, s, -1, hd)
               for w in ("wq", "wk", "wv"))
    q, k = T._qk_norm(q, k, p, prefix, cfg)
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    if cfg.rope:
        q, k = T._rope(q, k, cfg.rope_theta, positions=positions,
                       scaling=cfg.rope_scaling_dict)
    return q, k, v


def _qkv_case(b, s, qk_norm, rope, dtype):
    import dataclasses
    cfg = dataclasses.replace(tiny_config(), qk_norm=qk_norm, rope=rope,
                              dtype=dtype)
    p = {k: v.astype(dtype) for k, v in
         init_params(jax.random.key(5), cfg).items()}
    x = jax.random.normal(jax.random.key(6), (b, s, cfg.d_model), dtype)
    # a decode step hands every row its own position, a prefill none
    positions = (jnp.arange(b, dtype=jnp.float32)[:, None] + 3
                 if s == 1 else None)
    return cfg, p, x, positions


@pytest.mark.parametrize("qk_norm,rope", [(False, True), (True, True),
                                          (False, False), (True, False)])
@pytest.mark.parametrize("b,s", [(16, 1), (2, 128)])
def test_qkv_project_equals_plain_formula(b, s, qk_norm, rope):
    """The serving form of the projections (products materialised before
    the head split, so that a TPU reads each weight where it lies) is the
    plain formula: equal bit for bit in float32 AND in bf16 — a product's
    result was bf16 before the rotation's float32 upcast already — at a
    decode step's shape and at a prefill's, under jit as the servers
    run it."""
    from nvme_strom_tpu.models.transformer import qkv_project
    for dtype in (jnp.float32, jnp.bfloat16):
        cfg, p, x, positions = _qkv_case(b, s, qk_norm, rope, dtype)
        got = jax.jit(lambda x, p: qkv_project(
            x, p, "layers.0.", cfg, positions))(x, p)
        want = jax.jit(lambda x, p: _plain_qkv(
            x, p, "layers.0.", cfg, positions))(x, p)
        for name, g, w in zip("qkv", got, want):
            assert g.shape == w.shape and g.dtype == dtype
            np.testing.assert_array_equal(
                np.asarray(g, np.float32), np.asarray(w, np.float32),
                err_msg=f"{name} {dtype.__name__}")


def test_attention_grad_through_fenced_projections(monkeypatch):
    """``jax.grad`` through ``attention`` with an explicit ``attn_fn`` (the
    path that takes ``qkv_project``) and ``jax.vmap`` over it: the fence
    around the products is transparent to both — gradients equal the plain
    formula's."""
    from nvme_strom_tpu.models import transformer as T
    cfg, p, x, _ = _qkv_case(2, 32, True, True, jnp.float32)

    def loss(p, x):
        out = T.attention(x, p, "layers.0.", cfg,
                          attn_fn=T.dense_causal_attention)
        return jnp.sum(out * out)

    g_new, gx_new = jax.grad(loss, argnums=(0, 1))(p, x)
    per_row = jax.vmap(lambda row: loss(p, row[None]))(x)
    monkeypatch.setattr(T, "qkv_project", _plain_qkv)
    g_ref, gx_ref = jax.grad(loss, argnums=(0, 1))(p, x)
    np.testing.assert_allclose(np.asarray(per_row), np.asarray(
        jnp.stack([loss(p, row[None]) for row in x])), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gx_new), np.asarray(gx_ref),
                               rtol=1e-6, atol=1e-6)
    for k in ("wq", "wk", "wv", "wo", "q_norm", "k_norm"):
        np.testing.assert_allclose(
            np.asarray(g_new["layers.0." + k]),
            np.asarray(g_ref["layers.0." + k]), rtol=1e-6, atol=1e-6,
            err_msg=k)

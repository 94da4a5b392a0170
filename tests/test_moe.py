"""MoE layer + expert parallelism (models/moe.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nvme_strom_tpu.models.moe import (
    expert_capacity, moe_dispatch_combine, moe_mlp)
from nvme_strom_tpu.models.transformer import (
    init_params, loss_fn, make_train_step, tiny_config, tiny_moe_config)


def test_dispatch_combine_invariants():
    T, E, k = 32, 4, 2
    rng = jax.random.key(0)
    probs = jax.nn.softmax(jax.random.normal(rng, (T, E)), axis=-1)
    C = expert_capacity(T, E, k, capacity_factor=10.0)  # huge: no drops
    dispatch, combine, aux = moe_dispatch_combine(probs, k, C)

    assert dispatch.shape == (T, E, C)
    d = np.asarray(dispatch)
    # every token dispatched exactly k times (capacity never binds)
    np.testing.assert_array_equal(d.sum(axis=(1, 2)), np.full(T, k))
    # a slot holds at most one token
    assert (d.sum(axis=0) <= 1.0 + 1e-6).all()
    # combine weights sum to 1 per token (renormalised top-k gates)
    np.testing.assert_allclose(np.asarray(combine).sum(axis=(1, 2)),
                               np.ones(T), rtol=1e-5)
    assert np.isfinite(float(aux))


def test_capacity_drops_tokens():
    T, E, k = 32, 4, 1
    probs = jnp.tile(jnp.array([[1.0, 0.0, 0.0, 0.0]]), (T, 1))  # all → e0
    dispatch, combine, _ = moe_dispatch_combine(probs, k, capacity := 8)
    d = np.asarray(dispatch)
    assert d.sum() == capacity          # only C tokens fit on expert 0
    assert d[:, 1:, :].sum() == 0


def test_single_expert_equals_dense_mlp():
    """n_experts=1, k=1, ample capacity ⇒ MoE == plain SwiGLU MLP."""
    from nvme_strom_tpu.models.transformer import mlp

    cfg = tiny_moe_config()
    cfg = type(cfg)(**{**cfg.__dict__, "n_experts": 1, "expert_top_k": 1,
                       "capacity_factor": 2.0, "moe_every": 1})
    params = init_params(jax.random.key(1), cfg)
    x = jax.random.normal(jax.random.key(2), (2, 8, cfg.d_model),
                          cfg.dtype)
    L = "layers.0."
    out, aux = moe_mlp(x, params, L, cfg)
    dense_p = {L + "w_gate": params[L + "moe_w_gate"][0],
               L + "w_up": params[L + "moe_w_up"][0],
               L + "w_down": params[L + "moe_w_down"][0]}
    ref = mlp(x, dense_p, L)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=5e-2, rtol=5e-2)  # bf16 einsum order


def test_grouped_dispatch_memory_linear_in_tokens():
    """Dispatch tensor elements grow linearly with T, not O(T²): doubling
    the batch doubles (not quadruples) the largest routing intermediate."""
    from nvme_strom_tpu.models.moe import moe_group_size

    cfg = tiny_moe_config()

    def dispatch_elems(b):
        T, s = b * cfg.max_seq, cfg.max_seq
        S = moe_group_size(cfg, T, s)
        C = expert_capacity(S, cfg.n_experts, cfg.expert_top_k,
                            cfg.capacity_factor)
        return (T // S) * S * cfg.n_experts * C

    e1, e2, e4 = dispatch_elems(1), dispatch_elems(2), dispatch_elems(4)
    assert e2 == 2 * e1 and e4 == 4 * e1


def test_grouped_matches_global_with_ample_capacity():
    """With capacity that never binds, routing per group == routing the
    whole batch at once (grouping only changes where capacity binds)."""
    cfg0 = tiny_moe_config()
    big = type(cfg0)(**{**cfg0.__dict__, "capacity_factor": 4.0,
                       "moe_every": 1})
    params = init_params(jax.random.key(5), big)
    x = jax.random.normal(jax.random.key(6), (4, 8, big.d_model), big.dtype)
    L = "layers.0."
    out_rows, _ = moe_mlp(x, params, L, big)                 # S = 8, G = 4
    whole = type(cfg0)(**{**big.__dict__, "moe_group_size": 32})
    out_glob, _ = moe_mlp(x, params, L, whole)               # S = 32, G = 1
    np.testing.assert_allclose(np.asarray(out_rows, np.float32),
                               np.asarray(out_glob, np.float32),
                               atol=5e-2, rtol=5e-2)


def test_moe_train_step_runs_and_learns():
    import optax

    cfg = tiny_moe_config()
    params = init_params(jax.random.key(0), cfg)
    step = jax.jit(make_train_step(cfg, optax.adamw(1e-2)))
    opt_state = optax.adamw(1e-2).init(params)
    tokens = jax.random.randint(jax.random.key(3), (4, cfg.max_seq),
                                0, cfg.vocab)
    l0 = float(loss_fn(params, tokens, cfg))
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, tokens)
    assert np.isfinite(float(loss))
    assert float(loss) < l0


def test_moe_aux_loss_nonzero_and_dense_zero():
    cfg = tiny_moe_config()
    from nvme_strom_tpu.models.transformer import forward_with_aux
    params = init_params(jax.random.key(0), cfg)
    tokens = jnp.zeros((2, cfg.max_seq), jnp.int32)
    _, aux = forward_with_aux(params, tokens, cfg)
    assert float(aux) > 0.0

    dense = tiny_config()
    dp = init_params(jax.random.key(0), dense)
    _, aux0 = forward_with_aux(dp, tokens, dense)
    assert float(aux0) == 0.0


@pytest.mark.parametrize("axes", [("dp", "ep"), ("ep", "tp")])
def test_moe_sharded_matches_single_device(axes):
    """Forward under an ep-containing mesh == unsharded forward."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from nvme_strom_tpu.parallel.shardings import param_shardings

    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 devices")
    mesh = Mesh(np.array(devs[:4]).reshape(2, 2), axes)

    cfg = tiny_moe_config()
    params = init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (4, cfg.max_seq),
                                0, cfg.vocab)
    ref = loss_fn(params, tokens, cfg)

    p_sh = param_shardings(cfg, mesh)
    sp = {k: jax.device_put(v, p_sh[k]) for k, v in params.items()}
    tok_spec = P("dp") if "dp" in mesh.shape else P()
    st = jax.device_put(tokens, NamedSharding(mesh, tok_spec))
    got = jax.jit(lambda p, t: loss_fn(p, t, cfg))(sp, st)
    np.testing.assert_allclose(float(got), float(ref), rtol=2e-2)


@pytest.mark.parametrize("factor,drops", [(1.25, True), (64.0, False)],
                         ids=["capacity_1.25_drops", "ample_capacity_agrees"])
def test_capacity_layer_against_the_exact_layer(factor, drops):
    """The nature of the capacity-dropping path, pinned for the day it goes
    (ROADMAP C11): under a skewed router ``moe_mlp`` at capacity factor 1.25
    drops pairs and differs from the exact layer (``expert_mlp``: every
    selected pair computed); at a capacity that holds every pair the two
    are the same function of the same weights."""
    import dataclasses

    from nvme_strom_tpu.models.moe import expert_mlp
    cfg = dataclasses.replace(
        tiny_moe_config(), dtype=jnp.float32, n_experts=8, expert_top_k=2,
        moe_every=1, capacity_factor=factor, moe_group_size=64)
    params = init_params(jax.random.key(4), cfg)
    L = "layers.0."
    # a skewed router: experts 0 and 1 draw most first and second choices
    skew = jnp.zeros((cfg.d_model, 8)).at[:, 0].set(0.6).at[:, 1].set(0.4)
    params[L + "router"] = 0.3 * params[L + "router"] + skew
    x = jnp.abs(jax.random.normal(jax.random.key(5), (1, 64, cfg.d_model)))
    dropped, _ = moe_mlp(x, params, L, cfg)
    exact_cfg = dataclasses.replace(cfg, mlp_kinds=("experts",) * 2)
    exact, counts, _ = expert_mlp(x, params, L, exact_cfg)
    assert int(counts.sum()) == 64 * 2              # the exact layer: all
    cap = expert_capacity(64, 8, 2, factor)
    over = int(np.maximum(np.asarray(counts) - cap, 0).sum())
    diff = np.abs(np.asarray(dropped) - np.asarray(exact)).max()
    scale = np.abs(np.asarray(exact)).max()
    if drops:
        assert over > 0 and diff > 1e-2 * scale
    else:
        assert over == 0 and diff < 1e-5 * scale


def _routed(n_experts, held, top_k=8):
    import dataclasses
    return dataclasses.replace(
        tiny_moe_config(), n_experts=n_experts, expert_top_k=top_k,
        experts_held=held, mlp_kinds=("experts",) * 2)


@pytest.mark.parametrize("rows,top_k,n_experts,held,bound,layout", [
    # Kimi-K2's share of a deployment, 12 of 384 experts, top-8: twice the
    # expected local pairs at the four prompt lengths of k2c.flood8k ...
    (8192, 8, 384, 12, 4096, 5632),
    (4096, 8, 384, 12, 2048, 3584),
    (2048, 8, 384, 12, 1024, 1792),
    (1024, 8, 384, 12, 512, 896),
    # ... and a decode step's 64 slots at the floor: all of its pairs
    (64, 8, 384, 12, 512, 704),
    (16, 8, 384, 12, 128, 320),
    # LFM2 holds all 64: every pair, whatever the call (0 says "all" too)
    (2048, 4, 64, 64, 8192, 16384),
    (2048, 4, 64, 0, 8192, 16384),
    (128, 4, 64, 0, 512, 1536),
    # half of the experts: the headroom is all of the pairs
    (4096, 2, 8, 4, 8192, 8704),
], ids=lambda v: str(v))
def test_pair_bound_follows_the_share_of_the_experts_held(
        rows, top_k, n_experts, held, bound, layout):
    """The pairs one grouped layout is made for: the held share of the
    call's pairs times the headroom, at least the floor, at most all; and
    the static rows of that layout at the call's tile."""
    from nvme_strom_tpu.models.moe import pair_bound
    from nvme_strom_tpu.ops import moe as ops
    cfg = _routed(n_experts, held, top_k)
    assert pair_bound(rows * top_k, cfg) == bound
    tm = ops.tile_rows(rows * top_k, n_experts)
    assert ops.padded_rows(bound, cfg.experts_local, tm) == layout


def test_add_load_sums_a_calls_rounds_beside_its_rows():
    from nvme_strom_tpu.models import moe
    cfg = _routed(16, 4, 4)
    calls = [(jnp.asarray([3, 0, 5, 1]), jnp.asarray([48, 2])),
             (jnp.asarray([0, 0, 0, 0]), jnp.asarray([0, 1]))]
    got = moe.add_load(moe.add_load(moe.load_counters(cfg), calls), calls)
    assert moe.SUMS == ("experts_touched", "rows_computed", "load_max",
                        "rounds")
    np.testing.assert_array_equal(got["load"], [[6, 0, 10, 2], [0, 0, 0, 0]])
    np.testing.assert_array_equal(got["sums"], [[6, 96, 10, 4], [0, 0, 0, 2]])


def test_shared_experts_gate_scales_the_shared_part_alone():
    """``shared_gate``: the shared expert's output times sigmoid(x . w), one
    scalar a row — a gate driven far negative leaves the routed part alone,
    one driven far positive is the ungated layer, and in between the layer
    is routed + gate x shared, row by row."""
    import dataclasses
    from nvme_strom_tpu.models import moe
    from nvme_strom_tpu.models import transformer as tr
    cfg = tr.TransformerConfig(
        vocab=32, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2, d_ff=64,
        mlp_kinds=("experts",), n_experts=8, expert_top_k=3, d_expert=16,
        d_shared=16, shared_gate=True, dtype=jnp.float32)
    keys = iter(jax.random.split(jax.random.key(3), 16))
    p = moe.init_moe_params(keys, cfg, "", tr.dense_init)
    assert p["shared_gate"].shape == (32, 1)
    x = jax.random.normal(jax.random.key(5), (2, 7, 32), jnp.float32)
    plain = dataclasses.replace(cfg, shared_gate=False)
    routed = moe.expert_mlp(x, p, "", dataclasses.replace(
        plain, d_shared=0))[0]
    ungated = moe.expert_mlp(x, p, "", plain)[0]
    shared = ungated - routed
    got = moe.expert_mlp(x, p, "", cfg)[0]
    gate = jax.nn.sigmoid(x @ p["shared_gate"])
    np.testing.assert_allclose(got, routed + gate * shared, atol=1e-5)
    # the bias-free gate's two ends, by scaling its weight
    far = dict(p, shared_gate=p["shared_gate"] * 1e4)
    sign = np.asarray(x @ p["shared_gate"]) > 0
    want = np.where(sign, np.asarray(ungated), np.asarray(routed))
    np.testing.assert_allclose(moe.expert_mlp(x, far, "", cfg)[0], want,
                               atol=1e-5)

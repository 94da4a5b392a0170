"""Pipeline parallelism: GPipe-style microbatching over a ``pp`` mesh axis.

The reference has no parallelism concepts (SURVEY.md §2 "Parallelism
strategies: NOT PRESENT") — pipeline parallelism is here because it is a
first-class requirement of the TPU framework build, composing with the
dp/tp/sp/ep axes the other ``parallel/`` modules provide.

TPU-first design (the scaling-book "collective pipeline"): the layer stack
is split into ``pp`` contiguous stages, each device holds its stage's
weights as a stacked ``(layers_per_stage, ...)`` slice, and activations
flow stage→stage with ``lax.ppermute`` — a neighbor exchange XLA maps onto
the ICI torus.  Microbatches keep every stage busy outside the unavoidable
GPipe warmup/drain bubble of (pp−1) ticks; inside a tick every stage runs
the same jitted block, so the whole schedule is ONE ``lax.scan`` — static
shapes, no Python control flow, one compilation.

Tensor parallelism inside the manual region is explicit-collective
Megatron: wq/wk/wv/w_gate/w_up are column-sharded over ``tp``, wo/w_down
row-sharded, with a ``lax.psum`` over ``tp`` after each row-parallel
matmul (the collectives the annotation-based path in
``parallel/shardings.py`` gets from the SPMD partitioner, written by hand
because shard_map regions are manual).  Everything works at any axis size,
including 1, so one step function serves every mesh shape.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from nvme_strom_tpu.models.transformer import (
    TransformerConfig, _rope, dense_causal_attention, rms_norm)
from nvme_strom_tpu.parallel.ring_attention import _ring_block

_STACKED = ("attn_norm", "wq", "wk", "wv", "wo",
            "mlp_norm", "w_gate", "w_up", "w_down")


def _shard_map(fn, mesh, in_specs, out_specs):
    """shard_map without VMA/replication checking (the schedule's masked
    psum broadcasts are replicated by construction, not by type)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


_ATTN = ("attn_norm", "wq", "wk", "wv", "wo")
_MOE_MLP = ("mlp_norm", "router", "moe_w_gate", "moe_w_up", "moe_w_down")


def _moe_period(cfg: TransformerConfig) -> int:
    """Super-layer period for MoE stacks: 0 for dense configs, else
    cfg.moe_every (layer g·p+p−1 of each group of p is the MoE layer —
    exactly cfg.is_moe_layer's pattern, so any config is stackable)."""
    if not any(cfg.is_moe_layer(i) for i in range(cfg.n_layers)):
        return 0
    p = cfg.moe_every
    if cfg.n_layers % p:
        raise ValueError(
            f"MoE pipeline needs n_layers ({cfg.n_layers}) divisible by "
            f"moe_every ({p}) to form super-layers")
    return p


def split_layer_stack(params: Dict, cfg: TransformerConfig
                      ) -> tuple[Dict, Dict]:
    """Flat {name: array} params → (stack, rest).

    Dense configs: ``stack[name]`` has shape (n_layers, *per_layer) — the
    leading axis is what ``P("pp", ...)`` shards into stages.

    MoE configs: the stack is nested — ``stack["dense"][name]`` holds the
    p−1 dense sub-layers of each super-layer, shape (n_super, p−1,
    *per_layer), and ``stack["moe"][name]`` the MoE sub-layer, shape
    (n_super, *per_layer) with experts sharded over ``ep`` — so ep
    composes with pp (VERDICT round 1 #6).

    ``rest`` holds the unstacked embed/head/final-norm weights applied
    outside the pipeline.
    """
    p = _moe_period(cfg)
    if p == 0:
        stack = {n: jnp.stack([params[f"layers.{i}.{n}"]
                               for i in range(cfg.n_layers)])
                 for n in _STACKED}
    else:
        n_super = cfg.n_layers // p
        stack = {"moe": {}}
        if p > 1:
            stack["dense"] = {
                n: jnp.stack([
                    jnp.stack([params[f"layers.{g * p + j}.{n}"]
                               for j in range(p - 1)])
                    for g in range(n_super)])
                for n in _STACKED}
        for n in _ATTN + _MOE_MLP:
            stack["moe"][n] = jnp.stack(
                [params[f"layers.{g * p + p - 1}.{n}"]
                 for g in range(n_super)])
    rest = {k: v for k, v in params.items() if not k.startswith("layers.")}
    return stack, rest


def merge_layer_stack(stack: Dict, rest: Dict) -> Dict:
    """Inverse of split_layer_stack (checkpoint round-trips by name)."""
    out = dict(rest)
    if "moe" in stack:   # nested MoE super-layer stack
        n_super = stack["moe"]["attn_norm"].shape[0]
        p = (stack["dense"]["attn_norm"].shape[1] + 1
             if "dense" in stack else 1)
        for g in range(n_super):
            for j in range(p - 1):
                for n in _STACKED:
                    out[f"layers.{g * p + j}.{n}"] = stack["dense"][n][g, j]
            for n in _ATTN + _MOE_MLP:
                out[f"layers.{g * p + p - 1}.{n}"] = stack["moe"][n][g]
        return out
    n_layers = next(iter(stack.values())).shape[0]
    for i in range(n_layers):
        for n in _STACKED:
            out[f"layers.{i}.{n}"] = stack[n][i]
    return out


def stacked_specs(cfg: TransformerConfig = None) -> Dict:
    """PartitionSpecs matching split_layer_stack's output shape (pass the
    config for MoE stacks; default is the dense flat stack)."""
    col = P("pp", None, "tp")   # (L, d, out·/tp) column-parallel
    row = P("pp", "tp", None)   # (L, in·/tp, d) row-parallel → psum
    norm = P("pp", None)
    dense = {"attn_norm": norm, "wq": col, "wk": col, "wv": col, "wo": row,
             "mlp_norm": norm, "w_gate": col, "w_up": col, "w_down": row}
    p = _moe_period(cfg) if cfg is not None else 0
    if p == 0:
        return dense
    specs = {"moe": {
        "attn_norm": norm, "wq": col, "wk": col, "wv": col, "wo": row,
        "mlp_norm": norm, "router": P("pp", None, None),
        # experts over ep, each expert's FFN Megatron-split over tp
        "moe_w_gate": P("pp", "ep", None, "tp"),
        "moe_w_up": P("pp", "ep", None, "tp"),
        "moe_w_down": P("pp", "ep", "tp", None),
    }}
    if p > 1:   # dense sub-layers gain the (n_super, p-1) leading dims
        def widen(s):
            t = tuple(s)
            return P(*(t[:1] + (None,) + t[1:]))
        specs["dense"] = {k: widen(s) for k, s in dense.items()}
    return specs


def stacked_shardings(mesh, cfg: TransformerConfig = None) -> Dict:
    from nvme_strom_tpu.parallel.shardings import prune_spec
    return jax.tree.map(
        lambda s: NamedSharding(mesh, prune_spec(s, mesh)),
        stacked_specs(cfg),
        is_leaf=lambda x: isinstance(x, P))


# ------------------- per-device stage computation -------------------

def _attn_sub(x, lp, cfg: TransformerConfig, tp_axis, tp_size: int,
              sp_axis=None, sp_size: int = 1):
    """Attention sub-layer (x + attn) with explicit-psum tensor
    parallelism and (optionally) ring-attention sequence parallelism.
    x (b, s_local, d); lp = per-layer weight dict with tp-local shards.
    ``tp_axis``/``sp_axis`` are None when the mesh lacks the axis.
    With sp, the sequence dim is sharded: RoPE uses the shard's absolute
    positions and attention runs the ppermute ring over ``sp_axis``."""
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    b, s, _ = h.shape
    hd = cfg.head_dim
    nh_l = cfg.n_heads // tp_size
    nkv_l = cfg.n_kv_heads // tp_size
    q = (h @ lp["wq"].astype(h.dtype)).reshape(b, s, nh_l, hd)
    k = (h @ lp["wk"].astype(h.dtype)).reshape(b, s, nkv_l, hd)
    v = (h @ lp["wv"].astype(h.dtype)).reshape(b, s, nkv_l, hd)
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    if sp_axis is not None and sp_size > 1:
        positions = (lax.axis_index(sp_axis) * s
                     + jnp.arange(s)).astype(jnp.float32)
    else:
        positions = None
    q, k = _rope(q, k, cfg.rope_theta, positions=positions)
    if nkv_l != nh_l:
        k = jnp.repeat(k, nh_l // nkv_l, axis=1)
        v = jnp.repeat(v, nh_l // nkv_l, axis=1)
    if sp_axis is not None and sp_size > 1:
        a = _ring_block(q, k, v, axis_name=sp_axis, n_sp=sp_size,
                        causal=True)
    else:
        a = dense_causal_attention(q, k, v)
    a = a.transpose(0, 2, 1, 3).reshape(b, s, nh_l * hd)
    a = a @ lp["wo"].astype(h.dtype)
    if tp_axis is not None:               # row-parallel reduce over tp
        a = lax.psum(a, tp_axis)
    return x + a


def _block(x, lp, cfg: TransformerConfig, tp_axis, tp_size: int,
           sp_axis=None, sp_size: int = 1):
    """One dense decoder layer (attention + SwiGLU MLP)."""
    x = _attn_sub(x, lp, cfg, tp_axis, tp_size, sp_axis, sp_size)
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    gate = jax.nn.silu(h @ lp["w_gate"].astype(h.dtype))
    up = h @ lp["w_up"].astype(h.dtype)
    m = (gate * up) @ lp["w_down"].astype(h.dtype)
    if tp_axis is not None:
        m = lax.psum(m, tp_axis)
    # f32 norm weights promote the residual; pin the carry dtype so the
    # layer scan's carry type is invariant.
    return (x + m).astype(cfg.dtype)


def _moe_block(x, lp, cfg: TransformerConfig, tp_axis, tp_size: int,
               sp_axis=None, sp_size: int = 1, ep_axis=None,
               ep_size: int = 1):
    """One MoE decoder layer inside the manual pipeline region.

    Dense-dispatch expert parallelism with hand-written collectives (the
    manual mirror of models/moe.py's annotation path): routing runs on
    the device-local tokens (replicated across tp/ep, so every rank
    computes identical dispatch tensors), each rank applies only its
    E/ep local experts (tp-split FFN inside each expert), and ONE fused
    psum over (tp, ep) after the combine einsum sums both the
    row-parallel and the expert partial results.  Groups are the local
    rows (GShard grouping — capacity binds per local batch row).

    Returns (x, aux): the router load-balancing aux loss (mean over the
    local routing groups) rides the pipeline schedule back out — see
    _pipeline_local — so the pipelined train step regularizes routing
    exactly like the annotation path.
    """
    from nvme_strom_tpu.models.moe import (
        expert_capacity, moe_dispatch_combine)

    x = _attn_sub(x, lp, cfg, tp_axis, tp_size, sp_axis, sp_size)
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    b, s, d = h.shape
    E, k = cfg.n_experts, cfg.expert_top_k
    G, S = b, s                           # per-row routing groups
    C = expert_capacity(S, E, k, cfg.capacity_factor)
    xg = h.reshape(G, S, d)
    logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32),
                        lp["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    dispatch, combine, aux = jax.vmap(
        lambda pr: moe_dispatch_combine(pr, k, C))(probs)
    aux = aux.mean()

    E_local = E // ep_size
    e0 = (lax.axis_index(ep_axis) * E_local) if ep_axis is not None else 0
    disp_l = lax.dynamic_slice_in_dim(dispatch, e0, E_local, axis=2)
    comb_l = lax.dynamic_slice_in_dim(combine, e0, E_local, axis=2)
    xd = jnp.einsum("gsec,gsd->egcd", disp_l.astype(h.dtype), xg)
    xd = xd.reshape(E_local, G * C, d)
    gate = jax.nn.silu(jnp.einsum(
        "ecd,edf->ecf", xd, lp["moe_w_gate"].astype(h.dtype)))
    up = jnp.einsum("ecd,edf->ecf", xd, lp["moe_w_up"].astype(h.dtype))
    hh = jnp.einsum("ecf,efd->ecd", gate * up,
                    lp["moe_w_down"].astype(h.dtype))
    hh = hh.reshape(E_local, G, C, d)
    out = jnp.einsum("gsec,egcd->gsd", comb_l.astype(h.dtype), hh)
    # combine is linear: defer BOTH the row-parallel (tp) and the
    # expert-partial (ep) reductions past it — one psum on (G,S,d)
    # instead of one on (E_local,G,C,d) plus another on (G,S,d).
    axes = tuple(a for a in (tp_axis, ep_axis) if a is not None)
    if axes:
        out = lax.psum(out, axes)
    return (x + out.reshape(b, s, d)).astype(cfg.dtype), aux


def _pipeline_local(stack, x_mb, *, cfg, pp_axis, tp_axis, n_pp, tp_size,
                    n_mb, sp_axis=None, sp_size=1, ep_axis=None,
                    ep_size=1, dp_axis=None):
    """Per-device pipeline schedule (inside shard_map).

    stack: stage-local weights (n_layers/pp — or, for MoE, n_super/pp —
    leading axis); x_mb: (n_mb, mb_local, s, d) microbatched activations
    (every pp rank sees all of them; only stage 0 consumes).  Returns
    ((n_mb, mb_local, s, d) final-stage outputs, value-replicated across
    pp/tp via a masked psum broadcast, and the scalar router aux loss —
    stage-summed, microbatch- and dp/sp-meaned, 0 for dense stacks).
    """
    stage = lax.axis_index(pp_axis) if pp_axis is not None else 0

    block, moe_block = _block, _moe_block
    if cfg.remat:   # recompute each stage layer in backward (GPipe-style)
        # prevent_cse=False: lax.scan already blocks CSE; the default
        # barriers would only inhibit XLA fusion in the hot path
        block = jax.checkpoint(_block, static_argnums=(2, 3, 4, 5, 6),
                               prevent_cse=False)
        moe_block = jax.checkpoint(
            _moe_block, static_argnums=(2, 3, 4, 5, 6, 7, 8),
            prevent_cse=False)

    def stage_apply(x):
        """→ (x, aux): aux is this stage's summed router aux loss (0 for
        dense stacks)."""
        if "moe" in stack:   # super-layer scan: p−1 dense + 1 MoE each
            def super_body(carry, slp):
                c, aux = carry
                if "dense" in slp:
                    def dbody(c2, lp):
                        return block(c2, lp, cfg, tp_axis, tp_size,
                                     sp_axis, sp_size), None
                    c, _ = lax.scan(dbody, c, slp["dense"])
                c, a = moe_block(c, slp["moe"], cfg, tp_axis, tp_size,
                                 sp_axis, sp_size, ep_axis, ep_size)
                return (c, aux + a), None
            (x, aux), _ = lax.scan(super_body,
                                   (x, jnp.zeros((), jnp.float32)), stack)
            return x, aux
        def body(c, lp):
            return block(c, lp, cfg, tp_axis, tp_size,
                         sp_axis, sp_size), None
        x, _ = lax.scan(body, x, stack)
        return x, jnp.zeros((), jnp.float32)

    perm = [(i, i + 1) for i in range(n_pp - 1)]

    def tick(carry, t):
        state, out, aux_acc = carry
        inp = lax.dynamic_index_in_dim(
            x_mb, jnp.clip(t, 0, n_mb - 1), 0, keepdims=False)
        x = jnp.where(stage == 0, inp, state)
        y, aux = stage_apply(x)
        # A stage processes microbatch t−stage at tick t; outside
        # [0, n_mb) it chews warmup/drain zeros whose router stats are
        # garbage — mask them out of the aux accumulation.
        valid = jnp.logical_and(t >= stage, t - stage < n_mb)
        aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
        # Last stage writes microbatch t-(pp-1) once the pipe is full.
        oidx = jnp.clip(t - (n_pp - 1), 0, n_mb - 1)
        write = jnp.logical_and(stage == n_pp - 1, t >= n_pp - 1)
        cur = lax.dynamic_index_in_dim(out, oidx, 0, keepdims=False)
        out = lax.dynamic_update_index_in_dim(
            out, jnp.where(write, y, cur), oidx, 0)
        state = lax.ppermute(y, pp_axis, perm) if n_pp > 1 else y
        return (state, out, aux_acc), None

    carry0 = (jnp.zeros_like(x_mb[0]), jnp.zeros_like(x_mb),
              jnp.zeros((), jnp.float32))
    (state, out, aux), _ = lax.scan(tick, carry0,
                                    jnp.arange(n_mb + n_pp - 1))
    if pp_axis is not None and n_pp > 1:
        # broadcast the last stage's outputs to every pp rank; sum the
        # per-stage aux contributions (each stage holds its own layers)
        out = lax.psum(
            jnp.where(stage == n_pp - 1, out, jnp.zeros_like(out)), pp_axis)
        aux = lax.psum(aux, pp_axis)
    aux = aux / n_mb                     # mean over microbatches
    # mean over data/sequence shards (tp/ep ranks compute identical aux)
    daxes = tuple(a for a in (dp_axis, sp_axis) if a is not None)
    if daxes:
        aux = lax.pmean(aux, daxes)
    return out, aux


# ------------------------- public entry points -------------------------

def _axis_size(mesh, name: str) -> int:
    return mesh.shape.get(name, 1)


def make_pp_forward_with_aux(cfg: TransformerConfig, mesh,
                             n_microbatches: int,
                             pp_axis: str = "pp", tp_axis: str = "tp",
                             dp_axis: str = "dp", sp_axis: str = "sp",
                             ep_axis: str = "ep"):
    """Returns fwd(stack, rest, tokens) -> (logits (B, s, vocab) f32,
    router aux loss scalar — 0 for dense configs).

    Embedding, final norm and the LM head run outside the shard_map under
    ordinary sharding annotations; the layer stack runs inside the
    pipelined manual region.  MoE configs pipeline as super-layers with
    experts sharded over ``ep_axis`` (see split_layer_stack).
    """
    cfg.require_pre_norm("the pipeline (parallel/pipeline.py)")
    n_pp = _axis_size(mesh, pp_axis)
    tp_size = _axis_size(mesh, tp_axis)
    sp_size = _axis_size(mesh, sp_axis)
    ep_size = _axis_size(mesh, ep_axis)
    p = _moe_period(cfg)
    if sp_size > 1 and cfg.max_seq % sp_size:
        raise ValueError(f"seq {cfg.max_seq} not divisible by "
                         f"sp={sp_size}")
    n_units = cfg.n_layers if p == 0 else cfg.n_layers // p
    if n_units % n_pp:
        raise ValueError(
            f"{n_units} {'layers' if p == 0 else 'super-layers'} not "
            f"divisible into {n_pp} pipeline stages")
    if cfg.n_heads % tp_size or cfg.n_kv_heads % tp_size:
        raise ValueError(f"heads ({cfg.n_heads}/{cfg.n_kv_heads}) not "
                         f"divisible by tp={tp_size}")
    if p and cfg.n_experts % ep_size:
        raise ValueError(f"{cfg.n_experts} experts not divisible by "
                         f"ep={ep_size}")

    from nvme_strom_tpu.parallel.shardings import prune_spec
    specs = jax.tree.map(lambda s: prune_spec(s, mesh),
                         stacked_specs(cfg),
                         is_leaf=lambda x: isinstance(x, P))
    x_spec = prune_spec(P(None, dp_axis, sp_axis, None), mesh)
    run = _shard_map(
        partial(_pipeline_local, cfg=cfg,
                pp_axis=pp_axis if pp_axis in mesh.shape else None,
                tp_axis=tp_axis if tp_axis in mesh.shape else None,
                sp_axis=sp_axis if sp_axis in mesh.shape else None,
                ep_axis=(ep_axis if p and ep_axis in mesh.shape
                         else None),
                dp_axis=dp_axis if dp_axis in mesh.shape else None,
                n_pp=n_pp, tp_size=tp_size, sp_size=sp_size,
                ep_size=ep_size if p else 1,
                n_mb=n_microbatches),
        mesh, in_specs=(specs, x_spec), out_specs=(x_spec, P()))

    def fwd_hidden_aux(stack: Dict, rest: Dict, tokens: jax.Array):
        B, s = tokens.shape
        # Validate against the *actual* sequence, not cfg.max_seq — a
        # caller with s != max_seq would otherwise pass the constructor
        # check and die inside shard_map with an opaque partition error.
        if sp_size > 1 and s % sp_size:
            raise ValueError(f"seq {s} not divisible by sp={sp_size}")
        if B % n_microbatches:
            raise ValueError(f"batch {B} not divisible into "
                             f"{n_microbatches} microbatches")
        dp_size = _axis_size(mesh, dp_axis)
        if (B // n_microbatches) % dp_size:
            raise ValueError(
                f"microbatch size {B // n_microbatches} not divisible by "
                f"dp={dp_size}")
        x = rest["tok_embed"].astype(cfg.dtype)[tokens]
        x = x.reshape(n_microbatches, B // n_microbatches, s, cfg.d_model)
        x, aux = run(stack, x)
        x = x.reshape(B, s, cfg.d_model)
        return rms_norm(x, rest["final_norm"], cfg.norm_eps), aux

    def fwd_with_aux(stack, rest, tokens):
        x, aux = fwd_hidden_aux(stack, rest, tokens)
        logits = (x @ rest["lm_head"].astype(x.dtype)).astype(jnp.float32)
        return logits, aux

    fwd_with_aux.hidden = fwd_hidden_aux
    return fwd_with_aux


def make_pp_forward(cfg: TransformerConfig, mesh, n_microbatches: int,
                    **axes):
    """Returns fwd(stack, rest, tokens) -> logits (B, s, vocab) f32."""
    fwd_aux = make_pp_forward_with_aux(cfg, mesh, n_microbatches, **axes)

    def fwd(stack, rest, tokens):
        return fwd_aux(stack, rest, tokens)[0]

    return fwd


def make_pp_loss(cfg, mesh, n_microbatches, **axes):
    """Next-token cross-entropy + router aux term — the pipelined mirror
    of transformer.loss_fn (same coef, same per-row grouping, so the two
    agree to fp tolerance on MoE configs).  ``cfg.xent_chunks > 1``
    takes the chunked lm_head+softmax exactly like the unpipelined
    loss (transformer.chunked_xent reads ``rest["lm_head"]``)."""
    fwd_aux = make_pp_forward_with_aux(cfg, mesh, n_microbatches, **axes)

    def loss_fn(stack, rest, tokens):
        if cfg.xent_chunks > 1:
            from nvme_strom_tpu.models.transformer import chunked_xent
            hidden, aux = fwd_aux.hidden(stack, rest, tokens)
            return (chunked_xent(rest, hidden, tokens, cfg)
                    + cfg.router_aux_coef * aux)
        logits, aux = fwd_aux(stack, rest, tokens)
        logits = logits[:, :-1]
        targets = tokens[:, 1:]
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return -jnp.mean(ll) + cfg.router_aux_coef * aux

    return loss_fn


def make_pp_train_step(cfg: TransformerConfig, optimizer, mesh,
                       n_microbatches: int, **axes):
    """step(stack, rest, opt_state, tokens) -> (stack, rest, opt_state,
    loss) — the pipelined analogue of transformer.make_train_step; jit it
    at the call site."""
    import optax

    loss_fn = make_pp_loss(cfg, mesh, n_microbatches, **axes)

    def step(stack, rest, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            stack, rest, tokens)
        updates, opt_state = optimizer.update(grads, opt_state,
                                              (stack, rest))
        stack, rest = optax.apply_updates((stack, rest), updates)
        return stack, rest, opt_state, loss

    return step

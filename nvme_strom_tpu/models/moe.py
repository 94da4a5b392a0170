"""Mixture-of-experts MLPs: the exact expert layer the serving path runs
(``expert_mlp``, below the GShard code) and the capacity-dropping GShard
layer with expert parallelism over an ``ep`` mesh axis (``moe_mlp``: the
training path's, reached only from a config that places it by
``moe_every``).

The reference has no model-parallel concepts (SURVEY.md §2 "Parallelism
strategies: NOT PRESENT") — expert parallelism is here because it is a
first-class requirement of the TPU framework build, exercised by the
flagship transformer and the driver's multi-chip dry run.

TPU-first design: GShard/Switch-style *dense dispatch*.  Routing is
expressed as one-hot dispatch/combine tensors contracted with einsum, so
every shape is static, everything lands on the MXU, and under ``jit`` with
expert weights sharded ``P("ep", ...)`` the SPMD partitioner inserts the
all-to-alls over ICI itself — no hand-written NCCL-style exchange (the
reference has none either; its transport is PCIe P2P DMA, SURVEY.md §5).

Per-token cost is O(k/E) of a dense MLP of the same total width, at the
price of a fixed per-expert capacity: tokens routed beyond an expert's
capacity are dropped (contribute zero for that slot), the standard
static-shape trade XLA needs.

Scalability: dispatch is *grouped* (GShard §3.2 pattern).  Tokens are
reshaped to (G, S) along the batch-major dim and routed per group with a
per-group capacity C = ceil(k·S/E·factor), so the dispatch/combine
tensors are (G, S, E, C) — O(T·k·S·factor) elements, linear in the total
token count T for a fixed group size S.  The ungrouped form is O(k·T²)
and melts HBM at flagship scale (round-1 advisor finding, ADVICE.md).
Groups follow the dp/batch sharding, so routing is local to each dp
shard and only the expert einsums cross the ep axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from nvme_strom_tpu.models import transformer as _tr


def moe_dispatch_combine(router_probs: jax.Array, top_k: int, capacity: int):
    """Build dense dispatch/combine tensors from router probabilities.

    router_probs: (T, E) float32 softmax output.
    Returns (dispatch, combine, aux_loss):
      dispatch (T, E, C) ∈ {0,1} — token t occupies slot c of expert e;
      combine  (T, E, C) float32 — dispatch scaled by the (renormalised)
      top-k gate weight, so ``einsum('tec,ecd->td', combine, expert_out)``
      is the weighted sum over a token's experts;
      aux_loss — Switch-style load-balancing loss (scalar, f32).

    Slot priority is k-major (every token's first choice is placed before
    any second choice), position within an expert is token-major cumsum —
    the GShard ordering.
    """
    T, E = router_probs.shape
    gate_vals, gate_idx = jax.lax.top_k(router_probs, top_k)     # (T, k)
    gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    mask = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)        # (T, k, E)
    # Load-balancing aux: fraction of tokens whose top-1 lands on e, times
    # mean router prob of e, summed — minimised by a uniform router.
    f = mask[:, 0, :].mean(axis=0)                               # (E,)
    p = router_probs.mean(axis=0)                                # (E,)
    aux_loss = E * jnp.sum(f * p)

    mask_kt = mask.transpose(1, 0, 2).reshape(top_k * T, E)      # (kT, E)
    pos = jnp.cumsum(mask_kt, axis=0) - mask_kt                  # 0-based
    keep = mask_kt * (pos < capacity)                            # (kT, E)
    pos_oh = (jax.nn.one_hot(pos.astype(jnp.int32), capacity)
              * keep[..., None])                                 # (kT, E, C)
    pos_oh = pos_oh.reshape(top_k, T, E, capacity).transpose(1, 0, 2, 3)

    dispatch = pos_oh.sum(axis=1)                                # (T, E, C)
    combine = (pos_oh * gate_vals[:, :, None, None]).sum(axis=1)  # (T, E, C)
    return dispatch, combine, aux_loss


def expert_capacity(n_tokens: int, n_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    """Static per-expert slot count: ceil(k·T/E · factor), ≥ 1."""
    import math
    return max(1, math.ceil(n_tokens * top_k / n_experts * capacity_factor))


def moe_group_size(cfg, n_tokens: int, seq: int) -> int:
    """Routing-group size.  Unset (0): one batch row (the dp-local GShard
    default).  Explicit: must divide the token count — except when it
    exceeds the whole batch (the decode / tiny-eval case), where a single
    global group is the natural semantics.  A non-dividing explicit size
    raises rather than silently changing drop behavior."""
    gs = getattr(cfg, "moe_group_size", 0)
    if not gs:
        return seq                    # batch rows always divide b*s
    if gs >= n_tokens:
        return n_tokens
    if n_tokens % gs:
        raise ValueError(
            f"moe_group_size={gs} does not divide token count "
            f"{n_tokens}; pick a divisor or 0 (per-batch-row groups)")
    return gs


def moe_mlp(x: jax.Array, p: dict, prefix: str, cfg) -> tuple:
    """MoE SwiGLU MLP block.  x (b, s, d) → (out (b, s, d), aux_loss).

    Params (flat dict, same namespace as the safetensors lazy loader):
      {prefix}router     (d, E)
      {prefix}moe_w_gate (E, d, ff)
      {prefix}moe_w_up   (E, d, ff)
      {prefix}moe_w_down (E, ff, d)

    Routing is per group of S tokens (see module docstring): capacity
    binds within each group, aux loss is the mean over groups.
    """
    b, s, d = x.shape
    T = b * s
    E, k = cfg.n_experts, cfg.expert_top_k
    S = moe_group_size(cfg, T, s)
    G = T // S
    C = expert_capacity(S, E, k, cfg.capacity_factor)
    xg = x.reshape(G, S, d)

    logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32),
                        p[prefix + "router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                       # (G, S, E)
    dispatch, combine, aux = jax.vmap(
        lambda pr: moe_dispatch_combine(pr, k, C))(probs)
    aux = aux.mean()

    # (G,S,E,C)·(G,S,d) → (E,G,C,d): experts see G·C slots regardless of
    # where the group boundary fell; G rides the dp sharding of x.
    xd = jnp.einsum("gsec,gsd->egcd", dispatch.astype(x.dtype), xg)
    xd = xd.reshape(E, G * C, d)
    gate = jax.nn.silu(jnp.einsum(
        "ecd,edf->ecf", xd, _tr.wmat(p, prefix + "moe_w_gate", x.dtype)))
    up = jnp.einsum("ecd,edf->ecf", xd,
                    _tr.wmat(p, prefix + "moe_w_up", x.dtype))
    h = jnp.einsum("ecf,efd->ecd", gate * up,
                   _tr.wmat(p, prefix + "moe_w_down", x.dtype))
    h = h.reshape(E, G, C, d)
    out = jnp.einsum("gsec,egcd->gsd", combine.astype(x.dtype), h)
    return out.reshape(b, s, d), aux


# ------------------------------------------------------ the exact layer
#
#   s    = score(h W_g) in float32            sigmoid, or softmax over E
#   sel  = top-k of (s + b)                   b: "router_bias", chooses only
#   w    = s[sel] / (sum s[sel] + 1e-6)       (router_norm_topk) x router_scale
#   f    = sum_{e in sel} w_e W2_e (silu(W1_e h) * W3_e h)
#
# Every selected (row, expert) pair is computed: no capacity, nothing
# dropped, and no row's result depends on any other row of the call.

def route(x, p: dict, prefix: str, cfg):
    """x (T, d) -> (sel (T, k) int32, w (T, k) float32)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        p[prefix + "router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    s = (jax.nn.sigmoid(logits) if cfg.router_kind == "sigmoid"
         else jax.nn.softmax(logits, axis=-1))
    choose = s
    if cfg.router_bias:
        choose = s + p[prefix + "router_bias"].astype(jnp.float32)
    _, sel = jax.lax.top_k(choose, cfg.expert_top_k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if cfg.router_norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
    if cfg.router_scale != 1.0:
        w = w * jnp.float32(cfg.router_scale)
    return sel.astype(jnp.int32), w


#: A device that holds a share of the router's experts sizes its grouped
#: layout for that share of a call's (row, expert) pairs times this headroom,
#: not for all of them; what exceeds it is computed in further rounds.
HEADROOM = 2
#: ... and for no fewer pairs than this: under it a layout is the held
#: experts' own tiles and little else, so a small call (a decode step) keeps
#: the layout of all its pairs and runs no compaction.
MIN_PAIRS = 512


def pair_bound(pairs: int, cfg) -> int:
    """(row, expert) pairs one grouped layout is made for, of a call's
    ``pairs``: all of them where every expert is held here, else the held
    share with ``HEADROOM`` (8,192 rows x 8 on 12 of 384 experts: 4,096
    pairs for ~2,048 expected, a layout of 5,632 rows)."""
    share = -(-HEADROOM * pairs * cfg.experts_local // cfg.n_experts)
    # (a row's pairs are one round's: never fewer than a row's)
    return min(pairs, max(MIN_PAIRS, share, cfg.expert_top_k))


def _layout(xt, pair, expert, k: int, p: dict, prefix: str, cfg, tm: int):
    """One grouped layout and its two products.  pair (n,) int32: which of
    the call's pairs (pair i reads row i // k of xt (T, d)), T * k for none;
    expert (n,): its expert among those held here, ``experts_local`` for a
    pair that is computed nowhere.  Returns (y (rows, d), dest (n,) — the
    pair's row of y, ``rows`` for none —, counts, tiles used)."""
    from nvme_strom_tpu.ops import moe as _ops
    n_pairs, E = xt.shape[0] * k, cfg.experts_local
    with jax.named_scope("strom.moe.route"):
        dest, tile_expert, n_tiles, counts = _ops.group_rows(expert, E, tm)
        rows = _ops.padded_rows(pair.shape[0], E, tm)
        # the layout's row r holds pair src[r] (row src[r] // k of x), or
        # zeros; one spare row takes the pairs that go nowhere
        src = jnp.full((rows + 1,), n_pairs, jnp.int32).at[dest].set(
            pair)[:rows]
        xs = jnp.where((src < n_pairs)[:, None],
                       xt[jnp.minimum(src, n_pairs - 1) // k], 0)
    with jax.named_scope("strom.moe.experts"):
        h = _ops.gmm(xs, (_tr.wmat(p, prefix + "moe_w_gate", xt.dtype),
                          _tr.wmat(p, prefix + "moe_w_up", xt.dtype)),
                     tile_expert, n_tiles, tm=tm)
        y = _ops.gmm(h, (_tr.wmat(p, prefix + "moe_w_down", xt.dtype),),
                     tile_expert, n_tiles, tm=tm)
    return y, dest, counts, n_tiles


def _all_pairs(xt, sel, w, p: dict, prefix: str, cfg, tm: int) -> tuple:
    """The routed part of ``expert_mlp`` in one layout of all the call's
    pairs.  Returns (out (T, d) float32, counts, rows the grouped product
    ran, rounds: one)."""
    T, k = sel.shape
    y, dest, counts, n_tiles = _layout(
        xt, jnp.arange(T * k, dtype=jnp.int32), sel.reshape(T * k), k, p,
        prefix, cfg, tm)
    with jax.named_scope("strom.moe.route"):
        dest = dest.reshape(T, k)
        live = dest < y.shape[0]
        picked = y[jnp.minimum(dest, y.shape[0] - 1)].astype(jnp.float32)
        out = jnp.sum(jnp.where(live[..., None], w[..., None] * picked, 0.0),
                      axis=1)
    return out, counts, n_tiles * tm, 1


def _local_pairs(xt, sel, w, p: dict, prefix: str, cfg, tm: int,
                 bound: int) -> tuple:
    """The routed part of ``expert_mlp`` where few of the call's pairs fall
    on the experts held here: the local pairs are numbered by a running
    count and computed ``bound`` at a time, every round in one layout of
    ``bound`` pairs.  A round more is all a hot share costs: no pair is
    dropped.  Only int32 arrays (and the pairs' float32 weights) are as
    long as the call's pairs; nothing is scattered but int32.  Returns
    (out (T, d) in xt's dtype — the float32 sums, rounded —, counts, rows
    the grouped product ran, rounds run: at least one)."""
    T, k = sel.shape
    n_pairs, held = T * k, cfg.experts_local
    with jax.named_scope("strom.moe.route"):
        expert, weight = sel.reshape(n_pairs), w.reshape(n_pairs)
        local = expert < held
        seen = jnp.cumsum(local, dtype=jnp.int32)
        # per row: the local pairs before it, and its own
        first = (seen - local).reshape(T, k)[:, 0]
        mine = jnp.sum(local.reshape(T, k), axis=1)
        # order[j]: the j-th local pair, n_pairs past the last; long enough
        # for a window to start at any of them
        slots = n_pairs + bound + 1
        order = jnp.full((slots,), n_pairs, jnp.int32).at[
            jnp.where(local, seen - 1, slots)].set(
            jnp.arange(n_pairs, dtype=jnp.int32), mode="drop")
        ahead = jnp.arange(bound, dtype=jnp.int32)

    def one_round(carry):
        out, counts, tiles, start, rounds = carry
        with jax.named_scope("strom.moe.route"):
            window = jax.lax.dynamic_slice(order, (start,), (bound + 1,))
            pair, beyond = window[:bound], window[bound]
            # a row's pairs are ONE round's (its sum is made once, in
            # float32): those of a row that goes on past the window wait
            there = (pair < n_pairs) & (pair // k != beyond // k)
            at = jnp.minimum(pair, n_pairs - 1)
        y, dest, c, n_tiles = _layout(
            xt, jnp.where(there, pair, n_pairs),
            jnp.where(there, expert[at], held), k, p, prefix, cfg, tm)
        with jax.named_scope("strom.moe.route"):
            picked = y[jnp.minimum(dest, y.shape[0] - 1)]
            row = jnp.where(there, pair // k, T)
            scale = jnp.where(there, weight[at], 0.0)
            # a row's pairs are neighbours in the order: each row's first
            # one sums the row's, at most k of them, in float32
            total = jnp.zeros(picked.shape, jnp.float32)
            for j in range(k):
                same = there & (jnp.roll(row, -j) == row) & (ahead < bound - j)
                total = total + jnp.where(
                    same[:, None], jnp.roll(scale, -j)[:, None]
                    * jnp.roll(picked, -j, axis=0).astype(jnp.float32), 0.0)
            taken = jnp.sum(there, dtype=jnp.int32)
            here = (mine > 0) & (first >= start) & (first < start + taken)
            out = jnp.where(
                here[:, None],
                total.astype(out.dtype)[jnp.clip(first - start, 0, bound - 1)],
                out)
        return out, counts + c, tiles + n_tiles, start + taken, rounds + 1

    # the first round is every call's and stands in the open, where XLA
    # schedules it with its neighbours; the loop is the overflow's alone
    out, counts, tiles, _, rounds = jax.lax.while_loop(
        lambda carry: carry[3] < seen[-1], one_round,
        one_round((jnp.zeros(xt.shape, xt.dtype),
                   jnp.zeros((held,), jnp.int32), jnp.int32(0), jnp.int32(0),
                   jnp.int32(0))))
    return out, counts, tiles * tm, rounds


def expert_mlp(x: jax.Array, p: dict, prefix: str, cfg, valid=None) -> tuple:
    """The exact expert layer.  x (b, s, d) -> (out (b, s, d), counts
    (experts held,) int32 — pairs that fell on each expert held here —,
    work (2,) int32: rows the grouped product ran, tile padding included,
    and the rounds it took).

    ``valid`` (b, s) bool or None: rows that are not valid (right padding
    of a prefill, a free serving slot) are routed nowhere — they cost no
    expert a row, count in no histogram, and come out as zeros.

    The router scores all ``n_experts``; a device that holds a share of
    them (``cfg.experts_held`` from ``cfg.expert_offset``) computes the
    pairs that fall on its own and sends the others nowhere, as it does pad
    rows: their part of the sum is another device's.  Its layout is sized
    by that share (``pair_bound``), and a call whose local pairs exceed it
    takes further rounds.  The weights are normalised over all the selected
    experts, held or not.  A shared expert (``cfg.d_shared``) takes every
    row beside the routed ones, under its own sigmoid gate where the config
    has one (``cfg.shared_gate``)."""
    from nvme_strom_tpu.ops import moe as _ops
    b, s, d = x.shape
    T, k, held = b * s, cfg.expert_top_k, cfg.experts_local
    xt = x.reshape(T, d)
    tm = _ops.tile_rows(T * k, cfg.n_experts)
    bound = pair_bound(T * k, cfg)
    with jax.named_scope("strom.moe.route"):
        sel, w = route(xt, p, prefix, cfg)
        if held != cfg.n_experts:
            sel = sel - cfg.expert_offset
            sel = jnp.where((sel >= 0) & (sel < held), sel, held)
        if valid is not None:
            sel = jnp.where(valid.reshape(T, 1), sel, held)
    if bound == T * k:
        out, counts, rows, rounds = _all_pairs(xt, sel, w, p, prefix, cfg, tm)
    else:
        out, counts, rows, rounds = _local_pairs(xt, sel, w, p, prefix, cfg,
                                                 tm, bound)
    out = out.astype(x.dtype).reshape(b, s, d)
    if cfg.d_shared:
        with jax.named_scope("strom.moe.shared"):
            shared = _tr.mlp(x, p, prefix + "shared_")
            if cfg.shared_gate:
                # one scalar a row: sigmoid(x . w), float32
                gate = jax.nn.sigmoid(jnp.einsum(
                    "bsd,de->bse", x, _tr.wmat(p, prefix + "shared_gate",
                                               x.dtype),
                    preferred_element_type=jnp.float32))
                shared = (shared.astype(jnp.float32) * gate).astype(x.dtype)
            out = out + shared
    return out, counts, jnp.stack([rows, rounds]).astype(jnp.int32)


#: columns of ``load_counters``' "sums": per expert layer, summed over calls
SUMS = ("experts_touched", "rows_computed", "load_max", "rounds")


def load_counters(cfg) -> dict:
    """Zeroed device counters of the exact expert layers: ``load``
    (expert layers, experts held) int32, pairs that fell on each expert
    held here, and ``sums``
    (expert layers, 4) int32: experts touched, rows computed, the busiest
    expert's load and the rounds run (``expert_mlp``: one a call unless a
    call's local pairs overflowed its layout), each summed over the
    calls."""
    n = len(cfg.expert_layers)
    return {"load": jnp.zeros((n, cfg.experts_local), jnp.int32),
            "sums": jnp.zeros((n, len(SUMS)), jnp.int32)}


def add_load(counters: dict, calls: list) -> dict:
    """``counters`` plus one call of every expert layer: ``calls`` is
    [(counts (E,), work (2,): rows computed, rounds)] in layer order."""
    load = jnp.stack([c for c, _ in calls])
    sums = jnp.stack([jnp.stack([jnp.sum(c > 0), work[0], jnp.max(c),
                                 work[1]])
                      for c, work in calls])
    return {"load": counters["load"] + load,
            "sums": counters["sums"] + sums.astype(jnp.int32)}


def init_moe_params(keys, cfg, prefix: str, dense) -> dict:
    """MoE weights for one layer.  ``keys`` is an iterator of PRNG keys;
    ``dense`` is the caller's initializer (transformer.dense_init — passed
    in rather than imported to keep moe.py import-cycle-free)."""
    E, dm, ff = cfg.n_experts, cfg.d_model, cfg.expert_width
    held = cfg.experts_local        # the router is whole, the experts a share
    out = {
        prefix + "router": dense(next(keys), dm, (dm, E)),
        prefix + "moe_w_gate": dense(next(keys), dm, (held, dm, ff)),
        prefix + "moe_w_up": dense(next(keys), dm, (held, dm, ff)),
        prefix + "moe_w_down": dense(next(keys), ff, (held, ff, dm)),
    }
    if cfg.router_bias:
        out[prefix + "router_bias"] = jnp.zeros((E,), jnp.float32)
    if cfg.d_shared:
        ds = cfg.d_shared
        out.update({
            prefix + "shared_w_gate": dense(next(keys), dm, (dm, ds)),
            prefix + "shared_w_up": dense(next(keys), dm, (dm, ds)),
            prefix + "shared_w_down": dense(next(keys), ds, (ds, dm))})
        if cfg.shared_gate:
            out[prefix + "shared_gate"] = dense(next(keys), dm, (dm, 1))
    return out


def moe_param_specs(cfg, layer_prefix: str) -> dict:
    """PartitionSpecs for one MoE layer: experts over ``ep``, each expert's
    FFN Megatron-split over ``tp`` (column-parallel gate/up, row-parallel
    down — the psum over tp is inserted by the partitioner)."""
    from jax.sharding import PartitionSpec as P
    L = layer_prefix
    return {
        L + "router": P(),
        L + "moe_w_gate": P("ep", None, "tp"),
        L + "moe_w_up": P("ep", None, "tp"),
        L + "moe_w_down": P("ep", "tp", None),
    }

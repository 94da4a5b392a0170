"""Continuous batching: one decode server over a paged K/V pool.

Serving completes the inference stack the way PG-Strom completes the
reference's storage stack (SURVEY.md §3.5 — the consumer that turns a
data path into a product).  Requests arrive at arbitrary times with
arbitrary prompt lengths; the server packs them into a fixed number of
slots, admits new work the moment a slot frees and the pool has the
request's blocks, and every decode step advances EVERY active slot — no
head-of-line blocking on the longest request.

TPU-first shape: the batch step is one jitted program with static
shapes (``_paged_step``).  Per-slot sequence positions are data (a
``(B,)`` vector), not shapes: cache writes go to per-row (block, offset)
targets, attention masks by ``pos[b]``, RoPE takes per-row positions
(transformer._rope's 2-D form).  One compiled step program serves every
mix of request states.  The step moves nothing of the K/V pool's size:
its new rows go into the donated pool where they lie and the kernel reads
the 5-D pool by layer index (ops/paged_attention.py ``write_rows`` /
``paged_attention``).

Admission is one compiled program too (``_paged_prefill``), and an
admission is a GROUP: the prompts one ``step_many`` call admits that may
share a program (``models/admission.form_groups``: while the group's rows
stay under the rows at which a prefill's arithmetic outlasts its weight
read) go through one call, each weight read once for all of them; a single
request is a group of one.  The program: the cached prefix gathered into a
dense cache, ``decode.block_step`` over the right-padded suffixes, and each
row's new KV rows written into its own blocks of the donated pool.  It is
keyed on shapes alone — (width, padded suffix length, dense cache length),
the lengths multiples of ``block_len``, the width what the length's rows
allow (``admission.width_for``: one program a length).  Each row's true
last position, slot and block ids are data, so prompts of 97 and 128
tokens share one program, and a row that holds no prompt writes to the
trash block and the sacrificial state row;
``timings["prefill_programs"]`` counts the shapes a server has used.
The call returns at dispatch: the logits stay on the device.

A config may keep more than K/V pages per sequence, and the same two
programs carry it, donated (``init_carried``): the recurrent layers' state
rows, the expert layers' load counters, and for WINDOW layers (a row sees
its last ``window`` rows only) a ring of ``ring_blocks`` blocks per slot —
written whole by the slot's prefill from the prompt's last rows, in place by
the step, never a block of the pool: the allocator hands out the full
layers' pages alone.

Per-request decoding params: ``max_new``, ``eos_id``, and sampling —
``temperature``/``top_p``/``seed`` are per-SLOT vectors (data, like the
positions), so one compiled step serves any greedy/sampled mix.
Greedy requests (the default) are token-identical to running each
alone through ``decode.generate`` (the equivalence test in
tests/test_serving.py); sampled requests are reproducible per
(seed, position).
"""

from __future__ import annotations

import functools
import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from nvme_strom_tpu.io.tenants import (
    TokenBucket, tenant_context, tenants_enabled, tier_rank)
from nvme_strom_tpu.models import admission as _adm
from nvme_strom_tpu.models import decode as _dec
from nvme_strom_tpu.models import mla as _mla
from nvme_strom_tpu.models import moe as _moe
from nvme_strom_tpu.models import ssm as _ssm
from nvme_strom_tpu.models.decode import _mlp_block
from nvme_strom_tpu.models.transformer import (
    TransformerConfig, add_residual, embed_tokens, lm_logits,
    gate_heads, norm_in, norm_out, qkvg_project, rms_norm, wmat)


@dataclass
class _Request:
    rid: object
    prompt: List[int]
    max_new: int
    eos_id: Optional[int]
    temperature: float = 0.0      # 0 = greedy
    top_p: float = 1.0
    seed: int = 0
    out: List[int] = field(default_factory=list)
    # a diffusion server: the denoising step each token of ``out`` was
    # committed at (handed on at retirement, ``request_metrics``)
    steps: List[int] = field(default_factory=list)
    chain_keys: object = None     # paged prefix-cache memo
    store_keys: object = None     # NVMe prefix-store memo (may differ:
    #                               store page size vs HBM block size)
    # serving-SLO timeline (docs/PERF.md §5): queued, admitted, first
    # token DELIVERED (the host readback — the moment a client could
    # see it); stats() aggregates TTFT and admission wait from these
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: Optional[float] = None
    # request-scoped causal trace (utils/trace.py TraceContext,
    # docs/OBSERVABILITY.md): the ROOT of this request's span tree,
    # created at submit when tracing is on — admission, KV restore,
    # scheduler queue wait, cache hit/fill, and engine I/O all
    # correlate under its trace_id
    trace: object = None
    t_submit_ns: int = 0
    # resolved io/tenants.Tenant — None while STROM_TENANTS=0 (every
    # tenant branch below short-circuits to the pre-tenant path)
    tenant: object = None


@jax.jit
def _sample_slots(logits, temps, top_ps, seeds, pos):
    """Per-slot temperature/top-p sampling, all quantities DATA so one
    compiled program serves any mix of greedy and sampled requests
    (the per-slot-position trick applied to decoding params).

    Jitted at this level because an eager call re-traces the
    ``lax.cond``'s branches every time (~175 ms per admission on a CPU run
    when admission still called it so); inside the jitted programs
    (``_paged_step``, ``_admit_slots``) the wrapper is inlined and changes
    nothing.

    logits (B, V) f32; temps/top_ps (B,) f32; seeds (B,) uint32 (per
    request, from submit); pos (B,) int32 — the step index folds into
    the key so each step draws fresh randomness, reproducibly per
    (seed, position).  Rows with temperature <= 0 take argmax exactly
    (bit-identical to the greedy server)."""
    greedy = jnp.argmax(logits, -1).astype(jnp.int32)

    def sample(_):
        scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
        masked = _dec.nucleus_truncate(scaled, top_ps)

        def one(seed, p, row):
            key = jax.random.fold_in(
                jax.random.PRNGKey(seed.astype(jnp.uint32)), p)
            return jax.random.categorical(key, row)

        sampled = jax.vmap(one)(seeds, pos, masked).astype(jnp.int32)
        return jnp.where(temps > 0, sampled, greedy)

    # all-greedy batches (the default) skip the whole sort/softmax/
    # PRNG pipeline — one compiled program either way, lax.cond picks
    # the branch from the live slot params
    return jax.lax.cond(jnp.any(temps > 0), sample, lambda _: greedy,
                        None)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _scatter_blocks(k_pool, v_pool, blks, k_rows, v_rows):
    """KV rows (L, n, nkv, bk, hd) into pool blocks ``blks`` (n,): the
    tail of ``_paged_prefill``, and on its own (one donated program, no
    per-block pool copies) for pages restored from the store.  A latent
    pool is the one array ``k_pool`` (L, blocks, width, bk) beside no
    ``v_pool``; its rows come as blocks of it, (L, n, width, bk)."""
    k_pool = k_pool.at[:, blks].set(k_rows.astype(k_pool.dtype))
    if v_pool is not None:
        v_pool = v_pool.at[:, blks].set(v_rows.astype(v_pool.dtype))
    return k_pool, v_pool


@jax.jit
def _gather_prefix(k_pool, v_pool, blks):
    """Pool blocks ``blks`` (b, c) → a dense (L, b, nkv, c * bk, hd) cache
    pair: the cached prefixes at the head of a group's prefill (one gather
    per program — prefix caching trades this HBM read for the prefix's
    quadratic prefill compute), and the pages ``_store_put`` pulls.  A
    latent pool (no ``v_pool``) gives (L, b, 1, c * bk, width) and None."""
    if v_pool is None:
        rows = k_pool[:, blks]                 # (L, b, c, width, bk)
        L, b, c, width, bk = rows.shape
        return rows.transpose(0, 1, 2, 4, 3).reshape(L, b, 1, c * bk,
                                                     width), None

    def to_dense(pool):
        rows = pool[:, blks]                   # (L, b, c, nkv, bk, hd)
        L, b, c, nkv, bk, hd = rows.shape
        return rows.transpose(0, 1, 3, 2, 4, 5).reshape(L, b, nkv, c * bk,
                                                        hd)
    return to_dense(k_pool), to_dense(v_pool)


def _rids(group: list) -> str:
    """The request ids of a group's plans as ONE span argument (the
    profiler's annotation takes scalars, and cuts at a ",")."""
    return " ".join(str(plan["req"].rid) for plan in group)


def _prefill_rows(params: Dict, cfg: TransformerConfig, tokens,
                  k_head, v_head, last, ssm=None, moe=None, ring_rows=0):
    """The admission prefill, traced inside ``_paged_prefill``:
    ``block_step`` of the right-padded suffixes ``tokens`` (b, m) behind
    the cached prefixes ``k_head``/``v_head`` ((L, b, nkv, c, hd), or None
    when nothing is cached — block_step at pos 0 IS the dense prefill,
    so every admission shares one math).

    Returns (logits (b, vocab) f32 at each row's own suffix row ``last``
    (b,), k, v dense (L, b, nkv, c + m, hd), for a config with recurrent
    layers their state after each row's ``last`` — else None —, the
    expert layers' load counters ``moe`` with the group's valid rows added,
    and what the window layers' rings keep of their K and V (Lw, b, nkv_w,
    ring rows, hd | vd) (``decode.ring_rows``), None without such layers).
    The pad rows sit past ``last``: causality keeps them out of the logits,
    and their cache entries are dead — decode overwrites a position before
    its mask exposes it.  A recurrence has no mask to hide behind:
    ``n_valid`` tells it where each prompt ends (``last`` -1: a row that
    holds no prompt, routed nowhere, its state left empty)."""
    b, m = tokens.shape
    with jax.named_scope("strom.prefill.gather"):
        cache = _dec.init_cache(cfg, b, m)
        if cfg.window_layers:
            # no prefix (``_req_keys``): the window layers hand back their
            # rings' rows and keep no dense cache over the prompt
            assert k_head is None
            del cache["wk"], cache["wv"]
            cache["ring_rows"] = ring_rows
        if ssm is not None:
            cache["ssm"] = ssm
        if moe is not None:
            cache["moe"] = moe
        if k_head is not None:
            cache["k"] = jnp.concatenate(
                [k_head.astype(cfg.dtype), cache["k"]], axis=3)
            if v_head is not None:
                cache["v"] = jnp.concatenate(
                    [v_head.astype(cfg.dtype), cache["v"]], axis=3)
            cache["pos"] = jnp.asarray(k_head.shape[3], jnp.int32)
        n_valid = last + 1
    logits, cache = _dec.block_step(params, tokens, cfg, cache, last=last,
                                    n_valid=n_valid)
    return (logits, cache["k"], cache["v"], cache.get("ssm"),
            cache.get("moe"), (cache.get("wk"), cache.get("wv")))


def ring_blocks(cfg: TransformerConfig, block_len: int) -> int:
    """Blocks of ``block_len`` rows in a window layer's ring: the rows
    ``pos - window + 1 .. pos`` span at most ``ceil(window / block_len) + 1``
    blocks wherever ``pos`` lies in its own (two of 128 for a window of
    128).  Row ``p`` of a sequence lies in its ring's block ``(p //
    block_len) % ring_blocks`` at offset ``p % block_len``."""
    return -(-cfg.window // block_len) + 1 if cfg.window_layers else 0


def prefill_program(width: int, suffix: int, cache: int) -> str:
    """The name of one compiled shape of ``_paged_prefill``: prompts in the
    group x suffix rows x rows of cache each attends.  The host span
    ``strom.serve.prefill`` carries it as ``program=`` and every device
    operation of that shape lies under the scope ``strom.prefill.<name>``,
    so a slow span and its device time are found by one string."""
    return f"{width}x{suffix}x{cache}"


@functools.partial(jax.jit, static_argnums=(1,), donate_argnums=(2, 3, 7))
def _paged_prefill(params: Dict, cfg: TransformerConfig, k_pool, v_pool,
                   tokens, blks, last, state=None, slot=None):
    """One admission, a group of b prompts: ``blks`` (b, n) are each row's
    pool blocks over the group's longest padded prompt, ``tokens`` (b, m)
    the suffixes past the cached blocks — so the first ``n - m //
    block_len`` of every row's ``blks`` are gathered as its prefix
    (``_gather_prefix``), ``_prefill_rows`` runs the suffixes, and each
    row's rows land in its remaining blocks of the donated pools.  A shorter
    prompt has no block for the rows past its own padded length: its table
    names the trash block there (the pool's last), as a free slot's write
    does in the step; a row that holds no prompt names nothing else.  Block
    ids and ``last`` (b,) are data: the program is keyed on (b, m, n) only.

    With recurrent or expert layers in ``cfg``, ``state`` is what the
    server's programs carry beside the K/V pools (``init_carried``,
    donated) and ``slot`` (b,) the admitted slots' rows of its pools: every
    prompt starts from an empty state (there is no prefix to resume: a page
    without the state at its boundary is not one) and the state after its
    row ``last`` overwrites its slot's row — which is why releasing a slot
    clears nothing; a row that holds no prompt names the sacrificial last
    row.  The expert layers' ``"prefill"`` counters take the group's valid
    rows.  Returns (logits (b, vocab), k_pool, v_pool, state); ``state``
    stays None for a plain decoder."""
    bk = k_pool.shape[3]
    b, m = tokens.shape
    ct = blks.shape[1] - m // bk
    # this compiled shape's label, over every operation of it
    with jax.named_scope(
            "strom.prefill." + prefill_program(b, m, blks.shape[1] * bk)):
        k_head = v_head = None
        with jax.named_scope("strom.prefill.gather"):
            if ct:
                k_head, v_head = _gather_prefix(k_pool, v_pool, blks[:, :ct])
            ssm0 = _ssm.init_state(cfg, b) if cfg.recurrent_layers else None
        moe = state.get("moe") if state else None
        ring = ring_blocks(cfg, bk)
        logits, k, v, ssm, load, wkv = _prefill_rows(
            params, cfg, tokens, k_head, v_head, last, ssm0,
            moe and moe["prefill"], ring * bk)

        def new_rows(dense):               # → (L, b * (n - ct), nkv, bk, hd)
            if dense is None:
                return None
            L, _, nkv, _, hd = dense.shape
            if cfg.latent:                 # → (L, b * (n - ct), width, bk)
                return (dense[:, :, 0, ct * bk:].reshape(L, -1, bk, hd)
                        .transpose(0, 1, 3, 2))
            return (dense[:, :, :, ct * bk:].reshape(L, b, nkv, -1, bk, hd)
                    .transpose(0, 1, 3, 2, 4, 5).reshape(L, -1, nkv, bk, hd))

        with jax.named_scope("strom.prefill.scatter"):
            if state is not None:
                state = dict(state, **{key: tuple(
                    pool.at[slot].set(new.astype(pool.dtype))
                    for pool, new in zip(state[key], ssm[key]))
                    for key in ssm or ()})
                if moe:
                    state["moe"] = dict(moe, prefill=load)
                if cfg.window_layers:
                    # a window layer keeps the prompt's LAST rows only, in
                    # the admitted slot's ring (never a block of the pool)
                    ids = (slot[:, None] * ring
                           + jnp.arange(ring)).reshape(-1)
                    for key, rows in zip(("wk", "wv"), wkv):
                        Lw, _, nkv, _, d = rows.shape   # -> blocks of rings
                        state[key] = state[key].at[:, ids].set(
                            rows.reshape(Lw, b, nkv, ring, bk, d)
                            .transpose(0, 1, 3, 2, 4, 5)
                            .reshape(Lw, b * ring, nkv, bk, d))
            k_pool, v_pool = _scatter_blocks(
                k_pool, v_pool, blks[:, ct:].reshape(-1), new_rows(k),
                new_rows(v))
    return logits, k_pool, v_pool, state


@jax.jit
def _admit_slots(logits, temp, topp, seed, pos, tok, slots, temps, top_ps,
                 seeds, lens):
    """The tail of a group's admission, one program: the b first tokens
    sampled from ``logits`` (b, vocab) under each request's own params
    (position ``lens - 1`` folds in, so the first draw differs from the
    next step's), and the server's per-slot arrays with the group's rows
    set at ``slots`` (b,) — ``pos`` the prompt's length (nothing decoded
    past it yet), ``tok`` the token entering the cache on the next step.  A
    row that holds no prompt names a slot past the last: dropped.  Returns
    (the b first tokens, each a device scalar, temp, topp, seed, pos, tok)."""
    def put(rows, values):
        return rows.at[slots].set(values.astype(rows.dtype), mode="drop")

    with jax.named_scope("strom.head"):
        first = _sample_slots(logits, temps, top_ps, seeds, lens - 1)
        return (tuple(first[i] for i in range(first.shape[0])),
                put(temp, temps), put(topp, top_ps), put(seed, seeds),
                put(pos, lens), put(tok, first))


def paged_logits(params: Dict, cfg: TransformerConfig, tok,
                 k_pool, v_pool, blk, off, table, pos, state=None,
                 sidx=None):
    """The decode step of every slot at its OWN position against the
    shared block pool, before sampling: (logits (B, vocab) f32, k_pool,
    v_pool, state) — ``state`` the recurrent layers' pool after the step,
    None for a plain decoder.

    ``tok`` (B,) is one row a slot at ``pos``.  ``tok`` (B, 2 R) is two
    diffusion blocks a slot (``cfg.diffusion_block`` R; ``bd_rows``): the
    block the slot has just finished, clean, at ``pos - R .. pos - 1`` and
    its current block at ``pos .. pos + R - 1``.  Each half's K and V go
    where they will lie — ``blk`` / ``off`` are then (B, 2) and name each
    half's FIRST row's place (a block never straddles a pool block, the two
    may lie in two) —, every row sees the history up to the end of its OWN
    block (``paged_attention``'s ``lag``), and the logits are the current
    block's: (B, R, vocab).  A half aimed at the trash block is dead — routed
    to no expert, its output unread: the first where the slot owes no
    finished block, both where it holds position or is free.

    blk/off (B,) int32: each slot's write target (block id in the pool,
    row offset inside it); table (B, max_blocks) int32 + pos (B,) feed
    the paged-attention kernel.  state/sidx: the recurrent layers' pool
    (``models/ssm.init_state``) and each slot's row of it — a free slot's
    is the sacrificial last row, as its ``blk`` is the trash block (the
    pool's last).  An expert layer routes the live slots only (a free
    slot is told by its trash block) and ``state["moe"]["decode"]`` takes
    their load."""
    from nvme_strom_tpu.ops.mla_attention import (latent_write,
                                                  mla_attention)
    from nvme_strom_tpu.ops.paged_attention import (paged_attention,
                                                    write_rows)
    B = tok.shape[0]
    blocks = tok.ndim == 2              # two blocks of R rows a slot
    rows = tok.shape[1] if blocks else 1
    R = rows // 2
    bk = k_pool.shape[-1] if cfg.latent else k_pool.shape[3]
    ring = ring_blocks(cfg, bk)
    with jax.named_scope("strom.embed"):
        dead = blk == k_pool.shape[1] - 1       # (B,), or (B, 2) a half
        free = dead[:, 1] if blocks else dead
        # a free slot keeps its last pos over a table row of zeros; nobody
        # reads its output, so to the kernel its history is one row
        attn_pos = jnp.where(free, 0, pos)
        live = ~free[:, None] if cfg.expert_layers else None
        if not blocks:
            x = embed_tokens(params, cfg, tok[:, None])       # (B,1,d)
            positions = pos.astype(jnp.float32)[:, None]      # (B,1)
        else:
            # every row of a block sees its whole block: the current one
            # up to pos + R - 1, the finished one R short of that
            attn_pos = jnp.where(free, 0, pos + R - 1)
            if cfg.expert_layers:
                live = jnp.repeat(~dead, R, axis=1)           # (B,2R)
            x = embed_tokens(params, cfg, tok)                # (B,2R,d)
            positions = (pos[:, None] - R
                         + jnp.arange(rows)).astype(jnp.float32)  # (B,2R)
        if ring:
            # a window layer's cache: the slot's own ring of ``ring`` blocks
            # (row sidx of the rings; a free slot's is the sacrificial last),
            # written at the block the position falls in
            wk_pool, wv_pool = state["wk"], state["wv"]
            ring_table = sidx[:, None] * ring + jnp.arange(ring)
            ring_blk = sidx * ring + (pos // bk) % ring
    s_pools, tails = ((list(state["s"]), list(state["conv"]))
                      if cfg.recurrent_layers else ([], []))
    calls = []              # the expert layers' (counts, work)
    # this layer's place among its kind's caches: attention pages, a
    # recurrent layer's state matrix (mamba, gdn), its conv tail (mamba,
    # gdn, conv), a window layer's ring
    ai = mi = ti = wi = 0
    # every operation under one family of scopes, the same partition as
    # the prefill's (``decode.MIXER_SCOPES``, docs/OBSERVABILITY.md)
    for i in range(cfg.n_layers):
        L = f"layers.{i}."
        before, after = _dec.MIXER_SCOPES[cfg.mixer(i)]
        with jax.named_scope(before):
            h = norm_in(x, params[L + "attn_norm"], cfg)
        if cfg.is_mamba_layer(i):
            a, s_pools[mi], tails[ti] = _ssm.mamba_step(
                h, params, L, cfg, s_pools[mi], tails[ti], sidx)
            mi += 1
            ti += 1
        elif cfg.mixer(i) == "conv":
            a, tails[ti] = _ssm.conv_step(h, params, L, cfg, tails[ti], sidx)
            ti += 1
        elif cfg.mixer(i) == "gdn":
            a, s_pools[mi], tails[ti] = _ssm.gdn_step(
                h, params, L, cfg, s_pools[mi], tails[ti], sidx)
            mi += 1
            ti += 1
        elif cfg.latent:
            with jax.named_scope("strom.attn.mla"):
                # the absorbed form: the step's latent row goes into the
                # (donated) pool where it lies, and every head attends
                # over the pool's rows themselves (models/mla.py)
                q, rows = _mla.project(h, params, L, cfg, positions)
                k_pool = latent_write(k_pool, rows[:, 0], blk, off, layer=ai)
                a = mla_attention(
                    _mla.absorb_q(q[:, 0], params, L, cfg), k_pool, table,
                    attn_pos, layer=ai, dc=cfg.kv_lora_rank)
                a = _mla.unabsorb(a, params, L, cfg)[:, None]
            with jax.named_scope(after):
                a = a @ wmat(params, L + "wo", a.dtype)
            ai += 1
        else:
            win = cfg.mixer(i) == "window"
            with jax.named_scope(before):
                q, k, v, g = qkvg_project(h, params, L, cfg,
                                          positions=positions)
            with jax.named_scope("strom.attn.window" if win
                                 else "strom.attn.paged"):
                # the new rows go into the (donated) pools where they lie
                # and the kernel reads layer ai of them in place: nothing
                # pool-sized moves.  A window layer's go into its slot's
                # ring, of which the kernel walks the blocks that hold the
                # last ``window`` rows: a lower bound beside the upper one
                if win:
                    wk_pool, wv_pool = write_rows(
                        wk_pool, wv_pool, k[:, :, 0], v[:, :, 0], ring_blk,
                        off, layer=wi, name="strom_window_write")
                    a = paged_attention(
                        q, wk_pool, wv_pool, ring_table, attn_pos, layer=wi,
                        scale=cfg.attn_scale, window=cfg.window,
                        sink=params.get(L + "sink"))
                elif blocks:
                    # a call a half: the finished block's tile and the
                    # current one's may be one, and a grid step patches
                    # the tile as it was before the call
                    for j in range(2):
                        k_pool, v_pool = write_rows(
                            k_pool, v_pool, k[:, :, j * R:(j + 1) * R],
                            v[:, :, j * R:(j + 1) * R], blk[:, j],
                            off[:, j], layer=ai)
                    a = paged_attention(q, k_pool, v_pool, table, attn_pos,
                                        layer=ai, scale=cfg.attn_scale,
                                        lag=R)
                else:
                    k_pool, v_pool = write_rows(
                        k_pool, v_pool, k[:, :, 0], v[:, :, 0], blk, off,
                        layer=ai)
                    a = paged_attention(q, k_pool, v_pool, table, attn_pos,
                                        layer=ai, scale=cfg.attn_scale)
            with jax.named_scope(after):
                a = gate_heads(a.transpose(0, 2, 1, 3).reshape(B, rows, -1),
                               g)
                a = a @ wmat(params, L + "wo", a.dtype)
            wi, ai = wi + win, ai + (not win)
        with jax.named_scope(after):
            x = add_residual(x, norm_out(a, params[L + "attn_norm"], cfg),
                             cfg)
        with jax.named_scope("strom.mlp"):
            h = norm_in(x, params[L + "mlp_norm"], cfg)
            f = _mlp_block(h, params, L, cfg, live, calls)
            x = add_residual(x, norm_out(f, params[L + "mlp_norm"], cfg),
                             cfg).astype(cfg.dtype)
    with jax.named_scope("strom.head"):
        # (the finished block's rows were forwarded for their K/V alone)
        x = rms_norm(x[:, R:] if blocks else x[:, 0], params["final_norm"],
                     cfg.norm_eps)
    if state is not None:
        state = dict(state, s=tuple(s_pools), conv=tuple(tails))
        if ring:
            state.update(wk=wk_pool, wv=wv_pool)
        if calls:
            with jax.named_scope("strom.mlp"):
                state["moe"] = dict(state["moe"], decode=_moe.add_load(
                    state["moe"]["decode"], calls))
    with jax.named_scope("strom.head"):
        logits = lm_logits(params, cfg, x)
    return logits, k_pool, v_pool, state


def init_carried(cfg: TransformerConfig, rows: int, block_len: int = 128):
    """What the server's two programs carry on the device beside the K/V
    pools, donated and updated in place: the recurrent layers' pools of
    ``rows`` rows (``models/ssm.init_state``: ``"s"``, ``"conv"``), for
    a config with expert layers their load counters ``"moe"``
    (``models/moe.load_counters``), ``"decode"`` and ``"prefill"`` apart,
    and for one with window layers their rings ``"wk"`` / ``"wv"``: (window
    layers, rows x ``ring_blocks``, window KV heads, block_len, head width)
    — row r's ring is blocks ``r * ring_blocks ..``, shaped as a pool so
    that the pool's row writer and kernel serve it.  A per-slot array and
    not entries of the block table: a ring's blocks never change hands, so
    there is nothing for an allocator to decide, and a request's 136 table
    entries would say the same two ids 68 times over.
    None for a plain decoder."""
    if not (cfg.recurrent_layers or cfg.expert_layers or cfg.window_layers):
        return None
    state = _ssm.init_state(cfg, rows)
    if cfg.expert_layers:
        state["moe"] = {"decode": _moe.load_counters(cfg),
                        "prefill": _moe.load_counters(cfg)}
    if cfg.window_layers:
        shape = (len(cfg.window_layers), rows * ring_blocks(cfg, block_len),
                 cfg.kv_heads(cfg.window_layers[0]), block_len)
        state["wk"] = jnp.zeros(shape + (cfg.head_dim,), cfg.dtype)
        state["wv"] = jnp.zeros(shape + (cfg.v_dim,), cfg.dtype)
    return state


#: the phase of a slot at one forward of a diffusion server, as the step
#: reports it (``bd_select``'s ``info``): holding position (free, or past its
#: last block) or denoising.  No forward only WRITES a finished block's K/V:
#: those rows ride the next block's first denoising forward (``info``'s
#: ``fused`` flag), and a request's last block is never written — nothing
#: reads it
BD_HOLD, BD_DENOISE = 0, 1
#: ``info``'s columns before the block's R tokens and R commit steps
BD_INFO = 5


def bd_state(cfg: TransformerConfig, slots: int) -> dict:
    """What a diffusion server's step carries a slot beside ``pos`` (the
    first position of the slot's current block) and ``tok`` (slots, Bl, the
    block's tokens): ``masked`` (slots, Bl) — which of them are still to be
    made; a FLAG, not the mask token's id, since an arg-max may be that id
    and a commit must stay one —, ``cstep`` (slots, Bl) the denoising step
    each was committed at (-1: given by the prompt), ``step`` the block's
    denoising forwards so far, ``n0`` its masked positions at its start,
    ``prev`` (slots, Bl) the block the slot finished last and ``pending``
    whether that block's clean K/V are still owed to the cache (an
    admission owes none: the prefill wrote the prompt's blocks), ``end`` the
    position the slot's last block ends before, and the request's rule:
    ``T`` denoising steps a block (0: one position at least a forward) and
    ``tau`` the confidence that commits by itself (inf: none does)."""
    Bl = cfg.diffusion_block
    return {"masked": jnp.zeros((slots, Bl), bool),
            "cstep": jnp.full((slots, Bl), -1, jnp.int32),
            "step": jnp.zeros((slots,), jnp.int32),
            "n0": jnp.zeros((slots,), jnp.int32),
            "prev": jnp.zeros((slots, Bl), jnp.int32),
            "pending": jnp.zeros((slots,), bool),
            "end": jnp.zeros((slots,), jnp.int32),
            "T": jnp.ones((slots,), jnp.int32),
            "tau": jnp.full((slots,), jnp.inf, jnp.float32)}


def bd_rows(cfg: TransformerConfig, tok, pos, bd: dict, hold, table,
            trash: int, bk: int):
    """What a diffusion step forwards, from the slots' state: (rows (B, 2 R)
    — the block each slot finished last, clean, then its current block with
    the mask token at its masked positions —, blk (B, 2), off (B, 2): where
    each half's K/V rows go).  The current block's go to the table's entry
    of ``pos``, the finished one's to that of ``pos - R`` — the same pool
    block or the one before — where they are still owed (``bd["pending"]``);
    a half that owes nothing, and both of a slot that holds position
    (``hold`` (B,) bool), go to the ``trash`` block."""
    R = tok.shape[1]
    at = jnp.stack([pos - R, pos], axis=1)                   # (B, 2)
    entry = jnp.clip(at // bk, 0, table.shape[1] - 1)
    dead = hold[:, None] | jnp.stack(
        [~bd["pending"], jnp.zeros_like(hold)], axis=1)
    blk = jnp.where(dead, trash, jnp.take_along_axis(table, entry, axis=1))
    rows = jnp.concatenate(
        [bd["prev"], jnp.where(bd["masked"], cfg.mask_token_id, tok)], axis=1)
    return rows, blk, at % bk


def bd_select(logits, tok, pos, bd: dict, hold):
    """The tail of a diffusion step: confidence, selection and every slot's
    block state after the forward.  logits (B, R, vocab) f32 of the slots'
    current blocks as they went in; ``hold`` (B,) bool the slots that did not
    take part.

    A slot that takes part DENOISES: at each masked position the candidate
    is the arg-max token and its confidence that token's softmax
    probability; the ``n`` most confident masked positions (ties to the
    lower position) take their candidates, and so does every one whose
    confidence passes ``tau`` — ``n`` is ``n0 // T`` and one more in the
    first ``n0 % T`` steps, or 1 where ``T`` is 0.  The forward that commits
    a block's last masked position FINISHES the block: the slot moves on to
    the next in that same forward, all masked, and keeps the finished block
    (``prev``) to forward it once more, clean, beside the next block's first
    denoising forward — which writes its K/V (``pending``; ``bd_rows``).

    Returns (info (B, BD_INFO + 2 R) int32 — ``pos`` before the forward, the
    phase (``BD_HOLD`` | ``BD_DENOISE``), the positions committed by it,
    whether it finished the block, whether its first R rows wrote the block
    before (``fused``), then the block's R tokens and their R commit steps
    after it —, tok, pos, bd after)."""
    B, R, _ = logits.shape
    masked, cstep, step = bd["masked"], bd["cstep"], bd["step"]
    with jax.named_scope("strom.bd.select"):
        best = jnp.max(logits, axis=-1)
        cand = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        conf = 1.0 / jnp.sum(jnp.exp(logits - best[..., None]), axis=-1)
        conf = jnp.where(masked, conf, -1.0)
        # rank among the slot's masked positions: more confident first,
        # then the lower position
        r = jnp.arange(R)
        ahead = (conf[:, None, :] > conf[:, :, None]) | (
            (conf[:, None, :] == conf[:, :, None])
            & (r[None, None, :] < r[None, :, None]))
        rank = jnp.sum(ahead & masked[:, None, :], axis=-1)
        T = jnp.maximum(bd["T"], 1)
        n = jnp.where(bd["T"] > 0,
                      bd["n0"] // T + (step < bd["n0"] % T), 1)
        denoise = ~hold
        commit = masked & denoise[:, None] & (
            (rank < n[:, None]) | (conf > bd["tau"][:, None]))
        tok = jnp.where(commit, cand, tok)
        cstep = jnp.where(commit, step[:, None], cstep)
        done = denoise & ~jnp.any(masked & ~commit, axis=-1)
        info = jnp.concatenate([
            pos[:, None], (denoise * BD_DENOISE)[:, None],
            jnp.sum(commit, axis=-1, dtype=jnp.int32)[:, None],
            done[:, None], (bd["pending"] & denoise)[:, None],
            tok, cstep], axis=1).astype(jnp.int32)
        d = done[:, None]
        bd = dict(bd, masked=(masked & ~commit) | d,
                  cstep=jnp.where(d, -1, cstep),
                  step=jnp.where(done, 0, step + denoise),
                  n0=jnp.where(done, R, bd["n0"]),
                  prev=jnp.where(d, tok, bd["prev"]),
                  # a slot that held wrote nothing: it owes what it owed
                  pending=jnp.where(hold, bd["pending"], done))
        return info, tok, jnp.where(done, pos + R, pos), bd


@functools.partial(jax.jit, static_argnums=(1,), donate_argnums=(3, 4, 12))
def _paged_step(params: Dict, cfg: TransformerConfig, tok,
                k_pool, v_pool, blk, off, table, pos, temps, top_ps,
                seeds, state=None, sidx=None, bd=None):
    """One decode step for every slot: ``paged_logits`` then the per-slot
    sampler.  tok, pos (B,) int32 → (next_tok (B,), k_pool, v_pool,
    state); ``state`` (donated, every layer's array updated in place) is
    None for a plain decoder.  Free slots compute too, but their writes
    land in the trash block (and the sacrificial state row) and the host
    ignores their outputs — one compiled program for every batch mix.

    With ``bd`` (``bd_state``; ``cfg.diffusion_block`` R) the same program
    forwards 2 R rows a slot (``bd_rows``) — ``tok`` (B, R) the slot's
    current block, ``pos`` its first position, before it the block the slot
    finished last, whose clean K/V rows this forward writes if they are
    still owed — and ends in ``bd_select`` over the current block's logits
    in place of the sampler.  What a slot does is data: it denoises, with
    or without a finished block's rows riding along, or holds position (a
    free slot, told by ``blk``, the trash block; a slot past its last block,
    ``pos >= bd["end"]``: both write to the trash block, are routed nowhere
    and change nothing), so that k sub-steps chain on the device without a
    readback.  The write targets are read off the table here (``blk`` says
    free or not, ``off`` is unread).
    Returns (info (B, BD_INFO + 2 R), k_pool, v_pool, state, tok, pos, bd)."""
    if bd is not None:
        with jax.named_scope("strom.embed"):
            trash, bk = k_pool.shape[1] - 1, k_pool.shape[3]
            hold = (blk == trash) | (pos >= bd["end"])
            rows, blk, off = bd_rows(cfg, tok, pos, bd, hold, table, trash,
                                     bk)
        logits, k_pool, v_pool, state = paged_logits(
            params, cfg, rows, k_pool, v_pool, blk, off, table, pos, state,
            sidx)
        info, tok, pos, bd = bd_select(logits, tok, pos, bd, hold)
        return info, k_pool, v_pool, state, tok, pos, bd
    logits, k_pool, v_pool, state = paged_logits(
        params, cfg, tok, k_pool, v_pool, blk, off, table, pos, state, sidx)
    with jax.named_scope("strom.head"):
        nxt = _sample_slots(logits, temps, top_ps, seeds, pos)
    return nxt, k_pool, v_pool, state


@jax.jit
def _admit_blocks(pos, tok, bd, slots, starts, toks, given, end, T, tau):
    """The tail of a diffusion group's admission, one program: the
    server's per-slot arrays with the group's rows set at ``slots`` (b,) —
    ``starts`` each prompt's first block that is not whole (where
    generation begins), ``toks`` (b, R) that block as the prompt gives it
    (its remainder, then anything), ``given`` how many of its rows that
    is, ``end`` / ``T`` / ``tau`` as ``bd_state`` has them.  A row that
    holds no prompt names a slot past the last: dropped.  An admission
    yields no token.  Returns (pos, tok, bd)."""
    def put(old, new):
        return old.at[slots].set(new.astype(old.dtype), mode="drop")

    R = toks.shape[1]
    return put(pos, starts), put(tok, toks), dict(
        bd,
        masked=put(bd["masked"], jnp.arange(R)[None, :] >= given[:, None]),
        cstep=put(bd["cstep"], jnp.full(toks.shape, -1)),
        step=put(bd["step"], jnp.zeros_like(given)),
        n0=put(bd["n0"], R - given),
        # the prefill wrote the prompt's whole blocks: nothing is owed
        pending=put(bd["pending"], jnp.zeros_like(given)),
        end=put(bd["end"], end), T=put(bd["T"], T), tau=put(bd["tau"], tau))


class DecodeServer:
    """Continuous batching over a SHARED block pool (paged attention).

    ``submit`` enqueues (optionally with per-request ``temperature``/
    ``top_p``/``seed`` — greedy by default); ``step`` admits waiting
    requests into free slots, advances every active slot one forward —
    one token, or, for a config that generates by diffusion over blocks
    (``cfg.diffusion_block`` R), the R rows of the slot's current block:
    0 to R tokens — and returns requests that finished this step
    ({request_id: token list}).  ``run`` drains everything.

    A diffusion config is served by the same two programs: the admission
    forwards a prompt's whole blocks under the block-causal mask and
    yields no token; a step forwards R rows a slot and commits the most
    confident masked positions — ``cfg.diffusion_steps`` denoising
    forwards a block under the static rule, every position whose
    confidence passes ``cfg.diffusion_threshold`` (and one at least)
    under the dynamic one, server-wide —, and the finished block rides
    the next block's first forward once more, clean, to write its K/V: 2 R
    rows a slot a forward (``_paged_step``, ``_step_blocks``).
    Greedy only; a finished request's ``request_metrics`` entry holds the
    denoising step each of its tokens was committed at
    (``"commit_steps"``, a byte a token).

    Capacity is ``total_blocks × block_len`` tokens across ALL slots —
    sized for expected live tokens, so short requests stop paying for the
    longest one's reservation.  Each request reserves its worst case
    (``ceil((prompt+max_new)/block)``) at admission, so an admitted
    request can never starve mid-decode; when the pool is exhausted,
    requests simply wait in the queue.  Both sizes have defaults the
    server works out: ``block_len`` is the store's page when a
    ``kv_store`` is given (pages scatter 1:1 into blocks), else 128;
    ``total_blocks`` is ``max_batch × ceil(max_len / block_len)`` — every
    slot's worst case, so a caller who names no pool gets fixed slots:
    admission never waits for a block.  Attention runs the
    scalar-prefetch Pallas kernel (ops/paged_attention.py) — the block
    indirection never materializes a gathered cache copy in HBM.

    Automatic PREFIX CACHING (``prefix_cache=True``): full prompt
    blocks register under chain hashes; a request whose prompt shares
    the chain reuses those blocks read-only and prefills only its
    suffix — the shared-system-prompt win.  refs==0 entries stay
    resident as LRU-evictable and are reclaimed under pool pressure
    before admission refuses.
    """

    def __init__(self, params: Dict, cfg: TransformerConfig,
                 max_batch: int, max_len: int,
                 total_blocks: Optional[int] = None,
                 block_len: Optional[int] = None,
                 prefix_cache: bool = True, kv_store=None,
                 shed_probe=None):
        if block_len is None:
            block_len = kv_store.page_tokens if kv_store is not None else 128
        if block_len < 1 or (total_blocks is not None and total_blocks < 1):
            raise ValueError("block_len and total_blocks must be >= 1")
        if total_blocks is None:
            total_blocks = max_batch * -(-max_len // block_len)
        if kv_store is not None and kv_store.page_tokens != block_len:
            # store pages scatter 1:1 into pool blocks; a mismatch
            # would need a re-chunking copy on every restore
            raise ValueError(
                f"kv_store.page_tokens ({kv_store.page_tokens}) must "
                f"equal block_len ({block_len})")
        #: rows a slot forwards a step: 1, or a diffusion config's block
        self.R = cfg.diffusion_block or 1
        if block_len % self.R or max_len % self.R:
            # a diffusion block never straddles a pool block, and a budget
            # rounded up to whole blocks stays inside max_len
            raise ValueError(
                f"block_len {block_len} and max_len {max_len} must be "
                f"multiples of the config's diffusion_block {self.R}")
        self.block_len = block_len
        self.total_blocks = total_blocks
        self.max_blocks = -(-max_len // block_len)
        self.prefix_cache = prefix_cache
        #: elastic cold-start (docs/RESILIENCE.md "Elastic cold-start"):
        #: ``params`` may be a demand-faulting source (anything with a
        #: ``materialize()`` — parallel/weights.py FaultingCheckpoint)
        #: instead of a resolved dict.  The server then constructs and
        #: accepts submissions immediately; the FIRST step resolves the
        #: params via ``materialize(klass="decode")`` — jit flattens the
        #: whole dict at trace time, so residency must be total before
        #: the first dispatch, and the decode class makes those faults
        #: overtake the background bulk/warmup streams in the QoS
        #: scheduler.  A plain dict (every existing caller) takes the
        #: eager path bit-for-bit.
        self._param_source = None
        if params is not None and not isinstance(params, dict) \
                and hasattr(params, "materialize"):
            self._param_source = params
            params = None
            coord = getattr(self._param_source, "coordinator", None)
            if coord is not None:
                coord.note_serving_started()
            start = getattr(self._param_source, "start_bulk", None)
            if start is not None:
                start()   # serve-while-restoring from the first moment
        self.params = params
        self.cfg = cfg
        self.B = max_batch
        self.max_len = max_len
        if kv_store is not None:
            cfg.require_causal("a kv_store (PrefixStore)")
            cfg.require_kv_pages("a kv_store (PrefixStore)")
        if cfg.recurrent_layers and kv_store is not None:
            # pages without the state at their boundary are not a prefix,
            # in the store as in the HBM prefix cache (_req_keys)
            cfg.require_no_recurrent("a kv_store (PrefixStore)")
        if cfg.recurrent_layers or cfg.stated_kv:
            shardings = {getattr(w, "sharding", None)
                         for w in (params or {}).values()
                         if not isinstance(w, dict)}
            if any(len(getattr(sh, "device_set", ())) > 1
                   for sh in shardings):
                (cfg.require_no_recurrent if cfg.recurrent_layers
                 else cfg.require_kv_pages)("a mesh (sharded params)")
        #: load-shedding probe (docs/RESILIENCE.md "failure domains"):
        #: a callable returning True while new prefill admissions should
        #: DEFER (requests wait queued; in-flight decode continues;
        #: nothing fails).  None (default) auto-wires to the KV store
        #: engine's failure-domain supervisor — when the NVMe tier is
        #: degraded, admitting a prefill would push restore/store
        #: traffic into a sick device and crater every in-flight
        #: request's p99; deferring sheds load until the half-open
        #: probe restores the fast path.
        self._shed_probe = shed_probe
        #: admission opportunities deferred by shedding (stats())
        self.admissions_shed = 0
        #: drain mode (io/handoff.py DrainCoordinator,
        #: docs/RESILIENCE.md "Drain & handoff"): True closes the
        #: admission gate with the shed path's DEFER semantics — queued
        #: requests wait (for export), nothing drops.  Never set unless
        #: a drain actually begins, so STROM_HANDOFF=0 stays inert.
        self._draining = False
        #: admission opportunities deferred by an active drain (stats())
        self.admissions_deferred = 0
        #: content-addressed NVMe prefix store (models/kv_offload.py
        #: PrefixStore, docs/PERF.md §5) — None (default) is today's
        #: per-session path bit-for-bit.  Shared system prompts across
        #: sessions/servers restore from NVMe instead of re-prefilling;
        #: each serve step batches EVERY admitting slot's due page
        #: reads into one decode-class plan_and_submit.
        self.kv_store = kv_store
        self.pos = jnp.zeros((max_batch,), jnp.int32)
        self.tok = jnp.zeros((max_batch,), jnp.int32)
        #: a diffusion server's block state a slot (``bd_state``; ``tok`` is
        #: then (slots, R), the slots' current blocks, and ``pos`` their
        #: first positions), None for one that makes a token a step
        self.bd = None
        if cfg.diffusion_block:
            self.tok = jnp.zeros((max_batch, self.R), jnp.int32)
            self.bd = bd_state(cfg, max_batch)
        # per-slot decoding params (DATA, not shapes: any greedy/
        # sampled mix runs the same compiled step)
        self.temp = jnp.zeros((max_batch,), jnp.float32)
        self.topp = jnp.ones((max_batch,), jnp.float32)
        self.seed = jnp.zeros((max_batch,), jnp.uint32)
        self.slots: List[Optional[_Request]] = [None] * max_batch
        self.queue: List[_Request] = []
        #: (slot, device scalar) first tokens whose host copy is
        #: deferred to the next batch readback — admission never syncs
        self._pending_first: List[tuple] = []
        #: retirements produced by _drain_pending_first while unwinding
        #: a failed step_many — merged into the NEXT call's result so a
        #: request finished during the drain is still delivered
        self._finished_carry: Dict[object, List[int]] = {}
        #: cumulative phase timers (the serving-gap attribution the
        #: round-3 verdict asked for): admission+prefill, device
        #: dispatch, and the host readback syncs.  Every key is numeric
        #: and cumulative (a reader subtracts two snapshots key by
        #: key).  Inside ``admit_s``: ``prefill_s`` (host seconds of
        #: the admissions' prefill calls — the DISPATCH of the compiled
        #: program, or its compilation on a shape's first use; its
        #: device time is paid where the host next waits, in
        #: ``readback_s``; the rest of ``admit_s`` is store pages, the
        #: first token and bookkeeping — spans only), ``admits``,
        #: ``queue_wait_s`` (Σ admission start − submit),
        #: ``prefill_tokens`` (padded tokens handed to prefill),
        #: ``prompt_tokens`` (prompt tokens those prefills had to
        #: compute: past the cached prefix), ``scan_tokens`` (valid
        #: tokens through the recurrent layers' scan, pads excluded; 0
        #: for a plain decoder), ``prefill_calls`` (prefill programs
        #: dispatched, one a group: ``admits`` over it is the mean group),
        #: ``prefill_rows_dead`` (rows of those programs that held no
        #: prompt: a group of 3 in a program of 4; ``prefill_tokens``
        #: counts width × length as handed to the program, so the padding
        #: of a shorter prompt and the dead rows both show in it) and
        #: ``prefill_programs`` (distinct (width, suffix, cache) shapes
        #: this server has prefilled with, each one compiled or fetched
        #: program, one a (suffix, cache) bucket: a handful on a healthy
        #: server, a climbing count is a shape leak).  Per decode
        #: step, from the host's position mirror: ``attn_blocks_live``
        #: (Σ over active slots of ``pos // block_len + 1``, the table
        #: entries paged attention has to read), ``attn_blocks_table``
        #: (``B × max_blocks``, the entries it would walk unbounded) and
        #: ``attn_grid_steps`` (the grid steps ONE layer's attention call
        #: issues: a step a live entry and one for each free slot — the
        #: latent kernel still walks slots × the longest slot, four
        #: entries a step; live over steps is the share of steps that
        #: carry a block).  Per
        #: call of an exact expert layer (one per layer per decode step;
        #: one per layer per prefill program under ``*_prefill``), read
        #: off the device's
        #: counters at each readback: ``moe_calls``, ``moe_pairs`` (the
        #: pairs computed here: those of ``moe_pairs_routed``, valid rows ×
        #: k from the host's own count, that fell on an expert this device
        #: holds — all of them unless ``cfg.experts_held`` says a share),
        #: ``moe_rows_computed`` (rows the grouped
        #: product ran, tile padding included), ``moe_experts_touched``
        #: (experts with at least one row), ``moe_load_max`` (the
        #: busiest expert's rows) and ``moe_rounds`` (layouts the calls
        #: took: ``moe_calls`` unless a call's local pairs overflowed its
        #: bounded layout, ``models/moe.pair_bound``) — sums over the calls.
        #: A diffusion server counts its slot-forwards by phase, read off
        #: the steps' reports at each readback (a slot that holds a request:
        #: ``bd_forwards_denoise``, ``bd_forwards_write`` — a forward that
        #: ONLY writes a finished block's clean K/V: none does, the key
        #: stays for its readers —, ``bd_forwards_hold`` — past its last
        #: block until the host retires it), ``bd_writes_fused`` (the
        #: denoising forwards whose first R rows wrote the block finished
        #: before), ``bd_tokens`` (positions committed) and ``bd_rows`` (live
        #: rows forwarded by those slots: R a forward, 2 R a fused one)
        self.timings: Dict[str, float] = {
            "admit_s": 0.0, "dispatch_s": 0.0, "readback_s": 0.0,
            "steps": 0,
            "admits": 0, "queue_wait_s": 0.0, "prefill_s": 0.0,
            "prefill_tokens": 0, "prompt_tokens": 0,
            "prefill_calls": 0, "prefill_rows_dead": 0,
            "prefill_programs": 0, "scan_tokens": 0,
            "attn_blocks_live": 0, "attn_blocks_table": 0,
            "attn_grid_steps": 0, "window_rows_live": 0,
            "bd_forwards_denoise": 0, "bd_forwards_write": 0,
            "bd_forwards_hold": 0, "bd_writes_fused": 0, "bd_tokens": 0,
            "bd_rows": 0,
            **{key + sfx: 0 for sfx in ("", "_prefill") for key in (
                "moe_calls", "moe_pairs", "moe_pairs_routed",
                "moe_rows_computed", "moe_experts_touched",
                "moe_load_max", "moe_rounds")}}
        #: cumulative (expert layers, E) load histogram of the decode steps
        #: (numpy; None without expert layers)
        self.moe_load = None
        self._prefill_shapes: set = set()
        #: per-request serving metrics of RETIRED requests ({rid:
        #: {"ttft_ms", "admit_wait_ms"}}, newest last, bounded) plus
        #: the running aggregates stats() reports
        self.request_metrics: Dict[object, Dict[str, float]] = {}
        self._metrics_agg = {"n": 0, "ttft_sum": 0.0, "ttft_max": 0.0,
                             "wait_sum": 0.0, "wait_max": 0.0}
        #: retained per-request metric entries (STROM_SERVE_METRICS_MAX;
        #: generous default — entries are two floats, but an unbounded
        #: dict on a long-lived server is still a leak)
        self._metrics_keep = int(os.environ.get(
            "STROM_SERVE_METRICS_MAX", str(self._METRICS_KEEP)))
        # multi-tenant admission state (docs/RESILIENCE.md "Multi-tenant
        # isolation") — all empty until a tenant-tagged request arrives,
        # so the single-tenant stack never pays for any of it
        self._tenant_cfg = None           # utils.config.TenantConfig
        self._buckets: Dict[str, TokenBucket] = {}
        #: cumulative per-tenant sheds (stats())
        self.tenant_sheds: Dict[str, int] = {}
        #: sheds since the last tenant_storm flight dump, per tenant
        self._storm_window: Dict[str, int] = {}
        #: recent decode TTFTs per tenant (the per-tenant SLO lane's
        #: p99 window, fed to SloGovernor.observe_tenant at retire)
        self._tenant_ttft: Dict[str, List[float]] = {}
        nkv, hd = cfg.n_kv_heads, cfg.head_dim
        # K/V for the layers that attend: all of them in a plain decoder
        L = len(cfg.attn_layers)
        # +1: a sacrificial TRASH block — a free slot still computes a
        # (masked) step and its frozen-pos write must never land in a
        # block some live request owns
        shape = (L, self.total_blocks + 1, nkv, self.block_len, hd)
        if cfg.latent:
            # ONE array of latent rows, a block's tokens along the lanes
            # (ops/mla_attention.py), and no second pool
            shape = shape[:2] + (cfg.latent_width, self.block_len)
        self.k_pool = jnp.zeros(shape, cfg.dtype)
        self.v_pool = None if cfg.latent else jnp.zeros(
            shape[:-1] + (cfg.v_dim,), cfg.dtype)
        self._trash = self.total_blocks
        # the second kind of cache: per recurrent layer what its mixer
        # declares per sequence (a Mamba-2 state and conv tail, a short
        # conv's tail) for every slot, +1 sacrificial row that free slots
        # step into (row B, as their K/V goes to the trash block) — and
        # with it the expert layers' load counters, carried by the same
        # two programs — and the THIRD kind: a window layer's ring of its
        # last rows per slot (``init_carried``), which holds no block of the
        # pool above, is never on the free list and is overwritten whole by
        # its slot's next prefill.  None for a plain decoder.
        self.state = init_carried(cfg, self.B + 1, self.block_len)
        #: host copy of the device's load counters at the last readback
        #: ({"decode" | "prefill": {"load", "sums"}} as uint32: a counter
        #: may wrap, a difference of two readings does not)
        self._moe_seen = None
        self._moe_prefills = 0      # prefill programs at the last readback
        #: rows a prefill program may hold before its arithmetic outlasts
        #: its weight read on this server's device: what groups are formed
        #: within (models/admission.py)
        self._group_rows = _adm.breakeven_rows(
            cfg, next(iter(self.k_pool.devices())).device_kind)
        self.free: List[int] = list(range(self.total_blocks))
        self.blocks: List[List[int]] = [[] for _ in range(self.B)]
        self._pos_h: List[int] = [0] * self.B   # host mirror of pos
        self._table_dev = None                  # cache until blocks move
        # prefix cache (vLLM-style automatic prefix sharing): every FULL
        # prompt block is registered under its CHAIN hash (the KV of a
        # block depends on the entire prefix, so key_i = H(key_{i-1},
        # tokens_i)); a later request whose prompt starts with the same
        # chain reuses those pool blocks read-only and prefills only its
        # suffix.  refs==0 entries stay resident as LRU-evictable — the
        # pool reclaims them under pressure before refusing admission.
        self._pc: Dict[bytes, dict] = {}        # key -> {blk, refs}
        self._pc_by_blk: Dict[int, bytes] = {}
        self._pc_lru: Dict[bytes, None] = {}    # insertion-ordered LRU
        self._pc_hits = 0
        self._pc_shared_blocks = 0

    def _table(self):
        """(B, max_blocks) device table, cached until block membership
        changes; padding entries are 0 — their positions sit past pos
        and the kernel masks them."""
        if self._table_dev is None:
            import numpy as np
            t = np.zeros((self.B, self.max_blocks), np.int32)
            for b, blks in enumerate(self.blocks):
                t[b, :len(blks)] = blks
            self._table_dev = jnp.asarray(t)
        return self._table_dev

    # -- prefix cache ------------------------------------------------------

    def _chain_keys(self, prompt: List[int]) -> List[bytes]:
        """Chain hash per FULL prompt block, capped at (s-1)//bk so at
        least one suffix token always prefills live (the first-token
        logits must come from a real forward, and decode's first write
        must never target a shared block)."""
        import hashlib
        import numpy as np
        bk = self.block_len
        n = (len(prompt) - 1) // bk
        keys, h = [], b""
        for i in range(n):
            chunk = np.asarray(prompt[i * bk:(i + 1) * bk],
                               np.int32).tobytes()
            h = hashlib.sha1(h + chunk).digest()
            keys.append(h)
        return keys

    def _req_keys(self, req: _Request) -> List[bytes]:
        """The request's chain keys, hashed ONCE — _can_admit runs per
        step while a request queues, and per-wait rehashing of a long
        prompt is O(prompt) host work on the decode path."""
        if (not self.prefix_cache or self.cfg.recurrent_layers
                or self.cfg.window_layers):
            # recurrent layers: a cached page is worthless without the
            # state at its boundary, which nobody keeps — no keys, so no
            # match (_pc_match) and nothing registered.  Window layers
            # likewise: a prefix would need their last ``window`` rows at
            # its boundary, and a ring keeps only its slot's newest
            return []
        if req.chain_keys is None:
            req.chain_keys = self._chain_keys(req.prompt)
        return req.chain_keys

    def _pc_match(self, keys: List[bytes]) -> List[bytes]:
        """Longest cached chain prefix (keys of matched entries)."""
        out = []
        for kx in keys:
            if kx not in self._pc:
                break
            out.append(kx)
        return out

    def _pc_acquire(self, key: bytes) -> int:
        e = self._pc[key]
        e["refs"] += 1
        self._pc_lru.pop(key, None)     # referenced: not evictable
        return e["blk"]

    def _pc_register(self, key: bytes, blk: int) -> None:
        if key in self._pc:             # a concurrent admit won the race
            return
        self._pc[key] = {"blk": blk, "refs": 1}
        self._pc_by_blk[blk] = key

    def _pc_release(self, blk: int) -> bool:
        """Retiring request drops its ref; True if the block stays
        cached (evictable) rather than returning to the free list."""
        key = self._pc_by_blk.get(blk)
        if key is None:
            return False
        e = self._pc[key]
        e["refs"] -= 1
        if e["refs"] == 0:
            self._pc_lru[key] = None    # oldest-first eviction order
        return True

    def _pc_evict_one(self) -> int:
        key = next(iter(self._pc_lru))
        del self._pc_lru[key]
        blk = self._pc.pop(key)["blk"]
        del self._pc_by_blk[blk]
        return blk

    def _alloc_blocks(self, n: int) -> List[int]:
        """Pop n free blocks, evicting LRU refs==0 cache entries when
        the free list runs short.  (Blocks matched by the in-flight
        admission were acquired first — refs > 0 keeps them out of the
        LRU, so eviction can never take them.)"""
        out = []
        for _ in range(n):
            if not self.free:
                self.free.append(self._pc_evict_one())
            out.append(self.free.pop())
        return out

    # -- intake -----------------------------------------------------------

    def submit(self, rid, prompt_ids: List[int], max_new: int,
               eos_id: Optional[int] = None,
               temperature: float = 0.0, top_p: float = 1.0,
               seed: int = 0, tenant=None) -> None:
        if not prompt_ids:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        if temperature < 0:
            raise ValueError(f"temperature must be >= 0, got "
                             f"{temperature}")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if temperature > 0:
            self.cfg.require_causal("sampling (temperature > 0)")
        # (a diffusion server generates whole blocks: max_len is a multiple
        # of the block, so a budget that fits does so rounded up too)
        if len(prompt_ids) + max_new > self.max_len:
            raise ValueError(
                f"prompt {len(prompt_ids)} + max_new {max_new} exceeds "
                f"server max_len {self.max_len}")
        in_flight = ({r.rid for r in self.queue}
                     | {r.rid for r in self.slots if r is not None})
        if rid in in_flight:
            # results key on rid — a duplicate would silently clobber
            raise ValueError(f"request id {rid!r} already in flight")
        req = _Request(rid, list(prompt_ids), max_new,
                       eos_id, temperature=temperature,
                       top_p=top_p,
                       seed=seed & 0xFFFFFFFF,
                       t_submit=time.monotonic())
        if tenant is not None and tenants_enabled():
            # resolve (and lazily register) the tenant ONCE at submit;
            # with STROM_TENANTS=0 the tag is ignored and the request
            # walks the exact pre-tenant path
            from nvme_strom_tpu.io.tenants import get_registry
            req.tenant = get_registry().get(tenant)
        if self._tracer().enabled:
            from nvme_strom_tpu.utils.trace import TraceContext
            req.trace = TraceContext.new()
            req.t_submit_ns = time.monotonic_ns()
        self.queue.append(req)

    # -- admission (plan / restore / finish) ------------------------------
    #
    # Admission is split in two so ONE serve step can gather every
    # admitting slot's due NVMe page reads into a single decode-class
    # plan_and_submit batch (the prefix store, docs/PERF.md §5): the
    # PLAN phase makes the capacity decisions sequentially (block
    # allocation, HBM prefix-cache refs — exactly the old per-slot
    # order, so admission control is unchanged), the batched restore
    # runs between, and the FINISH phase prefills/scatters.  With no
    # store attached the two halves compose to the old _admit verbatim.

    def _tracer(self):
        """The span sink of this server, enabled or not: the KV-store
        engine's tracer when a store is attached (one file for the
        whole stack), else the global tracer.  Call sites that build a
        span's arguments by hand check ``.enabled`` first."""
        store = self.kv_store
        tracer = (getattr(getattr(store, "engine", None), "tracer",
                          None) if store is not None else None)
        if tracer is None:
            from nvme_strom_tpu.utils import trace
            tracer = trace.global_tracer
        return tracer

    def _span(self, name: str, ctx=None, **args):
        """One of this server's spans (``Tracer.span``: on the JAX
        profiler's timeline inside a profiler session, in the tracer
        when it is on).
        Names are fixed — docs/OBSERVABILITY.md lists them; none is one
        of the benchmark loop's bare phase names."""
        return self._tracer().span(name, "strom.serve", ctx, **args)

    def _form_groups(self, plans: list, restored: Dict[int, dict]) -> list:
        """This step's admissions as groups, each a list of plans that goes
        through one prefill program (``models/admission.form_groups`` over
        the padded suffix lengths, within the break-even rows of this
        server's config on its device).  Only requests that differ in
        nothing a program or a scope is keyed on share one: a request with
        store pages to scatter or a tenant scope of its own is a group of
        one, and a group's rows share their count of cached prefix
        blocks."""
        bk = self.block_len
        alone, by_cached = [], {}
        for plan in plans:
            if plan["req"].tenant is not None or restored.get(plan["slot"]):
                alone.append([plan])
            else:
                by_cached.setdefault(plan["c"], []).append(plan)
        groups = []
        for c, peers in by_cached.items():
            lengths = [(-(-len(p["req"].prompt) // bk) - c) * bk
                       for p in peers]
            groups += [[peers[i] for i in idx] for idx in _adm.form_groups(
                lengths, self._group_rows)]
        return alone + groups

    def _finish_traced(self, plan: list, restored: dict) -> None:
        """The one door an admission goes through: ``plan`` is a GROUP, the
        list of the plans (``_admit_plan``) that share a prefill program — a
        single request is a group of one — and ``restored`` the store pages
        of its only member where it has any.  The admission span (prefill +
        scatter + first token) lands in the request's tree, and everything
        the finish triggers — store puts, engine writes — auto-parents to
        it via the contextvar.  A tenant-tagged request (always a group of
        one) additionally finishes under its TENANT scope, so the
        host-cache lines the prefill touches and the store pages the put
        writes are quota-charged to their owner (io/tenants.py)."""
        tenant = plan[0]["req"].tenant
        if tenant is not None:
            with tenant_context(tenant):
                self._finish_traced_inner(plan, restored)
        else:
            self._finish_traced_inner(plan, restored)

    def _finish_traced_inner(self, group: list, restored: dict) -> None:
        now = time.monotonic()
        waits = [now - p["req"].t_submit for p in group]
        self.timings["admits"] += len(group)
        self.timings["queue_wait_s"] += sum(waits)
        # ONE span serves the group's requests: scope it, and with it the
        # prefill and the first token, under the FIRST traced request's
        # tree (a group of one is exact) and name every trace id, as the
        # batched store restore does; the other requests' trees get the
        # admission as a copy that names the tree the rest is in
        traced = [p["req"] for p in group if p["req"].trace is not None]
        ctx = traced[0].trace.child() if traced else None
        t0_ns = time.monotonic_ns() if len(traced) > 1 else 0
        # rid last: the profiler's encoding cuts the arguments at a ","
        with self._span("strom.serve.admit", ctx, slot=group[0]["slot"],
                        rows=len(group),
                        prompt_tokens=sum(len(p["req"].prompt)
                                          for p in group),
                        cached_blocks=group[0].get("c", 0),
                        restored_pages=len(restored),
                        queue_wait_ms=round(1000.0 * max(waits), 3),
                        rid=_rids(group)) as span:
            if span and len(traced) > 1:
                span.set_metadata(traces=" ".join(
                    f"{r.trace.trace_id:x}" for r in traced))
            logits = self._admit_prefill(group, restored)
            for plan in group:
                self._admit_finish(plan, restored)
            if self.bd is None:
                self._admit_first(group, logits)
            else:
                self._admit_block_state(group, logits.shape[0])
        for req in traced[1:]:
            self._tracer().add_span(
                "strom.serve.admit", t0_ns, time.monotonic_ns(),
                category="strom.serve", ctx=req.trace.child(),
                rows=len(group), group=f"{traced[0].trace.trace_id:x}",
                queue_wait_ms=round(1000.0 * (now - req.t_submit), 3),
                rid=str(req.rid))

    def _admit_plan(self, slot: int, req: _Request) -> dict:
        """Capacity phase: HBM prefix-cache refs + block allocation, in
        the exact order sequential admission made them (so a later
        queue head's _can_admit sees the updated free list)."""
        s = len(req.prompt)
        bk = self.block_len
        need = -(-(s + req.max_new) // bk)
        keys = self._req_keys(req)
        matched = self._pc_match(keys)
        c = len(matched)
        shared = [self._pc_acquire(kx) for kx in matched]
        new_blks = self._alloc_blocks(need - c)
        return {"slot": slot, "req": req, "keys": keys, "c": c,
                "blks": shared + new_blks}

    def _store_keys(self, req: _Request) -> list:
        """The request's prefix-store chain keys, hashed once."""
        if self.kv_store is None:
            return []
        if req.store_keys is None:
            req.store_keys = self.kv_store.chain_keys(req.prompt)
        return req.store_keys

    def _restore_prefixes(self, plans: list) -> Dict[int, dict]:
        """Batch-restore every admitting slot's store-resident pages:
        ONE plan_and_submit under the decode class (cross-request
        locality for the coalescing planner and the ring scheduler).
        Returns {slot: {chain_index: (k, v) numpy pages}}."""
        store = self.kv_store
        wants: Dict[int, tuple] = {}
        misses = 0
        for plan in plans:
            req = plan["req"]
            keys = self._store_keys(req)
            if not keys:
                continue
            # pages the in-HBM block cache already serves cost one gather
            # — cheaper than any NVMe read, so the store starts past them
            skip = plan["c"]
            matched = store.match(keys)
            misses += len(keys) - matched
            if matched > skip:     # they land in already-reserved blocks
                wants[plan["slot"]] = (skip, keys[skip:matched])
        if misses and store.stats is not None:
            store.stats.add(kv_prefix_misses=misses)
        if not wants:
            return {}
        by_slot = {p["slot"]: p["req"] for p in plans}
        # tenant scope mirrors the trace scope below: the FIRST
        # participating tenant owns the batched restore (exact for the
        # single-request step; a mixed batch is one shared read either
        # way), so the decode-class batch and the host-cache lines it
        # fills are quota-charged to an owner instead of nobody
        ten = next((by_slot[s].tenant for s in wants
                    if by_slot[s].tenant is not None), None)
        # ONE batched restore serves several admitting requests: scope
        # it under the FIRST participating request's tree (the single-
        # request case — the acceptance walkthrough — is exact) and
        # name every trace id so a multi-request step stays attributable
        traced = [by_slot[s].trace for s in wants
                  if by_slot[s].trace is not None]
        ctx = traced[0].child() if traced else None
        with self._span("strom.serve.kv_restore", ctx,
                        slots=len(wants)) as span, tenant_context(ten):
            if span:        # something records: build the rest
                # traces: a string, not a list — the profiler's
                # annotation takes scalars (docs/OBSERVABILITY.md)
                span.set_metadata(
                    pages=sum(len(k) for _s, k in wants.values()),
                    traces=" ".join(f"{t.trace_id:x}" for t in traced))
            return store.restore_many(wants)

    def _admit_prefill(self, group: list, restored: dict):
        """Restored pages into the request's blocks, then the group's
        prefill (suffixes only, past the cached and restored blocks): one
        ``_paged_prefill`` call of (width, the longest padded suffix).
        Prompts right-pad to a block multiple and the group, with dead
        rows, to the one width its length has (``admission.width_for``),
        so admission compiles once per (suffix, prompt) bucket, not once
        per prompt length, per width or per mix: a burst of equal lengths
        (a warm-up, a cold fill) builds the program that every narrower or
        mixed group of that length runs later.  Each row's logits are read
        at its own last position.  Returns the logits (width, vocab), on
        the device."""
        import numpy as np
        bk = self.block_len
        c = group[0]["c"]
        # NVMe-restored pages (chain indices past the HBM match, from
        # the step's batched decode-class read) scatter into this
        # request's own new blocks and REGISTER in the HBM cache — the
        # next same-prefix admission hits DRAM, not NVMe
        use = []                # chain indices c, c + 1, ... without a gap
        while c + len(use) in restored:
            use.append(restored[c + len(use)])
        c2 = len(use)
        if use:
            plan, = group       # _form_groups leaves such a request alone
            blks, keys = plan["blks"], plan["keys"]
            with self._span("strom.serve.scatter", blocks=c2,
                            rid=str(plan["req"].rid)):
                rows_k = jnp.asarray(np.stack([k for k, _ in use],
                                              axis=1))
                rows_v = jnp.asarray(np.stack([v for _, v in use],
                                              axis=1))
                self.k_pool, self.v_pool = _scatter_blocks(
                    self.k_pool, self.v_pool,
                    jnp.asarray(blks[c:c + c2], jnp.int32), rows_k,
                    rows_v)
                if keys:
                    # keys is empty with prefix_cache=False (store
                    # restores still work; there is just no HBM
                    # registry to join)
                    for j in range(c2):
                        self._pc_register(keys[c + j], blks[c + j])
        ct = c + c2
        for plan in group:
            plan["ct"] = ct

        # prefill: ONE program gathers the cached prefixes (HBM-shared +
        # just-restored blocks), runs block_step over the suffixes and
        # writes each row's rows into its new blocks; pad rows sit past pos
        # and are overwritten before the mask reaches them, and what a
        # shorter prompt has no block for goes to the trash block
        n = max(-(-len(p["req"].prompt) // bk) for p in group)
        m = (n - ct) * bk
        b = _adm.width_for(m, self._group_rows)
        if len(group) > b:
            raise ValueError(f"{len(group)} prompts in a program of {b}")
        tokens = np.zeros((b, m), np.int32)
        blks = np.full((b, n), self._trash, np.int32)
        last = np.full((b,), -1, np.int32)      # a dead row: nothing valid
        slots = np.full((b,), self.B, np.int32)     # ... the sacrificial row
        for i, plan in enumerate(group):
            suffix = plan["req"].prompt[ct * bk:]
            n_pb = -(-len(plan["req"].prompt) // bk)
            tokens[i, :len(suffix)] = suffix
            blks[i, :n_pb] = plan["blks"][:n_pb]
            last[i] = len(suffix) - 1
            slots[i] = plan["slot"]
        useful = int(last.sum()) + b
        # b x m tokens are handed to the program, ``useful`` of them prompt
        # past the cached prefix; the rest is padding, dead rows included
        self.timings["prefill_calls"] += 1
        self.timings["prefill_rows_dead"] += b - len(group)
        self.timings["prefill_tokens"] += b * m
        self.timings["prompt_tokens"] += useful
        if self.cfg.recurrent_layers:
            self.timings["scan_tokens"] += useful
        self.timings["moe_pairs_routed_prefill"] += (
            useful * self.cfg.expert_top_k * len(self.cfg.expert_layers))
        self._prefill_shapes.add((b, m, n * bk))
        self.timings["prefill_programs"] = len(self._prefill_shapes)
        t0 = time.monotonic()
        with self._span("strom.serve.prefill", tokens=b * m, useful=useful,
                        rows=len(group), program=prefill_program(b, m, n * bk),
                        rid=_rids(group)):
            # positional, so that the state pool is donated with the rest
            recur = () if self.state is None else (self.state, slots)
            logits, self.k_pool, self.v_pool, self.state = _paged_prefill(
                self.params, self.cfg, self.k_pool, self.v_pool, tokens,
                blks, last, *recur)
        self.timings["prefill_s"] += time.monotonic() - t0
        return logits

    def _admit_finish(self, plan: dict, restored: dict) -> None:
        """One request of a group after the group's prefill: its blocks
        into the table, its newly computed full blocks into the prefix
        cache and the store, the request into its slot.  (``restored`` is
        the door's second argument, passed on unread: what wraps this
        method on an instance — the benchmark's controls — wraps the
        pair.)"""
        slot, req, blks = plan["slot"], plan["req"], plan["blks"]
        keys, ct = plan["keys"], plan["ct"]
        self.blocks[slot] = blks
        self._table_dev = None
        if plan["c"]:
            self._pc_hits += 1
            self._pc_shared_blocks += plan["c"]
        with self._span("strom.serve.scatter",
                        blocks=-(-len(req.prompt) // self.block_len) - ct,
                        rid=str(req.rid)):
            # newly computed FULL blocks join the cache for future
            # requests
            for i in range(ct, len(keys)):
                self._pc_register(keys[i], blks[i])
            if self.kv_store is not None:
                self._store_put(req, slot, ct)
        self.slots[slot] = req
        # (a diffusion server: where the prompt's last whole block ends)
        self._pos_h[slot] = len(req.prompt) // self.R * self.R

    def _admit_first(self, group: list, logits) -> None:
        """The group's first tokens and decoding state, one program
        (``_admit_slots``) — a dispatch, nothing read back: admission must
        never block on a value crossing the link (the round-4 on-silicon
        row spent 20.6 of 27 s in admit that way); the host copies ride
        ``step_many``'s single batch readback."""
        import numpy as np
        b = logits.shape[0]
        slots = np.full((b,), self.B, np.int32)     # a dead row: dropped
        temps = np.zeros((b,), np.float32)
        top_ps = np.ones((b,), np.float32)
        seeds = np.zeros((b,), np.uint32)
        lens = np.ones((b,), np.int32)
        for i, plan in enumerate(group):
            req = plan["req"]
            slots[i], lens[i] = plan["slot"], len(req.prompt)
            temps[i], top_ps[i], seeds[i] = (req.temperature, req.top_p,
                                             req.seed)
        with self._span("strom.serve.first_token", rows=len(group),
                        rid=_rids(group)):
            (first, self.temp, self.topp, self.seed, self.pos,
             self.tok) = _admit_slots(
                logits, self.temp, self.topp, self.seed, self.pos, self.tok,
                slots, temps, top_ps, seeds, lens)
            t_admit = time.monotonic()
            for plan, tok in zip(group, first):
                self._pending_first.append((plan["slot"], tok))
                plan["req"].t_admit = t_admit

    def _admit_block_state(self, group: list, b: int) -> None:
        """A diffusion group's block state, one program (``_admit_blocks``)
        and nothing read back: the admission has forwarded each prompt's
        whole blocks under the block-causal mask and yields NO token — the
        prompt's remainder joins the first block as given, the rest of it
        masked, and the first token is that block's first commit.  The
        server's ``diffusion_steps`` / ``diffusion_threshold`` ride as
        per-slot data (``bd_state``)."""
        import numpy as np
        R, cfg = self.R, self.cfg
        slots = np.full((b,), self.B, np.int32)     # a dead row: dropped
        starts, given, end = (np.zeros((b,), np.int32) for _ in range(3))
        toks = np.zeros((b, R), np.int32)
        for i, plan in enumerate(group):
            req = plan["req"]
            P = len(req.prompt)
            slots[i], starts[i], given[i] = plan["slot"], P // R * R, P % R
            toks[i, :P % R] = req.prompt[P // R * R:]
            end[i] = -(-(P + req.max_new) // R) * R
        dynamic = cfg.diffusion_threshold > 0
        T = np.full((b,), 0 if dynamic else cfg.diffusion_steps or R,
                    np.int32)
        tau = np.full((b,), cfg.diffusion_threshold if dynamic else np.inf,
                      np.float32)
        with self._span("strom.serve.first_token", rows=len(group),
                        rid=_rids(group)):
            self.pos, self.tok, self.bd = _admit_blocks(
                self.pos, self.tok, self.bd, slots, starts, toks, given,
                end, T, tau)
            t_admit = time.monotonic()
            for plan in group:
                plan["req"].t_admit = t_admit

    def _store_put(self, req: _Request, slot: int, have: int) -> None:
        """Persist this admission's newly computed full prompt pages
        (chain indices ``have..``) — written once store-wide however
        many sessions share them (put() dedupes by content key).  The
        device→host pull is one slice per admission; admission already
        tolerates host work, and the write itself is async."""
        import numpy as np
        keys = self._store_keys(req)
        n_full = len(keys)
        if n_full <= have:
            return
        # one pull for the whole new-page range, then page slices
        P = self.block_len
        k_all, v_all = (np.asarray(a[:, 0]) for a in _gather_prefix(
            self.k_pool, self.v_pool,
            jnp.asarray([self.blocks[slot][have:n_full]], jnp.int32)))
        pages = [(keys[i],
                  k_all[:, :, (i - have) * P:(i - have + 1) * P],
                  v_all[:, :, (i - have) * P:(i - have + 1) * P])
                 for i in range(have, n_full)]
        self.kv_store.put(pages)

    def _drain_pending_first(self) -> None:
        """Deliver deferred first tokens while ``step_many`` unwinds
        from an exception.

        Without this, an error between admission and the batch readback
        (e.g. a device fault mid-dispatch) leaves ``_pending_first``
        entries alive into the NEXT call, replaying each slot's first
        token a full batch late — after tokens generated later — so the
        output order and the TTFT/inflight accounting are both wrong.
        Draining here appends the first tokens in generation order
        before anything newer can land.  Retirements go to
        ``_finished_carry`` (returned by the next step_many) because
        our caller's ``finished`` dict is lost to the exception.  If
        the readback itself fails (device wedged) the entries are
        RESTORED: late replay on a dead device beats silently dropping
        a token from a request's output."""
        pending, self._pending_first = self._pending_first, []
        if not pending:
            return
        try:
            first_h = jax.device_get([v for _, v in pending])
        except Exception:
            self._pending_first = pending
            return
        t_now = time.monotonic()
        for (slot, _), v in zip(pending, first_h):
            if self.slots[slot] is None:
                continue
            self.slots[slot].t_first = t_now
            self.slots[slot].out.append(int(v))
            ret = self._retire_or_keep(slot)
            if ret:
                self._finished_carry[ret[0]] = ret[1]

    def _retire_or_keep(self, slot: int) -> Optional[tuple]:
        req = self.slots[slot]
        done_len = len(req.out) >= req.max_new
        done_eos = req.eos_id is not None and req.out[-1] == req.eos_id
        if done_len or done_eos:
            self.slots[slot] = None
            self._release_slot(slot)
            self._record_metrics(req)
            return req.rid, req.out
        return None

    #: default per-request metric retention — generous (entries are a
    #: few floats) but BOUNDED: a long-lived server retiring millions
    #: of requests must not grow ``request_metrics`` without limit.
    #: ``STROM_SERVE_METRICS_MAX`` overrides per process.
    _METRICS_KEEP = 4096

    def _record_metrics(self, req: _Request) -> None:
        """Retire-time serving metrics: TTFT (submit → first token
        DELIVERED at a host readback) and admission wait (submit →
        admitted into a slot) — the observable form of the SLO story
        (docs/PERF.md §5)."""
        ttft_ms = (1000.0 * (req.t_first - req.t_submit)
                   if req.t_first is not None else 0.0)
        wait_ms = 1000.0 * (req.t_admit - req.t_submit)
        tracer = self._tracer()
        if tracer.enabled and req.trace is not None:
            end_ns = time.monotonic_ns()
            # the request's ROOT span, submit → retirement: the tree
            # every admit/restore/queue/engine span hangs under
            tracer.add_span("strom.serve.request", req.t_submit_ns,
                            end_ns,
                            category="strom.serve", ctx=req.trace,
                            rid=str(req.rid), ttft_ms=round(ttft_ms, 3),
                            admit_wait_ms=round(wait_ms, 3),
                            tokens=len(req.out))
            # critical-path attribution (obs/attrib.py): fold this
            # request's span tree into the per-class profiles —
            # serving requests are the decode class
            from nvme_strom_tpu.obs.attrib import get_collector
            col = get_collector()
            if col is not None:
                col.request_retired(req.trace.trace_id, req.t_submit_ns,
                                    end_ns, klass="decode",
                                    extra={"rid": str(req.rid),
                                           "ttft_ms": round(ttft_ms, 3)})
        self.request_metrics[req.rid] = {
            "ttft_ms": round(ttft_ms, 3),
            "admit_wait_ms": round(wait_ms, 3)}
        if self.bd is not None:
            # the denoising step each token of the answer was committed at,
            # a byte a token
            self.request_metrics[req.rid]["commit_steps"] = bytes(req.steps)
        while len(self.request_metrics) > self._metrics_keep:
            self.request_metrics.pop(next(iter(self.request_metrics)))
        agg = self._metrics_agg
        agg["n"] += 1
        agg["ttft_sum"] += ttft_ms
        agg["ttft_max"] = max(agg["ttft_max"], ttft_ms)
        agg["wait_sum"] += wait_ms
        agg["wait_max"] = max(agg["wait_max"], wait_ms)
        if req.tenant is not None:
            self._observe_tenant_ttft(req.tenant, ttft_ms)

    #: TTFT samples kept per tenant for the p99 window, and the fill
    #: level before the window is trusted to call a violation
    _TENANT_TTFT_WIN = 64
    _TENANT_TTFT_MIN = 8

    def _observe_tenant_ttft(self, tenant, ttft_ms: float) -> None:
        """Feed the per-tenant SLO lane: a sliding TTFT window per
        tenant; once warm, its p99 goes to the store's SloGovernor,
        which may notch the tenant's fair-share boost (never the
        hedge budget — kv_offload.observe_tenant)."""
        win = self._tenant_ttft.setdefault(tenant.id, [])
        win.append(ttft_ms)
        if len(win) > self._TENANT_TTFT_WIN:
            del win[0]
        stats = self._engine_stats()
        if stats is not None:
            stats.add_tenant_stat(tenant.id, requests_finished=1)
        if (tenant.slo_p99_ms <= 0 or self.kv_store is None
                or len(win) < self._TENANT_TTFT_MIN):
            return
        slo = getattr(self.kv_store, "slo", None)
        if slo is None:
            return
        w = sorted(win)
        p99 = w[min(len(w) - 1, int(0.99 * len(w)))]
        slo.observe_tenant(getattr(self.kv_store, "engine", None),
                           tenant, p99, stats=stats)

    # -- serving ----------------------------------------------------------

    @property
    def idle(self) -> bool:
        return not self.queue and all(s is None for s in self.slots)

    def stats(self) -> Dict[str, int]:
        """Point-in-time serving gauges (the STAT_INFO discipline for
        the inference tier): slot occupancy, queue depth, tokens
        generated by in-flight requests, the retired requests' TTFT /
        admission-wait aggregates (per-request values live in
        ``request_metrics``), and the prefill programs (shapes) used."""
        agg = self._metrics_agg
        n = agg["n"]
        out = {
            "slots_total": self.B,
            "slots_busy": sum(r is not None for r in self.slots),
            "queued": len(self.queue),
            "inflight_tokens": sum(len(r.out) for r in self.slots
                                   if r is not None),
            "requests_finished": n,
            "ttft_ms_avg": round(agg["ttft_sum"] / n, 3) if n else 0.0,
            "ttft_ms_max": round(agg["ttft_max"], 3),
            "admit_wait_ms_avg": round(agg["wait_sum"] / n, 3)
            if n else 0.0,
            "admit_wait_ms_max": round(agg["wait_max"], 3),
            "admissions_shed": self.admissions_shed,
            "prefill_programs": len(self._prefill_shapes),
            "blocks_total": self.total_blocks,
            "blocks_free": len(self.free),
            "prefix_cached_blocks": len(self._pc),
            "prefix_evictable": len(self._pc_lru),
            "prefix_hits": self._pc_hits,
            "prefix_shared_blocks": self._pc_shared_blocks,
            # the two kinds of cache: layers that keep K/V pages, and the
            # recurrent layers' fixed state (bytes on the device, rows)
            "kv_layers": self.k_pool.shape[0],
            # table entries paged attention had to read, and those an
            # unbounded walk would have, summed over the decode steps
            "attn_blocks_live": self.timings["attn_blocks_live"],
            "attn_blocks_table": self.timings["attn_blocks_table"],
            # and the grid steps one layer's attention call issued for them
            "attn_grid_steps": self.timings["attn_grid_steps"],
        }
        state = jax.tree_util.tree_leaves(
            [self.state[k] for k in ("s", "conv")] if self.state else None)
        out["state_bytes"] = sum(a.nbytes for a in state)
        out["state_slots"] = self.B + 1 if state else 0
        # one sequence's share of it, whatever its length, and the layers
        # that carry it (beside ``kv_layers``, the layers that keep pages)
        out["state_bytes_per_slot"] = out["state_bytes"] // (self.B + 1)
        out["state_layers"] = len(self.cfg.recurrent_layers)
        # the Mamba-2 or delta-rule heads a lane row of the state pool
        # holds side by side (1: nothing is packed)
        out["state_heads_per_lane_row"] = 1
        if self.cfg.mamba_layers:
            from nvme_strom_tpu.ops.ssm import heads_per_lane_row
            out["state_heads_per_lane_row"] = heads_per_lane_row(
                self.cfg.ssm_heads, self.cfg.ssm_head_dim)
        elif "gdn" in self.cfg.layer_kinds:
            from nvme_strom_tpu.ops.gdn import heads_per_lane_row
            out["state_heads_per_lane_row"] = heads_per_lane_row(
                self.cfg.gdn_v_heads, self.cfg.gdn_v_dim)
        # layers whose MLP is the exact expert layer, and what they routed
        # (decode steps; the prefill's own under *_prefill in timings)
        out["moe_layers"] = len(self.cfg.expert_layers)
        for key in ("moe_pairs", "moe_pairs_routed", "moe_rows_computed",
                    "moe_experts_touched", "moe_load_max", "moe_calls",
                    "moe_rounds", "moe_calls_prefill", "moe_rounds_prefill"):
            out[key] = self.timings[key]
        # what a deployment's share looks like from here: the routed
        # experts whose weights this device holds, and the bytes one token
        # costs the pool (K and V of every attention layer, or one latent
        # row a layer)
        out["experts_held"] = (self.cfg.experts_local
                               if self.cfg.expert_layers else 0)
        pool = self.k_pool
        out["latent_bytes_per_token"] = (
            pool.shape[0] * pool.shape[2] * pool.dtype.itemsize
            if self.cfg.latent else 0)
        # K and V of the layers that keep pages (a token's bytes in the
        # pool), and a slot's rings of the window layers (bytes that do not
        # grow with the context)
        out["kv_bytes_per_token"] = 0 if self.cfg.latent else sum(
            a.nbytes // (a.shape[1] * a.shape[3])
            for a in (self.k_pool, self.v_pool))
        out["window_layers"] = len(self.cfg.window_layers)
        out["window_bytes_per_slot"] = sum(
            self.state[key].nbytes // (self.B + 1)
            for key in ("wk", "wv")) if self.cfg.window_layers else 0
        out["window_rows_live"] = self.timings["window_rows_live"]
        # generation by diffusion over blocks: the block's length (0: a
        # token a step) and the tokens a slot-forward has committed so far
        out["diffusion_block"] = self.cfg.diffusion_block
        forwards = sum(self.timings["bd_forwards_" + phase]
                       for phase in ("denoise", "write", "hold"))
        out["bd_tokens_per_forward"] = (
            round(self.timings["bd_tokens"] / forwards, 4) if forwards
            else 0.0)
        # ... and the forwards that wrote a finished block's K/V on the way
        out["bd_writes_fused"] = self.timings["bd_writes_fused"]
        if self.tenant_sheds:     # key appears only once tenancy acted
            out["tenant_sheds"] = dict(self.tenant_sheds)
        if self._draining:        # and these only once a drain began
            out["draining"] = True
            out["admissions_deferred"] = self.admissions_deferred
        return out

    def _can_admit(self, req: _Request) -> bool:
        # submit() bounds prompt+max_new by max_len, so need can never
        # exceed max_blocks — only pool availability gates admission.
        # Capacity counts cached-prefix reuse (matched blocks need no
        # allocation) and LRU-evictable refs==0 cache entries (the pool
        # reclaims them before refusing); without a prefix cache there
        # are neither.
        need = -(-(len(req.prompt) + req.max_new) // self.block_len)
        matched = set(self._pc_match(self._req_keys(req)))
        evictable = sum(1 for k in self._pc_lru if k not in matched)
        return (len(self.free) + evictable
                >= need - len(matched))

    def _shed_now(self) -> bool:
        """True while new prefill admissions should defer (the engine's
        failure domains are degraded, or the explicit probe says so)."""
        if self._shed_probe is not None:
            return bool(self._shed_probe())
        store = self.kv_store
        sup = getattr(getattr(store, "engine", None), "supervisor",
                      None) if store is not None else None
        if sup is None:
            return False
        # the serving loop is a supervision heartbeat while it sheds:
        # with admissions deferred there may be NO other I/O left to
        # carry the half-open probe, and tick() re-probes from the
        # last degraded span (time-gated inside)
        sup.tick()
        return bool(sup.degraded())

    def _engine_stats(self):
        """The shared StatCounters behind the KV store's engine (None
        without a store — serving counters then live on the server)."""
        store = self.kv_store
        return (getattr(getattr(store, "engine", None), "stats", None)
                if store is not None else None)

    def _note_shed(self, n: int) -> None:
        self.admissions_shed += n
        stats = self._engine_stats()
        if stats is not None:
            stats.add(serve_admissions_shed=n)

    # -- drain & handoff (io/handoff.py, docs/RESILIENCE.md) --------------

    @property
    def draining(self) -> bool:
        return self._draining

    def begin_drain(self) -> None:
        """Close the admission gate for the remainder of this server's
        life (drains are forward-only, like the phase machine driving
        them): queued prefills DEFER — they stay queued for session
        export, nothing is dropped — while in-flight decode keeps its
        slots and runs to completion."""
        self._draining = True

    def _note_drain_defer(self, n: int) -> None:
        self.admissions_deferred += n
        stats = self._engine_stats()
        if stats is not None:
            stats.add(handoff_deferred=n)

    def export_sessions(self, limit: int = 256,
                        pop: bool = False) -> List[dict]:
        """Export live session state for a handoff bundle: in-flight
        slots first (their decode progress is the expensive part), then
        the deferred queue, up to ``limit``.  Each entry carries the
        prompt token chain, the tokens already DELIVERED (``emitted``),
        the remaining ``max_new`` budget, the sampling params (seeded
        sampling is position-keyed, so the replacement's continuation
        is token-identical), and the session's NVMe prefix-store page
        keys so its KV restores instead of re-prefilling.

        ``pop`` removes exported sessions so the retiring server can
        reach ``idle`` — their results are now the replacement's to
        deliver."""
        self.cfg.require_causal("export_sessions (a session inside a "
                                "block would resume mid-denoising)")
        self.cfg.require_no_recurrent("export_sessions (the hand-off "
                                      "bundle holds K/V page keys only)")
        self.cfg.require_kv_pages("export_sessions (the hand-off bundle)")
        out: List[dict] = []
        taken_slots: List[int] = []
        taken_q: List[_Request] = []
        for i, r in enumerate(self.slots):
            if len(out) >= limit:
                break
            if r is None or r.max_new - len(r.out) < 1:
                continue          # retiring this step anyway
            out.append(self._export_one(r, emitted=list(r.out)))
            taken_slots.append(i)
        for r in self.queue:
            if len(out) >= limit:
                break
            out.append(self._export_one(r, emitted=[]))
            taken_q.append(r)
        if pop:
            for i in taken_slots:
                self._release_slot(i)
                self.slots[i] = None
            self.queue = [r for r in self.queue
                          if r not in taken_q]
        return out

    def _export_one(self, r: _Request, emitted: List[int]) -> dict:
        doc = {
            "rid": r.rid, "prompt": list(r.prompt),
            "emitted": emitted,
            "max_new": r.max_new - len(emitted),
            "eos_id": r.eos_id, "temperature": r.temperature,
            "top_p": r.top_p, "seed": int(r.seed),
            "tenant": (r.tenant.id if r.tenant is not None else None),
            "kv_keys": [],
        }
        store = self.kv_store
        if store is not None:
            try:
                doc["kv_keys"] = [k.hex() for k in store.chain_keys(
                    list(r.prompt) + emitted)]
            except Exception:
                doc["kv_keys"] = []
        return doc

    def _release_slot(self, slot: int) -> None:
        """The slot's blocks back to the pool, at retirement and when a
        drain-time session export vacates it: cache-registered blocks
        drop a ref (staying resident as evictable when it hits 0 — the
        next same-prefix request reuses them); private blocks go straight
        back to the free list."""
        for blk in self.blocks[slot]:
            if not self._pc_release(blk):
                self.free.append(blk)
        self.blocks[slot] = []
        self._table_dev = None

    # -- multi-tenant admission (docs/RESILIENCE.md) ----------------------

    def _tenant_config(self):
        if self._tenant_cfg is None:
            # the registry's config, not a fresh env read: an explicit
            # tenants.configure() (tests/bench) must govern here too
            from nvme_strom_tpu.io.tenants import get_registry
            self._tenant_cfg = get_registry().config
        return self._tenant_cfg

    def _bucket(self, tenant) -> TokenBucket:
        """The tenant's admission token bucket, built on first sight
        from its own rate/burst (spec) or the STROM_TENANT_* defaults."""
        b = self._buckets.get(tenant.id)
        if b is None:
            cfg = self._tenant_config()
            rate = tenant.rate if tenant.rate > 0 else cfg.default_rate
            burst = (tenant.burst if tenant.burst > 0
                     else cfg.default_burst)
            b = TokenBucket(rate, burst)
            self._buckets[tenant.id] = b
        return b

    def _admit_tenants(self) -> list:
        """Tier-aware admission: under backlog pressure (more queued
        than free slots) only the BEST SLO tier present may admit this
        step — worse tiers are shed (they stay queued, re-checked next
        step, exactly the degraded-defer semantics) and counted per
        tenant.  Each admission also spends a token from its tenant's
        bucket; an empty bucket sheds that request without blocking the
        tenants behind it.  Within the admissible set the queue stays
        strict FIFO, and a ``_can_admit`` refusal still STOPS the scan
        — the pool's no-starvation order is unchanged."""
        free = sum(s is None for s in self.slots)
        plans: list = []
        if not free:
            return plans
        pressure = len(self.queue) > free
        best = None
        if pressure:
            best = min(tier_rank(r.tenant.tier) for r in self.queue
                       if r.tenant is not None)
        shed: Dict[str, int] = {}
        slots = iter([s for s in range(self.B)
                      if self.slots[s] is None])
        i = 0
        while free and i < len(self.queue):
            req = self.queue[i]
            t = req.tenant
            if t is not None:
                if pressure and tier_rank(t.tier) > best:
                    shed[t.id] = shed.get(t.id, 0) + 1
                    i += 1
                    continue
                if not self._bucket(t).try_take():
                    shed[t.id] = shed.get(t.id, 0) + 1
                    i += 1
                    continue
            if not self._can_admit(req):
                break
            plans.append(self._admit_plan(next(slots),
                                          self.queue.pop(i)))
            free -= 1
        if shed:
            self._note_tenant_shed(shed)
        return plans

    def _note_tenant_shed(self, shed: Dict[str, int]) -> None:
        """Account one step's tenant sheds: server + engine counters,
        the per-tenant breakdown, and the storm trigger's window."""
        n = sum(shed.values())
        self.admissions_shed += n
        stats = self._engine_stats()
        if stats is not None:
            stats.add(tenant_admissions_shed=n)
        for tid, k in shed.items():
            self.tenant_sheds[tid] = self.tenant_sheds.get(tid, 0) + k
            self._storm_window[tid] = (self._storm_window.get(tid, 0)
                                       + k)
            if stats is not None:
                stats.add_tenant_stat(tid, admissions_shed=k)
        self._maybe_storm_dump(stats)

    def _maybe_storm_dump(self, stats) -> None:
        """Flight-record a misbehaving tenant: once a tenant's sheds
        since the last dump cross ``STROM_TENANT_STORM_SHEDS``, capture
        the op ring under ``reason=tenant_storm`` with the per-tenant
        breakdown — the post-mortem wants WHO stormed and who paid,
        not just that p99 moved.  Per-reason rate limiting inside
        flightrec keeps a sustained storm from spamming dumps."""
        thresh = self._tenant_config().storm_sheds
        hot = [t for t, k in self._storm_window.items() if k >= thresh]
        if not hot:
            return
        for tid in hot:
            self._storm_window[tid] = 0
        store = self.kv_store
        flight = (getattr(getattr(store, "engine", None), "flight",
                          None) if store is not None else None)
        if flight is None:
            return
        path = flight.dump("tenant_storm",
                           extra={"tenants": hot,
                                  "sheds": dict(self.tenant_sheds),
                                  "queued": len(self.queue)})
        # count only PUBLISHED dumps: a sustained storm re-arms the
        # window every few steps, but per-reason rate limiting inside
        # flightrec swallows most of those triggers
        if path is not None and stats is not None:
            stats.add(tenant_storm_dumps=1)
            for tid in hot:
                stats.add_tenant_stat(tid, storm_dumps=1)

    def _run_step(self):
        """One batched decode step → next-token device array."""
        # write targets from the HOST position mirror — no device sync
        # sits in front of the step launch
        blk = jnp.asarray(
            [(self.blocks[b][self._pos_h[b] // self.block_len]
              if self.blocks[b] else self._trash)
             for b in range(self.B)], jnp.int32)
        off = self.pos % self.block_len
        # the table entries each slot's walk reads (a free slot is handed
        # ``pos`` 0: one entry), and the grid steps a layer's call makes of
        # them
        walks = [self._pos_h[b] // self.block_len + 1
                 if self.slots[b] is not None else 0 for b in range(self.B)]
        self.timings["attn_blocks_live"] += sum(walks)
        self.timings["attn_blocks_table"] += self.B * self.max_blocks
        if self.cfg.latent:
            from nvme_strom_tpu.ops.mla_attention import GROUP
            self.timings["attn_grid_steps"] += self.B * -(
                -max(max(walks), 1) // GROUP)
        else:
            self.timings["attn_grid_steps"] += sum(max(n, 1) for n in walks)
        if self.cfg.window_layers:
            self.timings["window_rows_live"] += sum(
                min(self._pos_h[b] + 1, self.cfg.window)
                for b in range(self.B) if self.slots[b] is not None)
        # an expert layer routes every slot that holds blocks (a free one
        # is told by its trash block)
        self.timings["moe_pairs_routed"] += (
            sum(1 for blks in self.blocks if blks)
            * self.cfg.expert_top_k * len(self.cfg.expert_layers))
        recur = () if self.state is None else (
            self.state,
            jnp.asarray([b if self.slots[b] is not None else self.B
                         for b in range(self.B)], jnp.int32))
        nxt, self.k_pool, self.v_pool, self.state = _paged_step(
            self.params, self.cfg, self.tok, self.k_pool, self.v_pool,
            blk, off, self._table(), self.pos, self.temp, self.topp,
            self.seed, *recur)
        return nxt

    def _step_blocks(self, k_steps: int, active_slots: List[int],
                     finished: dict) -> dict:
        """``_step_many`` past its admissions on a diffusion server: up to
        ``k_steps`` forwards of R rows a slot back to back, ONE readback of
        their reports (``bd_select``'s ``info``), and the host's replay.

        A forward yields 0 to R tokens a slot, in no left-to-right order
        inside the block, so what the host counts down is FORWARDS: a slot
        has at most (blocks left) x (denoising steps) to go — a finished
        block's clean K/V rows ride the next block's first forward, and a
        request's last block is never written: nothing reads it (the
        prefix cache and the store hold prompt blocks only) —, and the
        batch is as long as the slot with most.  The device tells the
        slots apart itself (``_paged_step``): a slot past its last block
        holds position — it writes to the trash block and changes nothing —
        so positions never pass ``ceil((prompt + max_new) / R) * R``, which
        the admission's reservation ``ceil((prompt + max_new) / block)``
        covers (R divides the block).  A finished block reaches
        ``req.out`` whole, at the readback of the forward that committed
        its last position, cut at ``max_new`` and at an EOS; ``t_first`` is
        the first readback that shows a commit.  ``bd_writes_fused`` counts
        the slot-forwards whose first R rows wrote a finished block."""
        import numpy as np
        R, bk = self.R, self.block_len
        per_block = (R if self.cfg.diffusion_threshold > 0
                     else min(self.cfg.diffusion_steps or R, R))
        left = max(-(-(len(self.slots[b].prompt) + self.slots[b].max_new)
                     // R) - self._pos_h[b] // R for b in active_slots)
        k_eff = max(1, min(k_steps, left * per_block))
        # an expert layer routes every slot that takes part; a free slot is
        # told by its trash block (the step reads the others' off the table)
        blk = jnp.asarray([0 if self.blocks[b] else self._trash
                           for b in range(self.B)], jnp.int32)
        infos = []
        t0 = time.monotonic()
        with self._span("strom.serve.dispatch", steps=k_eff, rows=2 * R):
            for _ in range(k_eff):
                # (``state``: the expert layers' counters, donated; no layer
                # of such a config keeps a row a slot, so ``sidx`` is unread)
                (info, self.k_pool, self.v_pool, self.state, self.tok,
                 self.pos, self.bd) = _paged_step(
                    self.params, self.cfg, self.tok, self.k_pool,
                    self.v_pool, blk, blk, self._table(), self.pos,
                    self.temp, self.topp, self.seed, self.state, blk,
                    bd=self.bd)
                infos.append(info)
        self.timings["dispatch_s"] += time.monotonic() - t0
        t0 = time.monotonic()
        with self._span("strom.serve.readback", steps=k_eff, first=0):
            info_h, moe_h = jax.device_get((      # the ONE readback
                infos, self.state.get("moe") if self.state else None))
        self.timings["readback_s"] += time.monotonic() - t0
        self.timings["steps"] += k_eff
        if moe_h:
            self._note_moe(moe_h, k_eff)
        with self._span("strom.serve.replay") as replay_span:
            t_now = time.monotonic()
            info_h = np.stack(info_h)[:, active_slots]    # (k, slots, 5+2R)
            pos, phase, new, done, fused = (info_h[..., i]
                                            for i in range(BD_INFO))
            busy = phase != BD_HOLD
            n_busy, n_fused = int(busy.sum()), int(fused.sum())
            counts = {"bd_forwards_denoise": n_busy,
                      # (no forward only writes a finished block)
                      "bd_forwards_write": 0,
                      "bd_forwards_hold": busy.size - n_busy,
                      "bd_writes_fused": n_fused,
                      "bd_tokens": int(new.sum()),
                      "bd_rows": R * (n_busy + n_fused),
                      # the table entries each taking slot's walk reads, the
                      # grid steps a layer's call makes of them (one for a
                      # slot that holds or is free), the pairs routed
                      "attn_blocks_live": int(
                          ((pos + R - 1) // bk + 1)[busy].sum()),
                      "attn_blocks_table": k_eff * self.B * self.max_blocks,
                      "moe_pairs_routed": R * (n_busy + n_fused)
                      * self.cfg.expert_top_k * len(self.cfg.expert_layers)}
            counts["attn_grid_steps"] = (counts["attn_blocks_live"]
                                         + k_eff * self.B - n_busy)
            for key, n in counts.items():
                self.timings[key] += n
            for i, slot in enumerate(active_slots):
                req = self.slots[slot]
                if req.t_first is None and new[:, i].any():
                    req.t_first = t_now     # first commit DELIVERED
                P = len(req.prompt)
                for j in np.nonzero(done[:, i])[0]:
                    if self.slots[slot] is None:
                        break       # retired at an earlier sub-step: its
                                    # surplus blocks are discarded
                    at = int(pos[j, i])
                    self._pos_h[slot] = at + R
                    for r in range(max(P - at, 0), R):
                        req.out.append(int(info_h[j, i, BD_INFO + r]))
                        req.steps.append(int(info_h[j, i, BD_INFO + R + r]))
                        ret = self._retire_or_keep(slot)
                        if ret:
                            finished[ret[0]] = ret[1]
                            break
            replay_span.set_metadata(
                finished=len(finished), tokens=counts["bd_tokens"],
                rows=counts["bd_rows"],
                denoise=counts["bd_forwards_denoise"],
                write=counts["bd_forwards_write"],
                hold=counts["bd_forwards_hold"],
                writes_fused=counts["bd_writes_fused"])
        return finished

    def _note_moe(self, moe_h: dict, steps: int) -> None:
        """The expert layers' device counters, as read back with a batch's
        tokens, into ``timings`` and ``moe_load``: what was added since the
        last reading."""
        import numpy as np
        now = jax.tree_util.tree_map(lambda a: np.asarray(a).view(np.uint32),
                                     moe_h)
        seen = self._moe_seen or jax.tree_util.tree_map(np.zeros_like, now)
        self._moe_seen = now
        n_layers = len(self.cfg.expert_layers)
        prefills = self.timings["prefill_calls"]
        for phase, sfx, calls in (
                ("decode", "", steps * n_layers),
                ("prefill", "_prefill",
                 (prefills - self._moe_prefills) * n_layers)):
            load = (now[phase]["load"] - seen[phase]["load"]).astype(np.int64)
            sums = (now[phase]["sums"] - seen[phase]["sums"]).astype(np.int64)
            self.timings["moe_calls" + sfx] += calls
            self.timings["moe_pairs" + sfx] += int(load.sum())
            for j, name in enumerate(_moe.SUMS):
                self.timings[f"moe_{name}{sfx}"] += int(sums[:, j].sum())
            if phase == "decode":
                self.moe_load = load if self.moe_load is None \
                    else self.moe_load + load
        self._moe_prefills = prefills

    def _ensure_params(self) -> None:
        """Resolve a demand-faulting param source on first use: every
        tensor not yet resident is faulted at ``decode`` class, ahead
        of the bulk-restore/warmup streams.  Tensors the background
        bulk thread already landed are returned from its claim table
        without touching NVMe again.  No-op (one attribute test) on
        the eager path."""
        if self.params is None and self._param_source is not None:
            self.params = self._param_source.materialize(klass="decode")

    def step(self) -> Dict[object, List[int]]:
        """Admit → one batched decode step → retire finished."""
        return self.step_many(1)

    def step_many(self, k_steps: int) -> Dict[object, List[int]]:
        """Admit → up to ``k_steps`` batched decode steps → ONE host
        readback → retire finished.  (A step is a forward of every active
        slot: one token a slot, or on a diffusion server R rows a slot and
        0 to R tokens — ``_step_blocks`` has what differs there.)

        The lookahead exists for high-latency links: the round-3
        on-silicon row served 43.6 tok/s against a 6,826 tok/s decode
        row on the same chip (verdict weak #6) because ``step()`` paid
        a blocking device→host readback per generated token.  Here the
        k sub-steps dispatch back to back and the (k, B) token stack
        crosses the link once.

        The tradeoff is the classic one: a request that hits EOS at
        sub-step j keeps decoding to the batch end — its surplus
        tokens are computed, then discarded by the host replay below.
        Surplus steps are SAFE: each slot's sub-steps are capped at
        its max_new remainder, so positions never pass the
        admission-time allocation of blocks, and a
        post-EOS write touches only the slot's own rows at positions
        the next occupant overwrites-before-attending.  Admission
        happens once per batch, so a freed slot idles at most
        ``k_steps - 1`` sub-steps."""
        with self._span("strom.serve.step", k=k_steps):
            return self._step_many(k_steps)

    def _step_many(self, k_steps: int) -> Dict[object, List[int]]:
        self._ensure_params()
        finished: Dict[object, List[int]] = {}
        if self._finished_carry:
            # retirements completed by _drain_pending_first while a
            # previous call unwound — deliver them now, exactly once
            finished.update(self._finished_carry)
            self._finished_carry.clear()
        t0 = time.monotonic()
        # plan every admission first (capacity decisions in the same
        # sequential order as per-slot admission), batch-restore ALL
        # their store-resident prefix pages in ONE decode-class read
        # batch, then finish each admission — dispatch-only: the first
        # token stays on device (in _pending_first) and retirement is
        # decided after the batch readback below, so admission
        # pipelines with the decode dispatches instead of paying a
        # link round trip per request
        with self._span("strom.serve.plan") as plan_span:
            plans = self._plan_admissions()
            plan_span.set_metadata(admitted=len(plans))
        # everything from here to the batch readback runs with
        # _pending_first possibly non-empty; an exception must not
        # leak those entries into the next call (first tokens would
        # replay a full batch LATE, after newer tokens) — the except
        # path drains them in generation order before re-raising
        pending = None
        try:
            restored = (self._restore_prefixes(plans)
                        if plans and self.kv_store is not None else {})
            for group in self._form_groups(plans, restored):
                self._finish_traced(group,
                                    restored.get(group[0]["slot"], {}))
            self.timings["admit_s"] += time.monotonic() - t0
            active_slots = [i for i, r in enumerate(self.slots)
                            if r is not None]
            if not active_slots:
                return finished
            if self.bd is not None:
                return self._step_blocks(k_steps, active_slots, finished)
            # steps each slot may still take: positions must never pass
            # the s + max_new blocks admission reserved.  A deferred
            # first token counts against max_new; a first-token EOS
            # decodes surplus sub-steps (safe — discarded at replay,
            # writes stay in the slot's own reservation, same invariant
            # as mid-batch EOS).
            pending_slots = {s for s, _ in self._pending_first}
            left = {b: (self.slots[b].max_new - len(self.slots[b].out)
                        - (1 if b in pending_slots else 0))
                    for b in active_slots}
            k_eff = max(1, min(k_steps, max(left.values())))
            toks: List = []
            stepped: List[List[int]] = []
            t0 = time.monotonic()
            with self._span("strom.serve.dispatch", steps=k_eff):
                for j in range(k_eff):
                    stepping = [b for b in active_slots if left[b] > j]
                    if not stepping:
                        break
                    mask = jnp.asarray([left.get(b, 0) > j
                                        for b in range(self.B)])
                    nxt = self._run_step()
                    # the step ingested tok at pos for every stepping
                    # slot; exhausted slots hold position (their next
                    # step rewrites the same row — self-overwrite, never
                    # another slot's)
                    self.pos = jnp.where(mask, self.pos + 1, self.pos)
                    self.tok = jnp.where(mask, nxt, self.tok)
                    for b in stepping:          # host mirror of pos
                        self._pos_h[b] += 1
                    toks.append(nxt)
                    stepped.append(stepping)
            self.timings["dispatch_s"] += time.monotonic() - t0
            t0 = time.monotonic()
            pending, self._pending_first = self._pending_first, []
            with self._span("strom.serve.readback", steps=len(toks),
                            first=len(pending)):
                first_h, tok_h, moe_h = jax.device_get((  # the ONE readback
                    [v for _, v in pending],
                    jnp.stack(toks) if toks else None,
                    self.state.get("moe") if self.state else None))
        except BaseException:
            if pending:
                # the batch readback itself failed AFTER the swap
                # emptied _pending_first: re-stash the entries so the
                # drain below still owns them — otherwise the deferred
                # first tokens would be silently dropped, breaking
                # _drain_pending_first's restore-on-failure contract
                self._pending_first = pending
            self._drain_pending_first()
            raise
        self.timings["readback_s"] += time.monotonic() - t0
        self.timings["steps"] += len(toks)
        if moe_h:
            self._note_moe(moe_h, len(toks))
        # replay in generation order: deferred first tokens precede
        # this batch's sub-step tokens for their slots
        with self._span("strom.serve.replay") as replay_span:
            t_now = time.monotonic()
            for (slot, _), v in zip(pending, first_h):
                self.slots[slot].t_first = t_now  # first token DELIVERED
                self.slots[slot].out.append(int(v))
                ret = self._retire_or_keep(slot)
                if ret:
                    finished[ret[0]] = ret[1]
            for j, stepping in enumerate(stepped):
                for slot in stepping:
                    if self.slots[slot] is None:
                        continue    # retired at an earlier sub-step:
                                    # its surplus tokens are discarded
                    self.slots[slot].out.append(int(tok_h[j][slot]))
                    ret = self._retire_or_keep(slot)
                    if ret:
                        finished[ret[0]] = ret[1]
            replay_span.set_metadata(finished=len(finished))
        return finished

    #: padded prompt rows one call admits ALONE IN THEIR PROGRAMS while some
    #: slot is decoding: a call's prefills run before its decode steps, so
    #: without a bound a burst of long prompts holds every decoding slot for
    #: seconds (21 admissions of 1k-8k rows: 3.3 s in one call, PERF.md §6,
    #: PR 32).  Two 8,192-row prompts' worth.  Prompts that share a program
    #: (models/admission.py) are not counted: admitted together is what
    #: makes them cheap; one that runs alone loses nothing by waiting a
    #: call.  Always at least one prompt a call, and an empty server fills
    #: at once: there is nobody to hold up
    ADMIT_ROWS = 16384

    def _plan_admissions(self) -> list:
        """This step's admission plans (capacity decisions only), or
        none while the gate is closed.  Free slots fill in queue order;
        beside decoding slots, until the prompts that go alone in their
        programs pass ``ADMIT_ROWS`` padded rows."""
        plans = []
        # load shedding (docs/RESILIENCE.md "failure domains"): while
        # the engine behind the KV store is degraded, new prefills
        # DEFER — they stay queued (re-checked every step; nothing
        # fails) and in-flight decode keeps its slots, so the sick
        # device serves the work it already owes instead of taking more
        if self.queue and self._draining:
            # drain mode (io/handoff.py): the gate is closed for NEW
            # prefills only — queued requests hold for export to the
            # replacement's bundle while in-flight slots run out
            self._note_drain_defer(min(sum(s is None
                                           for s in self.slots),
                                       len(self.queue)))
        elif self.queue and self._shed_now():
            self._note_shed(min(sum(s is None for s in self.slots),
                                len(self.queue)))
        elif any(r.tenant is not None for r in self.queue):
            # at least one queued request carries a tenant: tier-aware
            # admission (sheds by tier under pressure, token buckets);
            # an all-untagged queue — STROM_TENANTS=0 always — never
            # reaches this branch and runs the loop below verbatim
            plans = self._admit_tenants()
        else:
            rows = 0
            decoding = any(r is not None for r in self.slots)
            for slot in range(self.B):
                if (self.slots[slot] is None and self.queue
                        and self._can_admit(self.queue[0])):
                    m = -(-len(self.queue[0].prompt)
                          // self.block_len) * self.block_len
                    if _adm.width_for(m, self._group_rows) == 1:
                        rows += m
                        if decoding and plans and rows > self.ADMIT_ROWS:
                            break   # the rest wait one call, queued
                    plans.append(self._admit_plan(slot,
                                                  self.queue.pop(0)))
        return plans

    def run(self, lookahead: int = 1) -> Dict[object, List[int]]:
        """Drain the queue: step until every request finishes.

        ``lookahead``: decode sub-steps per host readback (see
        :meth:`step_many`) — 1 reproduces the per-token readback;
        8-16 amortizes a high-latency link.

        Raises RuntimeError instead of spinning when the queue head can
        NEVER be admitted (e.g. a request whose worst case
        exceeds the whole pool) and nothing is in flight to free
        capacity."""
        if lookahead < 1:
            raise ValueError(f"lookahead must be >= 1, got {lookahead}")
        results: Dict[object, List[int]] = {}
        while not self.idle:
            if (self._draining
                    and all(s is None for s in self.slots)):
                # only drain-deferred queue entries remain; they belong
                # to the handoff bundle now — spinning on the closed
                # admission gate would never converge
                break
            if (self.queue and all(s is None for s in self.slots)
                    and not self._can_admit(self.queue[0])):
                raise RuntimeError(
                    f"request {self.queue[0].rid!r} cannot ever be "
                    f"admitted (needs more capacity than the server "
                    f"has) and no in-flight work can free any")
            results.update(self.step_many(lookahead))
        return results

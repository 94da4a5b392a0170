"""Autoregressive decoding with a KV cache for the flagship transformer.

The reference is a storage engine with no inference concepts (SURVEY.md
§1) — this module completes the model family the framework ships: the
weights land in HBM via the lazy safetensors loader (parallel/weights.py)
and serve from there.

TPU-first choices: the whole generation loop is ONE ``lax.scan`` under
jit (static length, no Python control flow); the cache is a pytree of
preallocated ``(n_layers, batch, n_kv_heads, max_len, head_dim)`` arrays
updated with ``lax.dynamic_update_slice`` (static shapes, in-place under
donation); GQA keeps the cache at kv-head width and expands at use; under
a dp×tp mesh the cache shards over heads like the attention weights, so
decode runs SPMD with the same annotations as training.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from nvme_strom_tpu.models.transformer import (
    wmat,
    TransformerConfig, add_residual, attention, embed_tokens,
    expand_gqa, gate_heads, lm_logits, mlp, norm_in, norm_out, qkv_project,
    qkvg_project, rms_norm, valid_rows)
from nvme_strom_tpu.models import mla as _mla
from nvme_strom_tpu.models import moe as _moe


def init_cache(cfg: TransformerConfig, batch: int, max_len: int) -> Dict:
    """Empty KV cache.  ``pos`` is the number of valid positions.

    Contract: callers must not push more than ``max_len`` total positions
    through prefill+decode_step — past that, dynamic_update_slice clamps
    and silently overwrites the last slot (generate() sizes the cache as
    prompt_len + max_new_tokens, exactly enough)."""
    shape = (len(cfg.attn_layers), batch, cfg.n_kv_heads, max_len,
             cfg.head_dim)
    if cfg.latent:
        # one latent row a token a layer (models/mla.py) where the others
        # keep K and V: "k" holds it as one head of latent_width, "v" nothing
        shape = shape[:2] + (1, max_len, cfg.latent_width)
    cache = {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": None if cfg.latent else jnp.zeros(shape[:-1] + (cfg.v_dim,),
                                               cfg.dtype),
        "pos": jnp.zeros((), jnp.int32),
    }
    if cfg.window_layers:
        # a window layer's K/V apart (its own KV-head count), dense like the
        # rest here: only a server bounds them to a ring (models/serving.py)
        wshape = (len(cfg.window_layers), batch,
                  cfg.kv_heads(cfg.window_layers[0]), max_len)
        cache["wk"] = jnp.zeros(wshape + (cfg.head_dim,), cfg.dtype)
        cache["wv"] = jnp.zeros(wshape + (cfg.v_dim,), cfg.dtype)
    if cfg.recurrent_layers:
        # K/V for the attention layers only; the recurrent layers carry a
        # fixed-size state instead (models/ssm.py)
        from nvme_strom_tpu.models.ssm import init_state
        cache["ssm"] = init_state(cfg, batch)
    return cache


def cache_shardings(mesh, tp_axis: str = "tp", dp_axis: str = "dp"):
    """Cache sharded like attention: batch over dp, kv heads over tp."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from nvme_strom_tpu.parallel.shardings import prune_spec
    kv = NamedSharding(mesh, prune_spec(
        P(None, dp_axis, tp_axis, None, None), mesh))
    return {"k": kv, "v": kv,
            "pos": NamedSharding(mesh, prune_spec(P(), mesh))}


#: a dense MLP over more rows than this (one prompt's: the programs the
#: accepted cells compile hold at most 8,192) runs in chunks of rows
DENSE_MLP_ROWS, DENSE_MLP_CHUNK = 8192, 4096


def mlp_block(h, p, L, cfg, valid=None, calls=None):
    """The MLP of one layer, as the config's per-layer description says —
    shared by every decode and serving layer loop, so layer-kind routing
    can never diverge between them.

    An expert layer (``mlp_kind`` "experts") computes every selected pair
    (``moe.expert_mlp``); ``valid`` (b, m) bool marks the rows that are
    real (None: all) and ``calls``, a list, collects the layer's (counts,
    work) for ``moe.add_load``.  The capacity-dropping GShard layer
    runs only for a config that places it by ``moe_every``; a config that
    describes a model's own router gets an error, never a dropped token."""
    kind = cfg.mlp_kind(int(L.split(".")[1]))
    if kind == "experts":
        out, counts, work = _moe.expert_mlp(h, p, L, cfg, valid)
        if calls is not None:
            calls.append((counts, work))
        return out
    if kind == "gshard":
        if cfg.router_kind != "softmax" or cfg.router_bias or cfg.d_expert:
            raise NotImplementedError(
                "the capacity-dropping GShard layer (moe_every) routes by "
                "softmax at d_ff and drops pairs over its capacity; a "
                "config with a sigmoid router, a selection bias or an "
                "expert width names its expert layers in mlp_kinds")
        out, _ = _moe.moe_mlp(h, p, L, cfg)
        return out
    b, m, d = h.shape
    if m > DENSE_MLP_ROWS and m % DENSE_MLP_CHUNK == 0:
        # a prompt this long walks the MLP in chunks of rows: its float32
        # gate alone is m x d_ff x 4 bytes (1 GiB at 16,384 x 16,384)
        chunks = h.reshape(b, m // DENSE_MLP_CHUNK, DENSE_MLP_CHUNK, d)
        return lax.map(lambda c: mlp(c, p, L),
                       chunks.swapaxes(0, 1)).swapaxes(0, 1).reshape(b, m, d)
    return mlp(h, p, L)


_mlp_block = mlp_block      # original (private) name, kept for callers


def prefill(params: Dict, tokens: jax.Array, cfg: TransformerConfig,
            cache: Dict, last: Optional[int] = None) -> tuple[jax.Array,
                                                              Dict]:
    """Run the prompt through the model, filling cache[0:seq].

    tokens (b, s) int32 → (logits (b, vocab) f32 at position ``last``
    (default s-1), cache).  ``last`` serves right-padded prompts
    (bucketed serving admission): causality keeps positions <= last
    unaffected by the padding, and the pad rows' cache entries are
    dead — the consumer overwrites them before its mask ever exposes
    them.
    """
    b, s = tokens.shape
    if (cfg.recurrent_layers or cfg.expert_layers or cfg.latent
            or cfg.stated_kv):
        # block_step from an empty cache IS the prefill, state included
        # (and it is what tells an expert layer which rows are padding)
        return block_step(params, tokens, cfg, cache,
                          last=s - 1 if last is None else last,
                          n_valid=None if last is None else last + 1)
    x = embed_tokens(params, cfg, tokens)
    positions = jnp.arange(s, dtype=jnp.float32)
    for i in range(cfg.n_layers):
        L = f"layers.{i}."
        h = norm_in(x, params[L + "attn_norm"], cfg)
        a, k, v = attention(h, params, L, cfg, positions=positions,
                            return_kv=True)
        cache["k"] = lax.dynamic_update_slice(
            cache["k"], k[None].astype(cfg.dtype), (i, 0, 0, 0, 0))
        cache["v"] = lax.dynamic_update_slice(
            cache["v"], v[None].astype(cfg.dtype), (i, 0, 0, 0, 0))
        x = add_residual(x, norm_out(a, params[L + "attn_norm"], cfg), cfg)
        h = norm_in(x, params[L + "mlp_norm"], cfg)
        f = norm_out(_mlp_block(h, params, L, cfg), params[L + "mlp_norm"],
                     cfg)
        x = add_residual(x, f, cfg).astype(cfg.dtype)
    cache["pos"] = jnp.asarray(s, jnp.int32)
    x = rms_norm(x[:, s - 1 if last is None else last],
                 params["final_norm"], cfg.norm_eps)
    return lm_logits(params, cfg, x), cache


def decode_step(params: Dict, token: jax.Array, cfg: TransformerConfig,
                cache: Dict, cache_attn=None) -> tuple[jax.Array, Dict]:
    """One incremental step: token (b,) int32 at position cache['pos'].

    Returns (next-token logits (b, vocab) f32, updated cache).
    Contract: cache['pos'] must be < the cache's max_len (see init_cache).
    ``cache_attn(q, k_cache, v_cache, pos) -> (b, h, 1, d)`` swaps the
    attention inner (e.g. ops/decode_attention.make_decode_attn — the
    fused Pallas kernel); it receives the cache at kv-head width.
    Default is a masked dense einsum over the GQA-expanded cache.
    """
    cfg.require_causal("decode_step (one token a step)")
    if cache_attn is None:
        # the dense path IS block_step with m=1 — one masked-attention
        # implementation to maintain
        logits, cache = block_step(params, token[:, None], cfg, cache)
        return logits[:, 0], cache
    cfg.require_no_recurrent("decode_step with a cache_attn kernel")
    cfg.require_kv_pages("decode_step with a cache_attn kernel")
    b = token.shape[0]
    pos = cache["pos"]
    x = embed_tokens(params, cfg, token[:, None])              # (b, 1, d)
    positions = pos.astype(jnp.float32)[None]
    for i in range(cfg.n_layers):
        L = f"layers.{i}."
        h = norm_in(x, params[L + "attn_norm"], cfg)
        q, k, v = qkv_project(h, params, L, cfg,       # (b, nkv, 1, hd)
                              positions=positions)
        cache["k"] = lax.dynamic_update_slice(
            cache["k"], k[None].astype(cfg.dtype), (i, 0, 0, pos, 0))
        cache["v"] = lax.dynamic_update_slice(
            cache["v"], v[None].astype(cfg.dtype), (i, 0, 0, pos, 0))
        # kv-width cache straight into the kernel: the GQA query
        # group maps to its kv head inside (no expanded HBM copy)
        a = cache_attn(q, cache["k"][i], cache["v"][i], pos)
        a = a.transpose(0, 2, 1, 3).reshape(b, 1, -1)
        a = a @ wmat(params, L + "wo", a.dtype)
        x = add_residual(x, norm_out(a, params[L + "attn_norm"], cfg), cfg)
        h = norm_in(x, params[L + "mlp_norm"], cfg)
        f = norm_out(_mlp_block(h, params, L, cfg), params[L + "mlp_norm"],
                     cfg)
        x = add_residual(x, f, cfg).astype(cfg.dtype)
    cache["pos"] = pos + 1
    x = rms_norm(x[:, 0], params["final_norm"], cfg.norm_eps)
    return lm_logits(params, cfg, x), cache


#: A layer's mixer by the scopes around its kernel's own: (its norm and input
#: projection, its output projection and residual add).  With ``strom.embed``,
#: ``strom.mlp`` (norm and residual add included) and ``strom.head`` they put
#: every operation of ``block_step`` and of the serving step
#: (``serving.paged_logits``) under one family — the first ``strom.<family>``
#: of its scope path, which the benchmark reads device time by
#: (docs/OBSERVABILITY.md has the table).  A scope is metadata on the lowered
#: program and costs nothing at run time.
MIXER_SCOPES = {"attention": ("strom.attn.proj", "strom.attn.out"),
                "window": ("strom.attn.proj", "strom.attn.out"),
                "mamba": ("strom.ssm.proj", "strom.ssm.out"),
                "gdn": ("strom.ssm.proj", "strom.ssm.out"),
                "conv": ("strom.conv", "strom.conv")}


def cache_attention(q, ck, cv, limit, cfg: TransformerConfig):
    """Masked attention of an m-row query block over a live KV cache —
    the ONE dense cache-attention implementation (block_step, and the
    per-row-position serving step, models/serving.py).

    q (b, nh, m, hd); ck/cv kv-width (b, nkv, S, hd); limit (b, m):
    row t of batch b attends cache positions <= limit[b, t].
    Returns (b, nh, m, hd)."""
    S = ck.shape[2]
    cke = expand_gqa(ck, cfg)
    cve = expand_gqa(cv, cfg)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, cke,
                        preferred_element_type=jnp.float32)
    if cfg.attn_scale is None:
        scores = scores / jnp.sqrt(jnp.float32(cfg.head_dim))
    else:
        scores = scores * jnp.float32(cfg.attn_scale)
    valid = jnp.arange(S)[None, None, None, :] <= limit[:, None, :, None]
    scores = jnp.where(valid, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(cve.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, cve)


#: a block of more rows than this (one prompt's: the accepted cells' hold at
#: most 8,192) goes through a stated-geometry layer's attention in chunks of
#: query rows: q alone is m x heads x 192 values in three layouts on its way
#: to the kernel (1.25 GiB at 16,384 rows)
ATTN_ROWS, ATTN_CHUNK = 8192, 4096


def ring_rows(dense, n, rows: int):
    """What a window layer's ring keeps of a sequence: ``dense`` (b, nkv, S,
    d) at positions 0 .. S - 1 and ``n`` (b,) each sequence's length -> (b,
    nkv, rows, d), ring row r the LAST position <= n - 1 that is r modulo
    ``rows``.  Only the last ``window`` of them are ever read; what lies
    below position 0 (a prompt shorter than the ring) is some row of the
    prompt, masked by the kernel's lower bound."""
    r = jnp.arange(rows)
    at = (n[:, None] - 1) - ((n[:, None] - 1 - r[None]) % rows)
    return jnp.take_along_axis(
        dense, jnp.clip(at, 0, dense.shape[2] - 1)[:, None, :, None], axis=2)


def _blocked_attention(h, params, L, cfg, k_l, v_l, pos, win):
    """One stated-geometry attention layer of ``block_step``: h (b, m, d),
    already normed, -> (the layer's output (b, m, d), W_o applied, and the
    layer's two caches (b, kv_heads, S, hd | vd) with the block's rows at
    ``pos ..``).  The attention is the blocked kernel
    (ops/kv_prefill.py): no (heads, m, S) score tensor, a band and the sink
    for a window layer (``win``).  A long block runs as a scan over chunks
    of its rows — each chunk's K and V go into the cache, then its queries
    attend over the cache up to their own rows: the kernel takes the first
    query row's position as data, so a chunk is a block behind a prefix."""
    from nvme_strom_tpu.ops.kv_prefill import kv_prefill_attention
    cfg.require_causal("the blocked prefill kernel (ops/kv_prefill.py)")
    b, m, d = h.shape
    before, after = MIXER_SCOPES["attention"]
    scale = cfg.head_dim ** -0.5 if cfg.attn_scale is None else cfg.attn_scale

    def chunk(carry, hc_first):
        k_l, v_l = carry
        hc, first = hc_first
        rows = hc.shape[1]
        with jax.named_scope(before):
            q, k, v, g = qkvg_project(
                hc, params, L, cfg, positions=first.astype(jnp.float32)
                + jnp.arange(rows, dtype=jnp.float32))
        with jax.named_scope("strom.attn.window" if win
                             else "strom.attn.paged"):
            k_l = lax.dynamic_update_slice(k_l, k.astype(cfg.dtype),
                                           (0, 0, first, 0))
            v_l = lax.dynamic_update_slice(v_l, v.astype(cfg.dtype),
                                           (0, 0, first, 0))
            a = kv_prefill_attention(
                q, k_l, v_l, first, scale=scale,
                window=cfg.window if win else 0, sink=params.get(L + "sink"))
        with jax.named_scope(after):
            a = gate_heads(a.transpose(0, 2, 1, 3).reshape(b, rows, -1), g)
            a = a @ wmat(params, L + "wo", a.dtype)
        return (k_l, v_l), a

    carry = (k_l, v_l)
    if m > ATTN_ROWS and m % ATTN_CHUNK == 0:
        n = m // ATTN_CHUNK
        carry, a = lax.scan(chunk, carry, (
            h.reshape(b, n, ATTN_CHUNK, d).swapaxes(0, 1),
            pos + ATTN_CHUNK * jnp.arange(n, dtype=jnp.int32)))
        a = a.swapaxes(0, 1).reshape(b, m, d)
    else:
        carry, a = chunk(carry, (h, pos))
    return (a,) + carry


def block_step(params: Dict, tokens: jax.Array, cfg: TransformerConfig,
               cache: Dict, last=None, n_valid=None
               ) -> tuple[jax.Array, Dict]:
    """Multi-token incremental step: tokens (b, m) int32 enter the cache
    at positions pos..pos+m-1 and every position gets logits.

    Row t of the block attends to the whole cache up to pos+t (causal
    within the block, full history before it) — the verify forward of
    speculative decoding, and the general "ingest a block mid-stream"
    primitive.  Under ``cfg.diffusion_block`` Bl the mask is BLOCK-causal:
    row t sees up to the end of its own diffusion block, ``((pos + t) // Bl
    + 1) * Bl - 1``, blocks counted from position 0 (the later rows of its
    block too; with the rows of a half-filled last block as right padding,
    the whole blocks before it see none of them).  Returns (logits (b, m,
    vocab) f32, cache with pos += m).  Contract: pos + m <= max_len.

    ``last``: project lm_head at only this row → logits (b, vocab) —
    admission-style callers that need one next-token distribution skip
    m-1 useless vocab projections (a 128k-vocab lm_head over thousands
    of pad rows is real FLOPs).  One row for every sequence, (), or each
    sequence's own, (b,): a group of prompts of unequal lengths.

    ``n_valid``, () or (b,) likewise: rows from it on are right padding (a
    sequence with 0 is all padding: it is routed nowhere and its state
    comes out as it went in).  Attention needs no
    telling (pad rows sit past every valid row's mask and are overwritten
    before a mask reaches them); a recurrent layer does — its state and
    conv tail (``cache["ssm"]``) stop at row ``n_valid - 1`` — and an
    expert layer routes only the rows before it.  ``cache["moe"]``, where
    a caller put ``moe.load_counters``, comes back with this call added.
    """
    b, m = tokens.shape
    pos = cache["pos"]
    with jax.named_scope("strom.embed"):
        x = embed_tokens(params, cfg, tokens)
        positions = (pos.astype(jnp.float32)
                     + jnp.arange(m, dtype=jnp.float32))
        # row t sees cache positions <= pos + t (same limit for every row)
        limit = jnp.broadcast_to(pos + jnp.arange(m), (b, m))
        if cfg.diffusion_block:
            # ... or, block-causal, up to the end of its diffusion block
            limit = (limit // cfg.diffusion_block + 1) \
                * cfg.diffusion_block - 1
        valid = (valid_rows(n_valid, b, m)
                 if n_valid is not None and cfg.expert_layers else None)
    ssm = cache.get("ssm")
    states, tails = (list(ssm["s"]), list(ssm["conv"])) if ssm else ([], [])
    calls = []            # the expert layers' (counts, work)
    # this layer's place among its kind's caches: attention, a recurrent
    # layer's state matrix, its conv tail, a window layer's ring
    ai = mi = ti = wi = 0
    # ``cache["ring_rows"]`` (a server's prefill from an EMPTY cache): the
    # window layers keep no dense cache, and ``cache["wk"]`` / ``["wv"]``
    # come back as what their rings hold, (Lw, b, kv_heads, ring_rows, d)
    ring, rings = cache.get("ring_rows"), []
    for i in range(cfg.n_layers):
        L = f"layers.{i}."
        before, after = MIXER_SCOPES[cfg.mixer(i)]
        with jax.named_scope(before):
            h = norm_in(x, params[L + "attn_norm"], cfg)
        if cfg.is_mamba_layer(i):
            from nvme_strom_tpu.models.ssm import mamba_block
            a, states[mi], tails[ti] = mamba_block(
                h, params, L, cfg, states[mi], tails[ti], n_valid)
            mi += 1
            ti += 1
        elif cfg.mixer(i) == "conv":
            from nvme_strom_tpu.models.ssm import conv_block
            a, tails[ti] = conv_block(h, params, L, cfg, tails[ti], n_valid)
            ti += 1
        elif cfg.mixer(i) == "gdn":
            from nvme_strom_tpu.models.ssm import gdn_block
            a, states[mi], tails[ti] = gdn_block(
                h, params, L, cfg, states[mi], tails[ti], n_valid)
            mi += 1
            ti += 1
        elif cfg.latent:
            # the expanded form over the cached latent rows, the block's
            # own among them (models/mla.py)
            with jax.named_scope("strom.attn.mla"):
                q, rows = _mla.project(h, params, L, cfg, positions)
                cache["k"] = lax.dynamic_update_slice(
                    cache["k"], rows[None, :, None].astype(cfg.dtype),
                    (ai, 0, 0, pos, 0))
                a = _mla.attend(q, cache["k"][ai, :, 0], pos, params, L,
                                cfg)
            with jax.named_scope(after):
                a = a @ wmat(params, L + "wo", a.dtype)
            ai += 1
        elif cfg.stated_kv and not cfg.diffusion_block:
            # a window layer's cache, scope and place apart from a full one's
            win = cfg.mixer(i) == "window"
            ck, cv, at = ("wk", "wv", wi) if win else ("k", "v", ai)
            scope = "strom.attn.window" if win else "strom.attn.paged"
            if win and ring:
                # a prompt from an empty cache whose caller keeps rings: the
                # layer's K and V live for this layer only, and what its
                # ring holds of them is all that leaves
                with jax.named_scope(scope):
                    shape = (b, cfg.kv_heads(i), m)
                    k_l = jnp.zeros(shape + (cfg.head_dim,), cfg.dtype)
                    v_l = jnp.zeros(shape + (cfg.v_dim,), cfg.dtype)
                a, k_l, v_l = _blocked_attention(h, params, L, cfg, k_l, v_l,
                                                 pos, win)
                with jax.named_scope(scope):
                    n = jnp.broadcast_to(jnp.asarray(
                        m if n_valid is None else n_valid, jnp.int32), (b,))
                    rings.append((ring_rows(k_l, n, ring),
                                  ring_rows(v_l, n, ring)))
            else:
                with jax.named_scope(scope):
                    k_l, v_l = cache[ck][at], cache[cv][at]
                a, k_l, v_l = _blocked_attention(h, params, L, cfg, k_l, v_l,
                                                 pos, win)
                with jax.named_scope(scope):
                    cache[ck] = lax.dynamic_update_slice(
                        cache[ck], k_l[None], (at, 0, 0, 0, 0))
                    cache[cv] = lax.dynamic_update_slice(
                        cache[cv], v_l[None], (at, 0, 0, 0, 0))
            wi, ai = wi + win, ai + (not win)
        else:
            with jax.named_scope(before):
                q, k, v = qkv_project(h, params, L, cfg, positions=positions)
            with jax.named_scope("strom.attn.paged"):
                cache["k"] = lax.dynamic_update_slice(
                    cache["k"], k[None].astype(cfg.dtype),
                    (ai, 0, 0, pos, 0))
                cache["v"] = lax.dynamic_update_slice(
                    cache["v"], v[None].astype(cfg.dtype),
                    (ai, 0, 0, pos, 0))
                a = cache_attention(q, cache["k"][ai], cache["v"][ai],
                                    limit, cfg)
            with jax.named_scope(after):
                a = a.transpose(0, 2, 1, 3).reshape(b, m, -1)
                a = a @ wmat(params, L + "wo", a.dtype)
            ai += 1
        with jax.named_scope(after):
            x = add_residual(x, norm_out(a, params[L + "attn_norm"], cfg),
                             cfg)
        with jax.named_scope("strom.mlp"):
            h = norm_in(x, params[L + "mlp_norm"], cfg)
            f = _mlp_block(h, params, L, cfg, valid, calls)
            x = add_residual(x, norm_out(f, params[L + "mlp_norm"], cfg),
                             cfg).astype(cfg.dtype)
    with jax.named_scope("strom.head"):
        cache["pos"] = pos + m
    if ssm:
        cache["ssm"] = {"s": tuple(states), "conv": tuple(tails)}
    if rings:
        with jax.named_scope("strom.attn.window"):
            cache["wk"], cache["wv"] = (jnp.stack(r) for r in zip(*rings))
    if "moe" in cache and calls:
        with jax.named_scope("strom.mlp"):
            cache["moe"] = _moe.add_load(cache["moe"], calls)
    with jax.named_scope("strom.head"):
        if last is not None:
            rows = jnp.broadcast_to(jnp.asarray(last, jnp.int32), (b,))
            x = jnp.take_along_axis(x, rows[:, None, None], axis=1)[:, 0]
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = lm_logits(params, cfg, x)
    return logits, cache


def nucleus_truncate(logits, top_p):
    """Zero out (to -inf) everything outside the smallest prefix of the
    sorted distribution whose cumulative probability reaches ``top_p``
    (the first token is always kept).  ``top_p`` may be a python float
    or a per-row array (broadcast against logits' leading dims) — the
    ONE nucleus rule both the static sampler here and the serving
    per-slot sampler use."""
    top_p = jnp.asarray(top_p, jnp.float32)
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = cum - probs < top_p[..., None]
    cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf),
                     axis=-1, keepdims=True)
    return jnp.where(logits < cutoff, -jnp.inf, logits)


def _sample(logits, temperature: float, rng,
            top_k: int = 0, top_p: float = 1.0):
    """Greedy (temperature 0) or categorical sampling with optional
    top-k / nucleus (top-p) truncation — all branch-free under jit
    (the knobs are static python values, so each combination traces
    its own specialized program)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / jnp.float32(temperature)
    if top_k > 0:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        logits = nucleus_truncate(logits, top_p)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def generate(params: Dict, prompt: jax.Array, cfg: TransformerConfig,
             max_new_tokens: int, temperature: float = 0.0,
             rng: Optional[jax.Array] = None,
             eos_id: Optional[int] = None,
             pad_id: int = 0, cache_attn=None,
             top_k: int = 0, top_p: float = 1.0) -> jax.Array:
    """Greedy/temperature generation with optional top-k / top-p
    truncation.  prompt (b, s) int32 → (b, max_new_tokens) int32.  The
    decode loop is one lax.scan; jit this whole function
    (``static_argnums`` for cfg, max_new_tokens, temperature, top_k,
    top_p AND cache_attn — a function is not a jax type) or wrap them
    all in a partial.  After ``eos_id`` a sequence emits ``pad_id``
    forever (static shapes; no early exit under jit)."""
    cfg.require_causal("decode.generate")
    b, s = prompt.shape
    if rng is None:
        rng = jax.random.key(0)
    if top_k < 0 or not 0.0 < top_p <= 1.0:
        raise ValueError(f"bad top_k={top_k} / top_p={top_p}")
    cache = init_cache(cfg, b, s + max_new_tokens)
    logits, cache = prefill(params, prompt, cfg, cache)
    rng, sub = jax.random.split(rng)
    tok = _sample(logits, temperature, sub, top_k, top_p)
    # An eos IS emitted (even as the very first token); only tokens after
    # it become pad — same semantics at every position.
    done = (jnp.zeros((b,), bool) if eos_id is None
            else tok == eos_id)

    def step(carry, _):
        tok, cache, rng, done = carry
        logits, cache = decode_step(params, tok, cfg, cache, cache_attn)
        rng, sub = jax.random.split(rng)
        nxt = _sample(logits, temperature, sub, top_k, top_p)
        if eos_id is not None:
            nxt = jnp.where(done, pad_id, nxt)
            done = done | (nxt == eos_id)
        return (nxt, cache, rng, done), tok

    (last, cache, rng, done), toks = lax.scan(
        step, (tok, cache, rng, done), None, length=max_new_tokens - 1)
    toks = jnp.moveaxis(toks, 0, 1)                    # (b, n-1)
    return jnp.concatenate([toks, last[:, None]], axis=1)

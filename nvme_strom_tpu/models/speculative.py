"""Speculative decoding: a small draft model proposes, the target
verifies k tokens per forward.

Two acceptance schemes: :func:`speculative_generate` (greedy — the
emitted sequence is PROVABLY identical to the target model's own
greedy decode) and :func:`speculative_sample` (rejection sampling —
the emitted sequence is distributed EXACTLY as sampling from the
target at the requested temperature/top_p).  Either way the draft only
changes how many target forwards it takes to produce the output.  The win is
wall-clock: a verify forward over k+1 positions costs barely more than
a single-token step (the same weights stream through the MXU; the
sequence axis just grows), so acceptance rate ~a turns into ~a·k fewer
target steps.

Host-orchestrated control loop (acceptance counts are data-dependent —
the anti-pattern for one big jit), with both models' work in jitted
blocks: the draft's k proposals are one ``lax.scan``, the target's
verify is one :func:`~nvme_strom_tpu.models.decode.block_step`.
Cache rewind after partial acceptance is free: positions past ``pos``
are dead by construction (every mask tests ``<= pos``; later writes
overwrite in place).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from nvme_strom_tpu.models import decode as _dec
from nvme_strom_tpu.models.transformer import TransformerConfig


@dataclass
class SpecStats:
    """Acceptance accounting for one generate call."""
    target_forwards: int = 0
    drafted: int = 0
    accepted: int = 0

    @property
    def accept_rate(self) -> float:
        return self.accepted / self.drafted if self.drafted else 0.0


@functools.partial(jax.jit, static_argnums=(2, 3), donate_argnums=(1,))
def _draft_k(params: Dict, cache: Dict, cfg: TransformerConfig, k: int,
             tok: jax.Array):
    """k greedy draft steps as one scan → ((b, k) tokens, cache)."""
    def step(carry, _):
        tok, cache = carry
        logits, cache = _dec.decode_step(params, tok, cfg, cache)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return (nxt, cache), nxt

    (_, cache), toks = lax.scan(step, (tok, cache), None, length=k)
    return jnp.moveaxis(toks, 0, 1), cache


@functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(1,))
def _verify(params: Dict, cache: Dict, cfg: TransformerConfig, blk):
    """Model forward over the block → (greedy picks (b, m), cache)."""
    logits, cache = _dec.block_step(params, blk, cfg, cache)
    return jnp.argmax(logits, -1).astype(jnp.int32), cache


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5),
                   donate_argnums=(1,))
def _draft_k_probs(params: Dict, cache: Dict, cfg: TransformerConfig,
                   k: int, temperature: float, top_p: float, tok, key):
    """k SAMPLED draft steps → (tokens (b,k), warped draft
    distributions (b,k,V), cache).  The full per-step distribution is
    kept — rejection sampling needs q_i everywhere, not just at the
    chosen token (the residual draw reads the whole row)."""
    def step(carry, _):
        tok, cache, key = carry
        logits, cache = _dec.decode_step(params, tok, cfg, cache)
        warped = logits / jnp.float32(temperature)
        if top_p < 1.0:   # static: the no-op case pays no vocab sort
            warped = _dec.nucleus_truncate(warped, top_p)
        key, sub = jax.random.split(key)
        nxt = jax.random.categorical(sub, warped, -1).astype(jnp.int32)
        return (nxt, cache, key), (nxt, jax.nn.softmax(warped, -1))

    (_, cache, _), (toks, probs) = lax.scan(step, (tok, cache, key),
                                            None, length=k)
    return (jnp.moveaxis(toks, 0, 1), jnp.moveaxis(probs, 0, 1), cache)


@functools.partial(jax.jit, static_argnums=(2, 3, 4),
                   donate_argnums=(1,))
def _verify_probs(params: Dict, cache: Dict, cfg: TransformerConfig,
                  temperature: float, top_p: float, blk):
    """Target forward over the block → warped target distributions
    (b, m, V); the same temperature/top-p warp as the draft, per the
    speculative-sampling recipe (warp both, then accept-test)."""
    logits, cache = _dec.block_step(params, blk, cfg, cache)
    warped = logits / jnp.float32(temperature)
    if top_p < 1.0:       # static: the no-op case pays no vocab sort
        warped = _dec.nucleus_truncate(warped, top_p)
    return jax.nn.softmax(warped, -1), cache


def _rewind(cache: Dict, pos: int) -> Dict:
    cache["pos"] = jnp.asarray(pos, jnp.int32)
    return cache


def _validate_spec(max_new_tokens: int, k: int, b: int) -> None:
    """Shared argument contract of both speculation schemes."""
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, "
                         f"got {max_new_tokens}")
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if b != 1:
        raise ValueError(f"speculative decode is batch-1 (got b={b})")


def _setup_caches(draft_params, target_params, prompt, cfg, dcfg,
                  max_new_tokens: int, k: int, st: SpecStats):
    """Prefill both models → (target logits, t_cache, d_cache)."""
    for c in (cfg, dcfg):
        c.require_causal("speculative decoding (models/speculative.py)")
    b, s = prompt.shape
    cap = s + max_new_tokens + k + 1
    t_cache = _dec.init_cache(cfg, b, cap)
    d_cache = _dec.init_cache(dcfg, b, cap)
    t_logits, t_cache = _dec.prefill(target_params, prompt, cfg,
                                     t_cache)
    _, d_cache = _dec.prefill(draft_params, prompt, dcfg, d_cache)
    st.target_forwards += 1
    return t_logits, t_cache, d_cache


def _catch_up_and_rewind(draft_params, dcfg, drafts, n_acc, kk,
                         t_cache, d_cache, t_pos, d_pos, n_emitted):
    """Post-round cache invariant, shared by both schemes: each cache
    holds every emitted token EXCEPT the newest (it enters on the next
    round's block).  The target ingested the whole kk+1 block; the
    draft ingested only up to d_kk-1, so a full acceptance leaves it
    one token short — catch it up by ingesting d_kk (picks
    discarded)."""
    if n_acc == kk:
        _, d_cache = _verify(draft_params, d_cache, dcfg,
                             drafts[:, -1:])
    return (_rewind(t_cache, t_pos + n_emitted),
            _rewind(d_cache, d_pos + n_emitted))


def _finalize(out, max_new_tokens: int, eos_id, pad_id: int):
    """eos trim + right-pad to the fixed output shape."""
    out = out[:max_new_tokens]
    if eos_id is not None and eos_id in out:
        cut = out.index(eos_id) + 1
        out = out[:cut] + [pad_id] * (max_new_tokens - cut)
    out += [pad_id] * (max_new_tokens - len(out))
    return jnp.asarray([out], jnp.int32)


def speculative_generate(draft_params: Dict, target_params: Dict,
                         prompt: jax.Array, cfg: TransformerConfig,
                         max_new_tokens: int, k: int = 4,
                         draft_cfg: Optional[TransformerConfig] = None,
                         eos_id: Optional[int] = None, pad_id: int = 0,
                         stats: Optional[SpecStats] = None):
    """Greedy generation via draft-k/verify — token-identical to
    ``decode.generate(target_params, ...)`` with temperature 0.

    prompt (1, s) int32 → (1, max_new_tokens) int32.  Batch 1 only:
    acceptance lengths are per-sequence, and a shared cache position
    cannot diverge per row.  ``draft_cfg`` defaults to ``cfg`` (same
    architecture, smaller weights is the usual pairing — e.g. a
    lower-rank or distilled checkpoint in the same layout).
    Pass a :class:`SpecStats` to collect acceptance accounting.
    """
    _validate_spec(max_new_tokens, k, prompt.shape[0])
    dcfg = draft_cfg or cfg
    st = stats if stats is not None else SpecStats()
    t_logits, t_cache, d_cache = _setup_caches(
        draft_params, target_params, prompt, cfg, dcfg,
        max_new_tokens, k, st)

    out = [int(jnp.argmax(t_logits, -1)[0])]
    while len(out) < max_new_tokens:
        if eos_id is not None and out[-1] == eos_id:
            break
        tok = jnp.asarray([out[-1]], jnp.int32)
        t_pos = int(t_cache["pos"])
        d_pos = int(d_cache["pos"])

        kk = min(k, max_new_tokens - len(out))
        drafts, d_cache = _draft_k(draft_params, d_cache, dcfg, kk, tok)
        # verify block: [current token, d_1 .. d_kk]; pick row t is the
        # target's choice AFTER seeing row t — row kk's pick is the
        # free bonus token when every draft is accepted (kk+1 emitted
        # per target forward at acceptance 1.0)
        blk = jnp.concatenate([tok[:, None], drafts], axis=1)
        picks, t_cache = _verify(target_params, t_cache, cfg, blk)
        st.target_forwards += 1
        st.drafted += kk

        # ONE device→host transfer for both arrays, not 2·kk scalars
        drafts_h, picks_h = jax.device_get((drafts[0], picks[0]))
        drafts_h, picks_h = drafts_h.tolist(), picks_h.tolist()
        n_acc = 0
        while n_acc < kk and picks_h[n_acc] == drafts_h[n_acc]:
            n_acc += 1
        st.accepted += n_acc
        # accepted drafts + the target's row-n_acc pick: the correction
        # on a mismatch, the bonus on full acceptance — same expression
        emitted = drafts_h[:n_acc] + [picks_h[n_acc]]
        out.extend(emitted)

        t_cache, d_cache = _catch_up_and_rewind(
            draft_params, dcfg, drafts, n_acc, kk, t_cache, d_cache,
            t_pos, d_pos, len(emitted))

    return _finalize(out, max_new_tokens, eos_id, pad_id)


def speculative_sample(draft_params: Dict, target_params: Dict,
                       prompt: jax.Array, cfg: TransformerConfig,
                       max_new_tokens: int, temperature: float,
                       k: int = 4, top_p: float = 1.0, seed: int = 0,
                       draft_cfg: Optional[TransformerConfig] = None,
                       eos_id: Optional[int] = None, pad_id: int = 0,
                       stats: Optional[SpecStats] = None):
    """Speculative SAMPLING (rejection scheme): the emitted sequence is
    distributed exactly as sampling from the target at this
    temperature/top_p — the draft only changes how many target
    forwards it takes.

    Per round: the draft samples k tokens from its own warped
    distribution q; one target forward yields p at every position;
    token x_i is accepted with probability min(1, p_i(x_i)/q_i(x_i)),
    and the first rejection emits a draw from the residual
    norm(max(p_i − q_i, 0)) — the correction that makes the output
    law exactly p.  Full acceptance earns a bonus draw from p_{k+1}.
    Accept/residual math runs host-side on the fetched distribution
    rows (batch-1 control flow, like the greedy path); model work is
    the same jitted scan/block-step blocks.

    ``temperature`` must be > 0 — at 0 use
    :func:`speculative_generate`, whose greedy acceptance is this
    scheme's limit.  Reproducible per ``seed``.
    """
    import numpy as np
    if temperature <= 0:
        raise ValueError(
            "speculative_sample needs temperature > 0; temperature 0 "
            "is speculative_generate's greedy acceptance")
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    _validate_spec(max_new_tokens, k, prompt.shape[0])
    dcfg = draft_cfg or cfg
    st = stats if stats is not None else SpecStats()
    rng = np.random.default_rng(seed & 0xFFFFFFFF)
    draft_key = jax.random.PRNGKey((seed ^ 0x5EED) & 0xFFFFFFFF)

    def host_draw(p_row) -> int:
        p_row = np.clip(np.asarray(p_row, np.float64), 0, None)
        tot = p_row.sum()
        if tot <= 0:                    # fully truncated row: greedy
            return int(p_row.argmax())
        return int(rng.choice(p_row.shape[0], p=p_row / tot))

    t_logits, t_cache, d_cache = _setup_caches(
        draft_params, target_params, prompt, cfg, dcfg,
        max_new_tokens, k, st)
    first_w = t_logits / jnp.float32(temperature)
    if top_p < 1.0:
        first_w = _dec.nucleus_truncate(first_w, top_p)
    first_p = jax.nn.softmax(first_w, -1)
    out = [host_draw(jax.device_get(first_p[0]))]

    while len(out) < max_new_tokens:
        if eos_id is not None and out[-1] == eos_id:
            break
        tok = jnp.asarray([out[-1]], jnp.int32)
        t_pos = int(t_cache["pos"])
        d_pos = int(d_cache["pos"])

        kk = min(k, max_new_tokens - len(out))
        draft_key, sub = jax.random.split(draft_key)
        drafts, q, d_cache = _draft_k_probs(
            draft_params, d_cache, dcfg, kk, float(temperature),
            float(top_p), tok, sub)
        blk = jnp.concatenate([tok[:, None], drafts], axis=1)
        p, t_cache = _verify_probs(target_params, t_cache, cfg,
                                   float(temperature), float(top_p),
                                   blk)
        st.target_forwards += 1
        st.drafted += kk

        # one device→host fetch of the round's distributions
        drafts_h, q_h, p_h = jax.device_get((drafts[0], q[0], p[0]))
        drafts_h = drafts_h.tolist()
        emitted = []
        n_acc = 0
        for i in range(kk):
            x = drafts_h[i]
            qx = float(q_h[i, x])
            px = float(p_h[i, x])
            if qx <= 0 or rng.random() < min(1.0, px / qx):
                emitted.append(x)
                n_acc += 1
                continue
            # rejection: the residual draw makes the output law exactly p
            emitted.append(host_draw(
                np.maximum(p_h[i] - q_h[i], 0.0)))
            break
        else:
            emitted.append(host_draw(p_h[kk]))   # bonus from p_{k+1}
        st.accepted += n_acc
        out.extend(emitted)

        t_cache, d_cache = _catch_up_and_rewind(
            draft_params, dcfg, drafts, n_acc, kk, t_cache, d_cache,
            t_pos, d_pos, len(emitted))

    return _finalize(out, max_new_tokens, eos_id, pad_id)

"""Plain reference of an SDAR-MoE decoder (``model_type`` sdar_moe:
SDAR-30B-A3B-Chat's architecture — a Qwen3-MoE decoder, whose model file is
``transformers/models/qwen3_moe``, that generates by diffusion over blocks),
written from the published description and importing nothing of the program.
``x`` (T, d); ``N(y) = y / sqrt(mean y^2 + eps) * w``; no bias anywhere:

    x = E[tokens]
    per layer:  h = x + Attn(N_a(x));   x = h + MoE(N_f(h))
    logits = W_head N(x)                                     (untied head)

    Attn:  q = N_q(W_q x), k = N_k(W_k x) A HEAD AT A TIME (weights of
           head_dim), then rotary over all of a head's features (theta, no
           scaling, half-split pairs as HF rotate_half) at the row's
           POSITION; kv heads repeated to the query heads; scores q.k /
           sqrt(head_dim); softmax over the rows the MASK lets the row see;
           then W_o.
    MoE:   p = softmax(W_r h) over all the experts, float32; sel = the
           top-k; w = p[sel] / sum p[sel]                  (norm_topk_prob)
           f = sum_{e in sel} w_e W2_e (silu(W1_e h) * W3_e h)

The mask and the positions are DATA (``see`` (S, L, L) bool, ``positions``
(S, L)), so one forward serves every mask this file builds:

    block_causal(L, Bl)   row i sees row j iff j // Bl <= i // Bl, blocks
                          counted from position 0: its whole block, later
                          rows of it included, and every earlier block.
    two_stream(L, Bl)     the sequence [noisy | clean] of twice the length:
                          a noisy row sees the noisy rows of its own block
                          and the clean rows of the blocks before it; a clean
                          row sees the clean rows up to the end of its own
                          block.  Row i of either stream is at position i.
                          One forward then gives, for EVERY block at once,
                          the logits of a denoising step at that step's
                          input: the block's own rows as the noisy stream
                          holds them, every earlier block clean.

Generation (``generate``; greedy): a prompt of P tokens has P // Bl whole
blocks; then block after block, from ``[the prompt's remainder | MASK ...]``:
a DENOISING forward gives at each still-masked position a candidate (the
arg-max) and its confidence (that token's softmax probability); the n most
confident masked positions take their candidates (ties to the lower
position), and with a threshold tau every masked position whose confidence
passes it too.  Static rule: n = (masked at the block's start) // T, one more
in the first (...) % T steps.  Dynamic rule: n = 1.  A token is never masked
again.  When nothing is masked the block is finished (the program then
forwards the clean block once more to write its K/V: a cache is the
program's business — this file has none and forwards the whole sequence
every time).  The answer is the tokens at positions P .. P + budget - 1, cut
at an EOS.

The expert layer is the PLAIN form: a loop over all the experts, each
computed on every row and kept where a mask says the row chose it.  float32
throughout under ``jax.default_matmul_precision("highest")``, no cache, no
kernels, one sequence at a time inside the attention.  Weights are drawn
layer by layer (inside an expert layer, expert by expert) from the
benchmark's seeded generator (``benchmark/weights_sdar.py``), never taken
from the program.  Departures from the equations: none in the mathematics
(the program's router divides by ``sum + 1e-6``: 1.6e-5 of a weight at
most); the weights are random, and ``router_gain`` is folded into the
router's columns as ``weights_sdar.served`` does it.

``low`` is a control's arithmetic: "int8" quantises every weight per output
channel and every activation row to int8 before each matrix product (W8A8) —
the nearest precision below bf16; "bf16" rounds both operands of every
product to bfloat16 (the configuration's own precision)."""

from __future__ import annotations

import functools

import numpy as np

from benchmark import weights_sdar as WS
from benchmark.reference.dense_gqa import _q8, _rmsnorm


def _mm(x, w, low):
    import jax.numpy as jnp
    if low == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    elif low == "bf16":
        x, w = (t.astype(jnp.bfloat16).astype(jnp.float32) for t in (x, w))
    return jnp.matmul(x, w)


def _silu(a):
    import jax
    return a * jax.nn.sigmoid(a)


def _rope_at(t, theta, positions):
    """t (S, L, H, D) float32 turned by ``positions`` (S, L): half-split."""
    import jax.numpy as jnp
    half = t.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs      # (S, L, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)


def attention(h, w, hf, see, positions, low=None):
    """h (S, L, d), see (S, L, L) bool, positions (S, L) -> (S, L, d)."""
    import jax
    import jax.numpy as jnp
    S, L, _ = h.shape
    z = WS.sizes(hf)
    nh, nkv, hd, eps = z["nh"], z["nkv"], z["hd"], hf["rms_norm_eps"]
    q = _mm(h, w["wq"], low).reshape(S, L, nh, hd)
    k = _mm(h, w["wk"], low).reshape(S, L, nkv, hd)
    v = _mm(h, w["wv"], low).reshape(S, L, nkv, hd)
    q = _rope_at(_rmsnorm(q, w["q_norm"], eps), z["theta"], positions)
    k = _rope_at(_rmsnorm(k, w["k_norm"], eps), z["theta"], positions)
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)

    def one_seq(qkvm):
        q1, k1, v1, m1 = qkvm
        sc = jnp.einsum("qhd,khd->hqk", q1, k1) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(m1[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v1)

    a = jax.lax.map(one_seq, (q, k, v, see)).reshape(S, L, nh * hd)
    return _mm(a, w["wo"], low)


def routing(h, w, hf, low=None):
    """h (..., d) -> weight (..., E) float32, 0 where not selected."""
    import jax
    import jax.numpy as jnp
    z = WS.sizes(hf)
    p = jax.nn.softmax(_mm(h, w["router"], low), axis=-1)
    _, sel = jax.lax.top_k(p, z["k"])
    chosen = jnp.any(sel[..., None] == jnp.arange(z["E"]), axis=-2)
    wt = jnp.where(chosen, p, 0.0)
    if hf.get("norm_topk_prob", True):
        wt = wt / wt.sum(-1, keepdims=True)
    return wt


def moe(h, w, hf, expert_weights, low=None):
    """Every expert on every row, weighted by the router's mask.
    ``expert_weights(e)`` gives expert e's (W1, W3, W2), e traced."""
    import jax
    import jax.numpy as jnp
    wt = routing(h, w, hf, low)

    def one(acc, e):
        w1, w3, w2 = expert_weights(e)
        f = _mm(_silu(_mm(h, w1, low)) * _mm(h, w3, low), w2, low)
        return acc + jnp.take(wt, e, axis=-1)[..., None] * f, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          jnp.arange(WS.sizes(hf)["E"]))
    return out


_KEYS = ("hidden_size", "vocab_size", "num_attention_heads",
         "num_key_value_heads", "head_dim", "rope_theta",
         "moe_intermediate_size", "num_experts", "num_experts_per_tok",
         "num_hidden_layers", "rms_norm_eps", "norm_topk_prob")


@functools.lru_cache(maxsize=None)
def _programs(hf_items: tuple, low):
    """(embed, layer, head), jitted once per configuration and arithmetic;
    weights are generated inside from traced stream ids."""
    import jax
    import jax.numpy as jnp
    hf = dict(hf_items)
    top, shapes = WS.top_shapes(hf), WS.layer_shapes(hf)
    eps = hf["rms_norm_eps"]

    def gen(base, name, shape, first=0):
        return WS.make_tensor(base, name, shape, first)

    def embed(base, tokens):
        return gen(base, "tok_embed", top["tok_embed"]).astype(
            jnp.float32)[tokens]

    def layer(x, layer_bases, see, positions):
        at = {leaf: layer_bases[j] for j, leaf in enumerate(WS.LAYER_LEAVES)}
        w = WS.served({leaf: gen(at[leaf], leaf, shapes[leaf])
                       for leaf in WS.LAYER_LEAVES if leaf not in WS.STACKED})
        w = {leaf: t.astype(jnp.float32) for leaf, t in w.items()}
        x = x + attention(_rmsnorm(x, w["attn_norm"], eps), w, hf, see,
                          positions, low)

        def expert_weights(e):           # one expert's slices, drawn alone
            def one(leaf):
                n = shapes[leaf][1] * shapes[leaf][2]
                return gen(at[leaf], leaf, shapes[leaf][1:],
                           e.astype(jnp.uint32) * jnp.uint32(n)
                           ).astype(jnp.float32)
            return one("moe_w_gate"), one("moe_w_up"), one("moe_w_down")

        return x + moe(_rmsnorm(x, w["mlp_norm"], eps), w, hf,
                       expert_weights, low)

    def head(x, base_norm, base_head, at):
        xs = jnp.take_along_axis(x, at[:, :, None], axis=1)
        h = _rmsnorm(xs, gen(base_norm, "final_norm", top["final_norm"]
                             ).astype(jnp.float32), eps)
        return _mm(h, gen(base_head, "lm_head", top["lm_head"]
                          ).astype(jnp.float32), low)

    return (jax.jit(embed), jax.jit(layer, donate_argnums=(0,)),
            jax.jit(head))


def logits(hf: dict, seed: int, tokens, see, positions, at, low=None):
    """Reference logits (S, K, vocab) float32 at rows ``at`` (S, K) of the
    sequences ``tokens`` (S, L) int32 under the mask ``see`` (S, L, L) —
    row i sees row j where true — with row i at ``positions`` (S, L)."""
    import jax
    small = {k: hf[k] for k in _KEYS if hf.get(k) is not None}
    embed, layer, head = _programs(tuple(sorted(small.items())), low)
    bs, idx = WS.bases(hf, seed), WS.layer_indices(hf)
    see = np.asarray(see, bool)
    positions = np.asarray(positions, np.int32)
    with jax.default_matmul_precision("highest"):
        x = embed(bs[idx["tok_embed"]], np.asarray(tokens, np.int32))
        for i in range(hf["num_hidden_layers"]):
            lb = np.asarray([bs[idx[f"layers.{i}.{leaf}"]]
                             for leaf in WS.LAYER_LEAVES], np.uint32)
            x = layer(x, lb, see, positions)
        return head(x, bs[idx["final_norm"]], bs[idx["lm_head"]],
                    np.asarray(at, np.int32))


# ------------------------------------------------------------------- masks

def block_causal(L: int, Bl: int) -> np.ndarray:
    """(L, L) bool: row i sees row j iff j // Bl <= i // Bl."""
    blk = np.arange(L) // Bl
    return blk[None, :] <= blk[:, None]


def two_stream(L: int, Bl: int) -> tuple:
    """The mask (2L, 2L) and the positions (2L,) of [noisy | clean]."""
    blk = np.arange(L) // Bl
    same, before = blk[None, :] == blk[:, None], blk[None, :] < blk[:, None]
    see = np.zeros((2 * L, 2 * L), bool)
    see[:L, :L] = same                  # noisy sees its own noisy block
    see[:L, L:] = before                # ... and the clean blocks before it
    see[L:, L:] = same | before         # clean sees clean up to its block
    return see, np.concatenate([np.arange(L), np.arange(L)])


# -------------------------------------------------------------- generation

def commit_count(n0: int, T: int, step: int) -> int:
    """Positions the static rule commits at ``step`` of a block that began
    with ``n0`` masked; T = 0 (the dynamic rule): one at least."""
    return n0 // T + (step < n0 % T) if T else 1


def select(conf, masked, n: int, tau: float) -> np.ndarray:
    """Which masked positions commit: the ``n`` most confident (ties to the
    lower position) and every one whose confidence passes ``tau``."""
    order = sorted(np.nonzero(masked)[0], key=lambda r: (-conf[r], r))
    take = np.zeros(len(conf), bool)
    take[order[:n]] = True
    return masked & (take | (conf > tau))


def confidence(lg) -> tuple:
    """lg (..., vocab) -> (the arg-max token, its softmax probability)."""
    lg = np.asarray(lg, np.float64)
    best = lg.max(-1)
    return lg.argmax(-1), 1.0 / np.exp(lg - best[..., None]).sum(-1)


def generate(hf: dict, seed: int, prompt: list, budget: int, Bl: int,
             mask_id: int, steps: int = 0, threshold: float = 0.0,
             eos_id=None, low=None, on_forward=None) -> tuple:
    """The loop above for one prompt, a full forward of the sequence so far
    a denoising step (no cache).  ``steps`` T > 0 with ``threshold`` 0 is
    the static rule (T = 0: one position a step, Bl of them a block);
    ``threshold`` tau > 0 the dynamic one (n = 1).  ``on_forward(block
    start, step, logits (Bl, vocab))`` sees every denoising forward.
    Returns (the answer's tokens, the denoising step each was committed
    at)."""
    P = len(prompt)
    T = 0 if threshold > 0 else steps or Bl
    tau = threshold if threshold > 0 else np.inf
    end = -(-(P + budget) // Bl) * Bl
    seq = list(prompt[:P // Bl * Bl])
    toks, csteps = [], []
    for start in range(P // Bl * Bl, end, Bl):
        given = max(P - start, 0)
        block = np.asarray(list(prompt[start:P]) + [mask_id] * (Bl - given))
        masked = np.arange(Bl) >= given
        cstep = np.full(Bl, -1)
        n0, step = Bl - given, 0
        while masked.any():
            # (right-padded to the answer's end: later blocks are seen by
            # nobody, and one length is one compiled shape)
            rows = np.zeros(end, np.int32)
            rows[:start] = seq
            rows[start:start + Bl] = np.where(masked, mask_id, block)
            L = end
            lg = np.asarray(logits(
                hf, seed, rows[None], block_causal(L, Bl)[None],
                np.arange(L)[None], np.arange(start, start + Bl)[None],
                low=low))[0]
            if on_forward:
                on_forward(start, step, lg)
            cand, conf = confidence(lg)
            take = select(conf, masked, commit_count(n0, T, step), tau)
            block = np.where(take, cand, block)
            cstep = np.where(take, step, cstep)
            masked &= ~take
            step += 1
        seq += [int(t) for t in block]
        toks += [int(t) for t in block[given:]]
        csteps += [int(c) for c in cstep[given:]]
    toks, csteps = toks[:budget], csteps[:budget]
    if eos_id is not None and eos_id in toks:
        cut = toks.index(eos_id) + 1
        toks, csteps = toks[:cut], csteps[:cut]
    return toks, csteps


# ------------------------------------------------------------------ replay

#: a control's fault, emulated on the reference's side of the replay: the
#: mask, the positions or the clean stream's tokens as a program with that
#: fault would have had them (``replay_inputs``) — or, last, the positions
#: it would have committed (``control_choice``)
VARIANTS = ("causal_block", "causal_prompt", "stale_pages", "equal_rope",
            "lowest_first")


def replay_inputs(sample: list, Bl: int, mask_id: int, step: int,
                  variant=None) -> tuple:
    """The two-stream forward that gives denoising step ``step`` of every
    block of every request of ``sample`` ([{"prompt", "tokens", "steps"}],
    the served tokens and the step each was committed at): (tokens (S, 2L),
    see (S, 2L, 2L), positions (S, 2L), at (S, K) — the noisy rows of the
    answers' positions —, valid (S, K)).  The noisy stream holds a served
    token where it was committed before ``step`` (the prompt's always) and
    the mask's id elsewhere; the clean stream the sequence as served."""
    S = len(sample)
    L = max(len(r["prompt"]) + len(r["tokens"]) for r in sample)
    L = -(-L // 128) * 128              # one compiled shape a bucket
    K = -(-max(len(r["tokens"]) for r in sample) // Bl) * Bl
    see0, pos0 = two_stream(L, Bl)
    toks = np.zeros((S, 2 * L), np.int32)
    see = np.broadcast_to(see0, (S,) + see0.shape).copy()
    positions = np.broadcast_to(pos0, (S, 2 * L)).copy()
    at = np.zeros((S, K), np.int32)
    valid = np.zeros((S, K), bool)
    row = np.arange(L)
    for i, r in enumerate(sample):
        P, n = len(r["prompt"]), len(r["tokens"])
        seq = np.asarray(list(r["prompt"]) + list(r["tokens"]), np.int32)
        cstep = np.concatenate([np.full(P, -1), np.asarray(r["steps"])])
        toks[i, L:L + P + n] = seq
        toks[i, :P + n] = np.where(cstep < step, seq, mask_id)
        at[i, :n], valid[i, :n] = P + np.arange(n), True
        first = P // Bl * Bl            # where generation begins
        if variant == "causal_block":
            # a causal mask inside the block, in every forward of it
            see[i, :L, :L] &= row[None, :] <= row[:, None]
            see[i, L:, L:] &= row[None, :] <= row[:, None]
        elif variant == "causal_prompt":
            # the admission's mask causal: the prompt's whole blocks
            see[i, L:L + first, L:L + first] = np.tril(
                np.ones((first, first), bool))
        elif variant == "stale_pages":
            # a finished block's K/V as its LAST denoising forward wrote
            # them: that forward's input in the clean stream's place
            last = np.zeros(P + n, np.int64)
            for b in range(first, P + n, Bl):
                last[b:b + Bl] = cstep[b:b + Bl].max()
            toks[i, L + first:L + P + n] = np.where(
                cstep < last, seq, mask_id)[first:]
        elif variant == "equal_rope":
            # a step's rows all at its block's first position
            positions[i, first:L] = row[first:] // Bl * Bl
            positions[i, L + first:] = row[first:] // Bl * Bl
    return toks, see, positions, at, valid


@functools.lru_cache(maxsize=None)
def _stats():
    """(logits (S, K, vocab), chosen (S, K)) -> what ``replay`` keeps of a
    step's logits, jitted once."""
    import jax
    import jax.numpy as jnp

    def stats(lg, chosen):
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, chosen[..., None], axis=-1)[..., 0]
        lse = jax.nn.logsumexp(lg - best[..., None], axis=-1)
        return {"arg": jnp.argmax(lg, axis=-1), "conf": -lse, "got": got,
                "best": best}
    return jax.jit(stats)


def replay(hf: dict, seed: int, sample: list, Bl: int, mask_id: int,
           low=None, variant=None, tokens=None) -> list:
    """Per denoising step s = 0 .. (the last one any served token names):
    {"arg", "conf" (log), "got", "best"} (S, K) of the reference's logits at
    that step's input — the arg-max token, the log of its probability, the
    logit of ``tokens[s]`` (S, K) (default: the served token) and the best
    logit."""
    stats = _stats()
    out = []
    for step in range(max(max(r["steps"]) for r in sample) + 1):
        toks, see, positions, at, _ = replay_inputs(
            sample, Bl, mask_id, step, variant)
        chosen = np.zeros(at.shape, np.int32)
        for i, r in enumerate(sample):
            chosen[i, :len(r["tokens"])] = r["tokens"]
        if tokens is not None:
            chosen = np.asarray(tokens[step], np.int32)
        lg = logits(hf, seed, toks, see, positions, at, low=low)
        out.append({k: np.asarray(v) for k, v in stats(lg, chosen).items()})
        del lg
    return out


def served_choice(sample: list, Bl: int) -> dict:
    """What the requests of ``sample`` did, as ``gaps`` reads it: per
    denoising step, ``masked`` (S, K) — the answer's positions still masked
    at the step's input — and ``commit`` (S, K), those it committed;
    ``block`` (S, K) each position's diffusion block."""
    S = len(sample)
    K = -(-max(len(r["tokens"]) for r in sample) // Bl) * Bl
    cstep = np.full((S, K), -1)
    block = np.zeros((S, K), np.int64)
    for i, r in enumerate(sample):
        cstep[i, :len(r["tokens"])] = r["steps"]
        block[i] = (len(r["prompt"]) + np.arange(K)) // Bl
    steps = range(cstep.max() + 1)
    return {"block": block, "masked": [cstep >= s for s in steps],
            "commit": [cstep == s for s in steps]}


def control_choice(served: dict, stats: list, lowest: bool = False) -> dict:
    """What a program with a control's fault would have done at the served
    requests' own states: at every (block, step) it commits as many
    positions as were committed there, the most confident by ITS
    confidences ``stats[s]["conf"]`` (``lowest``: the lowest positions) —
    and takes ITS arg-max tokens (``tokens``, per step)."""
    commit = []
    for s, (masked, took) in enumerate(zip(served["masked"],
                                           served["commit"])):
        mine = np.zeros_like(took)
        for i, b in sorted({(int(i), int(served["block"][i, j]))
                            for i, j in zip(*np.nonzero(took))}):
            rows = np.nonzero((served["block"][i] == b) & masked[i])[0]
            n = int(took[i, rows].sum())
            order = rows if lowest else sorted(
                rows, key=lambda r: (-stats[s]["conf"][i, r], r))
            mine[i, list(order[:n])] = True
        commit.append(mine)
    return dict(served, commit=commit,
                tokens=[st["arg"] for st in stats])


def gaps(sound: list, choice: dict) -> dict:
    """The numbers ``correct`` is decided from, of a choice (``served_choice``
    or ``control_choice``) against the SOUND reference's per-step statistics
    (``replay``, its ``got`` taken at the choice's tokens): (a) the mean and
    the widest gap by which a committed token's logit lies below the best
    logit at its position and step; (b) over every (block, step) that
    committed some positions and left others masked, the mean and the widest
    gap (0 where there is none) by which the log-confidence of a committed
    position lies below that of the best position left masked."""
    token_gaps, conf_gaps = [], []
    for st, masked, took in zip(sound, choice["masked"], choice["commit"]):
        token_gaps.append((st["best"] - st["got"])[took])
        left = masked & ~took
        for i in range(took.shape[0]):
            for b in np.unique(choice["block"][i][left[i]]):
                mine = choice["block"][i] == b
                if (took[i] & mine).any():
                    conf_gaps.append(max(0.0, float(
                        st["conf"][i][left[i] & mine].max()
                        - st["conf"][i][took[i] & mine].min())))
    token_gaps = np.concatenate(token_gaps)
    return {"max_gap": float(token_gaps.max()),
            "mean_gap": float(token_gaps.mean()),
            "conf_gap": max(conf_gaps, default=0.0),
            "conf_mean_gap": float(np.mean(conf_gaps)) if conf_gaps else 0.0,
            "conf_off": int(np.count_nonzero(conf_gaps)),
            "choices": len(conf_gaps), "tokens": int(token_gaps.size),
            "off_best": int((token_gaps > 0).sum()),
            "scale": float(max(np.abs(st["best"]).max() for st in sound))}

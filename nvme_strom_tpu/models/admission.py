"""Which of the prompts one serve step admits share a prefill program.

A prefill program reads every weight once, however many prompt rows it
holds: while its rows are few its time IS that read, and prompts that go
through one program share it — an expert layer above all, whose weights
are ~E/k times what a row multiplies.  Past the rows at which the
arithmetic outlasts the read the program is compute-bound: merging saves
nothing there, and a pad row (a shorter prompt padded to the group's
longest) costs its full time outside the expert layers.  That row count
follows from counts of the config and one ratio of the device:

    rows = bytes of weights read x (operations / byte of the device)
           / operations a row costs

so a dense or hybrid bf16 decoder breaks even near the ratio itself (2
operations per 2-byte parameter) and a sparse one E/k times later.  The MXU
is never at its peak, so the real break-even lies lower: the rule is a
bound from counts, not a tuned number (PERF.md §6 has what the chip read).

``form_groups`` is the whole rule, a pure function; ``DecodeServer`` calls
it with what it sees (``cfg``, the device kind of its pool).  A padded
length has ONE program: at the widest width of the short ladder ``WIDTHS``
that its rows allow (``width_for``), a smaller group filling it with dead
rows — so the programs a server can ever need are one a length, as many as
when every prompt ran alone, and whatever meets a length first (a warm-up's
burst of equal prompts, a lone request) builds the program every later mix
runs.  A dead row costs its rows outside the expert layers and nothing
inside them.  That is cheap beside a group (PERF.md §6, PR 31, LFM2's first
12 layers on a v5e: 1–3 ms of a 25–43 ms program) and is what a request
that arrives ALONE pays for there being no second, narrow program of its
length: 2.5–12 ms on 21–33 (a second program a length was measured too: it
takes the penalty away and costs 1.6 s of set-up each).
"""

from __future__ import annotations

from typing import List, Sequence

import jax.numpy as jnp

#: bf16 operations the device's MXU retires per byte its HBM delivers.
#: "TPU v5 lite": Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16,
#: 16 GB HBM2e at 819 GB/s (the numbers ``benchmark/peaks.json`` quotes for
#: the benchmark).  "cpu": a nominal server socket (~1.6 TFLOP/s float32 over
#: ~100 GB/s); only the tests run there.  A kind that is not listed gives 0:
#: nothing is known about it, so no two prompts share a program.
DEVICE_OPS_PER_BYTE = {
    "TPU v5 lite": 197e12 / 819e9,
    "cpu": 16.0,
}

#: prompts a program may hold, dead rows included.  1024-row prompts pair
#: at most (a sparse config's break-even is ~2,200 rows); four of the
#: shorter ones cut their weight reads to a quarter.  A width of 8 or 16
#: would cut them further for a full group (PERF.md §6, PR 31: ~2–3 % of
#: ``lfm2.flood``) and cost a small group, or a request alone, as much again
#: in dead rows.
WIDTHS = (1, 2, 4)


def prefill_counts(cfg) -> tuple:
    """(parameters a prefill program reads, multiply-adds one prompt row
    costs), from the config's own description of its layers.  The head is
    read once and multiplies one row a prompt, so it counts as read only;
    the embedding is a gather; a Mamba-2 or delta-rule layer's scan costs a
    row the same at any length and is counted; attention's scores grow with
    the length (under 2 % of a row at the lengths a block table holds) and
    are not; norms, biases and conv taps are left out on both sides."""
    d, hd = cfg.d_model, cfg.head_dim
    read = rowops = 0
    for i in range(cfg.n_layers):
        kind = cfg.mixer(i)
        if kind == "mamba":
            mix = d * (2 * cfg.ssm_inner + 2 * cfg.ssm_state
                       + cfg.ssm_heads) + cfg.ssm_inner * d
            # the chunked scan's products a row (ops/ssm.py): C Bᵀ, the
            # masked product with Δx, the carried state's share, the
            # chunk's own state
            rowops += (cfg.ssm_chunk * cfg.ssm_state + cfg.ssm_inner
                       * (cfg.ssm_chunk + 2 * cfg.ssm_state))
        elif kind == "conv":
            mix = 4 * d * d
        elif kind == "gdn":
            value = cfg.gdn_v_heads * cfg.gdn_v_dim
            mix = d * (cfg.gdn_conv_dim + value + 2 * cfg.gdn_v_heads) \
                + value * d
            # the chunked scan's products a row a value head (ops/gdn.py):
            # K Kᵀ and Q Kᵀ, the masked product with U, and three products
            # against the state (W S, Q S, the chunk's own)
            rowops += cfg.gdn_v_heads * (
                cfg.gdn_chunk * (2 * cfg.gdn_k_dim + cfg.gdn_v_dim)
                + 3 * cfg.gdn_k_dim * cfg.gdn_v_dim)
        elif cfg.latent:
            mix = (d * cfg.q_lora_rank + cfg.q_lora_rank * cfg.n_heads
                   * (cfg.qk_nope_dim + cfg.qk_rope_dim)
                   + d * cfg.latent_width + cfg.kv_lora_rank * cfg.n_heads
                   * (cfg.qk_nope_dim + cfg.v_head_dim)
                   + cfg.n_heads * cfg.v_head_dim * d)
        else:
            # wq and wk at the key width, wv and wo at the value width (wq
            # twice as wide where it brings the output gate)
            mix = d * ((hd * (1 + cfg.attn_gate) + cfg.v_dim) * cfg.n_heads
                       + (hd + cfg.v_dim) * cfg.kv_heads(i))
        read += mix
        rowops += mix
        if cfg.mlp_kind(i) == "dense":
            read += 3 * d * cfg.d_ff
            rowops += 3 * d * cfg.d_ff
        else:
            width = (cfg.expert_width if cfg.mlp_kind(i) == "experts"
                     else cfg.d_ff)
            # every row runs the router and a shared expert; of its k
            # routed pairs the share that falls on the experts held here
            rest = d * cfg.n_experts + 3 * d * cfg.d_shared
            held = cfg.experts_local
            read += held * 3 * d * width + rest
            rowops += (cfg.expert_top_k * held * 3 * d * width
                       // cfg.n_experts + rest)
    return read + d * cfg.vocab, rowops


def breakeven_rows(cfg, device_kind: str) -> int:
    """Prompt rows at which one prefill program's arithmetic takes as long
    as reading its weights on ``device_kind`` (0 for an unknown kind)."""
    read, rowops = prefill_counts(cfg)
    ratio = DEVICE_OPS_PER_BYTE.get(device_kind, 0.0)
    return int(read * jnp.dtype(cfg.dtype).itemsize * ratio / (2 * rowops))


def width_for(m: int, limit_rows: int) -> int:
    """The width of THE program of prompts padded to ``m`` rows: the widest
    of the ladder whose rows stay within ``limit_rows`` (one prompt alone
    always may run)."""
    return max((w for w in WIDTHS if w * m <= limit_rows), default=1)


def form_groups(lengths: Sequence[int], limit_rows: int) -> List[List[int]]:
    """``lengths`` — padded prompt lengths, one per admission — into groups
    of indices, each group one call of the program of its longest length:
    longest first, a group takes the next shorter prompts up to that
    program's width.  Every index is in exactly one group; a limit of 0
    gives groups of one."""
    order = sorted(range(len(lengths)), key=lambda i: -lengths[i])
    groups: List[List[int]] = []
    while order:
        room = width_for(lengths[order[0]], limit_rows)
        groups.append(order[:room])
        order = order[room:]
    return groups

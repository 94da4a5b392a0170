"""Ulysses sequence parallelism: all-to-all head↔sequence re-sharding.

The second of the two standard long-context schemes (the other is the
ppermute ring in ``parallel/ring_attention.py``).  DeepSpeed-Ulysses
style: activations arrive sequence-sharded over ``sp``; one
``lax.all_to_all`` re-shards attention heads over ``sp`` while gathering
the FULL sequence per device, dense (or flash) attention runs locally on
that head slice with an ordinary causal mask, and a second all-to-all
restores sequence sharding.  Two collectives per attention vs the ring's
``n_sp`` neighbor exchanges — better when head count is plentiful and ICI
all-to-all bandwidth is good; the ring wins when s_local² tiles overlap
compute with transfer.  Both are drop-in ``attn_fn``s for
``models/transformer.forward``.

The reference has no parallelism concepts (SURVEY.md §2); this exists
because long-context support is a first-class requirement of the TPU
framework build.
"""

from __future__ import annotations

from functools import partial

import jax
from jax import lax
from jax.sharding import PartitionSpec as P


def _ulysses_block(q, k, v, *, sp_axis: str, n_sp: int, attn=None):
    """Per-device compute: q/k/v (b, h_local, s_local, d) seq-sharded →
    all_to_all → (b, h_local/n_sp, s_global, d) → causal attention →
    all_to_all back."""
    from nvme_strom_tpu.models.transformer import dense_causal_attention
    inner = attn or dense_causal_attention
    if n_sp == 1:
        return inner(q, k, v)
    # split heads across sp, gather sequence        (tiled=True keeps the
    # array layout: axis sizes multiply/divide by n_sp)
    a2a = partial(lax.all_to_all, axis_name=sp_axis, split_axis=1,
                  concat_axis=2, tiled=True)
    q, k, v = a2a(q), a2a(k), a2a(v)
    o = inner(q, k, v)
    # split sequence back across sp, gather heads
    return lax.all_to_all(o, axis_name=sp_axis, split_axis=2,
                          concat_axis=1, tiled=True)


def ulysses_attention(q, k, v, mesh, sp_axis: str = "sp",
                      dp_axis: str = "dp", tp_axis: str = "tp",
                      attn=None):
    """Causal attention with the sequence dim sharded over ``sp_axis``.

    Same contract as ``ring_attention.ring_attention``: q/k/v are global
    (batch, heads, seq, head_dim) arrays — batch over ``dp_axis``, heads
    over ``tp_axis`` (when present), seq over ``sp_axis``; K/V already
    GQA-expanded.  Heads-per-tp-shard must divide the sp extent.
    ``attn`` swaps the local attention inner (e.g. the Pallas flash
    kernel) — it sees the full sequence, so any causal kernel works.
    """
    n_sp = mesh.shape[sp_axis]
    dp = dp_axis if dp_axis in mesh.shape else None
    tp = tp_axis if tp_axis in mesh.shape else None
    n_heads = q.shape[1]
    h_local = n_heads // (mesh.shape[tp] if tp else 1)
    if h_local % n_sp:
        raise ValueError(
            f"{h_local} heads per tp shard not divisible by sp={n_sp}; "
            "use ring attention for head-poor configs")
    if q.shape[2] % n_sp:
        raise ValueError(
            f"seq {q.shape[2]} not divisible by sp={n_sp}")
    spec = P(dp, tp, sp_axis, None)
    fn = jax.shard_map(
        partial(_ulysses_block, sp_axis=sp_axis, n_sp=n_sp, attn=attn),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def make_ulysses_attn(mesh, sp_axis: str = "sp", dp_axis: str = "dp",
                      tp_axis: str = "tp", attn=None):
    """attn_fn(q, k, v) for models/transformer.forward(..., attn_fn=...) —
    the all-to-all drop-in alternative to make_ring_attn."""

    def attn_fn(q, k, v):
        return ulysses_attention(q, k, v, mesh, sp_axis=sp_axis,
                                 dp_axis=dp_axis, tp_axis=tp_axis,
                                 attn=attn)

    return attn_fn

"""Blocked causal attention of a block of query rows over a dense K/V cache
(``strom_kv_prefill``; over a band of it, ``strom_window_prefill``): the
prefill of K/V attention whose geometry the config states (grouped heads,
keys wider than values, a window, a sink).

It generalises the loop of ``ops/mla_attention.mla_prefill_attention`` (the
expanded latent form: as many key heads as query heads, causal, no window):
online softmax in float32 over key blocks, a key block past a query block's
last row neither fetched nor computed, the position of the first query row
data.  What is new here:

* **grouped heads.**  One grid step holds the ``g`` query heads of ONE KV
  head — ``g x block_q`` rows against one key block — so K and V are fetched
  once per KV head and query block, not once per query head (16 query heads
  a KV head at MiMo-V2.5's full layers), and the score block's rows fill
  the MXU however short ``block_q`` is.
* **a band.**  With ``window`` w row i sees the keys ``i - w < j <= i``.  The
  key axis of the grid is then RELATIVE: it is as long as the key blocks a
  query block's band can touch (``(block_q + w - 2) // block_k + 2``, two or
  three), and its step ``ki`` reads key block ``lo + ki`` where ``lo`` holds
  the band's first key — a 16,384-row prompt's window layer touches 1/64 of
  its causal half and walks no more than that.
* **a sink.**  ``sink`` (heads,) float32 joins each row's softmax as one
  more column that carries no value: ``l += exp(sink - m)`` before the last
  division.
* **unequal widths.**  Keys ``dq`` wide, values ``dv`` (192 / 128 there).

No (heads, rows, keys) score tensor exists: at 64 heads and 16,384 rows it
would be 64 GiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nvme_strom_tpu.ops.flash_attention import _pick_block
from nvme_strom_tpu.ops.paged_attention import _NEG_INF, _interpret

#: score rows (query heads of a group x query rows) and keys of one step of
#: the full walk: 512 x 1024 scores are 2 MiB of float32 in VMEM, as
#: ``strom_mla_prefill`` has them.  A window's steps are 1024 rows x 256 keys:
#: its band is 128 + block_q keys wide, and wider key blocks would be masked
#: for the most part.
ROWS, BLOCK_K = 512, 1024
WINDOW_ROWS, WINDOW_BLOCK_K = 1024, 256


def _kernel(pos_ref, q_ref, k_ref, v_ref, *refs, scale, bq, bk, window,
            sink):
    if sink:
        s_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    qi, ki = pl.program_id(2), pl.program_id(3)
    g, dq = q_ref.shape[2], q_ref.shape[4]
    first = pos_ref[0] + qi * bq            # the block's first row's position
    # the key block this step holds: the ki-th of the cache, or of the band
    kb = ki + (jnp.maximum(first - window + 1, 0) // bk if window else 0)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(kb * bk <= first + bq - 1)     # some row sees into the block
    def _update():
        q = q_ref[0, 0].reshape(g * bq, dq)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k_ref[0, 0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # row r of the score block is query row r % bq of head r // bq
        rows = first + jax.lax.rem(jax.lax.broadcasted_iota(
            jnp.int32, (g * bq, bk), 0), bq)
        cols = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (g * bq, bk), 1)
        seen = cols <= rows
        if window:
            seen = seen & (cols > rows - window)
        s = jnp.where(seen, s, _NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        # a row whose band lies wholly outside this block keeps m at its
        # floor: exp(s - m) would be 1 there, so the mask is applied again
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        l = l_ref[...]
        if sink:
            # one more column of the softmax, with no value behind it
            l = l + jnp.exp(s_ref[0] - m_ref[...])
        o_ref[0, 0] = (acc_ref[...] / l).reshape(
            g, bq, acc_ref.shape[1]).astype(o_ref.dtype)


def kv_prefill_attention(q, k, v, pos, *, scale: float, window: int = 0,
                         sink=None, block_q: int = None, block_k: int = None,
                         interpret: bool = None):
    """Causal attention of m query rows over a dense cache of S: q (b, heads,
    m, dq) sits at cache positions ``pos .. pos + m - 1`` (pos () int32:
    data, so a prefix of any length is one program) and row t sees the
    positions ``<= pos + t`` — with ``window`` w only the last w of them —
    of k (b, kv_heads, S, dq) and v (b, kv_heads, S, dv); query head h reads
    KV head ``h // (heads // kv_heads)``.  ``sink`` (heads,), or None: a
    learned score per head that joins the softmax and carries no value.
    Returns (b, heads, m, dv)."""
    b, nh, m, dq = q.shape
    nkv, S, dv = k.shape[1], k.shape[2], v.shape[3]
    if (k.shape != (b, nkv, S, dq) or v.shape != (b, nkv, S, dv)
            or nh % nkv):
        raise ValueError(f"q {q.shape} against k {k.shape}, v {v.shape}")
    g = nh // nkv
    rows, want_k = ((WINDOW_ROWS, WINDOW_BLOCK_K) if window
                    else (ROWS, BLOCK_K))
    bq = _pick_block(m, block_q or max(rows // g, 8))
    bk = _pick_block(S, block_k or want_k)
    n_k = S // bk
    if window:
        n_k = min(n_k, (bq + window - 2) // bk + 2)

    def kv_block(bi, hi, qi, ki, ps):
        # past the query block's last row the index stays where it is: an
        # unchanged block is not fetched again
        first = ps[0] + qi * bq
        lo = jnp.maximum(first - window + 1, 0) // bk if window else 0
        return (bi, hi, jnp.minimum(lo + ki, (first + bq - 1) // bk), 0)

    def q_block(bi, hi, qi, ki, ps):
        return (bi, hi, 0, qi, 0)

    in_specs = [pl.BlockSpec((1, 1, g, bq, dq), q_block),
                pl.BlockSpec((1, 1, bk, dq), kv_block),
                pl.BlockSpec((1, 1, bk, dv), kv_block)]
    args = [q.reshape(b, nkv, g, m, dq), k, v]
    if sink is not None:
        # each score row's own sink, as the kernel's rows lie: head-major
        in_specs.append(pl.BlockSpec(
            (1, g * bq, 1), lambda bi, hi, qi, ki, ps: (hi, 0, 0)))
        args.append(jnp.repeat(
            sink.astype(jnp.float32).reshape(nkv, g), bq,
            axis=1).reshape(nkv, g * bq, 1))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), bq=bq, bk=bk,
                          window=int(window), sink=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, nkv, m // bq, n_k),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, g, bq, dv), q_block),
            scratch_shapes=[pltpu.VMEM((g * bq, 1), jnp.float32),
                            pltpu.VMEM((g * bq, 1), jnp.float32),
                            pltpu.VMEM((g * bq, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, nkv, g, m, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="strom_window_prefill" if window else "strom_kv_prefill",
        interpret=_interpret(interpret),
    )(jnp.reshape(jnp.asarray(pos, jnp.int32), (1,)), *args)
    return out.reshape(b, nh, m, dv)

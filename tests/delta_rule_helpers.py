"""What the delta-rule test files share (``test_gdn.py``: Qwen3-Next's
geometry; ``test_olmo_hybrid.py``: Olmo-Hybrid's): the recurrence a token at
a time, and a fixture that records the logits every token of every request
was sampled from."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nvme_strom_tpu.models import serving


@pytest.fixture
def spy(monkeypatch):
    """Record the logits every token of every request was sampled from: the
    prefill's (``_admit_first``), then each decode step's (``paged_logits``
    compiled as the step compiles it, minus the donation)."""
    rows = {}
    step_logits = jax.jit(serving.paged_logits, static_argnums=(1,))

    def run(srv, lookahead=1):
        first = srv._admit_first

        def first_spy(group, logits):
            for i, plan in enumerate(group):
                rows.setdefault(plan["req"].rid, []).append(
                    np.asarray(logits[i]))
            return first(group, logits)

        def step_spy(params, cfg, tok, k_pool, v_pool, blk, off, table,
                     pos, temps, top_ps, seeds, *recur):
            logits, k_pool, v_pool, state = step_logits(
                params, cfg, tok, k_pool, v_pool, blk, off, table, pos,
                *recur)
            for b, req in enumerate(srv.slots):
                if req is not None:
                    rows[req.rid].append(np.asarray(logits[b]))
            nxt = serving._sample_slots(logits, temps, top_ps, seeds, pos)
            return nxt, k_pool, v_pool, state

        srv._admit_first = first_spy
        monkeypatch.setattr(serving, "_paged_step", step_spy)
        out = srv.run(lookahead=lookahead)
        return {rid: (toks, np.stack(rows[rid][:len(toks)]))
                for rid, toks in out.items()}
    return run


def recurrence(q, k, v, alpha, beta, s0, valid=None):
    """q, k (b, m, H, dk), v (b, m, H, dv), log alpha and beta (b, m, H), s0
    (b, H, dk, dv) -> (o (b, m, H, dv), S after the last VALID row),
    float32."""
    b, m = k.shape[:2]
    alpha = jnp.exp(alpha)
    valid = jnp.ones((b, m), bool) if valid is None else valid

    def step(s, x):
        q, k, v, a, bt, ok = x
        s1 = a[..., None, None] * s
        u = bt[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", s1, k))
        s1 = s1 + k[..., :, None] * u[..., None, :]
        o = jnp.einsum("bhkv,bhk->bhv", s1, q)
        return jnp.where(ok[:, None, None, None], s1, s), o

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, alpha, beta, valid))
    s, o = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(o, 0, 1), s

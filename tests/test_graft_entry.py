"""``__graft_entry__.py``: the single-device entry runs under ``jit`` and the
multi-chip dry run goes through on eight virtual devices."""

import jax
import jax.numpy as jnp

import __graft_entry__ as g


def test_graft_entry_contract():
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.ndim == 3 and bool(jnp.isfinite(out).all())


def test_graft_dryrun_multichip():
    g.dryrun_multichip(8)

"""The device-side names of the two serving programs (docs/OBSERVABILITY.md
"Scopes on the device"): every operation ``_paged_step`` and
``_paged_prefill`` issue lies under one family of ``strom.*`` scopes, a
prefill's under the label of its compiled shape as well — the string its host
span carries as ``program=`` — and a scope changes nothing but metadata.

Each case serves a few prompts on a tiny model of one kind, takes the
arguments the server really called its programs with, and walks their
jaxprs."""

import contextlib
import functools
import re

import jax
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from nvme_strom_tpu.models import serving

BLOCK = 8
FAMILIES = {"embed", "attn", "ssm", "conv", "mlp", "head", "prefill"}
BUCKET = re.compile(r"^strom\.prefill\.(\d+x\d+x\d+)$")


def _dense():
    import dataclasses
    import jax.numpy as jnp
    from nvme_strom_tpu.models.transformer import init_params, tiny_config
    cfg = dataclasses.replace(tiny_config(), dtype=jnp.float32)
    return cfg, init_params(jax.random.key(0), cfg)


def _tiny(module, weights):
    """The tiny model a sibling test file describes (``HF``, ``SEED``) on
    the benchmark's seeded weights, in float32."""
    import dataclasses
    import jax.numpy as jnp
    from nvme_strom_tpu.tools.convert_llama import config_from_hf
    cfg = dataclasses.replace(config_from_hf(module.HF), dtype=jnp.float32)
    return cfg, {k: v.astype(jnp.float32) for k, v in
                 weights.make_params(module.HF, module.SEED).items()}


def _hybrid():                        # mamba, mamba, attention, mamba
    import test_hybrid
    return _tiny(test_hybrid, test_hybrid.WH)


def _conv_experts():                  # (conv, conv, attention, conv) x 2
    import test_lfm2
    return _tiny(test_lfm2, test_lfm2.WM)


def _latent_shared():                 # MLA, a share of the experts + shared
    import test_mla
    return _tiny(test_mla, test_mla.WM)


def _window_experts():                # full, window, window, full, window
    import test_swa
    return _tiny(test_swa, test_swa.WS)


MODELS = {"dense": _dense, "hybrid": _hybrid, "conv_experts": _conv_experts,
          "latent_shared": _latent_shared, "window_experts": _window_experts}
#: the families a kind's programs must show (beside embed, mlp and head)
MIXERS = {"dense": {"attn"}, "hybrid": {"attn", "ssm"},
          "conv_experts": {"attn", "conv"}, "latent_shared": {"attn"},
          "window_experts": {"attn"}}
#: scopes inside a family that tell one kind of layer from another: a window
#: layer's row writer and kernel from a full layer's
INNER = {"window_experts": {"strom.attn.window", "strom.attn.paged"}}


def _shapes(args):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        if hasattr(a, "shape") else a, args)


@functools.cache
def _served(kind):
    """{"step": args, "prefill": [(program= of the span, args)]} of a server
    of this kind that admitted a group of two prompts, then one prompt
    behind a cached prefix, and decoded (once a kind: both tests read it)."""
    with pytest.MonkeyPatch.context() as monkeypatch:
        return _serve(kind, monkeypatch)


def _serve(kind, monkeypatch):
    cfg, params = MODELS[kind]()
    seen = {"step": None, "prefill": []}
    programs = []
    real = {n: getattr(serving, n) for n in ("_paged_step", "_paged_prefill")}

    def step(*args):
        seen["step"] = _shapes(args)
        return real["_paged_step"](*args)

    def prefill(*args):
        seen["prefill"].append((programs[-1], _shapes(args)))
        return real["_paged_prefill"](*args)

    monkeypatch.setattr(serving, "_paged_step", step)
    monkeypatch.setattr(serving, "_paged_prefill", prefill)
    srv = serving.DecodeServer(params, cfg, max_batch=4, max_len=64,
                               total_blocks=32, block_len=BLOCK)
    span = srv._span

    def spy(name, ctx=None, **args):
        if name == "strom.serve.prefill":
            programs.append(args["program"])
        return span(name, ctx, **args)

    srv._span = spy
    rng = np.random.default_rng(3)
    shared = rng.integers(0, cfg.vocab, 2 * BLOCK).tolist()
    srv.submit("a", shared + [5, 6, 7], 3)
    srv.submit("b", rng.integers(0, cfg.vocab, 11).tolist(), 3)
    srv.run()
    # another length; the prefix cache, where there is one, holds `shared`
    srv.submit("c", shared + list(range(10)), 3)
    srv.run()
    assert seen["step"] and len(seen["prefill"]) >= 2
    return seen


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for v in value if isinstance(value, (list, tuple)) else (value,):
            if isinstance(v, ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, Jaxpr):
                yield v


def _leaves(jaxpr, outer=()):
    """(primitive, scope path) of every equation that holds no jaxpr of its
    own; an inner jaxpr's name stacks are relative to its equation's, as
    lowering composes them."""
    for eqn in jaxpr.eqns:
        path = outer + tuple(
            s for s in str(eqn.source_info.name_stack).split("/") if s)
        subs = list(_sub_jaxprs(eqn))
        if not subs:
            yield eqn.primitive.name, path
        for sub in subs:
            yield from _leaves(sub, path)


def _family(path):
    """(bucket label or None, family or None) of a scope path: the family is
    its first ``strom.*`` component, the bucket label apart."""
    bucket = None
    for part in path:
        if BUCKET.match(part):
            bucket = bucket or BUCKET.match(part).group(1)
        elif part.startswith("strom."):
            return bucket, part.split(".")[1]
    return bucket, None


def _jaxpr(fn, args):
    return jax.make_jaxpr(fn, static_argnums=(1,))(*args).jaxpr


@pytest.mark.parametrize("kind", list(MODELS))
def test_every_operation_lies_under_one_family(kind):
    seen = _served(kind)
    found, parts = set(), set()
    for prim, path in _leaves(_jaxpr(serving._paged_step, seen["step"])):
        bucket, family = _family(path)
        assert family in FAMILIES - {"prefill"} and bucket is None, \
            (prim, path)
        found.add(family)
        parts.update(path)
    assert found == {"embed", "mlp", "head"} | MIXERS[kind]
    assert INNER.get(kind, set()) <= parts

    labels = set()
    for program, args in seen["prefill"]:
        found, parts = set(), set()
        for prim, path in _leaves(_jaxpr(serving._paged_prefill, args)):
            bucket, family = _family(path)
            assert family in FAMILIES, (prim, path)
            # ... under the very string the host span was opened with
            assert bucket == program, (prim, path, program)
            found.add(family)
            parts.update(path)
        assert found == {"embed", "mlp", "head", "prefill"} | MIXERS[kind]
        assert INNER.get(kind, set()) <= parts
        labels.add(program)
    # a whole prompt and one behind a cached prefix are two compiled shapes
    assert len(labels) >= 2


def _lowered(seen):
    texts = [serving._paged_step.lower(*seen["step"]).as_text()]
    texts += [serving._paged_prefill.lower(*args).as_text()
              for _, args in seen["prefill"]]
    return texts


@pytest.mark.parametrize("kind", list(MODELS))
def test_a_scope_changes_nothing_but_metadata(kind, monkeypatch):
    """The lowered programs, locations apart, are byte-equal with every
    ``named_scope`` a no-op."""
    seen = _served(kind)
    scoped = _lowered(seen)
    with_locations = serving._paged_step.lower(*seen["step"]).as_text(
        debug_info=True)
    assert "strom.head" in with_locations        # the scopes were there
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    try:
        bare = _lowered(seen)
        assert "strom." not in serving._paged_step.lower(
            *seen["step"]).as_text(debug_info=True)
    finally:
        jax.clear_caches()       # nothing traced without scopes stays cached
    assert scoped == bare

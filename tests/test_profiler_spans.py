"""The program's spans on the JAX profiler's timeline (PERF.md §3 "Spans
and counters", docs/OBSERVABILITY.md "With the JAX profiler").

ONE profiler session for the whole file (a process holds one at a time; the
tier-1 command's ``--dist loadfile`` keeps a file on one worker): the module
fixture runs a tiny ``DecodeServer`` with two admissions, a second
``DecodeServer`` over a prefix store (the ``kv_restore`` span) and a
``load_sharded`` of a two-tensor safetensors file under
``jax.profiler.start_trace``, reads the xplane back with
``jax.profiler.ProfileData``, and the tests assert on what it found.
"""

import glob
import json

import numpy as np
import pytest

from nvme_strom_tpu.io import StromEngine
from nvme_strom_tpu.utils.config import EngineConfig
from nvme_strom_tpu.utils import trace
from nvme_strom_tpu.utils.stats import StromStats
from nvme_strom_tpu.utils.trace import Tracer, connected_tree

SERVE_SPANS = ("strom.serve.step", "strom.serve.plan",
               "strom.serve.kv_restore", "strom.serve.admit",
               "strom.serve.prefill", "strom.serve.scatter",
               "strom.serve.first_token", "strom.serve.dispatch",
               "strom.serve.readback", "strom.serve.replay")
RESTORE_SPANS = ("strom.restore.load", "strom.restore.tensor",
                 "strom.restore.plan", "strom.restore.read_wait",
                 "strom.restore.put_wait", "strom.restore.slice",
                 "strom.h2d", "strom.restore.retire", "strom.restore.join")
#: the restore's spans that a ``PutStage`` worker opens (PR 45): they lie
#: on the workers' lines, beside the reading thread's and not inside them
WORKER_SPANS = ("strom.h2d", "strom.restore.slice", "strom.restore.retire",
                "strom.restore.join")
BLOCK = 8
#: a prompt that pads (11 -> 16) and one of whole blocks (16 -> 16)
PROMPT_LENS = (11, 16)


def _engine(tracer):
    return StromEngine(EngineConfig(chunk_bytes=1 << 20, queue_depth=8,
                                    buffer_pool_bytes=16 << 20),
                       stats=StromStats(), tracer=tracer)


def _serve_paged(params, cfg):
    """No store, so the server's spans go to the global tracer."""
    from nvme_strom_tpu.models.serving import DecodeServer
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64,
                       total_blocks=16, block_len=BLOCK)
    rng = np.random.default_rng(0)
    for i, n in enumerate(PROMPT_LENS):
        srv.submit(f"r{i}", rng.integers(0, cfg.vocab, n).tolist(), 6)
    out = {}
    calls = 0
    while not srv.idle:
        out.update(srv.step_many(2))
        calls += 1
    assert set(out) == {"r0", "r1"} and calls >= 2
    return dict(srv.timings)


def _serve_with_store(params, cfg, tracer, tmp):
    """A second request restores the first one's prompt pages from the
    store: the one path that opens ``strom.serve.kv_restore``."""
    from nvme_strom_tpu.models.kv_offload import PrefixStore
    from nvme_strom_tpu.models.serving import DecodeServer
    page = 4
    eng = _engine(tracer)
    page_bytes = 2 * cfg.n_layers * cfg.n_kv_heads * page * cfg.head_dim * 4
    store = PrefixStore(cfg, eng, str(tmp / "p.kvstore"), page_tokens=page,
                        capacity_bytes=64 * page_bytes)
    # no HBM block cache: it would serve "b" before the store is asked
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64, kv_store=store,
                       prefix_cache=False)
    shared = np.random.default_rng(1).integers(0, cfg.vocab,
                                               3 * page).tolist()
    srv.submit("a", shared + [7, 8], 2)
    srv.run()
    srv.submit("b", shared + [9], 2)
    srv.run()
    store.close()
    eng.close_all()


def _restore(tracer, tmp):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from nvme_strom_tpu.formats.safetensors import write_safetensors
    from nvme_strom_tpu.parallel.weights import LazyCheckpoint
    rng = np.random.default_rng(2)
    tensors = {"rows": rng.standard_normal((64, 32)).astype(np.float32),
               "cols": rng.standard_normal((16, 64)).astype(np.float32)}
    path = tmp / "two.safetensors"
    write_safetensors(path, tensors)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    shardings = {"rows": NamedSharding(mesh, P("tp", None)),
                 "cols": NamedSharding(mesh, P(None, "tp"))}  # host gather
    eng = _engine(tracer)
    try:
        out = LazyCheckpoint(str(path)).load_sharded(shardings, engine=eng)
        for name, want in tensors.items():
            np.testing.assert_array_equal(np.asarray(out[name]), want)
    finally:
        eng.close_all()


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    import jax
    import jax.numpy as jnp

    from nvme_strom_tpu.models.transformer import (TransformerConfig,
                                                   init_params, tiny_config)
    tmp = tmp_path_factory.mktemp("spans")
    cfg = TransformerConfig(**{**tiny_config().__dict__,
                               "dtype": jnp.float32})
    params = init_params(jax.random.key(0), cfg)
    tracer = Tracer(str(tmp / "strom.trace.json"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1          # as the benchmark's traced runs
    jax.profiler.start_trace(str(tmp / "prof"), profiler_options=opts)
    try:
        inside = type(Tracer().span("strom.test.off"))
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(trace, "global_tracer", tracer)
            timings = _serve_paged(params, cfg)
        _serve_with_store(params, cfg, tracer, tmp)
        _restore(tracer, tmp)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp / "prof" / "**" / "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    threads = []                        # one [(name, start, end)] a thread
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                threads.append([(e.name, e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events])
    tracer.export()
    with open(tmp / "strom.trace.json") as f:
        chrome = json.load(f)["traceEvents"]
    tracer.disable()
    return {"threads": threads, "timings": timings, "chrome": chrome,
            "off_span_type_in_session": inside}


def _lies_inside(threads, inner: str, outer: str) -> bool:
    """Every ``inner`` event lies inside an ``outer`` event of its thread."""
    found = False
    for evs in threads:
        outers = [(s, e) for n, s, e in evs if n == outer]
        for n, s, e in evs:
            if n == inner:
                found = True
                if not any(os <= s and e <= oe for os, oe in outers):
                    return False
    return found


@pytest.mark.parametrize("name", SERVE_SPANS + RESTORE_SPANS)
def test_span_is_on_the_profilers_timeline_under_its_bare_name(traced, name):
    # every one of them carries keyword arguments: ProfileData must still
    # hand back the bare name, since every reader matches on names
    assert any(n == name for evs in traced["threads"] for n, _, _ in evs)


def test_no_program_span_has_a_benchmark_phase_name(traced):
    names = {n for evs in traced["threads"] for n, _, _ in evs}
    assert not names & {"admit", "submit", "wait", "restore", "step"}


@pytest.mark.parametrize("inner,outer", [
    ("strom.serve.prefill", "strom.serve.admit"),
    ("strom.serve.scatter", "strom.serve.admit"),
    ("strom.serve.first_token", "strom.serve.admit"),
    ("strom.serve.admit", "strom.serve.step"),
    ("strom.serve.plan", "strom.serve.step"),
    ("strom.serve.dispatch", "strom.serve.step"),
    ("strom.serve.readback", "strom.serve.step"),
    ("strom.serve.replay", "strom.serve.step"),
    ("strom.restore.read_wait", "strom.restore.tensor"),
    ("strom.restore.plan", "strom.restore.tensor"),
    ("strom.restore.put_wait", "strom.restore.tensor"),
    ("strom.restore.tensor", "strom.restore.load"),
])
def test_spans_nest_as_the_code_nests(traced, inner, outer):
    assert _lies_inside(traced["threads"], inner, outer)


@pytest.mark.parametrize("name", WORKER_SPANS)
def test_restore_worker_spans_lie_beside_the_reading_thread(traced, name):
    """The gathers, puts, pushes and joins of a restore are the stage's
    workers': on lines without ``strom.restore.load``, and in time inside
    the load (``close`` ends the workers before the load's span does)."""
    loads = [(s, e) for evs in traced["threads"] for n, s, e in evs
             if n == "strom.restore.load"]
    (load,) = loads
    on_workers = 0
    for evs in traced["threads"]:
        reader = any(n == "strom.restore.load" for n, _, _ in evs)
        for n, s, e in evs:
            if n != name or not (load[0] <= s <= load[1]):
                continue            # (the serving half's own strom.h2d)
            assert e <= load[1]
            on_workers += not reader
    assert on_workers > 0


def test_admission_counters(traced):
    t = traced["timings"]
    assert t["admits"] == len(PROMPT_LENS)
    assert 0 < t["prefill_s"] <= t["admit_s"] and t["queue_wait_s"] >= 0
    # nothing cached: every prompt token is computed, padded to blocks
    assert t["prompt_tokens"] == sum(PROMPT_LENS) == 27
    assert t["prefill_tokens"] == sum(-(-n // BLOCK) * BLOCK
                                      for n in PROMPT_LENS) == 32
    assert all(isinstance(v, (int, float)) for v in t.values())


def test_chrome_spans_carry_one_trace_id_per_request(traced):
    evs = traced["chrome"]
    roots = [e for e in evs if e["name"] == "strom.serve.request"]
    assert len(roots) == 4              # r0, r1 (paged); a, b (store)
    ids = {e["args"]["trace"] for e in roots}
    assert len(ids) == 4
    by_int = {int(tid, 16): tid for tid in ids}
    shared = []
    for tid in ids:
        (admit,) = [e for e in evs if e["name"] == "strom.serve.admit"
                    and e["args"].get("trace") == tid]
        # a request that shared its prefill program with another has the
        # admission in its own tree and finds the program's spans in the
        # tree that admission names
        lead = tid
        if "group" in admit["args"]:
            lead = by_int[int(admit["args"]["group"], 16)]
            shared.append((tid, lead))
        names = [e["name"] for e in evs
                 if e.get("args", {}).get("trace") == lead]
        for name in ("strom.serve.prefill", "strom.serve.scatter",
                     "strom.serve.first_token"):
            assert names.count(name) >= 1, (tid, name)
        assert connected_tree(evs, tid)
    # r0 and r1 (11 and 16 tokens: one bucket) went through ONE program
    ((member, lead),) = shared
    (group,) = [e for e in evs if e["name"] == "strom.serve.admit"
                and e["args"].get("trace") == lead]
    assert group["args"]["rows"] == 2 and group["args"]["rid"] == "r0 r1"
    assert {int(t, 16) for t in group["args"]["traces"].split()} == {
        int(member, 16), int(lead, 16)}
    # the batched store restore names its requests in one string, and the
    # admission keeps the wait it always carried
    (kv,) = [e for e in evs if e["name"] == "strom.serve.kv_restore"]
    assert kv["args"]["slots"] == 1 and kv["args"]["pages"] == 3
    assert kv["args"]["traces"] == f"{int(kv['args']['trace'], 16):x}"
    assert all(e["args"]["queue_wait_ms"] >= 0 for e in evs
               if e["name"] == "strom.serve.admit")
    # the restore path's spans reached the same file, outside any request
    flat = {e["name"] for e in evs if "trace" not in e.get("args", {})}
    assert set(RESTORE_SPANS) <= flat


def test_disabled_tracer_builds_no_span_object(traced):
    t = Tracer()                        # no path: disabled
    a, b = t.span("strom.test.a", bytes=1), t.span("strom.test.b")
    assert a is b                       # one shared no-op, nothing allocated
    assert not a                        # falsy: callers skip late arguments
    with a as inside:
        inside.set_metadata(k=1)
    assert len(t) == 0
    # inside a profiler session the disabled tracer hands out the bare
    # annotation — still no span object of the tracer's
    kind = traced["off_span_type_in_session"]
    assert kind is not type(a)
    assert kind.__name__ == "TraceAnnotation"


def test_enabled_tracer_span_takes_late_arguments(tmp_path):
    t = Tracer(str(tmp_path / "t.json"))
    with t.span("strom.test.late", "strom.test", first=1) as sp:
        sp.set_metadata(second=2)
    (ev,) = t.events()
    assert ev["cat"] == "strom.test"
    assert ev["args"] == {"first": 1, "second": 2}
    t.disable()


def test_prefill_is_an_attribution_component():
    from nvme_strom_tpu.obs.attrib import (COMPONENTS, component_of,
                                           fold_events)
    assert "prefill" in COMPONENTS
    assert component_of("strom.serve.prefill") == "prefill"
    assert component_of("strom.serve.scatter") is None     # structural
    assert component_of("strom.h2d") == "bridge"
    fold = fold_events([("strom.serve.request", 0, 1_000_000),
                        ("strom.serve.admit", 0, 700_000),
                        ("strom.serve.prefill", 100_000, 600_000)],
                       0, 1_000_000)
    assert fold["components"]["prefill"] == pytest.approx(500.0)
    assert fold["unattributed_us"] == pytest.approx(500.0)

"""What every cell shares: reading BENCHMARK.json and the data files it names,
the look for the chip, the compile cache, the compile counter, the profiler's
window, and the one result line."""

from __future__ import annotations

import glob
import importlib
import json
import os
import shutil
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
#: the profiler's window inside a traced run: long traces overflow buffers
#: and slow the host, so at most this much of the window is traced — its END,
#: so that the seconds the profiler takes to stop fall after the window
TRACE_CAP_S = 20.0


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """The cell, its configuration's file and its traffic file, all found by
    the names ``BENCHMARK.json`` gives."""
    bench = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json (have {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return {"bench": bench, "cell": cell,
            "config": load_json(cfg_entry["file"]),
            "traffic": load_json("benchmark", "traffic",
                                 cell["traffic"] + ".json")}


def plugin(group: str, name: str):
    """``benchmark/<group>/<name>.py`` — a runner, a traffic kind, a metric's
    reader or a reference, found by its name (a metric ``a.b`` is read by
    ``a.py``: the suffix only says which cells report it)."""
    if group in ("layer_metrics", "end_to_end"):
        name = name.split(".", 1)[0]
    return importlib.import_module(f"benchmark.{group}.{name}")


def require_chips(chips: int, allow_cpu: bool = False) -> dict:
    """The device as JAX reports it; exits non-zero without a TPU or with
    fewer chips than the cell asks for.  ``allow_cpu`` exists for the
    benchmark's own tests only (never reachable from the command line)."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: platform={info['platform']} kind={info['kind']!r} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu" and not allow_cpu:
        raise SystemExit(f"benchmark: no TPU (platform "
                         f"{info['platform']!r}); it measures on the chip only")
    if info["count"] < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, "
                         f"JAX sees {info['count']}")
    return info


def enable_compile_cache() -> str:
    """JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says, else
    at the fixed ``<checkout>/.jax_cache`` (the program's own rule, through
    its own function).  Sub-second compiles are kept too: the eager prefill is
    hundreds of them, and each run is a new process."""
    import jax

    from nvme_strom_tpu.utils.compile_cache import enable_compile_cache as en
    path = en()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts backend compiles (cache misses that really compile) through
    JAX's own monitoring events; ``mark()`` starts a new count."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def mark(self) -> None:
        self.count, self.seconds = 0, 0.0


def memory_peak_bytes(devices) -> int:
    """Peak on the fullest chip (0 where the backend reports none)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class TraceWindow:
    """The profiler around (part of) the measured window.  Off (``on`` false)
    every call is a no-op, so runners call it unconditionally."""

    def __init__(self, on: bool, workload: str):
        self.on = on
        self.dir = os.path.join(BENCH_DIR, ".trace", workload)
        self.t0 = self.t1 = None
        self._running = False

    def tick(self, elapsed: float, seconds: float) -> None:
        """Called from the measuring loops: starts the profiler once the
        window has at most TRACE_CAP_S left."""
        if self.on and self.t0 is None \
                and elapsed >= seconds - TRACE_CAP_S:
            self.start()

    def start(self) -> None:
        if not self.on:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        # spans come from TraceAnnotations (level 1); the runtime's own host
        # events (level 2) slow the eager prefill enough to overload the
        # chat cell at its rate (my chip run, PR 23)
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.monotonic()
        self._running = True

    def stop(self) -> None:
        if not self._running:
            return
        import jax
        self.t1 = time.monotonic()
        jax.profiler.stop_trace()
        self._running = False

    def annotate(self, name: str):
        """A host span on the profiler's clock (a no-op context when off)."""
        if not self._running:
            import contextlib
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def file(self):
        paths = sorted(glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        return paths[-1] if paths else None


def peaks_for(kind: str) -> dict:
    table = load_json("benchmark", "peaks.json")
    if kind not in table:
        raise SystemExit(f"benchmark: device kind {kind!r} is not in "
                         "benchmark/peaks.json; add it with its source")
    return table[kind]


def print_checks(checks: list) -> bool:
    """Every number compared, beside its limit; True if all hold."""
    ok = True
    for name, value, limit in checks:
        good = value is not None and value <= limit
        ok = ok and good
        print(f"check: {name} = {value} (limit {limit}) "
              f"{'ok' if good else 'FAILS'}", flush=True)
    return ok


def emit(result: dict) -> None:
    """The numbers compared as the last lines on standard error, then the
    result as the last line on standard output."""
    for name, c in result.get("checks", {}).items():
        print(f"check: {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    sys.stdout.flush()
    print(json.dumps(result), flush=True)

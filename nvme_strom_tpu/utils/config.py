"""Configuration dataclasses — the analogue of NVMe-Strom's module params and
ioctl arguments (chunk size, number of in-flight requests; SURVEY.md §5
"Config/flags")."""

from __future__ import annotations

import os
from dataclasses import dataclass, field


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


@dataclass(frozen=True)
class EngineConfig:
    """strom-io C++ engine knobs.

    ``chunk_bytes`` mirrors the reference benchmark's chunk size argument and
    ``queue_depth`` its "number of async buffers" (SURVEY.md §3.4).  Chunks
    must be multiples of the O_DIRECT logical block alignment.  STROM_*
    environment variables are read at construction time.
    """

    chunk_bytes: int = field(
        default_factory=lambda: _env_int("STROM_CHUNK_BYTES", 4 << 20))
    queue_depth: int = field(
        default_factory=lambda: _env_int("STROM_QUEUE_DEPTH", 16))
    alignment: int = field(
        default_factory=lambda: _env_int("STROM_ALIGNMENT", 4096))
    buffer_pool_bytes: int = field(
        default_factory=lambda: _env_int("STROM_POOL_BYTES", 256 << 20))
    use_io_uring: bool = field(
        default_factory=lambda: os.environ.get("STROM_IO_URING", "1") != "0")
    lock_buffers: bool = field(
        default_factory=lambda: os.environ.get("STROM_MLOCK", "1") != "0")
    max_retries: int = field(
        default_factory=lambda: _env_int("STROM_MAX_RETRIES", 2))
    #: attribute read payload to md-raid0 members per stripe geometry
    #: (per-member counters in stats/strom_stat; small per-submit cost).
    #: STROM_STRIPE_SIM="<chunk_kib>:<n>" simulates geometry on a
    #: non-raid device (bench/test evidence without raid hardware).
    stripe_accounting: bool = field(
        default_factory=lambda: os.environ.get("STROM_STRIPE_ACCT",
                                               "0") == "1")
    #: submission rings the engine shards into (docs/PERF.md): each ring
    #: is an independent io_uring (or worker pool) with its own staging
    #: pool slice, deferral queue, and completion reaping, so concurrent
    #: traffic classes never serialize behind one doorbell.  0 (default)
    #: = auto from CPU topology and the NVMe device's hardware queue
    #: count, capped by what the configured queue_depth/buffer pool can
    #: feed (an engine too small to shard stays single-ring — the exact
    #: pre-sharding behavior, also forced by STROM_RINGS=1).
    n_rings: int = field(
        default_factory=lambda: _env_int("STROM_RINGS", 0))

    def __post_init__(self):
        if (self.alignment < 512 or self.alignment > (1 << 22)
                or (self.alignment & (self.alignment - 1))):
            raise ValueError(
                f"alignment ({self.alignment}) must be a power of two in "
                f"[512, 4MiB] (O_DIRECT logical-block constraint)"
            )
        if self.chunk_bytes <= 0 or self.chunk_bytes % self.alignment:
            raise ValueError(
                f"chunk_bytes ({self.chunk_bytes}) must be a positive "
                f"multiple of alignment ({self.alignment})"
            )
        if not 1 <= self.queue_depth <= 4096:
            raise ValueError(
                f"queue_depth ({self.queue_depth}) must be in [1, 4096]")
        if self.buffer_pool_bytes < self.chunk_bytes:
            raise ValueError(
                f"buffer_pool_bytes ({self.buffer_pool_bytes}) must hold at "
                f"least one chunk ({self.chunk_bytes})")
        if self.max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        if not 0 <= self.n_rings <= 64:
            raise ValueError(
                f"n_rings ({self.n_rings}) must be in [0, 64] "
                "(0 = auto; 64 = STROM_MAX_RINGS, the request-id "
                "ring-bits budget)")


@dataclass(frozen=True)
class SchedConfig:
    """QoS scheduler knobs (io/sched.py; semantics in docs/PERF.md).

    The scheduler sits at the planned-batch boundary of a multi-ring
    engine: every batch carries a latency class, classes share rings by
    weighted fair-share (strict priority order, one round's deficit of
    banking), and aging promotes any batch stuck longer than
    ``aging_rounds`` dispatch rounds so the lowest class can never
    starve outright.  STROM_* environment variables are read at
    construction time, mirroring EngineConfig.
    """

    #: scheduler on/off (STROM_SCHED=0 disables even on a multi-ring
    #: engine: batches then route round-robin exactly like scalar reads)
    enabled: bool = field(
        default_factory=lambda: os.environ.get("STROM_SCHED", "1") != "0")
    #: dispatch rounds a queued batch may wait before aging promotes it
    #: ahead of every weight/priority consideration — the starvation
    #: bound (tests/test_sched.py proves it)
    aging_rounds: int = field(
        default_factory=lambda: _env_int("STROM_SCHED_AGING_K", 16))
    #: per-ring in-flight I/O budget gating dispatch; 0 = the ring's
    #: queue depth.  Measured as submitted-minus-COMPLETED (not
    #: released), so a consumer sitting on completed views can never
    #: wedge admission.
    max_inflight_per_ring: int = field(
        default_factory=lambda: _env_int("STROM_SCHED_INFLIGHT", 0))
    #: "decode=8,restore=4,prefetch=2,scan=2,scrub=1" — overrides the default
    #: class weights (io/sched.py DEFAULT_POLICIES)
    class_weights: str = field(
        default_factory=lambda: os.environ.get("STROM_CLASS_WEIGHTS", ""))

    def __post_init__(self):
        if self.aging_rounds < 1:
            raise ValueError("aging_rounds must be >= 1")
        if self.max_inflight_per_ring < 0:
            raise ValueError("max_inflight_per_ring must be >= 0")


@dataclass(frozen=True)
class HostCacheConfig:
    """Tiered pinned-host DRAM cache knobs (io/hostcache.py; semantics in
    docs/PERF.md §4).

    The cache sits between NVMe and HBM at the planner boundary: repeat
    reads of hot spans (weight shards re-streamed per replica, hot KV
    prefixes, hot SQL partitions) are served from an mlock'd host arena
    at DRAM speed instead of re-paying SSD latency.  STROM_* environment
    variables are read at construction time, mirroring EngineConfig.
    """

    #: arena budget in MiB; 0 (default) disables the tier entirely —
    #: the planner's submit path is then bit-for-bit the pre-cache code
    budget_mb: int = field(
        default_factory=lambda: _env_int("STROM_HOSTCACHE_MB", 0))
    #: cache-line size override in bytes (0 = the ``chunk_bytes`` of
    #: the first engine that touches the tier, rounded down to a power
    #: of two); must be a power of two >= 4096
    line_bytes: int = field(
        default_factory=lambda: _env_int("STROM_HOSTCACHE_LINE_BYTES", 0))
    #: "decode=8,restore=4,prefetch=2,scan=2,scrub=1" — per-QoS-class residency
    #: quota weights (normalized over the budget); empty = the QoS
    #: scheduler's stock class weights, so the two layers agree on
    #: relative generosity by default
    class_quotas: str = field(
        default_factory=lambda: os.environ.get(
            "STROM_HOSTCACHE_CLASS_QUOTAS", ""))
    #: ghost-list capacity as a multiple of the line capacity — how long
    #: a once-missed line key is remembered for the second-chance
    #: admission verdict
    ghost_factor: int = field(
        default_factory=lambda: _env_int("STROM_HOSTCACHE_GHOST_FACTOR", 4))
    #: pin the arena (mlock) — shares the engine pool's STROM_MLOCK knob:
    #: one switch for "no pinned memory on this box"
    lock_arena: bool = field(
        default_factory=lambda: os.environ.get("STROM_MLOCK", "1") != "0")

    def __post_init__(self):
        if self.budget_mb < 0:
            raise ValueError("budget_mb must be >= 0")
        if self.line_bytes and (self.line_bytes < 4096
                                or self.line_bytes & (self.line_bytes - 1)):
            raise ValueError(
                f"line_bytes ({self.line_bytes}) must be 0 (auto) or a "
                f"power of two >= 4096 (O_DIRECT block alignment)")
        if self.ghost_factor < 1:
            raise ValueError("ghost_factor must be >= 1")
        if self.class_quotas:
            # validate HERE, like every other knob: a malformed value
            # must fail loudly at construction, not out of the first
            # consumer read that lazily builds the tier.  One grammar:
            # the tier's own parser (lazy import breaks no cycle — this
            # module is fully loaded before any config is constructed).
            from nvme_strom_tpu.io.hostcache import parse_class_quotas
            parse_class_quotas(self.class_quotas)


@dataclass(frozen=True)
class BreakerConfig:
    """Failure-domain supervision knobs (io/health.py; semantics in
    docs/RESILIENCE.md "Failure domains").

    The supervisor sits above ResilientEngine: per-ring rolling error
    windows + a completion-stall detector feed a circuit breaker per
    ring (trip → route around it via the QoS scheduler → hot-restart it
    → half-open → closed) and a device-level breaker whose open state
    is the degraded buffered mode — ``plan_and_submit`` serves plain
    ``pread``s until a half-open probe restores the fast path.  STROM_*
    environment variables are read at construction time, mirroring
    EngineConfig.
    """

    #: master switch (STROM_BREAKER=0 removes the supervision layer
    #: entirely: no health polling, no degraded fallback — the exact
    #: pre-supervision engine)
    enabled: bool = field(
        default_factory=lambda: os.environ.get("STROM_BREAKER",
                                               "1") != "0")
    #: rolling error-window span: errors older than this stop counting
    #: toward any breaker verdict
    window_s: float = field(
        default_factory=lambda: _env_float("STROM_BREAKER_WINDOW_S", 5.0))
    #: per-ring error budget: this many errors inside the window trips
    #: the ring's breaker
    ring_errors: int = field(
        default_factory=lambda: _env_int("STROM_BREAKER_ERRORS", 8))
    #: device-level error budget: this many errors across ALL rings
    #: inside the window opens the device breaker (degraded mode)
    device_errors: int = field(
        default_factory=lambda: _env_int("STROM_BREAKER_DEVICE_ERRORS",
                                         16))
    #: a ring whose oldest in-flight request is older than this is
    #: declared stalled (completions never arrived) and trips its
    #: breaker — the reap-side stall detector
    stall_s: float = field(
        default_factory=lambda: _env_float("STROM_BREAKER_STALL_S", 5.0))
    #: hot-restart drain budget: how long the restart waits for a
    #: tripped ring's dispatched I/O before aborting -ETIMEDOUT
    drain_s: float = field(
        default_factory=lambda: _env_float("STROM_BREAKER_DRAIN_S", 0.5))
    #: clean time a restarted (half-open) ring must serve before its
    #: breaker closes again
    half_open_s: float = field(
        default_factory=lambda: _env_float("STROM_BREAKER_HALF_OPEN_S",
                                           2.0))
    #: min interval between hot-restart attempts of one ring (a ring
    #: that re-trips immediately must not be restarted in a tight loop)
    restart_backoff_s: float = field(
        default_factory=lambda: _env_float("STROM_BREAKER_RESTART_S", 5.0))
    #: degraded-mode half-open probe interval: while browned out, one
    #: read per interval rides the REAL path; success restores it
    probe_s: float = field(
        default_factory=lambda: _env_float("STROM_DEGRADED_PROBE_S", 1.0))
    #: wait budget of one half-open probe (a wedged device must not
    #: stall the degraded reader behind its own probe for long)
    probe_timeout_s: float = field(
        default_factory=lambda: _env_float(
            "STROM_DEGRADED_PROBE_TIMEOUT_S", 2.0))

    def __post_init__(self):
        if self.window_s <= 0:
            raise ValueError("window_s must be > 0")
        if self.ring_errors < 1 or self.device_errors < 1:
            raise ValueError("error budgets must be >= 1")
        if self.stall_s <= 0 or self.drain_s <= 0:
            raise ValueError("stall_s/drain_s must be > 0")
        if self.half_open_s < 0 or self.restart_backoff_s < 0:
            raise ValueError("half_open_s/restart_backoff_s must be >= 0")
        if self.probe_s < 0 or self.probe_timeout_s <= 0:
            raise ValueError("probe_s must be >= 0, probe_timeout_s > 0")


@dataclass(frozen=True)
class FlightConfig:
    """Flight-recorder knobs (io/flightrec.py; semantics in
    docs/OBSERVABILITY.md).

    An always-on bounded ring buffer of recent per-op records (class,
    ring, bytes, latency, outcome) that dumps itself to disk when a
    failure trigger fires — breaker trip, ring restart, SLO violation,
    watchdog stall — so the post-mortem starts with the exact ops that
    preceded the event instead of aggregate counters.  STROM_*
    environment variables are read at construction time, mirroring
    EngineConfig.
    """

    #: master switch (STROM_FLIGHT=0 removes the recorder entirely:
    #: no per-op record, no trigger dumps — the exact pre-recorder
    #: engine)
    enabled: bool = field(
        default_factory=lambda: os.environ.get("STROM_FLIGHT",
                                               "1") != "0")
    #: ring-buffer capacity in op records (each ~100 B of Python tuple;
    #: the default keeps the always-on footprint under ~1 MiB)
    ops: int = field(
        default_factory=lambda: _env_int("STROM_FLIGHT_OPS", 4096))
    #: dump directory; empty = the system temp dir (dumps are named
    #: strom_flight_<pid>_<reason>_<n>.json)
    dir: str = field(
        default_factory=lambda: os.environ.get("STROM_FLIGHT_DIR", ""))
    #: min seconds between dumps — a flapping breaker must not bury the
    #: disk in near-identical post-mortems (the FIRST dump of a burst
    #: is the interesting one)
    min_interval_s: float = field(
        default_factory=lambda: _env_float("STROM_FLIGHT_MIN_S", 5.0))

    def __post_init__(self):
        if self.ops < 16:
            raise ValueError(f"ops ({self.ops}) must be >= 16 — a "
                             "post-mortem of 15 ops explains nothing")
        if self.min_interval_s < 0:
            raise ValueError("min_interval_s must be >= 0")


@dataclass(frozen=True)
class KVServeConfig:
    """Serving KV prefix-store knobs (models/kv_offload.py PrefixStore;
    semantics in docs/PERF.md §5).

    The store sits under the decode servers (models/serving.py): prompt
    KV pages are content-addressed by a rolling hash of their token
    chain (per model identity), written ONCE however many sessions
    share the prefix, and restored through the decode-class batched
    read path instead of being re-prefilled.  STROM_* environment
    variables are read at construction time, mirroring EngineConfig.
    """

    #: master switch: STROM_KV_PREFIX=1 enables the store for servers
    #: built through ``build_prefix_store``; 0 (default) is bit-for-bit
    #: today's per-session path (proven by tests/test_kvserve.py)
    prefix_enabled: bool = field(
        default_factory=lambda: os.environ.get("STROM_KV_PREFIX",
                                               "0") == "1")
    #: NVMe budget of the page store in MiB; eviction reclaims the
    #: lowest benefit score (reuse frequency x restore cost) first
    store_mb: int = field(
        default_factory=lambda: _env_int("STROM_KV_STORE_MB", 64))
    #: tokens per content-addressed page; 0 (default) adopts the
    #: server's own granularity (DecodeServer.block_len)
    page_tokens: int = field(
        default_factory=lambda: _env_int("STROM_KV_PAGE_TOKENS", 0))
    #: decode-path restore p99 target in ms; a violation makes the SLO
    #: governor raise the decode class's concurrent-hedge budget (and
    #: scheduler weight) until the p99 recovers.  0 (default) = no SLO.
    p99_target_ms: float = field(
        default_factory=lambda: _env_float("STROM_KV_P99_MS", 0.0))

    def __post_init__(self):
        if self.store_mb < 0:
            raise ValueError("store_mb must be >= 0")
        if self.page_tokens < 0:
            raise ValueError("page_tokens must be >= 0")
        if self.p99_target_ms < 0:
            raise ValueError("p99_target_ms must be >= 0")


@dataclass(frozen=True)
class ResilientConfig:
    """Recovery policy of ``io/resilient.py``'s ``ResilientEngine``.

    One knob block for the three recovery mechanisms (docs/RESILIENCE.md):
    bounded retry with exponential backoff + jitter, hedged duplicate
    reads past a latency threshold, and cancel-then-resubmit of stuck
    requests.  STROM_* environment variables are read at construction
    time, mirroring EngineConfig.
    """

    #: failed/short/stuck read resubmissions before giving up loudly
    max_retries: int = field(
        default_factory=lambda: _env_int("STROM_RETRY_MAX", 3))
    #: first backoff sleep; doubles per attempt up to backoff_max_s
    backoff_base_s: float = field(
        default_factory=lambda: _env_float("STROM_RETRY_BACKOFF_S", 0.01))
    backoff_max_s: float = field(
        default_factory=lambda: _env_float("STROM_RETRY_BACKOFF_MAX_S", 1.0))
    #: uniform jitter fraction applied to every backoff sleep (0..1);
    #: deterministic per engine via ``seed``
    jitter: float = field(
        default_factory=lambda: _env_float("STROM_RETRY_JITTER", 0.5))
    #: issue a duplicate (hedged) read when the original is still in
    #: flight after this many seconds; 0 = derive from the engine's
    #: latency histogram (hedge_percentile * hedge_multiplier)
    hedge_after_s: float = field(
        default_factory=lambda: _env_float("STROM_HEDGE_AFTER_S", 0.0))
    hedge_percentile: int = 99
    hedge_multiplier: float = field(
        default_factory=lambda: _env_float("STROM_HEDGE_MULTIPLIER", 3.0))
    #: floor for the derived threshold — a cold histogram must not turn
    #: every read into a hedge
    hedge_min_s: float = field(
        default_factory=lambda: _env_float("STROM_HEDGE_MIN_S", 0.005))
    #: 0 disables hedging entirely (retry/stuck handling stays on)
    hedging: bool = field(
        default_factory=lambda: os.environ.get("STROM_HEDGE", "1") != "0")
    #: a request still in flight after this long is presumed wedged:
    #: cancel (release) it and resubmit — counts against max_retries
    stuck_timeout_s: float = field(
        default_factory=lambda: _env_float("STROM_STUCK_TIMEOUT_S", 30.0))
    #: seed of the deterministic backoff-jitter stream
    seed: int = field(
        default_factory=lambda: _env_int("STROM_RETRY_SEED", 0))

    def __post_init__(self):
        if self.max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        if self.backoff_base_s < 0 or self.backoff_max_s < 0:
            raise ValueError("backoff times must be >= 0")
        if not 0 <= self.jitter <= 1:
            raise ValueError(f"jitter ({self.jitter}) must be in [0, 1]")
        if self.hedge_after_s < 0 or self.hedge_min_s < 0:
            raise ValueError("hedge thresholds must be >= 0")
        if self.stuck_timeout_s <= 0:
            raise ValueError("stuck_timeout_s must be > 0")


@dataclass(frozen=True)
class LoaderConfig:
    """Dataloader knobs: per-host shard selection + device prefetch depth."""

    batch_size: int = 32
    #: batches dispatched ahead of the consumer; 4 covers the
    #: bandwidth-delay product of the probe-tuned stream operating
    #: points (the window-5 stable block rode depth 4-8 at 0.83-0.93
    #: of ceiling) — 2 left the link idle half of every batch cycle
    prefetch: int = 4
    shuffle_buffer: int = 0
    drop_remainder: bool = True
    seed: int = 0
    #: total cached index entries (samples) across shards before the
    #: oldest shard's index is evicted — bounds host RSS on web-scale
    #: datasets while small/medium datasets index each shard once per
    #: loader instead of once per epoch
    index_cache_samples: int = 1_000_000
    #: shard-quarantine error budget (docs/RESILIENCE.md): a shard whose
    #: index/read/decode fails is skipped-and-logged (counted as
    #: shards_quarantined, skipped for the loader's remaining epochs) as
    #: long as fewer than this many shards have been quarantined; the
    #: budget exhausted, the next failure raises loudly with the full
    #: quarantine list.  0 (default) preserves fail-fast behavior.
    #: CAVEAT (multi-host): a quarantined shard shrinks only THIS host's
    #: epoch, so hosts yield different batch counts and the collective
    #: batch assembly desynchronizes at epoch end — keep the default 0
    #: in multi-host training (fail fast, restart from checkpoint) and
    #: use budgets on single-host / per-host-symmetric runs; with
    #: ``drop_remainder=False`` a quarantined shard can also surface as
    #: the partial-final-batch ValueError.
    shard_error_budget: int = 0
    #: drop a shard's page-cache residue after a Python-side index walk
    #: (tfrecord): the walk faults the file resident, which would flip
    #: the engine's residency planner to the buffered path for every
    #: record read that follows.  The native wds walker reads O_DIRECT
    #: and needs no cleanup.  Set False to keep pre-warmed files warm.
    drop_index_pollution: bool = True


@dataclass(frozen=True)
class TenantConfig:
    """Multi-tenant isolation knobs (io/tenants.py; semantics in
    docs/RESILIENCE.md "Multi-tenant isolation").

    One gate and one table: ``STROM_TENANTS=1`` turns the tenant layer
    on (default 0 keeps today's single-tenant stack bit-for-bit), and
    ``STROM_TENANT_SPEC`` declares the tenants the operator cares about
    (tier/weight/quota/rate/burst/SLO per id).  Ids not in the spec
    register on first sight with the ``STROM_TENANT_*`` defaults, so a
    replayed production trace with thousands of tenant ids needs no
    spec entry each.  STROM_* environment variables are read at
    construction time, mirroring EngineConfig.
    """

    #: master gate; 0 (default) = no tenant is ever attached anywhere —
    #: the exact pre-tenant stack (proven bit-for-bit by test)
    enabled: bool = field(
        default_factory=lambda: os.environ.get("STROM_TENANTS",
                                               "0") == "1")
    #: ";"-separated tenant table, each ``<id>[:key=value,...]`` with
    #: keys tier/weight/quota/rate/burst/slo_ms — e.g.
    #: ``gold:tier=gold,weight=8,quota=0.5,slo_ms=50;batch:tier=bronze``
    spec: str = field(
        default_factory=lambda: os.environ.get("STROM_TENANT_SPEC", ""))
    #: admission token-bucket refill (requests/s) of a tenant the spec
    #: does not name; 0 = unlimited
    default_rate: float = field(
        default_factory=lambda: _env_float("STROM_TENANT_RATE", 0.0))
    #: token-bucket burst depth of an unnamed tenant (floored at 1)
    default_burst: float = field(
        default_factory=lambda: _env_float("STROM_TENANT_BURST", 8.0))
    #: residency-quota fraction of an unnamed tenant; 0 = fair share
    #: (1/N of the tenants the cache has seen)
    default_quota_frac: float = field(
        default_factory=lambda: _env_float("STROM_TENANT_QUOTA_FRAC",
                                           0.0))
    #: sheds of ONE tenant inside a metrics window that trip the
    #: ``tenant_storm`` flight-recorder dump
    storm_sheds: int = field(
        default_factory=lambda: _env_int("STROM_TENANT_STORM_SHEDS", 32))

    def __post_init__(self):
        if self.default_rate < 0 or self.default_burst < 0:
            raise ValueError("tenant default rate/burst must be >= 0")
        if not 0.0 <= self.default_quota_frac <= 1.0:
            raise ValueError(
                f"default_quota_frac ({self.default_quota_frac}) must "
                f"be in [0, 1]")
        if self.storm_sheds < 1:
            raise ValueError("storm_sheds must be >= 1")
        if self.spec:
            # validate HERE, like every other knob (HostCacheConfig's
            # class_quotas pattern): malformed specs fail loudly at
            # construction, not out of the first serving submit
            from nvme_strom_tpu.io.tenants import parse_tenant_spec
            parse_tenant_spec(self.spec)


@dataclass(frozen=True)
class ColdStartConfig:
    """Elastic cold-start knobs (io/coldstart.py + parallel/weights.py
    FaultingCheckpoint; semantics in docs/RESILIENCE.md "Elastic
    cold-start").

    One gate and a small SLO block: ``STROM_COLDSTART=1`` lets a
    serving replica take traffic immediately — weights the first
    requests touch are demand-faulted at ``decode`` class ahead of the
    background bulk restore stream (``restore`` class), and warm-state
    manifests (KV prefix pages + hostcache warmup hints) prefetch at
    ``prefetch`` class.  Default 0 keeps today's restore-then-serve
    stack bit-for-bit (proven by test).  STROM_* environment variables
    are read at construction time, mirroring EngineConfig.
    """

    #: master gate; 0 (default) = no faulting front-end, no boot-phase
    #: machine, no warmup prefetch — the exact pre-coldstart stack
    enabled: bool = field(
        default_factory=lambda: os.environ.get("STROM_COLDSTART",
                                               "0") == "1")
    #: demand-fault p99 target in ms during the ``faulting`` boot
    #: phase; a violation trips the ``coldstart_stall`` flight-recorder
    #: dump (boot phase + per-class backlog in the payload).  0
    #: (default) = no stall trigger.
    fault_slo_ms: float = field(
        default_factory=lambda: _env_float("STROM_COLDSTART_FAULT_SLO_MS",
                                           0.0))
    #: demand-fault latencies retained for the stall trigger's rolling
    #: p99 (bounded — a long faulting phase must not grow a list)
    fault_window: int = field(
        default_factory=lambda: _env_int("STROM_COLDSTART_WINDOW", 64))
    #: hostcache spans retained per ``.warmhints.json`` manifest —
    #: largest-first, so a truncated hint list still warms the lines
    #: that buy the most DRAM hits
    warm_hint_spans: int = field(
        default_factory=lambda: _env_int("STROM_WARM_HINT_SPANS", 1024))
    #: KV prefix pages the warming phase re-reads at ``prefetch`` class
    #: (top benefit score first) so a scaled-out replica's hot prefixes
    #: restore from DRAM, not NVMe
    warm_pages: int = field(
        default_factory=lambda: _env_int("STROM_WARM_PAGES", 256))

    def __post_init__(self):
        if self.fault_slo_ms < 0:
            raise ValueError("fault_slo_ms must be >= 0")
        if self.fault_window < 8:
            raise ValueError("fault_window must be >= 8")
        if self.warm_hint_spans < 0 or self.warm_pages < 0:
            raise ValueError("warm hint/page budgets must be >= 0")


def coldstart_enabled() -> bool:
    """The one gate read (``STROM_COLDSTART``) consumers check before
    touching any cold-start machinery — mirrors tenants_enabled()."""
    return os.environ.get("STROM_COLDSTART", "0") == "1"


@dataclass
class HandoffConfig:
    """Zero-downtime drain & warm handoff knobs (io/handoff.py;
    semantics in docs/RESILIENCE.md "Drain & handoff").

    One gate and a small deadline block: ``STROM_HANDOFF=1`` arms the
    rolling-replacement protocol — a retiring replica stops admitting
    new prefills (deferred, never dropped), lets in-flight sessions
    finish under ``STROM_DRAIN_DEADLINE_S``, then publishes an atomic
    ``.handoff.json`` warm-state bundle the replacement consumes at
    boot.  Default 0 keeps today's abrupt-kill replacement bit-for-bit
    (proven by test).  STROM_* environment variables are read at
    construction time, mirroring ColdStartConfig.
    """

    #: master gate; 0 (default) = no drain machinery, no bundle
    #: publish/consume, no drain_phase gauge — the exact pre-handoff
    #: stack
    enabled: bool = field(
        default_factory=lambda: os.environ.get("STROM_HANDOFF",
                                               "0") == "1")
    #: seconds the draining phase waits for in-flight sessions before
    #: exporting the stragglers into the bundle instead (prompt chain +
    #: KV page keys — the replacement re-admits them through the prefix
    #: store).  0 = export immediately, no grace decode.
    deadline_s: float = field(
        default_factory=lambda: _env_float("STROM_DRAIN_DEADLINE_S",
                                           30.0))
    #: 1 = install SIGTERM/SIGINT handlers that enter drain and, on
    #: exit, flush a final metrics snapshot + force flight dump — a
    #: TERM mid-decode otherwise loses both the tail ops and the warm
    #: manifests.  Default 0: signals keep their stock semantics.
    drain_on_sigterm: bool = field(
        default_factory=lambda: os.environ.get("STROM_DRAIN_ON_SIGTERM",
                                               "0") == "1")
    #: sessions exported into one bundle, newest-submitted first — a
    #: pathological queue must not grow an unbounded manifest
    max_sessions: int = field(
        default_factory=lambda: _env_int("STROM_HANDOFF_MAX_SESSIONS",
                                         256))
    #: drain-progress poll cadence in ms (the coordinator's wait loop
    #: between serving steps; small — drain latency, not throughput)
    poll_ms: float = field(
        default_factory=lambda: _env_float("STROM_DRAIN_POLL_MS", 50.0))

    def __post_init__(self):
        if self.deadline_s < 0:
            raise ValueError("deadline_s must be >= 0")
        if self.max_sessions < 0:
            raise ValueError("max_sessions must be >= 0")
        if self.poll_ms <= 0:
            raise ValueError("poll_ms must be > 0")


def handoff_enabled() -> bool:
    """The one gate read (``STROM_HANDOFF``) consumers check before
    touching any drain/handoff machinery — mirrors
    coldstart_enabled()."""
    return os.environ.get("STROM_HANDOFF", "0") == "1"

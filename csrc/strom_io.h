/* strom_io.h — C ABI of the strom-io engine.
 *
 * This header is the TPU build's analogue of the reference's nvme_strom.h
 * ioctl ABI (SURVEY.md §1 L2): the stable contract between the native I/O
 * engine and all userspace consumers (the ctypes wrapper in
 * nvme_strom_tpu/io/).  Correspondence:
 *
 *   STROM_IOCTL__CHECK_FILE        -> strom_check_file()
 *   STROM_IOCTL__MAP_GPU_MEMORY    -> engine-owned locked buffer pool
 *                                     (created once in strom_engine_create)
 *   STROM_IOCTL__MEMCPY_SSD2GPU    -> strom_submit_read()
 *   STROM_IOCTL__MEMCPY_SSD2GPU_WAIT -> strom_wait()
 *   STROM_IOCTL__STAT_INFO         -> strom_get_stats()
 *
 * All functions return 0 / a non-negative id on success and a negative errno
 * on failure, mirroring the ioctl convention.
 */
#ifndef STROM_IO_H
#define STROM_IO_H

#include <stdint.h>
#include <stddef.h>

#ifdef __cplusplus
extern "C" {
#endif

typedef struct strom_engine strom_engine;

/* Result of strom_check_file — the CHECK_FILE eligibility probe
 * (SURVEY.md §3.3).  Instead of "is ext4/xfs on NVMe", the TPU-relevant
 * questions are: does the fs accept O_DIRECT (page-cache bypass possible)
 * and what alignment does it demand. */
typedef struct strom_file_info {
  int64_t  size;           /* file size in bytes */
  int32_t  supports_direct;/* 1 if O_DIRECT open+read works here */
  int32_t  block_size;     /* required O_DIRECT alignment (logical block) */
  uint64_t fs_magic;       /* statfs f_type */
} strom_file_info;

typedef struct strom_stats_blk {
  uint64_t bytes_direct;         /* payload read via O_DIRECT (no host copy) */
  uint64_t bytes_fallback;       /* payload via buffered fallback            */
  uint64_t bounce_bytes;         /* bytes memcpy'd host-side after landing   */
  uint64_t bytes_written_direct; /* write path (checkpointing)               */
  uint64_t requests_submitted;
  uint64_t requests_completed;
  uint64_t requests_failed;
  uint64_t retries;
  uint64_t bytes_resident;       /* planned page-cache reads: the submit-time
                                    mincore probe found the span resident and
                                    CHOSE buffered (the reference's proactive
                                    resident-block return, SURVEY.md §3.1) —
                                    a subset of bytes_fallback, and NOT a
                                    rescue (retries unaffected)              */
  uint64_t submit_batches;       /* strom_submit_readv calls (n >= 1)        */
  uint64_t submit_syscalls_saved;/* INLINE-dispatched extents per batch
                                    beyond the first: submission round trips
                                    a per-extent caller would have paid
                                    (io_uring_enter doorbells on the uring
                                    backend).  Extents that defer on pool
                                    pressure ring their own doorbell later
                                    and are never credited.  With SQPOLL
                                    active this ALSO counts every doorbell
                                    the poller made unnecessary (an
                                    io_uring_enter the submitter skipped
                                    because the SQ thread was awake; the
                                    worker-pool backend counts elided
                                    dispatch wakeups the same way).         */
  uint64_t submit_enters;        /* submission doorbells actually rung:
                                    io_uring_enter submit/wakeup calls on
                                    the uring backend, dispatch wakeups on
                                    the worker pool.  enters/GiB is the
                                    steady-state submission-syscall rate;
                                    SQPOLL drives it toward zero.           */
} strom_stats_blk;

typedef struct strom_completion {
  const uint8_t *data;   /* pointer into an engine buffer; valid until
                            strom_release(req_id). Payload starts here
                            (alignment head already skipped).            */
  uint64_t len;          /* payload length actually read                 */
  int32_t  status;       /* 0 ok; negative errno                         */
  int32_t  was_fallback; /* 1 if this request took the buffered path     */
  uint64_t submit_ns;    /* CLOCK_MONOTONIC at submit                    */
  uint64_t complete_ns;  /* CLOCK_MONOTONIC at completion                */
} strom_completion;

/* Per-request latency histograms (submit->complete), log2-ns buckets:
 * bucket i counts SUCCESSFUL requests with latency in [2^i, 2^(i+1)) ns
 * (failed requests are excluded; see requests_failed).  The
 * reference exposes only aggregate byte/request counters via STAT_INFO
 * (SURVEY.md §5 Tracing: "minimal") — this is the promised upgrade. */
#define STROM_LAT_BUCKETS 64
void strom_get_latency(strom_engine *eng,
                       uint64_t out_read[STROM_LAT_BUCKETS],
                       uint64_t out_write[STROM_LAT_BUCKETS]);

/* Create an engine.
 *   queue_depth  — io_uring SQ depth / worker count for the fallback pool
 *   n_buffers    — buffers in the staging pool (>= queue_depth recommended)
 *   buf_bytes    — payload capacity of each buffer (max read size)
 *   alignment    — O_DIRECT alignment (power of two, >= 512)
 *   use_io_uring — 0 forces the thread-pool backend
 *   lock_buffers — mlock the pool (pin pages, as MAP_GPU_MEMORY pins BAR1)
 * Returns NULL on failure (errno set).
 *
 * Fault injection below the C ABI (chaos/stress runs; default off) is
 * read from the environment at create time:
 *   STROM_FAULT_READ_EIO_EVERY=N    every Nth read completes -EIO
 *   STROM_FAULT_READ_SHORT_EVERY=N  every Nth read reports half its bytes
 *   STROM_FAULT_READ_DELAY_MS=D     every read completion held D ms
 *   STROM_FAULT_WRITE_EIO_EVERY=N   every Nth write completes -EIO
 *   STROM_FAULT_WRITE_ENOSPC_EVERY=N  every Nth write completes -ENOSPC
 *   STROM_FAULT_WRITE_SHORT_EVERY=N every Nth write reports half its bytes
 *   STROM_FAULT_WRITE_DELAY_MS=D    every write completion held D ms
 *   STROM_FAULT_RING_STALL_RING=R   arm ring R's stall injection (see
 *                                   strom_set_ring_stall): its requests
 *                                   park instead of dispatching
 *   STROM_FAULT_RING_STALL_AFTER=N  first N dispatches run clean before
 *                                   the stall engages (default 0)
 * The Python-level plan (nvme_strom_tpu/io/faults.py) is richer and
 * deterministic; these knobs exist to exercise the native completion
 * path itself.
 *
 * Zero-copy submission knobs (PR 12; also read at create time):
 *   STROM_REG_FILES=0     disable the registered-file slot table
 *                         (default on; soft-fails on kernels without
 *                         sparse IORING_REGISTER_FILES support)
 *   STROM_SQPOLL=1        enable SQPOLL: the uring backend sets
 *                         IORING_SETUP_SQPOLL so a kernel thread
 *                         consumes SQEs without io_uring_enter; the
 *                         worker-pool backend runs the same state
 *                         machine with polling workers (a dispatch
 *                         whose poller is awake skips the wakeup).
 *                         Default off: the poller burns a core.
 *   STROM_SQPOLL_IDLE_MS  poller idle budget before it sleeps and
 *                         submissions need a wakeup doorbell again
 *                         (default 50)
 */
strom_engine *strom_engine_create(uint32_t queue_depth, uint32_t n_buffers,
                                  uint64_t buf_bytes, uint32_t alignment,
                                  int use_io_uring, int lock_buffers);

/* Multi-ring engine: N independent submission rings (io_uring instance
 * or worker pool EACH, with private completion reaping and a private
 * request table) behind ONE file table, ONE public ABI, and ONE
 * fungible staging pool (global pool + global deferral FIFO: a batch
 * pinned to one ring can never deadlock behind a per-ring buffer slice
 * smaller than a consumer's in-flight window — buffers freed on any
 * ring hand over to the oldest deferred request engine-wide).  The
 * single-ring engine serializes every consumer through one doorbell;
 * sharding lets concurrent traffic classes (decode-critical reads vs
 * bulk prefetch vs scrub) ride disjoint queues — the QoS scheduler
 * above (io/sched.py) decides which class lands on which ring.
 * queue_depth and n_buffers are PER RING.  strom_engine_create(...) ==
 * strom_engine_create_rings(1, ...), bit-for-bit the old behavior.
 * Request ids encode their ring in the low STROM_RING_ID_BITS bits, so
 * wait/release route lock-free. */
#define STROM_MAX_RINGS 64
#define STROM_RING_ID_BITS 6
strom_engine *strom_engine_create_rings(uint32_t n_rings,
                                        uint32_t queue_depth,
                                        uint32_t n_buffers,
                                        uint64_t buf_bytes,
                                        uint32_t alignment,
                                        int use_io_uring, int lock_buffers);
void strom_engine_destroy(strom_engine *eng);

/* ---- unified pinned arena (io/arena.py, PR 12) ----------------------
 * ONE anonymous reservation (MAP_NORESERVE: virtual until touched) the
 * Python allocator carves into engine staging slices, host-cache lines
 * and bridge DMA slabs — one mmap, one mlock policy, zero copies
 * between pinned regions.  strom_arena_lock pins one carve (best
 * effort: returns 0 or -errno; RLIMIT_MEMLOCK refusal is not fatal). */
void *strom_arena_create(uint64_t bytes);
void strom_arena_destroy(void *base, uint64_t bytes);
int strom_arena_lock(void *base, uint64_t bytes);

/* Exact staging-pool footprint strom_engine_create_rings would map for
 * this geometry (buf_cap slack included) — what the arena carve for a
 * preallocated engine must provide.  0 on invalid geometry. */
uint64_t strom_engine_pool_bytes(uint32_t n_rings, uint32_t n_buffers,
                                 uint64_t buf_bytes, uint32_t alignment);

/* strom_engine_create_rings over a CALLER-OWNED staging pool (an arena
 * carve): the engine stages/DMA-targets/registers `pool` exactly as it
 * would its own mapping but never munmaps it — the arena outlives the
 * engine.  `pool_bytes` must be >= strom_engine_pool_bytes(...) and
 * `pool` alignment-conformant (the arena carves page-aligned).  NULL +
 * errno on failure, like strom_engine_create. */
strom_engine *strom_engine_create_prealloc(uint32_t n_rings,
                                           uint32_t queue_depth,
                                           uint32_t n_buffers,
                                           uint64_t buf_bytes,
                                           uint32_t alignment,
                                           int use_io_uring,
                                           int lock_buffers,
                                           void *pool,
                                           uint64_t pool_bytes);

/* Per-ring introspection: the scheduler's dispatch decisions key off
 * in-flight queue depth (submitted - completed, lock-free atomics — the
 * poll can run at dispatch frequency without touching the ring mutex);
 * free_buffers/deferred take the ring lock briefly. */
typedef struct strom_ring_info {
  uint32_t ring_id;
  uint32_t n_buffers;      /* TOTAL staging buffers (the pool is global) */
  uint32_t free_buffers;   /* free in the global pool                    */
  uint32_t deferred;       /* THIS ring's requests awaiting a buffer     */
  uint64_t submitted;      /* requests ever submitted to this ring      */
  uint64_t completed;      /* requests completed (I/O done, incl. fail) */
  uint32_t inflight_io;    /* submitted - completed: queue depth        */
  int32_t  backend_uring;  /* 1 if this ring runs on io_uring           */
  /* Failure-domain health (io/health.py supervision layer): */
  uint64_t failed;         /* completions with status < 0, cancels
                              excluded (a hot restart's -ECANCELED
                              requeue must not read as device damage)  */
  uint64_t restarts;       /* hot restarts this ring has survived       */
  uint32_t parked;         /* requests parked by stall injection or a
                              restart window (in flight, never
                              dispatched to a backend)                  */
  int32_t  stalled;        /* 1 while stall injection is armed          */
  uint64_t oldest_inflight_ns; /* age of the oldest dispatched-or-parked
                              un-completed request; 0 when idle.  The
                              reap-side stall detector: a completion
                              that never arrives shows up here as an
                              age that only grows.                      */
  /* Zero-copy submission state (PR 12): a silently-unregistered pool or
   * slot table is SLOW, not broken — these gauges make it visible in
   * strom_stat's engine block instead of only in a flamegraph. */
  int32_t  fixed_bufs;     /* staging pool registered as fixed buffers
                              with this ring's uring (pin-once DMA)     */
  int32_t  reg_files;      /* fd slot table registered (hot submissions
                              skip the per-op fget via IOSQE_FIXED_FILE) */
  int32_t  sqpoll;         /* 1 while this ring's submissions are
                              consumed by a kernel SQPOLL thread (uring)
                              or a polling worker (worker-pool analogue)
                              — steady-state submission needs no doorbell */
} strom_ring_info;

int strom_ring_count(strom_engine *eng);
int strom_get_ring_info(strom_engine *eng, uint32_t ring,
                        strom_ring_info *out);

/* Hot ring restart — the failure-domain recovery primitive (the
 * supervision layer in io/health.py drives it; docs/RESILIENCE.md
 * "failure domains").  Sequence:
 *   1. the ring stops dispatching (new submissions park, in order);
 *   2. dispatched in-flight I/O is drained for up to drain_timeout_ns.
 *      If it will not drain the restart ABORTS with -ETIMEDOUT and the
 *      ring resumes exactly as it was (nothing cancelled): an
 *      un-completable kernel I/O cannot be cancelled from userspace
 *      without recycling a live DMA target, so the caller's fallback
 *      is the degraded buffered path, not a forced cancel;
 *   3. the pre-restart stall-parked backlog is completed -ECANCELED —
 *      those requests never reached a backend, so their staging
 *      buffers are clean and the waiter's resubmission (ResilientRead's
 *      retry) is the requeue path;
 *   4. on the io_uring backend the uring is torn down and rebuilt
 *      (fresh fd, fresh SQ/CQ mappings, fresh reaper thread); if the
 *      rebuild fails the ring falls back to the worker-pool backend so
 *      it keeps serving;
 *   5. stall injection is disarmed (the injected wedge heals — that is
 *      the point of the restart) and requests parked during the window
 *      dispatch in order: consumers see one longer wait, never an
 *      error.
 * Returns the number of requests cancelled for requeue (>= 0), or
 * -EINVAL / -EBUSY (another restart, or an open/close updating the
 * registered-file table, holds the restart lock: nothing was done, try
 * again) / -ETIMEDOUT / -ECANCELED (engine stopping). */
int64_t strom_ring_restart(strom_engine *eng, uint32_t ring,
                           uint64_t drain_timeout_ns);

/* Ring-stall fault injection (chaos/stress; see also the env knobs
 * STROM_FAULT_RING_STALL_RING / STROM_FAULT_RING_STALL_AFTER read at
 * engine create): while armed, requests reaching the ring's dispatch
 * point are parked instead of dispatched — a wedged submission queue /
 * hung kernel worker as the waiters see it (completions never arrive,
 * lock-free counters freeze, oldest_inflight_ns grows).  Disarming
 * with on=0 dispatches the parked backlog (a transient stall that
 * healed itself); strom_ring_restart cancels it instead (the requeue
 * path).  Returns 0 or -EINVAL. */
int strom_set_ring_stall(strom_engine *eng, uint32_t ring, int on);

/* Degraded-mode read: a plain synchronous pread on the buffered fd
 * from the CALLING thread — no ring, no uring, no worker pool, no
 * staging buffer.  This is the brown-out path io/health.py falls back
 * to when every ring (or the device behind them) is unhealthy: reduced
 * bandwidth, but alive while the fast path is hot-restarted/probed.
 * Counted as fallback + bounce payload (the page-cache copy is real).
 * Returns bytes read (may be short at EOF) or -errno. */
int64_t strom_read_buffered(strom_engine *eng, int fh, uint64_t offset,
                            uint64_t len, void *dst);

/* Depth-only fast path: submitted - completed from the lock-free
 * per-ring atomics, NO mutex and NO deferral-queue walk — what the QoS
 * scheduler's admission poll calls at dispatch frequency (the full
 * strom_get_ring_info takes pool_mu for buffer/deferral occupancy and
 * belongs in stat dumps, not hot polls).  Returns >= 0, or -EINVAL for
 * a ring index out of range. */
int64_t strom_ring_inflight(strom_engine *eng, uint32_t ring);

/* Engine-independent file eligibility probe (CHECK_FILE analogue). */
int strom_check_file(const char *path, strom_file_info *out);

/* Backing block-device topology of the file at `path` — the other half of
 * the reference's CHECK_FILE verdict (SURVEY.md §3.3: "blockdev must be
 * NVMe, or md-raid0 whose members are all NVMe").  Resolved from sysfs:
 * st_dev -> /sys/dev/block -> partition->parent walk -> md member scan. */
#define STROM_MAX_RAID_MEMBERS 16
typedef struct strom_device_info {
  char    device[64];    /* whole-disk name ("nvme0n1", "md0", "vda");
                            empty when no backing blockdev is visible
                            (overlayfs, tmpfs, network fs)              */
  int32_t is_nvme;       /* whole disk is an NVMe namespace             */
  int32_t is_raid;       /* device is an md array                       */
  int32_t raid_level;    /* numeric md level (0 == raid0); -1 unknown   */
  int32_t n_members;     /* md member count (whole-disk resolved)       */
  int32_t rotational;    /* /sys/block/<dev>/queue/rotational; -1 unknown */
  int32_t nvme_backed;   /* the CHECK_FILE verdict: NVMe, or md-raid0
                            striped over all-NVMe members               */
  char    members[STROM_MAX_RAID_MEMBERS][64];
} strom_device_info;

/* Returns 0 (with device[0]=='\0' if unresolvable) or -errno when `path`
 * itself cannot be stat'ed. */
int strom_resolve_device(const char *path, strom_device_info *out);

/* File-offset -> physical-extent map, the analogue of the reference's
 * in-kernel extent walk that turns (inode, offset, len) into NVMe LBAs
 * (SURVEY.md §3.1).  Backed by the FIEMAP ioctl; filesystems without
 * FIEMAP yield one synthetic whole-file extent (physical == 0, flags =
 * STROM_EXTENT_SYNTHETIC) — the logical analogue of the reference's
 * page-cache fallback: the range is still readable, just not physically
 * addressable. */
#define STROM_EXTENT_SYNTHETIC 0x80000000u
typedef struct strom_extent {
  uint64_t logical;   /* byte offset in the file                        */
  uint64_t physical;  /* byte offset on the backing device (0 unknown)  */
  uint64_t length;    /* extent length in bytes                         */
  uint32_t flags;     /* raw fiemap fe_flags (| STROM_EXTENT_SYNTHETIC) */
  uint32_t pad;
} strom_extent;

/* Fills up to `max` extents covering [0, file_size). Returns the number
 * of extents written (>= 0) or -errno. */
int strom_file_extents(const char *path, strom_extent *out, uint32_t max);

/* md-raid0 stripe attribution: how many bytes of the physical span
 * [phys_off, phys_off + len) land on each of the n_members striped
 * devices (stripe chunk `chunk` bytes, member of chunk k = k mod n)?
 * Adds into out_bytes[0..n_members).  Closed-form over full stripe
 * periods plus a <= 2*n_members remainder walk — O(members), not
 * O(len/chunk).  Pure function: the per-member byte counters behind
 * `strom_stat --device` (the striped-scaling attribution the
 * reference's 6-10 GB/s md-raid0 claim implies, SURVEY.md §6) are
 * buildable and testable without raid hardware. */
void strom_stripe_attr(uint64_t phys_off, uint64_t len, uint64_t chunk,
                       uint32_t n_members, uint64_t *out_bytes);

/* Staging-pool introspection — the LIST_GPU_MEMORY / INFO_GPU_MEMORY
 * analogue (SURVEY.md §2 "GPU memory mapper"): the reference enumerates
 * pinned GPU mappings; we report the pinned staging pool and its
 * occupancy. */
typedef struct strom_pool_info {
  uint32_t n_buffers;     /* total staging buffers                     */
  uint32_t free_buffers;  /* currently unassigned                      */
  uint64_t buf_bytes;     /* payload capacity per buffer               */
  uint64_t pool_bytes;    /* total mapped bytes incl. alignment slack  */
  int32_t  locked;        /* 1 if mlock'd (pinned)                     */
  int32_t  queue_depth;
  uint32_t in_flight;     /* submitted, not yet released               */
  uint32_t deferred;      /* submitted, waiting for a free buffer      */
  int32_t  fixed_bufs;    /* 1 if pool registered as io_uring fixed
                             buffers (pin-once, READ_FIXED/WRITE_FIXED) */
  uint32_t pad;
  uint64_t pool_base;     /* staging pool base address: lets callers
                             PROVE a returned view aliases the pool
                             (zero-copy up to the device boundary)      */
} strom_pool_info;

void strom_get_pool_info(strom_engine *eng, strom_pool_info *out);

/* Open a file for engine I/O. Tries O_DIRECT first; transparently falls
 * back to buffered (counted per-request). Returns fh >= 0 or -errno.
 * flags: bit 0 = writable; bit 1 = force buffered I/O (debug/testing knob,
 * like the reference's module params — SURVEY.md §5 Config/flags). */
int strom_open(strom_engine *eng, const char *path, int flags);
#define STROM_OPEN_WRITABLE 1
#define STROM_OPEN_NO_DIRECT 2
int strom_close(strom_engine *eng, int fh);
int64_t strom_file_size(strom_engine *eng, int fh);
int strom_file_is_direct(strom_engine *eng, int fh);

/* Stable identity of the file BEHIND the open fh, via fstat on the
 * engine's own descriptor (never the path — a rename racing the open
 * could attribute one inode's bytes to another's identity): out =
 * {st_dev, st_ino, mtime_ns, size}.  The pinned-host cache tier keys
 * its lines by this. */
int strom_file_ident(strom_engine *eng, int fh, uint64_t out[4]);

/* Submit an async read of [offset, offset+len). len must be
 * <= buf_bytes. Unaligned offset/len are handled by reading the enclosing
 * aligned span; the completion's data pointer is pre-offset (no copy).
 * Blocks if no staging buffer is free. Returns req_id >= 0 or -errno. */
int64_t strom_submit_read(strom_engine *eng, int fh, uint64_t offset,
                          uint64_t len);

/* One extent of a vectored submission (strom_submit_readv). */
typedef struct strom_rd_ext {
  int32_t  fh;
  uint32_t pad;
  uint64_t offset;
  uint64_t length;     /* must be <= buf_bytes */
} strom_rd_ext;

/* Vectored read submission: stage every extent's SQE, then ring the
 * doorbell with a SINGLE io_uring_enter (the thread-pool backend queues
 * all extents under one lock hold) — the per-request ioctl/syscall
 * amortization the reference gets from multi-chunk MEMCPY_SSD2GPU
 * commands (SURVEY.md §3.1).  Validation is atomic: on any invalid
 * extent (-EINVAL over-size, -EBADF unknown fh) NOTHING is submitted.
 * On success returns 0 and fills out_ids[0..n) with per-extent request
 * ids (wait/release each exactly like strom_submit_read's).  Extents
 * whose buffers are exhausted defer, never block, preserving
 * submission order. */
int strom_submit_readv(strom_engine *eng, const strom_rd_ext *exts,
                       uint32_t n, int64_t *out_ids);

/* Ring-pinned variants: identical semantics, but the caller (the QoS
 * scheduler) names the ring instead of the engine's round-robin pick.
 * A whole readv batch lands on ONE ring — one doorbell, one deferral
 * queue, no cross-ring interleave within the batch.  -EINVAL for a
 * ring index out of range. */
int64_t strom_submit_read_ring(strom_engine *eng, uint32_t ring, int fh,
                               uint64_t offset, uint64_t len);
int strom_submit_readv_ring(strom_engine *eng, uint32_t ring,
                            const strom_rd_ext *exts, uint32_t n,
                            int64_t *out_ids);

/* Wait until req_id completes; fills *out. The buffer stays owned by the
 * request until strom_release. */
int strom_wait(strom_engine *eng, int64_t req_id, strom_completion *out);

/* Bounded wait: -ETIMEDOUT after timeout_ns if the request has not
 * completed (request stays live; retry or diagnose — the failure-
 * DETECTION half of the recovery story). */
int strom_wait_timeout(strom_engine *eng, int64_t req_id,
                       strom_completion *out, uint64_t timeout_ns);

/* Return the request's staging buffer to the pool. */
int strom_release(strom_engine *eng, int64_t req_id);

/* Async write of len bytes from src to [offset, offset+len) (checkpoint /
 * HBM->NVMe path). If src and offset/len are alignment-conformant the
 * write is O_DIRECT straight from src (zero copy); otherwise it bounces
 * through a pool buffer (counted). Returns req_id; wait with strom_wait;
 * release with strom_release. */
int64_t strom_submit_write(strom_engine *eng, int fh, uint64_t offset,
                           const void *src, uint64_t len);

/* Ring-pinned write (strom_submit_read_ring's mirror): the caller
 * names the ring instead of the engine's round-robin pick — how the
 * supervision layer keeps checkpoint/KV writes off a ring whose
 * breaker is open.  -EINVAL for a ring index out of range. */
int64_t strom_submit_write_ring(strom_engine *eng, uint32_t ring, int fh,
                                uint64_t offset, const void *src,
                                uint64_t len);

void strom_get_stats(strom_engine *eng, strom_stats_blk *out);
void strom_reset_stats(strom_engine *eng);
/* Atomically read-and-zero every counter (per-counter exchange): no
 * increment can be lost between the read and the reset. */
void strom_drain_stats(strom_engine *eng, strom_stats_blk *out);

/* Introspection for tests/bench. */
int strom_backend_is_uring(strom_engine *eng);

/* crc32c (Castagnoli), for TFRecord integrity checks: slice-by-8 software
 * implementation, hardware SSE4.2 path when the CPU supports it.
 * `crc` is the running value (0 to start); returns the updated crc. */
uint32_t strom_crc32c(const void *data, uint64_t len, uint32_t crc);

/* Pinned host-DRAM cache arena (io/hostcache.py — the tier between NVMe
 * and HBM).  Engine-independent, like strom_crc32c: the Python tier owns
 * line bookkeeping; this is just the mapped+pinned backing store and the
 * completion->line copy primitive.
 *
 * strom_hostcache_arena_create maps `bytes` of anonymous memory,
 * pre-faults it (MAP_POPULATE: a fill must memcpy, never page-fault, so
 * the staging buffer it drains recycles at DRAM speed) and — when
 * `lock_pages` — best-effort mlocks it so cache hits can never stall on
 * swapped-out lines.  *locked_out (optional) reports whether the mlock
 * held (RLIMIT_MEMLOCK may refuse; the arena still works, unpinned).
 * Returns NULL with errno set when the mapping itself fails.
 *
 * strom_hostcache_copy is the fill primitive: memcpy a completed staging
 * view into a line.  Called via ctypes, it runs with the GIL dropped —
 * the copy happens off the Python hot path exactly like the engine's own
 * bounce copies. */
void *strom_hostcache_arena_create(uint64_t bytes, int lock_pages,
                                   int32_t *locked_out);
void strom_hostcache_arena_destroy(void *base, uint64_t bytes);
void strom_hostcache_copy(void *dst, const void *src, uint64_t bytes);

/* Native tar shard indexer — the header walk that builds the
 * WebDataset sample map (formats/wds.py) without a Python-loop per
 * member: ustar (name+prefix), GNU longname ('L'), and pax ('x'
 * path=/size= overrides) are understood; directories and other
 * non-file members are skipped.  On success returns the number of
 * regular-file entries and sets *out to a malloc'd packed buffer of
 *
 *   u64 data_offset | u64 size | u32 name_len | name bytes
 *
 * records totalling *out_bytes (caller frees with
 * strom_tar_index_free).  Negative errno on IO error; -EBADMSG for a
 * malformed archive (bad checksum, truncated header/data, broken pax
 * records) and for member names over 4096 bytes — always loud, never
 * a silent partial or truncated-key index. */
int64_t strom_tar_index(const char *path, uint8_t **out,
                        uint64_t *out_bytes);
void strom_tar_index_free(uint8_t *buf);

#ifdef __cplusplus
}
#endif
#endif /* STROM_IO_H */

/* strom_io.cc — the strom-io engine: NVMe -> locked staging buffers with
 * zero host-side payload copies.
 *
 * This is the TPU build's equivalent of the reference's nvme_strom.c kernel
 * module (SURVEY.md §2: "SSD→GPU DMA engine", ~1.5-2k LoC of extent walking
 * + async NVMe command submission).  We cannot load kernel modules on TPU
 * VMs, so the same property — payload bytes never memcpy'd by the host CPU —
 * is obtained with io_uring + O_DIRECT: the NVMe controller DMAs file data
 * straight into this engine's mlock'd, alignment-conformant staging buffers,
 * which are then handed (by pointer, never by copy) to the JAX bridge as the
 * source of the host->TPU PCIe transfer.
 *
 * Design notes:
 *  - io_uring is driven by raw syscalls (425/426) — no liburing dependency.
 *  - A request for an unaligned [offset, len) range reads the enclosing
 *    aligned span and returns a pointer *into* the buffer (data = buf +
 *    head_slack): the reference handles the same problem with sector-aligned
 *    extent chunking in-kernel (SURVEY.md §3.1).
 *  - Files that reject O_DIRECT (tmpfs/overlayfs) or reads that come back
 *    EINVAL take the buffered-read fallback, counted in bytes_fallback and
 *    bounce_bytes — the analogue of the reference's page-cache fallback
 *    chunks, which are also host-copied (SURVEY.md §3.1 "page-cache
 *    fallback").
 *  - Stats counters mirror STROM_IOCTL__STAT_INFO (SURVEY.md §5).
 */

#include "strom_io.h"

#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstring>
#include <deque>
#include <memory>
#include <mutex>
#include <thread>
#include <unordered_map>
#include <vector>

#include <dirent.h>
#include <errno.h>
#include <fcntl.h>
#include <limits.h>
#include <stdio.h>
#include <stdlib.h>
#include <linux/fiemap.h>
#include <linux/fs.h>
#include <sys/ioctl.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <sys/uio.h>
#include <sys/statfs.h>
#include <sys/syscall.h>
#include <sys/sysmacros.h>
#include <time.h>
#include <unistd.h>

/* ---------------- raw io_uring plumbing (no liburing) ---------------- */

#ifndef __NR_io_uring_setup
#define __NR_io_uring_setup 425
#endif
#ifndef __NR_io_uring_enter
#define __NR_io_uring_enter 426
#endif
#ifndef __NR_io_uring_register
#define __NR_io_uring_register 427
#endif

struct io_sqring_offsets_ {
  uint32_t head, tail, ring_mask, ring_entries, flags, dropped, array, resv1;
  uint64_t resv2;
};
struct io_cqring_offsets_ {
  uint32_t head, tail, ring_mask, ring_entries, overflow, cqes, flags, resv1;
  uint64_t resv2;
};
struct io_uring_params_ {
  uint32_t sq_entries, cq_entries, flags, sq_thread_cpu, sq_thread_idle;
  uint32_t features, wq_fd, resv[3];
  io_sqring_offsets_ sq_off;
  io_cqring_offsets_ cq_off;
};
struct io_uring_sqe_ {
  uint8_t opcode, flags;
  uint16_t ioprio;
  int32_t fd;
  uint64_t off, addr;
  uint32_t len, rw_flags;
  uint64_t user_data;
  uint16_t buf_index, personality;
  int32_t splice_fd_in;
  uint64_t pad2[2];
};
struct io_uring_cqe_ {
  uint64_t user_data;
  int32_t res;
  uint32_t flags;
};

static constexpr uint64_t kOffSqRing = 0ULL;
static constexpr uint64_t kOffCqRing = 0x8000000ULL;
static constexpr uint64_t kOffSqes = 0x10000000ULL;
static constexpr uint32_t kFeatSingleMmap = 1u << 0;
static constexpr uint32_t kEnterGetevents = 1u << 0;
/* SQPOLL plumbing: IORING_SETUP_SQPOLL asks the kernel for a dedicated
 * SQ-consuming thread; while it is awake submissions need NO syscall at
 * all — the tail store IS the submission (the natural endpoint of the
 * "one doorbell" arc: zero doorbells).  When the thread idles out
 * (sq_thread_idle ms) the SQ ring flags raise NEED_WAKEUP and the next
 * submit pays one io_uring_enter(SQ_WAKEUP). */
static constexpr uint32_t kSetupSqpoll = 1u << 1;
static constexpr uint32_t kSqNeedWakeup = 1u << 0;
static constexpr uint32_t kEnterSqWakeup = 1u << 1;
static constexpr uint8_t kOpNop = 0, kOpRead = 22, kOpWrite = 23;
/* Fixed-buffer variants: the kernel pins the staging pool ONCE at
 * registration instead of get_user_pages()-pinning every I/O — the same
 * pin-once pattern as the reference's MAP_GPU_MEMORY (SURVEY.md §3.2). */
static constexpr uint8_t kOpReadFixed = 4, kOpWriteFixed = 5;
static constexpr uint32_t kRegisterBuffers = 0;
/* Registered files: a slot table the kernel resolves instead of a per-op
 * fget()/fput() on the raw fd — IOSQE_FIXED_FILE turns sqe->fd into a
 * table index.  The table registers sparse (-1 slots) at ring init and
 * is updated at strom_open/strom_close. */
static constexpr uint32_t kRegisterFiles = 2;
static constexpr uint32_t kRegisterFilesUpdate = 6;
static constexpr uint8_t kSqeFixedFile = 1u << 0;
static constexpr uint64_t kShutdownUserData = ~0ULL;

struct io_uring_files_update_ {
  uint32_t offset, resv;
  uint64_t fds;   /* pointer to int32_t fds */
};

inline uint64_t now_ns() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (uint64_t)ts.tv_sec * 1000000000ull + (uint64_t)ts.tv_nsec;
}

/* How long a submission waits on a full SQ under SQPOLL for the kernel's
 * SQ thread to consume an entry before the request fails with -EBUSY. */
static constexpr uint64_t kSqFullWaitNs = 2000000000ull;

struct Uring {
  int fd = -1;
  uint32_t *sq_head = nullptr, *sq_tail = nullptr, *sq_mask = nullptr;
  uint32_t *sq_array = nullptr;
  uint32_t *sq_flags = nullptr;   /* NEED_WAKEUP lives here (SQPOLL) */
  uint32_t *cq_head = nullptr, *cq_tail = nullptr, *cq_mask = nullptr;
  io_uring_cqe_ *cqes = nullptr;
  io_uring_sqe_ *sqes = nullptr;
  void *sq_ring_ptr = nullptr, *cq_ring_ptr = nullptr;
  size_t sq_ring_sz = 0, cq_ring_sz = 0, sqes_sz = 0;
  uint32_t sq_entries = 0;
  bool single_mmap = false;
  bool fixed_bufs = false;   /* staging pool registered with the kernel */
  /* fd slot table registered (FIXED_FILE).  Atomic: cleared under
   * files_mu by a refused slot update while dispatchers read it under
   * their ring mutex — a plain bool would be a (benign) race. */
  std::atomic<bool> reg_files{false};
  bool sqpoll = false;       /* IORING_SETUP_SQPOLL accepted            */
  /* requested mode, preserved across a hot restart's teardown/re-init */
  bool want_sqpoll = false;
  uint32_t sqpoll_idle_ms = 50;
  /* submission-doorbell accounting (engine-owned atomics; see
   * strom_stats_blk.submit_enters): enters = doorbells actually rung,
   * elided = doorbells SQPOLL made unnecessary */
  std::atomic<uint64_t> *c_enters = nullptr, *c_elided = nullptr;

  void count_enter() {
    if (c_enters) c_enters->fetch_add(1, std::memory_order_relaxed);
  }
  void count_elided() {
    if (c_elided) c_elided->fetch_add(1, std::memory_order_relaxed);
  }
  /* SQEs published to the ring but not yet consumed by io_uring_enter
   * (enter can fail with EINTR/EBUSY after the tail was advanced; the
   * entry then MUST be submitted by a later enter, never abandoned —
   * an abandoned SQE would be consumed by the next enter and DMA into
   * a buffer that has since been reassigned). */
  std::atomic<uint32_t> unsubmitted{0};

  bool init(uint32_t entries) {
    io_uring_params_ p;
    memset(&p, 0, sizeof(p));
    int r = -1;
    sqpoll = false;
    if (want_sqpoll) {
      /* SQPOLL first; refused (old kernel, privileges pre-5.11) falls
       * back to the plain ring — slower, never broken. */
      p.flags = kSetupSqpoll;
      p.sq_thread_idle = sqpoll_idle_ms;
      r = (int)syscall(__NR_io_uring_setup, entries, &p);
      if (r >= 0) sqpoll = true;
      else memset(&p, 0, sizeof(p));
    }
    if (r < 0) r = (int)syscall(__NR_io_uring_setup, entries, &p);
    if (r < 0) return false;
    fd = r;
    sq_entries = p.sq_entries;
    sq_ring_sz = p.sq_off.array + p.sq_entries * sizeof(uint32_t);
    cq_ring_sz = p.cq_off.cqes + p.cq_entries * sizeof(io_uring_cqe_);
    single_mmap = (p.features & kFeatSingleMmap) != 0;
    if (single_mmap && cq_ring_sz > sq_ring_sz) sq_ring_sz = cq_ring_sz;
    sq_ring_ptr = mmap(nullptr, sq_ring_sz, PROT_READ | PROT_WRITE,
                       MAP_SHARED | MAP_POPULATE, fd, kOffSqRing);
    if (sq_ring_ptr == MAP_FAILED) { close(fd); fd = -1; return false; }
    if (single_mmap) {
      cq_ring_ptr = sq_ring_ptr;
      cq_ring_sz = sq_ring_sz;
    } else {
      cq_ring_ptr = mmap(nullptr, cq_ring_sz, PROT_READ | PROT_WRITE,
                         MAP_SHARED | MAP_POPULATE, fd, kOffCqRing);
      if (cq_ring_ptr == MAP_FAILED) { teardown(); return false; }
    }
    sqes_sz = p.sq_entries * sizeof(io_uring_sqe_);
    sqes = (io_uring_sqe_ *)mmap(nullptr, sqes_sz, PROT_READ | PROT_WRITE,
                                 MAP_SHARED | MAP_POPULATE, fd, kOffSqes);
    if (sqes == MAP_FAILED) { sqes = nullptr; teardown(); return false; }
    auto *sqb = (uint8_t *)sq_ring_ptr;
    sq_head = (uint32_t *)(sqb + p.sq_off.head);
    sq_tail = (uint32_t *)(sqb + p.sq_off.tail);
    sq_mask = (uint32_t *)(sqb + p.sq_off.ring_mask);
    sq_array = (uint32_t *)(sqb + p.sq_off.array);
    sq_flags = (uint32_t *)(sqb + p.sq_off.flags);
    auto *cqb = (uint8_t *)cq_ring_ptr;
    cq_head = (uint32_t *)(cqb + p.cq_off.head);
    cq_tail = (uint32_t *)(cqb + p.cq_off.tail);
    cq_mask = (uint32_t *)(cqb + p.cq_off.ring_mask);
    cqes = (io_uring_cqe_ *)(cqb + p.cq_off.cqes);
    return true;
  }

  /* Register the staging pool as fixed buffers (one iovec per staging
   * buffer; SQE buf_index selects one). Soft-fail: EOPNOTSUPP/ENOMEM
   * (old kernel, RLIMIT_MEMLOCK) just leaves the non-fixed opcodes. */
  void try_register(uint8_t *pool, uint64_t buf_cap, uint32_t n) {
    std::vector<struct iovec> iov(n);
    for (uint32_t i = 0; i < n; i++) {
      iov[i].iov_base = pool + (uint64_t)i * buf_cap;
      iov[i].iov_len = buf_cap;
    }
    fixed_bufs = syscall(__NR_io_uring_register, fd, kRegisterBuffers,
                         iov.data(), n) == 0;
  }

  /* Register the fd slot table (sparse: -1 slots are empty).  Soft-fail
   * like try_register: kernels without sparse REGISTER_FILES support
   * just keep resolving raw fds per op. */
  void try_register_files(const int32_t *fds, uint32_t n) {
    reg_files = syscall(__NR_io_uring_register, fd, kRegisterFiles,
                        fds, n) == 0;
  }

  /* Point one slot of the registered table at `newfd` (-1 clears).
   * Returns false when the kernel refused — the caller downgrades that
   * file to raw-fd submission rather than risking a stale slot. */
  bool update_file(uint32_t slot, int32_t newfd) {
    if (!reg_files) return false;
    io_uring_files_update_ up;
    up.offset = slot;
    up.resv = 0;
    up.fds = (uint64_t)(uintptr_t)&newfd;
    return syscall(__NR_io_uring_register, fd, kRegisterFilesUpdate,
                   &up, 1) == 1;
  }

  /* Wait until the kernel has CONSUMED every published SQE (sq_head
   * caught up).  An unconsumed SQE carrying IOSQE_FIXED_FILE resolves
   * its slot at consumption time — so a slot must not be recycled to
   * another file while any SQE referencing it is still in the SQ.
   * Bounded: returns false if the queue would not drain (the caller
   * then leaks the slot instead of recycling it — safe, never
   * wrong). */
  bool drain_sq() {
    if (fd < 0) return true;
    for (int i = 0; i < 100000; i++) {
      if (!sqpoll) flush();
      else sqpoll_kick(/*count_elide=*/false);
      uint32_t head = __atomic_load_n(sq_head, __ATOMIC_ACQUIRE);
      uint32_t tail = __atomic_load_n(sq_tail, __ATOMIC_ACQUIRE);
      if (head == tail &&
          unsubmitted.load(std::memory_order_acquire) == 0)
        return true;
      usleep(10);
    }
    return false;
  }

  void teardown() {
    if (sqes) munmap(sqes, sqes_sz);
    if (cq_ring_ptr && cq_ring_ptr != sq_ring_ptr) munmap(cq_ring_ptr, cq_ring_sz);
    if (sq_ring_ptr) munmap(sq_ring_ptr, sq_ring_sz);
    if (fd >= 0) close(fd);
    sqes = nullptr; cq_ring_ptr = sq_ring_ptr = nullptr; fd = -1;
    sqpoll = false; reg_files = false;
  }

  /* SQPOLL doorbell: the kernel thread consumes published SQEs on its
   * own; only when it idled out (NEED_WAKEUP raised) does the submitter
   * pay one io_uring_enter(SQ_WAKEUP).  Every skipped doorbell counts —
   * that is the syscall elision the whole mode exists for.
   * ``count_elide=false`` for polls that do not correspond to a
   * published SQE (the SQ-full spin), so backpressure noise cannot
   * inflate the elision counter. */
  void sqpoll_kick(bool count_elide = true) {
    /* Full fence between the tail store and the NEED_WAKEUP load: the
     * SQ thread sets NEED_WAKEUP after seeing an empty queue, and a
     * StoreLoad reordering here (legal on x86 AND arm) could read the
     * flags from before it slept — doorbell elided, SQE stranded, the
     * waiter hangs.  This is the io_uring_smp_mb() liburing documents
     * for exactly this handshake. */
    __atomic_thread_fence(__ATOMIC_SEQ_CST);
    if (__atomic_load_n(sq_flags, __ATOMIC_ACQUIRE) & kSqNeedWakeup) {
      syscall(__NR_io_uring_enter, fd, 0, 0, kEnterSqWakeup, nullptr, 0);
      count_enter();
    } else if (count_elide) {
      count_elided();
    }
  }

  /* Push any published-but-unconsumed SQEs into the kernel. Safe to call
   * from any thread. Returns 0 when the backlog is drained. */
  int flush() {
    if (sqpoll) {
      /* nothing tracked in `unsubmitted` under SQPOLL (publishing IS
       * submitting); just make sure the poller is awake */
      if (unsubmitted.load(std::memory_order_acquire) == 0) {
        sqpoll_kick();
        return 0;
      }
    }
    for (int attempt = 0; attempt < 1000; attempt++) {
      uint32_t n = unsubmitted.load(std::memory_order_acquire);
      if (n == 0) return 0;
      int r = (int)syscall(__NR_io_uring_enter, fd, n, 0, 0, nullptr, 0);
      if (r >= 0) count_enter();
      if (r > 0) {
        unsubmitted.fetch_sub((uint32_t)r, std::memory_order_acq_rel);
        continue;
      }
      if (r < 0 && errno != EINTR && errno != EAGAIN && errno != EBUSY)
        return -errno;
      if (r == 0 || errno == EAGAIN || errno == EBUSY) usleep(10);
    }
    return -EBUSY; /* backlog persists; a later flush will retry it */
  }

  /* Caller must serialise submissions (engine holds a mutex). Returns 0 or
   * -errno. The SQE is always published; a transient enter failure leaves
   * it queued for the next flush rather than failing the request.
   * ``flush_now = false`` stages the SQE without ringing the doorbell —
   * the vectored submit path publishes a whole batch, then pays ONE
   * io_uring_enter via flush() (an SQ that fills mid-batch still flushes
   * inline below; correctness never depends on the deferred flush). */
  int submit(uint8_t opcode, int fd_, uint64_t off, void *addr, uint32_t len,
             uint64_t user_data, uint16_t buf_index = 0,
             bool flush_now = true, uint8_t sqe_flags = 0) {
    uint32_t tail = *sq_tail;
    uint32_t head = __atomic_load_n(sq_head, __ATOMIC_ACQUIRE);
    if (tail - head >= sq_entries && sqpoll) {
      /* SQ full under SQPOLL: only the kernel's SQ thread consumes
       * entries, and it does so as soon as it RUNS — a full queue means
       * it has not been scheduled since the last sq_entries submissions
       * (a busy or virtualised host; sanitizer slowdown on this side).
       * Spinning on the doorbell does not run it any sooner: wake it if
       * it sleeps, try a few times for a poller that is mid-drain on
       * another core, then give up the CPU between checks until the
       * deadline.  These polls are not elided doorbells. */
      const uint64_t deadline = now_ns() + kSqFullWaitNs;
      for (int i = 0; tail - head >= sq_entries; i++) {
        if (i >= 64) {
          if (now_ns() >= deadline) return -EBUSY;
          usleep(50);
        }
        sqpoll_kick(/*count_elide=*/false);
        head = __atomic_load_n(sq_head, __ATOMIC_ACQUIRE);
      }
    } else if (tail - head >= sq_entries) {
      /* SQ full: nudge the kernel and spin-wait (bounded by in-flight I/O). */
      for (int i = 0; i < 100000 && tail - head >= sq_entries; i++) {
        flush();
        syscall(__NR_io_uring_enter, fd, 0, 0, 0, nullptr, 0);
        head = __atomic_load_n(sq_head, __ATOMIC_ACQUIRE);
      }
      if (tail - head >= sq_entries) return -EBUSY;
    }
    uint32_t idx = tail & *sq_mask;
    io_uring_sqe_ *sqe = &sqes[idx];
    memset(sqe, 0, sizeof(*sqe));
    sqe->opcode = opcode;
    sqe->flags = sqe_flags;
    sqe->fd = fd_;
    sqe->off = off;
    sqe->addr = (uint64_t)addr;
    sqe->len = len;
    sqe->user_data = user_data;
    sqe->buf_index = buf_index;
    sq_array[idx] = idx;
    __atomic_store_n(sq_tail, tail + 1, __ATOMIC_RELEASE);
    if (sqpoll) {
      /* publishing IS submitting: the SQ thread consumes the tail on
       * its own.  `unsubmitted` stays 0 — there is no backlog an
       * abandoned enter could strand. */
      if (flush_now) sqpoll_kick();
      return 0;
    }
    unsubmitted.fetch_add(1, std::memory_order_acq_rel);
    if (flush_now) flush();
    return 0; /* published: the op WILL reach the kernel */
  }

  /* Blocks for >=1 completion; invokes fn(user_data, res) per CQE.
   * Returns number consumed, or -errno. */
  template <typename F>
  int reap(F &&fn) {
    if (unsubmitted.load(std::memory_order_acquire) > 0) flush();
    uint32_t head = *cq_head;
    uint32_t tail = __atomic_load_n(cq_tail, __ATOMIC_ACQUIRE);
    if (head == tail) {
      int r = (int)syscall(__NR_io_uring_enter, fd, 0, 1, kEnterGetevents,
                           nullptr, 0);
      if (r < 0 && errno != EINTR) return -errno;
      tail = __atomic_load_n(cq_tail, __ATOMIC_ACQUIRE);
    }
    int n = 0;
    while (head != tail) {
      io_uring_cqe_ *cqe = &cqes[head & *cq_mask];
      fn(cqe->user_data, cqe->res);
      head++;
      n++;
    }
    __atomic_store_n(cq_head, head, __ATOMIC_RELEASE);
    return n;
  }
};

/* ---------------------------- engine ---------------------------- */

struct RingCtx;

namespace {

inline uint64_t align_down(uint64_t x, uint64_t a) { return x & ~(a - 1); }
inline uint64_t align_up(uint64_t x, uint64_t a) { return (x + a - 1) & ~(a - 1); }

struct FileEnt {
  int fd_direct = -1;   /* -1 when the fs refused O_DIRECT */
  int fd_buffered = -1;
  int64_t size = 0;
  bool writable = false;
  /* registered-file slots (-1 = not in the table): hot submissions use
   * IOSQE_FIXED_FILE with the slot index so the kernel skips the
   * per-op fget/fput of the raw fd */
  int slot_direct = -1;
  int slot_buffered = -1;
};

enum class ReqState { kInflight, kDone };

/* Is [offset, offset+len) fully resident in the page cache?  The
 * reference's kernel module checks this per block and returns resident
 * blocks to userspace instead of issuing NVMe reads (SURVEY.md §3.1);
 * here a transient mmap + mincore answers the same question from
 * userspace without faulting anything in (mmap does not populate).
 * preadv2(RWF_NOWAIT) would also work but performs the copy during the
 * probe — under the submit lock that would stall other submitters. */
static bool span_resident(int fd, uint64_t offset, uint64_t len) {
  if (len == 0) return false;
  static const uint64_t pg = (uint64_t)sysconf(_SC_PAGESIZE);
  uint64_t m_off = align_down(offset, pg);
  uint64_t m_len = offset + len - m_off;
  void *m = mmap(nullptr, m_len, PROT_READ, MAP_SHARED, fd, (off_t)m_off);
  if (m == MAP_FAILED) return false;
  size_t npg = (size_t)((m_len + pg - 1) / pg);
  bool all = true;
  std::vector<unsigned char> vec(npg);
  if (mincore(m, m_len, vec.data()) != 0) {
    all = false;
  } else {
    for (size_t i = 0; i < npg; i++)
      if (!(vec[i] & 1)) { all = false; break; }
  }
  munmap(m, m_len);
  return all;
}

struct Req {
  int64_t id = 0;
  RingCtx *rc = nullptr;               /* owning ring                 */
  int fh = -1;
  uint64_t offset = 0, len = 0;        /* caller's request            */
  uint64_t a_off = 0, a_len = 0;       /* aligned span actually read  */
  int buf_idx = -1;                    /* -1: zero-copy direct write  */
  uint8_t *buf = nullptr;              /* base of staging buffer      */
  const void *wsrc = nullptr;          /* write source (write path)   */
  bool is_write = false;
  bool direct = false;                 /* submitted O_DIRECT          */
  bool was_fallback = false;
  bool dispatched = false;             /* handed to a backend (uring
                                          SQE staged / worker queued) —
                                          a restart's drain waits ONLY
                                          for these; deferred and
                                          parked requests hold no
                                          kernel-visible I/O          */
  bool parked = false;                 /* on the ring's stall/restart
                                          park queue                  */
  bool planned_resident = false;       /* submit-time mincore probe chose
                                          the page-cache path on purpose */
  ReqState state = ReqState::kInflight;
  int status = 0;                      /* 0 or -errno                 */
  uint64_t done_len = 0;               /* payload bytes transferred   */
  uint64_t t_submit = 0, t_complete = 0; /* CLOCK_MONOTONIC ns        */
};

}  // namespace

/* One submission ring: an io_uring instance (or worker pool) with its
 * own completion reaping, its own slice of the staging pool, its own
 * deferral queue, and its own lock.  The engine shards into N of these
 * (strom_engine_create_rings) so concurrent traffic classes never
 * serialize behind one doorbell or one pool mutex; request ids carry
 * the ring index in their low STROM_RING_ID_BITS bits, so wait/release
 * route without any shared map. */
struct RingCtx {
  strom_engine *eng = nullptr;
  uint32_t idx = 0;
  Uring ring;
  bool use_uring = false;
  std::thread reaper;
  std::vector<std::thread> workers;
  std::deque<Req *> work_q;             /* thread-pool backend queue */

  std::mutex mu;
  std::condition_variable cv_done;      /* request completed       */
  std::condition_variable cv_work;      /* thread-pool work queue  */
  std::unordered_map<int64_t, Req *> reqs;

  /* Lock-free per-ring counters: the QoS scheduler polls queue depth
   * (submitted - completed) at dispatch frequency without ever taking
   * the ring mutex. */
  std::atomic<uint64_t> rg_sub{0}, rg_comp{0};
  /* Failure-domain health (strom_ring_info / io/health.py): completions
   * with a real error (cancels excluded) and hot restarts survived. */
  std::atomic<uint64_t> rg_fail{0}, rg_restarts{0};

  /* Stall injection + restart window (all under mu): while `stalled`
   * (chaos) or `restarting`, requests reaching dispatch park here in
   * order instead of going to a backend — the deterministic stand-in
   * for a wedged submission queue.  strom_ring_restart cancels the
   * backlog (-ECANCELED, the requeue path); strom_set_ring_stall(.., 0)
   * dispatches it (a stall that healed itself). */
  std::deque<Req *> park_q;
  bool stalled = false;
  bool restarting = false;
  uint64_t stall_after = 0;   /* clean dispatches before the stall bites */
  uint64_t stall_seen = 0;

  /* Worker-pool SQPOLL analogue (under mu): workers POLL the work
   * queue for sq_idle_ns before sleeping, and a dispatch that finds a
   * poller awake skips the wakeup notification entirely — the same
   * doorbell-elision state machine as the kernel SQ thread, same
   * counters, so the mode is benchable and testable on hosts without
   * io_uring. */
  bool sq_poll = false;
  uint64_t sq_idle_ns = 0;
  int poll_workers = 0;       /* workers currently awake-polling */

  void complete_locked(Req *r);
  void complete(Req *r) {
    std::lock_guard<std::mutex> g(mu);
    complete_locked(r);
  }
  void dispatch_locked(Req *r, bool flush_now = true);
  void reaper_loop();
  void worker_loop();
};

struct strom_engine {
  uint32_t queue_depth, n_buffers, alignment;  /* PER RING */
  uint32_t n_rings = 1;
  uint64_t buf_bytes;     /* payload capacity */
  uint64_t buf_cap;       /* buf_bytes + 2*alignment slack */
  bool locked = false;
  bool owns_pool = true;  /* false: pool is an arena carve the caller
                             owns — never munmap'd here (PR 12)       */
  /* Zero-copy submission modes (env at create; see strom_io.h): */
  bool sqpoll_enabled = false;
  uint32_t sqpoll_idle_ms = 50;
  bool reg_files_enabled = true;
  std::atomic<bool> stopping{false};

  uint8_t *pool = nullptr;   /* ONE mapping, ONE fungible pool: any ring
                                may stage into any buffer (each ring
                                registers the whole pool as fixed
                                buffers).  A global pool is load-bearing
                                for deadlock freedom: consumers size
                                their in-flight window against the WHOLE
                                pool, and a batch pinned to one ring
                                must never deadlock behind a per-ring
                                slice smaller than that window. */
  size_t pool_sz = 0;
  std::mutex pool_mu;        /* leaf lock (may nest under a ring mutex):
                                guards free_bufs + the GLOBAL deferral
                                FIFO, which preserves engine-wide
                                submission order for buffer handoff */
  std::vector<int> free_bufs;
  std::deque<Req *> defer_q; /* submitted, awaiting a buffer (any ring) */
  std::vector<std::unique_ptr<RingCtx>> rings;
  std::atomic<uint64_t> rr{0};          /* round-robin ring pick  */
  std::atomic<int64_t> next_req{1};
  std::mutex restart_mu;                /* serializes hot ring restarts
                                           against each other and
                                           against engine destroy (held
                                           across the whole restart —
                                           outermost, never taken under
                                           a ring mutex) */

  std::mutex files_mu;                  /* leaf lock: may be taken while
                                           a ring mutex is held, never
                                           the other way around */
  std::unordered_map<int, FileEnt> files;
  int next_fh = 1;
  /* Registered-file slot table (under files_mu): the canonical fd-per-
   * slot view every uring registered at init and updates at open/close
   * — and what a hot restart re-registers from after its rebuild. */
  std::vector<int32_t> reg_fds;
  std::vector<uint32_t> reg_free;

  /* Update one slot on every registered ring.  Caller holds
   * restart_mu, NOT files_mu: the syscall touches each Uring's fd,
   * which only a hot restart ever tears down/rebuilds — restart_mu is
   * exactly the lock that excludes restarts (taking a ring mutex here
   * instead would invert the ring-mutex→files_mu order).  A ring that
   * refuses the update drops its reg_files flag — raw-fd submission
   * is always correct, a stale slot never is.  (On pre-5.11 kernels a
   * raw fd on a SQPOLL ring completes -EBADF; the reaper's sync
   * rescue path then serves the op buffered — degraded, never
   * wrong.) */
  void reg_update_all(uint32_t slot, int32_t newfd);
  int32_t reg_alloc_slot(int fd);      /* files_mu held; -1 = full    */
  void reg_clear_slot(int32_t slot);   /* files_mu held: table -1,
                                          slot NOT yet reusable       */
  void reg_recycle_slot(int32_t slot); /* files_mu held: back to free */

  RingCtx *pick_ring() {
    return rings[rr.fetch_add(1, std::memory_order_relaxed)
                 % n_rings].get();
  }
  RingCtx *ring_of_id(int64_t id) {
    if (id < 0) return nullptr;
    uint32_t ri = (uint32_t)(id & ((1 << STROM_RING_ID_BITS) - 1));
    return ri < n_rings ? rings[ri].get() : nullptr;
  }
  int64_t alloc_id(RingCtx *rc) {
    return (next_req.fetch_add(1, std::memory_order_relaxed)
            << STROM_RING_ID_BITS) | (int64_t)rc->idx;
  }
  bool file_copy(int fh, FileEnt *out) {
    std::lock_guard<std::mutex> g(files_mu);
    auto it = files.find(fh);
    if (it == files.end()) return false;
    *out = it->second;
    return true;
  }

  /* Assign a free staging buffer to r (1), park it on the global
   * deferral FIFO (0), or refuse because the engine is stopping (-1 —
   * the caller completes it -ECANCELED).  Never blocks.  The owning
   * ring's mutex must be held (pool_mu nests under it).  The stopping
   * re-check under pool_mu closes the race with destroy's cancel
   * sweep: either the sweep (also under pool_mu) sees our parked
   * request, or we see stopping — a request can never park AFTER the
   * sweep and wedge the drain. */
  int acquire_or_defer(Req *r) {
    std::lock_guard<std::mutex> g(pool_mu);
    if (!free_bufs.empty()) {
      r->buf_idx = free_bufs.back();
      free_bufs.pop_back();
      r->buf = buf_ptr(r->buf_idx);
      return 1;
    }
    if (stopping.load(std::memory_order_acquire)) return -1;
    defer_q.push_back(r);
    return 0;
  }

  void recycle_buffer(int buf_idx);   /* defined after RingCtx methods */

  std::atomic<uint64_t> st_direct{0}, st_fallback{0}, st_bounce{0},
      st_written{0}, st_sub{0}, st_comp{0}, st_fail{0}, st_retry{0},
      st_resident{0}, st_batches{0}, st_sysc_saved{0}, st_enters{0};
  bool probe_residency = true;   /* STROM_NO_RESIDENCY_PROBE disables */

  /* Fault injection BELOW Python (stress/chaos runs; see
   * nvme_strom_tpu/io/faults.py for the Python-level plan): read at
   * engine create from STROM_FAULT_READ_EIO_EVERY /
   * STROM_FAULT_READ_SHORT_EVERY / STROM_FAULT_READ_DELAY_MS.  All
   * zero (the default) keeps this path entirely off the hot loop. */
  uint64_t fault_eio_every = 0, fault_short_every = 0, fault_delay_ns = 0;
  std::atomic<uint64_t> fault_seq{0};
  /* Write-path mirror (STROM_FAULT_WRITE_*): the checkpoint/offload
   * durability story needs the native completion path to fail too —
   * EIO, ENOSPC, short write, completion delay. */
  uint64_t wfault_eio_every = 0, wfault_enospc_every = 0,
      wfault_short_every = 0, wfault_delay_ns = 0;
  std::atomic<uint64_t> wfault_seq{0};

  /* Applied at the read completion boundary (both backends funnel
   * through here right before complete(r)): a delay holds the
   * completion in flight — a latency straggler as the waiter sees it —
   * then every Nth read is failed with -EIO or halved (a short read
   * the caller must detect and recover). */
  void maybe_inject_read_fault(Req *r) {
    if (r->is_write ||
        !(fault_eio_every | fault_short_every | fault_delay_ns))
      return;
    uint64_t n = fault_seq.fetch_add(1, std::memory_order_relaxed) + 1;
    if (fault_delay_ns) {
      struct timespec ts = {
          (time_t)(fault_delay_ns / 1000000000ull),
          (long)(fault_delay_ns % 1000000000ull)};
      nanosleep(&ts, nullptr);
    }
    if (fault_eio_every && n % fault_eio_every == 0) {
      r->status = -EIO;
      r->done_len = 0;
      st_fail.fetch_add(1, std::memory_order_relaxed);
    } else if (fault_short_every && n % fault_short_every == 0 &&
               r->status == 0 && r->done_len > 1) {
      r->done_len /= 2;
    }
  }

  /* Write-completion injection (both backends funnel through here right
   * before complete(r) on the write branch): delay holds the completion
   * in flight, then every Nth write fails -EIO / -ENOSPC or reports
   * half its bytes written — the short-write resubmission case the
   * Python-level retry path must detect and finish. */
  void maybe_inject_write_fault(Req *r) {
    if (!r->is_write ||
        !(wfault_eio_every | wfault_enospc_every | wfault_short_every |
          wfault_delay_ns))
      return;
    uint64_t n = wfault_seq.fetch_add(1, std::memory_order_relaxed) + 1;
    if (wfault_delay_ns) {
      struct timespec ts = {
          (time_t)(wfault_delay_ns / 1000000000ull),
          (long)(wfault_delay_ns % 1000000000ull)};
      nanosleep(&ts, nullptr);
    }
    if (wfault_eio_every && n % wfault_eio_every == 0) {
      r->status = -EIO;
      r->done_len = 0;
      st_fail.fetch_add(1, std::memory_order_relaxed);
    } else if (wfault_enospc_every && n % wfault_enospc_every == 0) {
      r->status = -ENOSPC;
      r->done_len = 0;
      st_fail.fetch_add(1, std::memory_order_relaxed);
    } else if (wfault_short_every && n % wfault_short_every == 0 &&
               r->status == 0 && r->done_len > 1) {
      r->done_len /= 2;
    }
  }
  std::atomic<uint64_t> lat_read[STROM_LAT_BUCKETS] = {};
  std::atomic<uint64_t> lat_write[STROM_LAT_BUCKETS] = {};

  uint8_t *buf_ptr(int idx) { return pool + (uint64_t)idx * buf_cap; }

  /* Synchronous read with the full fallback ladder; used by the thread-pool
   * backend and by the reaper when an io_uring direct read needs rescue.
   * Fills req->status/done_len/was_fallback. Caller does NOT hold mu. */
  void read_sync(Req *r, const FileEnt &fe) {
    uint64_t avail = r->offset < (uint64_t)fe.size
                         ? std::min<uint64_t>(r->len, fe.size - r->offset)
                         : 0;
    if (avail == 0) { r->status = 0; r->done_len = 0; return; }
    uint64_t head = r->offset - r->a_off;
    if (fe.fd_direct >= 0 && r->direct) {
      uint64_t got = 0;
      bool ok = true;
      while (got < r->a_len) {
        ssize_t n = pread(fe.fd_direct, r->buf + got, r->a_len - got,
                          (off_t)(r->a_off + got));
        if (n < 0) { ok = false; break; }
        if (n == 0) break; /* EOF */
        got += (uint64_t)n;
      }
      if (ok && got >= head + avail) {
        r->status = 0;
        r->done_len = avail;
        st_direct.fetch_add(avail, std::memory_order_relaxed);
        return;
      }
      st_retry.fetch_add(1, std::memory_order_relaxed);
    }
    /* Buffered fallback: page cache in the middle -> host copy, counted. */
    uint64_t got = 0;
    while (got < avail) {
      ssize_t n = pread(fe.fd_buffered, r->buf + head + got, avail - got,
                        (off_t)(r->offset + got));
      if (n < 0) { r->status = -errno; st_fail.fetch_add(1); return; }
      if (n == 0) break;
      got += (uint64_t)n;
    }
    r->status = 0;
    r->done_len = got;
    r->was_fallback = true;
    st_fallback.fetch_add(got, std::memory_order_relaxed);
    st_bounce.fetch_add(got, std::memory_order_relaxed);
    if (r->planned_resident)
      st_resident.fetch_add(got, std::memory_order_relaxed);
  }

  void write_sync(Req *r, const FileEnt &fe) {
    const uint8_t *src = r->buf_idx >= 0 ? r->buf : (const uint8_t *)r->wsrc;
    int fd = (r->direct && fe.fd_direct >= 0) ? fe.fd_direct : fe.fd_buffered;
    uint64_t put = 0;
    while (put < r->len) {
      ssize_t n = pwrite(fd, src + put, r->len - put, (off_t)(r->offset + put));
      if (n < 0) {
        if (errno == EINVAL && fd == fe.fd_direct) {
          fd = fe.fd_buffered;
          r->was_fallback = true;
          st_retry.fetch_add(1, std::memory_order_relaxed);
          continue;
        }
        r->status = -errno;
        st_fail.fetch_add(1);
        return;
      }
      put += (uint64_t)n;
    }
    r->status = 0;
    r->done_len = put;
    if (!r->was_fallback && r->direct)
      st_written.fetch_add(put, std::memory_order_relaxed);
    else if (r->buf_idx < 0)
      /* Zero-copy attempt that fell back to buffered: the kernel's
       * page-cache copy is the bounce. (Staged writes already counted
       * their bounce at the memcpy into the staging buffer.) */
      st_bounce.fetch_add(put, std::memory_order_relaxed);
  }

};

void RingCtx::complete_locked(Req *r) {
  if (r->state == ReqState::kDone) return;  /* idempotent: a restart's
                                               cancel must not race a
                                               backend completion into
                                               double accounting */
  r->state = ReqState::kDone;
  r->t_complete = now_ns();
  if (r->status < 0 && r->status != -ECANCELED)
    rg_fail.fetch_add(1, std::memory_order_relaxed);
  if (r->status == 0) {
    /* Failures are counted in st_fail; bucketing their near-instant
     * "latency" would drag the p50/p99 gauges toward zero exactly when
     * the system is misbehaving. */
    uint64_t lat = r->t_complete - r->t_submit;
    int b = 63 - __builtin_clzll(lat | 1);
    (r->is_write ? eng->lat_write : eng->lat_read)[b].fetch_add(
        1, std::memory_order_relaxed);
  }
  /* release: pairs with the acquire load in strom_get_stats so an
   * observer that sees this completion also sees the corresponding
   * st_sub increment (which happens-before it via the request's
   * submit->complete chain). */
  eng->st_comp.fetch_add(1, std::memory_order_release);
  rg_comp.fetch_add(1, std::memory_order_release);
  cv_done.notify_all();
}

/* Hand a buffer-holding request to the backend. The ring mutex must be
 * held (files_mu is a leaf lock and may be taken under it).
 * A submission waits only on a full SQ: without SQPOLL it enters the
 * kernel, which drains the SQ; under SQPOLL it sleeps in 50 us steps until
 * the kernel's SQ thread has run (common on a busy or virtualised host,
 * where that thread is not scheduled for milliseconds).  A queue still
 * full after kSqFullWaitNs fails the request with -EBUSY.
 * ``flush_now = false`` defers the uring doorbell (vectored submit:
 * the caller flushes once for the whole batch). */
void RingCtx::dispatch_locked(Req *r, bool flush_now) {
  /* Failure-domain hooks: a restart window or an armed stall parks the
   * request (in order) instead of dispatching — it stays kInflight
   * with no backend I/O, exactly what a wedged submission queue looks
   * like to its waiter. */
  if (restarting) {
    r->parked = true;
    park_q.push_back(r);
    return;
  }
  if (stalled) {
    if (stall_seen >= stall_after) {
      r->parked = true;
      park_q.push_back(r);
      return;
    }
    stall_seen++;
  }
  r->dispatched = true;
  FileEnt fe;
  if (!eng->file_copy(r->fh, &fe)) {
    r->status = -EBADF;
    eng->st_fail.fetch_add(1, std::memory_order_relaxed);
    complete_locked(r);
    return;
  }
  if (use_uring) {
    int rc;
    /* A request holding a staging buffer targets registered memory:
     * use the fixed-buffer opcode so the kernel skips per-I/O pinning.
     * Every ring registered the WHOLE pool, so buf_index is global. */
    bool fixed = ring.fixed_bufs && r->buf_idx >= 0;
    uint16_t bidx = fixed ? (uint16_t)r->buf_idx : 0;
    /* Registered file: sqe->fd becomes the slot index and the kernel
     * skips the per-op fget — the hot-path half of "one doorbell". */
    int slot = r->direct ? fe.slot_direct : fe.slot_buffered;
    bool ff = ring.reg_files && slot >= 0;
    uint8_t sflags = ff ? kSqeFixedFile : 0;
    if (r->is_write) {
      const uint8_t *s = r->buf_idx >= 0 ? r->buf : (const uint8_t *)r->wsrc;
      int fd = r->direct ? fe.fd_direct : fe.fd_buffered;
      rc = ring.submit(fixed ? kOpWriteFixed : kOpWrite,
                       ff ? slot : fd,
                       r->offset, (void *)s, (uint32_t)r->len,
                       (uint64_t)r->id, bidx, flush_now, sflags);
    } else {
      int fd = r->direct ? fe.fd_direct : fe.fd_buffered;
      uint64_t off = r->direct ? r->a_off : r->offset;
      uint8_t *dst = r->direct ? r->buf : r->buf + (r->offset - r->a_off);
      uint32_t rlen = (uint32_t)(r->direct ? r->a_len : r->len);
      rc = ring.submit(fixed ? kOpReadFixed : kOpRead, ff ? slot : fd,
                       off, dst, rlen,
                       (uint64_t)r->id, bidx, flush_now, sflags);
    }
    if (rc != 0) {
      r->status = rc;
      eng->st_fail.fetch_add(1, std::memory_order_relaxed);
      complete_locked(r);
    }
    return;
  }
  work_q.push_back(r);
  if (sq_poll && poll_workers >= (int)work_q.size()) {
    /* SQPOLL analogue: enough pollers are awake to absorb the WHOLE
     * queue on their next poll tick — the wakeup doorbell is
     * unnecessary, which is the whole point of the mode.  Counted
     * exactly like the uring backend's elided io_uring_enter.  The
     * queue-size bound matters: unlike the kernel SQ thread (which
     * only consumes submissions), our pollers execute the full I/O —
     * eliding more wakeups than there are awake pollers would
     * serialize a burst behind one worker while the rest sleep. */
    eng->st_sysc_saved.fetch_add(1, std::memory_order_relaxed);
  } else {
    eng->st_enters.fetch_add(1, std::memory_order_relaxed);
    cv_work.notify_one();
  }
}

/* A staging buffer became free: hand it to the OLDEST deferred request
 * engine-wide (whatever its ring — this cross-ring handoff is the
 * deadlock-freedom guarantee a batch pinned to one ring relies on), or
 * return it to the global pool.  Called with NO locks held. */
void strom_engine::recycle_buffer(int buf_idx) {
  Req *next = nullptr;
  {
    std::lock_guard<std::mutex> g(pool_mu);
    if (defer_q.empty()) {
      free_bufs.push_back(buf_idx);
      return;
    }
    next = defer_q.front();
    defer_q.pop_front();
    next->buf_idx = buf_idx;
    next->buf = buf_ptr(buf_idx);
  }
  RingCtx *rc = next->rc;
  std::lock_guard<std::mutex> g(rc->mu);
  if (next->is_write) {
    /* Deferred bounce write: stage the caller bytes now. The wrapper
     * keeps the source alive until wait(). */
    memcpy(next->buf, next->wsrc, next->len);
    st_bounce.fetch_add(next->len, std::memory_order_relaxed);
  }
  rc->dispatch_locked(next);
}

void RingCtx::reaper_loop() {
  bool stop = false;
  while (!stop) {
    ring.reap([&](uint64_t ud, int32_t res) {
      if (ud == kShutdownUserData) { stop = true; return; }
      Req *r;
      {
        std::lock_guard<std::mutex> g(mu);
        auto it = reqs.find((int64_t)ud);
        if (it == reqs.end()) return;
        r = it->second;
      }
      FileEnt fe;
      if (!eng->file_copy(r->fh, &fe)) {
        r->status = -EBADF;
        complete(r);
        return;
      }
      if (r->is_write) {
        if (res >= 0 && (uint64_t)res == r->len) {
          r->status = 0;
          r->done_len = r->len;
          if (r->direct)
            eng->st_written.fetch_add(r->len, std::memory_order_relaxed);
          else if (r->buf_idx < 0)
            /* See write_sync: staged writes counted their bounce at the
             * staging memcpy already. */
            eng->st_bounce.fetch_add(r->len, std::memory_order_relaxed);
        } else {
          eng->st_retry.fetch_add(1, std::memory_order_relaxed);
          eng->write_sync(r, fe); /* rescue: finish/retry synchronously */
        }
        eng->maybe_inject_write_fault(r);
        complete(r);
        return;
      }
      /* Direct reads were submitted over the aligned span (head bytes of
       * slack precede the payload); buffered reads were submitted at the
       * exact offset and return at most `avail`. */
      uint64_t head = r->direct ? r->offset - r->a_off : 0;
      uint64_t avail = r->offset < (uint64_t)fe.size
                           ? std::min<uint64_t>(r->len, fe.size - r->offset)
                           : 0;
      if (res >= 0 && (uint64_t)res >= head + avail) {
        r->status = 0;
        r->done_len = avail;
        if (r->direct)
          eng->st_direct.fetch_add(avail, std::memory_order_relaxed);
        else {
          r->was_fallback = true;
          eng->st_fallback.fetch_add(avail, std::memory_order_relaxed);
          eng->st_bounce.fetch_add(avail, std::memory_order_relaxed);
          if (r->planned_resident)
            eng->st_resident.fetch_add(avail, std::memory_order_relaxed);
        }
      } else {
        /* Short read or error (EINVAL on tmpfs etc.): rescue path.
         * A rescued read is a RETRY, whatever the original plan —
         * clear planned_resident so its bytes never count as a
         * planned page-cache hit (header contract: resident is not
         * a rescue). */
        eng->st_retry.fetch_add(1, std::memory_order_relaxed);
        r->direct = false;
        r->planned_resident = false;
        eng->read_sync(r, fe);
        r->was_fallback = true;
      }
      eng->maybe_inject_read_fault(r);
      complete(r);
    });
  }
}

void RingCtx::worker_loop() {
  for (;;) {
    Req *r;
    {
      std::unique_lock<std::mutex> lk(mu);
      auto ready = [&] {
        return eng->stopping.load(std::memory_order_acquire) ||
               !work_q.empty();
      };
      if (sq_poll) {
        /* SQPOLL analogue: poll the queue in short ticks for up to
         * sq_idle_ns before sleeping.  While polling, this worker is
         * counted in poll_workers so dispatchers elide their wakeup
         * (the doorbell the mode removes); once the idle budget is
         * spent the worker sleeps indefinitely and the NEXT dispatch
         * pays one wakeup — exactly the kernel SQ thread's
         * NEED_WAKEUP handshake. */
        uint64_t idle_start = now_ns();
        while (!ready()) {
          poll_workers++;
          /* system-clock wait_until, NOT wait_for: libstdc++'s
           * steady-clock wait lands on pthread_cond_clockwait, which
           * gcc-10-era TSAN does not intercept — every poll tick would
           * then read as a phantom double-lock.  The poll cadence does
           * not care which clock measures 200 us. */
          cv_work.wait_until(lk, std::chrono::system_clock::now() +
                                     std::chrono::microseconds(200));
          poll_workers--;
          if (ready()) break;
          if (now_ns() - idle_start >= sq_idle_ns) {
            cv_work.wait(lk, ready);   /* asleep: doorbell required */
            break;
          }
        }
      } else {
        cv_work.wait(lk, ready);
      }
      if (work_q.empty()) return;  /* stopping, queue drained */
      r = work_q.front();
      work_q.pop_front();
    }
    FileEnt fe;
    if (!eng->file_copy(r->fh, &fe)) {
      r->status = -EBADF;
      complete(r);
      continue;
    }
    if (r->is_write)
      eng->write_sync(r, fe);
    else
      eng->read_sync(r, fe);
    eng->maybe_inject_read_fault(r);
    eng->maybe_inject_write_fault(r);
    complete(r);
  }
}

/* ------------------------- public C ABI ------------------------- */

extern "C" {

/* Registered-file slot budget per engine: big enough for every consumer
 * pattern in the repo (each open costs <= 2 slots: direct + buffered
 * fd); files past it simply submit by raw fd. */
#define STROM_REG_FILE_SLOTS 128

static strom_engine *engine_create_common(
    uint32_t n_rings, uint32_t queue_depth, uint32_t n_buffers,
    uint64_t buf_bytes, uint32_t alignment, int use_io_uring,
    int lock_buffers, void *prealloc, uint64_t prealloc_bytes) {
  if (!n_rings || n_rings > STROM_MAX_RINGS || !queue_depth || !n_buffers ||
      !buf_bytes || !alignment || (alignment & (alignment - 1))) {
    errno = EINVAL;
    return nullptr;
  }
  auto *e = new strom_engine();
  e->n_rings = n_rings;
  e->queue_depth = queue_depth;
  e->n_buffers = n_buffers;
  e->alignment = alignment;
  e->buf_bytes = buf_bytes;
  e->buf_cap = align_up(buf_bytes, alignment) + 2 * (uint64_t)alignment;
  /* ONE formula, shared with the public helper: a prealloc caller's
   * computed carve size must never drift from the engine's own check */
  e->pool_sz = (size_t)strom_engine_pool_bytes(n_rings, n_buffers,
                                               buf_bytes, alignment);
  if (prealloc != nullptr) {
    /* Arena carve (io/arena.py): the caller owns (and outlives) the
     * mapping; the engine stages into it but never unmaps it. */
    if (prealloc_bytes < e->pool_sz) {
      delete e;
      errno = EINVAL;
      return nullptr;
    }
    e->pool = (uint8_t *)prealloc;
    e->owns_pool = false;
  } else {
    e->pool = (uint8_t *)mmap(nullptr, e->pool_sz, PROT_READ | PROT_WRITE,
                              MAP_PRIVATE | MAP_ANONYMOUS, -1, 0);
    if (e->pool == MAP_FAILED) { delete e; return nullptr; }
  }
  /* Pin the pool — the MAP_GPU_MEMORY analogue: the reference pins BAR1
   * pages so DMA targets never move (SURVEY.md §3.2); we pin staging pages
   * so neither NVMe DMA nor the TPU transfer hits a fault. Soft-fail.
   * (A prealloc'd pool is re-mlocked here harmlessly: destroy skips the
   * munmap, so the arena's lock outlives the engine either way.) */
  if (lock_buffers) e->locked = mlock(e->pool, e->pool_sz) == 0;
  e->probe_residency = getenv("STROM_NO_RESIDENCY_PROBE") == nullptr;
  {
    /* Chaos knobs (tests/stress only; all default off — see
     * maybe_inject_read_fault). */
    auto env_u64 = [](const char *name) -> uint64_t {
      const char *v = getenv(name);
      return v ? strtoull(v, nullptr, 10) : 0;
    };
    e->fault_eio_every = env_u64("STROM_FAULT_READ_EIO_EVERY");
    e->fault_short_every = env_u64("STROM_FAULT_READ_SHORT_EVERY");
    e->fault_delay_ns = env_u64("STROM_FAULT_READ_DELAY_MS") * 1000000ull;
    e->wfault_eio_every = env_u64("STROM_FAULT_WRITE_EIO_EVERY");
    e->wfault_enospc_every = env_u64("STROM_FAULT_WRITE_ENOSPC_EVERY");
    e->wfault_short_every = env_u64("STROM_FAULT_WRITE_SHORT_EVERY");
    e->wfault_delay_ns = env_u64("STROM_FAULT_WRITE_DELAY_MS") * 1000000ull;
  }
  {
    /* Zero-copy submission modes (PR 12; defaults: registered files
     * on — they soft-fail harmlessly — SQPOLL opt-in: the poller burns
     * a core while idle, a deliberate spend). */
    const char *v = getenv("STROM_REG_FILES");
    e->reg_files_enabled = !(v && v[0] == '0' && v[1] == '\0');
    v = getenv("STROM_SQPOLL");
    e->sqpoll_enabled = v && v[0] == '1' && v[1] == '\0';
    if (const char *ims = getenv("STROM_SQPOLL_IDLE_MS")) {
      uint64_t ms = strtoull(ims, nullptr, 10);
      if (ms > 0 && ms <= 10000) e->sqpoll_idle_ms = (uint32_t)ms;
    }
  }
  e->reg_fds.assign(STROM_REG_FILE_SLOTS, -1);
  for (int s = STROM_REG_FILE_SLOTS - 1; s >= 0; s--)
    e->reg_free.push_back((uint32_t)s);
  for (int i = (int)(n_buffers * n_rings) - 1; i >= 0; i--)
    e->free_bufs.push_back(i);
  /* Ring-stall injection (chaos; default off): the named ring parks
   * its dispatches after the first N — the deterministic wedged-ring
   * drive for the supervision layer (io/health.py). */
  const char *stall_ring_env = getenv("STROM_FAULT_RING_STALL_RING");
  int64_t stall_ring = stall_ring_env ? strtoll(stall_ring_env, nullptr, 10)
                                      : -1;
  uint64_t stall_after = 0;
  if (const char *v = getenv("STROM_FAULT_RING_STALL_AFTER"))
    stall_after = strtoull(v, nullptr, 10);
  for (uint32_t ri = 0; ri < n_rings; ri++) {
    auto rcp = std::unique_ptr<RingCtx>(new RingCtx());
    RingCtx *rc = rcp.get();
    rc->eng = e;
    rc->idx = ri;
    if (stall_ring >= 0 && (uint32_t)stall_ring == ri) {
      rc->stalled = true;
      rc->stall_after = stall_after;
    }
    rc->ring.want_sqpoll = e->sqpoll_enabled;
    rc->ring.sqpoll_idle_ms = e->sqpoll_idle_ms;
    rc->ring.c_enters = &e->st_enters;
    rc->ring.c_elided = &e->st_sysc_saved;
    if (use_io_uring && rc->ring.init(queue_depth * 2)) {
      rc->use_uring = true;
      /* Each ring registers the WHOLE pool with its uring fd: buffers
       * are fungible across rings (deadlock freedom — see pool_mu). */
      rc->ring.try_register(e->pool, e->buf_cap, n_buffers * n_rings);
      if (e->reg_files_enabled)
        rc->ring.try_register_files(e->reg_fds.data(),
                                    STROM_REG_FILE_SLOTS);
      rc->reaper = std::thread([rc] { rc->reaper_loop(); });
    } else {
      rc->sq_poll = e->sqpoll_enabled;
      rc->sq_idle_ns = (uint64_t)e->sqpoll_idle_ms * 1000000ull;
      uint32_t nw = queue_depth < 32 ? queue_depth : 32;
      for (uint32_t i = 0; i < nw; i++)
        rc->workers.emplace_back([rc] { rc->worker_loop(); });
    }
    e->rings.push_back(std::move(rcp));
  }
  return e;
}

strom_engine *strom_engine_create_rings(uint32_t n_rings,
                                        uint32_t queue_depth,
                                        uint32_t n_buffers,
                                        uint64_t buf_bytes,
                                        uint32_t alignment,
                                        int use_io_uring, int lock_buffers) {
  return engine_create_common(n_rings, queue_depth, n_buffers, buf_bytes,
                              alignment, use_io_uring, lock_buffers,
                              nullptr, 0);
}

strom_engine *strom_engine_create_prealloc(uint32_t n_rings,
                                           uint32_t queue_depth,
                                           uint32_t n_buffers,
                                           uint64_t buf_bytes,
                                           uint32_t alignment,
                                           int use_io_uring,
                                           int lock_buffers,
                                           void *pool,
                                           uint64_t pool_bytes) {
  if (!pool) { errno = EINVAL; return nullptr; }
  return engine_create_common(n_rings, queue_depth, n_buffers, buf_bytes,
                              alignment, use_io_uring, lock_buffers,
                              pool, pool_bytes);
}

uint64_t strom_engine_pool_bytes(uint32_t n_rings, uint32_t n_buffers,
                                 uint64_t buf_bytes, uint32_t alignment) {
  if (!n_rings || n_rings > STROM_MAX_RINGS || !n_buffers || !buf_bytes ||
      !alignment || (alignment & (alignment - 1)))
    return 0;
  uint64_t cap = align_up(buf_bytes, alignment) + 2 * (uint64_t)alignment;
  return cap * n_buffers * n_rings;
}

strom_engine *strom_engine_create(uint32_t queue_depth, uint32_t n_buffers,
                                  uint64_t buf_bytes, uint32_t alignment,
                                  int use_io_uring, int lock_buffers) {
  return strom_engine_create_rings(1, queue_depth, n_buffers, buf_bytes,
                                   alignment, use_io_uring, lock_buffers);
}

/* ---- unified pinned arena (io/arena.py) ---- */

void *strom_arena_create(uint64_t bytes) {
  if (bytes == 0) { errno = EINVAL; return NULL; }
  /* NORESERVE: the arena is a cheap VIRTUAL reservation — pages commit
   * (and pin, via strom_arena_lock) per CARVE, so a generously sized
   * arena costs nothing until consumers actually stage into it. */
  void *base = mmap(NULL, bytes, PROT_READ | PROT_WRITE,
                    MAP_PRIVATE | MAP_ANONYMOUS | MAP_NORESERVE, -1, 0);
  if (base == MAP_FAILED) {
    base = mmap(NULL, bytes, PROT_READ | PROT_WRITE,
                MAP_PRIVATE | MAP_ANONYMOUS, -1, 0);
    if (base == MAP_FAILED) return NULL;
  }
  return base;
}

void strom_arena_destroy(void *base, uint64_t bytes) {
  if (base && bytes) munmap(base, bytes);
}

int strom_arena_lock(void *base, uint64_t bytes) {
  if (!base || !bytes) return -EINVAL;
  return mlock(base, bytes) == 0 ? 0 : -errno;
}

void strom_engine_destroy(strom_engine *e) {
  if (!e) return;
  e->stopping.store(true, std::memory_order_release);
  /* Flush any in-flight restart before tearing rings down (bounded
   * wait: a restart's drain is bounded by its timeout).  Acquire-and-
   * release: `stopping` is already visible, and strom_ring_restart
   * re-checks it under this mutex, so no NEW restart can start — and
   * the guard must not live across the `delete e` below. */
  { std::lock_guard<std::mutex> restart_guard(e->restart_mu); }
  for (auto &rcp : e->rings) {
    /* Parked (stalled / restart-window) requests never reached a
     * backend: cancel them so the per-ring drain below cannot wedge
     * waiting for completions that will never arrive. */
    RingCtx *rc = rcp.get();
    std::lock_guard<std::mutex> g(rc->mu);
    while (!rc->park_q.empty()) {
      Req *r = rc->park_q.front();
      rc->park_q.pop_front();
      r->parked = false;
      r->status = -ECANCELED;
      r->done_len = 0;
      rc->complete_locked(r);
    }
  }
  {
    /* Cancel the global deferral FIFO first: a deferred request's ring
     * drain below would otherwise wait forever for a buffer that no
     * releaser will recycle once callers stop. */
    std::deque<Req *> cancelled;
    {
      std::lock_guard<std::mutex> g(e->pool_mu);
      cancelled.swap(e->defer_q);
    }
    for (Req *r : cancelled) {
      RingCtx *rc = r->rc;
      std::lock_guard<std::mutex> g(rc->mu);
      r->status = -ECANCELED;
      rc->complete_locked(r);
    }
  }
  for (auto &rcp : e->rings) {
    RingCtx *rc = rcp.get();
    std::unique_lock<std::mutex> lk(rc->mu);
    rc->cv_work.notify_all();
    /* Drain: every in-flight request's DMA targets the staging pool — the
     * pool cannot be unmapped until the kernel is done with it. */
    rc->cv_done.wait(lk, [&] {
      for (auto &kv : rc->reqs)
        if (kv.second->state != ReqState::kDone) return false;
      return true;
    });
  }
  for (auto &rcp : e->rings) {
    RingCtx *rc = rcp.get();
    if (rc->use_uring) {
      {
        std::lock_guard<std::mutex> g(rc->mu);
        rc->ring.submit(kOpNop, -1, 0, nullptr, 0, kShutdownUserData);
      }
      if (rc->reaper.joinable()) rc->reaper.join();
      rc->ring.teardown();
    }
    for (auto &w : rc->workers)
      if (w.joinable()) w.join();
  }
  for (auto &kv : e->files) {
    if (kv.second.fd_direct >= 0) close(kv.second.fd_direct);
    if (kv.second.fd_buffered >= 0) close(kv.second.fd_buffered);
  }
  for (auto &rcp : e->rings)
    for (auto &kv : rcp->reqs) delete kv.second;
  /* An arena-carved pool belongs to the caller (io/arena.py recycles
   * the carve); unmapping it here would yank live cache lines and DMA
   * slabs sharing the arena. */
  if (e->pool && e->owns_pool) munmap(e->pool, e->pool_sz);
  delete e;
}

/* ---- registered-file slot table (files_mu held by callers) ---- */

int32_t strom_engine::reg_alloc_slot(int fd) {
  if (fd < 0 || reg_free.empty()) return -1;
  uint32_t slot = reg_free.back();
  reg_free.pop_back();
  reg_fds[slot] = fd;
  return (int32_t)slot;
}

void strom_engine::reg_clear_slot(int32_t slot) {
  if (slot >= 0) reg_fds[slot] = -1;
}

void strom_engine::reg_recycle_slot(int32_t slot) {
  /* Only AFTER the rings' slot entries were updated to -1: recycling
   * first would let a concurrent open re-allocate the slot and
   * register a fresh fd that our in-flight -1 update then clobbers. */
  if (slot >= 0) reg_free.push_back((uint32_t)slot);
}

void strom_engine::reg_update_all(uint32_t slot, int32_t newfd) {
  for (auto &rcp : rings) {
    RingCtx *rc = rcp.get();
    if (rc->use_uring && rc->ring.reg_files) {
      if (!rc->ring.update_file(slot, newfd))
        rc->ring.reg_files = false;   /* stale slots are never risked */
    }
  }
}

int strom_ring_count(strom_engine *e) { return (int)e->n_rings; }

int64_t strom_ring_inflight(strom_engine *e, uint32_t ring) {
  if (ring >= e->n_rings) return -EINVAL;
  RingCtx *rc = e->rings[ring].get();
  /* completed first: see strom_get_ring_info */
  uint64_t comp = rc->rg_comp.load(std::memory_order_acquire);
  uint64_t sub = rc->rg_sub.load(std::memory_order_relaxed);
  return sub > comp ? (int64_t)(sub - comp) : 0;
}

int strom_get_ring_info(strom_engine *e, uint32_t ring,
                        strom_ring_info *out) {
  if (ring >= e->n_rings) return -EINVAL;
  RingCtx *rc = e->rings[ring].get();
  /* completed BEFORE submitted: any completion implies visibility of its
   * own submission, so the snapshot's depth (sub - comp) is never
   * negative. */
  uint64_t comp = rc->rg_comp.load(std::memory_order_acquire);
  uint64_t sub = rc->rg_sub.load(std::memory_order_relaxed);
  out->ring_id = ring;
  out->n_buffers = e->n_buffers * e->n_rings;  /* pool is global */
  out->submitted = sub;
  out->completed = comp;
  out->inflight_io = (uint32_t)(sub > comp ? sub - comp : 0);
  out->backend_uring = rc->use_uring ? 1 : 0;
  out->failed = rc->rg_fail.load(std::memory_order_relaxed);
  out->restarts = rc->rg_restarts.load(std::memory_order_relaxed);
  {
    /* Health walk under the ring mutex (request maps are queue-depth
     * sized — this is a stat poll, not the dispatch hot path): parked
     * backlog plus the age of the oldest request a backend owes a
     * completion for.  Deferred requests are excluded from the age —
     * pool pressure is not a ring stall. */
    std::lock_guard<std::mutex> g(rc->mu);
    out->parked = (uint32_t)rc->park_q.size();
    out->stalled = rc->stalled ? 1 : 0;
    /* zero-copy submission state (PR 12), read under the ring mutex (a
     * hot restart rewrites these during its rebuild): a silently-
     * unregistered pool or slot table must be VISIBLE, not just slow */
    out->fixed_bufs = rc->use_uring && rc->ring.fixed_bufs ? 1 : 0;
    out->reg_files = rc->use_uring &&
        rc->ring.reg_files.load(std::memory_order_relaxed) ? 1 : 0;
    out->sqpoll = (rc->use_uring ? rc->ring.sqpoll : rc->sq_poll) ? 1 : 0;
    uint64_t oldest = 0;
    for (auto &kv : rc->reqs) {
      Req *r = kv.second;
      if (r->state == ReqState::kDone || !(r->dispatched || r->parked))
        continue;
      if (oldest == 0 || r->t_submit < oldest) oldest = r->t_submit;
    }
    out->oldest_inflight_ns = oldest ? now_ns() - oldest : 0;
  }
  {
    std::lock_guard<std::mutex> g(e->pool_mu);
    out->free_buffers = (uint32_t)e->free_bufs.size();
    uint32_t d = 0;
    for (Req *r : e->defer_q)
      if (r->rc == rc) d++;
    out->deferred = d;
  }
  return 0;
}

int strom_set_ring_stall(strom_engine *e, uint32_t ring, int on) {
  if (ring >= e->n_rings) return -EINVAL;
  RingCtx *rc = e->rings[ring].get();
  std::lock_guard<std::mutex> g(rc->mu);
  rc->stalled = on != 0;
  rc->stall_after = 0;
  rc->stall_seen = 0;
  if (!rc->stalled && !rc->restarting) {
    /* Disarm = the wedge healed on its own: dispatch the parked
     * backlog in order (waiters just saw one longer wait). */
    while (!rc->park_q.empty()) {
      Req *r = rc->park_q.front();
      rc->park_q.pop_front();
      r->parked = false;
      rc->dispatch_locked(r);
    }
  }
  return 0;
}

int64_t strom_ring_restart(strom_engine *e, uint32_t ring,
                           uint64_t drain_timeout_ns) {
  if (ring >= e->n_rings) return -EINVAL;
  if (e->stopping.load(std::memory_order_acquire)) return -ECANCELED;
  /* One restart at a time engine-wide, and never concurrent with
   * destroy (restart_mu is outermost; the drain below is bounded, so
   * a destroy blocked on it waits at most drain_timeout_ns). */
  std::unique_lock<std::mutex> restart_guard(e->restart_mu,
                                             std::try_to_lock);
  if (!restart_guard.owns_lock()) return -EBUSY;
  if (e->stopping.load(std::memory_order_acquire)) return -ECANCELED;
  RingCtx *rc = e->rings[ring].get();
  int64_t cancelled = 0;
  bool drained;
  {
    std::unique_lock<std::mutex> lk(rc->mu);
    rc->restarting = true;  /* new dispatches park until the rebuild */
    /* requests parked BEFORE this restart are the wedged backlog the
     * restart exists to requeue; anything parking during the window
     * (appended behind them) is fresh traffic that must DISPATCH
     * after the rebuild, never cancel */
    size_t pre_parked = rc->park_q.size();
    /* 1) bounded drain of I/O a backend actually owns (the predicate
     * ignores parked requests — no backend ever saw those).  An
     * un-completable request cannot be cancelled from here (its
     * staging buffer is a live DMA target): on timeout the restart
     * ABORTS with the ring truly as it was — parked requests stay
     * parked, nothing was cancelled — and the caller falls back to
     * degraded buffered reads. */
    auto quiesced = [&] {
      for (auto &kv : rc->reqs) {
        Req *r = kv.second;
        if (r->state != ReqState::kDone && r->dispatched) return false;
      }
      return true;
    };
    drained = rc->cv_done.wait_for(
        lk, std::chrono::nanoseconds(drain_timeout_ns), quiesced);
    if (!drained) {
      rc->restarting = false;
      /* requests parked during the window resume on the (still-sick)
       * backend — status quo ante; the supervisor keeps the breaker
       * open and routes around the ring.  Drain via a LOCAL queue:
       * with stall injection still armed, dispatch_locked re-parks
       * each request into rc->park_q — draining that same queue
       * in place would spin forever under both mutexes. */
      std::deque<Req *> resume;
      resume.swap(rc->park_q);
      while (!resume.empty()) {
        Req *r = resume.front();
        resume.pop_front();
        r->parked = false;
        rc->dispatch_locked(r);
      }
      return -ETIMEDOUT;
    }
    /* 2) the restart is now committed: cancel the stall-parked
     * backlog.  No backend ever saw these, so their buffers are clean
     * — the waiter's retry (ResilientRead) resubmits them, and the
     * engine's healthy-ring routing lands the resubmission elsewhere:
     * the requeue path.  Cancelling only AFTER the drain succeeded
     * keeps the return value exact (a timed-out restart requeued
     * nothing) and the abort contract honest. */
    while (pre_parked-- > 0 && !rc->park_q.empty()) {
      Req *r = rc->park_q.front();
      rc->park_q.pop_front();
      r->parked = false;
      r->status = -ECANCELED;
      r->done_len = 0;
      rc->complete_locked(r);
      cancelled++;
    }
  }
  /* 3) rebuild the uring outside the ring mutex (the nop handshake
   * below needs the reaper to keep consuming).  The quiesced ring has
   * nothing in flight, so the teardown/re-init races nobody. */
  if (rc->use_uring) {
    {
      std::lock_guard<std::mutex> g(rc->mu);
      rc->ring.submit(kOpNop, -1, 0, nullptr, 0, kShutdownUserData);
    }
    if (rc->reaper.joinable()) rc->reaper.join();
    /* In-place rebuild under the ring mutex (strom_get_pool_info reads
     * ring.fixed_bufs under it): the quiesced ring has no in-flight
     * I/O and the reaper is joined, so nobody else touches the Uring. */
    std::lock_guard<std::mutex> g(rc->mu);
    rc->ring.teardown();
    rc->ring.unsubmitted.store(0, std::memory_order_relaxed);
    rc->ring.fixed_bufs = false;
    if (rc->ring.init(e->queue_depth * 2)) {
      rc->ring.try_register(e->pool, e->buf_cap,
                            e->n_buffers * e->n_rings);
      if (e->reg_files_enabled) {
        /* Fresh uring, fresh registrations: re-register the CURRENT
         * slot table (files_mu is a leaf lock under the ring mutex) so
         * files opened before the restart keep their fixed slots.
         * init() preserved want_sqpoll, so SQPOLL re-arms identically.
         */
        std::lock_guard<std::mutex> fg(e->files_mu);
        rc->ring.try_register_files(e->reg_fds.data(),
                                    STROM_REG_FILE_SLOTS);
      }
      rc->reaper = std::thread([rc] { rc->reaper_loop(); });
    } else {
      /* Rebuild refused (fd limits, kernel state): fall back to the
       * worker-pool backend so the ring keeps serving. */
      rc->use_uring = false;
      rc->sq_poll = e->sqpoll_enabled;
      rc->sq_idle_ns = (uint64_t)e->sqpoll_idle_ms * 1000000ull;
      uint32_t nw = e->queue_depth < 32 ? e->queue_depth : 32;
      for (uint32_t i = 0; i < nw; i++)
        rc->workers.emplace_back([rc] { rc->worker_loop(); });
    }
  }
  {
    /* 4) reopen: disarm stall injection (the restart heals the wedge —
     * that is its contract) and dispatch requests parked during the
     * window, in order. */
    std::lock_guard<std::mutex> g(rc->mu);
    rc->stalled = false;
    rc->stall_seen = 0;
    rc->restarting = false;
    while (!rc->park_q.empty()) {
      Req *r = rc->park_q.front();
      rc->park_q.pop_front();
      r->parked = false;
      rc->dispatch_locked(r);
    }
    rc->rg_restarts.fetch_add(1, std::memory_order_relaxed);
  }
  return cancelled;
}

int64_t strom_read_buffered(strom_engine *e, int fh, uint64_t offset,
                            uint64_t len, void *dst) {
  FileEnt fe;
  if (!e->file_copy(fh, &fe)) return -EBADF;
  uint64_t got = 0;
  while (got < len) {
    ssize_t n = pread(fe.fd_buffered, (uint8_t *)dst + got, len - got,
                      (off_t)(offset + got));
    if (n < 0) {
      if (errno == EINTR) continue;
      return -errno;
    }
    if (n == 0) break; /* EOF */
    got += (uint64_t)n;
  }
  /* Honest accounting: this payload rode the page cache and was host-
   * copied into the caller's buffer — fallback + bounce, exactly like
   * the engine's own buffered rescue path. */
  e->st_fallback.fetch_add(got, std::memory_order_relaxed);
  e->st_bounce.fetch_add(got, std::memory_order_relaxed);
  return (int64_t)got;
}

int strom_check_file(const char *path, strom_file_info *out) {
  memset(out, 0, sizeof(*out));
  struct stat st;
  if (stat(path, &st) != 0) return -errno;
  out->size = (int64_t)st.st_size;
  out->block_size = (int32_t)(st.st_blksize ? st.st_blksize : 4096);
  struct statfs sfs;
  if (statfs(path, &sfs) == 0) out->fs_magic = (uint64_t)sfs.f_type;
  int fd = open(path, O_RDONLY | O_DIRECT);
  if (fd >= 0) {
    /* Probe an actual aligned read — some filesystems accept the open but
     * fail reads (the reference probes fs type + blockdev instead,
     * SURVEY.md §3.3). */
    void *p = nullptr;
    if (posix_memalign(&p, 4096, 4096) == 0) {
      ssize_t n = pread(fd, p, 4096, 0);
      out->supports_direct = (n >= 0) ? 1 : 0;
      free(p);
    }
    close(fd);
  }
  return 0;
}

/* ---- backing-device topology (CHECK_FILE's blockdev half, §3.3) ---- */

static int sysfs_read_line(const char *path, char *buf, size_t n) {
  FILE *f = fopen(path, "r");
  if (!f) return -1;
  char *got = fgets(buf, (int)n, f);
  fclose(f);
  if (!got) return -1;
  buf[strcspn(buf, "\n")] = 0;
  return 0;
}

/* Resolve a sysfs block-device link (/sys/dev/block/M:m or
 * /sys/class/block/<name>) to the WHOLE-DISK name: partitions step up to
 * their parent directory, mirroring the reference's partition->blockdev
 * walk in the CHECK_FILE handler (SURVEY.md §2 "File eligibility"). */
static int whole_disk_name(const char *sys_link, char *name, size_t n) {
  char real[PATH_MAX];
  if (!realpath(sys_link, real)) return -1;
  char probe[PATH_MAX + 16];
  snprintf(probe, sizeof(probe), "%s/partition", real);
  if (access(probe, F_OK) == 0) {
    char *slash = strrchr(real, '/');
    if (!slash) return -1;
    *slash = '\0';
  }
  const char *base = strrchr(real, '/');
  if (!base || !base[1]) return -1;
  snprintf(name, n, "%s", base + 1);
  return 0;
}

static int name_is_nvme(const char *name) {
  return strncmp(name, "nvme", 4) == 0;
}

int strom_resolve_device(const char *path, strom_device_info *out) {
  memset(out, 0, sizeof(*out));
  out->raid_level = -1;
  out->rotational = -1;
  struct stat st;
  if (stat(path, &st) != 0) return -errno;
  char link[96];
  snprintf(link, sizeof(link), "/sys/dev/block/%u:%u",
           major(st.st_dev), minor(st.st_dev));
  if (whole_disk_name(link, out->device, sizeof(out->device)) != 0)
    return 0; /* overlay/tmpfs/network fs: no visible backing blockdev */

  char p[PATH_MAX];
  char buf[64];
  snprintf(p, sizeof(p), "/sys/block/%s/queue/rotational", out->device);
  if (sysfs_read_line(p, buf, sizeof(buf)) == 0)
    out->rotational = atoi(buf);
  out->is_nvme = name_is_nvme(out->device);

  snprintf(p, sizeof(p), "/sys/block/%s/md", out->device);
  if (access(p, F_OK) != 0) {
    out->nvme_backed = out->is_nvme;
    return 0;
  }
  /* md array: level + member walk (reference: "md-raid0 stripe
   * resolution", SURVEY.md §2/§3.1). */
  out->is_raid = 1;
  snprintf(p, sizeof(p), "/sys/block/%s/md/level", out->device);
  if (sysfs_read_line(p, buf, sizeof(buf)) == 0 &&
      strncmp(buf, "raid", 4) == 0)
    out->raid_level = atoi(buf + 4);
  snprintf(p, sizeof(p), "/sys/block/%s/slaves", out->device);
  DIR *d = opendir(p);
  int all_nvme = 1;
  if (d) {
    struct dirent *de;
    /* Scan EVERY member for the all-NVMe verdict; members[] records only
     * the first STROM_MAX_RAID_MEMBERS names — ordered by md SLOT, not
     * readdir order: raid0 chunk k lives on slot (k mod n), so stripe
     * attribution (strom_stripe_attr) is only meaningful against the
     * slot order.  /sys/block/mdX/md/dev-<name>/slot holds it; members
     * with no readable slot (spares, legacy sysfs) keep scan order
     * after the slotted ones. */
    int slots[STROM_MAX_RAID_MEMBERS];
    while ((de = readdir(d)) != nullptr) {
      if (de->d_name[0] == '.') continue;
      char slink[PATH_MAX];
      char mname[64];
      snprintf(slink, sizeof(slink), "/sys/class/block/%.200s", de->d_name);
      if (whole_disk_name(slink, mname, sizeof(mname)) != 0)
        snprintf(mname, sizeof(mname), "%.63s", de->d_name);
      if (out->n_members < STROM_MAX_RAID_MEMBERS) {
        int slot = INT32_MAX;  /* unknown slots sort last, stably */
        char sp[PATH_MAX];
        snprintf(sp, sizeof(sp), "/sys/block/%s/md/dev-%.200s/slot",
                 out->device, de->d_name);
        FILE *sf = fopen(sp, "r");
        if (sf) {
          if (fscanf(sf, "%d", &slot) != 1) slot = INT32_MAX;
          fclose(sf);
        }
        int i = out->n_members;
        while (i > 0 && slots[i - 1] > slot) {  /* insertion sort */
          slots[i] = slots[i - 1];
          memcpy(out->members[i], out->members[i - 1],
                 sizeof(out->members[0]));
          i--;
        }
        slots[i] = slot;
        memcpy(out->members[i], mname, sizeof(mname));
      }
      out->n_members++;
      if (!name_is_nvme(mname)) all_nvme = 0;
    }
    closedir(d);
  }
  out->nvme_backed =
      (out->raid_level == 0 && out->n_members > 0 && all_nvme) ? 1 : 0;
  return 0;
}

int strom_file_extents(const char *path, strom_extent *out, uint32_t max) {
  if (max == 0) return 0;
  int fd = open(path, O_RDONLY);
  if (fd < 0) return -errno;
  struct stat st;
  if (fstat(fd, &st) != 0) { int e = -errno; close(fd); return e; }
  if (st.st_size == 0) { close(fd); return 0; }

#ifdef FS_IOC_FIEMAP
  size_t sz = sizeof(struct fiemap) + (size_t)max * sizeof(struct fiemap_extent);
  struct fiemap *fm = (struct fiemap *)calloc(1, sz);
  if (!fm) { close(fd); return -ENOMEM; }
  /* Batched walk with an advancing window: a map that does not fit in
   * `max` entries is an error (-E2BIG), never a silent truncation — the
   * caller retries with a bigger buffer.  The reference's extent walk has
   * the same never-drop-the-tail property (it chunks the whole range,
   * SURVEY.md §3.1). */
  uint32_t count = 0;
  uint64_t start = 0;
  bool supported = true;
  int err = 0;
  while (true) {
    memset(fm, 0, sizeof(struct fiemap));
    fm->fm_start = start;
    fm->fm_length = (uint64_t)st.st_size - start;
    fm->fm_flags = FIEMAP_FLAG_SYNC;
    fm->fm_extent_count = max - count;
    if (ioctl(fd, FS_IOC_FIEMAP, fm) != 0) {
      if (errno == ENOTTY || errno == EOPNOTSUPP) {
        supported = false; /* fs has no FIEMAP: synthetic fallback below */
      } else {
        err = -errno;      /* real I/O error: propagate, do not mask */
      }
      break;
    }
    uint32_t n = fm->fm_mapped_extents;
    if (n == 0) break; /* sparse tail hole — map complete */
    if (n > max - count) n = max - count;
    bool last = false;
    for (uint32_t i = 0; i < n; i++) {
      out[count + i].logical = fm->fm_extents[i].fe_logical;
      out[count + i].physical = fm->fm_extents[i].fe_physical;
      out[count + i].length = fm->fm_extents[i].fe_length;
      out[count + i].flags = fm->fm_extents[i].fe_flags;
      out[count + i].pad = 0;
      if (fm->fm_extents[i].fe_flags & FIEMAP_EXTENT_LAST) last = true;
    }
    count += n;
    start = out[count - 1].logical + out[count - 1].length;
    if (last || start >= (uint64_t)st.st_size) break;
    if (count == max) { err = -E2BIG; break; } /* more extents than room */
  }
  free(fm);
  if (err != 0) { close(fd); return err; }
  if (supported) { close(fd); return (int)count; }
#endif
  /* No FIEMAP (tmpfs/overlay/proc): one synthetic whole-file extent. */
  out[0].logical = 0;
  out[0].physical = 0;
  out[0].length = (uint64_t)st.st_size;
  out[0].flags = STROM_EXTENT_SYNTHETIC;
  out[0].pad = 0;
  close(fd);
  return 1;
}

void strom_stripe_attr(uint64_t phys_off, uint64_t len, uint64_t chunk,
                       uint32_t n_members, uint64_t *out_bytes) {
  if (len == 0 || n_members == 0 || chunk == 0) return;
  if (n_members == 1) { out_bytes[0] += len; return; }
  const uint64_t period = chunk * (uint64_t)n_members;
  /* whole stripe periods cover every member equally */
  const uint64_t full = len / period;
  if (full) {
    for (uint32_t m = 0; m < n_members; m++) out_bytes[m] += full * chunk;
  }
  /* remainder: walk at most n_members+1 chunk fragments */
  uint64_t off = phys_off + full * period;
  uint64_t left = len % period;
  while (left) {
    const uint64_t in_chunk = chunk - (off % chunk);
    const uint64_t take = left < in_chunk ? left : in_chunk;
    out_bytes[(off / chunk) % n_members] += take;
    off += take;
    left -= take;
  }
}

void strom_get_pool_info(strom_engine *e, strom_pool_info *out) {
  /* Global pool + per-ring request maps; per-ring occupancy is
   * strom_get_ring_info. */
  uint32_t freeb = 0, infl = 0, def = 0;
  int fixed = e->rings.empty() ? 0 : 1;
  {
    std::lock_guard<std::mutex> g(e->pool_mu);
    freeb = (uint32_t)e->free_bufs.size();
    def = (uint32_t)e->defer_q.size();
  }
  for (auto &rcp : e->rings) {
    RingCtx *rc = rcp.get();
    std::lock_guard<std::mutex> g(rc->mu);
    infl += (uint32_t)rc->reqs.size();
    if (!rc->ring.fixed_bufs) fixed = 0;
  }
  out->n_buffers = e->n_buffers * e->n_rings;
  out->free_buffers = freeb;
  out->buf_bytes = e->buf_bytes;
  out->pool_bytes = (uint64_t)e->pool_sz;
  out->locked = e->locked ? 1 : 0;
  out->queue_depth = (int32_t)(e->queue_depth * e->n_rings);
  out->in_flight = infl;
  out->deferred = def;
  out->fixed_bufs = fixed;
  out->pad = 0;
  out->pool_base = (uint64_t)(uintptr_t)e->pool;
}

int strom_open(strom_engine *e, const char *path, int flags) {
  int writable = flags & STROM_OPEN_WRITABLE;
  int oflags = writable ? (O_RDWR | O_CREAT) : O_RDONLY;
  int fdb = open(path, oflags, 0644);
  if (fdb < 0) return -errno;
  int fdd = (flags & STROM_OPEN_NO_DIRECT)
                ? -1
                : open(path, oflags | O_DIRECT, 0644);
  /* fdd == -1 is fine: tmpfs/overlayfs — all I/O takes the fallback path. */
  struct stat st;
  if (fstat(fdb, &st) != 0) {
    int err = -errno;
    close(fdb);
    if (fdd >= 0) close(fdd);
    return err;
  }
  int fh;
  int slot_b = -1, slot_d = -1;
  {
    std::lock_guard<std::mutex> g(e->files_mu);
    fh = e->next_fh++;
    FileEnt fe;
    fe.fd_direct = fdd;
    fe.fd_buffered = fdb;
    fe.size = (int64_t)st.st_size;
    fe.writable = writable != 0;
    if (e->reg_files_enabled) {
      /* Dynamic slot table: point registered slots at the new fds so
       * hot submissions ride IOSQE_FIXED_FILE.  Table full / kernel
       * refusal leaves the slots -1 — raw-fd submission, never an
       * error.  Slots are claimed (and reg_fds filled) HERE under
       * files_mu; the per-ring syscalls run below under restart_mu. */
      fe.slot_buffered = slot_b = e->reg_alloc_slot(fdb);
      fe.slot_direct = slot_d = e->reg_alloc_slot(fdd);
    }
    e->files[fh] = fe;
  }
  if (slot_b >= 0 || slot_d >= 0) {
    /* restart_mu excludes hot restarts (the only writer of a ring's
     * uring fd), so the FILES_UPDATE syscalls can never race a
     * teardown/rebuild onto a recycled descriptor.  Either ordering
     * with a restart is consistent: reg_fds already carries the new
     * fds, so a racing rebuild re-registers the complete table. */
    std::lock_guard<std::mutex> rg(e->restart_mu);
    if (slot_b >= 0) e->reg_update_all((uint32_t)slot_b, fdb);
    if (slot_d >= 0) e->reg_update_all((uint32_t)slot_d, fdd);
  }
  return fh;
}

int strom_close(strom_engine *e, int fh) {
  int slot_b, slot_d, fdd, fdb;
  {
    std::lock_guard<std::mutex> g(e->files_mu);
    auto it = e->files.find(fh);
    if (it == e->files.end()) return -EBADF;
    slot_b = it->second.slot_buffered;
    slot_d = it->second.slot_direct;
    fdd = it->second.fd_direct;
    fdb = it->second.fd_buffered;
    /* Table entries go -1 FIRST (a restart's re-register must not
     * resurrect slots for fds about to close); the slots become
     * re-allocatable only after the rings were updated below. */
    e->reg_clear_slot(slot_b);
    e->reg_clear_slot(slot_d);
    e->files.erase(it);
  }
  if (slot_b >= 0 || slot_d >= 0) {
    bool drained = true;
    {
      std::lock_guard<std::mutex> rg(e->restart_mu);
      /* A published-but-unconsumed SQE resolves IOSQE_FIXED_FILE slots
       * at CONSUMPTION time (SQPOLL thread / later flush): drain every
       * ring's SQ first, so any straggler referencing these slots
       * still resolves to OUR fds (held open until below).  Only then
       * may the slots point elsewhere. */
      for (auto &rcp : e->rings) {
        RingCtx *rc = rcp.get();
        if (rc->use_uring && rc->ring.reg_files)
          drained = rc->ring.drain_sq() && drained;
      }
      if (slot_b >= 0) e->reg_update_all((uint32_t)slot_b, -1);
      if (slot_d >= 0) e->reg_update_all((uint32_t)slot_d, -1);
    }
    std::lock_guard<std::mutex> g(e->files_mu);
    if (drained) {
      e->reg_recycle_slot(slot_b);
      e->reg_recycle_slot(slot_d);
    }
    /* !drained: LEAK the slot ids — a slot that might still be named
     * by an un-consumed SQE must never be recycled to another file
     * (the table entry is already -1, so nothing NEW can use it; the
     * 128-slot budget degrades to raw-fd submission long before this
     * matters). */
  }
  /* fds close LAST: every registered slot that pointed at them is
   * cleared, so no straggler submission can land in a recycled
   * descriptor. */
  if (fdd >= 0) close(fdd);
  close(fdb);
  return 0;
}

int64_t strom_file_size(strom_engine *e, int fh) {
  std::lock_guard<std::mutex> g(e->files_mu);
  auto it = e->files.find(fh);
  return it == e->files.end() ? -EBADF : it->second.size;
}

int strom_file_is_direct(strom_engine *e, int fh) {
  std::lock_guard<std::mutex> g(e->files_mu);
  auto it = e->files.find(fh);
  return it == e->files.end() ? -EBADF : (it->second.fd_direct >= 0 ? 1 : 0);
}

int strom_file_ident(strom_engine *e, int fh, uint64_t out[4]) {
  int fd;
  {
    std::lock_guard<std::mutex> g(e->files_mu);
    auto it = e->files.find(fh);
    if (it == e->files.end()) return -EBADF;
    fd = it->second.fd_buffered;
  }
  struct stat st;
  if (fstat(fd, &st) != 0) return -errno;
  out[0] = (uint64_t)st.st_dev;
  out[1] = (uint64_t)st.st_ino;
  out[2] = (uint64_t)st.st_mtim.tv_sec * 1000000000ull +
           (uint64_t)st.st_mtim.tv_nsec;
  out[3] = (uint64_t)st.st_size;
  return 0;
}

/* Shared submit body: validate + size-refresh under files_mu (leaf
 * lock), residency-probe with NO lock held, then stage on the chosen
 * ring under that ring's mutex only. */
static int64_t submit_read_on(strom_engine *e, RingCtx *rcx, int fh,
                              uint64_t offset, uint64_t len) {
  if (len > e->buf_bytes) return -EINVAL;
  if (e->stopping.load(std::memory_order_acquire)) return -ECANCELED;
  bool direct = false;
  int pfd = -1;
  int64_t fsize = 0;
  {
    std::lock_guard<std::mutex> g(e->files_mu);
    auto it = e->files.find(fh);
    if (it == e->files.end()) return -EBADF;
    /* Refresh size: the file may have grown since open. */
    struct stat st;
    if (fstat(it->second.fd_buffered, &st) == 0)
      it->second.size = (int64_t)st.st_size;
    fsize = it->second.size;
    direct = it->second.fd_direct >= 0;
    /* Residency-aware planning: if every page of the span is already in
     * the page cache, a buffered read is a memcpy and the NVMe
     * round-trip pure waste — CHOOSE the cache deliberately.  Counted
     * as bytes_resident (+fallback+bounce: the host copy is real),
     * never as a retry/rescue.  The probe's mmap/mincore syscalls run
     * OUTSIDE any lock (on a dup so a concurrent close cannot retarget
     * the fd) — a cold streaming submitter must not serialize behind
     * them. */
    if (direct && e->probe_residency && offset < (uint64_t)fsize)
      pfd = dup(it->second.fd_buffered);
  }
  bool resident = false;
  if (pfd >= 0) {
    uint64_t avail = std::min<uint64_t>(len, (uint64_t)fsize - offset);
    resident = span_resident(pfd, offset, avail);
    close(pfd);
  }
  Req *r = new Req();
  r->offset = offset;
  r->len = len;
  r->a_off = align_down(offset, e->alignment);
  r->a_len = align_up(offset + len, e->alignment) - r->a_off;
  r->direct = direct && !resident;
  r->planned_resident = direct && resident;
  r->fh = fh;
  r->rc = rcx;
  std::lock_guard<std::mutex> g(rcx->mu);
  if (e->stopping.load(std::memory_order_acquire)) {
    delete r;
    return -ECANCELED;
  }
  r->id = e->alloc_id(rcx);
  r->t_submit = now_ns();
  rcx->reqs[r->id] = r;
  e->st_sub.fetch_add(1, std::memory_order_relaxed);
  rcx->rg_sub.fetch_add(1, std::memory_order_relaxed);
  int got = e->acquire_or_defer(r);  /* never blocks the submitter */
  if (got > 0) {
    rcx->dispatch_locked(r);
  } else if (got < 0) {
    r->status = -ECANCELED;          /* raced engine destroy */
    rcx->complete_locked(r);
  }
  return r->id;
}

int64_t strom_submit_read(strom_engine *e, int fh, uint64_t offset,
                          uint64_t len) {
  return submit_read_on(e, e->pick_ring(), fh, offset, len);
}

int64_t strom_submit_read_ring(strom_engine *e, uint32_t ring, int fh,
                               uint64_t offset, uint64_t len) {
  if (ring >= e->n_rings) return -EINVAL;
  return submit_read_on(e, e->rings[ring].get(), fh, offset, len);
}

/* Shared vectored-submit body: the whole batch stages on ONE ring. */
static int submit_readv_on(strom_engine *e, RingCtx *rcx,
                           const strom_rd_ext *exts, uint32_t n,
                           int64_t *out_ids) {
  if (n == 0) return 0;
  for (uint32_t i = 0; i < n; i++)
    if (exts[i].length > e->buf_bytes) return -EINVAL;
  if (e->stopping.load(std::memory_order_acquire)) return -ECANCELED;
  /* Residency probes run with NO lock held (same discipline as
   * submit_read_on: mmap/mincore must not serialize other submitters;
   * dup so a concurrent close cannot retarget the fd). */
  struct Probe { uint32_t i; int pfd; uint64_t off, avail; };
  std::vector<Probe> probes;
  std::vector<char> resident(n, 0);
  std::vector<char> direct(n, 0);
  {
    /* Atomic validation + one size refresh per distinct fh under
     * files_mu: on any bad extent NOTHING has been submitted. */
    std::lock_guard<std::mutex> g(e->files_mu);
    std::unordered_map<int, int64_t> sized;
    for (uint32_t i = 0; i < n; i++) {
      auto it = e->files.find(exts[i].fh);
      if (it == e->files.end()) {
        for (auto &p : probes) close(p.pfd);
        return -EBADF;
      }
      if (sized.find(exts[i].fh) == sized.end()) {
        struct stat st;
        if (fstat(it->second.fd_buffered, &st) == 0)
          it->second.size = (int64_t)st.st_size;
        sized.emplace(exts[i].fh, it->second.size);
      }
      direct[i] = it->second.fd_direct >= 0 ? 1 : 0;
      if (direct[i] && e->probe_residency &&
          exts[i].offset < (uint64_t)it->second.size) {
        uint64_t avail = std::min<uint64_t>(
            exts[i].length, (uint64_t)it->second.size - exts[i].offset);
        int pfd = dup(it->second.fd_buffered);
        if (pfd >= 0)
          probes.push_back(Probe{i, pfd, exts[i].offset, avail});
      }
    }
  }
  for (auto &p : probes) {
    resident[p.i] = span_resident(p.pfd, p.off, p.avail) ? 1 : 0;
    close(p.pfd);
  }
  /* Stage every extent — uring SQEs publish WITHOUT ringing the
   * doorbell — then pay one io_uring_enter for the whole batch.
   * Only extents dispatched inline share that doorbell; extents that
   * defer on pool pressure ring their own when a buffer frees, so
   * they must not be credited as saved syscalls. */
  uint32_t inline_n = 0;
  std::lock_guard<std::mutex> g(rcx->mu);
  if (e->stopping.load(std::memory_order_acquire)) return -ECANCELED;
  for (uint32_t i = 0; i < n; i++) {
    const strom_rd_ext &x = exts[i];
    Req *r = new Req();
    r->offset = x.offset;
    r->len = x.length;
    r->a_off = align_down(x.offset, e->alignment);
    r->a_len = align_up(x.offset + x.length, e->alignment) - r->a_off;
    r->direct = direct[i] && !resident[i];
    r->planned_resident = direct[i] != 0 && resident[i] != 0;
    r->id = e->alloc_id(rcx);
    r->fh = x.fh;
    r->rc = rcx;
    r->t_submit = now_ns();
    rcx->reqs[r->id] = r;
    e->st_sub.fetch_add(1, std::memory_order_relaxed);
    rcx->rg_sub.fetch_add(1, std::memory_order_relaxed);
    out_ids[i] = r->id;
    int got = e->acquire_or_defer(r);  /* never blocks: deferred
                                          requests dispatch on the next
                                          buffer free */
    if (got > 0) {
      rcx->dispatch_locked(r, /*flush_now=*/false);
      inline_n++;
    } else if (got < 0) {
      r->status = -ECANCELED;          /* raced engine destroy */
      rcx->complete_locked(r);
    }
  }
  e->st_batches.fetch_add(1, std::memory_order_relaxed);
  if (inline_n > 1)
    e->st_sysc_saved.fetch_add(inline_n - 1, std::memory_order_relaxed);
  if (rcx->use_uring) rcx->ring.flush();
  return 0;
}

int strom_submit_readv(strom_engine *e, const strom_rd_ext *exts,
                       uint32_t n, int64_t *out_ids) {
  return submit_readv_on(e, e->pick_ring(), exts, n, out_ids);
}

int strom_submit_readv_ring(strom_engine *e, uint32_t ring,
                            const strom_rd_ext *exts, uint32_t n,
                            int64_t *out_ids) {
  if (ring >= e->n_rings) return -EINVAL;
  return submit_readv_on(e, e->rings[ring].get(), exts, n, out_ids);
}

static int fill_completion(Req *r, strom_completion *out) {
  if (out) {
    out->data = r->is_write ? nullptr
                            : r->buf + (r->offset - r->a_off);
    out->len = r->done_len;
    out->status = r->status;
    out->was_fallback = r->was_fallback ? 1 : 0;
    out->submit_ns = r->t_submit;
    out->complete_ns = r->t_complete;
  }
  return r->status;
}

int strom_wait(strom_engine *e, int64_t req_id, strom_completion *out) {
  RingCtx *rc = e->ring_of_id(req_id);
  if (!rc) return -ENOENT;
  std::unique_lock<std::mutex> lk(rc->mu);
  auto it = rc->reqs.find(req_id);
  if (it == rc->reqs.end()) return -ENOENT;
  Req *r = it->second;
  rc->cv_done.wait(lk, [&] { return r->state == ReqState::kDone; });
  return fill_completion(r, out);
}

int strom_wait_timeout(strom_engine *e, int64_t req_id,
                       strom_completion *out, uint64_t timeout_ns) {
  /* Hang DETECTION (SURVEY.md §5 failure detection): a stalled device
   * or wedged backend turns into -ETIMEDOUT the caller can act on
   * (diagnose, rescue, abort) instead of blocking forever.  The
   * request stays live — a timed-out wait may be retried. */
  RingCtx *rc = e->ring_of_id(req_id);
  if (!rc) return -ENOENT;
  std::unique_lock<std::mutex> lk(rc->mu);
  auto it = rc->reqs.find(req_id);
  if (it == rc->reqs.end()) return -ENOENT;
  Req *r = it->second;
  bool done = rc->cv_done.wait_for(
      lk, std::chrono::nanoseconds(timeout_ns),
      [&] { return r->state == ReqState::kDone; });
  if (!done) return -ETIMEDOUT;
  return fill_completion(r, out);
}

int strom_release(strom_engine *e, int64_t req_id) {
  RingCtx *rc = e->ring_of_id(req_id);
  if (!rc) return -ENOENT;
  int buf_idx = -1;
  {
    std::lock_guard<std::mutex> g(rc->mu);
    auto it = rc->reqs.find(req_id);
    if (it == rc->reqs.end()) return -ENOENT;
    Req *r = it->second;
    if (r->state != ReqState::kDone) return -EBUSY;
    buf_idx = r->buf_idx;
    rc->reqs.erase(it);
    delete r;
  }
  /* Buffer handoff runs with no ring lock held: the recipient may live
   * on a DIFFERENT ring (global deferral FIFO), and two ring mutexes
   * must never nest. */
  if (buf_idx >= 0) e->recycle_buffer(buf_idx);
  return 0;
}

static int64_t submit_write_on(strom_engine *e, RingCtx *rcx, int fh,
                               uint64_t offset, const void *src,
                               uint64_t len) {
  if (e->stopping.load(std::memory_order_acquire)) return -ECANCELED;
  bool conformant;
  {
    std::lock_guard<std::mutex> g(e->files_mu);
    auto it = e->files.find(fh);
    if (it == e->files.end()) return -EBADF;
    if (!it->second.writable) return -EACCES;
    conformant = ((uint64_t)src % e->alignment == 0) &&
                 (offset % e->alignment == 0) &&
                 (len % e->alignment == 0) && it->second.fd_direct >= 0;
  }
  if (!conformant && len > e->buf_bytes) return -EINVAL;
  Req *r = new Req();
  r->is_write = true;
  r->fh = fh;
  r->rc = rcx;
  r->offset = offset;
  r->len = len;
  r->direct = conformant;
  r->wsrc = src; /* wrapper keeps src alive until wait() */
  std::lock_guard<std::mutex> g(rcx->mu);
  if (e->stopping.load(std::memory_order_acquire)) {
    delete r;
    return -ECANCELED;
  }
  r->id = e->alloc_id(rcx);
  r->t_submit = now_ns();
  rcx->reqs[r->id] = r;
  e->st_sub.fetch_add(1, std::memory_order_relaxed);
  rcx->rg_sub.fetch_add(1, std::memory_order_relaxed);
  if (conformant) {
    /* zero-copy: O_DIRECT DMA straight from caller memory, no buffer */
    r->buf_idx = -1;
    rcx->dispatch_locked(r);
    return r->id;
  }
  int got = e->acquire_or_defer(r);  /* else staged when a buffer frees */
  if (got > 0) {
    memcpy(r->buf, src, len); /* the one counted bounce */
    e->st_bounce.fetch_add(len, std::memory_order_relaxed);
    rcx->dispatch_locked(r);
  } else if (got < 0) {
    r->status = -ECANCELED;          /* raced engine destroy */
    rcx->complete_locked(r);
  }
  return r->id;
}

int64_t strom_submit_write(strom_engine *e, int fh, uint64_t offset,
                           const void *src, uint64_t len) {
  return submit_write_on(e, e->pick_ring(), fh, offset, src, len);
}

int64_t strom_submit_write_ring(strom_engine *e, uint32_t ring, int fh,
                                uint64_t offset, const void *src,
                                uint64_t len) {
  if (ring >= e->n_rings) return -EINVAL;
  return submit_write_on(e, e->rings[ring].get(), fh, offset, src, len);
}

void strom_get_stats(strom_engine *e, strom_stats_blk *out) {
  out->bytes_direct = e->st_direct.load(std::memory_order_relaxed);
  out->bytes_fallback = e->st_fallback.load(std::memory_order_relaxed);
  out->bounce_bytes = e->st_bounce.load(std::memory_order_relaxed);
  out->bytes_written_direct = e->st_written.load(std::memory_order_relaxed);
  /* completed is read BEFORE submitted, acquire paired with the release
   * increment in complete_locked: any completion the observer sees
   * implies visibility of its submission, so completed <= submitted
   * always holds in the snapshot. */
  out->requests_completed = e->st_comp.load(std::memory_order_acquire);
  out->requests_submitted = e->st_sub.load(std::memory_order_relaxed);
  out->requests_failed = e->st_fail.load(std::memory_order_relaxed);
  out->retries = e->st_retry.load(std::memory_order_relaxed);
  out->bytes_resident = e->st_resident.load(std::memory_order_relaxed);
  out->submit_batches = e->st_batches.load(std::memory_order_relaxed);
  out->submit_syscalls_saved =
      e->st_sysc_saved.load(std::memory_order_relaxed);
  out->submit_enters = e->st_enters.load(std::memory_order_relaxed);
}

void strom_drain_stats(strom_engine *e, strom_stats_blk *out) {
  out->bytes_direct = e->st_direct.exchange(0, std::memory_order_acq_rel);
  out->bytes_fallback = e->st_fallback.exchange(0, std::memory_order_acq_rel);
  out->bounce_bytes = e->st_bounce.exchange(0, std::memory_order_acq_rel);
  out->bytes_written_direct =
      e->st_written.exchange(0, std::memory_order_acq_rel);
  out->requests_submitted = e->st_sub.exchange(0, std::memory_order_acq_rel);
  out->requests_completed = e->st_comp.exchange(0, std::memory_order_acq_rel);
  out->requests_failed = e->st_fail.exchange(0, std::memory_order_acq_rel);
  out->retries = e->st_retry.exchange(0, std::memory_order_acq_rel);
  out->bytes_resident = e->st_resident.exchange(0, std::memory_order_acq_rel);
  out->submit_batches = e->st_batches.exchange(0, std::memory_order_acq_rel);
  out->submit_syscalls_saved =
      e->st_sysc_saved.exchange(0, std::memory_order_acq_rel);
  out->submit_enters = e->st_enters.exchange(0, std::memory_order_acq_rel);
}

void strom_reset_stats(strom_engine *e) {
  e->st_direct = 0; e->st_fallback = 0; e->st_bounce = 0; e->st_written = 0;
  e->st_sub = 0; e->st_comp = 0; e->st_fail = 0; e->st_retry = 0;
  e->st_resident = 0; e->st_batches = 0; e->st_sysc_saved = 0;
  e->st_enters = 0;
  for (int i = 0; i < STROM_LAT_BUCKETS; i++) {
    e->lat_read[i].store(0, std::memory_order_relaxed);
    e->lat_write[i].store(0, std::memory_order_relaxed);
  }
}

int strom_backend_is_uring(strom_engine *e) {
  return (!e->rings.empty() && e->rings[0]->use_uring) ? 1 : 0;
}

void strom_get_latency(strom_engine *e,
                       uint64_t out_read[STROM_LAT_BUCKETS],
                       uint64_t out_write[STROM_LAT_BUCKETS]) {
  for (int i = 0; i < STROM_LAT_BUCKETS; i++) {
    if (out_read)
      out_read[i] = e->lat_read[i].load(std::memory_order_relaxed);
    if (out_write)
      out_write[i] = e->lat_write[i].load(std::memory_order_relaxed);
  }
}

/* ---------------- crc32c (Castagnoli) ---------------- */

static uint32_t g_crc_tbl[8][256];
static bool g_crc_init = false;

static void crc_init_tables() {
  for (uint32_t i = 0; i < 256; i++) {
    uint32_t c = i;
    for (int k = 0; k < 8; k++) c = (c & 1) ? (c >> 1) ^ 0x82F63B78u : c >> 1;
    g_crc_tbl[0][i] = c;
  }
  for (uint32_t i = 0; i < 256; i++) {
    uint32_t c = g_crc_tbl[0][i];
    for (int t = 1; t < 8; t++) {
      c = g_crc_tbl[0][c & 0xFF] ^ (c >> 8);
      g_crc_tbl[t][i] = c;
    }
  }
  g_crc_init = true;
}

#if defined(__x86_64__)
#include <cpuid.h>
static bool has_sse42() {
  unsigned a, b, c, d;
  if (!__get_cpuid(1, &a, &b, &c, &d)) return false;
  return (c & (1u << 20)) != 0;
}
__attribute__((target("sse4.2")))
static uint32_t crc32c_hw(const uint8_t *p, uint64_t n, uint32_t c) {
  while (n && ((uintptr_t)p & 7)) { c = __builtin_ia32_crc32qi(c, *p++); n--; }
  uint64_t c64 = c;
  while (n >= 8) {
    c64 = __builtin_ia32_crc32di(c64, *(const uint64_t *)p);
    p += 8;
    n -= 8;
  }
  c = (uint32_t)c64;
  while (n--) c = __builtin_ia32_crc32qi(c, *p++);
  return c;
}
#endif

uint32_t strom_crc32c(const void *data, uint64_t len, uint32_t crc) {
  if (!g_crc_init) crc_init_tables();
  const uint8_t *p = (const uint8_t *)data;
  uint32_t c = ~crc;
#if defined(__x86_64__)
  static int hw = -1;
  if (hw < 0) hw = has_sse42() ? 1 : 0;
  if (hw) return ~crc32c_hw(p, len, c);
#endif
  while (len && ((uintptr_t)p & 7)) {
    c = g_crc_tbl[0][(c ^ *p++) & 0xFF] ^ (c >> 8);
    len--;
  }
  while (len >= 8) {
    uint64_t w;
    memcpy(&w, p, 8);
    w ^= c;
    c = g_crc_tbl[7][w & 0xFF] ^ g_crc_tbl[6][(w >> 8) & 0xFF] ^
        g_crc_tbl[5][(w >> 16) & 0xFF] ^ g_crc_tbl[4][(w >> 24) & 0xFF] ^
        g_crc_tbl[3][(w >> 32) & 0xFF] ^ g_crc_tbl[2][(w >> 40) & 0xFF] ^
        g_crc_tbl[1][(w >> 48) & 0xFF] ^ g_crc_tbl[0][(w >> 56) & 0xFF];
    p += 8;
    len -= 8;
  }
  while (len--) c = g_crc_tbl[0][(c ^ *p++) & 0xFF] ^ (c >> 8);
  return ~c;
}

/* ------------- pinned host-DRAM cache arena (io/hostcache.py) ------------- */

void *strom_hostcache_arena_create(uint64_t bytes, int lock_pages,
                                   int32_t *locked_out) {
  if (locked_out) *locked_out = 0;
  if (bytes == 0) {
    errno = EINVAL;
    return NULL;
  }
  void *base = mmap(NULL, bytes, PROT_READ | PROT_WRITE,
                    MAP_PRIVATE | MAP_ANONYMOUS | MAP_POPULATE, -1, 0);
  if (base == MAP_FAILED) {
    /* MAP_POPULATE can fail on exotic kernels; the arena is still
     * usable unfaulted — retry plain before giving up. */
    base = mmap(NULL, bytes, PROT_READ | PROT_WRITE,
                MAP_PRIVATE | MAP_ANONYMOUS, -1, 0);
    if (base == MAP_FAILED) return NULL;
  }
  if (lock_pages && mlock(base, bytes) == 0 && locked_out)
    *locked_out = 1; /* best-effort: RLIMIT_MEMLOCK refusal is not fatal */
  return base;
}

void strom_hostcache_arena_destroy(void *base, uint64_t bytes) {
  if (base && bytes) munmap(base, bytes); /* munlock implied */
}

void strom_hostcache_copy(void *dst, const void *src, uint64_t bytes) {
  if (dst && src && bytes) memcpy(dst, src, bytes);
}

}  /* extern "C" */

/* ------------------------- tar shard indexer ------------------------- */

/* Octal field (NUL/space padded), with GNU base-256 (first byte 0x80)
 * for sizes beyond 8 GiB.  Returns -1 on garbage. */
static int64_t tar_num(const uint8_t *f, size_t n) {
  if (f[0] & 0x80) {               /* base-256 */
    uint64_t v = f[0] & 0x7F;
    for (size_t i = 1; i < n; i++) v = (v << 8) | f[i];
    return (int64_t)v;
  }
  int64_t v = 0;
  size_t i = 0;
  while (i < n && (f[i] == ' ')) i++;
  for (; i < n && f[i] >= '0' && f[i] <= '7'; i++)
    v = v * 8 + (f[i] - '0');
  return v;
}

static int tar_checksum_ok(const uint8_t *h) {
  int64_t want = tar_num(h + 148, 8);
  if (want < 0) return 0;
  uint64_t sum = 0;
  for (int i = 0; i < 512; i++)
    sum += (i >= 148 && i < 156) ? ' ' : h[i];
  return (int64_t)sum == want;
}

namespace {
struct TarBuf {               /* growable packed result */
  uint8_t *p = nullptr;
  uint64_t len = 0, cap = 0;
  bool push(uint64_t off, uint64_t size, const char *name, uint32_t nl) {
    uint64_t need = len + 8 + 8 + 4 + nl;
    if (need > cap) {
      uint64_t ncap = cap ? cap * 2 : 4096;
      while (ncap < need) ncap *= 2;
      uint8_t *np = (uint8_t *)realloc(p, ncap);
      if (!np) return false;
      p = np; cap = ncap;
    }
    memcpy(p + len, &off, 8);
    memcpy(p + len + 8, &size, 8);
    memcpy(p + len + 16, &nl, 4);
    memcpy(p + len + 20, name, nl);
    len = need;
    return true;
  }
};

/* pax "len key=value\n" records: extract path= / size= overrides.
 * Returns 0; -1 on a malformed record (caller: -EBADMSG — never a
 * silent partial parse: kvlen underflow here was an OOB heap read
 * before 2026-07-31); -2 when a path exceeds path_cap — a VALID
 * archive this walker just doesn't support (caller: -ENOTSUP, so the
 * Python side can fall back to tarfile). */
static int pax_parse(const uint8_t *data, size_t n, char *path_out,
                     size_t path_cap, int *have_path,
                     int64_t *size_out, int *have_size) {
  size_t i = 0;
  while (i < n) {
    size_t reclen = 0, j = i;
    while (j < n && data[j] >= '0' && data[j] <= '9') {
      reclen = reclen * 10 + (data[j++] - '0');
      if (reclen > n) return -1;       /* bounds the accumulation too */
    }
    if (j >= n || data[j] != ' ' || reclen == 0 || i + reclen > n)
      return -1;
    size_t hdr = (j + 1) - i;          /* digits + space */
    if (reclen < hdr + 1 || data[i + reclen - 1] != '\n') return -1;
    const uint8_t *kv = data + j + 1;
    size_t kvlen = reclen - hdr - 1;   /* minus trailing \n */
    if (kvlen > 5 && memcmp(kv, "path=", 5) == 0) {
      size_t pl = kvlen - 5;
      if (pl >= path_cap) return -2;   /* valid archive, name beyond our
                                        * cap: unsupported, not corrupt */
      memcpy(path_out, kv + 5, pl);
      path_out[pl] = 0;
      *have_path = 1;
    } else if (kvlen > 5 && memcmp(kv, "size=", 5) == 0) {
      int64_t v = 0;
      for (size_t k = 5; k < kvlen; k++)
        if (kv[k] >= '0' && kv[k] <= '9') v = v * 10 + (kv[k] - '0');
      *size_out = v;
      *have_size = 1;
    }
    i += reclen;
  }
  return 0;
}
}  /* namespace */

extern "C" int64_t strom_tar_index(const char *path, uint8_t **out,
                                   uint64_t *out_bytes) {
  *out = nullptr;
  *out_bytes = 0;
  /* O_DIRECT first: the header walk faults its windows through the
   * page cache otherwise, and a resident member span makes the
   * engine's submit-time mincore planner deliberately choose the
   * buffered path for every member read that follows — one index pass
   * silently demoting the O_DIRECT pipeline to memcpy (a cold wds_raw
   * epoch measured 100% fallback+bounce from exactly this).  Direct
   * windows bypass the cache entirely — no pollution AND no eviction
   * of pages that were legitimately warm before the walk. */
  int direct = 1;
  int fd = open(path, O_RDONLY | O_CLOEXEC | O_DIRECT);
  if (fd < 0) { direct = 0; fd = open(path, O_RDONLY | O_CLOEXEC); }
  if (fd < 0) return -errno;
  struct stat st;
  if (fstat(fd, &st) != 0) { int e = errno; close(fd); return -e; }
  TarBuf buf;
  /* name overrides pending for the NEXT header (GNU 'L' / pax 'x') */
  char longname[4097];
  int have_long = 0;
  int64_t pax_size = -1;
  int have_pax_size = 0;
  int64_t count = 0;
  uint64_t off = 0;
  uint8_t h[512];
  int zeros = 0;
  /* windowed header reads: one 4 MiB pread serves ~1k headers of a
   * small-member shard instead of one syscall each (the syscall loop
   * measured 4.5x tarfile; the window ~3x further).  Large members
   * simply land the next header outside the window and trigger a
   * refill at the new offset — a seek, not a full-file read. */
  enum { WIN = 4 << 20 };
  uint8_t *win = nullptr;
  if (posix_memalign((void **)&win, 4096, WIN) != 0 || !win) {
    close(fd); return -ENOMEM;
  }
  uint64_t win_off = 0, win_len = 0;
  /* Every archive byte the walk touches — headers AND 'L'/'x'/'g'
   * payloads — goes through this one window fill, so the direct-mode
   * alignment rules hold everywhere (a stray unaligned pread on the
   * O_DIRECT fd EINVALs on ext4, which would silently demote every
   * pax-format archive to the polluting Python fallback). */
  int ferr = 0;
  auto fill = [&](uint64_t o, uint64_t need) -> uint8_t * {
    if (need == 0) return win;
    if (need > (uint64_t)WIN) { ferr = -ENOTSUP; return nullptr; }
    if (o < win_off || o + need > win_off + win_len) {
      uint64_t roff = direct ? (o & ~(uint64_t)4095) : o;
      ssize_t got = pread(fd, win, WIN, (off_t)roff);
      if (got < 0 && direct) {
        /* fs accepted O_DIRECT open but refuses the read: reopen
         * buffered once and continue the walk.  Keep the ORIGINAL
         * read errno if the reopen fails — a media error must not
         * masquerade as an fd-limit problem. */
        int rerr = errno;
        int bfd = open(path, O_RDONLY | O_CLOEXEC);
        if (bfd >= 0) {
          close(fd); fd = bfd; direct = 0; roff = o;
          got = pread(fd, win, WIN, (off_t)roff);
        } else {
          errno = rerr;
        }
      }
      if (got < 0) { ferr = -errno; return nullptr; }
      if ((uint64_t)got < (o - roff) + need) {
        ferr = -EBADMSG;              /* genuinely short: truncated */
        return nullptr;
      }
      win_off = roff;
      win_len = (uint64_t)got;
    }
    return win + (o - win_off);
  };
  while ((int64_t)(off + 512) <= st.st_size) {
    uint8_t *hp = fill(off, 512);
    if (!hp) { close(fd); free(win); free(buf.p); return ferr; }
    memcpy(h, hp, 512);
    int allz = 1;
    for (int i = 0; i < 512 && allz; i++) allz = (h[i] == 0);
    if (allz) {
      if (++zeros == 2) break;       /* end-of-archive marker */
      off += 512;
      continue;
    }
    zeros = 0;
    if (!tar_checksum_ok(h)) { close(fd); free(win); free(buf.p);
                           return -EBADMSG; }
    int64_t size = tar_num(h + 124, 12);
    if (size < 0) { close(fd); free(win); free(buf.p);
                return -EBADMSG; }
    uint8_t type = h[156];
    uint64_t data = off + 512;
    uint64_t adv = 512 + (((uint64_t)size + 511) & ~511ULL);
    if (type == 'L' || type == 'x' || type == 'g') {
      /* 'L'/'x' override the NEXT real header; 'g' sets GLOBAL pax
       * defaults.  Error split (advisor round-3): -EBADMSG only for
       * genuine corruption; a VALID archive using a feature this
       * walker doesn't implement returns -ENOTSUP so the caller can
       * fall back to tarfile instead of failing where it used to
       * succeed. */
      size_t n = (size_t)size;
      if (n > sizeof(longname) * 4) { close(fd); free(win);
                                free(buf.p); return -ENOTSUP; }
      uint8_t *tmp = (uint8_t *)malloc(n + 1);
      if (!tmp) { close(fd); free(win); free(buf.p); return -ENOMEM; }
      uint8_t *pp = fill(data, n);
      if (!pp) {
        free(tmp); close(fd); free(win); free(buf.p);
        return ferr;
      }
      memcpy(tmp, pp, n);
      tmp[n] = 0;
      int bad = 0;                   /* -EBADMSG: corrupt */
      int unsup = 0;                 /* -ENOTSUP: valid, unimplemented */
      if (type == 'L') {
        size_t nl = strnlen((char *)tmp, n);
        if (nl >= sizeof(longname)) unsup = 1;  /* loud, never a silent
                                                   truncated member key */
        else {
          memcpy(longname, tmp, nl);
          longname[nl] = 0;
          have_long = 1;
        }
      } else if (type == 'g') {
        /* Parse the global payload into throwaway slots purely to
         * CLASSIFY it: global path=/size= overrides would change every
         * later member's identity — indexing with raw header fields
         * would be silently wrong, so that's unsupported; globals that
         * carry neither (comment=, mtime=, ...) are safely ignored. */
        char gpath[4097];
        int g_have_path = 0, g_have_size = 0;
        int64_t g_size = -1;
        int rc = pax_parse(tmp, n, gpath, sizeof(gpath),
                           &g_have_path, &g_size, &g_have_size);
        if (rc == -2) unsup = 1;
        else if (rc != 0) bad = 1;
        else if (g_have_path || g_have_size) unsup = 1;
      } else {
        int rc = pax_parse(tmp, n, longname, sizeof(longname),
                           &have_long, &pax_size, &have_pax_size);
        if (rc == -2) unsup = 1;
        else if (rc != 0) bad = 1;
      }
      free(tmp);
      if (bad || unsup) { close(fd); free(win); free(buf.p);
                          return bad ? -EBADMSG : -ENOTSUP; }
      off += adv;
      continue;
    }
    if (have_pax_size) {            /* pax size overrides the header's */
      size = pax_size;
      adv = 512 + (((uint64_t)size + 511) & ~511ULL);
      have_pax_size = 0;
      pax_size = -1;
    }
    if (type == '0' || type == 0) {  /* regular file */
      /* the member's data must actually exist — a truncated archive
       * yields a loud error, never a partial index */
      if ((int64_t)(data + (uint64_t)size) > st.st_size) {
        close(fd); free(win); free(buf.p); return -EBADMSG;
      }
      char name[4097];
      if (have_long) {
        size_t nl = strnlen(longname, sizeof(longname) - 1);
        memcpy(name, longname, nl);
        name[nl] = 0;
      } else {
        /* ustar: prefix (155) "/" name (100) */
        char nm[101], pf[156];
        memcpy(nm, h, 100); nm[100] = 0;
        memcpy(pf, h + 345, 155); pf[155] = 0;
        int has_ustar = (memcmp(h + 257, "ustar", 5) == 0);
        if (has_ustar && pf[0]) snprintf(name, sizeof(name),
                                         "%s/%s", pf, nm);
        else snprintf(name, sizeof(name), "%s", nm);
      }
      uint32_t nl = (uint32_t)strnlen(name, sizeof(name) - 1);
      if (!buf.push(data, (uint64_t)size, name, nl)) {
        close(fd); free(win); free(buf.p); return -ENOMEM;
      }
      count++;
    }
    have_long = 0;
    off += adv;
  }
  close(fd);
  free(win);
  *out = buf.p;
  *out_bytes = buf.len;
  return count;
}

extern "C" void strom_tar_index_free(uint8_t *buf) { free(buf); }

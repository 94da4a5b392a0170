/* stress_test — concurrency stress harness for the strom-io engine.
 *
 * SURVEY.md §5 "Race detection": the reference has nothing beyond kernel
 * lockdep; the promised TPU-build upgrade is TSAN + stress tests for the
 * C++ engine.  This binary hammers one engine from many threads at once:
 *
 *   - reader threads: random-offset reads, each verified against the
 *     deterministic content pattern (catches buffer-recycling races);
 *   - a writer thread appending to a scratch file;
 *   - burst-writer threads keeping several submit_writes in flight at
 *     once (the checkpoint/offload pipelined-write pattern), each with
 *     length verification of the completion;
 *   - a mixed thread alternating submit_write with submit_readv batches
 *     on the same ring (write path racing the vectored read path);
 *   - an observer thread polling stats/pool-info/latency (lock-free
 *     counter reads racing the hot path);
 *   - an open/close churn thread (file-table mutation under I/O).
 *
 * Build plain (`make stress`) for the functional stress run, or with
 * ThreadSanitizer (`make tsan`) to turn every data race into a
 * report.  Exit code 0 = no mismatches, no request failures; TSAN adds
 * its own non-zero exit on findings.
 *
 * Usage: stress_test [iters-per-thread] [n-readers] [tmpdir]
 */

#include "strom_io.h"

#include <atomic>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>
#include <thread>
#include <vector>

#include <unistd.h>

namespace {

constexpr uint64_t kFileBytes = 8ull << 20;
constexpr uint64_t kMaxRead = 256 * 1024;

/* Deterministic byte pattern: content is a pure function of offset, so a
 * read of any range verifies without a reference buffer. */
inline uint8_t pat(uint64_t off) {
  return (uint8_t)((off * 2654435761ull) >> 7);
}

std::atomic<uint64_t> g_errors{0};

void fail(const char *what) {
  fprintf(stderr, "stress: FAIL %s\n", what);
  g_errors.fetch_add(1);
}

/* xorshift — per-thread deterministic RNG, no libc rand() races. */
struct Rng {
  uint64_t s;
  explicit Rng(uint64_t seed) : s(seed * 0x9E3779B97F4A7C15ull + 1) {}
  uint64_t next() {
    s ^= s << 13;
    s ^= s >> 7;
    s ^= s << 17;
    return s;
  }
};

void reader_thread(strom_engine *eng, int fh, int iters, int seed) {
  Rng rng(seed);
  for (int i = 0; i < iters; i++) {
    uint64_t off = rng.next() % (kFileBytes - 1);
    uint64_t len = 1 + rng.next() % kMaxRead;
    if (off + len > kFileBytes) len = kFileBytes - off;
    int64_t id = strom_submit_read(eng, fh, off, len);
    if (id < 0) { fail("submit_read"); continue; }
    strom_completion c;
    if (strom_wait(eng, id, &c) != 0 || c.status != 0) {
      fail("read status");
      strom_release(eng, id);
      continue;
    }
    if (c.len != len) fail("short read");
    for (uint64_t k = 0; k < c.len; k += 997)  /* sparse verify: cheap */
      if (c.data[k] != pat(off + k)) { fail("payload mismatch"); break; }
    strom_release(eng, id);
  }
}

/* Vectored submitter: batches of random extents through
 * strom_submit_readv, racing the scalar readers for buffers and the
 * deferred-flush doorbell against concurrent dispatches. */
void readv_thread(strom_engine *eng, int fh, int iters, int seed) {
  Rng rng(seed * 7919 + 3);
  for (int i = 0; i < iters; i++) {
    const uint32_t n = 1 + (uint32_t)(rng.next() % 8);
    strom_rd_ext exts[8];
    for (uint32_t j = 0; j < n; j++) {
      uint64_t off = rng.next() % (kFileBytes - 1);
      uint64_t len = 1 + rng.next() % (kMaxRead / 4);
      if (off + len > kFileBytes) len = kFileBytes - off;
      exts[j] = strom_rd_ext{fh, 0, off, len};
    }
    int64_t ids[8];
    if (strom_submit_readv(eng, exts, n, ids) != 0) {
      fail("submit_readv");
      continue;
    }
    for (uint32_t j = 0; j < n; j++) {
      strom_completion c;
      if (strom_wait(eng, ids[j], &c) != 0 || c.status != 0) {
        fail("readv status");
        strom_release(eng, ids[j]);
        continue;
      }
      if (c.len != exts[j].length) fail("readv short");
      for (uint64_t k = 0; k < c.len; k += 997)
        if (c.data[k] != pat(exts[j].offset + k)) {
          fail("readv payload mismatch");
          break;
        }
      strom_release(eng, ids[j]);
    }
  }
}

/* Restart-tolerant vectored reader: the hot-restart phase's consumer.
 * A completion cancelled by a ring restart (-ECANCELED) is RESUBMITTED
 * round-robin (the Python supervision layer's requeue path, here in
 * miniature) and must then verify — any other error, short read, or
 * payload mismatch is a hard failure.  Counts requeues so the phase
 * can assert the restart actually cancelled something. */
void restart_reader_thread(strom_engine *eng, int fh, int iters, int seed,
                           std::atomic<uint64_t> *requeued) {
  Rng rng(seed * 104729 + 11);
  for (int i = 0; i < iters; i++) {
    const uint32_t n = 1 + (uint32_t)(rng.next() % 4);
    strom_rd_ext exts[4];
    for (uint32_t j = 0; j < n; j++) {
      uint64_t off = rng.next() % (kFileBytes - 1);
      uint64_t len = 1 + rng.next() % (kMaxRead / 8);
      if (off + len > kFileBytes) len = kFileBytes - off;
      exts[j] = strom_rd_ext{fh, 0, off, len};
    }
    int64_t ids[4];
    uint32_t ring = (uint32_t)(rng.next() % 2); /* rings 0-1; 1 restarts */
    if (strom_submit_readv_ring(eng, ring, exts, n, ids) != 0) {
      fail("restart submit_readv_ring");
      continue;
    }
    for (uint32_t j = 0; j < n; j++) {
      int64_t id = ids[j];
      for (int attempt = 0; attempt < 64; attempt++) {
        strom_completion c;
        int rc = strom_wait(eng, id, &c);
        if (rc == -ECANCELED) {
          /* requeue: release the cancelled request, resubmit the same
           * range (round-robin — lands on whichever ring is healthy) */
          strom_release(eng, id);
          requeued->fetch_add(1);
          id = strom_submit_read(eng, fh, exts[j].offset, exts[j].length);
          if (id < 0) { fail("requeue resubmit"); break; }
          continue;
        }
        if (rc != 0 || c.status != 0) {
          fail("restart-phase read status");
          strom_release(eng, id);
          break;
        }
        if (c.len != exts[j].length) fail("restart-phase short read");
        for (uint64_t k = 0; k < c.len; k += 997)
          if (c.data[k] != pat(exts[j].offset + k)) {
            fail("restart-phase payload mismatch");
            break;
          }
        strom_release(eng, id);
        break;
      }
    }
  }
}

void writer_thread(strom_engine *eng, const std::string &dir, int iters) {
  std::string path = dir + "/stress_w.bin";
  int fh = strom_open(eng, path.c_str(), STROM_OPEN_WRITABLE);
  if (fh < 0) { fail("open writable"); return; }
  std::vector<uint8_t> buf(64 * 1024);
  Rng rng(0xAB07);
  for (int i = 0; i < iters; i++) {
    uint64_t off = (rng.next() % 64) * buf.size();
    for (size_t k = 0; k < buf.size(); k++) buf[k] = pat(off + k);
    int64_t id = strom_submit_write(eng, fh, off, buf.data(), buf.size());
    if (id < 0) { fail("submit_write"); continue; }
    strom_completion c;
    if (strom_wait(eng, id, &c) != 0) fail("write wait");
    strom_release(eng, id);
  }
  strom_close(eng, fh);
}

/* Restart-tolerant writer: like writer_thread, but a -ECANCELED
 * completion (the request parked on a ring being hot-restarted) is the
 * REQUEUE contract, not damage — resubmit the same range, exactly as
 * ResilientWrite's retry does.  Used by phases that restart rings
 * under live write traffic. */
void restart_writer_thread(strom_engine *eng, const std::string &dir,
                           int iters, int seed) {
  std::string path = dir + "/stress_rw" + std::to_string(seed) + ".bin";
  int fh = strom_open(eng, path.c_str(), STROM_OPEN_WRITABLE);
  if (fh < 0) { fail("open restart writable"); return; }
  std::vector<uint8_t> buf(64 * 1024);
  Rng rng(seed * 6700417 + 3);
  for (int i = 0; i < iters; i++) {
    uint64_t off = (rng.next() % 64) * buf.size();
    for (size_t k = 0; k < buf.size(); k++) buf[k] = pat(off + k);
    int64_t id = strom_submit_write(eng, fh, off, buf.data(), buf.size());
    if (id < 0) { fail("restart submit_write"); continue; }
    for (int attempt = 0; attempt < 64; attempt++) {
      strom_completion c;
      int rc = strom_wait(eng, id, &c);
      int st = rc == 0 ? c.status : rc;
      strom_release(eng, id);
      if (st == -ECANCELED) {
        id = strom_submit_write(eng, fh, off, buf.data(), buf.size());
        if (id < 0) { fail("restart write resubmit"); break; }
        continue;
      }
      if (st != 0) fail("restart write status");
      break;
    }
  }
  strom_close(eng, fh);
  unlink(path.c_str());
}

/* Pipelined writer: keeps kBurst submit_writes in flight on one fh
 * (each source buffer owned until its wait returns), racing the readv
 * batches and scalar readers for ring slots and pool buffers — the
 * write half of the checkpoint/offload submit pattern, on ONE ring.
 * Each thread owns a disjoint file so content verification stays a
 * pure function of (seed, offset). */
void writer_burst_thread(strom_engine *eng, const std::string &dir,
                         int iters, int seed) {
  constexpr int kBurst = 6;
  std::string path = dir + "/stress_wb" + std::to_string(seed) + ".bin";
  int fh = strom_open(eng, path.c_str(), STROM_OPEN_WRITABLE);
  if (fh < 0) { fail("open burst writable"); return; }
  struct Slot { int64_t id; uint64_t len; std::vector<uint8_t> buf; };
  std::vector<Slot> inflight;
  Rng rng(seed * 131071 + 17);
  auto drain_one = [&]() {
    Slot s = std::move(inflight.front());
    inflight.erase(inflight.begin());
    strom_completion c;
    if (strom_wait(eng, s.id, &c) != 0 || c.status != 0)
      fail("burst write status");
    else if (c.len != s.len)
      fail("burst short write");
    strom_release(eng, s.id);
  };
  for (int i = 0; i < iters; i++) {
    uint64_t off = (rng.next() % 128) * 4096;
    uint64_t len = 1 + rng.next() % (64 * 1024);
    Slot s;
    s.len = len;
    s.buf.resize(len);
    for (uint64_t k = 0; k < len; k++) s.buf[k] = pat(off + k);
    s.id = strom_submit_write(eng, fh, off, s.buf.data(), len);
    if (s.id < 0) { fail("burst submit_write"); continue; }
    inflight.push_back(std::move(s));
    while ((int)inflight.size() >= kBurst) drain_one();
  }
  while (!inflight.empty()) drain_one();
  strom_close(eng, fh);
  unlink(path.c_str());
}

/* Mixed submitter: alternates a write and a readv batch on the SAME
 * ring iteration — the exact interleaving a checkpoint save overlapping
 * a loader epoch produces (submit_write and submit_readv racing for the
 * SQ and the deferred-dispatch queue). */
void mixed_rw_thread(strom_engine *eng, int read_fh, const std::string &dir,
                     int iters, int seed) {
  std::string path = dir + "/stress_mx" + std::to_string(seed) + ".bin";
  int wfh = strom_open(eng, path.c_str(), STROM_OPEN_WRITABLE);
  if (wfh < 0) { fail("open mixed writable"); return; }
  Rng rng(seed * 524287 + 29);
  std::vector<uint8_t> wbuf(16 * 1024);
  for (int i = 0; i < iters; i++) {
    uint64_t woff = (rng.next() % 32) * wbuf.size();
    for (size_t k = 0; k < wbuf.size(); k++) wbuf[k] = pat(woff + k);
    int64_t wid = strom_submit_write(eng, wfh, woff, wbuf.data(),
                                     wbuf.size());
    strom_rd_ext exts[4];
    const uint32_t n = 1 + (uint32_t)(rng.next() % 4);
    for (uint32_t j = 0; j < n; j++) {
      uint64_t off = rng.next() % (kFileBytes - 1);
      uint64_t len = 1 + rng.next() % (kMaxRead / 8);
      if (off + len > kFileBytes) len = kFileBytes - off;
      exts[j] = strom_rd_ext{read_fh, 0, off, len};
    }
    int64_t ids[4];
    if (strom_submit_readv(eng, exts, n, ids) != 0) {
      fail("mixed submit_readv");
    } else {
      for (uint32_t j = 0; j < n; j++) {
        strom_completion c;
        if (strom_wait(eng, ids[j], &c) != 0 || c.status != 0)
          fail("mixed readv status");
        else
          for (uint64_t k = 0; k < c.len; k += 997)
            if (c.data[k] != pat(exts[j].offset + k)) {
              fail("mixed readv payload");
              break;
            }
        strom_release(eng, ids[j]);
      }
    }
    if (wid < 0) {
      fail("mixed submit_write");
    } else {
      strom_completion c;
      if (strom_wait(eng, wid, &c) != 0 || c.status != 0)
        fail("mixed write status");
      strom_release(eng, wid);
    }
  }
  strom_close(eng, wfh);
  unlink(path.c_str());
}

void observer_thread(strom_engine *eng, std::atomic<bool> *stop) {
  uint64_t rd[STROM_LAT_BUCKETS], wr[STROM_LAT_BUCKETS];
  while (!stop->load(std::memory_order_acquire)) {
    strom_stats_blk st;
    strom_get_stats(eng, &st);
    if (st.requests_completed > st.requests_submitted)
      fail("completed > submitted");
    strom_pool_info pi;
    strom_get_pool_info(eng, &pi);
    if (pi.free_buffers > pi.n_buffers) fail("pool accounting");
    strom_get_latency(eng, rd, wr);
    /* per-ring counters race the hot path lock-free: completed may
     * never exceed submitted within one ring's snapshot */
    int nr = strom_ring_count(eng);
    for (int r = 0; r < nr; r++) {
      strom_ring_info ri;
      if (strom_get_ring_info(eng, (uint32_t)r, &ri) != 0) {
        fail("ring_info rc");
        continue;
      }
      if (ri.completed > ri.submitted) fail("ring completed > submitted");
      if (ri.free_buffers > ri.n_buffers) fail("ring pool accounting");
    }
    usleep(500);
  }
}

/* Multi-ring mixed-class reader: models the QoS scheduler's dispatch —
 * each thread plays one latency class pinned round-robin over a ring
 * subset (decode -> ring 0, bulk -> the rest), batches via
 * strom_submit_readv_ring racing scalar strom_submit_read_ring
 * stragglers on the SAME rings from sibling threads.  Payload verified
 * against the offset pattern: a cross-ring buffer-recycling bug shows
 * up as a mismatch, a routing bug as -EINVAL/-ENOENT failures. */
void ring_class_thread(strom_engine *eng, int fh, int iters, int seed,
                       uint32_t ring_lo, uint32_t ring_hi) {
  Rng rng(seed * 2654435761ull + 11);
  const uint32_t span = ring_hi - ring_lo + 1;
  for (int i = 0; i < iters; i++) {
    uint32_t ring = ring_lo + (uint32_t)(rng.next() % span);
    if ((i & 3) == 3) {          /* scalar straggler on the same ring */
      uint64_t off = rng.next() % (kFileBytes - 1);
      uint64_t len = 1 + rng.next() % (kMaxRead / 8);
      if (off + len > kFileBytes) len = kFileBytes - off;
      int64_t id = strom_submit_read_ring(eng, ring, fh, off, len);
      if (id < 0) { fail("submit_read_ring"); continue; }
      strom_completion c;
      if (strom_wait(eng, id, &c) != 0 || c.status != 0)
        fail("ring read status");
      else
        for (uint64_t k = 0; k < c.len; k += 997)
          if (c.data[k] != pat(off + k)) { fail("ring payload"); break; }
      strom_release(eng, id);
      continue;
    }
    const uint32_t n = 1 + (uint32_t)(rng.next() % 6);
    strom_rd_ext exts[6];
    for (uint32_t j = 0; j < n; j++) {
      uint64_t off = rng.next() % (kFileBytes - 1);
      uint64_t len = 1 + rng.next() % (kMaxRead / 4);
      if (off + len > kFileBytes) len = kFileBytes - off;
      exts[j] = strom_rd_ext{fh, 0, off, len};
    }
    int64_t ids[6];
    if (strom_submit_readv_ring(eng, ring, exts, n, ids) != 0) {
      fail("submit_readv_ring");
      continue;
    }
    for (uint32_t j = 0; j < n; j++) {
      strom_completion c;
      if (strom_wait(eng, ids[j], &c) != 0 || c.status != 0)
        fail("ring readv status");
      else {
        if (c.len != exts[j].length) fail("ring readv short");
        for (uint64_t k = 0; k < c.len; k += 997)
          if (c.data[k] != pat(exts[j].offset + k)) {
            fail("ring readv payload");
            break;
          }
      }
      strom_release(eng, ids[j]);
    }
  }
}

void churn_thread(strom_engine *eng, const std::string &path, int iters) {
  for (int i = 0; i < iters; i++) {
    int fh = strom_open(eng, path.c_str(), 0);
    if (fh < 0) { fail("churn open"); continue; }
    int64_t id = strom_submit_read(eng, fh, (uint64_t)i * 4096 % kFileBytes,
                                   4096);
    if (id >= 0) {
      strom_wait(eng, id, nullptr);
      strom_release(eng, id);
    }
    strom_close(eng, fh);
  }
}

}  // namespace

int main(int argc, char **argv) {
  int iters = argc > 1 ? atoi(argv[1]) : 300;
  int n_readers = argc > 2 ? atoi(argv[2]) : 6;
  std::string dir = argc > 3 ? argv[3] : "/tmp";

  std::string path = dir + "/stress_r.bin";
  FILE *f = fopen(path.c_str(), "wb");
  if (!f) { perror("fopen"); return 2; }
  std::vector<uint8_t> chunk(1 << 20);
  for (uint64_t off = 0; off < kFileBytes; off += chunk.size()) {
    for (size_t k = 0; k < chunk.size(); k++) chunk[k] = pat(off + k);
    fwrite(chunk.data(), 1, chunk.size(), f);
  }
  fclose(f);

  for (int use_uring = 1; use_uring >= 0; use_uring--) {
    strom_engine *eng =
        strom_engine_create(16, 8, kMaxRead + 8192, 4096, use_uring, 1);
    if (!eng) { perror("engine_create"); return 2; }
    int fh = strom_open(eng, path.c_str(), 0);
    if (fh < 0) { fprintf(stderr, "open failed\n"); return 2; }

    std::atomic<bool> stop{false};
    std::vector<std::thread> ts;
    for (int r = 0; r < n_readers; r++)
      ts.emplace_back(reader_thread, eng, fh, iters, r + 1);
    for (int r = 0; r < 2; r++)
      ts.emplace_back(readv_thread, eng, fh, iters / 2 + 1, r + 1);
    ts.emplace_back(writer_thread, eng, dir, iters / 2 + 1);
    for (int r = 0; r < 2; r++)
      ts.emplace_back(writer_burst_thread, eng, dir, iters / 2 + 1, r + 1);
    ts.emplace_back(mixed_rw_thread, eng, fh, dir, iters / 2 + 1, 1);
    ts.emplace_back(churn_thread, eng, path, iters / 2 + 1);
    std::thread obs(observer_thread, eng, &stop);
    for (auto &t : ts) t.join();
    stop.store(true, std::memory_order_release);
    obs.join();

    strom_stats_blk st;
    strom_get_stats(eng, &st);
    fprintf(stderr,
            "stress[%s]: submitted=%llu completed=%llu failed=%llu "
            "errors=%llu\n",
            use_uring ? "io_uring" : "threadpool",
            (unsigned long long)st.requests_submitted,
            (unsigned long long)st.requests_completed,
            (unsigned long long)st.requests_failed,
            (unsigned long long)g_errors.load());
    if (st.requests_failed != 0) fail("requests_failed != 0");
    strom_close(eng, fh);
    strom_engine_destroy(eng);
  }

  /* Multi-ring phase: 4 rings, mixed-class reader threads pinned the
   * way the QoS scheduler pins them (one decode-class thread owning
   * ring 0, bulk threads spread over rings 1-3), racing the writer and
   * churn paths that route round-robin across ALL rings — the
   * cross-ring file-table and pool-slice interactions TSAN must bless. */
  for (int use_uring = 1; use_uring >= 0; use_uring--) {
    strom_engine *eng = strom_engine_create_rings(
        4, 4, 4, kMaxRead + 8192, 4096, use_uring, 1);
    if (!eng) { perror("engine_create_rings"); return 2; }
    if (strom_ring_count(eng) != 4) fail("ring_count");
    /* ring routing validation is loud, not silent */
    if (strom_submit_read_ring(eng, 9, 1, 0, 4096) != -EINVAL)
      fail("bad ring index not rejected");
    int fh = strom_open(eng, path.c_str(), 0);
    if (fh < 0) { fprintf(stderr, "open failed\n"); return 2; }

    std::atomic<bool> stop{false};
    std::vector<std::thread> ts;
    ts.emplace_back(ring_class_thread, eng, fh, iters, 101, 0u, 0u);
    for (int r = 0; r < n_readers; r++)
      ts.emplace_back(ring_class_thread, eng, fh, iters, 200 + r, 1u, 3u);
    ts.emplace_back(writer_thread, eng, dir, iters / 2 + 1);
    ts.emplace_back(mixed_rw_thread, eng, fh, dir, iters / 2 + 1, 9);
    ts.emplace_back(churn_thread, eng, path, iters / 2 + 1);
    std::thread obs(observer_thread, eng, &stop);
    for (auto &t : ts) t.join();
    stop.store(true, std::memory_order_release);
    obs.join();

    strom_stats_blk st;
    strom_get_stats(eng, &st);
    uint64_t ring_sub = 0, ring_comp = 0;
    for (int r = 0; r < 4; r++) {
      strom_ring_info ri;
      strom_get_ring_info(eng, (uint32_t)r, &ri);
      ring_sub += ri.submitted;
      ring_comp += ri.completed;
      if (ri.inflight_io != 0) fail("ring inflight after drain");
    }
    if (ring_sub != st.requests_submitted) fail("ring submit accounting");
    if (ring_comp != st.requests_completed) fail("ring comp accounting");
    fprintf(stderr,
            "stress[rings=4,%s]: submitted=%llu completed=%llu "
            "failed=%llu errors=%llu\n",
            use_uring ? "io_uring" : "threadpool",
            (unsigned long long)st.requests_submitted,
            (unsigned long long)st.requests_completed,
            (unsigned long long)st.requests_failed,
            (unsigned long long)g_errors.load());
    if (st.requests_failed != 0) fail("requests_failed != 0");
    strom_close(eng, fh);
    strom_engine_destroy(eng);
  }
  /* Hot-restart phase: 2 rings; readers pin batches to both rings while
   * the main thread repeatedly wedges ring 1 (stall injection parks its
   * dispatches), hot-restarts it (parked requests cancel -ECANCELED and
   * the readers requeue them), and lets traffic resume on the rebuilt
   * ring.  TSAN must bless the restart's drain/rebuild racing live
   * submitters, waiters, and the stat observer; functionally every read
   * must end verified — cancellation is a requeue, never a loss. */
  for (int use_uring = 1; use_uring >= 0; use_uring--) {
    strom_engine *eng = strom_engine_create_rings(
        2, 4, 8, kMaxRead + 8192, 4096, use_uring, 1);
    if (!eng) { perror("engine_create_rings(restart)"); return 2; }
    if (strom_ring_restart(eng, 9, 1000000ull) != -EINVAL)
      fail("bad restart ring index not rejected");
    if (strom_set_ring_stall(eng, 9, 1) != -EINVAL)
      fail("bad stall ring index not rejected");
    int fh = strom_open(eng, path.c_str(), 0);
    if (fh < 0) { fprintf(stderr, "open failed\n"); return 2; }

    std::atomic<bool> stop{false};
    std::atomic<uint64_t> requeued{0};
    std::vector<std::thread> ts;
    for (int r = 0; r < 3; r++)
      ts.emplace_back(restart_reader_thread, eng, fh, iters, 300 + r,
                      &requeued);
    ts.emplace_back(churn_thread, eng, path, iters / 2 + 1);
    std::thread obs(observer_thread, eng, &stop);
    std::thread killer([&] {
      int restarts = 0;
      while (!stop.load(std::memory_order_acquire)) {
        strom_set_ring_stall(eng, 1, 1);
        usleep(3000);               /* let dispatches park */
        int64_t rc = strom_ring_restart(eng, 1, 500000000ull);
        if (rc < 0 && rc != -EBUSY) fail("ring_restart");
        restarts++;
        usleep(2000);               /* healthy window: traffic drains */
      }
      if (restarts < 1) fail("killer never restarted");
    });
    for (auto &t : ts) t.join();
    stop.store(true, std::memory_order_release);
    killer.join();
    obs.join();

    strom_ring_info ri;
    if (strom_get_ring_info(eng, 1, &ri) != 0) fail("ring_info(1)");
    if (ri.restarts < 1) fail("restart counter never moved");
    if (ri.parked != 0) fail("parked requests survived the phase");
    fprintf(stderr,
            "stress[restart,%s]: restarts=%llu requeued=%llu "
            "failed_comps=%llu errors=%llu\n",
            use_uring ? "io_uring" : "threadpool",
            (unsigned long long)ri.restarts,
            (unsigned long long)requeued.load(),
            (unsigned long long)ri.failed,
            (unsigned long long)g_errors.load());
    strom_stats_blk st;
    strom_get_stats(eng, &st);
    if (st.requests_failed != 0) fail("restart phase requests_failed != 0");
    if (ri.failed != 0) fail("cancels counted as ring failures");
    strom_close(eng, fh);
    strom_engine_destroy(eng);
  }
  /* Zero-copy submission phase (PR 12): SQPOLL + registered files +
   * an arena-prealloc'd staging pool, hammered by mixed read / readv /
   * write threads with a mid-run hot restart of ring 1.  The doorbell
   * elision, the slot-table updates racing open/close churn, the
   * restart's re-registration, AND the caller-owned pool must all be
   * TSAN-clean — and functionally every read still verifies. */
  setenv("STROM_SQPOLL", "1", 1);
  setenv("STROM_SQPOLL_IDLE_MS", "20", 1);
  setenv("STROM_REG_FILES", "1", 1);
  for (int use_uring = 1; use_uring >= 0; use_uring--) {
    uint64_t pool_bytes =
        strom_engine_pool_bytes(2, 8, kMaxRead + 8192, 4096);
    if (pool_bytes == 0) { fail("engine_pool_bytes"); break; }
    void *arena = strom_arena_create(pool_bytes);
    if (!arena) { perror("arena_create"); return 2; }
    strom_arena_lock(arena, pool_bytes);   /* best effort */
    strom_engine *eng = strom_engine_create_prealloc(
        2, 4, 8, kMaxRead + 8192, 4096, use_uring, 1,
        arena, pool_bytes);
    if (!eng) { perror("engine_create_prealloc"); return 2; }
    int fh = strom_open(eng, path.c_str(), 0);
    if (fh < 0) { fprintf(stderr, "open failed\n"); return 2; }

    std::atomic<bool> stop{false};
    std::atomic<uint64_t> requeued{0};
    std::vector<std::thread> ts;
    for (int r = 0; r < n_readers; r++)
      ts.emplace_back(restart_reader_thread, eng, fh, iters, 400 + r,
                      &requeued);
    /* write traffic must be restart-tolerant here: the mid-run stall
     * parks round-robin writes on ring 1 and the restart cancels them
     * for requeue — plain writer_thread would read that as damage */
    for (int r = 0; r < 2; r++)
      ts.emplace_back(restart_writer_thread, eng, dir, iters / 2 + 1,
                      50 + r);
    ts.emplace_back(churn_thread, eng, path, iters / 2 + 1);
    std::thread obs(observer_thread, eng, &stop);
    std::thread killer([&] {
      /* one mid-run restart cycle: the rebuilt uring must re-register
       * buffers + files and re-arm SQPOLL (checked below) */
      usleep(5000);
      strom_set_ring_stall(eng, 1, 1);
      usleep(3000);
      /* -EBUSY: the churn thread's open/close holds the restart lock
       * for its slot-table update.  The restart is what disarms the
       * stall, so one refused attempt must not be the last: the parked
       * requests would wait for ever. */
      int64_t rc = -EBUSY;
      for (int i = 0; i < 1000 && rc == -EBUSY; i++) {
        rc = strom_ring_restart(eng, 1, 500000000ull);
        if (rc == -EBUSY) usleep(1000);
      }
      if (rc < 0) {
        fail("sqpoll-phase ring_restart");
        strom_set_ring_stall(eng, 1, 0);   /* let the phase end */
      }
    });
    for (auto &t : ts) t.join();
    stop.store(true, std::memory_order_release);
    killer.join();
    obs.join();

    strom_ring_info ri;
    if (strom_get_ring_info(eng, 1, &ri) != 0) fail("ring_info(1)");
    /* The kernel may legitimately refuse IORING_SETUP_SQPOLL
     * (privileges pre-5.13, old kernels): the engine's documented
     * soft-fallback is a plain ring.  Only a backend that ACCEPTED the
     * mode must keep it across the restart — the worker-pool analogue
     * always does. */
    bool sq_active = ri.sqpoll == 1;
    if (!ri.backend_uring && !sq_active)
      fail("worker-pool sqpoll analogue not active after restart");
    if (!sq_active)
      fprintf(stderr, "stress[sqpoll]: note: kernel refused SQPOLL, "
                      "phase ran on the plain ring\n");
    if (ri.backend_uring && !ri.reg_files)
      fprintf(stderr, "stress[sqpoll]: note: reg_files soft-failed\n");
    strom_pool_info pi;
    strom_get_pool_info(eng, &pi);
    if (pi.pool_base != (uint64_t)(uintptr_t)arena)
      fail("prealloc pool base mismatch");
    strom_stats_blk st;
    strom_get_stats(eng, &st);
    fprintf(stderr,
            "stress[sqpoll+regfiles+arena,%s]: submitted=%llu "
            "enters=%llu elided=%llu requeued=%llu failed=%llu "
            "errors=%llu\n",
            use_uring ? "io_uring" : "threadpool",
            (unsigned long long)st.requests_submitted,
            (unsigned long long)st.submit_enters,
            (unsigned long long)st.submit_syscalls_saved,
            (unsigned long long)requeued.load(),
            (unsigned long long)st.requests_failed,
            (unsigned long long)g_errors.load());
    if (st.requests_failed != 0) fail("sqpoll phase requests_failed != 0");
    if (sq_active && st.submit_syscalls_saved == 0)
      fail("sqpoll phase elided no doorbells");
    strom_close(eng, fh);
    strom_engine_destroy(eng);
    strom_arena_destroy(arena, pool_bytes);
  }
  unsetenv("STROM_SQPOLL");
  unsetenv("STROM_SQPOLL_IDLE_MS");
  unsetenv("STROM_REG_FILES");
  unlink(path.c_str());
  unlink((dir + "/stress_w.bin").c_str());
  return g_errors.load() == 0 ? 0 : 1;
}

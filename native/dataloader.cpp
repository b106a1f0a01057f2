// Native data-loading runtime for burst-attn-tpu.
//
// The reference is an op library that delegates training IO to its host
// framework (BMTrain / CPM-Live integration, reference README.md:36-38);
// this framework carries its own trainer (models/train.py), so it carries
// its own native loader: a memory-mapped token-shard reader with background
// prefetch threads and a bounded buffer queue, exposed through a plain C ABI
// (consumed from Python via ctypes — burst_attn_tpu/data/loader.py).
//
// Design notes (TPU-first):
//   * The hot path hands the host a ready [batch, seq_len+1] int32 buffer;
//     the Python side slices inputs/targets and `jax.device_put`s them while
//     the workers are already filling the next window — host IO overlaps
//     device compute the same way the ring overlaps comm with the tile.
//   * Deterministic, seedable shuffling via a stateless mix of
//     (seed, epoch, index) — every data-parallel rank can reconstruct any
//     step's batch without coordination, which is what checkpoint/resume
//     needs (utils/checkpoint.py restores the step counter; the loader is
//     repositioned with dl_seek).
//   * Sharding for data parallelism happens at the window level: rank r of
//     R takes windows w with w % R == r, so ranks read disjoint data with
//     no communication.
//
// File format ("BATD"): 16-byte header
//   [0:4)  magic "BATD"
//   [4:8)  uint32 version (1)
//   [8:12) uint32 bytes per token (2 or 4)
//   [12:16) uint32 reserved (0)
// followed by little-endian token ids.

#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <mutex>
#include <thread>
#include <vector>

#include <fcntl.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <unistd.h>

namespace {

constexpr uint32_t kMagic = 0x44544142;  // "BATD" little-endian
constexpr int kHeaderBytes = 16;

// SplitMix64: stateless, high-quality 64-bit mix — the round function of the
// shuffle permutation and the key scheduler, so every (seed, epoch, index)
// triple maps to the same window on every rank and after every resume.
inline uint64_t mix64(uint64_t x) {
  x += 0x9e3779b97f4a7c15ULL;
  x = (x ^ (x >> 30)) * 0xbf58476d1ce4e5b9ULL;
  x = (x ^ (x >> 27)) * 0x94d049bb133111ebULL;
  return x ^ (x >> 31);
}

// Stateless pseudo-random PERMUTATION of [0, n): 4-round balanced Feistel
// over the smallest even-width power-of-two domain >= n, cycle-walked back
// into [0, n).  A permutation (not a hash draw) guarantees epoch sampling
// WITHOUT replacement, which keeps data-parallel shard windows disjoint
// under shuffle.  Cycle-walking terminates: the Feistel net is a bijection
// of the padded domain, so iterating it from a point < n must return to
// [0, n) within domain/n steps in expectation (< 4).
inline uint64_t permute_index(uint64_t i, uint64_t n, uint64_t key) {
  int half_bits = 1;
  while ((1ULL << (2 * half_bits)) < n) ++half_bits;  // domain = 2^(2*half)
  const uint64_t half_mask = (1ULL << half_bits) - 1;
  uint64_t x = i;
  do {
    uint64_t l = x >> half_bits, r = x & half_mask;
    for (int round = 0; round < 4; ++round) {
      uint64_t f = mix64(r ^ mix64(key + (uint64_t)round)) & half_mask;
      uint64_t nl = r, nr = l ^ f;
      l = nl;
      r = nr;
    }
    x = (l << half_bits) | r;
  } while (x >= n);
  return x;
}

struct Slot {
  int64_t step = -1;  // global step this buffer holds; -1 = free
  std::vector<int32_t> data;
};

}  // namespace

struct DLHandle {
  // immutable after open
  int fd = -1;
  const uint8_t* base = nullptr;  // mmap base (token region)
  size_t map_bytes = 0;
  int64_t n_tokens = 0;
  int dtype_bytes = 2;
  int64_t seq_len = 0;    // window length handed out is seq_len + 1
  int64_t batch = 0;
  int64_t shard_id = 0;
  int64_t num_shards = 1;
  uint64_t seed = 0;
  bool shuffle = true;
  int64_t windows_per_epoch = 0;  // windows owned by THIS shard per epoch

  // prefetch machinery
  std::vector<std::thread> workers;
  std::vector<Slot> slots;
  std::mutex mu;
  std::condition_variable cv_full;   // consumer waits: slot for next_step ready
  std::condition_variable cv_free;   // workers wait: a slot is free
  std::atomic<bool> stop{false};
  int64_t next_fill = 0;   // next step a worker will claim
  int64_t next_read = 0;   // next step the consumer will take
  int64_t gen = 0;         // bumped by dl_seek; stale fills are discarded

  int64_t window_tokens() const { return seq_len + 1; }

  // Global window index (within an epoch, before sharding) for (epoch, i).
  // With shuffle, a stateless exact permutation of the windows (keyed by
  // seed and epoch) — sampling WITHOUT replacement, so every window is
  // visited exactly once per epoch and shard ownership stays disjoint.
  // Without shuffle, sequential order.
  int64_t window_start(int64_t epoch, int64_t i) const {
    int64_t total = n_tokens / window_tokens();
    int64_t w = i % total;
    if (shuffle) {
      uint64_t key = mix64(seed ^ mix64((uint64_t)epoch));
      w = (int64_t)permute_index((uint64_t)w, (uint64_t)total, key);
    }
    return w * window_tokens();
  }

  // Fill `out` with the batch for global step `step` (this shard's view).
  void fill(int64_t step, int32_t* out) const {
    const int64_t wpe = windows_per_epoch;
    const int64_t wt = window_tokens();
    for (int64_t b = 0; b < batch; ++b) {
      int64_t k = step * batch + b;              // k-th window of this shard
      int64_t epoch = k / wpe;
      int64_t local = k % wpe;
      int64_t i = local * num_shards + shard_id;  // de-interleave shards
      int64_t start = window_start(epoch, i);
      const uint8_t* src = base + start * dtype_bytes;
      int32_t* dst = out + b * wt;
      if (dtype_bytes == 2) {
        const uint16_t* s16 = reinterpret_cast<const uint16_t*>(src);
        for (int64_t t = 0; t < wt; ++t) dst[t] = (int32_t)s16[t];
      } else {
        std::memcpy(dst, src, (size_t)(wt * 4));
      }
    }
  }

  void worker() {
    const size_t n = slots.size();
    while (true) {
      int64_t step, my_gen;
      Slot* slot;
      {
        std::unique_lock<std::mutex> lk(mu);
        cv_free.wait(lk, [&] {
          return stop.load() || slots[next_fill % n].step == -1;
        });
        if (stop.load()) return;
        step = next_fill++;
        my_gen = gen;
        slot = &slots[step % n];
        slot->step = -2;  // claimed, filling
      }
      fill(step, slot->data.data());
      {
        std::lock_guard<std::mutex> lk(mu);
        // a dl_seek between claim and publish invalidates this fill
        slot->step = (my_gen == gen) ? step : -1;
      }
      cv_full.notify_all();
      cv_free.notify_all();
    }
  }
};

extern "C" {

// Returns nullptr on failure.  dtype/seq/batch/shard semantics in the header
// comment.  queue_depth buffers of batch*(seq_len+1) int32 are kept in
// flight by num_threads workers.
DLHandle* dl_open(const char* path, int64_t seq_len, int64_t batch,
                  int64_t shard_id, int64_t num_shards, uint64_t seed,
                  int num_threads, int queue_depth, int shuffle) {
  if (seq_len <= 0 || batch <= 0 || num_shards <= 0 || shard_id < 0 ||
      shard_id >= num_shards || num_threads <= 0 || queue_depth < num_threads)
    return nullptr;
  int fd = ::open(path, O_RDONLY);
  if (fd < 0) return nullptr;
  struct stat st;
  if (fstat(fd, &st) != 0 || st.st_size < kHeaderBytes) {
    ::close(fd);
    return nullptr;
  }
  void* map = ::mmap(nullptr, (size_t)st.st_size, PROT_READ, MAP_PRIVATE, fd, 0);
  if (map == MAP_FAILED) {
    ::close(fd);
    return nullptr;
  }
  const uint8_t* bytes = static_cast<const uint8_t*>(map);
  uint32_t magic, version, dtype_bytes;
  std::memcpy(&magic, bytes, 4);
  std::memcpy(&version, bytes + 4, 4);
  std::memcpy(&dtype_bytes, bytes + 8, 4);
  if (magic != kMagic || version != 1 || (dtype_bytes != 2 && dtype_bytes != 4)) {
    ::munmap(map, (size_t)st.st_size);
    ::close(fd);
    return nullptr;
  }
  auto* h = new DLHandle();
  h->fd = fd;
  h->map_bytes = (size_t)st.st_size;
  h->base = bytes + kHeaderBytes;
  h->dtype_bytes = (int)dtype_bytes;
  h->n_tokens = (st.st_size - kHeaderBytes) / dtype_bytes;
  h->seq_len = seq_len;
  h->batch = batch;
  h->shard_id = shard_id;
  h->num_shards = num_shards;
  h->seed = seed;
  h->shuffle = shuffle != 0;
  int64_t total_windows = h->n_tokens / h->window_tokens();
  // shard r owns windows {r, r+R, r+2R, ...}; require at least one batch
  h->windows_per_epoch = total_windows / num_shards;
  if (h->windows_per_epoch < 1 || total_windows < 1) {
    ::munmap(map, (size_t)st.st_size);
    ::close(fd);
    delete h;
    return nullptr;
  }
  ::madvise(const_cast<uint8_t*>(bytes), h->map_bytes,
            h->shuffle ? MADV_RANDOM : MADV_SEQUENTIAL);
  h->slots.resize((size_t)queue_depth);
  for (auto& s : h->slots) s.data.resize((size_t)(batch * h->window_tokens()));
  for (int i = 0; i < num_threads; ++i)
    h->workers.emplace_back([h] { h->worker(); });
  return h;
}

// Copy the batch for the next step into `out` (batch * (seq_len+1) int32,
// row-major).  Blocks until a prefetched buffer is ready.  Returns the
// global step number (>= 0) delivered, or -1 on error.
int64_t dl_next(DLHandle* h, int32_t* out) {
  if (!h) return -1;
  Slot* slot;
  int64_t step;
  const size_t n = h->slots.size();
  {
    std::unique_lock<std::mutex> lk(h->mu);
    step = h->next_read;
    slot = &h->slots[step % n];
    h->cv_full.wait(lk, [&] { return slot->step == step; });
    h->next_read++;
  }
  std::memcpy(out, slot->data.data(), slot->data.size() * 4);
  {
    std::lock_guard<std::mutex> lk(h->mu);
    slot->step = -1;  // free the slot
  }
  h->cv_free.notify_all();
  return step;
}

// Reposition the stream so the next dl_next returns `step` (checkpoint
// resume).  Discards all in-flight buffers.
void dl_seek(DLHandle* h, int64_t step) {
  if (!h || step < 0) return;
  {
    std::lock_guard<std::mutex> lk(h->mu);
    h->gen++;  // claimed-but-unpublished fills will self-discard
    for (auto& s : h->slots)
      if (s.step >= 0) s.step = -1;  // drop ready buffers
    h->next_read = step;
    h->next_fill = step;
  }
  h->cv_free.notify_all();
  h->cv_full.notify_all();
}

int64_t dl_num_tokens(DLHandle* h) { return h ? h->n_tokens : -1; }
int64_t dl_windows_per_epoch(DLHandle* h) { return h ? h->windows_per_epoch : -1; }

void dl_close(DLHandle* h) {
  if (!h) return;
  {
    // under the workers' mutex: a worker that has just found its predicate
    // false and not yet blocked would otherwise miss both the flag and the
    // notify below, and the join would wait for ever
    std::lock_guard<std::mutex> lk(h->mu);
    h->stop.store(true);
  }
  h->cv_free.notify_all();
  h->cv_full.notify_all();
  for (auto& t : h->workers) t.join();
  ::munmap(const_cast<uint8_t*>(h->base) - kHeaderBytes, h->map_bytes);
  ::close(h->fd);
  delete h;
}

}  // extern "C"

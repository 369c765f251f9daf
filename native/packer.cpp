// Parallel host-side cohort packer for fedml_tpu.
//
// Role: the per-round host hot path — gathering P sampled clients' ragged
// sample arrays into the dense, device-ready [P, n_pad, ...] round input
// (fedml_tpu/data/base.py pack_clients). The reference pays this cost as
// torch DataLoader iteration + pickle per message
// (fedml_api/distributed/fedavg/MyModelTrainer.py batch loop); here it is
// one memcpy/memset pass per client, spread across host cores (threads
// over clients). On a single-core host this degenerates to exactly the
// numpy loop's cost; multi-channel hosts get parallel bandwidth.
//
// The destination is the caller's, and every byte of it is written (real
// rows, zeroed tail, mask): pack_clients hands over either arrays it has
// just allocated or buffers its own caller recycles (out=), and keeps no
// reference to either. Which of the two matters more than the threads: a
// fresh destination faults in and zeroes a page for every 4 KB written
// (104 ms for a 230 MB cohort on a 30-core TPU host with 16 threads; the
// same copy into a buffer that has its pages 13-17 ms, and 26 ms with one
// thread: PERF.md, PR 36).
//
// Threads are started per call, and a start costs about as much as
// copying 1-2 MB, so a call takes one thread per kBytesPerThread of
// destination and never more than the caller allows: a cohort's labels
// (tens of KB) are copied by the calling thread alone.
//
// Layout contract (enforced by the Python wrapper): every client i owns a
// C-contiguous [counts[i], row_bytes] buffer; dst is C-contiguous
// [P, n_pad, row_bytes]; mask (optional) is [P, n_pad] float32.

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

namespace {
constexpr int64_t kBytesPerThread = int64_t{16} << 20;
}  // namespace

extern "C" {

// Returns 0 on success, -1 if any counts[i] > n_pad (nothing written).
int fedml_pack_clients(const uint8_t* const* src_ptrs,
                       const int64_t* counts, int64_t P, int64_t n_pad,
                       int64_t row_bytes, uint8_t* dst, float* mask,
                       int n_threads) {
  for (int64_t i = 0; i < P; ++i) {
    if (counts[i] > n_pad || counts[i] < 0) return -1;
  }
  auto work = [&](int64_t i) {
    const int64_t n = counts[i];
    uint8_t* out = dst + i * n_pad * row_bytes;
    if (n > 0) std::memcpy(out, src_ptrs[i], n * row_bytes);
    std::memset(out + n * row_bytes, 0, (n_pad - n) * row_bytes);
    if (mask != nullptr) {
      float* m = mask + i * n_pad;
      std::fill(m, m + n, 1.0f);
      std::fill(m + n, m + n_pad, 0.0f);
    }
  };
  const int64_t by_size = 1 + P * n_pad * row_bytes / kBytesPerThread;
  const int k = static_cast<int>(std::max<int64_t>(
      1, std::min<int64_t>({n_threads, P, by_size})));
  if (k == 1) {
    for (int64_t i = 0; i < P; ++i) work(i);
    return 0;
  }
  std::atomic<int64_t> next{0};
  std::vector<std::thread> threads;
  threads.reserve(k);
  for (int t = 0; t < k; ++t) {
    threads.emplace_back([&] {
      for (int64_t i; (i = next.fetch_add(1)) < P;) work(i);
    });
  }
  for (auto& t : threads) t.join();
  return 0;
}

}  // extern "C"

// Native host hash shard: uint64 key → dense row id.
//
// TPU-native counterpart of the DRAM tier's per-shard hash map
// (reference: MemorySparseTable shards, ps/table/memory_sparse_table.h:39;
// GPU-side concurrent map hashtable.h:53).  Values stay in numpy SoA arrays
// owned by Python and indexed by the dense row ids this map hands out —
// the map only does key→row translation, so the C ABI stays tiny.
//
// Open addressing, power-of-two capacity, linear probing, 0.75 max load
// (the reference's load factor, hashtable.h:211).

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

namespace {

constexpr uint64_t kEmpty = 0xFFFFFFFFFFFFFFFFull;

inline uint64_t mix(uint64_t k) {
  // splitmix64 finalizer — full-avalanche for clustered feasigns
  k += 0x9E3779B97F4A7C15ull;
  k = (k ^ (k >> 30)) * 0xBF58476D1CE4E5B9ull;
  k = (k ^ (k >> 27)) * 0x94D049BB133111EBull;
  return k ^ (k >> 31);
}

struct HashShard {
  std::vector<uint64_t> keys;   // capacity slots, kEmpty = free
  std::vector<int64_t> rows;
  std::vector<uint64_t> by_row;  // row id → key
  uint64_t mask = 0;
  int64_t size = 0;

  explicit HashShard(int64_t hint) {
    int64_t cap = 16;
    while (cap * 3 < hint * 4) cap <<= 1;  // cap >= hint / 0.75
    keys.assign(static_cast<size_t>(cap), kEmpty);
    rows.assign(static_cast<size_t>(cap), -1);
    mask = static_cast<uint64_t>(cap - 1);
  }

  void grow() {
    std::vector<uint64_t> old_keys;
    std::vector<int64_t> old_rows;
    old_keys.swap(keys);
    old_rows.swap(rows);
    size_t cap = old_keys.size() * 2;
    keys.assign(cap, kEmpty);
    rows.assign(cap, -1);
    mask = cap - 1;
    for (size_t i = 0; i < old_keys.size(); ++i) {
      if (old_keys[i] == kEmpty) continue;
      uint64_t slot = mix(old_keys[i]) & mask;
      while (keys[slot] != kEmpty) slot = (slot + 1) & mask;
      keys[slot] = old_keys[i];
      rows[slot] = old_rows[i];
    }
  }

  int64_t upsert(uint64_t key) {
    if ((size + 1) * 4 > static_cast<int64_t>(keys.size()) * 3) grow();
    uint64_t slot = mix(key) & mask;
    while (true) {
      if (keys[slot] == key) return rows[slot];
      if (keys[slot] == kEmpty) {
        keys[slot] = key;
        rows[slot] = size;
        by_row.push_back(key);
        return size++;
      }
      slot = (slot + 1) & mask;
    }
  }

  int64_t find(uint64_t key) const {
    uint64_t slot = mix(key) & mask;
    while (true) {
      if (keys[slot] == key) return rows[slot];
      if (keys[slot] == kEmpty) return -1;
      slot = (slot + 1) & mask;
    }
  }
};

// The pass's key dedup (≙ the agent's key merge before the pass build,
// box_wrapper.cc EndFeedPass): the distinct nonzero keys of a list of key
// chunks, ascending.  Each thread owns one key range, cut at splitters
// drawn from a sample of the input (so dense ids and 64-bit hashes
// split alike), scans every chunk in place for its range's keys, inserts
// them into its own open-addressing set (mix() and linear probing as
// above; key 0 is never returned, so it marks a free slot) and sorts what
// it found: ranges ascend, so the threads' sorted runs laid end to end are
// the answer, with no merge.  The sets and runs are kept from call to call:
// after the first pass nothing of the input's size is allocated.
struct KeyDedup {
  struct Range {
    std::vector<uint64_t> set;  // power-of-two capacity, 0 = free
    std::vector<uint64_t> run;  // the range's distinct keys, sorted
  };
  std::vector<Range> ranges;
  int32_t n_ranges = 0;

  static constexpr int64_t kMinSet = 1 << 12;
  static constexpr int64_t kSample = 1 << 16;

  // keys k with (k - lo) < width, in unsigned arithmetic (lo >= 1, so key
  // 0 is in no range; width 0 - lo is "to the top of the key space")
  static void fill(Range* r, const uint64_t* const* chunks,
                   const int64_t* lens, int64_t n_chunks, uint64_t lo,
                   uint64_t width) {
    std::vector<uint64_t>& set = r->set;
    if (set.size() < static_cast<size_t>(kMinSet)) set.assign(kMinSet, 0);
    uint64_t mask = set.size() - 1;
    int64_t size = 0;
    int64_t limit = static_cast<int64_t>(set.size()) / 2;  // 0.5 max load
    for (int64_t c = 0; c < n_chunks; ++c) {
      const uint64_t* p = chunks[c];
      const int64_t n = lens[c];
      for (int64_t i = 0; i < n; ++i) {
        const uint64_t k = p[i];
        if (k - lo >= width) continue;
        uint64_t slot = mix(k) & mask;
        while (set[slot] != 0 && set[slot] != k) slot = (slot + 1) & mask;
        if (set[slot] == k) continue;
        set[slot] = k;
        if (++size > limit) {
          std::vector<uint64_t> old(set.size() * 2, 0);
          old.swap(set);
          mask = set.size() - 1;
          limit = static_cast<int64_t>(set.size()) / 2;
          for (uint64_t v : old) {
            if (v == 0) continue;
            uint64_t s = mix(v) & mask;
            while (set[s] != 0) s = (s + 1) & mask;
            set[s] = v;
          }
        }
      }
    }
    // collect and free the slots in one sweep: the set starts the next
    // call empty at the capacity this one reached
    r->run.clear();
    r->run.reserve(static_cast<size_t>(size));
    for (uint64_t& v : set) {
      if (v != 0) {
        r->run.push_back(v);
        v = 0;
      }
    }
    std::sort(r->run.begin(), r->run.end());
  }

  int64_t run(const uint64_t* const* chunks, const int64_t* lens,
              int64_t n_chunks, int32_t n_threads) {
    int64_t n = 0;
    for (int64_t c = 0; c < n_chunks; ++c) n += lens[c];
    // splitters: T-1 evenly spaced distinct values of a sample drawn at
    // hashed positions (a stride would alias with a record's slot layout
    // and sample one slot's keys)
    std::vector<uint64_t> splits;
    if (n_threads > 1 && n >= (1 << 16)) {
      std::vector<int64_t> ends(static_cast<size_t>(n_chunks));
      int64_t base = 0;
      for (int64_t c = 0; c < n_chunks; ++c) ends[c] = base += lens[c];
      std::vector<uint64_t> sample;
      sample.reserve(static_cast<size_t>(kSample));
      for (int64_t j = 0; j < kSample; ++j) {
        const int64_t at = static_cast<int64_t>(
            mix(static_cast<uint64_t>(j)) % static_cast<uint64_t>(n));
        const int64_t c =
            std::upper_bound(ends.begin(), ends.end(), at) - ends.begin();
        const uint64_t k = chunks[c][at - (ends[c] - lens[c])];
        if (k != 0) sample.push_back(k);
      }
      std::sort(sample.begin(), sample.end());
      sample.erase(std::unique(sample.begin(), sample.end()), sample.end());
      const int64_t d = static_cast<int64_t>(sample.size());
      const int64_t t = n_threads < d ? n_threads : d;
      for (int64_t i = 1; i < t; ++i) splits.push_back(sample[i * d / t]);
    }
    n_ranges = static_cast<int32_t>(splits.size()) + 1;
    if (static_cast<int32_t>(ranges.size()) < n_ranges) ranges.resize(n_ranges);
    auto bounds = [&](int32_t i, uint64_t* lo, uint64_t* width) {
      *lo = i == 0 ? 1 : splits[i - 1];
      const uint64_t hi = i + 1 < n_ranges ? splits[i] : 0;
      *width = hi - *lo;
    };
    if (n_ranges == 1) {
      uint64_t lo, width;
      bounds(0, &lo, &width);
      fill(&ranges[0], chunks, lens, n_chunks, lo, width);
    } else {
      std::vector<std::thread> ts;
      for (int32_t i = 0; i < n_ranges; ++i) {
        uint64_t lo, width;
        bounds(i, &lo, &width);
        ts.emplace_back(fill, &ranges[i], chunks, lens, n_chunks, lo, width);
      }
      for (auto& t : ts) t.join();
    }
    int64_t total = 0;
    for (int32_t i = 0; i < n_ranges; ++i)
      total += static_cast<int64_t>(ranges[i].run.size());
    return total;
  }

  void take(uint64_t* out) const {
    for (int32_t i = 0; i < n_ranges; ++i) {
      const std::vector<uint64_t>& r = ranges[i].run;
      if (!r.empty()) memcpy(out, r.data(), r.size() * sizeof(uint64_t));
      out += r.size();
    }
  }
};

}  // namespace

extern "C" {

void* pbox_hash_new(int64_t capacity_hint) {
  return new HashShard(capacity_hint < 16 ? 16 : capacity_hint);
}

void pbox_hash_free(void* h) { delete static_cast<HashShard*>(h); }

int64_t pbox_hash_size(void* h) { return static_cast<HashShard*>(h)->size; }

void pbox_hash_upsert(void* h, const uint64_t* in_keys, int64_t n,
                      int64_t* out_rows) {
  auto* m = static_cast<HashShard*>(h);
  for (int64_t i = 0; i < n; ++i) out_rows[i] = m->upsert(in_keys[i]);
}

void pbox_hash_find(void* h, const uint64_t* in_keys, int64_t n,
                    int64_t* out_rows) {
  auto* m = static_cast<HashShard*>(h);
  for (int64_t i = 0; i < n; ++i) out_rows[i] = m->find(in_keys[i]);
}

void pbox_hash_keys(void* h, uint64_t* out) {
  auto* m = static_cast<HashShard*>(h);
  memcpy(out, m->by_row.data(), m->by_row.size() * sizeof(uint64_t));
}

// Pass-key translation hot path (≙ DedupKeysAndFillIdx,
// box_wrapper_impl.h:129, done once per pass): key → insertion-row + 1,
// missing/zero keys → 0 (the reserved zero-embedding row).  Read-only over
// the table, so lookups fan out over threads.
void pbox_hash_find_rows1_i32(void* h, const uint64_t* in_keys, int64_t n,
                              int32_t* out_rows, int32_t n_threads) {
  auto* m = static_cast<HashShard*>(h);
  if (n_threads < 1) n_threads = 1;
  auto work = [m, in_keys, out_rows](int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; ++i) {
      uint64_t k = in_keys[i];
      int64_t row = (k == 0) ? -1 : m->find(k);
      out_rows[i] = static_cast<int32_t>(row + 1);
    }
  };
  if (n_threads == 1 || n < (1 << 16)) {
    work(0, n);
    return;
  }
  std::vector<std::thread> ts;
  int64_t step = (n + n_threads - 1) / n_threads;
  for (int32_t t = 0; t < n_threads; ++t) {
    int64_t lo = t * step;
    int64_t hi = lo + step < n ? lo + step : n;
    if (lo >= hi) break;
    ts.emplace_back(work, lo, hi);
  }
  for (auto& t : ts) t.join();
}

// The pass's distinct nonzero keys over n_chunks arrays given in place:
// run returns their count, take writes them ascending (out holds that many).
void* pbox_dedup_new() { return new KeyDedup(); }

void pbox_dedup_free(void* d) { delete static_cast<KeyDedup*>(d); }

int64_t pbox_dedup_run(void* d, const uint64_t* const* chunks,
                       const int64_t* lens, int64_t n_chunks,
                       int32_t n_threads) {
  return static_cast<KeyDedup*>(d)->run(chunks, lens, n_chunks, n_threads);
}

void pbox_dedup_take(void* d, uint64_t* out) {
  static_cast<KeyDedup*>(d)->take(out);
}

}  // extern "C"

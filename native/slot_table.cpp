// Native slot table: cache-key -> HBM-slot assignment on the serving
// hot path.
//
// Same contract as the Python SlotTable (ratelimit_tpu/backends/
// slot_table.py — the behavioral spec, kept as the differential-test
// oracle and fallback): exact key->slot mapping, lazy-deletion expiry
// min-heap, evict-soonest-expiring when full, batch pinning so two
// live keys in one device batch never share a slot.  The win over the
// Python version is batch granularity: one ctypes call assigns a whole
// batch (keys passed as a single length-prefixed utf-8 blob), so the
// per-descriptor interpreter cost disappears from the dispatcher
// thread.
//
// sk_assign_dedup_batch additionally folds the host-side duplicate
// aggregation (engine.py _dedup_chunk) into the SAME walk: while
// assigning each key it accumulates per-group hit totals, per-lane
// exclusive prefixes (Redis-pipeline order), group freshness and
// max-limit, and hands back the groups in sorted-slot order — one
// C call replaces assign_batch + np.unique + three scatter passes on
// the dispatcher thread.
//
// The key store is a FLAT open-addressing table (linear probing,
// power-of-2 capacity, 64-bit stored hashes, keys in one arena):
// std::unordered_map::find dominated the fused call at ~63 ns/key
// (pointer-chasing buckets + rehashing the key bytes); the flat table
// compares the stored hash before touching key bytes and keeps probe
// sequences cache-local.  The hash is seeded per table so externally
// controlled descriptor values cannot precompute a flooding set.
//
// The reference has no native code (SURVEY.md section 2: pure Go); the
// analog of this component is Redis's keyspace itself — the piece of
// the reference's hot path that lived outside Go.
//
// Build: make native   (g++ -O2 -std=c++20 -shared -fPIC -> libslottable.so)

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <ctime>
#include <numeric>
#include <queue>
#include <random>
#include <string>
#include <string_view>
#include <utility>
#include <vector>

namespace {

struct HeapItem {
  int64_t expiry;
  std::string key;
  bool operator>(const HeapItem& o) const {
    if (expiry != o.expiry) return expiry > o.expiry;
    return key > o.key;
  }
};

// Word-stride mix hash with a per-table random seed (blocks offline
// collision construction against externally controlled descriptor
// values).  8 bytes per iteration: a byte-at-a-time FNV measured ~50%
// SLOWER end-to-end on the ~30-byte serving keys.
inline uint64_t hash_key(uint64_t seed, std::string_view s) {
  const unsigned char* p =
      reinterpret_cast<const unsigned char*>(s.data());
  size_t n = s.size();
  uint64_t h = seed ^ (uint64_t(n) * 0x9e3779b97f4a7c15ull);
  while (n >= 8) {
    uint64_t k;
    std::memcpy(&k, p, 8);
    k *= 0x9ddfea08eb382d69ull;
    k ^= k >> 29;
    h = (h ^ k) * 0x9e3779b97f4a7c15ull;
    p += 8;
    n -= 8;
  }
  if (n) {
    uint64_t k = 0;
    std::memcpy(&k, p, n);
    h = (h ^ k) * 0x9e3779b97f4a7c15ull;
  }
  // Final mix so linear probing sees high-entropy low bits.
  h ^= h >> 32;
  h *= 0xd6e8feb86659fd93ull;
  h ^= h >> 32;
  return h;
}

// Open-addressing key -> (slot, expiry) map.  States: EMPTY, FULL,
// TOMBSTONE.  Erases leave tombstones (and leak their arena bytes)
// until the next rehash compacts both.
class FlatMap {
 public:
  explicit FlatMap(uint64_t seed, size_t initial_pow2 = 1024)
      : seed_(seed) {
    rehash(initial_pow2);
  }

  uint64_t hash_of(std::string_view key) const {
    return hash_key(seed_, key);
  }

  // Index of `key`, or -1.
  int64_t find(std::string_view key) const {
    return find_hashed(hash_of(key), key);
  }

  int64_t find_hashed(uint64_t h, std::string_view key) const {
    size_t i = h & mask_;
    while (true) {
      const uint8_t st = state_[i];
      if (st == kEmpty) return -1;
      if (st == kFull && hashes_[i] == h) {
        const Meta& m = meta_[i];
        if (m.key_len == key.size() &&
            std::memcmp(arena_.data() + m.key_off, key.data(),
                        key.size()) == 0)
          return static_cast<int64_t>(i);
      }
      i = (i + 1) & mask_;
    }
  }

  // Insert a key known to be absent.
  void insert(std::string_view key, int64_t slot, int64_t expiry) {
    insert_hashed(hash_of(key), key, slot, expiry);
  }

  void insert_hashed(uint64_t h, std::string_view key, int64_t slot,
                     int64_t expiry) {
    // Grow/compact triggers: probe load (live+tombstones), and dead
    // arena bytes — steady-state expiry churn reuses tombstones (the
    // load sum never grows) while appending key bytes every insert,
    // so without the dead-byte trigger the arena would grow without
    // bound and eventually wrap the u32 key offsets.
    if ((live_ + tombstones_ + 1) * 10 >= capacity() * 7 ||
        (dead_bytes_ > (1u << 20) && dead_bytes_ * 2 > arena_.size())) {
      rehash(capacity() * (live_ * 10 >= capacity() * 4 ? 2 : 1));
      ++compactions_;
    }
    size_t i = h & mask_;
    while (state_[i] == kFull) i = (i + 1) & mask_;
    if (state_[i] == kTombstone) --tombstones_;
    state_[i] = kFull;
    hashes_[i] = h;
    Meta& m = meta_[i];
    m.key_off = static_cast<uint64_t>(arena_.size());
    m.key_len = static_cast<uint32_t>(key.size());
    m.slot = slot;
    m.expiry = expiry;
    arena_.append(key.data(), key.size());
    ++live_;
  }

  void erase(int64_t idx) {
    state_[idx] = kTombstone;
    dead_bytes_ += meta_[idx].key_len;
    ++tombstones_;
    --live_;
  }

  int64_t slot(int64_t idx) const { return meta_[idx].slot; }
  int64_t expiry(int64_t idx) const { return meta_[idx].expiry; }
  size_t size() const { return live_; }
  size_t arena_bytes() const { return arena_.size(); }
  // Rehashes since construction: each rebuilds the table (same size
  // or doubled) and compacts the arena — tombstones and the key bytes
  // of erased entries go.
  int64_t compactions() const { return compactions_; }

  std::string_view key_at(int64_t idx) const {
    const Meta& m = meta_[idx];
    return {arena_.data() + m.key_off, m.key_len};
  }

  template <class F>
  void for_each(F f) const {
    for (size_t i = 0; i < capacity(); ++i)
      if (state_[i] == kFull)
        f(key_at(static_cast<int64_t>(i)), meta_[i].slot, meta_[i].expiry);
  }

 private:
  static constexpr uint8_t kEmpty = 0, kFull = 1, kTombstone = 2;
  struct Meta {
    // 64-bit offset: a u32 offset would silently wrap once ~4 GiB of
    // key bytes accumulate in the arena (tombstones included before
    // compaction), aliasing key comparisons onto wrong bytes.
    uint64_t key_off;
    uint32_t key_len;
    int64_t slot;
    int64_t expiry;
  };

  size_t capacity() const { return state_.size(); }

  void rehash(size_t new_cap) {
    // Round up to a power of two >= max(new_cap, live*2, 1024).
    size_t want = std::max<size_t>(
        {new_cap, live_ * 2, static_cast<size_t>(1024)});
    size_t cap = 1024;
    while (cap < want) cap <<= 1;

    std::vector<uint8_t> old_state = std::move(state_);
    std::vector<uint64_t> old_hashes = std::move(hashes_);
    std::vector<Meta> old_meta = std::move(meta_);
    std::string old_arena = std::move(arena_);

    state_.assign(cap, kEmpty);
    hashes_.assign(cap, 0);
    meta_.assign(cap, Meta{});
    arena_.clear();
    arena_.reserve(old_arena.size());
    mask_ = cap - 1;
    live_ = 0;
    tombstones_ = 0;
    dead_bytes_ = 0;

    for (size_t i = 0; i < old_state.size(); ++i) {
      if (old_state[i] != kFull) continue;
      const Meta& m = old_meta[i];
      insert({old_arena.data() + m.key_off, m.key_len}, m.slot, m.expiry);
    }
  }

  uint64_t seed_;
  size_t mask_ = 0;
  size_t live_ = 0;
  size_t tombstones_ = 0;
  size_t dead_bytes_ = 0;  // arena bytes owned by tombstoned keys
  int64_t compactions_ = 0;
  std::vector<uint8_t> state_;
  std::vector<uint64_t> hashes_;
  std::vector<Meta> meta_;
  std::string arena_;
};

struct SlotTable {
  int64_t num_slots;
  FlatMap map;  // key -> (slot, expiry)
  std::vector<int64_t> free_slots;  // LIFO, matches python list.pop()
  std::priority_queue<HeapItem, std::vector<HeapItem>, std::greater<HeapItem>> heap;
  int64_t evictions = 0;
  // Pins are slot ids ("this slot was handed out in the in-flight
  // batch"), epoch-stamped: pin_stamp[slot] == pin_epoch means
  // pinned.  A fresh epoch per assign call (or per begin_batch for
  // the cross-call protocol) replaces clearing a set — and per-lane
  // pinning becomes one array store instead of an unordered_set
  // insert.
  bool batch_active = false;
  std::vector<uint32_t> pin_stamp;
  uint32_t pin_epoch = 0;

  // slot -> group-id scratch for the fused dedup, epoch-stamped so
  // no per-call clearing: stamp[slot] == dedup_epoch marks a live gid.
  std::vector<int32_t> gid_by_slot;
  std::vector<uint32_t> gid_stamp;
  uint32_t dedup_epoch = 0;

  explicit SlotTable(int64_t n)
      : num_slots(n), map(std::random_device{}() |
                          (uint64_t(std::random_device{}()) << 32)) {
    free_slots.reserve(n);
    for (int64_t s = 0; s < n; ++s) free_slots.push_back(n - 1 - s);
    pin_stamp.assign(n, 0);
    gid_by_slot.assign(n, 0);
    gid_stamp.assign(n, 0);
  }

  // u32 wrap: stamp 0 must never alias a live epoch.
  static void bump_epoch(std::vector<uint32_t>& stamps, uint32_t& epoch) {
    if (++epoch == 0) {
      std::fill(stamps.begin(), stamps.end(), 0);
      epoch = 1;
    }
  }

  void next_pin_epoch() { bump_epoch(pin_stamp, pin_epoch); }

  // Start a new local pin scope unless a cross-call batch holds one.
  void begin_call_pins() {
    if (!batch_active) next_pin_epoch();
  }

  void pin(int64_t slot) { pin_stamp[slot] = pin_epoch; }
  bool is_pinned(int64_t slot) const {
    return pin_stamp[slot] == pin_epoch;
  }

  // Pinned slots (handed out in the in-flight batch) are skipped and
  // re-queued: reclaiming one mid-batch would alias two live keys in
  // one device step (same rule as evict_one).
  int64_t gc(int64_t now, bool use_pins) {
    int64_t freed = 0;
    std::vector<HeapItem> skipped;
    while (!heap.empty() && heap.top().expiry <= now) {
      HeapItem item = heap.top();
      heap.pop();
      int64_t idx = map.find(item.key);
      if (idx < 0 || map.expiry(idx) != item.expiry) continue;
      if (use_pins && is_pinned(map.slot(idx))) {
        skipped.push_back(std::move(item));
        continue;
      }
      free_slots.push_back(map.slot(idx));
      map.erase(idx);
      ++freed;
    }
    for (auto& s : skipped) heap.push(std::move(s));
    return freed;
  }

  // Returns false when the table is exhausted (batch pins more live
  // keys than slots).
  bool evict_one() {
    std::vector<HeapItem> skipped;
    bool ok = false;
    while (!heap.empty()) {
      HeapItem item = heap.top();
      heap.pop();
      int64_t idx = map.find(item.key);
      if (idx < 0 || map.expiry(idx) != item.expiry) continue;
      if (is_pinned(map.slot(idx))) {
        skipped.push_back(std::move(item));
        continue;
      }
      free_slots.push_back(map.slot(idx));
      map.erase(idx);
      ++evictions;
      ok = true;
      break;
    }
    for (auto& s : skipped) heap.push(std::move(s));
    return ok;
  }

  // Assign one key; returns (slot, fresh) via out params, false on
  // exhaustion.  `pinned` accumulates every slot handed out.
  bool assign_one(std::string_view key, int64_t now, int64_t expiry,
                  int64_t* out_slot, bool* out_fresh) {
    const uint64_t h = map.hash_of(key);  // hashed once: find + insert
    int64_t idx = map.find_hashed(h, key);
    if (idx >= 0) {
      *out_slot = map.slot(idx);
      *out_fresh = false;
      pin(*out_slot);
      return true;
    }
    if (free_slots.empty()) gc(now, /*use_pins=*/true);
    if (free_slots.empty() && !evict_one()) return false;
    int64_t slot = free_slots.back();
    free_slots.pop_back();
    heap.push(HeapItem{expiry, std::string(key)});
    map.insert_hashed(h, key, slot, expiry);
    pin(slot);
    *out_slot = slot;
    *out_fresh = true;
    return true;
  }
};

}  // namespace

extern "C" {

void* sk_create(int64_t num_slots) { return new SlotTable(num_slots); }

void sk_destroy(void* t) { delete static_cast<SlotTable*>(t); }

int64_t sk_len(void* t) {
  return static_cast<int64_t>(static_cast<SlotTable*>(t)->map.size());
}

int64_t sk_evictions(void* t) { return static_cast<SlotTable*>(t)->evictions; }

// Key-arena footprint (bytes), incl. not-yet-compacted tombstone keys
// — a live memory gauge and the churn-compaction test's probe.
int64_t sk_arena_bytes(void* t) {
  return static_cast<int64_t>(static_cast<SlotTable*>(t)->map.arena_bytes());
}

// Rehashes of the key map since construction (growth or same-size):
// each one compacts the arena.
int64_t sk_compactions(void* t) {
  return static_cast<SlotTable*>(t)->map.compactions();
}

int64_t sk_gc(void* tp, int64_t now) {
  SlotTable* t = static_cast<SlotTable*>(tp);
  return t->gc(now, /*use_pins=*/t->batch_active);
}

// Assign a whole batch in one call.
//   key_blob / key_lens[n]: concatenated utf-8 keys
//   expiries[n]:            per-key expiry (ignored for known keys)
//   out_slots[n], out_fresh[n]
// Keys appearing twice in the batch get the same slot (second sight is
// not fresh).  All slots handed out in the batch are pinned against
// eviction until the call returns.  Returns 0 on success, -1 when the
// table is exhausted (more pinned live keys than slots).
int64_t sk_assign_batch(void* tp, const uint8_t* key_blob,
                        const int64_t* key_lens, int64_t n, int64_t now,
                        const int64_t* expiries, int64_t* out_slots,
                        uint8_t* out_fresh) {
  SlotTable* t = static_cast<SlotTable*>(tp);
  t->begin_call_pins();
  const char* p = reinterpret_cast<const char*>(key_blob);
  for (int64_t i = 0; i < n; ++i) {
    std::string_view key(p, static_cast<size_t>(key_lens[i]));
    p += key_lens[i];
    int64_t slot;
    bool fresh;
    if (!t->assign_one(key, now, expiries[i], &slot, &fresh))
      return -1;
    out_slots[i] = slot;
    out_fresh[i] = fresh ? 1 : 0;
  }
  return 0;
}

// Fused assign + duplicate-slot aggregation (the C++ version of
// engine.py _dedup_chunk, folded into the assignment walk).
//
// Inputs as sk_assign_batch, plus per-lane hits[n] (uint32) and
// limits[n] (uint32).  Outputs (buffers sized n; only the first g
// group entries are written):
//   out_group[n]    lane -> group index, groups in ASCENDING SLOT
//                   order (matches np.unique's sorted order, which the
//                   sharded engine's bank routing relies on)
//   out_uniq[g]     sorted unique slots (int32)
//   out_totals[g]   per-group hit totals (uint64, unwrapped)
//   out_prefix[n]   per-lane exclusive same-group prefix of hits, in
//                   batch order (Redis pipeline-order semantics)
//   out_freshg[g]   group had a freshly-assigned slot
//   out_limitmax[g] max limit across the group's lanes
//   out_done_ns     CLOCK_MONOTONIC ns, written as the call's LAST act
//                   (null: not wanted).  ctypes takes the GIL back
//                   before Python runs again: time.monotonic_ns() the
//                   moment the call returns, minus this, is how long
//                   the calling thread waited for it.
// Returns g (number of groups), or -1 on table exhaustion.
int64_t sk_assign_dedup_batch(void* tp, const uint8_t* key_blob,
                              const int64_t* key_lens, int64_t n, int64_t now,
                              const int64_t* expiries, const uint32_t* hits,
                              const uint32_t* limits, int32_t* out_group,
                              int32_t* out_uniq, uint64_t* out_totals,
                              uint64_t* out_prefix, uint8_t* out_freshg,
                              uint32_t* out_limitmax, int64_t* out_done_ns) {
  SlotTable* t = static_cast<SlotTable*>(tp);
  t->begin_call_pins();

  // Epoch-stamped slot->gid scratch: O(1) array reads instead of a
  // per-call hash map (measured ~25% of the fused call).
  SlotTable::bump_epoch(t->gid_stamp, t->dedup_epoch);
  const uint32_t ep = t->dedup_epoch;
  std::vector<int64_t> g_slot;
  std::vector<uint64_t> g_total;
  std::vector<uint8_t> g_fresh;
  std::vector<uint32_t> g_limit;
  g_slot.reserve(n);
  g_total.reserve(n);
  g_fresh.reserve(n);
  g_limit.reserve(n);

  std::vector<int32_t> lane_gid(static_cast<size_t>(n));
  const char* p = reinterpret_cast<const char*>(key_blob);
  for (int64_t i = 0; i < n; ++i) {
    std::string_view key(p, static_cast<size_t>(key_lens[i]));
    p += key_lens[i];
    int64_t slot;
    bool fresh;
    if (!t->assign_one(key, now, expiries[i], &slot, &fresh))
      return -1;
    int32_t gid;
    if (t->gid_stamp[slot] == ep) {
      gid = t->gid_by_slot[slot];
    } else {
      gid = static_cast<int32_t>(g_slot.size());
      t->gid_stamp[slot] = ep;
      t->gid_by_slot[slot] = gid;
      g_slot.push_back(slot);
      g_total.push_back(0);
      g_fresh.push_back(0);
      g_limit.push_back(0);
    }
    out_prefix[i] = g_total[gid];
    g_total[gid] += hits[i];
    if (limits[i] > g_limit[gid]) g_limit[gid] = limits[i];
    if (fresh) g_fresh[gid] = 1;
    lane_gid[i] = gid;
  }

  // Sorted-slot group order (np.unique parity).
  const int32_t g = static_cast<int32_t>(g_slot.size());
  std::vector<int32_t> order(g);
  std::iota(order.begin(), order.end(), 0);
  std::sort(order.begin(), order.end(), [&](int32_t a, int32_t b) {
    return g_slot[a] < g_slot[b];
  });
  std::vector<int32_t> rank(g);
  for (int32_t k = 0; k < g; ++k) {
    rank[order[k]] = k;
    out_uniq[k] = static_cast<int32_t>(g_slot[order[k]]);
    out_totals[k] = g_total[order[k]];
    out_freshg[k] = g_fresh[order[k]];
    out_limitmax[k] = g_limit[order[k]];
  }
  for (int64_t i = 0; i < n; ++i) out_group[i] = rank[lane_gid[i]];
  if (out_done_ns) {
    timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    *out_done_ns = static_cast<int64_t>(ts.tv_sec) * 1000000000 + ts.tv_nsec;
  }
  return g;
}

void sk_begin_batch(void* tp) {
  SlotTable* t = static_cast<SlotTable*>(tp);
  t->batch_active = true;
  t->next_pin_epoch();  // fresh cross-call pin scope
}

void sk_end_batch(void* tp) {
  SlotTable* t = static_cast<SlotTable*>(tp);
  t->batch_active = false;
}

// Checkpoint export: call once with null buffers to get sizes, then
// with buffers of (total_key_bytes, n, n, n).
int64_t sk_export_size(void* tp, int64_t* out_total_key_bytes) {
  SlotTable* t = static_cast<SlotTable*>(tp);
  int64_t bytes = 0;
  t->map.for_each([&](std::string_view key, int64_t, int64_t) {
    bytes += static_cast<int64_t>(key.size());
  });
  *out_total_key_bytes = bytes;
  return static_cast<int64_t>(t->map.size());
}

void sk_export(void* tp, uint8_t* key_blob, int64_t* key_lens,
               int64_t* slots, int64_t* expiries) {
  SlotTable* t = static_cast<SlotTable*>(tp);
  uint8_t* p = key_blob;
  int64_t i = 0;
  t->map.for_each([&](std::string_view key, int64_t slot, int64_t expiry) {
    std::memcpy(p, key.data(), key.size());
    p += key.size();
    key_lens[i] = static_cast<int64_t>(key.size());
    slots[i] = slot;
    expiries[i] = expiry;
    ++i;
  });
}

// Checkpoint import: bulk-load entries into a fresh table.  Invalid or
// duplicate slots are skipped.  Returns how many entries were loaded.
int64_t sk_import(void* tp, const uint8_t* key_blob, const int64_t* key_lens,
                  const int64_t* slots, const int64_t* expiries, int64_t n) {
  SlotTable* t = static_cast<SlotTable*>(tp);
  std::vector<uint8_t> used(t->num_slots, 0);
  const char* p = reinterpret_cast<const char*>(key_blob);
  int64_t loaded = 0;
  for (int64_t i = 0; i < n; ++i) {
    std::string_view key(p, static_cast<size_t>(key_lens[i]));
    p += key_lens[i];
    int64_t slot = slots[i];
    if (slot < 0 || slot >= t->num_slots || used[slot]) continue;
    // Duplicate keys in a snapshot would leak the slot (marked used,
    // but the insert would create a shadowed duplicate): keep the
    // first entry.
    if (t->map.find(key) >= 0) continue;
    used[slot] = 1;
    t->heap.push(HeapItem{expiries[i], std::string(key)});
    t->map.insert(key, slot, expiries[i]);
    ++loaded;
  }
  t->free_slots.clear();
  for (int64_t s = t->num_slots - 1; s >= 0; --s)
    if (!used[s]) t->free_slots.push_back(s);
  return loaded;
}

}  // extern "C"

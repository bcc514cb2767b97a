// Native threshold state machine: the C++ mirror of the host decide
// path (ratelimit_tpu/limiter/base.py decide_batch fused with
// ratelimit_tpu/backends/engine.py _decide_host's per-lane
// reconstruction from per-group device afters).
//
// One pass replaces ~15 numpy kernel launches per batch on the
// completer thread (each launch costs dispatch overhead regardless of
// size — benchmarks/results/host_path.json complete_total).  The
// Python decide_batch stays as the behavioral oracle; differential
// tests lock the two together the same way the slot table is locked
// to its Python spec (tests/test_native_decide.py).
//
// Semantics mirrored exactly (reference src/limiter/base_limiter.go:
// 76-197 GetResponseDescriptorStatus + threshold checks):
// - near threshold computed in FLOAT32: floorf(float(limit) * ratio)
//   (base_limiter.go:94 uses float32 arithmetic; numpy mirrors it with
//   .astype(float32), so the C float here is bit-compatible);
// - over-limit when after > limit; partial-hit attribution when a
//   multi-hit batch straddles a threshold (base_limiter.go:150-179);
// - saturating u32 counter domain: a group's device `after` at u32 max
//   means the counter lapped — every lane of the group is fully-over
//   (engine.py _decide_host saturation regimes);
// - shadow mode flips OVER_LIMIT to OK but keeps stat attribution and
//   the local-cache insert marker (base_limiter.go:126-132).
//
// Build: compiled into _libslottable.so together with slot_table.cpp
// (make native / native_slot_table._build).

#include <cstdint>
#include <cmath>
#include <ctime>
#include <vector>

namespace {
constexpr uint64_t kU32Max = 0xFFFFFFFFull;
}

extern "C" {

// Fused reconstruction + decision for one deduped device chunk.
//
//   afters_g[g]   per-UNIQUE-slot device afters, widened to u32 (the
//                 compact u8/u16 readbacks widen exactly)
//   totals[g]     per-group uint64 hit totals (unwrapped)
//   inv[n]        lane -> group index
//   prefix[n]     per-lane exclusive same-group hit prefix (uint64,
//                 Redis-pipeline order)
//   hits[n], limits[n]  per-lane u32
//   shadow[n]     0/1 per-lane shadow-mode flag
//   near_ratio    near-limit ratio (float32 domain)
//   ok_code / over_code  wire values of Code.OK / Code.OVER_LIMIT
//
// Outputs (all length n): codes, limit_remaining, befores, afters,
// over_limit, near_limit, within_limit, shadow_mode stat deltas, and
// the set-local-cache marker; out_done_ns (null: not wanted) takes
// CLOCK_MONOTONIC ns as the call's last act — see
// sk_assign_dedup_batch: the GIL's return, measured from outside it.
void sk_decide_reconstruct(
    const uint32_t* afters_g, const uint64_t* totals, int64_t g,
    const int32_t* inv, const uint64_t* prefix, const uint32_t* hits,
    const uint32_t* limits, const uint8_t* shadow, int64_t n,
    float near_ratio, int32_t ok_code, int32_t over_code,
    int32_t* out_codes, int64_t* out_remaining, int64_t* out_befores,
    int64_t* out_afters, int64_t* out_over, int64_t* out_near,
    int64_t* out_within, int64_t* out_shadow, uint8_t* out_set_lc,
    int64_t* out_done_ns) {
  // Per-group 'before' once (engine.py _decide_host): saturated groups
  // pin before at u32 max so every lane lands fully-over.
  std::vector<uint64_t> before_g(static_cast<size_t>(g));
  for (int64_t k = 0; k < g; ++k) {
    const uint64_t ag = afters_g[k];
    const uint64_t t = totals[k];
    before_g[k] = (ag >= kU32Max) ? kU32Max : ag - (t < ag ? t : ag);
  }

  for (int64_t i = 0; i < n; ++i) {
    const uint64_t before_u64 = before_g[inv[i]] + prefix[i];
    const int64_t h = hits[i];
    uint64_t after_u64 = before_u64 + static_cast<uint64_t>(hits[i]);
    if (after_u64 > kU32Max) after_u64 = kU32Max;
    const int64_t before =
        static_cast<int64_t>(before_u64 > kU32Max ? kU32Max : before_u64);
    const int64_t after = static_cast<int64_t>(after_u64);
    const int64_t limit = limits[i];
    // float32 near threshold (base_limiter.go:94).
    const int64_t near = static_cast<int64_t>(
        std::floor(static_cast<float>(limit) * near_ratio));

    out_befores[i] = before;
    out_afters[i] = after;
    int64_t over_d = 0, near_d = 0, within_d = 0, shadow_d = 0;
    int64_t remaining = 0;
    int32_t code;
    uint8_t set_lc = 0;
    if (after > limit) {
      code = over_code;
      set_lc = 1;
      if (before >= limit) {
        over_d = h;
      } else {
        over_d = after - limit;
        near_d = limit - (near > before ? near : before);
      }
      if (shadow[i]) {
        code = ok_code;
        shadow_d = h;
      }
    } else {
      code = ok_code;
      remaining = limit - after;
      within_d = h;
      if (after > near) near_d = (before >= near) ? h : after - near;
    }
    out_codes[i] = code;
    out_remaining[i] = remaining;
    out_over[i] = over_d;
    out_near[i] = near_d;
    out_within[i] = within_d;
    out_shadow[i] = shadow_d;
    out_set_lc[i] = set_lc;
  }
  if (out_done_ns) {
    timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    *out_done_ns = static_cast<int64_t>(ts.tv_sec) * 1000000000 + ts.tv_nsec;
  }
}

}  // extern "C"

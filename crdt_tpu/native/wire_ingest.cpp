// wire_ingest — bulk ORSWOT wire-format decode straight into dense planes.
//
// The framework's wire codec (crdt_tpu/utils/serde.py, a deterministic
// varint/tag format — deliberately NOT the reference's bincode) is the
// replication payload: states arrive as byte blobs.  The Python decode
// path materializes a scalar Orswot per blob and then bulk-converts
// (~170k obj/s at 1M objects, reports/INGEST_PROFILE.md) — three orders
// off the north-star <1s end-to-end story.  This translation unit is the
// bulk path the reference's host serde (lib.rs:62-83) maps to: parse the
// blobs IN PARALLEL directly into the dense SoA planes, no Python objects
// anywhere.
//
// Fast-path grammar (the subset covering integer actors/members — the
// dense device types' native domain; any blob outside it is flagged for
// the Python fallback, never mis-parsed):
//
//   ORSWOT    := 0x26 clock_body entries deferred
//   clock_body:= uv n, n * pair
//   pair      := 0x03 uv zz(actor) 0x03 uv zz(counter)
//   entries   := uv n, n * ( 0x03 uv zz(member) 0x20 clock_body )
//   deferred  := uv n, n * ( clock_key uv m, m * (0x03 uv zz(member)) )
//   clock_key := 0x08 uv k, k * ( 0x08 uv(2) 0x03 uv zz(actor)
//                                            0x03 uv zz(counter) )
//
// (uv = unsigned LEB128 varint, zz = zigzag; tags from serde.py: 0x03 int,
// 0x08 tuple, 0x20 vclock, 0x26 orswot.)
//
// Identity interning: the caller guarantees a Universe whose actor index
// IS the actor value (< A) and whose member id IS the member value
// (int32) — see crdt_tpu.utils.interning.IdentityRegistry.  Named
// universes (str/bytes actors and members) take the same grammar with
// names for keys: the named codec at the end of this file.  Counters
// beyond the counter dtype flag the blob for fallback (the Python path
// raises OverflowError at the numpy conversion; the fast path must never
// silently wrap a causal counter).
//
// Per-object status codes (status[i]):
//   0 ok    1 fallback (structure outside the fast-path grammar)
//   2 member overflow (> M)      3 deferred overflow (> D)
//   4 actor out of range (>= A or negative)
//   5 (named codec only) a name the table has not interned yet
//
// Each object writes only its own rows, so the object loop is
// embarrassingly parallel (OpenMP).

#include <algorithm>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <limits>
#include <mutex>
#include <numeric>
#include <shared_mutex>
#include <vector>

#if defined(_OPENMP)
#include <omp.h>
#endif

namespace {

constexpr uint8_t kTagInt = 0x03;
constexpr uint8_t kTagTuple = 0x08;
constexpr uint8_t kTagVClock = 0x20;
constexpr uint8_t kTagPNCounter = 0x23;  // 0x22 (gcounter) arrives via the
                                         // clockish codec's tag parameter
constexpr uint8_t kTagLWW = 0x24;
constexpr uint8_t kTagMVReg = 0x25;
constexpr uint8_t kTagOrswot = 0x26;
constexpr uint8_t kTagGSet = 0x28;
constexpr int32_t kEmpty = -1;

struct Cursor {
  const uint8_t* p;
  const uint8_t* end;

  bool byte(uint8_t want) {
    if (p >= end || *p != want) return false;
    ++p;
    return true;
  }

  // unsigned LEB128, capped at the u64 range — anything longer (or any
  // byte contributing bits past 2^64) is a legitimate big-int payload
  // the fast path hands to Python rather than silently truncating.
  // Only the minimal encoding is taken: the parsers compare and look
  // keys up by their wire bytes, which stand for the value only when
  // the value has one encoding (serde's reader also takes an overlong
  // varint — a last byte of 0 after the first — so such a blob goes to
  // Python, which decodes it by value)
  bool uv(uint64_t* out) {
    uint64_t v = 0;
    int shift = 0;
    for (int i = 0; i < 10; ++i) {
      if (p >= end) return false;
      uint8_t b = *p++;
      if (shift == 63 && (b & 0x7F) > 1) return false;  // bits >= 2^64
      v |= static_cast<uint64_t>(b & 0x7F) << shift;
      if (!(b & 0x80)) {
        if (i > 0 && b == 0) return false;  // overlong
        *out = v;
        return true;
      }
      shift += 7;
    }
    return false;
  }

  // a zigzagged NON-NEGATIVE int (actors/members/counters are never
  // negative in valid states; negative means fallback)
  bool nonneg(uint64_t* out) {
    uint64_t z;
    if (!byte(kTagInt) || !uv(&z)) return false;
    if (z & 1) return false;  // negative
    *out = z >> 1;
    return true;
  }
};

// defined with the egress helpers below; declared here for the Map
// parser's canonical-order checks
bool varint_bytes_less(uint64_t za, uint64_t zb);

// python-bytes comparison of two encoded keys: lexicographic,
// shorter-prefix-first (the order of serde.py's sorted() over encoded
// key bytes)
inline bool span_less(const uint8_t* a, size_t la, const uint8_t* b,
                      size_t lb) {
  const size_t m = la < lb ? la : lb;
  const int c = std::memcmp(a, b, m);
  return c < 0 || (c == 0 && la < lb);
}

// Key readers: how an actor or member key on the wire becomes a dense
// index.  ``IntKeys`` is the identity universe (the key IS the index);
// ``NamedKeys`` (the named codec, below) looks names up in a table.
// Both return a status code (0 ok, 1 fallback); the caller range-checks
// an actor against A once its counter is read (status 4).
struct IntKeys {
  // 0x03 zz(actor): the actor column
  int actor(Cursor& c, uint64_t* out) const { return c.nonneg(out) ? 0 : 1; }
  // 0x03 zz(member), a member id in the int32 id space
  int member(Cursor& c, int32_t* out) const {
    uint64_t m;
    if (!c.nonneg(&m)) return 1;
    if (m > 0x7FFFFFFFull) return 1;  // beyond int32 id space
    *out = static_cast<int32_t>(m);
    return 0;
  }
};

// deferred section (shared by ORSWOT and Map): uv groups, each a
// clock-key tuple + member/key list.  One dense row per (clock, id)
// pair; the witnessing clock is decoded once into a thread-local
// scratch row and copied to every row buffered under it (matches
// from_scalar's layout: `for member in members: one row sharing the
// clock columns`).
template <typename C, typename K = IntKeys>
int parse_deferred_section(Cursor& c, int64_t A, int64_t D, int32_t* d_ids,
                           C* d_clocks, const K& keys = K{}) {
  constexpr uint64_t kCounterMax = static_cast<uint64_t>(~C{0});
  uint64_t n;
  if (!c.uv(&n)) return 1;
  static thread_local std::vector<C> scratch;
  int64_t drow = 0;
  // canonical-order enforcement (same rationale as the entry/key checks:
  // to_binary emits groups strictly ascending in encoded clock-key
  // bytes and members strictly ascending within a group — a duplicate
  // group or member would buffer extra dense rows where the Python
  // decode dedupes via dict/set, so non-canonical input falls back)
  const uint8_t* prev_key = nullptr;
  size_t prev_key_len = 0;
  for (uint64_t q = 0; q < n; ++q) {
    const uint8_t* key_start = c.p;
    if (!c.byte(kTagTuple)) return 1;
    uint64_t k;
    if (!c.uv(&k)) return 1;
    scratch.assign(static_cast<size_t>(A), C{0});
    for (uint64_t i = 0; i < k; ++i) {
      uint64_t two, actor, counter;
      if (!c.byte(kTagTuple) || !c.uv(&two) || two != 2) return 1;
      if (int st = keys.actor(c, &actor)) return st;
      if (!c.nonneg(&counter)) return 1;
      if (actor >= static_cast<uint64_t>(A)) return 4;
      if (counter > kCounterMax) return 1;
      scratch[actor] = static_cast<C>(counter);
    }
    const size_t key_len = static_cast<size_t>(c.p - key_start);
    // strictly ascending encoded clock-key bytes (the egress group
    // comparator)
    if (q > 0 && !span_less(prev_key, prev_key_len, key_start, key_len))
      return 1;
    prev_key = key_start;
    prev_key_len = key_len;
    uint64_t m;
    if (!c.uv(&m)) return 1;
    const uint8_t* prev_member = nullptr;
    size_t prev_len = 0;
    for (uint64_t j = 0; j < m; ++j) {
      const uint8_t* start = c.p;
      int32_t member;
      if (int st = keys.member(c, &member)) return st;
      const size_t len = static_cast<size_t>(c.p - start);
      if (j > 0 && !span_less(prev_member, prev_len, start, len)) return 1;
      prev_member = start;
      prev_len = len;
      if (drow >= D) return 3;
      std::memcpy(d_clocks + drow * A, scratch.data(), sizeof(C) * A);
      d_ids[drow] = member;
      ++drow;
    }
  }
  return 0;
}

// one full ORSWOT value from the cursor (tag 0x26 through the deferred
// section, NO end-of-blob check) — shared by the top-level blob parser
// and the Map<K, Orswot> entry values
template <typename C, typename K = IntKeys>
int parse_orswot_value(Cursor& c, int64_t A, int64_t M, int64_t D, C* clock,
                       int32_t* ids, C* dots, int32_t* d_ids, C* d_clocks,
                       const K& keys = K{}) {
  // counters beyond the counter dtype are NOT wrapped: the Python path
  // (numpy conversion) raises OverflowError, so the fast path flags the
  // blob for fallback and lets that exact behavior happen
  constexpr uint64_t kCounterMax = static_cast<uint64_t>(~C{0});
  if (!c.byte(kTagOrswot)) return 1;

  uint64_t n;
  // set clock
  if (!c.uv(&n)) return 1;
  for (uint64_t i = 0; i < n; ++i) {
    uint64_t actor, counter;
    if (int st = keys.actor(c, &actor)) return st;
    if (!c.nonneg(&counter)) return 1;
    if (actor >= static_cast<uint64_t>(A)) return 4;
    if (counter > kCounterMax) return 1;
    clock[actor] = static_cast<C>(counter);
  }

  // member entries (dense slots in wire order — the same order the
  // Python fallback's from_binary hands from_scalar).  Members must be
  // strictly ascending in encoded-key-bytes order — what to_binary
  // always emits; a duplicate would silently yield two live slots where
  // the Python dict decode dedupes into one, so anything non-canonical
  // falls back to the Python path (which dedupes/handles it ITS way)
  if (!c.uv(&n)) return 1;
  if (n > static_cast<uint64_t>(M)) return 2;
  const uint8_t* prev_member = nullptr;
  size_t prev_len = 0;
  for (uint64_t e = 0; e < n; ++e) {
    const uint8_t* start = c.p;
    if (int st = keys.member(c, ids + e)) return st;
    const size_t len = static_cast<size_t>(c.p - start);
    if (e > 0 && !span_less(prev_member, prev_len, start, len)) return 1;
    prev_member = start;
    prev_len = len;
    if (!c.byte(kTagVClock)) return 1;
    uint64_t k;
    if (!c.uv(&k)) return 1;
    C* row = dots + e * A;
    for (uint64_t i = 0; i < k; ++i) {
      uint64_t actor, counter;
      if (int st = keys.actor(c, &actor)) return st;
      if (!c.nonneg(&counter)) return 1;
      if (actor >= static_cast<uint64_t>(A)) return 4;
      if (counter > kCounterMax) return 1;
      row[actor] = static_cast<C>(counter);
    }
  }

  // deferred: one dense row per (clock, member) pair
  return parse_deferred_section<C>(c, A, D, d_ids, d_clocks, keys);
}

template <typename C, typename K = IntKeys>
int parse_one(const uint8_t* buf, int64_t lo, int64_t hi, int64_t A,
              int64_t M, int64_t D, C* clock, int32_t* ids, C* dots,
              int32_t* d_ids, C* d_clocks, const K& keys = K{}) {
  Cursor c{buf + lo, buf + hi};
  int st = parse_orswot_value<C>(c, A, M, D, clock, ids, dots, d_ids,
                                 d_clocks, keys);
  if (st) return st;
  if (c.p != c.end) return 1;  // trailing bytes: not a lone ORSWOT blob
  return 0;
}

template <typename C>
void clear_orswot_row(int64_t A, int64_t M, int64_t D, C* clock, int32_t* ids,
                      C* dots, int32_t* d_ids, C* d_clocks) {
  std::memset(clock, 0, sizeof(C) * A);
  std::memset(dots, 0, sizeof(C) * M * A);
  std::memset(d_clocks, 0, sizeof(C) * D * A);
  for (int64_t j = 0; j < M; ++j) ids[j] = kEmpty;
  for (int64_t j = 0; j < D; ++j) d_ids[j] = kEmpty;
}

// ``clear`` != 0: zero each object's output rows before parsing, so the
// caller may hand REUSED buffers (the pipelined loop's staging planes —
// a fresh np.zeros alloc per chunk page-faults ~GBs and was the measured
// e2e ingest collapse, PERF.md).  0 keeps the historical contract
// (caller pre-zeroed the planes) and skips the memset pass.
template <typename C, typename K = IntKeys>
int64_t ingest_impl(const uint8_t* buf, const int64_t* offsets, int64_t n,
                    int64_t A, int64_t M, int64_t D, C* clock, int32_t* ids,
                    C* dots, int32_t* d_ids, C* d_clocks, uint8_t* status,
                    int64_t clear, const K& keys = K{}) {
  int64_t bad = 0;
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 1024) reduction(+ : bad)
#endif
  for (int64_t i = 0; i < n; ++i) {
    if (clear)
      clear_orswot_row<C>(A, M, D, clock + i * A, ids + i * M,
                          dots + i * M * A, d_ids + i * D, d_clocks + i * D * A);
    int st = parse_one<C>(buf, offsets[i], offsets[i + 1], A, M, D,
                          clock + i * A, ids + i * M, dots + i * M * A,
                          d_ids + i * D, d_clocks + i * D * A, keys);
    status[i] = static_cast<uint8_t>(st);
    if (st != 0) {
      // leave the row pristine for the Python fallback / error report
      clear_orswot_row<C>(A, M, D, clock + i * A, ids + i * M,
                          dots + i * M * A, d_ids + i * D, d_clocks + i * D * A);
      ++bad;
    }
  }
  return bad;
}

// ---- bulk wire EGRESS: dense planes -> serde blobs -------------------------
//
// The inverse direction, byte-identical to
// `to_binary(batch.to_scalar(uni)[i])` for identity universes.  Three
// distinct deterministic orderings must be reproduced exactly
// (serde.py):
//   * pair/item lists sort by the ENCODED BYTES of the key
//     (enc_pairs_sorted / enc_items_sorted — python bytes comparison:
//     lexicographic, shorter-prefix-first),
//   * ClockKey tuples (deferred keys) sort their (actor, counter) pairs
//     by repr(actor) — DECIMAL-STRING order for ints (vclock.py key()),
//   * deferred GROUPS sort by the encoded bytes of the whole clock-key
//     tuple.

struct Emitter {
  uint8_t* p;      // nullptr = counting pass
  int64_t count = 0;

  void byte(uint8_t b) {
    if (p) *p++ = b;
    ++count;
  }

  void uv(uint64_t v) {
    while (true) {
      uint8_t b = v & 0x7F;
      v >>= 7;
      if (v) {
        byte(b | 0x80);
      } else {
        byte(b);
        return;
      }
    }
  }

  void tagged_nonneg(uint64_t v) {  // 0x03 + zigzag varint
    byte(kTagInt);
    uv(v << 1);
  }

  void raw(const uint8_t* src, int64_t n) {  // an already-encoded value
    if (p) {
      std::memcpy(p, src, static_cast<size_t>(n));
      p += n;
    }
    count += n;
  }
};

inline int write_varint(uint64_t v, uint8_t* out) {
  int n = 0;
  while (true) {
    uint8_t b = v & 0x7F;
    v >>= 7;
    if (v) {
      out[n++] = b | 0x80;
    } else {
      out[n++] = b;
      return n;
    }
  }
}

// python-bytes comparison of two encoded varints (zigzagged values):
// lexicographic, shorter-prefix-first
inline bool varint_bytes_less(uint64_t za, uint64_t zb) {
  uint8_t a[10], b[10];
  int la = write_varint(za, a), lb = write_varint(zb, b);
  int m = la < lb ? la : lb;
  int c = std::memcmp(a, b, static_cast<size_t>(m));
  if (c) return c < 0;
  return la < lb;
}

// repr-string (decimal) comparison of two non-negative ints —
// vclock.py's ClockKey pair order
inline bool decimal_repr_less(uint64_t a, uint64_t b) {
  char sa[24], sb[24];
  int la = std::snprintf(sa, sizeof(sa), "%llu",
                         static_cast<unsigned long long>(a));
  int lb = std::snprintf(sb, sizeof(sb), "%llu",
                         static_cast<unsigned long long>(b));
  int m = la < lb ? la : lb;
  int c = std::memcmp(sa, sb, static_cast<size_t>(m));
  if (c) return c < 0;
  return la < lb;
}

// Key writers: how a dense actor column or member id goes back on the
// wire, and the two orders serde gives keys.  ``IntEnc`` is the
// identity universe; ``NamedEnc`` (the named codec, below) writes names
// from a table.
struct IntEnc {
  void actor(Emitter& e, int64_t a) const {
    e.tagged_nonneg(static_cast<uint64_t>(a));
  }
  // keys are 0x03 + varint(2a): shared tag, so encoded-bytes order is
  // the varint-bytes order of 2a
  bool actor_less(int64_t x, int64_t y) const {
    return varint_bytes_less(static_cast<uint64_t>(x) << 1,
                             static_cast<uint64_t>(y) << 1);
  }
  // ClockKey pair order: repr(actor), the decimal string for ints
  bool actor_repr_less(int64_t x, int64_t y) const {
    return decimal_repr_less(static_cast<uint64_t>(x),
                             static_cast<uint64_t>(y));
  }
  void member(Emitter& e, int32_t m) const {
    e.tagged_nonneg(static_cast<uint64_t>(static_cast<uint32_t>(m)));
  }
  bool member_less(int32_t x, int32_t y) const {
    return varint_bytes_less(
        static_cast<uint64_t>(static_cast<uint32_t>(x)) << 1,
        static_cast<uint64_t>(static_cast<uint32_t>(y)) << 1);
  }
};

// emit one vclock BODY (uv n + sorted pairs) from a dense counter row.
// ``sorted=false`` skips the order work — the SIZE of the body is
// order-invariant, so the counting pass never pays for sorts.
template <typename C, typename K = IntEnc>
void emit_clock_body(Emitter& e, const C* row, int64_t A,
                     std::vector<int64_t>& idx, bool sorted = true,
                     const K& keys = K{}) {
  idx.clear();
  for (int64_t a = 0; a < A; ++a)
    if (row[a]) idx.push_back(a);
  if (sorted)
    std::sort(idx.begin(), idx.end(), [&](int64_t x, int64_t y) {
      return keys.actor_less(x, y);
    });
  e.uv(static_cast<uint64_t>(idx.size()));
  for (int64_t a : idx) {
    keys.actor(e, a);
    e.tagged_nonneg(static_cast<uint64_t>(row[a]));
  }
}

// the encoded clock-KEY tuple for a deferred group (0x08 uv k + pairs
// as 2-tuples, pair order = decimal repr of the actor)
template <typename C, typename K = IntEnc>
void emit_clock_key(Emitter& e, const C* row, int64_t A,
                    std::vector<int64_t>& idx, bool sorted = true,
                    const K& keys = K{}) {
  idx.clear();
  for (int64_t a = 0; a < A; ++a)
    if (row[a]) idx.push_back(a);
  if (sorted)
    std::sort(idx.begin(), idx.end(), [&](int64_t x, int64_t y) {
      return keys.actor_repr_less(x, y);
    });
  e.byte(kTagTuple);
  e.uv(static_cast<uint64_t>(idx.size()));
  for (int64_t a : idx) {
    e.byte(kTagTuple);
    e.uv(2);
    keys.actor(e, a);
    e.tagged_nonneg(static_cast<uint64_t>(row[a]));
  }
}

// deferred section on egress (shared by ORSWOT and Map): group live
// rows by identical clock rows; each group is (encoded clock key,
// sorted member blobs); groups sort by the encoded clock-key bytes.
// D is small (a handful of rows), so the quadratic grouping is free.
template <typename C, typename K = IntEnc>
void emit_deferred_section(Emitter& e, const int32_t* d_ids,
                           const C* d_clocks, int64_t A, int64_t D,
                           std::vector<int64_t>& scratch, bool sizing,
                           const K& keys = K{}) {
  std::vector<int64_t> rows;
  for (int64_t r = 0; r < D; ++r)
    if (d_ids[r] != kEmpty) rows.push_back(r);
  std::vector<char> used(rows.size(), 0);
  struct Group {
    const C* crow;                   // the witnessing clock's dense row
    std::vector<uint8_t> key;        // encoded clock-key tuple (write pass)
    std::vector<int32_t> members;    // member ids, deduped
  };
  std::vector<Group> groups;
  for (size_t i = 0; i < rows.size(); ++i) {
    if (used[i]) continue;
    Group g;
    g.crow = d_clocks + rows[i] * A;
    g.members.push_back(d_ids[rows[i]]);
    for (size_t j = i + 1; j < rows.size(); ++j) {
      if (used[j]) continue;
      const C* orow = d_clocks + rows[j] * A;
      bool same = true;
      for (int64_t a = 0; a < A; ++a)
        if (g.crow[a] != orow[a]) {
          same = false;
          break;
        }
      if (same) {
        used[j] = 1;
        g.members.push_back(d_ids[rows[j]]);
      }
    }
    // python set() deduplicates members buffered under one clock (dense
    // rows never legitimately repeat a (clock, member) pair, but match
    // to_binary on any input); dedup changes the SIZE, so both passes
    // run it — the sort is its implementation, members lists are tiny
    std::sort(g.members.begin(), g.members.end(),
              [&](int32_t x, int32_t y) { return keys.member_less(x, y); });
    g.members.erase(std::unique(g.members.begin(), g.members.end()),
                    g.members.end());
    if (!sizing) {
      // stage the encoded clock key for the cross-group sort
      Emitter cnt{nullptr};
      emit_clock_key(cnt, g.crow, A, scratch, true, keys);
      g.key.resize(static_cast<size_t>(cnt.count));
      Emitter w{g.key.data()};
      emit_clock_key(w, g.crow, A, scratch, true, keys);
    }
    groups.push_back(std::move(g));
  }
  if (!sizing)
    std::sort(groups.begin(), groups.end(),
              [](const Group& x, const Group& y) {
                size_t m = x.key.size() < y.key.size() ? x.key.size()
                                                       : y.key.size();
                int c = std::memcmp(x.key.data(), y.key.data(), m);
                if (c) return c < 0;
                return x.key.size() < y.key.size();
              });
  e.uv(static_cast<uint64_t>(groups.size()));
  for (const Group& g : groups) {
    if (sizing) {
      emit_clock_key(e, g.crow, A, scratch, false, keys);
    } else {
      for (uint8_t b : g.key) e.byte(b);
    }
    e.uv(static_cast<uint64_t>(g.members.size()));
    for (int32_t m : g.members) keys.member(e, m);
  }
}

template <typename C, typename K = IntEnc>
int64_t encode_one(const C* clock, const int32_t* ids, const C* dots,
                   const int32_t* d_ids, const C* d_clocks, int64_t A,
                   int64_t M, int64_t D, uint8_t* out, const K& keys = K{}) {
  // out == nullptr is the counting pass: every blob's SIZE is
  // order-invariant, so the sorts (and group-key staging buffers) are
  // skipped there — the write pass alone pays for ordering
  const bool sizing = (out == nullptr);
  Emitter e{out};
  std::vector<int64_t> scratch;
  e.byte(kTagOrswot);
  emit_clock_body(e, clock, A, scratch, !sizing, keys);

  // entries: member keys sorted by encoded bytes
  std::vector<int64_t> slots;
  for (int64_t s = 0; s < M; ++s)
    if (ids[s] != kEmpty) slots.push_back(s);
  if (!sizing)
    std::sort(slots.begin(), slots.end(), [&](int64_t x, int64_t y) {
      return keys.member_less(ids[x], ids[y]);
    });
  e.uv(static_cast<uint64_t>(slots.size()));
  for (int64_t s : slots) {
    keys.member(e, ids[s]);
    e.byte(kTagVClock);
    emit_clock_body(e, dots + s * A, A, scratch, !sizing, keys);
  }

  // deferred section
  emit_deferred_section(e, d_ids, d_clocks, A, D, scratch, sizing, keys);
  return e.count;
}

template <typename C>
void encode_impl(const C* clock, const int32_t* ids, const C* dots,
                 const int32_t* d_ids, const C* d_clocks, int64_t n,
                 int64_t A, int64_t M, int64_t D, int64_t* offsets,
                 uint8_t* buf) {
  if (buf == nullptr) {
    // pass 1: per-object sizes into offsets[1..n] (caller prefix-sums)
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 1024)
#endif
    for (int64_t i = 0; i < n; ++i)
      offsets[i + 1] = encode_one<C>(clock + i * A, ids + i * M,
                                     dots + i * M * A, d_ids + i * D,
                                     d_clocks + i * D * A, A, M, D, nullptr);
    return;
  }
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 1024)
#endif
  for (int64_t i = 0; i < n; ++i)
    encode_one<C>(clock + i * A, ids + i * M, dots + i * M * A,
                  d_ids + i * D, d_clocks + i * D * A, A, M, D,
                  buf + offsets[i]);
}

// ---- MVReg wire codec ------------------------------------------------------
//
// MVREG := 0x25 uv n, n * ( clock_body, 0x03 zz(val) )  — pair blobs
// sorted by their full encoded bytes (serde.py MVReg branch); clock_body
// pairs sorted by encoded key bytes.  Dense layout: clocks[K, A] +
// vals[K], slot live iff clock non-empty.

template <typename C>
int parse_mvreg_one(const uint8_t* buf, int64_t lo, int64_t hi, int64_t K,
                    int64_t A, C* clocks, C* vals) {
  constexpr uint64_t kCounterMax = static_cast<uint64_t>(~C{0});
  Cursor c{buf + lo, buf + hi};
  if (!c.byte(kTagMVReg)) return 1;
  uint64_t n;
  if (!c.uv(&n)) return 1;
  if (n > static_cast<uint64_t>(K)) return 2;
  for (uint64_t j = 0; j < n; ++j) {
    uint64_t k;
    if (!c.uv(&k)) return 1;
    C* row = clocks + j * A;
    for (uint64_t i = 0; i < k; ++i) {
      uint64_t actor, counter;
      if (!c.nonneg(&actor) || !c.nonneg(&counter)) return 1;
      if (actor >= static_cast<uint64_t>(A)) return 4;
      if (counter > kCounterMax) return 1;
      row[actor] = static_cast<C>(counter);
    }
    uint64_t val;
    if (!c.nonneg(&val)) return 1;
    // payload ids live in the identity registry's int32 space AND the
    // vals plane's counter dtype
    if (val > 0x7FFFFFFFull || val > kCounterMax) return 1;
    vals[j] = static_cast<C>(val);
  }
  if (c.p != c.end) return 1;
  return 0;
}

template <typename C>
int64_t mvreg_encode_one(const C* clocks, const C* vals, int64_t K,
                         int64_t A, uint8_t* out) {
  const bool sizing = (out == nullptr);
  std::vector<int64_t> scratch;
  // stage each live slot's pair blob (clock body + tagged val); the
  // cross-slot sort is by full blob bytes, which only the write pass
  // pays for (sizes are order-invariant)
  std::vector<std::vector<uint8_t>> blobs;
  int64_t blob_bytes = 0;
  int64_t n_live = 0;
  for (int64_t j = 0; j < K; ++j) {
    const C* row = clocks + j * A;
    bool live = false;
    for (int64_t a = 0; a < A; ++a)
      if (row[a]) {
        live = true;
        break;
      }
    if (!live) continue;
    ++n_live;
    Emitter cnt{nullptr};
    emit_clock_body(cnt, row, A, scratch, false);
    cnt.tagged_nonneg(static_cast<uint64_t>(vals[j]));
    blob_bytes += cnt.count;
    if (sizing) continue;
    std::vector<uint8_t> b(static_cast<size_t>(cnt.count));
    Emitter w{b.data()};
    emit_clock_body(w, row, A, scratch);
    w.tagged_nonneg(static_cast<uint64_t>(vals[j]));
    blobs.push_back(std::move(b));
  }
  Emitter e{out};
  e.byte(kTagMVReg);
  e.uv(static_cast<uint64_t>(n_live));
  if (sizing) return e.count + blob_bytes;
  std::sort(blobs.begin(), blobs.end(),
            [](const std::vector<uint8_t>& x, const std::vector<uint8_t>& y) {
              size_t m = x.size() < y.size() ? x.size() : y.size();
              int c = std::memcmp(x.data(), y.data(), m);
              if (c) return c < 0;
              return x.size() < y.size();
            });
  for (const auto& b : blobs)
    for (uint8_t x : b) e.byte(x);
  return e.count;
}

// ---- LWWReg wire codec -----------------------------------------------------
//
// LWWREG := 0x24 0x03 zz(val) 0x03 zz(marker).  Dense: vals[N] (payload
// ids) + markers[N], both u64 (markers are timestamps — lwwreg.rs:16-24).

// ---- GSet wire codec -------------------------------------------------------
//
// GSET := 0x28 uv n, n * (0x03 zz(member)) — items sorted by encoded
// bytes (serde.py enc_items_sorted).  Dense: bool bitmap[U], member id
// == bit index (identity universes).

inline int parse_gset_one(const uint8_t* buf, int64_t lo, int64_t hi,
                          int64_t U, uint8_t* bits) {
  Cursor c{buf + lo, buf + hi};
  if (!c.byte(kTagGSet)) return 1;
  uint64_t n;
  if (!c.uv(&n)) return 1;
  for (uint64_t i = 0; i < n; ++i) {
    uint64_t member;
    if (!c.nonneg(&member)) return 1;
    // beyond the identity registry's int32 id space: fall back so the
    // Python path raises ITS error, like every other leg
    if (member > 0x7FFFFFFFull) return 1;
    if (member >= static_cast<uint64_t>(U)) return 2;  // bitmap overflow
    bits[member] = 1;
  }
  if (c.p != c.end) return 1;
  return 0;
}

inline int64_t gset_encode_one(const uint8_t* bits, int64_t U, uint8_t* out) {
  const bool sizing = (out == nullptr);
  Emitter e{out};
  std::vector<int64_t> members;
  for (int64_t m = 0; m < U; ++m)
    if (bits[m]) members.push_back(m);
  if (!sizing)
    std::sort(members.begin(), members.end(), [](int64_t x, int64_t y) {
      return varint_bytes_less(static_cast<uint64_t>(x) << 1,
                               static_cast<uint64_t>(y) << 1);
    });
  e.byte(kTagGSet);
  e.uv(static_cast<uint64_t>(members.size()));
  for (int64_t m : members) e.tagged_nonneg(static_cast<uint64_t>(m));
  return e.count;
}

inline int parse_lww_one(const uint8_t* buf, int64_t lo, int64_t hi,
                         uint64_t* val, uint64_t* marker) {
  Cursor c{buf + lo, buf + hi};
  if (!c.byte(kTagLWW)) return 1;
  uint64_t v, m;
  if (!c.nonneg(&v)) return 1;
  if (v > 0x7FFFFFFFull) return 1;  // identity payload id space
  if (!c.nonneg(&m)) return 1;
  if (c.p != c.end) return 1;
  *val = v;
  *marker = m;
  return 0;
}

inline int64_t lww_encode_one(uint64_t val, uint64_t marker, uint8_t* out) {
  Emitter e{out};
  e.byte(kTagLWW);
  e.tagged_nonneg(val);
  e.tagged_nonneg(marker);
  return e.count;
}

}  // namespace

extern "C" {

int64_t mvreg_ingest_wire_u32(const uint8_t* buf, const int64_t* offsets,
                              int64_t n, int64_t K, int64_t A,
                              uint32_t* clocks, uint32_t* vals,
                              uint8_t* status) {
  int64_t bad = 0;
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 1024) reduction(+ : bad)
#endif
  for (int64_t i = 0; i < n; ++i) {
    int st = parse_mvreg_one<uint32_t>(buf, offsets[i], offsets[i + 1], K, A,
                                       clocks + i * K * A, vals + i * K);
    status[i] = static_cast<uint8_t>(st);
    if (st != 0) {
      std::memset(clocks + i * K * A, 0, sizeof(uint32_t) * K * A);
      std::memset(vals + i * K, 0, sizeof(uint32_t) * K);
      ++bad;
    }
  }
  return bad;
}

int64_t mvreg_ingest_wire_u64(const uint8_t* buf, const int64_t* offsets,
                              int64_t n, int64_t K, int64_t A,
                              uint64_t* clocks, uint64_t* vals,
                              uint8_t* status) {
  int64_t bad = 0;
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 1024) reduction(+ : bad)
#endif
  for (int64_t i = 0; i < n; ++i) {
    int st = parse_mvreg_one<uint64_t>(buf, offsets[i], offsets[i + 1], K, A,
                                       clocks + i * K * A, vals + i * K);
    status[i] = static_cast<uint8_t>(st);
    if (st != 0) {
      std::memset(clocks + i * K * A, 0, sizeof(uint64_t) * K * A);
      std::memset(vals + i * K, 0, sizeof(uint64_t) * K);
      ++bad;
    }
  }
  return bad;
}

void mvreg_encode_wire_u32(const uint32_t* clocks, const uint32_t* vals,
                           int64_t n, int64_t K, int64_t A, int64_t* offsets,
                           uint8_t* buf) {
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 1024)
#endif
  for (int64_t i = 0; i < n; ++i) {
    if (buf == nullptr)
      offsets[i + 1] = mvreg_encode_one<uint32_t>(
          clocks + i * K * A, vals + i * K, K, A, nullptr);
    else
      mvreg_encode_one<uint32_t>(clocks + i * K * A, vals + i * K, K, A,
                                 buf + offsets[i]);
  }
}

void mvreg_encode_wire_u64(const uint64_t* clocks, const uint64_t* vals,
                           int64_t n, int64_t K, int64_t A, int64_t* offsets,
                           uint8_t* buf) {
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 1024)
#endif
  for (int64_t i = 0; i < n; ++i) {
    if (buf == nullptr)
      offsets[i + 1] = mvreg_encode_one<uint64_t>(
          clocks + i * K * A, vals + i * K, K, A, nullptr);
    else
      mvreg_encode_one<uint64_t>(clocks + i * K * A, vals + i * K, K, A,
                                 buf + offsets[i]);
  }
}

int64_t gset_ingest_wire(const uint8_t* buf, const int64_t* offsets,
                         int64_t n, int64_t U, uint8_t* bits,
                         uint8_t* status) {
  int64_t bad = 0;
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 2048) reduction(+ : bad)
#endif
  for (int64_t i = 0; i < n; ++i) {
    int st = parse_gset_one(buf, offsets[i], offsets[i + 1], U, bits + i * U);
    status[i] = static_cast<uint8_t>(st);
    if (st != 0) {
      std::memset(bits + i * U, 0, static_cast<size_t>(U));
      ++bad;
    }
  }
  return bad;
}

void gset_encode_wire(const uint8_t* bits, int64_t n, int64_t U,
                      int64_t* offsets, uint8_t* buf) {
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 2048)
#endif
  for (int64_t i = 0; i < n; ++i) {
    if (buf == nullptr)
      offsets[i + 1] = gset_encode_one(bits + i * U, U, nullptr);
    else
      gset_encode_one(bits + i * U, U, buf + offsets[i]);
  }
}

int64_t lww_ingest_wire_u64(const uint8_t* buf, const int64_t* offsets,
                            int64_t n, uint64_t* vals, uint64_t* markers,
                            uint8_t* status) {
  int64_t bad = 0;
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 4096) reduction(+ : bad)
#endif
  for (int64_t i = 0; i < n; ++i) {
    int st = parse_lww_one(buf, offsets[i], offsets[i + 1], vals + i,
                           markers + i);
    status[i] = static_cast<uint8_t>(st);
    if (st != 0) {
      vals[i] = 0;
      markers[i] = 0;
      ++bad;
    }
  }
  return bad;
}

void lww_encode_wire_u64(const uint64_t* vals, const uint64_t* markers,
                         int64_t n, int64_t* offsets, uint8_t* buf) {
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 4096)
#endif
  for (int64_t i = 0; i < n; ++i) {
    if (buf == nullptr)
      offsets[i + 1] = lww_encode_one(vals[i], markers[i], nullptr);
    else
      lww_encode_one(vals[i], markers[i], buf + offsets[i]);
  }
}

}  // extern "C"

extern "C" {

void orswot_encode_wire_u32(const uint32_t* clock, const int32_t* ids,
                            const uint32_t* dots, const int32_t* d_ids,
                            const uint32_t* d_clocks, int64_t n, int64_t A,
                            int64_t M, int64_t D, int64_t* offsets,
                            uint8_t* buf) {
  encode_impl<uint32_t>(clock, ids, dots, d_ids, d_clocks, n, A, M, D,
                        offsets, buf);
}

void orswot_encode_wire_u64(const uint64_t* clock, const int32_t* ids,
                            const uint64_t* dots, const int32_t* d_ids,
                            const uint64_t* d_clocks, int64_t n, int64_t A,
                            int64_t M, int64_t D, int64_t* offsets,
                            uint8_t* buf) {
  encode_impl<uint64_t>(clock, ids, dots, d_ids, d_clocks, n, A, M, D,
                        offsets, buf);
}

}  // extern "C"

// ---- v10: indexed (gathered) ORSWOT encode --------------------------------
//
// Delta anti-entropy ships only diverged rows (crdt_tpu/sync/delta.py).
// Encoding k selected rows of an n-row fleet straight from the fleet
// planes skips the gather copy a compact sub-plane set would cost per
// delta frame.  Same two-pass contract as encode_impl: nullptr buf is
// the sizing pass (offsets[1..k] get per-row sizes, caller prefix-sums),
// the write pass fills buf at offsets[i].

template <typename C>
void encode_rows_impl(const C* clock, const int32_t* ids, const C* dots,
                      const int32_t* d_ids, const C* d_clocks,
                      const int64_t* rows, int64_t k, int64_t A, int64_t M,
                      int64_t D, int64_t* offsets, uint8_t* buf) {
  if (buf == nullptr) {
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 1024)
#endif
    for (int64_t i = 0; i < k; ++i) {
      const int64_t r = rows[i];
      offsets[i + 1] = encode_one<C>(clock + r * A, ids + r * M,
                                     dots + r * M * A, d_ids + r * D,
                                     d_clocks + r * D * A, A, M, D, nullptr);
    }
    return;
  }
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 1024)
#endif
  for (int64_t i = 0; i < k; ++i) {
    const int64_t r = rows[i];
    encode_one<C>(clock + r * A, ids + r * M, dots + r * M * A,
                  d_ids + r * D, d_clocks + r * D * A, A, M, D,
                  buf + offsets[i]);
  }
}

extern "C" {

void orswot_encode_wire_rows_u32(const uint32_t* clock, const int32_t* ids,
                                 const uint32_t* dots, const int32_t* d_ids,
                                 const uint32_t* d_clocks,
                                 const int64_t* rows, int64_t k, int64_t A,
                                 int64_t M, int64_t D, int64_t* offsets,
                                 uint8_t* buf) {
  encode_rows_impl<uint32_t>(clock, ids, dots, d_ids, d_clocks, rows, k, A,
                             M, D, offsets, buf);
}

void orswot_encode_wire_rows_u64(const uint64_t* clock, const int32_t* ids,
                                 const uint64_t* dots, const int32_t* d_ids,
                                 const uint64_t* d_clocks,
                                 const int64_t* rows, int64_t k, int64_t A,
                                 int64_t M, int64_t D, int64_t* offsets,
                                 uint8_t* buf) {
  encode_rows_impl<uint64_t>(clock, ids, dots, d_ids, d_clocks, rows, k, A,
                             M, D, offsets, buf);
}

}  // extern "C"

extern "C" {

int64_t orswot_ingest_wire_u32(const uint8_t* buf, const int64_t* offsets,
                               int64_t n, int64_t A, int64_t M, int64_t D,
                               uint32_t* clock, int32_t* ids, uint32_t* dots,
                               int32_t* d_ids, uint32_t* d_clocks,
                               uint8_t* status, int64_t clear) {
  return ingest_impl<uint32_t>(buf, offsets, n, A, M, D, clock, ids, dots,
                               d_ids, d_clocks, status, clear);
}

int64_t orswot_ingest_wire_u64(const uint8_t* buf, const int64_t* offsets,
                               int64_t n, int64_t A, int64_t M, int64_t D,
                               uint64_t* clock, int32_t* ids, uint64_t* dots,
                               int32_t* d_ids, uint64_t* d_clocks,
                               uint8_t* status, int64_t clear) {
  return ingest_impl<uint64_t>(buf, offsets, n, A, M, D, clock, ids, dots,
                               d_ids, d_clocks, status, clear);
}

}  // extern "C"

// ---- clock-shaped wire codecs ---------------------------------------------
//
// The remaining wire-friendly batch types are pure clock bodies:
//
//   VCLOCK    := 0x20 clock_body          (vclock.rs — the causality kernel)
//   GCOUNTER  := 0x22 clock_body          (gcounter.rs:26-28 — IS a VClock)
//   PNCOUNTER := 0x23 clock_body clock_body   (pncounter.rs:33-36 — P then N)
//
// clock_body as in the ORSWOT grammar above; pair order on egress is the
// encoded-key-bytes sort emit_clock_body already reproduces.  Dense
// layouts: clocks[N, A] (vclock/gcounter), planes[N, 2, A] (pncounter,
// P = plane 0).  One tag-parameterized implementation serves vclock and
// gcounter; status codes match the other legs (1 fallback, 4 actor out
// of range).

namespace {

template <typename C>
int parse_clock_body(Cursor& c, int64_t A, C* row) {
  constexpr uint64_t kCounterMax = static_cast<uint64_t>(~C{0});
  uint64_t n;
  if (!c.uv(&n)) return 1;
  for (uint64_t i = 0; i < n; ++i) {
    uint64_t actor, counter;
    if (!c.nonneg(&actor) || !c.nonneg(&counter)) return 1;
    if (actor >= static_cast<uint64_t>(A)) return 4;
    if (counter > kCounterMax) return 1;
    // duplicate actor keys canonicalize last-wins, like every other
    // leg's dense scatter (to_binary never emits them)
    row[actor] = static_cast<C>(counter);
  }
  return 0;
}

template <typename C>
int parse_clockish_one(const uint8_t* buf, int64_t lo, int64_t hi,
                       uint8_t tag, int64_t A, C* row) {
  Cursor c{buf + lo, buf + hi};
  if (!c.byte(tag)) return 1;
  int st = parse_clock_body(c, A, row);
  if (st) return st;
  if (c.p != c.end) return 1;
  return 0;
}

template <typename C>
int parse_pncounter_one(const uint8_t* buf, int64_t lo, int64_t hi,
                        int64_t A, C* planes) {
  Cursor c{buf + lo, buf + hi};
  if (!c.byte(kTagPNCounter)) return 1;
  int st = parse_clock_body(c, A, planes);      // P
  if (st) return st;
  st = parse_clock_body(c, A, planes + A);      // N
  if (st) return st;
  if (c.p != c.end) return 1;
  return 0;
}

template <typename C>
int64_t clockish_ingest_impl(const uint8_t* buf, const int64_t* offsets,
                             int64_t n, uint8_t tag, int64_t A, C* clocks,
                             uint8_t* status) {
  int64_t bad = 0;
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 2048) reduction(+ : bad)
#endif
  for (int64_t i = 0; i < n; ++i) {
    int st = parse_clockish_one<C>(buf, offsets[i], offsets[i + 1], tag, A,
                                   clocks + i * A);
    status[i] = static_cast<uint8_t>(st);
    if (st != 0) {
      std::memset(clocks + i * A, 0, sizeof(C) * A);
      ++bad;
    }
  }
  return bad;
}

template <typename C>
int64_t pncounter_ingest_impl(const uint8_t* buf, const int64_t* offsets,
                              int64_t n, int64_t A, C* planes,
                              uint8_t* status) {
  int64_t bad = 0;
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 2048) reduction(+ : bad)
#endif
  for (int64_t i = 0; i < n; ++i) {
    int st = parse_pncounter_one<C>(buf, offsets[i], offsets[i + 1], A,
                                    planes + i * 2 * A);
    status[i] = static_cast<uint8_t>(st);
    if (st != 0) {
      std::memset(planes + i * 2 * A, 0, sizeof(C) * 2 * A);
      ++bad;
    }
  }
  return bad;
}

template <typename C>
int64_t clockish_encode_one(uint8_t tag, const C* row, int64_t A,
                            uint8_t* out) {
  Emitter e{out};
  std::vector<int64_t> scratch;
  e.byte(tag);
  emit_clock_body(e, row, A, scratch, out != nullptr);
  return e.count;
}

template <typename C>
int64_t pncounter_encode_one(const C* planes, int64_t A, uint8_t* out) {
  Emitter e{out};
  std::vector<int64_t> scratch;
  const bool sorted = (out != nullptr);
  e.byte(kTagPNCounter);
  emit_clock_body(e, planes, A, scratch, sorted);
  emit_clock_body(e, planes + A, A, scratch, sorted);
  return e.count;
}

template <typename C>
void clockish_encode_impl(const C* clocks, int64_t n, uint8_t tag, int64_t A,
                          int64_t* offsets, uint8_t* buf) {
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 2048)
#endif
  for (int64_t i = 0; i < n; ++i) {
    if (buf == nullptr)
      offsets[i + 1] = clockish_encode_one<C>(tag, clocks + i * A, A, nullptr);
    else
      clockish_encode_one<C>(tag, clocks + i * A, A, buf + offsets[i]);
  }
}

template <typename C>
void pncounter_encode_impl(const C* planes, int64_t n, int64_t A,
                           int64_t* offsets, uint8_t* buf) {
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 2048)
#endif
  for (int64_t i = 0; i < n; ++i) {
    if (buf == nullptr)
      offsets[i + 1] = pncounter_encode_one<C>(planes + i * 2 * A, A, nullptr);
    else
      pncounter_encode_one<C>(planes + i * 2 * A, A, buf + offsets[i]);
  }
}

}  // namespace

extern "C" {

int64_t clockish_ingest_wire_u32(const uint8_t* buf, const int64_t* offsets,
                                 int64_t n, int64_t tag, int64_t A,
                                 uint32_t* clocks, uint8_t* status) {
  return clockish_ingest_impl<uint32_t>(buf, offsets, n,
                                        static_cast<uint8_t>(tag), A, clocks,
                                        status);
}

int64_t clockish_ingest_wire_u64(const uint8_t* buf, const int64_t* offsets,
                                 int64_t n, int64_t tag, int64_t A,
                                 uint64_t* clocks, uint8_t* status) {
  return clockish_ingest_impl<uint64_t>(buf, offsets, n,
                                        static_cast<uint8_t>(tag), A, clocks,
                                        status);
}

void clockish_encode_wire_u32(const uint32_t* clocks, int64_t n, int64_t tag,
                              int64_t A, int64_t* offsets, uint8_t* buf) {
  clockish_encode_impl<uint32_t>(clocks, n, static_cast<uint8_t>(tag), A,
                                 offsets, buf);
}

void clockish_encode_wire_u64(const uint64_t* clocks, int64_t n, int64_t tag,
                              int64_t A, int64_t* offsets, uint8_t* buf) {
  clockish_encode_impl<uint64_t>(clocks, n, static_cast<uint8_t>(tag), A,
                                 offsets, buf);
}

int64_t pncounter_ingest_wire_u32(const uint8_t* buf, const int64_t* offsets,
                                  int64_t n, int64_t A, uint32_t* planes,
                                  uint8_t* status) {
  return pncounter_ingest_impl<uint32_t>(buf, offsets, n, A, planes, status);
}

int64_t pncounter_ingest_wire_u64(const uint8_t* buf, const int64_t* offsets,
                                  int64_t n, int64_t A, uint64_t* planes,
                                  uint8_t* status) {
  return pncounter_ingest_impl<uint64_t>(buf, offsets, n, A, planes, status);
}

void pncounter_encode_wire_u32(const uint32_t* planes, int64_t n, int64_t A,
                               int64_t* offsets, uint8_t* buf) {
  pncounter_encode_impl<uint32_t>(planes, n, A, offsets, buf);
}

void pncounter_encode_wire_u64(const uint64_t* planes, int64_t n, int64_t A,
                               int64_t* offsets, uint8_t* buf) {
  pncounter_encode_impl<uint64_t>(planes, n, A, offsets, buf);
}

}  // extern "C"

// ---- Map<K, MVReg> wire codec ---------------------------------------------
//
// The most common monomorphic Map composition (the one the multichip
// dryrun and the reference's nested tests exercise).  Grammar
// (serde.py Map branch, integer keys, named val_type "MVReg"):
//
//   MAP    := 0x27 valtype clock_body entries deferred
//   valtype:= 0x50 uv(5) "MVReg"          (anything else: fallback)
//   entries:= uv n, n * ( 0x03 uv zz(key) clock_body MVREG )
//   MVREG  := 0x25 uv kv, kv * ( clock_body 0x03 uv zz(val) )
//   deferred as the shared section (clock keys -> key ids).
//
// NB: unlike ORSWOT entries, the per-key entry clock body carries NO
// 0x20 tag (serde writes the raw body), and the nested value arrives
// fully tagged.  Dense planes: clock[N,A], keys[N,K], eclocks[N,K,A],
// value antichains vclocks[N,K,KV,A] + vvals[N,K,KV], d_keys[N,D],
// d_clocks[N,D,A].  Status: 0 ok, 1 fallback, 2 key overflow,
// 3 deferred overflow, 4 actor out of range, 5 value overflow (> KV).

namespace {

constexpr uint8_t kTagMap = 0x27;
// val_type headers: the bytes between the 0x27 map tag and the clock
// body.  0x50 = named kernel (uv(len) + name), 0x51 = nested MapOf
// (followed by the inner val_type header) — serde.py
// _T_VALTYPE_NAMED/_T_VALTYPE_MAP.
constexpr uint8_t kMVRegHdr[] = {0x50, 0x05, 'M', 'V', 'R', 'e', 'g'};
constexpr uint8_t kOrswotHdr[] = {0x50, 0x06, 'O', 'r', 's', 'w', 'o', 't'};
constexpr uint8_t kMapMVRegHdr[] = {0x51, 0x50, 0x05, 'M', 'V', 'R', 'e', 'g'};

// the shared Map wire VALUE — tag, val_type header, map clock, the
// strictly-ascending key loop (key + raw entry clock body + one value
// via the functor), and the deferred section — parsed mid-stream from
// an existing cursor, so nested Map values recurse into it.  The
// per-entry value is the only thing that differs between Map
// compositions: ``parse_val(c, slot) -> status``.
template <typename C, typename ParseVal>
int parse_map_value(Cursor& c, const uint8_t* hdr, uint64_t hdr_len,
                    int64_t A, int64_t K, int64_t D, C* clock, int32_t* keys,
                    C* eclocks, int32_t* d_keys, C* d_clocks,
                    ParseVal&& parse_val) {
  if (!c.byte(kTagMap)) return 1;
  // val_type header: only the expected kernel parses fast
  if (c.p + hdr_len > c.end || std::memcmp(c.p, hdr, hdr_len) != 0) return 1;
  c.p += hdr_len;

  int st = parse_clock_body(c, A, clock);
  if (st) return st;

  uint64_t n;
  if (!c.uv(&n)) return 1;
  if (n > static_cast<uint64_t>(K)) return 2;
  // strictly ascending keys (canonical to_binary order) — a duplicate
  // key would yield two live slots where the Python dict dedupes; see
  // the matching check in parse_one
  uint64_t prev_key = 0;
  for (uint64_t e = 0; e < n; ++e) {
    uint64_t key;
    if (!c.nonneg(&key)) return 1;
    if (key > 0x7FFFFFFFull) return 1;  // beyond int32 id space
    if (e > 0 && !varint_bytes_less(prev_key << 1, key << 1)) return 1;
    prev_key = key;
    keys[e] = static_cast<int32_t>(key);
    st = parse_clock_body(c, A, eclocks + e * A);  // raw body, no 0x20 tag
    if (st) return st;
    st = parse_val(c, static_cast<int64_t>(e));
    if (st) return st;
  }

  return parse_deferred_section<C>(c, A, D, d_keys, d_clocks);
}

// top-level wrapper: one whole blob must be exactly one Map value
template <typename C, typename ParseVal>
int parse_map_shell(const uint8_t* buf, int64_t lo, int64_t hi,
                    const uint8_t* hdr, uint64_t hdr_len, int64_t A,
                    int64_t K, int64_t D, C* clock, int32_t* keys,
                    C* eclocks, int32_t* d_keys, C* d_clocks,
                    ParseVal&& parse_val) {
  Cursor c{buf + lo, buf + hi};
  int st = parse_map_value<C>(c, hdr, hdr_len, A, K, D, clock, keys, eclocks,
                              d_keys, d_clocks, parse_val);
  if (st) return st;
  if (c.p != c.end) return 1;
  return 0;
}

template <typename C, typename EmitVal>
int64_t map_shell_encode_one(const C* clock, const int32_t* keys,
                             const C* eclocks, const int32_t* d_keys,
                             const C* d_clocks, const uint8_t* hdr,
                             uint64_t hdr_len, int64_t A, int64_t K,
                             int64_t D, uint8_t* out, EmitVal&& emit_val) {
  const bool sizing = (out == nullptr);
  Emitter e{out};
  std::vector<int64_t> scratch;
  e.byte(kTagMap);
  for (uint64_t i = 0; i < hdr_len; ++i) e.byte(hdr[i]);
  emit_clock_body(e, clock, A, scratch, !sizing);

  std::vector<int64_t> slots;
  for (int64_t s = 0; s < K; ++s)
    if (keys[s] != kEmpty) slots.push_back(s);
  if (!sizing)
    std::sort(slots.begin(), slots.end(), [&](int64_t x, int64_t y) {
      return varint_bytes_less(
          static_cast<uint64_t>(static_cast<uint32_t>(keys[x])) << 1,
          static_cast<uint64_t>(static_cast<uint32_t>(keys[y])) << 1);
    });
  e.uv(static_cast<uint64_t>(slots.size()));
  for (int64_t s : slots) {
    e.tagged_nonneg(static_cast<uint64_t>(static_cast<uint32_t>(keys[s])));
    emit_clock_body(e, eclocks + s * A, A, scratch, !sizing);
    int64_t m = emit_val(s, e.p);
    if (e.p) e.p += m;
    e.count += m;
  }

  emit_deferred_section(e, d_keys, d_clocks, A, D, scratch, sizing);
  return e.count;
}

// one MVReg value (0x25 uv kv, kv * (clock_body 0x03 uv zz(val))) into
// per-slot antichain planes — shared by the flat Map<K, MVReg> leg and
// the nested Map<K, Map<K2, MVReg>> leg.  Status 5 = antichain > KV.
template <typename C>
int parse_mvreg_value_into(Cursor& c, int64_t A, int64_t KV, C* vclocks,
                           C* vvals) {
  constexpr uint64_t kCounterMax = static_cast<uint64_t>(~C{0});
  if (!c.byte(kTagMVReg)) return 1;
  uint64_t kv;
  if (!c.uv(&kv)) return 1;
  if (kv > static_cast<uint64_t>(KV)) return 5;
  for (uint64_t j = 0; j < kv; ++j) {
    int st = parse_clock_body(c, A, vclocks + j * A);
    if (st) return st;
    uint64_t val;
    if (!c.nonneg(&val)) return 1;
    if (val > 0x7FFFFFFFull || val > kCounterMax) return 1;
    vvals[j] = static_cast<C>(val);
  }
  return 0;
}

template <typename C>
int parse_map_mvreg_one(const uint8_t* buf, int64_t lo, int64_t hi,
                        int64_t A, int64_t K, int64_t D, int64_t KV,
                        C* clock, int32_t* keys, C* eclocks, C* vclocks,
                        C* vvals, int32_t* d_keys, C* d_clocks) {
  return parse_map_shell<C>(
      buf, lo, hi, kMVRegHdr, sizeof(kMVRegHdr), A, K, D, clock, keys,
      eclocks, d_keys, d_clocks, [&](Cursor& c, int64_t e) -> int {
        return parse_mvreg_value_into<C>(c, A, KV, vclocks + e * KV * A,
                                         vvals + e * KV);
      });
}

template <typename C>
int64_t map_mvreg_encode_one(const C* clock, const int32_t* keys,
                             const C* eclocks, const C* vclocks,
                             const C* vvals, int64_t A, int64_t K, int64_t D,
                             int64_t KV, const int32_t* d_keys,
                             const C* d_clocks, uint8_t* out) {
  return map_shell_encode_one<C>(
      clock, keys, eclocks, d_keys, d_clocks, kMVRegHdr, sizeof(kMVRegHdr),
      A, K, D, out, [&](int64_t s, uint8_t* p) -> int64_t {
        return mvreg_encode_one<C>(vclocks + s * KV * A, vvals + s * KV, KV,
                                   A, p);
      });
}

// -- nested Map<K, Map<K2, MVReg>> — the reference's canonical nesting
// (`/root/reference/test/map.rs:8`).  The outer val_type header is
// 0x51 (MapOf) followed by the inner header; each entry value is a
// full inner-Map encoding, recursing through parse_map_value.  Value
// planes per outer key slot: iclock[A], ikeys[K2], ieclocks[K2,A],
// vclocks[K2,KV,A], vvals[K2,KV], id_keys[D2], id_clocks[D2,A].
// Status: 0 ok, 1 fallback, 2 outer key overflow, 3 outer deferred
// overflow, 4 actor out of range, 5 any inner overflow (inner keys >
// K2, inner deferred > D2, antichain > KV).

template <typename C>
int parse_map_map_mvreg_one(
    const uint8_t* buf, int64_t lo, int64_t hi, int64_t A, int64_t K,
    int64_t D, int64_t K2, int64_t D2, int64_t KV, C* clock, int32_t* keys,
    C* eclocks, C* iclock, int32_t* ikeys, C* ieclocks, C* vclocks, C* vvals,
    int32_t* id_keys, C* id_clocks, int32_t* d_keys, C* d_clocks) {
  return parse_map_shell<C>(
      buf, lo, hi, kMapMVRegHdr, sizeof(kMapMVRegHdr), A, K, D, clock, keys,
      eclocks, d_keys, d_clocks, [&](Cursor& c, int64_t e) -> int {
        int st = parse_map_value<C>(
            c, kMVRegHdr, sizeof(kMVRegHdr), A, K2, D2, iclock + e * A,
            ikeys + e * K2, ieclocks + e * K2 * A, id_keys + e * D2,
            id_clocks + e * D2 * A, [&](Cursor& c2, int64_t e2) -> int {
              return parse_mvreg_value_into<C>(
                  c2, A, KV, vclocks + (e * K2 + e2) * KV * A,
                  vvals + (e * K2 + e2) * KV);
            });
        // the inner map's own capacity overflows must not masquerade as
        // the OUTER map's key/deferred overflow
        if (st == 2 || st == 3) return 5;
        return st;
      });
}

template <typename C>
int64_t map_map_mvreg_encode_one(
    const C* clock, const int32_t* keys, const C* eclocks, const C* iclock,
    const int32_t* ikeys, const C* ieclocks, const C* vclocks, const C* vvals,
    const int32_t* id_keys, const C* id_clocks, const int32_t* d_keys,
    const C* d_clocks, int64_t A, int64_t K, int64_t D, int64_t K2,
    int64_t D2, int64_t KV, uint8_t* out) {
  return map_shell_encode_one<C>(
      clock, keys, eclocks, d_keys, d_clocks, kMapMVRegHdr,
      sizeof(kMapMVRegHdr), A, K, D, out,
      [&](int64_t s, uint8_t* p) -> int64_t {
        return map_shell_encode_one<C>(
            iclock + s * A, ikeys + s * K2, ieclocks + s * K2 * A,
            id_keys + s * D2, id_clocks + s * D2 * A, kMVRegHdr,
            sizeof(kMVRegHdr), A, K2, D2, p,
            [&](int64_t s2, uint8_t* p2) -> int64_t {
              return mvreg_encode_one<C>(
                  vclocks + (s * K2 + s2) * KV * A, vvals + (s * K2 + s2) * KV,
                  KV, A, p2);
            });
      });
}

}  // namespace

extern "C" {

int64_t map_mvreg_ingest_wire_u32(const uint8_t* buf, const int64_t* offsets,
                                  int64_t n, int64_t A, int64_t K, int64_t D,
                                  int64_t KV, uint32_t* clock, int32_t* keys,
                                  uint32_t* eclocks, uint32_t* vclocks,
                                  uint32_t* vvals, int32_t* d_keys,
                                  uint32_t* d_clocks, uint8_t* status) {
  int64_t bad = 0;
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 512) reduction(+ : bad)
#endif
  for (int64_t i = 0; i < n; ++i) {
    int st = parse_map_mvreg_one<uint32_t>(
        buf, offsets[i], offsets[i + 1], A, K, D, KV, clock + i * A,
        keys + i * K, eclocks + i * K * A, vclocks + i * K * KV * A,
        vvals + i * K * KV, d_keys + i * D, d_clocks + i * D * A);
    status[i] = static_cast<uint8_t>(st);
    if (st != 0) {
      std::memset(clock + i * A, 0, sizeof(uint32_t) * A);
      std::memset(eclocks + i * K * A, 0, sizeof(uint32_t) * K * A);
      std::memset(vclocks + i * K * KV * A, 0, sizeof(uint32_t) * K * KV * A);
      std::memset(vvals + i * K * KV, 0, sizeof(uint32_t) * K * KV);
      std::memset(d_clocks + i * D * A, 0, sizeof(uint32_t) * D * A);
      for (int64_t j = 0; j < K; ++j) keys[i * K + j] = kEmpty;
      for (int64_t j = 0; j < D; ++j) d_keys[i * D + j] = kEmpty;
      ++bad;
    }
  }
  return bad;
}

int64_t map_mvreg_ingest_wire_u64(const uint8_t* buf, const int64_t* offsets,
                                  int64_t n, int64_t A, int64_t K, int64_t D,
                                  int64_t KV, uint64_t* clock, int32_t* keys,
                                  uint64_t* eclocks, uint64_t* vclocks,
                                  uint64_t* vvals, int32_t* d_keys,
                                  uint64_t* d_clocks, uint8_t* status) {
  int64_t bad = 0;
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 512) reduction(+ : bad)
#endif
  for (int64_t i = 0; i < n; ++i) {
    int st = parse_map_mvreg_one<uint64_t>(
        buf, offsets[i], offsets[i + 1], A, K, D, KV, clock + i * A,
        keys + i * K, eclocks + i * K * A, vclocks + i * K * KV * A,
        vvals + i * K * KV, d_keys + i * D, d_clocks + i * D * A);
    status[i] = static_cast<uint8_t>(st);
    if (st != 0) {
      std::memset(clock + i * A, 0, sizeof(uint64_t) * A);
      std::memset(eclocks + i * K * A, 0, sizeof(uint64_t) * K * A);
      std::memset(vclocks + i * K * KV * A, 0, sizeof(uint64_t) * K * KV * A);
      std::memset(vvals + i * K * KV, 0, sizeof(uint64_t) * K * KV);
      std::memset(d_clocks + i * D * A, 0, sizeof(uint64_t) * D * A);
      for (int64_t j = 0; j < K; ++j) keys[i * K + j] = kEmpty;
      for (int64_t j = 0; j < D; ++j) d_keys[i * D + j] = kEmpty;
      ++bad;
    }
  }
  return bad;
}

void map_mvreg_encode_wire_u32(const uint32_t* clock, const int32_t* keys,
                               const uint32_t* eclocks,
                               const uint32_t* vclocks, const uint32_t* vvals,
                               const int32_t* d_keys,
                               const uint32_t* d_clocks, int64_t n, int64_t A,
                               int64_t K, int64_t D, int64_t KV,
                               int64_t* offsets, uint8_t* buf) {
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 512)
#endif
  for (int64_t i = 0; i < n; ++i) {
    if (buf == nullptr)
      offsets[i + 1] = map_mvreg_encode_one<uint32_t>(
          clock + i * A, keys + i * K, eclocks + i * K * A,
          vclocks + i * K * KV * A, vvals + i * K * KV, A, K, D, KV,
          d_keys + i * D, d_clocks + i * D * A, nullptr);
    else
      map_mvreg_encode_one<uint32_t>(
          clock + i * A, keys + i * K, eclocks + i * K * A,
          vclocks + i * K * KV * A, vvals + i * K * KV, A, K, D, KV,
          d_keys + i * D, d_clocks + i * D * A, buf + offsets[i]);
  }
}

void map_mvreg_encode_wire_u64(const uint64_t* clock, const int32_t* keys,
                               const uint64_t* eclocks,
                               const uint64_t* vclocks, const uint64_t* vvals,
                               const int32_t* d_keys,
                               const uint64_t* d_clocks, int64_t n, int64_t A,
                               int64_t K, int64_t D, int64_t KV,
                               int64_t* offsets, uint8_t* buf) {
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 512)
#endif
  for (int64_t i = 0; i < n; ++i) {
    if (buf == nullptr)
      offsets[i + 1] = map_mvreg_encode_one<uint64_t>(
          clock + i * A, keys + i * K, eclocks + i * K * A,
          vclocks + i * K * KV * A, vvals + i * K * KV, A, K, D, KV,
          d_keys + i * D, d_clocks + i * D * A, nullptr);
    else
      map_mvreg_encode_one<uint64_t>(
          clock + i * A, keys + i * K, eclocks + i * K * A,
          vclocks + i * K * KV * A, vvals + i * K * KV, A, K, D, KV,
          d_keys + i * D, d_clocks + i * D * A, buf + offsets[i]);
  }
}

}  // extern "C"

// ---- Map<K, Orswot> wire codec --------------------------------------------
//
// The other monomorphic composition the reference tests (reset-remove
// over sets).  Grammar = the Map grammar with valtype "Orswot" and each
// entry value a full ORSWOT encoding (tag 0x26 ... deferred).  Value
// planes per key slot: clock[A], ids[MV], dots[MV,A], d_ids[DV],
// d_clocks[DV,A].  Status: 0 ok, 1 fallback, 2 key overflow, 3 map
// deferred overflow, 4 actor out of range, 5 value overflow (the
// value's member OR deferred table).

namespace {

template <typename C>
int parse_map_orswot_one(const uint8_t* buf, int64_t lo, int64_t hi,
                         int64_t A, int64_t K, int64_t D, int64_t MV,
                         int64_t DV, C* clock, int32_t* keys, C* eclocks,
                         C* vclock, int32_t* vids, C* vdots, int32_t* vdids,
                         C* vdclocks, int32_t* d_keys, C* d_clocks) {
  return parse_map_shell<C>(
      buf, lo, hi, kOrswotHdr, sizeof(kOrswotHdr), A, K, D, clock, keys,
      eclocks, d_keys, d_clocks, [&](Cursor& c, int64_t e) -> int {
        int st = parse_orswot_value<C>(
            c, A, MV, DV, vclock + e * A, vids + e * MV, vdots + e * MV * A,
            vdids + e * DV, vdclocks + e * DV * A);
        // the value's own capacity overflows (2 member / 3 deferred)
        // must not masquerade as the MAP's key/deferred overflow
        if (st == 2 || st == 3) return 5;
        return st;
      });
}

template <typename C>
int64_t map_orswot_encode_one(const C* clock, const int32_t* keys,
                              const C* eclocks, const C* vclock,
                              const int32_t* vids, const C* vdots,
                              const int32_t* vdids, const C* vdclocks,
                              const int32_t* d_keys, const C* d_clocks,
                              int64_t A, int64_t K, int64_t D, int64_t MV,
                              int64_t DV, uint8_t* out) {
  return map_shell_encode_one<C>(
      clock, keys, eclocks, d_keys, d_clocks, kOrswotHdr, sizeof(kOrswotHdr),
      A, K, D, out, [&](int64_t s, uint8_t* p) -> int64_t {
        return encode_one<C>(vclock + s * A, vids + s * MV,
                             vdots + s * MV * A, vdids + s * DV,
                             vdclocks + s * DV * A, A, MV, DV, p);
      });
}

}  // namespace

// OpenMP pragma helper for the macro-stamped Map kernels: expands to
// nothing in a non-OpenMP build (every hand-written loop guards its
// pragma with #if defined(_OPENMP); macros need the _Pragma form)
#if defined(_OPENMP)
#define CRDT_OMP_FOR(CLAUSES) _Pragma(CLAUSES)
#else
#define CRDT_OMP_FOR(CLAUSES)
#endif

#define CRDT_MAP_ORSWOT_INGEST(SUF, TYPE)                                     \
  int64_t map_orswot_ingest_wire_##SUF(                                       \
      const uint8_t* buf, const int64_t* offsets, int64_t n, int64_t A,       \
      int64_t K, int64_t D, int64_t MV, int64_t DV, TYPE* clock,              \
      int32_t* keys, TYPE* eclocks, TYPE* vclock, int32_t* vids, TYPE* vdots, \
      int32_t* vdids, TYPE* vdclocks, int32_t* d_keys, TYPE* d_clocks,        \
      uint8_t* status) {                                                      \
    int64_t bad = 0;                                                          \
    CRDT_OMP_FOR("omp parallel for schedule(dynamic, 512) reduction(+ : bad)") \
    for (int64_t i = 0; i < n; ++i) {                                         \
      int st = parse_map_orswot_one<TYPE>(                                    \
          buf, offsets[i], offsets[i + 1], A, K, D, MV, DV, clock + i * A,    \
          keys + i * K, eclocks + i * K * A, vclock + i * K * A,              \
          vids + i * K * MV, vdots + i * K * MV * A, vdids + i * K * DV,      \
          vdclocks + i * K * DV * A, d_keys + i * D, d_clocks + i * D * A);   \
      status[i] = static_cast<uint8_t>(st);                                   \
      if (st != 0) {                                                          \
        std::memset(clock + i * A, 0, sizeof(TYPE) * A);                      \
        std::memset(eclocks + i * K * A, 0, sizeof(TYPE) * K * A);            \
        std::memset(vclock + i * K * A, 0, sizeof(TYPE) * K * A);             \
        std::memset(vdots + i * K * MV * A, 0, sizeof(TYPE) * K * MV * A);    \
        std::memset(vdclocks + i * K * DV * A, 0,                             \
                    sizeof(TYPE) * K * DV * A);                               \
        std::memset(d_clocks + i * D * A, 0, sizeof(TYPE) * D * A);           \
        for (int64_t j = 0; j < K; ++j) keys[i * K + j] = kEmpty;             \
        for (int64_t j = 0; j < K * MV; ++j) vids[i * K * MV + j] = kEmpty;   \
        for (int64_t j = 0; j < K * DV; ++j) vdids[i * K * DV + j] = kEmpty;  \
        for (int64_t j = 0; j < D; ++j) d_keys[i * D + j] = kEmpty;           \
        ++bad;                                                                \
      }                                                                       \
    }                                                                         \
    return bad;                                                               \
  }

#define CRDT_MAP_ORSWOT_ENCODE(SUF, TYPE)                                     \
  void map_orswot_encode_wire_##SUF(                                          \
      const TYPE* clock, const int32_t* keys, const TYPE* eclocks,            \
      const TYPE* vclock, const int32_t* vids, const TYPE* vdots,             \
      const int32_t* vdids, const TYPE* vdclocks, const int32_t* d_keys,      \
      const TYPE* d_clocks, int64_t n, int64_t A, int64_t K, int64_t D,       \
      int64_t MV, int64_t DV, int64_t* offsets, uint8_t* buf) {               \
    CRDT_OMP_FOR("omp parallel for schedule(dynamic, 512)")                   \
    for (int64_t i = 0; i < n; ++i) {                                         \
      if (buf == nullptr)                                                     \
        offsets[i + 1] = map_orswot_encode_one<TYPE>(                         \
            clock + i * A, keys + i * K, eclocks + i * K * A,                 \
            vclock + i * K * A, vids + i * K * MV, vdots + i * K * MV * A,    \
            vdids + i * K * DV, vdclocks + i * K * DV * A, d_keys + i * D,    \
            d_clocks + i * D * A, A, K, D, MV, DV, nullptr);                  \
      else                                                                    \
        map_orswot_encode_one<TYPE>(                                          \
            clock + i * A, keys + i * K, eclocks + i * K * A,                 \
            vclock + i * K * A, vids + i * K * MV, vdots + i * K * MV * A,    \
            vdids + i * K * DV, vdclocks + i * K * DV * A, d_keys + i * D,    \
            d_clocks + i * D * A, A, K, D, MV, DV, buf + offsets[i]);         \
    }                                                                         \
  }

#define CRDT_MAP_MAP_MVREG_INGEST(SUF, TYPE)                                  \
  int64_t map_map_mvreg_ingest_wire_##SUF(                                    \
      const uint8_t* buf, const int64_t* offsets, int64_t n, int64_t A,       \
      int64_t K, int64_t D, int64_t K2, int64_t D2, int64_t KV, TYPE* clock,  \
      int32_t* keys, TYPE* eclocks, TYPE* iclock, int32_t* ikeys,             \
      TYPE* ieclocks, TYPE* vclocks, TYPE* vvals, int32_t* id_keys,           \
      TYPE* id_clocks, int32_t* d_keys, TYPE* d_clocks, uint8_t* status) {    \
    int64_t bad = 0;                                                          \
    CRDT_OMP_FOR("omp parallel for schedule(dynamic, 512) reduction(+ : bad)") \
    for (int64_t i = 0; i < n; ++i) {                                         \
      int st = parse_map_map_mvreg_one<TYPE>(                                 \
          buf, offsets[i], offsets[i + 1], A, K, D, K2, D2, KV,               \
          clock + i * A, keys + i * K, eclocks + i * K * A,                   \
          iclock + i * K * A, ikeys + i * K * K2,                             \
          ieclocks + i * K * K2 * A, vclocks + i * K * K2 * KV * A,           \
          vvals + i * K * K2 * KV, id_keys + i * K * D2,                      \
          id_clocks + i * K * D2 * A, d_keys + i * D, d_clocks + i * D * A);  \
      status[i] = static_cast<uint8_t>(st);                                   \
      if (st != 0) {                                                          \
        std::memset(clock + i * A, 0, sizeof(TYPE) * A);                      \
        std::memset(eclocks + i * K * A, 0, sizeof(TYPE) * K * A);            \
        std::memset(iclock + i * K * A, 0, sizeof(TYPE) * K * A);             \
        std::memset(ieclocks + i * K * K2 * A, 0,                             \
                    sizeof(TYPE) * K * K2 * A);                               \
        std::memset(vclocks + i * K * K2 * KV * A, 0,                         \
                    sizeof(TYPE) * K * K2 * KV * A);                          \
        std::memset(vvals + i * K * K2 * KV, 0,                               \
                    sizeof(TYPE) * K * K2 * KV);                              \
        std::memset(id_clocks + i * K * D2 * A, 0,                            \
                    sizeof(TYPE) * K * D2 * A);                               \
        std::memset(d_clocks + i * D * A, 0, sizeof(TYPE) * D * A);           \
        for (int64_t j = 0; j < K; ++j) keys[i * K + j] = kEmpty;             \
        for (int64_t j = 0; j < K * K2; ++j) ikeys[i * K * K2 + j] = kEmpty;  \
        for (int64_t j = 0; j < K * D2; ++j)                                  \
          id_keys[i * K * D2 + j] = kEmpty;                                   \
        for (int64_t j = 0; j < D; ++j) d_keys[i * D + j] = kEmpty;           \
        ++bad;                                                                \
      }                                                                       \
    }                                                                         \
    return bad;                                                               \
  }

#define CRDT_MAP_MAP_MVREG_ENCODE(SUF, TYPE)                                  \
  void map_map_mvreg_encode_wire_##SUF(                                       \
      const TYPE* clock, const int32_t* keys, const TYPE* eclocks,            \
      const TYPE* iclock, const int32_t* ikeys, const TYPE* ieclocks,         \
      const TYPE* vclocks, const TYPE* vvals, const int32_t* id_keys,         \
      const TYPE* id_clocks, const int32_t* d_keys, const TYPE* d_clocks,     \
      int64_t n, int64_t A, int64_t K, int64_t D, int64_t K2, int64_t D2,     \
      int64_t KV, int64_t* offsets, uint8_t* buf) {                           \
    CRDT_OMP_FOR("omp parallel for schedule(dynamic, 512)")                   \
    for (int64_t i = 0; i < n; ++i) {                                         \
      uint8_t* dst = (buf == nullptr) ? nullptr : buf + offsets[i];           \
      int64_t cnt = map_map_mvreg_encode_one<TYPE>(                           \
          clock + i * A, keys + i * K, eclocks + i * K * A,                   \
          iclock + i * K * A, ikeys + i * K * K2,                             \
          ieclocks + i * K * K2 * A, vclocks + i * K * K2 * KV * A,           \
          vvals + i * K * K2 * KV, id_keys + i * K * D2,                      \
          id_clocks + i * K * D2 * A, d_keys + i * D, d_clocks + i * D * A,   \
          A, K, D, K2, D2, KV, dst);                                          \
      if (buf == nullptr) offsets[i + 1] = cnt;                               \
    }                                                                         \
  }

extern "C" {
CRDT_MAP_ORSWOT_INGEST(u32, uint32_t)
CRDT_MAP_ORSWOT_INGEST(u64, uint64_t)
CRDT_MAP_ORSWOT_ENCODE(u32, uint32_t)
CRDT_MAP_ORSWOT_ENCODE(u64, uint64_t)
CRDT_MAP_MAP_MVREG_INGEST(u32, uint32_t)
CRDT_MAP_MAP_MVREG_INGEST(u64, uint64_t)
CRDT_MAP_MAP_MVREG_ENCODE(u32, uint32_t)
CRDT_MAP_MAP_MVREG_ENCODE(u64, uint64_t)
}  // extern "C"

// ---- named ORSWOT codec ----------------------------------------------------
//
// Real stores key by name: Riak's set members are binaries, and the
// actor of an update is the coordinating vnode's id.  The named codec
// reads and writes the ORSWOT grammar above with every actor and member
// key a serde str (0x05 uv(len) utf-8) or bytes (0x06 uv(len) raw)
// value, and maps each key to its dense index through a NameTable: the
// encoded bytes of every interned name, in id order, with a hash index
// over them.  The Python Registry (crdt_tpu/utils/interning.py) stays
// the truth: it appends its own names here before a call, and adopts
// the names a call interned after it.
//
// Ingest runs in two passes.  The parallel pass only looks names up; a
// blob holding a name the table lacks gets status 5 and an empty row.
// The serial pass re-parses those blobs in blob order and appends each
// unseen name where it is met — per registry the order Registry.intern
// sees the names of from_binary(blob) handed to OrswotBatch.from_scalar
// (set-clock actors, then entry members, entry dot actors, deferred
// clock-key actors and deferred members, each in wire order; the one
// difference: from_scalar takes the members buffered under one deferred
// clock in Python set order, this pass in wire order).
//
// Egress writes each key from the table; clock pairs and entries sort
// by encoded name bytes, deferred clock-key pairs by repr(actor) (a
// rank the caller computes, vclock.py ClockKey).  A table is
// append-only and guarded by a reader-writer lock: parses and encodes
// read under a shared lock, appends take it alone, so a parse on one
// thread may intern while another thread encodes.

namespace {

constexpr uint8_t kTagStr = 0x05;
constexpr uint8_t kTagBytes = 0x06;
constexpr int kNewName = 5;

inline uint64_t hash_name(const uint8_t* p, size_t n) {
  uint64_t h = 0x9E3779B97F4A7C15ull ^ (n * 0xC2B2AE3D27D4EB4Full);
  while (n >= 8) {
    uint64_t w;
    std::memcpy(&w, p, 8);
    h = (h ^ w) * 0xFF51AFD7ED558CCDull;
    h ^= h >> 32;
    p += 8;
    n -= 8;
  }
  uint64_t w = 0;
  std::memcpy(&w, p, n);
  h = (h ^ w) * 0xC4CEB9FE1A85EC53ull;
  return h ^ (h >> 29);
}

// strict UTF-8, as Python's decoder takes it (no overlongs, no
// surrogates, nothing past U+10FFFF)
inline bool valid_utf8(const uint8_t* s, size_t n) {
  size_t i = 0;
  while (i < n) {
    const uint8_t c = s[i];
    if (c < 0x80) {
      ++i;
      continue;
    }
    size_t k;
    uint32_t cp;
    if (c >= 0xC2 && c <= 0xDF) {
      k = 1;
      cp = c & 0x1F;
    } else if (c >= 0xE0 && c <= 0xEF) {
      k = 2;
      cp = c & 0x0F;
    } else if (c >= 0xF0 && c <= 0xF4) {
      k = 3;
      cp = c & 0x07;
    } else {
      return false;
    }
    if (n - i <= k) return false;
    for (size_t j = 1; j <= k; ++j) {
      const uint8_t b = s[i + j];
      if ((b & 0xC0) != 0x80) return false;
      cp = (cp << 6) | (b & 0x3F);
    }
    if (k == 2 && (cp < 0x800 || (cp >= 0xD800 && cp <= 0xDFFF))) return false;
    if (k == 3 && (cp < 0x10000 || cp > 0x10FFFF)) return false;
    i += k + 1;
  }
  return true;
}

struct NameTable {
  explicit NameTable(int64_t cap) : capacity(cap), slots(64, -1) {}

  mutable std::shared_mutex mu;
  int64_t capacity;               // ids handed out stay below it
  std::vector<uint8_t> bytes;     // encoded names back to back
  std::vector<int64_t> offs{0};   // name i = bytes[offs[i], offs[i + 1])
  std::vector<uint64_t> hashes;   // per id
  std::vector<int32_t> slots;     // open addressing over ids, -1 empty

  int64_t count() const { return static_cast<int64_t>(hashes.size()); }
  const uint8_t* ptr(int64_t i) const { return bytes.data() + offs[i]; }
  int64_t len(int64_t i) const { return offs[i + 1] - offs[i]; }

  int64_t find(const uint8_t* k, size_t n, uint64_t h) const {
    const size_t mask = slots.size() - 1;
    for (size_t s = h & mask;; s = (s + 1) & mask) {
      const int32_t id = slots[s];
      if (id < 0) return -1;
      if (hashes[id] == h && static_cast<size_t>(len(id)) == n &&
          std::memcmp(ptr(id), k, n) == 0)
        return id;
    }
  }

  void place(int64_t id) {
    const size_t mask = slots.size() - 1;
    size_t s = hashes[id] & mask;
    while (slots[s] >= 0) s = (s + 1) & mask;
    slots[s] = static_cast<int32_t>(id);
  }

  int64_t append(const uint8_t* k, size_t n, uint64_t h) {
    const int64_t id = count();
    bytes.insert(bytes.end(), k, k + n);
    offs.push_back(static_cast<int64_t>(bytes.size()));
    hashes.push_back(h);
    if (2 * static_cast<size_t>(id + 1) > slots.size()) {
      slots.assign(slots.size() * 2, -1);
      for (int64_t i = 0; i <= id; ++i) place(i);
    } else {
      place(id);
    }
    return id;
  }
};

// both tables of a universe under one lock kind; a universe may hand
// the same registry for actors and members, so that one is locked once
template <typename Lock>
struct TableLocks {
  Lock a, m;
  TableLocks(NameTable* actors, NameTable* members)
      : a(actors->mu),
        m(members == actors ? Lock() : Lock(members->mu)) {}
};
using ReadLocks = TableLocks<std::shared_lock<std::shared_mutex>>;
using WriteLocks = TableLocks<std::unique_lock<std::shared_mutex>>;

struct NamedKeys {
  NameTable* actors;
  NameTable* members;
  bool intern;  // the serial pass: unseen names are appended

  // one str/bytes key; ``*id`` past every index when the table is full
  int name(Cursor& c, NameTable* t, int64_t* id) const {
    const uint8_t* start = c.p;
    if (c.p >= c.end || (*c.p != kTagStr && *c.p != kTagBytes)) return 1;
    const bool is_str = *c.p == kTagStr;
    ++c.p;
    uint64_t n;
    if (!c.uv(&n) || n > static_cast<uint64_t>(c.end - c.p)) return 1;
    const uint8_t* raw = c.p;
    c.p += n;
    const size_t klen = static_cast<size_t>(c.p - start);
    const uint64_t h = hash_name(start, klen);
    *id = t->find(start, klen, h);
    if (*id >= 0) return 0;
    if (!intern) return kNewName;
    if (is_str && !valid_utf8(raw, n)) return 1;  // from_binary raises
    if (t->count() >= t->capacity) {
      *id = std::numeric_limits<int64_t>::max();
      return 0;
    }
    *id = t->append(start, klen, h);
    return 0;
  }

  int actor(Cursor& c, uint64_t* out) const {
    int64_t id;
    if (int st = name(c, actors, &id)) return st;
    *out = static_cast<uint64_t>(id);
    return 0;
  }

  int member(Cursor& c, int32_t* out) const {
    int64_t id;
    if (int st = name(c, members, &id)) return st;
    if (id > 0x7FFFFFFF) return 1;  // beyond int32 id space
    *out = static_cast<int32_t>(id);
    return 0;
  }
};

// the parallel pass: names looked up, never appended (status 5 marks a
// blob holding an unseen name); rows always cleared first
template <typename C>
int64_t named_ingest_impl(const uint8_t* buf, const int64_t* offsets,
                          int64_t n, int64_t A, int64_t M, int64_t D,
                          NameTable* actors, NameTable* members, C* clock,
                          int32_t* ids, C* dots, int32_t* d_ids, C* d_clocks,
                          uint8_t* status) {
  ReadLocks locks(actors, members);
  return ingest_impl<C>(buf, offsets, n, A, M, D, clock, ids, dots, d_ids,
                        d_clocks, status, 1,
                        NamedKeys{actors, members, false});
}

// the serial pass over blobs ``idx[0..k)`` (ascending), interning unseen
// names in order.  Stops after the first blob that comes out status 1,
// so the caller can decode it in Python (which interns its remaining
// names) before any later blob interns; returns the blobs taken.
template <typename C>
int64_t named_intern_impl(const uint8_t* buf, const int64_t* offsets,
                          const int64_t* idx, int64_t k, int64_t A, int64_t M,
                          int64_t D, NameTable* actors, NameTable* members,
                          C* clock, int32_t* ids, C* dots, int32_t* d_ids,
                          C* d_clocks, uint8_t* status) {
  WriteLocks locks(actors, members);
  const NamedKeys keys{actors, members, true};
  for (int64_t j = 0; j < k; ++j) {
    const int64_t i = idx[j];
    C* cl = clock + i * A;
    int32_t* id = ids + i * M;
    C* dt = dots + i * M * A;
    int32_t* di = d_ids + i * D;
    C* dc = d_clocks + i * D * A;
    clear_orswot_row<C>(A, M, D, cl, id, dt, di, dc);
    int st = parse_one<C>(buf, offsets[i], offsets[i + 1], A, M, D, cl, id,
                          dt, di, dc, keys);
    status[i] = static_cast<uint8_t>(st);
    if (st != 0) clear_orswot_row<C>(A, M, D, cl, id, dt, di, dc);
    if (st == 1) return j + 1;
  }
  return k;
}

struct NamedEnc {
  const NameTable* actors;
  const NameTable* members;
  const int32_t* byte_rank;  // actor column -> rank of its encoded name
  const int32_t* repr_rank;  // actor column -> rank of repr(name)

  void actor(Emitter& e, int64_t a) const {
    e.raw(actors->ptr(a), actors->len(a));
  }
  bool actor_less(int64_t x, int64_t y) const {
    return byte_rank[x] < byte_rank[y];
  }
  bool actor_repr_less(int64_t x, int64_t y) const {
    return repr_rank[x] < repr_rank[y];
  }
  void member(Emitter& e, int32_t m) const {
    e.raw(members->ptr(m), members->len(m));
  }
  bool member_less(int32_t x, int32_t y) const {
    return span_less(members->ptr(x), static_cast<size_t>(members->len(x)),
                     members->ptr(y), static_cast<size_t>(members->len(y)));
  }
};

// every key of one object row has a name: no counter in an actor column
// at or past ``n_act``, every live member id below ``n_mem``
template <typename C>
bool named_row_ok(const C* clock, const int32_t* ids, const C* dots,
                  const int32_t* d_ids, const C* d_clocks, int64_t A,
                  int64_t M, int64_t D, int64_t n_act, int64_t n_mem) {
  for (int64_t a = n_act; a < A; ++a) {
    if (clock[a]) return false;
    for (int64_t s = 0; s < M; ++s)
      if (ids[s] != kEmpty && dots[s * A + a]) return false;
    for (int64_t r = 0; r < D; ++r)
      if (d_ids[r] != kEmpty && d_clocks[r * A + a]) return false;
  }
  for (int64_t s = 0; s < M; ++s)
    if (ids[s] < kEmpty || ids[s] >= n_mem) return false;
  for (int64_t r = 0; r < D; ++r)
    if (d_ids[r] < kEmpty || d_ids[r] >= n_mem) return false;
  return true;
}

// the two-pass encode of encode_impl with names; the sizing pass
// returns how many rows hold a key without a name (nothing is written
// then: the caller takes the Python encoder)
template <typename C>
int64_t named_encode_impl(const C* clock, const int32_t* ids, const C* dots,
                          const int32_t* d_ids, const C* d_clocks, int64_t n,
                          int64_t A, int64_t M, int64_t D,
                          NameTable* actors, NameTable* members,
                          const int32_t* repr_rank, int64_t* offsets,
                          uint8_t* buf) {
  ReadLocks locks(actors, members);
  const int64_t n_act = std::min(actors->count(), A);
  const int64_t n_mem = members->count();
  std::vector<int32_t> order(static_cast<size_t>(n_act));
  std::iota(order.begin(), order.end(), 0);
  std::sort(order.begin(), order.end(), [&](int32_t x, int32_t y) {
    return span_less(actors->ptr(x), static_cast<size_t>(actors->len(x)),
                     actors->ptr(y), static_cast<size_t>(actors->len(y)));
  });
  std::vector<int32_t> byte_rank(static_cast<size_t>(A),
                                 std::numeric_limits<int32_t>::max());
  for (int64_t r = 0; r < n_act; ++r) byte_rank[order[r]] = static_cast<int32_t>(r);
  const NamedEnc keys{actors, members, byte_rank.data(), repr_rank};
  if (buf == nullptr) {
    int64_t bad = 0;
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 1024) reduction(+ : bad)
#endif
    for (int64_t i = 0; i < n; ++i) {
      const C* cl = clock + i * A;
      const int32_t* id = ids + i * M;
      const C* dt = dots + i * M * A;
      const int32_t* di = d_ids + i * D;
      const C* dc = d_clocks + i * D * A;
      if (!named_row_ok<C>(cl, id, dt, di, dc, A, M, D, n_act, n_mem)) {
        offsets[i + 1] = 0;
        ++bad;
        continue;
      }
      offsets[i + 1] = encode_one<C>(cl, id, dt, di, dc, A, M, D, nullptr,
                                     keys);
    }
    return bad;
  }
#if defined(_OPENMP)
#pragma omp parallel for schedule(dynamic, 1024)
#endif
  for (int64_t i = 0; i < n; ++i)
    encode_one<C>(clock + i * A, ids + i * M, dots + i * M * A,
                  d_ids + i * D, d_clocks + i * D * A, A, M, D,
                  buf + offsets[i], keys);
  return 0;
}

}  // namespace

extern "C" {

void* names_new(int64_t capacity) { return new NameTable(capacity); }

void names_free(void* t) { delete static_cast<NameTable*>(t); }

int64_t names_count(void* t) {
  auto* tab = static_cast<NameTable*>(t);
  std::shared_lock<std::shared_mutex> lock(tab->mu);
  return tab->count();
}

// append ``n`` encoded names (buf[offsets[i], offsets[i + 1])) in order;
// returns the new count
int64_t names_append(void* t, const uint8_t* buf, const int64_t* offsets,
                     int64_t n) {
  auto* tab = static_cast<NameTable*>(t);
  std::unique_lock<std::shared_mutex> lock(tab->mu);
  for (int64_t i = 0; i < n; ++i) {
    const uint8_t* k = buf + offsets[i];
    const size_t len = static_cast<size_t>(offsets[i + 1] - offsets[i]);
    tab->append(k, len, hash_name(k, len));
  }
  return tab->count();
}

// bytes of names [start, end)
int64_t names_span(void* t, int64_t start, int64_t end) {
  auto* tab = static_cast<NameTable*>(t);
  std::shared_lock<std::shared_mutex> lock(tab->mu);
  return tab->offs[end] - tab->offs[start];
}

// names [start, end) back to back into ``out``; ``offsets`` gets the
// end - start + 1 boundaries relative to ``out``
void names_read(void* t, int64_t start, int64_t end, uint8_t* out,
                int64_t* offsets) {
  auto* tab = static_cast<NameTable*>(t);
  std::shared_lock<std::shared_mutex> lock(tab->mu);
  const int64_t base = tab->offs[start];
  std::memcpy(out, tab->bytes.data() + base,
              static_cast<size_t>(tab->offs[end] - base));
  for (int64_t i = start; i <= end; ++i) offsets[i - start] = tab->offs[i] - base;
}

#define CRDT_ORSWOT_NAMED(SUF, TYPE)                                          \
  int64_t orswot_ingest_named_##SUF(                                          \
      const uint8_t* buf, const int64_t* offsets, int64_t n, int64_t A,       \
      int64_t M, int64_t D, void* actors, void* members, TYPE* clock,         \
      int32_t* ids, TYPE* dots, int32_t* d_ids, TYPE* d_clocks,               \
      uint8_t* status) {                                                      \
    return named_ingest_impl<TYPE>(                                           \
        buf, offsets, n, A, M, D, static_cast<NameTable*>(actors),            \
        static_cast<NameTable*>(members), clock, ids, dots, d_ids, d_clocks,  \
        status);                                                              \
  }                                                                           \
  int64_t orswot_intern_named_##SUF(                                          \
      const uint8_t* buf, const int64_t* offsets, const int64_t* idx,         \
      int64_t k, int64_t A, int64_t M, int64_t D, void* actors,               \
      void* members, TYPE* clock, int32_t* ids, TYPE* dots, int32_t* d_ids,   \
      TYPE* d_clocks, uint8_t* status) {                                      \
    return named_intern_impl<TYPE>(                                           \
        buf, offsets, idx, k, A, M, D, static_cast<NameTable*>(actors),       \
        static_cast<NameTable*>(members), clock, ids, dots, d_ids, d_clocks,  \
        status);                                                              \
  }                                                                           \
  int64_t orswot_encode_named_##SUF(                                          \
      const TYPE* clock, const int32_t* ids, const TYPE* dots,                \
      const int32_t* d_ids, const TYPE* d_clocks, int64_t n, int64_t A,       \
      int64_t M, int64_t D, void* actors, void* members,                      \
      const int32_t* repr_rank, int64_t* offsets, uint8_t* buf) {             \
    return named_encode_impl<TYPE>(                                           \
        clock, ids, dots, d_ids, d_clocks, n, A, M, D,                        \
        static_cast<NameTable*>(actors), static_cast<NameTable*>(members),    \
        repr_rank, offsets, buf);                                             \
  }

CRDT_ORSWOT_NAMED(u32, uint32_t)
CRDT_ORSWOT_NAMED(u64, uint64_t)

}  // extern "C"

// ---- compact ORSWOT ingest: a fleet as nonzero cells -----------------------
//
// The device fold ships each parsed fleet to the chip and densifies it
// there, so a fleet crosses the host link as what it holds rather than
// as dense planes (a ★-width replica-object is 4,936 B dense and about
// 13 nonzero counters).  Each blob is parsed by ``parse_one`` into a
// per-thread dense scratch row (one object's clock, dot and deferred
// clock rows, zero between objects), so the dense parse's semantics
// hold exactly: a repeated actor resolves last write wins, a zero
// counter is an absent one, statuses are the same.  The object's member
// and deferred ids are written out as dense int32 rows; each nonzero
// counter of its live rows becomes one (flat index, counter) cell and
// is zeroed again.  Flat indices address the plane-major space
//
//   [clock n*A | dots n*M*A | d_clocks n*D*A]
//
// (the caller checks it fits int32).  Every cell is unique, so the
// per-thread runs are concatenated in any order.  A blob with nonzero
// status keeps empty id rows and emits no cell.  Returns the number of
// cells; when it exceeds ``cap`` the cell output is incomplete and the
// caller grows its buffers and parses again.

namespace {

template <typename C>
inline void emit_row(C* row, int64_t A, int64_t base,
                     std::vector<int32_t>& idx, std::vector<C>& val) {
  for (int64_t a = 0; a < A; ++a) {
    if (row[a]) {
      idx.push_back(static_cast<int32_t>(base + a));
      val.push_back(row[a]);
      row[a] = C{0};
    }
  }
}

template <typename C, typename K>
int64_t cells_impl(const uint8_t* buf, const int64_t* offsets, int64_t n,
                   int64_t A, int64_t M, int64_t D, int32_t* ids,
                   int32_t* d_ids, int32_t* cell_idx, C* cell_val,
                   int64_t cap, uint8_t* status, const K& keys) {
  const int64_t row_len = A * (1 + M + D);
  const int64_t dots_base = n * A;
  const int64_t dclk_base = dots_base + n * M * A;
  int64_t total = 0;
#if defined(_OPENMP)
#pragma omp parallel
#endif
  {
    // kept per thread across calls: no allocation once warm
    static thread_local std::vector<C> scratch;
    static thread_local std::vector<int32_t> run_idx;
    static thread_local std::vector<C> run_val;
    if (static_cast<int64_t>(scratch.size()) != row_len)
      scratch.assign(static_cast<size_t>(row_len), C{0});
    run_idx.clear();
    run_val.clear();
    C* clock = scratch.data();
    C* dots = clock + A;
    C* d_clocks = dots + M * A;
#if defined(_OPENMP)
#pragma omp for schedule(dynamic, 1024) nowait
#endif
    for (int64_t i = 0; i < n; ++i) {
      int32_t* id = ids + i * M;
      int32_t* di = d_ids + i * D;
      for (int64_t j = 0; j < M; ++j) id[j] = kEmpty;
      for (int64_t j = 0; j < D; ++j) di[j] = kEmpty;
      int st = parse_one<C>(buf, offsets[i], offsets[i + 1], A, M, D, clock,
                            id, dots, di, d_clocks, keys);
      status[i] = static_cast<uint8_t>(st);
      if (st != 0) {
        std::fill(scratch.begin(), scratch.end(), C{0});
        for (int64_t j = 0; j < M; ++j) id[j] = kEmpty;
        for (int64_t j = 0; j < D; ++j) di[j] = kEmpty;
        continue;
      }
      // entries and deferred rows fill slots from 0, so the live rows
      // are the leading ones with an id
      emit_row<C>(clock, A, i * A, run_idx, run_val);
      for (int64_t e = 0; e < M && id[e] != kEmpty; ++e)
        emit_row<C>(dots + e * A, A, dots_base + (i * M + e) * A, run_idx,
                    run_val);
      for (int64_t r = 0; r < D && di[r] != kEmpty; ++r)
        emit_row<C>(d_clocks + r * A, A, dclk_base + (i * D + r) * A,
                    run_idx, run_val);
    }
    const int64_t k = static_cast<int64_t>(run_idx.size());
    int64_t at;
#if defined(_OPENMP)
#pragma omp atomic capture
#endif
    {
      at = total;
      total += k;
    }
    if (at + k <= cap) {
      std::memcpy(cell_idx + at, run_idx.data(), sizeof(int32_t) * k);
      std::memcpy(cell_val + at, run_val.data(), sizeof(C) * k);
    }
  }
  return total;
}

// ``actors`` null: integer keys (identity universe); else the named
// codec's parallel pass (names looked up, none appended: status 5 marks
// a blob holding an unseen name)
template <typename C>
int64_t ingest_cells(const uint8_t* buf, const int64_t* offsets, int64_t n,
                     int64_t A, int64_t M, int64_t D, void* actors,
                     void* members, int32_t* ids, int32_t* d_ids,
                     int32_t* cell_idx, C* cell_val, int64_t cap,
                     uint8_t* status) {
  if (actors == nullptr)
    return cells_impl<C>(buf, offsets, n, A, M, D, ids, d_ids, cell_idx,
                         cell_val, cap, status, IntKeys{});
  auto* at = static_cast<NameTable*>(actors);
  auto* mt = static_cast<NameTable*>(members);
  ReadLocks locks(at, mt);
  return cells_impl<C>(buf, offsets, n, A, M, D, ids, d_ids, cell_idx,
                       cell_val, cap, status, NamedKeys{at, mt, false});
}

}  // namespace

extern "C" {

int64_t orswot_ingest_cells_u32(const uint8_t* buf, const int64_t* offsets,
                                int64_t n, int64_t A, int64_t M, int64_t D,
                                void* actors, void* members, int32_t* ids,
                                int32_t* d_ids, int32_t* cell_idx,
                                uint32_t* cell_val, int64_t cap,
                                uint8_t* status) {
  return ingest_cells<uint32_t>(buf, offsets, n, A, M, D, actors, members,
                                ids, d_ids, cell_idx, cell_val, cap, status);
}

int64_t orswot_ingest_cells_u64(const uint8_t* buf, const int64_t* offsets,
                                int64_t n, int64_t A, int64_t M, int64_t D,
                                void* actors, void* members, int32_t* ids,
                                int32_t* d_ids, int32_t* cell_idx,
                                uint64_t* cell_val, int64_t cap,
                                uint8_t* status) {
  return ingest_cells<uint64_t>(buf, offsets, n, A, M, D, actors, members,
                                ids, d_ids, cell_idx, cell_val, cap, status);
}

}  // extern "C"

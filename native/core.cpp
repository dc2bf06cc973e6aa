// automerge_tpu native host runtime.
//
// Owns the host-resident document state (interner, clocks, change logs,
// registers, list arenas) and runs every per-op host stage of the batched
// resolver -- exact-order causal scheduling, columnar encoding, patch
// emission, mirror maintenance -- in C++, leaving only the three device
// kernels (register resolution, RGA linearization, dominance indexes) to
// JAX.  Python talks to it through a 3-phase C ABI (begin / mid / finish)
// passing columnar arrays by pointer, and changes/patches cross the
// boundary as msgpack bytes.
//
// Semantics are a faithful port of automerge_tpu/parallel/engine.py, which
// is itself byte-compatible with the reference backend
// (/root/reference/backend/op_set.js).  Differential tests in
// tests/test_native.py pin native output == Python pool output == oracle.
//
// Build: see native/Makefile (g++ -O2 -shared -fPIC).

#include <algorithm>
#include <array>
#include <cassert>
#include <chrono>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <map>
#include <unordered_set>
#include <memory>
#include <string>
#include <string_view>
#include <unordered_map>
#include <vector>

#include <zlib.h>
// zconf.h drags in <unistd.h>, whose legacy lseek L_* macros collide
// with this file's materializer literal ids
#ifdef L_SET
#undef L_SET
#endif
#ifdef L_INCR
#undef L_INCR
#endif
#ifdef L_XTND
#undef L_XTND
#endif

#include "msgpack.h"

namespace amtpu {

using u8 = uint8_t;
using i32 = int32_t;
using u32 = uint32_t;
using i64 = int64_t;
using u64 = uint64_t;

static const char* ROOT_ID = "00000000-0000-0000-0000-000000000000";

// ---------------------------------------------------------------------------
// interner
// ---------------------------------------------------------------------------

// Open-addressing hash map u64 -> V, linear probing, power-of-two
// capacity.  The per-op maps (interner slots, arena element index,
// register index) live on the hottest host loops; open addressing costs
// one cache line per probe instead of unordered_map's bucket-chain
// pointer chase, and inserting never allocates per node.
// Key 0xffff..ff is reserved as the empty marker (never a valid key here:
// composite keys are built from interner ids < 2^32).
inline size_t flatmap_mix(u64 k) {
  k ^= k >> 33;
  k *= 0xff51afd7ed558ccdULL;
  k ^= k >> 29;
  return static_cast<size_t>(k);
}

template <typename V>
struct FlatMap {
  std::vector<u64> keys;
  std::vector<V> vals;
  size_t mask = 0, n = 0;
  static constexpr u64 EMPTY = ~0ull;

  FlatMap() { rehash(16); }
  static inline size_t mix(u64 k) { return flatmap_mix(k); }
  void rehash(size_t cap) {
    std::vector<u64> ok = std::move(keys);
    std::vector<V> ov = std::move(vals);
    keys.assign(cap, EMPTY);
    vals.clear();
    vals.resize(cap);
    mask = cap - 1;
    for (size_t i = 0; i < ok.size(); ++i) {
      if (ok[i] == EMPTY) continue;
      size_t j = mix(ok[i]) & mask;
      while (keys[j] != EMPTY) j = (j + 1) & mask;
      keys[j] = ok[i];
      vals[j] = std::move(ov[i]);
    }
  }
  void reserve(size_t want) {
    size_t cap = mask + 1;
    while (want * 4 >= cap * 3) cap *= 2;
    if (cap != mask + 1) rehash(cap);
  }
  V* find(u64 k) {
    size_t i = mix(k) & mask;
    while (true) {
      if (keys[i] == k) return &vals[i];
      if (keys[i] == EMPTY) return nullptr;
      i = (i + 1) & mask;
    }
  }
  const V* find(u64 k) const {
    return const_cast<FlatMap*>(this)->find(k);
  }
  // returns (slot, inserted)
  std::pair<V*, bool> insert(u64 k) {
    if ((n + 1) * 4 >= (mask + 1) * 3) rehash((mask + 1) * 2);
    size_t i = mix(k) & mask;
    while (true) {
      if (keys[i] == k) return {&vals[i], false};
      if (keys[i] == EMPTY) {
        keys[i] = k;
        ++n;
        return {&vals[i], true};
      }
      i = (i + 1) & mask;
    }
  }
  // backward-shift deletion (linear probing invariant preserved); only
  // the rare rollback path erases
  void erase(u64 k) {
    size_t i = mix(k) & mask;
    while (true) {
      if (keys[i] == EMPTY) return;
      if (keys[i] == k) break;
      i = (i + 1) & mask;
    }
    size_t hole = i;
    size_t j = (i + 1) & mask;
    while (keys[j] != EMPTY) {
      size_t home = mix(keys[j]) & mask;
      // can keys[j] move into the hole? yes iff hole lies cyclically
      // between home and j
      if (((j - home) & mask) >= ((j - hole) & mask)) {
        keys[hole] = keys[j];
        vals[hole] = std::move(vals[j]);
        hole = j;
      }
      j = (j + 1) & mask;
    }
    keys[hole] = EMPTY;
    vals[hole] = V{};
    --n;
  }
};

// One open-addressing probing core (key -> dense index), two value
// storage policies.  The hash table stores (key, slot) pairs only, so
// rehash touches 12 B/slot regardless of sizeof(V) -- FlatMap<V>'s
// rehash default-constructed + zeroed a capacity-sized V array and
// moved every element on growth, which profiled as the largest single
// memory-traffic source in table-heavy batches (V=Register, ~100 B).
//
//   FlatMapDense  -- vector storage: value pointers move when vals
//                    grows (same aliasing caution as FlatMap's rehash;
//                    see emit()'s INVARIANT).  No erase.
//   FlatMapStable -- deque storage: value pointers NEVER move, so
//                    cached ObjMeta*/Arena* stashes survive insertion.
//                    Adds backward-shift erase for the rollback path.
template <typename V, typename Store>
struct FlatMapIdx {
  std::vector<u64> keys;
  std::vector<u32> slot;
  Store vals;
  size_t mask = 0, n = 0;
  static constexpr u64 EMPTY = ~0ull;

  FlatMapIdx() { rehash(16); }
  void rehash(size_t cap) {
    std::vector<u64> ok = std::move(keys);
    std::vector<u32> os = std::move(slot);
    keys.assign(cap, EMPTY);
    slot.assign(cap, 0);
    mask = cap - 1;
    for (size_t i = 0; i < ok.size(); ++i) {
      if (ok[i] == EMPTY) continue;
      size_t j = flatmap_mix(ok[i]) & mask;
      while (keys[j] != EMPTY) j = (j + 1) & mask;
      keys[j] = ok[i];
      slot[j] = os[i];
    }
  }
  V* find(u64 k) {
    size_t i = flatmap_mix(k) & mask;
    while (true) {
      if (keys[i] == k) return &vals[slot[i]];
      if (keys[i] == EMPTY) return nullptr;
      i = (i + 1) & mask;
    }
  }
  const V* find(u64 k) const {
    return const_cast<FlatMapIdx*>(this)->find(k);
  }
  // returns (slot, inserted)
  std::pair<V*, bool> insert(u64 k) {
    if ((n + 1) * 4 >= (mask + 1) * 3) rehash((mask + 1) * 2);
    size_t i = flatmap_mix(k) & mask;
    while (true) {
      if (keys[i] == k) return {&vals[slot[i]], false};
      if (keys[i] == EMPTY) {
        keys[i] = k;
        slot[i] = static_cast<u32>(vals.size());
        ++n;
        vals.emplace_back();
        return {&vals.back(), true};
      }
      i = (i + 1) & mask;
    }
  }
  V& operator[](u64 k) { return *insert(k).first; }
};

template <typename V>
struct FlatMapDense : FlatMapIdx<V, std::vector<V>> {
  void reserve(size_t want) {
    size_t cap = this->mask + 1;
    while (want * 4 >= cap * 3) cap *= 2;
    if (cap != this->mask + 1) this->rehash(cap);
    this->vals.reserve(want);
  }
};

template <typename V>
struct FlatMapStable : FlatMapIdx<V, std::deque<V>> {
  // backward-shift key removal; the deque slot is orphaned (reset to
  // V{}) -- only the rare rollback path erases
  void erase(u64 k) {
    auto& keys = this->keys;
    auto& slot = this->slot;
    const size_t mask = this->mask;
    size_t i = flatmap_mix(k) & mask;
    while (true) {
      if (keys[i] == this->EMPTY) return;
      if (keys[i] == k) break;
      i = (i + 1) & mask;
    }
    this->vals[slot[i]] = V{};
    size_t hole = i;
    size_t j = (i + 1) & mask;
    while (keys[j] != this->EMPTY) {
      size_t home = flatmap_mix(keys[j]) & mask;
      if (((j - home) & mask) >= ((j - hole) & mask)) {
        keys[hole] = keys[j];
        slot[hole] = slot[j];
        hole = j;
      }
      j = (j + 1) & mask;
    }
    keys[hole] = this->EMPTY;
    --this->n;
  }
};

struct Interner {
  // storage is a deque so string data never moves; the open-addressing
  // slot table stores (hash, id) and resolves rare collisions by string
  // compare against the stored string
  std::deque<std::string> strs;
  std::vector<u64> slot_hash;
  std::vector<u32> slot_id;
  size_t mask = 0, n = 0;

  Interner() { rehash(1 << 10); }
  // pre-size for an expected total entry count (amortizes the ~10
  // doubling rehashes a million-op batch otherwise pays on a fresh
  // pool); never shrinks
  void reserve(size_t want) {
    size_t cap = mask + 1;
    while (want * 4 >= cap * 3) cap *= 2;
    if (cap != mask + 1) rehash(cap);
  }
  static inline u64 hash_sv(std::string_view s) {
    u64 h = 1469598103934665603ull;           // FNV-1a 64
    for (char c : s) {
      h ^= static_cast<u8>(c);
      h *= 1099511628211ull;
    }
    return h ? h : 0x9e3779b97f4a7c15ull;      // 0 marks an empty slot
  }
  void rehash(size_t cap) {
    std::vector<u64> oh = std::move(slot_hash);
    std::vector<u32> oi = std::move(slot_id);
    slot_hash.assign(cap, 0);
    slot_id.assign(cap, 0);
    mask = cap - 1;
    for (size_t i = 0; i < oh.size(); ++i) {
      if (!oh[i]) continue;
      size_t j = oh[i] & mask;
      while (slot_hash[j]) j = (j + 1) & mask;
      slot_hash[j] = oh[i];
      slot_id[j] = oi[i];
    }
  }
  u32 id_of(std::string_view s) {
    u64 h = hash_sv(s);
    size_t i = h & mask;
    while (slot_hash[i]) {
      if (slot_hash[i] == h && strs[slot_id[i]] == s) return slot_id[i];
      i = (i + 1) & mask;
    }
    if ((n + 1) * 4 >= (mask + 1) * 3) {
      rehash((mask + 1) * 2);
      i = h & mask;
      while (slot_hash[i]) i = (i + 1) & mask;
    }
    u32 id = static_cast<u32>(strs.size());
    strs.emplace_back(s);
    slot_hash[i] = h;
    slot_id[i] = id;
    ++n;
    return id;
  }
  const std::string& str(u32 id) const { return strs[id]; }
  size_t size() const { return strs.size(); }
};

// composite integer keys replacing per-op string keys (hash-map identity
// must be exact, so fields are kept, not hashed together)
struct K3 {
  u32 a, b, c;
  bool operator==(const K3& o) const {
    return a == o.a && b == o.b && c == o.c;
  }
};
struct K3Hash {
  size_t operator()(const K3& k) const {
    u64 h = (u64(k.a) << 42) ^ (u64(k.b) << 21) ^ k.c;
    h ^= h >> 33; h *= 0xff51afd7ed558ccdULL; h ^= h >> 33;
    return static_cast<size_t>(h);
  }
};

// ---------------------------------------------------------------------------
// records
// ---------------------------------------------------------------------------

enum Action : u8 {
  A_SET, A_DEL, A_LINK, A_INS, A_MAKE_MAP, A_MAKE_LIST, A_MAKE_TEXT,
  A_MAKE_TABLE
};

enum ObjType : u8 { T_MAP, T_LIST, T_TEXT, T_TABLE };

static bool is_list_type(u8 t) { return t == T_LIST || t == T_TEXT; }
static bool is_assign(u8 a) { return a <= A_LINK; }

static const u32 NONE = 0xffffffffu;

// Defaults of the NUMERIC latch-at-first-batch env knobs, exported via
// amtpu_latch_defaults so the Python latch-flip guard derives effective
// values from the SAME constants the latching lambdas below use -- a
// default changed here can never silently drift from the warning logic.
// (The boolean knobs AMTPU_RESIDENT / AMTPU_RESIDENT_CLK /
// AMTPU_TRIVIAL_HOST all default ON and latch atoi(env) != 0.)
static const i64 DEF_RESIDENT_MIN = 16384;
static const i64 DEF_RESCLK_MAX_ACTORS = 512;
static const i64 DEF_RESCLK_MAX_ROWS = 1LL << 20;

// Values are interned raw msgpack spans (vid into Pool::vals): op records
// stay POD-copyable and identical values (e.g. single chars of a Text)
// dedup to one entry.
struct OpRec {
  u8 action;
  u32 obj;              // sid
  u32 key;              // sid of key / elemId string; NONE if absent
  i64 elem;             // for ins
  u32 actor;            // sid (authoring change)
  u32 seq;
  u32 datatype;         // sid or NONE
  u32 value_rid;        // vid of raw msgpack value bytes, NONE if absent
  u32 value_sid;        // sid when value is a string (link targets), else NONE
};

using Clock = std::vector<std::pair<u32, u32>>;  // (actor sid, seq), sorted

static u32 clock_get(const Clock& c, u32 actor) {
  for (auto& p : c) if (p.first == actor) return p.second;
  return 0;
}
static void clock_set_max(Clock& c, u32 actor, u32 seq) {
  for (auto& p : c) {
    if (p.first == actor) { if (seq > p.second) p.second = seq; return; }
  }
  c.emplace_back(actor, seq);
}

// Raw change bytes as a span into a shared payload slab: one batch copies
// its whole wire payload once, and every ChangeRec (and every ChangeRec
// copy -- queue snapshots, state entries) is a refcount bump instead of a
// per-change buffer copy.  Locally-built changes (undo/redo, stripped
// requestType) carry their own single-change slab.
struct RawRef {
  std::shared_ptr<std::vector<u8>> slab;
  u32 off = 0, len = 0;
  const u8* data() const { return slab->data() + off; }
  size_t size() const { return len; }
  void adopt(std::vector<u8>&& buf) {
    slab = std::make_shared<std::vector<u8>>(std::move(buf));
    off = 0;
    len = static_cast<u32>(slab->size());
  }
};

struct ChangeRec {
  u32 actor;
  u32 seq;
  Clock deps;
  std::vector<OpRec> ops;
  RawRef raw;                   // raw change msgpack (missing-changes replay)
  bool has_message = false;
  std::vector<u8> message;      // raw message value
};

static bool ops_equal(const OpRec& a, const OpRec& b) {
  return a.action == b.action && a.obj == b.obj && a.key == b.key &&
         a.elem == b.elem && a.datatype == b.datatype &&
         a.value_rid == b.value_rid;
}
static bool changes_equal(const ChangeRec& a, const ChangeRec& b) {
  if (a.actor != b.actor || a.seq != b.seq) return false;
  Clock da = a.deps, db = b.deps;
  std::sort(da.begin(), da.end());
  std::sort(db.begin(), db.end());
  if (da != db) return false;
  if (a.ops.size() != b.ops.size()) return false;
  for (size_t i = 0; i < a.ops.size(); ++i)
    if (!ops_equal(a.ops[i], b.ops[i])) return false;
  return true;
}

// Clock-vector folding (ISSUE 17 tentpole b): behind the settled GC
// frontier the per-change sparse `all_deps` vectors are the LAST
// per-history memory term -- O(actors) pairs per change, forever.
// amtpu_fold_clocks moves them into this per-doc densified table
// (the pool-resident clock table's row layout, doc-local actor ranks)
// and frees the sparse vectors; causal reads answer through the rows.
// Two sentinel encodings skip the table entirely:
//   * EMPTY   -- all_deps was {} (an actor's first change with no deps)
//   * TRIVIAL -- all_deps was exactly {(actor, seq-1)}, the linear-
//     history shape that dominates real corpora: ZERO retained bytes.
// Ranks are doc-local and append-only; rank lookup is a linear scan of
// `actor_order` (per-doc actor populations are small -- a pool-global
// sid-indexed vector per doc would dwarf the folded clocks at 1M docs).
// Rows re-widen in place when a new actor pushes A past the padded
// width Ap (bucket growth, floor 4).
static constexpr u32 FOLDROW_NONE = 0xffffffffu;     // sparse vector live
static constexpr u32 FOLDROW_EMPTY = 0xfffffffeu;    // all_deps == {}
static constexpr u32 FOLDROW_TRIVIAL = 0xfffffffdu;  // {(actor, seq-1)}
static constexpr u32 FOLDROW_MAX = 0xfffffffcu;      // highest real row

struct FoldClocks {
  std::vector<u32> actor_order;   // actor sids, first-folded order
  i64 A = 0, Ap = 0;              // actor count, padded row width
  std::vector<u32> tab;           // [n_rows * Ap] densified seqs
  i64 n_rows() const {
    return Ap ? static_cast<i64>(tab.size()) / Ap : 0;
  }
  i64 bytes() const {
    return static_cast<i64>(tab.size() * sizeof(u32) +
                            actor_order.size() * sizeof(u32));
  }
  i32 rank(u32 sid) const {   // linear: A is the doc's actor count
    for (size_t i = 0; i < actor_order.size(); ++i)
      if (actor_order[i] == sid) return static_cast<i32>(i);
    return -1;
  }
};

struct StateEntry {
  ChangeRec change;
  Clock all_deps;
  // op-state folding (amtpu_fold_settled): the change's op records /
  // deps / message were freed -- everything behind the settled frontier
  // is re-derivable from the doc's columnar snapshot, and the live
  // register/arena state already holds the fold's final values.
  // all_deps stays sparse until amtpu_fold_clocks moves it into the
  // doc's FoldClocks row `fold_row` (straggler closure walks then read
  // the row); duplicate consistency checks skip folded entries (the
  // original bytes were validated when the change first applied).
  bool folded = false;
  u32 fold_row = FOLDROW_NONE;
};

struct InboundRef {
  u32 obj, key, actor, value;
  u32 seq;
  bool operator==(const InboundRef& o) const {
    return obj == o.obj && key == o.key && actor == o.actor &&
           value == o.value && seq == o.seq;
  }
};

struct ObjMeta {
  u8 type = T_MAP;
  std::vector<InboundRef> inbound;
  std::vector<u32> key_order;   // register keys in first-write order
};

struct Arena {
  std::vector<i32> ctr;
  std::vector<u32> actor_sid;
  std::vector<i32> parent;
  std::vector<u8> visible;
  FlatMap<i32> index_of;    // ekey(actor_sid, elem) -> arena index
  std::vector<i32> visible_order;
  i64 max_elem = 0;
  u64 jstamp = 0;   // journal first-touch epoch (see BeginJournal)

  static u64 ekey(u32 actor_sid, i64 elem) {
    return (static_cast<u64>(actor_sid) << 32) ^ static_cast<u64>(elem);
  }
};

// Small-vector of field ops: nearly every register holds exactly one live
// writer, so the single-record case stays inline (no heap allocation per
// key -- half a million of these are created per 1M-op batch).  When a
// second record arrives, ALL records move to `rest` so iteration stays
// contiguous.
struct Register {
  OpRec first;
  std::vector<OpRec> rest;   // holds all records when n >= 2
  u32 n = 0;
  bool empty() const { return n == 0; }
  size_t size() const { return n; }
  void clear() { n = 0; rest.clear(); }
  void push_back(const OpRec& o) {
    if (n == 0) { first = o; n = 1; return; }
    if (n == 1) { rest.clear(); rest.push_back(first); }
    rest.push_back(o);
    ++n;
  }
  const OpRec* begin() const { return n <= 1 ? &first : rest.data(); }
  const OpRec* end() const { return begin() + n; }
  OpRec* begin() { return n <= 1 ? &first : rest.data(); }
  OpRec* end() { return begin() + n; }
  const OpRec& operator[](size_t i) const { return begin()[i]; }
  OpRec& operator[](size_t i) { return begin()[i]; }
};

struct DocState {
  Clock clock;
  Clock deps;
  std::unordered_map<u32, std::vector<StateEntry>> states;
  std::vector<u32> state_actor_order;   // actors in first-seen order
  std::vector<ChangeRec> queue;
  FlatMapStable<ObjMeta> objects;  // object sid -> meta
  FlatMapDense<Register> registers;  // rkey(obj, key) -> live field ops
  std::unordered_map<u32, Arena> arenas;
  // application-order log of (actor, seq): save() replays changes in
  // exactly this order so a loaded doc materializes byte-identically
  // (the reference's opSet.history list, op_set.js:270-276)
  std::vector<std::pair<u32, u32>> history;
  // bumped whenever the inbound-link index changes; pure-map path
  // renderings are cacheable while it holds still
  u64 path_epoch = 0;
  // undo machinery (reference: op_set.js:310-322 state; entries are
  // projected inverse ops -- action/obj/key/value only for undo entries,
  // + datatype for redo entries; actor=NONE, seq=0)
  std::vector<std::vector<OpRec>> undo_stack;
  size_t undo_pos = 0;
  std::vector<std::vector<OpRec>> redo_stack;
  // per-doc resource accounting (ISSUE 15, amtpu_doc_stats): retained
  // raw bytes / op records of the APPLIED states entries, kept in
  // lockstep at the four sites that mutate them (update_states push,
  // journal rollback pop, amtpu_truncate_history, amtpu_fold_settled).
  // The causal queue is deliberately NOT tracked here -- it is tiny
  // and walked fresh at stats time, so its accounting cannot drift.
  // Totals across docs reconcile bit-exactly with amtpu_history_bytes
  // / amtpu_op_count (the capacity tests pin it).
  i64 acct_raw_bytes = 0;
  i64 acct_ops = 0;
  i64 acct_folded_ops = 0;   // op records freed by amtpu_fold_settled
  // retained sparse all_deps pairs (update_states push / journal
  // rollback pop / amtpu_fold_clocks free); reconciles bit-exactly with
  // the fresh walk amtpu_clock_pairs does (the clock-fold tests pin it)
  i64 acct_clock_pairs = 0;
  // densified fold target for settled all_deps (amtpu_fold_clocks)
  FoldClocks foldclk;

  static u64 rkey(u32 obj, u32 key) {
    return (static_cast<u64>(obj) << 32) | key;
  }

  DocState() {}
};

struct Error : std::runtime_error {
  // kind 0 = AutomergeError, 1 = RangeError, 2 = TypeError
  int kind;
  Error(int k, const std::string& m) : std::runtime_error(m), kind(k) {}
};

// ---------------------------------------------------------------------------
// pool
// ---------------------------------------------------------------------------

// ---------------------------------------------------------------------------
// Pool-resident clock table (ISSUE 6 tentpole a).
//
// The per-batch clock table re-densifies and re-stages every change's
// all_deps row host->device on every batch, even though a row keyed
// (doc, actor, seq) is immutable once its change is applied.  This pool-
// LIFETIME table persists densified rows across batches: the batch's
// clock_idx then references pool-global rows, and the Python driver
// keeps a device-resident copy, uploading only the rows appended since
// the last batch (delta upload) -- the host->device clock traffic of a
// steady-state batch drops to its own new changes.
//
// Consistency contract (generation counter `gen`):
//   * rows densify against POOL-lifetime actor ranks (string lex order,
//     width Ap).  Registering ANY new actor invalidates every cached
//     row -- existing rows lack the new actor's column values (a row's
//     sparse all_deps may well contain an actor this table had never
//     ranked when the row was densified).  Steady actor populations
//     (serving traffic) keep the cache hot; a new actor costs one full
//     re-upload.
//   * a batch ROLLBACK invalidates: rows appended for its (now undone)
//     changes would go stale, and re-applied changes must re-densify.
//   * row count and Ap growth are append-only between invalidations, so
//     (gen, n_rows, Ap) is a complete validity token for the device
//     copy.
//   * pools past AMTPU_RESCLK_MAX_ACTORS (default 512) disable the
//     table permanently (row width is Ap: unbounded actor populations
//     would make every row pay for every actor ever seen); row count
//     past AMTPU_RESCLK_MAX_ROWS (default 1M) clears and restarts (a
//     rolling cache, bounding steady-state memory).
// ---------------------------------------------------------------------------
struct ResClockKey {
  const void* doc; u32 actor, seq;
  bool operator==(const ResClockKey& o) const {
    return doc == o.doc && actor == o.actor && seq == o.seq;
  }
};
struct ResClockKeyHash {
  size_t operator()(const ResClockKey& k) const {
    u64 h = reinterpret_cast<u64>(k.doc) ^ (u64(k.actor) << 21) ^ k.seq;
    h ^= h >> 33; h *= 0xff51afd7ed558ccdULL; h ^= h >> 33;
    return static_cast<size_t>(h);
  }
};

struct ResClock {
  std::vector<u32> actor_order;   // actor sids, string lex order
  std::vector<i32> rank_of;       // sid -> pool rank or -1
  i64 A = 0, Ap = 0;              // actor count, padded rank capacity
  std::vector<i32> tab;           // [n_rows * Ap] densified clock rows
  std::unordered_map<ResClockKey, u32, ResClockKeyHash> rows;
  u64 gen = 1;
  bool disabled = false;          // actor-population cap exceeded

  i64 n_rows() const {
    return Ap ? static_cast<i64>(tab.size()) / Ap : 0;
  }

  void invalidate() {
    tab.clear();
    rows.clear();
    ++gen;
  }
};

struct Pool {
  Interner intern;
  Interner vals;     // raw msgpack value spans, interned (vid)
  // single-character string values (every Text op carries one) bypass
  // the interner hash entirely via this table
  u32 char_sid[256];
  u32 char_rid[256];
  u32 root_sid;
  std::unordered_map<std::string, DocState> docs;
  std::vector<std::string> doc_order;   // first-seen order
  u64 epoch = 0;     // bumped per begin; arenas stamp their first touch
  // full host path (amtpu_pool_set_hostfull): the Python driver sets
  // this once per pool from the resolved jax backend (CPU -> on)
  bool host_full = false;
  // pool-resident clock table (ISSUE 6 tentpole a)
  ResClock resclk;

  Pool() {
    root_sid = intern.id_of(ROOT_ID);
    for (int i = 0; i < 256; ++i) char_sid[i] = char_rid[i] = NONE;
  }

  DocState& doc(const std::string& id) {
    auto it = docs.find(id);
    if (it != docs.end()) return it->second;
    DocState& d = docs[id];
    d.objects[root_sid] = ObjMeta{T_MAP, {}, {}};
    doc_order.push_back(id);
    return d;
  }
};

// interned raw msgpack bytes of an op's value
static inline const std::string& val_bytes(Pool& pool, const OpRec& op) {
  return pool.vals.str(op.value_rid);
}

// ---------------------------------------------------------------------------
// change decoding
// ---------------------------------------------------------------------------

static u8 parse_action_sv(std::string_view s) {
  if (s == "set") return A_SET;
  if (s == "del") return A_DEL;
  if (s == "link") return A_LINK;
  if (s == "ins") return A_INS;
  if (s == "makeMap") return A_MAKE_MAP;
  if (s == "makeList") return A_MAKE_LIST;
  if (s == "makeText") return A_MAKE_TEXT;
  if (s == "makeTable") return A_MAKE_TABLE;
  throw Error(1, "Unknown operation type " + std::string(s));
}
static const char* action_name(u8 a) {
  switch (a) {
    case A_SET: return "set";
    case A_DEL: return "del";
    case A_LINK: return "link";
    case A_INS: return "ins";
    case A_MAKE_MAP: return "makeMap";
    case A_MAKE_LIST: return "makeList";
    case A_MAKE_TEXT: return "makeText";
    default: return "makeTable";
  }
}
static u8 make_type(u8 a) {
  switch (a) {
    case A_MAKE_MAP: return T_MAP;
    case A_MAKE_LIST: return T_LIST;
    case A_MAKE_TEXT: return T_TEXT;
    default: return T_TABLE;
  }
}
static const char* type_name(u8 t) {
  switch (t) {
    case T_MAP: return "map";
    case T_LIST: return "list";
    case T_TEXT: return "text";
    default: return "table";
  }
}

// one-entry intern caches for strings that repeat across consecutive ops
// (object ids within a change, single-char text values): a short memcmp
// beats a hash+probe
// Two-way (current + previous, promote-on-hit) string caches for the
// hot decode fields.  Two entries, not one: table workloads alternate
// row-object ops with links into the table (obj: row,row,table,row2...)
// and row fields cycle two key names -- both patterns thrash a
// single-entry cache on every op.
struct DecodeCache {
  std::string_view obj_sv, obj_sv2, val_sv, key_sv, key_sv2;
  u32 obj_sid = NONE, obj_sid2 = NONE;
  u32 val_sid = NONE, val_rid = NONE;
  // key cache: text streams intern every elemId as a key TWICE in a row
  // (set, then the next op's ins) -- one intern hash instead of two
  u32 key_sid = NONE, key_sid2 = NONE;

  // shared two-way promote-on-hit scheme for both field caches
  static inline u32 lookup(Interner& in, std::string_view s,
                           std::string_view& sv, std::string_view& sv2,
                           u32& sid, u32& sid2) {
    if (sid == NONE || s != sv) {
      std::swap(sv, sv2);
      std::swap(sid, sid2);
      if (sid == NONE || s != sv) {
        sid = in.id_of(s);
        sv = s;
      }
    }
    return sid;
  }
  inline u32 obj_of(Interner& in, std::string_view s) {
    return lookup(in, s, obj_sv, obj_sv2, obj_sid, obj_sid2);
  }
  inline u32 key_of(Interner& in, std::string_view s) {
    // miss fallback: probe the obj entries before hashing -- a link's
    // key repeats the object id of the row ops just decoded (row add:
    // makeMap obj=row ... link key=row), which would otherwise evict
    // the two field-name keys every row
    if ((key_sid == NONE || s != key_sv) &&
        (key_sid2 == NONE || s != key_sv2)) {
      if (obj_sid != NONE && s == obj_sv) return obj_sid;
      if (obj_sid2 != NONE && s == obj_sv2) return obj_sid2;
    }
    return lookup(in, s, key_sv, key_sv2, key_sid, key_sid2);
  }
};

// Fixed-layout decode fast path.  The frontend's op builders (reference
// shapes: frontend/context.js:27-34; our encoders preserve the same key
// order) emit every op in canonical layout: {action, obj[, key[, value
// | elem][, datatype]]}.  This parser covers the WHOLE op vocabulary --
// ins/set/del/link/make* -- with literal memcmps instead of the per-key
// dispatch loop; any deviation (reordered keys, unknown fields, long
// headers) falls back to the generic decoder.
static const u8 FP_ACTION[7] = {0xa6, 'a','c','t','i','o','n'};
static const u8 FP_OBJ[4] = {0xa3, 'o','b','j'};
static const u8 FP_KEY[4] = {0xa3, 'k','e','y'};
static const u8 FP_ELEM[5] = {0xa4, 'e','l','e','m'};
static const u8 FP_VALUE[6] = {0xa5, 'v','a','l','u','e'};
static const u8 FP_DATATYPE[9] = {0xa8, 'd','a','t','a','t','y','p','e'};

static bool decode_op_fast(Reader& r, Pool& pool, u32 actor, u32 seq,
                           DecodeCache& dc, OpRec& op) {
  const u8* p = r.pos();
  const u8* end = r.end();
  if (end - p < 16) return false;
  const u8 m = p[0];
  if (m < 0x82 || m > 0x85) return false;
  const size_t nkeys = m & 0x0f;
  if (std::memcmp(p + 1, FP_ACTION, 7) != 0) return false;
  p += 8;
  const u8 ab = *p;
  if ((ab & 0xe0) != 0xa0) return false;
  const size_t alen = ab & 0x1f;
  if (static_cast<size_t>(end - p) < 1 + alen + 5) return false;
  std::string_view asv(reinterpret_cast<const char*>(p + 1), alen);
  // vocabulary probe without throwing: an unknown action string falls
  // back to the generic decoder, which raises the reference's error
  u8 action = 0xff;
  switch (alen) {
    case 3: action = asv == "set" ? A_SET : asv == "del" ? A_DEL
                     : asv == "ins" ? A_INS : 0xff; break;
    case 4: action = asv == "link" ? A_LINK : 0xff; break;
    case 7: action = asv == "makeMap" ? A_MAKE_MAP : 0xff; break;
    case 8: action = asv == "makeList" ? A_MAKE_LIST
                     : asv == "makeText" ? A_MAKE_TEXT : 0xff; break;
    case 9: action = asv == "makeTable" ? A_MAKE_TABLE : 0xff; break;
  }
  if (action == 0xff) return false;
  p += 1 + alen;
  if (std::memcmp(p, FP_OBJ, 4) != 0) return false;
  p += 4;
  // string header: fixstr or str8 (covers UUID object ids / 'uuid:ctr'
  // elemIds, which msgpack encodes as str8); anything longer falls back
  auto read_short_str = [&](std::string_view& out) {
    if (p >= end) return false;
    u8 hb = *p;
    size_t n, hdr;
    if (hb >= 0xa0 && hb <= 0xbf) { n = hb & 0x1f; hdr = 1; }
    else if (hb == 0xd9) {
      if (end - p < 2) return false;
      n = p[1]; hdr = 2;
    } else return false;
    if (static_cast<size_t>(end - p) < hdr + n) return false;
    out = std::string_view(reinterpret_cast<const char*>(p + hdr), n);
    p += hdr + n;
    return true;
  };
  std::string_view osv;
  if (!read_short_str(osv)) return false;

  op.action = action;
  op.elem = -1;
  op.actor = actor; op.seq = seq;
  op.datatype = NONE; op.value_rid = NONE; op.value_sid = NONE;
  op.key = NONE;
  op.obj = dc.obj_of(pool.intern, osv);

  if (action >= A_MAKE_MAP) {          // {action, obj}
    if (nkeys != 2) return false;
    r.advance_to(p);
    return true;
  }
  if (static_cast<size_t>(end - p) < 5 ||
      std::memcmp(p, FP_KEY, 4) != 0) return false;
  p += 4;
  std::string_view ksv;
  if (!read_short_str(ksv)) return false;
  op.key = dc.key_of(pool.intern, ksv);

  if (action == A_DEL) {               // {action, obj, key}
    if (nkeys != 3) return false;
    r.advance_to(p);
    return true;
  }
  if (action == A_INS) {               // {action, obj, key, elem}
    if (nkeys != 4 || static_cast<size_t>(end - p) < 6 ||
        std::memcmp(p, FP_ELEM, 5) != 0)
      return false;
    p += 5;
    u8 eb = *p;
    if (eb <= 0x7f) { op.elem = eb; p += 1; }
    else if (eb == 0xcc && end - p >= 2) { op.elem = p[1]; p += 2; }
    else if (eb == 0xcd && end - p >= 3) {
      op.elem = (u32(p[1]) << 8) | p[2]; p += 3;
    } else if (eb == 0xce && end - p >= 5) {
      op.elem = (u64(p[1]) << 24) | (u32(p[2]) << 16) |
                (u32(p[3]) << 8) | p[4];
      p += 5;
    } else return false;
    r.advance_to(p);
    return true;
  }

  // set / link: {action, obj, key, value[, datatype]}
  if (nkeys < 4 || static_cast<size_t>(end - p) < 7 ||
      std::memcmp(p, FP_VALUE, 6) != 0)
    return false;
  p += 6;
  u8 vb = *p;
  if (vb >= 0xa0 && vb <= 0xbf) {
    // short string value: intern via the single-char / run caches
    size_t vlen = vb & 0x1f;
    if (static_cast<size_t>(end - p) < 1 + vlen) return false;
    std::string_view s(reinterpret_cast<const char*>(p + 1), vlen);
    std::string_view raw(reinterpret_cast<const char*>(p), 1 + vlen);
    if (vlen == 1) {
      u8 c = static_cast<u8>(s[0]);
      if (pool.char_sid[c] == NONE) {
        pool.char_sid[c] = pool.intern.id_of(s);
        pool.char_rid[c] = pool.vals.id_of(raw);
      }
      op.value_sid = pool.char_sid[c];
      op.value_rid = pool.char_rid[c];
    } else {
      if (dc.val_sid == NONE || raw != dc.val_sv) {
        // link values repeat the key (a row add links the row object
        // under its own id): reuse the key's intern
        dc.val_sid = (s == ksv && op.key != NONE)
                         ? op.key : pool.intern.id_of(s);
        dc.val_rid = pool.vals.id_of(raw);
        dc.val_sv = raw;
      }
      op.value_sid = dc.val_sid;
      op.value_rid = dc.val_rid;
    }
    p += 1 + vlen;
  } else if (action == A_LINK) {
    // link targets must intern a value_sid (inbound-ref maintenance);
    // a non-fixstr target (str8 object id) takes the generic decoder
    return false;
  } else {
    // non-string or long-string value: generic raw-span capture
    Reader rv(p, end - p);
    auto span = rv.raw_value();
    op.value_rid = pool.vals.id_of(std::string_view(
        reinterpret_cast<const char*>(span.first), span.second));
    p = rv.pos();
  }
  if (nkeys == 5) {                    // trailing datatype
    if (static_cast<size_t>(end - p) < 10 ||
        std::memcmp(p, FP_DATATYPE, 9) != 0)
      return false;
    p += 9;
    std::string_view dsv;
    if (!read_short_str(dsv)) return false;
    op.datatype = pool.intern.id_of(dsv);
  } else if (nkeys != 4) {
    return false;
  }
  r.advance_to(p);
  return true;
}

static OpRec decode_op(Reader& r, Pool& pool, u32 actor, u32 seq,
                       DecodeCache& dc) {
  OpRec op;
  {
    if (decode_op_fast(r, pool, actor, seq, dc, op)) return op;
  }
  op.action = 0xff;
  op.obj = NONE; op.key = NONE; op.elem = -1;
  op.actor = actor; op.seq = seq;
  op.datatype = NONE; op.value_rid = NONE; op.value_sid = NONE;
  size_t n = r.read_map();
  for (size_t i = 0; i < n; ++i) {
    std::string_view k = r.read_str_view();
    // first-char dispatch: the op vocabulary is fixed and tiny, and this
    // loop runs once per op field of every change in a 1M-op batch
    const char k0 = k.empty() ? 0 : k[0];
    if (k0 == 'a' && k == "action") {
      op.action = parse_action_sv(r.read_str_view());
    } else if (k0 == 'o' && k == "obj") {
      op.obj = dc.obj_of(pool.intern, r.read_str_view());
    } else if (k0 == 'k' && k == "key") {
      op.key = dc.key_of(pool.intern, r.read_str_view());
    } else if (k0 == 'e' && k == "elem") {
      op.elem = r.read_int();
    } else if (k0 == 'd' && k == "datatype") {
      op.datatype = pool.intern.id_of(r.read_str_view());
    } else if (k0 == 'v' && k == "value") {
      if (r.peek_type() == Type::Str) {
        const uint8_t* start = r.pos();
        std::string_view s = r.read_str_view();
        std::string_view raw(reinterpret_cast<const char*>(start),
                             r.pos() - start);
        if (s.size() == 1) {
          u8 c = static_cast<u8>(s[0]);
          if (pool.char_sid[c] == NONE) {
            pool.char_sid[c] = pool.intern.id_of(s);
            pool.char_rid[c] = pool.vals.id_of(raw);
          }
          op.value_sid = pool.char_sid[c];
          op.value_rid = pool.char_rid[c];
        } else {
          if (dc.val_sid == NONE || raw != dc.val_sv) {
            dc.val_sid = pool.intern.id_of(s);
            dc.val_rid = pool.vals.id_of(raw);
            dc.val_sv = raw;
          }
          op.value_sid = dc.val_sid;
          op.value_rid = dc.val_rid;
        }
      } else {
        auto span = r.raw_value();
        op.value_rid = pool.vals.id_of(std::string_view(
            reinterpret_cast<const char*>(span.first), span.second));
      }
    } else r.skip();
  }
  if (op.action == 0xff) throw Error(1, "Unknown operation type undefined");
  return op;
}

// Local-change request envelope metadata (reference applyLocalChange
// validation, backend/index.js:175-190).  When passed to decode_change,
// the requestType pair is also STRIPPED from ch.raw -- requestType is
// transport-only and must not leak into the stored history that
// get_missing_changes ships to peers (backend/index.js:145).
struct LocalReq {
  bool has_actor = false, has_seq = false, has_request_type = false;
  std::string request_type;
};

static ChangeRec decode_change(Reader& r, Pool& pool,
                               const std::shared_ptr<std::vector<u8>>& slab,
                               LocalReq* lr = nullptr,
                               DecodeCache* dcp = nullptr) {
  ChangeRec ch;
  const uint8_t* start = r.pos();
  size_t n = r.read_map();
  const uint8_t* body = r.pos();
  ch.actor = NONE; ch.seq = 0;
  const uint8_t* ops_start = nullptr;
  const uint8_t* ops_end = nullptr;
  const uint8_t* rt_start = nullptr;
  const uint8_t* rt_end = nullptr;
  size_t ops_count = 0;
  // batch-shared cache (string_views into the batch slab, which
  // outlives every change): consecutive changes of one doc hit the
  // same object/keys, so resetting per change wastes most of the hits
  DecodeCache local_dc;
  DecodeCache& dc = dcp ? *dcp : local_dc;
  bool ops_inline = false;
  u32 stamp_actor = NONE, stamp_seq = 0;  // actor/seq at inline decode
  for (size_t i = 0; i < n; ++i) {
    const uint8_t* pair_start = r.pos();
    std::string_view k = r.read_str_view();
    if (k == "actor") {
      // local-request mode tolerates a missing/mistyped actor (it becomes
      // the reference's TypeError); the batch path stays strict
      if (!lr) {
        ch.actor = pool.intern.id_of(r.read_str_view());
      } else if (r.peek_type() == Type::Str) {
        ch.actor = pool.intern.id_of(r.read_str_view());
        lr->has_actor = true;
      } else r.skip();
    } else if (k == "seq") {
      if (!lr) {
        ch.seq = static_cast<u32>(r.read_int());
      } else if (r.peek_type() == Type::Int) {
        ch.seq = static_cast<u32>(r.read_int());
        lr->has_seq = true;
      } else r.skip();
    } else if (k == "deps") {
      size_t m = r.read_map();
      for (size_t j = 0; j < m; ++j) {
        u32 a = pool.intern.id_of(r.read_str_view());
        u32 s = static_cast<u32>(r.read_int());
        ch.deps.emplace_back(a, s);
      }
    } else if (k == "ops") {
      if (ch.actor != NONE && ch.seq != 0) {
        // canonical envelope order ({actor, seq, deps, ops, ...}): ops
        // decode inline in one walk
        ops_inline = true;
        stamp_actor = ch.actor;
        stamp_seq = ch.seq;
        // duplicate 'ops' keys follow last-wins like every other
        // envelope field (and the reference's JS object semantics)
        ch.ops.clear();
        ops_count = r.read_array();
        // payload-controlled count: clamp the reserve by what the
        // buffer could possibly hold (>=4 bytes/op) so a corrupt
        // header raises a decode error, not bad_alloc
        ch.ops.reserve(std::min(ops_count,
                                static_cast<size_t>(r.end() - r.pos()) / 4));
        for (size_t j = 0; j < ops_count; ++j)
          ch.ops.push_back(decode_op(r, pool, ch.actor, ch.seq, dc));
      } else {
        // ops need actor/seq which arrive after this key: remember the
        // span, generic-skip past it, re-parse once the map is read
        ops_start = r.pos();
        ops_count = r.read_array();
        for (size_t j = 0; j < ops_count; ++j) r.skip();
        ops_end = r.pos();
      }
    } else if (k == "message") {
      auto span = r.raw_value();
      ch.has_message = true;
      ch.message.assign(span.first, span.first + span.second);
    } else if (lr && k == "requestType") {
      lr->has_request_type = true;
      if (r.peek_type() == Type::Str)
        lr->request_type = std::string(r.read_str_view());
      else r.skip();
      rt_start = pair_start;
      rt_end = r.pos();
    } else r.skip();
  }
  if (rt_start) {
    Writer wr;
    wr.map(n - 1);
    wr.raw(body, static_cast<size_t>(rt_start - body));
    wr.raw(rt_end, static_cast<size_t>(r.pos() - rt_end));
    ch.raw.adopt(std::move(wr.buf));
  } else {
    ch.raw.slab = slab;
    ch.raw.off = static_cast<u32>(start - slab->data());
    ch.raw.len = static_cast<u32>(r.pos() - start);
  }
  if (ops_start && !ops_inline) {
    Reader ro(ops_start, static_cast<size_t>(ops_end - ops_start));
    ro.read_array();
    ch.ops.reserve(std::min(ops_count,
                            static_cast<size_t>(ops_end - ops_start) / 4));
    for (size_t j = 0; j < ops_count; ++j)
      ch.ops.push_back(decode_op(ro, pool, ch.actor, ch.seq, dc));
  } else if (ops_inline &&
             (ch.actor != stamp_actor || ch.seq != stamp_seq)) {
    // a malformed envelope repeated 'actor'/'seq' with a DIFFERENT value
    // after the 'ops' key: the envelope fields are last-wins (JS object
    // semantics, matching the span re-parse path), so re-stamp the
    // already-decoded ops with the final values
    for (OpRec& op : ch.ops) {
      op.actor = ch.actor;
      op.seq = ch.seq;
    }
  }
  return ch;
}

// parse elemId "actor:counter"; returns false for "_head" / malformed
static bool parse_elem_id(const std::string& s, Interner& intern,
                          u32* actor_sid, i64* ctr) {
  size_t pos = s.rfind(':');
  if (pos == std::string::npos) return false;
  i64 v = 0;
  if (pos + 1 >= s.size()) return false;
  for (size_t i = pos + 1; i < s.size(); ++i) {
    char c = s[i];
    if (c < '0' || c > '9') return false;
    v = v * 10 + (c - '0');
    if (v > 0x7fffffff) return false;   // arena counters are i32
  }
  *actor_sid = intern.id_of(s.substr(0, pos));
  *ctr = v;
  return true;
}

// ---------------------------------------------------------------------------
// batch
// ---------------------------------------------------------------------------

// shape bucket: next size in {2^k, 3*2^(k-1)} >= n, so padded kernel
// shapes waste at most 33% instead of 100% while the jit compile cache
// stays small (two shapes per octave)
static i64 bucket(i64 n, i64 floor_ = 16) {
  i64 size = floor_;
  while (size < n) {
    i64 mid = size + size / 2;
    if (mid >= n && mid % floor_ == 0) return mid;
    size *= 2;
  }
  return size;
}

// (the mid % floor_ guard also keeps dominance timelines a multiple of the
// kernel chunk, whose floor is the chunk length)

struct AppliedChange {
  u32 doc;            // dense batch doc index
  ChangeRec change;   // moved into st.states by update_states
  // the states entry holding the change after update_states; ops/raw live
  // there (OpRec heap data is stable across states-vector growth)
  ChangeRec* stored = nullptr;
};

struct DomEntry {    // one list-assign op in a per-object timeline
  i64 op_idx;
  i64 reg_row;
  i32 eidx;
};

// One dominance size class.  Built at begin() with the device-source index
// maps (er_src/orank_src/dom_src) that let the FUSED kernel gather its
// rank/delta inputs on device; the host-side er/orank/od mirrors are only
// filled by mid_phase() on the overflow-fallback path.
struct DomBlock {
  i64 W, Lp, Tp;
  std::vector<float> v0;       // [W*Lp] visibility at batch start
  std::vector<i32> er_src;     // [W*Lp] arena-global element index or -1
  std::vector<i32> oe;         // [W*Tp] local element index per timeline op
  std::vector<i32> orank_src;  // [W*Tp] arena-global element index or -1
  std::vector<i32> dom_src;    // [W*Tp] register row of the op or -1
  std::vector<u8> ov;          // [W*Tp]
  std::vector<i32> er, orank, od;  // fallback-path mirrors (filled in mid)
  std::vector<u64> akeys;      // slab rows: (doc << 32 | obj)
  std::vector<i32> indexes;    // filled by python, [W*Tp]
};

// prefix-sum Fenwick over rank positions (counts of visible elements);
// used by host dominance (mid) and the host-full in-emit index sweep
struct Fenwick {
  std::vector<i32> t;
  void reset(size_t n) { t.assign(n + 1, 0); }
  void add(i32 i, i32 d) {
    // i == -1 (an unranked arena row reaching a sweep) would loop
    // forever: x starts at 0 and x & -x stays 0.  Throw instead of
    // assert so -DNDEBUG release builds fail loudly rather than hang
    // (matching every other internal-invariant violation).
    if (i < 0)
      throw Error(0, "Fenwick add on unranked (negative) index");
    for (i32 x = i + 1; x < static_cast<i32>(t.size()); x += x & -x)
      t[x] += d;
  }
  i32 prefix(i32 i) const {  // sum of positions [0, i)
    i32 s = 0;
    for (i32 x = i; x > 0; x -= x & -x) s += t[x];
    return s;
  }
};

struct Batch {
  Pool* pool;
  // dense per-batch doc table: index -> (payload key, state)
  std::vector<std::string> bdoc_ids;
  std::vector<DocState*> bdocs;
  std::vector<AppliedChange> applied;
  std::vector<std::pair<u32, ChangeRec>> duplicates;

  // flat ops
  struct FlatOp { u32 doc; const OpRec* op; };
  std::vector<FlatOp> ops;

  // actor rank table
  std::vector<i32> rank_of;     // sid -> rank or -1
  std::vector<u32> rank_to_sid; // rank -> sid
  i64 A = 0, Ap = 0;

  // register rows
  i64 T = 0, Tp = 0;
  std::vector<i32> g_col, t_col, a_col, s_col, sort_idx;
  std::vector<u8> d_col;
  // deduplicated clock rows: ops of one change share one table row.
  // res_clock: clock_idx references the POOL-resident table instead
  // (clock_tab stays empty, CTp == 0; see ResClock)
  std::vector<i32> clock_tab;   // [CTp*Ap]
  std::vector<i32> clock_idx;   // [Tp] -> table row
  i64 CT = 0, CTp = 0;
  bool res_clock = false;
  bool resclk_appended = false;  // rollback must invalidate the pool table
  i64 resclk_hits = 0;           // rows served from persisted entries
  // trivial-group routing (ISSUE 6): single-stream register groups skip
  // the device batch and resolve in emit against the live mirror
  i64 n_triv_rows = 0, n_triv_groups = 0;
  // batch-owned copies of state register records: register mirrors are
  // REPLACED during emit, so src_records must never point into
  // st.registers (dangling after the first mirror update of a group)
  std::deque<OpRec> state_rec_store;
  std::vector<const OpRec*> src_records;  // row -> op record
  // op_idx -> register row; -1 = no row (non-assign), TRIVIAL_ROW = the
  // group resolves in emit via host_resolve_step (trivial-group routing)
  static constexpr i64 TRIVIAL_ROW = -2;
  std::vector<i64> assign_row_of_op;

  // arenas
  i64 L = 0, Lp = 0;
  i64 max_arena_len = 0;   // bound on DFS chain length (chains are per-object)
  std::vector<i32> obj_col, par_col, ctr_col, act_col, lin_sort;
  std::vector<u8> val_col;
  std::vector<u64> arena_keys;                   // (doc << 32 | obj), order
  std::unordered_map<u64, i64> arena_base;

  // register kernel outputs (copied in at mid())
  std::vector<i32> k_winner, k_conflicts, k_alive;
  std::vector<u8> k_overflow;
  // packed-mode alternative: the kernel's packed word per row (24-bit
  // winner | 6-bit alive, saturated at 63 | overflow in bit 30) +
  // conflicts only for the rare rows that kept >1 member, stored CSR
  // (row -> (offset, len) into sparse_vals) so escalation-tier rows of
  // ANY width ride the same channel as the base kernel's window-wide
  // rows
  std::vector<i32> k_packed;
  FlatMap<std::pair<i32, i32>> sparse_conflicts;
  std::vector<i32> sparse_vals;
  bool packed_mode = false;
  std::vector<i32> rank;        // [L]
  int window = 8;

  // overflow fallback
  std::unordered_map<i64, Register> host_registers;  // op_idx -> register

  // member-window mode (groups wider than the sliding window): per-row
  // candidate predecessor indexes + host-computed overflow flags
  bool use_members = false;
  bool any_ovf = false;
  i64 n_pre_ovf = 0;    // rows pre-flagged host_ovf at member build
  // resolve registers incrementally at emit against the live mirror --
  // no kernel dispatch at all (amtpu_mid_hostreg; map-only batches
  // whose groups are mostly wider than the member window)
  bool host_reg_mode = false;
  // stamp-reset dense clock projection for host_resolve_step: the
  // applying op's allDeps keyed by actor sid, refilled once per
  // (doc, actor, seq) change instead of scanned per register prior
  std::vector<u64> dense_stamp;       // [interner size], lazily grown
  std::vector<u32> dense_seq;
  u64 dense_epoch = 0;
  u32 dense_doc = ~0u, dense_actor = NONE, dense_seqno = 0;
  // full host path (CPU backend): encode skips register rows and member
  // windows, no kernel dispatch; emit resolves registers via
  // host_resolve_step and list indexes via an in-emit Fenwick sweep
  bool host_full = false;
  std::vector<i32> rank_host;             // host RGA ranks, lazy
  struct HostFen { Fenwick fen; i64 base = 0; };
  std::unordered_map<u64, HostFen> host_fens;   // akey -> running counts
  std::vector<i32> mem_idx;    // [Tp * WINDOW]
  std::vector<u8> host_ovf;    // [Tp]
  // Escalation member layout (built at begin when member-mode overflow
  // exists): every flagged group's rows in (group, time) order plus
  // each row's candidate window -- the same per-actor-latest-seq
  // streams rule as the base member build, at UNLIMITED width, with
  // same-change duplicate assigns accumulating -- so the Python tier
  // ladder pads tier chunks with vectorized copies instead of
  // re-deriving windows row by row (ISSUE 3 tentpole a/c).
  std::vector<i64> esc_group_meta;   // [n_groups * 3]: row_start, n, width
  std::vector<i32> esc_rows;         // [R] global rows
  std::vector<i64> esc_mem_off;      // [R + 1] CSR offsets
  std::vector<i32> esc_mem;          // CSR values, group-LOCAL indexes

  // per-op arena index resolved by prepass in application order:
  // -2 = not a list assign, -1 = dropped del on an absent element
  std::vector<i32> pre_eidx;

  // dominance
  std::vector<DomBlock> dom_blocks;
  // op_idx -> kernel list index; INT32_MIN = no entry (dense: op ids are
  // 0..n_ops, and ~half the headline workload's ops are list assigns)
  std::vector<i32> list_index_of_op;
  std::unordered_map<u64, std::vector<DomEntry>> obj_ops;
  std::vector<i32> eidx_of_op;                    // op_idx -> eidx or -1
  bool fused_ok = false;
  bool resident_ok = false;
  // widest register group in this batch (rows incl. pre-existing state);
  // the Python driver sizes the sliding window from it
  i64 max_group = 0;

  // load-batch mode (amtpu_begin_columnar): emit performs every state
  // mutation (mirrors, inbound, visibility, Fenwick) but writes NO
  // patch bytes -- checkpoint restores discard them, and at 1M docs
  // the skipped diff rendering is a measurable slice of cold start
  bool no_patch = false;
  // local-change mode (apply_local_change / undo / redo):
  // kind 0 = not local, 1 = undoable change, 2 = undo, 3 = redo
  int local_kind = 0;
  u32 local_actor = NONE;
  u32 local_seq = 0;
  std::vector<u8> capture;        // [n_ops] undo-capture flag (kind 1)
  std::vector<OpRec> undo_local;  // captured inverse ops (filled in emit)
  std::vector<OpRec> pending_redo;  // redo ops captured at begin (kind 2)

  // result
  std::vector<u8> result;

  std::string err_msg;
  int err_kind = -1;

  // phase wall times (seconds), read back via amtpu_batch_trace
  double tr_decode = 0, tr_schedule = 0, tr_encode = 0, tr_mid = 0,
         tr_emit = 0, tr_domlay = 0;
  // scheduler coverage counters (wavefront measurement, docs/PERF.md):
  // changes admitted by the in-order fast path vs through the causal
  // queue fixpoint
  i64 n_sched_fast = 0, n_sched_queued = 0;
  // caller declared it will fill indexes via amtpu_host_dominance, so
  // mid_phase must not fill the device-fallback mirrors (amtpu_mid's
  // host_dom parameter)
  bool host_dom = false;
};

// thread CPU time, not wall: phase costs stay truthful when sharded pools
// contend for the host's single core (descheduled time doesn't count)
static inline double mono_now() {
  timespec ts;
  clock_gettime(CLOCK_THREAD_CPUTIME_ID, &ts);
  return ts.tv_sec + ts.tv_nsec * 1e-9;
}

// ---------------------------------------------------------------------------
// phase 1: schedule + prepass + encode
// ---------------------------------------------------------------------------

// The transitively-closed clock of (actor, seq), readable whether the
// entry still holds its sparse all_deps vector or amtpu_fold_clocks
// already moved it into the doc's FoldClocks row.  Three access shapes
// replace the old materializing `all_deps_of` reference (a folded row
// has no sparse vector to reference):
//   * for_each_dep   -- iterate (actor, seq) pairs (closure walks,
//                       densify, actor marking)
//   * clock_get_deps -- O(rank) point lookup (rec_concurrent)
//   * read_all_deps  -- merge the pairs into a caller clock
// Pair ORDER is not part of the contract: every consumer merges via
// clock_set_max, densifies into ranked rows, or compares per-actor
// coverage -- clock semantics are order-insensitive throughout.
static const StateEntry* state_entry_of(DocState& st, u32 actor, u32 seq) {
  auto it = st.states.find(actor);
  if (it == st.states.end()) return nullptr;
  if (seq == 0 || seq > it->second.size()) return nullptr;
  return &it->second[seq - 1];
}

template <class F>
static void for_each_dep(DocState& st, u32 actor, u32 seq, F&& f) {
  const StateEntry* e = state_entry_of(st, actor, seq);
  if (!e) return;
  if (e->fold_row == FOLDROW_NONE) {
    for (auto& [a, s] : e->all_deps) f(a, s);
  } else if (e->fold_row == FOLDROW_EMPTY) {
    // no deps
  } else if (e->fold_row == FOLDROW_TRIVIAL) {
    f(actor, seq - 1);
  } else {
    const FoldClocks& fc = st.foldclk;
    const u32* row = fc.tab.data() +
                     static_cast<size_t>(e->fold_row) * fc.Ap;
    for (i64 r = 0; r < fc.A; ++r)
      if (row[r]) f(fc.actor_order[r], row[r]);
  }
}

static u32 clock_get_deps(DocState& st, u32 actor, u32 seq, u32 qa) {
  const StateEntry* e = state_entry_of(st, actor, seq);
  if (!e) return 0;
  if (e->fold_row == FOLDROW_NONE) return clock_get(e->all_deps, qa);
  if (e->fold_row == FOLDROW_EMPTY) return 0;
  if (e->fold_row == FOLDROW_TRIVIAL) return qa == actor ? seq - 1 : 0;
  const FoldClocks& fc = st.foldclk;
  i32 r = fc.rank(qa);
  if (r < 0) return 0;
  return fc.tab[static_cast<size_t>(e->fold_row) * fc.Ap + r];
}

static void read_all_deps(DocState& st, u32 actor, u32 seq, Clock& out) {
  for_each_dep(st, actor, seq,
               [&](u32 a, u32 s) { clock_set_max(out, a, s); });
}

static void schedule(Pool& pool, Batch& b,
                     std::vector<std::vector<ChangeRec>>& incoming) {
  for (u32 doc = 0; doc < incoming.size(); ++doc) {
    auto& changes = incoming[doc];
    DocState& st = *b.bdocs[doc];
    Clock shadow = st.clock;
    std::vector<ChangeRec> queue = std::move(st.queue);
    st.queue.clear();
    auto is_ready = [&](const ChangeRec& c) {
      if (clock_get(shadow, c.actor) < c.seq - 1) return false;
      for (auto& [da, ds] : c.deps)
        if (clock_get(shadow, da) < ds) return false;
      return true;
    };
    auto admit = [&](ChangeRec& c) {
      if (c.seq <= clock_get(shadow, c.actor)) {
        b.duplicates.emplace_back(doc, std::move(c));
      } else {
        clock_set_max(shadow, c.actor, c.seq);
        b.applied.push_back({doc, std::move(c)});
      }
    };
    for (auto& ch : changes) {
      // fast path (the common in-order case): nothing buffered and the
      // change is causally ready -- no queue machinery at all
      if (queue.empty() && is_ready(ch)) {
        ++b.n_sched_fast;
        admit(ch);
        continue;
      }
      ++b.n_sched_queued;
      queue.push_back(std::move(ch));
      bool progress = true;
      while (progress) {
        progress = false;
        std::vector<ChangeRec> next_q;
        for (auto& c : queue) {
          if (is_ready(c)) {
            progress = true;
            admit(c);
          } else {
            next_q.push_back(std::move(c));
          }
        }
        queue = std::move(next_q);
        if (!progress) break;
      }
    }
    st.queue = std::move(queue);
  }
}

// Rollback journal for the begin phases: a failed batch must leave the
// pool untouched (the reference backend is immutable and discards failed
// state), but journaling is much cheaper than a separate read-only
// validation pass -- the success path records one entry per touched
// doc/arena (plus one per applied change), and only error paths pay the
// walk-back.
struct BeginJournal {
  // queues: pre-schedule contents of non-empty queues (rare)
  std::vector<std::pair<u32, std::vector<ChangeRec>>> queues;
  // prepass: objects created in this batch, arena sizes at first touch
  // (appended elements are erased by re-deriving their ekeys from the
  // arena columns)
  std::vector<std::pair<u32, u32>> created_objs;        // (doc, obj sid)
  std::vector<std::tuple<u32, u32, i64, i64>> arenas;   // (doc,obj,n,max)
  // update_states: clock/deps snapshots at first touch + appended entries
  std::vector<u8> snapped;                              // per batch doc
  std::vector<std::pair<u32, size_t>> histories;        // (doc, old size)
  std::vector<std::pair<u32, std::pair<Clock, Clock>>> clocks;
  std::vector<std::pair<u32, u32>> state_pushes;        // (doc, actor sid)
  std::vector<std::pair<u32, size_t>> actor_orders;     // (doc, old size)

  void rollback(Batch& b) {
    for (auto it = state_pushes.rbegin(); it != state_pushes.rend(); ++it) {
      DocState& st = *b.bdocs[it->first];
      auto& entries = st.states[it->second];
      // per-doc accounting: the popped entry leaves the retained set
      // (entries pushed this batch are never folded, so ops is exact)
      st.acct_raw_bytes -=
          static_cast<i64>(entries.back().change.raw.size());
      st.acct_ops -= static_cast<i64>(entries.back().change.ops.size());
      // entries pushed this batch are never clock-folded, so the sparse
      // all_deps vector is still the whole contribution
      st.acct_clock_pairs -=
          static_cast<i64>(entries.back().all_deps.size());
      entries.pop_back();
      if (entries.empty()) st.states.erase(it->second);
    }
    // reverse: per-doc sizes were recorded increasing, the earliest wins
    for (auto it = actor_orders.rbegin(); it != actor_orders.rend(); ++it)
      b.bdocs[it->first]->state_actor_order.resize(it->second);
    for (auto& [d, sz] : histories)
      b.bdocs[d]->history.resize(sz);
    for (auto& [d, cd] : clocks) {
      b.bdocs[d]->clock = std::move(cd.first);
      b.bdocs[d]->deps = std::move(cd.second);
    }
    for (auto it = arenas.rbegin(); it != arenas.rend(); ++it) {
      auto [d, obj, n, max_elem] = *it;
      Arena& ar = b.bdocs[d]->arenas[obj];
      for (size_t i = n; i < ar.ctr.size(); ++i)
        ar.index_of.erase(Arena::ekey(ar.actor_sid[i], ar.ctr[i]));
      ar.ctr.resize(n);
      ar.actor_sid.resize(n);
      ar.parent.resize(n);
      ar.visible.resize(n);
      ar.max_elem = max_elem;
    }
    for (auto& [d, obj] : created_objs) {
      b.bdocs[d]->objects.erase(obj);
      b.bdocs[d]->arenas.erase(obj);
    }
    for (u32 d = 0; d < b.bdocs.size(); ++d) b.bdocs[d]->queue.clear();
    for (auto& [d, q] : queues) b.bdocs[d]->queue = std::move(q);
    // pool-resident clock rows appended for the rolled-back changes are
    // now stale (and a retry must re-densify them): cross-path
    // invalidation via the generation counter
    if (b.resclk_appended) {
      b.pool->resclk.invalidate();
      b.resclk_appended = false;
    }
  }
};

static void update_states(Pool& pool, Batch& b, BeginJournal& j) {
  j.snapped.assign(b.bdocs.size(), 0);
  j.state_pushes.reserve(b.applied.size());
  Clock dep_scratch;  // reused across changes (swap with st.deps below)
  for (auto& ac : b.applied) {
    DocState& st = *b.bdocs[ac.doc];
    ChangeRec& ch = ac.change;
    const u32 actor = ch.actor, seq = ch.seq;
    if (!j.snapped[ac.doc]) {
      j.snapped[ac.doc] = 1;
      j.clocks.emplace_back(ac.doc, std::make_pair(st.clock, st.deps));
      j.histories.emplace_back(ac.doc, st.history.size());
    }
    st.history.emplace_back(actor, seq);
    // Exact-closure fast seed: the authoring actor contributes exactly
    // (actor, seq-1) -- pinned regardless of what ch.deps claims -- and
    // its all_deps entry is already transitively closed, so start from
    // a copy of it.  Any other dep (da, ds) whose ds is already covered
    // by the seed contributes nothing (closed clocks are monotone:
    // allDeps(da,ds) is a subset of any closed clock containing da at
    // >= ds) -- the common linear-history / gossip case skips most
    // merges entirely.  (The former code materialized a pinned copy of
    // ch.deps first; iterating it directly drops one Clock alloc+copy
    // per change.)
    Clock all_deps;
    if (seq > 1) read_all_deps(st, actor, seq - 1, all_deps);
    auto cover = [&](u32 da, u32 ds) {
      if (ds == 0 || clock_get(all_deps, da) >= ds) return;
      read_all_deps(st, da, ds, all_deps);
      clock_set_max(all_deps, da, ds);
    };
    cover(actor, seq - 1);
    for (auto& [da, ds] : ch.deps)
      if (da != actor) cover(da, ds);
    auto sit = st.states.find(actor);
    if (sit == st.states.end()) {
      j.actor_orders.emplace_back(ac.doc, st.state_actor_order.size());
      st.state_actor_order.push_back(actor);
      sit = st.states.emplace(actor, std::vector<StateEntry>{}).first;
    }
    // the change MOVES into the states entry (its ops/raw heap data stays
    // put, so batch-held pointers into them remain valid)
    sit->second.push_back({std::move(ch), std::move(all_deps)});
    st.acct_raw_bytes +=
        static_cast<i64>(sit->second.back().change.raw.size());
    st.acct_ops += static_cast<i64>(sit->second.back().change.ops.size());
    st.acct_clock_pairs +=
        static_cast<i64>(sit->second.back().all_deps.size());
    const Clock& adeps = sit->second.back().all_deps;
    j.state_pushes.emplace_back(ac.doc, actor);
    clock_set_max(st.clock, actor, seq);
    // frontier rebuild into a reused scratch (swap leaves the old deps
    // buffer as next change's scratch -- zero allocs steady-state)
    dep_scratch.clear();
    for (auto& [a, s] : st.deps)
      if (s > clock_get(adeps, a)) dep_scratch.emplace_back(a, s);
    clock_set_max(dep_scratch, actor, seq);
    // deps[actor] = seq exactly (not max -- seq is the new frontier)
    for (auto& p : dep_scratch) if (p.first == actor) p.second = seq;
    st.deps.swap(dep_scratch);
  }
  // resolve stored pointers after all pushes (the entries vectors may have
  // reallocated; states[actor][seq-1] is the invariant address)
  for (auto& ac : b.applied)
    ac.stored = &b.bdocs[ac.doc]
                     ->states[ac.change.actor][ac.change.seq - 1].change;
}

// Duplicate consistency, read-only: compares against pre-batch states and
// against changes applied earlier in this same batch (in-batch seq reuse).
static void validate_duplicates(Pool& pool, Batch& b) {
  if (b.duplicates.empty()) return;
  std::unordered_map<K3, const ChangeRec*, K3Hash> applied_idx;
  for (auto& ac : b.applied)
    applied_idx[K3{ac.doc, ac.change.actor, ac.change.seq}] = &ac.change;
  for (auto& [doc, ch] : b.duplicates) {
    DocState& st = *b.bdocs[doc];
    const ChangeRec* prior = nullptr;
    auto it = st.states.find(ch.actor);
    if (it != st.states.end() && ch.seq >= 1 &&
        ch.seq - 1 < it->second.size()) {
      // folded entries freed their op records (amtpu_fold_settled);
      // the duplicate is behind the settled frontier, so its bytes
      // were already validated when the change first applied
      if (it->second[ch.seq - 1].folded) continue;
      prior = &it->second[ch.seq - 1].change;
    }
    if (!prior) {
      auto ait = applied_idx.find(K3{doc, ch.actor, ch.seq});
      if (ait != applied_idx.end()) prior = ait->second;
    }
    if (prior && !changes_equal(*prior, ch))
      throw Error(0, "Inconsistent reuse of sequence number " +
                         std::to_string(ch.seq) + " by " +
                         pool.intern.str(ch.actor));
  }
}

static void prepass(Pool& pool, Batch& b, BeginJournal& j) {
  for (auto& ac : b.applied) {
    DocState& st = *b.bdocs[ac.doc];
    for (const OpRec& op : ac.stored->ops) {
      if (op.action >= A_MAKE_MAP) {
        if (st.objects.find(op.obj))
          throw Error(0, "Duplicate creation of object " +
                             pool.intern.str(op.obj));
        ObjMeta meta;
        meta.type = make_type(op.action);
        st.objects[op.obj] = std::move(meta);
        if (is_list_type(make_type(op.action))) st.arenas[op.obj];
        j.created_objs.emplace_back(ac.doc, op.obj);
        b.pre_eidx.push_back(-2);
      } else if (op.action == A_INS) {
        if (!st.objects.find(op.obj))
          throw Error(0, "Modification of unknown object " +
                             pool.intern.str(op.obj));
        // arena columns are i32 (the kernel layout) and ekey packs elem
        // into the low 32 bits; out-of-range counters would corrupt the
        // index (and collide with FlatMap's reserved empty key at -1)
        if (op.elem < 0 || op.elem > 0x7fffffff)
          throw Error(0, "List element counter out of range: " +
                             std::to_string(op.elem));
        Arena& ar = st.arenas[op.obj];
        if (ar.jstamp != pool.epoch) {
          ar.jstamp = pool.epoch;
          j.arenas.emplace_back(ac.doc, op.obj,
                                static_cast<i64>(ar.ctr.size()),
                                ar.max_elem);
        }
        u64 ek = Arena::ekey(op.actor, op.elem);
        if (ar.index_of.find(ek))
          throw Error(0, "Duplicate list element ID " +
                             pool.intern.str(op.actor) + ":" +
                             std::to_string(op.elem));
        i32 parent_idx;
        const std::string& pkey = pool.intern.str(op.key);
        if (pkey == "_head") {
          parent_idx = -1;
        } else {
          u32 pa; i64 pc;
          bool ok = parse_elem_id(pkey, pool.intern, &pa, &pc);
          if (ok) {
            const i32* pit = ar.index_of.find(Arena::ekey(pa, pc));
            if (!pit) ok = false;
            else parent_idx = *pit;
          }
          if (!ok)
            throw Error(0, "Missing index entry for list element " + pkey);
        }
        *ar.index_of.insert(ek).first = static_cast<i32>(ar.ctr.size());
        ar.ctr.push_back(static_cast<i32>(op.elem));
        ar.actor_sid.push_back(op.actor);
        ar.parent.push_back(parent_idx);
        ar.visible.push_back(0);
        if (op.elem > ar.max_elem) ar.max_elem = op.elem;
        b.pre_eidx.push_back(-2);
      } else if (is_assign(op.action)) {
        ObjMeta* oit = st.objects.find(op.obj);
        if (!oit)
          throw Error(0, "Modification of unknown object " +
                             pool.intern.str(op.obj));
        // list assigns resolve their element HERE, in application order
        // (the oracle applies ops strictly in order, so an assign
        // referencing an element inserted later in the batch errors, and
        // a multi-error batch surfaces its FIRST error).  A set/link on
        // an absent element always resolves to a live register and
        // errors; a del never has surviving concurrent priors and is
        // silently dropped.  The resolved index is cached for dom_layout.
        if (is_list_type(oit->type)) {
          Arena& ar = st.arenas[op.obj];
          const std::string& kstr = pool.intern.str(op.key);
          u32 ea; i64 ec;
          i32 eidx = -1;
          if (parse_elem_id(kstr, pool.intern, &ea, &ec)) {
            const i32* eit = ar.index_of.find(Arena::ekey(ea, ec));
            if (eit) eidx = *eit;
          }
          if (eidx < 0 && op.action != A_DEL)
            throw Error(0, "Missing index entry for list element " + kstr);
          b.pre_eidx.push_back(eidx);
        } else {
          b.pre_eidx.push_back(-2);   // not a list assign
        }
      } else {
        throw Error(1, std::string("Unknown operation type ") +
                           action_name(op.action));
      }
    }
  }
}

static void encode(Pool& pool, Batch& b) {
  Interner& in = pool.intern;

  // flat op list; in undoable (local-change) mode also flag which assign
  // ops capture inverse ops: only those whose object was NOT created by
  // the same change (reference topLevel gate, op_set.js:233-250 newObjects
  // + :193-200)
  {
    size_t total = 0;
    for (auto& ac : b.applied) total += ac.stored->ops.size();
    b.ops.reserve(total);
  }
  for (auto& ac : b.applied) {
    std::unordered_set<u32> new_objs;
    for (const OpRec& op : ac.stored->ops) {
      b.ops.push_back({ac.doc, &op});
      if (b.local_kind == 1) {
        bool cap = is_assign(op.action) && !new_objs.count(op.obj);
        if (op.action >= A_MAKE_MAP) new_objs.insert(op.obj);
        b.capture.push_back(cap ? 1 : 0);
      }
    }
  }

  // --- discover groups / arenas; collect involved actors -----------------
  std::vector<u8> involved(in.size(), 0);
  auto mark = [&](u32 sid) {
    if (sid >= involved.size()) involved.resize(sid + 1, 0);
    involved[sid] = 1;
  };
  if (b.host_full) {
    // no kernel rows will be built, so actor ranks are only consumed by
    // the host paths (host_resolve_step's prior ordering, host_rank's
    // sibling sort).  Every register prior and every clock-dep actor
    // has a states entry by construction (they all arrived via applied
    // changes), so marking each batch doc's state_actor_order covers
    // them in O(actors) -- replacing the per-group register walks the
    // kernel path needs (group discovery below is skipped entirely).
    for (u32 d = 0; d < b.bdocs.size(); ++d)
      for (u32 a : b.bdocs[d]->state_actor_order) mark(a);
    for (auto& ac : b.applied) mark(ac.change.actor);
  } else {
    for (auto& ac : b.applied) {
      DocState& st = *b.bdocs[ac.doc];
      mark(ac.change.actor);
      for_each_dep(st, ac.change.actor, ac.change.seq,
                   [&](u32 da, u32) { mark(da); });
    }
  }

  // group ids per doc, keyed by rkey(obj, key): per-doc flat maps keep
  // probes in small hot tables instead of one giant shared one
  std::vector<FlatMap<u32>> doc_gids(b.bdocs.size());
  std::vector<K3> gid_order;
  auto akey_of = [](u32 doc, u32 obj) {
    return (static_cast<u64>(doc) << 32) | obj;
  };

  // register-state pointers per group, stashed at discovery so the
  // state-row pass below does not re-run the register lookups
  std::vector<const Register*> gid_regs;
  // consecutive ops overwhelmingly hit the same (doc, obj): cache the
  // object-type lookup and the arena-key emplace
  u32 last_doc = ~0u, last_obj = NONE;
  bool last_is_list = false, have_last = false;
  u64 last_ak = ~0ull;
  for (auto& f : b.ops) {
    DocState& st = *b.bdocs[f.doc];
    const OpRec& op = *f.op;
    if (is_assign(op.action)) {
      if (!b.host_full) {
        auto [slot, inserted] =
            doc_gids[f.doc].insert(DocState::rkey(op.obj, op.key));
        if (inserted) {
          *slot = static_cast<u32>(gid_order.size());
          gid_order.push_back(K3{f.doc, op.obj, op.key});
          const Register* reg =
              st.registers.find(DocState::rkey(op.obj, op.key));
          gid_regs.push_back(reg);
          if (reg) {
            for (auto& rec : *reg) {
              mark(rec.actor);
              for_each_dep(st, rec.actor, rec.seq,
                           [&](u32 da, u32) { mark(da); });
            }
          }
        }
      }
      if (!have_last || f.doc != last_doc || op.obj != last_obj) {
        ObjMeta* oit = st.objects.find(op.obj);
        last_is_list = oit != nullptr && is_list_type(oit->type);
        last_doc = f.doc; last_obj = op.obj; have_last = true;
      }
      if (last_is_list) {
        u64 ak = akey_of(f.doc, op.obj);
        if (ak != last_ak) {
          last_ak = ak;
          if (b.arena_base.emplace(ak, -1).second) b.arena_keys.push_back(ak);
        }
      }
    } else if (op.action == A_INS) {
      u64 ak = akey_of(f.doc, op.obj);
      if (ak != last_ak) {
        last_ak = ak;
        if (b.arena_base.emplace(ak, -1).second) b.arena_keys.push_back(ak);
      }
    }
  }
  for (u64 ak : b.arena_keys) {
    Arena& ar = b.bdocs[ak >> 32]->arenas[static_cast<u32>(ak)];
    for (u32 sid : ar.actor_sid) mark(sid);
  }

  // --- actor rank table (string lex order) --------------------------------
  std::vector<u32> inv_sids;
  for (u32 sid = 0; sid < involved.size(); ++sid)
    if (involved[sid]) inv_sids.push_back(sid);
  if (inv_sids.empty()) inv_sids.push_back(in.id_of(""));
  std::sort(inv_sids.begin(), inv_sids.end(),
            [&](u32 a, u32 c) { return in.str(a) < in.str(c); });

  // Resident clock table eligibility (latched env, like AMTPU_RESIDENT):
  // kernel-path batches share the pool-lifetime table; the full host
  // path never stages clocks at all.
  static const bool resclk_enabled = []() {
    const char* e = getenv("AMTPU_RESIDENT_CLK");
    if (!e) e = getenv("AMTPU_RESIDENT");
    return !e || atoi(e) != 0;     // default ON (follows the latch)
  }();
  static const i64 resclk_max_actors = []() {
    const char* e = getenv("AMTPU_RESCLK_MAX_ACTORS");
    return e ? atoll(e) : DEF_RESCLK_MAX_ACTORS;
  }();
  static const i64 resclk_max_rows = []() {
    const char* e = getenv("AMTPU_RESCLK_MAX_ROWS");
    return e ? atoll(e) : DEF_RESCLK_MAX_ROWS;
  }();
  ResClock& rc = pool.resclk;
  b.res_clock = resclk_enabled && !b.host_full && !rc.disabled;
  if (b.res_clock) {
    // register new actors into the pool order; ANY new actor
    // invalidates cached rows (their densified columns lack the new
    // actor's all_deps values)
    bool grew = false;
    for (u32 sid : inv_sids) {
      if (sid < rc.rank_of.size() && rc.rank_of[sid] >= 0) continue;
      auto pos = std::lower_bound(
          rc.actor_order.begin(), rc.actor_order.end(), sid,
          [&](u32 a, u32 c) { return in.str(a) < in.str(c); });
      rc.actor_order.insert(pos, sid);
      grew = true;
    }
    if (static_cast<i64>(rc.actor_order.size()) > resclk_max_actors) {
      rc.disabled = true;
      rc.invalidate();
      b.res_clock = false;
    } else {
      if (grew) {
        rc.invalidate();
        rc.rank_of.assign(in.size(), -1);
        for (size_t i = 0; i < rc.actor_order.size(); ++i)
          rc.rank_of[rc.actor_order[i]] = static_cast<i32>(i);
        rc.A = static_cast<i64>(rc.actor_order.size());
        rc.Ap = bucket(rc.A, 4);
      } else if (rc.rank_of.size() < in.size()) {
        rc.rank_of.resize(in.size(), -1);
      }
      if (rc.n_rows() > resclk_max_rows) rc.invalidate();
    }
  }
  if (b.res_clock) {
    b.rank_of = rc.rank_of;
    b.rank_to_sid = rc.actor_order;
    b.A = rc.A;
    b.Ap = rc.Ap;
  } else {
    b.rank_of.assign(in.size(), -1);
    b.rank_to_sid = inv_sids;
    for (size_t i = 0; i < inv_sids.size(); ++i)
      b.rank_of[inv_sids[i]] = static_cast<i32>(i);
    b.A = static_cast<i64>(inv_sids.size());
    b.Ap = bucket(b.A, 4);
  }

  // --- register rows ------------------------------------------------------
  auto densify = [&](DocState& st, u32 actor, u32 seq, i32* row) {
    std::memset(row, 0, sizeof(i32) * b.Ap);
    for_each_dep(st, actor, seq, [&](u32 a, u32 s) {
      i32 r = (a < b.rank_of.size()) ? b.rank_of[a] : -1;
      if (r >= 0) row[r] = static_cast<i32>(s);
    });
  };

  // clock rows dedup to one table entry per (doc, actor, seq).  In
  // resident mode the table is the POOL's (rows persist across batches,
  // keyed by the doc's stable address; a row for an applied change is
  // immutable); otherwise it is batch-local, as before.
  std::unordered_map<K3, u32, K3Hash> clock_cache;
  // rows below this index were persisted by EARLIER batches; hits on
  // rows this batch itself appended are intra-batch dedup, not resident
  // service, and must not satisfy the perf-smoke resident gate
  const u32 resclk_n0 = b.res_clock ? static_cast<u32>(rc.n_rows()) : 0;
  auto clock_row_of = [&](u32 doc, DocState& st, u32 actor, u32 seq) {
    if (b.res_clock) {
      ResClockKey rk{static_cast<const void*>(&st), actor, seq};
      auto rit = rc.rows.find(rk);
      if (rit != rc.rows.end()) {
        if (rit->second < resclk_n0) ++b.resclk_hits;
        return rit->second;
      }
      u32 idx = static_cast<u32>(rc.tab.size() / rc.Ap);
      rc.tab.resize(rc.tab.size() + rc.Ap);
      densify(st, actor, seq, rc.tab.data() + rc.tab.size() - rc.Ap);
      rc.rows.emplace(rk, idx);
      b.resclk_appended = true;
      return idx;
    }
    K3 ck{doc, actor, seq};
    auto cit = clock_cache.find(ck);
    if (cit != clock_cache.end()) return cit->second;
    u32 idx = static_cast<u32>(b.clock_tab.size() / b.Ap);
    b.clock_tab.resize(b.clock_tab.size() + b.Ap);
    densify(st, actor, seq,
            b.clock_tab.data() + b.clock_tab.size() - b.Ap);
    clock_cache.emplace(ck, idx);
    return idx;
  };

  // Host-full mode: no kernel will run, so the whole register-row /
  // member-window build is dead weight -- registers resolve in-emit
  // via host_resolve_step and list indexes via the in-emit Fenwick.
  // Arena columns below are still built (host_rank's sibling sort
  // consumes them).
  // 1 = the group resolves in emit (trivial-group routing below); empty
  // when the routing is disabled or host-full short-circuits
  std::vector<u8> gid_trivial;

  if (b.host_full) {
    b.T = 0;
    b.Tp = 0;
    b.assign_row_of_op.assign(b.ops.size(), -1);
    goto arena_columns;
  }

  // --- trivial-group routing (ISSUE 6) ------------------------------------
  // A register group whose rows form ONE totally-ordered actor stream
  // (<=1 mirror prior, every batch op from that same actor, no same-
  // change duplicate assign) has no concurrency to resolve: each op
  // simply supersedes its predecessor.  Shipping such groups through
  // the kernel pays padding + pairwise compute for a foregone
  // conclusion -- on the table workload they are ~60% of all register
  // rows.  Route them to the in-emit incremental resolver instead
  // (host_resolve_step, the same reference-semantics code the full host
  // path runs): their rows are never emitted into the batch columns, so
  // the device batch shrinks to the genuinely concurrent groups.
  // assign_row_of_op == TRIVIAL_ROW marks the ops; emit resolves them
  // against the live mirror in op order, byte-identical by construction
  // (host/kernel parity is pinned by the A/B fuzz lanes).  List-element
  // assigns are excluded: dominance timelines read aliveness through
  // their register row (dom_src feeds the DEVICE mirror fill), so they
  // keep kernel rows.  AMTPU_TRIVIAL_HOST=0 disables (latched).
  {
    static const bool trivial_host = []() {
      const char* e = getenv("AMTPU_TRIVIAL_HOST");
      return !e || atoi(e) != 0;
    }();
    if (trivial_host) {
      const u32 NOACT = ~0u;
      gid_trivial.assign(gid_order.size(), 1);
      std::vector<u32> g_actor(gid_order.size(), NOACT);
      std::vector<u32> g_seq(gid_order.size(), 0);
      for (u32 gid = 0; gid < gid_order.size(); ++gid) {
        if (gid_regs[gid] == nullptr) continue;
        auto& recs = *gid_regs[gid];
        if (recs.size() > 1) { gid_trivial[gid] = 0; continue; }
        // a del that covered the sole prior leaves an EMPTY register
        // in the mirror (host_resolve_step drops it; the other mirror
        // readers all guard !empty()): no prior stream to seed
        if (recs.empty()) continue;
        g_actor[gid] = recs[0].actor;
        g_seq[gid] = recs[0].seq;
      }
      for (size_t op_idx = 0; op_idx < b.ops.size(); ++op_idx) {
        auto& f = b.ops[op_idx];
        const OpRec& op = *f.op;
        if (!is_assign(op.action)) continue;
        u32 gid = *doc_gids[f.doc].find(DocState::rkey(op.obj, op.key));
        if (!gid_trivial[gid]) continue;
        if (b.pre_eidx[op_idx] != -2) { gid_trivial[gid] = 0; continue; }
        if (g_actor[gid] == NOACT) {
          g_actor[gid] = op.actor;
          g_seq[gid] = op.seq;
        } else if (op.actor != g_actor[gid] || op.seq == g_seq[gid]) {
          gid_trivial[gid] = 0;   // second stream / same-change dup
        } else {
          g_seq[gid] = op.seq;
        }
      }
    }
  }

  // state rows
  for (u32 gid = 0; gid < gid_order.size(); ++gid) {
    auto [doc, obj, key] = gid_order[gid];
    (void)obj; (void)key;
    DocState& st = *b.bdocs[doc];
    if (gid_regs[gid] == nullptr) continue;
    if (!gid_trivial.empty() && gid_trivial[gid]) {
      b.n_triv_rows += static_cast<i64>(gid_regs[gid]->size());
      continue;
    }
    auto& recs = *gid_regs[gid];
    // REVERSED iteration: the mirror stores winner-first (= newest-first
    // within an actor's ties) and the kernel orders ties by time
    // descending, so the newest mirror entry must carry the LARGEST
    // state time while array order stays time-ascending (the counting-
    // sort contract below).  Survivors are a concurrent antichain, so
    // state times only affect output order, never supersession.
    // (tests/test_tie_order.py pins this.)
    for (size_t j = recs.size(); j-- > 0;) {
      size_t i = recs.size() - 1 - j;  // emission position, time -n..-1
      b.g_col.push_back(static_cast<i32>(gid));
      b.t_col.push_back(static_cast<i32>(i) - static_cast<i32>(recs.size()));
      b.a_col.push_back(b.rank_of[recs[j].actor]);
      b.s_col.push_back(static_cast<i32>(recs[j].seq));
      b.d_col.push_back(0);
      b.clock_idx.push_back(static_cast<i32>(
          clock_row_of(doc, st, recs[j].actor, recs[j].seq)));
      b.state_rec_store.push_back(recs[j]);
      b.src_records.push_back(&b.state_rec_store.back());
    }
  }

  // batch assign rows (time = op index).  Ops of one change share
  // (doc, actor, seq), so the clock row and actor rank resolve once per
  // change, not once per op.
  b.assign_row_of_op.assign(b.ops.size(), -1);
  {
    u32 c_doc = ~0u, c_actor = NONE, c_seq = 0;
    i32 c_crow = -1, c_rank = 0;
    for (size_t op_idx = 0; op_idx < b.ops.size(); ++op_idx) {
      auto& f = b.ops[op_idx];
      const OpRec& op = *f.op;
      if (!is_assign(op.action)) continue;
      DocState& st = *b.bdocs[f.doc];
      if (f.doc != c_doc || op.actor != c_actor || op.seq != c_seq) {
        c_doc = f.doc; c_actor = op.actor; c_seq = op.seq;
        c_crow = -1;   // lazy: resolved when a kernel row needs it
        c_rank = b.rank_of[op.actor];
      }
      u32 gid = *doc_gids[f.doc].find(DocState::rkey(op.obj, op.key));
      if (!gid_trivial.empty() && gid_trivial[gid]) {
        b.assign_row_of_op[op_idx] = Batch::TRIVIAL_ROW;
        ++b.n_triv_rows;
        if (gid_trivial[gid] == 1) {   // count each group once
          gid_trivial[gid] = 2;
          ++b.n_triv_groups;
        }
        continue;
      }
      // densify the change's clock row only when a kernel row consumes
      // it: fully-trivial changes (~60% of table-workload rows) would
      // otherwise append pool-resident rows nothing reads, inflating
      // delta uploads and burning toward AMTPU_RESCLK_MAX_ROWS
      if (c_crow < 0)
        c_crow = static_cast<i32>(clock_row_of(f.doc, st, op.actor, op.seq));
      b.assign_row_of_op[op_idx] = static_cast<i64>(b.g_col.size());
      b.g_col.push_back(static_cast<i32>(gid));
      b.t_col.push_back(static_cast<i32>(op_idx));
      b.a_col.push_back(c_rank);
      b.s_col.push_back(static_cast<i32>(op.seq));
      b.d_col.push_back(op.action == A_DEL ? 1 : 0);
      b.clock_idx.push_back(c_crow);
      b.src_records.push_back(&op);
    }
  }

  b.T = static_cast<i64>(b.g_col.size());
  if (b.T > 0) {
    b.Tp = bucket(b.T);
    b.g_col.resize(b.Tp, -1);
    b.t_col.resize(b.Tp, 0);
    b.a_col.resize(b.Tp, 0);
    b.s_col.resize(b.Tp, 0);
    b.d_col.resize(b.Tp, 0);
    b.clock_idx.resize(b.Tp, 0);
    if (b.res_clock) {
      // pool table: Python reads dims via amtpu_resclk_info and keeps
      // the device copy itself; CTp == 0 marks "no batch-local table"
      b.CT = rc.n_rows();
      b.CTp = 0;
    } else {
      b.CT = static_cast<i64>(b.clock_tab.size() / b.Ap);
      if (b.CT == 0) { b.clock_tab.resize(b.Ap, 0); b.CT = 1; }
      b.CTp = bucket(b.CT, 4);
      b.clock_tab.resize(b.CTp * b.Ap, 0);
    }
    // host sort by (group, time), padding (g=-1) first.  Rows are already
    // emitted in time order within each group (state rows carry negative
    // times and precede batch rows, which are appended in op order), so a
    // stable counting sort on the group key alone yields the full (g, t)
    // order in O(T) -- no comparison sort.
    const i64 n_groups = static_cast<i64>(gid_order.size());
    std::vector<i32> bucket_pos(n_groups + 2, 0);
    for (i64 i = 0; i < b.Tp; ++i) bucket_pos[b.g_col[i] + 2]++;
    i32 max_count = 0;
    for (i64 g = 2; g < n_groups + 2; ++g)
      if (bucket_pos[g] > max_count) max_count = bucket_pos[g];
    b.max_group = max_count;
    for (i64 g = 1; g < n_groups + 2; ++g) bucket_pos[g] += bucket_pos[g - 1];
    b.sort_idx.resize(b.Tp);
    for (i64 i = 0; i < b.Tp; ++i)
      b.sort_idx[bucket_pos[b.g_col[i] + 1]++] = static_cast<i32>(i);

    // Hot keys: when any group holds more rows than the sliding window,
    // the window fills with dead sequential versions and the conservative
    // overflow rule would punt most of the batch off the fast path.
    // Build explicit member windows instead: each row's candidates are
    // the LATEST row per actor stream on its key (only those can survive
    // -- an op with a newer same-actor successor is always superseded).
    // Overflow then means >WINDOW genuinely concurrent streams, or a
    // change assigning one key twice (same actor+seq rows, which the
    // window cannot hold) -- both flagged host_ovf, which the Python
    // driver ESCALATES through wider member-window kernel tiers
    // (ops/registers.escalate_overflow); only groups wider than every
    // tier reach the mid-phase host oracle below.
    const int W = 8;   // ops/registers.WINDOW
    if (max_count > W) {
      b.use_members = true;
      b.mem_idx.assign(b.Tp * W, -1);
      b.host_ovf.assign(b.Tp, 0);
      std::vector<i32> gslot(n_groups, -1);
      std::vector<i32> counts(n_groups, 0);
      for (i64 i = 0; i < b.T; ++i)
        if (b.g_col[i] >= 0) counts[b.g_col[i]]++;
      i64 n_multi = 0;
      for (i64 g = 0; g < n_groups; ++g)
        if (counts[g] >= 2) gslot[g] = static_cast<i32>(n_multi++);
      std::vector<i32> wrow(n_multi * W);
      std::vector<i32> wactor(n_multi * W), wseq(n_multi * W);
      std::vector<u8> wn(n_multi, 0);
      std::vector<u8> govf(n_groups, 0);
      // rows are per-group time-ordered in array order (state rows per
      // gid first with negative times, batch rows in op order)
      for (i64 r = 0; r < b.T; ++r) {
        i32 g = b.g_col[r];
        if (g < 0) continue;
        i32 sl = gslot[g];
        if (sl < 0) continue;            // single-row group: empty window
        i32* rows = &wrow[sl * W];
        i32* acts = &wactor[sl * W];
        i32* seqs = &wseq[sl * W];
        u8 n = wn[sl];
        for (u8 k = 0; k < n; ++k) b.mem_idx[r * W + k] = rows[k];
        i32 a = b.a_col[r], s = b.s_col[r];
        u8 k = 0;
        for (; k < n; ++k)
          if (acts[k] == a) break;
        if (k < n) {
          if (seqs[k] == s) govf[g] = 1;   // same-change dup assign
          else { rows[k] = static_cast<i32>(r); seqs[k] = s; }
        } else if (n < W) {
          rows[n] = static_cast<i32>(r);
          acts[n] = a;
          seqs[n] = s;
          wn[sl] = n + 1;
        } else {
          govf[g] = 1;                     // >W concurrent streams
        }
      }
      for (i64 r = 0; r < b.T; ++r) {
        i32 g = b.g_col[r];
        if (g >= 0 && govf[g]) {
          b.host_ovf[r] = 1;
          b.any_ovf = true;
          ++b.n_pre_ovf;
        }
      }
      // Escalation member layout for the flagged groups: sort_idx is
      // the (group, time) bucket order, so each group is one contiguous
      // run.  Streams here are UNLIMITED width (the base build stops at
      // W) and same-change duplicate assigns accumulate -- exactly the
      // candidate rule the Python ladder's tiers need.
      if (b.any_ovf) {
        b.esc_mem_off.push_back(0);
        std::vector<std::vector<i32>> streams;
        std::vector<i32> s_actor, s_seq;
        for (i64 i = 0; i < b.Tp;) {
          i32 g = b.g_col[b.sort_idx[i]];
          i64 j = i;
          while (j < b.Tp && b.g_col[b.sort_idx[j]] == g) ++j;
          if (g < 0 || !govf[g]) { i = j; continue; }
          i64 start = static_cast<i64>(b.esc_rows.size());
          streams.clear();
          s_actor.clear();
          s_seq.clear();
          i32 width = 0;
          for (i64 p = i; p < j; ++p) {
            i32 r = b.sort_idx[p];
            i32 li = static_cast<i32>(p - i);   // group-LOCAL index
            b.esc_rows.push_back(r);
            i32 cnt = 0;
            for (auto& st : streams) {
              for (i32 c : st) b.esc_mem.push_back(c);
              cnt += static_cast<i32>(st.size());
            }
            b.esc_mem_off.push_back(static_cast<i64>(b.esc_mem.size()));
            if (cnt > width) width = cnt;
            i32 a = b.a_col[r], s = b.s_col[r];
            size_t k = 0;
            for (; k < s_actor.size(); ++k)
              if (s_actor[k] == a) break;
            if (k < s_actor.size()) {
              if (s_seq[k] == s) streams[k].push_back(li);
              else { streams[k].assign(1, li); s_seq[k] = s; }
            } else {
              s_actor.push_back(a);
              s_seq.push_back(s);
              streams.emplace_back(1, li);
            }
          }
          b.esc_group_meta.push_back(start);
          b.esc_group_meta.push_back(j - i);
          b.esc_group_meta.push_back(width);
          i = j;
        }
      }
    }
  } else {
    b.Tp = 0;
  }

  // --- arena columns ------------------------------------------------------
arena_columns:
  for (size_t k = 0; k < b.arena_keys.size(); ++k) {
    u64 akey = b.arena_keys[k];
    Arena& ar = b.bdocs[akey >> 32]->arenas[static_cast<u32>(akey)];
    if (static_cast<i64>(ar.ctr.size()) > b.max_arena_len)
      b.max_arena_len = static_cast<i64>(ar.ctr.size());
    i64 base = static_cast<i64>(b.obj_col.size());
    b.arena_base[akey] = base;
    for (size_t i = 0; i < ar.ctr.size(); ++i) {
      b.obj_col.push_back(static_cast<i32>(k));
      b.par_col.push_back(ar.parent[i] >= 0
                              ? static_cast<i32>(ar.parent[i] + base) : -1);
      b.ctr_col.push_back(ar.ctr[i]);
      b.act_col.push_back(b.rank_of[ar.actor_sid[i]]);
      b.val_col.push_back(1);
    }
  }
  b.L = static_cast<i64>(b.obj_col.size());
  if (b.L > 0) {
    b.Lp = bucket(b.L);
    b.obj_col.resize(b.Lp, 0);
    b.par_col.resize(b.Lp, -1);
    b.ctr_col.resize(b.Lp, 0);
    b.act_col.resize(b.Lp, 0);
    b.val_col.resize(b.Lp, 0);
  } else {
    b.Lp = 0;
  }
}

// Sibling sort: (obj-with-invalid-last, parent, -ctr, -actor).  Arena
// columns were emitted arena-by-arena (obj ascending), so sorting each
// arena's segment independently gives the global order with much
// smaller sorts; padding rows (val=0) sort last by construction.
// Built LAZILY on first amtpu_col_linsort call: the device-resident path
// never reads it (linearize sorts in-graph there), so a resident batch
// skips this O(L log L) host pass entirely.
static void build_lin_sort(Batch& b) {
  if (!b.lin_sort.empty() || b.Lp == 0) return;
  b.lin_sort.resize(b.Lp);
  for (i64 i = 0; i < b.Lp; ++i) b.lin_sort[i] = static_cast<i32>(i);
  auto sib_less = [&](i32 x, i32 y) {
    if (b.par_col[x] != b.par_col[y]) return b.par_col[x] < b.par_col[y];
    if (b.ctr_col[x] != b.ctr_col[y]) return b.ctr_col[x] > b.ctr_col[y];
    return b.act_col[x] > b.act_col[y];
  };
  i64 seg = 0;
  while (seg < b.L) {
    i64 end = seg + 1;
    const i32 o = b.obj_col[seg];
    while (end < b.L && b.obj_col[end] == o) ++end;
    std::sort(b.lin_sort.begin() + seg, b.lin_sort.begin() + end,
              sib_less);
    seg = end;
  }
}

// ---------------------------------------------------------------------------
// phase 2: register outputs in -> dominance blocks out
// ---------------------------------------------------------------------------

static bool rec_concurrent(DocState& st, const OpRec& o1, const OpRec& o2) {
  return clock_get_deps(st, o1.actor, o1.seq, o2.actor) < o2.seq &&
         clock_get_deps(st, o2.actor, o2.seq, o1.actor) < o1.seq;
}

// Built at the end of begin(): per-object dominance timelines and the
// packed kernel layout.  Deltas (od) and rank-derived inputs (er/orank)
// are NOT filled here -- the fused device kernel gathers them on device
// from its own register/linearize outputs via the *_src index maps; the
// host fallback path (amtpu_mid) fills the er/orank/od mirrors instead.
static void dom_layout(Pool& pool, Batch& b) {
  b.eidx_of_op.assign(b.ops.size(), -1);
  if (b.host_full) {
    // in-emit Fenwick replaces the dominance blocks entirely; emit only
    // needs the prepass-resolved element index per op
    for (size_t op_idx = 0; op_idx < b.ops.size(); ++op_idx) {
      if (!is_assign(b.ops[op_idx].op->action)) continue;
      i32 eidx = b.pre_eidx[op_idx];
      if (eidx >= 0) b.eidx_of_op[op_idx] = eidx;
    }
    b.list_index_of_op.assign(b.ops.size(), INT32_MIN);
    b.fused_ok = true;
    b.resident_ok = false;
    return;
  }
  std::vector<u64> obj_order;  // first-seen object order (layout-local)

  for (size_t op_idx = 0; op_idx < b.ops.size(); ++op_idx) {
    i64 row = b.assign_row_of_op[op_idx];
    if (row < 0) continue;
    auto& f = b.ops[op_idx];
    // element index resolved by prepass in application order; -2 = not a
    // list assign, -1 = dropped del on an absent element (set/link on an
    // absent element already errored in prepass)
    i32 eidx = b.pre_eidx[op_idx];
    if (eidx < 0) continue;
    const OpRec& op = *f.op;
    u64 ak = (static_cast<u64>(f.doc) << 32) | op.obj;
    b.eidx_of_op[op_idx] = eidx;
    auto oit2 = b.obj_ops.find(ak);
    if (oit2 == b.obj_ops.end()) {
      obj_order.push_back(ak);
      oit2 = b.obj_ops.emplace(ak, std::vector<DomEntry>{}).first;
    }
    oit2->second.push_back({static_cast<i64>(op_idx), row, eidx});
  }

  // one block per (Lp, Tp) size class
  const i64 K = 64;
  std::map<std::pair<i64, i64>, std::vector<u64>> classes;
  for (u64 ak : obj_order) {
    auto& entries = b.obj_ops[ak];
    if (entries.empty()) continue;
    Arena& ar = b.bdocs[ak >> 32]->arenas[static_cast<u32>(ak)];
    i64 n_elems = static_cast<i64>(ar.ctr.size());
    i64 Lp = bucket(std::max<i64>(n_elems, 1));
    i64 Tp = bucket(static_cast<i64>(entries.size()), K);
    classes[{Lp, Tp}].push_back(ak);
  }

  // resident precheck (full decision finalized below): a single big
  // single-object arena lets the device-resident driver derive v0 and
  // er_src from resident columns
  static const i64 resident_min_pre = []() {
    const char* e = getenv("AMTPU_RESIDENT_MIN");
    return e ? atoll(e) : DEF_RESIDENT_MIN;
  }();
  static const bool resident_enabled_pre = []() {
    const char* e = getenv("AMTPU_RESIDENT");
    return !e || atoi(e) != 0;     // default ON
  }();
  bool resident_candidate =
      resident_enabled_pre && classes.size() == 1 &&
      classes.begin()->second.size() == 1 && b.arena_keys.size() == 1 &&
      classes.begin()->first.first >= resident_min_pre && !b.use_members;

  for (auto& [key, aks] : classes) {
    auto [Lp, Tp] = key;
    // bucket the object-axis width too: every dim of the kernel shape
    // keys the jit compile cache, and arena counts vary batch to batch
    // (padding rows are zero-filled and inert)
    i64 W = bucket(static_cast<i64>(aks.size()), 1);
    DomBlock blk;
    blk.W = W; blk.Lp = Lp; blk.Tp = Tp;
    // v0/er_src are NOT filled here: every consumer goes through the
    // lazily-filling accessors (ensure_dom_fills), so a resident batch
    // never pays the O(arena) pass and non-resident paths fill once on
    // first read
    blk.oe.assign(W * Tp, -1);
    blk.orank_src.assign(W * Tp, -1);
    blk.dom_src.assign(W * Tp, -1);
    blk.ov.assign(W * Tp, 0);
    for (i64 o = 0; o < static_cast<i64>(aks.size()); ++o) {
      u64 ak = aks[o];
      i64 base = b.arena_base[ak];
      auto& entries = b.obj_ops[ak];
      for (size_t t = 0; t < entries.size(); ++t) {
        blk.oe[o * Tp + t] = entries[t].eidx;
        blk.orank_src[o * Tp + t] = static_cast<i32>(base + entries[t].eidx);
        blk.dom_src[o * Tp + t] = static_cast<i32>(entries[t].reg_row);
        blk.ov[o * Tp + t] = 1;
      }
      blk.akeys.push_back(ak);
    }
    blk.indexes.assign(W * Tp, 0);
    b.dom_blocks.push_back(std::move(blk));
  }

  // fused eligibility: at most one size class whose [W, Lp, chunk] mask
  // intermediate and [W, Tp] op arrays stay within device memory budget,
  // and T small enough for the packed-transfer winner field
  if (b.dom_blocks.empty()) {
    b.fused_ok = true;
  } else if (b.dom_blocks.size() == 1) {
    DomBlock& d = b.dom_blocks[0];
    b.fused_ok = d.W * d.Lp * K * 4 <= (2LL << 30) &&
                 d.W * d.Tp * 4 <= (1LL << 29);
  } else {
    b.fused_ok = false;
  }
  if (b.Tp >= (1 << 24)) b.fused_ok = false;
  if (b.any_ovf) b.fused_ok = false;

  // Device-resident eligibility (SURVEY hard part 5): a single big list
  // arena can keep its columns resident on device between batches; the
  // Python driver then uploads only per-batch deltas.  Conditions: one
  // block, one object, the batch arena IS that object's arena, big
  // enough to be worth it, and no member-window register mode.  The
  // v0/er_src fills were skipped above under the same precheck; any
  // path that still reads them (overflow fallback, non-fused) refills
  // lazily via ensure_dom_fills.
  b.resident_ok = resident_candidate && b.fused_ok;
}

// Lazy refill of the O(arena) dominance-layout arrays for paths that
// need them after a resident-mode skip (overflow fallback, non-fused).
static void ensure_dom_fills(Batch& b, size_t blk_idx) {
  DomBlock& blk = b.dom_blocks[blk_idx];
  if (!blk.v0.empty()) return;
  blk.v0.assign(blk.W * blk.Lp, 0.0f);
  blk.er_src.assign(blk.W * blk.Lp, -1);
  for (i64 o = 0; o < static_cast<i64>(blk.akeys.size()); ++o) {
    u64 ak = blk.akeys[o];
    i64 base = b.arena_base[ak];
    Arena& ar = b.bdocs[ak >> 32]->arenas[static_cast<u32>(ak)];
    for (size_t i = 0; i < ar.ctr.size(); ++i) {
      blk.v0[o * blk.Lp + i] = ar.visible[i] ? 1.0f : 0.0f;
      blk.er_src[o * blk.Lp + i] = static_cast<i32>(base + i);
    }
  }
}

// Shared begin pipeline.  Every error any phase can raise fires before the
// batch handle is returned, and the journal rolls the pool back to its
// pre-call state on ANY throw -- a failed apply leaves every doc exactly
// as it was (the reference backend is immutable and discards failed
// state).  After begin succeeds, no later phase (mid/emit) throws for
// well-formed pools.
static void begin_phases(Pool& pool, Batch& b,
                         std::vector<std::vector<ChangeRec>>& incoming,
                         BeginJournal& j) {
  double t1 = mono_now();
  ++pool.epoch;
  for (u32 d = 0; d < b.bdocs.size(); ++d)
    if (!b.bdocs[d]->queue.empty())
      j.queues.emplace_back(d, b.bdocs[d]->queue);
  schedule(pool, b, incoming);
  try {
    validate_duplicates(pool, b);
    update_states(pool, b, j);
    prepass(pool, b, j);
    double t2 = mono_now();
    b.tr_schedule = t2 - t1;
    encode(pool, b);
    double t3 = mono_now();
    b.tr_encode = t3 - t2;
    dom_layout(pool, b);
    b.tr_domlay = mono_now() - t3;
  } catch (...) {
    j.rollback(b);
    throw;
  }
}

// overflow fallback: re-resolve whole groups with oracle semantics.
// Flags live in k_overflow (assigned by amtpu_mid, or the RESIDUAL
// member-overflow vector of amtpu_mid_packed -- empty when the caller
// had no overflow at all).
static void oracle_replay(Pool& pool, Batch& b) {
  if (b.T > 0 && !b.k_overflow.empty()) {
    std::unordered_map<K3, char, K3Hash> overflowed;
    bool any = false;
    for (size_t op_idx = 0; op_idx < b.ops.size(); ++op_idx) {
      i64 row = b.assign_row_of_op[op_idx];
      if (row >= 0 && b.k_overflow[row]) {
        auto& f = b.ops[op_idx];
        overflowed[K3{f.doc, f.op->obj, f.op->key}] = 1;
        any = true;
      }
    }
    if (any) {
      std::unordered_map<K3, Register, K3Hash> scratch;
      for (size_t op_idx = 0; op_idx < b.ops.size(); ++op_idx) {
        auto& f = b.ops[op_idx];
        const OpRec& op = *f.op;
        if (!is_assign(op.action)) continue;
        K3 gk{f.doc, op.obj, op.key};
        if (!overflowed.count(gk)) continue;
        DocState& st = *b.bdocs[f.doc];
        auto sit = scratch.find(gk);
        if (sit == scratch.end()) {
          Register init;
          const Register* rit =
              st.registers.find(DocState::rkey(op.obj, op.key));
          if (rit) init = *rit;
          sit = scratch.emplace(gk, std::move(init)).first;
        }
        // oracle rule: keep concurrent priors, append op unless del,
        // sort by actor string descending
        Register remaining;
        // newest-first tie rule -- see backend/op_set.py apply_assign
        if (op.action != A_DEL) remaining.push_back(op);
        for (auto& o : sit->second)
          if (rec_concurrent(st, o, op)) remaining.push_back(o);
        std::stable_sort(remaining.begin(), remaining.end(),
                         [&](const OpRec& x, const OpRec& y) {
                           return pool.intern.str(x.actor) >
                                  pool.intern.str(y.actor);
                         });
        sit->second = remaining;
        b.host_registers[static_cast<i64>(op_idx)] = remaining;
      }
    }
  }
}

static void mid_phase(Pool& pool, Batch& b) {
  oracle_replay(pool, b);

  // fill the fallback-path mirrors (er/orank from the fetched rank, od
  // from running host visibility); timelines/layout were built at begin.
  // Host-dominance callers declared themselves via amtpu_mid's host_dom
  // flag: the mirrors only feed the device fallback kernel, which never
  // runs there.
  if (b.host_dom) {
    b.result.clear();
    return;
  }
  std::unordered_map<u64, char> vis_now;  // (arena base + eidx) -> bool
  for (auto& blk : b.dom_blocks) {
    blk.er.assign(blk.W * blk.Lp, -1);
    blk.orank.assign(blk.W * blk.Tp, -1);
    blk.od.assign(blk.W * blk.Tp, 0);
    for (size_t o = 0; o < blk.akeys.size(); ++o) {
      u64 ak = blk.akeys[o];
      i64 base = b.arena_base[ak];
      Arena& ar = b.bdocs[ak >> 32]->arenas[static_cast<u32>(ak)];
      for (size_t i = 0; i < ar.ctr.size(); ++i)
        blk.er[o * blk.Lp + i] = b.rank[base + i];
      auto& entries = b.obj_ops[ak];
      for (size_t t = 0; t < entries.size(); ++t) {
        const DomEntry& e = entries[t];
        bool alive_now;
        auto hit = b.host_registers.find(e.op_idx);
        if (hit != b.host_registers.end()) alive_now = !hit->second.empty();
        else if (b.packed_mode)
          alive_now = ((b.k_packed[e.reg_row] >> 24) & 0x3f) > 0;
        else alive_now = b.k_alive[e.reg_row] > 0;
        u64 vk = static_cast<u64>(base + e.eidx);
        bool before;
        auto vit = vis_now.find(vk);
        if (vit != vis_now.end()) before = vit->second;
        else before = ar.visible[e.eidx] != 0;
        vis_now[vk] = alive_now ? 1 : 0;
        blk.orank[o * blk.Tp + t] = b.rank[base + e.eidx];
        blk.od[o * blk.Tp + t] = static_cast<i32>(alive_now) -
                                 static_cast<i32>(before);
      }
    }
  }
  b.result.clear();
}

// ---------------------------------------------------------------------------
// host dominance: exact per-op list indexes without the device kernel.
//
// The fused device formulation computes index(op t on element e) =
// #{e': obj(e')==obj(e), rank(e')<rank(e), visible just before t} as
// [L]x[L,K] mask products -- MXU-shaped work that is the right design on
// an accelerator but O(T*L) scalar work on the CPU backend, where it
// dominates single-big-doc latency (config 1: ~85% of wall).  This host
// twin computes the same indexes in O((L+T) log L): RGA ranks from a
// pre-order walk of the sibling-sorted tree (the same total order the
// pointer-doubling `linearize` kernel produces,
// automerge_tpu/ops/list_rank.py:42), then a Fenwick-tree sweep over the
// timeline with visibility deltas from the resolved registers.
// Dispatched per-platform by the Python driver (AMTPU_HOST_DOM, default:
// on for the CPU backend only); parity is pinned by the differential
// suites run both ways (tests/test_native.py).
// ---------------------------------------------------------------------------

// Per-object RGA pre-order rank of every arena row, derived host-side
// from lin_sort: within an arena segment the rows are sorted by
// (parent, -ctr, -actor), so each parent's children are contiguous in
// sibling order and one explicit-stack DFS yields the pre-order.
static void host_rank(Batch& b, std::vector<i32>& rank) {
  build_lin_sort(b);
  rank.assign(static_cast<size_t>(b.L), -1);
  if (b.L == 0) return;
  // children ranges, indexed by global parent row (-1 handled per segment)
  std::vector<i32> child_start(static_cast<size_t>(b.L), -1);
  std::vector<i32> child_cnt(static_cast<size_t>(b.L), 0);
  i64 seg = 0;
  std::vector<i32> stack;
  while (seg < b.L) {
    i64 end = seg + 1;
    const i32 o = b.obj_col[seg];
    while (end < b.L && b.obj_col[end] == o) ++end;
    i64 head_start = -1, head_cnt = 0;
    for (i64 p = seg; p < end; ++p) {
      i32 par = b.par_col[b.lin_sort[p]];
      if (par < 0) {
        if (head_start < 0) head_start = p;
        ++head_cnt;
      } else {
        if (child_start[par] < 0) child_start[par] = static_cast<i32>(p);
        ++child_cnt[par];
      }
    }
    stack.clear();
    for (i64 c = head_cnt - 1; c >= 0; --c)
      stack.push_back(b.lin_sort[head_start + c]);
    i32 r = 0;
    while (!stack.empty()) {
      i32 node = stack.back();
      stack.pop_back();
      rank[node] = r++;
      i32 cs = child_start[node], cn = child_cnt[node];
      for (i32 c = cn - 1; c >= 0; --c)
        stack.push_back(b.lin_sort[cs + c]);
    }
    seg = end;
  }
}

static void host_dominance(Batch& b) {
  if (b.dom_blocks.empty()) return;
  std::vector<i32> rank;
  host_rank(b, rank);
  Fenwick fen;
  std::vector<u8> vis;
  for (auto& blk : b.dom_blocks) {
    for (size_t o = 0; o < blk.akeys.size(); ++o) {
      u64 ak = blk.akeys[o];
      i64 base = b.arena_base[ak];
      Arena& ar = b.bdocs[ak >> 32]->arenas[static_cast<u32>(ak)];
      size_t n = ar.ctr.size();
      fen.reset(n);
      vis.assign(n, 0);
      for (size_t i = 0; i < n; ++i) {
        if (ar.visible[i]) {
          vis[i] = 1;
          fen.add(rank[base + i], 1);
        }
      }
      auto& entries = b.obj_ops[ak];
      for (size_t t = 0; t < entries.size(); ++t) {
        const DomEntry& e = entries[t];
        bool alive_now;
        auto hit = b.host_registers.find(e.op_idx);
        if (hit != b.host_registers.end()) {
          alive_now = !hit->second.empty();
        } else if (b.packed_mode) {
          alive_now = ((b.k_packed[e.reg_row] >> 24) & 0x3f) > 0;
        } else {
          alive_now = b.k_alive[e.reg_row] > 0;
        }
        i32 r = rank[base + e.eidx];
        blk.indexes[o * blk.Tp + t] = fen.prefix(r);
        i32 before = vis[e.eidx];
        i32 delta = static_cast<i32>(alive_now) - before;
        if (delta) {
          fen.add(r, delta);
          vis[e.eidx] = alive_now ? 1 : 0;
        }
      }
    }
  }
}

// In-emit incremental register resolution (host_reg_mode): st.registers
// holds the running survivor set for each key -- actor descending, ties
// newest-first, maintained by update_register_mirror right after each
// emitted op -- so one O(w) merge applies the next op with oracle
// semantics (op_set.js:202-220) and NO sort: priors are already ordered
// and the new op slots in front of its own actor's run.  This replaces
// both the device register kernel and the mid-phase scratch oracle for
// batches where most groups are wider than the member window (the
// kernel's output would be discarded for every overflowed row anyway).
// returns the prior mirror register (or nullptr) so the caller can pass
// it straight to update_register_mirror -- one FlatMap probe per op,
// not two
static Register* host_resolve_step(Pool& pool, Batch& b, u32 doc,
                                   DocState& st, const OpRec& op,
                                   Register& reg) {
  reg.clear();
  Register* rit = st.registers.find(DocState::rkey(op.obj, op.key));
  const bool add = op.action != A_DEL;
  bool placed = false;
  if (rit && !rit->empty()) {
    // Dense clock projection, refilled once per (doc, actor, seq)
    // change.  A register prior can never know the op being applied
    // (causal admission would have required the op first; dedup forbids
    // re-application), so rec_concurrent's two O(A) clock scans per
    // prior collapse to ONE dense lookup: concurrent(o, op) <=>
    // clock_op[o.actor] < o.seq.  On 64-writer registers this is the
    // difference between O(w*A) and O(w) per op.
    if (doc != b.dense_doc || op.actor != b.dense_actor ||
        op.seq != b.dense_seqno) {
      if (b.dense_stamp.size() < pool.intern.size()) {
        b.dense_stamp.resize(pool.intern.size(), 0);
        b.dense_seq.resize(pool.intern.size(), 0);
      }
      ++b.dense_epoch;
      for_each_dep(st, op.actor, op.seq, [&](u32 a, u32 s) {
        b.dense_stamp[a] = b.dense_epoch;
        b.dense_seq[a] = s;
      });
      b.dense_doc = doc;
      b.dense_actor = op.actor;
      b.dense_seqno = op.seq;
    }
    // actor order by rank (string-lex-preserving; encode marked every
    // register actor, so rank_of covers all priors)
    const i32 orank = b.rank_of[op.actor];
    for (const OpRec& o : *rit) {
      if (add && !placed && b.rank_of[o.actor] <= orank) {
        reg.push_back(op);   // newest-first among same-actor ties
        placed = true;
      }
      u32 cov = (b.dense_stamp[o.actor] == b.dense_epoch)
                    ? b.dense_seq[o.actor] : 0;
      if (cov < o.seq) reg.push_back(o);   // concurrent -> survives
    }
  }
  if (add && !placed) reg.push_back(op);
  return rit;
}

// ---------------------------------------------------------------------------
// phase 3: emission
// ---------------------------------------------------------------------------

static void collect_indexes(Batch& b) {
  // map per-block kernel outputs back to op ids
  if (b.dom_blocks.empty()) return;
  b.list_index_of_op.assign(b.ops.size(), INT32_MIN);
  for (auto& blk : b.dom_blocks) {
    for (size_t o = 0; o < blk.akeys.size(); ++o) {
      u64 ak = blk.akeys[o];
      auto& entries = b.obj_ops[ak];
      for (size_t t = 0; t < entries.size(); ++t)
        b.list_index_of_op[entries[t].op_idx] =
            blk.indexes[o * blk.Tp + t];
    }
  }
}

static void register_from_kernel(Batch& b, i64 row, Register& reg) {
  reg.clear();
  if (b.packed_mode) {
    const i32 packed = b.k_packed[row];
    const i32 w = packed & 0xffffff;
    if (w != 0xffffff) reg.push_back(*b.src_records[w]);
    if (((packed >> 24) & 0x3f) > 1) {
      auto* conf = b.sparse_conflicts.find(static_cast<u64>(row));
      if (conf) {
        const i32* vals = b.sparse_vals.data() + conf->first;
        for (i32 c = 0; c < conf->second; ++c)
          if (vals[c] >= 0) reg.push_back(*b.src_records[vals[c]]);
      }
    }
    return;
  }
  i32 w = b.k_winner[row];
  if (w >= 0) reg.push_back(*b.src_records[w]);
  for (int c = 0; c < b.window; ++c) {
    i32 s = b.k_conflicts[row * b.window + c];
    if (s >= 0) reg.push_back(*b.src_records[s]);
  }
}

// Stores `new_register` as the live mirror for (op.obj, op.key) and
// maintains link inbound refs.  STEALS new_register's buffer (swap/move
// -- the caller's vector afterwards holds the old mirror's storage, to
// be clear()ed and recycled); returns the stored register, which emit
// reads instead of its own copy.  On 64-wide catch-up registers this
// removes a ~3.6 KB memcpy per op.
static const Register* update_register_mirror(
    Pool& pool, DocState& st, const OpRec& op, Register& new_register,
    ObjMeta* obj_meta, bool is_list, bool prior_known = false,
    Register* known_prior = nullptr) {
  u64 rk = DocState::rkey(op.obj, op.key);
  Register* rit = prior_known ? known_prior : st.registers.find(rk);
  if (rit) {
    // drop inbound refs of links no longer in the register
    for (auto& o : *rit) {
      if (o.action != A_LINK) continue;
      bool still = false;
      for (auto& n : new_register)
        if (n.actor == o.actor && n.seq == o.seq &&
            n.value_rid == o.value_rid) { still = true; break; }
      if (still) continue;
      if (o.value_sid == NONE) continue;
      ObjMeta* tit = st.objects.find(o.value_sid);
      if (!tit) continue;
      auto& inbound = tit->inbound;
      for (size_t i = 0; i < inbound.size(); ++i) {
        if (inbound[i].actor == o.actor && inbound[i].seq == o.seq &&
            inbound[i].key == o.key && inbound[i].obj == o.obj) {
          inbound.erase(inbound.begin() + i);
          // paths read only inbound[0] (get_path), so cached renderings
          // go stale ONLY when position 0 changes
          if (i == 0) st.path_epoch++;
          --i;
        }
      }
    }
  }
  if (op.action == A_LINK && op.value_sid != NONE) {
    ObjMeta* tit = st.objects.find(op.value_sid);
    if (tit) {
      InboundRef ref{op.obj, op.key, op.actor, op.value_sid, op.seq};
      bool present = false;
      for (auto& r : tit->inbound)
        if (r == ref) { present = true; break; }
      if (!present) {
        // no epoch bump: a push onto a NON-empty inbound never changes
        // inbound[0]; a 0->1 push only un-nulls paths through a
        // previously-unreachable object, and render_path never caches
        // unreachable results -- so no cached rendering can go stale
        tit->inbound.push_back(ref);
      }
    }
  }
  if (!rit) {
    // key_order drives map/table materialization only; list elements
    // materialize via visible_order, so skip the per-elemId bookkeeping
    if (!is_list && obj_meta) obj_meta->key_order.push_back(op.key);
    Register* stored = st.registers.insert(rk).first;
    *stored = std::move(new_register);
    return stored;
  }
  std::swap(*rit, new_register);
  return rit;
}

// path from root to object: list of either string keys or list indexes.
// Returns false if the object is unreachable (emit 'path: null').
struct PathElem { bool is_index; i32 index; u32 key; };

static bool get_path(Pool& pool, DocState& st, u32 object_id,
                     std::vector<PathElem>& out) {
  out.clear();
  while (object_id != pool.root_sid) {
    ObjMeta* mit = st.objects.find(object_id);
    if (mit == nullptr || mit->inbound.empty()) return false;
    const InboundRef& ref = mit->inbound[0];
    object_id = ref.obj;
    ObjMeta* pit = st.objects.find(object_id);
    u8 ptype = pit ? pit->type : T_MAP;
    if (is_list_type(ptype)) {
      auto ait = st.arenas.find(object_id);
      if (ait == st.arenas.end()) return false;
      Arena& ar = ait->second;
      const std::string& kstr = pool.intern.str(ref.key);
      u32 ea; i64 ec;
      if (!parse_elem_id(kstr, pool.intern, &ea, &ec)) return false;
      const i32* eit = ar.index_of.find(Arena::ekey(ea, ec));
      if (!eit) return false;
      i32 eidx = *eit;
      i32 pos = -1;
      for (size_t i = 0; i < ar.visible_order.size(); ++i)
        if (ar.visible_order[i] == eidx) { pos = static_cast<i32>(i); break; }
      if (pos < 0) return false;
      out.insert(out.begin(), PathElem{true, pos, 0});
    } else {
      out.insert(out.begin(), PathElem{false, 0, ref.key});
    }
  }
  return true;
}

static void write_path(Writer& w, Pool& pool, bool ok,
                       const std::vector<PathElem>& path) {
  if (!ok) { w.nil(); return; }
  w.array(path.size());
  for (auto& p : path) {
    if (p.is_index) w.integer(p.index);
    else w.str(pool.intern.str(p.key));
  }
}

// Precomputed msgpack fixstr literals for the constant patch vocabulary:
// one memcpy instead of strlen + header branch per emission.  fixstr
// header is 0xa0 | len (all of these are < 32 bytes).
#define MP_LIT(name, text) \
  static const std::string name = std::string(1, char(0xa0 | (sizeof(text) - 1))) + text
MP_LIT(L_ACTION, "action");
MP_LIT(L_TYPE, "type");
MP_LIT(L_OBJ, "obj");
MP_LIT(L_KEY, "key");
MP_LIT(L_PATH, "path");
MP_LIT(L_INDEX, "index");
MP_LIT(L_ELEMID, "elemId");
MP_LIT(L_VALUE, "value");
MP_LIT(L_LINK, "link");
MP_LIT(L_DATATYPE, "datatype");
MP_LIT(L_CONFLICTS, "conflicts");
MP_LIT(L_ACTOR, "actor");
MP_LIT(L_SET, "set");
MP_LIT(L_REMOVE, "remove");
MP_LIT(L_INSERT, "insert");
MP_LIT(L_CREATE, "create");
MP_LIT(L_CLOCK, "clock");
MP_LIT(L_DEPS, "deps");
MP_LIT(L_CANUNDO, "canUndo");
MP_LIT(L_CANREDO, "canRedo");
MP_LIT(L_DIFFS, "diffs");
MP_LIT(L_SEQ, "seq");
#undef MP_LIT
static const std::string L_TYPES[4] = {
    std::string("\xa3") + "map", std::string("\xa4") + "list",
    std::string("\xa4") + "text", std::string("\xa5") + "table"};

static void write_conflicts(Writer& w, Pool& pool, const Register& reg) {
  w.array(reg.size() - 1);
  for (size_t i = 1; i < reg.size(); ++i) {
    const OpRec& o = reg[i];
    size_t n = 2 + (o.action == A_LINK ? 1 : 0);
    w.map(n);
    w.raw(L_ACTOR); w.str(pool.intern.str(o.actor));
    w.raw(L_VALUE);
    if (o.value_rid != NONE) w.raw(val_bytes(pool, o)); else w.nil();
    if (o.action == A_LINK) { w.raw(L_LINK); w.boolean(true); }
  }
}

// emits one map/table diff; mirrors engine._emit_map_diff
// Stack-resident diff assembler: one bounds check up front, raw pointer
// bumps for every field, ONE append into the per-doc Writer at the end.
// The generic Writer pays a capacity check + memmove call per raw();
// a diff is ~12 such calls of 3-10 bytes each, so the per-call overhead
// dominates actual byte movement on the emit hot loop.
struct DiffBuf {
  static constexpr size_t CAP = 4096;
  // Red zone: the entry checks bound every variable-size component, so
  // the only overflow risk is the hand-computed fixed-overhead constant
  // being a few bytes short.  Writes land in tmp[CAP + RED) long before
  // commit()'s assert can fire, so the slack keeps a constant-sized
  // mistake INSIDE the buffer until the assert reports it.
  static constexpr size_t RED = 512;
  u8 tmp[CAP + RED];
  u8* p = tmp;
  size_t used() const { return static_cast<size_t>(p - tmp); }
  inline void lit(const std::string& s) {  // preencoded literal
    std::memcpy(p, s.data(), s.size());
    p += s.size();
  }
  inline void bytes(const void* d, size_t n) {
    std::memcpy(p, d, n);
    p += n;
  }
  inline void map_hdr(size_t n) { *p++ = static_cast<u8>(0x80 | n); }
  inline void str(const std::string& s) {
    // fast-path short strings (fixstr); longer keys take 3-byte headers
    size_t n = s.size();
    if (n <= 31) {
      *p++ = static_cast<u8>(0xa0 | n);
    } else if (n <= 0xff) {
      *p++ = 0xd9; *p++ = static_cast<u8>(n);
    } else {
      *p++ = 0xda; *p++ = static_cast<u8>(n >> 8);
      *p++ = static_cast<u8>(n & 0xff);
    }
    std::memcpy(p, s.data(), n);
    p += n;
  }
  inline void integer(i64 v) {
    if (v >= 0 && v <= 0x7f) { *p++ = static_cast<u8>(v); return; }
    if (v >= 0 && v <= 0xffff) {
      if (v <= 0xff) { *p++ = 0xcc; *p++ = static_cast<u8>(v); return; }
      *p++ = 0xcd; *p++ = static_cast<u8>(v >> 8);
      *p++ = static_cast<u8>(v & 0xff);
      return;
    }
    Writer t;  // rare: huge indexes
    t.integer(v);
    bytes(t.buf.data(), t.buf.size());
  }
  inline void nil() { *p++ = 0xc0; }
  inline void boolean(bool v) { *p++ = v ? 0xc3 : 0xc2; }
  inline void array_hdr(size_t n) { *p++ = static_cast<u8>(0x90 | n); }
  // Every fast-path emit must land through here: the entry checks are
  // hand-computed headroom constants, so a future added diff field can
  // silently exceed them -- this assert (live in production; no NDEBUG)
  // plus the RED slack above turns that into a loud failure while the
  // overshoot is still inside the buffer.
  inline void commit(Writer& w) {
    assert(used() <= CAP);
    w.raw(tmp, used());
  }
};

// worst-case byte size of the conflicts array for a register, so the
// stack fast path can take conflict-carrying diffs too (hot-key map
// workloads put a conflict set on most diffs); window <= 8 keeps the
// entry count within a fixarray
static size_t conflicts_bound(Pool& pool, const Register& reg) {
  size_t n = 4;
  for (size_t i = 1; i < reg.size(); ++i) {
    const OpRec& o = reg[i];
    n += 24 + pool.intern.str(o.actor).size() +
         (o.value_rid != NONE ? pool.vals.str(o.value_rid).size() : 1);
  }
  return n;
}

static void write_conflicts_fast(DiffBuf& d, Pool& pool,
                                 const Register& reg) {
  d.array_hdr(reg.size() - 1);
  for (size_t i = 1; i < reg.size(); ++i) {
    const OpRec& o = reg[i];
    bool link = o.action == A_LINK;
    d.map_hdr(link ? 3 : 2);
    d.lit(L_ACTOR); d.str(pool.intern.str(o.actor));
    d.lit(L_VALUE);
    if (o.value_rid != NONE) {
      const std::string& vb = pool.vals.str(o.value_rid);
      d.bytes(vb.data(), vb.size());
    } else {
      d.nil();
    }
    if (link) { d.lit(L_LINK); d.boolean(true); }
  }
}

static void emit_map_diff(Writer& w, Pool& pool, DocState& st,
                          const OpRec& op, const Register& reg, u8 obj_type,
                          const std::vector<u8>& path_bytes,
                          const std::string& obj_bytes) {
  const std::string& type_ =
      (op.obj == pool.root_sid) ? L_TYPES[T_MAP] : L_TYPES[obj_type];
  const std::string& kstr = pool.intern.str(op.key);
  if (reg.empty()) {
    if (128 + obj_bytes.size() + kstr.size() + path_bytes.size() <=
        DiffBuf::CAP) {
      DiffBuf d;
      d.map_hdr(5);
      d.lit(L_ACTION); d.lit(L_REMOVE);
      d.lit(L_TYPE); d.lit(type_);
      d.lit(L_OBJ); d.lit(obj_bytes);
      d.lit(L_KEY); d.str(kstr);
      d.lit(L_PATH); d.bytes(path_bytes.data(), path_bytes.size());
      d.commit(w);
      return;
    }
    w.map(5);
    w.raw(L_ACTION); w.raw(L_REMOVE);
    w.raw(L_TYPE); w.raw(type_);
    w.raw(L_OBJ); w.raw(obj_bytes);
    w.raw(L_KEY); w.str(kstr);
    w.raw(L_PATH); w.raw(path_bytes);
    return;
  }
  const OpRec& first = reg[0];
  size_t n = 6 + (first.action == A_LINK ? 1 : 0) +
             (first.datatype != NONE ? 1 : 0) + (reg.size() > 1 ? 1 : 0);
  const std::string* vb =
      first.value_rid != NONE ? &val_bytes(pool, first) : nullptr;
  const std::string* dt =
      first.datatype != NONE ? &pool.intern.str(first.datatype) : nullptr;
  // reg.size() <= 16: conflicts emit as a 1-byte fixarray header (<= 15
  // entries); overflow-oracle registers are unbounded and must take the
  // generic Writer path, whose array() encodes any count
  if (reg.size() <= 16 &&
      160 + obj_bytes.size() + kstr.size() + path_bytes.size() +
              (vb ? vb->size() : 1) + (dt ? dt->size() : 0) +
              (reg.size() > 1 ? conflicts_bound(pool, reg) : 0) <=
          DiffBuf::CAP) {
    DiffBuf d;
    d.map_hdr(n);
    d.lit(L_ACTION); d.lit(L_SET);
    d.lit(L_TYPE); d.lit(type_);
    d.lit(L_OBJ); d.lit(obj_bytes);
    d.lit(L_KEY); d.str(kstr);
    d.lit(L_PATH); d.bytes(path_bytes.data(), path_bytes.size());
    d.lit(L_VALUE);
    if (vb) d.bytes(vb->data(), vb->size());
    else d.nil();
    if (first.action == A_LINK) { d.lit(L_LINK); d.boolean(true); }
    if (dt) { d.lit(L_DATATYPE); d.str(*dt); }
    if (reg.size() > 1) {
      d.lit(L_CONFLICTS);
      write_conflicts_fast(d, pool, reg);
    }
    d.commit(w);
    return;
  }
  w.map(n);
  w.raw(L_ACTION); w.raw(L_SET);
  w.raw(L_TYPE); w.raw(type_);
  w.raw(L_OBJ); w.raw(obj_bytes);
  w.raw(L_KEY); w.str(kstr);
  w.raw(L_PATH); w.raw(path_bytes);
  w.raw(L_VALUE);
  if (vb) w.raw(*vb);
  else w.nil();
  if (first.action == A_LINK) { w.raw(L_LINK); w.boolean(true); }
  if (dt) { w.raw(L_DATATYPE); w.str(*dt); }
  if (reg.size() > 1) { w.raw(L_CONFLICTS); write_conflicts(w, pool, reg); }
}

// emits one list/text diff and maintains visibility mirrors;
// returns false when no diff is produced
// State-only twin of emit_list_diff's visibility transition: the
// mutation a list assign applies to the arena, without any patch
// bytes.  The no-patch load path (Batch::no_patch) runs this so a
// restored doc's visibility state is byte-identical to the patched
// path's -- the decode-parity lanes pin it.
static void apply_list_visibility(Arena& ar, const Register& reg,
                                  i64 op_idx, Batch& b) {
  i32 eidx = b.eidx_of_op[op_idx];
  if (eidx < 0 || op_idx >= static_cast<i64>(b.list_index_of_op.size()))
    return;
  i32 index = b.list_index_of_op[op_idx];
  if (index == INT32_MIN) return;
  bool visible_before = ar.visible[eidx] != 0;
  bool alive = !reg.empty();
  if (visible_before && !alive) {
    ar.visible_order.erase(ar.visible_order.begin() + index);
    ar.visible[eidx] = 0;
  } else if (!visible_before && alive) {
    ar.visible_order.insert(ar.visible_order.begin() + index, eidx);
    ar.visible[eidx] = 1;
  }
}

static bool emit_list_diff(Writer& w, Pool& pool, Arena& ar,
                           const OpRec& op, const Register& reg, i64 op_idx,
                           Batch& b, u8 obj_type,
                           const std::vector<u8>& path_bytes,
                           const std::string& obj_bytes) {
  i32 eidx = b.eidx_of_op[op_idx];  // cached by dom_layout at begin
  if (eidx < 0 || op_idx >= static_cast<i64>(b.list_index_of_op.size()))
    return false;
  i32 index = b.list_index_of_op[op_idx];
  if (index == INT32_MIN) return false;
  const std::string& kstr = pool.intern.str(op.key);
  bool visible_before = ar.visible[eidx] != 0;
  bool alive = !reg.empty();

  const char* action;
  if (visible_before && alive) {
    action = "set";
  } else if (visible_before && !alive) {
    action = "remove";
    ar.visible_order.erase(ar.visible_order.begin() + index);
    ar.visible[eidx] = 0;
  } else if (!visible_before && alive) {
    action = "insert";
    ar.visible_order.insert(ar.visible_order.begin() + index, eidx);
    ar.visible[eidx] = 1;
  } else {
    return false;
  }
  bool ins = action[0] == 'i';
  bool setlike = alive;
  const OpRec* first = alive ? &reg[0] : nullptr;
  size_t n = 5 + (ins ? 1 : 0);
  if (setlike) {
    n += 1 + (first->action == A_LINK ? 1 : 0) +
         (first->datatype != NONE ? 1 : 0) + (reg.size() > 1 ? 1 : 0);
  }
  const std::string* vb = (setlike && first->value_rid != NONE)
                              ? &val_bytes(pool, *first) : nullptr;
  const std::string* dt = (setlike && first->datatype != NONE)
                              ? &pool.intern.str(first->datatype) : nullptr;
  if (reg.size() <= 16 &&   // fixarray conflicts bound; see emit_map_diff
      160 + obj_bytes.size() + kstr.size() + path_bytes.size() +
              (vb ? vb->size() : 1) + (dt ? dt->size() : 0) +
              (reg.size() > 1 ? conflicts_bound(pool, reg) : 0) <=
          DiffBuf::CAP) {
    DiffBuf d;
    d.map_hdr(n);
    d.lit(L_ACTION);
    d.lit(action[0] == 's' ? L_SET : ins ? L_INSERT : L_REMOVE);
    d.lit(L_TYPE); d.lit(L_TYPES[obj_type]);
    d.lit(L_OBJ); d.lit(obj_bytes);
    d.lit(L_INDEX); d.integer(index);
    d.lit(L_PATH); d.bytes(path_bytes.data(), path_bytes.size());
    if (ins) { d.lit(L_ELEMID); d.str(kstr); }
    if (setlike) {
      d.lit(L_VALUE);
      if (vb) d.bytes(vb->data(), vb->size());
      else d.nil();
      if (first->action == A_LINK) { d.lit(L_LINK); d.boolean(true); }
      if (dt) { d.lit(L_DATATYPE); d.str(*dt); }
      if (reg.size() > 1) {
        d.lit(L_CONFLICTS);
        write_conflicts_fast(d, pool, reg);
      }
    }
    d.commit(w);
    return true;
  }
  w.map(n);
  w.raw(L_ACTION);
  w.raw(action[0] == 's' ? L_SET : ins ? L_INSERT : L_REMOVE);
  w.raw(L_TYPE); w.raw(L_TYPES[obj_type]);
  w.raw(L_OBJ); w.raw(obj_bytes);
  w.raw(L_INDEX); w.integer(index);
  w.raw(L_PATH); w.raw(path_bytes);
  if (ins) { w.raw(L_ELEMID); w.str(kstr); }
  if (setlike) {
    w.raw(L_VALUE);
    if (vb) w.raw(*vb);
    else w.nil();
    if (first->action == A_LINK) { w.raw(L_LINK); w.boolean(true); }
    if (dt) { w.raw(L_DATATYPE); w.str(*dt); }
    if (reg.size() > 1) { w.raw(L_CONFLICTS); write_conflicts(w, pool, reg); }
  }
  return true;
}

static void write_clock(Writer& w, Pool& pool, const Clock& c) {
  w.map(c.size());
  for (auto& [a, s] : c) {
    w.str(pool.intern.str(a));
    w.integer(s);
  }
}

static void emit(Pool& pool, Batch& b) {
  // diffs per doc, in op order
  std::vector<Writer> diff_bufs(b.bdoc_ids.size());
  std::vector<size_t> diff_counts(b.bdoc_ids.size(), 0);
  Register reg;  // reused across ops (capacity persists)

  // Direct emission: when every doc's ops form ONE contiguous run (the
  // universal catch-up shape -- payloads arrive {doc: [changes...]} and
  // the in-order fast path admits doc by doc), diffs stream straight
  // into the final result buffer: envelope at run start, diff count
  // backpatched into a fixed-width array32 header at run end.  The
  // buffered path pays the whole patch twice in memcpy (per-doc buffer
  // growth + assembly splice) -- ~90 MB/batch on table workloads.
  // Local changes stay buffered: their envelope reads undo/redo state
  // committed AFTER the op loop.
  std::vector<u8> doc_seen(b.bdoc_ids.size(), 0);
  bool direct = !b.local_kind && !b.no_patch;
  {
    u32 prev = ~0u;
    for (auto& f : b.ops) {
      if (f.doc == prev) continue;
      if (doc_seen[f.doc]) { direct = false; break; }
      doc_seen[f.doc] = 1;
      prev = f.doc;
    }
  }
  Writer out;
  u32 cur_doc = ~0u;
  size_t cnt_off = 0;
  if (direct) {
    out.buf.reserve(b.ops.size() * 64 + b.bdoc_ids.size() * 96);
    out.map(b.bdoc_ids.size());
  }
  // the ONE patch-envelope writer (both emission modes and the zero-op
  // loop use it): clock/deps/canUndo/canRedo then the 'diffs' label
  auto write_envelope = [&](Writer& w_, u32 d) {
    DocState& st = *b.bdocs[d];
    w_.str(b.bdoc_ids[d]);
    w_.map(b.local_kind ? 7 : 5);
    w_.raw(L_CLOCK); write_clock(w_, pool, st.clock);
    w_.raw(L_DEPS); write_clock(w_, pool, st.deps);
    w_.raw(L_CANUNDO); w_.boolean(st.undo_pos > 0);
    w_.raw(L_CANREDO); w_.boolean(!st.redo_stack.empty());
    w_.raw(L_DIFFS);
  };
  auto open_run = [&](u32 d) {
    write_envelope(out, d);
    cnt_off = out.buf.size();
    out.buf.push_back(0xdd);            // array32, count patched at close
    out.buf.insert(out.buf.end(), 4, 0);
  };
  auto close_run = [&](u32 d) {
    u32 c = static_cast<u32>(diff_counts[d]);
    u8* q = out.buf.data() + cnt_off;
    q[1] = c >> 24; q[2] = (c >> 16) & 0xff;
    q[3] = (c >> 8) & 0xff; q[4] = c & 0xff;
  };

  // pre-size the hot hash maps / buffers: most assign ops open a fresh
  // register (every Text elemId is its own), and rehash storms during
  // the emit loop dominate otherwise
  {
    std::vector<size_t> assigns(b.bdoc_ids.size(), 0), per(b.bdoc_ids.size(), 0);
    for (auto& f : b.ops) {
      per[f.doc]++;
      if (is_assign(f.op->action)) assigns[f.doc]++;
    }
    for (size_t d = 0; d < b.bdoc_ids.size(); ++d) {
      if (assigns[d])
        b.bdocs[d]->registers.reserve(b.bdocs[d]->registers.n + assigns[d]);
      if (!direct && !b.no_patch) diff_bufs[d].buf.reserve(per[d] * 48);
    }
  }

  // inline path cache: consecutive ops overwhelmingly target the same
  // object, and pure-map paths (no list indexes) are stable while the
  // doc's inbound-link index (path_epoch) holds still; list-index paths
  // shift with visibility mutations and are never cached.  TWO entries
  // (current + previous, promote-on-hit): table workloads alternate
  // row-object ops with links into the table, which thrashes a
  // single-entry cache every row
  struct PathEntry {
    u32 doc = ~0u, obj = NONE;
    u64 epoch = 0;
    std::vector<u8> bytes;
  };
  PathEntry pc, pc2;
  // encoded-object-id cache (same two-way scheme)
  struct ObjEntry {
    u32 obj = NONE;
    std::string bytes;
  };
  ObjEntry oc, oc2;
  struct TypeEntry {
    u32 doc = ~0u, obj = NONE;
    u8 type = 0;
    Arena* arena = nullptr;
    ObjMeta* meta = nullptr;
  };
  TypeEntry tc, tc2;
  auto render_obj = [&](u32 obj) -> const std::string& {
    if (oc.obj == obj) return oc.bytes;
    std::swap(oc, oc2);
    if (oc.obj == obj) return oc.bytes;
    const std::string& s = pool.intern.str(obj);
    oc.bytes.clear();
    if (s.size() < 32) {
      oc.bytes.push_back(static_cast<char>(0xa0 | s.size()));
      oc.bytes.append(s);
    } else {
      // rare long ids take the generic writer (str8/16/32 headers)
      Writer tmp;
      tmp.str(s);
      oc.bytes.assign(tmp.buf.begin(), tmp.buf.end());
    }
    oc.obj = obj;
    return oc.bytes;
  };

  // host-full Fenwick run cache (batch-lifetime: see use below)
  u64 last_hak = ~0ull;
  Batch::HostFen* last_hf = nullptr;

  std::vector<PathElem> path_scratch;
  auto render_path = [&](u32 doc, DocState& st,
                         u32 obj) -> const std::vector<u8>& {
    if (pc.doc == doc && pc.obj == obj && pc.epoch == st.path_epoch)
      return pc.bytes;
    std::swap(pc, pc2);
    if (pc.doc == doc && pc.obj == obj && pc.epoch == st.path_epoch)
      return pc.bytes;
    bool ok = get_path(pool, st, obj, path_scratch);
    Writer pw;
    write_path(pw, pool, ok, path_scratch);
    // cacheable = reachable pure-map paths only.  Unreachable (null)
    // renderings must NOT cache: a later link can un-null them without
    // any epoch bump (see update_register_mirror) -- and they cost two
    // lookups to recompute anyway.  List-index paths shift with
    // visibility mutations and are never cached either.
    bool cacheable = ok;
    if (ok)
      for (auto& p : path_scratch)
        if (p.is_index) { cacheable = false; break; }
    pc.bytes = std::move(pw.buf);
    if (cacheable) {
      pc.doc = doc; pc.obj = obj; pc.epoch = st.path_epoch;
    } else {
      pc.doc = ~0u; pc.obj = NONE;
    }
    return pc.bytes;
  };

  for (size_t op_idx = 0; op_idx < b.ops.size(); ++op_idx) {
    auto& f = b.ops[op_idx];
    const OpRec& op = *f.op;
    DocState& st = *b.bdocs[f.doc];
    if (direct && f.doc != cur_doc) {
      if (cur_doc != ~0u) close_run(cur_doc);
      open_run(f.doc);
      cur_doc = f.doc;
    }
    Writer& w = direct ? out : diff_bufs[f.doc];

    if (op.action >= A_MAKE_MAP) {
      if (b.no_patch) continue;   // creation happened in prepass
      const std::string& ob = render_obj(op.obj);
      const std::string& ty = L_TYPES[make_type(op.action)];
      if (64 + ob.size() + ty.size() <= DiffBuf::CAP) {
        DiffBuf d;
        d.map_hdr(3);
        d.lit(L_ACTION); d.lit(L_CREATE);
        d.lit(L_OBJ); d.lit(ob);
        d.lit(L_TYPE); d.lit(ty);
        d.commit(w);
      } else {
        w.map(3);
        w.raw(L_ACTION); w.raw(L_CREATE);
        w.raw(L_OBJ); w.raw(ob);
        w.raw(L_TYPE); w.raw(ty);
      }
      diff_counts[f.doc]++;
      continue;
    }
    if (op.action == A_INS) continue;

    i64 row = b.assign_row_of_op[op_idx];
    Register* prior = nullptr;
    bool prior_known = false;
    if (b.host_reg_mode || row == Batch::TRIVIAL_ROW) {
      // trivial-group routing: the group's whole stream resolves here,
      // incrementally against the live mirror (reference semantics)
      prior = host_resolve_step(pool, b, f.doc, st, op, reg);
      prior_known = true;
    } else {
      bool from_host = false;
      if (!b.host_registers.empty()) {
        auto hit = b.host_registers.find(static_cast<i64>(op_idx));
        if (hit != b.host_registers.end()) {
          reg = hit->second;
          from_host = true;
        }
      }
      if (!from_host) register_from_kernel(b, row, reg);
    }

    // undo capture reads the register BEFORE this op's mirror update --
    // the same interleaved order as the reference (op_set.js:193-200);
    // projection keeps only action/obj/key/value
    if (b.local_kind == 1 && b.capture[op_idx]) {
      const Register* rit = st.registers.find(DocState::rkey(op.obj, op.key));
      if (rit && !rit->empty()) {
        for (const OpRec& rec : *rit) {
          OpRec p = rec;
          p.actor = NONE; p.seq = 0; p.datatype = NONE; p.elem = -1;
          b.undo_local.push_back(p);
        }
      } else {
        OpRec d{};
        d.action = A_DEL; d.obj = op.obj; d.key = op.key;
        d.elem = -1; d.actor = NONE; d.seq = 0; d.datatype = NONE;
        d.value_rid = NONE; d.value_sid = NONE;
        b.undo_local.push_back(d);
      }
    }

    // object-type run cache: consecutive ops overwhelmingly target the
    // same object, and an object's type never changes once created.
    // Resolved BEFORE the mirror update so the mirror reuses the cached
    // ObjMeta instead of re-probing st.objects per op.  (ObjMeta
    // pointers are stable: st.objects stores values in a deque
    // (FlatMapStable) and emit never erases -- an erase would silently
    // reset the slot in place, so keep it that way.)
    u8 obj_type;
    Arena* arp = nullptr;
    ObjMeta* om = nullptr;
    if (f.doc != tc.doc || op.obj != tc.obj) std::swap(tc, tc2);
    if (f.doc == tc.doc && op.obj == tc.obj) {
      obj_type = tc.type;
      arp = tc.arena;
      om = tc.meta;
    } else {
      om = &st.objects[op.obj];
      obj_type = om->type;
      if (is_list_type(obj_type)) arp = &st.arenas[op.obj];
      tc.doc = f.doc; tc.obj = op.obj; tc.type = obj_type; tc.arena = arp;
      tc.meta = om;
    }
    // INVARIANT: ereg aliases a FlatMap slot in st.registers, whose
    // slots MOVE on rehash -- nothing between here and the emit_*_diff
    // reads below may insert into st.registers
    const Register& ereg = *update_register_mirror(
        pool, st, op, reg, om, is_list_type(obj_type), prior_known,
        prior);
    // path rendered AFTER the mirror update (the reference computes it
    // inside updateMapKey/updateListElement, post inbound maintenance)
    // but BEFORE this op's visibility mutation.  The no-patch load
    // path renders nothing -- the bytes are never read.
    static const std::vector<u8> kNoPath;
    static const std::string kNoObj;
    const std::vector<u8>& path_bytes =
        b.no_patch ? kNoPath : render_path(f.doc, st, op.obj);
    const std::string& obj_bytes =
        b.no_patch ? kNoObj : render_obj(op.obj);
    if (is_list_type(obj_type)) {
      // host-full: the list index is the in-emit Fenwick prefix count
      // (same contract as the dominance kernels: visible lower-ranked
      // elements just before this op), computed against host RGA ranks
      // and a per-arena running visibility count
      i32 heidx = b.host_full ? b.eidx_of_op[op_idx] : -1;
      Batch::HostFen* hf = nullptr;
      u8 vis_pre = 0;
      if (heidx >= 0) {
        u64 hak = (static_cast<u64>(f.doc) << 32) | op.obj;
        // run cache, same rationale as tc above: consecutive list ops
        // overwhelmingly hit the same arena.  (unordered_map guarantees
        // element-pointer stability across rehash, so growth on another
        // arena's first touch cannot dangle this.)
        if (last_hak == hak) {
          hf = last_hf;
        } else {
          hf = &b.host_fens[hak];
          last_hak = hak; last_hf = hf;
        }
        if (hf->fen.t.empty()) {
          if (b.rank_host.empty() && b.L > 0) host_rank(b, b.rank_host);
          hf->base = b.arena_base[hak];
          hf->fen.reset(arp->ctr.size());
          for (size_t i = 0; i < arp->ctr.size(); ++i)
            if (arp->visible[i])
              hf->fen.add(b.rank_host[hf->base + i], 1);
        }
        b.list_index_of_op[op_idx] =
            hf->fen.prefix(b.rank_host[hf->base + heidx]);
        vis_pre = arp->visible[heidx];
      }
      if (b.no_patch) {
        apply_list_visibility(*arp, ereg, static_cast<i64>(op_idx), b);
      } else if (emit_list_diff(w, pool, *arp, op, ereg,
                                static_cast<i64>(op_idx), b,
                                obj_type, path_bytes, obj_bytes)) {
        diff_counts[f.doc]++;
      }
      if (hf != nullptr) {
        u8 vis_post = arp->visible[heidx];
        if (vis_post != vis_pre)
          hf->fen.add(b.rank_host[hf->base + heidx],
                      static_cast<i32>(vis_post) -
                          static_cast<i32>(vis_pre));
      }
    } else if (!b.no_patch) {
      emit_map_diff(w, pool, st, op, ereg, obj_type, path_bytes,
                    obj_bytes);
      diff_counts[f.doc]++;
    }
  }

  // local-change stack commits BEFORE patch assembly, so canUndo/canRedo
  // report the post-change state (reference: pushUndoHistory before
  // makePatch, op_set.js:296-308; undo/redo stack updates before
  // addChange, backend/index.js:275-308)
  if (b.local_kind == 1) {
    DocState& st = *b.bdocs[0];
    st.undo_stack.resize(st.undo_pos);
    st.undo_stack.push_back(std::move(b.undo_local));
    st.undo_pos++;
    st.redo_stack.clear();
  } else if (b.local_kind == 2) {
    DocState& st = *b.bdocs[0];
    st.undo_pos--;
    st.redo_stack.push_back(std::move(b.pending_redo));
  } else if (b.local_kind == 3) {
    DocState& st = *b.bdocs[0];
    st.undo_pos++;
    st.redo_stack.pop_back();
  }

  // assemble {doc_id: patch}
  if (b.no_patch) {
    b.result.clear();
    return;
  }
  if (direct) {
    if (cur_doc != ~0u) close_run(cur_doc);
    // zero-op docs (duplicate-only deliveries, queued-only changes)
    // still get their envelope
    for (size_t d = 0; d < b.bdoc_ids.size(); ++d) {
      if (doc_seen[d]) continue;
      write_envelope(out, static_cast<u32>(d));
      out.array(0);
    }
    b.result = std::move(out.buf);
    return;
  }
  out.map(b.bdoc_ids.size());
  for (size_t d = 0; d < b.bdoc_ids.size(); ++d) {
    write_envelope(out, static_cast<u32>(d));
    out.array(diff_counts[d]);
    out.raw(diff_bufs[d].buf);
    if (b.local_kind) {
      out.raw(L_ACTOR); out.str(pool.intern.str(b.local_actor));
      out.raw(L_SEQ); out.integer(b.local_seq);
    }
  }
  b.result = std::move(out.buf);
}

// ---------------------------------------------------------------------------
// whole-doc materialization (getPatch parity)
// ---------------------------------------------------------------------------

// Two-phase materialization, mirroring the reference exactly
// (backend/index.js:5-119): instantiation is MEMOIZED per object (each
// object's own diff block builds once), but splicing recurses per link
// OCCURRENCE -- an object referenced by both a winner and a conflict
// (or by two fields) has its block spliced once per reference, exactly
// like makePatch's children recursion.  The scalar oracle reproduces
// this; a seen-set dedup at the splice level diverged from both.
struct MatBlock {
  Writer own;
  size_t count = 0;
  std::vector<u32> children;   // link occurrences, reference push order
};
struct MatCtx {
  // node-based map: MatBlock references stay valid across inserts
  std::unordered_map<u32, MatBlock> blocks;
};

static void mat_instantiate(Pool& pool, DocState& st, u32 object_id,
                            MatCtx& ctx);

// writes "value": ... (+ optional link/datatype) into `own`; link
// targets are recorded as child occurrences and instantiated (memoized)
static void mat_value(Pool& pool, DocState& st, const OpRec& rec,
                      MatCtx& ctx, MatBlock& blk, Writer& own,
                      size_t& extra_keys) {
  if (rec.action == A_LINK && rec.value_sid != NONE) {
    blk.children.push_back(rec.value_sid);
    mat_instantiate(pool, st, rec.value_sid, ctx);
    own.str("value");
    own.raw(val_bytes(pool, rec));
    own.str("link"); own.boolean(true);
    extra_keys = 1;
  } else {
    own.str("value");
    if (rec.value_rid != NONE) own.raw(val_bytes(pool, rec));
    else own.nil();
    if (rec.datatype != NONE) {
      own.str("datatype"); own.str(pool.intern.str(rec.datatype));
      extra_keys = 1;
    } else {
      extra_keys = 0;
    }
  }
}

static void mat_conflicts(Pool& pool, DocState& st, const Register& reg,
                          MatCtx& ctx, MatBlock& blk, Writer& out) {
  out.array(reg.size() - 1);
  for (size_t i = 1; i < reg.size(); ++i) {
    const OpRec& rec = reg[i];
    Writer val;
    size_t extra = 0;
    mat_value(pool, st, rec, ctx, blk, val, extra);
    out.map(1 + 1 + extra);
    out.str("actor"); out.str(pool.intern.str(rec.actor));
    out.raw(val.buf);
  }
}

static void mat_instantiate(Pool& pool, DocState& st, u32 object_id,
                            MatCtx& ctx) {
  if (ctx.blocks.count(object_id)) return;
  // insert BEFORE filling: a cyclic link encountered mid-fill
  // memo-returns, same as the reference setting this.diffs[objectId]
  // first (backend/index.js:92)
  MatBlock& blk = ctx.blocks[object_id];
  Writer& own = blk.own;
  const ObjMeta* mit = st.objects.find(object_id);
  u8 type_ = mit ? mit->type : T_MAP;

  if (is_list_type(type_)) {
    own.map(3);
    own.str("obj"); own.str(pool.intern.str(object_id));
    own.str("type"); own.str(type_name(type_));
    own.str("action"); own.str("create");
    blk.count++;
    auto ait = st.arenas.find(object_id);
    if (ait != st.arenas.end()) {
      Arena& ar = ait->second;
      // elemId strings per arena index
      for (size_t index = 0; index < ar.visible_order.size(); ++index) {
        i32 eidx = ar.visible_order[index];
        std::string elem_id = pool.intern.str(ar.actor_sid[eidx]) + ":" +
                              std::to_string(ar.ctr[eidx]);
        u32 key_sid = pool.intern.id_of(elem_id);
        const Register* rit =
            st.registers.find(DocState::rkey(object_id, key_sid));
        if (!rit || rit->empty()) continue;
        const Register& reg = *rit;
        Writer val;
        size_t extra = 0;
        mat_value(pool, st, reg[0], ctx, blk, val, extra);
        Writer conf;
        size_t nconf = 0;
        if (reg.size() > 1) {
          mat_conflicts(pool, st, reg, ctx, blk, conf);
          nconf = 1;
        }
        own.map(5 + 1 + extra + nconf);
        own.str("obj"); own.str(pool.intern.str(object_id));
        own.str("type"); own.str(type_name(type_));
        own.str("action"); own.str("insert");
        own.str("index"); own.integer(static_cast<i64>(index));
        own.str("elemId"); own.str(elem_id);
        own.raw(val.buf);
        if (nconf) { own.str("conflicts"); own.raw(conf.buf); }
        blk.count++;
      }
    }
  } else {
    if (object_id != pool.root_sid) {
      own.map(3);
      own.str("obj"); own.str(pool.intern.str(object_id));
      own.str("type"); own.str(type_name(type_));
      own.str("action"); own.str("create");
      blk.count++;
    }
    if (mit) {
      for (u32 key : mit->key_order) {
        const Register* rit =
            st.registers.find(DocState::rkey(object_id, key));
        if (!rit || rit->empty()) continue;
        const Register& reg = *rit;
        Writer val;
        size_t extra = 0;
        mat_value(pool, st, reg[0], ctx, blk, val, extra);
        Writer conf;
        size_t nconf = 0;
        if (reg.size() > 1) {
          mat_conflicts(pool, st, reg, ctx, blk, conf);
          nconf = 1;
        }
        own.map(4 + 1 + extra + nconf);
        own.str("obj"); own.str(pool.intern.str(object_id));
        own.str("type"); own.str(type_name(type_));
        own.str("action"); own.str("set");
        own.str("key"); own.str(pool.intern.str(key));
        own.raw(val.buf);
        if (nconf) { own.str("conflicts"); own.raw(conf.buf); }
        blk.count++;
      }
    }
  }
}

// the reference's makePatch recursion (backend/index.js:113-118) has no
// cycle guard -- a link cycle makes it recurse forever, so any
// terminating behavior here diverges only on inputs the reference
// cannot process at all; re-entrant occurrences are skipped
static void mat_splice(u32 object_id, MatCtx& ctx, Writer& w,
                       size_t& count, std::vector<u32>& on_stack) {
  for (u32 a : on_stack)
    if (a == object_id) return;
  MatBlock& blk = ctx.blocks[object_id];
  on_stack.push_back(object_id);
  for (u32 child : blk.children)
    mat_splice(child, ctx, w, count, on_stack);
  on_stack.pop_back();
  w.raw(blk.own.buf);
  count += blk.count;
}

static void materialize(Pool& pool, DocState& st, u32 object_id, Writer& w,
                        size_t& count, std::vector<u8>& seen) {
  (void)seen;
  MatCtx ctx;
  mat_instantiate(pool, st, object_id, ctx);
  std::vector<u32> stack;
  mat_splice(object_id, ctx, w, count, stack);
}

// ---------------------------------------------------------------------------
// local changes (applyLocalChange / undo / redo)
// ---------------------------------------------------------------------------

// Encodes an undo/redo-built change as msgpack with the oracle's key order:
// actor, seq, deps, ops[, message] (backend/__init__.py::_undo/_redo change
// construction; byte parity of shipped local changes matters for
// get_missing_changes).
static std::vector<u8> encode_change_raw(Pool& pool, const ChangeRec& ch,
                                         bool include_message) {
  Writer w;
  w.map(4 + (include_message ? 1 : 0));
  w.str("actor"); w.str(pool.intern.str(ch.actor));
  w.str("seq"); w.integer(ch.seq);
  w.str("deps"); write_clock(w, pool, ch.deps);
  w.str("ops"); w.array(ch.ops.size());
  for (const OpRec& op : ch.ops) {
    size_t k = 3 + (op.value_rid != NONE ? 1 : 0) +
               (op.datatype != NONE ? 1 : 0);
    w.map(k);
    w.str("action"); w.str(action_name(op.action));
    w.str("obj"); w.str(pool.intern.str(op.obj));
    w.str("key"); w.str(pool.intern.str(op.key));
    if (op.value_rid != NONE) { w.str("value"); w.raw(val_bytes(pool, op)); }
    if (op.datatype != NONE) {
      w.str("datatype"); w.str(pool.intern.str(op.datatype));
    }
  }
  if (include_message) { w.str("message"); w.raw(ch.message); }
  return w.buf;
}

static bool message_is_nil(const ChangeRec& ch) {
  return !ch.has_message ||
         (ch.message.size() == 1 && ch.message[0] == 0xc0);
}

// ===========================================================================
// Native columnar change codec (ISSUE 14 tentpole; docs/STORAGE.md).
//
// A C++ mirror of automerge_tpu/storage/columnar.py: the SAME wire
// format (AMTC v1 -- string table, interned change/op shapes, RLE'd
// shape columns, delta columns, typed value columns, residual column,
// whole-body zlib), with the byte-round-trip guarantee enforced the
// same way -- a change is only columnarized when this file's own
// canonical msgpack writer reproduces its exact input bytes; anything
// else rides the residual column verbatim.  The canonicality test here
// is deliberately CONSERVATIVE relative to the Python encoder (ext
// types, non-string map keys, very deep nesting all go residual):
// residual never breaks parity, it only costs compression, and every
// blob either codec writes decodes byte-identically on both sides.
//
// Decode is ARENA-DIRECT: amtpu_begin_columnar materializes the
// columns straight into ChangeRec state (canonical raw bytes rebuilt
// into one slab per blob, then the standard decode_change/begin_phases
// pipeline) without any Python change dicts -- the 1M-doc cold-start
// fast path.  AMTPU_STORAGE_NATIVE=0 keeps the Python codec as the
// parity oracle.
// ===========================================================================

namespace colnr {

using u128 = unsigned __int128;
using i128 = __int128;

static const int COL_VERSION = 1;
static const u8 COL_FLAG_ZLIB = 1;
// change-shape id 0 is reserved for residual (verbatim) changes
enum {
  V_INT = 0, V_STR = 1, V_TRUE = 2, V_FALSE = 3, V_NULL = 4,
  V_FLOAT = 5, V_MSGPACK = 6, V_BIN = 7
};
enum { K_STR = 0, K_ELEM = 1 };

static Error corrupt(const std::string& what) {
  // RangeError kind: the Python wrapper maps it to decode_columnar's
  // ValueError contract
  return Error(1, "corrupt columnar blob: " + what);
}

static void put_uvarint(std::vector<u8>& out, u128 n) {
  while (true) {
    u8 b = static_cast<u8>(n & 0x7f);
    n >>= 7;
    if (n) {
      out.push_back(b | 0x80);
    } else {
      out.push_back(b);
      return;
    }
  }
}

// sign-fold zigzag over (neg, mag): mirrors columnar.py's _zz_fold on
// unbounded ints -- wire msgpack bounds mag at 2^64, so u128 holds the
// folded value exactly
static u128 zz_fold(bool neg, u64 mag) {
  return neg ? (static_cast<u128>(mag) << 1) - 1
             : static_cast<u128>(mag) << 1;
}
static void put_zigzag(std::vector<u8>& out, i128 v) {
  u128 z = v < 0 ? ((static_cast<u128>(-(v + 1)) + 1) << 1) - 1
                 : static_cast<u128>(v) << 1;
  put_uvarint(out, z);
}

struct ColReader {
  const u8* p;
  const u8* end;
  ColReader(const u8* d, size_t n) : p(d), end(d + n) {}
  bool ok() const { return p != nullptr; }
  u128 uvarint() {
    u128 n = 0;
    int shift = 0;
    while (true) {
      if (p >= end) throw corrupt("truncated varint");
      u8 b = *p++;
      if (shift >= 121) throw corrupt("varint overflow");
      n |= static_cast<u128>(b & 0x7f) << shift;
      if (!(b & 0x80)) return n;
      shift += 7;
    }
  }
  u64 uvarint64() {
    u128 n = uvarint();
    if (n >> 64) throw corrupt("varint out of range");
    return static_cast<u64>(n);
  }
  i128 zigzag() {
    u128 n = uvarint();
    return (n & 1) ? -static_cast<i128>(n >> 1) - 1
                   : static_cast<i128>(n >> 1);
  }
  const u8* take(size_t n) {
    if (static_cast<size_t>(end - p) < n)
      throw corrupt("truncated section");
    const u8* out = p;
    p += n;
    return out;
  }
  u8 byte() {
    if (p >= end) throw corrupt("truncated section");
    return *p++;
  }
};

static bool utf8_valid(const u8* s, size_t n) {
  size_t i = 0;
  while (i < n) {
    u8 c = s[i];
    if (c < 0x80) { ++i; continue; }
    int len;
    u32 cp, min;
    if ((c & 0xe0) == 0xc0) { len = 2; cp = c & 0x1f; min = 0x80; }
    else if ((c & 0xf0) == 0xe0) { len = 3; cp = c & 0x0f; min = 0x800; }
    else if ((c & 0xf8) == 0xf0) { len = 4; cp = c & 0x07; min = 0x10000; }
    else return false;
    if (i + len > n) return false;
    for (int j = 1; j < len; ++j) {
      if ((s[i + j] & 0xc0) != 0x80) return false;
      cp = (cp << 6) | (s[i + j] & 0x3f);
    }
    if (cp < min || cp > 0x10ffff) return false;
    if (cp >= 0xd800 && cp <= 0xdfff) return false;  // surrogates
    i += len;
  }
  return true;
}

// ---------------------------------------------------------------------------
// canonical form: the bytes msgpack-python's packb(unpackb(raw)) gives.
// A value is checked in place; anything outside the conservative
// canonical subset (ext types, non-string or duplicate map keys, invalid
// utf-8, float32, a header longer than its length or value needs,
// nesting past the depth cap) is not canonical.
// ---------------------------------------------------------------------------

static const int CANON_MAX_DEPTH = 192;

// parsed int as (neg, mag): mag is |v| for neg, v for non-neg
struct IntVal { bool neg; u64 mag; };

static bool canon_read_uint(const u8*& p, const u8* end, size_t width,
                            u64* out) {
  if (static_cast<size_t>(end - p) < width) return false;
  u64 v = 0;
  for (size_t i = 0; i < width; ++i) v = (v << 8) | *p++;
  *out = v;
  return true;
}

// reads one int value (any wire width) as (neg, mag); false = not an
// int tag / truncated
static bool canon_read_int(const u8*& p, const u8* end, IntVal* out) {
  if (p >= end) return false;
  u8 b = *p++;
  u64 v;
  if (b <= 0x7f) { *out = {false, b}; return true; }
  if (b >= 0xe0) {
    *out = {true, static_cast<u64>(-static_cast<i64>(static_cast<int8_t>(b)))};
    return true;
  }
  switch (b) {
    case 0xcc: if (!canon_read_uint(p, end, 1, &v)) return false;
               *out = {false, v}; return true;
    case 0xcd: if (!canon_read_uint(p, end, 2, &v)) return false;
               *out = {false, v}; return true;
    case 0xce: if (!canon_read_uint(p, end, 4, &v)) return false;
               *out = {false, v}; return true;
    case 0xcf: if (!canon_read_uint(p, end, 8, &v)) return false;
               *out = {false, v}; return true;
    case 0xd0: case 0xd1: case 0xd2: case 0xd3: {
      size_t width = size_t(1) << (b - 0xd0);
      if (!canon_read_uint(p, end, width, &v)) return false;
      i64 sv;
      if (b == 0xd0) sv = static_cast<int8_t>(v);
      else if (b == 0xd1) sv = static_cast<int16_t>(v);
      else if (b == 0xd2) sv = static_cast<int32_t>(v);
      else sv = static_cast<i64>(v);
      if (sv >= 0) *out = {false, static_cast<u64>(sv)};
      else *out = {true, static_cast<u64>(-(sv + 1)) + 1};
      return true;
    }
    default: --p; return false;
  }
}

static inline bool is_array_tag(u8 b) {
  return (b & 0xf0) == 0x90 || b == 0xdc || b == 0xdd;
}
static inline bool is_map_tag(u8 b) {
  return (b & 0xf0) == 0x80 || b == 0xde || b == 0xdf;
}
// a str's bytes, valid utf-8; the ascii run is checked inline
static inline bool canon_str_body(const u8*& p, const u8* end, size_t n) {
  if (static_cast<size_t>(end - p) < n) return false;
  for (size_t i = 0; i < n; ++i)
    if (p[i] & 0x80) {
      if (!utf8_valid(p + i, n - i)) return false;
      break;
    }
  p += n;
  return true;
}

// true iff the value at p already is its canonical form: every header
// the shortest for its length or value, no float32 (packb(unpackb())
// widens it to float64) or ext, valid utf-8, unique string map keys,
// nesting within the depth cap.  Advances p past the value.  The
// one-byte tags come first: they are nearly every value of a change.
// With `ops`, the value must also be an array of maps (one doc's
// changes), and *ops gains the length of each map's 'ops' array, which
// must be an array where present.
static bool canon_asis(const u8*& p, const u8* end, int depth,
                       int64_t* ops = nullptr) {
  if (depth > CANON_MAX_DEPTH || p >= end) return false;
  u8 b = *p++;
  if (ops && !(depth == 0 ? is_array_tag(b) : is_map_tag(b))) return false;
  if (b <= 0x7f || b >= 0xe0) return true;
  if (b >= 0xa0 && b <= 0xbf) return canon_str_body(p, end, b & 0x1f);
  u64 v;
  bool is_map = false;
  if (b <= 0x9f) {                       // fixmap / fixarray
    is_map = b <= 0x8f;
    v = b & 0x0f;
  } else if (b == 0xdc || b == 0xdd || b == 0xde || b == 0xdf) {
    is_map = b >= 0xde;
    if (!canon_read_uint(p, end, (b & 1) ? 4 : 2, &v)) return false;
    if (v <= ((b & 1) ? 0xffff : 15)) return false;
  } else if (b >= 0xcc && b <= 0xd3) {
    size_t width = size_t(1) << ((b - 0xcc) & 3);
    if (!canon_read_uint(p, end, width, &v)) return false;
    static const u64 kUnsignedMin[4] = {0x80, 0x100, 0x10000,
                                        0x100000000ULL};
    if (b <= 0xcf) return v >= kUnsignedMin[b - 0xcc];
    // signed tags are canonical only below the next narrower range
    static const i64 kSignedMax[4] = {-33, -129, -32769, -2147483649LL};
    i64 sv;
    if (b == 0xd0) sv = static_cast<int8_t>(v);
    else if (b == 0xd1) sv = static_cast<int16_t>(v);
    else if (b == 0xd2) sv = static_cast<int32_t>(v);
    else sv = static_cast<i64>(v);
    return sv <= kSignedMax[b - 0xd0];
  } else {
    switch (b) {
      case 0xc0: case 0xc2: case 0xc3: return true;
      case 0xcb:
        if (end - p < 8) return false;
        p += 8;
        return true;
      case 0xd9: case 0xda: case 0xdb:
        if (!canon_read_uint(p, end, size_t(1) << (b - 0xd9), &v))
          return false;
        if (v <= (b == 0xd9 ? 31 : b == 0xda ? 0xff : 0xffff))
          return false;
        return canon_str_body(p, end, v);
      case 0xc4: case 0xc5: case 0xc6:
        if (!canon_read_uint(p, end, size_t(1) << (b - 0xc4), &v))
          return false;
        if ((b == 0xc5 && v <= 0xff) || (b == 0xc6 && v <= 0xffff))
          return false;
        if (static_cast<u64>(end - p) < v) return false;
        p += v;
        return true;
      default:
        return false;  // float32, ext, reserved
    }
  }
  if (!is_map) {
    for (u64 i = 0; i < v; ++i)
      if (!canon_asis(p, end, depth + 1, ops)) return false;
    return true;
  }
  const u8* small[16];
  std::vector<const u8*> big;
  for (u64 i = 0; i < v; ++i) {
    const u8* key = p;
    if (p >= end || !((*p & 0xe0) == 0xa0 || *p == 0xd9 || *p == 0xda ||
                      *p == 0xdb))
      return false;
    if (!canon_asis(p, end, depth + 1)) return false;
    // a key's header holds its length, so equal leading bytes are an
    // equal key; an earlier key lies before this one, so the compare
    // stays inside the span
    size_t kn = static_cast<size_t>(p - key);
    if (i == 16) big.assign(small, small + 16);
    const u8** seen = i < 16 ? small : big.data();
    for (u64 j = 0; j < i; ++j)
      if (std::memcmp(seen[j], key, kn) == 0) return false;
    if (i < 16) small[i] = key;
    else big.push_back(key);
    if (ops && kn == 4 && std::memcmp(key, "\xa3ops", 4) == 0) {
      if (p >= end || !is_array_tag(*p)) return false;
      u64 n_ops = *p & 0x0f;
      const u8* h = p + 1;
      if (*p >= 0xdc && !canon_read_uint(h, end, *p == 0xdc ? 2 : 4, &n_ops))
        return false;
      *ops += static_cast<int64_t>(n_ops);
    }
    if (!canon_asis(p, end, depth + 1)) return false;
  }
  return true;
}

// true iff raw is one canonical value and nothing follows it (the
// precondition for columnarizing; mirrors columnar.py _canonical)
static bool canonical_ok(const u8* raw, size_t len) {
  const u8* p = raw;
  return canon_asis(p, raw + len, 0) && p == raw + len;
}

// ---------------------------------------------------------------------------
// encoder
// ---------------------------------------------------------------------------

struct ColStrings {
  std::unordered_map<std::string_view, u32> idx;
  std::deque<std::string> store;   // stable addresses back the views
  u32 of(std::string_view s) {
    auto it = idx.find(s);
    if (it != idx.end()) return it->second;
    store.emplace_back(s);
    u32 id = static_cast<u32>(store.size() - 1);
    idx.emplace(std::string_view(store.back()), id);
    return id;
  }
  void dump(std::vector<u8>& body) const {
    put_uvarint(body, store.size());
    for (const std::string& s : store) {
      put_uvarint(body, s.size());
      body.insert(body.end(), s.begin(), s.end());
    }
  }
};

struct ColRLE {
  std::vector<std::pair<u64, u64>> runs;
  void push(u64 v) {
    if (!runs.empty() && runs.back().first == v) ++runs.back().second;
    else runs.emplace_back(v, 1);
  }
  void dump(std::vector<u8>& body) const {
    put_uvarint(body, runs.size());
    for (auto& [v, c] : runs) {
      put_uvarint(body, v);
      put_uvarint(body, c);
    }
  }
};

// one field of a parsed change/op map: key view + raw value span
struct Field {
  std::string_view key;
  const u8* p;
  size_t len;
};

struct ColEncoder {
  ColStrings strings;
  std::map<std::vector<std::string>, u32> cshapes;   // 1-based ids
  std::vector<const std::vector<std::string>*> cshape_list;
  std::map<std::pair<std::vector<std::string>, std::string>, u32> oshapes;
  std::vector<const std::pair<std::vector<std::string>, std::string>*>
      oshape_list;
  ColRLE cshape_col, oshape_col;
  std::map<std::pair<int, std::string>, std::vector<u8>> cols;
  std::vector<u8> residuals;
  i64 n_residual = 0;
  i64 n_changes = 0;
  std::unordered_map<u32, i128> last_seq;    // actor idx -> seq
  std::unordered_map<u32, i128> run_clock;   // actor idx -> max seq
  i128 last_elem = 0;
  i128 last_key_elem = 0;
  std::vector<Field> fields, op_fields;

  // per-level column cache: the field vocabulary is tiny and fixed,
  // and the map probe below pays a string construction per field of
  // every op otherwise (the same cost the decoder's sid caches remove)
  std::vector<std::pair<std::string, std::vector<u8>*>> col_cache[2];

  std::vector<u8>& col(int level, std::string_view name) {
    auto& cache = col_cache[level ? 1 : 0];
    for (auto& [n, ptr] : cache)
      if (n == name) return *ptr;
    auto it = cols.find({level, std::string(name)});
    if (it == cols.end())
      it = cols.emplace(std::make_pair(level, std::string(name)),
                        std::vector<u8>()).first;
    // std::map nodes are stable: the cached pointer survives inserts
    cache.emplace_back(std::string(name), &it->second);
    return it->second;
  }

  void add_residual(const u8* raw, size_t len) {
    cshape_col.push(0);
    put_uvarint(residuals, len);
    residuals.insert(residuals.end(), raw, raw + len);
    ++n_residual;
    ++n_changes;
  }

  // parses one map value into ordered (key, value-span) fields; false
  // when not a map / keys not strings (callers then go residual)
  static bool parse_fields(Reader& r, std::vector<Field>& out) {
    out.clear();
    if (r.peek_type() != Type::Map) return false;
    size_t n = r.read_map();
    for (size_t i = 0; i < n; ++i) {
      if (r.peek_type() != Type::Str) return false;
      std::string_view k = r.read_str_view();
      auto span = r.raw_value();
      out.push_back({k, span.first, span.second});
    }
    return true;
  }

  static bool is_wire_int(const u8* p, size_t len) {
    if (!len) return false;
    u8 b = p[0];
    return b <= 0x7f || b >= 0xe0 || (b >= 0xcc && b <= 0xd3);
  }
  static bool is_wire_uint(const u8* p, size_t len) {
    IntVal v;
    const u8* q = p;
    return canon_read_int(q, p + len, &v) && !v.neg;
  }
  static bool is_wire_str(const u8* p, size_t len) {
    if (!len) return false;
    u8 b = p[0];
    return (b & 0xe0) == 0xa0 || b == 0xd9 || b == 0xda || b == 0xdb;
  }

  // schema checks mirroring _columnarizable/_op_columnarizable: the
  // typed columns route obj/key/elem BY NAME, so those fields must
  // hold their schema types
  bool columnarizable(const std::vector<Field>& fs) {
    bool has_actor = false, has_seq = false;
    for (const Field& f : fs) {
      if (f.key == "actor") {
        if (!is_wire_str(f.p, f.len)) return false;
        has_actor = true;
      } else if (f.key == "seq") {
        if (!is_wire_uint(f.p, f.len)) return false;
        has_seq = true;
      } else if (f.key == "deps") {
        Reader r(f.p, f.len);
        if (r.peek_type() != Type::Map) return false;
        size_t n = r.read_map();
        for (size_t i = 0; i < n; ++i) {
          if (r.peek_type() != Type::Str) return false;
          r.read_str_view();
          if (r.peek_type() != Type::Int) return false;
          r.skip();
        }
      } else if (f.key == "ops") {
        Reader r(f.p, f.len);
        if (r.peek_type() != Type::Array) return false;
        size_t n = r.read_array();
        for (size_t i = 0; i < n; ++i) {
          if (!parse_fields(r, op_fields)) return false;
          bool has_action = false;
          for (const Field& of : op_fields) {
            if (of.key == "action") {
              if (!is_wire_str(of.p, of.len)) return false;
              has_action = true;
            } else if (of.key == "obj" || of.key == "key") {
              if (!is_wire_str(of.p, of.len)) return false;
            } else if (of.key == "elem") {
              if (!is_wire_int(of.p, of.len)) return false;
            }
          }
          if (!has_action) return false;
        }
      }
    }
    return has_actor && has_seq;
  }

  void value(std::vector<u8>& out, const u8* p, size_t len) {
    u8 b = p[0];
    if (b == 0xc3) { out.push_back(V_TRUE); return; }
    if (b == 0xc2) { out.push_back(V_FALSE); return; }
    if (b == 0xc0) { out.push_back(V_NULL); return; }
    if (is_wire_int(p, len)) {
      IntVal v;
      const u8* q = p;
      canon_read_int(q, p + len, &v);
      out.push_back(V_INT);
      put_uvarint(out, zz_fold(v.neg, v.mag));
      return;
    }
    if (is_wire_str(p, len)) {
      Reader r(p, len);
      out.push_back(V_STR);
      put_uvarint(out, strings.of(r.read_str_view()));
      return;
    }
    if (b == 0xcb) {  // float64: 8 bytes verbatim
      out.push_back(V_FLOAT);
      out.insert(out.end(), p + 1, p + 9);
      return;
    }
    if (b == 0xc4 || b == 0xc5 || b == 0xc6) {
      Reader r(p, len);
      auto bv = r.read_bin_view();
      out.push_back(V_BIN);
      put_uvarint(out, bv.second);
      out.insert(out.end(), bv.first, bv.first + bv.second);
      return;
    }
    out.push_back(V_MSGPACK);
    put_uvarint(out, len);
    out.insert(out.end(), p, p + len);
  }

  u32 cshape_of(const std::vector<Field>& fs) {
    std::vector<std::string> keys;
    keys.reserve(fs.size());
    for (const Field& f : fs) keys.emplace_back(f.key);
    auto it = cshapes.find(keys);
    if (it != cshapes.end()) return it->second;
    u32 id = static_cast<u32>(cshape_list.size() + 1);
    auto ins = cshapes.emplace(std::move(keys), id).first;
    cshape_list.push_back(&ins->first);
    return id;
  }

  u32 oshape_of(const std::vector<Field>& fs, std::string_view action) {
    std::vector<std::string> keys;
    keys.reserve(fs.size());
    for (const Field& f : fs) keys.emplace_back(f.key);
    std::pair<std::vector<std::string>, std::string> k(
        std::move(keys), std::string(action));
    auto it = oshapes.find(k);
    if (it != oshapes.end()) return it->second;
    u32 id = static_cast<u32>(oshape_list.size());
    auto ins = oshapes.emplace(std::move(k), id).first;
    oshape_list.push_back(&ins->first);
    return id;
  }

  // decimal-split rule for op 'key' values: mirrors columnar.py's
  // rpartition(':') + isdecimal + str(int(tail)) == tail (ASCII digits,
  // no leading zeros), conservatively bounded to i64 elems
  static bool split_elem_key(std::string_view v, std::string_view* head,
                             i64* elem) {
    size_t pos = v.rfind(':');
    if (pos == std::string_view::npos || pos == 0 ||
        pos + 1 >= v.size())
      return false;
    std::string_view tail = v.substr(pos + 1);
    if (tail.size() > 1 && tail[0] == '0') return false;
    if (tail.size() > 18) return false;   // conservative i64 bound
    i64 n = 0;
    for (char c : tail) {
      if (c < '0' || c > '9') return false;
      n = n * 10 + (c - '0');
    }
    *head = v.substr(0, pos);
    *elem = n;
    return true;
  }

  void add_op(Reader& r) {
    if (!parse_fields(r, op_fields))
      throw corrupt("internal: op reparse diverged");  // pre-validated
    std::string_view action;
    for (const Field& f : op_fields)
      if (f.key == "action") {
        Reader ar(f.p, f.len);
        action = ar.read_str_view();
      }
    oshape_col.push(oshape_of(op_fields, action));
    for (const Field& f : op_fields) {
      if (f.key == "action") continue;   // rides the shape id
      if (f.key == "obj") {
        Reader vr(f.p, f.len);
        put_uvarint(col(1, "obj"), strings.of(vr.read_str_view()));
      } else if (f.key == "elem") {
        IntVal v;
        const u8* q = f.p;
        canon_read_int(q, f.p + f.len, &v);
        i128 e = v.neg ? -static_cast<i128>(v.mag - 1) - 1
                       : static_cast<i128>(v.mag);
        put_zigzag(col(1, "elem"), e - last_elem);
        last_elem = e;
      } else if (f.key == "key") {
        Reader vr(f.p, f.len);
        std::string_view sv = vr.read_str_view();
        std::vector<u8>& out = col(1, "key");
        std::string_view head;
        i64 elem;
        if (split_elem_key(sv, &head, &elem)) {
          out.push_back(K_ELEM);
          put_uvarint(out, strings.of(head));
          put_zigzag(out, static_cast<i128>(elem) - last_key_elem);
          last_key_elem = elem;
        } else {
          out.push_back(K_STR);
          put_uvarint(out, strings.of(sv));
        }
      } else {
        value(col(1, std::string(f.key)), f.p, f.len);
      }
    }
  }

  void add(const u8* raw, size_t len) {
    if (!canonical_ok(raw, len)) {
      add_residual(raw, len);
      return;
    }
    Reader top(raw, len);
    if (!parse_fields(top, fields) || !columnarizable(fields)) {
      add_residual(raw, len);
      return;
    }
    ++n_changes;
    cshape_col.push(cshape_of(fields));
    // actor interns FIRST (mirrors the Python encoder's table order)
    u32 actor_i = 0;
    i128 seq = 0;
    for (const Field& f : fields) {
      if (f.key == "actor") {
        Reader vr(f.p, f.len);
        actor_i = strings.of(vr.read_str_view());
      } else if (f.key == "seq") {
        IntVal v;
        const u8* q = f.p;
        canon_read_int(q, f.p + f.len, &v);
        seq = static_cast<i128>(v.mag);
      }
    }
    for (const Field& f : fields) {
      if (f.key == "actor") {
        put_uvarint(col(0, "actor"), actor_i);
      } else if (f.key == "seq") {
        auto it = last_seq.find(actor_i);
        i128 prev = it == last_seq.end() ? 0 : it->second;
        put_zigzag(col(0, "seq"), seq - prev - 1);
      } else if (f.key == "deps") {
        std::vector<u8>& out = col(0, "deps");
        Reader vr(f.p, f.len);
        size_t n = vr.read_map();
        put_uvarint(out, n);
        for (size_t i = 0; i < n; ++i) {
          u32 di = strings.of(vr.read_str_view());
          IntVal v;
          const u8* q = vr.pos();
          canon_read_int(q, vr.end(), &v);
          vr.skip();
          i128 ds = v.neg ? -static_cast<i128>(v.mag - 1) - 1
                          : static_cast<i128>(v.mag);
          auto rit = run_clock.find(di);
          i128 rc = rit == run_clock.end() ? 0 : rit->second;
          put_uvarint(out, di);
          put_zigzag(out, ds - rc);
        }
      } else if (f.key == "ops") {
        Reader vr(f.p, f.len);
        size_t n = vr.read_array();
        put_uvarint(col(0, "ops"), n);
        for (size_t i = 0; i < n; ++i) add_op(vr);
      } else {
        value(col(0, std::string(f.key)), f.p, f.len);
      }
    }
    last_seq[actor_i] = seq;
    auto rit = run_clock.find(actor_i);
    if (rit == run_clock.end() || seq > rit->second)
      run_clock[actor_i] = seq;
  }

  std::vector<u8> dump() {
    // pre-intern late strings in the Python encoder's exact order:
    // change-shape keys, op-shape keys + actions, column names
    for (const auto* keys : cshape_list)
      for (const std::string& k : *keys) strings.of(k);
    for (const auto* sh : oshape_list) {
      for (const std::string& k : sh->first) strings.of(k);
      strings.of(sh->second);
    }
    for (const auto& [lk, _] : cols) strings.of(lk.second);
    std::vector<u8> body;
    put_uvarint(body, n_changes);
    strings.dump(body);
    put_uvarint(body, cshape_list.size());
    for (const auto* keys : cshape_list) {
      put_uvarint(body, keys->size());
      for (const std::string& k : *keys) put_uvarint(body, strings.of(k));
    }
    put_uvarint(body, oshape_list.size());
    for (const auto* sh : oshape_list) {
      put_uvarint(body, sh->first.size());
      for (const std::string& k : sh->first)
        put_uvarint(body, strings.of(k));
      put_uvarint(body, strings.of(sh->second));
    }
    cshape_col.dump(body);
    oshape_col.dump(body);
    put_uvarint(body, cols.size());
    for (const auto& [lk, c] : cols) {   // std::map: sorted (level, name)
      body.push_back(static_cast<u8>(lk.first));
      put_uvarint(body, strings.of(lk.second));
      put_uvarint(body, c.size());
      body.insert(body.end(), c.begin(), c.end());
    }
    put_uvarint(body, residuals.size());
    body.insert(body.end(), residuals.begin(), residuals.end());
    // whole-body zlib (level 6, same as the Python codec); store raw
    // when incompressible
    uLongf bound = compressBound(static_cast<uLong>(body.size()));
    std::vector<u8> packed(bound);
    int rc = compress2(packed.data(), &bound, body.data(),
                       static_cast<uLong>(body.size()), 6);
    u8 flags = COL_FLAG_ZLIB;
    if (rc != Z_OK || bound >= body.size()) {
      packed = std::move(body);
      flags = 0;
    } else {
      packed.resize(bound);
    }
    std::vector<u8> out;
    out.reserve(packed.size() + 6);
    out.push_back('A'); out.push_back('M');
    out.push_back('T'); out.push_back('C');
    out.push_back(COL_VERSION);
    out.push_back(flags);
    out.insert(out.end(), packed.begin(), packed.end());
    return out;
  }
};

// ---------------------------------------------------------------------------
// decoder: columns -> canonical raw change bytes, appended to one slab
// ---------------------------------------------------------------------------

static std::string i128_str(i128 v) {
  if (v >= INT64_MIN && v <= INT64_MAX)
    return std::to_string(static_cast<i64>(v));
  bool neg = v < 0;
  u128 m = neg ? static_cast<u128>(-(v + 1)) + 1 : static_cast<u128>(v);
  std::string s;
  while (m) {
    s.push_back('0' + static_cast<char>(m % 10));
    m /= 10;
  }
  if (neg) s.push_back('-');
  std::reverse(s.begin(), s.end());
  return s;
}

static void put_canon_i128(Writer& w, i128 v) {
  if (v >= 0) {
    if (v >> 64) throw corrupt("integer out of range");
    w.uinteger(static_cast<u64>(v));
  } else {
    if (v < static_cast<i128>(INT64_MIN))
      throw corrupt("integer out of range");
    w.integer(static_cast<i64>(v));
  }
}

// one reusable zlib inflater per thread: cold restarts decode
// thousands of small blobs, and a fresh inflateInit per blob is
// alloc-heavy (the ~40 KB inflate state)
struct Inflater {
  z_stream zs{};
  bool live = false;
  ~Inflater() {
    if (live) inflateEnd(&zs);
  }
};

static void inflate_body(const u8* in, size_t n, std::vector<u8>& out) {
  static thread_local Inflater inf;
  if (!inf.live) {
    if (inflateInit(&inf.zs) != Z_OK) throw corrupt("zlib init failed");
    inf.live = true;
  } else if (inflateReset(&inf.zs) != Z_OK) {
    throw corrupt("zlib reset failed");
  }
  inf.zs.next_in = const_cast<u8*>(in);
  inf.zs.avail_in = static_cast<uInt>(n);
  out.resize(std::max<size_t>(n * 4, 1 << 12));
  size_t have = 0;
  int rc;
  do {
    if (have == out.size()) out.resize(out.size() * 2);
    inf.zs.next_out = out.data() + have;
    inf.zs.avail_out = static_cast<uInt>(out.size() - have);
    rc = inflate(&inf.zs, Z_NO_FLUSH);
    have = out.size() - inf.zs.avail_out;
    if (rc != Z_OK && rc != Z_STREAM_END)
      throw corrupt("zlib inflate failed");
  } while (rc != Z_STREAM_END);
  out.resize(have);
}

struct ColDecoder {
  std::vector<u8> body_store;    // inflated body (columns point into it)
  size_t n_changes = 0;
  std::vector<std::string> strings;
  std::vector<std::vector<u32>> cshapes;               // key string ids
  std::vector<std::pair<std::vector<u32>, u32>> oshapes;
  std::vector<u64> cshape_ids;
  std::vector<u64> oshape_ids;
  size_t oshape_cursor = 0;
  std::map<std::pair<int, std::string>, ColReader> cols;
  ColReader residuals{nullptr, 0};
  std::unordered_map<u32, i128> last_seq, run_clock;
  i128 last_elem = 0, last_key_elem = 0;
  // hot-path caches: per-field column lookups by STRING ID instead of
  // a map probe with a string construction per field (the cold-start
  // profile's largest single cost); special keys compare as sids
  static constexpr u32 NOSID = 0xffffffffu;
  u32 sid_actor = NOSID, sid_seq = NOSID, sid_deps = NOSID,
      sid_ops = NOSID, sid_action = NOSID, sid_obj = NOSID,
      sid_elem = NOSID, sid_key = NOSID, sid_value = NOSID,
      sid_datatype = NOSID, sid_message = NOSID;
  // fused arena-direct state: blob string id -> pool intern sid, and
  // per-oshape parsed action enums (0xfe = not parsed yet)
  std::vector<u32> psid_cache;
  std::vector<u8> oshape_action;
  std::vector<ColReader*> c0_cache, c1_cache;
  ColReader* actor_col = nullptr;
  ColReader* seq_col = nullptr;
  ColReader* deps_col = nullptr;
  ColReader* ops_col = nullptr;
  ColReader* obj_col = nullptr;
  ColReader* elem_col = nullptr;
  ColReader* key_col = nullptr;

  const std::string& str_at(u64 i) const {
    if (i >= strings.size()) throw corrupt("string index out of range");
    return strings[static_cast<size_t>(i)];
  }

  ColReader* ccol(int level, u32 sid) {
    auto& cache = level ? c1_cache : c0_cache;
    ColReader*& slot = cache[sid];
    if (!slot) slot = &col(level, strings[sid]);
    return slot;
  }

  explicit ColDecoder(const u8* blob, size_t len) {
    if (len < 6 || std::memcmp(blob, "AMTC", 4) != 0)
      throw corrupt("not a columnar change blob (bad magic)");
    if (blob[4] != COL_VERSION)
      throw corrupt("unsupported columnar version " +
                    std::to_string(blob[4]));
    if (blob[5] & COL_FLAG_ZLIB) {
      inflate_body(blob + 6, len - 6, body_store);
    } else {
      body_store.assign(blob + 6, blob + len);
    }
    ColReader r(body_store.data(), body_store.size());
    n_changes = static_cast<size_t>(r.uvarint64());
    size_t n_strs = static_cast<size_t>(r.uvarint64());
    strings.reserve(std::min(n_strs,
                             body_store.size() / 2 + 1));
    for (size_t i = 0; i < n_strs; ++i) {
      size_t n = static_cast<size_t>(r.uvarint64());
      const u8* p = r.take(n);
      if (!utf8_valid(p, n)) throw corrupt("invalid utf-8 in table");
      strings.emplace_back(reinterpret_cast<const char*>(p), n);
    }
    size_t n_cshapes = static_cast<size_t>(r.uvarint64());
    for (size_t i = 0; i < n_cshapes; ++i) {
      size_t k = static_cast<size_t>(r.uvarint64());
      std::vector<u32> keys;
      keys.reserve(std::min<size_t>(k, 64));
      for (size_t j = 0; j < k; ++j) {
        u64 si = r.uvarint64();
        str_at(si);
        keys.push_back(static_cast<u32>(si));
      }
      cshapes.push_back(std::move(keys));
    }
    size_t n_oshapes = static_cast<size_t>(r.uvarint64());
    for (size_t i = 0; i < n_oshapes; ++i) {
      size_t k = static_cast<size_t>(r.uvarint64());
      std::vector<u32> keys;
      keys.reserve(std::min<size_t>(k, 64));
      for (size_t j = 0; j < k; ++j) {
        u64 si = r.uvarint64();
        str_at(si);
        keys.push_back(static_cast<u32>(si));
      }
      u64 ai = r.uvarint64();
      str_at(ai);
      oshapes.emplace_back(std::move(keys), static_cast<u32>(ai));
    }
    auto expand = [&](std::vector<u64>& out) {
      size_t n_runs = static_cast<size_t>(r.uvarint64());
      for (size_t i = 0; i < n_runs; ++i) {
        u64 v = r.uvarint64();
        u64 c = r.uvarint64();
        if (out.size() + c > body_store.size() * 8 + n_changes + 64)
          throw corrupt("RLE run count implausible");
        for (u64 j = 0; j < c; ++j) out.push_back(v);
      }
    };
    expand(cshape_ids);
    expand(oshape_ids);
    size_t n_cols = static_cast<size_t>(r.uvarint64());
    for (size_t i = 0; i < n_cols; ++i) {
      int level = r.byte();
      const std::string& name = str_at(r.uvarint64());
      size_t n = static_cast<size_t>(r.uvarint64());
      const u8* p = r.take(n);
      cols.emplace(std::make_pair(level, name), ColReader(p, n));
    }
    size_t rn = static_cast<size_t>(r.uvarint64());
    const u8* rp = r.take(rn);
    residuals = ColReader(rp, rn);
    c0_cache.assign(strings.size(), nullptr);
    c1_cache.assign(strings.size(), nullptr);
    for (size_t i = 0; i < strings.size(); ++i) {
      const std::string& s = strings[i];
      if (s == "actor") sid_actor = static_cast<u32>(i);
      else if (s == "seq") sid_seq = static_cast<u32>(i);
      else if (s == "deps") sid_deps = static_cast<u32>(i);
      else if (s == "ops") sid_ops = static_cast<u32>(i);
      else if (s == "action") sid_action = static_cast<u32>(i);
      else if (s == "obj") sid_obj = static_cast<u32>(i);
      else if (s == "elem") sid_elem = static_cast<u32>(i);
      else if (s == "key") sid_key = static_cast<u32>(i);
      else if (s == "value") sid_value = static_cast<u32>(i);
      else if (s == "datatype") sid_datatype = static_cast<u32>(i);
      else if (s == "message") sid_message = static_cast<u32>(i);
    }
  }

  u32 psid(Pool& pool, u64 i) {
    u32& slot = psid_cache[static_cast<size_t>(i)];
    if (slot == NOSID) slot = pool.intern.id_of(strings[i]);
    return slot;
  }

  ColReader& col(int level, const std::string& name) {
    auto it = cols.find({level, name});
    if (it == cols.end())
      throw corrupt("missing column " + name);
    return it->second;
  }

  void write_value(Writer& w, ColReader& r) {
    u8 tag = r.byte();
    switch (tag) {
      case V_TRUE: w.boolean(true); return;
      case V_FALSE: w.boolean(false); return;
      case V_NULL: w.nil(); return;
      case V_INT: {
        u128 n = r.uvarint();
        i128 v = (n & 1) ? -static_cast<i128>(n >> 1) - 1
                         : static_cast<i128>(n >> 1);
        put_canon_i128(w, v);
        return;
      }
      case V_STR: w.str(str_at(r.uvarint64())); return;
      case V_FLOAT: {
        const u8* p = r.take(8);
        w.buf.push_back(0xcb);
        w.raw(p, 8);
        return;
      }
      case V_BIN: {
        size_t n = static_cast<size_t>(r.uvarint64());
        const u8* p = r.take(n);
        if (n <= 0xff) {
          w.buf.push_back(0xc4);
          w.buf.push_back(static_cast<u8>(n));
        } else if (n <= 0xffff) {
          w.buf.push_back(0xc5);
          w.buf.push_back(static_cast<u8>(n >> 8));
          w.buf.push_back(static_cast<u8>(n & 0xff));
        } else {
          w.buf.push_back(0xc6);
          for (int i = 3; i >= 0; --i)
            w.buf.push_back(static_cast<u8>((n >> (8 * i)) & 0xff));
        }
        w.raw(p, n);
        return;
      }
      case V_MSGPACK: {
        size_t n = static_cast<size_t>(r.uvarint64());
        w.raw(r.take(n), n);
        return;
      }
      default: throw corrupt("bad value tag " + std::to_string(tag));
    }
  }

  void write_op(Writer& w) {
    if (oshape_cursor >= oshape_ids.size())
      throw corrupt("op shape column exhausted");
    u64 sid = oshape_ids[oshape_cursor++];
    if (sid >= oshapes.size()) throw corrupt("op shape id out of range");
    auto& [keys, action] = oshapes[static_cast<size_t>(sid)];
    w.map(keys.size());
    for (u32 k : keys) {
      w.str(strings[k]);
      if (k == sid_action) {
        w.str(strings[action]);
      } else if (k == sid_obj) {
        if (!obj_col) obj_col = &col(1, "obj");
        w.str(str_at(obj_col->uvarint64()));
      } else if (k == sid_elem) {
        if (!elem_col) elem_col = &col(1, "elem");
        last_elem += elem_col->zigzag();
        put_canon_i128(w, last_elem);
      } else if (k == sid_key) {
        if (!key_col) key_col = &col(1, "key");
        ColReader& r = *key_col;
        u8 tag = r.byte();
        if (tag == K_ELEM) {
          const std::string& head = str_at(r.uvarint64());
          last_key_elem += r.zigzag();
          w.str(head + ":" + i128_str(last_key_elem));
        } else if (tag == K_STR) {
          w.str(str_at(r.uvarint64()));
        } else {
          throw corrupt("bad key tag " + std::to_string(tag));
        }
      } else {
        write_value(w, *ccol(1, k));
      }
    }
  }

  // ---- fused arena-direct decode (amtpu_begin_columnar) -------------
  // Builds each change's canonical raw bytes AND its ChangeRec in ONE
  // column walk -- no second msgpack parse.  Field semantics mirror
  // decode_change/decode_op exactly (intern routing, the single-char
  // value table, last-wins casts); the decode-parity lanes pin the
  // output byte-identical to the dict-replay path.

  OpRec fused_op(Pool& pool, Writer& w, u32 ch_actor, u32 ch_seq,
                 std::string& ekey_buf, u32& ekey_sid) {
    if (oshape_cursor >= oshape_ids.size())
      throw corrupt("op shape column exhausted");
    u64 sid = oshape_ids[oshape_cursor++];
    if (sid >= oshapes.size()) throw corrupt("op shape id out of range");
    auto& [keys, action] = oshapes[static_cast<size_t>(sid)];
    u8& act = oshape_action[static_cast<size_t>(sid)];
    if (act == 0xfe) act = parse_action_sv(strings[action]);
    OpRec op;
    op.action = act;
    op.obj = NONE; op.key = NONE; op.elem = -1;
    op.actor = ch_actor; op.seq = ch_seq;
    op.datatype = NONE; op.value_rid = NONE; op.value_sid = NONE;
    w.map(keys.size());
    for (u32 k : keys) {
      w.str(strings[k]);
      if (k == sid_action) {
        w.str(strings[action]);
      } else if (k == sid_obj) {
        if (!obj_col) obj_col = &col(1, "obj");
        u64 oi = obj_col->uvarint64();
        w.str(str_at(oi));
        op.obj = psid(pool, oi);
      } else if (k == sid_elem) {
        if (!elem_col) elem_col = &col(1, "elem");
        last_elem += elem_col->zigzag();
        put_canon_i128(w, last_elem);
        // same cast chain as decode_op's r.read_int() (i64 via u64)
        op.elem = static_cast<i64>(static_cast<u64>(last_elem));
      } else if (k == sid_key) {
        if (!key_col) key_col = &col(1, "key");
        ColReader& r = *key_col;
        u8 tag = r.byte();
        if (tag == K_ELEM) {
          const std::string& head = str_at(r.uvarint64());
          last_key_elem += r.zigzag();
          std::string key_s = head + ":" + i128_str(last_key_elem);
          w.str(key_s);
          // set-then-ins interns each elemId key twice in a row
          if (ekey_sid == NOSID || key_s != ekey_buf) {
            ekey_sid = pool.intern.id_of(key_s);
            ekey_buf = std::move(key_s);
          }
          op.key = ekey_sid;
        } else if (tag == K_STR) {
          u64 ki = r.uvarint64();
          w.str(str_at(ki));
          op.key = psid(pool, ki);
        } else {
          throw corrupt("bad key tag " + std::to_string(tag));
        }
      } else if (k == sid_value) {
        ColReader& r = *ccol(1, k);
        u8 tag = r.p < r.end ? *r.p : 0xff;
        if (tag == V_STR) {
          ++r.p;
          u64 vi = r.uvarint64();
          const std::string& s = str_at(vi);
          size_t voff = w.buf.size();
          w.str(s);
          std::string_view raw(
              reinterpret_cast<const char*>(w.buf.data() + voff),
              w.buf.size() - voff);
          if (s.size() == 1) {
            u8 c = static_cast<u8>(s[0]);
            if (pool.char_sid[c] == NONE) {
              pool.char_sid[c] = pool.intern.id_of(s);
              pool.char_rid[c] = pool.vals.id_of(raw);
            }
            op.value_sid = pool.char_sid[c];
            op.value_rid = pool.char_rid[c];
          } else {
            op.value_sid = psid(pool, vi);
            op.value_rid = pool.vals.id_of(raw);
          }
        } else {
          size_t voff = w.buf.size();
          write_value(w, r);
          op.value_rid = pool.vals.id_of(std::string_view(
              reinterpret_cast<const char*>(w.buf.data() + voff),
              w.buf.size() - voff));
        }
      } else if (k == sid_datatype) {
        ColReader& r = *ccol(1, k);
        u8 tag = r.p < r.end ? *r.p : 0xff;
        if (tag == V_STR) {
          ++r.p;
          u64 di = r.uvarint64();
          w.str(str_at(di));
          op.datatype = psid(pool, di);
        } else {
          // non-string datatype cannot come from either encoder's
          // schema check; decode generically (decode_op would skip it)
          write_value(w, r);
        }
      } else {
        write_value(w, *ccol(1, k));
      }
    }
    return op;
  }

  void decode_changes(Pool& pool,
                      const std::shared_ptr<std::vector<u8>>& slab,
                      std::vector<ChangeRec>& out) {
    std::vector<u8>& sl = *slab;
    Writer w;
    psid_cache.assign(strings.size(), NOSID);
    oshape_action.assign(oshapes.size(), 0xfe);
    std::string ekey_buf;
    u32 ekey_sid = NOSID;
    out.reserve(out.size() + cshape_ids.size());
    for (u64 sid : cshape_ids) {
      if (sid == 0) {   // residual: verbatim bytes, generic decode
        size_t n = static_cast<size_t>(residuals.uvarint64());
        const u8* p = residuals.take(n);
        size_t off = sl.size();
        sl.insert(sl.end(), p, p + n);
        // fresh DecodeCache per residual: the shared-cache views would
        // dangle across this slab's later growth
        Reader cr(sl.data() + off, n);
        out.push_back(decode_change(cr, pool, slab));
        continue;
      }
      if (sid > cshapes.size()) throw corrupt("shape id out of range");
      const std::vector<u32>& keys = cshapes[static_cast<size_t>(sid - 1)];
      w.buf.clear();
      if (!actor_col) actor_col = &col(0, "actor");
      if (!seq_col) seq_col = &col(0, "seq");
      u64 actor_i = actor_col->uvarint64();
      str_at(actor_i);
      i128 d = seq_col->zigzag();
      auto lit = last_seq.find(static_cast<u32>(actor_i));
      i128 seq = (lit == last_seq.end() ? 0 : lit->second) + 1 + d;
      ChangeRec ch;
      ch.actor = psid(pool, actor_i);
      ch.seq = static_cast<u32>(static_cast<u64>(seq));
      w.map(keys.size());
      for (u32 k : keys) {
        w.str(strings[k]);
        if (k == sid_actor) {
          w.str(strings[static_cast<size_t>(actor_i)]);
        } else if (k == sid_seq) {
          put_canon_i128(w, seq);
        } else if (k == sid_deps) {
          if (!deps_col) deps_col = &col(0, "deps");
          ColReader& r = *deps_col;
          size_t n = static_cast<size_t>(r.uvarint64());
          w.map(n);
          ch.deps.reserve(n);
          for (size_t i = 0; i < n; ++i) {
            u64 di = r.uvarint64();
            w.str(str_at(di));
            auto rit = run_clock.find(static_cast<u32>(di));
            i128 ds = (rit == run_clock.end() ? 0 : rit->second) +
                      r.zigzag();
            put_canon_i128(w, ds);
            ch.deps.emplace_back(psid(pool, di),
                                 static_cast<u32>(static_cast<u64>(ds)));
          }
        } else if (k == sid_ops) {
          if (!ops_col) ops_col = &col(0, "ops");
          size_t n = static_cast<size_t>(ops_col->uvarint64());
          w.array(n);
          ch.ops.reserve(n);
          for (size_t i = 0; i < n; ++i)
            ch.ops.push_back(fused_op(pool, w, ch.actor, ch.seq,
                                      ekey_buf, ekey_sid));
        } else {
          size_t voff = w.buf.size();
          write_value(w, *ccol(0, k));
          if (k == sid_message) {
            ch.has_message = true;
            ch.message.assign(w.buf.begin() + voff, w.buf.end());
          }
        }
      }
      last_seq[static_cast<u32>(actor_i)] = seq;
      auto rit = run_clock.find(static_cast<u32>(actor_i));
      if (rit == run_clock.end() || seq > rit->second)
        run_clock[static_cast<u32>(actor_i)] = seq;
      size_t off = sl.size();
      sl.insert(sl.end(), w.buf.begin(), w.buf.end());
      ch.raw.slab = slab;
      ch.raw.off = static_cast<u32>(off);
      ch.raw.len = static_cast<u32>(w.buf.size());
      out.push_back(std::move(ch));
    }
  }

  // appends every change's canonical raw bytes to `slab`, recording
  // (offset, length) spans; residual changes splice verbatim
  void decode_all(std::vector<u8>& slab,
                  std::vector<std::pair<size_t, size_t>>& spans) {
    Writer w;
    for (u64 sid : cshape_ids) {
      if (sid == 0) {   // residual change: verbatim bytes
        size_t n = static_cast<size_t>(residuals.uvarint64());
        const u8* p = residuals.take(n);
        size_t off = slab.size();
        slab.insert(slab.end(), p, p + n);
        spans.emplace_back(off, n);
        continue;
      }
      if (sid > cshapes.size()) throw corrupt("shape id out of range");
      const std::vector<u32>& keys = cshapes[static_cast<size_t>(sid - 1)];
      w.buf.clear();
      // actor resolves FIRST regardless of its key position (the seq
      // delta is keyed on the actor; mirrors the Python decoder)
      if (!actor_col) actor_col = &col(0, "actor");
      if (!seq_col) seq_col = &col(0, "seq");
      u64 actor_i = actor_col->uvarint64();
      str_at(actor_i);
      i128 d = seq_col->zigzag();
      auto lit = last_seq.find(static_cast<u32>(actor_i));
      i128 seq = (lit == last_seq.end() ? 0 : lit->second) + 1 + d;
      w.map(keys.size());
      for (u32 k : keys) {
        w.str(strings[k]);
        if (k == sid_actor) {
          w.str(strings[static_cast<size_t>(actor_i)]);
        } else if (k == sid_seq) {
          put_canon_i128(w, seq);
        } else if (k == sid_deps) {
          if (!deps_col) deps_col = &col(0, "deps");
          ColReader& r = *deps_col;
          size_t n = static_cast<size_t>(r.uvarint64());
          w.map(n);
          for (size_t i = 0; i < n; ++i) {
            u64 di = r.uvarint64();
            w.str(str_at(di));
            auto rit = run_clock.find(static_cast<u32>(di));
            i128 ds = (rit == run_clock.end() ? 0 : rit->second) +
                      r.zigzag();
            put_canon_i128(w, ds);
          }
        } else if (k == sid_ops) {
          if (!ops_col) ops_col = &col(0, "ops");
          size_t n = static_cast<size_t>(ops_col->uvarint64());
          w.array(n);
          for (size_t i = 0; i < n; ++i) write_op(w);
        } else {
          write_value(w, *ccol(0, k));
        }
      }
      last_seq[static_cast<u32>(actor_i)] = seq;
      auto rit = run_clock.find(static_cast<u32>(actor_i));
      if (rit == run_clock.end() || seq > rit->second)
        run_clock[static_cast<u32>(actor_i)] = seq;
      size_t off = slab.size();
      slab.insert(slab.end(), w.buf.begin(), w.buf.end());
      spans.emplace_back(off, w.buf.size());
    }
  }
};

static bool is_columnar_blob(const u8* p, size_t n) {
  return n >= 4 && std::memcmp(p, "AMTC", 4) == 0;
}

}  // namespace colnr

}  // namespace amtpu

// ===========================================================================
// C ABI
// ===========================================================================

using namespace amtpu;

struct BatchHandle {
  Pool* pool;
  Batch batch;
  // the begin journal OUTLIVES begin so amtpu_batch_rollback can undo a
  // batch whose device/mid phase failed AFTER begin committed schedule
  // state -- the resilience layer's retry/bisect re-applies are only
  // byte-safe against a pool restored to its pre-begin state.  emit is
  // the first phase that mutates docs beyond the journal's reach, so
  // amtpu_finish revokes rollback at entry.
  BeginJournal journal;
  bool can_rollback = false;
};

static thread_local std::string g_error;
static thread_local int g_error_kind = 0;

extern "C" {

void* amtpu_pool_new() { return new Pool(); }
void amtpu_pool_free(void* p) { delete static_cast<Pool*>(p); }

// number of materialized docs; lets tests assert that read-only queries
// on unknown ids never create phantom state
int64_t amtpu_doc_count(void* p) {
  return static_cast<int64_t>(static_cast<Pool*>(p)->docs.size());
}

const char* amtpu_last_error() { return g_error.c_str(); }
int amtpu_last_error_kind() { return g_error_kind; }

// ---- phase 1 --------------------------------------------------------------
// input: msgpack map {doc_id: [change, ...]}
void* amtpu_begin(void* pool_ptr, const uint8_t* data, int64_t len) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  auto h = std::make_unique<BatchHandle>();
  h->pool = &pool;
  h->batch.pool = &pool;
  try {
    double t0 = mono_now();
    if (len < 0 || len >= (1LL << 32))
      throw Error(0, "payload too large (raw spans use 32-bit offsets; "
                     "split batches below 4 GiB)");
    // one payload copy into a shared slab; every change's raw bytes are
    // spans into it (the caller's buffer may be freed after this call)
    auto slab = std::make_shared<std::vector<u8>>(data, data + len);
    // pre-size the intern tables from the payload: text catch-up
    // payloads intern roughly one string (elemId) per ~45 wire bytes,
    // so a fresh pool otherwise pays ~10 doubling rehashes inside the
    // decode loop.  Over-estimate is one-time slack; under-estimate
    // just means fewer doublings than before.
    // capped: the byte heuristic over-counts value-heavy payloads (a
    // few huge values, few distinct strings), and reserve never
    // shrinks -- 4M entries covers ~180 MB of change payload per call
    // while bounding a pool's table memory at ~48 MB
    pool.intern.reserve(pool.intern.n +
                        std::min<size_t>(static_cast<size_t>(len) / 45,
                                         size_t(4) << 20));
    pool.vals.reserve(pool.vals.n +
                      std::min<size_t>(static_cast<size_t>(len) / 90,
                                       size_t(2) << 20));
    Reader r(slab->data(), slab->size());
    size_t n_docs = r.read_map();
    Batch& b = h->batch;
    b.host_full = pool.host_full;
    std::vector<std::vector<ChangeRec>> incoming;
    incoming.reserve(n_docs);
    DecodeCache dc;   // batch-shared: views point into the batch slab
    for (size_t i = 0; i < n_docs; ++i) {
      std::string doc_id = r.read_str();
      size_t n_changes = r.read_array();
      std::vector<ChangeRec> chs;
      chs.reserve(std::min(n_changes,
                           static_cast<size_t>(r.end() - r.pos()) / 8));
      for (size_t j = 0; j < n_changes; ++j)
        chs.push_back(decode_change(r, pool, slab, nullptr, &dc));
      b.bdocs.push_back(&pool.doc(doc_id));
      b.bdoc_ids.push_back(std::move(doc_id));
      incoming.push_back(std::move(chs));
    }
    b.tr_decode = mono_now() - t0;
    begin_phases(pool, h->batch, incoming, h->journal);
    h->can_rollback = true;
    if (getenv("AMTPU_TRACE_BEGIN")) {
      double t_phases = mono_now();
      incoming.clear();  // measure ChangeRec teardown separately
      double t_td = mono_now();
      fprintf(stderr,
              "[begin] total=%.4f decode=%.4f sched=%.4f enc=%.4f "
              "dom=%.4f teardown=%.4f gap=%.4f\n",
              t_phases - t0, b.tr_decode, b.tr_schedule, b.tr_encode,
              b.tr_domlay, t_td - t_phases,
              (t_phases - t0) - b.tr_decode - b.tr_schedule -
                  b.tr_encode - b.tr_domlay);
    }
    // unpin the payload slab when most of it was NOT retained (duplicate-
    // heavy sync payloads re-send already-applied changes): re-adopt
    // private copies of the few retained spans so long-lived states/queue
    // entries don't hold the whole wire buffer alive
    size_t kept = 0;
    for (auto& ac : b.applied)
      if (ac.stored->raw.slab == slab) kept += ac.stored->raw.len;
    for (auto* d : b.bdocs)
      for (auto& qc : d->queue)
        if (qc.raw.slab == slab) kept += qc.raw.len;
    if (kept * 4 < slab->size()) {
      auto copy_out = [&](ChangeRec& c) {
        if (c.raw.slab != slab) return;
        std::vector<u8> buf(c.raw.data(), c.raw.data() + c.raw.len);
        c.raw.adopt(std::move(buf));
      };
      for (auto& ac : b.applied) copy_out(*ac.stored);
      for (auto* d : b.bdocs)
        for (auto& qc : d->queue) copy_out(qc);
    }
  } catch (const Error& e) {
    g_error = e.what(); g_error_kind = e.kind;
    return nullptr;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    return nullptr;
  }
  return h.release();
}

// Local change request entry (reference: backend/index.js:175-197).  The
// returned handle is driven through the same mid/finish phases as
// amtpu_begin; the patch gains actor/seq keys and real canUndo/canRedo.
void* amtpu_begin_local(void* pool_ptr, const char* doc_id,
                        const uint8_t* data, int64_t len) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  auto h = std::make_unique<BatchHandle>();
  h->pool = &pool;
  h->batch.pool = &pool;
  try {
    if (len < 0 || len >= (1LL << 32))
      throw Error(0, "payload too large (raw spans use 32-bit offsets; "
                     "split batches below 4 GiB)");
    auto slab = std::make_shared<std::vector<u8>>(data, data + len);
    Reader r(slab->data(), slab->size());
    LocalReq lr;
    ChangeRec req = decode_change(r, pool, slab, &lr);
    if (!lr.has_actor || !lr.has_seq)
      // 'requries' [sic]: parity with the reference's own error text
      // (backend/index.js:177)
      throw Error(2, "Change request requries `actor` and `seq` properties");
    DocState& st = pool.doc(doc_id);
    if (req.seq <= clock_get(st.clock, req.actor))
      throw Error(1, "Change request has already been applied");

    Batch& b = h->batch;
    b.host_full = pool.host_full;
    b.local_actor = req.actor;
    b.local_seq = req.seq;
    ChangeRec change;
    if (lr.has_request_type && lr.request_type == "change") {
      b.local_kind = 1;
      change = std::move(req);  // raw already stripped of requestType
    } else if (lr.has_request_type && (lr.request_type == "undo" ||
                                       lr.request_type == "redo")) {
      bool is_undo = lr.request_type == "undo";
      const std::vector<OpRec>* src_ops;
      if (is_undo) {
        if (st.undo_pos < 1 || st.undo_pos > st.undo_stack.size())
          throw Error(1, "Cannot undo: there is nothing to be undone");
        b.local_kind = 2;
        src_ops = &st.undo_stack[st.undo_pos - 1];
        for (const OpRec& op : *src_ops) {
          if (!is_assign(op.action))
            throw Error(1,
                        std::string("Unexpected operation type in undo "
                                    "history: ") + action_name(op.action));
        }
        // redo ops from the CURRENT field state, captured before the undo
        // change applies (backend/index.js:264-278); projection keeps
        // everything except actor/seq (datatype survives)
        for (const OpRec& op : *src_ops) {
          const Register* rit =
              st.registers.find(DocState::rkey(op.obj, op.key));
          if (!rit || rit->empty()) {
            OpRec d{};
            d.action = A_DEL; d.obj = op.obj; d.key = op.key;
            d.elem = -1; d.actor = NONE; d.seq = 0; d.datatype = NONE;
            d.value_rid = NONE; d.value_sid = NONE;
            b.pending_redo.push_back(d);
          } else {
            for (const OpRec& rec : *rit) {
              OpRec p = rec;
              p.actor = NONE; p.seq = 0; p.elem = -1;
              b.pending_redo.push_back(p);
            }
          }
        }
      } else {
        if (st.redo_stack.empty())
          throw Error(1, "Cannot redo: the last change was not an undo");
        b.local_kind = 3;
        src_ops = &st.redo_stack.back();
      }
      change.actor = req.actor;
      change.seq = req.seq;
      change.deps = req.deps;
      change.has_message = req.has_message;
      change.message = req.message;
      change.ops = *src_ops;
      for (OpRec& op : change.ops) {
        op.actor = req.actor;
        op.seq = req.seq;
      }
      change.raw.adopt(
          encode_change_raw(pool, change, !message_is_nil(change)));
    } else {
      // oracle parity: missing requestType reports as Python None
      // (backend/__init__.py::apply_local_change)
      throw Error(1, "Unknown requestType: " +
                         (lr.has_request_type ? lr.request_type
                                              : std::string("None")));
    }

    Batch& bb = h->batch;
    bb.bdocs.push_back(&st);
    bb.bdoc_ids.push_back(doc_id);
    std::vector<std::vector<ChangeRec>> incoming(1);
    incoming[0].push_back(std::move(change));
    begin_phases(pool, bb, incoming, h->journal);
    h->can_rollback = true;
  } catch (const Error& e) {
    g_error = e.what(); g_error_kind = e.kind;
    return nullptr;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    return nullptr;
  }
  return h.release();
}

void amtpu_batch_free(void* b) { delete static_cast<BatchHandle*>(b); }

// Undo everything this batch's begin committed (clocks, history, states,
// arena appends, created objects, causal queues): the pool returns to
// its byte-identical pre-begin state, so the caller may re-apply the
// same changes (retry) or any subset (poison bisection) without seq
// dedup swallowing them.  Legal from begin success until amtpu_finish
// is first entered (mid phases only mutate batch-local state); the
// handle still must be freed afterwards.  Returns 0 on success, -1 when
// the batch can no longer be rolled back.
int amtpu_batch_rollback(void* bp) {
  BatchHandle& h = *static_cast<BatchHandle*>(bp);
  if (!h.can_rollback) {
    g_error = "batch can no longer be rolled back (emit already ran)";
    g_error_kind = 0;
    return -1;
  }
  try {
    h.journal.rollback(h.batch);
    h.can_rollback = false;   // rollback moves journal state: one-shot
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    return -1;
  }
  return 0;
}

// dims: [T, Tp, A, Ap, L, Lp, n_dom_blocks, max_arena_len, CTp,
//        use_members, any_ovf, max_group]
void amtpu_batch_dims(void* bp, int64_t* out) {
  Batch& b = static_cast<BatchHandle*>(bp)->batch;
  out[0] = b.T; out[1] = b.Tp; out[2] = b.A; out[3] = b.Ap;
  out[4] = b.L; out[5] = b.Lp;
  out[6] = static_cast<int64_t>(b.dom_blocks.size());
  out[7] = b.max_arena_len;
  out[8] = b.CTp;
  out[9] = b.use_members ? 1 : 0;
  out[10] = b.any_ovf ? 1 : 0;
  out[11] = b.max_group;
  out[12] = b.n_pre_ovf;
  out[13] = b.host_full ? 1 : 0;
}

// full host path toggle (see Pool::host_full); set once per pool by the
// Python driver from the resolved jax backend before the first batch
void amtpu_pool_set_hostfull(void* pool_ptr, int on) {
  static_cast<Pool*>(pool_ptr)->host_full = on != 0;
}

const int32_t* amtpu_col_memidx(void* bp) { return static_cast<BatchHandle*>(bp)->batch.mem_idx.data(); }
const uint8_t* amtpu_col_hostovf(void* bp) { return static_cast<BatchHandle*>(bp)->batch.host_ovf.data(); }

// escalation member layout (built when member-mode overflow exists):
// dims = [n_groups, n_rows, mem_total]; group_meta packs
// (row_start, n, width) i64 triples; mem is CSR with group-LOCAL values
void amtpu_esc_dims(void* bp, int64_t* out) {
  Batch& b = static_cast<BatchHandle*>(bp)->batch;
  out[0] = static_cast<i64>(b.esc_group_meta.size() / 3);
  out[1] = static_cast<i64>(b.esc_rows.size());
  out[2] = static_cast<i64>(b.esc_mem.size());
}
const int64_t* amtpu_esc_group_meta(void* bp) { return static_cast<BatchHandle*>(bp)->batch.esc_group_meta.data(); }
const int32_t* amtpu_esc_rows(void* bp) { return static_cast<BatchHandle*>(bp)->batch.esc_rows.data(); }
const int64_t* amtpu_esc_mem_off(void* bp) { return static_cast<BatchHandle*>(bp)->batch.esc_mem_off.data(); }
const int32_t* amtpu_esc_mem(void* bp) { return static_cast<BatchHandle*>(bp)->batch.esc_mem.data(); }

// register columns (valid when Tp > 0)
const int32_t* amtpu_col_g(void* bp) { return static_cast<BatchHandle*>(bp)->batch.g_col.data(); }
const int32_t* amtpu_col_t(void* bp) { return static_cast<BatchHandle*>(bp)->batch.t_col.data(); }
const int32_t* amtpu_col_a(void* bp) { return static_cast<BatchHandle*>(bp)->batch.a_col.data(); }
const int32_t* amtpu_col_s(void* bp) { return static_cast<BatchHandle*>(bp)->batch.s_col.data(); }
const uint8_t* amtpu_col_d(void* bp) { return static_cast<BatchHandle*>(bp)->batch.d_col.data(); }
const int32_t* amtpu_col_clocktab(void* bp) { return static_cast<BatchHandle*>(bp)->batch.clock_tab.data(); }
const int32_t* amtpu_col_clockidx(void* bp) { return static_cast<BatchHandle*>(bp)->batch.clock_idx.data(); }
const int32_t* amtpu_col_sort(void* bp) { return static_cast<BatchHandle*>(bp)->batch.sort_idx.data(); }

// arena columns (valid when Lp > 0)
const int32_t* amtpu_col_obj(void* bp) { return static_cast<BatchHandle*>(bp)->batch.obj_col.data(); }
const int32_t* amtpu_col_par(void* bp) { return static_cast<BatchHandle*>(bp)->batch.par_col.data(); }
const int32_t* amtpu_col_ctr(void* bp) { return static_cast<BatchHandle*>(bp)->batch.ctr_col.data(); }
const int32_t* amtpu_col_act(void* bp) { return static_cast<BatchHandle*>(bp)->batch.act_col.data(); }
const uint8_t* amtpu_col_val(void* bp) { return static_cast<BatchHandle*>(bp)->batch.val_col.data(); }
const int32_t* amtpu_col_linsort(void* bp) {
  Batch& b = static_cast<BatchHandle*>(bp)->batch;
  build_lin_sort(b);
  return b.lin_sort.data();
}

// ---- phase 2 --------------------------------------------------------------
// feed register kernel outputs ([Tp] / [Tp, window]) and rank [Lp];
// computes overflow fallbacks + dominance blocks
int amtpu_mid(void* bp, const int32_t* winner, const int32_t* conflicts,
              int window, const int32_t* alive,
              const uint8_t* overflow, const int32_t* rank, int host_dom) {
  BatchHandle& h = *static_cast<BatchHandle*>(bp);
  Batch& b = h.batch;
  try {
    b.window = window;
    b.host_dom = host_dom != 0;
    if (b.host_dom && rank)
      throw Error(0, "amtpu_mid: host_dom callers must pass rank=NULL");
    if (!b.host_dom && !rank && !b.dom_blocks.empty())
      throw Error(0, "amtpu_mid: device-dominance callers must pass rank");
    if (b.Tp > 0) {
      b.k_winner.assign(winner, winner + b.Tp);
      b.k_conflicts.assign(conflicts, conflicts + b.Tp * window);
      b.k_alive.assign(alive, alive + b.Tp);
      b.k_overflow.assign(overflow, overflow + b.Tp);
    }
    // rank is only consumed by the dominance-block mirror fill; callers
    // with no dominance work pass an empty buffer, and host-dominance
    // callers pass NULL (ranks are recomputed host-side there)
    if (b.Lp > 0 && !b.dom_blocks.empty() && rank)
      b.rank.assign(rank, rank + b.Lp);
    double t0 = mono_now();
    mid_phase(*h.pool, b);
    b.tr_mid = mono_now() - t0;
  } catch (const Error& e) {
    g_error = e.what(); g_error_kind = e.kind;
    return -1;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    return -1;
  }
  return 0;
}

// fused-path entry: register outputs + dominance indexes in one call, no
// rank transfer.  Caller must have verified no overflow bit is set.
int amtpu_mid_fused(void* bp, const int32_t* winner, const int32_t* conflicts,
                    int window, const int32_t* alive, const uint8_t* overflow,
                    const int32_t* dom_idx) {
  BatchHandle& h = *static_cast<BatchHandle*>(bp);
  Batch& b = h.batch;
  try {
    double t0 = mono_now();
    b.window = window;
    if (b.Tp > 0) {
      b.k_winner.assign(winner, winner + b.Tp);
      b.k_conflicts.assign(conflicts, conflicts + b.Tp * window);
      b.k_alive.assign(alive, alive + b.Tp);
      b.k_overflow.assign(overflow, overflow + b.Tp);
    }
    i64 off = 0;
    if (dom_idx) {
      for (auto& blk : b.dom_blocks) {
        blk.indexes.assign(dom_idx + off, dom_idx + off + blk.W * blk.Tp);
        off += blk.W * blk.Tp;
      }
    }
    b.tr_mid = mono_now() - t0;
  } catch (const Error& e) {
    g_error = e.what(); g_error_kind = e.kind;
    return -1;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    return -1;
  }
  return 0;
}

// packed-path entry: the register summary stays in its packed form (C++
// unpacks winner/alive lazily per row) and conflicts arrive SPARSE as
// CSR -- conf_rows[i]'s members are conf_vals[conf_offs[i] ..
// conf_offs[i+1]), which covers both the base kernel's window-wide rows
// and escalation-tier rows of ANY width.  host_ovf (nullable) carries
// the RESIDUAL member-overflow flags left after the host's escalation
// merge: rows still flagged take the in-C++ oracle replay
// (fallback.oracle).  Exactly one dominance source applies: dom_idx
// (fused-path device indexes), rank (device-dominance mirror fill, as
// amtpu_mid), or host_dom=1 (amtpu_host_dominance follows).  Caller
// guarantees b.Tp < 2^24.
int amtpu_mid_packed(void* bp, const int32_t* packed, int window,
                     const int32_t* conf_rows, const int32_t* conf_offs,
                     const int32_t* conf_vals, int64_t n_conf,
                     const uint8_t* host_ovf, const int32_t* rank,
                     const int32_t* dom_idx, int host_dom) {
  BatchHandle& h = *static_cast<BatchHandle*>(bp);
  Batch& b = h.batch;
  try {
    double t0 = mono_now();
    b.window = window;
    b.packed_mode = true;
    b.host_dom = host_dom != 0;
    if (b.host_dom && (rank || dom_idx))
      throw Error(0, "amtpu_mid_packed: host_dom callers must pass "
                     "rank=NULL and dom_idx=NULL");
    if (b.Tp > 0) b.k_packed.assign(packed, packed + b.Tp);
    b.sparse_vals.assign(
        conf_vals, conf_vals + (n_conf > 0 ? conf_offs[n_conf] : 0));
    b.sparse_conflicts.reserve(static_cast<size_t>(n_conf) + 1);
    for (int64_t i = 0; i < n_conf; ++i)
      *b.sparse_conflicts.insert(static_cast<u64>(conf_rows[i])).first =
          std::pair<i32, i32>(conf_offs[i],
                              conf_offs[i + 1] - conf_offs[i]);
    if (host_ovf && b.Tp > 0)
      b.k_overflow.assign(host_ovf, host_ovf + b.Tp);
    if (dom_idx) {
      i64 off = 0;
      for (auto& blk : b.dom_blocks) {
        blk.indexes.assign(dom_idx + off, dom_idx + off + blk.W * blk.Tp);
        off += blk.W * blk.Tp;
      }
      oracle_replay(*h.pool, b);   // no-op unless host_ovf flagged rows
    } else {
      if (!b.host_dom && !rank && !b.dom_blocks.empty())
        throw Error(0, "amtpu_mid_packed: device-dominance callers must "
                       "pass rank or dom_idx");
      if (b.Lp > 0 && !b.dom_blocks.empty() && rank)
        b.rank.assign(rank, rank + b.Lp);
      mid_phase(*h.pool, b);
    }
    b.tr_mid = mono_now() - t0;
  } catch (const Error& e) {
    g_error = e.what(); g_error_kind = e.kind;
    return -1;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    return -1;
  }
  return 0;
}

// fused eligibility + single-class dims: [fused_ok, W, Lp, Tp,
// resident_ok, res_clock]
void amtpu_fused_dims(void* bp, int64_t* out) {
  Batch& b = static_cast<BatchHandle*>(bp)->batch;
  out[0] = b.fused_ok ? 1 : 0;
  if (b.dom_blocks.size() == 1) {
    DomBlock& d = b.dom_blocks[0];
    out[1] = d.W; out[2] = d.Lp; out[3] = d.Tp;
  } else {
    out[1] = out[2] = out[3] = 0;
  }
  out[4] = b.resident_ok ? 1 : 0;
  out[5] = b.res_clock ? 1 : 0;
}

// Defaults of the numeric latch-at-first-batch knobs:
// [AMTPU_RESIDENT_MIN, AMTPU_RESCLK_MAX_ACTORS, AMTPU_RESCLK_MAX_ROWS].
// The Python latch-flip guard reads these instead of re-hardcoding them
// (the boolean knobs default ON, atoi != 0 -- mirrored directly).
void amtpu_latch_defaults(int64_t* out) {
  out[0] = DEF_RESIDENT_MIN;
  out[1] = DEF_RESCLK_MAX_ACTORS;
  out[2] = DEF_RESCLK_MAX_ROWS;
}

// Pool-resident clock table state: [n_rows, Ap, gen, disabled].  The
// Python driver keys its device-resident copy on (gen, n_rows, Ap):
// same gen + same Ap + grown n_rows = delta-upload just the appended
// rows; anything else = full re-upload (see ResClock).
void amtpu_resclk_info(void* pool_ptr, int64_t* out) {
  ResClock& rc = static_cast<Pool*>(pool_ptr)->resclk;
  out[0] = rc.n_rows();
  out[1] = rc.Ap;
  out[2] = static_cast<int64_t>(rc.gen);
  out[3] = rc.disabled ? 1 : 0;
}

const int32_t* amtpu_resclk_tab(void* pool_ptr) {
  return static_cast<Pool*>(pool_ptr)->resclk.tab.data();
}

// per-batch resident-clock accounting: [rows served from persisted
// entries, 0/1 whether this batch appended any rows]
void amtpu_resclk_batch_stats(void* bp, int64_t* out) {
  Batch& b = static_cast<BatchHandle*>(bp)->batch;
  out[0] = b.resclk_hits;
  out[1] = b.resclk_appended ? 1 : 0;
}

// Resident-path metadata for dom block `blk`: per object, FOUR i64s
// (batch doc index, obj sid, arena base in the batch layout, arena
// length).  The Python resident driver keys its device cache on
// (doc id, obj sid) and uploads only rows beyond its cached length.
int64_t amtpu_dom_obj_meta(void* bp, int64_t blk, int64_t* out) {
  Batch& b = static_cast<BatchHandle*>(bp)->batch;
  DomBlock& d = b.dom_blocks[blk];
  for (size_t o = 0; o < d.akeys.size(); ++o) {
    u64 ak = d.akeys[o];
    Arena& ar = b.bdocs[ak >> 32]->arenas[static_cast<u32>(ak)];
    out[o * 4 + 0] = static_cast<i64>(ak >> 32);
    out[o * 4 + 1] = static_cast<i64>(static_cast<u32>(ak));
    out[o * 4 + 2] = b.arena_base[ak];
    out[o * 4 + 3] = static_cast<i64>(ar.ctr.size());
  }
  return static_cast<i64>(d.akeys.size());
}

const char* amtpu_batch_doc_id(void* bp, int64_t doc_idx) {
  Batch& b = static_cast<BatchHandle*>(bp)->batch;
  return b.bdoc_ids[doc_idx].c_str();
}

const char* amtpu_intern_str(void* pool_ptr, uint32_t sid) {
  return static_cast<Pool*>(pool_ptr)->intern.str(sid).c_str();
}

// Raw arena column pointers for (doc, obj): ctr/actor_sid/parent i32*,
// visible u8*; returns the arena length (0 when the doc/obj is absent).
// The delta-uploading resident driver reads rows [cached_n, n) directly
// from these -- no batch-layout copies, no O(arena) re-encode.
int64_t amtpu_arena_raw(void* pool_ptr, const char* doc_id,
                        uint32_t obj_sid, const int32_t** ctr,
                        const uint32_t** actor, const int32_t** parent,
                        const uint8_t** visible) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  auto it = pool.docs.find(doc_id);
  if (it == pool.docs.end()) return 0;
  auto ait = it->second.arenas.find(obj_sid);
  if (ait == it->second.arenas.end()) return 0;
  Arena& ar = ait->second;
  *ctr = ar.ctr.data();
  *actor = ar.actor_sid.data();
  *parent = ar.parent.data();
  *visible = ar.visible.data();
  return static_cast<i64>(ar.ctr.size());
}

// fused-path device-source index maps (block 0)
const int32_t* amtpu_fdom_ersrc(void* bp) {
  Batch& b = static_cast<BatchHandle*>(bp)->batch;
  ensure_dom_fills(b, 0);
  return b.dom_blocks[0].er_src.data();
}
const int32_t* amtpu_fdom_oranksrc(void* bp) {
  return static_cast<BatchHandle*>(bp)->batch.dom_blocks[0].orank_src.data();
}
const int32_t* amtpu_fdom_domsrc(void* bp) {
  return static_cast<BatchHandle*>(bp)->batch.dom_blocks[0].dom_src.data();
}

// dominance block accessors
void amtpu_dom_dims(void* bp, int64_t blk, int64_t* out) {
  DomBlock& d = static_cast<BatchHandle*>(bp)->batch.dom_blocks[blk];
  out[0] = d.W; out[1] = d.Lp; out[2] = d.Tp;
}
const float* amtpu_dom_v0(void* bp, int64_t blk) {
  Batch& b = static_cast<BatchHandle*>(bp)->batch;
  ensure_dom_fills(b, static_cast<size_t>(blk));
  return b.dom_blocks[blk].v0.data();
}
const int32_t* amtpu_dom_er(void* bp, int64_t blk) { return static_cast<BatchHandle*>(bp)->batch.dom_blocks[blk].er.data(); }
const int32_t* amtpu_dom_oe(void* bp, int64_t blk) { return static_cast<BatchHandle*>(bp)->batch.dom_blocks[blk].oe.data(); }
const int32_t* amtpu_dom_orank(void* bp, int64_t blk) { return static_cast<BatchHandle*>(bp)->batch.dom_blocks[blk].orank.data(); }
const int32_t* amtpu_dom_od(void* bp, int64_t blk) { return static_cast<BatchHandle*>(bp)->batch.dom_blocks[blk].od.data(); }
const uint8_t* amtpu_dom_ov(void* bp, int64_t blk) { return static_cast<BatchHandle*>(bp)->batch.dom_blocks[blk].ov.data(); }
void amtpu_dom_set_indexes(void* bp, int64_t blk, const int32_t* idx) {
  DomBlock& d = static_cast<BatchHandle*>(bp)->batch.dom_blocks[blk];
  d.indexes.assign(idx, idx + d.W * d.Tp);
}

// Host-register mode: no kernel dispatch at all -- emit resolves each
// register incrementally against the live mirror (host_resolve_step).
// Caller gates on: map-only batch (no dominance blocks) with most
// register rows pre-flagged host_ovf (the driver's _host_reg_on).
int amtpu_mid_hostreg(void* bp) {
  BatchHandle& h = *static_cast<BatchHandle*>(bp);
  Batch& b = h.batch;
  try {
    if (!b.dom_blocks.empty())
      throw Error(0, "hostreg mode requires a batch with no list work");
    b.host_reg_mode = true;
  } catch (const Error& e) {
    g_error = e.what(); g_error_kind = e.kind;
    return -1;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    return -1;
  }
  return 0;
}

// Fenwick-sweep dominance indexes on the host (CPU-backend fast path);
// call after amtpu_mid/amtpu_mid_packed stored the register outputs.
int amtpu_host_dominance(void* bp) {
  BatchHandle& h = *static_cast<BatchHandle*>(bp);
  try {
    double t0 = mono_now();
    host_dominance(h.batch);
    h.batch.tr_mid += mono_now() - t0;
  } catch (const Error& e) {
    g_error = e.what(); g_error_kind = e.kind;
    return -1;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    return -1;
  }
  return 0;
}

// ---- phase 3 --------------------------------------------------------------
int amtpu_finish(void* bp) {
  BatchHandle& h = *static_cast<BatchHandle*>(bp);
  // emit mutates register mirrors / undo stacks / patches -- state the
  // begin journal never recorded -- so rollback stops being legal here
  h.can_rollback = false;
  try {
    double t0 = mono_now();
    collect_indexes(h.batch);
    emit(*h.pool, h.batch);
    h.batch.tr_emit = mono_now() - t0;
  } catch (const Error& e) {
    g_error = e.what(); g_error_kind = e.kind;
    return -1;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    return -1;
  }
  return 0;
}

// phase CPU times:
// [decode, schedule+states+prepass, encode, mid, emit, dom_layout]
void amtpu_batch_trace(void* bp, double* out) {
  Batch& b = static_cast<BatchHandle*>(bp)->batch;
  out[0] = b.tr_decode; out[1] = b.tr_schedule; out[2] = b.tr_encode;
  out[3] = b.tr_mid; out[4] = b.tr_emit; out[5] = b.tr_domlay;
}

// scheduler coverage: [fast-path admits, queue-machinery admits,
// trivial-routed register rows, trivial-routed groups]
void amtpu_sched_counts(void* bp, int64_t* out) {
  Batch& b = static_cast<BatchHandle*>(bp)->batch;
  out[0] = b.n_sched_fast; out[1] = b.n_sched_queued;
  out[2] = b.n_triv_rows; out[3] = b.n_triv_groups;
}

const uint8_t* amtpu_result(void* bp, int64_t* len) {
  Batch& b = static_cast<BatchHandle*>(bp)->batch;
  *len = static_cast<int64_t>(b.result.size());
  return b.result.data();
}

// ---- queries --------------------------------------------------------------

// Read-only lookup: unknown doc ids must NOT materialize pool state (a
// typo'd id in a query would otherwise create a permanent phantom doc --
// and, in ShardedNativePool, possibly on the wrong shard).  Queries fall
// back to this empty state instead.
static DocState g_empty_doc;

static DocState& find_doc(Pool& pool, const char* doc_id) {
  auto it = pool.docs.find(doc_id);
  return it == pool.docs.end() ? g_empty_doc : it->second;
}

// whole-doc materialization patch; returns malloc'd buffer (caller frees
// via amtpu_buf_free)
uint8_t* amtpu_get_patch(void* pool_ptr, const char* doc_id, int64_t* len) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  try {
    DocState& st = find_doc(pool, doc_id);
    Writer diffs;
    size_t count = 0;
    std::vector<u8> seen;
    materialize(pool, st, pool.root_sid, diffs, count, seen);
    Writer out;
    out.map(5);
    out.str("clock"); write_clock(out, pool, st.clock);
    out.str("deps"); write_clock(out, pool, st.deps);
    out.str("canUndo"); out.boolean(st.undo_pos > 0);
    out.str("canRedo"); out.boolean(!st.redo_stack.empty());
    out.str("diffs");
    out.array(count);
    out.raw(diffs.buf);
    *len = static_cast<int64_t>(out.buf.size());
    uint8_t* res = static_cast<uint8_t*>(std::malloc(out.buf.size()));
    std::memcpy(res, out.buf.data(), out.buf.size());
    return res;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    *len = -1;
    return nullptr;
  }
}

// checkpoint: {"format": "amtpu-doc-v1", "changes": [raw change...]} with
// changes in APPLICATION order -- a batched replay of this array through
// apply_batch reproduces the doc byte-identically (the reference's save
// serializes opSet.history the same way, src/automerge.js:45-52; load
// here is ONE kernel-speed batch instead of a scalar O(history) replay)
uint8_t* amtpu_save(void* pool_ptr, const char* doc_id, int64_t* len) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  try {
    DocState& st = find_doc(pool, doc_id);
    Writer out;
    out.map(2);
    out.str("format"); out.str("amtpu-doc-v1");
    out.str("changes");
    out.array(st.history.size());
    for (auto& [actor, seq] : st.history) {
      const ChangeRec& ch = st.states[actor][seq - 1].change;
      out.raw(ch.raw.data(), ch.raw.size());
    }
    *len = static_cast<int64_t>(out.buf.size());
    uint8_t* res = static_cast<uint8_t*>(std::malloc(out.buf.size()));
    std::memcpy(res, out.buf.data(), out.buf.size());
    return res;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    *len = -1;
    return nullptr;
  }
}

// ---------------------------------------------------------------------------
// settled-history GC + cold-doc eviction (ISSUE 10, docs/STORAGE.md)
// ---------------------------------------------------------------------------

// Frees the raw change bytes of every applied change at or behind the
// causally-settled `frontier` ({actor: seq} msgpack map, clamped to the
// doc's clock) and drops those changes from the application-order
// history log -- amtpu_save then emits only the tail.  The op state
// (StateEntry.all_deps, registers, arenas) is untouched: settled ops
// still resolve conflicts and anchor list insertions; only their
// REPLAY bytes move out (into the caller's columnar snapshot, which is
// byte-lossless, so straggler backfill merges them back in Python).
// Returns bytes freed (0 if the doc is unknown), -1 on error.
// Raw refs share per-payload slabs, so the HEAP gives bytes back once
// every change of a slab settles -- per-batch payloads settle together
// in practice, and this return value tracks the retained-span sum that
// amtpu_history_bytes reports either way.
int64_t amtpu_truncate_history(void* pool_ptr, const char* doc_id,
                               const uint8_t* frontier, int64_t flen) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  try {
    auto it = pool.docs.find(doc_id);
    if (it == pool.docs.end()) return 0;
    DocState& st = it->second;
    Reader r(frontier, static_cast<size_t>(flen));
    Clock f;
    size_t n = r.read_map();
    for (size_t i = 0; i < n; ++i) {
      u32 a = pool.intern.id_of(r.read_str());
      i64 s = r.read_int();
      i64 applied = clock_get(st.clock, a);
      if (s > applied) s = applied;   // clamp: never truncate past what
      if (s > 0)                      // the doc has actually applied
        clock_set_max(f, a, static_cast<u32>(s));
    }
    int64_t freed = 0;
    for (auto& [a, s] : f) {
      auto sit = st.states.find(a);
      if (sit == st.states.end()) continue;
      auto& entries = sit->second;
      size_t upto = std::min<size_t>(s, entries.size());
      for (size_t i = 0; i < upto; ++i) {
        RawRef& raw = entries[i].change.raw;
        freed += static_cast<int64_t>(raw.size());
        raw.slab.reset();
        raw.off = raw.len = 0;
      }
    }
    std::vector<std::pair<u32, u32>> keep;
    keep.reserve(st.history.size());
    for (auto& [a, s] : st.history)
      if (s > clock_get(f, a)) keep.emplace_back(a, s);
    st.history.swap(keep);
    st.acct_raw_bytes -= freed;   // per-doc accounting (amtpu_doc_stats)
    return freed;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    return -1;
  }
}

// The transitively-closed {actor: from_seq} clock amtpu_get_missing_
// changes serves FROM for `have_deps` -- exposed so the Python merge
// path (snapshot + tail, docs/STORAGE.md) applies the SAME closure the
// C++ walk would, instead of re-deriving it from decoded history.
uint8_t* amtpu_get_missing_clock(void* pool_ptr, const char* doc_id,
                                 const uint8_t* have, int64_t have_len,
                                 int64_t* len) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  try {
    DocState& st = find_doc(pool, doc_id);
    Reader r(have, static_cast<size_t>(have_len));
    Clock have_deps;
    size_t n = r.read_map();
    for (size_t i = 0; i < n; ++i) {
      u32 a = pool.intern.id_of(r.read_str());
      u32 s = static_cast<u32>(r.read_int());
      have_deps.emplace_back(a, s);
    }
    Clock all_deps;
    for (auto& [da, ds] : have_deps) {
      if (ds == 0) continue;
      read_all_deps(st, da, ds, all_deps);
      clock_set_max(all_deps, da, ds);
    }
    // canonical actor-string order: the closure's pair order would
    // otherwise depend on whether entries were clock-folded (folded
    // rows iterate in doc-rank order, sparse vectors in insertion
    // order) -- sorting makes the bytes identical across fold arms
    std::sort(all_deps.begin(), all_deps.end(),
              [&](const std::pair<u32, u32>& x,
                  const std::pair<u32, u32>& y) {
                return pool.intern.str(x.first) < pool.intern.str(y.first);
              });
    Writer out;
    write_clock(out, pool, all_deps);
    *len = static_cast<int64_t>(out.buf.size());
    uint8_t* res = static_cast<uint8_t*>(std::malloc(out.buf.size()));
    std::memcpy(res, out.buf.data(), out.buf.size());
    return res;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    *len = -1;
    return nullptr;
  }
}

// Retained raw-change bytes (applied history + causal queue) of one doc
// (or, with doc_id = "", the whole pool) -- the arena measure the
// storage gate compares across the GC / no-GC arms.
int64_t amtpu_history_bytes(void* pool_ptr, const char* doc_id) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  try {
    auto sum_doc = [](const DocState& st) {
      int64_t b = 0;
      for (auto& [a, entries] : st.states)
        for (auto& e : entries) b += static_cast<int64_t>(e.change.raw.size());
      for (auto& ch : st.queue) b += static_cast<int64_t>(ch.raw.size());
      return b;
    };
    if (doc_id == nullptr || doc_id[0] == '\0') {
      int64_t total = 0;
      for (auto& [id, st] : pool.docs) total += sum_doc(st);
      return total;
    }
    auto it = pool.docs.find(doc_id);
    return it == pool.docs.end() ? 0 : sum_doc(it->second);
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    return -1;
  }
}

// ---------------------------------------------------------------------------
// native columnar codec + arena-direct load + op-state folding (ISSUE 14)
// ---------------------------------------------------------------------------

// Columnar-encodes a msgpack array of BIN-wrapped raw changes into one
// AMTC blob.  Bin framing (not a spliced join array) because element
// boundaries must be explicit: a residual raw with trailing bytes is
// not re-delimitable by msgpack skip.  stats (nullable) receives
// [n_changes, n_residual] for the Python wrapper's telemetry.  Returns
// a malloc'd buffer (amtpu_buf_free) or NULL on error -- the Python
// dispatch falls back to the pure-Python codec then.
uint8_t* amtpu_columnar_encode(const uint8_t* data, int64_t len,
                               int64_t* out_len, int64_t* stats) {
  try {
    Reader r(data, static_cast<size_t>(len));
    size_t n = r.read_array();
    colnr::ColEncoder enc;
    for (size_t i = 0; i < n; ++i) {
      auto span = r.read_bin_view();
      enc.add(span.first, span.second);
    }
    std::vector<u8> blob = enc.dump();
    if (stats) {
      stats[0] = enc.n_changes;
      stats[1] = enc.n_residual;
    }
    *out_len = static_cast<int64_t>(blob.size());
    uint8_t* res = static_cast<uint8_t*>(std::malloc(blob.size()));
    std::memcpy(res, blob.data(), blob.size());
    return res;
  } catch (const Error& e) {
    g_error = e.what(); g_error_kind = e.kind;
    *out_len = -1;
    return nullptr;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    *out_len = -1;
    return nullptr;
  }
}

// Decodes an AMTC blob back to a msgpack array of BIN-wrapped raw
// changes, byte-identical to the encode input (residuals verbatim;
// columnar changes rebuilt through the canonical writer; bin framing
// for the same boundary reason as encode).  Corruption raises kind 1
// (RangeError) -- the Python wrapper maps it to decode_columnar's
// ValueError contract.
uint8_t* amtpu_columnar_decode(const uint8_t* blob, int64_t len,
                               int64_t* out_len) {
  try {
    colnr::ColDecoder dec(blob, static_cast<size_t>(len));
    std::vector<u8> slab;
    std::vector<std::pair<size_t, size_t>> spans;
    dec.decode_all(slab, spans);
    Writer out;
    out.buf.reserve(slab.size() + spans.size() * 5 + 8);
    out.array(spans.size());
    for (auto& [off, n] : spans) out.bin(slab.data() + off, n);
    *out_len = static_cast<int64_t>(out.buf.size());
    uint8_t* res = static_cast<uint8_t*>(std::malloc(out.buf.size()));
    std::memcpy(res, out.buf.data(), out.buf.size());
    return res;
  } catch (const Error& e) {
    g_error = e.what(); g_error_kind = e.kind;
    *out_len = -1;
    return nullptr;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    *out_len = -1;
    return nullptr;
  }
}

// Arena-direct checkpoint load: payload is msgpack
// {doc_key: [part(bin), ...]} where each part is either an AMTC
// columnar blob (a v2 snapshot chunk or tail) or a raw msgpack array
// of changes (the v1 container remainder).  Columns materialize
// straight into ChangeRec arena state -- canonical raw bytes rebuild
// into one slab per blob, then the standard decode_change /
// begin_phases pipeline runs with the batch pinned HOST-FULL (no
// kernel dispatch; host/kernel byte parity is pinned by the
// differential suites, so the restored doc is byte-identical in every
// exec mode).  Returns a BatchHandle for the standard phase-b driver.
void* amtpu_begin_columnar(void* pool_ptr, const uint8_t* data,
                           int64_t len) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  auto h = std::make_unique<BatchHandle>();
  h->pool = &pool;
  h->batch.pool = &pool;
  try {
    double t0 = mono_now();
    if (len < 0 || len >= (1LL << 32))
      throw Error(0, "payload too large (raw spans use 32-bit offsets; "
                     "split batches below 4 GiB)");
    auto slab = std::make_shared<std::vector<u8>>(data, data + len);
    Reader r(slab->data(), slab->size());
    size_t n_docs = r.read_map();
    Batch& b = h->batch;
    // arena-direct decode always resolves host-side: begin skips the
    // kernel rows, emit runs host_resolve_step + the in-emit Fenwick.
    // Checkpoint restores discard patches, so emit mutates state only
    b.host_full = true;
    b.no_patch = true;
    std::vector<std::vector<ChangeRec>> incoming;
    incoming.reserve(n_docs);
    DecodeCache dc;
    for (size_t i = 0; i < n_docs; ++i) {
      std::string doc_id = r.read_str();
      size_t n_parts = r.read_array();
      std::vector<ChangeRec> chs;
      for (size_t pi = 0; pi < n_parts; ++pi) {
        auto bv = r.read_bin_view();
        if (colnr::is_columnar_blob(bv.first, bv.second)) {
          auto dslab = std::make_shared<std::vector<u8>>();
          pool.intern.reserve(
              pool.intern.n + std::min<size_t>(bv.second / 12,
                                               size_t(4) << 20));
          pool.vals.reserve(
              pool.vals.n + std::min<size_t>(bv.second / 24,
                                             size_t(2) << 20));
          // FUSED decode: canonical raw bytes + ChangeRec in one
          // column walk (no second msgpack parse)
          colnr::ColDecoder dec(bv.first, bv.second);
          dec.decode_changes(pool, dslab, chs);
        } else {
          Reader pr(bv.first, bv.second);
          size_t n_changes = pr.read_array();
          chs.reserve(chs.size() +
                      std::min(n_changes,
                               static_cast<size_t>(bv.second) / 8));
          for (size_t j = 0; j < n_changes; ++j)
            chs.push_back(decode_change(pr, pool, slab, nullptr, &dc));
        }
      }
      b.bdocs.push_back(&pool.doc(doc_id));
      b.bdoc_ids.push_back(std::move(doc_id));
      incoming.push_back(std::move(chs));
    }
    b.tr_decode = mono_now() - t0;
    begin_phases(pool, b, incoming, h->journal);
    h->can_rollback = true;
    // unpin the payload slab when most of it was NOT retained (v1
    // parts re-loaded into live docs dedup to nothing): same re-adopt
    // as amtpu_begin.  Per-blob decode slabs are already exactly sized
    // and die with their last ChangeRec.
    size_t kept = 0;
    for (auto& ac : b.applied)
      if (ac.stored->raw.slab == slab) kept += ac.stored->raw.len;
    for (auto* d : b.bdocs)
      for (auto& qc : d->queue)
        if (qc.raw.slab == slab) kept += qc.raw.len;
    if (kept * 4 < slab->size()) {
      auto copy_out = [&](ChangeRec& c) {
        if (c.raw.slab != slab) return;
        std::vector<u8> buf(c.raw.data(), c.raw.data() + c.raw.len);
        c.raw.adopt(std::move(buf));
      };
      for (auto& ac : b.applied) copy_out(*ac.stored);
      for (auto* d : b.bdocs)
        for (auto& qc : d->queue) copy_out(qc);
    }
  } catch (const Error& e) {
    g_error = e.what(); g_error_kind = e.kind;
    return nullptr;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    return nullptr;
  }
  return h.release();
}

// Op-state folding (ISSUE 14 tentpole): settled changes at or behind
// `frontier` free their op records / deps / message -- the live
// register+arena state already holds their final values, the columnar
// snapshot holds their replay bytes, and all_deps stays for straggler
// closure walks.  Call AFTER amtpu_truncate_history with the same
// frontier (the Python compact path does); duplicate re-sends of
// folded seqs skip byte validation (validate_duplicates).  Returns op
// records freed (0 if the doc is unknown), -1 on error.
int64_t amtpu_fold_settled(void* pool_ptr, const char* doc_id,
                           const uint8_t* frontier, int64_t flen) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  try {
    auto it = pool.docs.find(doc_id);
    if (it == pool.docs.end()) return 0;
    DocState& st = it->second;
    Reader r(frontier, static_cast<size_t>(flen));
    Clock f;
    size_t n = r.read_map();
    for (size_t i = 0; i < n; ++i) {
      u32 a = pool.intern.id_of(r.read_str());
      i64 s = r.read_int();
      i64 applied = clock_get(st.clock, a);
      if (s > applied) s = applied;   // clamp, like truncate_history
      if (s > 0)
        clock_set_max(f, a, static_cast<u32>(s));
    }
    int64_t freed = 0;
    for (auto& [a, s] : f) {
      auto sit = st.states.find(a);
      if (sit == st.states.end()) continue;
      auto& entries = sit->second;
      size_t upto = std::min<size_t>(s, entries.size());
      for (size_t i = 0; i < upto; ++i) {
        StateEntry& e = entries[i];
        if (e.folded) continue;
        freed += static_cast<int64_t>(e.change.ops.size());
        std::vector<OpRec>().swap(e.change.ops);
        std::vector<u8>().swap(e.change.message);
        e.change.has_message = false;
        Clock().swap(e.change.deps);
        e.folded = true;
      }
    }
    st.acct_ops -= freed;          // per-doc accounting (amtpu_doc_stats)
    st.acct_folded_ops += freed;
    return freed;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    return -1;
  }
}

// Clock-vector folding (ISSUE 17 tentpole b): settled changes at or
// behind `frontier` move their sparse all_deps vectors into the doc's
// densified FoldClocks table (or a zero-byte sentinel for empty /
// linear-history shapes) and free the vectors -- the last per-history
// memory term goes O(live frontier) instead of O(changes).  Causal
// queries (rec_concurrent, straggler closure walks, clock-row densify)
// keep answering through the folded rows via for_each_dep /
// clock_get_deps; amtpu_get_missing_clock emits canonical actor order
// so its bytes cannot drift across fold arms.  Call on the same
// compact cadence as amtpu_fold_settled (any frontier clamped to the
// doc's clock is safe; folding is idempotent per entry).  Docs whose
// folded actor population would exceed `max_actors` stop folding
// NON-trivial entries (row width is the doc's actor count -- an
// unbounded population would make every row pay for every actor);
// sentinel folds still apply.  Returns sparse pairs freed (0 if the
// doc is unknown), -1 on error.
int64_t amtpu_fold_clocks(void* pool_ptr, const char* doc_id,
                          const uint8_t* frontier, int64_t flen,
                          int64_t max_actors) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  try {
    auto it = pool.docs.find(doc_id);
    if (it == pool.docs.end()) return 0;
    DocState& st = it->second;
    FoldClocks& fc = st.foldclk;
    Reader r(frontier, static_cast<size_t>(flen));
    Clock f;
    size_t n = r.read_map();
    for (size_t i = 0; i < n; ++i) {
      u32 a = pool.intern.id_of(r.read_str());
      i64 s = r.read_int();
      i64 applied = clock_get(st.clock, a);
      if (s > applied) s = applied;   // clamp, like fold_settled
      if (s > 0)
        clock_set_max(f, a, static_cast<u32>(s));
    }
    // doc-local rank, registering on first sight; re-widens every
    // existing row in place when A outgrows the padded width Ap
    auto rank_or_add = [&](u32 sid) {
      i32 rk = fc.rank(sid);
      if (rk >= 0) return rk;
      rk = static_cast<i32>(fc.actor_order.size());
      fc.actor_order.push_back(sid);
      fc.A = static_cast<i64>(fc.actor_order.size());
      if (fc.A > fc.Ap) {
        i64 new_ap = bucket(fc.A, 4);
        i64 rows = fc.Ap ? static_cast<i64>(fc.tab.size()) / fc.Ap : 0;
        std::vector<u32> wide(static_cast<size_t>(rows * new_ap), 0);
        for (i64 row = 0; row < rows; ++row)
          std::memcpy(wide.data() + row * new_ap,
                      fc.tab.data() + row * fc.Ap,
                      static_cast<size_t>(fc.Ap) * sizeof(u32));
        fc.tab.swap(wide);
        fc.Ap = new_ap;
      }
      return rk;
    };
    int64_t freed = 0;
    for (auto& [a, s] : f) {
      auto sit = st.states.find(a);
      if (sit == st.states.end()) continue;
      auto& entries = sit->second;
      size_t upto = std::min<size_t>(s, entries.size());
      for (size_t i = 0; i < upto; ++i) {
        StateEntry& e = entries[i];
        if (e.fold_row != FOLDROW_NONE) continue;   // already folded
        const u32 seq = static_cast<u32>(i + 1);
        if (e.all_deps.empty()) {
          e.fold_row = FOLDROW_EMPTY;
        } else if (e.all_deps.size() == 1 && e.all_deps[0].first == a &&
                   e.all_deps[0].second == seq - 1) {
          e.fold_row = FOLDROW_TRIVIAL;
        } else {
          // population cap: leave the sparse vector in place (still
          // readable through the FOLDROW_NONE path); sentinels above
          // keep applying either way
          i64 need = fc.A;
          for (auto& [da, ds] : e.all_deps)
            if (fc.rank(da) < 0) ++need;
          if (need > max_actors) continue;
          for (auto& [da, ds] : e.all_deps) rank_or_add(da);
          u32 row = static_cast<u32>(fc.n_rows());
          if (row > FOLDROW_MAX) continue;   // sentinel space exhausted
          fc.tab.resize(fc.tab.size() + fc.Ap, 0);
          u32* dst = fc.tab.data() + fc.tab.size() - fc.Ap;
          for (auto& [da, ds] : e.all_deps) dst[fc.rank(da)] = ds;
          e.fold_row = row;
        }
        freed += static_cast<int64_t>(e.all_deps.size());
        Clock().swap(e.all_deps);
      }
    }
    st.acct_clock_pairs -= freed;  // per-doc accounting (amtpu_doc_stats)
    return freed;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    return -1;
  }
}

// Retained sparse all_deps pairs of one doc (or, with doc_id = "", the
// whole pool), walked FRESH -- the reconciliation oracle the clock-fold
// tests pin against the incrementally-maintained acct_clock_pairs /
// amtpu_doc_stats column.
int64_t amtpu_clock_pairs(void* pool_ptr, const char* doc_id) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  try {
    auto sum_doc = [](const DocState& st) {
      int64_t n = 0;
      for (auto& [a, entries] : st.states)
        for (auto& e : entries)
          n += static_cast<int64_t>(e.all_deps.size());
      return n;
    };
    if (doc_id == nullptr || doc_id[0] == '\0') {
      int64_t total = 0;
      for (auto& [id, st] : pool.docs) total += sum_doc(st);
      return total;
    }
    auto it = pool.docs.find(doc_id);
    return it == pool.docs.end() ? 0 : sum_doc(it->second);
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    return -1;
  }
}

// Retained op records (applied history + causal queue) of one doc (or,
// with doc_id = "", the whole pool) -- the arena-growth measure the
// op-state folding lane gates on (flat, not merely sub-linear, under
// settled-overwrite churn).
int64_t amtpu_op_count(void* pool_ptr, const char* doc_id) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  try {
    auto sum_doc = [](const DocState& st) {
      int64_t n = 0;
      for (auto& [a, entries] : st.states)
        for (auto& e : entries)
          n += static_cast<int64_t>(e.change.ops.size());
      for (auto& ch : st.queue)
        n += static_cast<int64_t>(ch.ops.size());
      return n;
    };
    if (doc_id == nullptr || doc_id[0] == '\0') {
      int64_t total = 0;
      for (auto& [id, st] : pool.docs) total += sum_doc(st);
      return total;
    }
    auto it = pool.docs.find(doc_id);
    return it == pool.docs.end() ? 0 : sum_doc(it->second);
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    return -1;
  }
}

// ---------------------------------------------------------------------------
// per-doc resource accounting (ISSUE 15, docs/OBSERVABILITY.md capacity
// section): one C call returns the whole pool's per-doc cost rows.
// ---------------------------------------------------------------------------

// Doc ids of the pool in doc_order (first-seen) order as a msgpack
// array of strings -- the row order of amtpu_doc_stats.  malloc'd
// buffer (amtpu_buf_free), NULL on error.
uint8_t* amtpu_doc_ids(void* pool_ptr, int64_t* len) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  try {
    Writer out;
    out.array(pool.doc_order.size());
    for (auto& id : pool.doc_order) out.str(id);
    *len = static_cast<int64_t>(out.buf.size());
    uint8_t* res = static_cast<uint8_t*>(std::malloc(out.buf.size()));
    std::memcpy(res, out.buf.data(), out.buf.size());
    return res;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    *len = -1;
    return nullptr;
  }
}

// Per-doc resource stats, batch-wise: fills `out` with one 8-column
// int64 row per doc in doc_order order (same order as amtpu_doc_ids):
//   [0] hist_bytes   retained raw change bytes (states + causal queue)
//   [1] ops          retained op records (states + causal queue)
//   [2] folded_ops   op records freed by amtpu_fold_settled
//   [3] changes      retained change records (state entries + queue)
//   [4] queued       causally-parked queue length
//   [5] resclk_rows  pool-resident clock rows keyed by this doc
//   [6] clk_pairs    retained sparse all_deps pairs (what
//                    amtpu_fold_clocks has NOT yet folded; queued
//                    changes carry no all_deps, so states-only)
//   [7] foldclk_bytes  the doc's densified FoldClocks table bytes
//                    (rows + actor order -- the fold's residual cost)
// `cap` is the out capacity in int64s; rows past it are not written.
// Returns the number of ROWS written (never more than cap/8), -1 on
// error.  Column totals across all docs reconcile EXACTLY with
// amtpu_history_bytes(pool, "") / amtpu_op_count(pool, "") /
// amtpu_clock_pairs(pool, "") -- the states contribution comes from
// the incrementally-maintained per-doc counters and the queue is
// walked fresh here, so the capacity tests can pin bit-equality.
// resclk rows are attributed by matching the table's DocState-pointer
// keys against LIVE docs only: amtpu_drop_doc invalidates the table,
// so a reused DocState address can never inherit a dropped doc's rows
// (the drop/re-add test pins it).
int64_t amtpu_doc_stats(void* pool_ptr, int64_t* out, int64_t cap) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  try {
    std::unordered_map<const void*, size_t> doc_idx;
    doc_idx.reserve(pool.docs.size() * 2);
    size_t n_rows = std::min<size_t>(pool.doc_order.size(),
                                     cap > 0 ? cap / 8 : 0);
    for (size_t i = 0; i < n_rows; ++i) {
      auto it = pool.docs.find(pool.doc_order[i]);
      if (it == pool.docs.end()) {   // doc_order never dangles, but a
        std::memset(out + i * 8, 0, 8 * sizeof(int64_t));  // zero row
        continue;                    // is safer than UB if it ever did
      }
      DocState& st = it->second;
      doc_idx[static_cast<const void*>(&st)] = i;
      i64 qb = 0, qops = 0;
      for (auto& ch : st.queue) {
        qb += static_cast<i64>(ch.raw.size());
        qops += static_cast<i64>(ch.ops.size());
      }
      i64 n_entries = 0;
      for (auto& [a, entries] : st.states)
        n_entries += static_cast<i64>(entries.size());
      out[i * 8 + 0] = st.acct_raw_bytes + qb;
      out[i * 8 + 1] = st.acct_ops + qops;
      out[i * 8 + 2] = st.acct_folded_ops;
      out[i * 8 + 3] = n_entries + static_cast<i64>(st.queue.size());
      out[i * 8 + 4] = static_cast<i64>(st.queue.size());
      out[i * 8 + 5] = 0;
      out[i * 8 + 6] = st.acct_clock_pairs;
      out[i * 8 + 7] = st.foldclk.bytes();
    }
    for (auto& [key, _row] : pool.resclk.rows) {
      auto dit = doc_idx.find(key.doc);
      if (dit != doc_idx.end()) ++out[dit->second * 8 + 5];
    }
    return static_cast<int64_t>(n_rows);
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    return -1;
  }
}

// Cold-doc eviction: removes one doc's entire state from the pool (the
// caller has checkpointed it -- save() -> disk; reload-on-touch is
// load()).  The pool-resident clock table keys rows by DocState
// POINTER, and a future doc could reuse the freed address, so the
// cache invalidates (one full re-upload; eviction is the cold path by
// definition).  Interned strings stay -- the interner is append-only
// by design.  Returns 1 if the doc existed, 0 otherwise, -1 on error.
int64_t amtpu_drop_doc(void* pool_ptr, const char* doc_id) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  try {
    auto it = pool.docs.find(doc_id);
    if (it == pool.docs.end()) return 0;
    pool.docs.erase(it);
    for (auto dit = pool.doc_order.begin();
         dit != pool.doc_order.end(); ++dit)
      if (*dit == doc_id) { pool.doc_order.erase(dit); break; }
    pool.resclk.invalidate();
    return 1;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    return -1;
  }
}

// clock + deps only (no materialization): the cheap per-round query that
// batched replica catch-up gossips (reference advertises clocks the same
// way, connection.js:51-56, without shipping document state)
uint8_t* amtpu_get_clock(void* pool_ptr, const char* doc_id, int64_t* len) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  try {
    DocState& st = find_doc(pool, doc_id);
    Writer out;
    out.map(2);
    out.str("clock"); write_clock(out, pool, st.clock);
    out.str("deps"); write_clock(out, pool, st.deps);
    *len = static_cast<int64_t>(out.buf.size());
    uint8_t* res = static_cast<uint8_t*>(std::malloc(out.buf.size()));
    std::memcpy(res, out.buf.data(), out.buf.size());
    return res;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    *len = -1;
    return nullptr;
  }
}

// missing deps: msgpack map {actor: seq}
uint8_t* amtpu_get_missing_deps(void* pool_ptr, const char* doc_id,
                                int64_t* len) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  try {
    DocState& st = find_doc(pool, doc_id);
    Clock missing;
    for (auto& ch : st.queue) {
      Clock deps = ch.deps;
      bool found = false;
      for (auto& p : deps)
        if (p.first == ch.actor) { p.second = ch.seq - 1; found = true; }
      if (!found) deps.emplace_back(ch.actor, ch.seq - 1);
      for (auto& [da, ds] : deps)
        if (clock_get(st.clock, da) < ds) clock_set_max(missing, da, ds);
    }
    Writer out;
    write_clock(out, pool, missing);
    *len = static_cast<int64_t>(out.buf.size());
    uint8_t* res = static_cast<uint8_t*>(std::malloc(out.buf.size()));
    std::memcpy(res, out.buf.data(), out.buf.size());
    return res;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    *len = -1;
    return nullptr;
  }
}

// missing changes given have_deps msgpack map {actor: seq}:
// returns msgpack array of raw changes
uint8_t* amtpu_get_missing_changes(void* pool_ptr, const char* doc_id,
                                   const uint8_t* have, int64_t have_len,
                                   int64_t* len) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  try {
    DocState& st = find_doc(pool, doc_id);
    Reader r(have, static_cast<size_t>(have_len));
    Clock have_deps;
    size_t n = r.read_map();
    for (size_t i = 0; i < n; ++i) {
      u32 a = pool.intern.id_of(r.read_str());
      u32 s = static_cast<u32>(r.read_int());
      have_deps.emplace_back(a, s);
    }
    Clock all_deps;
    for (auto& [da, ds] : have_deps) {
      if (ds == 0) continue;
      read_all_deps(st, da, ds, all_deps);
      clock_set_max(all_deps, da, ds);
    }
    Writer out;
    size_t count = 0;
    for (u32 actor : st.state_actor_order) {
      auto& entries = st.states[actor];
      u32 from = clock_get(all_deps, actor);
      for (size_t i = from; i < entries.size(); ++i)
        if (entries[i].change.raw.size()) count++;
    }
    out.array(count);
    for (u32 actor : st.state_actor_order) {
      auto& entries = st.states[actor];
      u32 from = clock_get(all_deps, actor);
      // GC-truncated entries (amtpu_truncate_history freed their raw
      // bytes) are SKIPPED, consistently with the count above: the
      // Python wrapper merges them back from the doc's columnar
      // snapshot when the requester is behind the settled frontier
      for (size_t i = from; i < entries.size(); ++i)
        if (entries[i].change.raw.size())
          out.raw(entries[i].change.raw.data(),
                  entries[i].change.raw.size());
    }
    *len = static_cast<int64_t>(out.buf.size());
    uint8_t* res = static_cast<uint8_t*>(std::malloc(out.buf.size()));
    std::memcpy(res, out.buf.data(), out.buf.size());
    return res;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    *len = -1;
    return nullptr;
  }
}

void amtpu_buf_free(uint8_t* p) { std::free(p); }

// all changes authored by one actor after a given seq: msgpack array of
// raw changes (reference: op_set.js:347-357)
uint8_t* amtpu_get_changes_for_actor(void* pool_ptr, const char* doc_id,
                                     const char* actor, int64_t after_seq,
                                     int64_t* len) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  try {
    DocState& st = find_doc(pool, doc_id);
    u32 actor_sid = pool.intern.id_of(actor);
    Writer out;
    auto it = st.states.find(actor_sid);
    size_t from = static_cast<size_t>(std::max<int64_t>(after_seq, 0));
    if (it == st.states.end() || from >= it->second.size()) {
      out.array(0);
    } else {
      // GC-truncated entries are skipped (see amtpu_get_missing_changes)
      size_t count = 0;
      for (size_t i = from; i < it->second.size(); ++i)
        if (it->second[i].change.raw.size()) count++;
      out.array(count);
      for (size_t i = from; i < it->second.size(); ++i)
        if (it->second[i].change.raw.size())
          out.raw(it->second[i].change.raw.data(),
                  it->second[i].change.raw.size());
    }
    *len = static_cast<int64_t>(out.buf.size());
    uint8_t* res = static_cast<uint8_t*>(std::malloc(out.buf.size()));
    std::memcpy(res, out.buf.data(), out.buf.size());
    return res;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    *len = -1;
    return nullptr;
  }
}

// current register (field ops) of one (doc, obj, key): msgpack array of
// {action, obj, key, value?, datatype?, actor, seq} records, winner first.
// This is the Backend.getFieldOps query the undo/redo machinery needs
// (reference capture: op_set.js:193-200; redo build: backend/index.js:264-278)
uint8_t* amtpu_get_register(void* pool_ptr, const char* doc_id,
                            const char* obj, const char* key,
                            int64_t* len) {
  Pool& pool = *static_cast<Pool*>(pool_ptr);
  try {
    DocState& st = find_doc(pool, doc_id);
    u32 obj_sid = pool.intern.id_of(obj);
    u32 key_sid = pool.intern.id_of(key);
    Writer out;
    const Register* rit =
        st.registers.find(DocState::rkey(obj_sid, key_sid));
    if (!rit) {
      out.array(0);
    } else {
      out.array(rit->size());
      for (const OpRec& o : *rit) {
        size_t n = 5 + (o.value_rid != NONE ? 1 : 0) +
                   (o.datatype != NONE ? 1 : 0);
        out.map(n);
        out.str("action"); out.str(action_name(o.action));
        out.str("obj"); out.str(pool.intern.str(o.obj));
        out.str("key"); out.str(pool.intern.str(o.key));
        if (o.value_rid != NONE) {
          out.str("value"); out.raw(val_bytes(pool, o));
        }
        if (o.datatype != NONE) {
          out.str("datatype"); out.str(pool.intern.str(o.datatype));
        }
        out.str("actor"); out.str(pool.intern.str(o.actor));
        out.str("seq"); out.integer(o.seq);
      }
    }
    *len = static_cast<int64_t>(out.buf.size());
    uint8_t* res = static_cast<uint8_t*>(std::malloc(out.buf.size()));
    std::memcpy(res, out.buf.data(), out.buf.size());
    return res;
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    *len = -1;
    return nullptr;
  }
}

// ---- payload sharding -----------------------------------------------------
// Splits a {doc_id: [changes]} payload into n_shards sub-payloads by doc-id
// hash WITHOUT decoding the change bodies (values are copied as raw spans).
// The hash (FNV-1a over the doc-id string, mod n_shards) is mirrored in
// automerge_tpu/native/__init__.py for query routing -- keep in sync.

struct ShardSplit {
  std::vector<std::vector<uint8_t>> bufs;
};

uint32_t amtpu_doc_shard(const char* doc_id, int64_t len, int n_shards) {
  if (n_shards < 1) n_shards = 1;
  uint32_t h = 2166136261u;
  for (int64_t i = 0; i < len; ++i) {
    h ^= static_cast<uint8_t>(doc_id[i]);
    h *= 16777619u;
  }
  return h % static_cast<uint32_t>(n_shards);
}

void* amtpu_shard_split(const uint8_t* data, int64_t len, int n_shards) {
  if (n_shards < 1) {
    g_error = "n_shards must be >= 1"; g_error_kind = 0;
    return nullptr;
  }
  try {
    Reader r(data, static_cast<size_t>(len));
    size_t n_docs = r.read_map();
    std::vector<Writer> writers(n_shards);
    std::vector<size_t> counts(n_shards, 0);
    std::vector<std::vector<std::pair<const uint8_t*, size_t>>> spans(
        n_shards);
    for (size_t i = 0; i < n_docs; ++i) {
      auto kspan = r.raw_value();   // doc id (str)
      Reader kr(kspan.first, kspan.second);
      std::string key = kr.read_str();
      auto vspan = r.raw_value();   // change array
      int s = static_cast<int>(
          amtpu_doc_shard(key.data(), static_cast<int64_t>(key.size()),
                          n_shards));
      spans[s].emplace_back(kspan.first,
                            kspan.second + vspan.second);
      counts[s]++;
    }
    auto out = std::make_unique<ShardSplit>();
    out->bufs.resize(n_shards);
    for (int s = 0; s < n_shards; ++s) {
      Writer w;
      w.map(counts[s]);
      for (auto& sp : spans[s]) w.raw(sp.first, sp.second);
      out->bufs[s] = std::move(w.buf);
    }
    return out.release();
  } catch (const std::exception& e) {
    g_error = e.what(); g_error_kind = 0;
    return nullptr;
  }
}

const uint8_t* amtpu_shard_buf(void* sp, int shard, int64_t* len) {
  ShardSplit& s = *static_cast<ShardSplit*>(sp);
  *len = static_cast<int64_t>(s.bufs[shard].size());
  return s.bufs[shard].data();
}

void amtpu_shard_free(void* sp) { delete static_cast<ShardSplit*>(sp); }

// ---- request splice -------------------------------------------------------
// The gateway keeps an apply_batch frame's change arrays as the frame's
// bytes and splices them into the pool's payload
// (automerge_tpu/utils/request_map.py).  That is exact only where a span
// already is what msgpack-python's packb(unpackb(span)) gives, since the
// pool copies op values verbatim into its patches.  `spans` holds n
// (start, end) pairs of `data`, each one doc's changes array.  ops[i] is
// the number of ops that doc's changes hold when its span is canonical
// (canon_asis: valid utf-8, no float32, shortest headers, unique string
// keys) and every change is a map whose 'ops', when present, is
// an array; else -1, and the caller decodes that doc.  No pool, no
// shared state: any thread may call it.

void amtpu_scan_changes(const uint8_t* data, int64_t len,
                        const int64_t* spans, int64_t n, int64_t* ops) {
  for (int64_t i = 0; i < n; ++i) {
    ops[i] = -1;
    int64_t start = spans[2 * i], end = spans[2 * i + 1];
    if (start < 0 || end < start || end > len) continue;
    const uint8_t* p = data + start;
    int64_t count = 0;
    if (colnr::canon_asis(p, data + end, 0, &count) && p == data + end)
      ops[i] = count;
  }
}

}  // extern "C"


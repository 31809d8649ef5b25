// Native plan builder: the persistent host mirror of one document's struct
// columns, with the full flush pipeline (wire scan -> causal schedule ->
// pre-split -> row assignment -> YATA placement -> final links) implemented
// in C++.  This is the C++ twin of yjs_tpu/ops/columns.py DocMirror
// (reference pipeline: src/utils/encoding.js:127-198,225-321 decode +
// dependency-stack integration, src/structs/Item.js:84-120 splitItem,
// :354-397 getMissing, :403-517 integrate; recast as the columnar plan of
// SURVEY.md §7).  Python keeps a semantically identical pure-Python
// implementation as the conformance oracle; the differential fuzz tests
// assert plan-for-plan equality between the two.
//
// Ownership/ABI: one `Mirror` per doc behind an opaque handle.  Update
// buffers are borrowed (Python keeps the bytes objects alive and passes
// stable pointers); synthesized content (surrogate-straddling splits,
// compaction merges) lives in mirror-owned arena buffers registered in the
// same buffer table.  All plan/state getters fill caller-allocated numpy
// arrays.  Row content is described by (src_kind, buf, ofs, end, ...)
// descriptor columns; Python realizes payload objects lazily from these.
//
// Threading contract: a Mirror handle must NOT be used from two threads
// concurrently — even read-only getters may touch mutable lookup hints
// (frag_hint).  The ymx_prepare_many worker pool honors this by
// parallelizing ACROSS doc handles, never within one; Python callers that
// share a doc across threads must serialize per doc (BatchEngine does —
// all native calls for a doc happen on the flush thread).

#include "wire.h"

#include <unistd.h>

#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdlib>
#include <functional>
#include <mutex>
#include <thread>

#include <algorithm>
#include <array>
#include <cstring>
#include <deque>
#include <map>
#include <memory>
#include <set>
#include <string>
#include <tuple>
#include <unordered_map>
#include <vector>

using namespace ytpu_wire;

namespace {

constexpr int64_t kNull = -1;
// content-source kinds (superset of yjs_tpu/native/__init__.py SRC_*)
constexpr int64_t kKindNone = 0;     // GC row
constexpr int64_t kKindDeleted = 1;  // ContentDeleted: length only
constexpr int64_t kKindFramed = 2;   // V1-framed bytes, verbatim range
constexpr int64_t kKindUtf8 = 3;     // raw UTF-8 of a ContentString
constexpr int64_t kKindSpill = 4;    // Python-realized (never produced here)
constexpr int64_t kKindAnys = 5;     // `count` lib0 any values at [ofs,end)
constexpr int64_t kKindJsons = 6;    // `count` ContentJSON var_strings
constexpr int64_t kKindV2Lazy = 7;   // V2 embed/format/type byte ranges

// error codes returned by ymx_prepare / ymx_ingest helpers
constexpr int kErrMalformed = -1;    // bad bytes: caller retries via Python
constexpr int kErrUnsupported = -9;  // subdocument: demote doc to CPU core
constexpr int kErrLegacy = -4;       // payload kind the scanner won't carry
constexpr int kErrInternal = -8;

// chain-run anchor adoption (the native twin of the segment planner's
// fast set, ISSUE 15): when a scheduled ref's origin/rightOrigin sits
// inside the row emit_row just produced — typing and prepend chains —
// the anchor is adopted in O(1) instead of re-running the per-slot
// fragment binary search.  Hit/lookup totals feed the flush metrics.
std::atomic<long long> g_seg_fast{0};
std::atomic<long long> g_seg_lookup{0};

struct ContentDesc {
  int64_t kind = kKindNone;
  int64_t buf = kNull;
  int64_t ofs = kNull, end = kNull;
  int64_t ofs2 = kNull, end2 = kNull;
  int64_t count = kNull;  // elements (ANYS/JSONS) or v2 type_ref (V2Lazy k7)
  int64_t v2 = 0;         // source wire version (realize dispatch)
};

struct PendRef {
  int64_t client = 0, clock = 0, length = 0;
  int64_t oc = kNull, ok = 0;    // origin (client, clock); oc<0 = none
  int64_t rc = kNull, rk = 0;    // rightOrigin
  int64_t pic = kNull, pik = 0;  // parent type-item id
  int64_t name_id = kNull;       // interned root-type name
  int64_t sub_id = kNull;        // interned parentSub
  int64_t ref = 0;               // wire content ref (0 = GC)
  bool is_gc = false;
  ContentDesc c;
};

struct Plan {
  int64_t n_rows = 0;
  std::vector<std::array<int64_t, 2>> splits;
  std::vector<std::array<int64_t, 4>> sched;
  std::vector<int64_t> delete_rows;
  std::vector<std::array<int64_t, 3>> applied_ds;
  // bulk-apply form: FINAL link/head values of everything this step
  // changed (host-resolved YATA; see Mirror::list_insert).  Dedup rides
  // epoch marks in the Mirror (mark_link/mark_head); the finalize pass
  // sorts, matching the Python twin's `sorted(plan._dl)`.
  std::vector<int64_t> dirty_links, dirty_heads;
  std::vector<int64_t> link_rows, link_vals, head_segs, head_vals;
  // what this step planned, by kind (counts[3..5], plan_kind_counts):
  // rows it added, and of those the rows whose parent is a type item,
  // format rows, rows under a parentSub and type rows; segments before
  // it; map entries it deleted (a later writer's delete set, or the
  // last-writer-wins pass); format rows it deleted; rows the conflict
  // scan of list_insert stepped over (a sibling in the same gap each)
  int64_t rows_new = 0, rows_nested = 0, rows_format = 0;
  int64_t rows_attr = 0, rows_type = 0, segs_before = 0;
  int64_t lww_overwritten = 0, format_deleted = 0, conflict_steps = 0;
  // the mirror held no row before this step: with dense links, every
  // cell the room has ever written is in this plan (plan_shape)
  bool from_empty = false;
  // rows this step deleted (delete_rows also carries the fragments it
  // split off rows that earlier steps deleted)
  std::vector<int64_t> fresh_deletes;

  void clear() {
    fresh_deletes.clear();
    from_empty = false;
    n_rows = 0;
    rows_new = rows_nested = rows_format = 0;
    rows_attr = rows_type = segs_before = 0;
    lww_overwritten = format_deleted = conflict_steps = 0;
    splits.clear();
    sched.clear();
    delete_rows.clear();
    applied_ds.clear();
    dirty_links.clear();
    dirty_heads.clear();
    link_rows.clear();
    link_vals.clear();
    head_segs.clear();
    head_vals.clear();
  }
};

struct Mirror {
  // client <-> dense slot mapping (creation order = Python slot())
  std::vector<int64_t> client_of_slot;
  std::unordered_map<int64_t, int64_t> slot_of_client;
  // per-slot fragment index sorted by clock, and next expected clock
  std::vector<std::vector<int64_t>> frag_clock, frag_row;
  // per-slot last frag_containing hit: lookups chain forward one fragment
  // at a time (origin cuts / delete walks), so checking hint and hint+1
  // before the binary search hits most of the time.  Purely an index
  // guess — every use re-verifies bounds against the live frag lists, so
  // stale values (splits/compaction reindexing) cost a miss, never a
  // wrong answer.
  mutable std::vector<int64_t> frag_hint;
  std::vector<int64_t> state;

  // per-row columns
  std::vector<int64_t> r_slot, r_clock, r_len;
  std::vector<int64_t> r_oslot, r_oclock, r_rslot, r_rclock;
  std::vector<int64_t> r_ref, r_seg;
  std::vector<uint8_t> r_is_gc, r_countable;
  std::vector<ContentDesc> r_c;
  std::vector<uint8_t> r_host_deleted, r_lww_deleted;

  // segment registry: (name_id, sub_id, parent_row) -> seg, creation order
  std::map<std::tuple<int64_t, int64_t, int64_t>, int64_t> seg_lookup;
  std::vector<int64_t> seg_name_id, seg_sub_id, seg_parent;
  std::unordered_map<int64_t, std::vector<int64_t>> segs_of_parent;
  std::unordered_map<int64_t, std::vector<int64_t>> rows_of_seg;  // nested only
  std::unordered_map<int64_t, std::vector<int64_t>> map_chain;
  // host linked lists: the mirror of the device right_link/starts state
  // (the planner resolves YATA placement against these, so each flush
  // ships final link values)
  std::vector<int64_t> list_next;
  std::vector<int64_t> head_of_seg;

  // interned strings (UTF-8 blob + ranges); key = raw bytes
  std::vector<uint8_t> strings;
  std::unordered_map<std::string, int64_t> interned;
  std::vector<int64_t> intern_ofs, intern_len;

  // delete-set bookkeeping: per-slot ranges (slot-indexed — slots are
  // dense small ints, so indexing beats hashing per deleted row) + slot
  // first-note order; a slot is "present" iff its range list is
  // non-empty (note_deleted is the only writer and never leaves one
  // empty)
  std::vector<std::vector<std::array<int64_t, 2>>> ds;
  std::vector<int64_t> ds_slot_order;

  // pending causally-early refs per client + pending delete ranges
  std::map<int64_t, std::vector<PendRef>> pending;
  std::vector<std::array<int64_t, 3>> pending_ds;

  // buffer registry: borrowed update bytes + owned arena blocks
  std::vector<std::pair<const uint8_t*, uint64_t>> bufs;
  std::vector<std::unique_ptr<std::vector<uint8_t>>> owned;

  Plan plan;
  // which prepare (or state clone) wrote `plan`: readers that hold a
  // plan's number (counts[15]) can tell it has been overwritten
  uint64_t plan_seq = 0;
  uint64_t gen = 0;

  // dedup epochs for Plan.dirty_links/dirty_heads (one bump per prepare);
  // tm_mark dedups the touched-map-segs list in the rows loop
  std::vector<uint64_t> dl_mark, dh_mark, tm_mark;
  uint64_t dirty_epoch = 0;
  // list_insert conflict-scan marks: visited-walk id + visit order per row
  // (replaces two std::set<int64_t> per insert with O(1) membership)
  std::vector<uint64_t> walk_mark, walk_order;
  uint64_t walk_id = 0;
  // bump-allocated arena chunk for small synthesized buffers (surrogate
  // repairs); chunks live in `owned`, so their bytes never move
  int64_t cur_chunk = kNull;
  size_t chunk_used = 0;

  // ---- interning / slots / segments -------------------------------------

  int64_t intern(const uint8_t* p, int64_t n) {
    std::string key(reinterpret_cast<const char*>(p), (size_t)n);
    auto it = interned.find(key);
    if (it != interned.end()) return it->second;
    int64_t id = (int64_t)intern_ofs.size();
    intern_ofs.push_back((int64_t)strings.size());
    intern_len.push_back(n);
    strings.insert(strings.end(), p, p + n);
    interned.emplace(std::move(key), id);
    return id;
  }

  // tiny round-robin cache: each ref touches up to three clients (self,
  // origin, right-origin), so one entry thrashes; four cover the working
  // set.  Slots are never removed, so entries can only go stale on
  // nothing — a cached (client, slot) pair stays true forever.
  static constexpr int kSlotCache = 4;
  int64_t slot_cache_cl[kSlotCache] = {INT64_MIN, INT64_MIN, INT64_MIN,
                                       INT64_MIN};
  int64_t slot_cache_v[kSlotCache] = {kNull, kNull, kNull, kNull};
  int slot_cache_pos = 0;

  int64_t slot(int64_t client) {
    for (int i = 0; i < kSlotCache; i++)
      if (slot_cache_cl[i] == client) return slot_cache_v[i];
    int64_t s;
    auto it = slot_of_client.find(client);
    if (it != slot_of_client.end()) {
      s = it->second;
    } else {
      s = (int64_t)client_of_slot.size();
      slot_of_client.emplace(client, s);
      client_of_slot.push_back(client);
      frag_clock.emplace_back();
      frag_row.emplace_back();
      frag_hint.push_back(0);
      state.push_back(0);
    }
    slot_cache_cl[slot_cache_pos] = client;
    slot_cache_v[slot_cache_pos] = s;
    slot_cache_pos = (slot_cache_pos + 1) & (kSlotCache - 1);
    return s;
  }

  int64_t get_state(int64_t client) const {
    auto it = slot_of_client.find(client);
    return it == slot_of_client.end() ? 0 : state[it->second];
  }

  int64_t n_rows() const { return (int64_t)r_slot.size(); }
  int64_t n_segs() const { return (int64_t)seg_name_id.size(); }
  bool seg_is_map(int64_t s) const { return seg_sub_id[s] != kNull; }

  int64_t seg(int64_t name_id, int64_t sub_id, int64_t parent_row) {
    auto key = std::make_tuple(name_id, sub_id, parent_row);
    auto it = seg_lookup.find(key);
    if (it != seg_lookup.end()) return it->second;
    int64_t s = n_segs();
    seg_lookup.emplace(key, s);
    seg_name_id.push_back(name_id);
    seg_sub_id.push_back(sub_id);
    seg_parent.push_back(parent_row);
    head_of_seg.push_back(kNull);
    if (parent_row != kNull) segs_of_parent[parent_row].push_back(s);
    return s;
  }

  // ---- buffers / arena ---------------------------------------------------

  int64_t add_buf(const uint8_t* p, uint64_t n) {
    bufs.emplace_back(p, n);
    return (int64_t)bufs.size() - 1;
  }

  // synthesize an owned buffer (surrogate repairs, compaction merges)
  int64_t arena(std::vector<uint8_t>&& data) {
    owned.push_back(std::make_unique<std::vector<uint8_t>>(std::move(data)));
    auto& v = *owned.back();
    bufs.emplace_back(v.data(), (uint64_t)v.size());
    return (int64_t)bufs.size() - 1;
  }

  const uint8_t* buf_ptr(int64_t b) const { return bufs[(size_t)b].first; }
  uint64_t buf_len(int64_t b) const { return bufs[(size_t)b].second; }

  // two-part copy into the bump arena (surrogate repair buffers); avoids
  // a malloc'd std::vector per synthesized fragment
  static constexpr size_t kChunk = 1 << 16;
  int64_t arena2(const uint8_t* a, size_t na, const uint8_t* b, size_t nb) {
    size_t need = na + nb;
    if (need > kChunk) {
      std::vector<uint8_t> big;
      big.reserve(need);
      big.insert(big.end(), a, a + na);
      big.insert(big.end(), b, b + nb);
      return arena(std::move(big));
    }
    if (cur_chunk == kNull || chunk_used + need > kChunk) {
      owned.push_back(std::make_unique<std::vector<uint8_t>>(kChunk));
      cur_chunk = (int64_t)owned.size() - 1;
      chunk_used = 0;
    }
    uint8_t* dst = owned[(size_t)cur_chunk]->data() + chunk_used;
    std::memcpy(dst, a, na);
    if (nb) std::memcpy(dst + na, b, nb);
    chunk_used += need;
    bufs.emplace_back(dst, (uint64_t)need);
    return (int64_t)bufs.size() - 1;
  }

  // LSD radix sort for clock lists (non-negative, usually < 2^16): the
  // same ascending result std::sort produces, with branch-free counting
  // passes.  Scratch persists across prepares to avoid re-allocation.
  std::vector<int64_t> radix_tmp;

  void radix_sort_clocks(std::vector<int64_t>& v) {
    size_t n = v.size();
    if (n < 96) {  // small lists: introsort's constant wins
      std::sort(v.begin(), v.end());
      return;
    }
    int64_t mx = 0;
    bool neg = false;
    for (int64_t x : v) {
      mx = x > mx ? x : mx;
      neg |= x < 0;
    }
    if (neg) {  // outside the clock domain (hostile bytes): total order
      std::sort(v.begin(), v.end());
      return;
    }
    if (radix_tmp.size() < n) radix_tmp.resize(n);
    int64_t* src = v.data();
    int64_t* dst = radix_tmp.data();
    // shift < 64 bounds the pass loop even for mx >= 2^56 (a shift of 64
    // would be UB; byte 7 of a non-negative int64 is covered at shift 56)
    for (int shift = 0; shift < 64 && (mx >> shift) > 0; shift += 8) {
      size_t cnt[256] = {0};
      for (size_t i = 0; i < n; i++) cnt[(src[i] >> shift) & 0xFF]++;
      size_t sum = 0;
      for (int b = 0; b < 256; b++) {
        size_t c = cnt[b];
        cnt[b] = sum;
        sum += c;
      }
      for (size_t i = 0; i < n; i++)
        dst[cnt[(src[i] >> shift) & 0xFF]++] = src[i];
      std::swap(src, dst);
    }
    if (src != v.data()) std::memcpy(v.data(), src, n * sizeof(int64_t));
  }

  // dedup'd dirty-row / dirty-head notes (sorted once at plan finalize)
  void mark_link(int64_t row) {
    if ((size_t)row >= dl_mark.size()) dl_mark.resize((size_t)row + 64, 0);
    if (dl_mark[(size_t)row] != dirty_epoch) {
      dl_mark[(size_t)row] = dirty_epoch;
      plan.dirty_links.push_back(row);
    }
  }
  void mark_head(int64_t sg) {
    if ((size_t)sg >= dh_mark.size()) dh_mark.resize((size_t)sg + 64, 0);
    if (dh_mark[(size_t)sg] != dirty_epoch) {
      dh_mark[(size_t)sg] = dirty_epoch;
      plan.dirty_heads.push_back(sg);
    }
  }

  // ---- content descriptor splitting -------------------------------------

  // byte index of UTF-16 unit `units` within the UTF-8 range; *mid_pair set
  // when the cut lands between the two units of a 4-byte char (the char is
  // consumed; reference ContentString.js:51-66 replaces both halves)
  static uint64_t utf8_at_u16(const uint8_t* b, uint64_t ofs, uint64_t end,
                              int64_t units, bool* mid_pair) {
    uint64_t i = ofs;
    int64_t got = 0;
    *mid_pair = false;
    while (got < units && i < end) {
      uint8_t c = b[i];
      if (c < 0x80) { got += 1; i += 1; }
      else if (c < 0xE0) { got += 1; i += 2; }
      else if (c < 0xF0) { got += 1; i += 3; }
      else {
        if (got + 2 <= units) { got += 2; i += 4; }
        else { got += 2; i += 4; *mid_pair = true; }
      }
    }
    return i;
  }

  // advance an element-range descriptor past `k` elements; returns new ofs
  int64_t elem_skip(const ContentDesc& c, int64_t k) const {
    Reader r{buf_ptr(c.buf), (uint64_t)c.end, (uint64_t)c.ofs, false};
    for (int64_t i = 0; i < k && !r.fail; i++) {
      if (c.kind == kKindAnys) r.skip_any();
      else { uint64_t o, b; r.var_string(&o, &b); }
    }
    return r.fail ? kNull : (int64_t)r.pos;
  }

  // split `c` (a row/ref of total length `total`) at element offset `off`:
  // `c` keeps the left part, the returned descriptor is the right part.
  // ok=false on malformed content (caller degrades to Python).
  ContentDesc desc_split(ContentDesc& c, int64_t total, int64_t off, bool* ok) {
    *ok = true;
    ContentDesc right = c;
    switch (c.kind) {
      case kKindDeleted:
        return right;  // length-only content: columns carry the lengths
      case kKindAnys:
      case kKindJsons: {
        int64_t cut = elem_skip(c, off);
        if (cut == kNull) { *ok = false; return right; }
        right = c;
        right.ofs = cut;
        right.count = c.count - off;
        c.end = cut;
        c.count = off;
        return right;
      }
      case kKindUtf8: {
        bool mid = false;
        uint64_t cut = utf8_at_u16(buf_ptr(c.buf), (uint64_t)c.ofs,
                                   (uint64_t)c.end, off, &mid);
        if (cut > (uint64_t)c.end) {  // truncated trailing sequence
          *ok = false;
          return right;
        }
        if (!mid) {
          right = c;
          right.ofs = (int64_t)cut;
          c.end = (int64_t)cut;
          return right;
        }
        // the cut consumed a surrogate pair: left = prefix + U+FFFD,
        // right = U+FFFD + suffix (both synthesized into arena buffers)
        static const uint8_t kFFFD[3] = {0xEF, 0xBF, 0xBD};
        const uint8_t* base = buf_ptr(c.buf);
        int64_t lb = arena2(base + c.ofs, (size_t)(cut - 4 - (uint64_t)c.ofs),
                            kFFFD, 3);
        int64_t rb = arena2(kFFFD, 3, base + cut,
                            (size_t)((uint64_t)c.end - cut));
        c.buf = lb; c.ofs = 0; c.end = (int64_t)buf_len(lb);
        right.kind = kKindUtf8;
        right.buf = rb; right.ofs = 0; right.end = (int64_t)buf_len(rb);
        right.v2 = c.v2;
        return right;
      }
      default:
        *ok = false;  // V2Lazy/Spill/None are length-1 or unsplittable
        return right;
    }
  }

  bool desc_trim_left(ContentDesc* c, int64_t total, int64_t off) {
    bool ok = true;
    ContentDesc right = desc_split(*c, total, off, &ok);
    if (ok) *c = right;
    return ok;
  }

  // ---- row / fragment bookkeeping (DocMirror._add_row etc.) -------------

  void note_deleted(int64_t slot_, int64_t clock, int64_t len) {
    if ((size_t)slot_ >= ds.size()) ds.resize((size_t)slot_ + 1);
    auto& v = ds[(size_t)slot_];
    if (v.empty()) ds_slot_order.push_back(slot_);
    v.push_back({{clock, len}});
  }

  void reserve_rows(size_t extra) {
    size_t want = r_slot.size() + extra;
    if (r_slot.capacity() >= want) return;
    r_slot.reserve(want); r_clock.reserve(want); r_len.reserve(want);
    r_oslot.reserve(want); r_oclock.reserve(want);
    r_rslot.reserve(want); r_rclock.reserve(want);
    r_ref.reserve(want); r_seg.reserve(want);
    r_is_gc.reserve(want); r_countable.reserve(want);
    r_c.reserve(want); r_host_deleted.reserve(want);
    r_lww_deleted.reserve(want); list_next.reserve(want);
  }

  // oslot_/rslot_ are PRE-RESOLVED slots (kNull = no origin): every caller
  // has already paid the client->slot lookup, so add_row must not repeat it
  int64_t add_row(int64_t slot_, int64_t clock, int64_t length,
                  int64_t oslot_, int64_t ok_, int64_t rslot_, int64_t rk,
                  bool is_gc, const ContentDesc& c, int64_t ref,
                  int64_t seg_) {
    int64_t row = n_rows();
    r_slot.push_back(slot_);
    r_clock.push_back(clock);
    r_len.push_back(length);
    if (oslot_ == kNull) { r_oslot.push_back(kNull); r_oclock.push_back(0); }
    else { r_oslot.push_back(oslot_); r_oclock.push_back(ok_); }
    if (rslot_ == kNull) { r_rslot.push_back(kNull); r_rclock.push_back(0); }
    else { r_rslot.push_back(rslot_); r_rclock.push_back(rk); }
    r_is_gc.push_back(is_gc ? 1 : 0);
    r_countable.push_back((!is_gc && ref != 0 && ref != 1 && ref != 6) ? 1 : 0);
    r_c.push_back(c);
    r_ref.push_back(ref);
    r_seg.push_back(is_gc ? kNull : seg_);
    list_next.push_back(kNull);
    r_host_deleted.push_back(0);
    r_lww_deleted.push_back(0);
    if (!is_gc && seg_ != kNull && seg_parent[seg_] != kNull)
      rows_of_seg[seg_].push_back(row);
    gen++;
    if (is_gc) note_deleted(slot_, clock, length);
    auto& fc = frag_clock[slot_];
    auto& fr = frag_row[slot_];
    if (fc.empty() || clock > fc.back()) {
      fc.push_back(clock);
      fr.push_back(row);
    } else {
      auto it = std::lower_bound(fc.begin(), fc.end(), clock);
      size_t i = (size_t)(it - fc.begin());
      fc.insert(fc.begin() + i, clock);
      fr.insert(fr.begin() + i, row);
    }
    int64_t end = clock + length;
    if (end > state[slot_]) state[slot_] = end;
    return row;
  }

  // index into the frag lists of the fragment covering `clock`, or -1
  int64_t frag_containing(int64_t slot_, int64_t clock) const {
    const auto& fc = frag_clock[slot_];
    int64_t n = (int64_t)fc.size();
    if (n == 0) return kNull;
    // fast path: appends dominate, so most lookups hit the last fragment
    if (clock >= fc.back()) {
      int64_t i = n - 1;
      int64_t row = frag_row[slot_][(size_t)i];
      return clock < r_clock[row] + r_len[row] ? i : kNull;
    }
    // hint path: chained lookups land on the same or the next fragment
    int64_t i;
    int64_t h = frag_hint[slot_];
    if (h >= 0 && h + 1 < n && fc[(size_t)h] <= clock) {
      if (clock < fc[(size_t)h + 1]) i = h;
      else if (h + 2 < n ? clock < fc[(size_t)h + 2]
                         : clock < fc.back())
        i = h + 1;
      else
        i = std::upper_bound(fc.begin() + h + 2, fc.end(), clock) -
            fc.begin() - 1;
    } else {
      i = std::upper_bound(fc.begin(), fc.end(), clock) - fc.begin() - 1;
    }
    if (i < 0) return kNull;
    frag_hint[slot_] = i;
    int64_t row = frag_row[slot_][(size_t)i];
    if (clock < r_clock[row] + r_len[row]) return i;
    return kNull;
  }

  int64_t split_existing(int64_t slot_, int64_t frag_idx, int64_t at_clock,
                         bool* ok) {
    int64_t row = frag_row[slot_][(size_t)frag_idx];
    int64_t offset = at_clock - r_clock[row];
    ContentDesc right = desc_split(r_c[row], r_len[row], offset, ok);
    if (!*ok) return kNull;
    gen++;
    int64_t sg = r_seg[row];
    int64_t new_row = add_row(
        slot_, at_clock, r_len[row] - offset,
        slot_, at_clock - 1, r_rslot[row], r_rclock[row],
        false, right, r_ref[row], sg);
    r_len[row] = offset;
    plan.splits.push_back({{row, new_row}});
    list_next[new_row] = list_next[row];
    list_next[row] = new_row;
    mark_link(row);
    mark_link(new_row);
    if (r_host_deleted[row]) {
      r_host_deleted[new_row] = 1;
      // ship the fragment's deleted bit: the bulk-apply path has no
      // on-device split surgery to copy it from the original
      plan.delete_rows.push_back(new_row);
    }
    if (sg != kNull && seg_is_map(sg)) {
      auto& chain = map_chain[sg];
      auto it = std::find(chain.begin(), chain.end(), row);
      chain.insert(it + 1, new_row);
      if (r_lww_deleted[row]) r_lww_deleted[new_row] = 1;
    }
    return new_row;
  }

  // ---- map-chain YATA insert (DocMirror._chain_insert) ------------------

  int64_t origin_row_of(int64_t row) const {
    int64_t s = r_oslot[row];
    if (s == kNull) return kNull;
    int64_t fi = frag_containing(s, r_oclock[row]);
    return fi == kNull ? kNull : frag_row[s][(size_t)fi];
  }

  bool row_origin_eq(int64_t a, int64_t b) const {
    int64_t sa = r_oslot[a], sb = r_oslot[b];
    return sa == sb && (sa == kNull || r_oclock[a] == r_oclock[b]);
  }

  bool row_right_eq(int64_t a, int64_t b) const {
    int64_t sa = r_rslot[a], sb = r_rslot[b];
    return sa == sb && (sa == kNull || r_rclock[a] == r_rclock[b]);
  }

  int64_t row_client(int64_t row) const {
    return client_of_slot[r_slot[row]];
  }

  // resolve the row's YATA placement against the host list and splice —
  // the host twin of the device conflict scan (reference Item.js:403-517,
  // the same itemsBeforeOrigin/conflictingItems walk).  Returns the
  // resolved left row (kNull = new head).
  int64_t list_insert(int64_t sg, int64_t row, int64_t left_row,
                      int64_t right_row) {
    int64_t left = left_row;
    int64_t o = left_row != kNull ? list_next[left_row] : head_of_seg[sg];
    if (o != kNull && o != right_row) {
      // conflict scan with O(1) membership: `items_before` = rows stamped
      // with this walk id; `conflicting` = those with visit order >=
      // conf_start (clear() == bump conf_start past the current row).
      // Stale stamps (older walks, pre-compaction ids) are always < the
      // freshly bumped walk id, so lazy sizing is safe.
      if (walk_mark.size() < r_slot.size()) {
        walk_mark.resize(r_slot.size(), 0);
        walk_order.resize(r_slot.size(), 0);
      }
      uint64_t wid = ++walk_id;
      uint64_t idx = 0, conf_start = 0;
      while (o != kNull && o != right_row) {
        plan.conflict_steps++;
        walk_mark[(size_t)o] = wid;
        walk_order[(size_t)o] = idx++;
        if (row_origin_eq(row, o)) {
          if (row_client(o) < row_client(row)) {
            left = o;
            conf_start = idx;
          } else if (row_right_eq(row, o)) {
            break;
          }
        } else {
          int64_t oor = origin_row_of(o);
          if (oor != kNull && walk_mark[(size_t)oor] == wid) {
            if (walk_order[(size_t)oor] < conf_start) {
              left = o;
              conf_start = idx;
            }
          } else {
            break;
          }
        }
        o = list_next[o];
      }
    }
    if (left != kNull) {
      list_next[row] = list_next[left];
      list_next[left] = row;
      mark_link(left);
      mark_link(row);
    } else {
      list_next[row] = head_of_seg[sg];
      head_of_seg[sg] = row;
      mark_link(row);
      mark_head(sg);
    }
    return left;
  }

  // ---- deletes (DocMirror._delete_row / _lww_pass) ----------------------

  void delete_row(int64_t row) {
    if (r_host_deleted[row] || r_is_gc[row]) return;
    r_host_deleted[row] = 1;
    plan.delete_rows.push_back(row);
    plan.fresh_deletes.push_back(row);
    if (r_ref[row] == 6) plan.format_deleted++;
    note_deleted(r_slot[row], r_clock[row], r_len[row]);
    plan.applied_ds.push_back({{row_client(row), r_clock[row], r_len[row]}});
    int64_t sg = r_seg[row];
    if (sg != kNull && seg_is_map(sg)) {
      r_lww_deleted[row] = 1;
      plan.lww_overwritten++;
    }
    if (r_ref[row] == 7) {
      auto it = segs_of_parent.find(row);
      if (it != segs_of_parent.end()) {
        for (int64_t cs : it->second) {
          auto rit = rows_of_seg.find(cs);
          if (rit == rows_of_seg.end()) continue;
          std::vector<int64_t> children = rit->second;  // copy: recursion mutates
          for (int64_t child : children) delete_row(child);
        }
      }
    }
  }

  void lww_pass(const std::vector<int64_t>& segs) {
    for (int64_t sg : segs) {
      auto it = map_chain.find(sg);
      if (it == map_chain.end() || it->second.empty()) continue;
      int64_t tail = it->second.back();
      for (int64_t r : it->second)
        if (r != tail && !r_lww_deleted[r]) delete_row(r);
    }
  }

  // ---- formatting clean-up (ops/engine.py _cleanup_room is the twin) -----

  // a format row's key and JSON value, where its payload is V1-framed
  bool format_of(int64_t row, std::string* key, std::string* value) const {
    const ContentDesc& c = r_c[row];
    if (c.kind != kKindFramed || c.buf < 0) return false;
    Reader r{buf_ptr(c.buf), (uint64_t)c.end, (uint64_t)c.ofs, false};
    uint64_t o, b;
    r.var_string(&o, &b);
    if (r.fail) return false;
    key->assign(reinterpret_cast<const char*>(r.buf + o), (size_t)b);
    r.var_string(&o, &b);
    if (r.fail) return false;
    value->assign(reinterpret_cast<const char*>(r.buf + o), (size_t)b);
    return true;
  }

  // the type ref of a ContentType row, or -1
  int64_t type_ref_of(int64_t row) const {
    const ContentDesc& c = r_c[row];
    if (c.kind == kKindV2Lazy) return c.count;
    if (c.kind != kKindFramed || c.buf < 0) return -1;
    Reader r{buf_ptr(c.buf), (uint64_t)c.end, (uint64_t)c.ofs, false};
    uint64_t t = r.varuint();
    return r.fail ? -1 : (int64_t)t;
  }

  struct HeldAttr {
    int64_t row;        // the format row that set it
    std::string value;  // its JSON text
  };

  // JS `held || null` === the value of the format row `row`: an object
  // is itself alone, a primitive equals its like (JSON text)
  static bool attr_strict_eq(const HeldAttr* held, int64_t row,
                             const std::string& value) {
    static const std::string kNullText = "null";
    bool falsy = held == nullptr || held->value == "null" ||
                 held->value == "false" || held->value == "0" ||
                 held->value == "-0" || held->value == "\"\"";
    const std::string& a = falsy ? kNullText : held->value;
    bool a_obj = a[0] == '{' || a[0] == '[';
    bool v_obj = !value.empty() && (value[0] == '{' || value[0] == '[');
    if (a_obj || v_obj) return !falsy && held->row == row;
    return a == value;
  }

  // What a Y.Doc deletes from its texts after a remote transaction
  // (YText._callObserver -> cleanupYTextFormatting), for the step the
  // current plan is of; `rows_before`: the rows held before that step.
  // Writes (client, clock, length) a format row to delete and returns
  // their number; -1 where a payload is not V1-framed or `cap` is too
  // small (the caller then walks in Python).
  int64_t format_cleanup(int64_t rows_before, int64_t* out, int64_t cap,
                         int64_t* n_texts) {
    *n_texts = 0;
    std::vector<int64_t> changed, lost;
    bool brought = false;
    for (int64_t r = rows_before; r < n_rows(); r++) {
      if (r_ref[r] == 6 && !r_host_deleted[r] && !r_is_gc[r]) brought = true;
      if (r_seg[r] != kNull) changed.push_back(r_seg[r]);
    }
    for (int64_t r : plan.fresh_deletes) {
      int64_t sg = r_seg[r];
      if (sg == kNull) continue;
      changed.push_back(sg);
      if (r_ref[r] == 6) lost.push_back(sg);
    }
    std::sort(changed.begin(), changed.end());
    changed.erase(std::unique(changed.begin(), changed.end()), changed.end());
    std::sort(lost.begin(), lost.end());
    int64_t n_out = 0;
    std::string key, value;
    for (int64_t sg : changed) {
      int64_t parent = seg_parent[sg];
      if (seg_sub_id[sg] != kNull || parent == kNull) continue;
      if (!brought && !std::binary_search(lost.begin(), lost.end(), sg))
        continue;
      if (parent >= rows_before || r_host_deleted[parent] ||
          r_ref[parent] != 7)
        continue;
      int64_t tref = type_ref_of(parent);
      if (tref < 0) return -1;
      if (tref != 2 && tref != 6) continue;  // YText, YXmlText
      (*n_texts)++;
      std::map<std::string, HeldAttr> attrs, start_attrs;
      std::vector<int64_t> gap;  // live format rows since the last string
      for (int64_t r = head_of_seg[sg]; r != kNull; r = list_next[r]) {
        if (r_host_deleted[r]) continue;
        if (r_ref[r] == 6) {
          if (!format_of(r, &key, &value)) return -1;
          gap.push_back(r);
          if (value == "null") attrs.erase(key);
          else attrs[key] = HeldAttr{r, value};
        } else if (r_ref[r] == 4 || r_ref[r] == 5) {
          for (int64_t f : gap) {
            format_of(f, &key, &value);
            auto e = attrs.find(key);
            auto b = start_attrs.find(key);
            bool eq_end = attr_strict_eq(
                e == attrs.end() ? nullptr : &e->second, f, value);
            bool eq_start = attr_strict_eq(
                b == start_attrs.end() ? nullptr : &b->second, f, value);
            if (!eq_end || eq_start) {
              if (n_out >= cap) return -1;
              out[3 * n_out] = row_client(f);
              out[3 * n_out + 1] = r_clock[f];
              out[3 * n_out + 2] = r_len[f];
              n_out++;
            }
          }
          gap.clear();
          start_attrs = attrs;
        }
      }
    }
    return n_out;
  }

  // ---- wire scan (decode_update_refs twin) ------------------------------

  // scan one update into `out`; returns 0 or an error code
  int scan_update(int64_t buf_id, bool v2, std::vector<PendRef>* out,
                  std::vector<std::array<int64_t, 3>>* ds_out) {
    const uint8_t* buf = buf_ptr(buf_id);
    uint64_t blen = buf_len(buf_id);
    if (!v2) return scan_v1(buf, blen, buf_id, out, ds_out);
    return scan_v2(buf, blen, buf_id, out, ds_out);
  }

  int scan_v1(const uint8_t* buf, uint64_t blen, int64_t buf_id,
              std::vector<PendRef>* out,
              std::vector<std::array<int64_t, 3>>* ds_out) {
    Reader r{buf, blen, 0, false};
    uint64_t n_updates = r.varuint();
    for (uint64_t u = 0; u < n_updates && !r.fail; u++) {
      uint64_t n_structs = r.varuint();
      uint64_t client = r.varuint();
      uint64_t clock = r.varuint();
      for (uint64_t s = 0; s < n_structs && !r.fail; s++) {
        // build in place: a 176-byte PendRef copy per struct is real
        // memcpy traffic at millions of refs per flush
        out->emplace_back();
        PendRef& p = out->back();
        p.client = (int64_t)client;
        p.clock = (int64_t)clock;
        uint8_t info = r.u8();
        uint8_t ref = info & kBits5;
        p.ref = ref;
        if (ref == 0) {
          p.is_gc = true;
          p.length = (int64_t)r.varuint();
          p.c.kind = kKindNone;
        } else {
          if (ref == 9) return kErrUnsupported;  // ContentDoc: subdocument
          if (info & kBit8) {
            p.oc = (int64_t)r.varuint();
            p.ok = (int64_t)r.varuint();
          }
          if (info & kBit7) {
            p.rc = (int64_t)r.varuint();
            p.rk = (int64_t)r.varuint();
          }
          if (!(info & (kBit7 | kBit8))) {
            if (r.varuint() == 1) {
              uint64_t o, b;
              r.var_string(&o, &b);
              if (r.fail) return kErrMalformed;
              p.name_id = intern(buf + o, (int64_t)b);
            } else {
              p.pic = (int64_t)r.varuint();
              p.pik = (int64_t)r.varuint();
            }
            if (info & kBit6) {
              uint64_t o, b;
              r.var_string(&o, &b);
              if (r.fail) return kErrMalformed;
              p.sub_id = intern(buf + o, (int64_t)b);
            }
          }
          uint64_t c_ofs = r.pos;
          switch (ref) {
            case 1:
              p.length = (int64_t)r.varuint();
              p.c.kind = kKindDeleted;
              break;
            case 2: {  // ContentJSON: element range directly
              uint64_t n = r.varuint();
              uint64_t e_ofs = r.pos;
              for (uint64_t i = 0; i < n && !r.fail; i++) {
                uint64_t o, b;
                r.var_string(&o, &b);
              }
              p.length = (int64_t)n;
              p.c.kind = kKindJsons;
              p.c.buf = buf_id;
              p.c.ofs = (int64_t)e_ofs;
              p.c.end = (int64_t)r.pos;
              p.c.count = (int64_t)n;
              break;
            }
            case 3: {
              uint64_t n = r.varuint();
              r.skip(n);
              p.length = 1;
              p.c.kind = kKindFramed;
              p.c.buf = buf_id;
              p.c.ofs = (int64_t)c_ofs;
              p.c.end = (int64_t)r.pos;
              break;
            }
            case 4: {  // ContentString: raw UTF-8 range
              uint64_t o, b;
              r.var_string(&o, &b);
              p.length = (int64_t)r.utf16_len(o, b);
              p.c.kind = kKindUtf8;
              p.c.buf = buf_id;
              p.c.ofs = (int64_t)o;
              p.c.end = (int64_t)(o + b);
              break;
            }
            case 5: case 6: {
              uint64_t o, b;
              r.var_string(&o, &b);
              if (ref == 6) r.var_string(&o, &b);
              p.length = 1;
              p.c.kind = kKindFramed;
              p.c.buf = buf_id;
              p.c.ofs = (int64_t)c_ofs;
              p.c.end = (int64_t)r.pos;
              break;
            }
            case 7: {
              uint64_t tref = r.varuint();
              if (tref == 3 || tref == 5) {
                uint64_t o, b;
                r.var_string(&o, &b);
              }
              p.length = 1;
              p.c.kind = kKindFramed;
              p.c.buf = buf_id;
              p.c.ofs = (int64_t)c_ofs;
              p.c.end = (int64_t)r.pos;
              break;
            }
            case 8: {  // ContentAny: element range directly
              uint64_t n = r.varuint();
              uint64_t e_ofs = r.pos;
              for (uint64_t i = 0; i < n && !r.fail; i++) r.skip_any();
              p.length = (int64_t)n;
              p.c.kind = kKindAnys;
              p.c.buf = buf_id;
              p.c.ofs = (int64_t)e_ofs;
              p.c.end = (int64_t)r.pos;
              p.c.count = (int64_t)n;
              break;
            }
            default:
              return kErrMalformed;
          }
        }
        if (r.fail) return kErrMalformed;
        if (p.length == 0 && ref != 0) return kErrMalformed;
        clock += (uint64_t)p.length;
      }
    }
    if (r.fail) return kErrMalformed;
    uint64_t n_clients = r.varuint();
    for (uint64_t c = 0; c < n_clients && !r.fail; c++) {
      uint64_t client = r.varuint();
      uint64_t n = r.varuint();
      for (uint64_t i = 0; i < n && !r.fail; i++) {
        uint64_t clock = r.varuint();
        uint64_t len = r.varuint();
        ds_out->push_back({{(int64_t)client, (int64_t)clock, (int64_t)len}});
      }
    }
    if (r.fail || r.pos != blen) return kErrMalformed;
    return 0;
  }

  int scan_v2(const uint8_t* buf, uint64_t blen, int64_t buf_id,
              std::vector<PendRef>* out,
              std::vector<std::array<int64_t, 3>>* ds_out) {
    V2Streams v;
    if (!v.init(buf, blen)) return kErrMalformed;
    Reader* rest = &v.rest;
    uint64_t n_updates = rest->varuint();
    for (uint64_t u = 0; u < n_updates && !rest->fail; u++) {
      uint64_t n_structs = rest->varuint();
      int64_t client = v.client.read();
      uint64_t clock = rest->varuint();
      for (uint64_t s = 0; s < n_structs; s++) {
        if (v.any_fail()) return kErrMalformed;
        // build in place (mirrors scan_v1): no 176-byte copy per struct
        out->emplace_back();
        PendRef& p = out->back();
        p.client = client;
        p.clock = (int64_t)clock;
        p.c.v2 = 1;
        uint8_t info = (uint8_t)v.info.read();
        uint8_t ref = info & kBits5;
        p.ref = ref;
        if (ref == 0) {
          p.is_gc = true;
          p.length = v.len.read();
          p.c.kind = kKindNone;
        } else {
          if (ref == 9) return kErrUnsupported;
          if (ref == 2) return kErrLegacy;  // legacy ContentJSON in V2
          if (info & kBit8) { p.oc = v.client.read(); p.ok = v.left_clock.read(); }
          if (info & kBit7) { p.rc = v.client.read(); p.rk = v.right_clock.read(); }
          if (!(info & (kBit7 | kBit8))) {
            int64_t o = kNull, e = kNull;
            if (v.parent_info.read() == 1) {
              v.str.read(&o, &e);
              if (v.any_fail()) return kErrMalformed;
              p.name_id = intern(buf + o, e - o);
            } else {
              p.pic = v.client.read();
              p.pik = v.left_clock.read();
            }
            if (info & kBit6) {
              v.str.read(&o, &e);
              if (v.any_fail()) return kErrMalformed;
              p.sub_id = intern(buf + o, e - o);
            }
          }
          switch (ref) {
            case 1:
              p.length = v.len.read();
              p.c.kind = kKindDeleted;
              break;
            case 3: {
              int64_t c_ofs = (int64_t)rest->pos;
              uint64_t n = rest->varuint();
              rest->skip(n);
              p.length = 1;
              p.c.kind = kKindFramed;  // varuint+bytes: V1-compatible framing
              p.c.buf = buf_id;
              p.c.ofs = c_ofs;
              p.c.end = (int64_t)rest->pos;
              break;
            }
            case 4: {
              int64_t o, e;
              v.str.read(&o, &e);
              p.length = v.str.lens.s;
              p.c.kind = kKindUtf8;
              p.c.buf = buf_id;
              p.c.ofs = o;
              p.c.end = e;
              break;
            }
            case 5: {  // embed: lib0 any (V2-only framing)
              p.c.kind = kKindV2Lazy;
              p.c.buf = buf_id;
              p.c.ofs = (int64_t)rest->pos;
              rest->skip_any();
              p.c.end = (int64_t)rest->pos;
              p.c.count = 5;
              p.length = 1;
              break;
            }
            case 6: {  // format: key string + any value
              int64_t o, e;
              v.str.read(&o, &e);
              p.c.kind = kKindV2Lazy;
              p.c.buf = buf_id;
              p.c.ofs = o;
              p.c.end = e;
              p.c.ofs2 = (int64_t)rest->pos;
              rest->skip_any();
              p.c.end2 = (int64_t)rest->pos;
              p.c.count = 6;
              p.length = 1;
              break;
            }
            case 7: {
              int64_t tref = v.type_ref.read();
              int64_t o = kNull, e = kNull;
              if (tref == 3 || tref == 5) v.read_key(&o, &e);
              p.c.kind = kKindV2Lazy;
              p.c.buf = buf_id;
              p.c.ofs = o;
              p.c.end = e;
              p.c.count = tref;  // type ref rides in count
              p.length = 1;
              break;
            }
            case 8: {
              int64_t n = v.len.read();
              p.c.kind = kKindAnys;
              p.c.buf = buf_id;
              p.c.ofs = (int64_t)rest->pos;
              for (int64_t i = 0; i < n && !rest->fail; i++) rest->skip_any();
              p.c.end = (int64_t)rest->pos;
              p.c.count = n;
              p.length = n;
              break;
            }
            default:
              return kErrMalformed;
          }
        }
        if (v.any_fail()) return kErrMalformed;
        if (p.length == 0 && ref != 0) return kErrMalformed;
        clock += (uint64_t)p.length;
      }
    }
    if (rest->fail) return kErrMalformed;
    // DS section: delta-varint clocks, len-1 on the wire
    uint64_t n_clients = rest->varuint();
    for (uint64_t c = 0; c < n_clients && !rest->fail; c++) {
      int64_t cur = 0;
      uint64_t client = rest->varuint();
      uint64_t n = rest->varuint();
      for (uint64_t i = 0; i < n && !rest->fail; i++) {
        cur += (int64_t)rest->varuint();
        int64_t clock = cur;
        int64_t len = (int64_t)rest->varuint() + 1;
        cur += len;
        ds_out->push_back({{(int64_t)client, clock, len}});
      }
    }
    if (rest->fail || rest->pos != blen) return kErrMalformed;
    return 0;
  }

  // ---- the flush pipeline (DocMirror.prepare_step twin) -----------------

  // the phases a prepare's laps add into (seconds): ymx_prepare_many
  // sums them over a call's rooms into out_times[2..6]
  enum Lap { kLapScan, kLapMerge, kLapCuts, kLapRows, kLapFinalize, kNLaps };

  int prepare(const int64_t* buf_ids, const int64_t* v2_flags,
              int64_t n_updates, bool want_sched = true,
              double* laps = nullptr) {
    // nothing reads the sched section unless events are observed;
    // skipping it saves a 32-byte append per integrated row
    //
    // laps (double[kNLaps], or none: no clock is read): each lap adds
    // the seconds since the one before to its phase
    using clk = std::chrono::steady_clock;
    clk::time_point t0;
    if (laps) t0 = clk::now();
    auto lap = [&](Lap phase) {
      if (!laps) return;
      clk::time_point t1 = clk::now();
      laps[phase] += std::chrono::duration<double>(t1 - t0).count();
      t0 = t1;
    };
    plan.clear();
    plan.segs_before = n_segs();
    plan.from_empty = n_rows() == 0;
    plan_seq++;
    dirty_epoch++;

    // decode every staged update first (nothing merges on error; the doc
    // demotes wholesale, matching the Python flow).  Refs scan into ONE
    // flat buffer and move into the per-client queues afterwards — a
    // single fat-struct copy instead of the old scan/group/insert three.
    std::vector<PendRef> all_refs;
    {
      // structs are >= ~4 wire bytes each; over-reserving transiently is
      // far cheaper than re-copying 176-byte PendRefs on vector growth
      uint64_t total_bytes = 0;
      for (int64_t i = 0; i < n_updates; i++) total_bytes += buf_len(buf_ids[i]);
      all_refs.reserve(total_bytes / 4 + 64);
    }
    std::vector<std::array<int64_t, 3>> ds_ranges(pending_ds);
    {
      std::vector<std::array<int64_t, 3>> ds_new;
      for (int64_t i = 0; i < n_updates; i++) {
        std::vector<std::array<int64_t, 3>> ds_one;
        int rc = scan_update(buf_ids[i], v2_flags[i] != 0, &all_refs, &ds_one);
        if (rc != 0) return rc;
        for (auto& d : ds_one) ds_new.push_back(d);
      }
      for (auto& d : ds_new) ds_ranges.push_back(d);
    }
    lap(kLapScan);
    pending_ds.clear();

    // merge into per-client WORKING SETS of pointers (old pending refs
    // first, then this call's scan output, stable-sorted by clock) — the
    // same order the old fat-struct queues had, without moving a single
    // 176-byte PendRef.  `pending` stays untouched until the end of the
    // call, when only the UNCONSUMED tail is copied back (common case:
    // empty).  all_refs is function-scoped, so the pointers outlive every
    // consumer (fixpoint, cuts-collect, rows).
    // Clients interleave ref-by-ref in merged updates, so the working-set
    // lookup rides a small linear cache (few clients), spilling to a map
    // past kLinearClients.
    constexpr size_t kLinearClients = 32;
    std::vector<std::pair<int64_t, std::vector<PendRef*>>> qwork_lin;
    std::unordered_map<int64_t, std::vector<PendRef*>> qwork_wide;
    {
      auto qwork_of = [&](int64_t cl) -> std::vector<PendRef*>& {
        if (!qwork_wide.empty()) return qwork_wide[cl];
        for (auto& [c, w] : qwork_lin)
          if (c == cl) return w;
        if (qwork_lin.size() >= kLinearClients) {
          for (auto& [c, w] : qwork_lin)
            qwork_wide.emplace(c, std::move(w));
          qwork_lin.clear();
          return qwork_wide[cl];
        }
        qwork_lin.emplace_back(cl, std::vector<PendRef*>());
        return qwork_lin.back().second;
      };
      for (auto& [cl, q] : pending) {
        auto& w = qwork_of(cl);
        w.reserve(q.size() + 16);
        for (auto& r : q) w.push_back(&r);
      }
      int64_t cache_cl = INT64_MIN;
      std::vector<PendRef*>* cache_w = nullptr;
      for (auto& p : all_refs) {
        if (p.client != cache_cl) {
          cache_w = &qwork_of(p.client);
          cache_cl = p.client;
        }
        cache_w->push_back(&p);
      }
      auto by_clock = [](const PendRef* a, const PendRef* b) {
        return a->clock < b->clock;
      };
      for (auto& [cl, w] : qwork_lin)
        if (!std::is_sorted(w.begin(), w.end(), by_clock))
          std::stable_sort(w.begin(), w.end(), by_clock);
      for (auto& [cl, w] : qwork_wide)
        if (!std::is_sorted(w.begin(), w.end(), by_clock))
          std::stable_sort(w.begin(), w.end(), by_clock);
    }
    // descending-client iteration order for the fixpoint (the old
    // pending.rbegin() order), with consumed-prefix heads alongside
    std::vector<std::pair<int64_t, std::vector<PendRef*>*>> clients_desc;
    clients_desc.reserve(qwork_lin.size() + qwork_wide.size());
    for (auto& [cl, w] : qwork_lin) clients_desc.emplace_back(cl, &w);
    for (auto& [cl, w] : qwork_wide) clients_desc.emplace_back(cl, &w);
    std::sort(clients_desc.begin(), clients_desc.end(),
              [](const auto& a, const auto& b) { return a.first > b.first; });
    std::vector<size_t> q_head(clients_desc.size(), 0);

    lap(kLapMerge);
    // causal scheduling: per-client queue fixpoint, descending client order
    std::vector<PendRef*> sched;
    {
      size_t tot = 0;
      for (auto& [c, w] : clients_desc) tot += w->size();
      sched.reserve(tot);
    }
    // effective-state cache: the fixpoint probes state_of 3-4x per ref
    // (dep checks + clock gate); the old overlay map cost two hash
    // lookups per probe.  Live state[] never changes during the fixpoint
    // (rows are added later), so caching get_state is safe.  Linear for
    // the common few-client case, spilling to a map when wide.
    constexpr size_t kLinearStClients = 32;
    std::vector<std::pair<int64_t, int64_t>> st_lin;
    std::unordered_map<int64_t, int64_t> st_wide;
    auto state_of = [&](int64_t client) -> int64_t {
      if (!st_wide.empty()) {
        auto it = st_wide.find(client);
        if (it != st_wide.end()) return it->second;
        int64_t v = get_state(client);
        st_wide.emplace(client, v);
        return v;
      }
      for (auto& e : st_lin)
        if (e.first == client) return e.second;
      int64_t v = get_state(client);
      if (st_lin.size() >= kLinearStClients) {
        st_wide.insert(st_lin.begin(), st_lin.end());
        st_lin.clear();  // same spill discipline as qwork_of above
        st_wide.emplace(client, v);
      } else {
        st_lin.emplace_back(client, v);
      }
      return v;
    };
    auto bump_state = [&](int64_t client, int64_t v) {
      if (!st_wide.empty()) {
        st_wide[client] = v;
        return;
      }
      for (auto& e : st_lin)
        if (e.first == client) { e.second = v; return; }
      if (st_lin.size() >= kLinearStClients) {
        st_wide.insert(st_lin.begin(), st_lin.end());
        st_lin.clear();
        st_wide[client] = v;
      } else {
        st_lin.emplace_back(client, v);
      }
    };
    auto dep_ok = [&](int64_t dc, int64_t dk, bool has, int64_t client) {
      return !has || dc == client || state_of(dc) > dk;
    };
    bool progress = true;
    while (progress) {
      progress = false;
      for (size_t ci = 0; ci < clients_desc.size(); ci++) {
        int64_t client = clients_desc[ci].first;
        auto& q = *clients_desc[ci].second;
        size_t& head = q_head[ci];
        while (head < q.size()) {
          PendRef& ref = *q[head];
          int64_t st = state_of(client);
          if (ref.clock > st) break;
          if (ref.clock + ref.length <= st) {
            head++;
            progress = true;
            continue;
          }
          if (!(dep_ok(ref.oc, ref.ok, ref.oc >= 0, client) &&
                dep_ok(ref.rc, ref.rk, ref.rc >= 0, client) &&
                dep_ok(ref.pic, ref.pik, ref.pic >= 0, client)))
            break;
          if (ref.clock < st) {
            int64_t off = st - ref.clock;
            if (!ref.is_gc) {
              if (ref.c.kind != kKindNone &&
                  !desc_trim_left(&ref.c, ref.length, off))
                return kErrMalformed;
            }
            ref.clock += off;
            ref.length -= off;
            if (!ref.is_gc) {
              ref.oc = ref.client;
              ref.ok = ref.clock - 1;
            }
          }
          sched.push_back(&ref);
          bump_state(client, ref.clock + ref.length);
          head++;
          progress = true;
        }
      }
    }

    lap(kLapMerge);  // fixpoint
    // delete-set clamping against post-step state
    std::vector<std::array<int64_t, 3>> applicable;
    for (auto& [client, clock, ln] : ds_ranges) {
      int64_t st = state_of(client);
      if (clock < st)
        applicable.push_back({{client, clock, std::min(ln, st - clock)}});
      if (clock + ln > st) {
        int64_t lo = std::max(clock, st);
        pending_ds.push_back({{client, lo, clock + ln - lo}});
      }
    }

    lap(kLapCuts);  // ds-clamp
    // pre-split pass: every boundary this step needs (collected raw,
    // then sorted+deduped per client — matches Python's set semantics
    // without per-insert node allocation)
    std::vector<int64_t> cut_clients;  // first-need order (Python dict order)
    std::unordered_map<int64_t, std::vector<int64_t>> cuts;
    cuts.reserve(16);
    // one-entry cache (consecutive refs share clients) + consecutive-dup
    // elision: the sort+unique below makes dropped dups unobservable
    int64_t cut_cl_cache = INT64_MIN;
    std::vector<int64_t>* cut_ks_cache = nullptr;
    auto need_start = [&](int64_t client, int64_t clock) {
      if (client != cut_cl_cache) {
        auto it = cuts.find(client);
        if (it == cuts.end()) {
          cut_clients.push_back(client);
          it = cuts.emplace(client, std::vector<int64_t>()).first;
        }
        cut_cl_cache = client;
        cut_ks_cache = &it->second;
      }
      if (cut_ks_cache->empty() || cut_ks_cache->back() != clock)
        cut_ks_cache->push_back(clock);
    };
    // per-stream repeat elision: origin cuts chain forward one at a time
    // and right-origin cuts repeat across a typing burst, so most points
    // equal that client-stream's previous one; sort+unique makes drops
    // invisible.  Keyed per client (refs interleave clients ref-by-ref,
    // so a single-entry cache would thrash), linear scan over few clients.
    std::vector<std::array<int64_t, 3>> last_cut;  // client, last_o, last_r
    std::unordered_map<int64_t, std::array<int64_t, 2>> last_cut_wide;
    constexpr size_t kLinearCutClients = 32;
    auto cut_slot = [&](int64_t cl) -> int64_t* {
      // linear for the common few-client case; spill to a map when refs
      // span many historical clients (initial sync / bulk history load)
      if (last_cut.size() >= kLinearCutClients) {
        if (last_cut_wide.empty())
          for (auto& e : last_cut)
            last_cut_wide.emplace(e[0], std::array<int64_t, 2>{e[1], e[2]});
        return last_cut_wide
            .emplace(cl, std::array<int64_t, 2>{INT64_MIN, INT64_MIN})
            .first->second.data();
      }
      for (auto& e : last_cut)
        if (e[0] == cl) return &e[1];
      last_cut.push_back({cl, INT64_MIN, INT64_MIN});
      return &last_cut.back()[1];
    };
    for (const PendRef* rp : sched) {
      const PendRef& ref = *rp;
      if (ref.oc >= 0) {
        int64_t* e = cut_slot(ref.oc);
        if (e[0] != ref.ok + 1) {
          e[0] = ref.ok + 1;
          need_start(ref.oc, e[0]);
        }
      }
      if (ref.rc >= 0) {
        int64_t* e = cut_slot(ref.rc);
        if (e[1] != ref.rk) {
          e[1] = ref.rk;
          need_start(ref.rc, ref.rk);
        }
      }
    }
    for (auto& [client, clock, ln] : applicable) {
      need_start(client, clock);
      need_start(client, clock + ln);
    }
    lap(kLapCuts);  // cuts-collect
    for (auto& [client, ks] : cuts) {
      // mostly-ascending in practice (origins chain forward); skip the
      // sort when the scan produced them in order.  Clocks are small
      // non-negative ints, so the unsorted case takes an LSD radix sort
      // (branch-free counting passes beat introsort's compares on these
      // ~1k-element lists).
      if (!std::is_sorted(ks.begin(), ks.end()))
        radix_sort_clocks(ks);
      ks.erase(std::unique(ks.begin(), ks.end()), ks.end());
    }

    lap(kLapCuts);
    // cuts inside existing rows: split + device link surgery
    size_t pre_split_marker = plan.splits.size();
    for (int64_t client : cut_clients) {
      auto sit = slot_of_client.find(client);
      if (sit == slot_of_client.end()) continue;
      int64_t slot_ = sit->second;
      for (int64_t k : cuts[client]) {
        int64_t fi = frag_containing(slot_, k);
        if (fi == kNull) continue;
        int64_t row = frag_row[slot_][(size_t)fi];
        if (r_is_gc[row] || r_clock[row] == k) continue;
        bool ok = true;
        split_existing(slot_, fi, k, &ok);
        if (!ok) return kErrMalformed;
      }
    }
    std::sort(plan.splits.begin() + pre_split_marker, plan.splits.end(),
              [](const std::array<int64_t, 2>& a,
                 const std::array<int64_t, 2>& b) {
                if (a[0] != b[0]) return a[0] < b[0];
                return a[1] > b[1];
              });

    lap(kLapCuts);  // pre-split
    // row assignment + pointer resolution, fragmenting each scheduled ref
    // by its client's cut set inline (same fragment order as the old
    // two-pass frag_sched build, without the fat-struct copy pass)
    reserve_rows(sched.size());
    std::vector<int64_t> touched_map_segs;  // ascending on use (set below)
    if (tm_mark.size() < dh_mark.size()) tm_mark.resize(dh_mark.size(), 0);
    // last row emit_row produced: rows emitted this pass are never split
    // again within the pass (all cuts were applied in pre-split or
    // inline), so containment against it is exact — chained refs adopt
    // their anchor without the fragment binary search
    bool em_last_valid = false;
    int64_t em_last_row = kNull, em_last_slot = kNull;
    int64_t em_last_clock = 0, em_last_len = 0;
    int64_t seg_fast_n = 0, seg_lookup_n = 0;
    auto emit_row = [&](const PendRef& ref) -> int {
      int64_t slot_ = slot(ref.client);
      if (ref.is_gc) {
        int64_t row = add_row(slot_, ref.clock, ref.length, kNull, 0, kNull,
                              0, true, ContentDesc{}, 0, kNull);
        em_last_valid = true;
        em_last_row = row;
        em_last_slot = slot_;
        em_last_clock = ref.clock;
        em_last_len = ref.length;
        return 0;
      }
      int64_t left_row = kNull, right_row = kNull;
      int64_t oslot = kNull, rslot = kNull;
      bool degrade = false;
      if (ref.oc >= 0) {
        oslot = slot(ref.oc);
        if (em_last_valid && oslot == em_last_slot &&
            ref.ok >= em_last_clock &&
            ref.ok < em_last_clock + em_last_len) {
          left_row = em_last_row;
          seg_fast_n++;
        } else {
          int64_t fi = frag_containing(oslot, ref.ok);
          if (fi == kNull) return kErrInternal;
          left_row = frag_row[oslot][(size_t)fi];
          seg_lookup_n++;
        }
        if (r_is_gc[left_row]) degrade = true;
      }
      if (ref.rc >= 0) {
        rslot = slot(ref.rc);
        if (em_last_valid && rslot == em_last_slot &&
            ref.rk >= em_last_clock &&
            ref.rk < em_last_clock + em_last_len) {
          right_row = em_last_row;
          seg_fast_n++;
        } else {
          int64_t fi = frag_containing(rslot, ref.rk);
          if (fi == kNull) return kErrInternal;
          right_row = frag_row[rslot][(size_t)fi];
          seg_lookup_n++;
        }
        if (r_is_gc[right_row]) degrade = true;
      }
      int64_t parent_row = kNull;
      if (!degrade && ref.pic >= 0) {
        int64_t pslot = slot(ref.pic);
        int64_t fi = frag_containing(pslot, ref.pik);
        if (fi == kNull) return kErrInternal;
        parent_row = frag_row[pslot][(size_t)fi];
        if (r_is_gc[parent_row] || r_ref[parent_row] != 7) degrade = true;
      }
      if (degrade) {
        int64_t row = add_row(slot_, ref.clock, ref.length, kNull, 0, kNull,
                              0, true, ContentDesc{}, 0, kNull);
        em_last_valid = true;
        em_last_row = row;
        em_last_slot = slot_;
        em_last_clock = ref.clock;
        em_last_len = ref.length;
        return 0;
      }
      int64_t sg;
      if (parent_row != kNull) {
        sg = seg(kNull, ref.sub_id, parent_row);
      } else if (ref.name_id != kNull) {
        sg = seg(ref.name_id, ref.sub_id, kNull);
      } else if (left_row != kNull) {
        sg = r_seg[left_row];
      } else if (right_row != kNull) {
        sg = r_seg[right_row];
      } else {
        return kErrUnsupported;  // item with no derivable parent
      }
      int64_t row = add_row(slot_, ref.clock, ref.length, oslot, ref.ok,
                            rslot, ref.rk, false, ref.c, ref.ref, sg);
      em_last_valid = true;
      em_last_row = row;
      em_last_slot = slot_;
      em_last_clock = ref.clock;
      em_last_len = ref.length;
      if (want_sched) plan.sched.push_back({{row, left_row, right_row, sg}});
      plan.rows_new++;
      if (seg_parent[sg] != kNull) plan.rows_nested++;
      if (seg_is_map(sg)) plan.rows_attr++;
      if (ref.ref == 6) plan.rows_format++;
      if (ref.ref == 7) plan.rows_type++;
      int64_t actual_left = list_insert(sg, row, left_row, right_row);
      if (seg_is_map(sg)) {
        auto& chain = map_chain[sg];
        if (actual_left == kNull) {
          chain.insert(chain.begin(), row);
        } else {
          auto it = std::find(chain.begin(), chain.end(), actual_left);
          chain.insert(it + 1, row);
        }
        if ((size_t)sg >= tm_mark.size()) tm_mark.resize((size_t)sg + 64, 0);
        if (tm_mark[(size_t)sg] != dirty_epoch) {
          tm_mark[(size_t)sg] = dirty_epoch;
          touched_map_segs.push_back(sg);
        }
      }
      int64_t pr = seg_parent[sg];
      if (pr != kNull && r_host_deleted[pr]) delete_row(row);
      if (ref.ref == 1)
        applicable.push_back({{ref.client, ref.clock, ref.length}});
      return 0;
    };
    // per-ref cuts lookup cache + rolling cut cursor: sched's clocks
    // ascend per client, so within a client run the cut cursor only moves
    // forward (amortized O(1)); a client switch re-seeks once.  The hash
    // find per ref is gone with it.
    int64_t cuts_cl_cache = INT64_MIN;
    std::vector<int64_t>* cuts_ks_cache = nullptr;
    size_t cuts_idx_cache = 0;
    for (const PendRef* rp0 : sched) {
      const PendRef& ref0 = *rp0;
      // length-1 refs can never be fragmented (no strictly-interior cut)
      std::vector<int64_t>* ks_p = nullptr;
      if (!ref0.is_gc && ref0.length > 1) {
        if (ref0.client == cuts_cl_cache) {
          ks_p = cuts_ks_cache;
        } else {
          auto cit = cuts.find(ref0.client);
          ks_p = cit == cuts.end() ? nullptr : &cit->second;
          cuts_cl_cache = ref0.client;
          cuts_ks_cache = ks_p;
          if (ks_p)
            cuts_idx_cache =
                std::upper_bound(ks_p->begin(), ks_p->end(), ref0.clock) -
                ks_p->begin();
        }
      }
      if (ks_p == nullptr) {
        int rc = emit_row(ref0);
        if (rc != 0) return rc;
        continue;
      }
      PendRef cur = ref0;
      auto& ks = *ks_p;
      while (cuts_idx_cache < ks.size() && ks[cuts_idx_cache] <= cur.clock)
        cuts_idx_cache++;
      for (size_t ki = cuts_idx_cache;
           ki < ks.size() && ks[ki] < ref0.clock + ref0.length; ++ki) {
        int64_t k = ks[ki];
        if (k <= cur.clock) continue;
        PendRef right = cur;
        int64_t off = k - cur.clock;
        bool ok = true;
        if (cur.c.kind != kKindNone) {
          right.c = desc_split(cur.c, cur.length, off, &ok);
          if (!ok) return kErrMalformed;
        }
        right.clock = cur.clock + off;
        right.length = cur.length - off;
        right.oc = cur.client;
        right.ok = right.clock - 1;
        cur.length = off;
        int rc = emit_row(cur);
        if (rc != 0) return rc;
        cur = right;
      }
      int rc = emit_row(cur);
      if (rc != 0) return rc;
    }

    if (seg_fast_n)
      g_seg_fast.fetch_add(seg_fast_n, std::memory_order_relaxed);
    if (seg_lookup_n)
      g_seg_lookup.fetch_add(seg_lookup_n, std::memory_order_relaxed);

    lap(kLapRows);
    // resolve delete ranges to row ids.  Ranges arrive grouped per
    // client (update DS sections are per-client), so a 1-entry slot memo
    // avoids a hash find per range; the memo must NOT create slots
    // (unknown clients in a DS are skipped, not integrated).
    int64_t del_cl_memo = INT64_MIN, del_slot_memo = kNull;
    for (size_t ai = 0; ai < applicable.size(); ai++) {
      auto [client, clock, ln] = applicable[ai];
      if (client != del_cl_memo) {
        auto sit = slot_of_client.find(client);
        del_cl_memo = client;
        del_slot_memo = sit == slot_of_client.end() ? kNull : sit->second;
      }
      if (del_slot_memo == kNull) continue;
      int64_t slot_ = del_slot_memo;
      auto& fc = frag_clock[slot_];
      auto& fr = frag_row[slot_];
      auto it = std::upper_bound(fc.begin(), fc.end(), clock);
      int64_t i = (int64_t)(it - fc.begin()) - 1;
      if (i < 0) i = 0;
      int64_t end = clock + ln;
      while (i < (int64_t)fc.size() && fc[(size_t)i] < end) {
        if (fc[(size_t)i] >= clock) delete_row(fr[(size_t)i]);
        i++;
      }
    }

    lap(kLapRows);  // deletes
    // LWW: sorted seg order (delete order is consumer-order-independent)
    std::sort(touched_map_segs.begin(), touched_map_segs.end());
    lww_pass(touched_map_segs);
    lap(kLapRows);  // lww
    plan.n_rows = n_rows();
    // ascending row/seg order = the Python twin's `sorted(plan._dl)`.
    // When the dirty set is DENSE in the row range (bulk first flush),
    // recollect it ascending by scanning the dl_mark epoch array — O(range)
    // sequential loads beat an O(n log n) sort.  Sparse incremental
    // flushes on big mirrors keep the sort.
    {
      size_t nd = plan.dirty_links.size();
      if (nd > 16 && (size_t)n_rows() / 16 < nd) {
        plan.dirty_links.clear();
        size_t hi = std::min(dl_mark.size(), (size_t)n_rows());
        for (size_t r = 0; r < hi; r++)
          if (dl_mark[r] == dirty_epoch) plan.dirty_links.push_back((int64_t)r);
      } else {
        std::sort(plan.dirty_links.begin(), plan.dirty_links.end());
      }
    }
    std::sort(plan.dirty_heads.begin(), plan.dirty_heads.end());
    plan.link_rows.reserve(plan.dirty_links.size());
    plan.link_vals.reserve(plan.dirty_links.size());
    for (int64_t r : plan.dirty_links) {
      plan.link_rows.push_back(r);
      plan.link_vals.push_back(list_next[(size_t)r]);
    }
    for (int64_t s : plan.dirty_heads) {
      plan.head_segs.push_back(s);
      plan.head_vals.push_back(head_of_seg[(size_t)s]);
    }
    // rebuild `pending` from the unconsumed working-set tails: only refs
    // that failed the causal gate get a fat copy (common case: none).
    // Deferred to here because sched/qwork hold pointers into the OLD
    // pending vectors until the rows pass is done.
    {
      std::map<int64_t, std::vector<PendRef>> new_pending;
      for (size_t ci = 0; ci < clients_desc.size(); ci++) {
        auto& w = *clients_desc[ci].second;
        size_t head = q_head[ci];
        if (head >= w.size()) continue;
        auto& q = new_pending[clients_desc[ci].first];
        q.reserve(w.size() - head);
        for (size_t j = head; j < w.size(); j++) q.push_back(*w[j]);
      }
      pending.swap(new_pending);
    }
    lap(kLapFinalize);
    gen++;
    return 0;
  }

  // ---- compaction (DocMirror.rebuild_compacted twin) --------------------

  // whether the content descriptors of rows a, b merge: the test alone,
  // so the question before a compaction (compact_changes) and the merge
  // (desc_merge) cannot come to differ
  bool desc_can_merge(int64_t a, int64_t b) const {
    const ContentDesc& ca = r_c[(size_t)a];
    const ContentDesc& cb = r_c[(size_t)b];
    if (ca.kind != cb.kind) return false;
    switch (ca.kind) {
      case kKindDeleted:
      case kKindUtf8:
        return true;
      case kKindAnys:
      case kKindJsons:
        return ca.v2 == cb.v2;
      default:
        return false;  // Framed/V2Lazy: length-1 kinds never merge
    }
  }

  // merge content descriptors of rows a,b, which desc_can_merge allows
  void desc_merge(int64_t a, int64_t b) {
    ContentDesc& ca = r_c[(size_t)a];
    ContentDesc& cb = r_c[(size_t)b];
    if (ca.kind == kKindDeleted) return;
    if (ca.buf == cb.buf && ca.end == cb.ofs) {
      ca.end = cb.end;  // naturally adjacent: extend in place
    } else {
      std::vector<uint8_t> merged(buf_ptr(ca.buf) + ca.ofs,
                                  buf_ptr(ca.buf) + ca.end);
      merged.insert(merged.end(), buf_ptr(cb.buf) + cb.ofs,
                    buf_ptr(cb.buf) + cb.end);
      int64_t nb = arena(std::move(merged));
      ca.buf = nb;
      ca.ofs = 0;
      ca.end = (int64_t)buf_len(nb);
    }
    if (ca.kind != kKindUtf8) ca.count += cb.count;
  }

  // whether a compaction merges row b into row a, its left neighbour in a
  // list (or, GC structs, in its client's clock order); writes nothing
  bool can_merge(int64_t a, int64_t b, const uint8_t* deleted) const {
    if (r_slot[a] != r_slot[b]) return false;
    if (r_clock[a] + r_len[a] != r_clock[b]) return false;
    if ((deleted[a] != 0) != (deleted[b] != 0)) return false;
    if (r_is_gc[a] != r_is_gc[b]) return false;
    if (!r_is_gc[a]) {  // GC structs merge on contiguity alone
      if (r_oslot[b] != r_slot[a] ||
          r_oclock[b] != r_clock[a] + r_len[a] - 1)
        return false;
      if (!row_right_eq(a, b)) return false;
      if (r_ref[a] != r_ref[b]) return false;
      if (!desc_can_merge(a, b)) return false;
    }
    // a nested type's row keeps its identity: its children name it
    return segs_of_parent.empty() ||
           !(segs_of_parent.count(a) || segs_of_parent.count(b));
  }

  bool try_merge(int64_t a, int64_t b, const uint8_t* deleted) {
    if (!can_merge(a, b, deleted)) return false;
    if (!r_is_gc[a]) desc_merge(a, b);
    return true;
  }

  // the question a compaction look asks before it rebuilds: would
  // compact(), fed the mirror's own lists (ymx_compact_self), change
  // anything a reader can see?  What compact() decides, by the test it
  // decides with (can_merge), and nothing built: a pair of list
  // neighbours that merge, a pair of a client's GC structs that merge, a
  // row whose content `gc` would turn into a tombstone.  Where none is
  // found `keep` is every row, renumber() is the identity on every column
  // and index, and the rows, deleted bits and heads compact() hands back
  // are list_next, r_host_deleted and head_of_seg as they stand.  Two
  // things it would still do are not counted: `gen` moves (it says "the
  // rows were renumbered", and they were not), and each client's
  // delete-set ranges are sorted and unioned in place, which no reader
  // sees (ymx_ds's callers and build_diff_prep union what they read).
  //
  // compact() tries the neighbours of every list that is no map chain
  // and, client by client, consecutive GC structs.  A pair merges only
  // where b starts at the clock a ends at (can_merge's first two tests):
  // where b follows a in their client's fragment index.  So the pairs
  // are read off that index, in the order the rows lie in memory, and
  // one that compact() would try (neighbours by list_next, or two GC
  // structs) is put to can_merge; a walk of the lists would chase every
  // column in document order to try the same pairs.  Returns at the first
  // change found: only a room with nothing to merge is read to its end.
  bool compact_changes(bool gc) const {
    const uint8_t* deleted = r_host_deleted.data();
    for (const auto& rows : frag_row) {
      for (size_t k = 1; k < rows.size(); k++) {
        int64_t a = rows[k - 1], b = rows[k];
        bool tried = (r_is_gc[a] && r_is_gc[b]) ||
                     (list_next[(size_t)a] == b && r_seg[a] != kNull &&
                      !seg_is_map(r_seg[a]));
        if (tried && can_merge(a, b, deleted)) return true;
      }
    }
    if (gc) {
      int64_t n = n_rows();
      for (int64_t row = 0; row < n; row++)
        if (deleted[row] && !r_is_gc[row] && r_ref[row] != 1) return true;
    }
    return false;
  }

  // renumber every host structure after compaction decided `keep`
  void renumber(const std::vector<int64_t>& keep,
                const std::vector<int64_t>& new_of_old) {
    auto take_i = [&](std::vector<int64_t>& col) {
      std::vector<int64_t> out;
      out.reserve(keep.size());
      for (int64_t r : keep) out.push_back(col[(size_t)r]);
      col = std::move(out);
    };
    auto take_b = [&](std::vector<uint8_t>& col) {
      std::vector<uint8_t> out;
      out.reserve(keep.size());
      for (int64_t r : keep) out.push_back(col[(size_t)r]);
      col = std::move(out);
    };
    take_i(r_slot); take_i(r_clock); take_i(r_len);
    take_i(r_oslot); take_i(r_oclock); take_i(r_rslot); take_i(r_rclock);
    take_i(r_ref); take_i(r_seg);
    take_b(r_is_gc); take_b(r_countable);
    take_b(r_host_deleted); take_b(r_lww_deleted);
    {
      std::vector<ContentDesc> out;
      out.reserve(keep.size());
      for (int64_t r : keep) out.push_back(r_c[(size_t)r]);
      r_c = std::move(out);
    }
    gen++;
    // fragment index: rebuild clock-sorted per slot
    size_t n_slots = client_of_slot.size();
    for (size_t s = 0; s < n_slots; s++) {
      frag_clock[s].clear();
      frag_row[s].clear();
    }
    std::vector<std::vector<int64_t>> by_slot(n_slots);
    for (size_t row = 0; row < r_slot.size(); row++)
      by_slot[(size_t)r_slot[row]].push_back((int64_t)row);
    for (size_t s = 0; s < n_slots; s++) {
      auto& rows = by_slot[s];
      std::sort(rows.begin(), rows.end(), [&](int64_t a, int64_t b) {
        return r_clock[a] < r_clock[b];
      });
      for (int64_t r : rows) {
        frag_clock[s].push_back(r_clock[r]);
        frag_row[s].push_back(r);
      }
    }
    // map chains / nested bookkeeping
    for (auto& [sg, chain] : map_chain) {
      std::vector<int64_t> out;
      for (int64_t r : chain)
        if (new_of_old[(size_t)r] != kNull)
          out.push_back(new_of_old[(size_t)r]);
      chain = std::move(out);
    }
    {
      std::unordered_map<int64_t, std::vector<int64_t>> out;
      for (auto& [sg, rows] : rows_of_seg) {
        std::vector<int64_t> nr;
        for (int64_t r : rows)
          if (new_of_old[(size_t)r] != kNull)
            nr.push_back(new_of_old[(size_t)r]);
        out[sg] = std::move(nr);
      }
      rows_of_seg = std::move(out);
    }
    {
      // seg parents renumber (type rows never merge, so they survive)
      std::map<std::tuple<int64_t, int64_t, int64_t>, int64_t> lookup;
      std::unordered_map<int64_t, std::vector<int64_t>> parents;
      for (int64_t s = 0; s < n_segs(); s++) {
        if (seg_parent[s] != kNull)
          seg_parent[s] = new_of_old[(size_t)seg_parent[s]];
        lookup[std::make_tuple(seg_name_id[s], seg_sub_id[s],
                               seg_parent[s])] = s;
        if (seg_parent[s] != kNull) parents[seg_parent[s]].push_back(s);
      }
      seg_lookup = std::move(lookup);
      segs_of_parent = std::move(parents);
    }
    // compact DS ranges (sorted union per slot)
    for (auto& ranges : ds) {
      if (ranges.empty()) continue;
      std::sort(ranges.begin(), ranges.end());
      std::vector<std::array<int64_t, 2>> out;
      for (auto& [clock, ln] : ranges) {
        if (!out.empty() && clock <= out.back()[0] + out.back()[1]) {
          out.back()[1] =
              std::max(out.back()[1], clock + ln - out.back()[0]);
        } else {
          out.push_back({{clock, ln}});
        }
      }
      ranges = std::move(out);
    }
  }

  // full compaction entry: device read-back in, renumbered device state out
  int64_t compact(const int32_t* right_link, const uint8_t* deleted,
                  const int32_t* heads, int64_t n_heads, int gc,
                  int32_t* new_right, uint8_t* new_deleted,
                  int32_t* new_heads, int64_t new_heads_cap) {
    int64_t n = n_rows();
    // per-seg order from the read-back links
    std::vector<std::vector<int64_t>> order_of_seg((size_t)n_segs());
    for (int64_t sg = 0; sg < n_segs(); sg++) {
      int64_t head = sg < n_heads ? heads[sg] : kNull;
      int64_t r = head;
      while (r != kNull) {
        order_of_seg[(size_t)sg].push_back(r);
        r = right_link[r];
      }
    }
    if (gc) {
      for (int64_t row = 0; row < n; row++) {
        if (!r_is_gc[row] && deleted[row] && r_ref[row] != 1) {
          r_c[(size_t)row] = ContentDesc{};
          r_c[(size_t)row].kind = kKindDeleted;
          r_ref[row] = 1;
          r_countable[row] = 0;
        }
      }
    }
    std::unordered_map<int64_t, int64_t> absorbed;
    for (int64_t sg = 0; sg < n_segs(); sg++) {
      if (seg_is_map(sg)) continue;
      auto& order = order_of_seg[(size_t)sg];
      size_t i = 0;
      while (i + 1 < order.size()) {
        int64_t a = order[i], b = order[i + 1];
        if (try_merge(a, b, deleted)) {
          r_len[a] += r_len[b];
          absorbed[b] = a;
          order.erase(order.begin() + (ptrdiff_t)(i + 1));
        } else {
          i++;
        }
      }
    }
    // GC structs: merge contiguous runs per client (not in any list)
    for (size_t s = 0; s < client_of_slot.size(); s++) {
      int64_t prev = kNull;
      for (int64_t row : frag_row[s]) {
        if (!r_is_gc[row] || absorbed.count(row)) {
          prev = r_is_gc[row] ? row : kNull;
          continue;
        }
        if (prev != kNull && try_merge(prev, row, deleted)) {
          r_len[prev] += r_len[row];
          absorbed[row] = prev;
        } else {
          prev = row;
        }
      }
    }
    std::vector<int64_t> new_of_old((size_t)n, kNull);
    std::vector<int64_t> keep;
    keep.reserve((size_t)n);
    for (int64_t r = 0; r < n; r++) {
      if (!absorbed.count(r)) {
        new_of_old[(size_t)r] = (int64_t)keep.size();
        keep.push_back(r);
      }
    }
    renumber(keep, new_of_old);
    int64_t n_new = (int64_t)keep.size();
    for (int64_t r = 0; r < n_new; r++) {
      new_right[r] = (int32_t)kNull;
      new_deleted[r] = deleted[keep[(size_t)r]];
    }
    for (int64_t sg = 0; sg < std::min(new_heads_cap, n_segs()); sg++)
      new_heads[sg] = (int32_t)kNull;
    list_next.assign((size_t)n_new, kNull);
    head_of_seg.assign((size_t)n_segs(), kNull);
    for (int64_t sg = 0; sg < n_segs(); sg++) {
      int64_t prev = kNull;
      for (int64_t old : order_of_seg[(size_t)sg]) {
        int64_t nr = new_of_old[(size_t)old];
        if (prev == kNull) {
          if (sg < new_heads_cap) new_heads[sg] = (int32_t)nr;
          head_of_seg[(size_t)sg] = nr;
        } else {
          new_right[prev] = (int32_t)nr;
          list_next[(size_t)prev] = nr;
        }
        prev = nr;
      }
    }
    return n_new;
  }
};

// shared by the V1/V2 diff writers: remote state per slot, slot order
// (descending client), and the DS section groups
struct DiffPrep {
  std::vector<int64_t> remote;
  std::vector<size_t> slot_order;
  std::vector<int64_t> dg_client, dg_start, dg_len, d_clock, d_len;
};

inline void build_diff_prep(Mirror* m, const int64_t* sv_clients,
                            const int64_t* sv_clocks, int64_t n_sv,
                            const int64_t* ds_ranges, int64_t n_ds_override,
                            int ds_override, DiffPrep* p) {
  size_t n_slots = m->client_of_slot.size();
  p->remote.assign(n_slots, 0);
  p->dg_client.clear();  // a DiffPrep may be reused from room to room
  p->dg_start.clear();
  p->dg_len.clear();
  p->d_clock.clear();
  p->d_len.clear();
  for (int64_t i = 0; i < n_sv; i++) {
    auto it = m->slot_of_client.find(sv_clients[i]);
    if (it != m->slot_of_client.end())
      p->remote[(size_t)it->second] = sv_clocks[i];
  }
  p->slot_order.resize(n_slots);
  for (size_t s = 0; s < n_slots; s++) p->slot_order[s] = s;
  std::sort(p->slot_order.begin(), p->slot_order.end(),
            [&](size_t a, size_t b) {
              return m->client_of_slot[a] > m->client_of_slot[b];
            });
  auto push_union = [&](int64_t client,
                        std::vector<std::array<int64_t, 2>>& ranges) {
    std::sort(ranges.begin(), ranges.end());
    size_t start = p->d_clock.size();
    for (auto& [ck, ln] : ranges) {
      if (p->d_clock.size() > start &&
          ck <= p->d_clock.back() + p->d_len.back()) {
        p->d_len.back() =
            std::max(p->d_len.back(), ck + ln - p->d_clock.back());
      } else {
        p->d_clock.push_back(ck);
        p->d_len.push_back(ln);
      }
    }
    if (p->d_clock.size() > start) {
      p->dg_client.push_back(client);
      p->dg_start.push_back((int64_t)start);
      p->dg_len.push_back((int64_t)(p->d_clock.size() - start));
    }
  };
  if (ds_override) {
    std::vector<int64_t> order;
    std::unordered_map<int64_t, std::vector<std::array<int64_t, 2>>> by;
    for (int64_t i = 0; i < n_ds_override; i++) {
      int64_t cl = ds_ranges[i * 3];
      if (!by.count(cl)) order.push_back(cl);
      by[cl].push_back({{ds_ranges[i * 3 + 1], ds_ranges[i * 3 + 2]}});
    }
    for (int64_t cl : order) push_union(cl, by[cl]);
  } else {
    for (int64_t slot : m->ds_slot_order) {
      auto ranges = m->ds[slot];  // copy: union sorts
      push_union(m->client_of_slot[(size_t)slot], ranges);
    }
  }
}

// ---------------------------------------------------------------------------
// native V2 wire writer: the 9-stream columnar container (reference
// UpdateEncoder.js:264-408; byte-identical to yjs_tpu/coding.py
// UpdateEncoderV2, including the never-populated key_map quirk)
// ---------------------------------------------------------------------------

struct VecW {
  std::vector<uint8_t> b;
  void u8(uint8_t x) { b.push_back(x); }
  void varuint(uint64_t n) {
    while (n > 0x7f) { b.push_back(0x80 | (n & 0x7f)); n >>= 7; }
    b.push_back((uint8_t)n);
  }
  // lib0 signed varint (sign-magnitude, 6 bits in the first byte)
  void varint(int64_t num, bool neg_zero = false) {
    bool neg = num < 0 || neg_zero;
    uint64_t n = neg ? (uint64_t)(-num) : (uint64_t)num;
    b.push_back((n > 0x3f ? 0x80 : 0) | (neg ? 0x40 : 0) | (n & 0x3f));
    n >>= 6;
    while (n > 0) { b.push_back((n > 0x7f ? 0x80 : 0) | (n & 0x7f)); n >>= 7; }
  }
  void bytes(const uint8_t* p, size_t n) { b.insert(b.end(), p, p + n); }
};

struct RleW {  // lib0 RleEncoder over write_uint8 (no trailing count)
  VecW o;
  int64_t s = 0, count = 0;
  void write(int64_t v) {
    if (s == v && count > 0) { count++; return; }
    if (count > 0) o.varuint((uint64_t)(count - 1));
    count = 1;
    o.u8((uint8_t)v);
    s = v;
  }
};

struct UintOptW {  // lib0 UintOptRleEncoder
  VecW o;
  int64_t s = 0, count = 0;
  void write(int64_t v) {
    if (s == v) { count++; return; }
    flush();
    count = 1;
    s = v;
  }
  void flush() {
    if (count > 0) {
      if (count == 1) o.varint(s);
      else { o.varint(-s, s == 0); o.varuint((uint64_t)(count - 2)); }
    }
  }
};

struct IntDiffOptW {  // lib0 IntDiffOptRleEncoder
  VecW o;
  int64_t s = 0, count = 0, diff = 0;
  void write(int64_t v) {
    if (diff == v - s) { s = v; count++; return; }
    flush();
    count = 1;
    diff = v - s;
    s = v;
  }
  void flush() {
    if (count > 0) {
      o.varint(diff * 2 + (count == 1 ? 0 : 1));
      if (count > 1) o.varuint((uint64_t)(count - 2));
    }
  }
};

inline int64_t utf16_len_of(const uint8_t* p, uint64_t n);

struct StringW {  // lib0 StringEncoder: one UTF-8 arena + u16-length runs
  std::vector<uint8_t> arena;
  UintOptW lens;
  // append a raw UTF-8 range; u16len = its UTF-16 unit count
  void write(const uint8_t* p, size_t n, int64_t u16len) {
    arena.insert(arena.end(), p, p + n);
    lens.write(u16len);
  }
  // cut `off` UTF-16 units off the front (the partial-first-struct rule),
  // with the surrogate-pair U+FFFD repair of write_cut_string
  // false on a truncated trailing multi-byte sequence (the skip loop
  // would overshoot — same guard as desc_split's surrogate branch)
  bool write_cut(const uint8_t* s, uint64_t blen, int64_t off) {
    uint64_t i = 0;
    bool mid = false;
    int64_t skipped = 0;
    int64_t total = utf16_len_of(s, blen);
    while (skipped < off && i < blen) {
      uint8_t c = s[i];
      if (c < 0x80) { skipped += 1; i += 1; }
      else if (c < 0xE0) { skipped += 1; i += 2; }
      else if (c < 0xF0) { skipped += 1; i += 3; }
      else {
        skipped += 2; i += 4;
        if (skipped > off) mid = true;
      }
    }
    if (i > blen) return false;  // malformed UTF-8 tail
    if (mid) {  // the cut consumed a pair: emit the U+FFFD low half
      static const uint8_t rep[3] = {0xEF, 0xBF, 0xBD};
      arena.insert(arena.end(), rep, rep + 3);
    }
    arena.insert(arena.end(), s + i, s + blen);
    lens.write(total - off);
    return true;
  }
  void emit(VecW* out) {
    // StringEncoder.to_bytes = var_string(arena) + RAW lens bytes, the
    // whole thing wrapped in the container's var_uint8_array
    UintOptW tmp = lens;  // copy: flush is destructive
    tmp.flush();
    VecW inner;
    inner.varuint(arena.size());
    inner.bytes(arena.data(), arena.size());
    inner.bytes(tmp.o.b.data(), tmp.o.b.size());
    out->varuint(inner.b.size());
    out->bytes(inner.b.data(), inner.b.size());
  }
};

struct V2W {
  IntDiffOptW key_clock;
  UintOptW client;
  IntDiffOptW left_clock;
  IntDiffOptW right_clock;
  RleW info;
  StringW str;
  RleW parent_info;
  UintOptW type_ref;
  UintOptW len;
  VecW rest;
  int64_t key_counter = 0;

  void write_left_id(int64_t c, int64_t k) { client.write(c); left_clock.write(k); }
  void write_right_id(int64_t c, int64_t k) { client.write(c); right_clock.write(k); }
  // the v13.4.9 write_key quirk: the dictionary is never populated, so
  // every key emits a fresh clock AND the string (UpdateEncoder.js:399-407)
  void write_key(const uint8_t* p, size_t n, int64_t u16len) {
    key_clock.write(key_counter++);
    str.write(p, n, u16len);
  }

  std::vector<uint8_t> finish() {
    VecW out;
    out.u8(0);  // feature flag
    auto stream = [&](VecW& v) {
      out.varuint(v.b.size());
      out.bytes(v.b.data(), v.b.size());
    };
    auto opt = [&](UintOptW& e) { UintOptW t = e; t.flush(); stream(t.o); };
    auto idf = [&](IntDiffOptW& e) { IntDiffOptW t = e; t.flush(); stream(t.o); };
    idf(key_clock);
    opt(client);
    idf(left_clock);
    idf(right_clock);
    stream(info.o);
    str.emit(&out);
    stream(parent_info.o);
    opt(type_ref);
    opt(len);
    out.bytes(rest.b.data(), rest.b.size());
    return std::move(out.b);
  }
};

inline int64_t utf16_len_of(const uint8_t* p, uint64_t n) {
  int64_t u = 0;
  for (uint64_t i = 0; i < n;) {
    uint8_t c = p[i];
    if (c < 0x80) { u += 1; i += 1; }
    else if (c < 0xE0) { u += 1; i += 2; }
    else if (c < 0xF0) { u += 1; i += 3; }
    else { u += 2; i += 4; }
  }
  return u;
}

// full-native V2 sync encode (the V2 twin of mirror_encode_diff).
// Returns the update bytes via `out` vector; -7 when the selection needs
// the Python writer (V1-framed embed/format/type or spilled content).
int64_t mirror_encode_diff_v2(Mirror* m, const int64_t* sv_clients,
                              const int64_t* sv_clocks, int64_t n_sv,
                              const int64_t* ds_ranges, int64_t n_ds_override,
                              int ds_override,
                              std::vector<uint8_t>* out_bytes) {
  DiffPrep prep;
  build_diff_prep(m, sv_clients, sv_clocks, n_sv, ds_ranges, n_ds_override,
                  ds_override, &prep);
  auto& remote = prep.remote;
  auto& slot_order = prep.slot_order;
  // selection per slot (rows in clock order via the frag index)
  std::vector<std::pair<size_t, std::vector<int64_t>>> groups;
  for (size_t si : slot_order) {
    std::vector<int64_t> rows;
    int64_t rem = remote[si];
    for (int64_t r : m->frag_row[si])
      if (m->r_clock[r] + m->r_len[r] > rem) rows.push_back(r);
    if (!rows.empty()) groups.push_back({si, std::move(rows)});
  }
  // scope check first: fall back before writing anything
  for (auto& [si, rows] : groups) {
    for (int64_t r : rows) {
      const ContentDesc& c = m->r_c[(size_t)r];
      if (c.kind == kKindSpill) return -7;
      if (c.kind == kKindFramed && m->r_ref[r] != 3) return -7;
    }
  }
  V2W w;
  w.rest.varuint(groups.size());
  for (auto& [si, rows] : groups) {
    int64_t rem = remote[si];
    w.rest.varuint(rows.size());
    w.client.write(m->client_of_slot[si]);
    int64_t first_ofs = std::max<int64_t>(0, rem - m->r_clock[rows[0]]);
    w.rest.varuint((uint64_t)(m->r_clock[rows[0]] + first_ofs));
    bool first = true;
    for (int64_t r : rows) {
      int64_t ofs = first ? first_ofs : 0;
      first = false;
      const ContentDesc& c = m->r_c[(size_t)r];
      int64_t ref = m->r_ref[r];
      if (m->r_is_gc[r]) {
        w.info.write(0);
        w.len.write(m->r_len[r] - ofs);
        continue;
      }
      int64_t oc = m->r_oslot[r] == kNull
                       ? kNull
                       : m->client_of_slot[(size_t)m->r_oslot[r]];
      int64_t ok = m->r_oclock[r];
      if (ofs > 0) { oc = m->client_of_slot[si]; ok = m->r_clock[r] + ofs - 1; }
      int64_t rc = m->r_rslot[r] == kNull
                       ? kNull
                       : m->client_of_slot[(size_t)m->r_rslot[r]];
      int64_t rk = m->r_rclock[r];
      int64_t sg = m->r_seg[r];
      int64_t ni = sg == kNull ? kNull : m->seg_name_id[sg];
      int64_t sui = sg == kNull ? kNull : m->seg_sub_id[sg];
      int64_t pr = sg == kNull ? kNull : m->seg_parent[sg];
      uint8_t inf = (uint8_t)(ref & kBits5);
      if (oc >= 0) inf |= kBit8;
      if (rc >= 0) inf |= kBit7;
      if (sui != kNull) inf |= kBit6;
      w.info.write(inf);
      if (oc >= 0) w.write_left_id(oc, ok);
      if (rc >= 0) w.write_right_id(rc, rk);
      if (oc < 0 && rc < 0) {
        if (pr != kNull) {
          w.parent_info.write(0);
          w.write_left_id(
              m->client_of_slot[(size_t)m->r_slot[(size_t)pr]],
              m->r_clock[(size_t)pr]);
        } else if (ni != kNull) {
          w.parent_info.write(1);
          const uint8_t* np = m->strings.data() + m->intern_ofs[(size_t)ni];
          size_t nl = (size_t)m->intern_len[(size_t)ni];
          w.str.write(np, nl, utf16_len_of(np, nl));
        } else {
          return -3;
        }
        if (sui != kNull) {
          const uint8_t* sp = m->strings.data() + m->intern_ofs[(size_t)sui];
          size_t sl = (size_t)m->intern_len[(size_t)sui];
          w.str.write(sp, sl, utf16_len_of(sp, sl));
        }
      }
      // content (write order matches the Python Content*.write methods)
      switch (c.kind) {
        case kKindDeleted:
          w.len.write(m->r_len[r] - ofs);
          break;
        case kKindUtf8:
          if (!w.str.write_cut(m->buf_ptr(c.buf) + c.ofs,
                               (uint64_t)(c.end - c.ofs), ofs))
            return -4;
          break;
        case kKindAnys: {  // write_len + element any bytes into rest
          w.len.write(c.count - ofs);
          Reader er{m->buf_ptr(c.buf), (uint64_t)c.end, (uint64_t)c.ofs,
                    false};
          for (int64_t i = 0; i < ofs && !er.fail; i++) er.skip_any();
          if (er.fail) return -4;
          w.rest.bytes(m->buf_ptr(c.buf) + er.pos,
                       (size_t)(c.end - (int64_t)er.pos));
          break;
        }
        case kKindJsons: {  // write_len + each element into the str stream
          w.len.write(c.count - ofs);
          Reader er{m->buf_ptr(c.buf), (uint64_t)c.end, (uint64_t)c.ofs,
                    false};
          for (int64_t i = 0; i < c.count && !er.fail; i++) {
            uint64_t o, bl;
            er.var_string(&o, &bl);
            if (i >= ofs)
              w.str.write(m->buf_ptr(c.buf) + o, (size_t)bl,
                          utf16_len_of(m->buf_ptr(c.buf) + o, bl));
          }
          if (er.fail) return -4;
          break;
        }
        case kKindFramed:  // ref 3 only (checked above): varuint+bytes
          w.rest.bytes(m->buf_ptr(c.buf) + c.ofs, (size_t)(c.end - c.ofs));
          break;
        case kKindV2Lazy: {
          if (ref == 5) {  // embed: any into rest
            w.rest.bytes(m->buf_ptr(c.buf) + c.ofs,
                         (size_t)(c.end - c.ofs));
          } else if (ref == 6) {  // format: key via write_key, value any
            const uint8_t* kp = m->buf_ptr(c.buf) + c.ofs;
            size_t kl = (size_t)(c.end - c.ofs);
            w.write_key(kp, kl, utf16_len_of(kp, kl));
            w.rest.bytes(m->buf_ptr(c.buf) + c.ofs2,
                         (size_t)(c.end2 - c.ofs2));
          } else if (ref == 7) {  // type: type_ref (+ name via write_key)
            w.type_ref.write(c.count);
            if (c.count == 3 || c.count == 5) {
              if (c.ofs < 0) return -7;
              const uint8_t* np2 = m->buf_ptr(c.buf) + c.ofs;
              size_t nl2 = (size_t)(c.end - c.ofs);
              w.write_key(np2, nl2, utf16_len_of(np2, nl2));
            }
          } else {
            return -7;
          }
          break;
        }
        default:
          return -7;
      }
    }
  }
  // DS section (DSEncoderV2: delta clocks, len-1; groups from
  // build_diff_prep)
  auto& dg_client = prep.dg_client;
  auto& dg_start = prep.dg_start;
  auto& dg_len = prep.dg_len;
  auto& d_clock = prep.d_clock;
  auto& d_len = prep.d_len;
  w.rest.varuint(dg_client.size());
  for (size_t g = 0; g < dg_client.size(); g++) {
    int64_t cur = 0;
    w.rest.varuint((uint64_t)dg_client[g]);
    w.rest.varuint((uint64_t)dg_len[g]);
    for (int64_t i = dg_start[g]; i < dg_start[g] + dg_len[g]; i++) {
      w.rest.varuint((uint64_t)(d_clock[(size_t)i] - cur));
      cur = d_clock[(size_t)i];
      if (d_len[(size_t)i] <= 0) return -4;
      w.rest.varuint((uint64_t)(d_len[(size_t)i] - 1));
      cur += d_len[(size_t)i];
    }
  }
  *out_bytes = w.finish();
  return (int64_t)out_bytes->size();
}

}  // namespace

// the V1 wire writer (transcode.cpp, same shared object)
extern "C" int64_t ytpu_encode_v1(
    const uint8_t** bufs, const uint64_t* buf_lens, uint64_t n_bufs,
    const int64_t* group_client, const int64_t* group_start,
    const int64_t* group_len, uint64_t n_groups,
    const int64_t* clock, const int64_t* length, const int64_t* offset,
    const int64_t* origin_client, const int64_t* origin_clock,
    const int64_t* right_client, const int64_t* right_clock,
    const int64_t* content_ref,
    const int64_t* name_ofs, const int64_t* name_len,
    const int64_t* sub_ofs, const int64_t* sub_len,
    const int64_t* parent_client, const int64_t* parent_clock,
    const int64_t* src_kind, const int64_t* src_buf,
    const int64_t* src_ofs, const int64_t* src_end,
    const uint8_t* strings, uint64_t strings_len,
    const int64_t* ds_group_client, const int64_t* ds_group_start,
    const int64_t* ds_group_len, uint64_t n_ds_groups,
    const int64_t* ds_clock, const int64_t* ds_len,
    uint8_t* out, uint64_t out_cap);

namespace {


const uint8_t kNoBuf = 0;  // stands in for an empty buffer table or blob

// what one V1 diff encode selects: the write groups and the 18 per-row
// columns ytpu_encode_v1 reads, the DS section, the buffer table.  Kept
// by the caller, so a call over many rooms allocates once.
struct DiffCols {
  DiffPrep prep;
  std::vector<int64_t> g_client, g_start, g_len;
  std::vector<int64_t> c_clock, c_len, c_ofs, c_oc, c_ok, c_rc, c_rk, c_ref;
  std::vector<int64_t> c_no, c_nl, c_so, c_sl, c_pc, c_pk;
  std::vector<int64_t> c_sk, c_sb, c_sofs, c_send;
  std::vector<const uint8_t*> bptrs;
  std::vector<uint64_t> blens;
  // per slot, where the rows selected begin in its fragment index
  std::vector<size_t> first_frag;
  // no encode of the selection is longer than this
  int64_t bound = 0;

  std::array<std::vector<int64_t>*, 18> row_cols() {
    return {{&c_clock, &c_len, &c_ofs, &c_oc, &c_ok, &c_rc, &c_rk, &c_ref,
             &c_no, &c_nl, &c_so, &c_sl, &c_pc, &c_pk, &c_sk, &c_sb,
             &c_sofs, &c_send}};
  }
};

// rows beyond a remote state vector, selected from the mirror state in
// write order (reference encodeStateAsUpdate, encoding.js:490-526 +
// writeClientsStructs :94-116).  0, or -7 when a selected row needs the
// Python spill path (V2-framed embed/format/type payloads).
int64_t select_diff(Mirror* m, const int64_t* sv_clients,
                    const int64_t* sv_clocks, int64_t n_sv,
                    const int64_t* ds_ranges, int64_t n_ds_override,
                    int ds_override, DiffCols* d) {
  build_diff_prep(m, sv_clients, sv_clocks, n_sv, ds_ranges, n_ds_override,
                  ds_override, &d->prep);
  auto& remote = d->prep.remote;
  // slots in descending client order ("heavily improves the conflict
  // algorithm", encoding.js:112)
  auto& slot_order = d->prep.slot_order;
  // a slot's fragments are sorted by clock and do not overlap, so the
  // rows that end beyond the remote clock are a suffix of its index:
  // find where each begins, and the count of rows selected is known
  // before a column is written
  std::vector<size_t>& first = d->first_frag;
  first.assign(remote.size(), 0);
  size_t n_sel = 0;
  for (size_t si : slot_order) {
    const auto& fc = m->frag_clock[si];
    const auto& fr = m->frag_row[si];
    int64_t rem = remote[si];
    size_t i = 0;
    if (rem > 0) {
      i = (size_t)(std::upper_bound(fc.begin(), fc.end(), rem) - fc.begin());
      if (i > 0) {
        int64_t r = fr[i - 1];
        if (m->r_clock[r] + m->r_len[r] > rem) i--;
      }
    }
    first[si] = i;
    n_sel += fr.size() - i;
  }
  for (auto* v : d->row_cols()) {
    v->clear();
    v->reserve(n_sel);
  }
  d->g_client.clear();
  d->g_start.clear();
  d->g_len.clear();
  int64_t bound = 32;
  for (size_t si : slot_order) {
    int64_t rem = remote[si];
    size_t start = d->c_clock.size();
    const auto& fr = m->frag_row[si];
    for (size_t fi = first[si]; fi < fr.size(); fi++) {
      int64_t r = fr[fi];
      const ContentDesc& c = m->r_c[(size_t)r];
      if (c.kind == kKindV2Lazy || c.kind == kKindSpill) return -7;
      int64_t off = std::max<int64_t>(0, rem - m->r_clock[r]);
      d->c_clock.push_back(m->r_clock[r]);
      d->c_len.push_back(m->r_len[r]);
      d->c_ofs.push_back(off);
      d->c_oc.push_back(m->r_oslot[r] == kNull
                            ? kNull
                            : m->client_of_slot[(size_t)m->r_oslot[r]]);
      d->c_ok.push_back(m->r_oclock[r]);
      d->c_rc.push_back(m->r_rslot[r] == kNull
                            ? kNull
                            : m->client_of_slot[(size_t)m->r_rslot[r]]);
      d->c_rk.push_back(m->r_rclock[r]);
      d->c_ref.push_back(m->r_ref[r]);
      int64_t sg = m->r_seg[r];
      int64_t ni = sg == kNull ? kNull : m->seg_name_id[sg];
      int64_t sui = sg == kNull ? kNull : m->seg_sub_id[sg];
      int64_t pr = sg == kNull ? kNull : m->seg_parent[sg];
      int64_t nl = ni == kNull ? 0 : m->intern_len[(size_t)ni];
      int64_t sl = sui == kNull ? 0 : m->intern_len[(size_t)sui];
      d->c_no.push_back(ni == kNull ? kNull : m->intern_ofs[(size_t)ni]);
      d->c_nl.push_back(nl);
      d->c_so.push_back(sui == kNull ? kNull : m->intern_ofs[(size_t)sui]);
      d->c_sl.push_back(sl);
      d->c_pc.push_back(
          pr == kNull ? kNull
                      : m->client_of_slot[(size_t)m->r_slot[(size_t)pr]]);
      d->c_pk.push_back(pr == kNull ? 0 : m->r_clock[(size_t)pr]);
      d->c_sk.push_back(m->r_is_gc[r] ? kSrcNone : c.kind);
      d->c_sb.push_back(c.buf);
      d->c_sofs.push_back(c.ofs);
      d->c_send.push_back(c.end);
      // info byte, four ids, parent info with both strings, the
      // content's own length prefix and a cut surrogate's U+FFFD: 96
      // covers them at ten bytes a varuint
      bound += 96 + nl + sl +
               ((c.end >= 0 && c.ofs >= 0) ? (c.end - c.ofs) : 16);
    }
    if (d->c_clock.size() > start) {
      d->g_client.push_back(m->client_of_slot[si]);
      d->g_start.push_back((int64_t)start);
      d->g_len.push_back((int64_t)(d->c_clock.size() - start));
      bound += 32;
    }
  }
  d->bound = bound + 32 * (int64_t)d->prep.dg_client.size() +
             24 * (int64_t)d->prep.d_clock.size();
  d->bptrs.clear();
  d->blens.clear();
  d->bptrs.reserve(m->bufs.size() + 1);
  d->blens.reserve(m->bufs.size() + 1);
  for (auto& [p, ln] : m->bufs) {
    d->bptrs.push_back(p);
    d->blens.push_back(ln);
  }
  if (d->bptrs.empty()) {
    d->bptrs.push_back(&kNoBuf);
    d->blens.push_back(0);
  }
  return 0;
}

// the selection written as one V1 update.  Returns bytes written, <0 on
// writer errors (-2: `cap` was too small).
int64_t write_diff(Mirror* m, DiffCols& d, uint8_t* out, uint64_t cap) {
  static const int64_t kZero = 0;
  auto dat = [](std::vector<int64_t>& v) {
    return v.empty() ? &kZero : v.data();
  };
  auto& p = d.prep;
  return ytpu_encode_v1(
      d.bptrs.data(), d.blens.data(), d.bptrs.size(),
      dat(d.g_client), dat(d.g_start), dat(d.g_len), d.g_client.size(),
      dat(d.c_clock), dat(d.c_len), dat(d.c_ofs),
      dat(d.c_oc), dat(d.c_ok), dat(d.c_rc), dat(d.c_rk), dat(d.c_ref),
      dat(d.c_no), dat(d.c_nl), dat(d.c_so), dat(d.c_sl), dat(d.c_pc),
      dat(d.c_pk), dat(d.c_sk), dat(d.c_sb), dat(d.c_sofs), dat(d.c_send),
      m->strings.empty() ? &kNoBuf : m->strings.data(), m->strings.size(),
      dat(p.dg_client), dat(p.dg_start), dat(p.dg_len), p.dg_client.size(),
      dat(p.d_clock), dat(p.d_len), out, cap);
}

// full-native sync encode into a buffer of the caller's.  Returns bytes
// written, -7 for the Python spill path, <0 on writer errors.
int64_t mirror_encode_diff(Mirror* m, const int64_t* sv_clients,
                           const int64_t* sv_clocks, int64_t n_sv,
                           const int64_t* ds_ranges, int64_t n_ds_override,
                           int ds_override, uint8_t* out, uint64_t cap) {
  DiffCols d;
  int64_t rc = select_diff(m, sv_clients, sv_clocks, n_sv, ds_ranges,
                           n_ds_override, ds_override, &d);
  if (rc != 0) return rc;
  return write_diff(m, d, out, cap);
}

// the bytes of the last ymx_encode_steps_many this thread made: the
// library keeps the buffer, so no room's encode allocates its own (and
// none is zero-filled first, which a std::vector's resize would do)
struct EncodeArena {
  std::unique_ptr<uint8_t[]> p;
  size_t len = 0, cap = 0;

  // room for `n` more bytes at the end; what is there stays
  uint8_t* tail(size_t n) {
    if (len + n > cap) {
      size_t grown = std::max(cap * 2, len + n);
      std::unique_ptr<uint8_t[]> q(new uint8_t[grown]);
      if (len) std::memcpy(q.get(), p.get(), len);
      p = std::move(q);
      cap = grown;
    }
    return p.get() + len;
  }
};
thread_local EncodeArena g_encode_arena;

}  // namespace

// ---------------------------------------------------------------------------
// C ABI
// ---------------------------------------------------------------------------

extern "C" {

void* ymx_new() { return new Mirror(); }
void ymx_free(void* h) { delete static_cast<Mirror*>(h); }

// batched buffer registration across docs: one ctypes crossing for the
// whole flush's staged updates (hs[i] may repeat for docs staging more
// than one update; ids come back in input order)
void ymx_add_bufs_many(void** hs, const uint8_t* const* ptrs,
                       const uint64_t* lens, int64_t n, int64_t* out_ids) {
  for (int64_t i = 0; i < n; i++)
    out_ids[i] =
        static_cast<Mirror*>(hs[i])->add_buf(ptrs[i], lens[i]);
}

int64_t ymx_add_buf(void* h, const uint8_t* p, uint64_t n) {
  return static_cast<Mirror*>(h)->add_buf(p, n);
}

int64_t ymx_n_bufs(void* h) {
  return (int64_t)static_cast<Mirror*>(h)->bufs.size();
}

// roll back buffer registrations from a failed scan (nothing referenced
// them: scan failures happen before any ref merges; arena chunks are only
// created by later phases, so the tail is exactly the staged updates)
void ymx_drop_bufs_from(void* h, int64_t first) {
  Mirror* m = static_cast<Mirror*>(h);
  if (first >= 0 && (size_t)first < m->bufs.size())
    m->bufs.resize((size_t)first);
}

int64_t ymx_buf_len(void* h, int64_t idx) {
  Mirror* m = static_cast<Mirror*>(h);
  if (idx < 0 || (size_t)idx >= m->bufs.size()) return -1;
  return (int64_t)m->buf_len(idx);
}

// run the flush pipeline over the staged updates (buf ids + v2 flags).
// out_counts (int64[16]): n_rows, n_splits, n_sched, [3..5] reserved (0:
// cached plans and the packer index this layout), n_delete_rows,
// n_applied_ds, has_pending, pending_depth, n_slots, n_segs, n_links,
// n_heads, [14] the step's shape (plan_shape), [15] the plan's number
// (Mirror::plan_seq).  Returns 0 or an error code (<0).
// counts[3..5]: the step's rows by kind, 21 bits a field (a field that
// would not fit reads its largest value)
// counts[14]: bit 0, the step's links are dense (link_rows == [0..n_rows):
// the packer ships values only); bit 1, the mirror held no row before the
// step.  3 is a room loaded whole into an empty slot: the engine writes
// it to the device as a row (ymx_pack_rows)
static int64_t plan_shape(const Mirror* m) {
  const Plan& p = m->plan;
  int64_t k = (int64_t)p.link_rows.size();
  int64_t dense = k > 0 && k == p.n_rows && p.link_rows.back() == k - 1;
  return dense | ((int64_t)p.from_empty << 1);
}

static void plan_kind_counts(const Mirror* m, int64_t* c) {
  auto f = [](int64_t v) { return v < 0 ? 0 : (v > 0x1FFFFF ? 0x1FFFFF : v); };
  const Plan& p = m->plan;
  c[3] = f(p.rows_type) | (f(p.format_deleted) << 21) |
         (f(p.conflict_steps) << 42);
  c[4] = f(p.rows_new) | (f(p.rows_nested) << 21) | (f(p.rows_format) << 42);
  c[5] = f(p.rows_attr) | (f(m->n_segs() - p.segs_before) << 21) |
         (f(p.lww_overwritten) << 42);
}

int ymx_prepare(void* h, const int64_t* buf_ids, const int64_t* v2_flags,
                int64_t n_updates, int64_t* out_counts) {
  Mirror* m = static_cast<Mirror*>(h);
  int rc = m->prepare(buf_ids, v2_flags, n_updates);
  if (rc != 0) return rc;
  int64_t depth = (int64_t)m->pending_ds.size();
  for (auto& [c, q] : m->pending) depth += (int64_t)q.size();
  out_counts[0] = m->plan.n_rows;
  out_counts[1] = (int64_t)m->plan.splits.size();
  out_counts[2] = (int64_t)m->plan.sched.size();
  plan_kind_counts(m, out_counts);
  out_counts[6] = (int64_t)m->plan.delete_rows.size();
  out_counts[7] = (int64_t)m->plan.applied_ds.size();
  out_counts[8] = (m->pending.empty() && m->pending_ds.empty()) ? 0 : 1;
  out_counts[9] = depth;
  out_counts[10] = (int64_t)m->client_of_slot.size();
  out_counts[11] = m->n_segs();
  out_counts[12] = (int64_t)m->plan.link_rows.size();
  out_counts[13] = (int64_t)m->plan.head_segs.size();
  out_counts[14] = plan_shape(m);
  out_counts[15] = (int64_t)m->plan_seq;
  return 0;
}

// the most threads one ymx_prepare_many call may plan on, the caller
// included: YTPU_PLAN_THREADS wins, else the hardware concurrency of the
// host.  Read every call (tests change it within a process); 1 is the
// serial branch, which never touches the pool
static int plan_pool_width() {
  const char* e = std::getenv("YTPU_PLAN_THREADS");
  if (e && *e) {
    int v = std::atoi(e);
    return v > 0 ? v : 1;
  }
  unsigned hc = std::thread::hardware_concurrency();
  return hc > 0 ? (int)hc : 1;
}

int ymx_plan_threads() { return plan_pool_width(); }

// cumulative [fast adoptions, fragment-search lookups] across every
// prepare in the process; callers diff around a flush
void ymx_plan_segment_stats(int64_t* out) {
  out[0] = g_seg_fast.load(std::memory_order_relaxed);
  out[1] = g_seg_lookup.load(std::memory_order_relaxed);
}

// The planner's workers, kept between calls.  One pool a process, made
// by the first call that wants a worker, grown by a later call that
// wants more, and never destroyed: its threads are detached and the
// pool is leaked, so a process that exits with workers parked destroys
// no joinable std::thread (std::terminate) and waits for nobody.
// Between calls every worker is blocked on work_cv; none spins (the
// flushing thread's ingest runs on the same shared cores).
//
// One call owns the pool at a time (`taken`); a caller that finds it
// taken plans on its own thread.  A forked child holds this object and
// none of its threads: acquire() sees another pid and makes the child a
// pool of its own (the parent's is left as it is: its mutex may be held
// by a thread the child does not have).
struct PlanPool {
  std::mutex mu;
  std::condition_variable work_cv;  // the workers, for a ticket
  std::condition_variable done_cv;  // the owning call, for active == 0
  // all below under mu
  const std::function<void(int)>* job = nullptr;
  int tickets = 0;    // wakes of the current call that nobody has taken
  int next_slot = 0;  // the job's argument for the next ticket's taker
  int active = 0;     // tickets not yet brought back, taken or not
  int n_workers = 0;
  std::atomic<bool> taken{false};
  const pid_t pid = getpid();

  // the process's pool if no other call holds it, else null
  static PlanPool* acquire() {
    static std::atomic<PlanPool*> the_pool{nullptr};
    PlanPool* p = the_pool.load(std::memory_order_acquire);
    if (p == nullptr || p->pid != getpid()) {
      PlanPool* made = new PlanPool;
      if (the_pool.compare_exchange_strong(p, made,
                                           std::memory_order_acq_rel))
        p = made;
      else
        delete made;  // another thread's came first, and is p now
    }
    return p->taken.exchange(true, std::memory_order_acquire) ? nullptr : p;
  }
  void release() { taken.store(false, std::memory_order_release); }

  // f(0) on the calling thread and f(1), f(2), ... on the workers that
  // take one of `want` tickets, one each; returns when every ticket
  // taken has been brought back.  A ticket nobody has taken by the time
  // the caller's own f(0) returns is withdrawn: the caller waits for
  // workers inside f, never for one that is still waking.  Returns the
  // threads it had to construct (0 once the pool is as wide as its
  // widest call); *handed is when the caller turned to f(0).
  int run(int want, const std::function<void(int)>& f,
          std::chrono::steady_clock::time_point* handed) {
    int made = 0;
    {
      std::lock_guard<std::mutex> lk(mu);
      for (; n_workers < want; n_workers++, made++)
        std::thread([this] { work(); }).detach();
      job = &f;
      tickets = active = want;
      next_slot = 1;
    }
    if (want == n_workers)
      work_cv.notify_all();
    else
      for (int k = 0; k < want; k++) work_cv.notify_one();
    *handed = std::chrono::steady_clock::now();
    f(0);
    std::unique_lock<std::mutex> lk(mu);
    active -= tickets;
    tickets = 0;
    done_cv.wait(lk, [this] { return active == 0; });
    job = nullptr;
    return made;
  }

  void work() {
    std::unique_lock<std::mutex> lk(mu);
    for (;;) {
      work_cv.wait(lk, [this] { return tickets > 0; });
      tickets--;
      const std::function<void(int)>* f = job;
      int slot = next_slot++;
      lk.unlock();
      (*f)(slot);
      lk.lock();
      if (--active == 0) done_cv.notify_one();
    }
  }
};

// batched twin of ymx_prepare: one call plans EVERY staged doc, writing a
// 16-wide counts row per doc (ymx_prepare's layout) and a per-doc rc.  Kills the
// per-doc Python/ctypes round trip that dominated distinct-doc flushes.
// Per-doc plans are independent (each touches only its own Mirror; the
// only shared data are the const update bytes), so the loop fans out over
// the worker pool on multi-core hosts — results are bit-identical at any
// width because no doc reads another doc's state.  Callers must not pass
// the same handle twice in one call.
//
// The calling thread and the workers it wakes take docs from one queue:
// the call's long docs first, longest first, then the
// others in index order (a permutation of the work index; every output
// lands at its doc's own i, so counts, rcs and plans are what index
// order gives): a long document's plan runs behind the short ones'
// instead of starting when the other threads have nothing left.  Long
// is kLongDocFactor times the call's mean staged bytes or more; a call
// of like docs keeps index order, which is the order their mirrors were
// made in: sorted by size, like docs were planned in effect shuffled,
// and a cold load's plan phase took half again as long on the chip's
// host (PERF.md 6, PR 34).
//
// How many threads a call plans on is read from the call: one, the
// caller among them, for each kThreadWorthNs that its docs are reckoned
// to take (kDocNs a doc and kByteNs a staged byte: a keystroke's prepare
// takes 6.5 us and a 15.7 KB room's 0.31 ms on the chip's host), and no
// more than YTPU_PLAN_THREADS or docs.  A wake costs the caller 14 us
// and a doc planned on a core that did not stage it twice its time, so
// a flood's 72 keystrokes (0.48 ms on one thread, 0.38-0.47 on 2 to 13)
// and a storm's 11 rooms (0.10 ms, 0.17-0.22) wake nobody, and a cold
// load's 256 rooms (80 ms) every worker (PERF.md 6, PR 41).  A call
// reckoned under two threads' worth, a call of one doc, a width of 1
// and a call that finds the pool taken by another thread's call all
// take the serial branch.
//
// out_times (double[kPlanTimes], seconds) is the call's own clock, which
// the caller's wall clock around the call cannot tell apart: [0] the
// longest single doc's prepare, [1] the sum over docs; [2..6] that sum
// by phase (Mirror::Lap: scan, merge + fixpoint, the cuts from the
// delete-set clamp to the pre-split, rows + deletes + LWW, finalize;
// they leave out of [1] only the clock reads themselves); and what the
// pool costs the calling thread, both 0 on the serial branch: [7]
// handing the call to the pool (the job published and its workers
// woken; threads constructed only where the pool grows), [8] from the
// moment the last thread found the queue empty to the caller running
// again (nothing where the caller was that thread).
// out_pool (int64[3]) counts: [0] the threads the call planned on, the
// caller included, [1] the workers it woke, [2] the threads it
// constructed (0 in every call once the pool is as wide as its widest).
static const uint64_t kLongDocFactor = 4;
static const uint64_t kDocNs = 6000, kByteNs = 20, kThreadWorthNs = 500000;
static const int kPlanTimes = 2 + Mirror::kNLaps + 2;
void ymx_prepare_many(void** hs, int64_t n_docs, const int64_t* buf_ofs,
                      const int64_t* ids_flat, const int64_t* v2_flat,
                      int want_sched, int64_t* out_counts, int64_t* out_rc,
                      double* out_times, int64_t* out_pool) {
  using clk = std::chrono::steady_clock;
  std::vector<double> took((size_t)n_docs, 0.0);
  // a row of laps a doc, so that no two workers add into one sum
  std::vector<double> laps((size_t)n_docs * Mirror::kNLaps, 0.0);
  auto plan_one = [&](int64_t i) {
    clk::time_point t0 = clk::now();
    Mirror* m = static_cast<Mirror*>(hs[i]);
    int64_t lo = buf_ofs[i], hi = buf_ofs[i + 1];
    int rc = m->prepare(ids_flat + lo, v2_flat + lo, hi - lo,
                        want_sched != 0,
                        laps.data() + (size_t)i * Mirror::kNLaps);
    took[(size_t)i] = std::chrono::duration<double>(clk::now() - t0).count();
    out_rc[i] = rc;
    int64_t* c = out_counts + i * 16;
    if (rc != 0) {
      for (int j = 0; j < 16; j++) c[j] = 0;
      return;
    }
    int64_t depth = (int64_t)m->pending_ds.size();
    for (auto& [cl, q] : m->pending) depth += (int64_t)q.size();
    c[0] = m->plan.n_rows;
    c[1] = (int64_t)m->plan.splits.size();
    c[2] = (int64_t)m->plan.sched.size();
    plan_kind_counts(m, c);
    c[6] = (int64_t)m->plan.delete_rows.size();
    c[7] = (int64_t)m->plan.applied_ds.size();
    c[8] = (m->pending.empty() && m->pending_ds.empty()) ? 0 : 1;
    c[9] = depth;
    c[10] = (int64_t)m->client_of_slot.size();
    c[11] = m->n_segs();
    c[12] = (int64_t)m->plan.link_rows.size();
    c[13] = (int64_t)m->plan.head_segs.size();
    c[14] = plan_shape(m);
    c[15] = (int64_t)m->plan_seq;
  };
  auto report = [&](double pool_start, double pool_join, int threads,
                    int made) {
    for (int j = 0; j < kPlanTimes; j++) out_times[j] = 0.0;
    for (double t : took) {
      out_times[1] += t;
      if (t > out_times[0]) out_times[0] = t;
    }
    for (size_t k = 0; k < laps.size(); k++)
      out_times[2 + k % Mirror::kNLaps] += laps[k];
    out_times[2 + Mirror::kNLaps] = pool_start;
    out_times[3 + Mirror::kNLaps] = pool_join;
    out_pool[0] = threads;
    out_pool[1] = threads - 1;
    out_pool[2] = made;
  };
  int64_t nt = std::min<int64_t>(plan_pool_width(), n_docs);
  std::vector<uint64_t> staged;
  uint64_t staged_total = 0;
  if (nt > 1) {
    staged.assign((size_t)n_docs, 0);
    for (int64_t i = 0; i < n_docs; i++) {
      const Mirror* m = static_cast<const Mirror*>(hs[i]);
      for (int64_t b = buf_ofs[i]; b < buf_ofs[i + 1]; b++) {
        int64_t id = ids_flat[b];
        if (id >= 0 && (size_t)id < m->bufs.size())
          staged[(size_t)i] += m->buf_len(id);
      }
      staged_total += staged[(size_t)i];
    }
    nt = std::min<int64_t>(
        nt, (int64_t)(((uint64_t)n_docs * kDocNs + staged_total * kByteNs) /
                      kThreadWorthNs));
  }
  PlanPool* pool = nt > 1 ? PlanPool::acquire() : nullptr;
  if (pool == nullptr) {
    for (int64_t i = 0; i < n_docs; i++) plan_one(i);
    report(0.0, 0.0, 1, 0);
    return;
  }
  std::vector<int64_t> order, rest;
  order.reserve((size_t)n_docs);
  for (int64_t i = 0; i < n_docs; i++) {
    bool is_long = staged_total > 0 &&
                   staged[(size_t)i] * (uint64_t)n_docs >=
                       kLongDocFactor * staged_total;
    (is_long ? order : rest).push_back(i);
  }
  std::stable_sort(order.begin(), order.end(), [&](int64_t a, int64_t b) {
    return staged[(size_t)a] > staged[(size_t)b];
  });
  order.insert(order.end(), rest.begin(), rest.end());
  std::atomic<int64_t> next{0};
  // when each thread found the queue empty; a slot nobody took keeps
  // the clock's epoch
  std::vector<clk::time_point> ended((size_t)nt);
  std::function<void(int)> drain = [&](int slot) {
    for (int64_t k;
         (k = next.fetch_add(1, std::memory_order_relaxed)) < n_docs;)
      plan_one(order[(size_t)k]);
    ended[(size_t)slot] = clk::now();
  };
  clk::time_point t_call = clk::now(), t_handed;
  int made = pool->run((int)nt - 1, drain, &t_handed);
  clk::time_point t_back = clk::now();
  pool->release();
  clk::time_point work_end = *std::max_element(ended.begin(), ended.end());
  report(std::chrono::duration<double>(t_handed - t_call).count(),
         std::chrono::duration<double>(t_back - work_end).count(), (int)nt,
         made);
}

// deep state clone: dst becomes a bit-identical twin of src — same rows,
// segments, pending queues, delete sets, AND the same last-prepare plan,
// so pack_apply / plan readback / encode work on the clone unchanged.
// Owned arena blocks are deep-copied and every bufs pointer into a
// src-owned block is remapped to the dst copy; borrowed pointers (the
// Python-pinned update bytes) are shared, so the caller must keep those
// buffers alive for the clone's lifetime (the plan cache pins them).
// Returns an approximate host byte size of the cloned state (cache
// accounting); dst's previous state is discarded.
int64_t ymx_clone_state(void* dst_h, void* src_h) {
  Mirror* d = static_cast<Mirror*>(dst_h);
  const Mirror* s = static_cast<const Mirror*>(src_h);
  if (d == s) return 0;

  d->client_of_slot = s->client_of_slot;
  d->slot_of_client = s->slot_of_client;
  d->frag_clock = s->frag_clock;
  d->frag_row = s->frag_row;
  d->frag_hint = s->frag_hint;
  d->state = s->state;

  d->r_slot = s->r_slot;
  d->r_clock = s->r_clock;
  d->r_len = s->r_len;
  d->r_oslot = s->r_oslot;
  d->r_oclock = s->r_oclock;
  d->r_rslot = s->r_rslot;
  d->r_rclock = s->r_rclock;
  d->r_ref = s->r_ref;
  d->r_seg = s->r_seg;
  d->r_is_gc = s->r_is_gc;
  d->r_countable = s->r_countable;
  d->r_c = s->r_c;
  d->r_host_deleted = s->r_host_deleted;
  d->r_lww_deleted = s->r_lww_deleted;

  d->seg_lookup = s->seg_lookup;
  d->seg_name_id = s->seg_name_id;
  d->seg_sub_id = s->seg_sub_id;
  d->seg_parent = s->seg_parent;
  d->segs_of_parent = s->segs_of_parent;
  d->rows_of_seg = s->rows_of_seg;
  d->map_chain = s->map_chain;
  d->list_next = s->list_next;
  d->head_of_seg = s->head_of_seg;

  d->strings = s->strings;
  d->interned = s->interned;
  d->intern_ofs = s->intern_ofs;
  d->intern_len = s->intern_len;

  d->ds = s->ds;
  d->ds_slot_order = s->ds_slot_order;
  d->pending = s->pending;
  d->pending_ds = s->pending_ds;

  d->plan = s->plan;
  d->plan_seq++;  // dst's own count: its previous plan is gone
  d->gen = s->gen;
  d->dl_mark = s->dl_mark;
  d->dh_mark = s->dh_mark;
  d->tm_mark = s->tm_mark;
  d->dirty_epoch = s->dirty_epoch;
  d->walk_mark = s->walk_mark;
  d->walk_order = s->walk_order;
  d->walk_id = s->walk_id;
  d->cur_chunk = s->cur_chunk;
  d->chunk_used = s->chunk_used;
  for (int i = 0; i < Mirror::kSlotCache; i++) {
    d->slot_cache_cl[i] = s->slot_cache_cl[i];
    d->slot_cache_v[i] = s->slot_cache_v[i];
  }
  d->slot_cache_pos = s->slot_cache_pos;
  d->radix_tmp.clear();  // pure scratch: never read before resize

  // owned arena blocks: deep copy, then remap the bufs pointers that
  // point INTO a src block (arena/arena2 hand out interior pointers for
  // bump-allocated fragments) onto the dst copy at the same offset
  d->owned.clear();
  d->owned.reserve(s->owned.size());
  struct Range {
    const uint8_t* lo;
    const uint8_t* hi;
    size_t idx;
  };
  std::vector<Range> ranges;
  ranges.reserve(s->owned.size());
  int64_t owned_bytes = 0;
  for (size_t i = 0; i < s->owned.size(); i++) {
    const auto& blk = *s->owned[i];
    d->owned.push_back(std::make_unique<std::vector<uint8_t>>(blk));
    owned_bytes += (int64_t)blk.size();
    if (!blk.empty())
      ranges.push_back({blk.data(), blk.data() + blk.size(), i});
  }
  std::sort(ranges.begin(), ranges.end(),
            [](const Range& a, const Range& b) { return a.lo < b.lo; });
  d->bufs = s->bufs;
  for (auto& [p, n] : d->bufs) {
    if (p == nullptr || ranges.empty()) continue;
    // rightmost block starting at or before p (blocks never overlap)
    auto it = std::upper_bound(
        ranges.begin(), ranges.end(), p,
        [](const uint8_t* q, const Range& r) { return q < r.lo; });
    if (it == ranges.begin()) continue;
    --it;
    if (p >= it->lo && p < it->hi)
      p = d->owned[it->idx]->data() + (p - it->lo);
  }

  // approximate host footprint (cache eviction accounting): the int64
  // row/fragment columns dominate real mirrors
  int64_t bytes = owned_bytes + (int64_t)s->strings.size();
  bytes += (int64_t)(s->r_slot.size() *
                     (sizeof(int64_t) * 9 + sizeof(ContentDesc) + 4));
  bytes += (int64_t)(s->list_next.size() * sizeof(int64_t));
  for (const auto& fc : s->frag_clock)
    bytes += (int64_t)(fc.size() * 2 * sizeof(int64_t));
  bytes += (int64_t)((s->plan.link_rows.size() + s->plan.link_vals.size() +
                      s->plan.sched.size() * 4 + s->plan.delete_rows.size()) *
                     sizeof(int64_t));
  for (const auto& [cl, q] : s->pending)
    bytes += (int64_t)(q.size() * sizeof(PendRef));
  return bytes;
}

// native twin of BatchEngine._flush_apply's pack loop: bins every doc's
// plan into the per-shard scatter-lane layout
//   [4*b_loc counts | k_dn dense vals | k_sp sparse rows | k_sp sparse
//    vals | k_h head segs | k_h head vals | k_d delete rows]
// writing pads (null/oob) for the unused tail of each section.  stats =
// {n_dense, n_sparse, n_heads, n_dels} real lane elements.
}  // extern "C"

template <typename T>
static void pack_apply_t(void** hs, const int64_t* doc_ids, int64_t n_plans,
                         int64_t b_loc, int64_t n_shards, int64_t k_dn,
                         int64_t k_sp, int64_t k_h, int64_t k_d, T oob_r,
                         T oob_s, T null_val, T* lanes, int64_t* stats) {
  int64_t lane_w = 4 * b_loc + k_dn + 2 * k_sp + 2 * k_h + k_d;
  std::vector<int64_t> cur_dn(n_shards, 0), cur_sp(n_shards, 0),
      cur_h(n_shards, 0), cur_d(n_shards, 0);
  for (int64_t s = 0; s < n_shards; s++)
    std::memset(lanes + s * lane_w, 0, (size_t)(4 * b_loc) * sizeof(T));
  for (int64_t pi = 0; pi < n_plans; pi++) {
    Mirror* m = static_cast<Mirror*>(hs[pi]);
    Plan& p = m->plan;
    int64_t i = doc_ids[pi];
    int64_t s = i / b_loc, li = i % b_loc;
    T* counts = lanes + s * lane_w;
    T* dn = counts + 4 * b_loc;
    T* sp_r = dn + k_dn;
    T* sp_v = sp_r + k_sp;
    T* hd_s = sp_v + k_sp;
    T* hd_v = hd_s + k_h;
    T* dl_r = hd_v + k_h;
    int64_t k = (int64_t)p.link_rows.size();
    bool dense = k > 0 && k == p.n_rows && p.link_rows.back() == k - 1;
    if (dense) {
      counts[0 * b_loc + li] = (T)k;
      int64_t o = cur_dn[s];
      for (int64_t j = 0; j < k; j++)
        dn[o + j] = (T)p.link_vals[(size_t)j];
      cur_dn[s] = o + k;
    } else if (k) {
      counts[1 * b_loc + li] = (T)k;
      int64_t o = cur_sp[s];
      for (int64_t j = 0; j < k; j++) {
        sp_r[o + j] = (T)p.link_rows[(size_t)j];
        sp_v[o + j] = (T)p.link_vals[(size_t)j];
      }
      cur_sp[s] = o + k;
    }
    int64_t hn = (int64_t)p.head_segs.size();
    if (hn) {
      counts[2 * b_loc + li] = (T)hn;
      int64_t o = cur_h[s];
      for (int64_t j = 0; j < hn; j++) {
        hd_s[o + j] = (T)p.head_segs[(size_t)j];
        hd_v[o + j] = (T)p.head_vals[(size_t)j];
      }
      cur_h[s] = o + hn;
    }
    int64_t dnn = (int64_t)p.delete_rows.size();
    if (dnn) {
      counts[3 * b_loc + li] = (T)dnn;
      int64_t o = cur_d[s];
      for (int64_t j = 0; j < dnn; j++)
        dl_r[o + j] = (T)p.delete_rows[(size_t)j];
      cur_d[s] = o + dnn;
    }
  }
  stats[0] = stats[1] = stats[2] = stats[3] = 0;
  for (int64_t s = 0; s < n_shards; s++) {
    T* dn = lanes + s * lane_w + 4 * b_loc;
    T* sp_r = dn + k_dn;
    T* sp_v = sp_r + k_sp;
    T* hd_s = sp_v + k_sp;
    T* hd_v = hd_s + k_h;
    T* dl_r = hd_v + k_h;
    stats[0] += cur_dn[s];
    stats[1] += cur_sp[s];
    stats[2] += cur_h[s];
    stats[3] += cur_d[s];
    for (int64_t j = cur_dn[s]; j < k_dn; j++) dn[j] = null_val;
    for (int64_t j = cur_sp[s]; j < k_sp; j++) {
      sp_r[j] = oob_r;
      sp_v[j] = null_val;
    }
    for (int64_t j = cur_h[s]; j < k_h; j++) {
      hd_s[j] = oob_s;
      hd_v[j] = null_val;
    }
    for (int64_t j = cur_d[s]; j < k_d; j++) dl_r[j] = oob_r;
  }
}

extern "C" {

void ymx_pack_apply(void** hs, const int64_t* doc_ids, int64_t n_plans,
                    int64_t b_loc, int64_t n_shards, int64_t k_dn,
                    int64_t k_sp, int64_t k_h, int64_t k_d, int32_t oob_r,
                    int32_t oob_s, int32_t null_val, int32_t* lanes,
                    int64_t* stats) {
  pack_apply_t<int32_t>(hs, doc_ids, n_plans, b_loc, n_shards, k_dn, k_sp,
                        k_h, k_d, oob_r, oob_s, null_val, lanes, stats);
}

// int16 twin: engines whose row/seg capacity fits 16 bits ship half the
// flush bytes over the host->device link
void ymx_pack_apply16(void** hs, const int64_t* doc_ids, int64_t n_plans,
                      int64_t b_loc, int64_t n_shards, int64_t k_dn,
                      int64_t k_sp, int64_t k_h, int64_t k_d, int32_t oob_r,
                      int32_t oob_s, int32_t null_val, int16_t* lanes,
                      int64_t* stats) {
  pack_apply_t<int16_t>(hs, doc_ids, n_plans, b_loc, n_shards, k_dn, k_sp,
                        k_h, k_d, (int16_t)oob_r, (int16_t)oob_s,
                        (int16_t)null_val, lanes, stats);
}

}  // extern "C"

// the row form of a room loaded whole into an empty slot (plan_shape 3):
// plan i becomes row pos[i] of a staged block, as wide as the tables'
// cells it covers: `right` holds its links (dense: link_vals in row
// order) and null_val beyond them, `deleted` its tombstones, `starts`
// its list heads and null_val elsewhere.  Every cell of a row is written;
// rows of the block no plan is given stay as the caller left them.
// Returns the writes that fell outside a row (0 unless the caller sized
// the block wrong; nothing is written for them).
template <typename T>
static int64_t pack_rows_t(void** hs, const int64_t* pos, int64_t n_plans,
                           int64_t w, int64_t ws, T null_val, T* right,
                           uint8_t* deleted, T* starts) {
  int64_t outside = 0;
  for (int64_t pi = 0; pi < n_plans; pi++) {
    const Plan& p = static_cast<Mirror*>(hs[pi])->plan;
    T* r = right + pos[pi] * w;
    uint8_t* d = deleted + pos[pi] * w;
    T* s = starts + pos[pi] * ws;
    int64_t k = (int64_t)p.link_vals.size();
    if (k > w) {
      outside += k - w;
      k = w;
    }
    for (int64_t j = 0; j < k; j++) r[j] = (T)p.link_vals[(size_t)j];
    for (int64_t j = k; j < w; j++) r[j] = null_val;
    std::memset(d, 0, (size_t)w);
    for (int64_t row : p.delete_rows) {
      if (row < 0 || row >= w) {
        outside++;
        continue;
      }
      d[row] = 1;
    }
    for (int64_t j = 0; j < ws; j++) s[j] = null_val;
    for (size_t j = 0; j < p.head_segs.size(); j++) {
      int64_t sg = p.head_segs[j];
      if (sg < 0 || sg >= ws) {
        outside++;
        continue;
      }
      s[sg] = (T)p.head_vals[j];
    }
  }
  return outside;
}

extern "C" {

int64_t ymx_pack_rows(void** hs, const int64_t* pos, int64_t n_plans,
                      int64_t w, int64_t ws, int32_t null_val, int32_t* right,
                      uint8_t* deleted, int32_t* starts) {
  return pack_rows_t<int32_t>(hs, pos, n_plans, w, ws, null_val, right,
                              deleted, starts);
}

// int16 twin: a block no wider than 32767 holds no row index beyond it
int64_t ymx_pack_rows16(void** hs, const int64_t* pos, int64_t n_plans,
                        int64_t w, int64_t ws, int32_t null_val,
                        int16_t* right, uint8_t* deleted, int16_t* starts) {
  return pack_rows_t<int16_t>(hs, pos, n_plans, w, ws, (int16_t)null_val,
                              right, deleted, starts);
}

void ymx_plan_links(void* h, int64_t* rows, int64_t* vals) {
  Mirror* m = static_cast<Mirror*>(h);
  std::memcpy(rows, m->plan.link_rows.data(),
              m->plan.link_rows.size() * sizeof(int64_t));
  std::memcpy(vals, m->plan.link_vals.data(),
              m->plan.link_vals.size() * sizeof(int64_t));
}

void ymx_plan_heads(void* h, int64_t* segs, int64_t* vals) {
  Mirror* m = static_cast<Mirror*>(h);
  std::memcpy(segs, m->plan.head_segs.data(),
              m->plan.head_segs.size() * sizeof(int64_t));
  std::memcpy(vals, m->plan.head_vals.data(),
              m->plan.head_vals.size() * sizeof(int64_t));
}

void ymx_plan_splits(void* h, int64_t* out) {
  Mirror* m = static_cast<Mirror*>(h);
  for (auto& s : m->plan.splits) { *out++ = s[0]; *out++ = s[1]; }
}

void ymx_plan_sched(void* h, int64_t* out) {
  Mirror* m = static_cast<Mirror*>(h);
  for (auto& s : m->plan.sched)
    for (int i = 0; i < 4; i++) *out++ = s[i];
}

void ymx_plan_deletes(void* h, int64_t* out) {
  Mirror* m = static_cast<Mirror*>(h);
  for (int64_t r : m->plan.delete_rows) *out++ = r;
}

void ymx_plan_applied_ds(void* h, int64_t* out) {
  Mirror* m = static_cast<Mirror*>(h);
  for (auto& d : m->plan.applied_ds) { *out++ = d[0]; *out++ = d[1]; *out++ = d[2]; }
}

int64_t ymx_n_rows(void* h) { return static_cast<Mirror*>(h)->n_rows(); }
int64_t ymx_n_slots(void* h) {
  return (int64_t)static_cast<Mirror*>(h)->client_of_slot.size();
}
int64_t ymx_n_segs(void* h) { return static_cast<Mirror*>(h)->n_segs(); }
uint64_t ymx_gen(void* h) { return static_cast<Mirror*>(h)->gen; }

// bulk row columns [start:] — 19 parallel int64 arrays
void ymx_rows(void* h, int64_t start,
              int64_t* slot, int64_t* clock, int64_t* len,
              int64_t* oslot, int64_t* oclock, int64_t* rslot,
              int64_t* rclock, int64_t* is_gc, int64_t* countable,
              int64_t* ref, int64_t* seg, int64_t* src_kind,
              int64_t* src_buf, int64_t* src_ofs, int64_t* src_end,
              int64_t* src_ofs2, int64_t* src_end2, int64_t* src_count,
              int64_t* src_v2, int64_t* host_deleted, int64_t* lww_deleted) {
  Mirror* m = static_cast<Mirror*>(h);
  int64_t n = m->n_rows();
  for (int64_t r = start; r < n; r++) {
    int64_t i = r - start;
    slot[i] = m->r_slot[r]; clock[i] = m->r_clock[r]; len[i] = m->r_len[r];
    oslot[i] = m->r_oslot[r]; oclock[i] = m->r_oclock[r];
    rslot[i] = m->r_rslot[r]; rclock[i] = m->r_rclock[r];
    is_gc[i] = m->r_is_gc[r]; countable[i] = m->r_countable[r];
    ref[i] = m->r_ref[r]; seg[i] = m->r_seg[r];
    const ContentDesc& c = m->r_c[(size_t)r];
    src_kind[i] = c.kind; src_buf[i] = c.buf;
    src_ofs[i] = c.ofs; src_end[i] = c.end;
    src_ofs2[i] = c.ofs2; src_end2[i] = c.end2;
    src_count[i] = c.count; src_v2[i] = c.v2;
    host_deleted[i] = m->r_host_deleted[r];
    lww_deleted[i] = m->r_lww_deleted[r];
  }
}

void ymx_clients(void* h, int64_t* out) {
  Mirror* m = static_cast<Mirror*>(h);
  for (int64_t c : m->client_of_slot) *out++ = c;
}

void ymx_state(void* h, int64_t* out) {
  Mirror* m = static_cast<Mirror*>(h);
  for (int64_t s : m->state) *out++ = s;
}

// the state vector as a sync step 1 carries it (writeStateVector,
// encoding.js:565-573): the count of clients whose state is past 0, then
// (client, clock) varuints in slot order.  Returns the bytes it needs,
// and writes them only where `cap` holds them (the caller asks again).
int64_t ymx_encode_state_vector(void* h, uint8_t* out, uint64_t cap) {
  Mirror* m = static_cast<Mirror*>(h);
  VecW w;
  uint64_t live = 0;
  for (int64_t s : m->state) live += s > 0;
  w.varuint(live);
  for (size_t i = 0; i < m->state.size(); i++)
    if (m->state[i] > 0) {
      w.varuint((uint64_t)m->client_of_slot[i]);
      w.varuint((uint64_t)m->state[i]);
    }
  if (w.b.size() <= cap) std::memcpy(out, w.b.data(), w.b.size());
  return (int64_t)w.b.size();
}

void ymx_segs(void* h, int64_t* name_ofs, int64_t* name_len,
              int64_t* sub_ofs, int64_t* sub_len, int64_t* parent_row) {
  Mirror* m = static_cast<Mirror*>(h);
  for (int64_t s = 0; s < m->n_segs(); s++) {
    int64_t ni = m->seg_name_id[s], si = m->seg_sub_id[s];
    name_ofs[s] = ni == kNull ? kNull : m->intern_ofs[(size_t)ni];
    name_len[s] = ni == kNull ? 0 : m->intern_len[(size_t)ni];
    sub_ofs[s] = si == kNull ? kNull : m->intern_ofs[(size_t)si];
    sub_len[s] = si == kNull ? 0 : m->intern_len[(size_t)si];
    parent_row[s] = m->seg_parent[s];
  }
}

uint64_t ymx_strings_len(void* h) {
  return (uint64_t)static_cast<Mirror*>(h)->strings.size();
}
void ymx_strings(void* h, uint8_t* out) {
  Mirror* m = static_cast<Mirror*>(h);
  std::memcpy(out, m->strings.data(), m->strings.size());
}

int64_t ymx_chain_len(void* h, int64_t seg) {
  Mirror* m = static_cast<Mirror*>(h);
  auto it = m->map_chain.find(seg);
  return it == m->map_chain.end() ? 0 : (int64_t)it->second.size();
}
void ymx_chain(void* h, int64_t seg, int64_t* out) {
  Mirror* m = static_cast<Mirror*>(h);
  auto it = m->map_chain.find(seg);
  if (it == m->map_chain.end()) return;
  for (int64_t r : it->second) *out++ = r;
}

// raw DS ranges in slot first-note order: (slot, clock, len) triples
int64_t ymx_ds_count(void* h) {
  Mirror* m = static_cast<Mirror*>(h);
  int64_t n = 0;
  for (auto& v : m->ds) n += (int64_t)v.size();
  return n;
}
void ymx_ds(void* h, int64_t* slot, int64_t* clock, int64_t* len) {
  Mirror* m = static_cast<Mirror*>(h);
  for (int64_t s : m->ds_slot_order)
    for (auto& [c, l] : m->ds[(size_t)s]) {
      *slot++ = s; *clock++ = c; *len++ = l;
    }
}

// host list state (the device right_link/starts mirror)
void ymx_links(void* h, int64_t* out) {
  Mirror* m = static_cast<Mirror*>(h);
  std::memcpy(out, m->list_next.data(),
              m->list_next.size() * sizeof(int64_t));
}

void ymx_heads(void* h, int64_t* out) {
  Mirror* m = static_cast<Mirror*>(h);
  std::memcpy(out, m->head_of_seg.data(),
              m->head_of_seg.size() * sizeof(int64_t));
}

// fragment-index export: per-slot sizes, then one slot's (clock, row)
// pairs — lets the facade mirror the index with memcpys instead of a
// Python-side sort/rebuild
void ymx_frag_counts(void* h, int64_t* out) {
  Mirror* m = static_cast<Mirror*>(h);
  for (size_t s = 0; s < m->client_of_slot.size(); s++)
    out[s] = (int64_t)m->frag_clock[s].size();
}

void ymx_frag(void* h, int64_t slot, int64_t* clocks, int64_t* rows) {
  Mirror* m = static_cast<Mirror*>(h);
  auto& fc = m->frag_clock[(size_t)slot];
  auto& fr = m->frag_row[(size_t)slot];
  std::memcpy(clocks, fc.data(), fc.size() * sizeof(int64_t));
  std::memcpy(rows, fr.data(), fr.size() * sizeof(int64_t));
}

int64_t ymx_pending_depth(void* h) {
  Mirror* m = static_cast<Mirror*>(h);
  int64_t depth = (int64_t)m->pending_ds.size();
  for (auto& [c, q] : m->pending) depth += (int64_t)q.size();
  return depth;
}
int ymx_has_pending(void* h) {
  Mirror* m = static_cast<Mirror*>(h);
  return (m->pending.empty() && m->pending_ds.empty()) ? 0 : 1;
}

int64_t ymx_find_seg(void* h, const uint8_t* name, int64_t name_len,
                     const uint8_t* sub, int64_t sub_len, int64_t parent_row) {
  Mirror* m = static_cast<Mirror*>(h);
  auto find_id = [&](const uint8_t* p, int64_t n) -> int64_t {
    if (n < 0) return kNull;
    std::string key(reinterpret_cast<const char*>(p), (size_t)n);
    auto it = m->interned.find(key);
    return it == m->interned.end() ? -2 : it->second;  // -2: never interned
  };
  int64_t ni = find_id(name, name_len);
  int64_t si = find_id(sub, sub_len);
  if (ni == -2 || si == -2) return kNull;
  auto it = m->seg_lookup.find(std::make_tuple(ni, si, parent_row));
  return it == m->seg_lookup.end() ? kNull : it->second;
}

int64_t ymx_segs_of_parent(void* h, int64_t row, int64_t* out, int64_t cap) {
  Mirror* m = static_cast<Mirror*>(h);
  auto it = m->segs_of_parent.find(row);
  if (it == m->segs_of_parent.end()) return 0;
  int64_t n = 0;
  for (int64_t s : it->second) {
    if (n < cap) out[n] = s;
    n++;
  }
  return n;
}

// copy bytes out of a registered buffer (arena chunks included) so Python
// can realize synthesized content
int ymx_copy_bytes(void* h, int64_t buf, int64_t ofs, int64_t end,
                   uint8_t* out) {
  Mirror* m = static_cast<Mirror*>(h);
  if (buf < 0 || (size_t)buf >= m->bufs.size()) return -1;
  if (ofs < 0 || end < ofs || (uint64_t)end > m->buf_len(buf)) return -1;
  std::memcpy(out, m->buf_ptr(buf) + ofs, (size_t)(end - ofs));
  return 0;
}

// upper bound on any encode of this mirror (all rows + framing slack)
int64_t ymx_encode_bound(void* h) {
  Mirror* m = static_cast<Mirror*>(h);
  int64_t content = 0;
  for (auto& c : m->r_c)
    content += (c.end >= 0 && c.ofs >= 0) ? (c.end - c.ofs) : 16;
  int64_t n_ds = 0;
  for (auto& v : m->ds) n_ds += (int64_t)v.size();
  return 256 + m->n_rows() * 80 + content + (int64_t)m->strings.size() * 2 +
         24 * n_ds;
}

// encode the diff against a remote state vector, fully natively.
// sv: n_sv (client, clock) pairs.  ds_override!=0 replaces the derived
// DeleteSet with the given (client, clock, len) triples.  Returns bytes
// written, -7 = needs the Python spill path, other <0 = writer error.
int64_t ymx_encode_diff(void* h, const int64_t* sv_clients,
                        const int64_t* sv_clocks, int64_t n_sv,
                        const int64_t* ds_ranges, int64_t n_ds,
                        int ds_override, uint8_t* out, uint64_t cap) {
  return mirror_encode_diff(static_cast<Mirror*>(h), sv_clients, sv_clocks,
                            n_sv, ds_ranges, n_ds, ds_override, out, cap);
}

// V2 twin of ymx_encode_diff (byte-identical to the Python
// UpdateEncoderV2 output).  Same fallback contract: -7 -> Python writer.
int64_t ymx_encode_diff_v2(void* h, const int64_t* sv_clients,
                           const int64_t* sv_clocks, int64_t n_sv,
                           const int64_t* ds_ranges, int64_t n_ds,
                           int ds_override, uint8_t* out, uint64_t cap) {
  std::vector<uint8_t> bytes;
  int64_t rc = mirror_encode_diff_v2(static_cast<Mirror*>(h), sv_clients,
                                     sv_clocks, n_sv, ds_ranges, n_ds,
                                     ds_override, &bytes);
  if (rc < 0) return rc;
  if (bytes.size() > cap)  // needed size, negative-encoded (caller
    return -(int64_t)bytes.size();  // retries once with an exact buffer)
  std::memcpy(out, bytes.data(), bytes.size());
  return (int64_t)bytes.size();
}

// the step updates of a flush, every planned room in one call (the
// batched twin of ymx_encode_diff as the emit phase uses it; V1).  Room i
// is encoded against the state vector sv_*[sv_ofs[i]..sv_ofs[i+1]) with
// the DS section ds_mode[i] names:
//   0  the applied delete set of the mirror's own current plan, read in
//      place; plan_seq[i] is the number the plan was handed out under
//      (counts[15]), and a mirror that has planned again since is
//      refused with -8, not encoded
//   1  the triples ds_triples[3*ds_ofs[i]..3*ds_ofs[i+1])
//   2  the mirror's whole derived delete set (encodeStateAsUpdate, a sync
//      step 2)
// A work item has its own sv range and no mode writes the mirror, so one
// handle may appear any number of times: a tick's handshakes hold several
// sessions of one room, each with its own state vector.
// The bytes land back to back in this thread's arena (ymx_encode_arena),
// room i at out_ofs[i]..out_ofs[i+1]; out_rc[i] is 0, -7 where the room
// needs the Python writer, another negative on a writer error.  Every
// byte is what ymx_encode_diff writes for the same room.  Returns the
// arena's length.
int64_t ymx_encode_steps_many(void** hs, int64_t n_docs,
                              const int64_t* sv_ofs,
                              const int64_t* sv_clients,
                              const int64_t* sv_clocks,
                              const int64_t* ds_mode,
                              const int64_t* plan_seq, const int64_t* ds_ofs,
                              const int64_t* ds_triples, int64_t* out_ofs,
                              int64_t* out_rc) {
  auto& arena = g_encode_arena;
  // a cold load's arena (15 KB a room) is not kept for keystrokes
  if (arena.cap > ((size_t)16 << 20) && arena.len * 4 < arena.cap) {
    arena.p.reset();
    arena.cap = 0;
  }
  arena.len = 0;
  DiffCols d;
  for (int64_t i = 0; i < n_docs; i++) {
    Mirror* m = static_cast<Mirror*>(hs[i]);
    out_ofs[i] = (int64_t)arena.len;
    const int64_t* ds = nullptr;
    int64_t n_ds = 0;
    int override_ds = 1;
    if (ds_mode[i] == 0) {
      if ((int64_t)m->plan_seq != plan_seq[i]) {
        out_rc[i] = -8;
        continue;
      }
      ds = m->plan.applied_ds.empty() ? nullptr
                                      : m->plan.applied_ds[0].data();
      n_ds = (int64_t)m->plan.applied_ds.size();
    } else if (ds_mode[i] == 1) {
      ds = ds_triples + 3 * ds_ofs[i];
      n_ds = ds_ofs[i + 1] - ds_ofs[i];
    } else {
      override_ds = 0;
    }
    int64_t lo = sv_ofs[i];
    int64_t rc = select_diff(m, sv_clients + lo, sv_clocks + lo,
                             sv_ofs[i + 1] - lo, ds, n_ds, override_ds, &d);
    if (rc == 0) {
      rc = write_diff(m, d, arena.tail((size_t)d.bound), (uint64_t)d.bound);
      if (rc > 0) arena.len += (size_t)rc;
    }
    out_rc[i] = rc < 0 ? rc : 0;
  }
  out_ofs[n_docs] = (int64_t)arena.len;
  return (int64_t)arena.len;
}

const uint8_t* ymx_encode_arena() { return g_encode_arena.p.get(); }

uint64_t ymx_plan_seq(void* h) { return static_cast<Mirror*>(h)->plan_seq; }

int64_t ymx_format_cleanup(void* h, int64_t rows_before, int64_t* out,
                           int64_t cap, int64_t* n_texts) {
  return static_cast<Mirror*>(h)->format_cleanup(rows_before, out, cap,
                                                 n_texts);
}

// compaction from the mirror's OWN list/deleted state — the flush
// invariant keeps these equal to the device arrays, so no device
// read-back is needed to decide merges (the r3 readback-rebuild cycle
// was the 100k-doc scaling liability); the device gets the rebuilt
// arrays in one write-only scatter
int64_t ymx_compact_self(void* h, int gc, int32_t* new_right,
                         uint8_t* new_deleted, int32_t* new_heads,
                         int64_t new_heads_cap) {
  Mirror* m = static_cast<Mirror*>(h);
  int64_t n = m->n_rows();
  int64_t nseg = m->n_segs();
  std::vector<int32_t> right((size_t)std::max<int64_t>(1, n));
  std::vector<uint8_t> del((size_t)std::max<int64_t>(1, n));
  std::vector<int32_t> heads((size_t)std::max<int64_t>(1, nseg));
  for (int64_t i = 0; i < n; i++) {
    right[(size_t)i] = (int32_t)m->list_next[(size_t)i];
    del[(size_t)i] = m->r_host_deleted[(size_t)i];
  }
  for (int64_t s = 0; s < nseg; s++)
    heads[(size_t)s] = (int32_t)m->head_of_seg[(size_t)s];
  return m->compact(right.data(), del.data(), heads.data(), nseg, gc,
                    new_right, new_deleted, new_heads, new_heads_cap);
}

// the question before ymx_compact_self, every candidate of a compaction
// look in one call: out[i] is 1 where compacting room hs[i] would change
// something (Mirror::compact_changes), 0 where it would hand back the
// rows the room holds.  Reads the mirrors, writes none, allocates nothing.
void ymx_compact_changes_many(void** hs, int64_t n_docs, int gc,
                              uint8_t* out) {
  for (int64_t i = 0; i < n_docs; i++)
    out[i] = static_cast<const Mirror*>(hs[i])->compact_changes(gc != 0);
}

}  // extern "C"

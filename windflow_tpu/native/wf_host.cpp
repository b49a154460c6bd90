// windflow_tpu native host runtime.
//
// TPU-native equivalent of the reference's native data plane
// (/root/reference/wf: recycling.hpp / recycling_gpu.hpp free-list pools,
// ff::MPMC_Ptr_Queue lock-free queues, forward_emitter_gpu.hpp pinned
// staging, keyby_emitter.hpp hash routing): the pieces of the runtime that
// sit AROUND the XLA compute path and want to be native — bulk ingest
// parsing, key partitioning, and the watermark fold.  Exposed as a plain
// C ABI consumed via
// ctypes (windflow_tpu/native/__init__.py); no Python.h dependency so the
// library builds with any g++ and loads in any CPython.
//
// Build: `make -C native` -> native/libwfhost.so

#include <atomic>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <mutex>
#include <vector>

extern "C" {

// ---------------------------------------------------------------------------
// Hashing + keyby partitioning (reference keyby_emitter.hpp:216 hash%ndest).
// splitmix64: deterministic across processes, well-mixed for dense int keys.
// ---------------------------------------------------------------------------

static inline uint64_t splitmix64(uint64_t x) {
  x += 0x9e3779b97f4a7c15ULL;
  x = (x ^ (x >> 30)) * 0xbf58476d1ce4e5b9ULL;
  x = (x ^ (x >> 27)) * 0x94d049bb133111ebULL;
  return x ^ (x >> 31);
}

uint64_t wf_hash64(int64_t key) { return splitmix64((uint64_t)key); }

// dest_out[i] = hash(keys[i]) % ndest; counts_out[d] = #tuples for dest d.
void wf_keyby_partition(const int64_t* keys, int64_t n, int32_t ndest,
                        int32_t* dest_out, int64_t* counts_out) {
  memset(counts_out, 0, sizeof(int64_t) * (size_t)ndest);
  for (int64_t i = 0; i < n; ++i) {
    int32_t d = (int32_t)(splitmix64((uint64_t)keys[i]) % (uint64_t)ndest);
    dest_out[i] = d;
    counts_out[d]++;
  }
}


// ---------------------------------------------------------------------------
// Bulk ingest: parse binary frames / CSV into columns (the native
// data-loader; feeds the staging emitter with zero per-tuple Python work).
// Binary record layout: int64 key, int64 ts, nv x float64 values (LE).
// ---------------------------------------------------------------------------

int64_t wf_frame_record_bytes(int32_t nv) { return 16 + 8 * (int64_t)nv; }

// Returns #records parsed (caps at max_records; ignores trailing partial
// record — the caller carries the remainder into the next chunk).
int64_t wf_parse_frames(const uint8_t* buf, int64_t nbytes, int32_t nv,
                        int64_t* keys, int64_t* tss, double* vals,
                        int64_t max_records) {
  const int64_t rec = wf_frame_record_bytes(nv);
  int64_t n = nbytes / rec;
  if (n > max_records) n = max_records;
  for (int64_t i = 0; i < n; ++i) {
    const uint8_t* p = buf + i * rec;
    memcpy(&keys[i], p, 8);
    memcpy(&tss[i], p + 8, 8);
    memcpy(&vals[i * nv], p + 16, 8 * (size_t)nv);
  }
  return n;
}

// Min and max key of the whole records in buf (out[0], out[1]): the
// pre-scan of the one-pass route for a chunk that will split a batch, whose
// key width (FrameSourceReplica: int32 when every key fits) has to be known
// before its first rows ship.  Returns #records scanned.
int64_t wf_frames_key_range(const uint8_t* buf, int64_t nbytes, int32_t nv,
                            int64_t* out) {
  const int64_t rec = wf_frame_record_bytes(nv);
  const int64_t n = nbytes / rec;
  int64_t lo = INT64_MAX, hi = INT64_MIN;
  for (int64_t i = 0; i < n; ++i) {
    int64_t k;
    memcpy(&k, buf + i * rec, 8);
    lo = k < lo ? k : lo;
    hi = k > hi ? k : hi;
  }
  out[0] = lo;
  out[1] = hi;
  return n;
}

}  // extern "C"

// The one-pass route: each frame is read once and each field written once,
// at the width and the word offset the staged batch holds it
// (windflow_tpu/staging.py: [lane0 | lane1 | ... | ts | n], a 4-byte lane
// cap words, one a row; an int64 lane 2 * cap words in two planes, the rows'
// low words then their high words, as the egress buffer: the device reads
// either plane by contiguous slice).  KW is the key lane's words a row; V the
// value lanes' type: float(double) rounds as numpy's astype(float32), the
// integer casts truncate as its astype does.
namespace {

// Row `r` of an int64 lane that starts at `lane`: two 4-byte stores, one a
// plane.
inline void put_planes(uint32_t* lane, int64_t cap, int64_t r, int64_t x) {
  lane[r] = (uint32_t)x;
  lane[cap + r] = (uint32_t)((uint64_t)x >> 32);
}

template <int KW, typename V>
void frames_into_packed(const uint8_t* buf, int64_t m, int32_t nv,
                        uint32_t* dst, const int64_t* lane_off, int64_t cap,
                        int64_t row, const int64_t* ts_fixed,
                        int64_t* ranges) {
  // A block of rows is written lane by lane, four cache lines of a plane at
  // a time.  The lanes of a batch lie a power of two apart (cap words), so the
  // eight or more lines one row touches share a cache set, and a loop that
  // writes a row at a time evicts each before it is full (2.05 against 1.68 ms
  // a 262144-row batch on the chip's host, PERF.md PR 47).  The block's frames
  // (3.5 KB at five values) stay in L1 between the passes.
  constexpr int64_t BLOCK = 64;
  const int64_t rec = wf_frame_record_bytes(nv);
  uint32_t* kd = dst + lane_off[0];
  uint32_t* td = dst + lane_off[nv + 1];
  int64_t lo = INT64_MAX, hi = INT64_MIN;
  int64_t klo = INT64_MAX, khi = INT64_MIN;
  for (int64_t i0 = 0; i0 < m; i0 += BLOCK) {
    const int64_t nb = m - i0 < BLOCK ? m - i0 : BLOCK;
    const uint8_t* p0 = buf + i0 * rec;
    const int64_t r0 = row + i0;
    for (int64_t i = 0; i < nb; ++i) {
      int64_t k;
      memcpy(&k, p0 + i * rec, 8);
      klo = k < klo ? k : klo;
      khi = k > khi ? k : khi;
      if (KW == 1) {
        kd[r0 + i] = (uint32_t)k;
      } else {
        put_planes(kd, cap, r0 + i, k);
      }
    }
    for (int64_t i = 0; i < nb; ++i) {
      int64_t t;
      if (ts_fixed) {
        t = *ts_fixed;
      } else {
        memcpy(&t, p0 + i * rec + 8, 8);
      }
      lo = t < lo ? t : lo;
      hi = t > hi ? t : hi;
      put_planes(td, cap, r0 + i, t);
    }
    for (int32_t v = 0; v < nv; ++v) {
      uint32_t* vd = dst + lane_off[1 + v];
      for (int64_t i = 0; i < nb; ++i) {
        double d;
        memcpy(&d, p0 + i * rec + 16 + 8 * v, 8);
        const V x = (V)d;
        if (sizeof(V) == 8) {
          put_planes(vd, cap, r0 + i, (int64_t)x);
        } else {
          memcpy(vd + r0 + i, &x, 4);
        }
      }
    }
  }
  ranges[0] = lo;
  ranges[1] = hi;
  ranges[2] = klo;
  ranges[3] = khi;
}

}  // namespace

extern "C" {

// Value-lane kinds of wf_parse_frames_packed.
enum { WF_VAL_F32 = 0, WF_VAL_I32 = 1, WF_VAL_I64 = 2 };

// Parse up to `room` whole frames of buf straight into the packed staging
// buffer `dst` of `cap` rows, from row `row` on.  lane_off holds nv + 2 word
// offsets of row 0: the key lane's, the nv value lanes' in wire order, the ts
// lane's.  key_words 1 writes the key's low word, 2 the int64 as its two
// planes.  ts_fixed,
// when not null, stamps every row with *ts_fixed instead of the frame's own
// ts (ingress time: one arrival stamp a chunk).  ranges[0..3] = min / max of
// the timestamps written, min / max of the keys read: a caller that wrote
// low words learns here whether every key fit, before it commits the rows.
// Returns #rows written, -1 for an unknown key_words / val_kind; the caller
// carries what was not consumed.
int64_t wf_parse_frames_packed(const uint8_t* buf, int64_t nbytes, int32_t nv,
                               uint32_t* dst, const int64_t* lane_off,
                               int64_t cap, int32_t key_words,
                               int32_t val_kind, int64_t row, int64_t room,
                               const int64_t* ts_fixed, int64_t* ranges) {
  int64_t m = nbytes / wf_frame_record_bytes(nv);
  if (m > room) m = room;
#define WF_INTO(KW, V)                                                      \
  frames_into_packed<KW, V>(buf, m, nv, dst, lane_off, cap, row, ts_fixed, \
                            ranges)
  switch (key_words * 4 + val_kind) {
    case 4 + WF_VAL_F32: WF_INTO(1, float); break;
    case 4 + WF_VAL_I32: WF_INTO(1, int32_t); break;
    case 4 + WF_VAL_I64: WF_INTO(1, int64_t); break;
    case 8 + WF_VAL_F32: WF_INTO(2, float); break;
    case 8 + WF_VAL_I32: WF_INTO(2, int32_t); break;
    case 8 + WF_VAL_I64: WF_INTO(2, int64_t); break;
    default: return -1;
  }
#undef WF_INTO
  return m;
}

// CSV lines "key,ts,v0[,v1...]\n".  Returns #records; stops at max_records
// or at the last complete line; *consumed_out = bytes consumed.
int64_t wf_parse_csv(const char* buf, int64_t nbytes, int32_t nv,
                     int64_t* keys, int64_t* tss, double* vals,
                     int64_t max_records, int64_t* consumed_out) {
  int64_t n = 0, pos = 0;
  std::vector<char> scratch(512);
  while (n < max_records) {
    // find end of line
    int64_t eol = pos;
    while (eol < nbytes && buf[eol] != '\n') eol++;
    if (eol >= nbytes) break;  // partial line: leave for next chunk
    // copy the line into a NUL-terminated scratch so strto* cannot scan
    // past the newline (a field like "5,50,\n6" must not steal digits from
    // the next line) or past the end of the buffer
    int64_t len = eol - pos;
    if (len + 1 > (int64_t)scratch.size()) scratch.resize((size_t)len + 1);
    char* line = scratch.data();
    memcpy(line, buf + pos, (size_t)len);
    line[len] = '\0';
    char* end;
    int64_t key = strtoll(line, &end, 10);
    // malformed (empty key or no separator): skip line
    if (end == line || *end != ',') { pos = eol + 1; continue; }
    const char* ts_start = end + 1;
    int64_t ts = strtoll(ts_start, &end, 10);
    bool ok = (end != ts_start);
    for (int32_t v = 0; ok && v < nv; ++v) {
      if (*end != ',') { ok = false; break; }
      const char* start = end + 1;
      vals[n * nv + v] = strtod(start, &end);
      if (end == start) { ok = false; break; }  // empty field
    }
    if (ok) {
      keys[n] = key;
      tss[n] = ts;
      n++;
    }
    pos = eol + 1;
  }
  *consumed_out = pos;
  return n;
}

// ---------------------------------------------------------------------------
// Watermark fold: min over per-channel maxima, ignoring unset channels
// (reference watermark_collector.hpp:63-76 inner loop).
// ---------------------------------------------------------------------------

int64_t wf_min_watermark(const int64_t* channel_wms, int32_t n,
                         int64_t wm_none) {
  int64_t m = wm_none;
  for (int32_t i = 0; i < n; ++i) {
    int64_t w = channel_wms[i];
    if (w == wm_none) return wm_none;  // some channel has no watermark yet
    if (m == wm_none || w < m) m = w;
  }
  return m;
}

}  // extern "C"

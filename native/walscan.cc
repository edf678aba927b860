// Native WAL data-loader tier: framing scan, single-core replay
// (the reference wal.ReadAll hot loop, wal/wal.go:164-216 +
// wal/decoder.go:28-47), synthetic stream generation, and row
// padding for device upload.
//
// The reference achieves its replay throughput with Go's stdlib
// hash/crc32 (SSE4.2-accelerated) in a strictly sequential loop; this
// file reproduces that loop in C++ as the *baseline* the device path
// is measured against, and provides the framing pass the
// device path runs on host (record offsets/lengths/stored CRCs) —
// everything byte-level and branchy, i.e. the wrong shape for a TPU,
// stays here; everything batchable goes to the device.
//
// Wire layout (wal/decoder.go:30-35, wal/walpb/record.proto:10-14):
//   stream  := { int64-LE length | record bytes } *
//   record  := (1: type varint) (2: crc varint) (3: data bytes)?
//   entry   := (1: type varint) (2: term varint) (3: index varint)
//              (4: data bytes)
//
// Exported error codes are negative; record counts are >= 0.

#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

namespace {

// ---------------------------------------------------------------------------
// CRC32-Castagnoli: slicing-by-8 software path + SSE4.2 hardware path.
// Raw recurrence (no pre/post inversion) matches pkg/crc's linear map;
// Go-convention update() adds the inversions (hash/crc32 semantics).
// ---------------------------------------------------------------------------

constexpr uint32_t kPolyReflected = 0x82F63B78u;

struct Tables {
  uint32_t t[8][256];
  Tables() {
    for (uint32_t i = 0; i < 256; i++) {
      uint32_t c = i;
      for (int k = 0; k < 8; k++) c = (c >> 1) ^ ((c & 1) ? kPolyReflected : 0);
      t[0][i] = c;
    }
    for (int s = 1; s < 8; s++)
      for (uint32_t i = 0; i < 256; i++)
        t[s][i] = t[0][t[s - 1][i] & 0xFF] ^ (t[s - 1][i] >> 8);
  }
};
const Tables kTab;

uint32_t raw_soft(uint32_t s, const uint8_t* p, uint64_t n) {
  while (n && (reinterpret_cast<uintptr_t>(p) & 7)) {
    s = kTab.t[0][(s ^ *p++) & 0xFF] ^ (s >> 8);
    n--;
  }
  while (n >= 8) {
    uint64_t w;
    std::memcpy(&w, p, 8);
    w ^= s;
    s = kTab.t[7][w & 0xFF] ^ kTab.t[6][(w >> 8) & 0xFF] ^
        kTab.t[5][(w >> 16) & 0xFF] ^ kTab.t[4][(w >> 24) & 0xFF] ^
        kTab.t[3][(w >> 32) & 0xFF] ^ kTab.t[2][(w >> 40) & 0xFF] ^
        kTab.t[1][(w >> 48) & 0xFF] ^ kTab.t[0][(w >> 56) & 0xFF];
    p += 8;
    n -= 8;
  }
  while (n--) s = kTab.t[0][(s ^ *p++) & 0xFF] ^ (s >> 8);
  return s;
}

#if defined(__x86_64__)
__attribute__((target("sse4.2"))) uint32_t raw_hw(uint32_t s, const uint8_t* p,
                                                  uint64_t n) {
  uint64_t s64 = s;
  while (n && (reinterpret_cast<uintptr_t>(p) & 7)) {
    s64 = __builtin_ia32_crc32qi(s64, *p++);
    n--;
  }
  while (n >= 8) {
    uint64_t w;
    std::memcpy(&w, p, 8);
    s64 = __builtin_ia32_crc32di(s64, w);
    p += 8;
    n -= 8;
  }
  while (n--) s64 = __builtin_ia32_crc32qi(s64, *p++);
  return static_cast<uint32_t>(s64);
}

bool have_sse42() {
  static const bool ok = __builtin_cpu_supports("sse4.2");
  return ok;
}
#endif

uint32_t raw(uint32_t s, const uint8_t* p, uint64_t n) {
#if defined(__x86_64__)
  if (have_sse42()) return raw_hw(s, p, n);
#endif
  return raw_soft(s, p, n);
}

// Go crc32.Update convention: invert in, invert out.
inline uint32_t go_update(uint32_t crc, const uint8_t* p, uint64_t n) {
  return ~raw(~crc, p, n);
}

// ---------------------------------------------------------------------------
// varint
// ---------------------------------------------------------------------------

// Returns new position, or 0 on truncation/overflow.
inline uint64_t uvarint(const uint8_t* buf, uint64_t pos, uint64_t end,
                        uint64_t* out) {
  uint64_t result = 0;
  int shift = 0;
  while (pos < end && shift < 70) {
    uint8_t b = buf[pos++];
    result |= static_cast<uint64_t>(b & 0x7F) << shift;
    if (!(b & 0x80)) {
      *out = result;
      return pos;
    }
    shift += 7;
  }
  return 0;
}

inline uint64_t put_uvarint(uint8_t* buf, uint64_t pos, uint64_t v) {
  while (v >= 0x80) {
    buf[pos++] = static_cast<uint8_t>(v) | 0x80;
    v >>= 7;
  }
  buf[pos++] = static_cast<uint8_t>(v);
  return pos;
}

constexpr int64_t kErrTruncated = -1;
constexpr int64_t kErrProto = -2;
constexpr int64_t kErrCapacity = -3;
constexpr int64_t kErrCRC = -4;

constexpr int64_t kEntryType = 2;
constexpr int64_t kCrcType = 4;

// Parse one record body [pos, rend). Writes type/crc and data span
// (absolute offsets); data_off/len are 0 if field 3 absent.
int64_t parse_record(const uint8_t* buf, uint64_t pos, uint64_t rend,
                     int64_t* type, uint32_t* crc, uint64_t* data_off,
                     uint64_t* data_len) {
  *type = 0;
  *crc = 0;
  *data_off = 0;
  *data_len = 0;
  while (pos < rend) {
    uint64_t tag;
    pos = uvarint(buf, pos, rend, &tag);
    if (!pos) return kErrProto;
    uint64_t fnum = tag >> 3, wt = tag & 7;
    if (fnum == 0) return kErrProto;  // illegal tag 0 (proto.py _tag parity)
    uint64_t v;
    switch (fnum) {
      case 1:
        if (wt != 0) return kErrProto;
        pos = uvarint(buf, pos, rend, &v);
        if (!pos) return kErrProto;
        *type = static_cast<int64_t>(v);
        break;
      case 2:
        if (wt != 0) return kErrProto;
        pos = uvarint(buf, pos, rend, &v);
        if (!pos) return kErrProto;
        *crc = static_cast<uint32_t>(v);
        break;
      case 3:
        if (wt != 2) return kErrProto;
        pos = uvarint(buf, pos, rend, &v);
        if (!pos || v > rend - pos) return kErrProto;  // overflow-safe
        *data_off = pos;
        *data_len = v;
        pos += v;
        break;
      default:  // skip unknown (proto semantics)
        if (wt == 0) {
          pos = uvarint(buf, pos, rend, &v);
          if (!pos) return kErrProto;
        } else if (wt == 2) {
          pos = uvarint(buf, pos, rend, &v);
          if (!pos || v > rend - pos) return kErrProto;
          pos += v;
        } else if (wt == 1) {
          if (rend - pos < 8) return kErrProto;
          pos += 8;
        } else if (wt == 5) {
          if (rend - pos < 4) return kErrProto;
          pos += 4;
        } else {
          return kErrProto;
        }
    }
  }
  return 0;
}

// Parse entry type/index/term out of an entry payload (fields 1-3).
int64_t parse_entry(const uint8_t* buf, uint64_t pos, uint64_t rend,
                    uint64_t* etype, uint64_t* term, uint64_t* index) {
  *etype = 0;
  *term = 0;
  *index = 0;
  while (pos < rend) {
    uint64_t tag;
    pos = uvarint(buf, pos, rend, &tag);
    if (!pos) return kErrProto;
    uint64_t fnum = tag >> 3, wt = tag & 7;
    if (fnum == 0) return kErrProto;  // illegal tag 0 (proto.py _tag parity)
    uint64_t v;
    if (wt == 0) {
      pos = uvarint(buf, pos, rend, &v);
      if (!pos) return kErrProto;
      if (fnum == 1) *etype = v;
      if (fnum == 2) *term = v;
      if (fnum == 3) *index = v;
    } else if (wt == 2) {
      pos = uvarint(buf, pos, rend, &v);
      if (!pos || v > rend - pos) return kErrProto;
      pos += v;
    } else {
      return kErrProto;
    }
  }
  return 0;
}

inline uint64_t read_len_le(const uint8_t* buf) {
  uint64_t v;
  std::memcpy(&v, buf, 8);
  return v;  // int64 little-endian; lengths are small positive
}

// The frame length is an int64; a set sign bit is framing corruption
// (kErrProto -> plain WALError), NOT a torn tail (kErrTruncated ->
// repairable TornTailError) — the Python scanner and the host decoder
// make the same distinction, and strict-tpu replay policy depends on
// all three lanes agreeing on which errors are healable.
inline bool len_negative(uint64_t rlen) { return (rlen >> 63) != 0; }

}  // namespace

extern "C" {

uint32_t etcd_crc32c_raw(uint32_t state, const uint8_t* data, uint64_t len) {
  return raw(state, data, len);
}

// Rolling-chain CRC verification over pre-scanned record spans
// (decoder.go:28-47 chain semantics, CRC work only — the framing and
// proto parse already happened in etcd_wal_scan, so the
// no-accelerator replay path pays exactly one parse sweep plus one
// CRC sweep instead of re-parsing every record).  Returns `count`
// when the whole chain verifies, the index of the first bad record
// otherwise, or kErrTruncated for an out-of-range span.
int64_t etcd_chain_verify(const uint8_t* buf, uint64_t n,
                          const uint64_t* doff, const uint64_t* dlen,
                          const uint32_t* stored, uint64_t count,
                          uint32_t seed) {
  uint32_t chain = seed;
  for (uint64_t i = 0; i < count; i++) {
    uint64_t o = doff[i], l = dlen[i];
    if (o > n || l > n - o) return kErrTruncated;
    chain = go_update(chain, buf + o, l);
    if (stored[i] != chain) return static_cast<int64_t>(i);
  }
  return static_cast<int64_t>(count);
}

// Sharded rolling-chain CRC verification: the chain links depend only
// on their *stored* predecessor, so record ranges verify independently
// — thread t seeds from stored[lo-1] and sweeps [lo, hi).  Worth it
// once the CRC work dwarfs thread startup (callers gate on count);
// nthreads <= 1 falls back to the sequential sweep.  Returns `count`
// when the whole chain verifies, the smallest bad-record index
// otherwise, or kErrTruncated for an out-of-range span.
int64_t etcd_chain_verify_mt(const uint8_t* buf, uint64_t n,
                             const uint64_t* doff, const uint64_t* dlen,
                             const uint32_t* stored, uint64_t count,
                             uint32_t seed, uint64_t nthreads) {
  if (nthreads <= 1 || count < 2 * nthreads)
    return etcd_chain_verify(buf, n, doff, dlen, stored, count, seed);
  if (nthreads > 64) nthreads = 64;
  std::vector<int64_t> results(nthreads, static_cast<int64_t>(count));
  std::vector<std::thread> workers;
  uint64_t per = (count + nthreads - 1) / nthreads;
  for (uint64_t t = 0; t < nthreads; t++) {
    uint64_t lo = t * per;
    uint64_t hi = lo + per < count ? lo + per : count;
    if (lo >= hi) break;
    workers.emplace_back([&, t, lo, hi] {
      uint32_t chain = lo ? stored[lo - 1] : seed;
      int64_t r = etcd_chain_verify(buf, n, doff + lo, dlen + lo,
                                    stored + lo, hi - lo, chain);
      if (r < 0)
        results[t] = r;  // span error (negative code)
      else if (static_cast<uint64_t>(r) < hi - lo)
        results[t] = static_cast<int64_t>(lo) + r;  // first bad link
    });
  }
  for (auto& w : workers) w.join();
  int64_t best = static_cast<int64_t>(count);
  for (int64_t r : results) {
    if (r < 0) return r;
    if (r < best) best = r;
  }
  return best;
}

// Batched GroupEntry parse for multi-group restart replay: given the
// record-data spans a WAL scan produced (each = one marshaled Entry),
// locate the Entry's data field and extract the GroupEntry envelope's
// fixed fields, all in one native sweep (the per-entry Python
// unmarshal walk was the restart bottleneck at 1M entries).
// Entry wire: (1: type) (2: term) (3: index) varints, (4: data bytes).
// GroupEntry wire (etcd_tpu/wire/proto.py GroupEntry.marshal):
//   (1: kind varint) (2: group varint) (3: gindex varint)
//   (4: gterm varint) (5: payload bytes)?
// payload_off is absolute into buf; payload_len 0 when absent; an
// Entry without a data field yields kind = -1 (never a group record).
int64_t etcd_ge_scan(const uint8_t* buf, uint64_t n, const uint64_t* off,
                     const uint64_t* len, uint64_t count, int64_t* kind,
                     int64_t* group, int64_t* gindex, int64_t* gterm,
                     uint64_t* payload_off, uint64_t* payload_len) {
  for (uint64_t i = 0; i < count; i++) {
    uint64_t epos = off[i];
    if (epos > n || len[i] > n - epos) return kErrTruncated;
    uint64_t eend = epos + len[i];
    kind[i] = -1;
    group[i] = 0;
    gindex[i] = 0;
    gterm[i] = 0;
    payload_off[i] = 0;
    payload_len[i] = 0;
    // Entry envelope walk -> inner GroupEntry span
    uint64_t pos = 0, rend = 0;
    while (epos < eend) {
      uint64_t tag;
      epos = uvarint(buf, epos, eend, &tag);
      if (!epos) return kErrProto;
      uint64_t fnum = tag >> 3, wt = tag & 7, v;
      if (fnum == 0) return kErrProto;  // illegal tag 0 (proto.py _tag parity)
      if (fnum == 4 && wt == 2) {
        epos = uvarint(buf, epos, eend, &v);
        if (!epos || v > eend - epos) return kErrProto;
        pos = epos;
        rend = epos + v;
        epos += v;
      } else if (wt == 0) {
        epos = uvarint(buf, epos, eend, &v);
        if (!epos) return kErrProto;
      } else if (wt == 2) {
        epos = uvarint(buf, epos, eend, &v);
        if (!epos || v > eend - epos) return kErrProto;
        epos += v;
      } else if (wt == 1) {
        if (eend - epos < 8) return kErrProto;
        epos += 8;
      } else if (wt == 5) {
        if (eend - epos < 4) return kErrProto;
        epos += 4;
      } else {
        return kErrProto;
      }
    }
    if (rend == 0) continue;  // no data field
    kind[i] = 0;
    while (pos < rend) {
      uint64_t tag;
      pos = uvarint(buf, pos, rend, &tag);
      if (!pos) return kErrProto;
      uint64_t fnum = tag >> 3, wt = tag & 7;
      if (fnum == 0) return kErrProto;  // illegal tag 0 (proto.py _tag parity)
      uint64_t v;
      if (fnum >= 1 && fnum <= 4) {
        if (wt != 0) return kErrProto;
        pos = uvarint(buf, pos, rend, &v);
        if (!pos) return kErrProto;
        if (fnum == 1) kind[i] = static_cast<int64_t>(v);
        else if (fnum == 2) group[i] = static_cast<int64_t>(v);
        else if (fnum == 3) gindex[i] = static_cast<int64_t>(v);
        else gterm[i] = static_cast<int64_t>(v);
      } else if (fnum == 5) {
        if (wt != 2) return kErrProto;
        pos = uvarint(buf, pos, rend, &v);
        if (!pos || v > rend - pos) return kErrProto;
        payload_off[i] = pos;
        payload_len[i] = v;
        pos += v;
      } else {  // skip unknown (proto semantics)
        if (wt == 0) {
          pos = uvarint(buf, pos, rend, &v);
          if (!pos) return kErrProto;
        } else if (wt == 2) {
          pos = uvarint(buf, pos, rend, &v);
          if (!pos || v > rend - pos) return kErrProto;
          pos += v;
        } else if (wt == 1) {
          if (rend - pos < 8) return kErrProto;
          pos += 8;
        } else if (wt == 5) {
          if (rend - pos < 4) return kErrProto;
          pos += 4;
        } else {
          return kErrProto;
        }
      }
    }
  }
  return static_cast<int64_t>(count);
}

uint32_t etcd_crc32c_update(uint32_t crc, const uint8_t* data, uint64_t len) {
  return go_update(crc, data, len);
}

// Count framed records (length hops only — no parsing). Lets callers
// allocate scan outputs exactly instead of at worst-case capacity.
int64_t etcd_wal_count(const uint8_t* buf, uint64_t n) {
  uint64_t pos = 0;
  int64_t count = 0;
  while (pos < n) {
    if (pos + 8 > n) return kErrTruncated;
    uint64_t rlen = read_len_le(buf + pos);
    pos += 8;
    if (len_negative(rlen)) return kErrProto;
    if (rlen > n - pos) return kErrTruncated;
    pos += rlen;
    count++;
  }
  return count;
}

// Framing pass for the device replay path: one sequential sweep that
// records, for every framed record, its type, stored crc, data span,
// and (for entries) index/term. NO checksum math here — that is the
// device's job. Returns record count.
int64_t etcd_wal_scan(const uint8_t* buf, uint64_t n, int64_t* types,
                      uint32_t* crcs, uint64_t* data_off, uint64_t* data_len,
                      uint64_t* ent_index, uint64_t* ent_term,
                      uint64_t* ent_type, uint64_t cap) {
  uint64_t pos = 0;
  int64_t count = 0;
  while (pos < n) {
    if (pos + 8 > n) return kErrTruncated;
    uint64_t rlen = read_len_le(buf + pos);
    pos += 8;
    if (len_negative(rlen)) return kErrProto;
    if (rlen > n - pos) return kErrTruncated;
    if (static_cast<uint64_t>(count) >= cap) return kErrCapacity;
    int64_t rc = parse_record(buf, pos, pos + rlen, &types[count],
                              &crcs[count], &data_off[count],
                              &data_len[count]);
    if (rc < 0) return rc;
    ent_index[count] = 0;
    ent_term[count] = 0;
    ent_type[count] = 0;
    if (types[count] == kEntryType && data_len[count]) {
      rc = parse_entry(buf, data_off[count], data_off[count] + data_len[count],
                       &ent_type[count], &ent_term[count], &ent_index[count]);
      if (rc < 0) return rc;
    }
    pos += rlen;
    count++;
  }
  return count;
}

// Length-hop record count over [pos, pos+budget): counts the framed
// records a scan-chunk call starting at `pos` would emit (a record
// straddling the budget boundary counts toward this chunk), so
// chunked callers allocate exactly.  Sets *next_pos to the first
// byte after the chunk's last record.
int64_t etcd_wal_count_range(const uint8_t* buf, uint64_t n, uint64_t pos,
                             uint64_t budget, uint64_t* next_pos) {
  uint64_t start = pos;
  int64_t count = 0;
  while (pos < n && pos - start < budget) {
    if (pos + 8 > n) return kErrTruncated;
    uint64_t rlen = read_len_le(buf + pos);
    pos += 8;
    if (len_negative(rlen)) return kErrProto;
    if (rlen > n - pos) return kErrTruncated;
    pos += rlen;
    count++;
  }
  *next_pos = pos;
  return count;
}

// The single-pass fused scan the reference's hot loop implies
// (wal/wal.go:164-216): frame, proto-parse, entry extraction, and —
// when `verify` is nonzero — the rolling-chain CRC check, all in ONE
// sweep over [pos, min-record-boundary >= pos+budget).  This is both
// the whole-stream fused replay (budget = n: parse + verify with no
// second pass over the blob, closing etcd_chain_verify's re-read) and
// the streaming pipeline's per-chunk scanner (budget = chunk size;
// records never split across chunks — a straddling record belongs to
// the chunk it starts in).
//
// `chain` seeds the rolling CRC; a leading crcType record at stream
// offset 0 re-seeds it (the fresh-decoder rule, wal/wal.go:184-191 —
// its own link then holds trivially).  On a mismatch, returns kErrCRC
// with *first_bad = the CHUNK-LOCAL index of the bad record (output
// arrays are valid up to and including it).  Otherwise returns the
// record count and sets *next_pos to the next chunk's start.
int64_t etcd_wal_scan_chunk(const uint8_t* buf, uint64_t n, uint64_t pos,
                            uint64_t budget, uint32_t chain, int64_t verify,
                            int64_t* types, uint32_t* crcs,
                            uint64_t* data_off, uint64_t* data_len,
                            uint64_t* ent_index, uint64_t* ent_term,
                            uint64_t* ent_type, uint64_t cap,
                            uint64_t* next_pos, int64_t* first_bad) {
  uint64_t start = pos;
  int64_t count = 0;
  *first_bad = -1;
  while (pos < n && pos - start < budget) {
    if (pos + 8 > n) return kErrTruncated;
    uint64_t rlen = read_len_le(buf + pos);
    pos += 8;
    if (len_negative(rlen)) return kErrProto;
    if (rlen > n - pos) return kErrTruncated;
    if (static_cast<uint64_t>(count) >= cap) return kErrCapacity;
    int64_t rc = parse_record(buf, pos, pos + rlen, &types[count],
                              &crcs[count], &data_off[count],
                              &data_len[count]);
    if (rc < 0) return rc;
    ent_index[count] = 0;
    ent_term[count] = 0;
    ent_type[count] = 0;
    if (types[count] == kEntryType && data_len[count]) {
      rc = parse_entry(buf, data_off[count],
                       data_off[count] + data_len[count],
                       &ent_type[count], &ent_term[count],
                       &ent_index[count]);
      if (rc < 0) return rc;
    }
    if (verify) {
      if (start == 0 && count == 0 && types[0] == kCrcType)
        chain = crcs[0];  // fresh-decoder re-seed at the stream head
      chain = go_update(chain, buf + data_off[count], data_len[count]);
      if (crcs[count] != chain) {
        *first_bad = count;
        return kErrCRC;
      }
    }
    pos += rlen;
    count++;
  }
  *next_pos = pos;
  return count;
}

// The reference's sequential hot loop, natively: frame, proto-parse,
// rolling-chain CRC verify per record (decoder.go:28-47), entry
// index/term extraction. This is the single-core baseline the
// device path is measured against. Returns entry count.
int64_t etcd_replay_verify(const uint8_t* buf, uint64_t n, uint32_t seed,
                           uint64_t* last_index, uint64_t* last_term) {
  uint64_t pos = 0;
  uint32_t chain = seed;
  int64_t entries = 0;
  *last_index = 0;
  *last_term = 0;
  while (pos < n) {
    if (pos + 8 > n) return kErrTruncated;
    uint64_t rlen = read_len_le(buf + pos);
    pos += 8;
    if (len_negative(rlen)) return kErrProto;
    if (rlen > n - pos) return kErrTruncated;
    int64_t type;
    uint32_t crc;
    uint64_t doff, dlen;
    int64_t rc = parse_record(buf, pos, pos + rlen, &type, &crc, &doff, &dlen);
    if (rc < 0) return rc;
    chain = go_update(chain, buf + doff, dlen);
    if (crc != chain) return kErrCRC;
    if (type == kEntryType) {
      uint64_t etype, term, index;
      rc = parse_entry(buf, doff, doff + dlen, &etype, &term, &index);
      if (rc < 0) return rc;
      *last_index = index;
      *last_term = term;
      entries++;
    }
    pos += rlen;
  }
  return entries;
}

// Synthetic WAL stream: n_entries entry records, payload_len-byte
// xorshift payloads, rolling chain seeded at `seed`, indices from
// start_index. Returns bytes written.
int64_t etcd_wal_gen(uint64_t n_entries, uint64_t payload_len,
                     uint64_t start_index, uint32_t seed, uint8_t* out,
                     uint64_t out_cap) {
  uint64_t pos = 0;
  uint32_t chain = seed;
  uint64_t rng = 0x9E3779B97F4A7C15ull ^ seed;
  // worst-case record: 8 frame + 2 type + 6 crc + 6 hdr + entry
  uint64_t ent_max = 2 + 11 + 11 + 2 + payload_len + 16;
  for (uint64_t i = 0; i < n_entries; i++) {
    if (pos + 8 + ent_max + 24 > out_cap) return kErrCapacity;
    // entry payload = proto Entry{type=1·0, term, index, data}
    uint8_t* ent = out + pos + 8 + 16;  // leave room; assemble then frame
    uint64_t ep = 0;
    ent[ep++] = 0x08;
    ep = put_uvarint(ent, ep, 0);  // type = EntryNormal
    ent[ep++] = 0x10;
    ep = put_uvarint(ent, ep, 1);  // term = 1
    ent[ep++] = 0x18;
    ep = put_uvarint(ent, ep, start_index + i);
    ent[ep++] = 0x22;
    ep = put_uvarint(ent, ep, payload_len);
    for (uint64_t j = 0; j < payload_len; j++) {
      rng ^= rng << 13;
      rng ^= rng >> 7;
      rng ^= rng << 17;
      ent[ep++] = static_cast<uint8_t>(rng);
    }
    chain = go_update(chain, ent, ep);
    // record = {type=2, crc=chain, data=ent}
    uint8_t hdr[32];
    uint64_t hp = 0;
    hdr[hp++] = 0x08;
    hp = put_uvarint(hdr, hp, 2);
    hdr[hp++] = 0x10;
    hp = put_uvarint(hdr, hp, chain);
    hdr[hp++] = 0x1A;
    hp = put_uvarint(hdr, hp, ep);
    uint64_t rlen = hp + ep;
    std::memcpy(out + pos, &rlen, 8);
    std::memmove(out + pos + 8, hdr, hp);
    std::memmove(out + pos + 8 + hp, ent, ep);
    pos += 8 + rlen;
  }
  return static_cast<int64_t>(pos);
}

// Right-align record data spans into a zero-padded row-major [n, L]
// buffer for device upload. Rows longer than L are an error.
int64_t etcd_pad_rows(const uint8_t* blob, const uint64_t* data_off,
                      const uint64_t* data_len, uint64_t n, uint64_t L,
                      uint8_t* out) {
  std::memset(out, 0, n * L);
  for (uint64_t i = 0; i < n; i++) {
    if (data_len[i] > L) return kErrCapacity;
    std::memcpy(out + i * L + (L - data_len[i]), blob + data_off[i],
                data_len[i]);
  }
  return 0;
}

}  // extern "C"

// The serial, byte-walking parts of an ORC stripe's decode
// (io/orc_device.py): compression-block deframing into one buffer, the
// check of a decimal's varint stream, and the RLEv2 run-STRUCTURE walk
// (`_rlev2_runs`), the serial part of an integer stream's decode. One pass over the run headers fills a
// run table without expanding a value: SHORT_REPEAT and fixed-delta DELTA
// runs as (base, step), DIRECT runs with their big-endian payload copied
// into `packed`, and PATCHED_BASE and literal-delta DELTA runs as the byte
// position of their header, for the caller to decode (a few hundred runs
// in a stream of hundreds of thousands). Python's walk of 387,064 runs took
// 0.45 s a query; this one is the same table.

#include <cstdint>
#include <cstring>

namespace {

inline int decode_width(int code) {
  if (code <= 23) return code + 1;
  static const int wide[8] = {26, 28, 30, 32, 40, 48, 56, 64};
  return wide[code - 24];
}

inline int closest_fixed_bits(int n) {
  if (n <= 24) return n < 1 ? 1 : n;
  static const int wide[8] = {26, 28, 30, 32, 40, 48, 56, 64};
  for (int w : wide)
    if (n <= w) return w;
  return 64;
}

inline bool uvarint(const uint8_t* buf, int64_t len, int64_t* pos,
                    uint64_t* out) {
  uint64_t v = 0;
  for (int shift = 0; shift < 70; shift += 7) {
    if (*pos >= len) return false;
    uint8_t b = buf[(*pos)++];
    v |= static_cast<uint64_t>(b & 0x7F) << shift;
    if (!(b & 0x80)) {
      *out = v;
      return true;
    }
  }
  return false;
}

inline int64_t unzigzag(uint64_t v) {
  return static_cast<int64_t>(v >> 1) ^ -static_cast<int64_t>(v & 1);
}

}  // namespace

extern "C" {

int32_t srtpu_snappy_decompress(const uint8_t* src, int64_t slen,
                                uint8_t* dst, int64_t dlen);

// A compressed stream's blocks (3-byte headers: length << 1 | stored as it
// is) into `dst`. kind: 0 none, 2 snappy (a block starts with its
// uncompressed length as a varint). With dst == nullptr only the
// uncompressed length is summed. Returns that length, -1 on a truncated or
// malformed stream or a `dst` too small.
int64_t srtpu_orc_deframe(const uint8_t* src, int64_t len, int32_t kind,
                          uint8_t* dst, int64_t cap) {
  if (kind == 0) {
    if (dst) {
      if (len > cap) return -1;
      std::memcpy(dst, src, len);
    }
    return len;
  }
  if (kind != 2) return -1;
  int64_t pos = 0, out = 0;
  while (pos < len) {
    if (pos + 3 > len) return -1;
    const uint32_t h = src[pos] | (src[pos + 1] << 8) | (src[pos + 2] << 16);
    const int64_t n = h >> 1;
    pos += 3;
    if (pos + n > len) return -1;
    int64_t size = n;
    if (!(h & 1)) {
      int64_t p = pos;
      uint64_t u;
      if (!uvarint(src, pos + n, &p, &u)) return -1;
      size = static_cast<int64_t>(u);
    }
    if (dst) {
      if (out + size > cap) return -1;
      if (h & 1)
        std::memcpy(dst + out, src + pos, n);
      else if (srtpu_snappy_decompress(src + pos, n, dst + out, size) != 0)
        return -1;
    }
    out += size;
    pos += n;
  }
  return out;
}

// One pass over a zigzag-varint stream: the number of values that end in
// it (bytes under 128) and the longest value among the first `values` of
// them, in bytes. Returns 0, or 1 where the stream ends inside a value.
int32_t srtpu_varint_scan(const uint8_t* buf, int64_t len, int64_t values,
                          int64_t* ends, int64_t* longest) {
  int64_t n = 0, run = 0, most = 0;
  for (int64_t i = 0; i < len; ++i) {
    ++run;
    if (buf[i] < 128) {
      if (n < values && run > most) most = run;
      ++n;
      run = 0;
    }
  }
  *ends = n;
  *longest = most;
  return run != 0;
}

// kinds: 0 repeat(base), 1 arithmetic(base, step), 2 packed(offs = bit
// offset into `packed`, width), 4 literal-delta DELTA and 5 PATCHED_BASE
// (offs = byte position of the run's header in `buf`). Output arrays hold
// one run per 2 stream bytes at most; `packed` holds `len` bytes. Returns
// the run count and writes the packed byte count, -1 on a truncated or
// malformed stream, -2 on a stream that ends before `num_values` values.
int64_t srtpu_orc_rlev2_scan(const uint8_t* buf, int64_t len,
                             int64_t num_values, int32_t is_signed,
                             uint8_t* kinds, int64_t* counts, int64_t* base,
                             int64_t* step, int64_t* offs, uint8_t* width,
                             uint8_t* packed, int64_t* packed_len) {
  int64_t pos = 0, total = 0, n = 0, plen = 0;
  while (total < num_values && pos < len) {
    const uint8_t b0 = buf[pos];
    const int enc = b0 >> 6;
    int64_t cnt;
    kinds[n] = 0;
    base[n] = step[n] = offs[n] = 0;
    width[n] = 0;
    if (enc == 0) {  // SHORT_REPEAT
      const int nbytes = ((b0 >> 3) & 7) + 1;
      cnt = (b0 & 7) + 3;
      if (pos + 1 + nbytes > len) return -1;
      uint64_t v = 0;
      for (int i = 0; i < nbytes; ++i) v = (v << 8) | buf[pos + 1 + i];
      base[n] = is_signed ? unzigzag(v) : static_cast<int64_t>(v);
      pos += 1 + nbytes;
    } else {
      if (pos + 2 > len) return -1;
      const int wcode = (b0 >> 1) & 0x1F;
      cnt = (((b0 & 1) << 8) | buf[pos + 1]) + 1;
      if (enc == 1) {  // DIRECT
        const int w = decode_width(wcode);
        const int64_t nbytes = (cnt * w + 7) / 8;
        if (pos + 2 + nbytes > len) return -1;
        kinds[n] = 2;
        offs[n] = plen * 8;
        width[n] = static_cast<uint8_t>(w);
        std::memcpy(packed + plen, buf + pos + 2, nbytes);
        plen += nbytes;
        pos += 2 + nbytes;
      } else if (enc == 3) {  // DELTA
        int64_t p = pos + 2;
        uint64_t b, d;
        if (!uvarint(buf, len, &p, &b) || !uvarint(buf, len, &p, &d))
          return -1;
        if (wcode == 0) {  // fixed delta: v_i = base + i * delta
          kinds[n] = 1;
          base[n] = is_signed ? unzigzag(b) : static_cast<int64_t>(b);
          step[n] = unzigzag(d);
        } else {
          if (cnt < 2) return -1;
          const int64_t nbytes = ((cnt - 2) * decode_width(wcode) + 7) / 8;
          if (p + nbytes > len) return -1;
          kinds[n] = 4;
          offs[n] = pos;
          p += nbytes;
        }
        pos = p;
      } else {  // PATCHED_BASE
        if (pos + 4 > len) return -1;
        const int w = decode_width(wcode);
        const uint8_t b2 = buf[pos + 2], b3 = buf[pos + 3];
        const int bw = ((b2 >> 5) & 7) + 1;
        const int pw = decode_width(b2 & 0x1F);
        const int pgw = ((b3 >> 5) & 7) + 1;
        const int pl = b3 & 0x1F;
        const int64_t nbytes =
            4 + bw + (cnt * w + 7) / 8 +
            (static_cast<int64_t>(pl) * closest_fixed_bits(pgw + pw) + 7) / 8;
        if (pos + nbytes > len) return -1;
        kinds[n] = 5;
        offs[n] = pos;
        pos += nbytes;
      }
    }
    counts[n] = cnt;
    total += cnt;
    ++n;
  }
  *packed_len = plen;
  return total < num_values ? -2 : n;
}

// A run table (`srtpu_orc_rlev2_scan`'s arrays, literal runs already given
// a bit offset and a width by the caller) written straight into the two
// arrays the device reads (io/orc_device.py `_RunTable.device_arrays`):
// `ends` int32[rb], the runs' exclusive end slots, padding runs ending where
// the last real one does, and `table` uint32[7][rb], zeroed by the caller:
// first slot, base and step as 32-bit halves, bit offset, width | packed << 8.
// One pass, no temporary. Returns 1 where a width passes 32 bits.
int32_t srtpu_orc_run_table(const uint8_t* kinds, const int64_t* counts,
                            const int64_t* base, const int64_t* step,
                            const int64_t* offs, const uint8_t* width,
                            int64_t n, int64_t rb, int32_t* ends,
                            uint32_t* table) {
  int64_t end = 0;
  int32_t wide = 0;
  for (int64_t i = 0; i < n; ++i) {
    const uint64_t b = static_cast<uint64_t>(base[i]);
    const uint64_t s = static_cast<uint64_t>(step[i]);
    table[0 * rb + i] = static_cast<uint32_t>(end);
    table[1 * rb + i] = static_cast<uint32_t>(b);
    table[2 * rb + i] = static_cast<uint32_t>(b >> 32);
    table[3 * rb + i] = static_cast<uint32_t>(s);
    table[4 * rb + i] = static_cast<uint32_t>(s >> 32);
    table[5 * rb + i] = static_cast<uint32_t>(offs[i]);
    table[6 * rb + i] = width[i] | (kinds[i] >= 2 ? 0x100u : 0u);
    if (width[i] > 32) wide = 1;
    end += counts[i];
    ends[i] = static_cast<int32_t>(end);
  }
  for (int64_t i = n; i < rb; ++i) ends[i] = static_cast<int32_t>(end);
  return wide;
}

}  // extern "C"

// CRC32 (IEEE / zlib polynomial) + snapshot frame scanning — the framing
// layer under the persistence input/operator snapshot logs (reference analog:
// src/persistence/input_snapshot.rs chunk framing).  zlib-compatible so the
// Python fallback can use zlib.crc32 and read the same files.
#include "../include/pathway_native.h"

#include <cstring>

namespace {

struct Crc32Table {
  uint32_t t[256];
  Crc32Table() {
    for (uint32_t i = 0; i < 256; ++i) {
      uint32_t c = i;
      for (int k = 0; k < 8; ++k)
        c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
      t[i] = c;
    }
  }
};
const Crc32Table kCrc;

inline uint32_t read_u32(const uint8_t* p) {
  uint32_t v;
  std::memcpy(&v, p, 4);
  return v;
}

}  // namespace

extern "C" {

uint32_t pn_crc32(const uint8_t* data, int64_t len, uint32_t crc) {
  crc = ~crc;
  for (int64_t i = 0; i < len; ++i)
    crc = kCrc.t[(crc ^ data[i]) & 0xFF] ^ (crc >> 8);
  return ~crc;
}

int64_t pn_frame_scan(const uint8_t* buf, int64_t len, int64_t* offsets,
                      int64_t* lengths, int64_t max_frames, int64_t* consumed) {
  int64_t pos = 0, count = 0;
  while (count < max_frames && pos + 8 <= len) {
    uint32_t payload_len = read_u32(buf + pos);
    uint32_t crc = read_u32(buf + pos + 4);
    if (pos + 8 + (int64_t)payload_len > len) break;  // truncated tail
    if (pn_crc32(buf + pos + 8, payload_len, 0) != crc) break;  // corruption
    offsets[count] = pos + 8;
    lengths[count] = payload_len;
    ++count;
    pos += 8 + payload_len;
  }
  *consumed = pos;
  return count;
}

}  // extern "C"

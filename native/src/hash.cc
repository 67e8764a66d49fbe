// Row key hashing: xxh3-64 over each serialized row slice produced by
// pn_serialize_rows.  Removes the per-row Python xxhash call from
// ref_scalars_batch (internals/keys.py) — with 50k-row deltas that loop is
// the single hottest line of the relational engine.
//
// The algorithm must be bit-identical to python-xxhash's xxh3_64_intdigest,
// so we use the canonical header-only xxHash implementation when one is
// discoverable at build time (pyarrow vendors it; native.build() passes its
// include dir).  Without the header, pn_hash_rows reports "unavailable" and
// the Python side keeps its per-row loop — behavior identical, just slower.
#include "../include/pathway_native.h"

#if defined(__has_include)
#if __has_include(<xxhash.h>)
#define PN_HAVE_XXHASH 1
#define XXH_INLINE_ALL
#include <xxhash.h>
#endif
#endif

extern "C" int32_t pn_hash_rows(const uint8_t* buf, int64_t /*buf_len*/,
                                const int64_t* offsets, int64_t n_rows,
                                uint64_t* out) {
#ifdef PN_HAVE_XXHASH
  for (int64_t i = 0; i < n_rows; ++i) {
    out[i] = (uint64_t)XXH3_64bits(buf + offsets[i],
                                   (size_t)(offsets[i + 1] - offsets[i]));
  }
  return 0;
#else
  (void)buf;
  (void)offsets;
  (void)n_rows;
  (void)out;
  return -1;
#endif
}

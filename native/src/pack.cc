// Sequence-packing row layout — the host side of a packed forward
// (models/packing.py).
//
// What `pack_rows` + `pad_packed_rows` + the rerank pipeline's `pair_slot`
// loop do in a Python loop per sequence and some thirty numpy calls, in one
// call: under the rerank cell's 32 callers every one of those array calls is
// a chance to hand the GIL over, and the chip waits for the rows.
//
// Placement is `pack_rows`' best-fit-decreasing to the letter (the Python
// body stays the fallback and the tests' oracle): stable order by descending
// length; the open row with the least capacity that still holds the sequence,
// ties to the lower row id (`bisect_left` on ascending (cap_left, row_id));
// at most max_docs_per_row sequences a row; a row stays open only while it
// has fewer than that and at least 2 tokens left.
#include "../include/pathway_native.h"

#include <algorithm>
#include <cstring>
#include <numeric>
#include <utility>
#include <vector>

extern "C" int32_t pn_pack_rows(
    const int32_t* ids_b, int64_t n, int64_t width, const int64_t* lens,
    int64_t L, int64_t max_docs_per_row, const int64_t* row_buckets,
    int64_t n_row_buckets, const int64_t* seg_buckets, const int32_t* slot_ids,
    int32_t drop_slot, int32_t* out_ids, int32_t* out_segments,
    int32_t* out_positions, int32_t* out_pair_slot, int64_t* row_of,
    int64_t* seg_of, int64_t* out_dims) {
  if (n < 1 || L < 1 || max_docs_per_row < 1 || n_row_buckets < 1) return -1;
  for (int64_t i = 0; i < n; ++i)
    if (lens[i] < 0 || lens[i] > L || lens[i] > width) return -1;

  std::vector<int64_t> order(n);
  std::iota(order.begin(), order.end(), 0);
  std::stable_sort(order.begin(), order.end(),
                   [lens](int64_t a, int64_t b) { return lens[a] > lens[b]; });

  using Open = std::pair<int64_t, int64_t>;  // (cap_left, row_id), ascending
  std::vector<Open> open_caps;
  std::vector<int64_t> off_of(n), row_fill, row_count;
  for (int64_t i : order) {
    const int64_t need = lens[i];
    auto it = std::lower_bound(open_caps.begin(), open_caps.end(),
                               Open(need, -1));
    if (it != open_caps.end()) {
      const int64_t cap_left = it->first, rid = it->second;
      open_caps.erase(it);
      row_of[i] = rid;
      seg_of[i] = row_count[rid]++;
      off_of[i] = row_fill[rid];
      row_fill[rid] += need;
      const Open left(cap_left - need, rid);
      if (row_count[rid] < max_docs_per_row && left.first >= 2)
        open_caps.insert(
            std::lower_bound(open_caps.begin(), open_caps.end(), left), left);
    } else {
      const int64_t rid = (int64_t)row_fill.size();
      row_of[i] = rid;
      seg_of[i] = 0;
      off_of[i] = 0;
      row_fill.push_back(need);
      row_count.push_back(1);
      const Open left(L - need, rid);
      if (max_docs_per_row > 1 && left.first >= 2)
        open_caps.insert(
            std::lower_bound(open_caps.begin(), open_caps.end(), left), left);
    }
  }
  const int64_t R = (int64_t)row_fill.size();
  const int64_t n_seg = *std::max_element(row_count.begin(), row_count.end());
  const int64_t* rb = std::lower_bound(row_buckets, row_buckets + n_row_buckets, R);
  if (rb == row_buckets + n_row_buckets) return -1;
  const int64_t Rb = *rb, Sb = seg_buckets[n_seg - 1];
  if (Sb < n_seg) return -1;

  const size_t bytes = (size_t)(Rb * L) * sizeof(int32_t);
  std::memset(out_ids, 0, bytes);  // pad rows: segment 0 = fully masked
  std::memset(out_segments, 0, bytes);
  std::memset(out_positions, 0, bytes);
  for (int64_t i = 0; i < n; ++i) {
    const int64_t at = row_of[i] * L + off_of[i], len = lens[i];
    std::copy_n(ids_b + i * width, len, out_ids + at);
    std::fill_n(out_segments + at, len, (int32_t)(seg_of[i] + 1));
    std::iota(out_positions + at, out_positions + at + len, 0);
  }
  if (slot_ids != nullptr) {
    std::fill_n(out_pair_slot, Rb * Sb, drop_slot);
    for (int64_t i = 0; i < n; ++i)
      out_pair_slot[row_of[i] * Sb + seg_of[i]] = slot_ids[i];
  }
  out_dims[0] = R;
  out_dims[1] = n_seg;
  out_dims[2] = Rb;
  out_dims[3] = Sb;
  return 0;
}

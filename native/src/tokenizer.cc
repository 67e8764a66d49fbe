// Hashing tokenizer — the ingest hot loop (models/tokenizer.py).
//
// The Python tokenizer does, per word: regex scan, .lower().encode(), one
// python-xxhash call.  At ~80k docs/s it was the binding constraint on
// streaming embed+index ingest (bench.py phase_ingest) — the TPU forward
// pass is >10x faster than the host could feed it.  This native path
// tokenizes a whole text batch in one call.
//
// Semantics are BIT-IDENTICAL to HashTokenizer for ASCII input (the caller
// routes non-ASCII batches to the Python path):
//   token pattern [\w']+|[^\w\s] with \w = [A-Za-z0-9_], \s = " \t\n\r\f\v"
//   id = reserved + xxh3_64(token.lower()) % (vocab_size - reserved)
#include "../include/pathway_native.h"

#include <algorithm>
#include <memory>
#include <new>

#if defined(__has_include)
#if __has_include(<xxhash.h>)
#define PN_HAVE_XXHASH 1
#define XXH_INLINE_ALL
#include <xxhash.h>
#endif
#endif

#ifdef PN_HAVE_XXHASH
namespace {
inline bool is_word(uint8_t c) {
  return (c >= 'a' && c <= 'z') || (c >= 'A' && c <= 'Z') ||
         (c >= '0' && c <= '9') || c == '_';
}
inline bool is_space(uint8_t c) {
  // Python's \s over ASCII: space, \t-\r (0x09-0x0D), AND the separator
  // controls \x1c-\x1f (unicodedata puts FS/GS/RS/US in the \s class)
  return c == ' ' || (c >= 0x09 && c <= 0x0D) || (c >= 0x1C && c <= 0x1F);
}
inline uint8_t lower(uint8_t c) {
  return (c >= 'A' && c <= 'Z') ? (uint8_t)(c + 32) : c;
}

void tokenize(const uint8_t* blob, const int64_t* offsets, int64_t n_texts,
              int32_t vocab_size, int32_t reserved, int32_t* out_ids,
              int64_t* out_offsets) {
  const uint64_t mod = (uint64_t)(vocab_size - reserved);
  uint8_t word[4096];  // lowered-token scratch; longer tokens hash streamed
  int64_t out_n = 0;
  for (int64_t t = 0; t < n_texts; ++t) {
    out_offsets[t] = out_n;
    const uint8_t* p = blob + offsets[t];
    const uint8_t* end = blob + offsets[t + 1];
    while (p < end) {
      uint8_t c = *p;
      if (is_word(c) || c == '\'') {
        // maximal [\w']+ run, lowered into scratch (or streamed when huge)
        const uint8_t* start = p;
        size_t n = 0;
        while (p < end && (is_word(*p) || *p == '\'')) {
          if (n < sizeof(word)) word[n] = lower(*p);
          ++n;
          ++p;
        }
        uint64_t h;
        if (n <= sizeof(word)) {
          h = (uint64_t)XXH3_64bits(word, n);
        } else {
          XXH3_state_t* st = XXH3_createState();
          XXH3_64bits_reset(st);
          uint8_t chunk[4096];
          for (size_t i = 0; i < n; i += sizeof(chunk)) {
            size_t m = n - i < sizeof(chunk) ? n - i : sizeof(chunk);
            for (size_t j = 0; j < m; ++j) chunk[j] = lower(start[i + j]);
            XXH3_64bits_update(st, chunk, m);
          }
          h = (uint64_t)XXH3_64bits_digest(st);
          XXH3_freeState(st);
        }
        out_ids[out_n++] = (int32_t)(reserved + (h % mod));
      } else if (is_space(c)) {
        ++p;
      } else {
        // single non-word, non-space char ([^\w\s]); ASCII lower is identity
        // for punctuation but apply it anyway to mirror .lower()
        uint8_t lc = lower(c);
        uint64_t h = (uint64_t)XXH3_64bits(&lc, 1);
        out_ids[out_n++] = (int32_t)(reserved + (h % mod));
        ++p;
      }
    }
  }
  out_offsets[n_texts] = out_n;
}
}  // namespace
#endif

// Pair rows ``CLS a SEP b SEP`` for a whole batch (HashTokenizer.encode_pairs):
// tokenize the batch's DISTINCT texts once, then lay out pair i from texts
// a_slot[i], b_slot[i].  Truncation is HashTokenizer.encode's longest-first
// loop in closed form: while over ``budget`` (>= 2) it pops from a if
// len(a) >= len(b) and len(a) > 1, else from b if len(b) > 1 — so a side at
// or under its half (budget/2 for a, the larger half for b: ties take from
// a) is kept whole and the other gets the rest; with both over, each gets
// its half; an empty side stays empty.  Rows are written at ``stride``
// (>= budget + 3) int32s into zeroed out_ids/out_mask; out_lens[i] is the
// row's token count.  tok_ids (capacity >= blob length) and tok_offsets
// (n_texts + 1) are scratch.
extern "C" int32_t pn_encode_pairs(
    const uint8_t* blob, const int64_t* offsets, int64_t n_texts,
    int32_t vocab_size, int32_t reserved, const int64_t* a_slot,
    const int64_t* b_slot, int64_t n_pairs, int64_t budget, int32_t cls_id,
    int32_t sep_id, int64_t stride, int32_t* tok_ids, int64_t* tok_offsets,
    int32_t* out_ids, int32_t* out_mask, int64_t* out_lens) {
#ifdef PN_HAVE_XXHASH
  tokenize(blob, offsets, n_texts, vocab_size, reserved, tok_ids, tok_offsets);
  const int64_t half = budget / 2;
  for (int64_t i = 0; i < n_pairs; ++i) {
    const int32_t* a = tok_ids + tok_offsets[a_slot[i]];
    const int32_t* b = tok_ids + tok_offsets[b_slot[i]];
    const int64_t la = tok_offsets[a_slot[i] + 1] - tok_offsets[a_slot[i]];
    const int64_t lb = tok_offsets[b_slot[i] + 1] - tok_offsets[b_slot[i]];
    const int64_t ka = std::min(la, std::max(half, budget - lb));
    const int64_t kb = std::min(lb, std::max(budget - half, budget - la));
    int32_t* row = out_ids + i * stride;
    int64_t n = 0;
    row[n++] = cls_id;
    for (int64_t j = 0; j < ka; ++j) row[n++] = a[j];
    row[n++] = sep_id;
    for (int64_t j = 0; j < kb; ++j) row[n++] = b[j];
    row[n++] = sep_id;
    std::fill_n(out_mask + i * stride, n, 1);
    out_lens[i] = n;
  }
  return 0;
#else
  (void)blob; (void)offsets; (void)n_texts; (void)vocab_size; (void)reserved;
  (void)a_slot; (void)b_slot; (void)n_pairs; (void)budget; (void)cls_id;
  (void)sep_id; (void)stride; (void)tok_ids; (void)tok_offsets;
  (void)out_ids; (void)out_mask; (void)out_lens;
  return -1;
#endif
}

// Single-text rows ``CLS t... SEP`` of a batch, padded
// (HashTokenizer.encode_batch): tokenize every text, keep at most
// max_length - 2 tokens of each, take the shared width L = widths[longest
// framed row] (the width RULE stays in Python, models/tokenizer.py ``_width``,
// and comes in as that table of max_length + 1 entries), cut rows to L - 2
// tokens where a caller's ``pad_to`` is shorter than they are, and write
// n_rows >= n_texts rows AT STRIDE L: the caller sizes out_ids / out_mask for
// width_cap >= L a row and takes a view of their head, no copy.  Rows past
// n_texts are pad_id / 0.  The ragged token ids are scratch of this call's
// own (every token spans >= 1 byte of the blob).
extern "C" int32_t pn_encode_batch(
    const uint8_t* blob, const int64_t* offsets, int64_t n_texts,
    int32_t vocab_size, int32_t reserved, int64_t max_length,
    const int64_t* widths, int64_t width_cap, int32_t cls_id, int32_t sep_id,
    int32_t pad_id, int64_t n_rows, int32_t* out_ids, int32_t* out_mask,
    int64_t* out_width) {
#ifdef PN_HAVE_XXHASH
  if (max_length < 2 || n_texts < 1 || n_rows < n_texts) return -1;
  for (int64_t t = 0; t < n_texts; ++t)
    if (offsets[t + 1] < offsets[t]) return -1;
  const int64_t blob_len = offsets[n_texts] - offsets[0];
  std::unique_ptr<int32_t[]> ids_buf(new (std::nothrow) int32_t[blob_len + 1]);
  std::unique_ptr<int64_t[]> off_buf(new (std::nothrow) int64_t[n_texts + 1]);
  if (!ids_buf || !off_buf) return -1;
  int32_t* tok_ids = ids_buf.get();
  int64_t* tok_offsets = off_buf.get();
  tokenize(blob, offsets, n_texts, vocab_size, reserved, tok_ids, tok_offsets);
  int64_t longest = 0;
  for (int64_t t = 0; t < n_texts; ++t)
    longest = std::max(longest, tok_offsets[t + 1] - tok_offsets[t]);
  const int64_t L = widths[std::min(longest, max_length - 2) + 2];
  if (L < 2 || L > width_cap) return -1;
  const int64_t keep = std::min(max_length, L) - 2;
  for (int64_t t = 0; t < n_texts; ++t) {
    const int32_t* tok = tok_ids + tok_offsets[t];
    const int64_t k = std::min(tok_offsets[t + 1] - tok_offsets[t], keep);
    int32_t* row = out_ids + t * L;
    row[0] = cls_id;
    std::copy_n(tok, k, row + 1);
    row[k + 1] = sep_id;
    std::fill(row + k + 2, row + L, pad_id);
    int32_t* m = out_mask + t * L;
    std::fill_n(m, k + 2, 1);
    std::fill(m + k + 2, m + L, 0);
  }
  std::fill(out_ids + n_texts * L, out_ids + n_rows * L, pad_id);
  std::fill(out_mask + n_texts * L, out_mask + n_rows * L, 0);
  *out_width = L;
  return 0;
#else
  (void)blob; (void)offsets; (void)n_texts; (void)vocab_size; (void)reserved;
  (void)max_length; (void)widths; (void)width_cap; (void)cls_id; (void)sep_id;
  (void)pad_id; (void)n_rows; (void)out_ids; (void)out_mask; (void)out_width;
  return -1;
#endif
}

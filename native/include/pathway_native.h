/* C ABI of the pathway-tpu native runtime library.
 *
 * Host-side hot loops that the reference implements in Rust (connector
 * scanners src/connectors/scanner/, value serialization src/engine/value.rs,
 * snapshot framing src/persistence/) are implemented here in C++ and loaded
 * from Python via ctypes (pathway_tpu/native/__init__.py).  Every entry point
 * has a pure-Python fallback with identical semantics.
 */
#pragma once
#include <stdint.h>

#ifdef __cplusplus
extern "C" {
#endif

/* ---- CSV scanning (RFC-4180: quoted fields, "" escapes, \r\n) ----
 *
 * Two-pass API over an in-memory buffer:
 *   pass 1: pn_csv_count fills n_rows / n_cells so the caller can allocate;
 *   pass 2: pn_csv_scan fills
 *     row_cell_start[n_rows+1] — cumulative cell index per row,
 *     cell_off[n_cells], cell_len[n_cells] — byte extents of each cell
 *       (excluding the outer quotes of a quoted field),
 *     cell_quoted[n_cells] — 1 if the field was quoted (may contain "").
 * Rows are terminated by \n or \r\n; a trailing row without a newline counts.
 * Empty lines produce zero-cell rows (callers usually skip them).
 * Returns 0 on success, -1 on inconsistent arguments. */
int pn_csv_count(const uint8_t* buf, int64_t len, uint8_t delim, uint8_t quote,
                 int64_t* n_rows, int64_t* n_cells);
int pn_csv_scan(const uint8_t* buf, int64_t len, uint8_t delim, uint8_t quote,
                int64_t* row_cell_start, int64_t* cell_off, int64_t* cell_len,
                uint8_t* cell_quoted);

/* Collapse "" -> " in a quoted field body; dst must hold len bytes.
 * Returns the number of bytes written. */
int64_t pn_csv_unescape(const uint8_t* src, int64_t len, uint8_t quote,
                        uint8_t* dst);

/* ---- typed field parsers (columnar, ASCII) ----
 * Parse n fields given by (off, len) into typed outputs; ok[i]=1 on success,
 * 0 on malformed input (out[i] is then 0/NaN). */
void pn_parse_int64(const uint8_t* buf, const int64_t* off, const int64_t* len,
                    int64_t n, int64_t* out, uint8_t* ok);
void pn_parse_float64(const uint8_t* buf, const int64_t* off, const int64_t* len,
                      int64_t n, double* out, uint8_t* ok);

/* ---- row serialization for key derivation ----
 * Byte-for-byte identical to pathway_tpu.internals.keys._serialize_value.
 * col_types: 0=none, 1=bool, 2=int64, 3=float64, 4=str, 5=bytes, 6=pointer.
 * col_data[c]: pointer to int64_t / uint8_t / double data per type; for
 * str/bytes it is the concatenated blob with col_offsets[c] =
 * int64_t[n_rows+1] extents.
 * col_null[c]: optional byte mask (1 = null -> serialize as None), or NULL.
 * Writes rows into out (capacity out_cap) and row_offsets[n_rows+1].
 * Returns total bytes needed; if > out_cap nothing useful was written and the
 * caller must retry with a larger buffer. */
int64_t pn_serialize_rows(int64_t n_rows, int32_t n_cols,
                          const uint8_t* col_types,
                          const void* const* col_data,
                          const int64_t* const* col_offsets,
                          const uint8_t* const* col_null,
                          uint8_t* out, int64_t out_cap,
                          int64_t* row_offsets);

/* ---- row key hashing ----
 * xxh3-64 of each row slice [offsets[i], offsets[i+1]) of buf (the layout
 * pn_serialize_rows produces) into out[n_rows].  Returns 0, or -1 when the
 * library was built without an xxhash implementation (caller falls back to
 * hashing in Python; see native/src/hash.cc). */
int32_t pn_hash_rows(const uint8_t* buf, int64_t buf_len,
                     const int64_t* offsets, int64_t n_rows, uint64_t* out);

/* ---- CRC32 (IEEE, zlib-compatible) and snapshot frame scanning ----
 * Frame format: [u32 LE payload_len][u32 LE crc32(payload)][payload].
 * pn_frame_scan walks buf, validating frames; fills offsets/lengths of up to
 * max_frames payloads, sets *consumed to the byte length of the valid prefix
 * (truncation/corruption point), and returns the number of valid frames. */
uint32_t pn_crc32(const uint8_t* data, int64_t len, uint32_t crc);
int64_t pn_frame_scan(const uint8_t* buf, int64_t len, int64_t* offsets,
                      int64_t* lengths, int64_t max_frames, int64_t* consumed);

/* ---- hashing tokenizer (ASCII fast path; models/tokenizer.py) ----
 * blob = concatenated ASCII texts, offsets[n_texts+1] their boundaries.
 * Both entry points scan each text into word-hash ids ([\w']+ runs and
 * single punctuation chars, lowered, xxh3 % (vocab_size - reserved) +
 * reserved), then lay out framed rows.  Both return -1 when built without
 * xxhash (caller uses the Python tokenizer). */

/* Pair rows ``CLS a SEP b SEP`` of a batch (HashTokenizer.encode_pairs):
 * scans the n_texts DISTINCT texts of blob once each (into the scratch
 * tok_ids, capacity >= blob length: every token spans >= 1 byte, and
 * tok_offsets, n_texts + 1), then writes pair
 * i = (text a_slot[i], text b_slot[i]), truncated longest-first to budget
 * (>= 2) tokens, at out_ids + i*stride with ones in out_mask (both zeroed by
 * the caller, stride >= budget + 3) and its token count in out_lens[i].
 * Returns 0, or -1 when built without xxhash. */
int32_t pn_encode_pairs(const uint8_t* blob, const int64_t* offsets,
                        int64_t n_texts, int32_t vocab_size, int32_t reserved,
                        const int64_t* a_slot, const int64_t* b_slot,
                        int64_t n_pairs, int64_t budget, int32_t cls_id,
                        int32_t sep_id, int64_t stride, int32_t* tok_ids,
                        int64_t* tok_offsets, int32_t* out_ids,
                        int32_t* out_mask, int64_t* out_lens);

/* Single-text rows ``CLS t... SEP`` of a batch, padded
 * (HashTokenizer.encode_batch): scans the n_texts >= 1 texts of blob, keeps
 * at most max_length - 2 (>= 0) tokens of each, and takes the shared width
 * L = widths[longest framed row]: widths[0..max_length] is the caller's
 * width rule as a table (models/tokenizer.py _width).  Rows longer than L (a
 * pad_to under the longest row) keep L - 2 tokens.  Writes n_rows >= n_texts
 * rows at stride L into out_ids / out_mask (capacity n_rows * width_cap
 * int32 each, their contents on entry do not matter): cls_id, the tokens,
 * sep_id, pad_id to the end, ones under the framed tokens; rows past
 * n_texts all pad_id / 0.  *out_width = L.  Returns 0, or -1 when built
 * without xxhash or on arguments it cannot lay out (offsets that descend,
 * L < 2, L > width_cap). */
int32_t pn_encode_batch(const uint8_t* blob, const int64_t* offsets,
                        int64_t n_texts, int32_t vocab_size, int32_t reserved,
                        int64_t max_length, const int64_t* widths,
                        int64_t width_cap, int32_t cls_id, int32_t sep_id,
                        int32_t pad_id, int64_t n_rows, int32_t* out_ids,
                        int32_t* out_mask, int64_t* out_width);

/* ---- sequence packing (models/packing.py) ----
 * pack_rows + pad_packed_rows + the rerank pipeline's pair_slot loop in one
 * call.  ids_b [n, width] holds n >= 1 tokenized sequences of lens[i] tokens
 * (0 <= lens[i] <= min(L, width)); they are placed best-fit-decreasing into
 * rows of L tokens exactly as pack_rows' Python body does (stable descending
 * length; least open capacity that holds the sequence, ties to the lower
 * row; at most max_docs_per_row a row; a row stays open while it has fewer
 * than that and >= 2 tokens left).  The bucket RULES stay in Python and come
 * in as tables: row_buckets[n_row_buckets] ascending, last >= n (the padded
 * row count Rb is the first >= R); seg_buckets[c - 1] = the segment width Sb
 * of a batch whose fullest row holds c sequences, c = 1..max_docs_per_row.
 * Writes ids / segments (1-based per row) / positions (restarting per
 * sequence) at [Rb, L], pad rows zero, into buffers of row_buckets[last] * L
 * int32 (their contents on entry do not matter); row_of / seg_of [n] = the
 * (row, 0-based segment) of sequence i; out_dims = {R, n_seg, Rb, Sb}.  With
 * slot_ids != NULL also out_pair_slot[Rb * Sb] (capacity row_buckets[last] *
 * seg_buckets[last]): drop_slot everywhere, slot_ids[i] at row * Sb + seg.
 * Returns 0, or -1 on arguments it cannot lay out (caller packs in Python). */
int32_t pn_pack_rows(const int32_t* ids_b, int64_t n, int64_t width,
                     const int64_t* lens, int64_t L, int64_t max_docs_per_row,
                     const int64_t* row_buckets, int64_t n_row_buckets,
                     const int64_t* seg_buckets, const int32_t* slot_ids,
                     int32_t drop_slot, int32_t* out_ids,
                     int32_t* out_segments, int32_t* out_positions,
                     int32_t* out_pair_slot, int64_t* row_of, int64_t* seg_of,
                     int64_t* out_dims);

/* ---- shard routing ----
 * shard(key) = (key & shard_mask) % n_shards (reference
 * src/engine/dataflow/shard.rs:6 + value.rs:38).  Produces per-shard counts
 * and a stable permutation `order` grouping row indices by shard — the host
 * side of the mesh exchange. */
void pn_shard_rows(const uint64_t* keys, int64_t n, uint32_t n_shards,
                   uint64_t shard_mask, int64_t* counts, int64_t* order);

#ifdef __cplusplus
}
#endif

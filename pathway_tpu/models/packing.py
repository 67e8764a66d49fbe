"""Sequence-packing row layout — shared by the embedder and cross-encoder.

Best-fit-decreasing bin packing of tokenized sequences into fixed-length
rows for block-diagonal segment attention (models/transformer.py): several
short sequences share one row, so the MXU sees full-length matmuls
regardless of the input length distribution.  Split out of
``SentenceEncoder._pack`` so the cross-encoder's (query, doc) pair scoring
packs through the exact same layout code.
"""

from __future__ import annotations

import bisect
import functools
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "PackedRows",
    "pack_padded",
    "pack_rows",
    "pad_packed_rows",
    "row_length_bucket",
    "seg_bucket",
]

_ROW_LEN_BUCKETS = (32, 64, 128, 256, 512)


def seg_bucket(n_seg: int) -> int:
    """Segment width is a compile dimension: bucket it (8 wide, then /4
    steps) so every packed consumer compiles the same handful of shapes."""
    return 8 if n_seg <= 8 else max(1, ((n_seg + 3) // 4) * 4)


def pad_packed_rows(
    ids: np.ndarray,
    segments: np.ndarray,
    positions: np.ndarray,
    rows: int,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Zero-pad the packed [R, L] layout arrays up to ``rows`` rows (pad
    rows carry segment 0 everywhere = fully masked)."""
    R, L = ids.shape
    if rows > R:
        pad = np.zeros((rows - R, L), np.int32)
        ids = np.concatenate([ids, pad])
        segments = np.concatenate([segments, pad])
        positions = np.concatenate([positions, pad])
    return ids, segments, positions


def row_length_bucket(longest: int, max_len: int) -> int:
    """Length-bucketed row width: the smallest power-of-two bucket that
    holds the longest sequence, capped at ``max_len`` — short micro-batches
    compile a handful of (R, L) shapes instead of one per input length,
    and an all-short batch never pays a ``max_len``-wide forward."""
    for b in _ROW_LEN_BUCKETS:
        if b >= max_len:
            return max_len
        if longest <= b:
            return b
    return max_len


def pack_rows(
    ids_b: np.ndarray,
    lens: np.ndarray,
    L: int,
    max_docs_per_row: int = 8,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, List[Tuple[int, int]], int]:
    """Pack ``n`` tokenized sequences (``ids_b`` [n, L_tok] padded, ``lens``
    [n] real token counts, already clipped to ``L``) into rows of ``L``
    tokens.  Returns (ids [R, L], mask, segments, positions, doc_slots,
    n_seg) where doc_slots[i] = (row, segment-1) of input sequence i;
    segments are 1-based per row, positions restart per sequence (so
    positional embeddings match the unpacked encoding)."""
    n = int(ids_b.shape[0])
    lens = np.asarray(lens, np.int64)
    order = np.argsort(-lens, kind="stable")
    # best-fit-decreasing via a capacity-sorted open-row list: O(log R)
    # placement per doc (a naive scan-all-rows loop measured 68 ms per
    # 2.5k-doc chunk — more than the device forward it feeds).  The
    # per-row doc cap keeps the segment width (a compile dimension)
    # small and stable across chunks.
    open_caps: list = []  # ascending (cap_left, row_id)
    row_of = np.empty(n, np.int64)
    seg_of = np.empty(n, np.int64)
    off_of = np.empty(n, np.int64)
    row_fill: list = []  # tokens used per row
    row_count: list = []  # docs per row
    for i in order.tolist():
        need = int(lens[i])
        j = bisect.bisect_left(open_caps, (need, -1))
        if j < len(open_caps):
            cap_left, rid = open_caps.pop(j)
            row_of[i] = rid
            seg_of[i] = row_count[rid]
            off_of[i] = row_fill[rid]
            row_count[rid] += 1
            row_fill[rid] += need
            new_cap = cap_left - need
            if row_count[rid] < max_docs_per_row and new_cap >= 2:
                bisect.insort(open_caps, (new_cap, rid))
        else:
            rid = len(row_fill)
            row_of[i] = rid
            seg_of[i] = 0
            off_of[i] = 0
            row_fill.append(need)
            row_count.append(1)
            if max_docs_per_row > 1 and L - need >= 2:
                bisect.insort(open_caps, (L - need, rid))
    R = len(row_fill)
    n_seg = max(row_count) if row_count else 1
    # vectorized assembly: one flat scatter for all token positions
    total = int(lens.sum())
    within = np.arange(total) - np.repeat(
        np.concatenate([[0], np.cumsum(lens)[:-1]]), lens
    )
    src = np.repeat(np.arange(n) * ids_b.shape[1], lens) + within
    dest = np.repeat(row_of * L + off_of, lens) + within
    ids = np.zeros(R * L, np.int32)
    mask = np.zeros(R * L, np.int32)
    segments = np.zeros(R * L, np.int32)
    positions = np.zeros(R * L, np.int32)
    ids[dest] = ids_b.reshape(-1)[src]
    mask[dest] = 1
    segments[dest] = np.repeat(seg_of + 1, lens)
    positions[dest] = within
    doc_slots = list(zip(row_of.tolist(), seg_of.tolist()))
    return (
        ids.reshape(R, L),
        mask.reshape(R, L),
        segments.reshape(R, L),
        positions.reshape(R, L),
        doc_slots,
        n_seg,
    )


class PackedRows(NamedTuple):
    """A packed batch at its compile shape (``pack_padded``)."""

    ids: np.ndarray  # [Rb, L] int32, pad rows zero
    segments: np.ndarray  # [Rb, L], 1-based per row, 0 = masked
    positions: np.ndarray  # [Rb, L], restarting per sequence
    pair_slot: Optional[np.ndarray]  # [Rb * Sb] scatter targets, if asked for
    row_of: np.ndarray  # int64 [n]: the row of input sequence i
    seg_of: np.ndarray  # int64 [n]: its 0-based segment in that row
    rows: int  # R, the rows that carry tokens
    n_seg: int  # the fullest row's sequence count
    seg_width: int  # Sb = seg_bucket(n_seg)
    native: bool  # laid out by the native call, not the Python body


@functools.lru_cache(maxsize=256)
def _bucket_tables(
    n: int, max_docs_per_row: int, row_bucket: Callable[[int], int]
) -> Tuple[np.ndarray, np.ndarray]:
    """The bucket rules as the tables the native call reads: every padded
    row count ``row_bucket`` can answer for 1..n rows, ascending, and
    ``seg_bucket`` of 1..max_docs_per_row.  (A bucket function is a
    non-decreasing step function with ``row_bucket(b) == b`` at each step's
    top, so stepping from top + 1 visits every step.)"""
    rows = [row_bucket(1)]
    while rows[-1] < n:
        rows.append(row_bucket(rows[-1] + 1))
    tables = (
        np.asarray(rows, np.int64),
        np.asarray(
            [seg_bucket(c) for c in range(1, max_docs_per_row + 1)], np.int64
        ),
    )
    for t in tables:  # one copy serves every caller
        t.flags.writeable = False
    return tables


def pack_padded(
    ids_b: np.ndarray,
    lens: np.ndarray,
    L: int,
    row_bucket: Callable[[int], int],
    max_docs_per_row: int = 8,
    slot_ids: Optional[Sequence[int]] = None,
    drop_slot: int = 0,
) -> PackedRows:
    """``pack_rows``, then the padding to the compile shape every packed
    consumer does next: rows up to ``Rb = row_bucket(R)`` (zero rows = fully
    masked), segment width ``Sb = seg_bucket(n_seg)``.  With ``slot_ids``
    (one per sequence) also the flat ``[Rb * Sb]`` table whose entry ``row *
    Sb + seg`` is that sequence's slot id and every other entry ``drop_slot``
    (the rerank pipeline scatters its score table through it).

    ONE native call (``pn_pack_rows``) whenever the library is loaded; else
    ``pack_rows`` + ``pad_packed_rows`` + a loop, equal element for element
    (tests/test_pack_pairs_native.py)."""
    from .. import native as _native

    n = len(lens)
    out = _native.pack_rows(
        ids_b, lens, L, max_docs_per_row,
        *_bucket_tables(n, max_docs_per_row, row_bucket),
        slot_ids=slot_ids, drop_slot=drop_slot,
    )
    if out is not None:
        return PackedRows(*out, native=True)
    ids, _mask, segments, positions, doc_slots, n_seg = pack_rows(
        ids_b, lens, L, max_docs_per_row
    )
    R = ids.shape[0]
    Rb, Sb = row_bucket(R), seg_bucket(n_seg)
    ids, segments, positions = pad_packed_rows(ids, segments, positions, Rb)
    slots = np.asarray(doc_slots, np.int64).reshape(n, 2)
    pair_slot = None
    if slot_ids is not None:
        pair_slot = np.full(Rb * Sb, drop_slot, np.int32)
        for i, (r, s) in enumerate(doc_slots):
            pair_slot[r * Sb + s] = slot_ids[i]
    return PackedRows(
        ids, segments, positions, pair_slot, slots[:, 0], slots[:, 1],
        R, n_seg, Sb, native=False,
    )

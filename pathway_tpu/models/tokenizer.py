"""Deterministic hashing tokenizer.

Offline-friendly replacement for downloaded vocabularies (the reference
relies on HF/tiktoken tokenizers, xpacks/llm/splitters.py:13): words and
char-trigram fallbacks hash into a fixed id space with xxh3.  Embeddings
trained in-framework are consistent because the mapping is deterministic.
"""

from __future__ import annotations

import re
from functools import lru_cache
from typing import List, Sequence, Tuple

import numpy as np
import xxhash

from .. import observe

__all__ = ["HashTokenizer"]

_WORD_RE = re.compile(r"[\w']+|[^\w\s]")

# pairs tokenised by ``encode_pairs``, by the path that took them
_PAIRS_NATIVE = observe.counter("pathway_tokenizer_pairs_total", path="native")
_PAIRS_PYTHON = observe.counter("pathway_tokenizer_pairs_total", path="python")
# single texts tokenised by ``encode_batch``, likewise
_TEXTS_NATIVE = observe.counter("pathway_tokenizer_texts_total", path="native")
_TEXTS_PYTHON = observe.counter("pathway_tokenizer_texts_total", path="python")


def _width(longest: int, max_length: int, pad_to: int | None) -> int:
    """The shared padded length: ``pad_to``, else the longest row rounded up
    to a multiple of 16 (to bound jit shape variants), at most max_length."""
    return pad_to or min(max_length, ((longest + 15) // 16) * 16)


@lru_cache(maxsize=64)
def _width_table(max_length: int, pad_to: int | None) -> Tuple[np.ndarray, int]:
    """``_width`` by longest row, 0..max_length, and its largest entry: how
    the rule reaches the native batch call (no second copy of it in C++)."""
    # ``_width`` over 0..max_length at once: a generator's table has its whole context's 262,144 entries and a key
    # for every answer budget, and a Python loop over them held the decode engine's thread 60-90 ms a new budget
    n = np.arange(max_length + 1, dtype=np.int64)
    widths = np.full_like(n, pad_to) if pad_to else np.minimum(max_length, (n + 15) // 16 * 16)
    widths.setflags(write=False)  # one table, handed to every caller
    return widths, int(widths.max())


class HashTokenizer:
    PAD = 0
    CLS = 1
    SEP = 2
    UNK = 3
    _RESERVED = 8

    def __init__(self, vocab_size: int = 32768, max_length: int = 128):
        self.vocab_size = vocab_size
        self.max_length = max_length

    def _word_id(self, word: str) -> int:
        h = xxhash.xxh3_64_intdigest(word.lower().encode())
        return self._RESERVED + (h % (self.vocab_size - self._RESERVED))

    def tokenize(self, text: str) -> List[int]:
        return [self._word_id(w) for w in _WORD_RE.findall(str(text))]

    def count_tokens(self, text: str) -> int:
        return len(_WORD_RE.findall(str(text)))

    def encode(
        self, text: str, pair: str | None = None, max_length: int | None = None
    ) -> List[int]:
        max_length = max_length or self.max_length
        if pair is None:
            ids = [self.CLS] + self.tokenize(text)
            return ids[: max_length - 1] + [self.SEP]
        # sentence pairs truncate longest-first (HF semantics): both segments
        # keep tokens, so an over-long query can't silently evict the whole
        # document and collapse every pair to the same score
        a = self.tokenize(text)
        b = self.tokenize(pair)
        budget = max(max_length - 3, 2)
        while len(a) + len(b) > budget:
            if len(a) >= len(b) and len(a) > 1:
                a.pop()
            elif len(b) > 1:
                b.pop()
            else:
                break
        return [self.CLS] + a + [self.SEP] + b + [self.SEP]

    def encode_batch(
        self,
        texts: Sequence[str],
        pairs: Sequence[str] | None = None,
        max_length: int | None = None,
        pad_to: int | None = None,
        rows: int | None = None,
        span=None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Returns (ids [B, L], mask [B, L]) padded to a shared length, and
        to ``rows`` rows where that is more than the texts (pad rows: all
        ``PAD``, mask 0).  Single texts take the native path whenever the
        input allows it (ASCII batch, the native library loaded), same
        arrays as ``encode`` + ``_pad`` to the element, and are counted
        under the path they took; ``span``, the caller's open bracket
        around the call, learns it too (``native_texts``)."""
        if pairs is not None:
            return self.encode_pairs(texts, pairs, max_length, pad_to)[:2]
        max_length = max_length or self.max_length
        rows = max(rows or 0, len(texts))
        out = self._encode_batch_native(texts, max_length, pad_to, rows)
        native = out is not None
        if not native:
            out = self._pad([self.encode(t, None, max_length) for t in texts],
                            max_length, pad_to, rows)
        (_TEXTS_NATIVE if native else _TEXTS_PYTHON).inc(len(texts))
        if span is not None:
            span.set(native_texts=len(texts) if native else 0)
        return out

    def encode_pairs(
        self,
        texts: Sequence[str],
        pairs: Sequence[str],
        max_length: int | None = None,
        pad_to: int | None = None,
    ) -> Tuple[np.ndarray, np.ndarray, bool]:
        """``encode_batch(texts, pairs=pairs)`` plus the path that made it:
        (ids, mask, native).  The native path is taken whenever the input
        allows it (ASCII batch, the native library loaded) and
        is bit-identical to the per-pair loop; the pairs are counted under
        the path they took."""
        max_length = max_length or self.max_length
        fast = self._encode_pairs_native(texts, pairs, max_length, pad_to)
        if fast is not None:
            _PAIRS_NATIVE.inc(len(texts))
            return (*fast, True)
        _PAIRS_PYTHON.inc(len(texts))
        ids, mask = self._pad(
            [self.encode(t, pairs[i], max_length) for i, t in enumerate(texts)],
            max_length, pad_to,
        )
        return ids, mask, False

    def _pad(
        self,
        encoded: List[List[int]],
        max_length: int,
        pad_to: int | None,
        rows: int = 0,
    ) -> Tuple[np.ndarray, np.ndarray]:
        L = _width(max((len(e) for e in encoded), default=1), max_length, pad_to)
        rows = max(rows, len(encoded))
        ids = np.full((rows, L), self.PAD, dtype=np.int32)
        mask = np.zeros((rows, L), dtype=np.int32)
        for i, e in enumerate(encoded):
            e = e[:L]
            ids[i, : len(e)] = e
            mask[i, : len(e)] = 1
        return ids, mask

    @staticmethod
    def _ascii_blob(strings: List[str]) -> Tuple[bytes, np.ndarray] | None:
        """What the C++ scanner (native/src/tokenizer.cc — bit-identical ids
        for ASCII input) reads: the texts joined into one blob and their
        boundaries int64[n+1].  None for a non-ASCII batch: the caller
        keeps the Python path."""
        joined = "".join(strings)
        if not joined.isascii():
            return None
        n = len(strings)
        offsets = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(np.fromiter(map(len, strings), np.int64, count=n),
                  out=offsets[1:])
        return joined.encode(), offsets

    def _encode_batch_native(
        self, texts: Sequence[str], max_length: int, pad_to: int | None, rows: int
    ) -> Tuple[np.ndarray, np.ndarray] | None:
        """The rows of a whole batch of single texts in ONE native call
        (``pn_encode_batch``): scan, truncate, frame ``CLS t... SEP`` and pad
        to the shared width and to ``rows`` rows.  The numpy form of the
        framing (some eighteen array calls around a GIL-releasing scan) took
        0.06 ms alone and 1.0-4.8 ms on the serve path's one scheduler
        thread (PERF.md section 6, ISSUE 31).  None (caller keeps the Python
        path) for non-ASCII batches or without the native library."""
        if len(texts) == 0:
            return None
        blob = self._ascii_blob([t if isinstance(t, str) else str(t) for t in texts])
        if blob is None:
            return None
        from .. import native as _native

        return _native.encode_batch(
            *blob, self.vocab_size, self._RESERVED, max_length,
            *_width_table(max_length, pad_to), self.CLS, self.SEP, self.PAD, rows,
        )

    def _encode_pairs_native(
        self,
        texts: Sequence[str],
        pairs: Sequence[str],
        max_length: int,
        pad_to: int | None,
    ) -> Tuple[np.ndarray, np.ndarray] | None:
        """The pair rows of a whole batch in ONE native call
        (``pn_encode_pairs``): each DISTINCT string is tokenised once (a
        reranked query occurs once per candidate), ``encode``'s longest-first
        truncation is taken in closed form and the rows ``CLS a SEP b SEP``
        are laid out there.  The numpy form of that assembly measured 4.8 ms
        a batch under the rerank cell's 32 callers (0.5 ms alone: some thirty
        array calls, each a chance to hand the GIL over).  Same ids, mask and
        width as ``encode`` + ``_pad``; None for non-ASCII batches or without
        the native library."""
        n = len(texts)
        if n == 0:
            return None
        slot_of: dict = {}
        slots = np.fromiter(
            (
                slot_of.setdefault(s if isinstance(s, str) else str(s), len(slot_of))
                for s in (*texts, *pairs)
            ),
            np.int64, count=2 * n,
        )
        blob = self._ascii_blob(list(slot_of))
        if blob is None:
            return None
        from .. import native as _native

        budget = max(max_length - 3, 2)
        out = _native.encode_pairs(
            *blob, slots[:n], slots[n:], self.vocab_size, self._RESERVED,
            budget, self.CLS, self.SEP, max(budget + 3, pad_to or 0),
        )
        if out is None:
            return None
        ids, mask, lens = out
        L = _width(int(lens.max()), max_length, pad_to)
        if L < ids.shape[1]:  # rows were laid out whole: cut as ``_pad`` cuts
            ids = np.ascontiguousarray(ids[:, :L])
            mask = np.ascontiguousarray(mask[:, :L])
        return ids, mask

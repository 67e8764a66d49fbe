"""Single-text tokenisation in one native call (ISSUE 31).

``HashTokenizer.encode_batch`` scans, truncates, frames ``CLS t... SEP`` and
pads a whole batch, to the shared width and to the number of rows the caller
asks for, in ONE call of ``pn_encode_batch`` that keeps the GIL for a short
blob.  The Python path (``encode`` + ``_pad``) stays as the fallback and is
the reference here: ids, mask, shape and dtype must be equal to the last
element.
"""

from __future__ import annotations

import ctypes
import random

import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu import native, observe
from pathway_tpu.models.encoder import SentenceEncoder, _bucket
from pathway_tpu.models.tokenizer import HashTokenizer, _width, _width_table
from pathway_tpu.observe import trace
from pathway_tpu.ops.knn import DeviceKnnIndex
from pathway_tpu.ops.serving import FusedEncodeSearch


needs_native = pytest.mark.usefixtures("needs_native")  # tests/conftest.py

WORDS = [
    "alpha", "Beta", "it's", "x_1", "don't", "foo", "bar", "HELLO", "q",
    "rock'n'roll", "end", "tab",
]


def _text(rng: random.Random, n_words: int) -> str:
    return " ".join(rng.choice(WORDS) for _ in range(n_words))


def _texts(seed: int, n: int, lo: int = 0, hi: int = 40):
    rng = random.Random(seed)
    return [_text(rng, rng.randint(lo, hi)) for _ in range(n)]


def _words(*counts: int):
    """One text of exactly ``n`` one-token words for every n."""
    return [" ".join(WORDS[i % len(WORDS)] for i in range(n)) for n in counts]


def _python_batch(tok, texts, max_length=None, pad_to=None, rows=None):
    """``encode`` + ``_pad``, then the pad rows as ``FusedEncodeSearch.submit``
    used to append them: two ``np.concatenate`` of zeros."""
    max_length = max_length or tok.max_length
    ids, mask = tok._pad(
        [tok.encode(t, None, max_length) for t in texts], max_length, pad_to
    )
    if rows is not None and rows > len(texts):
        extra = rows - len(texts)
        ids = np.concatenate([ids, np.zeros((extra, ids.shape[1]), ids.dtype)])
        mask = np.concatenate([mask, np.zeros((extra, mask.shape[1]), mask.dtype)])
    return ids, mask


def _text_counts():
    return tuple(
        observe.counter("pathway_tokenizer_texts_total", path=p).value
        for p in ("native", "python")
    )


# (texts, max_length, pad_to, rows, the path the batch takes)
CASES = {
    "1 text": (_texts(1, 1, 4, 32), None, None, None, "native"),
    "5 texts": (_texts(2, 5, 4, 32), None, None, None, "native"),
    "11 texts": (_texts(3, 11, 4, 32), None, None, None, "native"),
    "64 texts": (_texts(4, 64), None, None, None, "native"),
    "empty and whitespace-only": (
        ["", "   \t\n ", "alpha beta", " "], None, None, None, "native",
    ),
    "only empty strings": (["", ""], None, None, None, "native"),
    "punctuation and case": (
        ["Hello, World! (it's) 3.14;", "a-b_c 'q' ROCK'n'roll?"],
        None, None, None, "native",
    ),
    "a text over max_length: cut, SEP at the cut": (
        _words(3, 300, 126, 127), None, None, None, "native",
    ),
    "max_length given": (_texts(5, 9, 0, 60), 24, None, None, "native"),
    "max_length 2: CLS SEP alone": (_words(0, 1, 5), 2, None, None, "native"),
    "pad_to given": (_texts(6, 7, 0, 30), None, 128, None, "native"),
    "max_length and pad_to given": (_texts(7, 7, 0, 90), 64, 64, None, "native"),
    # framed rows of 15 / 16 / 17 and 31 / 32 / 33 tokens
    "longest row 15": (_words(2, 13), None, None, None, "native"),
    "longest row 16": (_words(14, 2), None, None, None, "native"),
    "longest row 17": (_words(2, 15, 0), None, None, None, "native"),
    "longest row 31": (_words(29), None, None, None, "native"),
    "longest row 32": (_words(30, 7), None, None, None, "native"),
    "longest row 33": (_words(31, 7), None, None, None, "native"),
    "rows above the texts: 5 in 8": (_texts(8, 5, 4, 32), None, None, 8, "native"),
    "rows above the texts: 11 in 16": (
        _texts(9, 11, 4, 32), None, None, 16, "native",
    ),
    "rows above the texts, pad_to given": (
        _texts(10, 3, 0, 20), None, 64, 4, "native",
    ),
    "rows equal to the texts": (_texts(11, 4, 4, 32), None, None, 4, "native"),
    "rows under the texts are the texts": (
        _texts(12, 6, 4, 32), None, None, 2, "native",
    ),
    "a non-ASCII text in the batch": (
        [*_texts(13, 4, 4, 32), "café naïve 中文 text"], None, None, 8, "python",
    ),
    "a text that is not a str": (["alpha", 314, None], None, None, None, "native"),
}


@needs_native
@pytest.mark.parametrize("disable", [False, True], ids=["library", "native.disable"])
@pytest.mark.parametrize("case", CASES)
def test_native_batch_equals_encode_and_pad(case, disable, monkeypatch):
    texts, max_length, pad_to, rows, path = CASES[case]
    if disable:  # what ``lib()`` answers under ``native.disable``
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_tried", True)
        path = "python"
    tok = HashTokenizer(vocab_size=30522, max_length=128)
    before = _text_counts()
    ids, mask = tok.encode_batch(
        texts, max_length=max_length, pad_to=pad_to, rows=rows
    )
    want_ids, want_mask = _python_batch(tok, texts, max_length, pad_to, rows)
    assert ids.dtype == mask.dtype == np.int32
    assert ids.shape == mask.shape == want_ids.shape
    assert ids.flags.c_contiguous and mask.flags.c_contiguous
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(mask, want_mask)
    native_n, python_n = (a - b for a, b in zip(_text_counts(), before))
    assert (native_n, python_n) == (
        (len(texts), 0) if path == "native" else (0, len(texts))
    )


@needs_native
def test_pad_to_under_the_longest_row_still_ends_in_sep():
    """The one input on which the native rows are not ``_pad``'s (which cuts
    the SEP off with the tail): they are what the numpy framing this call
    replaced returned, ``pad_to - 2`` tokens between CLS and SEP."""
    tok = HashTokenizer(vocab_size=30522, max_length=128)
    texts = _words(40, 3)
    ids, mask = tok.encode_batch(texts, pad_to=16)
    full = tok.encode(texts[0])
    assert ids.shape == (2, 16)
    assert ids[0].tolist() == full[:15] + [tok.SEP]
    assert ids[1].tolist() == tok.encode(texts[1]) + [tok.PAD] * 11
    assert mask.sum(axis=1).tolist() == [16, 5]


def test_the_width_table_is_the_width_rule():
    for max_length, pad_to in ((128, None), (24, None), (2, None), (64, 64), (128, 16)):
        widths, cap = _width_table(max_length, pad_to)
        assert widths.dtype == np.int64 and len(widths) == max_length + 1
        assert widths.tolist() == [
            _width(n, max_length, pad_to) for n in range(max_length + 1)
        ]
        assert cap == max(widths)


@needs_native
def test_native_call_refuses_what_it_cannot_lay_out():
    tok = HashTokenizer(vocab_size=30522, max_length=128)
    blob = tok._ascii_blob(["alpha beta", "gamma"])
    widths, cap = _width_table(128, None)
    args = (30522, 8, 128, widths, cap, tok.CLS, tok.SEP, tok.PAD)
    assert native.encode_batch(*blob, *args, 2) is not None
    assert native.encode_batch(*blob, *args, 1) is None  # fewer rows than texts
    assert native.encode_batch(b"", np.zeros(1, np.int64), *args, 4) is None
    # boundaries outside the blob, or descending inside it
    for offsets in ([0, 10, 99], [1, 10, 15], [0, 15, 15, 10, 15]):
        assert native.encode_batch(blob[0], np.array(offsets), *args, 8) is None
    # a table of another max_length, a width past the buffer, a width under 2
    assert native.encode_batch(*blob, 30522, 8, 64, widths, cap, 1, 2, 0, 2) is None
    assert native.encode_batch(*blob, 30522, 8, 128, widths, 8, 1, 2, 0, 2) is None
    assert native.encode_batch(
        *blob, 30522, 8, 128, np.ones(129, np.int64), 1, 1, 2, 0, 2
    ) is None
    # and the tokenizer keeps the Python path then (max_length 1: SEP alone)
    ids, mask = tok.encode_batch(["alpha beta"], max_length=1)
    assert ids[0, 0] == tok.SEP and mask.sum() == 1


@needs_native
@pytest.mark.parametrize("blob_bytes, held", [(40, True), (400, False)])
def test_a_long_blob_goes_through_the_releasing_handle(
    blob_bytes, held, monkeypatch
):
    """``_lib_for``: the GIL-keeping ``PyDLL`` twin up to ``_HOLD_GIL_BYTES``
    (a query batch, a commit of documents), ``CDLL`` above it (a bulk
    tokenise); the threshold is lowered here, not the blob made a MiB."""
    monkeypatch.setattr(native, "_HOLD_GIL_BYTES", 100)
    native.lib()
    asked = []
    real = native._lib_for

    def spy(nbytes):
        dll = real(nbytes)
        asked.append((nbytes, dll))
        return dll

    monkeypatch.setattr(native, "_lib_for", spy)
    tok = HashTokenizer(vocab_size=30522, max_length=128)
    texts = ["word " * (blob_bytes // 5)]
    ids, mask = tok.encode_batch(texts)
    np.testing.assert_array_equal(ids, _python_batch(tok, texts)[0])
    assert asked == [(blob_bytes, native._lib_held if held else native._lib)]
    assert isinstance(native._lib_held, ctypes.PyDLL)
    assert not isinstance(native._lib, ctypes.PyDLL)


# -- the counter and the span attribute that say it engaged --------------------


DOCS = {
    i: f"document number {i} about {topic} with live updates"
    for i, topic in enumerate(
        ["dataflow", "indexes", "exactly once", "joins", "windows", "replay"] * 2
    )
}


@pytest.fixture(scope="module")
def fused():
    enc = SentenceEncoder(
        dimension=32, n_layers=2, n_heads=4, max_length=32,
        vocab_size=512, dtype=jnp.float32,
    )
    index = DeviceKnnIndex(dimension=32, metric="cos", initial_capacity=64)
    index.add(sorted(DOCS), enc.encode([DOCS[i] for i in sorted(DOCS)]))
    return FusedEncodeSearch(enc, index, k=3)


@needs_native
@pytest.mark.parametrize("queries, path", [
    (["exactly once replay", "window joins", "live dataflow"], "native"),
    (["exactly once replay", "cafés with windows"], "python"),
])
def test_stage1_texts_are_counted_under_the_path_they_took(fused, queries, path):
    before = _text_counts()
    ctx = trace.start_trace("t", sample=False)
    with trace.use(ctx):
        rows = fused(queries)
    assert [len(r) for r in rows] == [3] * len(queries)
    native_n, python_n = (a - b for a, b in zip(_text_counts(), before))
    assert (native_n, python_n) == (
        (len(queries), 0) if path == "native" else (0, len(queries))
    )
    (tokenize,) = [s for s in ctx.spans if s[2] == "stage1.tokenize"]
    assert tokenize[6]["native_texts"] == native_n
    series = 'pathway_tokenizer_texts_total{path="%s"}' % path  # on /metrics
    assert any(line.startswith(series) for line in observe.render_prometheus())


@needs_native
@pytest.mark.parametrize("n", [1, 3, 5, 11])
def test_submit_hands_the_compiled_function_the_bucket_padded_rows(
    fused, n, monkeypatch
):
    """What stage 1 launches with: ``_bucket(n)`` rows, the texts' rows then
    all-zero rows, as the two ``np.concatenate`` it dropped made them."""
    handed = []
    compiled = fused._compiled

    def spy(*key, **kw):
        fn = compiled(*key, **kw)

        def call(params, ids, mask, *planes):
            handed.append((np.asarray(ids), np.asarray(mask)))
            return fn(params, ids, mask, *planes)

        return call

    monkeypatch.setattr(fused, "_compiled", spy)
    rng = random.Random(n)
    queries = [
        " ".join(rng.choice(list(DOCS.values())).split()[: rng.randint(2, 7)])
        for _ in range(n)
    ]
    rows = fused.submit(queries)()
    assert len(rows) == n
    ((ids, mask),) = handed
    want_ids, want_mask = _python_batch(
        fused.encoder.tokenizer, queries, rows=_bucket(n)
    )
    assert ids.shape == (_bucket(n), want_ids.shape[1]) and ids.dtype == np.int32
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(mask, want_mask)


@pytest.mark.parametrize("max_length,pad_to", [(5, None), (128, None), (128, 64), (1000, 0), (262016, None)],
                         ids=["tiny", "the-default", "a-fixed-width", "pad-to-zero-is-none", "a-generators-whole-context"])
def test_the_width_table_is_the_width_rule_at_every_length(max_length, pad_to):
    """The table the native batch call reads is built in one array expression
    (a Python loop over a long-context generator's 262,144 entries held the
    decode engine's thread 60-90 ms for every new answer budget: PERF.md
    section 6, ISSUE 34); it is ``_width`` at every length all the same."""
    from pathway_tpu.models import tokenizer as tok

    tok._width_table.cache_clear()
    widths, most = tok._width_table(max_length, pad_to)
    at = np.unique(np.r_[np.arange(min(max_length, 100) + 1), np.linspace(0, max_length, 257).astype(np.int64)])
    assert [int(widths[n]) for n in at] == [tok._width(int(n), max_length, pad_to) for n in at]
    assert widths.shape == (max_length + 1,) and widths.dtype == np.int64 and most == int(widths.max()) and not widths.flags.writeable

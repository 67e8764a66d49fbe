"""Pair tokenisation off the per-pair Python loop (ISSUE 25).

``HashTokenizer.encode_pairs`` tokenises each distinct text of a batch once
and lays out the ``CLS a SEP b SEP`` rows in one native call.  The
per-pair loop (``encode`` + ``_pad``) stays as the fallback and is the
reference here: ids, mask and padded width must be equal to the last bit.
"""

from __future__ import annotations

import hashlib
import random

import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu import native, observe
from pathway_tpu.models.cross_encoder import CrossEncoderModel
from pathway_tpu.models.encoder import SentenceEncoder
from pathway_tpu.models.tokenizer import HashTokenizer
from pathway_tpu.observe import trace
from pathway_tpu.ops.knn import DeviceKnnIndex
from pathway_tpu.ops.retrieve_rerank import RetrieveRerankPipeline
from pathway_tpu.ops.serving import FusedEncodeSearch


needs_native = pytest.mark.usefixtures("needs_native")  # tests/conftest.py

WORDS = [
    "alpha", "Beta", "it's", "x_1", "don't", "foo,", "bar.", "(baz)", "q?",
    "HELLO", "a-b", "'", "rock'n'roll", "3.14", "end;", "tab\tbed",
]


def _text(rng: random.Random, n_words: int) -> str:
    return " ".join(rng.choice(WORDS) for _ in range(n_words))


def _python_pairs(tok, qs, ds, max_length=None, pad_to=None):
    """The per-pair loop, as ``encode_pairs`` runs it without the library."""
    max_length = max_length or tok.max_length
    return tok._pad(
        [tok.encode(q, d, max_length) for q, d in zip(qs, ds)], max_length, pad_to
    )


def _batch(seed: int):
    """Repeated queries (one per candidate, as stage 2 sends them), empty
    strings on either side, a 300-word document, a 200-word query, both at
    once, punctuation and apostrophes."""
    rng = random.Random(seed)
    qs, ds = [], []
    for n_q in (0, 1, 4, 32, 200, rng.randint(2, 40)):
        q = _text(rng, n_q)
        for n_d in (0, 1, 8, 63, 300, rng.randint(2, 120)):
            qs.append(q)
            ds.append(_text(rng, n_d))
    return qs, ds


# -- (a) the closed-form truncation against encode's loop ---------------------


@needs_native
@pytest.mark.parametrize("max_length", range(5, 35))
def test_closed_form_truncation_equals_the_loop_over_the_grid(max_length):
    """``pn_encode_pairs`` truncates in closed form; ``encode`` pops one token
    at a time.  Every (len(a), len(b)) of 0..40 x 0..40 under this budget."""
    tok = HashTokenizer(vocab_size=512, max_length=max_length)
    texts = [" ".join(f"w{i}" for i in range(n)) for n in range(41)]
    qs = [texts[a] for a in range(41) for _ in range(41)]
    ds = [texts[b] for _ in range(41) for b in range(41)]
    ids, mask, took_native = tok.encode_pairs(qs, ds)
    assert took_native
    want_ids, want_mask = _python_pairs(tok, qs, ds)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(mask, want_mask)
    # what the loop promises: neither side under one token, an empty side
    # stays empty, ties take from the first segment
    kept_a = (ids == tok.SEP).argmax(axis=1) - 1
    kept_b = mask.sum(axis=1) - kept_a - 3
    la, lb = np.repeat(np.arange(41), 41), np.tile(np.arange(41), 41)
    assert ((kept_a >= np.minimum(la, 1)) & (kept_b >= np.minimum(lb, 1))).all()
    over = la + lb > max(max_length - 3, 2)
    assert (kept_a[over] + kept_b[over] == max(max_length - 3, 2)).all()
    assert (kept_a[over & (la == lb)] <= kept_b[over & (la == lb)]).all()


# -- (b) native against Python, whole batches ---------------------------------


@needs_native
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("max_length, pad_to", [
    (None, None), (None, 128), (64, None), (16, None), (5, None),
    (None, 48),  # a pad_to under the longest row cuts rows, as _pad does
])
def test_native_pairs_equal_the_python_loop(seed, max_length, pad_to):
    tok = HashTokenizer(vocab_size=30522, max_length=128)
    qs, ds = _batch(seed)
    ids, mask, took_native = tok.encode_pairs(qs, ds, max_length, pad_to)
    want_ids, want_mask = _python_pairs(tok, qs, ds, max_length, pad_to)
    assert took_native
    assert ids.shape == want_ids.shape and ids.dtype == want_ids.dtype
    assert mask.dtype == want_mask.dtype
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(mask, want_mask)
    assert ids.flags.c_contiguous
    # encode_batch(pairs=) is the same call
    again = tok.encode_batch(qs, pairs=ds, max_length=max_length, pad_to=pad_to)
    np.testing.assert_array_equal(again[0], ids)
    np.testing.assert_array_equal(again[1], mask)


@needs_native
def test_single_text_branch_is_unchanged_by_the_shared_helper():
    tok = HashTokenizer(vocab_size=30522, max_length=32)
    rng = random.Random(7)
    texts = [_text(rng, n) for n in (0, 1, 5, 30, 31, 64)]
    ids, mask = tok.encode_batch(texts)
    want_ids, want_mask = tok._pad(
        [tok.encode(t, None, 32) for t in texts], 32, None
    )
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(mask, want_mask)


# -- (c) what the input is decides the path ------------------------------------


@pytest.mark.parametrize("where", ["query", "document"])
def test_one_non_ascii_text_sends_the_whole_batch_down_the_python_path(where):
    tok = HashTokenizer(vocab_size=30522, max_length=64)
    qs, ds = _batch(5)
    (qs if where == "query" else ds)[7] = "café naïve 中文 text"
    ids, mask, took_native = tok.encode_pairs(qs, ds)
    assert not took_native
    want_ids, want_mask = _python_pairs(tok, qs, ds)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(mask, want_mask)


def test_without_the_library_the_python_path_answers(monkeypatch):
    tok = HashTokenizer(vocab_size=30522, max_length=64)
    qs, ds = _batch(6)
    monkeypatch.setattr(native, "encode_pairs", lambda *a, **k: None)
    ids, mask, took_native = tok.encode_pairs(qs, ds)
    assert not took_native
    want_ids, want_mask = _python_pairs(tok, qs, ds)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(mask, want_mask)


# -- (d) _pack_pairs: the same five values as the parent commit ---------------


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str((a.shape, a.dtype.str)).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


@pytest.fixture(scope="module")
def cross_encoder():
    return CrossEncoderModel(
        dimension=32, n_layers=2, n_heads=4, max_length=64,
        vocab_size=512, dtype=jnp.float32,
    )


# _digest of the five values at commit 1e7c7fe (the per-pair loop), same batch
PARENT_PACK_DIGEST = "ab4250468e1af3d8"


def test_pack_pairs_returns_the_parents_five_values(cross_encoder, monkeypatch):
    qs, ds = _batch(11)
    pairs = list(zip(qs, ds))

    def five(values):
        ids, segments, positions, doc_slots, n_seg = values
        return _digest(
            ids, segments, positions, np.asarray(doc_slots, np.int64),
            np.asarray(n_seg),
        )

    got = cross_encoder._pack_pairs(pairs)
    assert len(got) == 5
    assert five(got) == PARENT_PACK_DIGEST
    monkeypatch.setattr(native, "encode_pairs", lambda *a, **k: None)
    assert five(cross_encoder._pack_pairs(pairs)) == PARENT_PACK_DIGEST


# -- (e) the counter and the span attribute that say it engaged ---------------


DOCS = {
    i: f"document number {i} about {topic} with live updates"
    for i, topic in enumerate(
        ["dataflow", "indexes", "exactly once", "joins", "windows", "replay"] * 3
    )
}


def _pair_counts():
    return tuple(
        observe.counter("pathway_tokenizer_pairs_total", path=p).value
        for p in ("native", "python")
    )


@needs_native
@pytest.mark.parametrize("docs, path", [
    (DOCS, "native"),
    ({**DOCS, 3: "document number 3 about cafés"}, "python"),
])
def test_pairs_are_counted_under_the_path_they_took(cross_encoder, docs, path):
    enc = SentenceEncoder(
        dimension=32, n_layers=2, n_heads=4, max_length=32,
        vocab_size=512, dtype=jnp.float32,
    )
    index = DeviceKnnIndex(dimension=32, metric="cos", initial_capacity=64)
    index.add(sorted(docs), enc.encode([docs[i] for i in sorted(docs)]))
    pool = len(docs)  # every document is a candidate, the non-ASCII one too
    pipe = RetrieveRerankPipeline(
        FusedEncodeSearch(enc, index, k=pool), cross_encoder, docs,
        k=3, candidates=pool,
    )
    queries = ["exactly once replay", "window joins"]
    tokenize = observe.histogram(
        "pathway_serve_stage_seconds", stage="stage2_pair_tokenize"
    )
    before, brackets = _pair_counts(), tokenize.count
    ctx = trace.start_trace("t", sample=False)
    with trace.use(ctx):
        res = pipe(queries)
    assert [len(r) for r in res] == [3, 3]
    n_pairs = len(queries) * pool
    native_n, python_n = (a - b for a, b in zip(_pair_counts(), before))
    assert (native_n, python_n) == (
        (n_pairs, 0) if path == "native" else (0, n_pairs)
    )
    assert tokenize.count == brackets + 1  # one bracket per batch
    by_name = {s[2]: s for s in ctx.spans}
    pack, tok_span = by_name["stage2.pack"], by_name["stage2.tokenize"]
    assert pack[6]["native_pairs"] == native_n
    assert tok_span[1] == pack[0]  # the bracket sits inside stage2.pack
    assert tok_span[4] <= pack[4]
    series = 'pathway_tokenizer_pairs_total{path="%s"}' % path  # on /metrics
    assert any(line.startswith(series) for line in observe.render_prometheus())

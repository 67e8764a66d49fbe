"""Stage 2's row layout off the Python loop (ISSUE 29).

``models.packing.pack_padded`` places, assembles, pads and slots a packed
batch in ONE native call (``pn_pack_rows``).  ``pack_rows`` +
``pad_packed_rows`` + the ``pair_slot`` loop, as the rerank pipeline ran them
before, stay as the fallback and are the oracle here: every array and every
(row, seg) must be equal, element for element.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu import native, observe
from pathway_tpu.models import packing
from pathway_tpu.models.cross_encoder import CrossEncoderModel
from pathway_tpu.models.encoder import SentenceEncoder, _bucket
from pathway_tpu.models.packing import (
    pack_padded, pack_rows, pad_packed_rows, seg_bucket,
)
from pathway_tpu.observe import trace
from pathway_tpu.ops.knn import DeviceKnnIndex
from pathway_tpu.ops.retrieve_rerank import RetrieveRerankPipeline
from pathway_tpu.ops.serving import FusedEncodeSearch


needs_native = pytest.mark.usefixtures("needs_native")  # tests/conftest.py


def _oracle(ids_b, lens, L, max_docs, slot_ids, drop_slot, row_bucket=_bucket):
    """What ``_submit_stage2`` did before: the Python body, the padding and
    the slot loop, written out."""
    ids, _mask, segments, positions, doc_slots, n_seg = pack_rows(
        ids_b, lens, L, max_docs
    )
    R = ids.shape[0]
    Rb = row_bucket(R)
    ids, segments, positions = pad_packed_rows(ids, segments, positions, Rb)
    Sb = seg_bucket(n_seg)
    pair_slot = np.full(Rb * Sb, drop_slot, np.int32)
    for i, (r, s) in enumerate(doc_slots):
        pair_slot[r * Sb + s] = slot_ids[i]
    return ids, segments, positions, pair_slot, doc_slots, R, n_seg, Sb


def _tokens(lens, width, seed=0):
    """ids_b [n, width]: distinct ids in the real tokens, and junk past each
    sequence's length (``lens`` may clip a row: the tail must not travel)."""
    rng = np.random.default_rng(seed)
    return rng.integers(8, 30000, size=(len(lens), width)).astype(np.int32)


def _assert_equal(got, want):
    ids, segments, positions, pair_slot, doc_slots, R, n_seg, Sb = want
    assert got.native
    for name, a, b in (
        ("ids", got.ids, ids), ("segments", got.segments, segments),
        ("positions", got.positions, positions),
        ("pair_slot", got.pair_slot, pair_slot),
    ):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert a.flags.c_contiguous, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    assert list(zip(got.row_of.tolist(), got.seg_of.tolist())) == doc_slots
    assert (got.rows, got.n_seg, got.seg_width) == (R, n_seg, Sb)


def _rows_of_pairs(n_rows):
    """Lengths that pack into exactly ``n_rows`` rows of 128: two a row."""
    return [65, 63] * n_rows


# every branch of the placement, by name
CASES = {
    # all lengths equal: every placement is a tie, broken by row id and by
    # the stable order
    "ties_all_equal": (128, 8, [16] * 40),
    "ties_two_lengths": (64, 8, [20, 12] * 17),
    # eight short pairs close a row that still has room
    "closed_by_the_cap_of_8": (128, 8, [4] * 20 + [90, 3, 3]),
    # 127 of 128 used: under 2 tokens left, the row closes with one pair
    "closed_by_under_2_left": (128, 8, [127, 127, 1, 1, 126, 2, 5]),
    "exactly_2_left_stays_open": (32, 8, [30, 2, 30, 2, 29, 3]),
    # every length at the row width (what clipping to L leaves)
    "clipped_to_L": (32, 8, [32] * 5 + [31, 1, 7]),
    "one_pair": (64, 8, [9]),
    "one_full_pair": (128, 8, [128]),
    "zero_length_rides_along": (32, 8, [0, 5, 0, 32, 3]),
    "one_per_row": (32, 1, [5, 9, 3, 30]),
    "cap_of_2": (64, 2, [10] * 9),
    # segment width past the first bucket: 12 a row -> Sb 12, 9 -> 12
    "sb_past_8": (128, 12, [5] * 30),
    "sb_at_9": (128, 9, [6] * 9),
    "sb_at_8": (128, 8, [6] * 8),
    # Rb at a bucket's edge and one past it
    "rb_at_4": (128, 8, _rows_of_pairs(4)),
    "rb_at_5": (128, 8, _rows_of_pairs(5)),
    "rb_at_16": (128, 8, _rows_of_pairs(16)),
    "rb_at_17": (128, 8, _rows_of_pairs(17)),
    "rb_at_64": (128, 8, _rows_of_pairs(64)),
    "rb_at_65": (128, 8, _rows_of_pairs(65)),
    "rb_at_256": (128, 8, _rows_of_pairs(256)),
    "rb_at_257": (128, 8, _rows_of_pairs(257)),
}


@needs_native
@pytest.mark.parametrize("case", sorted(CASES))
def test_native_layout_equals_the_python_body(case):
    L, max_docs, lens = CASES[case]
    lens = np.asarray(lens, np.int64)
    n = len(lens)
    ids_b = _tokens(lens, L + 5)
    slot_ids = [3 * i + 1 for i in range(n)]
    drop = 3 * n + 7
    got = pack_padded(
        ids_b, lens, L, _bucket, max_docs, slot_ids=slot_ids, drop_slot=drop
    )
    _assert_equal(got, _oracle(ids_b, lens, L, max_docs, slot_ids, drop))
    if case.startswith("rb_at_"):
        assert got.rows == int(case[6:])  # the edge the case names


@needs_native
@pytest.mark.parametrize("L", [32, 64, 128])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_native_layout_equals_the_python_body_on_seeded_batches(seed, L):
    """The rerank cell's shape (11 queries x 32 documents) and others, with
    lengths as ``_pack_pairs`` hands them over: clipped to L."""
    rng = np.random.default_rng(1000 * L + seed)
    n = int(rng.choice([1, 7, 32, 352, 700]))
    lens = np.minimum(rng.integers(3, 150, size=n), L).astype(np.int64)
    ids_b = _tokens(lens, 128, seed)
    slot_ids = rng.permutation(n).astype(np.int64)
    got = pack_padded(ids_b, lens, L, _bucket, slot_ids=slot_ids, drop_slot=n)
    _assert_equal(got, _oracle(ids_b, lens, L, 8, slot_ids, n))
    # nothing past a sequence's length travelled, and every slot landed once
    assert int((got.segments > 0).sum()) == int(lens.sum())
    assert sorted(got.pair_slot[got.pair_slot != n].tolist()) == list(range(n))


@needs_native
def test_without_slot_ids_there_is_no_slot_table():
    lens = np.asarray([10, 20, 30], np.int64)
    got = pack_padded(_tokens(lens, 32), lens, 32, _bucket)
    assert got.native and got.pair_slot is None
    want = _oracle(_tokens(lens, 32), lens, 32, 8, [0, 0, 0], 0)
    np.testing.assert_array_equal(got.ids, want[0])
    assert list(zip(got.row_of.tolist(), got.seg_of.tolist())) == want[4]


@needs_native
def test_the_bucket_rule_is_the_callers():
    """The tables carry whatever rule the caller passes: no copy in C++."""
    def by_threes(r):
        return ((r + 2) // 3) * 3

    lens = np.asarray(_rows_of_pairs(7), np.int64)
    ids_b = _tokens(lens, 128)
    slot_ids = list(range(len(lens)))
    got = pack_padded(ids_b, lens, 128, by_threes, slot_ids=slot_ids, drop_slot=99)
    _assert_equal(got, _oracle(ids_b, lens, 128, 8, slot_ids, 99, by_threes))
    assert got.ids.shape == (9, 128)


@needs_native
@pytest.mark.parametrize("lens", [[40], [-1, 3], [3, 200]])
def test_a_length_the_row_cannot_hold_is_refused(lens):
    """Lengths come clipped to L; one that is not is refused by the native
    call (it never reads or writes past a row)."""
    lens = np.asarray(lens, np.int64)
    ids_b = _tokens(lens, 256)
    tables = packing._bucket_tables(len(lens), 8, _bucket)
    assert native.pack_rows(ids_b, lens, 32, 8, *tables) is None


# -- the fallbacks --------------------------------------------------------------


def _python_expected(ids_b, lens, slot_ids, drop):
    got = pack_padded(ids_b, lens, 64, _bucket, slot_ids=slot_ids, drop_slot=drop)
    want = _oracle(ids_b, lens, 64, 8, slot_ids, drop)
    assert not got.native
    for a, b in zip(got[:4], want[:4]):
        np.testing.assert_array_equal(a, b)
    assert list(zip(got.row_of.tolist(), got.seg_of.tolist())) == want[4]
    assert (got.rows, got.n_seg, got.seg_width) == want[5:]


def test_without_the_library_the_python_body_answers(monkeypatch):
    """No library (it is disabled, or did not compile): the Python body,
    same arrays."""
    monkeypatch.setattr(native, "lib", lambda: None)
    rng = np.random.default_rng(5)
    lens = rng.integers(3, 64, size=50).astype(np.int64)
    _python_expected(_tokens(lens, 64), lens, list(range(50)), 50)


# -- the pair path: counter, span attribute, scores ---------------------------


DOCS = {
    i: f"document number {i} about {topic} with live updates"
    for i, topic in enumerate(
        ["dataflow", "indexes", "exactly once", "joins", "windows", "replay"] * 3
    )
}


@pytest.fixture(scope="module")
def cross_encoder():
    return CrossEncoderModel(
        dimension=32, n_layers=2, n_heads=4, max_length=64,
        vocab_size=512, dtype=jnp.float32,
    )


@pytest.fixture(scope="module")
def pipeline(cross_encoder):
    enc = SentenceEncoder(
        dimension=32, n_layers=2, n_heads=4, max_length=32,
        vocab_size=512, dtype=jnp.float32,
    )
    index = DeviceKnnIndex(dimension=32, metric="cos", initial_capacity=64)
    index.add(sorted(DOCS), enc.encode([DOCS[i] for i in sorted(DOCS)]))
    return RetrieveRerankPipeline(
        FusedEncodeSearch(enc, index, k=len(DOCS)), cross_encoder, DOCS,
        k=3, candidates=len(DOCS),
    )


def _packed_counts():
    return tuple(
        observe.counter("pathway_serve_pack_pairs_total", path=p).value
        for p in ("native", "python")
    )


QUERIES = ["exactly once replay", "window joins"]


def _serve_traced(pipeline):
    before = _packed_counts()
    ctx = trace.start_trace("t", sample=False)
    with trace.use(ctx):
        res = pipeline(QUERIES)
    counted = tuple(a - b for a, b in zip(_packed_counts(), before))
    pack = {s[2]: s for s in ctx.spans}["stage2.pack"]
    return res, counted, pack[6]


@needs_native
@pytest.mark.parametrize("path", ["native", "python"])
def test_pairs_are_counted_under_the_layout_path_they_took(
    pipeline, monkeypatch, path
):
    """``pathway_serve_pack_pairs_total{path}`` and the ``stage2.pack`` span's
    ``native_packed``, beside ISSUE 25's ``native_pairs`` (the tokenizer's
    path is its own: a fallback layout leaves it native)."""
    if path == "python":
        monkeypatch.setattr(native, "pack_rows", lambda *a, **k: None)
    res, counted, attrs = _serve_traced(pipeline)
    n_pairs = len(QUERIES) * len(DOCS)
    assert [len(r) for r in res] == [3, 3]
    assert counted == ((n_pairs, 0) if path == "native" else (0, n_pairs))
    assert attrs["native_packed"] == (n_pairs if path == "native" else 0)
    assert attrs["native_pairs"] == n_pairs
    series = 'pathway_serve_pack_pairs_total{path="%s"}' % path  # on /metrics
    assert any(line.startswith(series) for line in observe.render_prometheus())


@needs_native
def test_both_layout_paths_serve_the_same_rows(pipeline, monkeypatch):
    native_rows = [list(r) for r in pipeline(QUERIES)]
    monkeypatch.setattr(native, "pack_rows", lambda *a, **k: None)
    assert [list(r) for r in pipeline(QUERIES)] == native_rows


@needs_native
def test_predict_gathers_the_same_scores_on_both_layout_paths(
    cross_encoder, monkeypatch
):
    """``CrossEncoderModel.submit``'s packed path reads its scores back
    through (row, seg): same numbers either way, in input order."""
    pairs = [(q, d) for q in QUERIES for d in DOCS.values()]
    fast = cross_encoder.predict(pairs)
    monkeypatch.setattr(native, "pack_rows", lambda *a, **k: None)
    np.testing.assert_array_equal(cross_encoder.predict(pairs), fast)
    assert fast.shape == (len(pairs),)

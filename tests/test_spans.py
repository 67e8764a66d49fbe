"""The span primitive (observe/spans.py) and the sites converted to it.

``observe.span`` feeds ONE pair of clock reads to the wall histogram, the
thread-CPU twin, a counter of seconds, the active trace tree and a
``pw.<name>`` profiler event; ``observe.interval`` records what crossed
threads or was a wait (histogram + tree, no profiler event).  The sites'
identities hold by construction, on the histograms' own nanosecond sums:

- stage1_tokenize + stage1_lock_wait + stage1_dispatch == tokenize_pack
- stage2_gather + stage2_packrows + stage2_dispatch == stage2_pack
- per request, queue_wait == admission_wait + its batch's launch
"""

from __future__ import annotations

import textwrap
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu import observe
from pathway_tpu.analysis import analyze_source
from pathway_tpu.models.cross_encoder import CrossEncoderModel
from pathway_tpu.models.encoder import SentenceEncoder
from pathway_tpu.observe import spans, trace
from pathway_tpu.ops.ivf import IvfKnnIndex
from pathway_tpu.ops.knn import DeviceKnnIndex
from pathway_tpu.ops.retrieve_rerank import RetrieveRerankPipeline
from pathway_tpu.ops.serving import FusedEncodeSearch
from pathway_tpu.robust import ServeResult
from pathway_tpu.serve import ServeScheduler

DOCS = {
    i: f"document number {i} about {topic} case {i % 5} with live updates"
    for i, topic in enumerate(
        [
            "incremental dataflow", "vector indexes", "exactly once",
            "stream joins", "window aggregation", "schema registries",
            "kafka offsets", "snapshot replay", "rag retrieval",
            "sharded state", "commit ticks", "key ownership",
        ]
        * 3
    )
}
QUERIES = [
    "rag retrieval serving", "exactly once stream", "kafka offsets replay",
    "vector index search", "window aggregation ticks", "snapshot of state",
]


def _sum_ns(family: str, **labels) -> int:
    return observe.histogram(family, **labels).snapshot()[1]


def _stage_ns(stage: str) -> int:
    return _sum_ns("pathway_serve_stage_seconds", stage=stage)


def _stage_count(stage: str) -> int:
    return observe.histogram("pathway_serve_stage_seconds", stage=stage).count


@pytest.fixture(scope="module")
def models():
    enc = SentenceEncoder(
        dimension=32, n_layers=2, n_heads=4, max_length=32,
        vocab_size=512, dtype=jnp.float32,
    )
    ce = CrossEncoderModel(
        dimension=32, n_layers=2, n_heads=4, max_length=64,
        vocab_size=512, dtype=jnp.float32,
    )
    vecs = enc.encode([DOCS[i] for i in sorted(DOCS)])
    return enc, ce, vecs


def _index(models, kind: str):
    _enc, _ce, vecs = models
    if kind == "exact":
        index = DeviceKnnIndex(dimension=32, metric="cos", initial_capacity=64)
        index.add(sorted(DOCS), vecs)
        return index
    index = IvfKnnIndex(
        dimension=32, metric="cos", n_clusters=4, n_probe=4,
        absorb_threshold=4096, seed=3,
    )
    index.add(sorted(DOCS), vecs)
    index.build()
    return index


# -- the primitive -----------------------------------------------------------


def test_span_feeds_histogram_cpu_twin_counter_and_tree_from_one_pair_of_reads():
    hist = observe.histogram("t_spans_seconds", case="one")
    cpu_hist = observe.histogram("t_spans_cpu_seconds", case="one")
    seconds = observe.counter("t_spans_busy_seconds_total", case="one")
    for series in (hist, cpu_hist, seconds):
        series.reset()
    ctx = trace.start_trace("t", sample=False)
    with trace.use(ctx):
        with observe.span(
            "unit.work", hist=hist, cpu_hist=cpu_hist, counter=seconds, rows=3
        ) as sp:
            sum(i * i for i in range(20_000))  # on-CPU work
            sp.set(extra="seen")
    wall = sp.t1_ns - sp.t0_ns
    assert wall > 0 and sp.cpu_ns > 0
    assert hist.snapshot()[1:] == (wall, 1)
    assert cpu_hist.snapshot()[1:] == (sp.cpu_ns, 1)
    assert seconds.value == pytest.approx(wall * 1e-9)
    (recorded,) = [s for s in ctx.spans if s[2] == "unit.work"]
    _sid, parent, _name, t0, dur, status, attrs, exemplar = recorded
    assert (t0, dur, parent, status) == (sp.t0_ns, wall, 1, "ok")
    assert exemplar is hist
    assert attrs["rows"] == 3 and attrs["extra"] == "seen"
    assert attrs["cpu_ms"] == pytest.approx(sp.cpu_ns * 1e-6)
    trace.finish(ctx)


def test_cpu_twin_is_sampled_every_seventh_bracket_of_its_series():
    hist = observe.histogram("t_spans_seconds", case="sampled")
    cpu_hist = observe.histogram("t_spans_cpu_seconds", case="sampled")
    other = observe.histogram("t_spans_cpu_seconds", case="sampled_other")
    for series in (hist, cpu_hist, other):
        series.reset()
    measured = []
    for i in range(15):
        with observe.span("unit.sampled", hist=hist, cpu_hist=cpu_hist) as sp:
            pass
        measured.append(sp.cpu_hist is not None)
        # a second series between them has its own tick: no aliasing
        with observe.span("unit.other", cpu_hist=other):
            pass
    assert [i for i, m in enumerate(measured) if m] == [0, 7, 14]
    assert (hist.count, cpu_hist.count, other.count) == (15, 3, 3)


def test_disabled_span_is_a_shared_noop_that_reads_no_clock(monkeypatch):
    hist = observe.histogram("t_spans_seconds", case="off")
    hist.reset()

    def no_clock():
        raise AssertionError("a clock was read while the recorder is off")

    observe.set_enabled(False)
    monkeypatch.setattr(spans, "_perf_counter_ns", no_clock)
    monkeypatch.setattr(spans, "_thread_time_ns", no_clock)
    try:
        first = observe.span("unit.off", hist=hist, cpu_hist=hist)
        second = observe.span("unit.other")
        assert first is second  # one shared object: nothing allocated
        with first as sp:
            sp.set(status="skipped", rows=1)
        assert (sp.t0_ns, sp.t1_ns, sp.cpu_ns) == (0, 0, 0)
        # an interval fed from a disabled span's reads is dropped too
        observe.interval("unit.off.wait", sp.t0_ns, sp.t1_ns, hist=hist)
    finally:
        monkeypatch.undo()
        observe.set_enabled(True)
    assert hist.count == 0
    # ... and once the recorder is back on, a start of 0 still marks an
    # interval whose first read never happened
    observe.interval("unit.off.wait", 0, time.perf_counter_ns(), hist=hist)
    assert hist.count == 0


def test_spans_nest_and_chain():
    ctx = trace.start_trace("t", sample=False)
    with trace.use(ctx):
        with observe.span("unit.outer") as outer:
            with observe.span("unit.first") as first:
                pass
            with observe.span("unit.second", after=first) as second:
                pass
            observe.interval("unit.wait", first.t0_ns, first.t1_ns)
        observe.interval("unit.top", outer.t0_ns, outer.t1_ns)
    # chained: the second starts exactly where the first ended
    assert second.t0_ns == first.t1_ns
    by_name = {s[2]: s for s in ctx.spans}
    outer_sid = by_name["unit.outer"][0]
    for child in ("unit.first", "unit.second", "unit.wait"):
        assert by_name[child][1] == outer_sid, child
    assert by_name["unit.outer"][1] == 1 and by_name["unit.top"][1] == 1
    trace.finish(ctx, force_keep=True)
    root = trace.get_trace(ctx.trace_id)["root"]
    (outer_node,) = [c for c in root["children"] if c["name"] == "unit.outer"]
    assert [c["name"] for c in outer_node["children"]] == [
        "unit.first", "unit.wait", "unit.second",
    ]
    # self time = a span minus its children
    covered = sum(
        c["duration_ms"] for c in outer_node["children"] if c["name"] != "unit.wait"
    )
    assert 0 <= outer_node["duration_ms"] - covered


def test_span_records_an_exception_as_error_and_still_observes():
    hist = observe.histogram("t_spans_seconds", case="raise")
    hist.reset()
    ctx = trace.start_trace("t", sample=False)
    with trace.use(ctx), pytest.raises(ValueError):
        with observe.span("unit.raises", hist=hist):
            raise ValueError("boom")
    assert hist.count == 1
    (recorded,) = [s for s in ctx.spans if s[2] == "unit.raises"]
    assert recorded[5] == "error"
    trace.finish(ctx)


def test_interval_targets_a_named_tree_and_the_serve_ring():
    hist = observe.histogram("t_spans_seconds", case="interval")
    hist.reset()
    rider = trace.start_trace("rider", sample=False)
    batch = trace.start_trace("batch", kind="batch", sample=False)
    t0 = time.perf_counter_ns()
    before = observe.snapshot()["events_total"]
    with trace.use(batch):  # e.g. the scheduler thread, inside a batch
        observe.interval(
            "unit.rider_wait", t0, t0 + 5_000, hist=hist, tree=rider,
            status="late", ring=True, rows=2,
        )
        observe.interval("unit.untraced", t0, t0 + 7_000, hist=hist, tree=None)
    assert [s[2] for s in rider.spans] == ["unit.rider_wait"]
    assert rider.spans[0][4:6] == (5_000, "late")
    assert batch.spans == []
    assert hist.snapshot()[1:] == (12_000, 2)
    snap = observe.snapshot()
    assert snap["events_total"] == before + 1
    event = snap["events"][-1]
    assert (event["kind"], event["tag"], event["dur_ns"]) == (
        "serve", "unit.rider_wait", 5_000,
    )
    trace.finish(rider)
    trace.finish(batch)


def test_span_exports_its_real_start_and_end_to_otlp(monkeypatch):
    sent = []

    class _Exporter:
        def export_span(self, name, start_unix_ns, end_unix_ns, **attrs):
            sent.append((name, start_unix_ns, end_unix_ns, attrs))

    monkeypatch.setattr(spans, "_otlp", _Exporter())
    with observe.span("unit.exported", rows=4) as sp:
        time.sleep(0.002)
    ((name, start, end, attrs),) = sent
    assert name == "pathway.unit.exported" and attrs["rows"] == 4
    assert end - start == sp.t1_ns - sp.t0_ns >= 2_000_000
    assert abs(start - time.time_ns()) < 60e9  # unix time, not perf_counter


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One short ``jax.profiler`` session on the CPU around a traced span,
    an untraced span and an interval; returns ``(events, trace_id)`` with
    ``events`` the host events read back through ``ProfileData``."""
    import glob

    from jax.profiler import ProfileData

    out = str(tmp_path_factory.mktemp("profile"))
    ctx = trace.start_trace("t", sample=False)
    # an id the profiler's metadata parser would read as a number (1e…)
    ctx.trace_id = "1e16325700000001"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 2
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        deadline = time.monotonic() + 2.0
        with trace.use(ctx):
            with observe.span("unit.profiled"):
                time.sleep(0.01)
        with observe.span("unit.untraced"):
            time.sleep(0.01)
        t0 = time.perf_counter_ns()
        observe.interval("unit.interval", t0, t0 + 10_000_000)
        while time.monotonic() < deadline:  # a two-second session
            time.sleep(0.05)
    finally:
        jax.profiler.stop_trace()
    trace.finish(ctx)
    (xplane,) = glob.glob(f"{out}/plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(xplane)
    events = [
        (ev.name, ev.duration_ns, dict(ev.stats))
        for plane in data.planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
    ]
    return events, ctx.trace_id


def test_span_emits_exactly_one_profiler_event_named_pw(profiled):
    events, trace_id = profiled
    (traced,) = [e for e in events if e[0] == "pw.unit.profiled"]
    assert traced[1] >= 10_000_000
    # the tree's id rides the event: a device trace joins to GET /traces
    assert traced[2].get("trace") == "t" + trace_id
    (untraced,) = [e for e in events if e[0] == "pw.unit.untraced"]
    assert "trace" not in untraced[2]


def test_interval_emits_no_profiler_event(profiled):
    events, _ = profiled
    assert not [e for e in events if "unit.interval" in e[0]]
    # and the benchmark's reader sees the span where it collects host events
    assert sum(1 for e in events if e[0].startswith("pw.unit.")) == 2


# -- the converted sites -----------------------------------------------------


@pytest.mark.parametrize("kind", ["exact", "ivf"])
def test_stage1_parts_sum_to_tokenize_pack(models, kind):
    enc, _ce, _vecs = models
    fused = FusedEncodeSearch(enc, _index(models, kind), k=5)
    fused(QUERIES[:2])  # compile
    observe.reset()
    spans._cpu_ticks.clear()
    for q in QUERIES:
        rows = fused([q])
        assert len(rows[0]) == 5
    parts = sum(
        _stage_ns(s)
        for s in ("stage1_tokenize", "stage1_lock_wait", "stage1_dispatch")
    )
    assert _stage_count("tokenize_pack") == len(QUERIES)
    assert parts == _stage_ns("tokenize_pack") > 0
    # the fetch bracket is the np.asarray line only, inside the round trip
    assert 0 < _stage_ns("stage1_fetch") <= _stage_ns("stage1_rtt")
    # stage 1's own postprocess feeds the shared series one for one
    assert _stage_ns("stage1_postprocess") == _stage_ns("postprocess")
    # the CPU twin reads a costly clock: every 7th bracket of a series
    sampled = -(-len(QUERIES) // spans._CPU_EVERY)
    for stage in ("stage1_tokenize", "stage1_dispatch", "stage1_postprocess"):
        cpu = observe.histogram("pathway_serve_stage_cpu_seconds", stage=stage)
        assert cpu.count == sampled, stage


def test_stage2_parts_sum_to_stage2_pack_and_queue_wait_splits(models):
    enc, ce, _vecs = models
    pipe = RetrieveRerankPipeline(
        FusedEncodeSearch(enc, _index(models, "exact"), k=8), ce, DOCS,
        k=3, candidates=8,
    )
    pipe(QUERIES[:2])  # compile both stages
    observe.reset()
    trace.reset()
    wake = observe.histogram("pathway_serve_ticket_wake_seconds")
    with ServeScheduler(pipe, window_us=500, result_cache=None) as sched:
        for q in QUERIES:  # one at a time: every batch has one rider
            res = sched.serve([q])
            assert len(res[0]) == 3 and not res.degraded
        assert wake.count == len(QUERIES)  # once per rider
        ticket = sched.submit([QUERIES[0] + " again"])
        ticket()
        ticket()  # a second read of the same ticket is not a second wake-up
        n = len(QUERIES) + 1
        assert wake.count == n
    parts = sum(
        _stage_ns(s)
        for s in ("stage2_gather", "stage2_packrows", "stage2_dispatch")
    )
    assert _stage_count("stage2_pack") == n
    assert parts == _stage_ns("stage2_pack") > 0
    # the two stages' postprocess, apart and in the old shared series
    assert (
        _stage_ns("stage1_postprocess") + _stage_ns("stage2_postprocess")
        == _stage_ns("postprocess")
    )
    # per request (single-rider batches, so the sums compare exactly):
    # queue_wait == admission_wait + its batch's launch
    assert _stage_count("launch") == n
    assert _sum_ns("pathway_serve_queue_wait_seconds") == (
        _sum_ns("pathway_serve_admission_wait_seconds") + _stage_ns("launch")
    )
    # the scheduler thread's phases are counted in seconds
    phases = {
        p: observe.counter("pathway_serve_dispatcher_seconds_total", phase=p).value
        for p in ("idle", "window", "launch", "advance")
    }
    assert phases["launch"] == pytest.approx(_stage_ns("launch") * 1e-9)
    assert phases["window"] > 0 and phases["idle"] > 0
    # a rider's tree: admission wait and wake-up on the rider, launch with
    # its children on the linked batch tree
    snap = trace.snapshot_traces()
    assert snap["spans_dropped_total"] == 0
    events = observe.snapshot()["events"]
    assert any(e["tag"] == "rerank_stage2" for e in events)


def test_rider_tree_links_to_a_batch_tree_with_launch_over_its_parts(models):
    enc, _ce, _vecs = models
    fused = FusedEncodeSearch(enc, _index(models, "ivf"), k=5)
    fused(QUERIES[:1])
    trace.reset()

    class _Keep:  # a degraded answer is always kept by the tail sampler
        index_generation = staticmethod(fused.index_generation)

        @staticmethod
        def submit(texts, k, deadline=None):
            done = fused.submit(texts, k, deadline=deadline)
            return lambda: ServeResult(list(done()), degraded=("unit_kept",))

    with ServeScheduler(_Keep, window_us=500, result_cache=None) as sched:
        sched.serve([QUERIES[0]])
    (rider,) = [
        t for t in trace.snapshot_traces()["traces"] if t["kind"] == "request"
    ]
    top = {c["name"]: c for c in rider["root"]["children"]}
    assert {"admission", "batch"} <= set(top)
    link = top["batch"]
    by_name = {c["name"]: c for c in link["linked"]["root"]["children"]}
    # the lock wait is the gap between the launch's two children
    tokenize, dispatch = by_name["sched.launch"]["children"]
    assert (tokenize["name"], dispatch["name"]) == (
        "stage1.tokenize", "stage1.dispatch",
    )
    assert dispatch["start_ms"] >= tokenize["start_ms"] + tokenize["duration_ms"]
    assert {"stage1.fetch", "stage1.postprocess"} <= set(by_name)
    # the link span is the queue wait: the wait for a launch, then the launch
    assert link["attrs"]["wake_ms"] > 0
    assert link["duration_ms"] == pytest.approx(
        link["attrs"]["admission_wait_ms"]
        + by_name["sched.launch"]["duration_ms"]
    )


def test_admission_wait_excludes_and_queue_wait_includes_a_slow_launch():
    class _SlowTarget:
        k = 3

        def submit(self, texts, k, deadline=None):
            time.sleep(0.05)  # the launch: on the scheduler thread
            return lambda: ServeResult([[(1, 1.0)] for _ in texts])

    observe.reset()
    with ServeScheduler(_SlowTarget(), window_us=1000, result_cache=None) as sched:
        assert sched.serve(["q"])[0] == [(1, 1.0)]
    admission = _sum_ns("pathway_serve_admission_wait_seconds")
    launch = _stage_ns("launch")
    queue = _sum_ns("pathway_serve_queue_wait_seconds")
    assert launch >= 50_000_000
    assert admission < 40_000_000  # backlog + a 1 ms window, no launch
    assert queue == admission + launch


def test_lock_wait_sees_a_lock_held_elsewhere_and_dispatch_does_not(models):
    enc, _ce, _vecs = models
    index = _index(models, "ivf")
    fused = FusedEncodeSearch(enc, index, k=5)
    fused(QUERIES[:1])  # compile
    observe.reset()
    held, release = threading.Event(), threading.Event()

    def hold():
        with index._lock:
            held.set()
            release.wait(5.0)

    holder = threading.Thread(target=hold)
    holder.start()
    assert held.wait(5.0)
    threading.Timer(0.08, release.set).start()
    rows = fused(QUERIES[:1])
    holder.join(5.0)
    assert not holder.is_alive() and len(rows[0]) == 5
    lock_wait = _stage_ns("stage1_lock_wait")
    dispatch = _stage_ns("stage1_dispatch")
    assert lock_wait >= 60_000_000, lock_wait
    assert dispatch < 60_000_000, dispatch  # opened after the locks were held
    assert _stage_ns("stage1_tokenize") + lock_wait + dispatch == _stage_ns(
        "tokenize_pack"
    )


def test_absorb_commit_span_opens_after_the_lock(models):
    _enc, _ce, vecs = models
    index = _index(models, "ivf")
    rng = np.random.default_rng(5)
    fresh = vecs[:6] + 0.01 * rng.standard_normal((6, 32)).astype(np.float32)
    index.add(range(1000, 1006), fresh)  # below the threshold: stays in the tail
    observe.reset()
    planned, go = threading.Event(), threading.Event()
    plan_absorb = index._plan_absorb

    def plan_then_wait(snap):
        plan = plan_absorb(snap)
        planned.set()
        go.wait(5.0)
        return plan

    index._plan_absorb = plan_then_wait
    absorber = threading.Thread(target=index._absorb_bg)
    absorber.start()
    assert planned.wait(10.0)
    with index._lock:  # the commit now has to wait for this lock
        go.set()
        time.sleep(0.08)
    absorber.join(10.0)
    assert not absorber.is_alive() and index.stats["absorbs"] == 1
    commit = _sum_ns("pathway_ivf_absorb_stage_seconds", stage="commit")
    plan = _sum_ns("pathway_ivf_absorb_stage_seconds", stage="plan")
    whole = _sum_ns("pathway_ivf_absorb_seconds")
    assert 0 < commit < 60_000_000, commit  # the scatter, not the wait
    assert whole >= plan + commit + 60_000_000  # the wait is in the whole only


def test_ingest_embed_span_feeds_wall_and_cpu_twin(models):
    from pathway_tpu.serve import LiveIngestRunner

    enc, _ce, _vecs = models
    index = _index(models, "ivf")
    observe.reset()
    spans._cpu_ticks.clear()
    runner = LiveIngestRunner(enc, index, name="spans")
    try:
        conn = runner.connector()
        conn.insert(4242, "a freshly committed document about vector indexes")
        conn.commit()
        assert runner.flush(timeout=20.0)
    finally:
        runner.stop()
    wall = observe.histogram("pathway_freshness_stage_seconds", stage="embed")
    cpu = observe.histogram("pathway_freshness_stage_cpu_seconds", stage="embed")
    assert wall.count == cpu.count == 1
    assert 0 < cpu.snapshot()[1] and wall.snapshot()[1] > 0


def test_analyzer_flags_observe_span_whose_body_takes_a_lock():
    def lint(src):
        found = analyze_source(textwrap.dedent(src), "fixtures/mod.py")
        return [
            f for f in found if f.rule == "lock-discipline" and not f.suppressed
        ]

    bad = """
        # pathway: serve-path
        import threading

        from pathway_tpu import observe

        class Pipe:
            def __init__(self):
                self._lock = threading.Lock()

            def submit(self, q):
                with observe.span("stage1.dispatch"):
                    with self._lock:
                        fn = self._fns.get(q)
                return fn
    """
    (finding,) = lint(bad)
    assert "span opened across" in finding.message
    assert "observe.interval" in finding.message
    good = """
        # pathway: serve-path
        import threading
        import time

        from pathway_tpu import observe

        class Pipe:
            def __init__(self):
                self._lock = threading.Lock()

            def submit(self, q):
                t_ready = time.perf_counter_ns()
                with self._lock:
                    with observe.span("stage1.dispatch") as dispatch:
                        fn = self._fns.get(q)
                observe.interval("stage1.lock_wait", t_ready, dispatch.t0_ns)
                return fn
    """
    assert lint(good) == []

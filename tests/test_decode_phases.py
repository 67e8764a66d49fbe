"""The decode engine's own accounting (serve/decode.py, ISSUE 36).

The engine thread's loop is a chain of sibling ``observe.span``s, each
starting where the one before it ended, so the phases of
``pathway_generator_engine_seconds_total`` sum to the thread's wall time;
and every request that leaves the slot pool has its life split into six
phases that sum to its ``pathway_generator_ttlt_seconds`` observation:
slot wait, its own join, stalled by others' joins, stepping, engine host
work, wake-up.  Both hold by construction, on the spans' own clock reads:
the tests below check the construction, on the toy trunk and on one
decoder family (the looped one, which brings a prefix tier).
"""

from __future__ import annotations

import glob
import threading
import time

import jax
import pytest

from pathway_tpu import observe
from pathway_tpu.analysis import analyze_source
from pathway_tpu.models.generator import TextGenerator
from pathway_tpu.observe import trace
from pathway_tpu.serve import ContinuousDecoder, decode

PHASES = ("slot_wait", "join", "stalled", "stepping", "host", "wake")
ENGINE_PHASES = (
    "idle", "join_host", "prefill_operands", "prefill_prefix", "prefill_call", "prefill_fetch",
    "prefill_settle", "step_operands", "step_dispatch", "step_fetch", "step_replay",
)
JOIN_SPANS = ("gen.prefill.dispatch", "gen.prefill.operands", "gen.prefill.prefix", "gen.prefill.call",
              "gen.prefill.fetch", "gen.prefill.settle")
STEP_SPANS = ("gen.step.operands", "gen.step.dispatch", "gen.step.fetch", "gen.step.replay")
LOOPED = dict(
    vocab_size=512, hidden_size=64, num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    intermediate_size=160, num_hidden_layers=3, total_ut_steps=4, rms_norm_eps=1e-6, rope_theta=1e6,
    max_position_embeddings=256, hidden_act="silu", tie_word_embeddings=False,
)
PROMPTS = [
    "hello world", "the quick brown fox jumps over", "alpha beta gamma delta",
    "continuous batching decode engine", "one more prompt to decode", "short",
]


@pytest.fixture(scope="module", params=["toy", "looped"])
def generator(request):
    if request.param == "toy":
        return TextGenerator(dimension=32, n_layers=2, n_heads=4, max_length=64, vocab_size=512, kv_cache=None)
    return TextGenerator(architecture=LOOPED, seed=5)


def _engine(generator, **kw):
    args = dict(slots=2, step_bucket=4)
    args.update(kw)
    if generator.family is not None:
        args.setdefault("kv_width", 64)
    return ContinuousDecoder(generator, **args)


def _sum_ns(family: str, **labels) -> int:
    return observe.histogram(family, **labels).snapshot()[1]


def _count(family: str, **labels) -> int:
    return observe.histogram(family, **labels).count


def _engine_seconds():
    return {p: observe.counter("pathway_generator_engine_seconds_total", phase=p).value for p in ENGINE_PHASES}


class _Spy:
    """Every span the engine opens, as the object the engine itself reads."""

    def __init__(self, monkeypatch):
        self.spans = []
        real = observe.span

        def span(name, *a, **kw):
            sp = real(name, *a, **kw)
            self.spans.append((name, sp))
            return sp

        monkeypatch.setattr(decode.observe, "span", span)

    def named(self, name):
        return [sp for n, sp in self.spans if n == name]


# -- B: a request's life ------------------------------------------------------


def test_six_phases_are_non_negative_and_sum_to_the_riders_own_time(generator):
    eng = _engine(generator)
    try:
        eng.submit(PROMPTS[0], max_new_tokens=6)()  # compiles
        observe.reset()
        seen, lock = [], threading.Lock()

        def caller(k):
            for i in range(3):
                ticket = eng.submit(PROMPTS[(k + 2 * i) % len(PROMPTS)], max_new_tokens=3 + (k + i) % 6)
                out = ticket()
                t_back = time.perf_counter_ns()
                with lock:
                    seen.append((out, t_back - ticket._request.t_enqueue_ns))

        threads = [threading.Thread(target=caller, args=(k,)) for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        eng.stop()
    assert len(seen) == 12
    for out, outer_ns in seen:
        assert not out.degraded
        phases = out.meta["phases_ms"]
        assert tuple(phases) == PHASES
        assert all(v >= 0 for v in phases.values()), phases
        total_ns = sum(phases.values()) * 1e6
        # enqueue -> woke, read inside the ticket; the test reads its own clock once more after it
        assert total_ns <= outer_ns and outer_ns - total_ns < 2e9
    # to the nanosecond, on the histograms' own sums: the six series sum to the ttlt series
    assert all(_count("pathway_generator_request_seconds", phase=p) == 12 for p in PHASES)
    assert _count("pathway_generator_ttlt_seconds") == 12
    assert sum(_sum_ns("pathway_generator_request_seconds", phase=p) for p in PHASES) == _sum_ns(
        "pathway_generator_ttlt_seconds"
    )


def test_a_lane_is_stalled_by_anothers_join_and_a_lone_one_by_none(generator):
    eng = _engine(generator)
    first_chunk, second_queued = threading.Event(), threading.Event()
    step = eng._step_chunk

    def gated():
        if not first_chunk.is_set():
            first_chunk.set()  # the long request is live; hold the engine until the second one is queued
            second_queued.wait(10)
        step()

    try:
        eng.submit(PROMPTS[1], max_new_tokens=30)()  # compiles the join and the step
        eng._step_chunk = gated
        long = eng.submit(PROMPTS[1], max_new_tokens=30)
        assert first_chunk.wait(10)
        observe.histogram("pathway_generator_phase_seconds", phase="prefill").reset()
        short = eng.submit(PROMPTS[2], max_new_tokens=2)
        second_queued.set()
        a, b = long(), short()
        joined_ns = _sum_ns("pathway_generator_phase_seconds", phase="prefill")
        assert _count("pathway_generator_phase_seconds", phase="prefill") == 1
        # the long request decoded while the short one joined: it stood still for at least that round trip
        assert a.meta["phases_ms"]["stalled"] * 1e6 >= joined_ns - 1 > 0
        assert b.meta["phases_ms"]["stalled"] == 0
        # alone: no join but its own, and it steps for exactly its chunks' round trips
        eng._step_chunk = step
        observe.histogram("pathway_generator_phase_seconds", phase="step").reset()
        alone = eng.submit(PROMPTS[3], max_new_tokens=9)()
        chunks_ns = _sum_ns("pathway_generator_phase_seconds", phase="step")
    finally:
        second_queued.set()
        eng.stop()
    assert len(a.meta["token_ids"]) == 30 and len(alone.meta["token_ids"]) == 9
    assert alone.meta["phases_ms"]["stalled"] == 0
    assert chunks_ns > 0 and alone.meta["phases_ms"]["stepping"] * 1e6 == pytest.approx(chunks_ns, rel=1e-9)


def test_the_split_rides_the_trace_tree_and_the_rider_ends_its_trace(generator, monkeypatch):
    trace.set_sample(1.0)
    created, start = [], trace.start_trace

    def capture(*a, **k):
        ctx = start(*a, **k)
        if ctx is not None:
            created.append(ctx)
        return ctx

    monkeypatch.setattr(trace, "start_trace", capture)
    eng = _engine(generator)
    try:
        out = eng.submit(PROMPTS[0], max_new_tokens=6)()
    finally:
        eng.stop()
    (ctx,) = [c for c in created if c.name == "generate.request"]
    by_name = {s[2]: s for s in ctx.spans}
    # the rider's waits and the residency span reach the tree before it ends (the engine used to end it first)
    assert {"admission_wait", "decode", "ticket_wake", "decode.prefill", "decode.step"} <= set(by_name)
    attrs = by_name["decode"][6]
    assert attrs["tokens"] == 6
    for phase, ms in out.meta["phases_ms"].items():
        assert attrs[f"{phase}_ms"] == ms
    assert ctx.finished and ctx.dropped == 0


def test_with_the_recorder_off_no_phase_is_recorded_and_the_marks_stay(generator):
    eng = _engine(generator)
    try:
        eng.submit(PROMPTS[0], max_new_tokens=6)()
        observe.reset()
        before = _engine_seconds()
        stalled = eng._stalled_s
        observe.set_enabled(False)
        try:
            t_sent = time.perf_counter()
            # two slots: the third request joins when the second has left, beside the first's live lane
            tickets = [eng.submit(p, max_new_tokens=n) for p, n in zip(PROMPTS, (24, 4, 4))]
            outs = [t() for t in tickets]
            t_back = time.perf_counter()
        finally:
            observe.set_enabled(True)
    finally:
        eng.stop()
    for out in outs:
        assert not out.degraded and "phases_ms" not in out.meta
        # what callers and the benchmark read keeps its meaning: the first token's time on the host's clock
        assert t_sent < out.meta["t_first_token"] < t_back
    assert eng._stalled_s > stalled  # a join ran beside a live lane, on the engine's own clock reads
    assert all(_count("pathway_generator_request_seconds", phase=p) == 0 for p in PHASES)
    assert _count("pathway_generator_ttlt_seconds") == 0
    assert _engine_seconds() == before


# -- A: the engine thread's chain ---------------------------------------------


def test_engine_phases_grow_by_the_threads_wall_time_and_idle_alone_while_empty(generator):
    eng = _engine(generator)
    samples = []
    collect = eng._collect_joins

    def sampled():  # the top of every iteration of the engine's loop: every bracket before it has ended
        samples.append((time.perf_counter_ns(), _engine_seconds()))
        return collect()

    try:
        for p in PROMPTS:  # compiles every shape the stretch will use
            eng.submit(p, max_new_tokens=12)()
        eng._collect_joins = sampled

        def caller(k):
            for i in range(4):
                eng.submit(PROMPTS[(k + i) % len(PROMPTS)], max_new_tokens=12)()

        threads = [threading.Thread(target=caller, args=(k,)) for k in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        time.sleep(0.3)  # the pool is empty: the thread waits in ``gen.idle``
    finally:
        eng.stop()
    after = _engine_seconds()
    busy = [s for s in samples if any(s[1][p] > samples[0][1][p] for p in ENGINE_PHASES if p != "idle")]
    (t_a, at_a), (t_b, at_b) = busy[0], samples[-1]
    wall_s = (t_b - t_a) * 1e-9
    grown = sum(at_b.values()) - sum(at_a.values())
    assert wall_s > 0.05 and abs(grown - wall_s) <= 0.02 * wall_s, (grown, wall_s)
    for phase in ("join_host", "prefill_operands", "prefill_prefix", "prefill_call", "prefill_fetch",
                  "prefill_settle", "step_operands", "step_dispatch", "step_fetch", "step_replay"):
        assert at_b[phase] > at_a[phase], phase
    # from the last iteration's top (the pool empty) to the stop: nothing but ``idle`` moved
    assert after["idle"] - at_b["idle"] >= 0.3
    assert {p: after[p] for p in ENGINE_PHASES if p != "idle"} == {p: at_b[p] for p in ENGINE_PHASES if p != "idle"}


def test_round_trip_series_are_fed_from_the_spans_own_reads(generator, monkeypatch):
    eng = _engine(generator)
    try:
        eng.submit(PROMPTS[0], max_new_tokens=6)()
        observe.reset()
        spy = _Spy(monkeypatch)
        outs = [t() for t in [eng.submit(p, max_new_tokens=7) for p in PROMPTS[:3]]]
    finally:
        eng.stop()
    assert all(not o.degraded for o in outs)
    dispatch, fetch = spy.named("gen.prefill.dispatch"), spy.named("gen.prefill.fetch")
    operands, fetched = spy.named("gen.step.operands"), spy.named("gen.step.fetch")
    assert len(dispatch) == len(fetch) >= 2 and len(operands) == len(fetched) >= 2
    joins_ns = sum(f.t1_ns - d.t0_ns for d, f in zip(dispatch, fetch))
    assert _sum_ns("pathway_generator_phase_seconds", phase="prefill") == joins_ns
    assert sum(_sum_ns("pathway_generator_join_seconds", start=s) for s in ("warm", "cold")) == joins_ns
    assert _sum_ns("pathway_generator_phase_seconds", phase="step") == sum(
        f.t1_ns - o.t0_ns for o, f in zip(operands, fetched)
    )
    # the chain: every member starts where the one before it ended, so nothing of the thread's time is lost
    chain = [sp for name, sp in spy.spans if name not in ("gen.prefill.dispatch", "gen.prefix.admit")]
    assert all(b.t0_ns == a.t1_ns for a, b in zip(chain, chain[1:]))
    # a join's three children lie in their parent and sum to it: the first also holds the glue before it
    stage = lambda s: _sum_ns("pathway_generator_stage_seconds", stage=s)  # noqa: E731
    parts = stage("prefill_operands") + stage("prefill_prefix") + stage("prefill_call")
    glue = sum(d.t0_ns - o.t0_ns for d, o in zip(dispatch, spy.named("gen.prefill.operands")))
    assert glue >= 0 and 0 < parts - glue <= stage("prefill_dispatch")
    calls = spy.named("gen.prefill.call")
    assert all(d.t0_ns <= c.t0_ns and c.t1_ns <= d.t1_ns for d, c in zip(dispatch, calls))
    if generator.kv_cache is not None:
        assert _count("pathway_generator_stage_seconds", stage="prefix_admit") == 3


@pytest.fixture(scope="module")
def profiled(generator, tmp_path_factory):
    """A profiler session on the CPU around three requests through the
    engine: the host events named ``pw.gen.*`` with their thread, start and
    duration, and what the engine counted meanwhile."""
    from jax.profiler import ProfileData

    out = str(tmp_path_factory.mktemp("profile"))
    eng = _engine(generator)
    try:
        for p in PROMPTS[:3]:
            eng.submit(p, max_new_tokens=6)()  # compiles; nothing compiles under the profiler
        before = dict(eng.pool_stats)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level, opts.host_tracer_level = 0, 2
        jax.profiler.start_trace(out, profiler_options=opts)
        try:
            for t in [eng.submit(p, max_new_tokens=6) for p in PROMPTS[:3]]:
                t()
            time.sleep(0.2)  # idle, inside the session
            eng.stop()  # ends the idle bracket
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.stop()
    counted = {k: eng.pool_stats[k] - before[k] for k in ("joins", "chunks")}
    (xplane,) = glob.glob(f"{out}/plugins/profile/*/*.xplane.pb")
    events = [
        (ev.name, line.name, ev.start_ns, ev.duration_ns)
        for plane in ProfileData.from_file(xplane).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith("pw.gen.")
    ]
    return events, counted


def test_each_span_shows_once_a_join_or_a_chunk_and_children_lie_in_their_parent(profiled, generator):
    events, counted = profiled
    names = [e[0] for e in events]
    assert counted["joins"] >= 2 and counted["chunks"] >= 2
    for name in JOIN_SPANS:
        assert names.count("pw." + name) == counted["joins"], name
    for name in STEP_SPANS:
        assert names.count("pw." + name) == counted["chunks"], name
    assert names.count("pw.gen.join") == 3 and names.count("pw.gen.idle") == 1
    if generator.kv_cache is not None:
        assert names.count("pw.gen.prefix.admit") == 3
    assert len({e[1] for e in events}) == 1  # one thread: the engine's
    parents = sorted(e for e in events if e[0] == "pw.gen.prefill.dispatch")
    for _, _, start, dur in parents:
        inside = [e for e in events if e[0] in ("pw.gen.prefill.operands", "pw.gen.prefill.prefix", "pw.gen.prefill.call")
                  and start <= e[2] and e[2] + e[3] <= start + dur]
        assert sorted(e[0] for e in inside) == ["pw.gen.prefill.call", "pw.gen.prefill.operands", "pw.gen.prefill.prefix"]
        covered = sum(e[3] for e in inside)
        assert 0 < covered <= dur  # what is left: the lookup's lock, the brackets' own cost
    # siblings do not overlap: each gap of the device trace falls under one label
    chain = sorted(e for e in events if e[0] not in ("pw.gen.prefill.dispatch", "pw.gen.prefix.admit"))
    chain.sort(key=lambda e: e[2])
    assert all(a[2] + a[3] <= b[2] for a, b in zip(chain, chain[1:]))


def test_no_engine_span_encloses_a_lock_acquisition():
    """The analyzer's span-across-lock rule (analysis/lock_discipline.py) on
    the engine's source, read as a serve-path module: the compiled lookups
    (``gen._lock``), the slot pop (``_pool_lock``) and the admission queue
    (``_cond``) are all taken between brackets or around them, never inside."""
    with open(decode.__file__) as fh:
        src = fh.read()
    for name in ("gen.idle", "gen.join", *JOIN_SPANS, *STEP_SPANS, "gen.prefix.admit"):
        assert f'observe.span(\n                "{name}"' in src or f'observe.span("{name}"' in src, name
    found = analyze_source("# pathway: serve-path\n" + src, "pathway_tpu/serve/decode.py")
    assert [f.message for f in found if f.rule == "lock-discipline" and not f.suppressed] == []
    for gone in ("stage_cpu_seconds", "slots_live", "generator_admission_wait", "generator_ticket_wake"):
        assert gone not in src

"""Continuous cross-request batching: a coalescing serve scheduler with
double-buffered stage pipelining.

The fused pipeline already hits the 2-dispatch + 2-fetch budget (a
count of host syncs) — but only *per request*: concurrent callers
serialize on the pipeline, so the device idles while requests queue.  Cross-request micro-batching is the standard fix in
neural-ranking serving ("Accelerating Retrieval-Augmented Generation",
arxiv 2412.15246; Zamani et al., arxiv 1707.08275: retrieval+rerank
throughput is dominated by batch occupancy, not per-query FLOPs).

One scheduler thread owns admission; the **future-handoff** contract
splits the work so no thread ever blocks while holding the queue lock:

    caller ──submit()──► admission queue ───hold───► scheduler thread
                                                  │  sorted-unique pack,
                                                  │  ONE stage-1 dispatch
                                                  │  (batch N), then
                                                  │  advance(batch N-1)
    caller ◄──ticket()─── per-request demux ◄─────┘
              (the WAITER performs the host fetch)

- **Launch when the pipeline has room, hold only behind a full one**: the
  scheduler keeps the last two batches it launched (the double buffer
  below).  While fewer than two of them are still unfetched it pops and
  launches whatever is queued AT ONCE — a timer in front of an idle device
  and a scheduler thread with room buys a denser batch nobody needs.  With
  both places taken it HOLDS the queue, coalescing arrivals, until the
  first of: a rider's fetch frees a place (``_Batch.result()`` notifies
  the scheduler, once a batch), the **coalescing window** runs out
  (``PATHWAY_SERVE_COALESCE_US``, default 2000, anchored at the oldest
  queued request: the cap on a hold, not a wait), half of the budget any
  queued request was admitted with does, or ``max_batch`` unique items
  are queued.  Under load the queue forms behind the pipeline without any
  timer, and the pipeline's own acknowledgements are the launch clock.
  ``stats`` counts launches by what let them go (``launched_at_once``,
  ``held_pipeline``, ``held_window``, ``held_full``; they sum to
  ``batches``).  A request admitted with almost no slack serves SOLO on
  its own thread instead of queueing at all.  An explicit ``window_us=``
  pins the window and keeps the older meaning: every batch is held for it
  from its oldest request, whatever is in flight.
- **Double-buffered pipelining**: after dispatching batch N's stage 1
  the scheduler ``advance()``s batch N-1 (completing its stage-1 fetch
  and dispatching its stage-2 rerank), so stage 2 of N-1 overlaps
  stage 1 of N on the device — the 2+2 dispatch budget is paid once
  *per batch* and amortized across every coalesced request.
- **Dedup**: hash-identical texts inside a window encode once; the
  packed results scatter to every waiter.  Batch composition is the
  *sorted* unique text list, so identical windows produce bit-identical
  device batches (and therefore bit-identical results) regardless of
  thread arrival order.
- **Tier-0 result cache** (``pathway_tpu/cache``): cross-WINDOW repeats
  — the hot-head traffic in-batch dedup cannot see — resolve before
  admission under ``(text, index generation, k)``: zero dispatches, no
  queueing, generation-bump invalidation (see ``ServeScheduler``).
- **Degradation stays per-request**: a stage-1 failure inside a
  coalesced batch flags ``retrieval_failed`` on (and counts) each rider
  of that batch, and the next batch starts clean — one bad window never
  poisons the scheduler.

The scheduler fronts anything with the repo's submit/complete serving
contract — ``submit(texts, k, deadline=...) -> handle`` where the handle
is a zero-arg completion, optionally with a non-blocking-ish
``advance()`` (``RetrieveRerankPipeline``, ``FusedEncodeSearch``).
``SharedBatcher`` reuses the same engine for flat scoring calls
(``submit(items, deadline=...) -> completion -> scores aligned with
items``, e.g. ``CrossEncoderModel.submit``) so the QA layer's rerank
stage coalesces across dataflow rows too.

Nothing in this module touches jax; the admission lock is held only for
list/int work (lock-discipline clean by construction, and the analyzer's
future-handoff rule keeps it that way).
"""

from __future__ import annotations

# pathway: serve-path  (hidden-sync lint applies: no implicit host round trips)

import inspect
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import config, observe
from ..cache import normalize_generation, query_key, result_cache_from_env
from ..observe import slo as slo_mod
from ..observe import trace
from ..robust import (
    Deadline,
    LOAD_SHED,
    RETRIEVAL_FAILED,
    ServeResult,
    log_once,
    record_degraded,
)

__all__ = [
    "ServeScheduler",
    "SharedBatcher",
    "coalesce_window_s",
    "max_batch_queries",
]


def coalesce_window_s() -> float:
    """Coalescing window from ``serve.coalesce_us`` (default 2000 µs,
    tuner-adjustable): the longest a batch is held behind a full launch
    pipeline, from its oldest request; 0 disables holding (batches still
    form from whatever is queued when the scheduler thread comes around)."""
    return config.get("serve.coalesce_us") * 1e-6


def max_batch_queries() -> int:
    """Per-batch cap on UNIQUE queries from ``serve.max_batch``
    (default 64 — the second-largest stage-1 batch bucket, so one
    coalesced dispatch never jumps to a cold compile shape under a
    traffic spike).  The cap bounds the DEVICE batch, not admissions:
    duplicate queries ride a batch for free, so hot traffic packs many
    more requests than ``max_batch`` into one bucket-aligned dispatch."""
    return config.get("serve.max_batch")


# time-in-queue: enqueue → handoff of the shared batch to the waiters
# (shared series across scheduler instances, like the serve stage
# histograms; per-instance split rides the provider counters below).  It
# CONTAINS the batch's launch, which runs on the scheduler thread: per
# request, queue_wait == admission_wait + launch, by the same clock reads.
_H_QUEUE_WAIT = observe.histogram("pathway_serve_queue_wait_seconds")
# enqueue → the launch that took the request: backlog + coalescing window
_H_ADMISSION_WAIT = observe.histogram("pathway_serve_admission_wait_seconds")
# handoff (event set) → the rider returns from its wait: thread wake-up
_H_TICKET_WAKE = observe.histogram("pathway_serve_ticket_wake_seconds")
_LAUNCH = observe.serve_stage("launch")
# the scheduler thread's wall time in seconds, by phase: nothing queued,
# first request present → batch popped (a hold behind a full launch
# pipeline; near zero where nothing is held), packing + launching a batch,
# advancing the previous batch's pipeline
_C_PHASE = {
    p: observe.counter("pathway_serve_dispatcher_seconds_total", phase=p)
    for p in ("idle", "window", "launch", "advance")
}

# requests shed at admission, by priority class — pre-created for the
# known classes so the family renders at 0 before the first shed
_C_SHED = {
    p: observe.counter("pathway_serve_shed_total", priority=p)
    for p in ("high", "normal", "low")
}

# the launch pipeline's depth: the batch whose stage 1 is on the device and
# the one before it, which ``_run`` advances behind it
_PIPELINE_DEPTH = 2
# why a shared batch went (``stats`` keys; they sum to ``batches``): a place
# in the pipeline was free / held behind a full pipeline until a fetch freed
# one / until the window or half a rider's deadline ran out / ``max_batch``
# unique items were queued
_RELEASES = ("launched_at_once", "held_pipeline", "held_window", "held_full")


def _shed_classes() -> frozenset:
    """Priority classes eligible for shedding (``serve.shed_priorities``,
    CSV, default "low")."""
    raw = str(config.get("serve.shed_priorities"))
    return frozenset(p.strip().lower() for p in raw.split(",") if p.strip())


class _Request:
    """One admitted serve/score call: resolved by the scheduler with the
    shared batch + this request's slot mapping into it."""

    __slots__ = (
        "items", "k", "deadline", "t_enqueue_ns", "hold_until_ns", "event",
        "batch", "slots", "cache_store", "trace", "t_handoff_ns",
    )

    def __init__(self, items: Sequence[Any], k: Optional[int], deadline):
        self.items = list(items)
        self.k = k
        self.deadline = deadline
        self.t_enqueue_ns = time.perf_counter_ns()
        # the latest a hold may keep this request queued: half of what its
        # budget was at admission (None: no deadline)
        self.hold_until_ns = (
            None if deadline is None
            else self.t_enqueue_ns + int(0.5e9 * deadline.remaining_s())
        )
        # when the scheduler handed this request its batch (0: resolved
        # without a handoff, or its wake-up is already recorded)
        self.t_handoff_ns = 0
        self.event = threading.Event()
        self.batch: Optional["_Batch"] = None
        self.slots: List[int] = []
        # tier-0 capture flag: set at admission when a result cache is
        # armed (cache-hit tickets never re-store their own rows)
        self.cache_store = False
        # per-request TraceContext (observe/trace.py), created at
        # submit() admission and finished at demux — None when tracing
        # is off or the request was head-sampled out
        self.trace = None


class _Batch:
    """The future-handoff point: the scheduler thread created the handle
    (dispatch); whichever WAITER arrives first performs the host fetch.
    ``result()`` is idempotent and thread-safe — the per-batch lock only
    ever guards the once-only completion, never a queue."""

    __slots__ = ("_handle", "_n_items", "_n_requests", "_degrade_empty",
                 "_lock", "_done", "_result", "_error", "_trace", "_fetched",
                 "t_launch_ns", "link")

    def __init__(self, handle, n_items: int, n_requests: int,
                 degrade_empty: bool, trace_ctx=None, fetched=None):
        self._handle = handle
        self._n_items = n_items
        self._n_requests = n_requests
        self._degrade_empty = degrade_empty
        self._lock = threading.Lock()
        self._done = False
        self._result: Any = None
        self._error: Optional[BaseException] = None
        # the BATCH trace (observe/trace.py): the shared work — stage-1
        # dispatch, shard fan-out, cascade stages — records into it;
        # advance()/result() re-activate it because they run on other
        # threads (scheduler thread / whichever waiter fetches first)
        self._trace = trace_ctx
        # the launching scheduler's condition (None: the batch holds no place
        # in a launch pipeline): notified once, by the rider whose fetch
        # completes the batch, so a launch held behind it can go
        self._fetched = fetched
        # the launch bracket's start (0: none) and the riders' link-span
        # attrs: each rider records its own waits from them after it wakes
        self.t_launch_ns = 0
        self.link: Dict[str, Any] = {}

    def advance(self) -> None:
        """Pipelining hook: complete stage 1 and dispatch stage 2 of this
        batch without blocking on the final fetch (no-op for handles
        without ``advance``).  Failures are deferred to ``result()`` —
        the ladder lands in one place."""
        adv = getattr(self._handle, "advance", None)
        if adv is None:
            return
        try:
            with trace.use(self._trace):
                adv()
        except Exception:
            pass  # surfaces (once) at result() via the same handle

    def result(self) -> Any:
        fetched = None
        with self._lock:
            if not self._done:
                fetched = self._fetched
                try:
                    with trace.use(self._trace):
                        self._result = self._handle()
                except Exception as exc:
                    if self._degrade_empty:
                        # a target without an internal degradation ladder
                        # (e.g. bare FusedEncodeSearch) raised past its
                        # retry budget: every rider of THIS batch is
                        # affected — flag and count each, serve empty
                        log_once(
                            f"scheduler.batch:{type(exc).__name__}",
                            "coalesced serve batch failed (%r); serving "
                            "empty degraded results to its riders",
                            exc,
                        )
                        record_degraded(RETRIEVAL_FAILED, self._n_requests)
                        self._result = ServeResult(
                            [[] for _ in range(self._n_items)],
                            degraded=(RETRIEVAL_FAILED,),
                        )
                    else:
                        self._error = exc
                self._done = True
                if self._trace is not None:
                    # finish INSIDE the batch lock: a rider's demux (and
                    # its link promotion) must never observe the batch
                    # trace unfinished once result() has returned
                    flags = tuple(getattr(self._result, "degraded", ()) or ())
                    if self._error is not None:
                        flags = flags + ("error",)
                    trace.finish(self._trace, statuses=flags)
        if fetched is not None:
            # off the batch lock: the riders behind it go on to their demux
            with fetched:
                fetched.notify_all()
        if self._error is not None:
            raise self._error
        return self._result


def _record_waits(req: "_Request", t_handoff: int, t_woke: int) -> None:
    """A rider's waits, recorded by the rider after its fetch (the scheduler
    thread, the bottleneck, only stamps the handoff).  All from the launch
    bracket's clock reads, so queue_wait == admission_wait + launch.  One
    tree node, the LINK span: its duration is the queue wait, its attrs say
    which batch the rider rode, how long it waited for a launch and to wake
    (every node costs each request: measured, PERF.md ISSUE 24); /traces
    inlines the linked batch tree under it."""
    batch, rt, t_enqueue = req.batch, req.trace, req.t_enqueue_ns
    admission_ns, wake_ns = batch.t_launch_ns - t_enqueue, t_woke - t_handoff
    _H_ADMISSION_WAIT.observe_ns(admission_ns)
    _H_TICKET_WAKE.observe_ns(wake_ns)
    linked = batch.link.get("linked_trace")
    if rt is not None and linked is not None:
        rt.add_link(linked)
    observe.interval(
        "batch", t_enqueue, t_handoff, hist=_H_QUEUE_WAIT, tree=rt,
        admission_wait_ms=admission_ns * 1e-6, wake_ms=wake_ns * 1e-6,
        **batch.link,
    )


class _Ticket:
    """Per-request future.  Calling it (or ``result(timeout)``) blocks
    until the scheduler hands this request its shared batch, then the
    CALLER performs the batch fetch (idempotent across riders) and
    demuxes its own rows — dispatch on the scheduler thread, fetch on
    the waiter."""

    __slots__ = ("_owner", "_request")

    def __init__(self, owner: "_CoalescerBase", request: _Request):
        self._owner = owner
        self._request = request

    def result(self, timeout: Optional[float] = None):
        req = self._request
        if not req.event.wait(timeout):
            raise TimeoutError("serve ticket not dispatched within timeout")
        t_woke = time.perf_counter_ns()
        result = req.batch.result()  # the fetch first: it is what riders wait for
        t_handoff, req.t_handoff_ns = req.t_handoff_ns, 0
        if t_handoff:
            _record_waits(req, t_handoff, t_woke)
        return self._owner._demux(req, result)

    def __call__(self):
        return self.result()


class _CoalescerBase:
    """The coalescing engine: admission queue + window + one scheduler
    thread + double-buffered dispatch.  Subclasses define how a batch
    launches (``_launch``) and how one request's share of the shared
    result is extracted (``_demux``)."""

    _degrade_empty = False  # subclass: empty-degrade vs re-raise on failure
    _metric_prefix = "pathway_serve_queue"

    def __init__(
        self,
        name: Optional[str] = None,
        window_us: Optional[float] = None,
        max_batch: Optional[int] = None,
        autostart: bool = True,
    ):
        self.name = name or f"serve-{observe.next_id()}"
        # window_us=None -> LIVE registry read per hold: the online tuner
        # (serve/tuner.py) adjusts ``serve.coalesce_us`` while the batcher
        # runs, and the window only bounds a hold behind a full launch
        # pipeline; an explicit window_us pins it AND holds every batch for
        # it from its oldest request, whatever is in flight
        self._window_pinned = window_us is not None
        self._window_s = (
            coalesce_window_s() if window_us is None else max(0.0, window_us) * 1e-6
        )
        self._max_batch = max_batch or max_batch_queries()
        self._qlock = threading.Lock()
        self._cond = threading.Condition(self._qlock)
        self._queue: Deque[_Request] = deque()
        self._queued_items = 0
        self._running = False
        self._thread: Optional[threading.Thread] = None
        # the last batches this scheduler launched, newest last (scheduler
        # thread only): those no rider has fetched yet take the places
        self._pipeline: Deque[_Batch] = deque(maxlen=_PIPELINE_DEPTH)
        # plain-int stats; the flight recorder samples them at scrape
        # time through the provider registry (zero hot-path cost)
        self.stats: Dict[str, int] = {
            "requests": 0,       # admitted through the queue
            "solo": 0,           # deadline-preempted (or stopped) direct serves
            "batches": 0,        # shared dispatches; by why they went:
            **dict.fromkeys(_RELEASES, 0),
            "items": 0,          # queries/items admitted (pre-dedup)
            "items_dispatched": 0,  # unique items actually dispatched
            "dedup_hits": 0,     # duplicate items served from a shared slot
        }
        observe.register_provider(self)
        if autostart:
            self.start()

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        with self._cond:
            if self._running:
                return
            self._running = True
        self._thread = threading.Thread(
            target=self._run, name=f"{self.name}-scheduler", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        """Stop the scheduler thread, draining the queue first — every
        admitted ticket resolves.  Requests submitted after stop serve
        solo on their caller's thread."""
        with self._cond:
            self._running = False
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        # a submit() that raced the shutdown may have enqueued after the
        # drain loop exited: resolve the stragglers here
        while True:
            reqs = self._pop_batch()
            if not reqs:
                break
            self._dispatch_batch(reqs)

    close = stop

    def __enter__(self) -> "_CoalescerBase":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- admission ----------------------------------------------------------
    def _admit(
        self, items: Sequence[Any], k: Optional[int], deadline, trace_ctx=None
    ) -> _Ticket:
        req = _Request(items, k, deadline)
        # attach the trace BEFORE the queue sees the request: the
        # scheduler thread may pop and dispatch it immediately, and the
        # link span is recorded from whatever ``r.trace`` holds then
        req.trace = trace_ctx
        if trace_ctx is not None:
            trace_ctx.add_span(
                "admission", trace_ctx.t0_ns, req.t_enqueue_ns,
                items=len(req.items),
            )
        if not req.items:
            req.slots = []
            req.batch = _Batch(lambda: ServeResult(), 0, 1, self._degrade_empty)
            req.event.set()
            return _Ticket(self, req)
        # deadline-preemption rung: a request whose remaining budget is
        # within a few windows of the coalescing wait serves SOLO — the
        # window must never be what pushes a tight serve over budget
        solo = deadline is not None and (
            deadline.remaining_s() <= 4.0 * self._window_s
        )
        with self._cond:
            if solo or not self._running:
                self.stats["solo"] += 1
                self.stats["items"] += len(req.items)
            else:
                self.stats["requests"] += 1
                self.stats["items"] += len(req.items)
                self._queue.append(req)
                self._queued_items += len(req.items)
                self._cond.notify_all()
                return _Ticket(self, req)
        self._dispatch_batch([req], solo=True)
        return _Ticket(self, req)

    # -- scheduler thread ---------------------------------------------------
    def _run(self) -> None:
        while True:
            reqs: Optional[List[_Request]] = None
            try:
                collected = self._collect()
                if collected is None:
                    return
                reqs, release = collected
                if reqs:
                    batch = self._dispatch_batch(reqs, release=release)
                    if self._pipeline:
                        # double buffering: stage-1 of the batch just
                        # dispatched is on the device queue; completing the
                        # PREVIOUS batch's stage 1 and dispatching its
                        # stage 2 now overlaps the two on device (a no-op
                        # where a waiter got there first, a wait where one
                        # is at it: an interval, not a profiler event)
                        t_advance = time.perf_counter_ns()
                        self._pipeline[-1].advance()
                        observe.interval(
                            "sched.advance", t_advance, time.perf_counter_ns(),
                            counter=_C_PHASE["advance"], tree=None,
                        )
                    self._pipeline.append(batch)
            except Exception as exc:
                # the scheduler thread must OUTLIVE any one bad batch:
                # a dead thread would hang every queued and future ticket
                # forever.  Resolve whatever was popped with the error
                # (degrade-or-reraise per subclass policy) and keep going.
                log_once(
                    f"scheduler.run:{type(exc).__name__}",
                    "serve scheduler iteration failed (%r); failing the "
                    "affected batch and continuing",
                    exc,
                )
                for r in reqs or []:
                    if not r.event.is_set():
                        self._resolve_with_error(r, exc)

    def _resolve_with_error(self, req: _Request, exc: BaseException) -> None:
        def handle(_exc: BaseException = exc):
            raise _exc

        if len(req.slots) != len(req.items):
            req.slots = [-1] * len(req.items)
        req.batch = _Batch(handle, len(req.items), 1, self._degrade_empty)
        req.event.set()
        if req.trace is not None:
            # the ticket will raise (or demux a degraded empty); either
            # way this trace's outcome is known — keep it
            trace.finish(req.trace, statuses=("error",))

    def _collect(self) -> Optional[Tuple[List[_Request], str]]:
        """Block until work arrives, then pop one batch and say why it went
        (one of ``_RELEASES``).  With a place free in the launch pipeline
        (fewer than ``_PIPELINE_DEPTH`` of the last launched batches still
        unfetched) that is at once, with whatever is queued.  Behind a full
        pipeline the batch is HELD, coalescing arrivals, until a rider's
        fetch frees a place (it notifies ``_cond``), the coalescing window
        anchored at the oldest request runs out, half of the budget a
        queued request was admitted with does, or ``max_batch`` unique
        items are queued.  A window pinned by the constructor holds from the
        oldest request whatever is in flight.  Returns None when stopped
        and drained."""
        with self._cond:
            if self._running and not self._queue:
                with observe.span("sched.idle", counter=_C_PHASE["idle"]):
                    while self._running and not self._queue:
                        self._cond.wait(0.1)
            if not self._queue:
                return None  # stopped and drained
            with observe.span("sched.window", counter=_C_PHASE["window"]):
                anchor_ns = self._queue[0].t_enqueue_ns
                release, held = "launched_at_once", False
                while self._running:
                    # the cap bounds UNIQUE items (the device batch shape), so
                    # a hold stays open for hot duplicate-heavy traffic even
                    # when the raw queued count is past it — those riders
                    # dedup in (unique <= raw: most passes count nothing)
                    if (
                        self._queued_items >= self._max_batch
                        and self._queued_unique_locked() >= self._max_batch
                    ):
                        release = "held_full"
                        break
                    if not self._window_pinned:
                        self._window_s = coalesce_window_s()
                        if self._unfetched() < _PIPELINE_DEPTH:
                            if held:
                                release = "held_pipeline"
                            break
                    end_ns = anchor_ns + int(self._window_s * 1e9)
                    for r in self._queue:
                        if r.hold_until_ns is not None:
                            # the hold never eats more than half of the
                            # budget a queued request was admitted with
                            end_ns = min(end_ns, r.hold_until_ns)
                    end_s = (end_ns - time.perf_counter_ns()) * 1e-9
                    if end_s <= 0:
                        release = "held_window"
                        break
                    held = True
                    self._cond.wait(end_s)
                return self._pop_batch_locked(), release

    def _unfetched(self) -> int:
        """Places taken in the launch pipeline: the last launched batches
        no rider has fetched yet (scheduler thread only)."""
        return sum(1 for b in self._pipeline if not b._done)

    def _pop_batch(self) -> List[_Request]:
        with self._cond:
            return self._pop_batch_locked()

    def _queued_unique_locked(self) -> int:
        try:
            return len({it for r in self._queue for it in r.items})
        except TypeError:
            # unhashable items cannot dedup: fall back to the raw count
            # (the bad request itself fails downstream in _dispatch_batch)
            return self._queued_items

    def _pop_batch_locked(self) -> List[_Request]:
        # the cap bounds UNIQUE items (the device batch shape): duplicate
        # queries dedup into an existing slot, so hot requests keep
        # riding a batch that is already full of their text
        take: List[_Request] = []
        seen: set = set()
        while self._queue:
            r = self._queue[0]
            try:
                fresh = sum(1 for it in r.items if it not in seen)
            except TypeError:
                fresh = len(r.items)  # unhashable: counts as all-fresh
            if take and len(seen) + fresh > self._max_batch:
                break
            take.append(self._queue.popleft())
            self._queued_items -= len(r.items)
            try:
                seen.update(r.items)
            except TypeError:
                pass  # the request still dispatches; dedup just skips it
        return take

    # -- dispatch -----------------------------------------------------------
    def _dispatch_batch(
        self, reqs: List[_Request], solo: bool = False,
        release: str = "launched_at_once",
    ) -> _Batch:
        """Pack one shared batch (sorted-unique items — deterministic
        composition regardless of arrival order), launch it, and hand
        the batch to every rider.  Every ticket resolves no matter what
        the launch does.  ``solo`` dispatches (deadline preemption,
        stopped scheduler) skip the coalescing counters — ``batches``
        counts shared dispatches only, and ``release`` (what let this one
        go, from ``_collect``) is counted beside it."""
        items: List[Any] = []
        total = sum(len(r.items) for r in reqs)
        error: Optional[BaseException] = None
        # one BATCH trace for the shared work, linked from every traced
        # rider: sampling already happened at the riders' admission, so
        # the batch trace is created iff a traced rider is aboard
        bctx = None
        if any(r.trace is not None for r in reqs):
            bctx = trace.start_trace(
                "serve.batch",
                deadline=self._batch_deadline(reqs),
                kind="batch",
                sample=False,
            )
            if bctx is not None:
                bctx.annotate(
                    scheduler=self.name, riders=len(reqs), solo=bool(solo)
                )
        # a solo dispatch runs on its caller's thread: it stays out of the
        # scheduler thread's phase counter
        with trace.use(bctx), observe.span(
            "sched.launch", counter=None if solo else _C_PHASE["launch"],
            riders=len(reqs), solo=bool(solo), **_LAUNCH,
        ) as launch:
            try:
                index: Dict[Any, int] = {}
                for r in reqs:
                    for it in r.items:
                        if it not in index:
                            index[it] = -1
                            items.append(it)
                items.sort()
                for i, it in enumerate(items):
                    index[it] = i
                for r in reqs:
                    r.slots = [index[it] for it in r.items]
                if bctx is not None:
                    bctx.annotate(items=len(items), deduped=total - len(items))
                handle = self._launch(items, reqs)
            except Exception as exc:
                # packing or launch failed: every ticket still resolves —
                # the error lands in _Batch.result() (degrade or re-raise)
                error = exc
                for r in reqs:
                    if len(r.slots) != len(r.items):
                        r.slots = [-1] * len(r.items)

                def handle(_exc: BaseException = error):
                    raise _exc
        batch = _Batch(
            handle, len(items), len(reqs), self._degrade_empty, trace_ctx=bctx,
            fetched=None if solo else self._cond,
        )
        with self._qlock:
            if not solo:
                self.stats["batches"] += 1
                self.stats[release] += 1
            self.stats["items_dispatched"] += len(items)
            self.stats["dedup_hits"] += total - len(items)
        batch.t_launch_ns, t_now = launch.t0_ns, launch.t1_ns
        batch.link = {"riders": len(reqs), "solo": bool(solo)}
        if bctx is not None:
            batch.link.update(linked_trace=bctx.trace_id, batch_items=len(items))
        for r in reqs:
            r.batch = batch
            r.t_handoff_ns = t_now
            r.event.set()
        return batch

    @staticmethod
    def _batch_deadline(reqs: List[_Request]):
        """The shared dispatch runs under the MOST generous rider's
        deadline (None if any rider has none): a coalesced request
        accepted the window's cost at admission, and killing the whole
        batch on the tightest budget would fail its patient riders."""
        deadline = None
        for r in reqs:
            if r.deadline is None:
                return None
            if deadline is None or r.deadline.remaining_s() > deadline.remaining_s():
                deadline = r.deadline
        return deadline

    # -- subclass hooks -----------------------------------------------------
    def _launch(self, items: List[Any], reqs: List[_Request]):
        raise NotImplementedError

    def _demux(self, req: _Request, batch_result):
        raise NotImplementedError

    # -- flight-recorder provider ------------------------------------------
    def observe_metrics(self):
        labels = {"scheduler": self.name}
        yield ("gauge", f"{self._metric_prefix}_depth", labels, len(self._queue))
        for mode in ("requests", "solo"):
            yield (
                "counter",
                f"{self._metric_prefix}_requests_total",
                {**labels, "mode": "coalesced" if mode == "requests" else mode},
                self.stats[mode],
            )
        yield ("counter", f"{self._metric_prefix}_batches_total", labels, self.stats["batches"])
        for release in _RELEASES:
            yield (
                "counter",
                f"{self._metric_prefix}_launches_total",
                {**labels, "release": release},
                self.stats[release],
            )
        for kind, key in (
            ("admitted", "items"),
            ("dispatched", "items_dispatched"),
            ("deduped", "dedup_hits"),
        ):
            yield (
                "counter",
                f"{self._metric_prefix}_queries_total",
                {**labels, "kind": kind},
                self.stats[key],
            )


class _ReplicaHandle:
    """Completion wrapper that releases its replica's in-flight slot
    exactly once, whether the batch completes, fails, or is advanced
    first — the placement layer's load signal must drain even when the
    degradation ladder swallows the failure."""

    __slots__ = ("_handle", "_release", "_released", "_rlock")

    def __init__(self, handle, release):
        self._handle = handle
        self._release = release
        self._released = False
        self._rlock = threading.Lock()

    def advance(self) -> None:
        adv = getattr(self._handle, "advance", None)
        if adv is not None:
            adv()

    def _release_once(self) -> None:
        with self._rlock:
            if self._released:
                return
            self._released = True
        self._release()

    def __call__(self):
        try:
            return self._handle()
        finally:
            self._release_once()


class ServeScheduler(_CoalescerBase):
    """Coalescing front-end for the retrieve(→rerank) serve path.

    ``target`` is a ``RetrieveRerankPipeline`` or ``FusedEncodeSearch``
    (anything with ``submit(texts, k, deadline=...) -> completion``).
    Concurrent ``serve()``/``submit()`` calls coalesce into ONE shared
    stage-1 batch at the existing bucket shapes; per-request ``k`` is
    honored by truncating the shared top-``max(k)`` rows, and per-request
    results carry the batch's degradation flags (a stage-1 failure
    degrades exactly the riders of that batch).

    **Generation-keyed dedup**: the in-window dedup key is
    ``(text, index_generation)``, not the text alone — an absorb/retrain
    landing inside an open coalescing window bumps the target index's
    generation, so a later duplicate admits into its OWN slot instead of
    sharing one dispatched against the pre-mutation index state.

    **Replica placement**: ``replicas`` adds data-parallel serve targets
    (each a full pipeline over its own device group) behind this ONE
    shared admission queue.  Each coalesced batch is assigned to the
    least-loaded replica (in-flight batches, ties rotated), so a slow
    or recovering replica sheds load automatically; per-replica
    queue-depth gauges and placement counters export on the scrape
    surface (``pathway_serve_replica_*``).

    **Tier-0 result cache** (``pathway_tpu/cache``): before admission,
    the request's rows are looked up under ``(text, index generation,
    k)`` — a full hit resolves the ticket immediately: no coalescing
    window, ZERO device dispatches, bit-identical to the serve that
    populated the entry.  Rows are captured at demux (on the waiter's
    thread, off every scheduler lock) only for CLEAN results whose
    dispatch-time generation matches the admission generation, so an
    absorb/retrain/remove — which bumps the index generation — makes
    every stale entry structurally unreachable.  ``result_cache`` is an
    explicit ``ResultCache``, ``"auto"`` (the default: built from the
    ``PATHWAY_CACHE[_RESULT]*`` env knobs), or ``None`` to disable.
    """

    _degrade_empty = True

    def __init__(
        self,
        target,
        k: Optional[int] = None,
        name: Optional[str] = None,
        window_us: Optional[float] = None,
        max_batch: Optional[int] = None,
        autostart: bool = True,
        replicas: Optional[Sequence[Any]] = None,
        result_cache: Any = "auto",
    ):
        self.target = target
        self.k = k or getattr(target, "k", 10)
        self._result_cache = (
            result_cache_from_env() if result_cache == "auto" else result_cache
        )
        # data-parallel replica set: the placement layer spreads batches
        # over [target, *replicas]; a single-target scheduler is the
        # degenerate one-replica case with zero extra cost
        self._replicas: List[Any] = [target] + list(replicas or ())
        self._inflight: List[int] = [0] * len(self._replicas)
        self._placed: List[int] = [0] * len(self._replicas)
        gen_fn = getattr(target, "index_generation", None)
        self._generation = gen_fn if callable(gen_fn) else None
        try:
            params = inspect.signature(target.submit).parameters
        except (TypeError, ValueError):
            params = {}
        self._submit_n_requests = "n_requests" in params
        self._submit_deadline = "deadline" in params
        super().__init__(
            name=name, window_us=window_us, max_batch=max_batch, autostart=autostart
        )
        self.stats.setdefault("cache_hits", 0)

    # -- public serve surface ----------------------------------------------
    def submit(
        self,
        texts: Sequence[str],
        k: Optional[int] = None,
        deadline: Optional[Deadline] = None,
        priority: Optional[str] = None,
    ) -> _Ticket:
        """Admit one serve request; returns a ticket (zero-arg callable /
        ``result(timeout)``) resolving to this request's ``ServeResult``.
        ``deadline`` defaults to the target's own policy
        (``deadline_ms``/``PATHWAY_SERVE_DEADLINE_MS``); a deadline too
        tight for the coalescing window serves solo immediately.
        ``priority`` (high/normal/low; default ``serve.default_priority``)
        is the load-shedding class — shed-class requests get an empty
        ``load_shed``-flagged result while a shed-enabled SLO burns."""
        if deadline is None:
            default = getattr(self.target, "_default_deadline", Deadline.from_env)
            deadline = default()
        if priority is None:
            priority = config.get("serve.default_priority")
        priority = str(priority).lower()
        # SLO-burn load shedding (observe/slo.py): while a shed-enabled
        # objective (serve latency/availability, ingest freshness) burns
        # past threshold, shed-class requests are turned away AT
        # admission — an immediately-resolved ticket carrying an empty
        # ``load_shed``-flagged ServeResult (counted, flagged, never an
        # exception), zero dispatches, no window wait.  The probe is a
        # throttled cached read and may never fail or slow an admission.
        # ``PATHWAY_SERVE_SHED=0`` restores the round-15 advisory-only
        # behavior (log + count, admit normally).
        if slo_mod.should_shed():
            if config.get("serve.shed") and priority in _shed_classes():
                c = _C_SHED.get(priority)
                if c is None:
                    c = observe.counter(
                        "pathway_serve_shed_total", priority=priority
                    )
                c.inc()
                record_degraded(LOAD_SHED, 1)
                with self._qlock:
                    self.stats["shed"] = self.stats.get("shed", 0) + 1
                ctx = trace.start_trace("serve.request", deadline=deadline)
                if ctx is not None:
                    ctx.annotate(priority=priority, shed=True)
                req = _Request(list(texts), k or self.k, deadline)
                req.trace = ctx
                req.slots = list(range(len(texts)))
                shed = ServeResult(
                    [[] for _ in texts],
                    degraded=(LOAD_SHED,),
                    meta={"priority": priority, "shed": True},
                )
                req.batch = _Batch(
                    lambda: shed, len(texts), 1, self._degrade_empty
                )
                req.event.set()
                return _Ticket(self, req)
            log_once(
                "serve.slo_shed",
                "SLO burn-rate alert firing: should_shed() advises "
                "load shedding (advisory only — PATHWAY_SERVE_SHED off "
                "or priority not shed-class; see GET /slo)",
            )
            slo_mod.record_shed_advised()
        # per-request trace root (observe/trace.py): admission → cache →
        # batch link → demux all hang off this context; None (one flag
        # check, no allocation) when tracing is off or sampled out
        ctx = trace.start_trace("serve.request", deadline=deadline)
        gen = 0
        if self._generation is not None:
            try:
                gen = normalize_generation(self._generation())
            except Exception:
                gen = 0
        # dedup item = (text, generation-at-admission): only duplicates
        # that observed the SAME index state may share a dispatched slot.
        # The SAME helper derives the result-cache key (cache/keys.py),
        # so the two spellings can never drift.  Against a PARTITIONED
        # fabric ``gen`` is the fleet generation VECTOR — an absorb on
        # ANY partition changes it, so a result cached via host A can
        # never be served after host B's absorb.
        items = [query_key(t, gen) for t in texts]
        k_eff = k or self.k
        cache = self._result_cache
        if cache is not None and items:
            # tier-0 lookup BEFORE admission (and before any scheduler
            # lock): a full hit is a zero-dispatch serve that skips the
            # coalescing window entirely; any miss (or cache failure)
            # falls through to the shared batch unchanged
            t_lookup = time.perf_counter_ns()
            with trace.use(ctx):  # tier events annotate this trace
                rows = cache.get_rows(items, k_eff, deadline=deadline)
            observe.interval(
                "cache.result", t_lookup, time.perf_counter_ns(), tree=ctx,
                status="hit" if rows is not None else "miss", items=len(items),
            )
            if rows is not None:
                with self._qlock:
                    self.stats["cache_hits"] = (
                        self.stats.get("cache_hits", 0) + 1
                    )
                    self.stats["items"] += len(items)
                req = _Request(items, k_eff, deadline)
                req.trace = ctx
                if ctx is not None:
                    ctx.annotate(cache="hit")
                req.slots = list(range(len(items)))
                hit = ServeResult(rows)
                req.batch = _Batch(
                    lambda: hit, len(items), 1, self._degrade_empty
                )
                req.event.set()
                return _Ticket(self, req)
        ticket = self._admit(items, k_eff, deadline, trace_ctx=ctx)
        if cache is not None:
            ticket._request.cache_store = True
        return ticket

    def serve(
        self,
        texts: Sequence[str],
        k: Optional[int] = None,
        deadline: Optional[Deadline] = None,
        priority: Optional[str] = None,
    ) -> ServeResult:
        return self.submit(texts, k, deadline=deadline, priority=priority)()

    __call__ = serve

    # -- replica placement --------------------------------------------------
    def _pick_replica(self) -> int:
        """Least-loaded replica (in-flight batches), ties rotated by
        lifetime placement count so an idle fleet round-robins instead
        of hammering replica 0."""
        with self._qlock:
            r = min(
                range(len(self._replicas)),
                key=lambda i: (self._inflight[i], self._placed[i], i),
            )
            self._inflight[r] += 1
            self._placed[r] += 1
            return r

    def _release_replica(self, r: int) -> None:
        with self._qlock:
            self._inflight[r] = max(0, self._inflight[r] - 1)

    # -- engine hooks -------------------------------------------------------
    def _launch(self, items: List[Tuple[str, int]], reqs: List[_Request]):
        k_batch = max((r.k or self.k) for r in reqs)
        deadline = self._batch_deadline(reqs)
        kwargs: Dict[str, Any] = {}
        if self._submit_deadline:
            kwargs["deadline"] = deadline
        if self._submit_n_requests:
            # per-request degradation accounting: a stage-1 failure in
            # this batch flags + counts every rider, not "one batch"
            kwargs["n_requests"] = len(reqs)
        # composition stays deterministic: items are the sorted-unique
        # (text, gen) pairs, so the text list the target sees is sorted
        # (a text straddling a generation bump appears once per gen —
        # same results, separate slots)
        texts = [t for t, _gen in items]
        r = self._pick_replica()
        try:
            handle = self._replicas[r].submit(texts, k_batch, **kwargs)
        except BaseException:
            self._release_replica(r)
            raise
        return _ReplicaHandle(handle, lambda: self._release_replica(r))

    def _demux(self, req: _Request, batch_result) -> ServeResult:
        k = req.k or self.k
        rows = []
        for slot in req.slots:
            row = (
                batch_result[slot]
                if 0 <= slot < len(batch_result)
                else []
            )
            rows.append(list(row[:k]))
        result = ServeResult(
            rows,
            degraded=tuple(getattr(batch_result, "degraded", ())),
            meta=getattr(batch_result, "meta", None),
        )
        cache = self._result_cache
        if cache is not None and req.cache_store and not result.degraded:
            # tier-0 capture, on the WAITER's thread off every scheduler
            # lock.  Clean results only (a cached degraded serve would
            # pin a transient outage for a TTL), and only when the
            # dispatch-time generation the serve path stamped into the
            # result matches this item's admission generation — a
            # mutation landing mid-flight must not be stored under the
            # pre-mutation key.
            meta_gen = result.meta.get("index_generation")
            ctx = req.trace
            with trace.use(ctx):
                for (text, gen), row in zip(req.items, rows):
                    if meta_gen is not None and (
                        normalize_generation(meta_gen)
                        != normalize_generation(gen)
                    ):
                        continue
                    cache.put_row(text, gen, k, row, deadline=req.deadline)
        ctx = req.trace
        if ctx is not None:
            # rider trace complete: the root span IS the request latency
            # (admission → demux); tail sampling runs now, when the
            # outcome (rungs, deadline, duration percentile) is known
            ctx.annotate(k=k)
            trace.finish(ctx, statuses=tuple(result.degraded))
        return result

    # -- flight-recorder provider ------------------------------------------
    def observe_metrics(self):
        yield from super().observe_metrics()
        labels = {"scheduler": self.name}
        if self._result_cache is not None:
            # requests resolved entirely from the tier-0 result cache
            # (zero-dispatch serves); per-tier hit/miss/bytes render
            # from the cache's own provider (pathway_cache_*)
            yield (
                "counter",
                "pathway_serve_queue_requests_total",
                {**labels, "mode": "cached"},
                self.stats.get("cache_hits", 0),
            )
        for r in range(len(self._replicas)):
            rl = {**labels, "replica": str(r)}
            yield (
                "gauge", "pathway_serve_replica_depth", rl, self._inflight[r]
            )
            yield (
                "counter",
                "pathway_serve_replica_batches_total",
                rl,
                self._placed[r],
            )


class SharedBatcher(_CoalescerBase):
    """The same coalescing engine for flat scoring calls: concurrent
    ``score(items)`` calls (e.g. (query, doc) pairs from QA dataflow
    rows) coalesce into ONE ``submit_fn(items, deadline=...)`` dispatch;
    per-call scores demux (and dedup) from the shared result.  A batch
    failure re-raises to every rider — the caller owns its ladder (the
    QA rerank path already converts scoring failures into
    ``rerank_skipped``)."""

    _degrade_empty = False

    def __init__(
        self,
        submit_fn,
        name: Optional[str] = None,
        window_us: Optional[float] = None,
        max_batch: Optional[int] = None,
        autostart: bool = True,
    ):
        self._submit_fn = submit_fn
        try:
            params = inspect.signature(submit_fn).parameters
        except (TypeError, ValueError):
            params = {}
        self._submit_deadline = "deadline" in params
        super().__init__(
            name=name or f"batch-{observe.next_id()}",
            window_us=window_us, max_batch=max_batch, autostart=autostart,
        )

    def submit(
        self, items: Sequence[Any], deadline: Optional[Deadline] = None
    ) -> _Ticket:
        return self._admit(list(items), None, deadline)

    def score(
        self, items: Sequence[Any], deadline: Optional[Deadline] = None
    ) -> np.ndarray:
        return self.submit(items, deadline=deadline)()

    __call__ = score

    def _launch(self, items: List[Any], reqs: List[_Request]):
        deadline = self._batch_deadline(reqs)
        if self._submit_deadline:
            return self._submit_fn(items, deadline=deadline)
        return self._submit_fn(items)

    def _demux(self, req: _Request, batch_result) -> np.ndarray:
        flat = np.asarray(batch_result)
        return np.asarray(
            [flat[slot] for slot in req.slots], dtype=flat.dtype
        )

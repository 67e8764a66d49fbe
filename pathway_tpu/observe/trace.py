"""End-to-end serve tracing: per-request span trees with tail-based
sampling and histogram exemplars.

The flight recorder's histograms answer "what is the fleet doing"; this
module answers "why was *this* request slow".  A single serve crosses
the cache tiers, the coalescing scheduler, an N-shard scatter-dispatch,
and a multi-stage rerank cascade — its latency is smeared across shared
batches that aggregate histograms cannot decompose.  The fix is the
Dapper one (PAPERS.md): per-request trace trees with aggregate↔trace
linkage.

Model
-----

- A ``TraceContext`` is created at ``ServeScheduler.submit`` admission
  (trace id, root span, deadline, head-sampling bit) and carried on the
  request; the scheduler activates it (``use``) around the hops that run
  on other threads, so every instrumentation site reaches it with one
  ``trace.current()`` call.
- Requests that share a coalesced batch each carry a **link span**: the
  batch's work (stage-1 dispatch, per-shard fan-out, merge, cascade
  stages, model round trips) records into ONE batch trace, and each
  rider's tree holds a ``batch`` span with the queue wait and the batch
  trace id — ``/traces`` inlines the linked batch tree so a rider's view
  shows who it rode with and where the shared time went.
- Spans carry EXPLICIT timestamps (``add_span(name, t0_ns, t1_ns)``),
  written by the two calls of ``observe/spans.py``: ``observe.span``
  brackets host work on the calling thread and feeds its ONE pair of
  clock reads to the histogram, this tree and the profiler;
  ``observe.interval`` records what crossed threads or was a wait.  The
  order at every site is lock-then-span: ``observe.span`` is never held
  across a lock acquisition (the analyzer's span-across-lock rule), a
  lock wait is an ``interval`` of its own.  A span opened inside another
  of the same trace is its child (``parent``), so self time is a span
  minus its children.

Tail-based sampling
-------------------

Spans buffer per-trace; the keep/drop decision happens at ``finish``,
when the outcome is known (the whole point of tail sampling).  Kept:

- **degraded** — any ladder rung recorded (``robust.record_degraded``
  stamps the active trace);
- **deadline** — the request's deadline expired;
- **slow** — the root duration reaches the top-percentile bucket of the
  ``pathway_serve_request_seconds`` histogram
  (``PATHWAY_TRACE_SLOW_PCT``, default 0.99, once ≥ 64 observations);
- **linked** — a batch trace referenced by a kept rider is promoted
  from the bounded pending ring so the rider's tree always resolves.

Kept traces land in a bounded LRU store (``PATHWAY_TRACE_KEEP``,
default 256) served as JSON span trees on ``GET /traces``; everything
else is dropped after a bounded stay in the pending ring.  On keep, the
trace id is stamped as an **exemplar** onto the histogram bucket each
span's duration landed in, so a p99 bucket on ``/metrics`` links
directly to a kept trace.

Cost discipline
---------------

``PATHWAY_OBSERVE=0`` / ``set_enabled(False)`` (or a zero
``PATHWAY_TRACE_SAMPLE``) makes ``start_trace`` return ``None`` after a
single flag check with zero allocations; every instrumentation site is
``t = trace.current()`` / ``if t is None: return`` — one context-var
read.  What the enabled path costs on the chip (PERF.md, ISSUE 24;
``vs1m-query-open``, ``latency_p50_ms``): 6.15 ms at sample 1.0 against
5.90-5.96 with ``PATHWAY_TRACE_SAMPLE=0``, about 4%.  Every tree node
costs every request (a first version with eleven nodes a request-and-batch
read 7.09 ms), so waits that are gaps between siblings stay gaps.

Chaos: the ``trace.record`` / ``trace.export`` sites (robust/inject.py)
prove that a faulted tracing path degrades to DROPPED spans (counted on
``pathway_trace_spans_dropped_total``), never a failed or slowed serve.
"""

from __future__ import annotations

import itertools
import random
import threading
import time
from collections import OrderedDict
from contextvars import ContextVar
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .. import config
from . import _state
from .recorder import counter, histogram, register_provider

__all__ = [
    "TraceContext",
    "current",
    "finish",
    "get_trace",
    "reset",
    "ring_stats",
    "sample_rate",
    "set_sample",
    "snapshot_traces",
    "start_trace",
    "stats",
    "use",
]


_KEEP_CAPACITY = config.get("observe.trace_keep")
_PENDING_CAPACITY = config.get("observe.trace_pending")
_MAX_SPANS = config.get("observe.trace_max_spans")
_SLOW_PCT = config.get("observe.trace_slow_pct")
_SLOW_MIN_COUNT = 64
_sample = config.get("observe.trace_sample")

# the request-level end-to-end latency histogram: observed at rider
# finish, it is BOTH the tail sampler's "slow" threshold source and the
# flagship exemplar family (a p99 bucket links to a kept trace id)
_H_REQUEST = histogram("pathway_serve_request_seconds")
# the ingest plane's arrival→retrievable histogram (observed by
# serve/ingest.py per document): its quantile is the slow threshold for
# kind="ingest" traces — a slow document keeps its trace exactly like a
# slow serve does
_H_INGEST = histogram("pathway_freshness_seconds")

# per-kind slow-rule source: the histogram whose tail quantile defines
# "slow" for traces of that kind
_SLOW_HISTS = {"request": _H_REQUEST, "ingest": _H_INGEST}

_C_SPANS_DROPPED = counter("pathway_trace_spans_dropped_total")
_C_SAMPLED_OUT = counter("pathway_trace_sampled_out_total")
_C_EXPORT_FAILURES = counter("pathway_trace_export_failures_total")
_kept_counters: Dict[str, Any] = {}


def _kept_counter(reason: str):
    c = _kept_counters.get(reason)
    if c is None:
        c = _kept_counters[reason] = counter(
            "pathway_trace_kept_total", reason=reason
        )
    return c


# deterministic-enough ids: a per-process nonce plus a monotone counter
# (uuid4 per trace would be an allocation-heavy syscall on admission)
_NONCE = f"{random.SystemRandom().getrandbits(32):08x}"
_ids = itertools.count(1)
_rng = random.Random(0x7A3CE)  # head-sampling draws (seeded: replayable)

_CURRENT: "ContextVar[Optional[TraceContext]]" = ContextVar(
    "pathway_trace_ctx", default=None
)

_store_lock = threading.Lock()
_kept: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()
_pending: "OrderedDict[str, tuple]" = OrderedDict()  # finish()'s entries
_kept_evicted = 0
_pending_evicted = 0
_started = 0

# lazy robust imports: robust/ imports the observe package, so a
# module-level import here would be circular.  Resolved once, cached.
_inject_mod = None


def _inject():
    global _inject_mod
    if _inject_mod is None:
        try:
            from ..robust import inject as mod
        except Exception:  # pragma: no cover - partial interpreter teardown
            return None
        _inject_mod = mod
    return _inject_mod


def _spent_deadline():
    """An already-expired Deadline: an armed ``hang`` at a tracing chaos
    site must release IMMEDIATELY (the tracing path may never stall a
    serve), and an armed ``delay`` is capped to ~10 ms by fire()'s
    remaining-budget clamp."""
    from ..robust.deadline import Deadline

    return Deadline.after_ms(0.0)


def _record_allowed(site: str) -> bool:
    """Chaos gate for the tracing path: True = record normally.  ANY
    armed fault at ``site`` — raise, delay, hang — means the affected
    span/export is dropped (and counted); the serve itself proceeds."""
    inj = _inject()
    if inj is None or not inj.any_armed():
        return True
    try:
        before = inj.fired_count(site)
        inj.fire(site, deadline=_spent_deadline())
        return inj.fired_count(site) == before
    except Exception:
        return False


class TraceContext:
    """One trace: the root span plus a bounded per-trace span buffer.

    Span tuples are ``(span_id, parent_id, name, t0_ns, dur_ns, status,
    attrs|None, exemplar_hist|None)`` — root is span id 1.  All methods
    are thread-safe; span recording is list-append under the context's
    own lock (never held across anything blocking)."""

    __slots__ = (
        "trace_id", "name", "kind", "t0_ns", "deadline", "spans",
        "statuses", "links", "attrs", "dispatches", "fetches",
        "physical_dispatches", "dropped", "finished", "force_keep",
        "_lock", "_sids",
    )

    def __init__(self, name: str, kind: str, deadline=None):
        self.trace_id = f"{_NONCE}{next(_ids):08x}"
        self.name = str(name)
        self.kind = str(kind)
        self.t0_ns = time.perf_counter_ns()
        self.deadline = deadline
        self.spans: List[tuple] = []
        self.statuses: List[str] = []
        self.links: List[str] = []
        self.attrs: Dict[str, Any] = {}
        self.dispatches = 0
        self.fetches = 0
        self.physical_dispatches = 0
        self.dropped = 0
        self.finished = False
        self.force_keep = False
        self._lock = threading.Lock()
        self._sids = itertools.count(2)  # next() is atomic: no lock

    # -- span recording -----------------------------------------------------
    def add_span(
        self,
        name: str,
        t0_ns: int,
        t1_ns: int,
        status: str = "ok",
        parent: int = 1,
        exemplar=None,
        sid: Optional[int] = None,
        **attrs: Any,
    ) -> int:
        """Record one finished span with explicit timestamps (the serve
        path measures its stages anyway — tracing reuses those clock
        reads).  ``exemplar`` is the LatencyHistogram this duration was
        also observed into: if the trace is KEPT, the trace id is
        stamped onto that histogram's matching bucket.  ``sid`` is an id
        taken earlier with ``reserve_span_id`` (a span that had children
        before it ended).  Returns the span id (0 = dropped: trace full,
        finished, or chaos-faulted)."""
        return self.record(
            sid, int(parent), str(name), int(t0_ns),
            max(0, int(t1_ns) - int(t0_ns)), str(status), attrs or None,
            exemplar,
        )

    def record(
        self, sid, parent, name, t0_ns, dur_ns, status, attrs, exemplar
    ) -> int:
        """``add_span`` without the keyword packing and coercions: the
        entry ``observe.span`` / ``observe.interval`` use, with values
        already of the right types."""
        inj = _inject_mod or _inject()
        if inj is not None and inj.any_armed() and not _record_allowed(
            "trace.record"
        ):
            with self._lock:
                self.dropped += 1
            _C_SPANS_DROPPED.inc()
            return 0
        if sid is None:
            sid = next(self._sids)
        with self._lock:
            if self.finished or len(self.spans) >= _MAX_SPANS:
                self.dropped += 1
                dropped = True
            else:
                dropped = False
                self.spans.append(
                    (sid, parent, name, t0_ns, dur_ns, status, attrs, exemplar)
                )
        if dropped:
            _C_SPANS_DROPPED.inc()
            return 0
        return sid

    def reserve_span_id(self) -> int:
        """An id for a span that is still open, so that spans ending
        inside it can name it as their ``parent``."""
        return next(self._sids)

    def add_event(self, name: str, status: str = "ok", **attrs: Any) -> int:
        """A zero-duration annotation span (cache hit/miss, shard skip,
        rung outcome) stamped at the current instant."""
        t = time.perf_counter_ns()
        return self.add_span(name, t, t, status=status, **attrs)

    # -- trace-level annotations --------------------------------------------
    def annotate(self, **attrs: Any) -> None:
        with self._lock:
            self.attrs.update(attrs)

    def set_status(self, reason: str) -> None:
        """Record one degradation-ladder rung on this trace (drives the
        tail sampler's "degraded" keep rule).  Deduped."""
        reason = str(reason)
        with self._lock:
            if reason not in self.statuses:
                self.statuses.append(reason)

    def add_link(self, trace_id: str) -> None:
        with self._lock:
            if trace_id not in self.links:
                self.links.append(trace_id)

    # -- dispatch/fetch stamping (ops/dispatch_counter.py) ------------------
    def note_dispatch(self, tag: str, shards: int = 1) -> None:
        # plain int bumps (GIL-atomic enough for stamped diagnostics)
        self.dispatches += 1
        self.physical_dispatches += max(1, int(shards))

    def note_fetch(self, tag: str, shards: int = 1) -> None:
        self.fetches += 1


class _Activation:
    """Context manager installing a TraceContext as the thread's current
    trace — how a trace follows its request across the scheduler thread
    (dispatch) and the waiter thread (fetch/demux)."""

    __slots__ = ("_ctx", "_token")

    def __init__(self, ctx: Optional[TraceContext]):
        self._ctx = ctx

    def __enter__(self) -> Optional[TraceContext]:
        self._token = _CURRENT.set(self._ctx)
        return self._ctx

    def __exit__(self, *exc) -> None:
        _CURRENT.reset(self._token)


def use(ctx: Optional[TraceContext]) -> _Activation:
    return _Activation(ctx)


def current() -> Optional[TraceContext]:
    """The thread's active TraceContext, or None.  THE instrumentation
    entry: every serve-path site does ``t = trace.current()`` and
    returns on None — one context-var read, zero allocations, whether
    tracing is disabled, sampled out, or simply not on this path."""
    return _CURRENT.get()


def start_trace(
    name: str,
    deadline=None,
    kind: str = "request",
    sample: bool = True,
) -> Optional[TraceContext]:
    """Create a trace — or None when the recorder is disabled (single
    flag check, no allocation) or head-sampling passes on this request.
    ``sample=False`` skips the head-sampling draw (batch traces: their
    riders already drew — a batch exists iff a traced rider does)."""
    if not _state.enabled:
        return None
    if sample:
        s = _sample
        if s <= 0.0:
            return None
        if s < 1.0 and _rng.random() >= s:
            return None
    global _started
    _started += 1
    return TraceContext(name, kind, deadline)


def set_sample(p: float) -> None:
    """Head-sampling probability (also ``PATHWAY_TRACE_SAMPLE``): 1.0
    traces every request, 0.0 none (the bench A/B switch).  Tail
    sampling then decides which TRACED requests are kept."""
    global _sample
    _sample = min(1.0, max(0.0, float(p)))


def sample_rate() -> float:
    return _sample


# -- tail sampling -----------------------------------------------------------
def _keep_reason(ctx: TraceContext, dur_ns: int) -> Optional[str]:
    if ctx.force_keep:
        return "forced"
    if ctx.statuses:
        return "degraded"
    d = ctx.deadline
    if d is not None:
        try:
            if d.expired():
                return "deadline"
        except Exception:
            pass
    h = _SLOW_HISTS.get(ctx.kind)
    if h is not None and h.count >= _SLOW_MIN_COUNT:
        q = _slow_threshold_s(ctx.kind, h)
        if q is not None and dur_ns * 1e-9 >= q:
            return "slow"
    return None


# kind -> (histogram count when scanned, quantile): the scan runs on every
# finish of every request and batch, and a power-of-two bucket bound moves
# rarely, so it is redone once per _SLOW_MIN_COUNT new observations
_slow_cache: Dict[str, Tuple[int, Optional[float]]] = {}


def _slow_threshold_s(kind: str, h) -> Optional[float]:
    n = h.count
    cached = _slow_cache.get(kind)
    if cached is None or not 0 <= n - cached[0] < _SLOW_MIN_COUNT:
        cached = _slow_cache[kind] = (n, h.quantile_s(_SLOW_PCT))
    return cached[1]


def _keep(record: Dict[str, Any], reason: str) -> None:
    global _kept_evicted
    record["keep_reason"] = reason
    tid = record["trace_id"]
    # aggregate↔trace linkage: stamp this trace id onto the histogram
    # bucket each exemplar-carrying span landed in — ONLY for kept
    # traces, so every exemplar on /metrics resolves on /traces
    for span in record["_spans"]:
        ex = span[7]
        if ex is not None:
            try:
                ex.set_exemplar(span[4], tid)
            except Exception:  # pragma: no cover - defensive
                pass
    if record["kind"] == "request":
        _H_REQUEST.set_exemplar(record["_dur_ns"], tid)
    with _store_lock:
        _pending.pop(tid, None)
        _kept[tid] = record
        while len(_kept) > _KEEP_CAPACITY:
            _kept.popitem(last=False)
            _kept_evicted += 1
    _kept_counter(reason).inc()


def finish(
    ctx: Optional[TraceContext],
    statuses: Sequence[str] = (),
    force_keep: bool = False,
) -> Optional[str]:
    """End a trace's root span and run the tail sampler.  Idempotent.
    Returns the keep reason, or None when the trace was sampled out
    (parked in the bounded pending ring for possible link promotion)."""
    global _pending_evicted
    if ctx is None:
        return None
    for s in statuses:
        ctx.set_status(s)
    if force_keep:
        ctx.force_keep = True
    with ctx._lock:
        if ctx.finished:
            return None
        ctx.finished = True
        spans = list(ctx.spans)
        links = list(ctx.links)
    dur_ns = time.perf_counter_ns() - ctx.t0_ns
    if ctx.kind == "request":
        _H_REQUEST.observe_ns(dur_ns)
    entry = (ctx, spans, links, dur_ns, time.time())
    reason = _keep_reason(ctx, dur_ns)
    if reason is None:
        # parked as it is: the record is built only if a kept rider's link
        # promotes it (99 of 100 traces are dropped from here)
        with _store_lock:
            _pending[ctx.trace_id] = entry
            while len(_pending) > _PENDING_CAPACITY:
                _pending.popitem(last=False)
                _pending_evicted += 1
        _C_SAMPLED_OUT.inc()
        return None
    _keep(_record(*entry), reason)
    # link promotion: a kept rider must be able to resolve its batch —
    # pull the linked traces out of the pending ring into the kept store
    for lid in links:
        with _store_lock:
            linked = _pending.pop(lid, None)
        if linked is not None:
            _keep(_record(*linked), "linked")
    return reason


def _record(ctx: TraceContext, spans, links, dur_ns: int, ts: float) -> Dict[str, Any]:
    return {
        "trace_id": ctx.trace_id,
        "name": ctx.name,
        "kind": ctx.kind,
        "ts": ts,
        "duration_ms": dur_ns * 1e-6,
        "statuses": list(ctx.statuses),
        "dispatches": ctx.dispatches,
        "physical_dispatches": ctx.physical_dispatches,
        "fetches": ctx.fetches,
        "spans_dropped": ctx.dropped,
        "attrs": dict(ctx.attrs),
        "links": links,
        "keep_reason": None,
        "_t0_ns": ctx.t0_ns,
        "_dur_ns": dur_ns,
        "_spans": spans,
    }


# -- export ------------------------------------------------------------------
def _span_dict(record: Dict[str, Any], span: tuple) -> Dict[str, Any]:
    sid, parent, name, t0, dur, status, attrs, _ex = span
    d: Dict[str, Any] = {
        "span_id": sid,
        "parent_id": parent,
        "name": name,
        "start_ms": (t0 - record["_t0_ns"]) * 1e-6,
        "duration_ms": dur * 1e-6,
        "status": status,
    }
    if attrs:
        d["attrs"] = dict(attrs)
    return d


def _tree(
    record: Dict[str, Any],
    index: Dict[str, Dict[str, Any]],
    inline: bool = True,
) -> Dict[str, Any]:
    """One kept trace as a JSON span tree.  Link spans carrying a
    ``linked_trace`` attr inline the linked (batch) trace's tree when it
    is also kept — a rider's view shows the shared batch work in place.
    Inlining is one level deep (batch traces do not link further)."""
    root: Dict[str, Any] = {
        "span_id": 1,
        "parent_id": 0,
        "name": record["name"],
        "start_ms": 0.0,
        "duration_ms": record["duration_ms"],
        "status": "degraded" if record["statuses"] else "ok",
        "children": [],
    }
    nodes: Dict[int, Dict[str, Any]] = {1: root}
    spans = sorted(record["_spans"], key=lambda s: (s[3], s[0]))
    for span in spans:
        d = _span_dict(record, span)
        d["children"] = []
        attrs = span[6] or {}
        linked_id = attrs.get("linked_trace")
        if linked_id is not None and inline:
            target = index.get(linked_id)
            if target is not None:
                d["linked"] = _tree(target, index, inline=False)
        nodes[span[0]] = d
    # a parent is recorded when it ENDS, after its children: attach only
    # once every node exists (a parent that was dropped falls to the root)
    for span in spans:
        nodes.get(span[1], root)["children"].append(nodes[span[0]])
    out = {k: v for k, v in record.items() if not k.startswith("_")}
    out["root"] = root
    return out


def snapshot_traces(limit: Optional[int] = None) -> Dict[str, Any]:
    """The ``GET /traces`` payload: kept traces (newest first) as span
    trees, plus the sampler/ring counters.  A faulted export
    (``trace.export`` chaos site) degrades to an empty, flagged payload
    — the endpoint never 500s."""
    base: Dict[str, Any] = {
        "enabled": _state.enabled,
        "sample": _sample,
        "capacity": _KEEP_CAPACITY,
        "started_total": _started,
        "sampled_out_total": _C_SAMPLED_OUT.value,
        "spans_dropped_total": _C_SPANS_DROPPED.value,
    }
    if not _record_allowed("trace.export"):
        _C_EXPORT_FAILURES.inc()
        base["traces"] = []
        base["export_failed"] = True
        return base
    with _store_lock:
        records = list(_kept.values())
        index = {r["trace_id"]: r for r in records}
    if limit is not None and limit > 0:
        records = records[-int(limit):]
    base["traces"] = [_tree(r, index) for r in reversed(records)]
    base["export_failed"] = False
    return base


def get_trace(trace_id: str) -> Optional[Dict[str, Any]]:
    """One kept trace's span tree by id (how an exemplar on /metrics
    resolves), or None."""
    with _store_lock:
        record = _kept.get(trace_id)
        index = {r["trace_id"]: r for r in _kept.values()}
    if record is None:
        return None
    return _tree(record, index)


# -- introspection / lifecycle ----------------------------------------------
def stats() -> Dict[str, int]:
    with _store_lock:
        kept = len(_kept)
        pending = len(_pending)
    return {
        "started": _started,
        "kept": kept,
        "pending": pending,
        "kept_evicted": _kept_evicted,
        "pending_evicted": _pending_evicted,
        "spans_dropped": _C_SPANS_DROPPED.value,
        "sampled_out": _C_SAMPLED_OUT.value,
    }


def ring_stats() -> List[Tuple[str, int, int]]:
    """(ring name, capacity, dropped/evicted) rows for the recorder's
    bounded-ring health rendering (pathway_observe_events_dropped_total
    / pathway_observe_ring_capacity)."""
    return [
        ("trace_kept", _KEEP_CAPACITY, _kept_evicted),
        ("trace_pending", _PENDING_CAPACITY, _pending_evicted),
    ]


def reset() -> None:
    """Drop every kept/pending trace (tests, bench phase boundaries).
    Counters are zeroed by ``observe.reset`` like every other series."""
    global _kept_evicted, _pending_evicted, _started
    with _store_lock:
        _kept.clear()
        _pending.clear()
        _slow_cache.clear()
        _kept_evicted = 0
        _pending_evicted = 0
    _started = 0


class _TraceProvider:
    """Scrape-time gauges for the trace stores (zero hot-path cost).
    Family name deliberately disjoint from the ``pathway_trace_kept_total``
    counter family: an OpenMetrics counter family ``x`` reserves the
    ``x_total`` sample name, so a gauge family ``x`` would clash and
    fail a strict scrape."""

    def observe_metrics(self):
        with _store_lock:
            kept = len(_kept)
            pending = len(_pending)
        yield ("gauge", "pathway_trace_store_entries", {"store": "kept"}, kept)
        yield (
            "gauge", "pathway_trace_store_entries", {"store": "pending"},
            pending,
        )


_provider = _TraceProvider()
register_provider(_provider)

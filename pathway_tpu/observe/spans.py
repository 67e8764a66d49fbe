"""One span primitive on the profiler's clock.

A stage boundary used to be written out by hand at every site: a
``perf_counter_ns()`` pair, ``hist.observe_ns``, ``trace.current()`` +
``add_span`` and, at stage 2, a ring event and a zero-length OTLP span.
Each site chose its own boundaries, so the histogram, the trace tree and
the device trace could disagree.  Two calls replace all of it:

- ``span(name, hist=, cpu_hist=, counter=)`` — a context manager for host
  work done ON THE CALLING THREAD.  One pair of clock reads feeds every
  outlet: the wall histogram, the thread-CPU twin (``time.thread_time_ns``,
  sampled: wall − CPU is the time the thread was blocked: GIL, lock,
  device), a
  counter of seconds, the active trace tree (``GET /traces``) and a
  ``jax.profiler.TraceAnnotation("pw." + name)``, which records only while
  a profiler session is live (the program never starts one) and puts the
  span on the device trace's own clock.  ``ProfileData`` times are relative
  to the profiler session's start and ``TraceMe`` takes no explicit start
  and end, so only a live bracket can share that clock.
- ``interval(name, t0_ns, t1_ns, hist=)`` — for intervals that CROSS
  THREADS or ARE WAITS (dispatch → fetched, enqueue → popped, handoff →
  rider resumes): histogram + trace tree, never a profiler event (96
  sleeping waiters would win every idle gap of the device trace).

A bracket never encloses a lock acquisition: take the lock, then open the
span, or measure the wait as an ``interval`` of its own (the analyzer's
span-across-lock rule, analysis/lock_discipline.py).  Names are constants:
the benchmark's trace reducer groups by name.

Spans nest: a span opened while another of the same trace is open on this
thread becomes its child, so a tree's self time is a span minus its
children.  ``PATHWAY_OBSERVE=0`` reduces ``span`` to one flag check that
hands back a shared no-op (no allocation, no clock read).
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Optional

from . import _state
from . import trace as _trace
from .recorder import histogram, record_event

__all__ = ["CURRENT", "interval", "serve_stage", "span"]

# ``interval(tree=...)`` default: attach to the calling thread's active trace
CURRENT: Any = object()

_tls = threading.local()
_current = _trace._CURRENT.get  # the thread's active TraceContext, or None
_perf_counter_ns = time.perf_counter_ns
_thread_time_ns = time.thread_time_ns
# The thread-CPU clock is a real system call: 5.9 us a read on the chip's
# host against 0.07 us for perf_counter_ns (my chip run, ISSUE 24), and a
# bracket reads it twice.  So the CPU twin is SAMPLED: every _CPU_EVERY-th
# bracket of a series, the first included, per series (a shared tick would
# alias with the fixed order of brackets in a batch).  Shares are therefore
# ratios of MEANS: mean CPU of the sampled brackets over mean wall of all.
_CPU_EVERY = 7
_cpu_ticks: dict = {}  # cpu_hist -> brackets seen (plain int; a lost
# update under a thread switch only shifts the sampling phase)
_annotation: Any = None  # jax.profiler.TraceAnnotation, resolved at first use
_otlp: Any = None  # internals/telemetry.py exporter; False = none configured
# perf_counter_ns → unix ns, for OTLP start/end times
_EPOCH_NS = time.time_ns() - time.perf_counter_ns()


def _resolve_annotation():
    global _annotation
    try:
        from jax.profiler import TraceAnnotation
    except Exception:  # pragma: no cover - jax is a hard dependency of serving
        def TraceAnnotation(*a: Any, **k: Any):  # doc tooling, launchers
            return contextlib.nullcontext()
    _annotation = TraceAnnotation
    return TraceAnnotation


def _resolve_otlp():
    global _otlp
    try:
        from ..internals.telemetry import NoopTelemetry, maybe_telemetry

        t = maybe_telemetry()
        _otlp = False if isinstance(t, NoopTelemetry) else t
    except Exception:
        _otlp = False
    return _otlp


def _parent_of(ctx) -> int:
    stack = getattr(_tls, "stack", None)
    if stack and stack[-1][0] is ctx:
        return stack[-1][1]
    return 1


class _NoopSpan:
    """What ``span`` returns while the recorder is off: shared, stateless."""

    __slots__ = ()
    t0_ns = t1_ns = cpu_ns = 0

    def set(self, status: Optional[str] = None, **attrs: Any) -> None:
        pass

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc: Any) -> None:
        return None


_NOOP = _NoopSpan()


class _Span:
    __slots__ = (
        "name", "hist", "cpu_hist", "counter", "attrs", "status",
        "t0_ns", "t1_ns", "cpu_ns", "_c0", "_ann", "_ctx", "_sid", "_parent",
        "_after",
    )

    def __init__(self, name, hist, cpu_hist, counter, after, attrs):
        self.name = name
        self._after = after
        self.hist = hist
        self.cpu_hist = cpu_hist
        self.counter = counter
        self.attrs = attrs
        self.status = "ok"
        self.t0_ns = self.t1_ns = self.cpu_ns = 0

    def set(self, status: Optional[str] = None, **attrs: Any) -> None:
        """Attributes (and the tree status) learned inside the body."""
        if status is not None:
            self.status = status
        if attrs:
            self.attrs.update(attrs)

    def __enter__(self) -> "_Span":
        annotation = _annotation or _resolve_annotation()
        ctx = self._ctx = _current()
        if ctx is None:
            ann = self._ann = annotation("pw." + self.name)
        else:
            stack = getattr(_tls, "stack", None)
            if stack is None:
                stack = _tls.stack = []
            self._parent = stack[-1][1] if stack and stack[-1][0] is ctx else 1
            sid = self._sid = next(ctx._sids)
            stack.append((ctx, sid))
            # the tree's id rides the profiler event, so a span of a kept
            # device trace can be joined to its tree on GET /traces ("t" +
            # id: the profiler reads a bare hex id such as 5e16… as a number)
            ann = self._ann = annotation(
                "pw." + self.name, trace="t" + ctx.trace_id
            )
        ann.__enter__()
        cpu_hist = self.cpu_hist
        if cpu_hist is not None:
            tick = _cpu_ticks.get(cpu_hist, 0)
            _cpu_ticks[cpu_hist] = tick + 1
            if tick % _CPU_EVERY:
                self.cpu_hist = None  # not this bracket's turn
            else:
                self._c0 = _thread_time_ns()
        # chained after another span: start where it ended, so the two
        # (and whatever glue ran between them) add up exactly
        after = self._after
        self.t0_ns = (after is not None and after.t1_ns) or _perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = self.t1_ns = _perf_counter_ns()
        t0 = self.t0_ns
        wall = t1 - t0
        attrs = self.attrs
        cpu_hist = self.cpu_hist
        if cpu_hist is not None:
            cpu = self.cpu_ns = _thread_time_ns() - self._c0
            cpu_hist.observe_ns(cpu)
        self._ann.__exit__(exc_type, exc, tb)
        hist = self.hist
        if hist is not None:
            hist.observe_ns(wall)
        if self.counter is not None:
            self.counter.inc(wall * 1e-9)
        if exc_type is not None and self.status == "ok":
            self.status = "error"
        ctx = self._ctx
        if ctx is not None:
            _tls.stack.pop()
            if cpu_hist is not None:
                attrs["cpu_ms"] = cpu * 1e-6
            ctx.record(
                self._sid, self._parent, self.name, t0, wall, self.status,
                attrs or None, hist,
            )
        if _otlp is not False:
            _export(self.name, t0, t1, attrs)


def _export(name: str, t0_ns: int, t1_ns: int, attrs) -> None:
    """The span with its real start and end to the OTLP endpoint, when one
    is configured (``PATHWAY_MONITORING_SERVER``); export never fails or
    slows the caller beyond the exporter's own batching."""
    otlp = _otlp if _otlp is not None else _resolve_otlp()
    if otlp is False:
        return
    try:
        otlp.export_span(
            "pathway." + name, _EPOCH_NS + t0_ns, _EPOCH_NS + t1_ns, **attrs
        )
    except Exception:
        pass


def serve_stage(stage: str, cpu: bool = True) -> dict:
    """``span`` keywords for one serve stage: its wall series on
    ``pathway_serve_stage_seconds{stage=...}`` and (``cpu``) the thread-CPU
    twin on ``pathway_serve_stage_cpu_seconds``.  Resolve once at import:
    ``with observe.span("stage1.tokenize", **_S1_TOKENIZE)``."""
    series = {"hist": histogram("pathway_serve_stage_seconds", stage=stage)}
    if cpu:
        series["cpu_hist"] = histogram(
            "pathway_serve_stage_cpu_seconds", stage=stage
        )
    return series


def span(
    name: str, hist=None, cpu_hist=None, counter=None, after=None, **attrs: Any
):
    """Bracket host work done on the calling thread (see module docstring).
    ``hist`` / ``cpu_hist`` are ``LatencyHistogram`` series, ``counter`` a
    ``Counter`` of seconds; ``attrs`` land on the trace-tree span.  After
    exit ``t0_ns`` / ``t1_ns`` / ``cpu_ns`` hold the clock reads, so an
    adjacent ``interval`` can share them exactly; ``after=<span>`` starts
    this span's interval at that span's end (consecutive steps of one
    stage then sum to the whole by construction)."""
    if not _state.enabled:
        return _NOOP
    return _Span(name, hist, cpu_hist, counter, after, attrs)


def interval(
    name: str,
    t0_ns: int,
    t1_ns: int,
    hist=None,
    counter=None,
    tree: Any = CURRENT,
    status: str = "ok",
    ring: bool = False,
    **attrs: Any,
) -> None:
    """Record an interval measured elsewhere (it crossed threads, or was a
    wait): histogram (and/or a counter of seconds) + trace tree, no
    profiler event.  ``tree`` names the
    ``TraceContext`` to attach to (default: the thread's active one; pass a
    rider's own context from the dispatcher thread); ``ring`` also appends
    a ``serve`` event to the ``/serve_stats`` ring.  A start of 0 means the
    span that measured it ran while the recorder was off: dropped."""
    if not _state.enabled or not t0_ns or t1_ns < t0_ns:
        return
    if hist is not None:
        hist.observe_ns(t1_ns - t0_ns)
    if counter is not None:
        counter.inc((t1_ns - t0_ns) * 1e-9)
    ctx = _current() if tree is CURRENT else tree
    if ctx is not None:
        ctx.record(
            None, _parent_of(ctx), name, t0_ns, t1_ns - t0_ns, status,
            attrs or None, hist,
        )
    if ring:
        record_event("serve", name, t1_ns - t0_ns, **attrs)

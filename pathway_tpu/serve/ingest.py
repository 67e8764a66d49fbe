"""Live-ingest runner + the freshness plane: ingest→retrievable, attributed.

The reference is an *incremental* dataflow engine — live data is its
identity — yet until round 19 the serve tier only read static indexes.
This module closes the gap: a continuous maintenance loop pulls
committed rows from connector sessions (the ``io/_connector.py`` idiom:
per-connector ``ConnectorMonitor`` + ``OffsetAntichain`` committed
positions), embeds them in bucketed off-serve-path batches, and absorbs
into the IVF **and** forward index under live serve traffic using their
existing off-lock-plan/locked-commit discipline.  Each document is
stamped at connector commit and becomes *retrievable* when the absorb
commit bumps the index generation — the scheduler's generation-keyed
result cache makes new documents visible to the next serve without any
invalidation traffic.

The freshness plane attributes every stage of that journey:

- ``pathway_freshness_seconds`` — arrival → retrievable, per document;
  ``pathway_freshness_stage_seconds{stage=queue_wait|embed|absorb_plan|
  commit}`` breaks the journey down (queue-wait per document; the three
  batch stages once per batch).
- one ingest trace per absorb batch (``kind="ingest"``) riding the
  round-13 TraceContext machinery, rooted at the OLDEST rider's arrival
  so the root duration IS that document's freshness; per-stage spans
  with explicit timestamps sum exactly to it.  A slow batch keeps its
  trace like a slow serve does (trace.py's tail sampler reads this
  module's histogram), and a batch older than the freshness SLO
  threshold is force-kept.
- maintenance-lag gauges per runner (docs pending, oldest-pending age,
  per-connector lag from ``ConnectorMonitor``) via the recorder's
  provider mechanism — zero hot-path cost, sampled at scrape time, and
  surfaced as the ``ingest`` column on ``/serve_stats``.
- the ``freshness`` SLO (observe/slo.py) reads the histogram AND
  ``overdue_pending()`` — queue residents older than the threshold burn
  budget *now*, so shedding starts while the backlog ages rather than
  after it lands.

Control loop closure: when ``serve_latency`` is firing and ``freshness``
is not, serve p99 is the binding constraint — the loop yields its absorb
cadence (``PATHWAY_INGEST_BACKPRESSURE_MS``, counted on
``pathway_ingest_backpressure_total``).  The reverse direction lives in
the scheduler: freshness burn feeds ``should_shed()`` which sheds
shed-class priorities at admission.

Degrade-never-fail chaos sites, all fired under a spent deadline so an
armed hang releases instantly:

- ``ingest.poll`` — the dequeue; a fault RETRIES (documents stay
  queued, nothing lost);
- ``ingest.embed`` — the encoder dispatch; a fault DROPS the batch's
  documents (counted on ``pathway_ingest_failures_total{stage}``);
- ``ingest.commit`` — the index commit; a fault DROPS the batch.

A faulted stage affects only its own documents: serve results stay
clean and bit-identical (the index simply does not advance), which is
exactly what tests/test_robust.py's ingest triples assert.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from typing import Any, Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from .. import config, observe
from ..observe import slo as slo_mod
from ..observe import trace
from ..robust import Deadline, inject, log_once

__all__ = ["IngestConnector", "LiveIngestRunner", "ingest_runners"]

_STAGES = ("queue_wait", "embed", "absorb_plan", "commit")

# pre-created at import so the families render at 0 on /metrics before
# the first fault/document (metrics-inventory drift gate convention)
_H_FRESH = observe.histogram("pathway_freshness_seconds")
_H_STAGE = {
    s: observe.histogram("pathway_freshness_stage_seconds", stage=s)
    for s in _STAGES
}
# the embed bracket's series.  Its thread-CPU twin: wall − CPU is how long
# the ingest thread was blocked (the GIL it shares with serving, the device)
_EMBED_SERIES = {
    "hist": _H_STAGE["embed"],
    "cpu_hist": observe.histogram(
        "pathway_freshness_stage_cpu_seconds", stage="embed"
    ),
}
_C_FAIL = {
    s: observe.counter("pathway_ingest_failures_total", stage=s)
    for s in ("poll", "embed", "commit")
}

_runners: "weakref.WeakSet" = weakref.WeakSet()


def ingest_runners() -> List["LiveIngestRunner"]:
    """Live runners (weak registry) — read by the freshness SLO's
    overdue-pending term and by tests."""
    return list(_runners)


def _spent() -> Deadline:
    return Deadline.after_ms(0.0)


def _stage_allowed(site: str) -> bool:
    """Chaos gate, trace-path style: True = proceed normally.  ANY armed
    fault at ``site`` (raise, delay, hang) counts as a stage fault; the
    spent deadline means an armed hang releases immediately and a delay
    is clamped to ~10 ms — maintenance must never stall unboundedly."""
    if not inject.any_armed():
        return True
    try:
        before = inject.fired_count(site)
        inject.fire(site, deadline=_spent())
        return inject.fired_count(site) == before
    except Exception:
        return False


class _Doc:
    __slots__ = ("key", "text", "t_arrival_ns", "connector")

    def __init__(self, key: int, text: str, t_arrival_ns: int, connector: str):
        self.key = int(key)
        self.text = str(text)
        self.t_arrival_ns = int(t_arrival_ns)
        self.connector = connector


class IngestConnector:
    """The live twin of ``io/_connector.py``'s ``SessionWriter``: buffers
    keyed rows, stamps them at ``commit()`` (the arrival clock the
    freshness plane attributes from), folds committed per-partition
    offsets into its ``ConnectorMonitor`` antichain, and hands the batch
    to its runner's pending queue.  Offsets follow the SessionWriter
    contract exactly — ``commit()`` returns the merged antichain like
    ``SessionWriter.commit_offsets`` does."""

    def __init__(self, runner: "LiveIngestRunner", name: str):
        # lazy, like SessionWriter.__init__: keeps the serve import
        # graph free of the io connector zoo until a connector exists
        from ..io._offsets import ConnectorMonitor

        self._runner = runner
        self.name = str(name)
        self.monitor = ConnectorMonitor(self.name)
        self._buf: List[Tuple[int, str]] = []
        self._lock = threading.Lock()

    def insert(self, key: int, text: str) -> None:
        with self._lock:
            self._buf.append((int(key), str(text)))
        self.monitor.on_insert()

    def insert_rows(self, rows: Iterable[Tuple[int, str]]) -> None:
        rows = [(int(k), str(t)) for k, t in rows]
        with self._lock:
            self._buf.extend(rows)
        self.monitor.on_insert(len(rows))

    def commit(self, offsets: Optional[Mapping[Any, Any]] = None):
        """Commit buffered rows: each document's freshness clock starts
        HERE (connector commit), mirroring the reference's
        commit-at-autocommit-tick semantics."""
        from ..io._offsets import OffsetAntichain

        with self._lock:
            rows, self._buf = self._buf, []
        t = time.perf_counter_ns()
        docs = [_Doc(k, txt, t, self.name) for k, txt in rows]
        self.monitor.on_commit(
            OffsetAntichain(dict(offsets)) if offsets is not None else None
        )
        if docs:
            self._runner._enqueue(docs)
        return self.monitor.offsets

    def close(self) -> None:
        self.monitor.on_finish()


class LiveIngestRunner:
    """One maintenance thread absorbing committed documents into a live
    IVF (+ optional forward) index, with the freshness plane attached.

    ``freshness_plane=False`` turns off the histograms, traces, and the
    provider registration — the bench's overhead A/B arm.  The absorb
    path itself is identical either way."""

    def __init__(
        self,
        encoder,
        index,
        forward=None,
        name: str = "live",
        autostart: bool = True,
        freshness_plane: bool = True,
    ):
        self.encoder = encoder
        self.index = index
        self.forward = forward
        self.name = str(name)
        self.freshness_plane = bool(freshness_plane)
        self._cv = threading.Condition()
        self._pending: "deque[_Doc]" = deque()
        self._inflight: List[_Doc] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._connectors: List[IngestConnector] = []
        self._docs_total = 0
        self._batches_total = 0
        self._backpressure_total = 0
        self._dropped_total = 0
        _runners.add(self)
        if self.freshness_plane:
            observe.register_provider(self)
        if autostart:
            self.start()

    # -- connector surface ---------------------------------------------------
    def connector(self, name: Optional[str] = None) -> IngestConnector:
        c = IngestConnector(self, name or f"{self.name}-connector")
        self._connectors.append(c)
        return c

    def ingest_routed(
        self,
        docs: Sequence[Tuple[int, str, int]],
        connector: str = "fleet",
    ) -> int:
        """Owner-routed absorb entry (``serve/fabric.py``): accept
        ``(key, text, t_arrival_ns)`` documents whose arrival stamp was
        taken at the FLEET connector's commit and enqueue them as if a
        local connector had committed them — the freshness plane then
        attributes the full connector→retrievable journey including the
        routing hop, because the clock started at the real commit, not
        at this host's receive."""
        batch = [
            _Doc(int(k), str(t), int(ns), str(connector))
            for k, t, ns in docs
        ]
        if batch:
            self._enqueue(batch)
        return len(batch)

    def _enqueue(self, docs: Sequence[_Doc]) -> None:
        cap = config.get("ingest.queue_cap")
        with self._cv:
            for d in docs:
                # connector commits block past the cap: ingest pressure
                # propagates to the producer, never to unbounded memory
                while len(self._pending) >= cap and not self._stop.is_set():
                    self._cv.wait(0.05)
                self._pending.append(d)
            self._cv.notify_all()

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name=f"ingest-{self.name}"
        )
        self._thread.start()

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout)
        self._thread = None

    def __enter__(self) -> "LiveIngestRunner":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    def flush(self, timeout: float = 10.0) -> bool:
        """Block until every queued document has been absorbed (or
        dropped by a chaos fault) — tests/bench determinism helper."""
        t_end = time.monotonic() + timeout
        while time.monotonic() < t_end:
            with self._cv:
                if not self._pending and not self._inflight:
                    return True
            time.sleep(0.002)
        return False

    # -- lag surface (SLO + provider) ---------------------------------------
    def pending_docs(self) -> int:
        with self._cv:
            return len(self._pending) + len(self._inflight)

    def oldest_pending_s(self) -> float:
        now = time.perf_counter_ns()
        with self._cv:
            oldest = None
            if self._pending:
                oldest = self._pending[0].t_arrival_ns
            for d in self._inflight:
                if oldest is None or d.t_arrival_ns < oldest:
                    oldest = d.t_arrival_ns
        if oldest is None:
            return 0.0
        return max(0.0, (now - oldest) * 1e-9)

    def overdue_pending(self, threshold_s: float) -> int:
        """Documents waiting LONGER than the freshness threshold — the
        maintenance-lag term the freshness SLO counts as bad events
        before they ever reach the histogram."""
        cut = time.perf_counter_ns() - int(threshold_s * 1e9)
        with self._cv:
            n = sum(1 for d in self._pending if d.t_arrival_ns < cut)
            n += sum(1 for d in self._inflight if d.t_arrival_ns < cut)
        return n

    @property
    def stats(self) -> Dict[str, Any]:
        return {
            "docs": self._docs_total,
            "batches": self._batches_total,
            "dropped": self._dropped_total,
            "backpressure": self._backpressure_total,
            "pending": self.pending_docs(),
        }

    def observe_metrics(self):
        labels = {"ingest": self.name}
        yield ("gauge", "pathway_ingest_pending_docs", labels,
               float(self.pending_docs()))
        yield ("gauge", "pathway_ingest_oldest_pending_seconds", labels,
               self.oldest_pending_s())
        yield ("counter", "pathway_ingest_docs_total", labels,
               self._docs_total)
        yield ("counter", "pathway_ingest_backpressure_total", labels,
               self._backpressure_total)
        for q in (0.5, 0.99):
            v = _H_FRESH.quantile_s(q)
            if v is not None:
                yield ("gauge", "pathway_freshness_quantile_seconds",
                       {**labels, "q": str(q)}, v)
        for c in self._connectors:
            lag = c.monitor.lag_seconds()
            if lag is not None:
                yield ("gauge", "pathway_ingest_connector_lag_seconds",
                       {**labels, "connector": c.name}, lag)

    # -- the maintenance loop ------------------------------------------------
    def _run(self) -> None:
        while not self._stop.is_set():
            # scheduler→ingest backpressure: when serve latency is the
            # binding SLO (firing while freshness is quiet), maintenance
            # yields absorb cadence — the serve tier keeps its p99, the
            # backlog ages, and the aging backlog re-arms the freshness
            # burn that eventually wins the yield back
            firing = slo_mod.firing_specs()
            if "serve_latency" in firing and "freshness" not in firing:
                self._backpressure_total += 1
                self._stop.wait(config.get("ingest.backpressure_ms") * 1e-3)
            batch = self._poll()
            if not batch:
                self._stop.wait(config.get("ingest.poll_ms") * 1e-3)
                continue
            try:
                self._absorb(batch)
            finally:
                with self._cv:
                    self._inflight = []
                    self._cv.notify_all()

    def _poll(self) -> List[_Doc]:
        if not _stage_allowed("ingest.poll"):
            # RETRY semantics: the documents never left the queue
            _C_FAIL["poll"].inc()
            self._stop.wait(config.get("ingest.poll_ms") * 1e-3)
            return []
        limit = config.get("ingest.batch_docs")
        with self._cv:
            batch: List[_Doc] = []
            while self._pending and len(batch) < limit:
                batch.append(self._pending.popleft())
            if batch:
                self._inflight = list(batch)
                self._cv.notify_all()
        return batch

    def _drop(self, stage: str, batch: List[_Doc], ctx) -> None:
        """DROP semantics for a faulted embed/commit: only this batch's
        documents are lost (counted per document); serve results stay
        bit-identical because the index simply did not advance."""
        _C_FAIL[stage].inc(len(batch))
        self._dropped_total += len(batch)
        log_once(
            f"ingest.{stage}:fault",
            "ingest %s stage faulted; dropped %d document(s) — counted "
            "on pathway_ingest_failures_total{stage=%s}, serving "
            "continues untouched", stage, len(batch), stage,
        )
        if ctx is not None:
            trace.finish(ctx, statuses=(f"ingest_{stage}_failed",))

    def _absorb(self, batch: List[_Doc]) -> None:
        t_oldest = min(d.t_arrival_ns for d in batch)
        plane = self.freshness_plane
        ctx = None
        if plane:
            ctx = trace.start_trace("ingest.batch", kind="ingest")
            if ctx is not None:
                # root the trace at the oldest rider's arrival: the root
                # duration IS that document's ingest→retrievable latency
                ctx.t0_ns = t_oldest
                ctx.annotate(
                    docs=len(batch),
                    connectors=sorted({d.connector for d in batch}),
                )
        texts = [d.text for d in batch]
        keys = [d.key for d in batch]
        # dequeue → embedded: tokenize + pack + encode, on this thread
        with trace.use(ctx), observe.span(
            "ingest.embed", **(_EMBED_SERIES if plane else {})
        ) as embed:
            vecs = None
            if _stage_allowed("ingest.embed"):
                try:
                    # sequence packing when the encoder offers it (the
                    # variable-length ingest hot path; same [B, d] contract)
                    enc = getattr(
                        self.encoder, "encode_packed_to_device", None
                    ) or self.encoder.encode_to_device
                    vecs = enc(texts)
                except Exception as exc:
                    log_once(
                        f"ingest.embed:{type(exc).__name__}",
                        "ingest embed failed (%r); dropping batch", exc,
                    )
        if vecs is None:
            self._drop("embed", batch, ctx)
            return
        t_dequeue, t_embed = embed.t0_ns, embed.t1_ns
        # absorb plan, off every lock: the device→host sync the IVF's
        # own off-lock normalize will consume (value-flow: the sync must
        # not happen under the index lock)
        try:
            host = np.asarray(vecs, np.float32)
        except Exception as exc:
            log_once(
                f"ingest.plan:{type(exc).__name__}",
                "ingest absorb-plan failed (%r); dropping batch", exc,
            )
            self._drop("embed", batch, ctx)
            return
        t_plan = time.perf_counter_ns()
        if not _stage_allowed("ingest.commit"):
            self._drop("commit", batch, ctx)
            return
        try:
            gen_before = getattr(self.index, "generation", None)
            self.index.add(keys, host)
            if self.forward is not None:
                # forward absorb counts its own failures and degrades
                # independently (late-interaction skips those docs)
                self.forward.add(keys, texts)
        except Exception as exc:
            log_once(
                f"ingest.commit:{type(exc).__name__}",
                "ingest commit failed (%r); dropping batch", exc,
            )
            self._drop("commit", batch, ctx)
            return
        t_commit = time.perf_counter_ns()
        # retrievable: the commit bumped the index generation — stamp
        # every rider's freshness and the per-stage attribution
        self._docs_total += len(batch)
        self._batches_total += 1
        if not plane:
            return
        for d in batch:
            _H_FRESH.observe_ns(t_commit - d.t_arrival_ns)
            _H_STAGE["queue_wait"].observe_ns(t_dequeue - d.t_arrival_ns)
        # the other stages wait or take locks: intervals, contiguous
        observe.interval("ingest.queue_wait", t_oldest, t_dequeue, tree=ctx)
        observe.interval(
            "ingest.absorb_plan", t_embed, t_plan,
            hist=_H_STAGE["absorb_plan"], tree=ctx,
        )
        observe.interval(
            "ingest.commit", t_plan, t_commit,
            hist=_H_STAGE["commit"], tree=ctx,
        )
        if ctx is not None:
            ctx.annotate(
                generation=getattr(self.index, "generation", None),
                generation_before=gen_before,
            )
            threshold_s = config.get("observe.slo_freshness_ms") * 1e-3
            slow = (t_commit - t_oldest) * 1e-9 >= threshold_s
            trace.finish(ctx, force_keep=slow)

"""Fused retrieve→rerank serving pipeline: TWO device round trips total.

Stage 1 is the existing ``FusedEncodeSearch`` dispatch (encode + score +
top-k in one launch); stage 2 re-scores the stage-1 candidates with the
on-device cross-encoder.  Every multi-stage ranking architecture pays this
chain per query (PAPERS.md: "An Exploration of Approaches to Integrating
Neural Reranking Models in Multi-Stage Ranking Architectures"; "Accelerating
Retrieval-Augmented Generation" names retrieve+rerank as the dominant
serving cost), and each extra dispatch or fetch is another host sync —
so the stage-2 design goal is the same as stage 1's: ONE dispatch, ONE
packed fetch.

Stage 2 compiles (packed cross-encoder forward over length-bucketed,
sequence-packed (query, doc) rows) → (scatter pair scores to a [Q, Kc]
table) → (``lax.top_k`` per query) into a single jitted function whose
output is one packed int32 array: ``k`` score bit-patterns plus the ``k``
winning candidate indices (the per-query permutation of stage-1 ranks).
Short pairs share rows under block-diagonal segment attention
(models/transformer.py) instead of each padding to ``max_length`` — a
20-token pair no longer burns a 256-token row of MXU work.

``submit``/``complete`` follow the stage-1 async pattern, so consecutive
serve calls pipeline: stage 2 of call N runs on device while stage 1 of
call N+1 is already queued behind it.

Rerank stages are PLUGGABLE (the refactor behind ROADMAP item 3's
configurable cascade): the pipeline runs a list of ``RerankStage``
objects, each carrying its score fn (``submit``), over-fetch factor,
deadline sub-budget, and degradation-ladder rung.  Two stages ship:

- ``CrossEncoderStage`` — the packed cross-encoder rescore above
  (rung ``rerank_skipped``);
- ``LateInteractionStage`` — MaxSim over a device-resident forward
  index (``pathway_tpu/index``): candidates' precomputed compressed
  token rows are gathered, dequantized, scored against the stage-1
  query token states and top-k'd in ONE fused dispatch (rung
  ``late_interaction_skipped``).  The query token states ride the
  stage-1 handle device-resident, so the happy-path serve stays at
  2 dispatches + 2 fetches — and the rerank device FLOPs drop by the
  document length (the cross-encoder re-encoded every pair; MaxSim is
  one ``Lq x T' x d`` score per pair).

A stage that fails (dispatch, fetch, deadline, circuit open, forward
index unavailable) flags its rung and the serve continues with the best
ranking so far — stage-by-stage degradation instead of all-or-nothing.
The default MaxSim->cross-encoder cascade runs the cross-encoder as an
optional high-precision pass over only the top few.
"""

from __future__ import annotations

# pathway: serve-path  (hidden-sync lint applies: no implicit host round trips)

import math
import threading
import time
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import observe
from ..observe import profile, trace
from ..robust import (
    CircuitBreaker,
    CircuitOpen,
    Deadline,
    DeadlineExceeded,
    LATE_INTERACTION_SKIPPED,
    RERANK_SKIPPED,
    RETRIEVAL_FAILED,
    RetryPolicy,
    ServeResult,
    breaker as robust_breaker,
    inject,
    log_once,
    record_degraded,
    retry_call,
    stage1_fraction,
)
from .dispatch_counter import record_dispatch, record_fetch
from .recompile_guard import RecompileTripwire
from .serving import FusedEncodeSearch

__all__ = [
    "CrossEncoderStage",
    "LateInteractionStage",
    "RerankStage",
    "RetrieveRerankPipeline",
]

# the packed stage-2 dispatch launches under the pipeline lock (the
# compile cache + stats it snapshots live there), so its retry backoff
# must stay in the low milliseconds — a long sleep would stall every
# concurrent serve's stage-2 submission
_STAGE2_RETRY = RetryPolicy(attempts=3, base_delay_s=0.002, max_delay_s=0.02)
# the HF host path wraps CrossEncoderModel.submit, whose OWN dispatch
# already retries under the "cross_encoder.dispatch" site: one outer
# attempt keeps the breaker gate + fault site without multiplying the
# inner attempt budget (3x3 dispatches and triple-counted breaker
# failures otherwise)
_OUTER_RETRY = RetryPolicy(attempts=1)

# flight-recorder stage series, each fed by one ``observe.span`` /
# ``observe.interval`` (see ops/serving.py).  Per batch, by the same clock
# reads: stage2_gather + stage2_packrows + stage2_dispatch == stage2_pack.
# The older series keep their boundaries: stage2_pack = pair assembly +
# packing up to the rescore dispatch returning, stage2_rtt = rescore
# dispatch → fetched, postprocess = host result assembly (shared with
# stage 1's completion).
_H_S2PACK = observe.histogram("pathway_serve_stage_seconds", stage="stage2_pack")
_H_S2RTT = observe.histogram("pathway_serve_stage_seconds", stage="stage2_rtt")
_H_POST = observe.histogram("pathway_serve_stage_seconds", stage="postprocess")
_S2_GATHER = observe.serve_stage("stage2_gather")
_S2_PACKROWS = observe.serve_stage("stage2_packrows")
_S2_DISPATCH = observe.serve_stage("stage2_dispatch")
_S2_FETCH = observe.serve_stage("stage2_fetch", cpu=False)
_S2_POST = observe.serve_stage("stage2_postprocess")


# -- pluggable rerank stages -------------------------------------------------
class RerankStage:
    """One rung of the ranking cascade.  A stage declares

    - ``name`` — its dispatch/diagnostic label;
    - ``rung`` — the degradation-ladder flag recorded when the stage is
      skipped (failure, deadline, circuit open);
    - ``over_fetch`` — candidate-pool factor: the stage rescores the
      previous stage's top ``width(k)`` rows (an explicit ``candidates``
      count overrides the factor);
    - ``budget_fraction`` — optional share of the REMAINING deadline
      this stage may spend (``None`` = whatever remains);

    and implements ``submit(pipeline, queries, cand_rows, keep,
    deadline, query_tokens, query_mask) -> completion`` where the
    completion returns ``(rows, meta)``: per-query ``[(key, score)]``
    rankings (descending, at most ``keep`` long) plus response metadata
    to merge.  A stage failure — at submit OR completion — must raise;
    the pipeline converts it into the stage's rung and serves the best
    ranking so far (degrade, never die)."""

    name = "rerank"
    rung = RERANK_SKIPPED
    over_fetch: float = 4.0
    budget_fraction: Optional[float] = None
    needs_query_tokens = False

    def __init__(
        self,
        candidates: Optional[int] = None,
        over_fetch: Optional[float] = None,
        budget_fraction: Optional[float] = None,
    ):
        self.candidates = candidates
        if over_fetch is not None:
            self.over_fetch = float(over_fetch)
        if budget_fraction is not None:
            self.budget_fraction = float(budget_fraction)

    def width(self, k: int) -> int:
        """Input candidate-pool width for final top-``k`` serving."""
        if self.candidates is not None:
            return max(int(self.candidates), 1)
        return max(int(math.ceil(self.over_fetch * k)), k, 1)

    def sub_deadline(self, deadline: Optional[Deadline]) -> Optional[Deadline]:
        if deadline is not None and self.budget_fraction is not None:
            return deadline.sub_budget(self.budget_fraction)
        return deadline

    def submit(
        self, pipeline, queries, cand_rows, keep, deadline,
        query_tokens=None, query_mask=None, pool_width=None,
    ):
        """``cand_rows`` arrive truncated to this stage's resolved pool
        width, which the chain also passes explicitly as ``pool_width``
        so the stage can pin device shapes to it (rows may be shorter
        when the corpus is small)."""
        raise NotImplementedError

    def note_failure(self, pipeline, exc: BaseException) -> None:
        """Hook for failure bookkeeping beyond the ladder (e.g. feeding
        a model's circuit breaker).  Policy outcomes (deadline, circuit
        open) are not model failures and never reach here."""


class CrossEncoderStage(RerankStage):
    """The packed cross-encoder rescore — now also the optional
    high-precision tail of a MaxSim cascade.  Scoring runs through the
    pipeline's ``_submit_stage2`` (one packed dispatch, one fetch) sized
    to THIS stage's pool width (a cascade tail over the top 10 must not
    pay the stage-1 over-fetch's [Q, 32] score table); failures feed the
    shared per-model circuit breaker."""

    name = "cross_encoder"
    rung = RERANK_SKIPPED

    def submit(
        self, pipeline, queries, cand_rows, keep, deadline,
        query_tokens=None, query_mask=None, pool_width=None,
    ):
        cand_keys = [[key for key, _ in row] for row in cand_rows]
        return pipeline._submit_stage2(
            queries, cand_keys, keep, deadline=deadline, pool=pool_width
        )

    def note_failure(self, pipeline, exc: BaseException) -> None:
        pipeline._breaker.record_failure()


class LateInteractionStage(RerankStage):
    """MaxSim late interaction over a device-resident ``ForwardIndex``
    (pathway_tpu/index): gather candidate rows by doc id, dequantize,
    score against the stage-1 query token states, top-k — ONE fused
    dispatch, no document re-encoding, no extra query encode (the token
    states ride the stage-1 handle device-resident).

    Candidates missing from the forward index (not yet absorbed, or
    evicted) are backfilled AFTER the MaxSim-ranked rows in their
    previous-stage order and reported in ``meta["forward_missing"]``; a
    gather with nothing resident (or no token states, or a spent
    deadline) raises and serves the previous stage's scores flagged
    ``late_interaction_skipped``."""

    name = "late_interaction"
    rung = LATE_INTERACTION_SKIPPED
    needs_query_tokens = True

    def __init__(
        self,
        forward_index,
        candidates: Optional[int] = None,
        over_fetch: Optional[float] = None,
        budget_fraction: Optional[float] = None,
    ):
        super().__init__(
            candidates=candidates, over_fetch=over_fetch,
            budget_fraction=budget_fraction,
        )
        self.forward = forward_index

    def submit(
        self, pipeline, queries, cand_rows, keep, deadline,
        query_tokens=None, query_mask=None, pool_width=None,
    ):
        done, missing = self.forward.gather_submit(
            query_tokens,
            query_mask,
            [[key for key, _ in row] for row in cand_rows],
            keep,
            deadline=deadline,
            # pin the gather grid to the stage's resolved pool width so a
            # growing corpus (wider stage-1 rows) never changes shape
            width=pool_width,
        )

        def complete():
            scores, perm = done()
            results: List[List[Tuple[int, float]]] = []
            missing_keys: List[int] = []
            for qi, row in enumerate(cand_rows):
                ranked: List[Tuple[int, float]] = []
                for j in range(perm.shape[1]):
                    s = float(scores[qi, j])
                    ci = int(perm[qi, j])
                    if not np.isfinite(s) or ci >= len(row):
                        continue
                    ranked.append((row[ci][0], s))
                # candidates the forward index has no rows for could not
                # be rescored: they backfill AFTER the MaxSim-ranked rows
                # in previous-stage order with previous-stage scores (an
                # honest partial rerank beats dropping them), and every
                # one is reported in the response metadata
                for j in missing[qi]:
                    if j < len(row):
                        missing_keys.append(row[j][0])
                        if len(ranked) < keep:
                            ranked.append(row[j])
                results.append(ranked[:keep])
            meta = (
                {"forward_missing": tuple(missing_keys)}
                if missing_keys
                else None
            )
            return results, meta

        return complete


class _PendingServe:
    """In-flight retrieve→rerank serve handle: ``advance()`` completes
    stage 1 and dispatches stage 2 without blocking on the final fetch;
    calling the handle finishes the serve.  A per-handle lock makes both
    idempotent — a handle shared across threads (or completed twice)
    dispatches stage 2 and fetches its result exactly once.

    The handle is also where the degradation ladder lands (robust/):
    stage-1 results that are already on host are NEVER discarded for a
    stage-2 problem.  Reranker down / circuit open / deadline spent ⇒
    the stage-1 ranking is served flagged ``rerank_skipped``; stage 1
    itself failing (after its retry budget) ⇒ an empty result flagged
    ``retrieval_failed``.  No failure mode raises out of the handle."""

    __slots__ = (
        "_pipeline", "_stage1", "_queries", "_k",
        "_stage2", "_result", "_done", "_hlock",
        "_deadline", "_stage1_rows", "_n_requests",
    )

    def __init__(
        self, pipeline, stage1, queries, k, deadline=None, n_requests=1
    ) -> None:
        self._pipeline = pipeline
        self._stage1 = stage1
        self._queries = queries
        self._k = k
        self._stage2: Any = None
        self._result: Any = None
        self._done = False
        self._hlock = threading.Lock()
        self._deadline: Optional[Deadline] = deadline
        self._stage1_rows: Any = None
        # how many coalesced caller REQUESTS ride this serve (the serve
        # scheduler packs several into one batch): degradation flags are
        # batch-scoped but the ladder counters must count affected
        # requests, not batches — a 16-rider batch failing stage 1 is 16
        # degraded serves on pathway_serve_degraded_total
        self._n_requests = max(1, int(n_requests))

    def advance(self) -> None:
        with self._hlock:
            self._advance_locked()

    def _advance_locked(self) -> None:
        if self._stage2 is not None:
            return
        deadline = self._deadline
        try:
            hits = self._stage1()  # host fetch #1 (stage-1 packed output)
        except Exception as exc:  # ladder bottom: retrieval itself is down
            if not isinstance(exc, DeadlineExceeded):
                log_once(
                    f"stage1:{type(exc).__name__}",
                    "stage-1 retrieval failed (%r); serving empty degraded "
                    "results — first occurrence, further ones counted on "
                    "pathway_serve_degraded_total",
                    exc,
                )
            # per-request accounting: every coalesced rider of this batch
            # is an affected request (the scheduler demuxes the flagged
            # empty rows to each of them); later batches start clean
            record_degraded(RETRIEVAL_FAILED, self._n_requests)
            empty = ServeResult(
                [[] for _ in self._queries], degraded=(RETRIEVAL_FAILED,)
            )
            self._stage2 = lambda: empty
            return
        self._stage1_rows = hits
        try:
            if deadline is not None:
                # deadline-tight rung: no budget left for the rescore
                # round trip — serve the stage-1 ranking immediately
                deadline.check("stage2_submit")
            # NO pipeline lock here: stage-2 pack is pure host prep and
            # must overlap other batches' device time (the compiled-fn
            # cache + stats take the lock internally, briefly).  The
            # stage chain handles per-stage failures internally (each
            # stage's rung, cascade falls through); only the spent
            # deadline above lands in the except below.
            self._stage2 = self._pipeline._submit_chain(
                self._queries, hits, self._k,
                deadline=deadline,
                query_tokens=getattr(self._stage1, "query_tokens", None),
                query_mask=getattr(self._stage1, "query_mask", None),
                n_requests=self._n_requests,
            )
        except Exception as exc:
            # CircuitOpen / DeadlineExceeded are policy outcomes (the
            # breaker bookkeeping happened inside retry_call); anything
            # else was a dispatch failure that exhausted its retries
            if not isinstance(exc, DeadlineExceeded):
                log_once(
                    f"stage2:{type(exc).__name__}",
                    "stage-2 rerank dispatch failed (%r); serving stage-1 "
                    "scores flagged %s",
                    exc,
                    self._pipeline.stages[0].rung,
                )
            self._stage2 = self._stage1_fallback_fn()

    def _stage1_fallback_fn(self):
        """A completion serving the stage-1 ranking truncated to ``k``,
        flagged with the FIRST rerank stage's rung (stage-1's own flags
        carried over).  Later stages never ran, so only the first rung
        is recorded — the serve degraded at that point of the cascade."""
        hits = self._stage1_rows
        if hits is None:
            hits = [[] for _ in self._queries]
        k = self._k
        rung = self._pipeline.stages[0].rung
        result = ServeResult(
            [list(row[:k]) for row in hits],
            degraded=tuple(getattr(hits, "degraded", ())) + (rung,),
        )
        record_degraded(rung, self._n_requests)
        return lambda: result

    def __call__(self) -> List[List[Tuple[int, float]]]:
        with self._hlock:
            if not self._done:
                self._advance_locked()
                try:
                    self._result = self._stage2()
                except DeadlineExceeded:
                    # stage 2 missed the deadline mid-fetch: the stage-1
                    # results already on host are the serve
                    self._result = self._stage1_fallback_fn()()
                except Exception as exc:
                    # last-resort safety net (the stage chain handles its
                    # own failures): serve the stage-1 ranking flagged
                    # with the first stage's rung
                    log_once(
                        f"stage2_fetch:{type(exc).__name__}",
                        "stage-2 rerank completion failed (%r); serving "
                        "stage-1 scores flagged %s",
                        exc,
                        self._pipeline.stages[0].rung,
                    )
                    self._result = self._stage1_fallback_fn()()
                self._done = True
            return self._result


class RetrieveRerankPipeline:
    """Chain ``FusedEncodeSearch`` (stage 1) with on-device cross-encoder
    rescoring (stage 2) at two round trips per serve call.

    ``doc_text`` maps a stage-1 winner key to its document text — a dict or
    a ``key -> str`` callable (the document store's chunk text column).
    ``candidates`` is the stage-1 shortlist width fed to the cross-encoder
    (fixed, so stage-2 compiles once per batch bucket); the final result is
    the rerank-ordered top ``k``.

    Recompiles per (row bucket, row length bucket, segment bucket, query
    bucket) — a handful of shapes in steady state.  HF-imported
    cross-encoders (no segment inputs) fall back to an unpacked host-side
    stage 2, same results, more transfers."""

    def __init__(
        self,
        retriever: FusedEncodeSearch,
        cross_encoder=None,
        doc_text: Union[Mapping[int, str], Callable[[int], str], None] = None,
        k: int = 10,
        candidates: Optional[int] = None,
        deadline_ms: Optional[float] = None,
        rerank_breaker: Optional[CircuitBreaker] = None,
        forward_index=None,
        cascade: Optional[int] = None,
        stages: Optional[Sequence[RerankStage]] = None,
    ):
        self.retriever = retriever
        self.cross_encoder = cross_encoder
        self.doc_text = doc_text
        self.k = k
        # per-serve wall-clock budget: explicit arg beats the
        # PATHWAY_SERVE_DEADLINE_MS env default; <= 0 disables
        self.deadline_ms = deadline_ms
        # per-model circuit breaker shared across pipelines scoring
        # through the same cross-encoder: persistent rerank failures
        # open it and every serve fast-paths to the rerank_skipped rung
        # until the half-open probe succeeds (robust/retry.py)
        self._breaker = rerank_breaker or robust_breaker("cross_encoder")
        # -- the ranking cascade (pluggable stages) -------------------------
        # explicit ``stages`` wins; else a ``forward_index`` builds the
        # MaxSim stage, with the cross-encoder as an optional
        # high-precision pass over the top ``cascade`` rows; else the
        # classic single cross-encoder stage
        width = candidates or max(4 * k, 16)
        if stages is not None:
            self.stages: List[RerankStage] = list(stages)
        elif forward_index is not None:
            self.stages = [LateInteractionStage(forward_index, candidates=width)]
            if cascade:
                self.stages.append(
                    CrossEncoderStage(candidates=max(int(cascade), k))
                )
        else:
            self.stages = [CrossEncoderStage(candidates=width)]
        if not self.stages:
            raise ValueError("RetrieveRerankPipeline needs at least one stage")
        if any(isinstance(s, CrossEncoderStage) for s in self.stages) and (
            cross_encoder is None or doc_text is None
        ):
            raise ValueError(
                "a CrossEncoderStage needs cross_encoder= and doc_text="
            )
        # stage-1 over-fetch = the first rerank stage's candidate pool
        self.candidates = self.stages[0].width(k)
        # the MaxSim stage scores against the stage-1 query token states:
        # flip the retriever's device-resident export on (no extra query
        # encode; the fused stage-1 kernel returns them alongside).  A
        # retriever that CANNOT export (HF-imported trunk, non-mean
        # pooling) must fail HERE — otherwise every serve would silently
        # degrade late_interaction_skipped forever
        if any(s.needs_query_tokens for s in self.stages):
            retriever.export_query_tokens = True
            # POSITIVE capability proof: a retriever that cannot show a
            # truthy ``_exporting()`` (HF trunk, non-mean pooling, or a
            # duck-typed retriever with no export support at all) would
            # serve every request late_interaction_skipped forever —
            # that is a construction error, not a runtime degradation
            exporting = getattr(retriever, "_exporting", None)
            if exporting is None or not exporting():
                raise ValueError(
                    "a late-interaction stage needs query token states, "
                    "but this retriever cannot export them (requires "
                    "FusedEncodeSearch over the in-framework "
                    "TransformerEncoder trunk with pool='mean'; "
                    "HF-imported encoders pool internally)"
                )
        self._lock = threading.Lock()
        self._fns: Dict[Tuple, Any] = {}
        # recompile tripwire (ops/recompile_guard.py): stage-2 shapes are
        # bucketed (row/length/segment/query); a leak trips under tests
        self._tripwire = RecompileTripwire("RetrieveRerankPipeline.stage2")
        self.stats = {"serves": 0, "stage2_pairs": 0, "stage2_rows": 0}

    def _default_deadline(self) -> Optional[Deadline]:
        if self.deadline_ms is not None:
            return (
                Deadline.after_ms(self.deadline_ms)
                if self.deadline_ms > 0
                else None
            )
        return Deadline.from_env()

    def index_generation(self) -> int:
        """Result-visibility generation of the stage-1 index, for the
        coalescing scheduler's generation-keyed in-window dedup (an
        absorb/retrain landing mid-window must not let a later rider
        share a slot dispatched against the pre-mutation index).

        The serve-cache plumb-through rides the same counter: stage 1
        stamps its DISPATCH-time generation into
        ``meta["index_generation"]`` (ops/serving.py), ``_submit_chain``
        merges stage-1 meta into the final ``ServeResult``, and the
        scheduler's tier-0 capture refuses any row whose dispatch
        observed a newer generation than its admission key
        (serve/scheduler.py ``_demux``)."""
        gen_fn = getattr(self.retriever, "index_generation", None)
        if callable(gen_fn):
            return int(gen_fn())
        return int(
            getattr(getattr(self.retriever, "index", None), "generation", 0)
        )

    # -- the stage chain ----------------------------------------------------
    def _submit_chain(
        self,
        queries: Sequence[str],
        hits,
        k: int,
        deadline: Optional[Deadline] = None,
        query_tokens=None,
        query_mask=None,
        n_requests: int = 1,
    ):
        """Dispatch the FIRST rerank stage now (so stage 2 of this serve
        overlaps stage 1 of the next — the pipelining contract) and
        return a completion that walks the remaining cascade.  Each
        stage rescores the best ranking so far, truncated to its own
        candidate width; a stage that fails — submit, fetch, deadline,
        circuit open — flags its rung, counts the affected requests, and
        the chain continues from the previous ranking (stage-by-stage
        degradation, never an exception out of the serve).

        The final ``ServeResult`` carries the union of stage-1 flags,
        every skipped stage's rung (each exactly once) and the merged
        stage metadata; ``ServeResult`` itself mirrors the flags into
        ``meta["degraded_reasons"]``."""
        stages = self.stages
        flags: List[str] = list(getattr(hits, "degraded", ()))
        meta: Dict[str, Any] = dict(getattr(hits, "meta", {}) or {})
        meta.pop("degraded_reasons", None)  # regenerated from final flags
        rows: List[List[Tuple[int, float]]] = [list(r) for r in hits]
        # keep_i: how many rows stage i must emit — the next stage's
        # candidate pool, or the final k for the last stage
        keeps = [
            stages[i + 1].width(k) if i + 1 < len(stages) else k
            for i in range(len(stages))
        ]
        # per-stage trace bookkeeping (observe/trace.py): submit time and
        # sub-budget, stamped onto each cascade-stage span so a kept
        # trace shows WHERE down the ladder a serve degraded and how
        # much budget the stage had when it ran
        t_stage: List[int] = [0] * len(stages)
        stage_budget_ms: List[Optional[float]] = [None] * len(stages)

        def stage_span(i: int, status: str, t_end: int, **attrs) -> None:
            observe.interval(
                "stage." + stages[i].name, t_stage[i] or t_end, t_end,
                status=status, budget_ms=stage_budget_ms[i], keep=keeps[i],
                **attrs,
            )

        def skip(stage: RerankStage, exc: BaseException) -> None:
            if not isinstance(exc, (DeadlineExceeded, CircuitOpen)):
                stage.note_failure(self, exc)
                log_once(
                    f"stage:{stage.name}:{type(exc).__name__}",
                    "rerank stage %s failed (%r); serving the previous "
                    "ranking flagged %s",
                    stage.name,
                    exc,
                    stage.rung,
                )
            stage_span(
                stages.index(stage), stage.rung, time.perf_counter_ns(),
                error=type(exc).__name__,
            )
            if stage.rung not in flags:
                flags.append(stage.rung)
                record_degraded(stage.rung, n_requests)

        def try_submit(i: int, cur_rows):
            stage = stages[i]
            if not any(cur_rows):
                return None  # nothing to rerank (empty retrieval): no rung
            if deadline is not None:
                deadline.check(f"{stage.name}_submit")
            width = stage.width(k)
            t_stage[i] = time.perf_counter_ns()
            sub = stage.sub_deadline(deadline)
            if sub is not None and trace.current() is not None:
                stage_budget_ms[i] = round(sub.remaining_s() * 1e3, 3)
            return stage.submit(
                self,
                queries,
                [r[:width] for r in cur_rows],
                keeps[i],
                sub,
                query_tokens=query_tokens,
                query_mask=query_mask,
                pool_width=width,
            )

        # stage 0 dispatches NOW (pipelining); its submit failure is
        # handled HERE like any other stage's, so the cascade falls
        # through — a cold forward index (gather unavailable) must not
        # rob a healthy cross-encoder tail of its rescore
        pending = None
        try:
            pending = try_submit(0, rows)
        except Exception as exc:
            skip(stages[0], exc)

        def complete() -> ServeResult:
            nonlocal rows
            i = 0
            cur = pending
            while i < len(stages):
                if cur is not None:
                    try:
                        res = cur()
                        if isinstance(res, tuple):
                            new_rows, stage_meta = res
                        else:  # a ServeResult-style completion
                            new_rows = list(res)
                            stage_meta = getattr(res, "meta", None)
                            for f in getattr(res, "degraded", ()):
                                if f not in flags:
                                    flags.append(f)
                        rows = [list(r) for r in new_rows]
                        if stage_meta:
                            stage_meta = dict(stage_meta)
                            stage_meta.pop("degraded_reasons", None)
                            meta.update(stage_meta)
                        stage_span(i, "ok", time.perf_counter_ns())
                    except Exception as exc:
                        skip(stages[i], exc)
                i += 1
                if i < len(stages):
                    cur = None
                    try:
                        cur = try_submit(i, rows)
                    except Exception as exc:
                        skip(stages[i], exc)
            return ServeResult(
                [list(r[:k]) for r in rows],
                degraded=flags,
                meta=meta or None,
            )

        return complete

    # -- host helpers -------------------------------------------------------
    def _text_of(self, key: int, missing: Optional[List[int]] = None) -> str:
        """Document text for a stage-1 winner.  A key evicted between
        retrieval and rerank (LookupError, or absent from the mapping)
        must not sink the serve: it scores against empty text and is
        reported in the response metadata (``meta["missing_docs"]``).
        Any OTHER exception is a real bug in ``doc_text`` and surfaces."""
        src = self.doc_text
        try:
            if callable(src):
                text = src(key)
            else:
                if key not in src:
                    raise LookupError(key)
                text = src[key]
        except LookupError:
            if missing is not None:
                missing.append(key)
            return ""
        return str(text or "")

    # -- stage 2 kernel -----------------------------------------------------
    def _compiled_stage2(
        self, R: int, L: int, S: int, Q: int, k_out: int,
        Kc: Optional[int] = None,
    ):
        """One dispatch: packed cross-encoder forward -> scatter the pair
        scores into the [Q, Kc] candidate table -> per-query top-k -> ONE
        packed int32 output [Q, 2*k_out] (score bit-patterns, then the
        winning stage-1 candidate indices).  Scores ride int lanes for the
        same reason as serving.py: TPU float lanes canonicalize NaN
        payloads; int lanes survive bit-exact.

        Takes the pipeline lock internally (cache dict + tripwire only):
        callers pack and dispatch OFF the lock so concurrent batches'
        host prep overlaps.  ``Kc`` is the calling stage's candidate-pool
        width (the [Q, Kc] score-table dimension) — a cascade's
        cross-encoder tail over the top few must not pay the stage-1
        over-fetch's table and top-k."""
        Kc = Kc or self.candidates
        key = (R, L, S, Q, k_out, Kc)
        with self._lock:
            fn = self._fns.get(key)
            if fn is not None:
                return fn
            self._tripwire.observe(key)
            module = self.cross_encoder.module

            @jax.jit
            def fused(params, ids, segments, positions, pair_slot):
                scores = module.apply(
                    {"params": params},
                    ids,
                    segments > 0,
                    segments=segments,
                    positions=positions,
                    n_segments=S,
                )  # [R, S] per-segment pair scores
                flat = scores.reshape(R * S).astype(jnp.float32)
                # pair_slot[r*S+s] = q*Kc + j for real pairs, Q*Kc (out of
                # range -> dropped) for pad segments; absent candidates keep
                # -inf and can never outrank real ones
                table = jnp.full((Q * Kc,), -jnp.inf, jnp.float32)
                table = table.at[pair_slot].set(flat, mode="drop")
                s, perm = jax.lax.top_k(table.reshape(Q, Kc), k_out)
                s_bits = jax.lax.bitcast_convert_type(s, jnp.int32)
                return jnp.concatenate([s_bits, perm.astype(jnp.int32)], axis=1)

            # device-time attribution (observe/profile.py)
            fused = profile.wrap("rerank.stage2", fused)
            self._fns[key] = fused
            return fused

    def _submit_stage2(
        self,
        queries: Sequence[str],
        cand_keys: List[List[int]],
        k: int,
        deadline: Optional[Deadline] = None,
        stage1_flags: Sequence[str] = (),
        pool: Optional[int] = None,
    ):
        """Pack the (query, candidate) pairs and dispatch the stage-2
        kernel; returns a completion -> ``ServeResult`` of
        [[(key, rerank_score)]] carrying the stage-1 degradation flags
        and any ``missing_docs`` metadata.  ``pool`` is the calling
        stage's candidate width (defaults to the pipeline's stage-1
        over-fetch — the classic single-stage configuration)."""
        from ..models.encoder import _bucket

        ce = self.cross_encoder
        Kc = pool or self.candidates
        k_out = min(k, Kc)
        nq = len(queries)
        pairs: List[Tuple[str, str]] = []
        slot_ids: List[int] = []
        missing: List[int] = []
        with observe.span("stage2.gather", **_S2_GATHER) as gather:
            for qi, row in enumerate(cand_keys):
                for j, key in enumerate(row[:Kc]):
                    pairs.append((queries[qi], self._text_of(key, missing)))
                    slot_ids.append(qi * Kc + j)
            gather.set(pairs=len(pairs))
        meta = {"missing_docs": tuple(missing)} if missing else None
        if not pairs:
            return lambda: ServeResult(
                [[] for _ in range(nq)], degraded=stage1_flags, meta=meta
            )
        if getattr(ce, "_hf", False):
            return self._submit_stage2_host(
                queries, cand_keys, pairs, k_out, gather,
                deadline=deadline, stage1_flags=stage1_flags, meta=meta,
                pool=Kc,
            )
        Qb = _bucket(nq)
        # pack OFF every lock: tokenization + row packing are pure host
        # work on stateless helpers, and under the coalescing scheduler
        # batch N+1's pack must overlap batch N's device time
        with observe.span("stage2.pack", after=gather, **_S2_PACKROWS) as pack:
            # pair_slot: slot_ids where a pair sits, Qb * Kc (out of range
            # -> dropped by the scatter) on pad segments
            packed = ce._pack_pairs_padded(
                pairs, slot_ids=slot_ids, drop_slot=Qb * Kc, span=pack
            )
            rows_real = packed.rows
            Rb, L = packed.ids.shape
            Sb = packed.seg_width
            pack.set(rows=Rb)
        with observe.span(
            "stage2.dispatch", after=pack, **_S2_DISPATCH
        ) as dispatch:
            fn = self._compiled_stage2(Rb, L, Sb, Qb, k_out, Kc=Kc)
            # retry transient dispatch failures; the per-model breaker both
            # gates the attempts (CircuitOpen fast-fails to the ladder) and
            # learns from their outcomes ("rerank.dispatch" is the chaos site)
            out = retry_call(
                "rerank.dispatch",
                fn,
                ce.params,
                jnp.asarray(packed.ids),
                jnp.asarray(packed.segments),
                jnp.asarray(packed.positions),
                jnp.asarray(packed.pair_slot),
                deadline=deadline,
                policy=_STAGE2_RETRY,
                breaker=self._breaker,
            )
            record_dispatch("rerank_stage2")
            if hasattr(out, "copy_to_host_async"):
                out.copy_to_host_async()
        with self._lock:
            self.stats["stage2_pairs"] += len(pairs)
            self.stats["stage2_rows"] += Rb
        t_pack, t_dispatch = gather.t0_ns, dispatch.t1_ns
        _H_S2PACK.observe_ns(t_dispatch - t_pack)
        # packing occupancy, both granularities: packed ROWS actually
        # carrying tokens vs the bucketed row count, and real PAIR
        # segments vs the padded [Rb, Sb] segment grid
        observe.record_occupancy("stage2", rows_real, Rb)
        observe.record_occupancy("stage2_pairs", len(pairs), Rb * Sb)

        def complete() -> List[List[Tuple[int, float]]]:
            inject.fire("cross_encoder.fetch", deadline=deadline)
            if deadline is not None:
                # budget spent before blocking on the stage-2 copy: the
                # stage-1 results already on host ARE the serve — the
                # caller (_PendingServe) converts this into the
                # rerank_skipped rung instead of waiting longer
                deadline.check("cross_encoder.fetch")
            with observe.span("stage2.fetch", **_S2_FETCH) as fetch:
                arr = np.asarray(out)[:nq]
            record_fetch("rerank_stage2")
            _H_S2RTT.observe_ns(fetch.t1_ns - t_dispatch)
            results: List[List[Tuple[int, float]]] = []
            with observe.span("stage2.postprocess", **_S2_POST) as post:
                scores = np.ascontiguousarray(arr[:, :k_out]).view(np.float32)
                perm = arr[:, k_out:]
                for qi in range(nq):
                    row: List[Tuple[int, float]] = []
                    cands = cand_keys[qi]
                    for j in range(k_out):
                        s = float(scores[qi, j])
                        ci = int(perm[qi, j])
                        if not np.isfinite(s) or ci >= len(cands):
                            continue
                        row.append((cands[ci], s))
                    results.append(row[:k])
            _H_POST.observe_ns(post.t1_ns - post.t0_ns)
            # the whole of stage 2, pack → done, across the threads it ran
            # on: the /serve_stats ring's serve event (the tree has it as
            # the cascade's ``stage.cross_encoder`` span)
            observe.interval(
                "rerank_stage2", t_pack, post.t1_ns, ring=True, tree=None,
                queries=nq, pairs=len(pairs), rows=Rb,
            )
            return ServeResult(results, degraded=stage1_flags, meta=meta)

        return complete

    def _submit_stage2_host(
        self,
        queries,
        cand_keys,
        pairs,
        k_out,
        gather,
        deadline: Optional[Deadline] = None,
        stage1_flags: Sequence[str] = (),
        meta=None,
        pool: Optional[int] = None,
    ):
        """HF fallback: unpacked async scoring + host-side per-query sort
        (HF modules take no segment inputs; still one dispatch + one fetch,
        just a max-length-padded batch).  ``gather`` is the caller's
        pair-gathering span (``stage2_pack`` runs from its start)."""
        from ..models.encoder import _bucket

        # the lambda forwards the deadline to the MODEL's submit (so its
        # inner "cross_encoder.dispatch" retries and its completion-time
        # check are budget-bounded) — retry_call's own deadline= kwarg is
        # consumed by the wrapper and would otherwise never reach it
        with observe.span(
            "stage2.dispatch", after=gather, host=True, **_S2_DISPATCH
        ) as dispatch:
            score_done = retry_call(
                "rerank.dispatch",
                lambda: self.cross_encoder.submit(
                    pairs, packed=False, deadline=deadline
                ),
                deadline=deadline,
                policy=_OUTER_RETRY,
                breaker=self._breaker,
            )
            record_dispatch("rerank_stage2_host")
        rows = _bucket(len(pairs))  # one row per pair
        with self._lock:
            self.stats["stage2_pairs"] += len(pairs)
            self.stats["stage2_rows"] += rows
        t_pack, t_dispatch = gather.t0_ns, dispatch.t1_ns
        _H_S2PACK.observe_ns(t_dispatch - t_pack)
        observe.record_occupancy("stage2", len(pairs), rows)

        def complete() -> List[List[Tuple[int, float]]]:
            inject.fire("cross_encoder.fetch", deadline=deadline)
            if deadline is not None:
                deadline.check("cross_encoder.fetch")
            with observe.span("stage2.fetch", host=True, **_S2_FETCH) as fetch:
                flat = score_done()
            record_fetch("rerank_stage2_host")
            _H_S2RTT.observe_ns(fetch.t1_ns - t_dispatch)
            results: List[List[Tuple[int, float]]] = []
            width = pool or self.candidates
            with observe.span("stage2.postprocess", **_S2_POST) as post:
                pos = 0
                for qi in range(len(queries)):
                    n_c = min(len(cand_keys[qi]), width)
                    scored = list(
                        zip(cand_keys[qi][:n_c], flat[pos : pos + n_c].tolist())
                    )
                    pos += n_c
                    scored.sort(key=lambda kv: -kv[1])
                    results.append(scored[:k_out])
            _H_POST.observe_ns(post.t1_ns - post.t0_ns)
            observe.interval(
                "rerank_stage2_host", t_pack, post.t1_ns, ring=True, tree=None,
                queries=len(queries), pairs=len(pairs),
            )
            return ServeResult(results, degraded=stage1_flags, meta=meta)

        return complete

    # -- serve --------------------------------------------------------------
    def submit(
        self,
        queries: Sequence[str],
        k: Optional[int] = None,
        deadline: Optional[Deadline] = None,
        n_requests: int = 1,
    ):
        """Dispatch stage 1 WITHOUT waiting; returns a handle that is also
        the completion callable.  ``handle.advance()`` completes stage 1
        and dispatches stage 2 without blocking on the final fetch, so a
        caller driving several in-flight serves keeps the device queue
        full (stage 2 of call N overlaps stage 1 of call N+1);
        ``handle()`` finishes the serve.  ``k`` is capped at the
        ``candidates`` pool width (standard top-k semantics: a serve cannot
        return more documents than stage 1 retrieved).

        ``deadline`` (default: ``deadline_ms`` ctor arg, then the
        ``PATHWAY_SERVE_DEADLINE_MS`` env knob) is the serve's wall-clock
        budget: stage 1 gets a ``stage1_fraction()`` sub-budget, stage 2
        whatever remains, and a spent budget degrades the serve down the
        ladder (rerank_skipped / retrieval_failed) instead of raising.

        ``n_requests`` is the coalesced-rider count when a serve
        scheduler packed several caller requests into this one batch:
        degradation COUNTERS then count affected requests, not batches
        (the flags on the shared ``ServeResult`` are demuxed to each
        rider by the scheduler)."""
        k = k or self.k
        queries = list(queries)
        if deadline is None:
            deadline = self._default_deadline()
        if not queries:
            done = _PendingServe(self, lambda: ServeResult(), [], k)
            done._stage2 = lambda: ServeResult()
            return done
        stage1_deadline = (
            deadline.sub_budget(stage1_fraction()) if deadline else None
        )
        try:
            # only pass the kwarg when there IS a deadline, so duck-typed
            # retrievers with the pre-deadline submit(texts, k) signature
            # keep working in the no-deadline configuration
            if stage1_deadline is not None:
                stage1 = self.retriever.submit(
                    queries, self.candidates, deadline=stage1_deadline
                )
            else:
                stage1 = self.retriever.submit(queries, self.candidates)
        except TypeError:
            # a signature mismatch is a programming error, not a
            # retrieval outage — it must surface loudly at submit time,
            # never masquerade as permanent retrieval_failed serves
            raise
        except Exception as exc:
            # stage-1 dispatch failed past its retry budget: the handle
            # re-raises at advance() time so the ladder lands in ONE
            # place (_PendingServe), whether dispatch or fetch failed
            def stage1(_exc: Exception = exc):
                raise _exc

        with self._lock:
            self.stats["serves"] += 1
        return _PendingServe(
            self, stage1, queries, k, deadline=deadline, n_requests=n_requests
        )

    def __call__(
        self,
        queries: Sequence[str],
        k: Optional[int] = None,
        deadline: Optional[Deadline] = None,
    ) -> List[List[Tuple[int, float]]]:
        return self.submit(queries, k, deadline=deadline)()

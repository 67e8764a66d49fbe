"""Continuous token-level batching for generator decode.

The serve tier made retrieval fast; generation is the next bottleneck
("Accelerating Retrieval-Augmented Generation", arxiv 2412.15246:
once retrieval is cached and batched, the LLM decode dominates
end-to-end latency), and the listwise-rerank workload the cascade's LLM
stage will issue (RankLLM, arxiv 2505.19284) is many SHORT, shared-
prefix generations — exactly what call-granular batching wastes:
concurrent ``generate()`` calls serialize into separate decode scans,
and every prompt in a batch pays the full ``steps`` budget even after
emitting EOS.

``ContinuousDecoder`` batches at TOKEN granularity instead:

- a persistent device-resident **slot pool** — per-layer K/V buffers
  ``[slots, L, H, T, d]`` plus per-slot rng chains — outlives any one
  request (``models/transformer.py SlotKVDecoder``, the params-
  compatible twin whose step advances only active slots);
- requests **JOIN** the step loop after a bucketed prefill
  (``TextGenerator._slot_prefill_fn``; shared-prefix prompts ride
  ``PrefixKVCache`` blocks and prefill only their tails) and **LEAVE**
  at EOS or budget exhaustion, freeing their slot for the next queued
  request mid-flight;
- the loop advances every active slot together in
  ``PATHWAY_DECODE_STEP_BUCKET``-step chunks — ONE compiled dispatch
  per chunk regardless of how many requests ride it (ONE compile
  signature per engine: the step shapes are (slots, T, chunk), all
  static).

**Token identity.**  Every request decoded through the pool yields
exactly the tokens of a solo ``generate([prompt])`` at the same seed —
regardless of join order, batch-mates, or which slot it lands in:

- each slot samples with its OWN rng chain (``PRNGKey(seed)``, one
  split per emitted token — the solo chain; a batch-level chain would
  make tokens depend on batch composition);
- masked K/V attention is width-invariant: key slots past a row's
  frontier carry exact-zero probability, so the pool's fixed buffer
  width ``T`` reproduces the solo decode's prompt+steps-wide buffer
  bit-for-bit;
- a reused slot cannot alias its previous occupant: a joining prefill
  (re)writes every position the request will ever attend, and inactive
  lanes' buffers are bit-frozen by ``SlotKVDecoder``'s select.

**Speculative decode** (``PATHWAY_DECODE_SPEC_K`` ≥ 2): instead of one
token per pool step, each round drafts ``k-1`` proposal tokens per
active slot — mined host-side from the slot's OWN context (prompt +
emitted tokens: RAG prompts quote their retrieved passages, so the
generation frequently re-walks n-grams the prompt already contains),
falling back to a reduced-layer trunk dispatch over the same params
(``TextGenerator._slot_draft_fn``) — then ONE batched verify dispatch
(``_slot_verify_fn``) scores all ``k`` positions pool-wide and accepts
each lane's longest agreeing prefix.  The verify replays EXACTLY the
plain step's sampling (same per-lane rng chain, one split per emitted
token), so acceptance only keeps tokens the plain path would have
drawn: spec-on, spec-off and solo ``generate()`` stay bit-identical at
any temperature, and a faulted draft/verify path degrades to the plain
step chunk — token-identical, counted on
``pathway_serve_degraded_total{reason="speculation_disabled"}``.
Per-round cost stays inside the 2+2 dispatch budget: at most two
dispatches (draft + verify) and two host fetches (draft tokens +
emitted tokens).

**int8 KV pool** (``PATHWAY_DECODE_KV_QUANT=int8``): the slot pool is
stored int8 with per-(layer, head, channel) scales (ops/kv_quant.py),
dequantized inside the fused attention reads — slots×context per HBM
byte doubles, witnessed by the HBM ledger's ``kv_pool`` component and
the ``decode_slots`` exhaustion ETA.

Admission reuses the coalescing machinery from ``scheduler.py``
(``_CoalescerBase``): queue + tickets + deadline-preemption (a request
too tight for any queueing serves SOLO through the legacy path on its
caller's thread) + stop-drain.  Faults (``generator.prefill`` /
``generator.step`` / ``generator.slot_free`` chaos sites) degrade the
AFFECTED request — to an empty flagged result the QA layer's
``extractive_answer`` rung absorbs, or to its tokens emitted so far,
flagged — and never stall the step loop or touch another slot's K/V.

The decode loop's per-chunk dispatch+fetch is intentional (token-level
scheduling IS a host round trip per chunk — amortized over every
active slot), so this module is not marked serve-path for the
hidden-sync budget rules; lock discipline still applies and the pool
lock covers ONLY slot allocation, never a dispatch.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import config, observe
from ..observe import hbm, trace
from ..robust import (
    Deadline,
    EXTRACTIVE_ANSWER,
    inject,
    log_once,
    record_degraded,
    retry_call,
)
from .scheduler import _Batch, _CoalescerBase, _Ticket

__all__ = ["ContinuousDecoder", "DecodeResult", "decode_slots"]


def decode_slots() -> int:
    """Slot-pool size from ``decode.slots`` (default 8): the max
    number of requests decoding concurrently in one step dispatch.
    More slots = more sharing per chunk but a larger resident pool
    (``slots × n_layers × max_len × d_model`` K/V elements × 2)."""
    return config.get("decode.slots")


# queue wait (enqueue → slot join) + per-phase device round trips
_H_QUEUE_WAIT = observe.histogram("pathway_generator_queue_wait_seconds")
_H_PREFILL = observe.histogram("pathway_generator_phase_seconds", phase="prefill")
_H_STEP = observe.histogram("pathway_generator_phase_seconds", phase="step")
# a join's round trip again, apart by where it started: behind a cached prefix (warm) or at token 0 (cold)
_H_JOIN = {start: observe.histogram("pathway_generator_join_seconds", start=start) for start in ("warm", "cold")}
# time-to-last-token per request, admission → completion at the waiter —
# the series the SLO engine's decode_ttlt objective reads
_H_TTLT = observe.histogram("pathway_generator_ttlt_seconds")
# accepted tokens per speculative round, PER LANE — token-valued on the
# seconds axis (observe_s(count): 1 token → the (0.5,1]s bucket, 2 →
# (1,2], 3-4 → (2,4], ...), so the power-of-two buckets resolve small
# counts exactly and _sum/_count recover the true mean acceptance
_H_DRAFT_ACCEPT = observe.histogram(
    "pathway_generator_draft_accepted_tokens"
)
# The engine thread's life, as a chain of sibling spans (observe/spans.py):
# each starts where the one before it ended (``after=self._prev``; the glue
# between two brackets, a lock's wait included, is the later one's), so the
# phases of ``pathway_generator_engine_seconds_total`` sum to the thread's
# wall time and every gap of the device trace lies under a label that names
# one thing.  ``G{x}`` = ``pathway_generator_stage_seconds{stage=x}`` is a
# bracket's wall series.  Siblings, not children, wherever a gap has to be
# labelled: the trace reducer gives a gap to the event that overlaps it most,
# so a child never beats its parent.
_STAGE = "pathway_generator_stage_seconds"
_ENGINE = "pathway_generator_engine_seconds_total"
_E = {
    phase: {"counter": observe.counter(_ENGINE, phase=phase), "hist": observe.histogram(_STAGE, stage=phase)}
    for phase in (
        "prefill_operands",  # slots popped, host arrays, pad rows, rng rows
        "prefill_prefix",    # the cached blocks joined into the prefix operands
        "prefill_call",      # compiled lookup, transfers, the jit call, the rng scatter
        "prefill_fetch",     # first tokens and their statistics on the host
        "prefill_settle",    # pool rebind, prefix capture, slot states
        "step_operands",     # the chunk's host arrays and their transfers
        "step_dispatch",     # the jit call
        "step_fetch",        # the chunk's tokens on the host: the wait for the device
        "step_replay",       # the per-slot replay, every leave and its resolve
    )
}
# a request's tokenising and prefix walk (its series kept the name it had), and
# the wait on ``_cond`` with nothing live and nothing queued (no series)
_E["join_host"] = {"counter": observe.counter(_ENGINE, phase="join_host"), "hist": observe.histogram(_STAGE, stage="join")}
_E["idle"] = {"counter": observe.counter(_ENGINE, phase="idle")}
# brackets outside the chain: the parent of a join's operands / prefix / call
# (it keeps the label and the series the ledger has history of; its three
# children sum to it) and the prefix tier's admission inside ``settle``
_S_PREFILL_DISPATCH = {"hist": observe.histogram(_STAGE, stage="prefill_dispatch")}
_S_PREFIX_ADMIT = {"hist": observe.histogram(_STAGE, stage="prefix_admit")}
# a request's life by phase, one observation each as it leaves the pool; the
# six sum to its ``pathway_generator_ttlt_seconds`` observation by construction:
# enqueue -> the join that took it began / -> its lane went live / live -> leave,
# split into the engine's time in OTHER requests' joins (what chunked prefill
# could give back), in step chunks, and the rest (replay, leaves, collect) /
# resolved -> the rider is back from its wait
_PHASES = ("slot_wait", "join", "stalled", "stepping", "host", "wake")
_H_REQUEST = {phase: observe.histogram("pathway_generator_request_seconds", phase=phase) for phase in _PHASES}
# what a request's meta carries of every emitted token (models/looped.py
# ``token_stats``): its logit, the log-sum-exp, the top ids and logits
_STAT_KEYS = ("logit", "lse", "top_ids", "top_logits")
# the most tokens one join program carries (rows x padded suffix): a cohort
# over it is cut into joins of fewer rows, one after another.  Sixteen rows of
# a few hundred tokens pass whole; sixteen prompts of thousands of tokens do
# not become one program of a hundred thousand
JOIN_TOKEN_BUDGET = 8192


class DecodeResult(str):
    """One request's generated text plus ladder metadata — a ``str``
    subclass so every existing caller that treats generator output as a
    string keeps working; ``.degraded`` / ``.meta`` follow the
    ``ServeResult`` convention (tuple of rung flags, JSON-able extras)."""

    def __new__(
        cls,
        text: str = "",
        degraded: Sequence[str] = (),
        meta: Optional[Dict[str, Any]] = None,
    ):
        self = super().__new__(cls, text)
        deduped: List[str] = []
        for flag in degraded:
            if flag not in deduped:
                deduped.append(flag)
        self.degraded = tuple(deduped)
        self.meta = dict(meta or {})
        if self.degraded and "degraded_reasons" not in self.meta:
            self.meta["degraded_reasons"] = list(self.degraded)
        return self

    @property
    def ok(self) -> bool:
        return not self.degraded


class _SlotState:
    """Host bookkeeping for one occupied slot (the authoritative K/V
    and rng state live device-side in the pool arrays)."""

    __slots__ = (
        "req", "budget", "temperature", "seed", "eos", "tokens", "pos",
        "left", "t_join_ns", "prompt_ids", "t_admit_ns", "stats", "prefix",
        "t_live_ns", "join_ns0", "step_ns0",
    )

    def __init__(self, req, budget: int, temperature: float, seed: int, eos: int):
        self.req = req
        self.budget = budget
        self.temperature = temperature
        self.seed = seed
        self.eos = eos
        self.tokens: List[int] = []
        self.pos = 0     # next K/V write position (= current length)
        self.left = 0    # decode-step tokens still allowed
        # prompt token ids (host copy) — the n-gram draft mining corpus
        self.prompt_ids: List[int] = []
        # when the join that took the request began, when its first token
        # reached the host (both ``perf_counter_ns``) and, per fetch, the
        # emitted tokens' stats (``_STAT_KEYS`` arrays)
        self.t_admit_ns = 0
        self.t_join_ns = 0
        self.stats: List[Tuple[np.ndarray, ...]] = []
        self.prefix = 0  # prompt tokens its join took from the prefix tier
        # when its lane went live (its join's ``settle`` ended; 0: it left
        # inside it, or the recorder is off) and the engine's running totals
        # of join and step-chunk time at that instant: what ``_resolve``
        # subtracts to split the request's life
        self.t_live_ns = 0
        self.join_ns0 = 0
        self.step_ns0 = 0


def _spent_deadline() -> Deadline:
    """An already-expired deadline: armed ``hang`` faults on bookkeeping
    sites release immediately instead of wedging the step loop (the
    same contract the tracing layer uses for its chaos sites)."""
    return Deadline(0.0)


class ContinuousDecoder(_CoalescerBase):
    """Continuous-batching decode engine over one ``TextGenerator``.

    ``submit(prompt, max_new_tokens=, temperature=, seed=, deadline=)``
    returns a ticket resolving to a :class:`DecodeResult` whose tokens
    are identical to ``generator.generate([prompt], ...)`` solo at the
    same seed.  ``generate(prompts, ...)`` is the blocking batch
    convenience.  One scheduler thread owns the pool: it joins queued
    requests into free slots (prefill), advances every active slot in
    compiled step chunks, and resolves tickets as requests leave at
    EOS/budget — slots free mid-flight, so a stream of short requests
    rides alongside one long request instead of queueing behind it.
    """

    _degrade_empty = False
    _metric_prefix = "pathway_generator_queue"

    def __init__(
        self,
        generator,
        slots: Optional[int] = None,
        step_bucket: Optional[int] = None,
        name: Optional[str] = None,
        window_us: Optional[float] = None,
        autostart: bool = True,
        eos_id: Any = "inherit",
        kv_width: Optional[int] = None,
        spec_k: Optional[int] = None,
        draft: Optional[str] = None,
        kv_quant: Optional[str] = None,
    ):
        import jax.numpy as jnp

        from ..models.generator import (
            decode_draft_layers,
            decode_draft_source,
            decode_kv_quant,
            decode_spec_k,
            decode_step_bucket,
        )

        self.generator = generator
        cfg = generator.config
        self.slots = max(1, int(slots or decode_slots()))
        self.chunk = max(1, int(step_bucket or decode_step_bucket()))
        self.eos_id = generator.eos_id if eos_id == "inherit" else eos_id
        # speculative decode + KV-quant knobs — constructor args win,
        # env (PATHWAY_DECODE_SPEC_K / _DRAFT / _KV_QUANT) is the default
        self.spec_k = (
            decode_spec_k() if spec_k is None
            else max(0, min(int(spec_k), 16))
        )
        self.draft_source = (
            decode_draft_source() if draft is None
            else (draft if draft in ("auto", "ngram", "trunk") else "auto")
        )
        self.kv_quant = (
            decode_kv_quant() if kv_quant is None
            else ("int8" if kv_quant == "int8" else "bf16")
        )
        self._quant = self.kv_quant == "int8"
        check = getattr(generator, "check_decode_options", None)
        if check is not None:
            check(self.spec_k, self.kv_quant)  # a family that cannot, refuses here
        self._draft_layers = decode_draft_layers(cfg.n_layers)
        # cooldown: after a draft/verify fault degrades a round to the
        # plain step, skip speculation for this many rounds so a
        # persistent fault doesn't pay the retry ladder on every chunk
        self._spec_hold = 0
        self._draft_sources = {"ngram": 0, "trunk": 0, "none": 0}
        # cross-request suffix corpus (the "prefix-cache blocks" half of
        # the n-gram well): every cleanly finished request feeds its full
        # token stream (prompt + emitted) into an n-gram → continuation
        # index, so a repeated or near-duplicate request drafts its whole
        # continuation from the previous run's output.  Greedy repeats
        # verify-accept wholesale; per-request sampling seeds reject
        # safely.  Engine-loop-thread only — no lock.
        self._suffix_idx: Dict[Tuple[int, ...], List[int]] = {}
        # pool buffer width: defaults to the position-embedding bound —
        # any prompt + budget the generator accepts fits (prompts are
        # tokenized to max_len - max_new_tokens), and masked attention
        # makes the width numerically invisible.  ``kv_width`` (or
        # ``PATHWAY_DECODE_KV_WIDTH``) narrows the pool when the served
        # workload is known-short: attention cost and per-step buffer
        # traffic scale with the width, and a request that does not fit
        # (prompt + budget > width) simply serves solo
        if kv_width is None:
            kv_width = config.get("decode.kv_width")
        self._T = min(cfg.max_len, kv_width) if kv_width else cfg.max_len
        # one cache row per (loop step, layer): the architecture says how
        # many, how many key/value heads and how wide a head; the generator,
        # which kinds of rows (one rectangle ``T`` wide, or full layers
        # beside window layers' rings: ``(kind, depth, rows)`` each)
        self._layout = generator.kv_pool_layout(self._T)
        self._depth = sum(depth for _, depth, _ in self._layout)
        self._heads, self._head_dim = getattr(cfg, "n_kv_heads", cfg.n_heads), cfg.head_dim
        self._loop_steps = cfg.total_ut_steps
        # choices of expert one forwarded token makes (0: no routed experts)
        self._expert_choices = cfg.n_layers * getattr(cfg, "experts_per_token", 0)
        # what a slot holds besides rows, whatever its length (models/hybrid.py: recurrent state)
        self._state_layout = generator.state_layout()
        self._state_bytes = {  # a slot's, by kind
            kind: layers * int(np.prod(shape)) * np.dtype(dtype).itemsize for kind, layers, shape, dtype in self._state_layout
        }
        if self._quant:
            # int8 pool + per-(layer, head, channel) stored scales — the
            # scales are derived from the generator's params off the
            # engine locks (generator.kv_pool_scales memoizes them)
            self._kscale, self._vscale = generator.kv_pool_scales()
            pool_dtype = jnp.int8
        else:
            self._kscale = self._vscale = None
            pool_dtype = cfg.dtype
        self._pool_dtype = pool_dtype
        self._alloc_pool()
        self._rngs = jnp.zeros((self.slots, 2), jnp.uint32)
        # the last span of the engine thread's chain (module comment at ``_E``)
        self._prev: Any = None
        # running totals from the chain's clock reads, in nanoseconds: the
        # engine's time in joins (a request's host prep up to its group's
        # ``settle``) and in step chunks (operands up to the fetch), and where
        # the join now running is booked from
        self._join_ns = 0
        self._step_ns = 0
        self._join_mark_ns = 0
        # seconds a prefill ran while decode lanes were live and waiting,
        # and the exit gate's mass per loop step over emitted tokens
        self._stalled_s = 0.0
        self._exit_mass_sum = np.zeros(self._loop_steps)
        self._exit_mass_n = 0
        # slot allocation/free under the pool lock; dispatches NEVER
        # hold it (the analyzer's slot-pool lock convention)
        self._pool_lock = threading.Lock()
        self._free: List[int] = list(range(self.slots))
        self._active: Dict[int, _SlotState] = {}
        self.pool_stats: Dict[str, int] = {
            "tokens_prefill": 0,   # prompt tokens the prefill computed
            "tokens_decode": 0,    # tokens emitted (prefill sample + steps)
            "finished": 0,         # requests that left at EOS/budget
            "evicted": 0,          # requests resolved degraded (fault/deadline)
            "quarantined": 0,      # slots retired by slot_free faults
            "chunks": 0,           # step-chunk dispatches
            "steps": 0,            # decode steps executed (chunks × chunk)
            "occupancy_sum": 0,    # Σ active slots per chunk (avg = /chunks)
            "spec_rounds": 0,      # speculative draft→verify rounds
            "spec_fallbacks": 0,   # rounds degraded to the plain step
            "draft_offered": 0,    # draft tokens proposed (Σ lanes × k-1)
            "draft_accepted": 0,   # draft tokens accepted by the verify
            "loop_passes": 0,      # loop steps executed x tokens forwarded
            "tokens_forwarded": 0, # tokens run through the stack (prefilled + stepped)
            "joins": 0,            # join programs run
            "join_tokens": 0,      # tokens they carried (rows x padded suffix)
            "join_tokens_kernel": 0,  # of those, tokens a flash kernel attended (generator.join_attention)
            "join_splits": 0,      # cohorts cut to JOIN_TOKEN_BUDGET
            # recurrent state beside the rows (models/hybrid.py): prompt tokens a join
            # skipped by starting from a restored snapshot, snapshots the prefix tier
            # admitted from joins, and their bytes
            "state_restored_tokens": 0,
            "state_snapshots_admitted": 0,
            "state_snapshot_bytes": 0,
            # routed experts (models/moe.py), by phase: (token, expert) choices
            # made, of those the pairs that fell to an expert held here (all of them,
            # unless the family holds a range), experts that got a token summed over
            # (program, layer), and the busiest expert's tokens summed likewise
            **{f"{what}_{phase}": 0 for what in ("expert_tokens", "expert_pairs_held", "experts_touched", "expert_load_max")
               for phase in ("prefill", "decode")},
        }
        super().__init__(
            name=name or f"decode-{observe.next_id()}",
            window_us=window_us,
            max_batch=self.slots,
            autostart=autostart,
        )
        # HBM ledger (observe/hbm.py): the slot KV pool is the
        # generator-side HBM owner; slot exhaustion-ETA derives from the
        # observed join rate vs frees at sample time
        hbm.track("decode", self, lambda d: d.hbm_components())
        hbm.track_resource(
            "decode_slots",
            self,
            lambda d: d.slots - len(d._free),
            lambda d: d.slots,
        )

    def _alloc_pool(self) -> None:
        self._pk, self._pv = self.generator.alloc_pool(self.slots, self._T, self._pool_dtype)

    def _pool_buffers(self) -> List[Any]:
        import jax

        return jax.tree_util.tree_leaves((self._pk, self._pv))

    def _pool_lost(self) -> bool:
        """A program that donates the pool and then fails leaves it deleted:
        nothing in flight can go on.  True if the pool had to be made anew."""
        if not any(buf.is_deleted() for buf in self._pool_buffers()):
            return False
        self._alloc_pool()
        return True

    def kv_bytes_per_token(self) -> int:
        """Cache bytes one token of one sequence holds: K and V, every
        (loop step, layer) row."""
        return 2 * self._depth * self._heads * self._head_dim * np.dtype(self._pool_dtype).itemsize

    def state_bytes_per_slot(self) -> int:
        """Bytes a slot holds besides its rows, whatever its length: a
        family's recurrent state, every layer that has one (0 without)."""
        return sum(self._state_bytes.values())

    def hbm_bytes(self) -> int:
        """Device bytes of the persistent slot pool (K + V buffers +
        per-slot rng chains) — ``.nbytes`` metadata, never a sync."""
        return sum(
            int(getattr(buf, "nbytes", 0))
            for buf in (*self._pool_buffers(), self._rngs)
        )

    def hbm_components(self) -> Dict[str, int]:
        """HBM-ledger components: the pool itself plus, under int8, the
        stored dequant scales — so the ledger shows the quantized pool's
        true footprint (int8 pool bytes + the tiny f32 scale arrays)
        next to the bf16 baseline's."""
        comp = {"kv_pool": self.hbm_bytes()}
        if self._state_layout:  # of the pool's bytes, the part that is state and not rows
            comp["state_pool"] = self.slots * self.state_bytes_per_slot()
            comp["kv_pool"] -= comp["state_pool"]
        if self._quant:
            comp["kv_scales"] = sum(
                int(getattr(s, "nbytes", 0))
                for s in (self._kscale, self._vscale)
            )
        return comp

    # -- public surface ------------------------------------------------------
    def submit(
        self,
        prompt: str,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        seed: int = 0,
        deadline: Optional[Deadline] = None,
        eos_id: Any = "inherit",
    ) -> _Ticket:
        if deadline is None:
            deadline = Deadline.from_env()
        eos = self.eos_id if eos_id == "inherit" else eos_id
        ctx = trace.start_trace("generate.request", deadline=deadline)
        item = (
            str(prompt),
            int(max_new_tokens),
            float(temperature),
            int(seed),
            -1 if eos is None else int(eos),
        )
        return self._admit([item], None, deadline, trace_ctx=ctx)

    def generate(
        self,
        prompts: Sequence[str],
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        seed: int = 0,
        deadline: Optional[Deadline] = None,
    ) -> List[str]:
        tickets = [
            self.submit(
                p, max_new_tokens, temperature, seed, deadline=deadline
            )
            for p in prompts
        ]
        return [t() for t in tickets]

    __call__ = generate

    # -- scheduler thread: the continuous step loop --------------------------
    def _run(self) -> None:
        while True:
            reqs: Optional[List[Any]] = None
            try:
                reqs = self._collect_joins()
                if reqs is None:
                    return
                if reqs:
                    self._join_group(reqs)
                if self._active:
                    if self._spec_ready():
                        self._spec_round()
                    else:
                        self._step_chunk()
            except Exception as exc:  # pragma: no cover - defensive
                # the loop must outlive any one bad iteration: resolve
                # every in-flight request with what it has, and any
                # popped-but-not-joined request with the error — every
                # admitted ticket resolves, no waiter hangs
                log_once(
                    f"decode.run:{type(exc).__name__}",
                    "continuous decode iteration failed (%r); degrading "
                    "in-flight requests and continuing",
                    exc,
                )
                self._evict_all(exc)
                for r in reqs or []:
                    if not r.event.is_set():
                        self._resolve_with_error(r, exc)

    def _collect_joins(self) -> Optional[List[Any]]:
        """Pop queued requests up to the free-slot count.  Blocks only
        when the pool is idle; with active slots it returns immediately
        so the step loop keeps advancing.  Returns None when stopped
        AND fully drained (queue empty, pool empty)."""
        with self._cond:
            if not self._active and self._running and not self._queue:
                # under the lock it waits on: lock, then span
                with observe.span("gen.idle", after=self._prev, **_E["idle"]) as self._prev:
                    while self._running and not self._queue:
                        self._cond.wait(0.1)
            if not self._queue and not self._active and not self._running:
                return None
            free = len(self._free)
            # every slot quarantined and nothing in flight: fall back to
            # per-request solo dispatches so admitted tickets still
            # resolve (the engine degrades to call-level batching)
            limit = free if (free or self._active) else len(self._queue)
            take: List[Any] = []
            while self._queue and len(take) < limit:
                r = self._queue.popleft()
                self._queued_items -= len(r.items)
                take.append(r)
            return take

    # -- join ---------------------------------------------------------------
    def _join_group(self, reqs: List[Any]) -> None:
        """Admit a cohort of queued requests: host prep (tokenize +
        prefix-cache walk) per request, then requests whose prefill
        shares a compile shape (suffix length, prefix split) batch into
        ONE prefill dispatch — the bucketed-join analog of the serve
        scheduler's coalesced stage-1 batches."""
        gen = self.generator
        cfg = gen.config
        ready: List[dict] = []
        # when this cohort's join began: a rider's slot wait ends here and the
        # engine's join time is booked from here (``_prefill_group``)
        t_join_ns = self._join_mark_ns = time.perf_counter_ns()
        for req in reqs:
            text, steps, temp, seed, eos = req.items[0]
            _H_QUEUE_WAIT.observe_ns(
                time.perf_counter_ns() - req.t_enqueue_ns
            )
            if req.deadline is not None and req.deadline.expired():
                self.pool_stats["evicted"] += 1
                record_degraded(EXTRACTIVE_ANSWER)
                self._resolve(
                    req,
                    DecodeResult(
                        "", degraded=(EXTRACTIVE_ANSWER,),
                        meta={"reason": "deadline_before_join"},
                    ),
                )
                continue
            try:
                # host prep — tokenize + prefix-cache walk — off every
                # lock.  Per-request guard: a bad request (e.g. a budget
                # larger than the model's max_len) must resolve ITS
                # ticket degraded, never hang the cohort's
                L_budget = cfg.max_len - steps
                if L_budget <= 0:
                    raise ValueError(
                        f"max_new_tokens={steps} leaves no prompt budget "
                        f"(max_len={cfg.max_len})"
                    )
                with observe.span("gen.join", after=self._prev, **_E["join_host"]) as self._prev:
                    ids, mask = gen.tokenizer.encode_batch(
                        [text], max_length=L_budget
                    )
                    ids = np.asarray(ids)
                    n = int(np.asarray(mask).sum())
                    fits = ids.shape[1] + steps <= self._T
                    P, matches = 0, []
                    if fits and gen.kv_cache is not None:
                        P, matches = gen._cached_prefix(
                            ids, np.asarray([n], np.int32), 1
                        )
                if not fits:
                    # narrowed pool (kv_width): this request does not fit
                    # — serve it solo through the legacy path instead
                    self._dispatch_batch([req], solo=True)
                    continue
            except Exception as exc:
                log_once(
                    f"decode.join:{type(exc).__name__}",
                    "continuous-decode join prep failed (%r); degrading "
                    "the request to an empty flagged result",
                    exc,
                )
                self.pool_stats["evicted"] += 1
                record_degraded(EXTRACTIVE_ANSWER)
                self._resolve(
                    req,
                    DecodeResult(
                        "", degraded=(EXTRACTIVE_ANSWER,),
                        meta={"error": repr(exc)},
                    ),
                )
                continue
            ready.append(dict(
                req=req, ids=ids, n=n, P=P,
                match=matches[0] if matches else None,
                L_sfx=ids.shape[1] - P, steps=steps, temp=temp,
                seed=seed, eos=eos, t_join_ns=t_join_ns,
            ))
        with self._pool_lock:
            free = len(self._free)
        if len(ready) > free:
            # more admitted than free slots (quarantine exhaustion):
            # the overflow serves solo so every ticket still resolves
            for rec in ready[free:]:
                self._dispatch_batch([rec["req"]], solo=True)
            ready = ready[:free]
        # cohort grouping: one batched prefill per PREFIX split; members
        # with shorter suffixes are right-padded to the group width (pad
        # positions carry garbage K/V that the decode overwrites before
        # it could ever be attended — causal masking + write-before-read)
        groups: Dict[int, List[dict]] = {}
        for rec in ready:
            groups.setdefault(rec["P"], []).append(rec)
        for P, grp in groups.items():
            # quantize the cohort suffix width UP to a power-of-two ×16
            # bucket (capped at the pool width) so the prefill shape
            # lattice stays O(log²) — compile churn, not correctness,
            # is the enemy here: pad positions are write-before-read
            L = max(r["L_sfx"] for r in grp)
            L_pad = 16
            while L_pad < L:
                L_pad *= 2
            L_sfx = min(L_pad, self._T - P)
            # the cohort, cut to the token budget: joins of as many rows as
            # the widest batch bucket under it holds, one after another
            rows = self._join_rows(L_sfx)
            if len(grp) > rows:
                self.pool_stats["join_splits"] += 1
            for a in range(0, len(grp), rows):
                self._prefill_group(grp[a : a + rows], L_sfx, P)

    @staticmethod
    def _join_rows(L_sfx: int) -> int:
        """The widest join batch bucket (1, 4, 16, ...) whose rows of
        ``L_sfx`` tokens stay inside ``JOIN_TOKEN_BUDGET``; one row always goes."""
        rows = 1
        while rows * 4 * L_sfx <= JOIN_TOKEN_BUDGET:
            rows *= 4
        return rows

    def _prefill_group(self, grp: List[dict], L_sfx: int, P: int) -> None:
        import jax

        gen = self.generator
        cfg = gen.config
        n_real = len(grp)
        n_live = len(self._active)  # decode lanes this prefill keeps waiting
        with self._pool_lock:
            slots_real = [self._free.pop() for _ in range(n_real)]
        # batch bucket: the model batch buckets (1, 4, 16, ...), so a
        # burst of joins costs O(log) compile signatures per cohort size
        B = 1
        while B < n_real:
            B *= 4
        attention = gen.join_attention(L_sfx)
        # where this join's scan hands back its state for the prefix tier, and what it restores
        snapshot_at = gen.snapshot_positions(P, L_sfx)
        restored = P * n_real if self._state_layout else 0
        try:
            # the parent of ``operands`` / ``prefix`` / ``call`` (``_dispatch_prefill``)
            with observe.span(
                "gen.prefill.dispatch", rows=n_real, batch=B,
                suffix_tokens=L_sfx, prefix_tokens=P, join_tokens=B * L_sfx, attention=attention,
                state_restored_tokens=restored, snapshots=len(snapshot_at) * n_real, **_S_PREFILL_DISPATCH,
            ) as dispatch:
                # the round trip runs from this bracket's start to the fetch's
                # end, on the spans' own reads; with the recorder off the
                # engine reads the clock itself, for ``_stalled_s`` and
                # ``meta["t_first_token"]``
                t0 = dispatch.t0_ns or time.perf_counter_ns()
                pk, pv, toks, rngs_all, extra = self._dispatch_prefill(
                    B, L_sfx, P, slots_real, grp,
                    self._batch_deadline([rec["req"] for rec in grp]),
                )
            with observe.span("gen.prefill.fetch", after=self._prev, **_E["prefill_fetch"]) as self._prev:
                # the prefill JOIN's one deliberate host fetch: first tokens
                # (and what the program read off their logits) must reach the
                # riders' tickets before the step loop takes over
                firsts = np.asarray(toks)
                # the prompt's keys and values, where a family hands them to the
                # prefix tier beside the pool: they stay on the device
                prompt_kv = extra.pop("prompt_kv", None)
                prompt_state = extra.pop("prompt_state", None)
                extra = jax.device_get(extra)
            fetch = self._prev
            t1 = fetch.t1_ns or time.perf_counter_ns()
            _H_PREFILL.observe_ns(t1 - t0)
            _H_JOIN["warm" if P else "cold"].observe_ns(t1 - t0)
            if n_live:
                self._stalled_s += (t1 - t0) * 1e-9
        except Exception as exc:
            for slot in slots_real:
                self._free_slot(slot)
            if self._pool_lost():
                self._evict_all(exc)
            log_once(
                f"decode.prefill:{type(exc).__name__}",
                "continuous-decode prefill failed (%r); degrading the "
                "affected request(s) to empty flagged results",
                exc,
            )
            for rec in grp:
                self.pool_stats["evicted"] += 1
                record_degraded(EXTRACTIVE_ANSWER)
                self._resolve(
                    rec["req"],
                    DecodeResult(
                        "", degraded=(EXTRACTIVE_ANSWER,),
                        meta={"error": repr(exc)},
                    ),
                )
            return
        joined: List[_SlotState] = []
        with observe.span("gen.prefill.settle", after=fetch, **_E["prefill_settle"]) as self._prev:
            self._pk, self._pv, self._rngs = pk, pv, rngs_all
            pk_now, pv_now = self._pk, self._pv
            self.pool_stats["joins"] += 1
            self.pool_stats["join_tokens"] += B * L_sfx
            if attention == "kernel":
                self.pool_stats["join_tokens_kernel"] += B * L_sfx
            self._note_expert_load("prefill", extra, sum(rec["n"] - P for rec in grp))
            self.pool_stats["state_restored_tokens"] += restored
            for j, rec in enumerate(grp):
                req = rec["req"]
                slot = slots_real[j]
                first = int(firsts[j])
                # prefix capture: admit the prompt's uncached full blocks as
                # async device slices of THIS pool version (functional
                # arrays — later steps never mutate them)
                if gen.kv_cache is not None:
                    blk = gen.kv_cache.block
                    matched, _blocks, chain = rec["match"]
                    if prompt_kv is not None:
                        # per row, the suffix's blocks as the program cut them:
                        # block ``jb`` of the prompt is the suffix's ``jb - P / blk``-th;
                        # the block that ends where the join handed back its state carries it
                        def capture(jb, _j=j):
                            kv = prompt_kv[0][_j][jb - P // blk], prompt_kv[1][_j][jb - P // blk]
                            if (jb + 1) * blk in snapshot_at:
                                kv += prompt_state[_j][snapshot_at.index((jb + 1) * blk)]
                            return kv
                    elif self._quant:
                        # int8 pool: captured blocks dequantize back to the
                        # cache's bf16 convention; a warm join re-quantizes
                        # them — idempotent (ops/kv_quant.py), so warm pool
                        # bytes match cold ones bit-for-bit
                        from ..ops.kv_quant import dequantize_kv

                        def capture(jb, _s=slot):
                            return (
                                dequantize_kv(
                                    pk_now[_s, :, jb * blk : (jb + 1) * blk],
                                    self._kscale, cfg.dtype,
                                ),
                                dequantize_kv(
                                    pv_now[_s, :, jb * blk : (jb + 1) * blk],
                                    self._vscale, cfg.dtype,
                                ),
                            )
                    else:
                        def capture(jb, _s=slot):
                            return (
                                pk_now[_s, :, jb * blk : (jb + 1) * blk],
                                pv_now[_s, :, jb * blk : (jb + 1) * blk],
                            )
                    filed = dict(gen.kv_cache.stats_state)
                    with observe.span("gen.prefix.admit", blocks=len(chain) - matched // blk, **_S_PREFIX_ADMIT):
                        gen.kv_cache.admit(chain, matched // blk, capture)
                    self.pool_stats["state_snapshots_admitted"] += gen.kv_cache.stats_state["snapshots"] - filed["snapshots"]
                    self.pool_stats["state_snapshot_bytes"] += gen.kv_cache.stats_state["bytes"] - filed["bytes"]
                    gen.kv_cache.note_prefill(reused=P, computed=rec["n"] - P)
                self.pool_stats["tokens_prefill"] += rec["n"] - P
                self.pool_stats["tokens_decode"] += 1
                self.pool_stats["tokens_forwarded"] += rec["n"] - P
                self.pool_stats["loop_passes"] += (rec["n"] - P) * self._loop_steps
                if req.trace is not None:
                    req.trace.add_span(
                        "decode.prefill", t0, t1,
                        slot=slot, prefix_tokens=P, suffix_tokens=L_sfx,
                        join_batch=n_real,
                    )
                state = _SlotState(
                    req, rec["steps"], rec["temp"], rec["seed"], rec["eos"]
                )
                state.tokens = [first]
                state.t_join_ns = t1
                state.t_admit_ns = rec["t_join_ns"]
                state.stats.append(tuple(extra[k][j : j + 1] for k in _STAT_KEYS))
                self._note_exit_mass(extra, (j,))
                state.pos = rec["n"]
                state.prefix = P
                state.left = rec["steps"] - 1
                # host copy of the prompt ids: the draft miner's corpus
                state.prompt_ids = [int(t) for t in rec["ids"][0, : rec["n"]]]
                self._active[slot] = state
                joined.append(state)
                if (rec["eos"] >= 0 and first == rec["eos"]) or state.left <= 0:
                    self._leave(slot, state)
        t_live = self._prev.t1_ns
        if t_live:
            # the group's lanes are live from here; what the engine spends in
            # joins after this instant is what each of them is stalled by
            self._join_ns += t_live - self._join_mark_ns
            self._join_mark_ns = t_live
            for state in joined:
                state.t_live_ns, state.join_ns0, state.step_ns0 = t_live, self._join_ns, self._step_ns

    def _dispatch_prefill(self, B, L_sfx, P, slots_real, grp, deadline):
        """One prefill dispatch of ``B`` rows, the first ``len(slots_real)``
        of them real (``grp``, their records): the host arrays and pad rows,
        the cached prefix operands, the compiled-fn lookup, the call; three
        chained spans.  Returns the new pools and rng chains (the caller
        rebinds them once the fetch has succeeded), the first tokens and what
        was read off their logits.  Every operand's shape follows from
        ``(B, L_sfx, P)`` alone, so ``warm`` covers what a join will run."""
        import jax
        import jax.numpy as jnp

        gen = self.generator
        n_real = len(slots_real)
        with observe.span("gen.prefill.operands", after=self._prev, **_E["prefill_operands"]) as self._prev:
            suffix = np.zeros((B, L_sfx), np.int32)
            n_len = np.zeros(B, np.int32)
            temps = np.zeros(B, np.float32)
            for j, rec in enumerate(grp):
                row = rec["ids"][0, P:]
                suffix[j, : row.shape[0]] = row
                n_len[j] = rec["n"]
                temps[j] = rec["temp"]
            # real rows first; a pad row repeats the first row (its slot, its
            # ids, its prefix, its seed), so it writes the same values again and
            # no slot index is ever out of bounds.  With no real row at all
            # (``warm``) every row names slot 0 of the idle pool: a join rewrites
            # every position it will attend before it attends it
            fill = lambda rows, blank: list(rows) + [rows[0] if n_real else blank] * (B - n_real)  # noqa: E731
            slot_rows = np.asarray(fill(slots_real, 0), np.int32)
            if n_real:
                suffix[n_real:], n_len[n_real:], temps[n_real:] = suffix[0], n_len[0], temps[0]
            rng_rows = np.stack(fill([np.asarray(jax.random.PRNGKey(rec["seed"])) for rec in grp], np.zeros(2, np.uint32)))
            n_blk = P // gen.kv_cache.block if P else 0
            blank = []  # a row of zero blocks: only ``warm`` has no real row to repeat
            if n_blk and not n_real:
                zero = jnp.zeros((self._depth, P // n_blk, self._heads, self._head_dim), gen.config.dtype)
                blank = [(zero, zero)] * (n_blk - 1) + [(zero, zero, *gen.blank_snapshot())]
            blocks = fill([rec["match"][1] if P else [] for rec in grp], blank)
        with observe.span("gen.prefill.prefix", after=self._prev, **_E["prefill_prefix"]) as self._prev:
            prefix_k, prefix_v = gen.slot_prefix(
                blocks, n_blk, (self._depth, P, self._heads, self._head_dim)
            )
        with gen._lock:  # between two brackets: no span encloses a lock
            fn = gen._slot_prefill_fn(
                self.slots, self._T, B, L_sfx, P, self._quant
            )
        with observe.span("gen.prefill.call", after=self._prev, **_E["prefill_call"]) as self._prev:
            slot_arr = jnp.asarray(slot_rows)
            sc = (self._kscale, self._vscale) if self._quant else ()
            # pathway: allow(recompile-hazard): prefill shapes are bucketed upstream — the tokenizer pads suffix length to /16 multiples, the prefix split is a power-of-two block multiple (PrefixKVCache.bucket_tokens) and the join batch is a power-of-two bucket; the census test bounds the signature set
            pk, pv, toks, rngs_out, extra = retry_call(
                "generator.prefill",
                fn,
                gen.params,
                self._pk,
                self._pv,
                slot_arr,
                jnp.asarray(suffix),
                jnp.asarray(n_len),
                prefix_k,
                prefix_v,
                jnp.asarray(rng_rows),
                jnp.asarray(temps),
                *sc,
                deadline=deadline,
            )
            rngs = self._rngs.at[slot_arr].set(rngs_out)
        return pk, pv, toks, rngs, extra

    def warm(self, prompt_tokens: Tuple[int, int], prefix_tokens: Sequence[int] = (0,)) -> int:
        """Compile and run once every program that joins and steps of prompts
        of ``prompt_tokens`` = (fewest, most) tokens can reach, with cached
        prefixes of ``prefix_tokens`` tokens: each join batch bucket at each
        suffix bucket, all rows padding (they write slot 0 of the idle pool,
        which its next occupant writes again before it reads), and the step
        chunk with no lane live.  For a server's
        start-up, before traffic: a shape first met in flight stalls every
        live lane for its compile.  Returns the programs run."""
        if self._active:
            raise RuntimeError("warm() runs on an idle pool: before traffic, not under it")
        lo, hi = prompt_tokens
        padded = sorted({min(self._T, -(-n // 16) * 16) for n in range(lo, hi + 1)})
        sizes = [1]
        while sizes[-1] < self.slots:
            sizes.append(sizes[-1] * 4)
        shapes = set()
        for P in prefix_tokens:
            for width in padded:
                L_pad = 16
                while L_pad < width - P:
                    L_pad *= 2
                L_sfx = min(L_pad, self._T - P)
                shapes.update((B, L_sfx, P) for B in sizes if B <= self._join_rows(L_sfx))
        for B, L_sfx, P in sorted(shapes):
            pk, pv, toks, rngs, _ = self._dispatch_prefill(B, L_sfx, P, [], [], None)
            np.asarray(toks)  # start-up, before traffic: wait for each program to have run
            self._pk, self._pv, self._rngs = pk, pv, rngs
        self._step_chunk()
        # these brackets ran on the caller's thread beside the engine's idle
        # one: the engine's chain does not go on from them
        self._prev = None
        return len(shapes) + 1

    # -- decode step chunk ---------------------------------------------------
    def _step_chunk(self) -> None:
        import jax
        import jax.numpy as jnp

        gen = self.generator
        S = self.slots
        with gen._lock:  # ahead of the chunk's brackets: no span encloses a lock
            fn = gen._slot_step_fn(S, self._T, self.chunk, self._quant)
        bctx = None
        try:
            with observe.span("gen.step.operands", after=self._prev, **_E["step_operands"]) as self._prev:
                tok = np.zeros(S, np.int32)
                pos = np.zeros(S, np.int32)
                act = np.zeros(S, bool)
                left = np.zeros(S, np.int32)
                temps = np.zeros(S, np.float32)
                eos = np.full(S, -1, np.int32)
                for s, st in self._active.items():
                    tok[s] = st.tokens[-1]
                    pos[s] = st.pos
                    act[s] = True
                    left[s] = st.left
                    temps[s] = st.temperature
                    eos[s] = st.eos
                sc = (self._kscale, self._vscale) if self._quant else ()
                n_steps = self.chunk
                if gen.family is not None:
                    # a decoder family's step program takes the number of steps to
                    # run: no further than the nearest budget's end, so a lane leaves
                    # (and the next request joins) at the step it finishes
                    n_steps = min([self.chunk] + [st.left for st in self._active.values()])
                    sc = (jnp.int32(max(n_steps, 1)),)
                deadline = self._batch_deadline(
                    [st.req for st in self._active.values()]
                )
                riders = [
                    st for st in self._active.values() if st.req.trace is not None
                ]
                if riders:
                    # ONE batch trace per step chunk, linked from every traced
                    # rider — the decode-loop analog of the coalescing
                    # scheduler's batch/link-span pattern
                    bctx = trace.start_trace(
                        "decode.batch", deadline=deadline, kind="batch", sample=False
                    )
                    if bctx is not None:
                        bctx.annotate(
                            engine=self.name, slots=len(self._active),
                            chunk=self.chunk,
                        )
                args = (
                    gen.params, self._pk, self._pv, jnp.asarray(tok),
                    jnp.asarray(pos), jnp.asarray(act), jnp.asarray(left),
                    self._rngs, jnp.asarray(temps), jnp.asarray(eos), *sc,
                )
            operands = self._prev
            with trace.use(bctx), observe.span(
                "gen.step.dispatch", after=operands, slots=len(self._active), **_E["step_dispatch"]
            ) as self._prev:
                pk, pv, rngs, em, extra = retry_call(
                    "generator.step", fn, *args, deadline=deadline
                )
            with observe.span("gen.step.fetch", after=self._prev, **_E["step_fetch"]) as self._prev:
                em = np.asarray(em)  # [chunk, S]: the per-chunk host fetch  # pathway: allow(value-flow): THE decode-loop fetch — one deliberate sync per step chunk delivers every slot's tokens to its rider
                extra = jax.device_get(extra)  # pathway: allow(value-flow): the same fetch's second half — what was read off those tokens' logits, outputs of the same program
        except Exception as exc:
            if bctx is not None:
                trace.finish(bctx, statuses=("error",))
            log_once(
                f"decode.step:{type(exc).__name__}",
                "continuous-decode step chunk failed (%r); resolving "
                "in-flight requests with their tokens so far",
                exc,
            )
            self._evict_all(exc)
            self._pool_lost()
            return
        # the chunk's round trip, operands to fetched, on the spans' own reads
        # (the recorder off: no read, and nothing that wants one)
        t0, t1 = operands.t0_ns, self._prev.t1_ns
        _H_STEP.observe_ns(t1 - t0)
        self._step_ns += t1 - t0
        with observe.span("gen.step.replay", after=self._prev, **_E["step_replay"]) as self._prev:
            self._pk, self._pv, self._rngs = pk, pv, rngs
            self.pool_stats["chunks"] += 1
            self.pool_stats["steps"] += n_steps
            self.pool_stats["occupancy_sum"] += len(self._active)
            self._note_expert_load("decode", extra, n_steps * len(self._active), n_steps)
            if bctx is not None:
                trace.finish(bctx)
                for st in riders:
                    rt = st.req.trace
                    rt.add_link(bctx.trace_id)
                    rt.add_span(
                        "decode.step", t0, t1,
                        linked_trace=bctx.trace_id, slots=len(self._active),
                    )
            # replay the chunk per slot — the EXACT mask rules the kernel
            # applied: a lane emits until EOS or budget, then freezes
            leaves: List[Tuple[int, _SlotState, Tuple[str, ...]]] = []
            for s, st in list(self._active.items()):
                flags: Tuple[str, ...] = ()
                finished = False
                took = 0
                for i in range(n_steps):
                    t = int(em[i, s])  # pathway: allow(value-flow): `em` was rebound to its HOST copy at the fetch above — the rule's name-level residency tracking cannot see the rebind; no device touch happens here
                    st.tokens.append(t)
                    took += 1
                    if (st.eos >= 0 and t == st.eos) or st.left - took <= 0:
                        finished = True
                        break
                self._took(st, s, took, extra)
                if not finished and (
                    st.req.deadline is not None and st.req.deadline.expired()
                ):
                    # mid-decode deadline: the request leaves with its
                    # tokens so far, flagged — its slot frees for the queue
                    finished = True
                    flags = (EXTRACTIVE_ANSWER,)
                if finished:
                    leaves.append((s, st, flags))
            for s, st, flags in leaves:
                self._leave(s, st, flags=flags)

    def _took(self, st: _SlotState, lane: int, n: int, extra) -> None:
        """Book the first ``n`` tokens of a fetched chunk to a lane."""
        st.pos += n
        st.left -= n
        st.stats.append(tuple(extra[k][:n, lane] for k in _STAT_KEYS))
        self.pool_stats["tokens_decode"] += n
        self.pool_stats["tokens_forwarded"] += n
        self.pool_stats["loop_passes"] += n * self._loop_steps
        self._note_exit_mass(extra, (slice(0, n), lane))

    def _note_expert_load(self, phase: str, extra, tokens: int, n_steps: Optional[int] = None) -> None:
        """Book what a program with routed experts read off its routers, per
        layer (and per step, of which the first ``n_steps`` ran): the experts
        that got a token and the busiest one's tokens."""
        touched = extra.get("experts_touched")
        if touched is None:
            return
        self.pool_stats[f"expert_tokens_{phase}"] += tokens * self._expert_choices
        held = extra.get("expert_pairs_held")  # a family that holds a range of the experts counts what fell to it
        self.pool_stats[f"expert_pairs_held_{phase}"] += tokens * self._expert_choices if held is None else int(np.sum(held[:n_steps]))
        self.pool_stats[f"experts_touched_{phase}"] += int(np.sum(touched[:n_steps]))
        self.pool_stats[f"expert_load_max_{phase}"] += int(np.sum(extra["expert_load_max"][:n_steps]))

    def _note_exit_mass(self, extra, at) -> None:
        mass = extra.get("exit_mass")  # [..., loop steps]: the looped family's gate
        if mass is None:  # one pass over the stack: every token leaves at step 0
            mass = np.ones(np.shape(extra["lse"]) + (1,))
        rows = np.asarray(mass[at], np.float64).reshape(-1, self._loop_steps)
        self._exit_mass_sum += rows.sum(axis=0)
        self._exit_mass_n += rows.shape[0]

    # -- speculative decode: draft → verify → accept -------------------------
    def _spec_ready(self) -> bool:
        """Should this iteration run a speculative round?  Requires
        ``spec_k >= 2`` (one committed token + at least one draft), no
        active fault cooldown, and room for all ``k`` K/V writes in
        every active lane (``dynamic_update_slice`` CLAMPS out-of-bounds
        starts, so a lane with pos+k > T would silently clobber valid
        rows — near the width frontier the engine takes plain steps)."""
        k = self.spec_k
        if k < 2:
            return False
        if self._spec_hold > 0:
            self._spec_hold -= 1
            return False
        return all(
            st.pos + k <= self._T for st in self._active.values()
        )

    @staticmethod
    def _mine_ngram(hist: List[int], want: int) -> List[int]:
        """Prompt-lookup draft mining: find the RIGHTMOST earlier
        occurrence of the history's trailing n-gram (n = 3, then 2,
        then 1) and propose the tokens that followed it.  RAG prompts
        quote their evidence, so generations re-walk prompt n-grams
        constantly — free drafts, no dispatch.  Host-side over a few
        hundred ints; returns [] when the well is dry."""
        L = len(hist)
        for n in (3, 2, 1):
            if L < n + 1:
                continue
            pat = hist[-n:]
            for j in range(L - n - 1, -1, -1):
                if hist[j : j + n] == pat:
                    cont = hist[j + n : j + n + want]
                    if cont:
                        return cont
        return []

    def _remember(self, st: _SlotState) -> None:
        """Feed a finished request's token stream into the suffix
        index.  Every n-gram (n = 1..6) of the stream maps
        to the (up to 16) tokens that followed it; the most recent
        writer wins, so the index tracks live traffic.  O(len) dict
        writes per finished request, bounded by a clear-on-overflow."""
        seq = st.prompt_ids + st.tokens
        if len(seq) < 2:
            return
        idx = self._suffix_idx
        if len(idx) > 100_000:
            idx.clear()  # bounded memory: rebuilt by ongoing traffic
        # WITHIN a sequence the FIRST occurrence wins (a later
        # overlapping occurrence inside a repeated-token run would
        # otherwise skip the rest of the run); ACROSS sequences the
        # most recent request wins, tracking live traffic
        fresh: Dict[Tuple[int, ...], List[int]] = {}
        for n in range(1, 7):
            for i in range(len(seq) - n):
                fresh.setdefault(
                    tuple(seq[i : i + n]), seq[i + n : i + n + 16]
                )
        idx.update(fresh)

    def _mine_corpus(self, hist: List[int], want: int) -> List[int]:
        """Cross-request half of ``_mine_ngram``: look the history's
        trailing n-gram up in the suffix index, longest context first —
        near-duplicate requests (shared RAG prefixes) collide on short
        n-grams, and the deeper context disambiguates which stream to
        continue.  O(1) per lane per round."""
        for n in (6, 5, 4, 3, 2, 1):
            if len(hist) < n:
                continue
            cont = self._suffix_idx.get(tuple(hist[-n:]))
            if cont:
                return cont[:want]
        return []

    def _spec_round(self) -> None:  # noqa: C901
        """One draft→verify→accept round over the pool: propose ``k-1``
        tokens per lane (n-gram mining, trunk fallback), verify all
        ``k`` positions in ONE batched dispatch, commit each lane's
        longest agreeing prefix.  Tokens are EXACTLY the plain path's
        (the verify replays its sampling rng-for-rng); only the number
        of dispatches per token changes.  Any draft/verify fault falls
        back to the plain step chunk for this round — pool untouched,
        token-identical — and arms a cooldown."""
        import jax
        import jax.numpy as jnp

        gen = self.generator
        S = self.slots
        k = self.spec_k
        with gen._lock:  # ahead of the round's brackets: no span encloses a lock
            vfn = gen._slot_verify_fn(S, self._T, k, self._quant)
            dfn = gen._slot_draft_fn(
                S, self._T, k - 1, self._draft_layers, self._quant
            )
        bctx = None
        # the step chunk's four brackets: the drafts mined and the operands
        # built / draft and verify dispatched (a trunk draft's fetch, which
        # seeds the verify, inside) / the accepted tokens fetched / replayed
        try:
            with observe.span("gen.step.operands", after=self._prev, **_E["step_operands"]) as self._prev:
                toks = np.zeros((S, k), np.int32)
                pos = np.zeros(S, np.int32)
                act = np.zeros(S, bool)
                left = np.zeros(S, np.int32)
                temps = np.zeros(S, np.float32)
                eos = np.full(S, -1, np.int32)
                src_of: Dict[int, str] = {}
                need_trunk: List[int] = []
                for s, st in self._active.items():
                    toks[s, 0] = st.tokens[-1]
                    pos[s] = st.pos
                    act[s] = True
                    left[s] = st.left
                    temps[s] = st.temperature
                    eos[s] = st.eos
                    mined: List[int] = []
                    if self.draft_source in ("auto", "ngram"):
                        hist = st.prompt_ids + st.tokens
                        mined = self._mine_ngram(hist, k - 1)
                        pooled = self._mine_corpus(hist, k - 1)
                        if len(pooled) > len(mined):
                            mined = pooled
                    if mined:
                        toks[s, 1 : 1 + len(mined)] = mined
                        src_of[s] = "ngram"
                    elif self.draft_source in ("auto", "trunk"):
                        need_trunk.append(s)
                        src_of[s] = "trunk"
                    else:
                        src_of[s] = "none"
                sc = (self._kscale, self._vscale) if self._quant else ()
                deadline = self._batch_deadline(
                    [st.req for st in self._active.values()]
                )
                riders = [
                    st for st in self._active.values() if st.req.trace is not None
                ]
                if riders:
                    bctx = trace.start_trace(
                        "decode.batch", deadline=deadline, kind="batch", sample=False
                    )
                    if bctx is not None:
                        bctx.annotate(
                            engine=self.name, slots=len(self._active),
                            spec_k=k, spec=True,
                        )
            operands = self._prev
            with observe.span(
                "gen.step.dispatch", after=operands, slots=len(self._active), **_E["step_dispatch"]
            ) as self._prev:
                # draft phase: ONE reduced-trunk dispatch covers every lane
                # that needs it; pure-ngram rounds still fire the chaos site
                # so a faulted draft path degrades ALL speculation uniformly
                if need_trunk:
                    # pathway: allow(recompile-hazard): every operand shape is static per engine — [S] / [S, k] with S = the pool size and k = spec_k, fixed at construction; the census test pins the signature count
                    dr = retry_call(
                        "generator.draft",
                        dfn,
                        gen.params, self._pk, self._pv,
                        jnp.asarray(toks[:, 0]), jnp.asarray(pos),
                        jnp.asarray(act), *sc,
                        deadline=deadline,
                    )
                    dr = np.asarray(dr)  # pathway: allow(value-flow): the draft fetch — proposals are host state (they seed the verify's token operand), one deliberate sync per speculative round
                    for s in need_trunk:
                        toks[s, 1:] = dr[s]
                else:
                    inject.fire("generator.draft", deadline=deadline)
                # verify phase: ONE batched dispatch scores all k positions
                args = (
                    gen.params, self._pk, self._pv, jnp.asarray(toks),
                    jnp.asarray(pos), jnp.asarray(act), jnp.asarray(left),
                    self._rngs, jnp.asarray(temps), jnp.asarray(eos), *sc,
                )
                with trace.use(bctx):
                    pk, pv, rngs, em, extra = retry_call(
                        "generator.verify", vfn, *args, deadline=deadline
                    )
            with observe.span("gen.step.fetch", after=self._prev, **_E["step_fetch"]) as self._prev:
                em = np.asarray(em)  # [k, S]  # pathway: allow(value-flow): THE decode-loop fetch (speculative flavor) — one deliberate sync per round delivers every slot's accepted tokens to its rider
                extra = jax.device_get(extra)  # pathway: allow(value-flow): the same fetch's second half — what was read off those tokens' logits, outputs of the same program
        except Exception as exc:
            if bctx is not None:
                trace.finish(bctx, statuses=("speculation_disabled",))
            # degrade-never-fail: the pool was NOT rebound (functional
            # updates — a failed dispatch leaves no partial state), so
            # the plain chunk below produces exactly the tokens the
            # spec round would have committed
            log_once(
                f"decode.spec:{type(exc).__name__}",
                "speculative round failed (%r); falling back to the "
                "plain step chunk (token-identical) and cooling down",
                exc,
            )
            self.pool_stats["spec_fallbacks"] += 1
            record_degraded("speculation_disabled")
            self._spec_hold = 8
            self._step_chunk()
            return
        t0, t1 = operands.t0_ns, self._prev.t1_ns
        _H_STEP.observe_ns(t1 - t0)
        self._step_ns += t1 - t0
        with observe.span("gen.step.replay", after=self._prev, **_E["step_replay"]) as self._prev:
            self._pk, self._pv, self._rngs = pk, pv, rngs
            self.pool_stats["chunks"] += 1
            self.pool_stats["steps"] += k
            self.pool_stats["spec_rounds"] += 1
            self.pool_stats["occupancy_sum"] += len(self._active)
            if bctx is not None:
                trace.finish(bctx)
                for st in riders:
                    rt = st.req.trace
                    rt.add_link(bctx.trace_id)
                    rt.add_span(
                        "decode.step", t0, t1,
                        linked_trace=bctx.trace_id, slots=len(self._active),
                        spec_k=k,
                    )
            # replay: commit each lane's accepted prefix — ``-1`` marks the
            # first rejected position (acceptance is a PREFIX by
            # construction); EOS inside the accepted prefix truncates it
            # there and frees the slot THIS round, exactly like a plain
            # chunk whose lane hits EOS mid-chunk
            leaves: List[Tuple[int, _SlotState, Tuple[str, ...]]] = []
            for s, st in list(self._active.items()):
                emitted = 0
                flags: Tuple[str, ...] = ()
                finished = False
                for i in range(k):
                    t = int(em[i, s])  # pathway: allow(value-flow): `em` was rebound to its HOST copy at the fetch above — no device touch here
                    if t < 0:
                        break
                    st.tokens.append(t)
                    emitted += 1
                    if (st.eos >= 0 and t == st.eos) or st.left - emitted <= 0:
                        finished = True
                        break
                self._took(st, s, emitted, extra)
                _H_DRAFT_ACCEPT.observe_s(float(emitted))
                self.pool_stats["draft_offered"] += k - 1
                self.pool_stats["draft_accepted"] += max(0, emitted - 1)
                self._draft_sources[src_of.get(s, "none")] += 1
                if not finished and (
                    st.req.deadline is not None and st.req.deadline.expired()
                ):
                    finished = True
                    flags = (EXTRACTIVE_ANSWER,)
                if finished:
                    leaves.append((s, st, flags))
            for s, st, flags in leaves:
                self._leave(s, st, flags=flags)

    # -- leave / resolve -----------------------------------------------------
    def _leave(
        self, slot: int, st: _SlotState, flags: Tuple[str, ...] = ()
    ) -> None:
        gen = self.generator
        meta: Dict[str, Any] = {
            "tokens": len(st.tokens), "slot": slot, "prefix_tokens": st.prefix,
            # what a caller may check or time (PERF.md): when the first token
            # reached the host (``time.perf_counter()``), the prompt's ids as
            # prefilled, the ids emitted, and per emitted token the float32
            # logit of the token chosen, the log-sum-exp and the top ids and
            # logits it was chosen from
            "t_first_token": st.t_join_ns * 1e-9,
            "prompt_ids": list(st.prompt_ids),
            "token_ids": list(st.tokens),
            "logprobs": {
                key: np.concatenate([part[i] for part in st.stats]).tolist()
                for i, key in enumerate(_STAT_KEYS)
            },
        }
        if flags:
            self.pool_stats["evicted"] += 1
            meta["partial"] = True
            for f in flags:
                record_degraded(f)
        else:
            self.pool_stats["finished"] += 1
            if self.spec_k >= 2:
                self._remember(st)
        # free BEFORE resolving: the waiter may act on the result the
        # instant the ticket fires, and the slot hand-off (including its
        # chaos site) must already be settled by then
        self._active.pop(slot, None)
        self._free_slot(slot)
        self._resolve(
            st.req,
            DecodeResult(
                gen.render_tokens(st.tokens), degraded=flags, meta=meta
            ),
            left=(slot, st),
        )

    def _evict_all(self, exc: BaseException) -> None:
        """Persistent step failure: every in-flight request resolves
        with its tokens emitted so far, flagged — the step loop itself
        survives and keeps serving the queue."""
        gen = self.generator
        for s, st in list(self._active.items()):
            self.pool_stats["evicted"] += 1
            record_degraded(EXTRACTIVE_ANSWER)
            self._active.pop(s, None)
            self._free_slot(s)
            self._resolve(
                st.req,
                DecodeResult(
                    gen.render_tokens(st.tokens),
                    degraded=(EXTRACTIVE_ANSWER,),
                    meta={
                        "partial": True,
                        "tokens": len(st.tokens),
                        "error": repr(exc),
                    },
                ),
            )

    def _free_slot(self, slot: int) -> None:
        """Return a slot to the free list.  A ``generator.slot_free``
        fault quarantines the slot (capacity shrinks by one, counted)
        instead of risking a corrupt hand-off — and fires under an
        already-spent deadline so even an armed hang releases
        immediately and the step loop never stalls."""
        try:
            inject.fire("generator.slot_free", deadline=_spent_deadline())
        except Exception as exc:
            log_once(
                f"decode.slot_free:{type(exc).__name__}",
                "slot free failed (%r); quarantining slot instead of "
                "reusing it",
                exc,
            )
            self.pool_stats["quarantined"] += 1
            return
        with self._pool_lock:
            self._free.append(slot)

    def _resolve(self, req, result: DecodeResult, left: Optional[Tuple[int, _SlotState]] = None) -> None:
        """Hand ``req`` its result.  ``left`` = (slot, state) of a request
        that left the pool (``_leave``): its rider then records its waits and
        its life by phase from what is noted here (``_demux``), and ends its
        trace, as the serve scheduler's riders do."""
        req.slots = [0]
        batch = req.batch = _Batch(
            lambda _r=result: [_r], 1, 1, self._degrade_empty
        )
        t_resolved = batch.link["t_resolved_ns"] = time.perf_counter_ns()
        if left is not None and observe.enabled():
            slot, st = left
            batch.t_launch_ns = st.t_admit_ns
            # live -> resolved, split by the engine's running totals; a lane
            # that left inside its own join's ``settle`` was never live
            t_live, stalled, stepping = t_resolved, 0, 0
            if st.t_live_ns:
                t_live, stalled, stepping = st.t_live_ns, self._join_ns - st.join_ns0, self._step_ns - st.step_ns0
            batch.link["life"] = (
                st.t_join_ns, {"tokens": len(st.tokens), "slot": slot},
                {
                    "join": t_live - st.t_admit_ns, "stalled": stalled, "stepping": stepping,
                    "host": t_resolved - t_live - stalled - stepping,
                },
            )
        req.event.set()

    # -- solo fallback (deadline preemption, stop-drain, quarantine) ---------
    def _launch(self, items: List[Any], reqs: List[Any]):
        gen = self.generator

        def run(_items=tuple(items)):
            out = []
            for text, steps, temp, seed, eos in _items:
                rows = gen.generate(
                    [text],
                    max_new_tokens=steps,
                    temperature=temp,
                    seed=seed,
                    eos_id=None if eos < 0 else eos,
                )
                out.append(DecodeResult(rows[0]))
            return out

        return run

    def _demux(self, req, batch_result) -> DecodeResult:
        # time-to-last-token, pool and solo paths alike (the waiter's
        # completion is the client-visible "last token")
        t_woke = time.perf_counter_ns()
        _H_TTLT.observe_ns(t_woke - req.t_enqueue_ns)
        out = []
        for slot in req.slots:
            if 0 <= slot < len(batch_result):
                out.append(batch_result[slot])
            else:  # pragma: no cover - defensive
                out.append(
                    DecodeResult("", degraded=(EXTRACTIVE_ANSWER,))
                )
        result = out[0]
        rt = req.trace
        life = req.batch.link.pop("life", None)
        if life is not None:
            # it left the pool with the recorder on: its life by phase, on the
            # engine's clock reads and the two waits the rider itself sees;
            # the six sum to the observation above
            t_admit, t_resolved = req.batch.t_launch_ns, req.batch.link["t_resolved_ns"]
            t_first, attrs, engine_ns = life
            phases = {"slot_wait": t_admit - req.t_enqueue_ns, **engine_ns, "wake": t_woke - t_resolved}
            for phase, ns in phases.items():
                _H_REQUEST[phase].observe_ns(ns)
            result.meta["phases_ms"] = {phase: ns * 1e-6 for phase, ns in phases.items()}
            if rt is not None:
                observe.interval("admission_wait", req.t_enqueue_ns, t_admit, tree=rt)
                observe.interval(  # first token on the host -> leave, with the request's split
                    "decode", t_first, t_resolved, tree=rt, **attrs,
                    **{f"{phase}_ms": ms for phase, ms in result.meta["phases_ms"].items()},
                )
                observe.interval("ticket_wake", t_resolved, t_woke, tree=rt)
        if rt is not None:
            # the rider's trace ends with the rider, pool and solo paths alike
            # (deadline preemption, stop-drain, quarantine/kv_width fallback):
            # the root span is the request's latency and tail sampling runs
            # once its outcome is known (idempotent)
            trace.finish(rt, statuses=tuple(getattr(result, "degraded", ())))
        return result

    # -- flight-recorder provider -------------------------------------------
    def observe_metrics(self):
        yield from super().observe_metrics()
        labels = {"generator": self.name}
        yield ("gauge", "pathway_generator_slots", labels, self.slots)
        yield (
            "gauge", "pathway_generator_slots_active", labels,
            len(self._active),
        )
        yield (
            "gauge", "pathway_generator_slots_quarantined", labels,
            self.pool_stats["quarantined"],
        )
        yield (
            "gauge", "pathway_generator_kv_bytes_per_token", labels,
            self.kv_bytes_per_token(),
        )
        yield (
            "counter", "pathway_generator_loop_passes_total", labels,
            self.pool_stats["loop_passes"],
        )
        for kind, depth, rows in self._layout:
            yield ("gauge", "pathway_generator_kv_rows", {**labels, "kind": kind}, depth * rows)
        for kind, nbytes in (self._state_bytes or {"none": 0}).items():  # one `none` series of 0 without state
            yield ("gauge", "pathway_generator_state_bytes", {**labels, "kind": kind}, nbytes)
        for stat in ("state_restored_tokens", "state_snapshots_admitted", "state_snapshot_bytes"):
            yield ("counter", f"pathway_generator_{stat}_total", labels, self.pool_stats[stat])
        yield ("counter", "pathway_generator_join_splits_total", labels, self.pool_stats["join_splits"])
        for phase in ("prefill", "decode"):
            for family, stat in (
                ("pathway_generator_expert_tokens_total", "expert_tokens"),
                ("pathway_generator_expert_pairs_held_total", "expert_pairs_held"),
                ("pathway_generator_experts_touched_total", "experts_touched"),
                ("pathway_generator_expert_load_max_total", "expert_load_max"),
            ):
                yield ("counter", family, {**labels, "phase": phase}, self.pool_stats[f"{stat}_{phase}"])
        yield (
            "counter", "pathway_generator_stalled_seconds_total", labels,
            self._stalled_s,
        )
        for u in range(self._loop_steps if self._exit_mass_n else 0):
            yield (
                "gauge", "pathway_generator_exit_mass", {**labels, "step": u},
                self._exit_mass_sum[u] / self._exit_mass_n,
            )
        for phase in ("prefill", "decode", "forwarded"):
            yield (
                "counter",
                "pathway_generator_tokens_total",
                {**labels, "phase": phase},
                self.pool_stats[f"tokens_{phase}"],
            )
        for outcome in ("finished", "evicted"):
            yield (
                "counter",
                "pathway_generator_requests_total",
                {**labels, "outcome": outcome},
                self.pool_stats[outcome],
            )
        yield (
            "counter", "pathway_generator_steps_total", labels,
            self.pool_stats["steps"],
        )
        yield (
            "counter", "pathway_generator_chunks_total", labels,
            self.pool_stats["chunks"],
        )
        # speculative decode: acceptance rate (accepted draft tokens /
        # offered draft tokens — 0.0 before any round) + which proposer
        # produced each lane-round's drafts.  All three sources render
        # even at zero so dashboards see the full label space
        offered = self.pool_stats["draft_offered"]
        yield (
            "gauge", "pathway_generator_draft_acceptance_rate", labels,
            (self.pool_stats["draft_accepted"] / offered) if offered else 0.0,
        )
        for source in ("ngram", "trunk", "none"):
            yield (
                "counter",
                "pathway_generator_draft_source_total",
                {**labels, "source": source},
                self._draft_sources[source],
            )

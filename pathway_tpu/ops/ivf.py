"""IVF approximate KNN — the sublinear tier above DeviceKnnIndex.

The reference reserves approximate search for usearch HNSW
(src/external_integration/usearch_integration.rs:20-42, f16-quantized
graph walks).  Graph traversal is hostile to TPUs (pointer chasing, dynamic
shapes); the TPU-idiomatic redesign is IVF:

- **train**: k-means centroids fitted with matmul assignment steps (the
  assignment [S, C] score matrix is one MXU matmul per iteration);
- **build**: every row is assigned to its nearest centroid under a balance
  cap, and the index is laid out CLUSTER-SORTED as padded slabs
  ``[C_pad, M_pad, d_pad]`` with an additive bias plane (0 live, -inf
  pad/removed) — rows of one cluster are physically contiguous;
- **search**: one [B, d]x[d, C] matmul scores the centroids, ``lax.top_k``
  picks the ``n_probe`` clusters per query, and the probed slabs are
  *exactly* rescored.  On TPU the rescore is a Pallas kernel
  (ops/ivf_pallas.py) that scalar-prefetches the probe table and streams
  each probed slab as one contiguous DMA onto the MXU — measured 2.5 ms
  per 64-query batch at 1M x 384 vs ~220 ms for XLA's per-row gather
  (HBM-random access cannot stream) and 5.1 ms for the exact full sweep.
  Off-TPU the same math runs as an XLA slab gather.

Scoring FLOPs drop from B·N·d to B·(C + n_probe·M_pad)·d: clusters target
~240 rows (so the 128-multiple M_pad wastes little) and the probe fraction
from ``_default_probe`` tapers the shortlist to ~16k rows/query (≈1.8% of
1M); ≥0.95 recall@10 on real text embeddings (tests/test_ivf.py).  The
exact DeviceKnnIndex remains the default for latency below ~1M rows; the
IVF tier wins on FLOPs (multi-tenant packing, larger-than-sweep corpora).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import observe
from ..observe import hbm, profile
from ..robust import (
    RetryPolicy,
    TAIL_SKIPPED,
    inject,
    log_once,
    record_degraded,
    retry_call,
)
from . import donation_guard
from .knn import _bucket, normalize_metric
from .recompile_guard import RecompileTripwire

__all__ = ["IvfKnnIndex", "ShardedIvfIndex"]

# backoff schedule for failed background maintenance passes (absorb /
# retrain): a transient device error must not leave the tail growing
# unboundedly, but a persistent one must not spin the maintenance
# thread either — bounded attempts, exponential backoff, seeded jitter
_MAINT_RETRY = RetryPolicy(attempts=3, base_delay_s=0.05, max_delay_s=1.0)
# the serve-path tail upload retries fast and briefly: it runs under
# the index lock, so its whole retry budget must stay in the low ms
_TAIL_RETRY = RetryPolicy(attempts=3, base_delay_s=0.002, max_delay_s=0.02)

# maintenance-duration histograms (flight recorder): absorb/retrain wall
# time, observed from the maintenance threads AFTER their lock sections
_H_ABSORB = observe.histogram("pathway_ivf_absorb_seconds")
# an absorb's halves: the plan runs off the index lock, the commit under it
# (the part that holds stage-1 dispatch off)
_H_ABSORB_PLAN = observe.histogram("pathway_ivf_absorb_stage_seconds", stage="plan")
_H_ABSORB_COMMIT = observe.histogram("pathway_ivf_absorb_stage_seconds", stage="commit")
_H_RETRAIN = observe.histogram("pathway_ivf_retrain_seconds")


def _kmeans(
    sample: np.ndarray, n_clusters: int, iters: int, seed: int
) -> np.ndarray:
    """k-means on device: assignment is a matmul+argmax per iteration;
    centroid update is a host segment-mean (C·d small)."""
    rng = np.random.default_rng(seed)
    n = sample.shape[0]
    n_clusters = min(n_clusters, n)
    centroids = sample[rng.choice(n, size=n_clusters, replace=False)].copy()
    sample_dev = jnp.asarray(sample)

    @jax.jit
    def assign(cents):
        scores = jnp.dot(
            sample_dev, cents.T, preferred_element_type=jnp.float32
        )
        return jnp.argmax(scores, axis=1)

    for _ in range(iters):
        # pathway: allow(recompile-hazard, value-flow): train-time — centroids keep one [C, d] shape for all iterations of a build; one compile per (C, d), and the synchronous fetch is the k-means loop's contract, off the serve path
        owner = np.asarray(assign(jnp.asarray(centroids)))
        sums = np.zeros_like(centroids)
        np.add.at(sums, owner, sample)
        counts = np.bincount(owner, minlength=n_clusters).astype(np.float32)
        empty = counts == 0
        counts[empty] = 1.0
        centroids = sums / counts[:, None]
        # re-seed empty clusters from random rows
        if empty.any():
            centroids[empty] = sample[
                rng.choice(n, size=int(empty.sum()), replace=False)
            ]
        norms = np.linalg.norm(centroids, axis=1, keepdims=True)
        centroids = centroids / np.where(norms == 0, 1.0, norms)
    return centroids.astype(np.float32)


from functools import partial


def _balanced_assign(order: np.ndarray, C: int, cap: int):
    """Balanced nearest-centroid assignment under a per-cluster cap:
    rows competing for one cluster are ranked by sort position and the
    first (cap - fill) win; losers retry at their next preference.
    ``order`` is [N, n_pref] centroid preferences.  Returns
    (assignment [N], counts [C])."""
    n, n_pref = order.shape
    counts = np.zeros(C, np.int64)
    assignment = np.full(n, -1, np.int64)
    unassigned = np.arange(n)
    for r in range(n_pref):
        if unassigned.size == 0:
            break
        cand = order[unassigned, r]
        sort_ix = np.argsort(cand, kind="stable")
        cand_sorted = cand[sort_ix]
        # within-cluster arrival rank of each competing row
        starts = np.searchsorted(cand_sorted, cand_sorted, side="left")
        within = np.arange(cand_sorted.size) - starts
        accept = within < (cap - counts[cand_sorted])
        winners = unassigned[sort_ix[accept]]
        assignment[winners] = cand_sorted[accept]
        np.add.at(counts, cand_sorted[accept], 1)
        unassigned = unassigned[sort_ix[~accept]]
    for i in unassigned:  # rare: all preferred clusters full
        c = int(np.argmin(counts))
        assignment[i] = c
        counts[c] += 1
    return assignment, counts


@partial(jax.jit, static_argnums=(2,))
def _tail_prefs(rows, centroids, n_pref):
    """Per-row top-``n_pref`` centroid preferences for absorb assignment."""
    s = jnp.dot(
        rows, centroids.T.astype(rows.dtype), preferred_element_type=jnp.float32
    )
    _, idx = jax.lax.top_k(s, n_pref)
    return idx


@partial(
    donation_guard.donating_jit,
    site="ivf.absorb_scatter",
    donate_argnums=(0, 1),
)
def _absorb_scatter(slabs, bias, slots, vecs):
    """Scatter absorbed rows into free slots; donated buffers so XLA can
    update the (possibly GB-scale) slabs in place instead of copying.
    Compiled through the donation tripwire (``PATHWAY_DONATION_GUARD=1``
    poisons the donated refs post-call — ops/donation_guard.py)."""
    C_pad, M_pad, d_pad = slabs.shape
    flat = slabs.reshape(C_pad * M_pad, d_pad).at[slots].set(vecs)
    b = bias.reshape(-1).at[slots].set(jnp.float32(0.0))
    return flat.reshape(C_pad, M_pad, d_pad), b.reshape(C_pad, M_pad)


class IvfKnnIndex:
    """Incrementally maintained approximate KNN (same host API as
    DeviceKnnIndex: add / remove / search / __len__).

    Streaming maintenance — NO stop-the-world rebuild on the serve path
    (VERDICT r4 #2; reference behavior to match: usearch streaming
    add/remove, src/external_integration/usearch_integration.rs:53-99):

    - **tail**: fresh rows are exact-scored alongside the probed shortlist
      (the as-of-now contract — results never miss recent writes);
    - **absorb**: once the tail passes ``absorb_threshold``, rows are
      assigned to their nearest centroid WITH spare slab capacity and
      scattered into free slots in one donated device update — a few ms,
      no retrain, runs in ``add()`` (ingest), never in search/submit;
    - **background retrain**: when the index has grown/churned past
      ``rebuild_fraction``, a daemon thread re-trains k-means and lays out
      fresh slabs from a snapshot, then atomically swaps them in under the
      lock; serving continues on the old slabs throughout.  Rows
      added/removed/upserted DURING the retrain are reconciled at install
      (masked or kept in the tail).
    """

    def __init__(
        self,
        dimension: int,
        metric: str = "cos",
        n_clusters: Optional[int] = None,
        n_probe: Optional[int] = None,
        dtype=jnp.float32,
        train_sample: int = 32768,
        kmeans_iters: int = 8,
        rebuild_fraction: float = 0.25,
        absorb_threshold: int = 4096,
        seed: int = 0,
    ):
        self.dimension = dimension
        self.metric = normalize_metric(metric)
        if self.metric == "l2sq":
            raise NotImplementedError(
                "IvfKnnIndex supports cos/dot; use DeviceKnnIndex for l2sq"
            )
        self.dtype = dtype
        self.n_clusters = n_clusters
        self.n_probe = n_probe
        self.train_sample = train_sample
        self.kmeans_iters = kmeans_iters
        self.rebuild_fraction = rebuild_fraction
        self.absorb_threshold = absorb_threshold
        self.seed = seed
        self._lock = threading.RLock()
        # host-of-record row store (rebuild source)
        self._rows: Dict[int, np.ndarray] = {}
        # device structures (built lazily): cluster-sorted padded slabs
        # [C_pad, M_pad, d_pad] + additive bias [C_pad, M_pad] (0 live,
        # -inf pad/removed); slot = c * M_pad + j
        self._slabs = None
        self._bias = None
        self._centroids = None  # [C, d]
        self._keys_by_slot = None  # uint64 [C_pad * M_pad]
        self._M_pad = 0
        self._d_pad = 0
        self._slot_of_key: Dict[int, int] = {}
        self._tail: Dict[int, None] = {}  # keys added since last build
        self._built_n = 0
        self._search_fns: Dict[tuple, Any] = {}
        # recompile tripwire (ops/recompile_guard.py): search shapes are
        # bucketed, so the signature census stays small; a leak trips
        self._tripwire = RecompileTripwire("IvfKnnIndex.search")
        # host mirror of slot occupancy (True = live row), for absorb's
        # free-slot allocation without a device fetch
        self._live_mask: Optional[np.ndarray] = None
        self._retraining = False
        self._absorbing = False
        # bumped whenever a freshly trained layout is installed — an
        # off-lock absorb whose snapshot predates the install must abort
        # (its slot plan refers to the replaced slabs)
        self._layout_gen = 0
        # PUBLIC result-visibility generation: bumped on every mutation
        # that can change what a serve returns (add/remove/absorb
        # commit/retrain install/bulk build).  The coalescing scheduler
        # keys its in-window dedup on (text, generation) so an absorb or
        # retrain landing mid-window can't hand a later rider results
        # from a slot dispatched against the pre-mutation index.
        self.generation = 0
        # device-resident exact-tail upload, cached between serves and
        # invalidated only when the tail mutates (ADVICE r5 #1): steady-
        # state serving with an unchanged tail pays no per-call transfer
        self._tail_cache: Optional[Tuple] = None
        # damping for absorb re-attempts: when an absorb could place
        # NOTHING (preferred clusters full), remember the tail size so
        # every subsequent add() doesn't pay a futile tail x C matmul;
        # re-arm once the tail grows another threshold, a slot frees, or
        # a retrain rebalances the layout
        self._absorb_stuck_at: Optional[int] = None
        # maintenance counters (observable by tests/bench: the serve path
        # must show sync_builds frozen while absorbs/retrains advance);
        # tail_cache_* counts device-upload reuse on the serve path
        self.stats = {
            "sync_builds": 0,
            "retrains": 0,
            "absorbs": 0,
            "tail_cache_hits": 0,
            "tail_cache_misses": 0,
            "absorb_failures": 0,
            "retrain_failures": 0,
        }
        # degradation-ladder flag: True while the LAST tail-snapshot
        # device upload failed past its retry budget (serving then runs
        # resident-only, flagged tail_skipped); cleared by any
        # successful snapshot.  Read by ops/serving.py under the lock.
        self.tail_degraded = False
        # flight-recorder export: index gauges sampled at scrape time
        # only (zero serve-path cost); id uniquifies multiple indexes
        self._observe_id = observe.next_id()
        observe.register_provider(self)
        # HBM ledger (observe/hbm.py): resident slabs/centroids + the
        # cached tail upload, sampled at scrape time only (weakly held)
        hbm.track("ivf", self)

    def hbm_bytes(self) -> Dict[str, int]:
        """Device-resident bytes by component: the built structure
        (slabs + bias + centroids) and the cached exact-tail upload.
        ``.nbytes`` is array metadata — reading it never syncs."""
        resident = 0
        for buf in (self._slabs, self._bias, self._centroids):
            if buf is not None:
                resident += int(getattr(buf, "nbytes", 0))
        tail = 0
        cache = self._tail_cache
        if cache is not None:
            _keys, dev_mat, dev_valid, _t_pad = cache
            tail = int(getattr(dev_mat, "nbytes", 0)) + int(
                getattr(dev_valid, "nbytes", 0)
            )
        return {"resident": resident, "tail": tail}

    def observe_metrics(self):
        """Scrape-time ``pathway_ivf_*`` samples (flight-recorder
        provider): structure gauges from live state, maintenance and
        tail-upload-cache counters from ``stats``.  Lock-free reads of
        GIL-consistent attributes — a scrape never touches the index
        lock."""
        labels = {"index": str(self._observe_id)}
        centroids = self._centroids
        nlist = int(centroids.shape[0]) if centroids is not None else 0
        yield ("gauge", "pathway_ivf_nlist", labels, nlist)
        yield ("gauge", "pathway_ivf_resident_vectors", labels, len(self))
        yield ("gauge", "pathway_ivf_tail_size", labels, len(self._tail))
        for kind in ("sync_builds", "retrains", "absorbs"):
            yield (
                "counter",
                "pathway_ivf_maintenance_total",
                {**labels, "kind": kind},
                self.stats.get(kind, 0),
            )
        # legacy alias: the absorb_errors series pre-dates the
        # maintenance_failures family; both read the ONE failure counter
        yield (
            "counter",
            "pathway_ivf_maintenance_total",
            {**labels, "kind": "absorb_errors"},
            self.stats.get("absorb_failures", 0),
        )
        for kind, key in (
            ("absorb", "absorb_failures"),
            ("retrain", "retrain_failures"),
        ):
            yield (
                "counter",
                "pathway_ivf_maintenance_failures_total",
                {**labels, "kind": kind},
                self.stats.get(key, 0),
            )
        for result, key in (("hit", "tail_cache_hits"), ("miss", "tail_cache_misses")):
            yield (
                "counter",
                "pathway_ivf_tail_cache_total",
                {**labels, "result": result},
                self.stats.get(key, 0),
            )

    def __len__(self) -> int:
        # built live keys + unbuilt tail — counts correctly both for the
        # host-of-record path (_rows holds everything) and for
        # build_from_matrix (corpus stays on device; _rows holds only tail)
        if self._slabs is None:
            return len(self._rows)
        return len(self._slot_of_key) + len(self._tail)

    # -- mutation (host-of-record; device rebuilt lazily) ------------------
    def add(self, keys: Sequence[int], vectors: np.ndarray) -> int:
        # coerce + normalize BEFORE the lock: callers hand the encoder's
        # device rows straight here, and the implicit device→host sync
        # must not stall every concurrent search/absorb on the index
        # lock (value-flow analyzer finding)
        vectors = np.asarray(vectors, np.float32).reshape(
            len(keys), self.dimension
        )
        if self.metric == "cos":
            norms = np.linalg.norm(vectors, axis=1, keepdims=True)
            vectors = vectors / np.where(norms == 0, 1.0, norms)
        with self._lock:
            # membership check covers BOTH stores: host rows and (after
            # build_from_matrix) device-only bulk keys known via their slot
            existing = [
                int(k)
                for k in keys
                if int(k) in self._rows or int(k) in self._slot_of_key
            ]
            self._forget_built(existing)
            for key, vec in zip(keys, vectors):
                key = int(key)
                self._rows[key] = vec
                self._tail[key] = None
            self._tail_cache = None
            self.generation += 1
            if (
                self._slabs is not None
                and not self._absorbing
                and len(self._tail) >= self.absorb_threshold
                and (
                    self._absorb_stuck_at is None
                    or len(self._tail)
                    >= self._absorb_stuck_at + self.absorb_threshold
                )
            ):
                # absorb runs OFF the index lock on a maintenance thread
                # (like retrain): the device prefs matmul + its host sync
                # used to block concurrent search()/submit() for the whole
                # absorb, a serve-latency spike at every absorb tick
                # (ADVICE r5 #5).  Only the final donated scatter +
                # bookkeeping re-acquire the lock.
                self._absorbing = True
                try:
                    threading.Thread(
                        target=self._absorb_bg, daemon=True, name="ivf-absorb"
                    ).start()
                except RuntimeError:
                    # thread exhaustion: re-arm so a later add() retries
                    # instead of disabling absorbs for the index lifetime
                    self._absorbing = False
            self.maybe_retrain_async()
            # the generation this commit produced: the live-ingest
            # runner stamps it on the batch trace — documents become
            # retrievable (and the scheduler's generation-keyed result
            # cache rolls over) exactly at this value
            return self.generation

    def remove(self, keys: Sequence[int]) -> None:
        with self._lock:
            dropped = []
            for k in keys:
                k = int(k)
                in_rows = self._rows.pop(k, None) is not None
                if in_rows or k in self._slot_of_key:
                    dropped.append(k)
            self._forget_built(dropped)
            if dropped:
                self.generation += 1

    def _forget_built(self, keys: Sequence[int]) -> None:
        """Invalidate built slots (upsert/remove path) in ONE device scatter;
        also drop the keys from the unbuilt tail."""
        slots = []
        for key in keys:
            slot = self._slot_of_key.pop(key, None)
            if slot is not None:
                slots.append(slot)
            if key in self._tail:
                del self._tail[key]
                self._tail_cache = None
        if slots and self._bias is not None:
            arr = np.asarray(slots, np.int64)
            self._bias = self._bias.at[
                arr // self._M_pad, arr % self._M_pad
            ].set(-np.inf)
            if self._live_mask is not None:
                self._live_mask[arr] = False  # freed: absorb may reuse
            self._absorb_stuck_at = None  # capacity changed: re-arm absorb

    # -- build -------------------------------------------------------------
    def _needs_rebuild(self) -> bool:
        if self._slabs is None:
            return True
        grown = len(self._rows) - self._built_n
        return grown > max(64, self.rebuild_fraction * max(self._built_n, 1))

    def build(self) -> None:
        """Synchronous full (re)train + install — the explicit BULK path
        (initial load, tests, bench setup).  The serve path never calls
        this; streaming maintenance goes through the background
        ``_absorb_bg`` and retrain threads instead."""
        with self._lock:
            if not self._rows:
                self._slabs = None
                self._tail = {}
                self._tail_cache = None
                self._layout_gen += 1
                self.generation += 1
                return
            snapshot = dict(self._rows)
            self.stats["sync_builds"] += 1
        built = self._train_layout(snapshot)
        with self._lock:
            self._install(built, snapshot)

    def maybe_retrain_async(self) -> None:
        """Kick a background retrain when the index has churned past
        ``rebuild_fraction`` since the last build.  Returns immediately;
        at most one retrain runs at a time.  Caller may hold the lock."""
        with self._lock:
            if (
                self._slabs is None
                or self._retraining
                or not self._needs_rebuild()
                # build_from_matrix keeps the corpus on device; the host
                # row store only holds the streamed tail, so a host-side
                # retrain would DROP the bulk — skip until a full
                # host-of-record exists (or build_from_matrix is re-run)
                or len(self._rows) < len(self)
            ):
                return
            self._retraining = True
        threading.Thread(
            target=self._retrain_bg, daemon=True, name="ivf-retrain"
        ).start()

    def _retrain_bg(self) -> None:
        """Background retrain with a failure policy: an exception no
        longer dies silently with the daemon thread — it is logged ONCE
        per failure type, counted on
        ``pathway_ivf_maintenance_failures_total{kind="retrain"}``, and
        the pass retries with backoff from a FRESH snapshot (a stale one
        could mask rows that changed during the failed attempt).  After
        the attempt budget the thread exits; serving continues on the
        old slabs and the next add()/search() re-kicks a retrain."""
        try:
            for attempt in range(_MAINT_RETRY.attempts):
                try:
                    inject.fire("ivf.retrain")
                    with self._lock:
                        snapshot = dict(self._rows)
                    if not snapshot:
                        return
                    # the expensive part (k-means + layout + upload) runs
                    # WITHOUT the lock: serving continues on the old
                    # slabs throughout
                    t0 = time.perf_counter_ns()
                    built = self._train_layout(snapshot)
                    with self._lock:
                        self._install(built, snapshot)
                        self.stats["retrains"] += 1
                    _H_RETRAIN.observe_ns(time.perf_counter_ns() - t0)
                    return
                except Exception as exc:
                    with self._lock:
                        self.stats["retrain_failures"] = (
                            self.stats.get("retrain_failures", 0) + 1
                        )
                    log_once(
                        f"ivf.retrain:{type(exc).__name__}",
                        "IVF background retrain failed (%r); retrying with "
                        "backoff — failures counted on "
                        "pathway_ivf_maintenance_failures_total",
                        exc,
                    )
                    if attempt + 1 >= _MAINT_RETRY.attempts:
                        return
                    time.sleep(_MAINT_RETRY.delay_s("ivf.retrain", attempt + 1))
        finally:
            self._retraining = False

    def _train_layout(self, rows: Dict[int, np.ndarray]) -> Dict[str, Any]:
        """Train k-means + balanced assignment + slab layout + device upload
        for a snapshot of rows.  Lock-free: touches only its arguments."""
        n = len(rows)
        keys = list(rows.keys())
        data = np.stack([rows[k] for k in keys])
        return self._layout_from_data(keys, data)

    def _layout_from_data(self, keys: List[int], data: np.ndarray) -> Dict[str, Any]:
        n = len(keys)
        # cluster count targets ~240 rows at the balance CAP; since
        # the cap is 2x the mean fill, slab occupancy is structurally
        # ~50% (bf16 slabs ≈ a dense f32 matrix in HBM — the padding
        # buys contiguous per-cluster DMA for the Pallas rescore).  The
        # probe fraction from _default_probe keeps the rescored
        # shortlist ≈ min(N/5, 16k) padded rows/query at any N
        C = self.n_clusters or int(
            np.clip(np.ceil(n / 120.0), 16, 65536)
        )
        rng = np.random.default_rng(self.seed)
        sample_n = min(n, max(self.train_sample, 8 * C))
        C = min(C, n, sample_n)
        sample = data[rng.choice(n, size=sample_n, replace=False)]
        centroids = _kmeans(sample, C, self.kmeans_iters, self.seed)

        # balanced assignment: nearest centroid with a 2N/C cap; overflow
        # rows fall to their next-best centroid (keeps M bounded so the
        # gather shapes stay small).  Vectorized per preference rank —
        # rows competing for one cluster are ranked by sort position and
        # the first (cap - fill) win; losers retry at the next rank.
        cap = max(1, int(np.ceil(2.0 * n / C)))
        n_pref = min(8, C)
        # per-row top centroids computed ON DEVICE, fetched as [N, 8]
        # indices — the full [N, C] score matrix is 8 GB at 1M x 2000
        # and must never cross the host link
        cents_dev = jnp.asarray(centroids.T)

        @jax.jit
        def _prefs(chunk_rows):
            s = jnp.dot(
                chunk_rows, cents_dev, preferred_element_type=jnp.float32
            )
            _, idx = jax.lax.top_k(s, n_pref)
            return idx

        parts = []
        step = 131072
        for start in range(0, n, step):
            chunk = data[start : start + step]
            if chunk.shape[0] < step and n > step:
                pad = np.zeros((step - chunk.shape[0], data.shape[1]), data.dtype)
                # pathway: allow(recompile-hazard, value-flow): build-time — chunks are padded to the fixed 131072-row step, so large builds compile once (the n<=step case once per corpus size), and the chunked synchronous fetch IS the layout build, off the serve path
                got = np.asarray(_prefs(jnp.asarray(np.concatenate([chunk, pad]))))
                parts.append(got[: chunk.shape[0]])
            else:
                # pathway: allow(recompile-hazard, value-flow): build-time — one compile per (n, d) layout build and a deliberate synchronous fetch, off the serve path (serving shapes go through _bucket)
                parts.append(np.asarray(_prefs(jnp.asarray(chunk))))
        order = np.concatenate(parts) if len(parts) > 1 else parts[0]
        assignment, counts = _balanced_assign(order, C, cap)
        # CLUSTER-SORTED SLAB LAYOUT: rows of one cluster are contiguous
        # and padded to [C_pad, M_pad, d_pad], so the rescore reads each
        # probed cluster as ONE sequential DMA (ops/ivf_pallas.py) —
        # per-row gathers measured 40x slower than this layout on TPU.
        # Padding follows Mosaic tiling: M_pad % 128 (also the output
        # block's lane dim), d_pad % 128, C_pad % 8 (bias block rows).
        M = int(counts.max())
        M_pad = max(128, ((M + 127) // 128) * 128)
        d = data.shape[1]
        d_pad = ((d + 127) // 128) * 128
        C_pad = ((C + 7) // 8) * 8
        keys_arr = np.asarray(keys, dtype=np.uint64)
        order_by_cluster = np.argsort(assignment, kind="stable")
        sorted_cluster = assignment[order_by_cluster]
        starts = np.searchsorted(sorted_cluster, sorted_cluster, "left")
        j_within = np.arange(n) - starts
        slots = sorted_cluster * M_pad + j_within
        slabs = np.zeros((C_pad * M_pad, d_pad), np.float32)
        slabs[slots, :d] = data[order_by_cluster]
        bias = np.full(C_pad * M_pad, -np.inf, np.float32)
        bias[slots] = 0.0
        keys_by_slot = np.zeros(C_pad * M_pad, dtype=np.uint64)
        sorted_keys = keys_arr[order_by_cluster]
        keys_by_slot[slots] = sorted_keys
        slot_of_key = dict(zip(sorted_keys.tolist(), slots.tolist()))
        live_mask = np.zeros(C_pad * M_pad, dtype=bool)
        live_mask[slots] = True
        return {
            "keys_by_slot": keys_by_slot,
            "slot_of_key": slot_of_key,
            "live_mask": live_mask,
            # uploads happen here, OFF the lock (install just swaps refs);
            # centroids live ON DEVICE: a host-resident copy would re-upload
            # C x d floats on every dispatch (12.8 MB at 1M-doc scale)
            "slabs": jnp.asarray(
                slabs.reshape(C_pad, M_pad, d_pad), self.dtype
            ),
            "bias": jnp.asarray(bias.reshape(C_pad, M_pad)),
            "centroids": jnp.asarray(centroids),
            "M_pad": M_pad,
            "d_pad": d_pad,
            "n": n,
        }

    def _install(self, built: Dict[str, Any], snapshot: Dict[int, np.ndarray]) -> None:
        """Swap freshly built structures in (caller holds the lock),
        reconciling rows that changed while the build ran off-lock:
        removed/upserted keys are masked out of the new slabs; keys the
        snapshot never saw stay in the exact tail."""
        slot_of_key = built["slot_of_key"]
        # a built key is stale iff it was removed, or UPSERTED since the
        # snapshot (add() binds a fresh array per key, so object identity
        # of the stored vector is an exact change detector)
        stale = [
            k
            for k in slot_of_key
            if self._rows.get(k) is not snapshot[k]
        ]
        if stale:
            slots = np.asarray(
                [slot_of_key.pop(k) for k in stale], np.int64
            )
            M_pad = built["M_pad"]
            built["bias"] = built["bias"].at[
                slots // M_pad, slots % M_pad
            ].set(-np.inf)
            built["live_mask"][slots] = False
        self._keys_by_slot = built["keys_by_slot"]
        self._slot_of_key = slot_of_key
        self._live_mask = built["live_mask"]
        self._slabs = built["slabs"]
        self._bias = built["bias"]
        self._centroids = built["centroids"]
        self._M_pad = built["M_pad"]
        self._d_pad = built["d_pad"]
        self._tail = {
            k: None for k in self._rows if k not in slot_of_key
        }
        self._built_n = built["n"]
        self._absorb_stuck_at = None  # fresh layout: re-arm absorb
        self._tail_cache = None
        self._layout_gen += 1  # in-flight off-lock absorb plans must abort
        self.generation += 1
        self._search_fns.clear()

    def _absorb_bg(self) -> None:
        """Background absorb (maintenance thread, like retrain): snapshot
        under the lock, run the expensive plan (centroid-preference matmul
        + host fetch + free-slot placement) WITHOUT the lock — serving
        continues throughout — then re-acquire the lock only for the
        donated scatter + bookkeeping.

        Failure policy (ISSUE 4): an exception used to kill this daemon
        thread with only an excepthook traceback, leaving the tail to
        grow unboundedly until the next threshold crossing.  Now each
        failure is logged ONCE per type, counted on
        ``pathway_ivf_maintenance_failures_total{kind="absorb"}``, and
        the pass retries with backoff from a FRESH snapshot (the failed
        attempt may have raced a layout swap).  After the attempt budget
        the flag clears and the next add() re-arms an absorb."""
        try:
            for attempt in range(_MAINT_RETRY.attempts):
                try:
                    t0 = time.perf_counter_ns()
                    with self._lock:
                        snap = self._absorb_snapshot()
                    if snap is None:
                        return
                    with observe.span("ivf.absorb.plan", hist=_H_ABSORB_PLAN):
                        plan = self._plan_absorb(snap)
                    # lock, then span: the bracket is the time serving is
                    # held off, not this thread's wait for the lock
                    with self._lock, observe.span(
                        "ivf.absorb.commit", hist=_H_ABSORB_COMMIT
                    ) as commit:
                        self._commit_absorb(snap, plan)
                    observe.interval("ivf.absorb", t0, commit.t1_ns, hist=_H_ABSORB)
                    return
                except Exception as exc:
                    with self._lock:
                        self.stats["absorb_failures"] = (
                            self.stats.get("absorb_failures", 0) + 1
                        )
                    log_once(
                        f"ivf.absorb:{type(exc).__name__}",
                        "IVF background absorb failed (%r); retrying with "
                        "backoff — failures counted on "
                        "pathway_ivf_maintenance_failures_total",
                        exc,
                    )
                    if attempt + 1 >= _MAINT_RETRY.attempts:
                        return
                    time.sleep(_MAINT_RETRY.delay_s("ivf.absorb", attempt + 1))
        finally:
            self._absorbing = False

    def _absorb_snapshot(self) -> Optional[Dict[str, Any]]:
        """Consistent view of the tail + slab occupancy for absorb planning
        (caller holds the lock)."""
        tail_keys = [k for k in self._tail if k in self._rows]
        if not tail_keys or self._slabs is None:
            return None
        vec_refs = [self._rows[k] for k in tail_keys]
        return {
            "tail_keys": tail_keys,
            # object identity of the stored vectors doubles as an exact
            # staleness detector at commit (add() binds a fresh array per
            # key, the same trick _install uses)
            "vec_refs": vec_refs,
            "data": np.stack(vec_refs),
            "live": self._live_mask.copy(),
            "centroids": self._centroids,
            "M_pad": self._M_pad,
            "C_pad": self._bias.shape[0],
            "d_pad": self._d_pad,
            "gen": self._layout_gen,
        }

    def _plan_absorb(self, snap: Dict[str, Any]) -> Dict[str, Any]:
        """Assign tail rows to FREE slab slots at their nearest centroid
        with spare capacity.  Lock-free: touches only the snapshot.  The
        device preference matmul + its host sync live here — the whole
        point of planning off the lock."""
        inject.fire("ivf.absorb")  # chaos site: the off-lock planning pass
        data = snap["data"]
        t = data.shape[0]
        M_pad = snap["M_pad"]
        C_pad = snap["C_pad"]
        C = snap["centroids"].shape[0]
        n_pref = min(4, C)
        tb = _bucket(t)  # bucketed batch: a handful of compile shapes
        data_p = (
            np.concatenate([data, np.zeros((tb - t, data.shape[1]), np.float32)])
            if tb > t
            else data
        )
        prefs = np.asarray(  # pathway: allow(value-flow): absorb PLAN phase — a deliberate synchronous preference fetch on the off-lock background planner, never on the serve path
            _tail_prefs(jnp.asarray(data_p), snap["centroids"], n_pref)
        )[:t]
        live = snap["live"]
        free_count = M_pad - np.add.reduceat(
            live.astype(np.int64), np.arange(0, C_pad * M_pad, M_pad)
        )
        target = np.full(t, -1, np.int64)
        fill = np.zeros(C_pad, np.int64)
        for r in range(n_pref):
            todo = target < 0
            if not todo.any():
                break
            cand = prefs[todo, r]
            room = free_count[cand] - fill[cand] > 0
            # rank competing rows within each cluster (same trick as build)
            idxs = np.flatnonzero(todo)[room]
            cand = cand[room]
            order = np.argsort(cand, kind="stable")
            cs = cand[order]
            starts = np.searchsorted(cs, cs, "left")
            within = np.arange(cs.size) - starts
            ok = within < (free_count[cs] - fill[cs])
            target[idxs[order[ok]]] = cs[ok]
            np.add.at(fill, cs[ok], 1)
        placed = np.flatnonzero(target >= 0)
        if placed.size == 0:
            return {"placed": placed, "slots": np.empty(0, np.int64)}
        # concrete free slot per placed row
        slots = np.empty(placed.size, np.int64)
        pos = 0
        for c in np.unique(target[placed]):
            rows_c = placed[target[placed] == c]
            free_js = np.flatnonzero(~live[c * M_pad : (c + 1) * M_pad])
            js = free_js[: rows_c.size]
            slots[pos : pos + rows_c.size] = c * M_pad + js
            pos += rows_c.size
        # keep (row -> slot) pairing aligned with the per-cluster slot fill
        order_rows = np.argsort(target[placed], kind="stable")
        placed = placed[order_rows]
        return {"placed": placed, "slots": slots}

    def _commit_absorb(self, snap: Dict[str, Any], plan: Dict[str, Any]) -> None:
        """Install an absorb plan (caller holds the lock): donated device
        scatter + bookkeeping only.  Rows that mutated while the plan ran
        off-lock (removed/upserted) are dropped; a layout swap (background
        retrain landed) aborts the whole plan — the retrain already
        reconciled the tail against its fresh slabs."""
        if snap["gen"] != self._layout_gen or self._slabs is None:
            return
        placed = plan["placed"]
        if placed.size == 0:
            # only suppress future absorbs if occupancy is unchanged since
            # the snapshot: a concurrent remove() freed capacity and
            # re-armed (_forget_built sets _absorb_stuck_at = None) while
            # the plan ran off-lock — a stale zero-placement plan must not
            # clobber that
            if np.array_equal(self._live_mask, snap["live"]):
                self._absorb_stuck_at = len(self._tail)
            return
        tail_keys = snap["tail_keys"]
        vec_refs = snap["vec_refs"]
        # staleness filter: key still in the tail with the SAME vector
        keep = np.asarray(
            [
                tail_keys[int(i)] in self._tail
                and self._rows.get(tail_keys[int(i)]) is vec_refs[int(i)]
                for i in placed
            ],
            bool,
        )
        placed = placed[keep]
        slots = plan["slots"][keep]
        if placed.size == 0:
            return
        self._absorb_stuck_at = None
        d = self.dimension
        vecs = np.zeros((placed.size, snap["d_pad"]), np.float32)
        vecs[:, :d] = snap["data"][placed]
        b = _bucket(placed.size)
        if b > placed.size:
            slots_p = np.concatenate(
                [slots, np.repeat(slots[-1], b - placed.size)]
            )
            vecs_p = np.concatenate(
                [vecs, np.repeat(vecs[-1:], b - placed.size, axis=0)]
            )
        else:
            slots_p, vecs_p = slots, vecs
        self._slabs, self._bias = _absorb_scatter(
            self._slabs,
            self._bias,
            jnp.asarray(slots_p, jnp.int32),
            jnp.asarray(vecs_p, self.dtype),
        )
        self._live_mask[slots] = True
        # copy-on-write: an in-flight serve dispatch snapshotted the OLD
        # keys_by_slot reference; mutating it in place could attribute a
        # reused slot's dispatch-time score to the newly absorbed key
        keys_by_slot = self._keys_by_slot.copy()
        for i, row_i in enumerate(placed):
            key = tail_keys[int(row_i)]
            slot = int(slots[i])
            keys_by_slot[slot] = key
            self._slot_of_key[key] = slot
            del self._tail[key]
        self._keys_by_slot = keys_by_slot
        self._tail_cache = None
        self.generation += 1
        self.stats["absorbs"] += 1

    def _tail_snapshot(self) -> Tuple[List[int], np.ndarray, np.ndarray, int]:
        """Materialize the exact tail for scoring (caller holds the lock):
        ``(tail_keys, tail_mat [t_pad, d], tail_valid [t_pad], t_pad)``.
        ``t_pad`` is the bucketed row count (0 = empty tail); pad rows are
        zero vectors masked invalid so they can never outrank real rows.
        Shared by host ``search`` and the fused serving path.

        A nonempty tail pads to at least ``absorb_threshold`` rows: the
        steady-state tail oscillates below the threshold, so this keeps
        the serving kernel at ONE compile shape instead of recompiling at
        every /256 tail bucket a stream passes through."""
        tail = [key for key in self._tail if key in self._rows]
        t_pad = (
            _bucket(max(len(tail), min(self.absorb_threshold, 8192)))
            if tail
            else 0
        )
        tail_mat = (
            np.stack([self._rows[key] for key in tail])
            if tail
            else np.zeros((0, self.dimension), np.float32)
        )
        if t_pad > len(tail):
            tail_mat = np.concatenate(
                [
                    tail_mat,
                    np.zeros((t_pad - len(tail), self.dimension), np.float32),
                ]
            )
        tail_valid = np.zeros(max(t_pad, 1), bool)
        tail_valid[: len(tail)] = True
        return tail, tail_mat, tail_valid, t_pad

    def _tail_snapshot_device(self) -> Tuple[List[int], Any, Any, int]:
        """Device-resident flavor of ``_tail_snapshot`` for the fused
        serving path (caller holds the lock): ``(tail_keys, tail_mat_dev,
        tail_valid_dev, t_pad)``.  The upload is CACHED on the index and
        invalidated only when the tail mutates (add / absorb / remove /
        layout install), so steady-state serving with an unchanged tail
        pays no per-dispatch host->device transfer — the padded tail is
        ~3 MB bf16 at d=384, previously re-sent on every serve call
        (ADVICE r5 #1)."""
        cache = self._tail_cache
        if cache is None:
            self.stats["tail_cache_misses"] += 1
            # a serve that paid the (cache-miss) tail re-upload shows it as
            # its own span — the classic "why was THIS one slow" answer
            # after an absorb invalidated the cache
            with observe.span("ivf.tail_upload") as upload:
                tail, tail_mat, tail_valid, t_pad = self._tail_snapshot()
                upload.set(rows=t_pad)

                def _upload():
                    if t_pad:
                        return (
                            jnp.asarray(tail_mat[:t_pad], self.dtype),
                            jnp.asarray(tail_valid[:t_pad]),
                        )
                    # placeholder shapes for the tail-less kernel signature
                    return (
                        jnp.asarray(
                            np.zeros((1, self.dimension), np.float32),
                            self.dtype,
                        ),
                        jnp.asarray(np.zeros(1, bool)),
                    )

                try:
                    # transient upload failures retry briefly (the caller
                    # holds the index lock, so the budget is milliseconds);
                    # "ivf.tail_upload" is the chaos-suite fault site
                    dev_mat, dev_valid = retry_call(
                        "ivf.tail_upload", _upload, policy=_TAIL_RETRY
                    )
                except Exception as exc:
                    # degradation ladder: tail unavailable ⇒ serve resident-
                    # only results, flagged + counted.  NOT cached, so the
                    # next serve retries the upload and recovery is automatic.
                    log_once(
                        f"ivf.tail_upload:{type(exc).__name__}",
                        "IVF exact-tail device upload failed (%r); serving "
                        "resident-only (tail_skipped) until it recovers",
                        exc,
                    )
                    record_degraded(TAIL_SKIPPED)
                    self.tail_degraded = True
                    upload.set(status=TAIL_SKIPPED, error=type(exc).__name__)
                    return (
                        [],
                        jnp.asarray(
                            np.zeros((1, self.dimension), np.float32),
                            self.dtype,
                        ),
                        jnp.asarray(np.zeros(1, bool)),
                        0,
                    )
            self.tail_degraded = False
            cache = (tail, dev_mat, dev_valid, t_pad)
            self._tail_cache = cache
        else:
            self.stats["tail_cache_hits"] += 1
            self.tail_degraded = False
        return cache

    def build_from_matrix(self, keys: Sequence[int], matrix_dev) -> None:
        """Bulk build directly from a DEVICE-RESIDENT row matrix [n, d]
        (e.g. the exact DeviceKnnIndex's HBM store) — the corpus never
        crosses the host link (VERDICT r4 #7).  Host transfers are only:
        the k-means training sample (one gather+fetch), the [n, n_pref]
        assignment preferences, and the layout index uploads; the slab
        scatter itself is a device gather.

        The host row store afterwards holds only streamed tail rows, so
        the background retrain is disabled until a full host-of-record
        exists (absorb + exact-tail streaming maintenance still work)."""
        n = int(matrix_dev.shape[0])
        keys = [int(k) for k in keys]
        assert len(keys) == n
        d = self.dimension
        C = self.n_clusters or int(np.clip(np.ceil(n / 120.0), 16, 65536))
        rng = np.random.default_rng(self.seed)
        sample_n = min(n, max(self.train_sample, 8 * C))
        C = min(C, n, sample_n)
        sample_idx = np.sort(rng.choice(n, size=sample_n, replace=False))
        sample = np.asarray(
            jnp.take(matrix_dev, jnp.asarray(sample_idx), axis=0),
            np.float32,
        )
        if self.metric == "cos":
            norms = np.linalg.norm(sample, axis=1, keepdims=True)
            sample = sample / np.where(norms == 0, 1.0, norms)
        centroids = _kmeans(sample, C, self.kmeans_iters, self.seed)

        cap = max(1, int(np.ceil(2.0 * n / C)))
        n_pref = min(8, C)
        cents_dev = jnp.asarray(centroids.T)

        @jax.jit
        def _prefs(chunk_rows):
            rows = chunk_rows.astype(jnp.float32)
            if self.metric == "cos":
                rows = rows / jnp.maximum(
                    jnp.linalg.norm(rows, axis=-1, keepdims=True), 1e-9
                )
            s = jnp.dot(rows, cents_dev, preferred_element_type=jnp.float32)
            _, idx = jax.lax.top_k(s, n_pref)
            return idx

        parts = []
        step = 131072
        for start in range(0, n, step):
            m = min(step, n - start)
            chunk = jax.lax.dynamic_slice_in_dim(matrix_dev, start, m, 0) \
                if m == step else matrix_dev[start : start + m]
            parts.append(np.asarray(_prefs(chunk)))  # pathway: allow(value-flow): bulk build — deliberate chunked synchronous fetch of cluster preferences, never on the serve path
        order = np.concatenate(parts) if len(parts) > 1 else parts[0]
        assignment, counts = _balanced_assign(order, C, cap)

        M = int(counts.max())
        M_pad = max(128, ((M + 127) // 128) * 128)
        d_pad = ((d + 127) // 128) * 128
        C_pad = ((C + 7) // 8) * 8
        keys_arr = np.asarray(keys, dtype=np.uint64)
        order_by_cluster = np.argsort(assignment, kind="stable")
        sorted_cluster = assignment[order_by_cluster]
        starts = np.searchsorted(sorted_cluster, sorted_cluster, "left")
        j_within = np.arange(n) - starts
        slots = sorted_cluster * M_pad + j_within

        # slab layout as ONE device gather+scatter — no host copy of rows
        @jax.jit
        def _layout(matrix, order_ix, slot_ix):
            rows = jnp.take(matrix, order_ix, axis=0).astype(jnp.float32)
            if self.metric == "cos":
                rows = rows / jnp.maximum(
                    jnp.linalg.norm(rows, axis=-1, keepdims=True), 1e-9
                )
            if d_pad > d:
                rows = jnp.concatenate(
                    [rows, jnp.zeros((rows.shape[0], d_pad - d), rows.dtype)],
                    axis=1,
                )
            flat = jnp.zeros((C_pad * M_pad, d_pad), self.dtype)
            return flat.at[slot_ix].set(rows.astype(self.dtype)).reshape(
                C_pad, M_pad, d_pad
            )

        # pathway: allow(recompile-hazard): bulk build — one compile per (n, layout) build_from_matrix call; never on the serve path
        slabs = _layout(
            matrix_dev,
            jnp.asarray(order_by_cluster, jnp.int32),
            jnp.asarray(slots, jnp.int32),
        )
        bias = np.full(C_pad * M_pad, -np.inf, np.float32)
        bias[slots] = 0.0
        keys_by_slot = np.zeros(C_pad * M_pad, dtype=np.uint64)
        sorted_keys = keys_arr[order_by_cluster]
        keys_by_slot[slots] = sorted_keys
        live_mask = np.zeros(C_pad * M_pad, dtype=bool)
        live_mask[slots] = True
        with self._lock:
            self._keys_by_slot = keys_by_slot
            self._slot_of_key = dict(
                zip(sorted_keys.tolist(), slots.tolist())
            )
            self._live_mask = live_mask
            self._slabs = slabs
            self._bias = jnp.asarray(bias.reshape(C_pad, M_pad))
            self._centroids = jnp.asarray(centroids)
            self._M_pad = M_pad
            self._d_pad = d_pad
            self._tail = {k: None for k in self._rows if k not in self._slot_of_key}
            self._built_n = n
            self._absorb_stuck_at = None
            self._tail_cache = None
            self._layout_gen += 1
            self.generation += 1
            self._search_fns.clear()
            self.stats["sync_builds"] += 1

    # -- durable warm state (serve/warmstate.py) -----------------------------
    def warm_state(self) -> Dict[str, Any]:
        """Snapshot everything a replica needs to serve bit-identically
        to this index: the host-of-record rows, the built device
        structures (resident slabs + bias + centroids), the slot
        bookkeeping, the exact tail, and the PUBLIC generation (cache /
        dedup keys on a restored replica must agree with the writer's).

        Refs are captured under the lock; device→host coercion runs OFF
        the lock (all device updates here are functional, so snapshotted
        refs stay valid — the same discipline as the off-lock absorb)."""
        with self._lock:
            rows = dict(self._rows)
            slabs, bias, cents = self._slabs, self._bias, self._centroids
            keys_by_slot = self._keys_by_slot
            live_mask = self._live_mask
            state: Dict[str, Any] = {
                "kind": "ivf",
                "dimension": int(self.dimension),
                "metric": self.metric,
                "M_pad": int(self._M_pad),
                "d_pad": int(self._d_pad),
                "slot_of_key": dict(self._slot_of_key),
                "tail": list(self._tail),
                "built_n": int(self._built_n),
                "generation": int(self.generation),
            }
        state["rows"] = rows
        state["slabs"] = None if slabs is None else np.asarray(slabs)
        state["bias"] = None if bias is None else np.asarray(bias)
        state["centroids"] = None if cents is None else np.asarray(cents)
        state["keys_by_slot"] = (
            None if keys_by_slot is None else np.array(keys_by_slot)
        )
        state["live_mask"] = None if live_mask is None else np.array(live_mask)
        return state

    def load_warm_state(self, state: Dict[str, Any]) -> None:
        """Install a ``warm_state()`` snapshot (replica bring-up): the
        restored index serves bit-identically to the writer at the
        snapshot's generation.  Uploads happen OFF the lock; the locked
        install is a pure pointer swap (the same launch-discipline as
        ``_install``).  Raises ``ValueError`` on a geometry mismatch —
        the warm-state manager turns that into a counted cold-start."""
        if state.get("kind") != "ivf":
            raise ValueError(f"not an IVF warm state: {state.get('kind')!r}")
        if int(state["dimension"]) != int(self.dimension):
            raise ValueError(
                f"dimension mismatch: snapshot {state['dimension']} "
                f"vs index {self.dimension}"
            )
        if state["metric"] != self.metric:
            raise ValueError(
                f"metric mismatch: snapshot {state['metric']!r} "
                f"vs index {self.metric!r}"
            )
        slabs = (
            None if state["slabs"] is None
            else jnp.asarray(state["slabs"], self.dtype)
        )
        bias = (
            None if state["bias"] is None
            else jnp.asarray(state["bias"], jnp.float32)
        )
        cents = (
            None if state["centroids"] is None
            else jnp.asarray(state["centroids"], jnp.float32)
        )
        rows = {
            int(k): np.asarray(v, np.float32) for k, v in state["rows"].items()
        }
        with self._lock:
            self._rows = rows
            self._slabs = slabs
            self._bias = bias
            self._centroids = cents
            self._keys_by_slot = state["keys_by_slot"]
            self._live_mask = state["live_mask"]
            self._M_pad = int(state["M_pad"])
            self._d_pad = int(state["d_pad"])
            self._slot_of_key = {
                int(k): int(s) for k, s in state["slot_of_key"].items()
            }
            self._tail = {int(k): None for k in state["tail"]}
            self._built_n = int(state["built_n"])
            self._absorb_stuck_at = None
            self._tail_cache = None
            self._layout_gen += 1  # in-flight off-lock plans must abort
            self.generation = int(state["generation"])
            self._search_fns.clear()

    def _default_probe(self) -> int:
        """Probe count bounding the rescore shortlist: up to 20% of
        clusters for small corpora (coarse clusters need generous probing
        for recall; exact search owns that regime anyway), tapering so
        n_probe*M_pad (the rescored rows per query) stays ~16k at large N."""
        C = self._centroids.shape[0]
        n = max(self._built_n, 1)
        # generous at small N (coarse clusters need more probes for recall;
        # exact search owns that regime anyway), tapering to ~16k rescored
        # rows per query at large N
        frac = min(0.2, 8192.0 / n)
        return max(1, min(C, int(np.ceil(C * frac))))

    # -- search ------------------------------------------------------------
    def search(  # pathway: allow(value-flow): reference host search — the synchronous host-results contract (serving uses submit/complete, which books its crossings); the fetch + float/int post-process below runs OFF the lock by design
        self, queries: np.ndarray, k: int, n_probe: Optional[int] = None
    ) -> List[List[Tuple[int, float]]]:
        # off-lock coercion: a device-array query batch syncs here, not
        # while holding the index lock
        queries = np.asarray(queries, np.float32).reshape(-1, self.dimension)
        with self._lock:
            nq = queries.shape[0]
            if nq == 0 or len(self) == 0:
                return [[] for _ in range(nq)]
            if self._slabs is None:
                # first build only: there is nothing to serve from yet.
                # After that the serve path NEVER rebuilds — staleness is
                # handled by absorb (in add) + background retrain.
                self.build()
            else:
                self.maybe_retrain_async()
            if self.metric == "cos":
                norms = np.linalg.norm(queries, axis=1, keepdims=True)
                queries = queries / np.where(norms == 0, 1.0, norms)
            C = self._centroids.shape[0]
            p = n_probe or self.n_probe or self._default_probe()
            p = min(p, C)
            b = _bucket(nq)
            if b > nq:
                queries = np.concatenate(
                    [queries, np.zeros((b - nq, self.dimension), np.float32)]
                )
            # exact tail of unbuilt recent rows, brute-force scored
            # alongside (device upload cached until the tail mutates)
            tail, tail_dev, tail_valid_dev, t_pad = self._tail_snapshot_device()
            fn = self._search_fn(b, k, p, t_pad)
            q_pad = queries
            if self._d_pad > self.dimension:
                q_pad = np.concatenate(
                    [
                        queries,
                        np.zeros(
                            (queries.shape[0], self._d_pad - self.dimension),
                            np.float32,
                        ),
                    ],
                    axis=1,
                )
            # dispatch must stay under the lock: a concurrent absorb commit
            # DONATES the slab/bias buffers (_absorb_scatter), so a launch
            # against refs snapshotted before the lock dropped could name
            # freed device memory.  The enqueue itself is async (no host
            # block); only the launch ordering needs the lock.
            scores, slots, t_scores, t_idx = fn(  # pathway: allow(lock-discipline): dispatch-only — donated absorb buffers force launch-before-unlock; fetch happens off-lock below
                jnp.asarray(q_pad, jnp.float32),
                self._slabs,
                self._bias,
                self._centroids if isinstance(self._centroids, jnp.ndarray)
                else jnp.asarray(self._centroids),
                tail_dev,
                tail_valid_dev,
            )
            # dispatch-time snapshot for off-lock completion: rebuilds and
            # absorbs REPLACE keys_by_slot (copy-on-write), so this ref is
            # the dispatch-time slot->key view.  No live-dict filter below:
            # rows removed BEFORE dispatch are already -inf-biased in the
            # dispatched arrays (bias is replaced functionally), and a
            # removal landing after dispatch must not shrink this result —
            # dispatch-time semantics, same as the fused serving path
            keys_by_slot = self._keys_by_slot
        # device round trip + python post-processing OFF the lock — holding
        # it across the fetch blocked every concurrent add()/absorb commit
        # and search for the full device latency (the round-5 bug class;
        # found by `python -m pathway_tpu.analysis`)
        scores = np.asarray(scores)[:nq]
        slots = np.asarray(slots)[:nq]
        t_scores = np.asarray(t_scores)[:nq] if t_pad else None
        t_idx = np.asarray(t_idx)[:nq] if t_pad else None
        out: List[List[Tuple[int, float]]] = []
        for qi in range(nq):
            row: List[Tuple[int, float]] = []
            for j in range(slots.shape[1]):
                s = float(scores[qi, j])
                slot = int(slots[qi, j])
                if not np.isfinite(s) or slot < 0:
                    continue
                row.append((int(keys_by_slot[slot]), s))
            if t_pad:
                for j in range(t_idx.shape[1]):
                    s = float(t_scores[qi, j])
                    ti = int(t_idx[qi, j])
                    if np.isfinite(s) and ti < len(tail):
                        row.append((tail[ti], s))
            row.sort(key=lambda kv: -kv[1])
            # drop duplicate keys (upsert landed in both built+tail)
            seen = set()
            dedup = []
            for key, s in row:
                if key not in seen:
                    seen.add(key)
                    dedup.append((key, s))
            out.append(dedup[:k])
        return out

    def _search_fn(self, B: int, k: int, p: int, t_pad: int):
        key = (
            B, k, p, t_pad,
            self._slabs.shape[0],
            self._M_pad,
            self._centroids.shape[0],
        )
        fn = self._search_fns.get(key)
        if fn is None:
            self._tripwire.observe(key)
            M = self._M_pad
            d = self.dimension
            k_main = min(k, p * M)
            k_tail = min(k, t_pad) if t_pad else 0
            use_pallas = jax.default_backend() == "tpu"

            @jax.jit
            def fn(q, slabs, bias, centroids, tail_mat, tail_valid):
                qf = q.astype(jnp.float32)
                cscores = jnp.dot(
                    qf[:, :d], centroids.T, preferred_element_type=jnp.float32
                )  # [B, C]
                _, probe = jax.lax.top_k(cscores, p)  # [B, p]
                probe = probe.astype(jnp.int32)
                from .ivf_pallas import rescore_shortlist

                scores3 = rescore_shortlist(
                    probe, qf, slabs, bias, use_pallas=use_pallas
                )
                scores = scores3.reshape(B, p * M)
                s, i = jax.lax.top_k(scores, k_main)
                jj = i // M
                mm = i % M
                slots = jnp.take_along_axis(probe, jj, axis=1) * M + mm
                slots = jnp.where(jnp.isfinite(s), slots, -1)
                if t_pad:
                    ts = jnp.dot(
                        qf[:, :d], tail_mat.T.astype(jnp.float32),
                        preferred_element_type=jnp.float32,
                    )
                    # mask pad rows: a 0.0 pad score would outrank real rows
                    # with negative similarity
                    ts = jnp.where(tail_valid[None, :], ts, -jnp.inf)
                    t_s, t_i = jax.lax.top_k(ts, k_tail)
                else:
                    t_s = jnp.zeros((B, 0), jnp.float32)
                    t_i = jnp.zeros((B, 0), jnp.int32)
                return s, slots, t_s, t_i

            # device-time attribution (observe/profile.py)
            fn = profile.wrap("ivf.search", fn)
            self._search_fns[key] = fn
        return self._search_fns[key]

    def search_oversampled(
        self,
        queries: np.ndarray,
        k: int,
        accept,  # callable(key) -> bool
        oversample: int = 4,
        max_rounds: int = 3,
    ) -> List[List[Tuple[int, float]]]:
        """Filtered search by over-sampling (same contract as
        DeviceKnnIndex.search_oversampled; shared loop in ops/knn.py)."""
        from .knn import oversampled_filtered_search

        return oversampled_filtered_search(
            self, queries, k, accept, oversample=oversample, max_rounds=max_rounds
        )

    # diagnostics ----------------------------------------------------------
    def score_flops_fraction(self) -> float:
        """Fraction of brute-force scoring FLOPs a probed search performs
        (centroid matmul + shortlist rescore vs full matrix)."""
        if self._slabs is None or len(self) == 0:
            return 1.0
        C = self._centroids.shape[0]
        M = self._M_pad
        p = self.n_probe or self._default_probe()
        n = max(self._built_n, 1)
        return (C + min(p, C) * M + len(self._tail)) / n


class _ShardIvf(IvfKnnIndex):
    """One shard-resident IVF partition: an ``IvfKnnIndex`` whose device
    structures live on a pinned device.  The synchronous entry points are
    wrapped by ``ShardedIvfIndex`` under ``jax.default_device``; the
    background maintenance threads (absorb/retrain) re-enter the pin here
    because ``jax.default_device`` is thread-local and a thread started
    inside ``add()`` would otherwise plan and scatter on device 0,
    migrating the shard's slabs off its home chip one absorb at a time."""

    def __init__(self, *args, device=None, **kwargs):
        self._device = device
        super().__init__(*args, **kwargs)

    def _absorb_bg(self) -> None:
        if self._device is None:
            return super()._absorb_bg()
        with jax.default_device(self._device):
            return super()._absorb_bg()

    def _retrain_bg(self) -> None:
        if self._device is None:
            return super()._retrain_bg()
        with jax.default_device(self._device):
            return super()._retrain_bg()


class ShardedIvfIndex:
    """Document-sharded IVF over a serve device group: ``n_shards``
    shard-resident ``IvfKnnIndex`` partitions (centroids, postings slabs,
    and exact tail all living on the owning shard's device), routed by
    the group's single placement rule ``owner_of(key)``.

    Same host API as the single-device indexes (add / remove / search /
    __len__ / build), so it drops into ``FusedEncodeSearch`` — which
    detects the ``shards`` attribute and switches to the scatter-dispatch
    serve path (ops/serving.py): encode once, fan the embedded batch out
    to every shard's resident search kernel, and tree-merge the per-shard
    candidates on device, all inside ONE logical dispatch (asserted by
    the dispatch counter's per-shard-group accounting).

    Maintenance stays shard-local: ``add()`` routes each document to its
    owning shard, whose own off-lock-plan/locked-commit absorb and
    background retrain discipline is unchanged — an absorb on shard 3
    never takes any other shard's lock.  The PUBLIC ``generation`` sums
    the children's mutation generations plus a routing-level counter, and
    every child bump happens under that child's lock, so the value moves
    atomically with the result-visible state of the whole group.

    Failure domains are per shard: the group's circuit breakers +
    ``shard.dispatch`` chaos site let one dead shard degrade recall on
    its partition (rung ``shard_skipped``) while the request succeeds.
    """

    def __init__(
        self,
        dimension: int,
        metric: str = "cos",
        group=None,
        n_shards: Optional[int] = None,
        devices: Optional[Sequence] = None,
        **ivf_kwargs: Any,
    ):
        from ..parallel.shards import ShardGroup

        self.group = group or ShardGroup(n_shards=n_shards, devices=devices)
        self.dimension = dimension
        self.metric = normalize_metric(metric)
        self.dtype = ivf_kwargs.get("dtype", jnp.float32)
        self._lock = threading.Lock()
        self._gen_base = 0  # routing-level bumps (e.g. dropped ingest)
        self.shards: List[_ShardIvf] = [
            _ShardIvf(
                dimension,
                metric=metric,
                device=self.group.device(s),
                **ivf_kwargs,
            )
            for s in range(self.group.n_shards)
        ]
        # routing-level failure accounting (a shard.absorb fault drops
        # that shard's documents from THIS ingest round only)
        self.stats: Dict[str, int] = {"route_drops": 0, "route_drop_docs": 0}
        self._observe_id = observe.next_id()
        observe.register_provider(self)

    # -- introspection ------------------------------------------------------
    def __len__(self) -> int:
        return sum(len(c) for c in self.shards)

    @property
    def generation(self) -> int:
        """Result-visibility generation of the whole group (see
        ``IvfKnnIndex.generation``): child bumps happen under the owning
        shard's lock, so any absorb/retrain/add landing anywhere in the
        group moves this value."""
        return self._gen_base + sum(c.generation for c in self.shards)

    @property
    def tail_degraded(self) -> bool:
        return any(c.tail_degraded for c in self.shards)

    # -- mutation (routed to the owning shard) ------------------------------
    def add(self, keys: Sequence[int], vectors: np.ndarray) -> None:
        keys = [int(k) for k in keys]
        if not keys:
            return
        vectors = np.asarray(vectors, np.float32).reshape(
            len(keys), self.dimension
        )
        for s, rows in sorted(self.group.route(keys).items()):
            try:
                # chaos sites: the per-shard ingest leg.  A raise drops
                # THIS shard's documents from this round only — the other
                # shards commit theirs, and the group stays serveable
                # (degrade-not-die, the forward-index failure policy).
                inject.fire(f"shard.absorb.{s}")
                inject.fire("shard.absorb")
                with jax.default_device(self.group.device(s)):
                    self.shards[s].add(
                        [keys[i] for i in rows], vectors[rows]
                    )
            except Exception as exc:
                with self._lock:
                    self.stats["route_drops"] += 1
                    self.stats["route_drop_docs"] += len(rows)
                    self._gen_base += 1
                log_once(
                    f"shard.absorb:{type(exc).__name__}",
                    "sharded ingest to shard %d failed (%r); its documents "
                    "are dropped from this round only — counted on "
                    "pathway_serve_shard_ingest_drops_total",
                    s,
                    exc,
                )

    def remove(self, keys: Sequence[int]) -> None:
        keys = [int(k) for k in keys]
        for s, rows in sorted(self.group.route(keys).items()):
            with jax.default_device(self.group.device(s)):
                self.shards[s].remove([keys[i] for i in rows])

    def build(self) -> None:
        """Synchronous bulk (re)build of every shard — the explicit bulk
        path, like ``IvfKnnIndex.build``.  The serve path never calls
        this; per-shard streaming maintenance handles staleness."""
        for s, child in enumerate(self.shards):
            with jax.default_device(self.group.device(s)):
                child.build()

    # -- host search (parity/reference; the serve path uses the fused
    # scatter-dispatch in ops/serving.py) -----------------------------------
    def search(
        self, queries: np.ndarray, k: int, n_probe: Optional[int] = None
    ) -> List[List[Tuple[int, float]]]:
        queries = np.asarray(queries, np.float32).reshape(-1, self.dimension)
        nq = queries.shape[0]
        merged: List[List[Tuple[int, float]]] = [[] for _ in range(nq)]
        for s, child in enumerate(self.shards):
            if len(child) == 0:
                continue
            with jax.default_device(self.group.device(s)):
                rows = child.search(queries, k, n_probe=n_probe)
            for qi, row in enumerate(rows):
                merged[qi].extend(row)
        out: List[List[Tuple[int, float]]] = []
        for row in merged:
            row.sort(key=lambda kv: -kv[1])
            out.append(row[:k])
        return out

    def search_oversampled(
        self, queries, k, accept, oversample: int = 4, max_rounds: int = 3
    ):
        from .knn import oversampled_filtered_search

        return oversampled_filtered_search(
            self, queries, k, accept, oversample=oversample,
            max_rounds=max_rounds,
        )

    # -- flight-recorder provider ------------------------------------------
    def observe_metrics(self):
        """Per-shard residency on the ``pathway_serve_shard_*`` family
        (the group's skip/breaker series ride the ``ShardGroup``
        provider; the children's own ``pathway_ivf_*`` series keep their
        per-index labels)."""
        labels = {"index": str(self._observe_id)}
        yield (
            "counter",
            "pathway_serve_shard_ingest_drops_total",
            labels,
            self.stats["route_drops"],
        )
        for s, child in enumerate(self.shards):
            shard_labels = {**labels, "shard": str(s)}
            yield (
                "gauge",
                "pathway_serve_shard_resident_vectors",
                shard_labels,
                len(child),
            )
            yield (
                "gauge",
                "pathway_serve_shard_tail_size",
                shard_labels,
                len(child._tail),
            )

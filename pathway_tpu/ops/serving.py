"""Fused serving path: text -> embedding -> top-k in ONE device dispatch.

Every dispatch and every fetch on the live-retrieval hot loop (SURVEY
§3.3) is a host sync.  Chaining ``encoder.encode`` (fetch) and
``index.search`` (dispatch + 2 fetches) pays 3-4 of them; this path compiles
tokenize-output -> transformer forward -> normalize -> [B,d]x[d,N] score ->
``lax.top_k`` into a single jitted function with ONE packed output and an
async host copy — exactly one dispatch and one fetch per serve call.
"""

from __future__ import annotations

# pathway: serve-path  (hidden-sync lint applies: no implicit host round trips)

import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import observe
from ..observe import profile
from ..models.transformer import TransformerEncoder
from ..robust import (
    CircuitOpen,
    Deadline,
    RetryPolicy,
    SHARD_SKIPPED,
    ServeResult,
    TAIL_SKIPPED,
    inject,
    log_once,
    record_degraded,
    retry_call,
)
from .dispatch_counter import record_dispatch, record_fetch
from .knn import _bucket
from .recompile_guard import RecompileTripwire

__all__ = ["FusedEncodeSearch"]

# retry schedule for the IVF dispatch, which launches while HOLDING the
# index + serve locks (the donated absorb buffers force launch-before-
# unlock): its backoff sleeps stall every concurrent add()/serve, so the
# whole budget must stay in the low milliseconds.  The off-lock exact
# path keeps the env-tunable default policy.
_LOCKED_DISPATCH_RETRY = RetryPolicy(
    attempts=3, base_delay_s=0.002, max_delay_s=0.02
)

# flight-recorder stage series (pathway_tpu/observe), resolved once at
# import.  Every boundary on this path is ONE ``observe.span`` (host work on
# the calling thread) or ``observe.interval`` (a wait, or what crossed
# threads); a span opens only AFTER the serve locks are held, the wait for
# them is an interval of its own.  Per batch, by the same clock reads:
# stage1_tokenize + stage1_lock_wait + stage1_dispatch == tokenize_pack.
# The three older series keep their boundaries (the benchmark reads them):
# tokenize_pack = host prep up to the dispatch returning, lock wait
# included; stage1_rtt = dispatch → fetched, on whichever waiter fetched;
# postprocess = host result assembly (stage 2's completion shares it).
_H_TOKENIZE = observe.histogram("pathway_serve_stage_seconds", stage="tokenize_pack")
_H_STAGE1 = observe.histogram("pathway_serve_stage_seconds", stage="stage1_rtt")
_H_POST = observe.histogram("pathway_serve_stage_seconds", stage="postprocess")
_S1_TOKENIZE = observe.serve_stage("stage1_tokenize")
_H_S1_LOCK_WAIT = observe.histogram("pathway_serve_stage_seconds", stage="stage1_lock_wait")
_S1_DISPATCH = observe.serve_stage("stage1_dispatch")
_S1_FETCH = observe.serve_stage("stage1_fetch", cpu=False)
_S1_POST = observe.serve_stage("stage1_postprocess")


def _stage1_launched(t_start: int, t_ready: int, dispatch) -> int:
    """Close stage 1's host side once its dispatch bracket ended: the wait
    between host prep ready and the bracket opening (the serve locks; on
    the exact path also its snapshot under them) and the older
    ``tokenize_pack`` series.  Histograms only: in the batch's tree the
    lock wait is the gap between ``stage1.tokenize`` and
    ``stage1.dispatch`` (a tree node costs every batch).  Returns the
    dispatch instant."""
    _H_S1_LOCK_WAIT.observe_ns(dispatch.t0_ns - t_ready)
    _H_TOKENIZE.observe_ns(dispatch.t1_ns - t_start)
    return dispatch.t1_ns


def _stage1_fetch(outs, n_real: int, t_dispatch: int, kind: str):
    """The blocking fetch of stage 1's packed output(s), on whichever
    waiter got here first, and the dispatch → fetched round trip it ends."""
    with observe.span("stage1.fetch", kind=kind, **_S1_FETCH) as fetch:
        arrs = [np.asarray(out)[:n_real] for out in outs]
    _H_STAGE1.observe_ns(fetch.t1_ns - t_dispatch)  # the round trip it ends
    return arrs


class FusedEncodeSearch:
    """Callable serving path over a ``SentenceEncoder`` plus either a
    ``DeviceKnnIndex`` (exact) or an ``IvfKnnIndex`` (approximate): encode,
    score — full matmul or centroid-probe + shortlist rescore — and top-k
    compile into ONE dispatch either way.

    Recompiles per (batch bucket, sequence length, k, index shape) —
    a handful of shapes in steady state; index *content* changes (add/
    remove) never recompile."""

    def __init__(self, encoder, index, k: int = 10,
                 export_query_tokens: bool = False,
                 embed_cache: Any = "env"):
        self.encoder = encoder
        self.index = index
        self.k = k
        self._lock = threading.Lock()
        self._fns: Dict[Tuple, Any] = {}
        # tier-1 query-embedding cache (pathway_tpu/cache): keyed on
        # token ids, so a known query skips the stage-1 trunk forward
        # even after an index mutation invalidated its result-cache
        # entry.  ``"env"`` resolves the PATHWAY_CACHE_EMBED knob
        # (opt-in); pass an EmbeddingCache or None explicitly otherwise.
        if embed_cache == "env":
            from ..cache import embedding_cache_from_env

            embed_cache = embedding_cache_from_env()
        self.embed_cache = embed_cache
        # recompile tripwire (ops/recompile_guard.py): the fused kernel
        # must stay at a handful of compile shapes in steady state
        self._tripwire = RecompileTripwire("FusedEncodeSearch")
        # IVF indexes lack device key planes; winners map slot->key on host
        self._ivf = hasattr(index, "_centroids")
        # sharded index (ops/ivf.ShardedIvfIndex): scatter-dispatch fan-out
        # + on-device hierarchical merge instead of one fused kernel
        self._sharded = hasattr(index, "shards") and hasattr(index, "group")
        # how the last IVF search kernel was built: True = the Pallas
        # slab rescore, False = the XLA gather form, None = none built
        self.ivf_use_pallas: Optional[bool] = None
        # bench/test probe: True makes the sharded completion fetch the
        # per-shard candidate lists and tree-merge them ON HOST instead
        # of dispatching the device merge — the A/B that prices the
        # merge's share of serve latency (and the NumPy reference the
        # merge-kernel parity test checks against)
        self.shard_host_merge = False
        # per-shard dispatch-latency histograms, resolved lazily per
        # shard id (pathway_serve_shard_stage_seconds{stage=...,shard=...})
        self._shard_hists: Dict[Tuple[str, int], Any] = {}
        # query TOKEN-STATE export for a downstream late-interaction
        # rerank stage (pathway_tpu/index): the fused kernel additionally
        # returns the per-token hidden states, DEVICE-RESIDENT (never
        # fetched here) — the MaxSim stage consumes them in its own single
        # dispatch, so the query is encoded exactly once per serve.  The
        # retrieve→rerank pipeline flips this on when it is built with a
        # forward index; HF-imported trunks (internal pooling) ignore it.
        self.export_query_tokens = bool(export_query_tokens)

    def _exporting(self) -> bool:
        module = self.encoder.module
        return (
            self.export_query_tokens
            and isinstance(module, TransformerEncoder)
            and module.config.pool == "mean"
        )

    def index_generation(self) -> int:
        """Result-visibility generation of the underlying index — the
        coalescing scheduler folds it into its in-window dedup key so a
        mutation landing mid-window (absorb, retrain install, add)
        can't hand a later rider results from a pre-mutation slot."""
        return int(getattr(self.index, "generation", 0))

    def _query_forward(self, export: bool):
        """The query-encode fragment of the fused kernels: returns a
        traced ``(params, ids, mask) -> (z [B, d] f32, qtok | None)``
        helper.  With ``export`` the trunk runs through a pool-free twin
        (same params) so the SAME single dispatch yields both the pooled
        embedding (bit-identical math to the module's own mean pool) and
        the L2-normalized per-token states for a MaxSim stage."""
        module = self.encoder.module
        if not export:
            def forward(params, ids, mask):
                z = module.apply({"params": params}, ids, mask)
                return z.astype(jnp.float32), None

            return forward
        from ..models.transformer import (
            normalized_token_states,
            token_state_trunk,
        )

        trunk = token_state_trunk(module.config)

        def forward(params, ids, mask):
            hidden = trunk.apply({"params": params}, ids, mask)
            # replicate the module's masked mean pool (same ops, same
            # order, same dtypes — TransformerEncoder.__call__)
            m = mask[:, :, None].astype(hidden.dtype)
            summed = jnp.sum(hidden * m, axis=1)
            counts = jnp.maximum(jnp.sum(m, axis=1), 1.0)
            z = (summed / counts).astype(jnp.float32)
            # the SAME canonical post-processing the doc-side ingest
            # export uses — one vector space for MaxSim by construction
            qtok = normalized_token_states(hidden, mask)
            return z, qtok

        return forward

    def _compiled(self, B: int, L: int, k: int, capacity: int,
                  from_z: bool = False):
        """Exact-index stage-1 kernel.  ``from_z=False`` is the classic
        fused encode+search (params, ids, mask, ...); ``from_z=True`` is
        the SEARCH-ONLY twin taking a precomputed (metric-normalized)
        ``[B, d]`` embedding — the embedding-cache path composes cached
        and fresh rows on device and skips the trunk forward here."""
        export = self._exporting() and not from_z
        key = (B, L, k, capacity, export, from_z)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        self._tripwire.observe(key)
        metric = self.index.metric
        normalize = metric == "cos"
        forward = self._query_forward(export)

        def search(z, qtok, matrix, valid, keys_hi, keys_lo):
            scores = jnp.dot(
                z.astype(matrix.dtype),
                matrix.T,
                preferred_element_type=jnp.float32,
            )
            if metric == "l2sq":
                scores = 2 * scores - jnp.sum(
                    matrix.astype(jnp.float32) ** 2, axis=1
                )[None, :]
            scores = jnp.where(valid[None, :], scores, -jnp.inf)
            s, i = jax.lax.top_k(scores, k)
            # gather the winners' KEYS on device (int32 hi/lo planes kept by
            # the index): completion then needs no host-side slot->key
            # snapshot at all — the old per-call set()/copy() of the 1M-row
            # host mapping was ~30 ms, dwarfing the actual compute
            hi = jnp.take(keys_hi, i, axis=0)
            lo = jnp.take(keys_lo, i, axis=0)
            # pack into ONE INT32 output so the host fetch is a single
            # transfer.  The scores are bitcast into int lanes — not the
            # keys into float lanes — because TPU canonicalizes NaN payloads
            # in float values (0x7fc00000), which would silently corrupt any
            # key whose 32-bit half happens to be a NaN bit pattern (~0.8%
            # of uniform xxh3 keys); integer lanes always survive bit-exact.
            s_bits = jax.lax.bitcast_convert_type(s, jnp.int32)
            packed = jnp.concatenate([s_bits, hi, lo], axis=1)
            if qtok is not None:
                return packed, qtok
            return packed

        if from_z:

            @jax.jit
            def fused(z, matrix, valid, keys_hi, keys_lo):
                # z arrives already metric-normalized (_encode_fn /
                # cached rows captured from it)
                return search(z, None, matrix, valid, keys_hi, keys_lo)

        else:

            @jax.jit
            def fused(params, ids, mask, matrix, valid, keys_hi, keys_lo):
                z, qtok = forward(params, ids, mask)
                if normalize:
                    z = z / jnp.maximum(
                        jnp.linalg.norm(z, axis=-1, keepdims=True), 1e-9
                    )
                return search(z, qtok, matrix, valid, keys_hi, keys_lo)

        # device-time attribution (observe/profile.py): the compiled fn
        # is stored wrapped, so every steady-state call is sampled
        fused = profile.wrap(
            "serve.exact_search" if from_z else "serve.fused_exact", fused
        )
        self._fns[key] = fused
        return fused

    def _compiled_ivf(self, B: int, L: int, k: int, t_pad: int,
                      from_z: bool = False):
        """Returns (fused_fn, k_main, k_tail) — the kernel's output is
        [B, 2*k_main + 2*k_tail] int32 columns: k_main score bit-patterns,
        k_main slots, then k_tail tail-score bit-patterns, k_tail tail row
        indices.  ``t_pad`` is the bucketed exact-tail size (0 = no tail):
        fresh rows not yet absorbed into the slabs are brute-force scored
        INSIDE the same dispatch, so serving never triggers a rebuild.
        ``from_z=True`` is the search-only twin over a precomputed
        metric-normalized ``[B, d]`` embedding (the embedding-cache
        path) — probe + rescore + tail scan unchanged, no trunk forward."""
        index = self.index
        normalize = index.metric == "cos"
        M = index._M_pad
        C = index._centroids.shape[0]
        d = index.dimension
        p = index.n_probe or index._default_probe()
        p = min(p, C)
        k_main = min(k, p * M)
        k_tail = min(k, t_pad) if t_pad else 0
        export = self._exporting() and not from_z
        shape_key = (
            "ivf", B, L, k, p, t_pad,
            index._slabs.shape[0],
            C,
            M,
            export,
            from_z,
        )
        fn = self._fns.get(shape_key)
        if fn is not None:
            return fn, k_main, k_tail
        self._tripwire.observe(shape_key)
        use_pallas = jax.default_backend() == "tpu"
        self.ivf_use_pallas = use_pallas
        forward = self._query_forward(export)

        def search(z, qtok, slabs, bias, centroids, tail_mat, tail_valid):
            cscores = jnp.dot(
                z.astype(centroids.dtype), centroids.T,
                preferred_element_type=jnp.float32,
            )
            _, probe = jax.lax.top_k(cscores, p)
            probe = probe.astype(jnp.int32)
            d_pad = slabs.shape[2]
            zq = z
            if d_pad > d:
                zq = jnp.concatenate(
                    [z, jnp.zeros((B, d_pad - d), z.dtype)], axis=1
                )
            from .ivf_pallas import rescore_shortlist

            scores3 = rescore_shortlist(
                probe, zq, slabs, bias, use_pallas=use_pallas
            )
            scores = scores3.reshape(B, p * M)
            s, i = jax.lax.top_k(scores, k_main)
            jj = i // M
            mm = i % M
            slots = jnp.take_along_axis(probe, jj, axis=1) * M + mm
            slots = jnp.where(jnp.isfinite(s), slots, -1)
            s_bits = jax.lax.bitcast_convert_type(s, jnp.int32)
            parts = [s_bits, slots]
            if t_pad:
                ts = jnp.dot(
                    z.astype(tail_mat.dtype), tail_mat.T,
                    preferred_element_type=jnp.float32,
                )
                ts = jnp.where(tail_valid[None, :], ts, -jnp.inf)
                t_s, t_i = jax.lax.top_k(ts, k_tail)
                parts += [
                    jax.lax.bitcast_convert_type(t_s, jnp.int32),
                    t_i.astype(jnp.int32),
                ]
            packed = jnp.concatenate(parts, axis=1)
            if qtok is not None:
                return packed, qtok
            return packed

        if from_z:

            @jax.jit
            def fused(z, slabs, bias, centroids, tail_mat, tail_valid):
                return search(
                    z, None, slabs, bias, centroids, tail_mat, tail_valid
                )

        else:

            @jax.jit
            def fused(
                params, ids, mask, slabs, bias, centroids, tail_mat, tail_valid
            ):
                z, qtok = forward(params, ids, mask)
                if normalize:
                    z = z / jnp.maximum(
                        jnp.linalg.norm(z, axis=-1, keepdims=True), 1e-9
                    )
                return search(
                    z, qtok, slabs, bias, centroids, tail_mat, tail_valid
                )

        fused = profile.wrap(
            "serve.ivf_search" if from_z else "serve.fused_ivf", fused
        )
        self._fns[shape_key] = fused
        return fused, k_main, k_tail

    # -- sharded scatter-dispatch serve path --------------------------------
    def _encode_fn(self, B: int, L: int):
        """Compiled query-encode kernel for the sharded path: ``(params,
        ids, mask) -> z [B, d] f32`` (metric-normalized), plus the
        device-resident per-token states when a late-interaction stage
        asked for the export.  The embedding is computed ONCE and then
        scattered to every shard — the per-shard search kernels take it
        as input instead of re-running the trunk S times."""
        export = self._exporting()
        key = ("encode", B, L, export, self.index.metric)
        with self._lock:
            fn = self._fns.get(key)
            if fn is not None:
                return fn
            self._tripwire.observe(key)
            normalize = self.index.metric == "cos"
            forward = self._query_forward(export)

            @jax.jit
            def fn(params, ids, mask):
                z, qtok = forward(params, ids, mask)
                if normalize:
                    z = z / jnp.maximum(
                        jnp.linalg.norm(z, axis=-1, keepdims=True), 1e-9
                    )
                if qtok is not None:
                    return z, qtok
                return z

            fn = profile.wrap("serve.encode", fn)
            self._fns[key] = fn
            return fn

    def _cached_embeddings(self, ids, mask, n_real: int, deadline=None):
        """Tier-1 cache wrapper (pathway_tpu/cache): resolve the batch's
        query embeddings — cached device rows where the token ids are
        known, ONE bucketed ``_encode_fn`` launch for the misses — and
        compose them into the shared ``[B, d]`` device batch the
        search-only kernels consume.  Returns ``(z, encoded)`` where
        ``encoded`` says whether an encode launch happened (the caller
        reports it inside the stage-1 logical dispatch group via
        ``record_dispatch(tag, shards=...)`` — the analyzer's
        cache-wrapper convention: a dispatch guarded by a cache lookup
        is accounted by the serve path that owns the group).  All cache
        traffic stays off the serve/index locks; fresh rows are captured
        as async device slices (no fetch, no upload).

        ``models/encoder.py _cached_encode_rows`` is this wrapper's twin
        for the plain encode contract ([n, d], its own retry site, no
        deadline plumbing) — deliberately parallel rather than shared,
        so each dispatch stays lexically visible to the analyzer; a
        cache-path fix here almost certainly applies there too."""
        cache = self.embed_cache
        B, L = ids.shape
        # value-space signature: rows here are the fused trunk's
        # metric-normalized f32 embeddings — a tier shared with the
        # plain encoder must never serve its rows into this space
        rows, misses, row_keys = cache.lookup_rows(
            ids, mask, n_real, deadline=deadline,
            space=f"serve:{self.index.metric}",
        )
        fresh: Dict[int, Any] = {}
        if misses:
            n_miss = len(misses)
            Bm = _bucket(n_miss)
            ids_m = ids[misses]
            mask_m = mask[misses]
            if Bm > n_miss:
                ids_m = np.concatenate(
                    [ids_m, np.zeros((Bm - n_miss, L), ids.dtype)]
                )
                mask_m = np.concatenate(
                    [mask_m, np.zeros((Bm - n_miss, L), mask.dtype)]
                )
            enc = self._encode_fn(Bm, L)
            z_m = retry_call(
                "serve.dispatch", enc, self.encoder.params,
                jnp.asarray(ids_m), jnp.asarray(mask_m), deadline=deadline,
            )
            for j, i in enumerate(misses):
                row = z_m[j]
                fresh[i] = row
                cache.put_row(row_keys[i], row, deadline=deadline)
        d = self.index.dimension
        parts = [
            rows[i] if rows[i] is not None else fresh[i]
            for i in range(n_real)
        ]
        parts += [jnp.zeros((d,), jnp.float32)] * (B - n_real)
        return jnp.stack(parts), bool(misses)

    def _shard_search_fn(self, child, B: int, K: int, t_pad: int):
        """Compiled per-shard search kernel: ``(z [B, d] f32, slabs,
        bias, centroids, tail_mat, tail_valid) -> [B, 2K] int32`` — the
        shard's best ``K`` candidates as score bit-patterns plus packed
        candidate ids (slab slot, or ``n_slotspace + tail_row`` for
        exact-tail winners; ``-1`` invalid).  Resident probe/rescore and
        the exact-tail scan are merged into the one per-shard top-K
        INSIDE the kernel, so the cross-shard merge reduces one sorted
        list per shard.  Returns ``(fn, n_slotspace)``.

        Cache key is pure shapes — shards with identical layout shapes
        (the steady state of balanced routing) share one compiled fn."""
        M = child._M_pad
        C = child._centroids.shape[0]
        C_pad = child._slabs.shape[0]
        d = child.dimension
        d_pad = child._d_pad
        p = child.n_probe or child._default_probe()
        p = min(p, C)
        k_main = min(K, p * M)
        k_tail = min(K, t_pad) if t_pad else 0
        n_slotspace = C_pad * M
        key = ("shard", B, K, p, t_pad, C_pad, C, M, d_pad)
        with self._lock:
            fn = self._fns.get(key)
            if fn is not None:
                return fn, n_slotspace
            self._tripwire.observe(key)
            use_pallas = jax.default_backend() == "tpu"
            self.ivf_use_pallas = use_pallas

            @jax.jit
            def fn(z, slabs, bias, centroids, tail_mat, tail_valid):
                cscores = jnp.dot(
                    z.astype(centroids.dtype), centroids.T,
                    preferred_element_type=jnp.float32,
                )
                _, probe = jax.lax.top_k(cscores, p)
                probe = probe.astype(jnp.int32)
                zq = z
                if d_pad > d:
                    zq = jnp.concatenate(
                        [z, jnp.zeros((B, d_pad - d), z.dtype)], axis=1
                    )
                from .ivf_pallas import rescore_shortlist

                scores3 = rescore_shortlist(
                    probe, zq, slabs, bias, use_pallas=use_pallas
                )
                scores = scores3.reshape(B, p * M)
                s, i = jax.lax.top_k(scores, k_main)
                jj = i // M
                mm = i % M
                slots = jnp.take_along_axis(probe, jj, axis=1) * M + mm
                cand_s = [s]
                cand_i = [jnp.where(jnp.isfinite(s), slots, -1)]
                if t_pad:
                    ts = jnp.dot(
                        z.astype(tail_mat.dtype), tail_mat.T,
                        preferred_element_type=jnp.float32,
                    )
                    ts = jnp.where(tail_valid[None, :], ts, -jnp.inf)
                    t_s, t_i = jax.lax.top_k(ts, k_tail)
                    cand_s.append(t_s)
                    cand_i.append(
                        jnp.where(
                            jnp.isfinite(t_s),
                            n_slotspace + t_i.astype(jnp.int32),
                            -1,
                        )
                    )
                cs = jnp.concatenate(cand_s, axis=1)
                ci = jnp.concatenate(cand_i, axis=1)
                if cs.shape[1] < K:
                    pad = K - cs.shape[1]
                    cs = jnp.pad(cs, ((0, 0), (0, pad)), constant_values=-jnp.inf)
                    ci = jnp.pad(ci, ((0, 0), (0, pad)), constant_values=-1)
                s_out, pos = jax.lax.top_k(cs, K)
                i_out = jnp.take_along_axis(ci, pos, axis=1)
                s_bits = jax.lax.bitcast_convert_type(s_out, jnp.int32)
                return jnp.concatenate([s_bits, i_out], axis=1)

            fn = profile.wrap("serve.shard_search", fn)
            self._fns[key] = fn
            return fn, n_slotspace

    def _merge_fn(self, S: int, B: int, K: int):
        """Compiled hierarchical merge kernel: ``S`` per-shard packed
        candidate lists ``[B, 2K]`` -> global top-K ``[B, 3K]`` int32
        (score bit-patterns, live-shard ordinals, shard-local candidate
        ids) via a pairwise tree reduce over the shard axis
        (ops/topk.tree_merge_topk) — ⌈log2 S⌉ 2K-wide top-k levels
        instead of one S·K selection."""
        from .topk import tree_merge_topk

        key = ("merge", S, B, K)
        with self._lock:
            fn = self._fns.get(key)
            if fn is not None:
                return fn
            self._tripwire.observe(key)

            @jax.jit
            def fn(*packed):
                scores = jnp.stack(
                    [
                        jax.lax.bitcast_convert_type(p[:, :K], jnp.float32)
                        for p in packed
                    ]
                )
                ids = jnp.stack([p[:, K:] for p in packed])
                shard_ids = jnp.stack(
                    [jnp.full((B, K), s, jnp.int32) for s in range(S)]
                )
                s, h, i = tree_merge_topk(scores, shard_ids, ids, K)
                s_bits = jax.lax.bitcast_convert_type(s, jnp.int32)
                return jnp.concatenate([s_bits, h, i], axis=1)

            fn = profile.wrap("serve.shard_merge", fn)
            self._fns[key] = fn
            return fn

    def _shard_hist(self, stage: str, shard: int):
        key = (stage, shard)
        h = self._shard_hists.get(key)
        if h is None:
            h = self._shard_hists[key] = observe.histogram(
                "pathway_serve_shard_stage_seconds",
                stage=stage,
                shard=str(shard),
            )
        return h

    def _submit_sharded(
        self,
        texts: Sequence[str],
        ids: np.ndarray,
        mask: np.ndarray,
        n_real: int,
        k: int,
        t_start: int,
        t_ready: int,
        deadline: Optional[Deadline] = None,
    ):
        """Scatter-dispatch serve over a ``ShardedIvfIndex``: encode the
        coalesced batch ONCE, fan the device-resident embedding out to
        every shard's resident search kernel (one ``device_put`` + one
        launch per shard, all asynchronous), and tree-merge the
        per-shard candidate lists on device — ONE logical dispatch, one
        packed fetch, so the happy-path serve stays at 2 logical
        dispatches + 2 fetches per batch (the dispatch counter's
        per-shard-group accounting carries the physical fan-out width).

        Per-shard failure domains: a shard whose dispatch fails (or
        whose breaker is open) is SKIPPED — the merge runs over the live
        shards, the response is flagged ``shard_skipped``, and only that
        shard's partition loses recall.  The whole serve fails only when
        every nonempty shard is down."""
        index = self.index
        group = index.group
        shards = index.shards
        # dispatch-time GROUP generation snapshot (sums the shard gens),
        # stamped into the result for the tier-0 capture guard
        gen0 = self.index_generation()
        if len(index) == 0:
            empty = ServeResult(
                [[] for _ in texts], meta={"index_generation": gen0}
            )
            handle = lambda: empty  # noqa: E731
            handle.query_tokens = None
            handle.query_mask = mask
            handle.n_queries = n_real
            return handle
        k_eff = min(k, len(index))
        B, L = ids.shape
        enc = self._encode_fn(B, L)
        # the encode launch opens the stage-1 logical dispatch group;
        # its failure (past retries) is a stage-1 outage — the caller's
        # ladder turns it into retrieval_failed
        with observe.span("stage1.encode", queries=n_real, batch=B):
            out = retry_call(
                "serve.dispatch", enc, self.encoder.params, ids, mask,
                deadline=deadline,
            )
        z, qtok = out if self._exporting() else (out, None)
        physical = 1  # the encode launch
        outs: List[Any] = []
        snaps: List[Any] = []
        skipped: List[int] = []
        for s, child in enumerate(shards):
            t_shard = time.perf_counter_ns()
            try:
                if len(child) == 0:
                    outs.append(None)
                    snaps.append(None)
                    continue
                breaker = group.breaker(s)
                if not breaker.allow():
                    raise CircuitOpen(breaker.name)
                # per-shard chaos site OUTSIDE the retry loop: arming
                # shard.dispatch.<s> kills exactly this shard
                # deterministically (the generic shard.dispatch site
                # fires inside retry_call and models transient faults)
                inject.fire(f"shard.dispatch.{s}", deadline=deadline)
                with jax.default_device(group.device(s)), child._lock:  # pathway: allow(lock-order): rank exception index(3)<scheduler(5) — the fused-serve pair order is index-before-pipeline at EVERY site (absorb DONATES slab buffers, forcing launch-before-unlock under the shard's index lock; the compiled-getter guard self._lock nests briefly inside), so the pair is globally ordered and deadlock-free
                    # lock, then span: the bracket times this shard's
                    # launch, not the wait for its lock
                    with observe.span(
                        "shard.dispatch",
                        hist=self._shard_hist("dispatch", s), shard=s,
                    ):
                        if child._slabs is None:
                            child.build()  # first build only
                        else:
                            child.maybe_retrain_async()
                        tail, tail_dev, tail_valid_dev, t_pad = (
                            child._tail_snapshot_device()
                        )
                        fn, n_slotspace = self._shard_search_fn(
                            child, B, k_eff, t_pad
                        )
                        # scatter leg: the shared embedding hops to the
                        # shard's device (async d2d), then the shard kernel
                        # launches — under the child lock, because a
                        # concurrent absorb commit DONATES the slab buffers
                        # (same launch-before-unlock rule as _submit_ivf)
                        z_s = jax.device_put(z, group.device(s))  # pathway: allow(lock-discipline, value-flow): device→device scatter of an UNFETCHED [B, d] embedding — an async ICI hop enqueued like a dispatch, not a host link round trip; the value is loop-invariant but the TARGET device varies per shard (mirrored in residency.DECLARED_TRANSFERS), and it must precede the launch that consumes it under this lock
                        out = retry_call(  # pathway: allow(lock-discipline): dispatch-only — donated absorb buffers force launch-before-unlock; the merged fetch happens off-lock in the completion
                            "shard.dispatch",
                            fn,
                            z_s,
                            child._slabs,
                            child._bias,
                            child._centroids
                            if isinstance(child._centroids, jax.Array)
                            else jnp.asarray(child._centroids),
                            tail_dev,
                            tail_valid_dev,
                            deadline=deadline,
                            policy=_LOCKED_DISPATCH_RETRY,
                            breaker=breaker,
                        )
                        keys_by_slot = child._keys_by_slot  # dispatch-time snap
            except Exception as exc:
                # a dead shard costs recall on its partition, never the
                # request: skip it, flag the serve, keep the rest going
                group.record_skip(s)
                if not skipped:
                    record_degraded(SHARD_SKIPPED)
                skipped.append(s)
                log_once(
                    f"shard.dispatch:{type(exc).__name__}",
                    "stage-1 dispatch to shard %d failed (%r); serving "
                    "without its partition (shard_skipped)",
                    s,
                    exc,
                )
                observe.interval(
                    "shard.dispatch", t_shard, time.perf_counter_ns(),
                    status="skipped", shard=s, error=type(exc).__name__,
                )
                outs.append(None)
                snaps.append(None)
                continue
            physical += 1
            outs.append(out)
            snaps.append((keys_by_slot, tail, n_slotspace, child))
        live = [s for s in range(len(shards)) if outs[s] is not None]
        if not live:
            if skipped:
                raise RuntimeError(
                    f"every nonempty shard failed stage-1 dispatch "
                    f"(skipped={skipped})"
                )
            empty = ServeResult(
                [[] for _ in texts], meta={"index_generation": gen0}
            )
            handle = lambda: empty  # noqa: E731
            handle.query_tokens = qtok
            handle.query_mask = mask
            handle.n_queries = n_real
            return handle
        tail_skipped = any(snaps[s][3].tail_degraded for s in live)
        host_merge = bool(self.shard_host_merge)
        merge_dev = getattr(z, "device", None) or group.device(0)
        out_m = None
        with observe.span(
            "shard.merge", hist=self._shard_hist("merge_dispatch", -1),
            shards=len(live), host_merge=host_merge, skipped=len(skipped),
        ) as merge:
            if not host_merge:
                # gather leg: per-shard packed candidate lists hop back to
                # the merge device (async d2d), then ONE tree-reduce merge
                # kernel produces the packed global top-K — the only output
                # the host ever fetches
                moved = [jax.device_put(outs[s], merge_dev) for s in live]
                mfn = self._merge_fn(len(live), B, k_eff)
                out_m = retry_call(
                    "shard.merge", mfn, *moved,
                    deadline=deadline, policy=_LOCKED_DISPATCH_RETRY,
                )
                physical += 1
                if hasattr(out_m, "copy_to_host_async"):
                    out_m.copy_to_host_async()
            record_dispatch("serve_sharded", shards=physical)
        # the fan-out has no one lock to wait for: its dispatch is the
        # whole of encode + per-shard launches + merge, an interval (the
        # shard.* spans above are its profiler events)
        t_dispatch = merge.t1_ns
        observe.interval(
            "stage1.dispatch", t_ready, t_dispatch,
            hist=_S1_DISPATCH["hist"], kind="sharded", queries=n_real, batch=B,
        )
        _H_TOKENIZE.observe_ns(t_dispatch - t_start)
        observe.record_occupancy("stage1", n_real, B)

        def complete() -> List[List[Tuple[int, float]]]:
            inject.fire("serve.fetch", deadline=deadline)
            if host_merge:
                # probe mode (bench A/B + merge parity reference): fetch
                # every shard's list and tree-merge on host
                from .topk import tree_merge_topk_host

                per_shard = _stage1_fetch(
                    [outs[s] for s in live], n_real, t_dispatch, "sharded_host"
                )
                record_fetch("serve_sharded_host", shards=len(live))
                scores = np.stack(
                    [
                        np.ascontiguousarray(a[:, :k_eff]).view(np.float32)
                        for a in per_shard
                    ]
                )
                cids = np.stack([a[:, k_eff:] for a in per_shard])
                ords = np.stack(
                    [np.full((n_real, k_eff), i, np.int32) for i in range(len(live))]
                )
                m_s, m_h, m_i = tree_merge_topk_host(
                    scores, ords, cids, k_eff
                )
            else:
                (arr,) = _stage1_fetch((out_m,), n_real, t_dispatch, "sharded")
                record_fetch("serve_sharded")
                m_s = np.ascontiguousarray(arr[:, :k_eff]).view(np.float32)
                m_h = arr[:, k_eff : 2 * k_eff]
                m_i = arr[:, 2 * k_eff :]
            results: List[List[Tuple[int, float]]] = []
            with observe.span("stage1.postprocess", **_S1_POST) as post:
                for qi in range(len(texts)):
                    row: List[Tuple[int, float]] = []
                    for j in range(m_s.shape[1]):
                        sc = float(m_s[qi, j])
                        if not np.isfinite(sc):
                            continue
                        ordinal = int(m_h[qi, j])
                        cid = int(m_i[qi, j])
                        if ordinal < 0 or cid < 0:
                            continue
                        keys_by_slot, tail_keys, n_slotspace, _child = snaps[
                            live[ordinal]
                        ]
                        if cid < n_slotspace:
                            row.append((int(keys_by_slot[cid]), sc))
                        elif cid - n_slotspace < len(tail_keys):
                            row.append((tail_keys[cid - n_slotspace], sc))
                    # merged list arrives score-sorted; dedupe upsert twins
                    # (a key resident in both the slab and the tail)
                    seen = set()
                    dedup = []
                    for key, sc in row:
                        if key not in seen:
                            seen.add(key)
                            dedup.append((key, sc))
                    results.append(dedup[:k])
            _H_POST.observe_ns(post.t1_ns - post.t0_ns)
            flags: List[str] = []
            if tail_skipped:
                flags.append(TAIL_SKIPPED)
            if skipped:
                flags.append(SHARD_SKIPPED)
            meta: Dict[str, Any] = {"index_generation": gen0}
            if skipped:
                meta["shards_skipped"] = tuple(skipped)
            return ServeResult(results, degraded=flags, meta=meta)

        complete.query_tokens = qtok
        complete.query_mask = mask
        complete.n_queries = n_real
        return complete

    def _submit_ivf(
        self,
        texts: Sequence[str],
        ids: np.ndarray,
        mask: np.ndarray,
        n_real: int,
        k: int,
        t_start: int,
        t_ready: int,
        deadline: Optional[Deadline] = None,
        z=None,
        stage1_launches: int = 1,
    ):
        """IVF flavor of submit (holds both locks; ``ids``/``mask`` were
        tokenized and bucket-padded OFF them by the caller): centroid
        probe + shortlist rescore + exact-tail scan + top-k in ONE
        dispatch.  NEVER rebuilds (VERDICT r4 #2): fresh rows ride the
        exact tail until add() absorbs them / the background retrain
        lands; staleness just kicks the async retrain.  Winners come back
        as built-index SLOTS (+ tail indices) and map to keys on host
        (O(B*k)) — the key mapping is snapshotted AT DISPATCH
        (keys_by_slot reference + tail key list), so completion reflects
        dispatch-time state even if a rebuild or removal lands in between
        (ADVICE r4 low #3).

        ``z`` (embedding-cache path) is a precomputed metric-normalized
        ``[B, d]`` device embedding: the search-only kernel twin skips
        the trunk forward, and ``stage1_launches`` carries the launch
        group's physical width (2 when the cache wrapper encoded misses,
        1 all-hit) into the dispatch counter's group accounting."""
        index = self.index
        if len(index) == 0:
            empty = ServeResult(
                [[] for _ in texts],
                meta={"index_generation": self.index_generation()},
            )
            return lambda: empty
        # the caller holds both locks: the bracket times the dispatch call
        # itself (tail snapshot, compiled lookup, jit call, async copy)
        with observe.span(
            "stage1.dispatch", kind="ivf", queries=n_real, batch=ids.shape[0],
            **_S1_DISPATCH,
        ) as dispatch:
            if index._slabs is None:
                index.build()  # first build only: nothing to serve from yet
            else:
                index.maybe_retrain_async()
            k_eff = min(k, len(index))
            # exact tail: rows not yet absorbed into the slabs.  The device
            # upload is CACHED on the index and invalidated only when the
            # tail mutates (add/absorb/remove/install) — re-uploading the
            # padded ~3 MB tail matrix on every dispatch was a per-call
            # host->device transfer on the one-RTT latency path (ADVICE r5 #1)
            tail, tail_dev, tail_valid_dev, t_pad = index._tail_snapshot_device()
            dispatch.set(tail=t_pad)
            # degradation ladder: a failed tail upload (after its retry
            # budget) serves resident-only results, flagged on the response;
            # the degraded counter was bumped by the snapshot itself
            tail_skipped = bool(getattr(index, "tail_degraded", False))
            fn, k_main, k_tail = self._compiled_ivf(
                ids.shape[0], ids.shape[1], k_eff, t_pad, from_z=z is not None
            )
            if z is not None:
                args = [z]
            else:
                args = [self.encoder.params, ids, mask]
            args += [
                index._slabs,
                index._bias,
                index._centroids
                if isinstance(index._centroids, jax.Array)
                else jnp.asarray(index._centroids),
                tail_dev,
                tail_valid_dev,
            ]
            # dispatch-time generation snapshot, stamped into the result so
            # the tier-0 capture can refuse a row whose dispatch observed a
            # newer index state than its admission key
            gen0 = self.index_generation()
            # transient dispatch failures retry with backoff under the
            # site's budget ("ivf.dispatch" is also the chaos-suite fault
            # site); the deadline bounds both the attempts and the sleeps
            out = retry_call(
                "ivf.dispatch", fn, *args,
                deadline=deadline, policy=_LOCKED_DISPATCH_RETRY,
            )
            out, qtok = out if self._exporting() and z is None else (out, None)
            record_dispatch("serve_ivf", shards=stage1_launches)
            if hasattr(out, "copy_to_host_async"):
                out.copy_to_host_async()
        t_dispatch = _stage1_launched(t_start, t_ready, dispatch)
        observe.record_occupancy("stage1", n_real, ids.shape[0])
        keys_by_slot = index._keys_by_slot  # rebuilds REPLACE the array

        def complete() -> List[List[Tuple[int, float]]]:
            inject.fire("serve.fetch", deadline=deadline)
            (arr,) = _stage1_fetch((out,), n_real, t_dispatch, "ivf")
            record_fetch("serve_ivf")
            results: List[List[Tuple[int, float]]] = []
            with observe.span("stage1.postprocess", **_S1_POST) as post:
                scores = np.ascontiguousarray(arr[:, :k_main]).view(np.float32)
                slots = arr[:, k_main : 2 * k_main]
                if k_tail:
                    t_scores = np.ascontiguousarray(
                        arr[:, 2 * k_main : 2 * k_main + k_tail]
                    ).view(np.float32)
                    t_idx = arr[:, 2 * k_main + k_tail :]
                for qi in range(len(texts)):
                    row: List[Tuple[int, float]] = []
                    for j in range(slots.shape[1]):
                        s = float(scores[qi, j])
                        slot = int(slots[qi, j])
                        if not np.isfinite(s) or slot < 0:
                            continue
                        # no live-dict filter: removed rows were already
                        # biased to -inf in the DISPATCHED arrays (dispatch-
                        # time semantics); keys_by_slot is the dispatch-time
                        # snapshot
                        row.append((int(keys_by_slot[slot]), s))
                    if k_tail:
                        for j in range(t_idx.shape[1]):
                            s = float(t_scores[qi, j])
                            ti = int(t_idx[qi, j])
                            if np.isfinite(s) and ti < len(tail):
                                row.append((tail[ti], s))
                    row.sort(key=lambda kv: -kv[1])
                    seen = set()
                    dedup = []
                    for key, s in row:
                        if key not in seen:
                            seen.add(key)
                            dedup.append((key, s))
                    results.append(dedup[:k])
            _H_POST.observe_ns(post.t1_ns - post.t0_ns)
            return ServeResult(
                results,
                degraded=(TAIL_SKIPPED,) if tail_skipped else (),
                meta={"index_generation": gen0},
            )

        # DEVICE-RESIDENT query token states for a late-interaction rerank
        # stage: rides the handle, never fetched on this path (the MaxSim
        # stage consumes it inside its own single dispatch)
        complete.query_tokens = qtok
        complete.query_mask = mask
        complete.n_queries = n_real
        return complete

    def submit(
        self,
        texts: Sequence[str],
        k: Optional[int] = None,
        deadline: Optional[Deadline] = None,
    ):
        """Dispatch one serve batch WITHOUT waiting for the result; returns a
        zero-arg callable that completes it (blocking on the async host
        copy).  Concurrent serving pipelines dispatches so the device queue
        stays full — per-batch wall time approaches pure device time instead
        of one host RTT per call.  ``deadline`` bounds the dispatch (and its
        retry budget); exceeding it raises ``DeadlineExceeded`` to the
        caller — the retrieve→rerank pipeline converts that into a flagged
        degraded response instead of surfacing it to the user."""
        k = k or self.k
        index = self.index
        if not texts:
            return lambda: ServeResult()
        # host prep FULLY OFF the serve lock: tokenize + bucket-pad here,
        # so batch N+1's tokenization overlaps batch N's device time and
        # concurrent submitters never serialize their host prep behind
        # one thread's lock hold (tokenizers are stateless; the bucket
        # padding matches encoder.encode's, so B in the compile key still
        # takes a handful of values — round-1 advice)
        n_real = len(texts)
        with observe.span("stage1.tokenize", **_S1_TOKENIZE) as tokenize:
            # the tokenizer lays out the bucket's pad rows (ids PAD, mask 0)
            ids, mask = self.encoder.tokenizer.encode_batch(
                texts, rows=_bucket(n_real), span=tokenize
            )
        # tokenize_pack runs from t_start to the dispatch returning; the
        # wait for the serve locks from t_ready to the dispatch bracket
        t_start, t_ready = tokenize.t0_ns, tokenize.t1_ns
        if self._sharded:
            # no global lock: per-shard child locks cover the donated
            # buffers, and the compile caches lock internally
            return self._submit_sharded(
                texts, ids, mask, n_real, k, t_start, t_ready, deadline
            )
        # tier-1 embedding cache (pathway_tpu/cache): resolve the batch's
        # embeddings BEFORE any serve lock — cached device rows compose
        # with one bucketed encode launch for the misses, and the search
        # kernels below run their from_z twins.  Gated off while a
        # late-interaction stage needs the per-token export (pooled rows
        # cannot stand in for token states).
        z = None
        stage1_launches = 1
        if self.embed_cache is not None and not self._exporting():
            with observe.span("stage1.embed_cache") as lookup:
                z, encoded = self._cached_embeddings(
                    ids, mask, n_real, deadline
                )
            t_ready = lookup.t1_ns or t_ready
            stage1_launches = 2 if encoded else 1
        if self._ivf:
            with index._lock, self._lock:  # pathway: allow(lock-order): rank exception index(3)<scheduler(5) — index-before-pipeline is the fused-serve pair order at EVERY site (IVF absorb DONATES slab buffers, so the stage-1 launch must precede unlocking the index; self._lock nests inside to guard the compiled-fn cache), globally ordered with the shard fan-out's child._lock→self._lock
                return self._submit_ivf(
                    texts, ids, mask, n_real, k, t_start, t_ready, deadline,
                    z=z, stage1_launches=stage1_launches,
                )
        return self._submit_exact(
            texts, ids, mask, n_real, k, t_start, t_ready, deadline,
            z=z, stage1_launches=stage1_launches,
        )

    def _submit_exact(
        self,
        texts: Sequence[str],
        ids: np.ndarray,
        mask: np.ndarray,
        n_real: int,
        k: int,
        t_start: int,
        t_ready: int,
        deadline: Optional[Deadline] = None,
        z=None,
        stage1_launches: int = 1,
    ):
        """Exact-index flavor of submit (``ids``/``mask`` tokenized and
        bucket-padded off-lock by the caller; ``z``/``stage1_launches``
        as in ``_submit_ivf``)."""
        index = self.index
        with index._lock, self._lock:  # pathway: allow(lock-order): rank exception index(3)<scheduler(5) — same index-before-pipeline pair order as the IVF branch (one global order for the pair keeps it deadlock-free; the exact index swaps buffers functionally but shares the submit shape)
            n_items = len(index.key_to_slot)
            if n_items == 0:
                empty = ServeResult(
                    [[] for _ in texts],
                    meta={"index_generation": self.index_generation()},
                )
                return lambda: empty
            k_eff = min(k, n_items)
            B, L = ids.shape
            fn = self._compiled(
                B, L, k_eff, index.capacity, from_z=z is not None
            )
            # capture the device view under the lock; LAUNCH off it.  The
            # exact index replaces matrix/valid/keys functionally (never
            # in place, never donated), so refs snapshotted here stay
            # valid and consistent after the lock drops — unlike the IVF
            # path, whose absorb DONATES slab buffers and must launch
            # before unlocking.  Nothing else host-side to snapshot: the
            # winners' keys come back IN the packed output, and a slot
            # removed at snapshot time scores -inf and is dropped below.
            planes = (
                index._matrix,
                index._valid,
                index._keys_hi,
                index._keys_lo,
            )
            args = (
                (z,) + planes
                if z is not None
                else (self.encoder.params, ids, mask) + planes
            )
            gen0 = self.index_generation()  # dispatch-time snapshot
        # transient dispatch failures retry with backoff ("serve.dispatch"
        # doubles as the chaos-suite fault site); deadline bounds attempts
        with observe.span(
            "stage1.dispatch", kind="exact", queries=n_real, batch=B,
            **_S1_DISPATCH,
        ) as dispatch:
            out = retry_call("serve.dispatch", fn, *args, deadline=deadline)
            out, qtok = out if self._exporting() and z is None else (out, None)
            record_dispatch("serve_exact", shards=stage1_launches)
            if hasattr(out, "copy_to_host_async"):
                out.copy_to_host_async()
        t_dispatch = _stage1_launched(t_start, t_ready, dispatch)
        observe.record_occupancy("stage1", n_real, B)

        def complete() -> List[List[Tuple[int, float]]]:
            inject.fire("serve.fetch", deadline=deadline)
            (arr,) = _stage1_fetch((out,), n_real, t_dispatch, "exact")
            record_fetch("serve_exact")
            results: List[List[Tuple[int, float]]] = []
            with observe.span("stage1.postprocess", **_S1_POST) as post:
                scores = np.ascontiguousarray(arr[:, :k_eff]).view(np.float32)
                ints = np.ascontiguousarray(arr[:, k_eff:]).view(np.uint32)
                hi = ints[:, :k_eff].astype(np.uint64)
                lo = ints[:, k_eff:].astype(np.uint64)
                keys = (hi << np.uint64(32)) | lo
                for qi in range(len(texts)):
                    row: List[Tuple[int, float]] = []
                    for j in range(k_eff):
                        s = float(scores[qi, j])
                        if not np.isfinite(s):
                            continue
                        row.append((int(keys[qi, j]), s))
                    results.append(row[:k])
            _H_POST.observe_ns(post.t1_ns - post.t0_ns)
            return ServeResult(results, meta={"index_generation": gen0})

        # device-resident query token states for a late-interaction stage
        # (see _submit_ivf): attached, never fetched here
        complete.query_tokens = qtok
        complete.query_mask = mask
        complete.n_queries = n_real
        return complete

    def __call__(
        self,
        texts: Sequence[str],
        k: Optional[int] = None,
        deadline: Optional[Deadline] = None,
    ) -> List[List[Tuple[int, float]]]:
        return self.submit(texts, k, deadline=deadline)()

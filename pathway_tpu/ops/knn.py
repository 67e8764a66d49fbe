"""Device-resident incremental KNN index — the framework's retrieval hot path.

TPU-first redesign of the reference's brute-force index
(src/external_integration/brute_force_knn_integration.rs:22-182: growable
Array2<f64> row store with 2x grow / 4x shrink and dot-product scoring):

- the embedding matrix lives in HBM as ``[capacity, d]``, row-sharded over
  the mesh "data" axis (multi-chip) or on the single device;
- add/remove are slot-allocator updates (free-list + capacity doubling) done
  as batched scatters — no host round-trip of the matrix;
- queries are padded to bucket sizes so XLA compiles a handful of shapes,
  scored as one [B,d]x[d,N] matmul (MXU) + ``lax.top_k``; multi-chip search
  does per-shard top-k then an ICI all-gather of k candidates per shard
  (ops/topk.py) — never the full score row.
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..internals.keys import KEY_DTYPE
from ..parallel.mesh import global_zeros, host_to_global, is_multiprocess
from .topk import local_score_topk, sharded_topk

__all__ = ["DeviceKnnIndex", "normalize_metric"]


def normalize_metric(metric) -> str:
    """Accepts "cos"/"l2sq"/"dot", the reference metric-kind enums, or any
    casing; anything unrecognised raises instead of silently mis-scoring."""
    value = getattr(metric, "value", metric)
    value = str(value).lower().replace("cosine", "cos")
    if value in ("ip", "inner_product"):
        value = "dot"
    if value not in ("cos", "l2sq", "dot"):
        raise ValueError(f"unknown KNN metric {metric!r}")
    return value

_QUERY_BUCKETS = (1, 4, 16, 64, 256, 1024)


def _bucket(n: int) -> int:
    for b in _QUERY_BUCKETS:
        if n <= b:
            return b
    return ((n + 1023) // 1024) * 1024


@jax.jit
def _scatter_rows(matrix: jnp.ndarray, slots: jnp.ndarray, rows: jnp.ndarray):
    return matrix.at[slots].set(rows.astype(matrix.dtype))


@partial(jax.jit, static_argnums=2)
def _scatter_flags(valid: jnp.ndarray, slots: jnp.ndarray, flag: bool):
    return valid.at[slots].set(flag)


@jax.jit
def _scatter_vals(arr: jnp.ndarray, slots: jnp.ndarray, vals: jnp.ndarray):
    return arr.at[slots].set(vals)


class DeviceKnnIndex:
    """Incrementally maintained dense KNN index on TPU.

    metric: "cos" (vectors L2-normalised at insert; score = cosine sim) or
    "l2sq" (score = -squared distance) or "dot".
    """

    def __init__(
        self,
        dimension: int,
        metric: str = "cos",
        initial_capacity: int = 1024,
        mesh: Optional[Mesh] = None,
        dtype=jnp.float32,
    ):
        self.dimension = dimension
        self.metric = normalize_metric(metric)
        self.dtype = dtype
        self.mesh = mesh
        self._lock = threading.RLock()
        self.n_shards = mesh.shape["data"] if mesh is not None else 1
        # multi-host mesh: host-side device_put can't target non-addressable
        # devices — all transfers go through host_to_global / jitted creation
        # (SPMD replicas supply identical host data; see parallel/distributed)
        self._multiproc = mesh is not None and is_multiprocess(mesh)
        cap = max(initial_capacity, self.n_shards * 8)
        cap = self._round_capacity(cap)
        self.capacity = cap
        self._matrix = self._device_zeros((cap, dimension))
        self._valid = self._device_zeros((cap,), dtype=jnp.bool_)
        # device-resident slot->key map as two int32 planes (jax runs with
        # 32-bit ints; keys are uint64).  The fused serving path gathers the
        # top slots' keys ON DEVICE so query completion needs no host-side
        # metadata snapshot — an O(len(index)) set/copy per call was the
        # dominant cost of the old host mapping at 1M rows (~30 ms/batch).
        self._keys_hi = self._device_zeros((cap,), dtype=jnp.int32)
        self._keys_lo = self._device_zeros((cap,), dtype=jnp.int32)
        self.key_to_slot: Dict[int, int] = {}
        self.slot_to_key = np.zeros(cap, dtype=KEY_DTYPE)
        self._free: List[int] = list(range(cap - 1, -1, -1))
        self._search_fns: Dict[Tuple[int, int, int], object] = {}
        # result-visibility generation (same contract as
        # IvfKnnIndex.generation): bumped on every mutation that can
        # change what a serve returns — the coalescing scheduler keys
        # its in-window dedup on (text, generation)
        self.generation = 0
        # HBM ledger (observe/hbm.py): the dense matrix + validity/key
        # planes, sampled at scrape time only (weakly held)
        from ..observe import hbm

        hbm.track("knn", self)

    def hbm_bytes(self) -> Dict[str, int]:
        """Device-resident bytes: the allocated-capacity matrix and the
        slot metadata planes (``.nbytes`` is metadata, never a sync)."""
        planes = sum(
            int(getattr(buf, "nbytes", 0))
            for buf in (self._valid, self._keys_hi, self._keys_lo)
        )
        return {
            "matrix": int(getattr(self._matrix, "nbytes", 0)),
            "planes": planes,
        }

    # -- storage helpers ---------------------------------------------------
    def _round_capacity(self, cap: int) -> int:
        """Capacity multiple of shards*8 so row-sharding divides evenly and
        tiles align with the (8,128) f32 layout."""
        unit = self.n_shards * 8
        return ((cap + unit - 1) // unit) * unit

    def _sharding(self, row_sharded: bool = True):
        if self.mesh is None:
            return None
        return NamedSharding(
            self.mesh, P("data", None) if row_sharded else P("data")
        )

    def _device_zeros(self, shape, dtype=None):
        dtype = dtype or self.dtype
        if self.mesh is None:
            return jnp.zeros(shape, dtype=dtype)
        spec = P("data", None) if len(shape) == 2 else P("data")
        return global_zeros(shape, dtype, self.mesh, spec)

    def _to_mesh(self, value, spec=P()):
        """Host (or local-device) data → array usable in jit on this index's
        mesh; replicated by default.  No-op for data already on the mesh."""
        if self.mesh is None:
            return value if isinstance(value, jax.Array) else jnp.asarray(value)
        if (
            isinstance(value, jax.Array)
            and getattr(value.sharding, "mesh", None) == self.mesh
        ):
            return value
        if not self._multiproc and isinstance(value, jax.Array):
            return value  # single-process: jit can reshard local arrays
        if isinstance(value, jax.Array) and not value.is_fully_addressable:
            raise ValueError(
                "device array lives on a different multi-process mesh than "
                "this index — re-shard it onto the index mesh first"
            )
        return host_to_global(np.asarray(value), self.mesh, spec)

    def __len__(self) -> int:
        return len(self.key_to_slot)

    # -- growth ------------------------------------------------------------
    def _grow(self, needed: int) -> None:
        new_cap = self._round_capacity(max(self.capacity * 2, self.capacity + needed))
        old_cap = self.capacity
        dim = self.dimension
        dtype = self.dtype
        if self.mesh is None:
            # device-side copy keeps data in HBM
            new_matrix = jax.lax.dynamic_update_slice(
                jnp.zeros((new_cap, dim), dtype), self._matrix, (0, 0)
            )
            new_valid = jax.lax.dynamic_update_slice(
                jnp.zeros((new_cap,), jnp.bool_), self._valid, (0,)
            )
            new_hi = jax.lax.dynamic_update_slice(
                jnp.zeros((new_cap,), jnp.int32), self._keys_hi, (0,)
            )
            new_lo = jax.lax.dynamic_update_slice(
                jnp.zeros((new_cap,), jnp.int32), self._keys_lo, (0,)
            )
        else:
            # jitted grow with explicit out_shardings: stays sharded, works on
            # multi-process meshes where host-side device_put cannot re-pin
            new_matrix = jax.jit(
                lambda m: jax.lax.dynamic_update_slice(
                    jnp.zeros((new_cap, dim), dtype), m, (0, 0)
                ),
                out_shardings=self._sharding(True),
            )(self._matrix)
            grow_flat = jax.jit(
                lambda v: jax.lax.dynamic_update_slice(
                    jnp.zeros((new_cap,), v.dtype), v, (0,)
                ),
                out_shardings=self._sharding(False),
            )
            new_valid = grow_flat(self._valid)
            new_hi = grow_flat(self._keys_hi)
            new_lo = grow_flat(self._keys_lo)
        self._matrix = new_matrix
        self._valid = new_valid
        self._keys_hi = new_hi
        self._keys_lo = new_lo
        self.slot_to_key = np.concatenate(
            [self.slot_to_key, np.zeros(new_cap - old_cap, dtype=KEY_DTYPE)]
        )
        self._free.extend(range(new_cap - 1, old_cap - 1, -1))
        self.capacity = new_cap
        self._search_fns.clear()  # capacity is baked into compiled shapes

    # -- mutation ----------------------------------------------------------
    def add(self, keys: Sequence[int], vectors: np.ndarray) -> None:
        if len(keys) == 0:
            return
        # coerce BEFORE the lock: callers hand the encoder's device rows
        # straight here — the device→host sync must not run under the
        # index lock (value-flow analyzer finding)
        vectors = np.asarray(vectors, dtype=np.float32).reshape(
            len(keys), self.dimension
        )
        with self._lock:
            # upsert: remove keys that already exist
            existing = [k for k in keys if int(k) in self.key_to_slot]
            if existing:
                self.remove(existing)
            if len(self._free) < len(keys):
                self._grow(len(keys) - len(self._free))
            slots = np.array(
                [self._free.pop() for _ in keys], dtype=np.int32
            )
            if self.metric == "cos":
                norms = np.linalg.norm(vectors, axis=1)
                safe = np.where(norms == 0, 1.0, norms)
                vectors = vectors / safe[:, None]
            for key, slot in zip(keys, slots):
                self.key_to_slot[int(key)] = int(slot)
                self.slot_to_key[slot] = int(key)
            self._scatter(slots, vectors, True, keys=keys)
            self.generation += 1

    def add_from_device(self, keys: Sequence[int], vectors) -> None:
        """Ingest vectors that already live on device (e.g. encoder output) —
        no host round trip at all: normalisation happens on device and
        nothing is fetched back, so a pipelined caller never blocks (l2sq
        ranking recomputes row norms inside the scoring kernel)."""
        with self._lock:
            if len(keys) == 0:
                return
            vectors = vectors.reshape(len(keys), self.dimension)
            existing = [k for k in keys if int(k) in self.key_to_slot]
            if existing:
                self.remove(existing)
            if len(self._free) < len(keys):
                self._grow(len(keys) - len(self._free))
            slots = np.array([self._free.pop() for _ in keys], dtype=np.int32)
            # route through the mesh first, then normalise on device
            vectors = self._to_mesh(vectors)
            norm_fn = getattr(self, "_norm_fn_cache", None)
            if norm_fn is None:
                cos = self.metric == "cos"
                dtype = self.dtype

                def _norms_and_rows(v):
                    norms = jnp.linalg.norm(v.astype(jnp.float32), axis=1)
                    if cos:
                        safe = jnp.where(norms == 0, 1.0, norms)
                        v = (v.astype(jnp.float32) / safe[:, None]).astype(dtype)
                    return norms, v

                out_sh = (
                    None
                    if self.mesh is None
                    else NamedSharding(self.mesh, P())
                )
                norm_fn = (
                    jax.jit(_norms_and_rows)
                    if out_sh is None
                    else jax.jit(_norms_and_rows, out_shardings=(out_sh, out_sh))
                )
                self._norm_fn_cache = norm_fn
            _norms_dev, vectors = norm_fn(vectors)
            for key, slot in zip(keys, slots):
                self.key_to_slot[int(key)] = int(slot)
                self.slot_to_key[slot] = int(key)
            self._scatter(slots, vectors, True, keys=keys)
            self.generation += 1

    def remove(self, keys: Sequence[int]) -> None:
        with self._lock:
            slots = []
            for key in keys:
                slot = self.key_to_slot.pop(int(key), None)
                if slot is not None:
                    slots.append(slot)
                    self._free.append(slot)
            if not slots:
                return
            slots = np.array(slots, dtype=np.int32)
            self._scatter(slots, np.zeros((len(slots), self.dimension), np.float32), False)
            self.generation += 1

    def _scatter(
        self, slots: np.ndarray, vectors, valid: bool, keys=None
    ) -> None:
        """Batched scatter, padded to a bucket to bound recompiles (pad rows
        repeat the first row — idempotent writes).  ``vectors`` may be a host
        numpy array or a device array (add_from_device path).  ``keys`` (add
        path) also updates the device slot->key planes; removals skip them —
        the cleared valid flag masks stale keys."""
        n = len(slots)
        b = _bucket(n)
        on_device = isinstance(vectors, jax.Array)
        if keys is not None:
            keys64 = np.fromiter(
                (int(k) for k in keys), dtype=np.uint64, count=n
            )
            hi = (keys64 >> np.uint64(32)).astype(np.uint32).view(np.int32)
            lo = (keys64 & np.uint64(0xFFFFFFFF)).astype(np.uint32).view(np.int32)
            if b > n:
                hi = np.concatenate([hi, np.full(b - n, hi[0], np.int32)])
                lo = np.concatenate([lo, np.full(b - n, lo[0], np.int32)])
        if b > n:
            slots = np.concatenate([slots, np.full(b - n, slots[0], np.int32)])
            xp = jnp if on_device else np
            vectors = xp.concatenate([vectors, xp.repeat(vectors[:1], b - n, 0)])
        if not on_device:
            vectors = np.asarray(vectors, dtype=self.dtype)
        slots_dev = self._to_mesh(np.asarray(slots))
        vectors_dev = self._to_mesh(vectors)
        if self.mesh is None:
            self._matrix = _scatter_rows(self._matrix, slots_dev, vectors_dev)
            self._valid = _scatter_flags(self._valid, slots_dev, valid)
            if keys is not None:
                self._keys_hi = _scatter_vals(self._keys_hi, slots_dev, self._to_mesh(hi))
                self._keys_lo = _scatter_vals(self._keys_lo, slots_dev, self._to_mesh(lo))
        else:
            row_fn, flag_fn = self._scatter_jits()
            self._matrix = row_fn(self._matrix, slots_dev, vectors_dev)
            self._valid = flag_fn(self._valid, slots_dev, valid)
            if keys is not None:
                val_fn = self._scatter_val_jit()
                self._keys_hi = val_fn(self._keys_hi, slots_dev, self._to_mesh(hi))
                self._keys_lo = val_fn(self._keys_lo, slots_dev, self._to_mesh(lo))

    def _scatter_jits(self):
        """Scatter fns with explicit sharded out_shardings (keeps the matrix
        pinned to the mesh without a host-side device_put re-pin — required
        on multi-process meshes, cheaper on single-process ones)."""
        fns = getattr(self, "_scatter_fn_cache", None)
        if fns is None:
            fns = (
                jax.jit(
                    lambda m, s, r: m.at[s].set(r.astype(m.dtype)),
                    out_shardings=self._sharding(True),
                ),
                jax.jit(
                    lambda v, s, f: v.at[s].set(f),
                    static_argnums=2,
                    out_shardings=self._sharding(False),
                ),
            )
            self._scatter_fn_cache = fns
        return fns

    def _scatter_val_jit(self):
        fn = getattr(self, "_scatter_val_cache", None)
        if fn is None:
            fn = jax.jit(
                lambda a, s, v: a.at[s].set(v),
                out_shardings=self._sharding(False),
            )
            self._scatter_val_cache = fn
        return fn

    # -- search ------------------------------------------------------------
    def search(
        self,
        queries: np.ndarray,
        k: int,
        candidate_keys: Optional[Sequence[Sequence[int]]] = None,
    ) -> List[List[Tuple[int, float]]]:
        """Top-k per query; returns [(key, score), ...] per query row.

        ``candidate_keys``: optional per-query allow-list (metadata filtering
        path) — scoring stays on device, the allow-mask is built host-side."""
        # off-lock coercion: a device-array query batch syncs here, not
        # while holding the index lock (value-flow analyzer finding)
        queries = np.asarray(queries, dtype=np.float32).reshape(-1, self.dimension)
        with self._lock:
            nq = queries.shape[0]
            if nq == 0 or not self.key_to_slot:
                return [[] for _ in range(nq)]
            if self.metric == "cos":
                norms = np.linalg.norm(queries, axis=1)
                queries = queries / np.where(norms == 0, 1.0, norms)[:, None]
            k_eff = min(k, len(self.key_to_slot))
            b = _bucket(nq)
            if b > nq:
                queries = np.concatenate(
                    [queries, np.zeros((b - nq, self.dimension), np.float32)]
                )
            q = self._to_mesh(queries.astype(self.dtype, copy=False))
            scores, idx = self._run_search(q, k_eff)
            # overlap the two d2h copies (each sync fetch is its own host
            # sync — see ops/serving.py)
            for a in (scores, idx):
                if hasattr(a, "copy_to_host_async"):
                    a.copy_to_host_async()
            scores = np.asarray(scores)[:nq]
            idx = np.asarray(idx)[:nq]
            out: List[List[Tuple[int, float]]] = []
            for qi in range(nq):
                allow = None
                if candidate_keys is not None and candidate_keys[qi] is not None:
                    allow = {int(c) for c in candidate_keys[qi]}
                row: List[Tuple[int, float]] = []
                for j in range(k_eff):
                    s = float(scores[qi, j])
                    if not np.isfinite(s):
                        continue
                    key = int(self.slot_to_key[int(idx[qi, j])])
                    if key not in self.key_to_slot:
                        continue
                    if allow is not None and key not in allow:
                        continue
                    row.append((key, s))
                out.append(row[:k])
            return out

    def search_oversampled(
        self,
        queries: np.ndarray,
        k: int,
        accept,  # callable(key) -> bool
        oversample: int = 4,
        max_rounds: int = 3,
    ) -> List[List[Tuple[int, float]]]:
        """Filtered search by over-sampling: fetch oversample*k, drop rejected,
        widen until satisfied or the index is exhausted."""
        return oversampled_filtered_search(
            self, queries, k, accept, oversample=oversample, max_rounds=max_rounds
        )

    def _run_search(self, q: jnp.ndarray, k: int):
        key = (q.shape[0], k, self.capacity)
        fn = self._search_fns.get(key)
        if fn is None:
            if self.mesh is not None:
                mesh = self.mesh
                metric = self.metric

                def fn(qq, m, v):
                    return sharded_topk(mesh, qq, m, v, k, metric=metric)

                fn = jax.jit(fn)
            else:
                metric = self.metric

                def fn(qq, m, v):
                    if metric == "l2sq":
                        # -||q - x||^2 = 2 q.x - ||x||^2 - ||q||^2; rank by 2qx - x2
                        scores = 2 * jnp.dot(
                            qq, m.T, preferred_element_type=jnp.float32
                        ) - jnp.sum(m * m, axis=1)[None, :]
                        scores = jnp.where(v[None, :], scores, -jnp.inf)
                        return jax.lax.top_k(scores, k)
                    return local_score_topk(qq, m, v, k)

                fn = jax.jit(fn)
            self._search_fns[key] = fn
        return fn(q, self._matrix, self._valid)

    # l2sq exact distances post-hoc (scores returned are ranking scores)
    def scores_to_distances(self, scores: np.ndarray, query_norms: np.ndarray):
        if self.metric == "cos":
            return 1.0 - scores
        if self.metric == "l2sq":
            return -(scores - query_norms[:, None] ** 2)
        return -scores


def oversampled_filtered_search(
    index,
    queries: np.ndarray,
    k: int,
    accept,  # callable(key) -> bool
    oversample: int = 4,
    max_rounds: int = 3,
) -> List[List[Tuple[int, float]]]:
    """Shared filtered-search-by-oversampling loop over any index with
    ``search(queries, k)`` / ``__len__`` / ``dimension`` (DeviceKnnIndex and
    IvfKnnIndex): fetch oversample*k, drop rejected, widen until satisfied
    or the index is exhausted."""
    nq = np.asarray(queries).reshape(-1, index.dimension).shape[0]
    results: List[List[Tuple[int, float]]] = [[] for _ in range(nq)]
    kk = k * oversample
    for _ in range(max_rounds):
        rows = index.search(queries, kk)
        done = True
        for qi, row in enumerate(rows):
            accepted = [(key, s) for key, s in row if accept(key)]
            results[qi] = accepted[:k]
            if len(accepted) < k and len(row) >= len(index):
                pass  # exhausted
            elif len(accepted) < k and len(row) == kk:
                done = False
        if done or kk >= max(len(index), 1):
            break
        kk *= 4
    return results

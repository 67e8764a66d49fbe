"""Sharded top-k retrieval kernels.

The retrieval recipe for a row-sharded score matrix (SURVEY.md §2.6 TPU
notes): compute per-shard scores [B, N/s] on each device, take a *local*
``lax.top_k``, all-gather only the (k, index) pairs over ICI, and merge —
moving s·B·k elements over the interconnect instead of B·N.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "sharded_topk",
    "merge_topk",
    "local_score_topk",
    "tree_merge_topk",
    "tree_merge_topk_host",
]


def local_score_topk(
    queries: jnp.ndarray,  # [B, d]
    matrix: jnp.ndarray,  # [N, d] (local shard rows)
    valid: jnp.ndarray,  # [N] bool
    k: int,
    metric: str = "dot",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense scores + local top-k.  MXU-shaped: one [B,d]x[d,N] matmul.

    metric "dot"/"cos" ranks by inner product (cos assumes normalised rows);
    "l2sq" ranks by 2*q.x - ||x||^2 (equivalent to -||q-x||^2 ordering)."""
    scores = jnp.dot(
        queries, matrix.T, preferred_element_type=jnp.float32
    )  # [B, N]
    if metric == "l2sq":
        scores = 2 * scores - jnp.sum(
            matrix.astype(jnp.float32) * matrix.astype(jnp.float32), axis=1
        )[None, :]
    scores = jnp.where(valid[None, :], scores, -jnp.inf)
    k_eff = min(k, matrix.shape[0])
    top_scores, top_idx = jax.lax.top_k(scores, k_eff)  # [B, k]
    if k_eff < k:
        pad = k - k_eff
        top_scores = jnp.pad(top_scores, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        top_idx = jnp.pad(top_idx, ((0, 0), (0, pad)), constant_values=0)
    return top_scores, top_idx


def merge_topk(
    all_scores: jnp.ndarray,  # [S, B, k] per-shard candidates
    all_idx: jnp.ndarray,  # [S, B, k] local row indices
    shard_offsets: jnp.ndarray,  # [S] global row offset of each shard
    k: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Merge per-shard candidate lists into global top-k (global row ids)."""
    S, B, kk = all_scores.shape
    global_idx = all_idx + shard_offsets[:, None, None]
    flat_scores = jnp.transpose(all_scores, (1, 0, 2)).reshape(B, S * kk)
    flat_idx = jnp.transpose(global_idx, (1, 0, 2)).reshape(B, S * kk)
    top_scores, positions = jax.lax.top_k(flat_scores, k)
    top_global = jnp.take_along_axis(flat_idx, positions, axis=1)
    return top_scores, top_global


def tree_merge_topk(
    scores: jnp.ndarray,  # [S, B, K] per-shard candidate scores (desc)
    shard_ids: jnp.ndarray,  # [S, B, K] int32 origin shard of each candidate
    ids: jnp.ndarray,  # [S, B, K] int32 shard-local candidate ids
    k: int,
):
    """Hierarchical top-k over the shard axis: pairwise tree reduce —
    each level merges two shards' sorted candidate lists with one
    ``lax.top_k`` over their 2K-wide concat, halving the shard count
    until one list remains (⌈log2 S⌉ levels instead of one S·K-wide
    selection; at large S the level-wise merges keep every operand at
    the 2K width the top-k unit is fastest at).  Traced helper — callers
    close over it inside their own jitted merge kernel.

    Returns ``(scores [B, k], shard_ids [B, k], ids [B, k])`` sorted by
    score descending.  Only finite scores are meaningful; callers mask
    absent candidates to ``-inf`` (their shard/id survive the merge but
    the host filters non-finite rows)."""
    level = [
        (scores[s], shard_ids[s], ids[s]) for s in range(scores.shape[0])
    ]
    while len(level) > 1:
        nxt = []
        for i in range(0, len(level) - 1, 2):
            sa, ha, ia = level[i]
            sb, hb, ib = level[i + 1]
            cs = jnp.concatenate([sa, sb], axis=1)
            ch = jnp.concatenate([ha, hb], axis=1)
            ci = jnp.concatenate([ia, ib], axis=1)
            kk = min(k, cs.shape[1])
            ms, pos = jax.lax.top_k(cs, kk)
            nxt.append(
                (
                    ms,
                    jnp.take_along_axis(ch, pos, axis=1),
                    jnp.take_along_axis(ci, pos, axis=1),
                )
            )
        if len(level) % 2:
            nxt.append(level[-1])
        level = nxt
    s, h, i = level[0]
    if s.shape[1] > k:
        s, pos = jax.lax.top_k(s, k)
        h = jnp.take_along_axis(h, pos, axis=1)
        i = jnp.take_along_axis(i, pos, axis=1)
    return s, h, i


def tree_merge_topk_host(scores, shard_ids, ids, k):
    """NumPy reference for ``tree_merge_topk`` (tests + the host-merge
    probe the bench uses to price the on-device merge): same candidate
    set and score ordering, host argsort instead of the device tree."""
    import numpy as np

    S, B, K = scores.shape
    flat_s = np.transpose(scores, (1, 0, 2)).reshape(B, S * K)
    flat_h = np.transpose(shard_ids, (1, 0, 2)).reshape(B, S * K)
    flat_i = np.transpose(ids, (1, 0, 2)).reshape(B, S * K)
    order = np.argsort(-flat_s, axis=1, kind="stable")[:, :k]
    take = lambda a: np.take_along_axis(a, order, axis=1)  # noqa: E731
    return take(flat_s), take(flat_h), take(flat_i)


def sharded_topk(
    mesh: Mesh,
    queries: jnp.ndarray,  # [B, d] replicated
    matrix: jnp.ndarray,  # [N, d] sharded on rows over "data"
    valid: jnp.ndarray,  # [N] sharded over "data"
    k: int,
    metric: str = "dot",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """shard_map: per-device score+topk, all-gather candidates, merge.

    Returns replicated ([B, k] scores, [B, k] global row indices)."""
    n_shards = mesh.shape["data"]
    rows_per_shard = matrix.shape[0] // n_shards

    def per_shard(q, m, v):
        local_scores, local_idx = local_score_topk(q, m, v, k, metric=metric)
        # [1, B, k] on each shard -> all_gather over "data" -> [S, B, k]
        gathered_scores = jax.lax.all_gather(local_scores, "data")  # [S, B, k]
        gathered_idx = jax.lax.all_gather(local_idx, "data")
        my_index = jax.lax.axis_index("data")
        offsets = jnp.arange(n_shards) * rows_per_shard
        return merge_topk(gathered_scores, gathered_idx, offsets, k)

    # check_vma off: the replication check rejects all-gather + merge
    fn = jax.shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(), P("data", None), P("data")),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return fn(queries, matrix, valid)

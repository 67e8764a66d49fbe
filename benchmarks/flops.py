"""Operations and bytes the algorithm needs, from shapes alone.

These count the work the requests need (real, unpadded tokens; the probed
slabs of the queries actually asked), not the work an implementation does
(padding to buckets, eight query rows to a kernel program, recomputation).
A multiply-add is two operations.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable


def layer_flops(tokens: int, hidden: int, intermediate: int) -> int:
    """One pre-LN transformer layer over one sequence of ``tokens`` real
    tokens: four hidden x hidden projections, the two attention products
    (scores and weighted values, over all heads together), two MLP products."""
    proj = 4 * 2 * tokens * hidden * hidden
    attn = 2 * 2 * tokens * tokens * hidden
    mlp = 2 * 2 * tokens * hidden * intermediate
    return proj + attn + mlp


def trunk_flops(tokens: int, model: Dict[str, Any]) -> int:
    return model["num_hidden_layers"] * layer_flops(
        tokens, model["hidden_size"], model["intermediate_size"]
    )


def encoder_flops(token_counts: Iterable[int], model: Dict[str, Any]) -> int:
    """Sentence encoder forward over sequences of the given real lengths
    (embedding look-ups, LayerNorms and pooling are not matrix products)."""
    return sum(trunk_flops(int(t), model) for t in token_counts)


def cross_encoder_flops(pair_token_counts: Iterable[int], model: Dict[str, Any]) -> int:
    """Cross-encoder forward over (query, document) pairs: the trunk plus
    the head's hidden x hidden and hidden x 1 products per pair."""
    h = model["hidden_size"]
    return sum(trunk_flops(int(t), model) + 2 * h * h + 2 * h for t in pair_token_counts)


def probe_flops(n_queries: int, n_centroids: int, dim: int) -> int:
    """Scoring every centroid for each query."""
    return 2 * n_queries * n_centroids * dim


def rescore_flops(n_queries: int, n_probe: int, m_pad: int, dim: int) -> int:
    """Exact rescore of the probed slabs: probes x M_pad rows of ``dim``."""
    return 2 * n_queries * n_probe * m_pad * dim


def rescore_bytes(n_queries: int, n_probe: int, m_pad: int, d_pad: int, itemsize: int) -> int:
    """Slab reads of the rescore: each query streams its probed slabs once
    (probes x M_pad x d_pad elements); queries, bias and scores are under
    one percent of that and are left out."""
    return n_queries * n_probe * m_pad * d_pad * itemsize


def roofline_seconds(flops: float, bytes_: float, peaks: Dict[str, float]) -> Dict[str, Any]:
    """Least time the chip could take, and which peak bounds it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return {
        "seconds": max(t_flops, t_bytes),
        "bound": "hbm_bytes_per_s" if t_bytes >= t_flops else "bf16_flops_per_s",
    }

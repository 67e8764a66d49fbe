"""Ring attention — sequence-parallel exact attention for long contexts.

The reference has no model-execution long-context machinery (SURVEY §5.7);
this is new TPU-first surface: shard the sequence over a mesh axis, keep
each device's Q block resident, and rotate K/V blocks around the ring with
``ppermute`` while accumulating softmax online (flash-attention style
running max / normalizer), so attention over length L costs O(L/n) memory
per device and the K/V transfers ride ICI neighbor links.  Equivalent in
exact arithmetic to full softmax attention — verified against the dense
computation in tests on a virtual 8-device mesh.

Layouts (per device, via shard_map):
  q, k, v: [B, L_local, H, Dh]   sharded on the sequence axis
  kv_mask: [B, L_local]          key validity (padding)
  positions: [B, L_local]        global token positions (for causal)
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["ring_attention", "ring_attention_sharded"]


def _block_attn(q, k, v, kv_allowed, q_pos, k_pos, causal, scale):
    """Scores of the local Q block against one K/V block + online-softmax
    pieces.  Returns (block_max, exp_scores @ v, exp_scores row-sums)."""
    s = jnp.einsum(
        "blhd,bmhd->bhlm", q, k, preferred_element_type=jnp.float32
    ) * scale
    allowed = kv_allowed[:, None, None, :]  # [B,1,1,M]
    if causal:
        allowed = jnp.logical_and(
            allowed, (k_pos[:, None, None, :] <= q_pos[:, None, :, None])
        )
    s = jnp.where(allowed, s, -jnp.inf)
    m = jnp.max(s, axis=-1)  # [B,H,L]
    # keep -inf rows finite: exp(-inf - finite) handled via where
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - safe_m[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    o = jnp.einsum("bhlm,bmhd->blhd", p, v.astype(jnp.float32))
    l = jnp.sum(p, axis=-1)  # [B,H,L]
    return m, o, l


def _axis_size(axis_name: str) -> int:
    """Static mapped-axis size across jax versions: ``jax.lax.axis_size``
    (new) falls back to the classic ``psum(1, axis)`` constant-fold on
    0.4.x — both yield a Python int at trace time, which the ring needs
    for its static permutation list and scan length."""
    size_fn = getattr(jax.lax, "axis_size", None)
    if size_fn is not None:
        return int(size_fn(axis_name))
    return int(jax.lax.psum(1, axis_name))


def ring_attention(
    q, k, v, kv_mask, positions, axis_name: str, causal: bool = False
):
    """Per-device body (call inside shard_map over ``axis_name``)."""
    n = _axis_size(axis_name)
    scale = 1.0 / np.sqrt(q.shape[-1])
    q32 = q.astype(jnp.float32)
    q_pos = positions

    def merge(m, o, l, bm, bo, bl):
        new_m = jnp.maximum(m, bm)
        safe = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        corr_old = jnp.where(jnp.isfinite(m), jnp.exp(m - safe), 0.0)
        corr_new = jnp.where(jnp.isfinite(bm), jnp.exp(bm - safe), 0.0)
        o = o * corr_old[..., None].transpose(0, 2, 1, 3) + bo * corr_new[
            ..., None
        ].transpose(0, 2, 1, 3)
        l = l * corr_old + bl * corr_new
        return new_m, o, l

    # local block first, then rotate-then-compute for the remaining n-1
    # blocks — n blocks need only n-1 rotations, so no wasted ICI round
    allowed0 = kv_mask.astype(bool)
    m, o, l = _block_attn(
        q32, k.astype(jnp.float32), v, allowed0, q_pos, positions, causal, scale
    )

    def step(carry, _):
        k_blk, v_blk, blk_mask, blk_pos, m, o, l = carry
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        blk_mask = jax.lax.ppermute(blk_mask, axis_name, perm)
        blk_pos = jax.lax.ppermute(blk_pos, axis_name, perm)
        bm, bo, bl = _block_attn(
            q32, k_blk.astype(jnp.float32), v_blk, blk_mask, q_pos, blk_pos,
            causal, scale,
        )
        m, o, l = merge(m, o, l, bm, bo, bl)
        return (k_blk, v_blk, blk_mask, blk_pos, m, o, l), None

    if n > 1:
        (k_f, v_f, m_f, p_f, m, o, l), _ = jax.lax.scan(
            step,
            (k, v, allowed0, positions, m, o, l),
            None,
            length=n - 1,
        )
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]  # [B,L,H,1]
    return (o / denom).astype(q.dtype)


def ring_attention_sharded(
    mesh: Mesh,
    q,
    k,
    v,
    kv_mask,
    positions,
    axis: str = "sp",
    causal: bool = False,
):
    """shard_map wrapper: q/k/v sharded on the sequence dim over ``axis``."""
    spec_qkv = P(None, axis, None, None)
    spec_mask = P(None, axis)
    fn = jax.shard_map(
        partial(ring_attention, axis_name=axis, causal=causal),
        mesh=mesh,
        in_specs=(spec_qkv, spec_qkv, spec_qkv, spec_mask, spec_mask),
        out_specs=spec_qkv,
        check_vma=False,
    )
    return fn(q, k, v, kv_mask, positions)

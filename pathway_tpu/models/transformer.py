"""Flax transformer encoder — the shared trunk for embedders/rerankers.

Designed for the MXU: all matmuls batched, static shapes, bf16 activations,
and flax logical-axis annotations so large configs shard over the mesh
"model" axis via tensor parallelism (SURVEY.md §7.6; the parallel module
turns logical axes into NamedSharding)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "KVTransformerDecoder",
    "SlotKVDecoder",
    "TransformerConfig",
    "TransformerEncoder",
    "normalized_token_states",
    "resolve_heads",
    "token_state_trunk",
]


def token_state_trunk(config: "TransformerConfig") -> "TransformerEncoder":
    """A pool-free twin of a trunk config — applies the SAME params (no
    pooling layer carries weights) and returns raw [B, L, d] hidden
    states.  The one constructor for every token-state export site."""
    from dataclasses import replace

    return TransformerEncoder(replace(config, pool="none"))


def normalized_token_states(hidden, mask):
    """Canonical token-state post-processing for late interaction
    (traced fragment): f32 cast, per-token L2 normalization (1e-9
    floor), pad tokens zeroed.  Doc-side ingest export
    (models/encoder.py) and query-side serve export (ops/serving.py)
    BOTH go through this one function — MaxSim is only meaningful if
    stored doc tokens and serve-time query tokens live in the identical
    vector space, so the math must not be able to drift between them."""
    hidden = hidden.astype(jnp.float32)
    hidden = hidden / jnp.maximum(
        jnp.linalg.norm(hidden, axis=-1, keepdims=True), 1e-9
    )
    return hidden * mask[:, :, None].astype(jnp.float32)


def resolve_heads(d_model: int, requested: int) -> int:
    """Largest head count <= requested that divides d_model (so arbitrary
    embedder dimensions work without manual head tuning)."""
    for h in range(min(requested, d_model), 0, -1):
        if d_model % h == 0:
            return h
    return 1


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32768
    d_model: int = 384
    n_heads: int = 6
    n_layers: int = 6
    d_ff: int = 1536
    max_len: int = 512
    dtype: Any = jnp.bfloat16
    pool: str = "mean"  # mean | cls | none
    causal: bool = False
    # long-context: shard the sequence dim over this mesh axis and attend
    # via ring attention (ops/ring_attention.py) — O(L/n) activation memory
    # per device, K/V rotated over ICI neighbor links
    mesh: Any = None
    sequence_axis: Optional[str] = None

    # what a cache of this trunk holds per sequence: one row per layer, one
    # pass over the stack (models/looped.py states its own)
    head_dim = property(lambda self: self.d_model // self.n_heads)
    total_ut_steps = property(lambda self: 1)
    cache_depth = property(lambda self: self.n_layers)


class MlpBlock(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h = nn.Dense(
            cfg.d_ff,
            dtype=cfg.dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.xavier_uniform(), ("embed", "mlp")
            ),
        )(x)
        h = nn.gelu(h)
        return nn.Dense(
            cfg.d_model,
            dtype=cfg.dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.xavier_uniform(), ("mlp", "embed")
            ),
        )(h)


class SelfAttention(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x, mask, segments=None):
        cfg = self.config
        B, L, D = x.shape
        head_dim = cfg.d_model // cfg.n_heads

        def proj(name, logical):
            return nn.Dense(
                cfg.d_model,
                dtype=cfg.dtype,
                name=name,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.xavier_uniform(), logical
                ),
            )

        q = proj("query", ("embed", "heads"))(x)
        k = proj("key", ("embed", "heads"))(x)
        v = proj("value", ("embed", "heads"))(x)
        q = q.reshape(B, L, cfg.n_heads, head_dim)
        k = k.reshape(B, L, cfg.n_heads, head_dim)
        v = v.reshape(B, L, cfg.n_heads, head_dim)
        if cfg.sequence_axis is not None and cfg.mesh is not None:
            # sequence packing and sequence sharding are mutually
            # exclusive: the ring walks one logical sequence, and packed
            # rows would attend across document boundaries undetected
            assert segments is None, (
                "packed (segments) forward is not supported with "
                "ring/sequence-parallel attention"
            )
            from ..ops.ring_attention import ring_attention_sharded

            positions = jnp.broadcast_to(jnp.arange(L)[None, :], (B, L))
            out = ring_attention_sharded(
                cfg.mesh,
                q,
                k,
                v,
                mask.astype(bool),
                positions,
                axis=cfg.sequence_axis,
                causal=cfg.causal,
            ).reshape(B, L, cfg.d_model)
            return proj("out", ("heads", "embed"))(out)
        scores = jnp.einsum("blhd,bmhd->bhlm", q, k) / np.sqrt(head_dim)
        big_neg = jnp.finfo(jnp.float32).min
        if segments is not None:
            # PACKED rows: token l attends token m iff both belong to the
            # SAME nonzero segment (block-diagonal attention) — several
            # short documents share one row with exact per-doc semantics
            same = segments[:, None, :, None] == segments[:, None, None, :]
            attn_mask = same & (segments[:, None, None, :] > 0)
        else:
            attn_mask = mask[:, None, None, :]  # [B,1,1,L] key mask
        if cfg.causal:
            causal = jnp.tril(jnp.ones((L, L), dtype=bool))
            attn_mask = attn_mask * causal[None, None, :, :]
        scores = jnp.where(attn_mask > 0, scores, big_neg)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(cfg.dtype)
        out = jnp.einsum("bhlm,bmhd->blhd", probs, v).reshape(B, L, cfg.d_model)
        return proj("out", ("heads", "embed"))(out)


class EncoderBlock(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x, mask, segments=None):
        cfg = self.config
        h = nn.LayerNorm(dtype=cfg.dtype)(x)
        x = x + SelfAttention(cfg)(h, mask, segments)
        h = nn.LayerNorm(dtype=cfg.dtype)(x)
        x = x + MlpBlock(cfg)(h)
        return x


class KVSelfAttention(nn.Module):
    """Params-compatible incremental twin of ``SelfAttention``: attends
    ``Ln`` NEW tokens against a persistent K/V buffer instead of
    re-projecting the whole sequence.  The new tokens' K/V are inserted
    at ``write_pos`` (per row) and the updated buffers returned — the
    caller (``KVTransformerDecoder``) threads them through the decode.

    Numerics are kept LINE-FOR-LINE with ``SelfAttention`` (same
    projection names/dtypes, same ``big_neg`` masking, f32 softmax):
    under causal attention a position's K/V depends only on tokens at or
    before it, so for real query positions the score rows here are
    bit-identical to the full re-attend — the parity test in
    tests/test_serve_cache.py holds token-for-token.

    ``quant=True`` (ops/kv_quant.py): the cache buffers are int8 with
    per-(head, channel) stored scales — new K/V quantize at the write
    and EVERY read dequantizes inside this kernel, so prefill and
    decode attend identical values and warm joins stay deterministic."""

    config: TransformerConfig
    quant: bool = False

    @nn.compact
    def __call__(
        self, x, k_cache, v_cache, write_pos, q_pos,
        k_scales=None, v_scales=None,
    ):
        cfg = self.config
        B, Ln, D = x.shape
        T = k_cache.shape[1]
        head_dim = cfg.d_model // cfg.n_heads

        def proj(name, logical):
            return nn.Dense(
                cfg.d_model,
                dtype=cfg.dtype,
                name=name,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.xavier_uniform(), logical
                ),
            )

        q = proj("query", ("embed", "heads"))(x)
        k_new = proj("key", ("embed", "heads"))(x)
        v_new = proj("value", ("embed", "heads"))(x)
        q = q.reshape(B, Ln, cfg.n_heads, head_dim)
        k_new = k_new.reshape(B, Ln, cfg.n_heads, head_dim)
        v_new = v_new.reshape(B, Ln, cfg.n_heads, head_dim)
        if self.quant:
            from ..ops.kv_quant import dequantize_kv, quantize_kv

            k_new = quantize_kv(k_new, k_scales)
            v_new = quantize_kv(v_new, v_scales)
        # insert the new tokens' K/V at each row's write position (rows
        # decode at different offsets: prompts have different lengths)
        insert = jax.vmap(
            lambda buf, new, p: jax.lax.dynamic_update_slice(
                buf, new, (p, 0, 0)
            )
        )
        k_cache = insert(k_cache, k_new, write_pos)
        v_cache = insert(v_cache, v_new, write_pos)
        if self.quant:
            k_att = dequantize_kv(k_cache, k_scales, cfg.dtype)
            v_att = dequantize_kv(v_cache, v_scales, cfg.dtype)
        else:
            k_att, v_att = k_cache, v_cache
        scores = jnp.einsum("blhd,bmhd->bhlm", q, k_att) / np.sqrt(head_dim)
        big_neg = jnp.finfo(jnp.float32).min
        # query at global position q_pos[b, l] attends key slot t iff
        # t <= q_pos — slots past the write frontier are either unwritten
        # (zeros) or stale pad K/V, and both are masked to exact zero
        # probability, so they can never perturb the output
        key_pos = jnp.arange(T, dtype=jnp.int32)
        attn_mask = key_pos[None, None, :] <= q_pos[:, :, None]
        scores = jnp.where(attn_mask[:, None, :, :], scores, big_neg)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(cfg.dtype)
        out = jnp.einsum("bhlm,bmhd->blhd", probs, v_att).reshape(
            B, Ln, cfg.d_model
        )
        return proj("out", ("heads", "embed"))(out), k_cache, v_cache


class KVEncoderBlock(nn.Module):
    """Params-compatible incremental twin of ``EncoderBlock`` — explicit
    submodule names pin the param tree to the trunk's layout."""

    config: TransformerConfig
    quant: bool = False

    @nn.compact
    def __call__(
        self, x, k_cache, v_cache, write_pos, q_pos,
        k_scales=None, v_scales=None,
    ):
        cfg = self.config
        h = nn.LayerNorm(dtype=cfg.dtype, name="LayerNorm_0")(x)
        attn, k_cache, v_cache = KVSelfAttention(
            cfg, name="SelfAttention_0", quant=self.quant
        )(h, k_cache, v_cache, write_pos, q_pos, k_scales, v_scales)
        x = x + attn
        h = nn.LayerNorm(dtype=cfg.dtype, name="LayerNorm_1")(x)
        x = x + MlpBlock(cfg, name="MlpBlock_0")(h)
        return x, k_cache, v_cache


class KVTransformerDecoder(nn.Module):
    """Incremental causal decode over the SAME params as a causal
    ``TransformerEncoder`` (the generator trunk): forward ``Ln`` new
    tokens against per-layer K/V buffers ``[B, n_layers, T, H, hd]``,
    returning the final-LN hidden states for those tokens plus the
    updated buffers.  One module serves both phases of a KV decode:

    - **prefill**: ``Ln`` = the prompt suffix, ``write_pos`` = the
      cached-prefix length (0 cold);
    - **decode step**: ``Ln = 1``, ``write_pos`` = the row's current
      token count.

    This is what turns the generator's O(steps × L²) re-attend decode
    into O(steps × L) — and, with the prefix cache
    (pathway_tpu/cache/prefix.py), lets prompts sharing a prefix skip
    its prefill entirely.

    ``quant=True``: the per-layer buffers are int8 and ``k_scales``/
    ``v_scales`` ``[n_layers, H, hd]`` must be passed — each layer's
    attention quantizes its writes and dequantizes its reads."""

    config: TransformerConfig
    quant: bool = False

    @nn.compact
    def __call__(
        self, ids_new, positions, k_caches, v_caches, write_pos, q_pos,
        k_scales=None, v_scales=None,
    ):
        cfg = self.config
        tok = nn.Embed(
            cfg.vocab_size,
            cfg.d_model,
            dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")
            ),
            name="tok_embed",
        )(ids_new)
        pos = nn.Embed(
            cfg.max_len,
            cfg.d_model,
            dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("pos", "embed")
            ),
            name="pos_embed",
        )(positions)
        x = tok + pos
        new_k = []
        new_v = []
        for i in range(cfg.n_layers):
            x, ki, vi = KVEncoderBlock(
                cfg, name=f"block_{i}", quant=self.quant
            )(
                x, k_caches[:, i], v_caches[:, i], write_pos, q_pos,
                None if k_scales is None else k_scales[i],
                None if v_scales is None else v_scales[i],
            )
            new_k.append(ki)
            new_v.append(vi)
        x = nn.LayerNorm(dtype=cfg.dtype, name="final_ln")(x)
        return x, jnp.stack(new_k, axis=1), jnp.stack(new_v, axis=1)


class SlotSelfAttention(nn.Module):
    """Params-compatible slot-pool twin of ``KVSelfAttention``: the
    batch dimension is a pool of persistent SLOTS and only ACTIVE lanes
    may move their K/V.  The freeze is applied at the WRITE, not with a
    post-hoc full-buffer select: the inserted value is the new token's
    K/V for active lanes and the buffer's EXISTING value for inactive
    ones — a single [S, Ln, H, hd] mask instead of two [S, T, H, hd]
    copies per layer per step, which keeps the per-step scatter
    in-place-friendly for XLA's loop optimizer.  For active lanes the
    inserted values (and therefore scores, probs, outputs) are
    line-for-line ``KVSelfAttention``'s — the twin relation the
    token-identity tests pin down.

    ``quant=True``: int8 pool with per-(head, channel) stored scales —
    same write-masking over int8 values, reads dequantized in-kernel
    (ops/kv_quant.py)."""

    config: TransformerConfig
    quant: bool = False

    @nn.compact
    def __call__(
        self, x, k_cache, v_cache, write_pos, q_pos, active,
        k_scales=None, v_scales=None,
    ):
        cfg = self.config
        B, Ln, D = x.shape
        T = k_cache.shape[1]
        head_dim = cfg.d_model // cfg.n_heads

        def proj(name, logical):
            return nn.Dense(
                cfg.d_model,
                dtype=cfg.dtype,
                name=name,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.xavier_uniform(), logical
                ),
            )

        q = proj("query", ("embed", "heads"))(x)
        k_new = proj("key", ("embed", "heads"))(x)
        v_new = proj("value", ("embed", "heads"))(x)
        q = q.reshape(B, Ln, cfg.n_heads, head_dim)
        k_new = k_new.reshape(B, Ln, cfg.n_heads, head_dim)
        v_new = v_new.reshape(B, Ln, cfg.n_heads, head_dim)
        if self.quant:
            from ..ops.kv_quant import dequantize_kv, quantize_kv

            k_new = quantize_kv(k_new, k_scales)
            v_new = quantize_kv(v_new, v_scales)
        # masked write: inactive lanes re-insert what the buffer already
        # holds at their write position — their K/V is bit-frozen
        read = jax.vmap(
            lambda buf, p: jax.lax.dynamic_slice(
                buf, (p, 0, 0), (Ln, cfg.n_heads, head_dim)
            )
        )
        sel = active[:, None, None, None]
        k_ins = jnp.where(sel, k_new, read(k_cache, write_pos))
        v_ins = jnp.where(sel, v_new, read(v_cache, write_pos))
        insert = jax.vmap(
            lambda buf, new, p: jax.lax.dynamic_update_slice(
                buf, new, (p, 0, 0)
            )
        )
        k_cache = insert(k_cache, k_ins, write_pos)
        v_cache = insert(v_cache, v_ins, write_pos)
        if self.quant:
            k_att = dequantize_kv(k_cache, k_scales, cfg.dtype)
            v_att = dequantize_kv(v_cache, v_scales, cfg.dtype)
        else:
            k_att, v_att = k_cache, v_cache
        scores = jnp.einsum("blhd,bmhd->bhlm", q, k_att) / np.sqrt(head_dim)
        big_neg = jnp.finfo(jnp.float32).min
        key_pos = jnp.arange(T, dtype=jnp.int32)
        attn_mask = key_pos[None, None, :] <= q_pos[:, :, None]
        scores = jnp.where(attn_mask[:, None, :, :], scores, big_neg)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(cfg.dtype)
        out = jnp.einsum("bhlm,bmhd->blhd", probs, v_att).reshape(
            B, Ln, cfg.d_model
        )
        return proj("out", ("heads", "embed"))(out), k_cache, v_cache


class SlotEncoderBlock(nn.Module):
    """Slot-pool twin of ``KVEncoderBlock`` — explicit submodule names
    pin the param tree to the trunk's layout."""

    config: TransformerConfig
    quant: bool = False

    @nn.compact
    def __call__(
        self, x, k_cache, v_cache, write_pos, q_pos, active,
        k_scales=None, v_scales=None,
    ):
        cfg = self.config
        h = nn.LayerNorm(dtype=cfg.dtype, name="LayerNorm_0")(x)
        attn, k_cache, v_cache = SlotSelfAttention(
            cfg, name="SelfAttention_0", quant=self.quant
        )(h, k_cache, v_cache, write_pos, q_pos, active, k_scales, v_scales)
        x = x + attn
        h = nn.LayerNorm(dtype=cfg.dtype, name="LayerNorm_1")(x)
        x = x + MlpBlock(cfg, name="MlpBlock_0")(h)
        return x, k_cache, v_cache


class SlotKVDecoder(nn.Module):
    """Slot-indexed twin of ``KVTransformerDecoder`` for the continuous
    decode engine (serve/decode.py): the batch dimension is a pool of
    ``S`` persistent SLOTS whose K/V buffers ``[S, n_layers, T, H, hd]``
    outlive any one request, and the step advances only ACTIVE slots.

    Requests JOIN a slot mid-flight (prefill writes their prompt K/V)
    and LEAVE at EOS; the pool buffers are then reused by the next
    request.  Two properties make the in-flight mixing safe:

    - **inactive slots do not move**: each layer's K/V write is masked
      per lane (``SlotSelfAttention`` re-inserts the existing value for
      inactive lanes), so an idle or finished slot's K/V is bit-frozen
      no matter what garbage its lane computed.  For active slots the
      buffers and hidden states are exactly what
      ``KVTransformerDecoder`` would have produced — the twin relation
      the token-identity tests pin down;
    - **stale K/V cannot leak**: the attention masks every key slot
      past a row's ``q_pos`` to exact-zero probability, and a joining
      request's prefill (re)writes every position it will ever attend —
      so a reused slot can never see its previous occupant.

    ``quant=True``: int8 pool + ``[n_layers, H, hd]`` stored scales
    (ops/kv_quant.py).  ``layers=D`` runs only the FIRST ``D`` trunk
    blocks (plus ``final_ln``) over the same param tree — the reduced-
    layer DRAFT trunk of the speculative decode path: its pool slice is
    ``[S, D, T, H, hd]`` and its proposals need no second model."""

    config: TransformerConfig
    quant: bool = False
    layers: Optional[int] = None

    @nn.compact
    def __call__(
        self, ids_new, positions, k_pool, v_pool, write_pos, q_pos, active,
        k_scales=None, v_scales=None,
    ):
        cfg = self.config
        tok = nn.Embed(
            cfg.vocab_size,
            cfg.d_model,
            dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")
            ),
            name="tok_embed",
        )(ids_new)
        pos = nn.Embed(
            cfg.max_len,
            cfg.d_model,
            dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("pos", "embed")
            ),
            name="pos_embed",
        )(positions)
        x = tok + pos
        new_k = []
        new_v = []
        n_layers = cfg.n_layers if self.layers is None else self.layers
        for i in range(n_layers):
            x, ki, vi = SlotEncoderBlock(
                cfg, name=f"block_{i}", quant=self.quant
            )(
                x, k_pool[:, i], v_pool[:, i], write_pos, q_pos, active,
                None if k_scales is None else k_scales[i],
                None if v_scales is None else v_scales[i],
            )
            new_k.append(ki)
            new_v.append(vi)
        x = nn.LayerNorm(dtype=cfg.dtype, name="final_ln")(x)
        return x, jnp.stack(new_k, axis=1), jnp.stack(new_v, axis=1)


class TransformerEncoder(nn.Module):
    """Token ids + mask -> pooled embedding (or full hidden states)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, ids, mask, segments=None, positions=None, n_segments=0):
        """Unpacked: ``(ids, mask) -> [B, d]`` pooled embeddings.

        PACKED (sequence packing — several short documents share one row,
        the TPU-idiomatic answer to variable-length corpora): pass
        ``segments`` [B, L] (0 = pad, 1..n_segments = document within the
        row), ``positions`` [B, L] (restarting per document so positional
        embeddings match the unpacked encoding), and static
        ``n_segments``; returns ``[B, n_segments, d]`` per-document
        embeddings (zero rows for absent segments).  Attention is
        block-diagonal per segment, so results equal the unpacked
        forward up to dtype accumulation order."""
        cfg = self.config
        B, L = ids.shape
        tok = nn.Embed(
            cfg.vocab_size,
            cfg.d_model,
            dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")
            ),
            name="tok_embed",
        )(ids)
        if positions is None:
            positions = jnp.arange(L)[None, :]
        pos = nn.Embed(
            cfg.max_len,
            cfg.d_model,
            dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("pos", "embed")
            ),
            name="pos_embed",
        )(positions)
        x = tok + pos
        for i in range(cfg.n_layers):
            x = EncoderBlock(cfg, name=f"block_{i}")(x, mask, segments)
        x = nn.LayerNorm(dtype=cfg.dtype, name="final_ln")(x)
        if segments is not None:
            # per-segment masked mean pool as ONE matmul per row:
            # onehot [B, L, S] x hidden [B, L, d] -> [B, S, d]
            assert n_segments > 0, "packed forward needs static n_segments"
            assert cfg.pool == "mean", (
                f"packed forward implements mean pooling only (pool="
                f"{cfg.pool!r} would silently change semantics)"
            )
            seg_ids = jnp.arange(1, n_segments + 1)
            onehot = (segments[:, :, None] == seg_ids[None, None, :]).astype(
                x.dtype
            )
            summed = jnp.einsum("bls,bld->bsd", onehot, x)
            counts = jnp.maximum(jnp.sum(onehot, axis=1), 1.0)[:, :, None]
            return (summed / counts).astype(jnp.float32)
        if cfg.pool == "none":
            return x
        if cfg.pool == "cls":
            return x[:, 0, :].astype(jnp.float32)
        # masked mean pool
        m = mask[:, :, None].astype(x.dtype)
        summed = jnp.sum(x * m, axis=1)
        counts = jnp.maximum(jnp.sum(m, axis=1), 1.0)
        return (summed / counts).astype(jnp.float32)

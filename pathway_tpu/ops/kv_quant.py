"""int8 KV-pool quantization — per-(layer, head, channel) stored scales.

The forward index (index/forward.py, PAPER.md §2.5) already ships the
int8+stored-scales idiom for token states: absmax-derived scales, values
``round(x / scale)`` clipped into [-127, 127], dequantized inside the
consuming kernel.  This module applies the same idiom to the continuous
decoder's slot K/V pool ``[slots, L, T, H, hd]`` (serve/decode.py):
halving bytes-per-cached-token doubles slots×context at fixed HBM.

Two properties drive the design:

- **scales are STATIC per (layer, head, channel)** — derived from the
  generator's own projection weights, not calibrated per token.  K/V
  entries are LayerNorm outputs pushed through the key/value Dense
  layers, so ``|k_c| <= sqrt(d) * ||gamma ⊙ W[:, c]||_2 +
  |beta · W[:, c]| + |b_c|`` (Cauchy–Schwarz over the unit-variance LN
  output) is a rigorous per-channel bound: no runtime clipping of
  in-bound values, no per-token scale storage (which would eat the 2×
  ratio the int8 pool exists for), and the same scale for every write
  makes quantization IDEMPOTENT — ``quantize(dequantize(q)) == q`` —
  so warm prefix-cache joins re-quantize to bit-identical pool bytes.
- **every read goes through the same dequant** — prefill and decode
  both attend ``dequantize(int8)`` (models/transformer.py quant twins),
  so warm and cold joins see identical attention inputs and int8
  decodes are deterministic; the drift from a float cache is bounded
  in tests/test_decode.py on the logits the tokens were chosen from
  (``INT8_LOGIT_LIMIT``), with two planted read faults above the limit.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax.numpy as jnp

__all__ = ["dequantize_kv", "kv_pool_scales", "quantize_kv"]


def kv_pool_scales(params, config) -> Tuple[Any, Any]:
    """Per-(layer, head, channel) K/V scales ``[L, H, hd]`` (f32) for a
    generator param tree (``block_i`` → LayerNorm_0 + SelfAttention_0
    key/value Dense).  ``scale = bound / 127`` with the channel bound
    above — host/init-time math, one tiny array per pool."""
    L = config.n_layers
    H = config.n_heads
    hd = config.d_model // H
    d = config.d_model
    k_rows = []
    v_rows = []
    sqrt_d = float(d) ** 0.5
    for i in range(L):
        blk = params[f"block_{i}"]
        gamma = jnp.asarray(blk["LayerNorm_0"]["scale"], jnp.float32)
        beta = jnp.asarray(blk["LayerNorm_0"]["bias"], jnp.float32)
        for name, rows in (("key", k_rows), ("value", v_rows)):
            dense = blk["SelfAttention_0"][name]
            W = jnp.asarray(dense["kernel"], jnp.float32)  # [d, d]
            b = jnp.asarray(dense["bias"], jnp.float32)    # [d]
            bound = (
                sqrt_d * jnp.linalg.norm(gamma[:, None] * W, axis=0)
                + jnp.abs(beta @ W)
                + jnp.abs(b)
            )
            rows.append(jnp.maximum(bound / 127.0, 1e-8).reshape(H, hd))
    return jnp.stack(k_rows), jnp.stack(v_rows)


def quantize_kv(x, scales):
    """``[..., T, H, hd]`` K/V values → int8 against ``[..., H, hd]``
    scales (broadcast over the T axis).  Traced fragment — used inside
    the compiled prefill/step/verify fns and at pool init alike."""
    s = jnp.expand_dims(scales, -3)
    q = jnp.round(x.astype(jnp.float32) / s)
    return jnp.clip(q, -127, 127).astype(jnp.int8)


def dequantize_kv(q, scales, dtype=jnp.float32):
    """int8 K/V back to ``dtype`` — the read-side half, fused into the
    attention kernels by XLA (the int8 buffer is the only HBM-resident
    copy; the dequantized values live in registers/VMEM)."""
    s = jnp.expand_dims(scales, -3)
    return (q.astype(jnp.float32) * s).astype(dtype)

"""The plain reference of the looped decoder: its equations, nothing else.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
one full causal forward over prompt + served tokens, no cache, no scan, no
batching tricks.  It imports nothing of the program; it reads token ids
(never text) and the weights the benchmark made from the seed, in the tree
``weights.py`` states, casting one layer's bfloat16 matrices to float32 as
that layer is used, so the whole model never exists in float32.

For hidden states ``x`` of one sequence (``R*`` = RMSNorm with its own
weight, no biases anywhere)::

    x = embed[ids]
    for u in 0..U-1:                  # total_ut_steps: the SAME layers each time
      for l in 0..L-1:
        a     = R_in[l](x)
        q,k,v = rope(a Wq[l]^T), rope(a Wk[l]^T), a Wv[l]^T # rotate-half, theta; kept [heads*head_dim, hidden]
        o     = softmax(q k^T / sqrt(head_dim), causal) v Wo[l]
        x     = x + R_in2[l](o)                             # sandwich norm
        m     = R_post[l](x)
        x     = x + R_post2[l]((silu(m Wg[l]) * (m Wu[l])) Wd[l])
      x      = R_final(x)             # after every loop step; feeds the next
      lam[u] = sigmoid(x w_gate + b_gate)
    logits = x W_head                 # from the last step's normed state

Departures from the published model, all stated in the configuration's
``assumed``: the sandwich norms, the norm after every loop step, the gate's
form and the absence of biases follow the model's published code and paper
as known here, not keys of ``config.json``; weights are random.

``precision="fp8"`` is the control: the same mathematics with both inputs
of every matrix product rounded to float8 (e4m3, per-tensor scale), the
nearest precision below the bfloat16 the configuration states.
``fault="stale_cache"`` is the planted fault the check must catch: loop
step ``u`` attends the keys and values loop step ``u - 1`` made (what a
cache with one row per layer, and not per (loop step, layer), serves).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np


def _fp8(x):
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _matmul(spec: str, a, b, precision: str):
    import jax
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate-half rotary embedding of ``x [B, L, H, hd]`` at positions 0..L-1."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[None, :, None, None] * inv
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang), b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def layer_forward(arch: Dict[str, Any], w: Dict[str, Any], x, stale_kv, precision: str):
    """One application of one layer to ``x [B, L, D]``.  Returns the new
    state and this application's keys and values; ``stale_kv`` (the planted
    fault) is attended in their place when given."""
    import jax
    import jax.numpy as jnp

    B, L, _ = x.shape
    H, hd, eps = arch["num_attention_heads"], arch["head_dim"], arch["rms_norm_eps"]
    a = _rms(x, w["in_norm"], eps)
    q = _rope(_matmul("bld,ed->ble", a, w["wq"], precision).reshape(B, L, H, hd), arch["rope_theta"])
    k = _rope(_matmul("bld,ed->ble", a, w["wk"], precision).reshape(B, L, H, hd), arch["rope_theta"])
    v = _matmul("bld,ed->ble", a, w["wv"], precision).reshape(B, L, H, hd)
    ka, va = (k, v) if stale_kv is None else stale_kv
    s = _matmul("bqhd,bkhd->bhqk", q, ka, precision) / np.sqrt(hd)
    causal = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
    o = _matmul("bhqk,bkhd->bqhd", p, va, precision).reshape(B, L, H * hd)
    x = x + _rms(_matmul("bld,de->ble", o, w["wo"], precision), w["attn_out_norm"], eps)
    m = _rms(x, w["post_norm"], eps)
    f = jax.nn.silu(_matmul("bld,df->blf", m, w["wg"], precision)) * _matmul("bld,df->blf", m, w["wu"], precision)
    return x + _rms(_matmul("blf,fd->bld", f, w["wd"], precision), w["mlp_out_norm"], eps), (k, v)


def exit_mass(lam: np.ndarray) -> np.ndarray:
    """Exit mass of each loop step from the gates ``lam [U, ...]``: step ``u``
    takes ``lam[u] * prod_{j<u}(1 - lam[j])``, the last step the remainder."""
    lam = np.asarray(lam, np.float64)
    mass, left = [], np.ones_like(lam[0])
    for u in range(lam.shape[0] - 1):
        mass.append(lam[u] * left)
        left = left * (1.0 - lam[u])
    return np.stack(mass + [left])


class Reference:
    """The model from an architecture (the published keys) and the weights."""

    def __init__(self, arch: Dict[str, Any], params: Dict[str, Any], precision: str = "f32",
                 fault: Optional[str] = None):
        import jax

        if fault not in (None, "stale_cache"):
            raise ValueError(f"unknown planted fault {fault!r}")
        self.arch = {**arch, "head_dim": arch.get("head_dim") or arch["hidden_size"] // arch["num_attention_heads"]}
        self.params, self.precision, self.fault = params, precision, fault
        self._layer = jax.jit(lambda w, x, stale: layer_forward(self.arch, w, x, stale, precision))
        self._close = jax.jit(self._close_step)
        self._logits = jax.jit(lambda x, rows, at: _matmul("bnd,dv->bnv", x[rows[:, None], at], self.params["head"], precision))

    def _close_step(self, x):
        import jax

        x = _rms(x, self.params["final_norm"], self.arch["rms_norm_eps"])
        return x, jax.nn.sigmoid(x @ self.params["gate_w"] + self.params["gate_b"])

    def forward(self, ids: np.ndarray, at: np.ndarray):
        """Full forward of ``ids [B, L]`` (rows right-padded: a causal model
        never looks right); logits ``[B, N, V]`` at positions ``at [B, N]``
        and the exit mass of every loop step there ``[U, B, N]``."""
        import jax
        import jax.numpy as jnp

        arch, layers = self.arch, self.params["layers"]
        x = self.params["embed"][jnp.asarray(ids)].astype(jnp.float32)
        lam: List[Any] = []
        kept: List[Any] = [None] * arch["num_hidden_layers"]
        for u in range(arch.get("total_ut_steps", 1)):
            for l in range(arch["num_hidden_layers"]):
                w = jax.tree_util.tree_map(lambda a: a[l], layers)
                stale = kept[l] if self.fault == "stale_cache" else None
                x, kv = self._layer(w, x, stale)
                if self.fault == "stale_cache":
                    kept[l] = kv
            x, g = self._close(x)
            lam.append(g)
        rows = jnp.arange(ids.shape[0])
        at = jnp.asarray(at)
        logits = self._logits(x, rows, at)
        lam_at = np.stack([np.asarray(g[rows[:, None], at]) for g in lam])
        return np.asarray(logits), exit_mass(lam_at)

    def score(self, sequences: Sequence[Sequence[int]], n_prompt: Sequence[int], rows_per_call: int = 4):
        """Per sequence (prompt + emitted ids), the float32 logits ``[n_emit,
        V]`` the model gives at the positions its emitted tokens were chosen
        from: position ``n_prompt - 1 + j`` chose emitted token ``j``.  Every
        call has one shape (the longest sequence, the most tokens emitted,
        ``rows_per_call`` rows: the programs compile once)."""
        L = -(-max(len(s) for s in sequences) // 32) * 32
        n_emit = [len(s) - n for s, n in zip(sequences, n_prompt)]
        out: List[np.ndarray] = []
        for a in range(0, len(sequences), rows_per_call):
            part = list(range(a, min(a + rows_per_call, len(sequences))))
            ids = np.zeros((rows_per_call, L), np.int32)
            at = np.zeros((rows_per_call, max(n_emit)), np.int32)
            for r, i in enumerate(part):
                ids[r, : len(sequences[i])] = sequences[i]
                at[r, : n_emit[i]] = n_prompt[i] - 1 + np.arange(n_emit[i])
            logits, _ = self.forward(ids, at)
            out.extend(logits[r, : n_emit[i]] for r, i in enumerate(part))
        return out

"""The looped decoder family: one stack of layers run ``total_ut_steps`` times.

A decoder-only language model in the shape the looped-transformer papers
publish (arXiv 2510.25741): RMSNorm before AND after each branch (a
"sandwich"), rotary positions (rotate-half), a gated SiLU feed-forward, an
untied output head, no biases, and the SAME ``num_hidden_layers`` weights
applied ``total_ut_steps`` times in a row with the final norm after every
pass and a sigmoid exit gate read off it.  With ``total_ut_steps`` = 1 it
is a plain pre/post-norm decoder.

Every (loop step, layer) application has keys and values of its own, so a
sequence's cache is ``total_ut_steps * num_hidden_layers`` rows deep
(``LoopedConfig.cache_depth``); the weights are read once per loop step.

There is ONE block, ``_block``: its attention gets the rows it attends
from ``append(carry, d, k, v) -> (carry, K, V)``, which files the new keys
and values under cache row ``d`` and hands back what to attend.  The full
forward (no cache), the slot prefill and the slot step differ only in that
function.  Weights are stacked ``[layers, ...]`` and the stack is a
``lax.scan`` over layers inside a ``lax.scan`` over loop steps, so program
size does not grow with depth.  Every product reads its layer's slice of a
stack where it lies: the query, key and value projections' outputs pass an
``optimization_barrier`` before they are cut into heads, since a reshape
that the compiler moves onto a slice stops the slice fusing into its
product and copies it first.

Precision: bfloat16 weights, bfloat16 matrix-product inputs with float32
accumulation; the residual stream, norms, rotary angles, softmax and the
gate in float32.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Mapping, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "LoopedConfig",
    "exit_mass",
    "forward",
    "init_params",
    "slot_prefill",
    "slot_step",
    "token_stats",
]

FAMILY = "looped"  # what models/generator.py calls this family where it refuses an option
TOP_LOGPROBS = 8  # ids and logits kept per emitted token (what serving APIs call logprobs)


@dataclass(frozen=True)
class LoopedConfig:
    """The architecture, under its published keys.  The short names
    (``d_model``, ``n_heads``, ``n_layers``, ``max_len``) are what the decode
    engine and the HBM ledger read off any generator's ``config``."""

    vocab_size: int
    hidden_size: int
    num_attention_heads: int
    head_dim: int
    intermediate_size: int
    num_hidden_layers: int
    total_ut_steps: int = 1
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    max_position_embeddings: int = 2048
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_architecture(cls, arch: Mapping[str, Any], dtype=jnp.bfloat16) -> "LoopedConfig":
        """Read a published ``config.json``; what this family cannot run is
        refused here, by name, and never approximated."""
        heads = int(arch["num_attention_heads"])
        kv_heads = int(arch.get("num_key_value_heads", heads))
        if kv_heads != heads:
            raise ValueError(
                f"num_key_value_heads={kv_heads} != num_attention_heads={heads}: "
                "grouped-query heads are not implemented for the looped family"
            )
        if arch.get("hidden_act", "silu") != "silu":
            raise ValueError(f"hidden_act={arch['hidden_act']!r}: the looped family's feed-forward is gated SiLU")
        if arch.get("sliding_window") or arch.get("use_sliding_window"):
            raise ValueError("sliding-window attention is not implemented for the looped family")
        if arch.get("tie_word_embeddings"):
            raise ValueError("tie_word_embeddings: the looped family's output head is untied")
        hidden = int(arch["hidden_size"])
        return cls(
            vocab_size=int(arch["vocab_size"]),
            hidden_size=hidden,
            num_attention_heads=heads,
            head_dim=int(arch.get("head_dim") or hidden // heads),
            intermediate_size=int(arch["intermediate_size"]),
            num_hidden_layers=int(arch["num_hidden_layers"]),
            total_ut_steps=int(arch.get("total_ut_steps", 1)),
            rms_norm_eps=float(arch.get("rms_norm_eps", 1e-6)),
            rope_theta=float(arch.get("rope_theta", 10000.0)),
            max_position_embeddings=int(arch.get("max_position_embeddings", 2048)),
            dtype=dtype,
        )

    d_model = property(lambda self: self.hidden_size)
    n_heads = property(lambda self: self.num_attention_heads)
    n_layers = property(lambda self: self.num_hidden_layers)
    max_len = property(lambda self: self.max_position_embeddings)
    cache_depth = property(lambda self: self.total_ut_steps * self.num_hidden_layers)


Config = LoopedConfig  # every decoder family's module names its configuration so


def init_params(cfg: LoopedConfig, seed: int, scale: float = 0.02) -> Dict[str, Any]:
    """Seeded random weights in the family's tree: matrices normal(0,
    ``scale``) in ``cfg.dtype``; norm weights 1 + normal(0, 0.1), the gate
    normal(0, ``scale``), in float32."""
    D, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    A, Ly = cfg.num_attention_heads * cfg.head_dim, cfg.num_hidden_layers
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def mat(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(cfg.dtype)

    def norm(*shape):
        return 1.0 + 0.1 * jax.random.normal(next(keys), shape, jnp.float32)

    return {
        "embed": mat(V, D),
        "head": mat(D, V),
        "final_norm": norm(D),
        "gate_w": jax.random.normal(next(keys), (D,), jnp.float32) * scale,
        "gate_b": jnp.zeros((), jnp.float32),
        "layers": {
            "wq": mat(Ly, A, D), "wk": mat(Ly, A, D), "wv": mat(Ly, A, D), "wo": mat(Ly, A, D),
            "wg": mat(Ly, D, F), "wu": mat(Ly, D, F), "wd": mat(Ly, F, D),
            "in_norm": norm(Ly, D), "attn_out_norm": norm(Ly, D),
            "post_norm": norm(Ly, D), "mlp_out_norm": norm(Ly, D),
        },
    }


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotate-half rotary embedding of ``x [B, L, H, hd]`` (float32) at
    absolute positions ``pos [B, L]``."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, :, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _mm(x, w):
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _mm_t(x, w):
    """``x @ w.T`` for the query, key and value projections, which are kept
    ``[heads * head_dim, hidden]``: the layout the TPU compiler wants them in
    (kept ``[hidden, heads * head_dim]`` it transposes all three, 1.2 GB at
    2.6B parameters, on every call)."""
    return jnp.einsum("...d,ad->...a", x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _attend(q, K, V, q_pos):
    """``q [B, Lq, H, hd]`` over rows ``K``/``V [B, Tk, H, hd]`` whose row
    index is the key's absolute position: a key is seen iff it is at or
    before the query's position, so rows past a sequence's frontier (another
    occupant's leftovers, padding) carry exactly zero weight."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, K, preferred_element_type=jnp.float32)
    s = s * (q.shape[-1] ** -0.5)
    seen = jnp.arange(K.shape[1])[None, None, None, :] <= q_pos[:, None, :, None]
    p = jax.nn.softmax(jnp.where(seen, s, jnp.finfo(jnp.float32).min), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(V.dtype), V, preferred_element_type=jnp.float32)


def _block(cfg: LoopedConfig, w, x, q_pos, carry, d, append):
    """One application of one layer to the residual stream ``x [B, L, D]``
    (float32).  ``append(carry, d, k, v)`` files this application's keys and
    values under cache row ``d`` and returns the rows to attend."""
    B, L, _ = x.shape
    H, hd, eps = cfg.num_attention_heads, cfg.head_dim, cfg.rms_norm_eps
    a = _rms(x, w["in_norm"], eps)
    # the barrier keeps the heads' reshape off the weights: moved onto them it copies each layer's wq, wk and wv into
    # VMEM before the products read the copies
    q, k, v = (jax.lax.optimization_barrier(_mm_t(a, w[n])).reshape(B, L, H, hd) for n in ("wq", "wk", "wv"))
    q = _rope(q, q_pos, cfg.rope_theta).astype(cfg.dtype)
    k = _rope(k, q_pos, cfg.rope_theta).astype(cfg.dtype)
    v = v.astype(cfg.dtype)
    carry, K, V = append(carry, d, k, v)
    o = _mm(_attend(q, K, V, q_pos).reshape(B, L, H * hd), w["wo"])
    x = x + _rms(o, w["attn_out_norm"], eps)
    m = _rms(x, w["post_norm"], eps)
    f = _mm(jax.nn.silu(_mm(m, w["wg"])) * _mm(m, w["wu"]), w["wd"])
    return x + _rms(f, w["mlp_out_norm"], eps), carry


def _stack(cfg: LoopedConfig, params, ids, q_pos, carry, append):
    """Embedding, then every loop step over every layer.  Returns the last
    loop step's normed state ``[B, L, D]``, the cache carry, and the exit
    gate of every loop step ``[U, B, L]``."""
    Ly = cfg.num_hidden_layers
    x = params["embed"][ids].astype(jnp.float32)

    def loop_step(c, u):
        def layer(c, xs):
            w, l = xs
            return _block(cfg, w, c[0], q_pos, c[1], u * Ly + l, append), None

        (x, carry), _ = jax.lax.scan(layer, c, (params["layers"], jnp.arange(Ly)))
        x = _rms(x, params["final_norm"], cfg.rms_norm_eps)
        return (x, carry), jax.nn.sigmoid(x @ params["gate_w"] + params["gate_b"])

    (x, carry), lam = jax.lax.scan(loop_step, (x, carry), jnp.arange(cfg.total_ut_steps))
    return x, carry, lam


def _head(params, x):
    return _mm(x, params["head"])


def exit_mass(lam):
    """Exit mass of each loop step from the gates ``lam [U, ...]``: step
    ``u`` takes ``lam[u]`` of what the steps before it left, the last step
    the remainder; the masses sum to 1."""
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([(lam * before)[:-1], before[-1:]], axis=0)


def token_stats(logits, tok):
    """What a caller may read of one emitted token, from the float32 logits
    ``[N, V]`` it was chosen from: its own logit, the log-sum-exp, and the
    top ``TOP_LOGPROBS`` ids and logits."""
    top, top_ids = jax.lax.top_k(logits, TOP_LOGPROBS)
    return {
        "logit": jnp.take_along_axis(logits, tok[:, None].astype(jnp.int32), axis=1)[:, 0],
        "lse": jax.nn.logsumexp(logits, axis=-1),
        "top_ids": top_ids.astype(jnp.int32),
        "top_logits": top,
    }


def _no_cache(carry, d, k, v):
    return carry, k, v


def forward(cfg: LoopedConfig, params, ids):
    """Full causal forward of ``ids [B, L]`` with no cache: logits
    ``[B, L, V]`` (float32) and the exit mass of every loop step ``[U, B, L]``."""
    B, L = ids.shape
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None, :], (B, L))
    x, _, lam = _stack(cfg, params, ids, pos, (), _no_cache)
    return _head(params, x), exit_mass(lam)


def _sample(logits, rngs, temps):
    """Greedy, or each row's own categorical draw from its own rng chain
    (one split per emitted token: the solo chain)."""
    greedy = jnp.argmax(logits, axis=-1)

    def draw(rngs):
        pairs = jax.vmap(jax.random.split)(rngs)
        drawn = jax.vmap(jax.random.categorical)(pairs[:, 1], logits / jnp.maximum(temps, 1e-4)[:, None])
        return pairs[:, 0], jnp.where(temps <= 0.0, greedy, drawn)

    rngs, tok = jax.lax.cond(jnp.all(temps <= 0.0), lambda r: (r, greedy), draw, rngs)
    return rngs, tok.astype(jnp.int32)


# ---------------------------------------------------------------------------
# the slot pool's programs (serve/decode.py): pool [S, cache_depth, T, H, hd]
# ---------------------------------------------------------------------------


def _prefix_rows(prefix, d, dtype):
    """Cache row ``d`` of every row's prefix blocks: ``[B, P, H, hd]``."""
    return jnp.stack([
        jnp.concatenate([jax.lax.dynamic_index_in_dim(b, d, axis=0, keepdims=False) for b in row], axis=0)
        for row in prefix
    ]).astype(dtype)


def slot_prefill(cfg: LoopedConfig, S: int, T: int, B: int, L_sfx: int, P: int, block: int = 0) -> Callable:
    """JOIN of ``B`` rows: ``(params, pool_k, pool_v, slots [B], suffix_ids
    [B, L_sfx], n_len [B], prefix_k, prefix_v, rngs [B, 2], temps [B]) ->
    (pool_k, pool_v, first [B], rngs, extra)``.  ``prefix_k`` / ``prefix_v``
    hold, per row, the prefix cache's own blocks ``[depth, block, H, hd]``
    covering ``[0, P)``, as they are (stacked per row they would be 0.8 GB
    of copies for 16 rows at 2.6B parameters); each (loop step, layer)
    reads its row of them.  Row
    ``i``'s cached prefix lands at positions ``[0, P)`` and its suffix's
    keys and values at ``[P, P + L_sfx)`` of slot ``slots[i]``, in every
    (loop step, layer) row, one write of ``[0, P + L_sfx)`` per row.  Every
    slot index is in bounds: a pad row repeats a real row (same slot, same
    ids), so it writes the same values again.  Rows past ``P + L_sfx`` keep
    the last occupant's values: no query sees a key past its own position,
    and a step writes a position before it attends it.  The pools are
    donated and updated in place.  (``block``, the prefix tier's, is every
    family's to take: this one's blocks are cut from the pool after the join.)"""

    def run(params, pool_k, pool_v, slots, suffix_ids, n_len, prefix_k, prefix_v, rngs, temps):
        pos = jnp.broadcast_to((P + jnp.arange(L_sfx, dtype=jnp.int32))[None, :], (B, L_sfx))
        # a single row is written twice over: XLA turns a scatter of one
        # index into a dynamic-update-slice, gives the loop's pool another
        # layout than the argument's for it, and copies both pools whole
        # (3.4 GB each at 2.6B parameters: the program no longer fits)
        twice = jnp.arange(B) if B > 1 else jnp.zeros(2, jnp.int32)
        rows = slots[twice]

        def append(carry, d, k, v):
            if P:
                k = jnp.concatenate([_prefix_rows(prefix_k, d, k.dtype), k], axis=1)
                v = jnp.concatenate([_prefix_rows(prefix_v, d, v.dtype), v], axis=1)
            pk = carry[0].at[rows, d, : P + L_sfx].set(k[twice], mode="promise_in_bounds")
            pv = carry[1].at[rows, d, : P + L_sfx].set(v[twice], mode="promise_in_bounds")
            return (pk, pv), k, v

        x, (pool_k, pool_v), lam = _stack(cfg, params, suffix_ids, pos, (pool_k, pool_v), append)
        last = jnp.maximum(n_len - 1 - P, 0)
        logits = _head(params, jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0])
        rngs, tok = _sample(logits, rngs, temps)
        mass = jnp.take_along_axis(exit_mass(lam), last[None, :, None], axis=2)[:, :, 0]
        return pool_k, pool_v, tok, rngs, {**token_stats(logits, tok), "exit_mass": mass.T}

    return jax.jit(run, donate_argnums=(1, 2))


def slot_step(cfg: LoopedConfig, S: int, T: int, chunk: int) -> Callable:
    """Up to ``chunk`` single-token steps over the whole pool: ``(params,
    pool_k, pool_v, tok [S], pos [S], active [S], left [S], rngs [S, 2],
    temps [S], eos [S], n_steps) -> (pool_k, pool_v, rngs, emitted [chunk,
    S], extra)``.  Only the first ``n_steps`` steps run (the rest are skipped
    on the device and emit ``-1``): the engine asks for no more steps than
    the nearest budget's end, so a lane leaves, and its slot is taken again,
    at the step it finishes and not at the chunk's end; one program serves
    every count.  A live
    lane forwards its last token at its position through every (loop step,
    layer), writes that row of its slot and attends its own slot.  A lane
    that is not live emits ``-1``; what it writes lands at its own slot's
    next position, past every key that slot's occupant (if it still has
    one) will ever attend, and a join rewrites what it attends.  The pools
    are donated and updated in place."""
    lanes = jnp.arange(S)

    def run(params, pool_k, pool_v, tok, pos, active, left, rngs, temps, eos, n_steps):
        def step(carry):
            pool_k, pool_v, tok, pos, act, left, rngs = carry
            live = act & (left > 0)
            at = jnp.minimum(pos, T - 1)

            def append(c, d, k, v):
                pk = c[0].at[lanes, d, at].set(k[:, 0], mode="promise_in_bounds")
                pv = c[1].at[lanes, d, at].set(v[:, 0], mode="promise_in_bounds")
                return (pk, pv), jax.lax.dynamic_index_in_dim(pk, d, axis=1, keepdims=False), jax.lax.dynamic_index_in_dim(pv, d, axis=1, keepdims=False)

            x, (pool_k, pool_v), lam = _stack(cfg, params, tok[:, None], pos[:, None], (pool_k, pool_v), append)
            logits = _head(params, x[:, 0])
            rngs2, nxt = _sample(logits, rngs, temps)
            stats = token_stats(logits, nxt)
            stats["exit_mass"] = exit_mass(lam)[:, :, 0].T
            carry = (
                pool_k, pool_v, jnp.where(live, nxt, tok), jnp.where(live, pos + 1, pos),
                live & (nxt != eos), jnp.where(live, left - 1, left),
                jnp.where(live[:, None], rngs2, rngs),
            )
            return carry, (jnp.where(live, nxt, -1), stats)

        carry = (pool_k, pool_v, tok, pos, active, left, rngs)
        blank = jax.tree_util.tree_map(
            lambda a: jnp.full(a.shape, -1 if a.dtype == jnp.int32 else 0, a.dtype), jax.eval_shape(step, carry)[1]
        )
        (pool_k, pool_v, _, _, _, _, rngs), (em, extra) = jax.lax.scan(
            lambda c, i: jax.lax.cond(i < n_steps, step, lambda c: (c, blank), c), carry, jnp.arange(chunk)
        )
        return pool_k, pool_v, rngs, em, extra

    return jax.jit(run, donate_argnums=(1, 2))

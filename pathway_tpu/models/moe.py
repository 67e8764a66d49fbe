"""The sparse-expert decoder family: routed experts, grouped-query heads,
full and sliding-window layers side by side.

A decoder-only language model in the shape arXiv 2507.20984 publishes:
RMSNorm before each branch (no norm after it), no biases, an untied output
head.  Layer ``l`` reads its router off the layer's NORMED INPUT, before
attention: the ``moe_num_active_primary_experts`` largest of
``moe_num_primary_experts`` logits, softmaxed among themselves, weight that
layer's gated-ReLU experts, which read the post-attention normed state.
Attention is grouped-query (``num_attention_heads`` query heads over
``num_key_value_heads`` key/value heads); ``rope_layout[l]`` says whether
layer ``l`` rotates its queries and keys (rotate-half) or carries no
positions at all, ``sliding_window_layout[l]`` whether it sees every earlier
key or only the last ``sliding_window_size``.

What shares code with the looped family (models/looped.py): ``_rms``,
``_rope``, ``_mm``, ``_mm_t``, ``_head``, ``_sample``, ``token_stats`` and
the ``append(carry, l, k, v)`` seam through which the full forward, the
slot prefill and the slot step hand one block the rows it attends.  What
is this family's own: the block (router, grouped-query attention, the
grouped expert product) and a slot pool of two kinds of rows:

- full layers keep ``T`` rows a slot, a key at the row of its position;
- window layers keep a ring of ``min(sliding_window_size, T)`` rows a slot:
  position ``p`` lives at row ``p mod ring``, so after a lane has written
  position ``pos`` row ``r`` holds position ``pos - ((pos - r) mod ring)``,
  seen iff that is not negative.  Nothing but the lane's position says what
  a ring holds, so a slot's next occupant can never see the last one's rows:
  a join writes the whole ring (row ``r`` gets the prompt's last position
  congruent to ``r``), and rows whose position would be negative are masked
  until the occupant writes them.

The stack is a ``lax.scan`` over periods of the layer pattern with one
period's layers unrolled inside, so all of a program but the expert product
does not grow with depth.  The experts' weights do not ride the scan (a
scanned operand is sliced, and a slice of one layer's experts is a 0.75 GB
copy; folding the layer into the group axis of one stack made the chip's
grouped kernel walk every layer's experts for each layer's product: a step
of 113 ms): each layer's experts are arrays of their own, and the product
is reached through a ``lax.switch`` on the period's index whose branches
each hold one layer's, by reference.

A prompt's attention (the join's and the full forward's) goes through one
seam, ``_attend_prompt``: on a TPU a banded flash kernel (the Pallas splash
attention that ships with JAX) that keeps scores, running maximum, sum and
accumulator in VMEM and walks only the (queries x keys) blocks the mask
leaves something of; elsewhere ``_attend_blocks``, query blocks whose
scores pass through memory: the fallback and the tests' oracle.  A step's
one query a lane is ``_attend_rows``.

Precision: bfloat16 weights and matrix-product inputs with float32
accumulation; the residual stream, norms, rotary angles and softmax in
float32; router logits and the choice of experts in float32 at ``highest``
precision from the float32 normed state.  The queries reach the attention
in float32 and are rounded to bfloat16 there, once: the kernel takes the
softmax's scale into that rounding (``q * head_dim ** -0.5`` in float32,
then bfloat16) and hands the values' product its float32 probabilities;
the block path rounds ``q``, scales the float32 scores and rounds the
probabilities to bfloat16.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Mapping, Tuple

import jax
import jax.numpy as jnp

from .looped import _head, _mm, _mm_t, _prefix_rows, _rms, _rope, _sample, token_stats

__all__ = ["MoeConfig", "forward", "init_params", "slot_prefill", "slot_step"]

FAMILY = "sparse-expert"
QUERY_BLOCK = 512  # queries the block path attends at a time: its scores are [B, heads, QUERY_BLOCK, keys], never [.., L, L]
# a prompt's attention: None chooses by what the program observes (``prompt_attention``), "kernel" or "blocks" names
# it: a test that compiles for a chip it does not have, or runs the kernel interpreted on the CPU
ATTENTION_KERNEL = None
ATTENTION_INTERPRET = False
KERNEL_BLOCK = 512  # the kernel's tile, queries and keys alike: chosen on the chip (PERF.md section 6, ISSUE 33)
# the grouped product's kernel: None chooses by the backend (the Pallas grouped matmul on a TPU, ``ragged_dot``
# elsewhere); a test that compiles for a chip it does not have names it
GROUPED_KERNEL = None


def _refuse(what: str) -> ValueError:
    return ValueError(f"{what} for the {FAMILY} family")


@dataclass(frozen=True)
class MoeConfig:
    """The architecture, under its published keys; the short names are what
    the decode engine and the HBM ledger read off any generator's ``config``."""

    vocab_size: int
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    moe_ffn_hidden_size: int
    moe_num_primary_experts: int
    moe_num_active_primary_experts: int
    num_hidden_layers: int
    rope_layout: Tuple[int, ...]
    sliding_window_layout: Tuple[int, ...]
    sliding_window_size: int
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    max_position_embeddings: int = 2048
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_architecture(cls, arch: Mapping[str, Any], dtype=jnp.bfloat16) -> "MoeConfig":
        """Read a published ``config.json``; what this family cannot run is
        refused here, by name, and never approximated."""
        if arch.get("rope_scaling") is not None:
            raise _refuse(f"rope_scaling={arch['rope_scaling']!r}: scaled rotary positions are not implemented")
        if arch.get("tie_word_embeddings"):
            raise _refuse("tie_word_embeddings: the output head is untied")
        if arch.get("hidden_act", "relu") != "relu":
            raise _refuse(f"hidden_act={arch['hidden_act']!r}: the experts are gated ReLU")
        for key in ("moe_primary_router_apply_softmax", "norm_topk_prob"):
            if not arch.get(key, True):
                raise _refuse(f"{key}=false: the chosen experts' logits are softmaxed among themselves")
        layers = int(arch["num_hidden_layers"])
        heads, kv_heads = int(arch["num_attention_heads"]), int(arch.get("num_key_value_heads") or arch["num_attention_heads"])
        if heads % kv_heads:
            raise _refuse(f"num_attention_heads={heads} is not a multiple of num_key_value_heads={kv_heads}")
        experts, active = int(arch["moe_num_primary_experts"]), int(arch["moe_num_active_primary_experts"])
        if not 1 <= active <= experts:
            raise _refuse(f"moe_num_active_primary_experts={active} of moe_num_primary_experts={experts}")
        layouts = {}
        for key in ("rope_layout", "sliding_window_layout"):
            layout = tuple(int(x) for x in arch.get(key) or (0,) * layers)
            if len(layout) != layers or set(layout) - {0, 1}:
                raise _refuse(f"{key} must hold num_hidden_layers={layers} entries of 0 or 1, got {list(layout)}")
            layouts[key] = layout
        window = int(arch.get("sliding_window_size") or 0)
        if any(layouts["sliding_window_layout"]) and window < 1:
            raise _refuse(f"sliding_window_size={window} with window layers in sliding_window_layout")
        hidden = int(arch["hidden_size"])
        return cls(
            vocab_size=int(arch["vocab_size"]), hidden_size=hidden, num_attention_heads=heads,
            num_key_value_heads=kv_heads, head_dim=int(arch.get("head_dim") or hidden // heads),
            moe_ffn_hidden_size=int(arch["moe_ffn_hidden_size"]), moe_num_primary_experts=experts,
            moe_num_active_primary_experts=active, num_hidden_layers=layers, sliding_window_size=window,
            rms_norm_eps=float(arch.get("rms_norm_eps", 1e-6)), rope_theta=float(arch.get("rope_theta", 10000.0)),
            max_position_embeddings=int(arch.get("max_position_embeddings", 2048)), dtype=dtype, **layouts,
        )

    d_model = property(lambda self: self.hidden_size)
    n_heads = property(lambda self: self.num_attention_heads)
    n_kv_heads = property(lambda self: self.num_key_value_heads)
    n_layers = property(lambda self: self.num_hidden_layers)
    max_len = property(lambda self: self.max_position_embeddings)
    cache_depth = property(lambda self: self.num_hidden_layers)
    total_ut_steps = property(lambda self: 1)
    n_experts = property(lambda self: self.moe_num_primary_experts)
    experts_per_token = property(lambda self: self.moe_num_active_primary_experts)

    @property
    def period(self) -> int:
        """The shortest stretch of layers that the two layouts repeat."""
        Ly, kinds = self.num_hidden_layers, list(zip(self.rope_layout, self.sliding_window_layout))
        return next(p for p in range(1, Ly + 1) if Ly % p == 0 and kinds == kinds[:p] * (Ly // p))

    def pool_layout(self, T: int) -> Tuple[Tuple[str, int, int], ...]:
        """The slot pool's kinds of rows as ``(kind, layers, rows a layer)``:
        full layers keep ``T`` rows, window layers a ring."""
        n_window = sum(self.sliding_window_layout)
        return (("full", self.num_hidden_layers - n_window, T), ("window", n_window, min(self.sliding_window_size or T, T)))


Config = MoeConfig


def init_params(cfg: MoeConfig, seed: int, scale: float = 0.02) -> Dict[str, Any]:
    """Seeded random weights in the family's tree: matrices normal(0,
    ``scale``) in ``cfg.dtype`` (the projections kept ``[heads * head_dim,
    hidden]`` as the looped family's, stacked over the layers; the experts
    ``wg wu [E, D, F]`` and ``wd [E, F, D]`` one array a layer, in a tuple),
    norm weights 1 + normal(0, 0.1) in float32."""
    D, F, V, E, Ly = cfg.hidden_size, cfg.moe_ffn_hidden_size, cfg.vocab_size, cfg.moe_num_primary_experts, cfg.num_hidden_layers
    A, Akv = cfg.num_attention_heads * cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16 + 3 * Ly))

    def mat(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(cfg.dtype)

    def norm(*shape):
        return 1.0 + 0.1 * jax.random.normal(next(keys), shape, jnp.float32)

    return {
        "embed": mat(V, D),
        "head": mat(D, V),
        "final_norm": norm(D),
        "layers": {
            "wq": mat(Ly, A, D), "wk": mat(Ly, Akv, D), "wv": mat(Ly, Akv, D), "wo": mat(Ly, A, D),
            "router": mat(Ly, D, E),
            "wg": tuple(mat(E, D, F) for _ in range(Ly)), "wu": tuple(mat(E, D, F) for _ in range(Ly)),
            "wd": tuple(mat(E, F, D) for _ in range(Ly)),
            "in_norm": norm(Ly, D), "post_norm": norm(Ly, D),
        },
    }


_EXPERT_LEAVES = ("wg", "wu", "wd")


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------


def route(cfg, a, router):
    """The layer's experts for every token of ``a [N, D]`` (float32, the
    normed state the router reads): ids ``[N, k]`` (ties broken as
    ``lax.top_k`` does: the lower id first) and their weights, a softmax over
    the chosen logits.  ``cfg`` is any family's with routed experts."""
    r = jnp.dot(a, router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    top, ids = jax.lax.top_k(r, cfg.experts_per_token)
    return ids.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def _tile(n: int, most: int) -> int:
    """The largest multiple of 128 that divides ``n`` and is at most ``most``."""
    return max(t for t in range(128, most + 1, 128) if n % t == 0)


def _row_tile(rows: int) -> int:
    """Rows of the grouped kernel's tile: a join's thousands take 512, a step's few dozen 128."""
    return 512 if rows >= 512 else 128


def grouped_product(rows, w, sizes, kernel=None, interpret: bool = False):
    """``rows [A, K]`` sorted by group times ``w [E, K, N]``, rows ``[sum(sizes[:e]),
    sum(sizes[:e + 1]))`` by ``w[e]``: ``[A, N]`` float32.  On a TPU the
    Pallas grouped matmul that ships with JAX (``megablox.gmm``: it walks
    only the groups that have rows; 1.6 ms for 40,512 rows of 2,560 into 64
    experts of 768 where the compiler's own ``ragged_dot`` kernel takes 6.8,
    my chip run, ISSUE 32), elsewhere ``jax.lax.ragged_dot``, the same sums."""
    kernel = kernel or GROUPED_KERNEL or ("gmm" if jax.default_backend() == "tpu" else "ragged_dot")
    if kernel == "ragged_dot":
        return jax.lax.ragged_dot(rows, w, sizes, preferred_element_type=jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    A, K = rows.shape
    pad = -A % _row_tile(A)  # ``grouped_experts`` hands whole tiles; rows past the groups' sum are not computed
    out = gmm(jnp.pad(rows, ((0, pad), (0, 0))) if pad else rows, w, sizes, jnp.float32,
              (_row_tile(A), _tile(K, 1280), _tile(w.shape[2], 768)), interpret=interpret)
    return out[:A] if pad else out


def grouped_experts(cfg, held, w, m, ids, gates, act=jax.nn.relu):
    """The expert layer as one grouped product: the ``N * k`` (token,
    expert) pairs sorted by expert, one grouped matrix product per weight
    (``grouped_product``) over one layer's experts ``w``: ``wg wu [E, D,
    F]``, ``wd [E, F, D]`` (gated by ``act``), the results weighted and
    summed back per token.  ``held = (lo, hi)`` says that ``w`` holds only
    the experts ``[lo, hi)`` of the ``cfg.n_experts`` the router chose among
    (what one chip of an expert axis holds): pairs routed to the others sort
    last, are computed by no one, and their part of the result is left out.
    ``None``: every expert is here.  Returns ``[N, D]`` float32."""
    N, k = ids.shape
    n = cfg.n_experts if held is None else held[1] - held[0]
    flat = ids.reshape(-1)
    if held is None:
        sizes = jnp.zeros(n, jnp.int32).at[flat].add(1)
    else:
        mine = (ids >= held[0]) & (ids < held[1])
        flat = jnp.where(mine, ids - held[0], n).reshape(-1)
        sizes = jnp.zeros(n, jnp.int32).at[flat].add(1, mode="drop")
    # whole row tiles for the kernel: pairs of no expert here sort last, are computed by none and read by none
    order = jnp.argsort(jnp.pad(flat, (0, -flat.shape[0] % _row_tile(flat.shape[0])), constant_values=n), stable=True)
    x = m.astype(cfg.dtype)[jnp.minimum(order // k, N - 1)]

    y = (act(grouped_product(x, w["wg"], sizes)) * grouped_product(x, w["wu"], sizes)).astype(cfg.dtype)
    o = grouped_product(y, w["wd"], sizes)
    back = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0], dtype=order.dtype))[: N * k]
    o = o[back].reshape(N, k, -1)
    if held is not None:  # an absent expert's rows hold whatever the kernel found there
        o = jnp.where(mine[:, :, None], o, 0.0)
    return jnp.sum(o * gates[:, :, None], axis=1)


def _expert_leaves(layers, l: int, held):
    """Layer ``l``'s experts, by reference; a share's are cut from them (a copy: for a test, not for a cell)."""
    return {n: layers[n][l] if held is None else layers[n][l][held[0] : held[1]] for n in _EXPERT_LEAVES}


def expert_load(cfg: MoeConfig, ids, real):
    """Of one layer's choices ``ids [N, k]``, counting the tokens ``real
    [N]`` only: how many experts got a token, and the busiest one's tokens."""
    flat = jnp.where(real[:, None], ids, cfg.moe_num_primary_experts).reshape(-1)
    sizes = jnp.zeros(cfg.moe_num_primary_experts + 1, jnp.int32).at[flat].add(1)[:-1]
    return jnp.sum(sizes > 0).astype(jnp.int32), jnp.max(sizes)


def _grouped(q, n_kv: int):
    B, L, H, hd = q.shape
    return q.reshape(B, L, n_kv, H // n_kv, hd)


def _attend_rows(q, K, V, seen):
    """One query a lane, ``q [S, 1, H, hd]``, over its slot's rows ``K`` /
    ``V [S, R, Hkv, hd]`` of which ``seen [S, R]`` are its to see."""
    S, _, H, hd = q.shape
    s = jnp.einsum("bqhgd,bkhd->bhgqk", _grouped(q, K.shape[2]), K, preferred_element_type=jnp.float32) * (hd ** -0.5)
    p = jax.nn.softmax(jnp.where(seen[:, None, None, None, :], s, jnp.finfo(jnp.float32).min), axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(V.dtype), V, preferred_element_type=jnp.float32).reshape(S, 1, H, hd)


def prompt_attention(cfg: "MoeConfig", L: int) -> str:
    """What attends a prompt's ``L`` tokens: "kernel" on a TPU where the
    heads fill the kernel's lanes and the prompt at least one of its tiles,
    else "blocks" (the tests' tiny models, a short prompt, any other backend)."""
    how = ATTENTION_KERNEL or ("kernel" if jax.default_backend() == "tpu" else "blocks")
    return how if cfg.head_dim % 128 == 0 and L >= KERNEL_BLOCK else "blocks"


def _attend_prompt(q, K, V, first_pos: int, window: int, how: str, block: int = 0):
    """The seam: ``q [B, L, H, hd]`` (float32) at positions ``first_pos +
    [0, L)`` over ``K`` / ``V [B, Tk, Hkv, hd]`` whose row index is the key's
    position; a key is seen at or before the query's position and, on a
    window layer, less than ``window`` before it.  ``how`` is
    ``prompt_attention``'s word, ``block`` a test's own in place of the
    path's.  Returns ``[B, L, H, hd]``."""
    if how == "blocks":
        return _attend_blocks(q.astype(K.dtype), K, V, first_pos, window, block or QUERY_BLOCK)
    return _attend_kernel(q, K, V, first_pos, window, block or KERNEL_BLOCK, ATTENTION_INTERPRET)


def _attend_kernel(q, K, V, first_pos: int, window: int, block: int, interpret: bool = False):
    """The flash kernel (``splash_attention``, shipped with JAX): per query
    head a grid over (query tile, key tile) of ``block`` each, the key/value
    head's tiles shared by its group's query heads where they lie (no copy);
    tiles the mask empties (above the diagonal; on a window layer further
    than ``window`` behind) are never fetched, tiles it leaves whole are not
    masked, and the mask of the tiles its edge crosses is computed in the
    kernel from the query's position.  Scores, maximum, sum and accumulator
    stay in VMEM, float32; the scores' product takes bfloat16 queries and
    keys, the values' the probabilities as they are, float32, and the
    bfloat16 values widened (the block path rounds the probabilities to
    bfloat16 first: never more exact than this).  Lengths are padded to whole
    tiles: a padded key lies after every real query, so the causal rule hides
    it, and padded queries are cut off.  The kernel multiplies no scale, so
    ``q`` is scaled in float32 before its one rounding.  Returns bfloat16,
    what ``wo``'s product takes."""
    from jax.experimental.pallas.ops.tpu import splash_attention as splash

    B, L, H, hd = q.shape
    Tk = K.shape[1]
    Lp, Tp = -(-L // block) * block, -(-Tk // block) * block
    seen = splash.LocalMask((Lp, Tp), (window - 1, 0), first_pos) if window else splash.CausalMask((Lp, Tp), first_pos)
    kernel = splash.make_splash_mha_single_device(
        splash.MultiHeadMask([seen] * H), block_sizes=splash.BlockSizes(block_q=block, block_kv=block), interpret=interpret
    )
    heads_first = lambda x, n: jnp.pad(x, ((0, 0), (0, n - x.shape[1]), (0, 0), (0, 0))).transpose(0, 2, 1, 3)  # noqa: E731
    out = jax.vmap(kernel)(heads_first((q * hd ** -0.5).astype(K.dtype), Lp), heads_first(K, Tp), heads_first(V, Tp))
    return out.transpose(0, 2, 1, 3)[:, :L]


def _attend_blocks(q, K, V, first_pos: int, window: int, block: int):
    """The block path: ``q [B, L, H, hd]`` at positions ``first_pos + [0,
    L)`` over ``K`` / ``V [B, Tk, Hkv, hd]`` whose row index is the key's
    position, ``block`` queries at a time.  A key is seen at or before the query's position and,
    on a window layer, less than ``window`` before it; a window layer's
    query block reads only the ``window + block`` keys that reach it."""
    B, L, H, hd = q.shape
    Tk, n_kv = K.shape[1], K.shape[2]
    block = min(block, L)
    n_blocks = -(-L // block)
    q = jnp.pad(q, ((0, 0), (0, n_blocks * block - L), (0, 0), (0, 0)))
    band = min(Tk, window + block) if window else Tk

    def one(i):
        at = first_pos + i * block
        k0 = jnp.clip(at + block - band, 0, Tk - band)
        qb = _grouped(jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=1), n_kv)
        Kb, Vb = (jax.lax.dynamic_slice_in_dim(x, k0, band, axis=1) for x in (K, V))
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, Kb, preferred_element_type=jnp.float32) * (hd ** -0.5)
        q_pos, k_pos = (at + jnp.arange(block))[:, None], (k0 + jnp.arange(band))[None, :]
        seen = k_pos <= q_pos
        if window:
            seen &= k_pos > q_pos - window
        p = jax.nn.softmax(jnp.where(seen, s, jnp.finfo(jnp.float32).min), axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(Vb.dtype), Vb, preferred_element_type=jnp.float32).reshape(B, block, H, hd)

    out = jax.lax.map(one, jnp.arange(n_blocks))
    return jnp.moveaxis(out, 0, 1).reshape(B, n_blocks * block, H, hd)[:, :L]


def _block(cfg: MoeConfig, w, experts, l, j: int, x, q_pos, real, carry, append):
    """Layer ``l`` (the ``j``-th of its period: its kind is static) applied
    to the residual stream ``x [B, L, D]`` (float32).  ``append(carry, l, j,
    q, k, v)`` files this layer's keys and values and returns the attention's
    result (``q`` float32: the attention rounds it); ``experts(m, ids,
    gates)`` is the layer's expert branch; ``real [B, L]`` says which tokens
    count in the expert load.  Returns the new state, the carry, the load and
    the layer's keys and values."""
    B, L, D = x.shape
    H, Hkv, hd, eps = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.rms_norm_eps
    a = _rms(x, w["in_norm"], eps)
    ids, gates = route(cfg, a.reshape(B * L, D), w["router"])
    q = _mm_t(a, w["wq"]).reshape(B, L, H, hd)
    k = _mm_t(a, w["wk"]).reshape(B, L, Hkv, hd)
    if cfg.rope_layout[j]:
        q, k = _rope(q, q_pos, cfg.rope_theta), _rope(k, q_pos, cfg.rope_theta)
    v = _mm_t(a, w["wv"]).reshape(B, L, Hkv, hd)
    k, v = k.astype(cfg.dtype), v.astype(cfg.dtype)
    carry, o = append(carry, l, j, q, k, v)
    x = x + _mm(o.reshape(B, L, H * hd), w["wo"])
    m = _rms(x, w["post_norm"], eps)
    f = experts(m.reshape(B * L, D), ids, gates)
    return x + f.reshape(B, L, D), carry, expert_load(cfg, ids, real.reshape(-1)), (k, v)


def _stack(cfg: MoeConfig, params, ids, q_pos, real, carry, append, held=None, keep_kv: bool = False):
    """Embedding, then every period of layers.  Returns the normed state
    ``[B, L, D]``, the cache carry, per layer the experts that got a real
    token and the busiest one's tokens (``[layers]`` each) and, asked for,
    every layer's keys and values ``[layers, B, L, Hkv, hd]``."""
    p, Ly = cfg.period, cfg.num_hidden_layers
    layers = params["layers"]
    rest = {n: a.reshape((Ly // p, p) + a.shape[1:]) for n, a in layers.items() if n not in _EXPERT_LEAVES}
    x = params["embed"][ids].astype(jnp.float32)

    def one_period(c, xs):
        w, i = xs
        x, carry = c
        loads, kvs = [], []
        for j in range(p):
            # the j-th layer of period i: one branch a period, each holding that layer's experts by reference
            branches = [partial(grouped_experts, cfg, held, _expert_leaves(layers, q * p + j, held)) for q in range(Ly // p)]
            x, carry, load, kv = _block(
                cfg, {n: a[j] for n, a in w.items()}, partial(jax.lax.switch, i, branches), i * p + j, j, x, q_pos, real, carry, append
            )
            loads.append(load)
            kvs.append(kv)
        ys = (jnp.stack([t for t, _ in loads]), jnp.stack([b for _, b in loads]))
        return (x, carry), ys + ((jnp.stack([k for k, _ in kvs]), jnp.stack([v for _, v in kvs])) if keep_kv else ())

    (x, carry), ys = jax.lax.scan(one_period, (x, carry), (rest, jnp.arange(Ly // p)))
    kept = tuple(a.reshape((Ly,) + a.shape[2:]) for a in ys[2:])
    return _rms(x, params["final_norm"], cfg.rms_norm_eps), carry, (ys[0].reshape(Ly), ys[1].reshape(Ly)), kept


def forward(cfg: MoeConfig, params, ids, held=None, query_block: int = 0):
    """Full causal forward of ``ids [B, L]`` with no cache: logits ``[B, L,
    V]`` (float32).  ``held = (lo, hi)`` computes only those experts' part of
    every expert layer (and lets it feed the next layer: a share, not the model)."""
    B, L = ids.shape
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None, :], (B, L))
    how = prompt_attention(cfg, L)

    def append(carry, l, j, q, k, v):
        window = cfg.sliding_window_size if cfg.sliding_window_layout[j] else 0
        return carry, _attend_prompt(q, k, v, 0, window, how, query_block)

    x = _stack(cfg, params, ids, pos, jnp.ones((B, L), bool), (), append, held)[0]
    return _head(params, x)


def expert_layer(cfg: MoeConfig, params, l: int, a, m, held=None):
    """One layer's expert branch alone, ``a`` / ``m [N, D]`` the normed
    states the router and the experts read: what the shares of an expert
    axis each compute, and what they must add up to."""
    layers = params["layers"]
    ids, gates = route(cfg, a, layers["router"][l])
    return grouped_experts(cfg, held, _expert_leaves(layers, l, held), m, ids, gates)


# ---------------------------------------------------------------------------
# the slot pool's programs (serve/decode.py): pools (full [S, n_full, T, Hkv,
# hd], ring [S, n_window, ring, Hkv, hd]), keys and values apart
# ---------------------------------------------------------------------------


def _kind_index(cfg: MoeConfig, l, j: int):
    """Layer ``l``'s row in the pool of its kind (``j``: its place in the period)."""
    layout = cfg.sliding_window_layout[: cfg.period]
    same = [i for i in range(cfg.period) if layout[i] == layout[j]]
    return (l // cfg.period) * len(same) + same.index(j)


def slot_prefill(cfg: MoeConfig, S: int, T: int, B: int, L_sfx: int, P: int, block: int = 0) -> Callable:
    """JOIN of ``B`` rows, the looped family's signature: ``(params, pool_k,
    pool_v, slots [B], suffix_ids [B, L_sfx], n_len [B], prefix_k, prefix_v,
    rngs [B, 2], temps [B]) -> (pool_k, pool_v, first [B], rngs, extra)``
    with each pool the pair (full, ring).  A full layer's keys and values
    land at rows ``[0, P + L_sfx)`` of the slot, as the looped family's; a
    window layer's ring is written whole, row ``r`` with the last position
    under ``n_len`` congruent to ``r`` (a cached prefix lands only where the
    window still holds it; pad positions past ``n_len`` never land).  The
    suffix attends through ``_attend_prompt``, window layers only the band of
    keys that reaches a query.  ``extra`` carries, beside the first token's
    stats, the per-layer expert load over real tokens (a pad row counts as
    the row it repeats) and, where the prefix tier's ``block`` is given,
    ``prompt_kv``: the suffix's keys and values cut into the tier's blocks,
    per row and block ``[layers, block, Hkv, hd]``, which stay on the device
    (a ring no longer holds a long prompt's first blocks, and a slice a
    block after the join cost the engine 0.45 s of dispatches a join).  The
    pools are donated and updated in place."""
    ring = cfg.pool_layout(T)[1][2]
    how = prompt_attention(cfg, L_sfx)

    def run(params, pool_k, pool_v, slots, suffix_ids, n_len, prefix_k, prefix_v, rngs, temps):
        pos = jnp.broadcast_to((P + jnp.arange(L_sfx, dtype=jnp.int32))[None, :], (B, L_sfx))
        # a single row is written twice over, as the looped family's join (models/looped.py)
        twice = jnp.arange(B) if B > 1 else jnp.zeros(2, jnp.int32)
        rows = slots[twice]
        last = n_len[:, None] - 1
        r = jnp.arange(ring, dtype=jnp.int32)[None, :]
        src = jnp.clip(last - jnp.mod(last - r, ring), 0, P + L_sfx - 1)[twice]  # [B, ring]: the position row r is to hold

        def append(carry, l, j, q, k, v):
            (full_k, ring_k), (full_v, ring_v) = carry
            if P:
                k = jnp.concatenate([_prefix_rows(prefix_k, l, k.dtype), k], axis=1)
                v = jnp.concatenate([_prefix_rows(prefix_v, l, v.dtype), v], axis=1)
            d = _kind_index(cfg, l, j)
            if cfg.sliding_window_layout[j]:
                held_k, held_v = (jnp.take_along_axis(x[twice], src[:, :, None, None], axis=1) for x in (k, v))
                ring_k = ring_k.at[rows, d].set(held_k, mode="promise_in_bounds")
                ring_v = ring_v.at[rows, d].set(held_v, mode="promise_in_bounds")
                window = cfg.sliding_window_size
            else:
                full_k = full_k.at[rows, d, : P + L_sfx].set(k[twice], mode="promise_in_bounds")
                full_v = full_v.at[rows, d, : P + L_sfx].set(v[twice], mode="promise_in_bounds")
                window = 0
            return ((full_k, ring_k), (full_v, ring_v)), _attend_prompt(q, k, v, P, window, how)

        real = pos < n_len[:, None]
        x, (pool_k, pool_v), (touched, busiest), kept = _stack(
            cfg, params, suffix_ids, pos, real, (pool_k, pool_v), append, keep_kv=bool(block)
        )
        at = jnp.maximum(n_len - 1 - P, 0)
        logits = _head(params, jnp.take_along_axis(x, at[:, None, None], axis=1)[:, 0])
        rngs, tok = _sample(logits, rngs, temps)
        extra = {**token_stats(logits, tok), "experts_touched": touched, "expert_load_max": busiest}
        if block:
            extra["prompt_kv"] = tuple(
                tuple(tuple(a[:, b, i * block : (i + 1) * block] for i in range(L_sfx // block)) for b in range(B)) for a in kept
            )
        return pool_k, pool_v, tok, rngs, extra

    return jax.jit(run, donate_argnums=(1, 2))


def slot_step(cfg: MoeConfig, S: int, T: int, chunk: int) -> Callable:
    """Up to ``chunk`` single-token steps over the whole pool, the looped
    family's signature and rules (only the first ``n_steps`` run; a lane
    that is not live emits ``-1``).  A live lane writes its token's keys and
    values at row ``min(pos, T - 1)`` of its slot's full layers and at row
    ``pos mod ring`` of its rings, then attends a full layer's rows up to
    ``pos`` and, of a ring, the rows whose position ``pos - ((pos - r) mod
    ring)`` is not negative.  A lane that is not live writes its own slot's
    rows at its frozen position: its occupant has left, and a join rewrites
    what the next one attends.  ``extra`` carries per step the per-layer
    expert load over live lanes.  The pools are donated and updated in place."""
    lanes = jnp.arange(S)
    ring = cfg.pool_layout(T)[1][2]

    def run(params, pool_k, pool_v, tok, pos, active, left, rngs, temps, eos, n_steps):
        def step(carry):
            pool_k, pool_v, tok, pos, act, left, rngs = carry
            live = act & (left > 0)
            at_full, at_ring = jnp.minimum(pos, T - 1), jnp.mod(pos, ring)
            seen_full = jnp.arange(T)[None, :] <= pos[:, None]
            seen_ring = pos[:, None] - jnp.mod(pos[:, None] - jnp.arange(ring)[None, :], ring) >= 0

            def append(c, l, j, q, k, v):
                (full_k, ring_k), (full_v, ring_v) = c
                d = _kind_index(cfg, l, j)
                if cfg.sliding_window_layout[j]:
                    ring_k = ring_k.at[lanes, d, at_ring].set(k[:, 0], mode="promise_in_bounds")
                    ring_v = ring_v.at[lanes, d, at_ring].set(v[:, 0], mode="promise_in_bounds")
                    K, V, seen = ring_k, ring_v, seen_ring
                else:
                    full_k = full_k.at[lanes, d, at_full].set(k[:, 0], mode="promise_in_bounds")
                    full_v = full_v.at[lanes, d, at_full].set(v[:, 0], mode="promise_in_bounds")
                    K, V, seen = full_k, full_v, seen_full
                K, V = (jax.lax.dynamic_index_in_dim(x, d, axis=1, keepdims=False) for x in (K, V))
                return ((full_k, ring_k), (full_v, ring_v)), _attend_rows(q.astype(K.dtype), K, V, seen)

            x, (pool_k, pool_v), (touched, busiest), _ = _stack(
                cfg, params, tok[:, None], pos[:, None], live[:, None], (pool_k, pool_v), append
            )
            logits = _head(params, x[:, 0])
            rngs2, nxt = _sample(logits, rngs, temps)
            stats = {**token_stats(logits, nxt), "experts_touched": touched, "expert_load_max": busiest}
            carry = (
                pool_k, pool_v, jnp.where(live, nxt, tok), jnp.where(live, pos + 1, pos),
                live & (nxt != eos), jnp.where(live, left - 1, left),
                jnp.where(live[:, None], rngs2, rngs),
            )
            return carry, (jnp.where(live, nxt, -1), stats)

        carry = (pool_k, pool_v, tok, pos, active, left, rngs)
        skipped = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype), jax.eval_shape(step, carry)[1][1])
        blank = (jnp.full((S,), -1, jnp.int32), skipped)
        (pool_k, pool_v, _, _, _, _, rngs), (em, extra) = jax.lax.scan(
            lambda c, i: jax.lax.cond(i < n_steps, step, lambda c: (c, blank), c), carry, jnp.arange(chunk)
        )
        return pool_k, pool_v, rngs, em, extra

    return jax.jit(run, donate_argnums=(1, 2))

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
each hold one layer's, by reference.  The other weights are no scanned
operand either: each layer takes its own by one dynamic index of the flat
``[layers, ...]`` stack, as the hybrid family's.

**The parallel block** (``use_parallel_block``, Command A+'s
``cohere2_moe`` keys; ``MoeConfig.from_architecture`` reads either set).
One LayerNorm (mean removed, a weight, no bias) feeds attention, the router
and the experts alike, and both branches are added to the stream: ``x = x +
Attn(a) + F(a)``.  The router's ``sigmoid`` scores choose the
``num_experts_per_tok`` largest, renormalised among themselves; the experts
are gated SiLU; ``num_shared_experts`` shared ones of the same width every
token takes (one gated MLP of their summed width: their sum) are averaged in,
``F = (routed + sum_i S_i / n) / 2``.  Window layers rotate queries and keys
GPT-J's way (the pairs ``(2i, 2i + 1)``), full layers carry no positions;
the head is the embedding's table, scaled by ``logit_scale``.  The choices
are static in the configuration: without them a SmallThinker program
computes what it did.

**What this chip holds** (``experts_held = (lo, hi)``, as the hybrid
family's): the leaves ``[hi - lo, ...]`` of the routed experts ``[lo, hi)``;
the router keeps every output and its choices, and pairs routed to an absent
expert are computed by no one.  Such a layer sorts its pairs, absent ones
last, and carries the held ones through the gather and the grouped products
in passes of a bounded number of rows (``_held_pass_rows``: twice what even
routing sends the range, at most ``HELD_BYTES`` of temporaries), as many
passes as the held pairs fill; the shared experts run over blocks of
``FFN_BLOCK`` tokens.  So a 12,544-token join's expert branch never holds a
row for every (token, expert) pair.

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
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from .looped import _head, _mm, _mm_t, _prefix_rows, _rms, _rope, _sample, token_stats

__all__ = ["MoeConfig", "ffn_blocks", "forward", "init_params", "join_rows", "slot_prefill", "slot_step"]

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
HELD_BYTES = 1 << 29  # a held range's pass: its gathered rows and its products' temporaries stay under this
FFN_BLOCK = 2048  # tokens the shared experts take at a time: [FFN_BLOCK, summed width] products, never [N, ...]
ATTEND_BYTES = 256 << 20  # a prompt's float32 queries made and attended at a time: whole key/value groups under this
JOIN_PREFIX_BYTES = 256 << 20  # the cached keys and values one join gathers behind its rows' prefixes, all rows together


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
    rms_norm_eps: float = 1e-6  # the norms' epsilon (the parallel block's LayerNorms': ``layer_norm_eps``)
    rope_theta: float = 10000.0
    max_position_embeddings: int = 2048
    dtype: Any = jnp.bfloat16
    # the parallel block (module docstring: its LayerNorm, sigmoid router, SiLU experts, GPT-J rotary and tied head
    # come together); off, SmallThinker's block
    parallel_block: bool = False
    num_shared_experts: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    logit_scale: float = 1.0

    @classmethod
    def from_architecture(cls, arch: Mapping[str, Any], dtype=jnp.bfloat16) -> "MoeConfig":
        """Read a published ``config.json``; what this family cannot run is
        refused here, by name, and never approximated."""
        if "use_parallel_block" in arch or arch.get("model_type") == "cohere2_moe":
            return cls._from_parallel(arch, dtype)
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

    @classmethod
    def _from_parallel(cls, arch: Mapping[str, Any], dtype) -> "MoeConfig":
        """The parallel block's keys (``cohere2_moe``), plus ``experts_held``."""
        rope = arch.get("rope_parameters") or {}
        refused = {
            "use_parallel_block": (not arch.get("use_parallel_block"), "false: attention and experts read one normed input side by side here"),
            "first_k_dense_replace": (int(arch.get("first_k_dense_replace") or 0) > 0, "> 0: a dense layer among the expert layers is not implemented"),
            "use_qk_norm": (bool(arch.get("use_qk_norm")), "true: query/key norms are not implemented in this family"),
            "attention_bias": (bool(arch.get("attention_bias")), "true: the projections have no bias"),
            "rope_scaling": (arch.get("rope_scaling") is not None or rope.get("rope_type", "default") != "default",
                             "set: scaled rotary positions are not implemented"),
            "position_embedding_type": (arch.get("position_embedding_type", "rope_gptj") != "rope_gptj", "not rope_gptj: window layers rotate GPT-J's pairs"),
            "rotary_pct": (float(arch.get("rotary_pct", 1)) != 1, "below 1: every dim of a head is rotated"),
            "hidden_act": (arch.get("hidden_act", "silu") != "silu" or not arch.get("use_gated_activation", True), "the experts are gated SiLU"),
            "expert_selection_fn": (arch.get("expert_selection_fn", "sigmoid") != "sigmoid", "the router's scores are sigmoids"),
            "norm_topk_prob": (not arch.get("norm_topk_prob", True), "false: the chosen experts' scores are renormalised among themselves"),
            "shared_expert_combination_strategy": (arch.get("shared_expert_combination_strategy", "average") != "average",
                                                   "not average: the shared experts are averaged in"),
            "tie_word_embeddings": (not arch.get("tie_word_embeddings", True), "false: the head is the embedding's table"),
            "use_parallel_embedding": (bool(arch.get("use_parallel_embedding")), "true: one embedding table"),
        }
        for key, (bad, why) in refused.items():
            if bad:
                raise _refuse(f"{key}={arch.get(key)!r} {why}")
        layers = int(arch["num_hidden_layers"])
        types = list(arch.get("layer_types") or ())
        if len(types) != layers or set(types) - {"sliding_attention", "full_attention"}:
            raise _refuse(f"layer_types must hold num_hidden_layers={layers} entries of sliding_attention or full_attention, got {types}")
        heads, kv_heads = int(arch["num_attention_heads"]), int(arch["num_key_value_heads"])
        if heads % kv_heads:
            raise _refuse(f"num_attention_heads={heads} is not a multiple of num_key_value_heads={kv_heads}")
        experts, active = int(arch["num_experts"]), int(arch["num_experts_per_tok"])
        if not 1 <= active <= experts:
            raise _refuse(f"num_experts_per_tok={active} of num_experts={experts}")
        lo, hi = (int(x) for x in arch.get("experts_held") or (0, experts))
        if not 0 <= lo < hi <= experts:
            raise _refuse(f"experts_held=[{lo}, {hi}) of num_experts={experts}")
        window = tuple(int(t == "sliding_attention") for t in types)
        if any(window) and int(arch.get("sliding_window") or 0) < 1:
            raise _refuse(f"sliding_window={arch.get('sliding_window')} with sliding_attention layers")
        hidden = int(arch["hidden_size"])
        return cls(
            vocab_size=int(arch["vocab_size"]), hidden_size=hidden, num_attention_heads=heads, num_key_value_heads=kv_heads,
            head_dim=int(arch.get("head_dim") or hidden // heads), moe_ffn_hidden_size=int(arch["intermediate_size"]),
            moe_num_primary_experts=experts, moe_num_active_primary_experts=active, num_hidden_layers=layers,
            rope_layout=window, sliding_window_layout=window, sliding_window_size=int(arch.get("sliding_window") or 0),
            rms_norm_eps=float(arch.get("layer_norm_eps", 1e-5)),
            rope_theta=float(arch.get("rope_theta") or rope.get("rope_theta", 10000.0)),
            max_position_embeddings=int(arch.get("max_position_embeddings", 2048)), dtype=dtype, parallel_block=True,
            num_shared_experts=int(arch.get("num_shared_experts") or 0), experts_held=(lo, hi),
            logit_scale=float(arch.get("logit_scale", 1.0)),
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
    norm weights 1 + normal(0, 0.1) in float32.  The parallel block has no
    ``post_norm`` and no ``head`` (it is the embedding's table); shared
    experts are ``shared_wg shared_wu [Ly, D, n * F]``, ``shared_wd [Ly, n *
    F, D]``; a held range's experts are ``[hi - lo, ...]``."""
    D, F, V, E, Ly = cfg.hidden_size, cfg.moe_ffn_hidden_size, cfg.vocab_size, cfg.moe_num_primary_experts, cfg.num_hidden_layers
    A, Akv = cfg.num_attention_heads * cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim
    here = E if cfg.experts_held is None else cfg.experts_held[1] - cfg.experts_held[0]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16 + 3 * Ly))

    def mat(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(cfg.dtype)

    def norm(*shape):
        return 1.0 + 0.1 * jax.random.normal(next(keys), shape, jnp.float32)

    params = {
        "embed": mat(V, D),
        "head": mat(D, V),
        "final_norm": norm(D),
        "layers": {
            "wq": mat(Ly, A, D), "wk": mat(Ly, Akv, D), "wv": mat(Ly, Akv, D), "wo": mat(Ly, A, D),
            "router": mat(Ly, D, E),
            "wg": tuple(mat(here, D, F) for _ in range(Ly)), "wu": tuple(mat(here, D, F) for _ in range(Ly)),
            "wd": tuple(mat(here, F, D) for _ in range(Ly)),
            "in_norm": norm(Ly, D), "post_norm": norm(Ly, D),
        },
    }
    if cfg.parallel_block:
        del params["head"], params["layers"]["post_norm"]
    if cfg.num_shared_experts:
        Fs = cfg.num_shared_experts * F
        params["layers"].update(shared_wg=mat(Ly, D, Fs), shared_wu=mat(Ly, D, Fs), shared_wd=mat(Ly, Fs, D))
    return params


_EXPERT_LEAVES = ("wg", "wu", "wd")


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------


def _layer_norm(x, w, eps):
    """LayerNorm with the mean removed, a weight and no bias."""
    c = x - jnp.mean(x, axis=-1, keepdims=True)
    return c * jax.lax.rsqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps) * w


def _norm(cfg: MoeConfig, x, w):
    return (_layer_norm if cfg.parallel_block else _rms)(x, w, cfg.rms_norm_eps)


def _pairs_first(x):
    """``x [B, L, H, hd]`` with each head's dims reordered evens first, then
    odds: GPT-J's rotary pairs ``(2i, 2i + 1)`` become rotate-half's ``(i, i
    + hd/2)``.  Queries and keys are reordered alike, so ``q . k`` is what it
    was, and the rotation is rotate-half's (``_rope``): the same pairs turned
    by the same angles, with no ``[..., hd / 2, 2]`` view left for the chip's
    tiles to pad."""
    B, L, H, hd = x.shape
    return x.reshape(B, L, H, hd // 2, 2).swapaxes(-1, -2).reshape(B, L, H, hd)


def _rotate(cfg: MoeConfig, x, pos):
    """A window layer's rotary positions: rotate-half, or in the parallel
    block GPT-J's pairs (reordered first: the cache keeps such a layer's keys
    so, one order).  The reorder is of the projection's output: behind a
    barrier, or the compiler moves it onto the weights and copies them on
    every call."""
    if cfg.parallel_block:
        x = _pairs_first(jax.lax.optimization_barrier(x))
    return _rope(x, pos, cfg.rope_theta)


def _head_chunks(cfg: MoeConfig, tokens: int) -> int:
    """Chunks of whole key/value groups a layer's queries are made and
    attended in, for ``tokens`` tokens: the fewest that keep a chunk's float32
    queries under ``ATTEND_BYTES`` (1 for a step, or a prompt that small)."""
    Hkv = cfg.num_key_value_heads
    q_bytes = 4 * tokens * cfg.num_attention_heads * cfg.head_dim
    return next((n for n in range(1, Hkv + 1) if Hkv % n == 0 and q_bytes <= n * ATTEND_BYTES), Hkv)


def _logits(cfg: MoeConfig, params, x):
    """The head: untied, or the embedding's table ``[V, D]`` contracted on its
    second axis as it lies (no transposed copy of it a call) and scaled by ``logit_scale``."""
    if not cfg.parallel_block:
        return _head(params, x)
    table = params["embed"]
    return jnp.einsum("...d,vd->...v", x.astype(table.dtype), table, preferred_element_type=jnp.float32) * cfg.logit_scale


def route(cfg, a, router):
    """The layer's experts for every token of ``a [N, D]`` (float32, the
    normed state the router reads): ids ``[N, k]`` (ties broken as
    ``lax.top_k`` does: the lower id first) and their weights, a softmax over
    the chosen logits, or, in the parallel block, the chosen sigmoid scores
    over their sum.  ``cfg`` is any family's with routed experts."""
    r = jnp.dot(a, router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    if getattr(cfg, "parallel_block", False):
        top, ids = jax.lax.top_k(jax.nn.sigmoid(r), cfg.experts_per_token)
        return ids.astype(jnp.int32), top / jnp.sum(top, axis=-1, keepdims=True)
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


def _held_pass_rows(pairs: int, share: float, D: int, F: int) -> int:
    """Rows one pass of a held range's grouped product carries, of ``pairs``
    routed pairs of which even routing sends ``share`` to the range: twice
    that, no more than the pairs, nor than ``HELD_BYTES`` of a pass's
    temporaries (a row's gathered input, its two products, their gated
    product and its output), in whole row tiles."""
    want = min(pairs, max(1, round(2 * share * pairs)), max(512, HELD_BYTES // (6 * D + 10 * F)))
    return -(-want // _row_tile(want)) * _row_tile(want)


def grouped_experts(cfg, held, w, m, ids, gates, act=jax.nn.relu):
    """The expert layer as one grouped product: the ``N * k`` (token,
    expert) pairs sorted by expert, one grouped matrix product per weight
    (``grouped_product``) over one layer's experts ``w``: ``wg wu [E, D,
    F]``, ``wd [E, F, D]`` (gated by ``act``), the results weighted and
    summed back per token.  ``held = (lo, hi)`` says that ``w`` holds only
    the experts ``[lo, hi)`` of the ``cfg.n_experts`` the router chose among
    (what one chip of an expert axis holds): pairs routed to the others sort
    last and are computed, gathered and carried by no one; the held ones go
    through in passes (``_held_pass_rows``), each the next stretch of the
    sorted pairs with its experts' groups cut to it.  ``None``: every expert
    is here, every pair in one pass.  Returns ``[N, D]`` float32 and the rows
    carried (every pass's, whole)."""
    N, k = ids.shape
    if held is None:
        flat = ids.reshape(-1)
        sizes = jnp.zeros(cfg.n_experts, jnp.int32).at[flat].add(1)
        # whole row tiles for the kernel: the padding sorts last, is computed by none and read by none
        order = jnp.argsort(jnp.pad(flat, (0, -flat.shape[0] % _row_tile(flat.shape[0])), constant_values=cfg.n_experts), stable=True)
        x = m.astype(cfg.dtype)[jnp.minimum(order // k, N - 1)]

        y = (act(grouped_product(x, w["wg"], sizes)) * grouped_product(x, w["wu"], sizes)).astype(cfg.dtype)
        o = grouped_product(y, w["wd"], sizes)
        back = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0], dtype=order.dtype))[: N * k]
        return jnp.sum(o[back].reshape(N, k, -1) * gates[:, :, None], axis=1), jnp.int32(order.shape[0])

    n = held[1] - held[0]
    _, D, F = w["wg"].shape
    rows = _held_pass_rows(N * k, n / cfg.n_experts, D, F)
    mine = (ids >= held[0]) & (ids < held[1])
    local = jnp.where(mine, ids - held[0], n).reshape(-1)
    sizes = jnp.zeros(n, jnp.int32).at[local].add(1, mode="drop")
    starts = jnp.cumsum(sizes) - sizes
    total = jnp.sum(sizes)
    order = jnp.argsort(local, stable=True)  # held pairs first, by expert
    back = jnp.zeros_like(order).at[order].set(jnp.arange(N * k, dtype=order.dtype)).reshape(N, k)  # a pair's place in that order
    order = jnp.pad(order, (0, rows))  # a pass may run past the last pair: those rows are in no group
    x_all = m.astype(cfg.dtype)

    def one_pass(c):
        s, out = c
        x = x_all[jax.lax.dynamic_slice_in_dim(order, s, rows) // k]
        cut = jnp.clip(starts + sizes, s, s + rows) - jnp.clip(starts, s, s + rows)  # each group's rows in this pass
        y = (act(grouped_product(x, w["wg"], cut)) * grouped_product(x, w["wu"], cut)).astype(cfg.dtype)
        o = grouped_product(y, w["wd"], cut)
        at = back - s
        inside = mine & (at >= 0) & (at < rows)

        def choice(j, out):  # this pass's rows back to their tokens, weighted (an absent expert's pair reads none)
            take = partial(jax.lax.dynamic_index_in_dim, index=j, axis=1, keepdims=False)
            return out + jnp.where(take(inside)[:, None], o[jnp.clip(take(at), 0, rows - 1)], 0.0) * take(gates)[:, None]

        # a loop, not k terms side by side: one [N, D] term lives at a time
        return s + rows, jax.lax.fori_loop(0, k, choice, out)

    _, out = jax.lax.while_loop(lambda c: c[0] < total, one_pass, (jnp.int32(0), jnp.zeros((N, m.shape[1]), jnp.float32)))
    return out, (-(-total // rows) * rows).astype(jnp.int32)


def _expert_leaves(layers, l: int, held):
    """Layer ``l``'s experts, by reference; a share's are cut from them (a copy: for a test, not for a cell)."""
    return {n: layers[n][l] if held is None else layers[n][l][held[0] : held[1]] for n in _EXPERT_LEAVES}


def expert_load(cfg, ids, real, held=None):
    """Of one layer's choices ``ids [N, k]``, counting the tokens ``real
    [N]`` only: how many of the experts held here (``held = (lo, hi)``, or
    all of them) got a token, the busiest one's tokens, and the pairs that
    fell to them.  ``cfg`` is any family's with routed experts."""
    lo, hi = held or (0, cfg.n_experts)
    local = jnp.where(real[:, None] & (ids >= lo) & (ids < hi), ids - lo, hi - lo).reshape(-1)
    sizes = jnp.zeros(hi - lo + 1, jnp.int32).at[local].add(1)[:-1]
    return jnp.sum(sizes > 0).astype(jnp.int32), jnp.max(sizes), jnp.sum(sizes)


def join_rows(cfg: MoeConfig, prefix: int) -> Optional[int]:
    """The most rows one join behind ``prefix`` cached tokens carries (None:
    no limit without a prefix): each row gathers its prefix's keys and
    values, every layer's, beside its queries; rows that would pass
    ``JOIN_PREFIX_BYTES`` of them join apart (one row always goes)."""
    per_row = 2 * cfg.num_hidden_layers * cfg.num_key_value_heads * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize * prefix
    return max(1, JOIN_PREFIX_BYTES // per_row) if prefix else None


def ffn_blocks(cfg: MoeConfig, tokens: int) -> int:
    """Blocks of ``FFN_BLOCK`` tokens the shared experts of ``tokens`` take (1 without shared experts)."""
    return -(-tokens // FFN_BLOCK) if cfg.num_shared_experts else 1


def _shared(cfg: MoeConfig, w, m):
    """The shared experts as one gated SiLU MLP of their summed width on ``m
    [N, D]``: ``sum_i S_i``, over ``ffn_blocks`` blocks of tokens."""
    def mlp(x):
        return _mm((jax.nn.silu(_mm(x, w["shared_wg"])) * _mm(x, w["shared_wu"])).astype(cfg.dtype), w["shared_wd"])

    N, n = m.shape[0], ffn_blocks(cfg, m.shape[0])
    size = -(-N // n)
    size += -size % 8
    # unrolled, not a loop (a loop's body would take each layer's shared weights as operands, copied out of the
    # stack), and one block after another: the next block's input waits on the last one's output
    out = []
    for a in range(0, N, size):
        x = m[a : a + size]
        if out:
            x, out[-1] = jax.lax.optimization_barrier((x, out[-1]))
        out.append(mlp(x))
    return jnp.concatenate(out, axis=0)


def _ffn(cfg: MoeConfig, w, routed, m, ids, gates):
    """The expert branch on the normed state ``m [N, D]``: the routed part
    (``routed(m, ids, gates)``) and, where the layer has shared experts, their
    average beside it, ``(routed + sum_i S_i / n) / 2``.  Returns it and the rows carried."""
    r, carried = routed(m, ids, gates)
    if cfg.num_shared_experts:
        r = 0.5 * (r + _shared(cfg, w, m) / cfg.num_shared_experts)
    return r, carried


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


def prompt_attention(cfg: "MoeConfig", L: int, rows: int = 1, keys: int = 0) -> str:
    """What attends ``rows`` prompts of ``L`` tokens over at most ``keys``
    keys each (``L`` where not given): "kernel" on a TPU where the heads fill
    the kernel's lanes and either the prompt fills one of its tiles or the
    block path's float32 scores of one query block would pass
    ``ATTEND_BYTES`` (128 heads over a long cached prefix), else "blocks"
    (the tests' tiny models, a short prompt, any other backend)."""
    how = ATTENTION_KERNEL or ("kernel" if jax.default_backend() == "tpu" else "blocks")
    scores = 4 * rows * cfg.num_attention_heads * min(L, QUERY_BLOCK) * max(keys, L)
    return how if cfg.head_dim % 128 == 0 and (L >= KERNEL_BLOCK or scores > ATTEND_BYTES) else "blocks"


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


def _block(cfg: MoeConfig, w, experts, l, j: int, x, q_pos, real, carry, append, held=None):
    """Layer ``l`` (the ``j``-th of its period: its kind is static) applied
    to the residual stream ``x [B, L, D]`` (float32).  ``append(carry, l, j,
    k, v)`` files this layer's keys and values and returns the carry and
    ``attend(q, groups)``: the attention's result for queries ``q`` (float32:
    the attention rounds it) of the key/value heads ``groups`` (a slice).  A
    prompt's queries are made and attended ``_head_chunks`` whole groups at
    a time, each chunk's output projected and summed; ``experts(m, ids,
    gates)`` is the layer's expert branch (it returns the rows it carried
    too); ``real [B, L]`` says which tokens count in the expert load; ``held``
    the experts held here.  Returns the new state, the carry, the load and
    the layer's keys and values.  In the parallel block the router, the
    attention and the experts read one normed input and both branches are
    added at once; else the experts read the post-attention normed state."""
    B, L, D = x.shape
    H, Hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    a = _norm(cfg, x, w["in_norm"])
    ids, gates = route(cfg, a.reshape(B * L, D), w["router"])
    k = _mm_t(a, w["wk"]).reshape(B, L, Hkv, hd)
    if cfg.rope_layout[j]:
        k = _rotate(cfg, k, q_pos)
    v = _mm_t(a, w["wv"]).reshape(B, L, Hkv, hd)
    k, v = k.astype(cfg.dtype), v.astype(cfg.dtype)
    carry, attend = append(carry, l, j, k, v)
    n = _head_chunks(cfg, B * L)
    rows, groups = H * hd // n, Hkv // n
    attn = None
    for c in range(n):  # a chunk of whole key/value groups' query heads at a time
        if c:  # one after another: the next chunk's queries are not made before the last one's output is summed
            a, attn = jax.lax.optimization_barrier((a, attn))
        # the barrier keeps the heads' reshape off the weights: moved onto them it copies every layer's wq a step
        q = jax.lax.optimization_barrier(_mm_t(a, w["wq"][c * rows : (c + 1) * rows])).reshape(B, L, H // n, hd)
        if cfg.rope_layout[j]:
            q = _rotate(cfg, q, q_pos)
        o = _mm(attend(q, slice(c * groups, (c + 1) * groups)).reshape(B, L, rows), w["wo"][c * rows : (c + 1) * rows])
        attn = o if attn is None else attn + o
    if cfg.parallel_block:
        f, carried = _ffn(cfg, w, experts, a.reshape(B * L, D), ids, gates)
        x = x + attn + f.reshape(B, L, D)
    else:
        x = x + attn
        f, carried = _ffn(cfg, w, experts, _norm(cfg, x, w["post_norm"]).reshape(B * L, D), ids, gates)
        x = x + f.reshape(B, L, D)
    return x, carry, expert_load(cfg, ids, real.reshape(-1), held) + (carried,), (k, v)


def _stack(cfg: MoeConfig, params, ids, q_pos, real, carry, append, held=None, keep_kv: bool = False):
    """Embedding, then every period of layers.  Returns the normed state
    ``[B, L, D]``, the cache carry, per layer the held experts that got a
    real token, the busiest one's tokens, the pairs that fell to held
    experts and the rows the expert layer carried (``[layers]`` each) and,
    asked for, every layer's keys and values ``[layers, B, L, Hkv, hd]``.
    ``held`` cuts a share's experts from weights that hold them all (a test's)."""
    p, Ly = cfg.period, cfg.num_hidden_layers
    layers = params["layers"]
    x = params["embed"][ids].astype(jnp.float32)
    here = held or cfg.experts_held
    act = jax.nn.silu if cfg.parallel_block else jax.nn.relu

    def take(l):
        # each layer indexes the flat stack itself, so its slice has one consumer and fuses into its product: a
        # period's slab scanned out for several layers is copied through HBM before they read it
        return {n: jax.lax.dynamic_index_in_dim(a, l, keepdims=False) for n, a in layers.items() if n not in _EXPERT_LEAVES}

    def one_period(c, i):
        x, carry = c
        loads, kvs = [], []
        for j in range(p):
            # the j-th layer of period i: one branch a period, each holding that layer's experts by reference
            branches = [partial(grouped_experts, cfg, here, _expert_leaves(layers, q * p + j, held), act=act) for q in range(Ly // p)]
            x, carry, load, kv = _block(
                cfg, take(i * p + j), partial(jax.lax.switch, i, branches), i * p + j, j, x, q_pos, real, carry, append, here,
            )
            loads.append(load)
            kvs.append(kv)
        ys = tuple(jnp.stack([load[n] for load in loads]) for n in range(4))
        return (x, carry), ys + ((jnp.stack([k for k, _ in kvs]), jnp.stack([v for _, v in kvs])) if keep_kv else ())

    (x, carry), ys = jax.lax.scan(one_period, (x, carry), jnp.arange(Ly // p))
    kept = tuple(a.reshape((Ly,) + a.shape[2:]) for a in ys[4:])
    return _norm(cfg, x, params["final_norm"]), carry, tuple(a.reshape(Ly) for a in ys[:4]), kept


def _load_stats(loads) -> Dict[str, Any]:
    """A program's per-layer expert load, under the names the decode engine books."""
    return dict(zip(("experts_touched", "expert_load_max", "expert_pairs_held", "expert_pairs_carried"), loads))


def forward(cfg: MoeConfig, params, ids, held=None, query_block: int = 0):
    """Full causal forward of ``ids [B, L]`` with no cache: logits ``[B, L,
    V]`` (float32).  ``held = (lo, hi)`` computes only those experts' part of
    every expert layer (and lets it feed the next layer: a share, not the model)."""
    B, L = ids.shape
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None, :], (B, L))
    how = prompt_attention(cfg, L, B)

    def append(carry, l, j, k, v):
        window = cfg.sliding_window_size if cfg.sliding_window_layout[j] else 0
        return carry, lambda q, g: _attend_prompt(q, k[:, :, g], v[:, :, g], 0, window, how, query_block)

    x = _stack(cfg, params, ids, pos, jnp.ones((B, L), bool), (), append, held)[0]
    return _logits(cfg, params, x)


def expert_layer(cfg: MoeConfig, params, l: int, a, m, held=None):
    """One layer's expert branch alone, ``a`` / ``m [N, D]`` the normed
    states the router and the experts read (the parallel block's are one):
    what the shares of an expert axis each compute, and what they must add
    up to once what each computes alike (the shared experts) is counted once.
    ``held`` cuts a share from weights that hold every expert."""
    layers = params["layers"]
    w = {n: x[l] for n, x in layers.items() if n not in _EXPERT_LEAVES}
    ids, gates = route(cfg, a, w["router"])
    act = jax.nn.silu if cfg.parallel_block else jax.nn.relu
    routed = partial(grouped_experts, cfg, held or cfg.experts_held, _expert_leaves(layers, l, held), act=act)
    return _ffn(cfg, w, routed, m, ids, gates)[0]


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
    the row it repeats), the rows the expert layers carried and, where the
    prefix tier's ``block`` is given, ``prompt_kv``: the suffix's keys and values cut into the tier's blocks,
    per row and block ``[layers, block, Hkv, hd]``, which stay on the device
    (a ring no longer holds a long prompt's first blocks, and a slice a
    block after the join cost the engine 0.45 s of dispatches a join).  The
    pools are donated and updated in place."""
    ring = cfg.pool_layout(T)[1][2]
    how = prompt_attention(cfg, L_sfx, B, P + L_sfx)

    def run(params, pool_k, pool_v, slots, suffix_ids, n_len, prefix_k, prefix_v, rngs, temps):
        pos = jnp.broadcast_to((P + jnp.arange(L_sfx, dtype=jnp.int32))[None, :], (B, L_sfx))
        # a single row is written twice over, as the looped family's join (models/looped.py)
        twice = jnp.arange(B) if B > 1 else jnp.zeros(2, jnp.int32)
        rows = slots[twice]
        last = n_len[:, None] - 1
        r = jnp.arange(ring, dtype=jnp.int32)[None, :]
        src = jnp.clip(last - jnp.mod(last - r, ring), 0, P + L_sfx - 1)[twice]  # [B, ring]: the position row r is to hold

        def append(carry, l, j, k, v):
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
            return ((full_k, ring_k), (full_v, ring_v)), lambda q, g: _attend_prompt(q, k[:, :, g], v[:, :, g], P, window, how)

        real = pos < n_len[:, None]
        x, (pool_k, pool_v), loads, kept = _stack(
            cfg, params, suffix_ids, pos, real, (pool_k, pool_v), append, keep_kv=bool(block)
        )
        at = jnp.maximum(n_len - 1 - P, 0)
        logits = _logits(cfg, params, jnp.take_along_axis(x, at[:, None, None], axis=1)[:, 0])
        rngs, tok = _sample(logits, rngs, temps)
        extra = {**token_stats(logits, tok), **_load_stats(loads)}
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

            def append(c, l, j, k, v):
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
                return ((full_k, ring_k), (full_v, ring_v)), lambda q, g: _attend_rows(q.astype(K.dtype), K[:, :, g], V[:, :, g], seen)

            x, (pool_k, pool_v), loads, _ = _stack(
                cfg, params, tok[:, None], pos[:, None], live[:, None], (pool_k, pool_v), append
            )
            logits = _logits(cfg, params, x[:, 0])
            rngs2, nxt = _sample(logits, rngs, temps)
            stats = {**token_stats(logits, nxt), **_load_stats(loads)}
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

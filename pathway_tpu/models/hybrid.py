"""The hybrid decoder family: gated delta-rule layers beside gated
full-attention layers, routed experts beside a shared one.

A decoder-only language model in the shape Qwen3-Next publishes: layer ``l``
is full attention where ``(l + 1) mod full_attention_interval == 0``, else a
Gated DeltaNet (linear-attention) layer, so one period of the pattern is
``[linear, ..., linear, full]``.  Every norm but the delta rule's output
norm is an RMSNorm with a zero-centred weight, ``x * rsqrt(mean(x^2) + eps)
* (1 + w)``; no biases; an untied output head.  Every layer is ``h = x +
Mixer(N_in(x))``, ``x' = h + Experts(N_post(h))``:

- the full mixer: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads of ``head_dim``; the query
  projection yields per head a query AND an output gate; queries and keys
  are normed per head, rotated (rotate-half) on their first ``head_dim *
  partial_rotary_factor`` dims only; the softmax attention's result is
  multiplied by ``sigmoid(gate)`` before the output projection;
- the delta mixer: one projection yields ``q k v z`` and another ``b a``; a
  depthwise causal convolution of ``linear_conv_kernel_dim`` taps (no bias)
  and a SiLU pass over the ``q k v`` channels; ``q`` and ``k`` are
  L2-normalised per head (``q`` also scaled by ``1 / sqrt(key_dim)``), value
  head ``h`` reading key head ``h // (value heads / key heads)``; per value
  head ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``
  and a state ``S [key_dim, value_dim]`` that each token updates by the gated
  delta rule: ``S = exp(g) S``, ``u = beta (v - S^T k)``, ``S = S + k u^T``,
  ``o = S^T q``; the result is normed per head (plain weight), multiplied by
  ``silu(z)`` and projected out;
- the experts: ``softmax`` over all ``num_experts`` router logits, the
  ``num_experts_per_tok`` largest renormalised among themselves (= a softmax
  over the chosen logits: ``moe.route``), gated-SiLU experts of
  ``moe_intermediate_size``, plus one shared gated-SiLU expert every token
  takes, weighted by ``sigmoid`` of its own one-output gate.

**What this chip holds is part of what the model is built with.**
``experts_held = (lo, hi)`` names the routed experts whose weights are here
(leaves ``[hi - lo, D, F]``): the router keeps its ``num_experts`` outputs
and its choices, pairs routed to an absent expert sort last in the grouped
product and are computed by no one, and their part of the result is left out
(of the layer, and so of what goes on to the next: a share, not the model).
``vocab_size`` is the slice of the vocabulary held: embedding, head, logits
and sampling are over it.

Two computations of the delta rule, the same mathematics: ``delta_scan`` for
a prompt (chunks of ``CHUNK`` tokens in the WY form: within a chunk the
updates' dependence on each other is a unit lower-triangular system, solved
once; between chunks the float32 state is the carry of a ``lax.scan``) and
the one-token recurrence for a step.  Positions past a row's length do not
touch the state: their ``beta`` is zeroed (no update) and their ``g`` too
(decay 1), and the convolution's carried rows are gathered at the row's
length, not at the padded end.

The slot pool is two donated trees, as every family's: ``(rows_k, delta)``
and ``(rows_v, conv)``.  ``rows_*`` are the full layers' keys and values
``[S, n_full, T, Hkv, hd]``; ``delta [n_linear, S, Hv, dk, dv]`` (float32)
is the delta rule's state and ``conv [n_linear, S, taps - 1, channels]`` the
last pre-convolution rows.  A join writes a slot's state whole (from zeros,
or from the snapshot the prefix tier restored), so a slot's next occupant
can never see the last one's; a step updates live lanes' state in place and
leaves the others' as it is.  A join also returns the state at every
position ``snapshot_positions`` names inside its suffix (the scan is cut
there, so it costs no arithmetic), for the prefix tier to keep beside the
block that ends there.

What is shared with the other families: ``looped._rope``, ``_mm``,
``_mm_t``, ``_head``, ``_sample``, ``token_stats``;
``moe.route``, ``expert_load``, ``grouped_experts`` (the sort, ``gmm`` and
un-sort, here with SiLU and the held range's passes), ``prompt_attention``
and ``_attend_prompt`` (the flash kernel on a TPU), and ``_attend_rows``.

Precision: bfloat16 weights and matrix-product inputs with float32
accumulation; the residual stream, norms, rotary angles, softmax, the gates'
sigmoids in float32; router logits and the choice of experts in float32 at
``highest`` precision; the pre-convolution rows are rounded to bfloat16 once
(what the pool carries), the convolution, ``g``, ``beta``, the L2 norms, the
delta rule's state, its chunk-local triangular solve and every product inside
the scan in float32 (``SCAN_PRECISION``).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Mapping, Tuple

import jax
import jax.numpy as jnp

from .looped import _head, _mm, _mm_t, _rope, _sample, token_stats
from .moe import _attend_prompt, _attend_rows, _load_stats, expert_load, grouped_experts, prompt_attention, route

__all__ = ["HybridConfig", "delta_scan", "forward", "init_params", "slot_prefill", "slot_step", "snapshot_positions"]

FAMILY = "hybrid"
CHUNK = 64  # tokens the scan takes at a time: its triangular system is CHUNK x CHUNK
SCAN_PRECISION = jax.lax.Precision.HIGHEST  # the delta rule's products are float32 by float32


def _refuse(what: str) -> ValueError:
    return ValueError(f"{what} for the {FAMILY} family")


@dataclass(frozen=True)
class HybridConfig:
    """The architecture, under its published keys, and what of it this chip
    holds (``experts_held``; ``vocab_size`` is the slice); the short names
    are what the decode engine and the HBM ledger read off any generator's
    ``config``."""

    vocab_size: int
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    full_attention_interval: int
    linear_conv_kernel_dim: int
    linear_key_head_dim: int
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_value_head_dim: int
    num_hidden_layers: int
    experts_held: Tuple[int, int]
    partial_rotary_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    max_position_embeddings: int = 2048
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_architecture(cls, arch: Mapping[str, Any], dtype=jnp.bfloat16) -> "HybridConfig":
        """Read a published ``config.json`` (plus ``experts_held``, the range
        of routed experts built here); what this family cannot run is refused
        here, by name, and never approximated."""
        if arch.get("rope_scaling") is not None:
            raise _refuse(f"rope_scaling={arch['rope_scaling']!r}: scaled rotary positions are not implemented")
        if arch.get("tie_word_embeddings"):
            raise _refuse("tie_word_embeddings: the output head is untied")
        if not arch.get("norm_topk_prob", True):
            raise _refuse("norm_topk_prob=false: the chosen experts' weights are renormalised among themselves")
        if arch.get("mlp_only_layers"):
            raise _refuse(f"mlp_only_layers={list(arch['mlp_only_layers'])}: every layer has routed experts")
        if int(arch.get("decoder_sparse_step", 1)) != 1:
            raise _refuse(f"decoder_sparse_step={arch['decoder_sparse_step']}: every layer has routed experts")
        if arch.get("use_sliding_window") or arch.get("sliding_window"):
            raise _refuse("use_sliding_window: the attention layers are full")
        if arch.get("hidden_act", "silu") != "silu":
            raise _refuse(f"hidden_act={arch['hidden_act']!r}: the experts are gated SiLU")
        layers, interval = int(arch["num_hidden_layers"]), int(arch["full_attention_interval"])
        if interval < 2 or layers % interval:
            raise _refuse(f"num_hidden_layers={layers} is not whole periods of full_attention_interval={interval}")
        heads, kv_heads = int(arch["num_attention_heads"]), int(arch.get("num_key_value_heads") or arch["num_attention_heads"])
        if heads % kv_heads:
            raise _refuse(f"num_attention_heads={heads} is not a multiple of num_key_value_heads={kv_heads}")
        k_heads, v_heads = int(arch["linear_num_key_heads"]), int(arch["linear_num_value_heads"])
        if v_heads % k_heads:
            raise _refuse(f"linear_num_value_heads={v_heads} is not a multiple of linear_num_key_heads={k_heads}")
        experts, active = int(arch["num_experts"]), int(arch["num_experts_per_tok"])
        if not 1 <= active <= experts:
            raise _refuse(f"num_experts_per_tok={active} of num_experts={experts}")
        lo, hi = (int(x) for x in arch.get("experts_held") or (0, experts))
        if not 0 <= lo < hi <= experts:
            raise _refuse(f"experts_held=[{lo}, {hi}) of num_experts={experts}")
        hidden = int(arch["hidden_size"])
        head_dim = int(arch.get("head_dim") or hidden // heads)
        rotary = float(arch.get("partial_rotary_factor", 1.0))
        if int(head_dim * rotary) % 2 or not 0 < rotary <= 1:
            raise _refuse(f"partial_rotary_factor={rotary} of head_dim={head_dim}: the rotated dims come in pairs")
        return cls(
            vocab_size=int(arch["vocab_size"]), hidden_size=hidden, num_attention_heads=heads, num_key_value_heads=kv_heads,
            head_dim=head_dim, num_experts=experts, num_experts_per_tok=active,
            moe_intermediate_size=int(arch["moe_intermediate_size"]),
            shared_expert_intermediate_size=int(arch["shared_expert_intermediate_size"]),
            full_attention_interval=interval, linear_conv_kernel_dim=int(arch["linear_conv_kernel_dim"]),
            linear_key_head_dim=int(arch["linear_key_head_dim"]), linear_num_key_heads=k_heads,
            linear_num_value_heads=v_heads, linear_value_head_dim=int(arch["linear_value_head_dim"]),
            num_hidden_layers=layers, experts_held=(lo, hi), partial_rotary_factor=rotary,
            rms_norm_eps=float(arch.get("rms_norm_eps", 1e-6)), rope_theta=float(arch.get("rope_theta", 10000.0)),
            max_position_embeddings=int(arch.get("max_position_embeddings", 2048)), dtype=dtype,
        )

    d_model = property(lambda self: self.hidden_size)
    n_heads = property(lambda self: self.num_attention_heads)
    n_kv_heads = property(lambda self: self.num_key_value_heads)
    n_layers = property(lambda self: self.num_hidden_layers)
    max_len = property(lambda self: self.max_position_embeddings)
    total_ut_steps = property(lambda self: 1)
    period = property(lambda self: self.full_attention_interval)
    n_full = property(lambda self: self.num_hidden_layers // self.full_attention_interval)
    n_linear = property(lambda self: self.num_hidden_layers - self.n_full)
    cache_depth = property(lambda self: self.n_full)  # layers that keep a row a token
    n_experts = property(lambda self: self.num_experts)
    experts_per_token = property(lambda self: self.num_experts_per_tok)
    key_dim = property(lambda self: self.linear_num_key_heads * self.linear_key_head_dim)
    value_dim = property(lambda self: self.linear_num_value_heads * self.linear_value_head_dim)
    conv_channels = property(lambda self: 2 * self.key_dim + self.value_dim)

    def pool_layout(self, T: int) -> Tuple[Tuple[str, int, int], ...]:
        """The slot pool's kinds of rows as ``(kind, layers, rows a layer)``:
        only the full layers keep rows."""
        return (("full", self.n_full, T),)

    def state_layout(self) -> Tuple[Tuple[str, int, Tuple[int, ...], Any], ...]:
        """What a slot holds whatever its length, as ``(kind, layers, shape a
        layer, dtype)``: the delta rule's state and the convolution's rows."""
        return (
            ("delta", self.n_linear, (self.linear_num_value_heads, self.linear_key_head_dim, self.linear_value_head_dim), jnp.float32),
            ("conv", self.n_linear, (self.linear_conv_kernel_dim - 1, self.conv_channels), self.dtype),
        )


Config = HybridConfig


def init_params(cfg: HybridConfig, seed: int, scale: float = 0.02) -> Dict[str, Any]:
    """Seeded random weights in the family's tree: matrices normal(0,
    ``scale``) in ``cfg.dtype``, projections kept ``[out, hidden]`` as the
    other families'; under ``layers`` the groups ``every`` (stacked over all
    layers), ``linear`` and ``full`` (over the layers of that kind) and the
    held experts ``wg wu [held, D, F]``, ``wd [held, F, D]``, one array a
    layer in a tuple.  Zero-centred norm weights normal(0, 0.1), the delta
    rule's output norm 1 + normal(0, 0.1), convolution taps normal(0, 0.5),
    ``A_log`` uniform over ``log`` of decays of 1/2048 to 1/16 a token,
    ``dt_bias`` zero, all float32."""
    D, V, Ly = cfg.hidden_size, cfg.vocab_size, cfg.num_hidden_layers
    F, Fs, E = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size, cfg.num_experts
    held = cfg.experts_held[1] - cfg.experts_held[0]
    H, Hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    nf, nl, Hv = cfg.n_full, cfg.n_linear, cfg.linear_num_value_heads
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 40 + 3 * Ly))

    def mat(*shape, deviation=scale, dtype=cfg.dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32) * deviation).astype(dtype)

    def norm(*shape, centre=0.0):
        return centre + 0.1 * jax.random.normal(next(keys), shape, jnp.float32)

    return {
        "embed": mat(V, D),
        "head": mat(D, V),
        "final_norm": norm(D),
        "layers": {
            "every": {
                "in_norm": norm(Ly, D), "post_norm": norm(Ly, D), "router": mat(Ly, D, E),
                "shared_wg": mat(Ly, D, Fs), "shared_wu": mat(Ly, D, Fs), "shared_wd": mat(Ly, Fs, D),
                "shared_gate": mat(Ly, D),
            },
            "full": {
                "wq": mat(nf, H * 2 * hd, D), "wk": mat(nf, Hkv * hd, D), "wv": mat(nf, Hkv * hd, D), "wo": mat(nf, H * hd, D),
                "q_norm": norm(nf, hd), "k_norm": norm(nf, hd),
            },
            "linear": {
                "wqkvz": mat(nl, cfg.conv_channels + cfg.value_dim, D), "wba": mat(nl, 2 * Hv, D),
                "conv": mat(nl, cfg.linear_conv_kernel_dim, cfg.conv_channels, deviation=0.5, dtype=jnp.float32),
                "A_log": jax.random.uniform(next(keys), (nl, Hv), jnp.float32, jnp.log(1 / 2048.0), jnp.log(1 / 16.0)),
                "dt_bias": jnp.zeros((nl, Hv), jnp.float32),
                "o_norm": norm(nl, cfg.linear_value_head_dim, centre=1.0), "wout": mat(nl, cfg.value_dim, D),
            },
            "wg": tuple(mat(held, D, F) for _ in range(Ly)), "wu": tuple(mat(held, D, F) for _ in range(Ly)),
            "wd": tuple(mat(held, F, D) for _ in range(Ly)),
        },
    }


_EXPERT_LEAVES = ("wg", "wu", "wd")


# ---------------------------------------------------------------------------
# the pieces
# ---------------------------------------------------------------------------


def _rms0(x, w, eps):
    """RMSNorm with a zero-centred weight."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _partial_rope(cfg: HybridConfig, x, pos):
    """Rotate-half on the first ``head_dim * partial_rotary_factor`` dims of ``x [B, L, H, hd]``, the rest as they are."""
    r = int(cfg.head_dim * cfg.partial_rotary_factor)
    return jnp.concatenate([_rope(x[..., :r], pos, cfg.rope_theta), x[..., r:]], axis=-1)


def _full_mixer(cfg: HybridConfig, w, a, q_pos, attend):
    """The gated attention of one full layer on the normed state ``a [B, L,
    D]``; ``attend(q, k, v)`` files the keys and values and returns the
    attention's result ``[B, L, H, hd]`` (``q`` float32: the attention rounds
    it).  Returns the mixer's output and the layer's keys and values."""
    B, L, _ = a.shape
    H, Hkv, hd, eps = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.rms_norm_eps
    qg = _mm_t(a, w["wq"]).reshape(B, L, H, 2, hd)  # per head a query and an output gate
    q = _partial_rope(cfg, _rms0(qg[:, :, :, 0], w["q_norm"], eps), q_pos)
    k = _partial_rope(cfg, _rms0(_mm_t(a, w["wk"]).reshape(B, L, Hkv, hd), w["k_norm"], eps), q_pos).astype(cfg.dtype)
    v = _mm_t(a, w["wv"]).reshape(B, L, Hkv, hd).astype(cfg.dtype)
    o = attend(q, k, v).astype(jnp.float32) * jax.nn.sigmoid(qg[:, :, :, 1])
    return _mm(o.reshape(B, L, H * hd), w["wo"]), (k, v)


def _project(cfg: HybridConfig, w, a):
    """A delta layer's projections of ``a [..., D]``: the pre-convolution
    rows (rounded to the pool's dtype, once), ``z``, ``beta`` and ``g``."""
    C, Hv = cfg.conv_channels, cfg.linear_num_value_heads
    y = _mm_t(a, w["wqkvz"])
    ba = _mm_t(a, w["wba"])
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[..., Hv:] + w["dt_bias"])
    return y[..., :C].astype(cfg.dtype), y[..., C:], jax.nn.sigmoid(ba[..., :Hv]), g


def _qkv(cfg: HybridConfig, c):
    """The convolution's result ``c [..., channels]`` (float32, after SiLU)
    as the delta rule's ``q k [..., Hv, dk]`` and ``v [..., Hv, dv]``."""
    Kd, Hk, Hv, dk = cfg.key_dim, cfg.linear_num_key_heads, cfg.linear_num_value_heads, cfg.linear_key_head_dim
    lead = c.shape[:-1]

    def unit(x):
        x = x.reshape(lead + (Hk, dk))
        return jnp.repeat(x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6), Hv // Hk, axis=-2)

    return unit(c[..., :Kd]) * dk ** -0.5, unit(c[..., Kd : 2 * Kd]), c[..., 2 * Kd :].reshape(lead + (Hv, cfg.linear_value_head_dim))


def _gated_out(cfg: HybridConfig, w, o, z):
    """``o [..., Hv, dv]`` normed per head, gated by ``silu(z)`` and projected out."""
    n = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_norm_eps) * w["o_norm"]
    return _mm((n * jax.nn.silu(z.reshape(o.shape))).reshape(o.shape[:-2] + (cfg.value_dim,)), w["wout"])


def _dot(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=SCAN_PRECISION, preferred_element_type=jnp.float32)


def delta_scan(q, k, v, beta, g, S0, marks: Tuple[int, ...] = ()):
    """The gated delta rule over ``L`` tokens (a multiple of ``CHUNK``) from
    the state ``S0 [B, H, dk, dv]``: ``q k [B, L, H, dk]``, ``v [B, L, H,
    dv]``, ``beta g [B, L, H]``, all float32.  Returns ``o [B, L, H, dv]``,
    the state after the last token and the state before each token of
    ``marks`` (ascending multiples of ``CHUNK`` inside ``(0, L)``).

    Within a chunk, with ``c`` the running sum of ``g``: the updates ``u``
    solve ``(I + A) U = beta V - (beta K e^c) S`` with ``A[i, j] = beta_i
    (k_i . k_j) e^(c_i - c_j)`` for ``j < i``, a unit lower-triangular
    system; solved once for both right-hand sides, the state only enters
    through products."""
    with jax.named_scope("delta_scan"):  # the name the compiled program's operations carry
        return _delta_scan(q, k, v, beta, g, S0, marks)


def _delta_scan(q, k, v, beta, g, S0, marks: Tuple[int, ...]):
    B, L, H, dk = q.shape
    N = L // CHUNK

    def chunks(x):  # [B, L, H, ...] -> [N, B, H, CHUNK, ...]
        x = x.reshape((B, N, CHUNK, H) + x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, beta, g = (chunks(x) for x in (q, k, v, beta, g))
    c = jnp.cumsum(g, axis=-1)  # [N, B, H, C]
    lower = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
    decay = jnp.exp(jnp.where(lower, c[..., :, None] - c[..., None, :], -jnp.inf))  # e^(c_i - c_j) for j <= i, else 0
    kb = k * beta[..., None]
    A = _dot("nbhid,nbhjd->nbhij", kb, k) * jnp.where(jnp.eye(CHUNK, dtype=bool), 0.0, decay)
    rhs = jnp.concatenate([v * beta[..., None], kb * jnp.exp(c)[..., None]], axis=-1)
    solved = jax.lax.linalg.triangular_solve(A + jnp.eye(CHUNK), rhs, left_side=True, lower=True, unit_diagonal=True)
    u, w = solved[..., : v.shape[-1]], solved[..., v.shape[-1] :]
    qk = _dot("nbhid,nbhjd->nbhij", q, k) * decay
    q_in = q * jnp.exp(c)[..., None]
    k_out = k * jnp.exp(c[..., -1:] - c)[..., None]
    last = jnp.exp(c[..., -1])

    def one(S, xs):
        u, w, qk, q_in, k_out, last = xs
        new = u - _dot("bhik,bhkv->bhiv", w, S)
        o = _dot("bhik,bhkv->bhiv", q_in, S) + _dot("bhij,bhjv->bhiv", qk, new)
        return S * last[..., None, None] + _dot("bhik,bhiv->bhkv", k_out, new), o

    xs = (u, w, qk, q_in, k_out, last)
    S, outs, states = S0, [], []
    cuts = [m // CHUNK for m in marks]
    for a, b in zip([0] + cuts, cuts + [N]):  # the scan, cut where a state is asked for
        if a:
            states.append(S)
        S, o = jax.lax.scan(one, S, jax.tree_util.tree_map(lambda x: x[a:b], xs))
        outs.append(o)
    o = jnp.concatenate(outs, axis=0)  # [N, B, H, C, dv]
    return jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(B, L, H, -1), S, tuple(states)


def delta_step(q, k, v, beta, g, S):
    """The recurrence, one token a row: ``q k [B, H, dk]``, ``v [B, H, dv]``,
    ``beta g [B, H]``, ``S [B, H, dk, dv]``.  Returns ``o [B, H, dv]`` and the new state."""
    S = S * jnp.exp(g)[..., None, None]
    u = beta[..., None] * (v - jnp.sum(S * k[..., None], axis=-2))
    S = S + k[..., :, None] * u[..., None, :]
    return jnp.sum(S * q[..., None], axis=-2), S


def _conv(cfg: HybridConfig, w, rows, L: int):
    """The depthwise causal convolution and its SiLU over ``rows [B, taps - 1
    + L, channels]``: token ``t`` reads rows ``t .. t + taps - 1``."""
    taps = cfg.linear_conv_kernel_dim
    return jax.nn.silu(sum(rows[:, i : i + L].astype(jnp.float32) * w["conv"][i] for i in range(taps)))


def _delta_prompt(cfg: HybridConfig, w, a, real, n_rel, conv0, S0, marks: Tuple[int, ...]):
    """A delta layer over a prompt's tokens ``a [B, L, D]`` that follow the
    carried rows ``conv0 [B, taps - 1, channels]`` and the state ``S0``;
    ``real [B, L]`` says which are tokens (the rest are padding and leave the
    state alone) and ``n_rel [B]`` how many.  ``marks`` are token offsets in
    ``(0, L)``, multiples of ``CHUNK``.  Returns the mixer's output, the
    state and carried rows after the ``n_rel``-th token, and those before
    each mark."""
    B, L, _ = a.shape
    taps = cfg.linear_conv_kernel_dim
    rows, z, beta, g = _project(cfg, w, a)
    rows = jnp.concatenate([conv0, rows], axis=1)
    q, k, v = _qkv(cfg, _conv(cfg, w, rows, L))
    beta, g = jnp.where(real[..., None], beta, 0.0), jnp.where(real[..., None], g, 0.0)
    pad = -L % CHUNK
    if pad:
        q, k, v, beta, g = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) for x in (q, k, v, beta, g))
    o, S, states = delta_scan(q, k, v, beta, g, S0, marks)
    at = n_rel[:, None] + jnp.arange(taps - 1)[None, :]  # rows index: token t's pre-convolution row is rows[t + taps - 1]
    carried = jnp.take_along_axis(rows, at[:, :, None], axis=1)
    return _gated_out(cfg, w, o[:, :L], z), S, carried, states, tuple(rows[:, m : m + taps - 1] for m in marks)


def _delta_token(cfg: HybridConfig, w, a, live, conv, S):
    """A delta layer over one token a lane, ``a [S, 1, D]``, from the lane's
    carried rows and state; a lane that is not ``live`` keeps both."""
    rows, z, beta, g = _project(cfg, w, a)
    rows = jnp.concatenate([conv, rows], axis=1)
    q, k, v = _qkv(cfg, _conv(cfg, w, rows, 1)[:, 0])
    o, S1 = delta_step(q, k, v, beta[:, 0], g[:, 0], S)
    keep = live[:, None, None]
    return _gated_out(cfg, w, o[:, None], z), jnp.where(keep, rows[:, 1:], conv), jnp.where(keep[..., None], S1, S)


def _experts(cfg: HybridConfig, w, routed, m):
    """The expert branch on the normed state ``m [N, D]``: the held routed
    experts' part (``routed(m, ids, gates)``) and the shared expert, which
    every chip of a layer computes alike.  Returns it, the choices and the
    rows the routed part carried."""
    ids, gates = route(cfg, m, w["router"])
    shared = _mm((jax.nn.silu(_mm(m, w["shared_wg"])) * _mm(m, w["shared_wu"])).astype(cfg.dtype), w["shared_wd"])
    r, carried = routed(m, ids, gates)
    return r + jax.nn.sigmoid(_mm(m, w["shared_gate"]))[:, None] * shared, ids, carried


def _held_experts(cfg: HybridConfig, w, m, ids, gates):
    return grouped_experts(cfg, cfg.experts_held, w, m, ids, gates, act=jax.nn.silu)


def _stack(cfg: HybridConfig, params, ids, q_pos, real, carry, delta, attend, keep: bool = False):
    """Embedding, then every period of layers.  ``delta(carry, d, w, a)``
    runs the ``d``-th delta layer's mixer and ``attend(carry, d, q, k, v)``
    files the ``d``-th full layer's keys and values and attends; both return
    the carry first.  Returns the normed state ``[B, L, D]``, the carry, per
    layer the held experts that got a real token, the busiest one's tokens,
    the pairs that fell to held experts and the rows the expert layer
    carried (``[layers]`` each) and, asked
    for, what ``delta`` returned beside its output, stacked ``[n_linear,
    ...]``, and the full layers' keys and values ``[n_full, B, L, Hkv, hd]``."""
    p, Ly = cfg.period, cfg.num_hidden_layers
    B, L = ids.shape
    layers = params["layers"]
    x = params["embed"][ids].astype(jnp.float32)

    def take(group, l):
        # each layer indexes the flat stack itself, so its slice has one consumer and fuses into its product: a
        # period's slab scanned out for several layers is copied whole through HBM before they read it (ISSUE 37)
        return {n: jax.lax.dynamic_index_in_dim(a, l, keepdims=False) for n, a in layers[group].items()}

    def one_period(c, i):
        x, carry = c
        loads, kept = [], []
        for j in range(p):
            w = take("every", i * p + j)
            a = _rms0(x, w["in_norm"], cfg.rms_norm_eps)
            if j < p - 1:
                d = i * (p - 1) + j
                carry, out, extra = delta(carry, d, take("linear", d), a)
                kept.append(extra)
            else:
                def attend_here(q, k, v):
                    nonlocal carry
                    carry, o = attend(carry, i, q, k, v)
                    return o

                out, kv = _full_mixer(cfg, take("full", i), a, q_pos, attend_here)
            x = x + out
            m = _rms0(x, w["post_norm"], cfg.rms_norm_eps).reshape(B * L, -1)
            # the j-th layer of period i: one branch a period, each holding that layer's experts by reference
            branches = [partial(_held_experts, cfg, {n: layers[n][q * p + j] for n in _EXPERT_LEAVES}) for q in range(Ly // p)]
            f, chosen, carried = _experts(cfg, w, partial(jax.lax.switch, i, branches), m)
            x = x + f.reshape(B, L, -1)
            loads.append(expert_load(cfg, chosen, real.reshape(-1), cfg.experts_held) + (carried,))
        ys = tuple(jnp.stack([load[n] for load in loads]) for n in range(4))
        if keep:
            ys += (jax.tree_util.tree_map(lambda *a: jnp.stack(a), *kept), kv)
        return (x, carry), ys

    (x, carry), ys = jax.lax.scan(one_period, (x, carry), jnp.arange(Ly // p))
    flat = lambda a: a.reshape((-1,) + a.shape[2:])  # noqa: E731 - [periods, layers of a period, ...] -> [layers, ...]
    loads = tuple(flat(a) for a in ys[:4])
    return _rms0(x, params["final_norm"], cfg.rms_norm_eps), carry, loads, (jax.tree_util.tree_map(flat, ys[4]), ys[5]) if keep else ()


def _zero_state(cfg: HybridConfig, B: int):
    return tuple(jnp.zeros((B,) + shape, dtype) for _, _, shape, dtype in cfg.state_layout())


def forward(cfg: HybridConfig, params, ids, query_block: int = 0):
    """Full causal forward of ``ids [B, L]`` with no cache: logits ``[B, L,
    V]`` (float32), the delta layers through the chunked scan from zeros."""
    B, L = ids.shape
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None, :], (B, L))
    how = prompt_attention(cfg, L, B)
    real = jnp.ones((B, L), bool)
    S0, conv0 = _zero_state(cfg, B)

    def delta(carry, d, w, a):
        return carry, _delta_prompt(cfg, w, a, real, jnp.full((B,), L), conv0, S0, ())[0], ()

    def attend(carry, d, q, k, v):
        return carry, _attend_prompt(q, k, v, 0, 0, how, query_block)

    return _head(params, _stack(cfg, params, ids, pos, real, (), delta, attend)[0])


def expert_layer(cfg: HybridConfig, params, l: int, m):
    """One layer's expert branch alone on the normed state ``m [N, D]``: the
    held experts' routed part plus the shared expert.  What the shares of an
    expert axis each compute; they add up to the layer once the shared
    expert, which each of them holds, is counted once."""
    layers = params["layers"]
    w = {n: a[l] for n, a in layers["every"].items()}
    return _experts(cfg, w, partial(_held_experts, cfg, {n: layers[n][l] for n in _EXPERT_LEAVES}), m)[0]


# ---------------------------------------------------------------------------
# the slot pool's programs (serve/decode.py): pool_k = (rows_k [S, n_full, T,
# Hkv, hd], delta [n_linear, S, Hv, dk, dv]), pool_v = (rows_v, conv
# [n_linear, S, taps - 1, channels])
# ---------------------------------------------------------------------------


def snapshot_positions(P: int, L_sfx: int, block: int) -> Tuple[int, ...]:
    """The positions inside a join's suffix ``(P, P + L_sfx)`` whose state
    the join returns: those the prefix tier can split a prompt at (``block *
    2^i``: ``PrefixKVCache.bucket_tokens``) that the scan passes as a chunk
    boundary.  A block of half a chunk is never one, so the position ``block``
    itself carries no state and no join starts there."""
    out, p = [], block
    while block and p < P + L_sfx:
        if p > P and (p - P) % CHUNK == 0:
            out.append(p)
        p *= 2
    return tuple(out)


def slot_prefill(cfg: HybridConfig, S: int, T: int, B: int, L_sfx: int, P: int, block: int = 0) -> Callable:
    """JOIN of ``B`` rows, the families' signature: ``(params, pool_k,
    pool_v, slots [B], suffix_ids [B, L_sfx], n_len [B], prefix_k, prefix_v,
    rngs [B, 2], temps [B]) -> (pool_k, pool_v, first [B], rngs, extra)``.
    ``prefix_k`` / ``prefix_v`` hold per row ``(rows, snapshot)``: the
    prefix tier's blocks of the full layers' keys (values) joined ``[n_full,
    P, Hkv, hd]`` and the delta state (carried rows) at ``P``, ``None`` both
    where ``P`` is 0.  A full layer's keys and values land at
    rows ``[0, P + L_sfx)`` of the slot; a delta layer's scan starts from the
    snapshot (from zeros where ``P`` is 0) and the slot's state and carried
    rows are overwritten whole with what the row's last real token leaves.
    ``extra`` carries, beside the first token's stats and the per-layer
    expert load over real tokens, where the prefix tier's ``block`` is given:
    ``prompt_kv`` (the suffix's keys and values cut into blocks, as the
    sparse-expert family's) and ``prompt_state``: per row and position of
    ``snapshot_positions`` the pair (delta state ``[n_linear, Hv, dk, dv]``,
    carried rows ``[n_linear, taps - 1, channels]``) at that position, which
    stay on the device.  The pools are donated and updated in place."""
    how = prompt_attention(cfg, L_sfx, B, P + L_sfx)
    positions = snapshot_positions(P, L_sfx, block)
    marks = tuple(p - P for p in positions)

    def run(params, pool_k, pool_v, slots, suffix_ids, n_len, prefix_k, prefix_v, rngs, temps):
        pos = jnp.broadcast_to((P + jnp.arange(L_sfx, dtype=jnp.int32))[None, :], (B, L_sfx))
        # a single row is written twice over, as the looped family's join (models/looped.py)
        twice = jnp.arange(B) if B > 1 else jnp.zeros(2, jnp.int32)
        rows = slots[twice]
        real = pos < n_len[:, None]
        if P:  # [n_linear, B, ...] and [n_full, B, P, Hkv, hd]: a layer's restored state and cached rows are one index away
            S0, conv0 = (jnp.stack([row[1] for row in prefix], axis=1) for prefix in (prefix_k, prefix_v))
            cached_k, cached_v = (jnp.stack([row[0] for row in prefix], axis=1) for prefix in (prefix_k, prefix_v))
        else:
            S0, conv0 = (x[None] for x in _zero_state(cfg, B))

        def delta(carry, d, w, a):
            (rows_k, state), (rows_v, conv) = carry
            at = d if P else 0
            out, S1, carried, states, convs = _delta_prompt(
                cfg, w, a, real, n_len - P, jax.lax.dynamic_index_in_dim(conv0, at, keepdims=False),
                jax.lax.dynamic_index_in_dim(S0, at, keepdims=False), marks,
            )
            state = state.at[d, rows].set(S1[twice], mode="promise_in_bounds")
            conv = conv.at[d, rows].set(carried[twice], mode="promise_in_bounds")
            return ((rows_k, state), (rows_v, conv)), out, (states, convs)

        def attend(carry, d, q, k, v):
            (rows_k, state), (rows_v, conv) = carry
            if P:
                k = jnp.concatenate([jax.lax.dynamic_index_in_dim(cached_k, d, keepdims=False), k], axis=1)
                v = jnp.concatenate([jax.lax.dynamic_index_in_dim(cached_v, d, keepdims=False), v], axis=1)
            rows_k = rows_k.at[rows, d, : P + L_sfx].set(k[twice], mode="promise_in_bounds")
            rows_v = rows_v.at[rows, d, : P + L_sfx].set(v[twice], mode="promise_in_bounds")
            return ((rows_k, state), (rows_v, conv)), _attend_prompt(q, k, v, P, 0, how)

        x, (pool_k, pool_v), loads, kept = _stack(
            cfg, params, suffix_ids, pos, real, (pool_k, pool_v), delta, attend, keep=bool(block)
        )
        at = jnp.maximum(n_len - 1 - P, 0)
        logits = _head(params, jnp.take_along_axis(x, at[:, None, None], axis=1)[:, 0])
        rngs, tok = _sample(logits, rngs, temps)
        extra = {**token_stats(logits, tok), **_load_stats(loads)}
        if block:
            (states, convs), kv = kept  # per mark [n_linear, B, ...]; keys and values [n_full, B, L, Hkv, hd]
            extra["prompt_kv"] = tuple(
                tuple(tuple(a[:, b, i * block : (i + 1) * block] for i in range(L_sfx // block)) for b in range(B)) for a in kv
            )
            extra["prompt_state"] = tuple(tuple((s[:, b], c[:, b]) for s, c in zip(states, convs)) for b in range(B))
        return pool_k, pool_v, tok, rngs, extra

    return jax.jit(run, donate_argnums=(1, 2))


def slot_step(cfg: HybridConfig, S: int, T: int, chunk: int) -> Callable:
    """Up to ``chunk`` single-token steps over the whole pool, the families'
    signature and rules (only the first ``n_steps`` run; a lane that is not
    live emits ``-1``).  A live lane writes its token's keys and values at
    row ``min(pos, T - 1)`` of its slot's full layers and attends the rows up
    to ``pos``; its delta layers take one step of the recurrence from the
    slot's state and carried rows and write both back.  A lane that is not
    live leaves its state and carried rows as they are (what it writes into
    its slot's rows lands past every key its occupant will attend, and a join
    rewrites what the next one attends).  ``extra`` carries per step the
    per-layer expert load over live lanes.  The pools are donated and updated
    in place."""
    lanes = jnp.arange(S)

    def run(params, pool_k, pool_v, tok, pos, active, left, rngs, temps, eos, n_steps):
        def step(carry):
            pool_k, pool_v, tok, pos, act, left, rngs = carry
            live = act & (left > 0)
            at = jnp.minimum(pos, T - 1)
            seen = jnp.arange(T)[None, :] <= pos[:, None]

            def delta(c, d, w, a):
                (rows_k, state), (rows_v, conv) = c
                out, carried, S1 = _delta_token(
                    cfg, w, a, live, jax.lax.dynamic_index_in_dim(conv, d, keepdims=False),
                    jax.lax.dynamic_index_in_dim(state, d, keepdims=False),
                )
                state = jax.lax.dynamic_update_index_in_dim(state, S1, d, axis=0)
                conv = jax.lax.dynamic_update_index_in_dim(conv, carried, d, axis=0)
                return ((rows_k, state), (rows_v, conv)), out, ()

            def attend(c, d, q, k, v):
                (rows_k, state), (rows_v, conv) = c
                rows_k = rows_k.at[lanes, d, at].set(k[:, 0], mode="promise_in_bounds")
                rows_v = rows_v.at[lanes, d, at].set(v[:, 0], mode="promise_in_bounds")
                K, V = (jax.lax.dynamic_index_in_dim(x, d, axis=1, keepdims=False) for x in (rows_k, rows_v))
                return ((rows_k, state), (rows_v, conv)), _attend_rows(q.astype(K.dtype), K, V, seen)

            x, (pool_k, pool_v), loads, _ = _stack(
                cfg, params, tok[:, None], pos[:, None], live[:, None], (pool_k, pool_v), delta, attend
            )
            logits = _head(params, x[:, 0])
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

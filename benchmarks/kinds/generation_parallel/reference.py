"""The plain reference of the parallel-block decoder: its equations, nothing else.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: one
full causal forward over prompt + served tokens from token 0, no cache, no
sort, no kernel.  It imports nothing of the program; it reads token ids
(never text) and the weights the benchmark made from the seed, in the tree
``weights.py`` states, casting bfloat16 matrices to float32 as each is used.

For the residual stream ``x`` of one sequence and layer ``l`` (``LN`` =
LayerNorm with the mean removed, its own weight and no bias; no biases
anywhere)::

    a      = LN_in[l](x)                         # the one normed input of the block
    q,k,v  = a Wq^T, a Wk^T, a Wv^T              # 128 query heads over 8 key/value heads of 128
    sliding_attention layer:                     # layer_types[l]
        q, k = rope(q), rope(k)                  # GPT-J: the pairs (2i, 2i+1) turn by p * theta^(-2i/hd)
        seen(i, j) = i - window < j <= i
    full_attention layer: no positions, seen(i, j) = j <= i
    attn   = softmax(q k^T / sqrt(hd), seen) v Wo^T
    s      = sigmoid(a W_router)                 # all num_experts outputs
    top    = the k largest of s ;  g_e = s_e / sum_top s, zero for an expert not chosen
    routed = sum_{e held} g_e (silu(a Wg_e) * (a Wu_e)) Wd_e
    S_i    = (silu(a Wg_si) * (a Wu_si)) Wd_si   # num_shared_experts shared experts
    f      = (routed + sum_i S_i / n) / 2        # "average"
    x      = x + attn + f
    logits = logit_scale * LN_final(x) E^T       # the head is the embedding's table (its slice)

**The share.**  The reference is given what the chip holds and nothing else:
the experts ``experts_held = [lo, hi)`` (the router still chooses among all
``num_experts``; a chosen expert that is absent adds nothing, here as in the
program) and the ``vocab_size`` rows of the vocabulary held.  Each held
expert and each shared expert is applied to every token as an MLP of its
own, in a Python loop, the held ones weighted by their gates (zero where not
chosen).  Attention is computed one key/value group of heads (its queries
made and projected out with it) and ``QUERY_BLOCK`` queries at a time, so
that it fits beside the weights.

Departures from the published modelling code, all stated in the
configuration's ``assumed``: how the shared experts combine with the routed
part (``shared_expert_combination_strategy: average`` read as the mean of the
two halves, the shared half itself the mean of the shared experts); no
expert-selection bias (none is published); the vision tower is left out (the
traffic is text); weights are random.

``precision="fp8"`` is the control: both inputs of every matrix product
rounded to float8 (e4m3, per-tensor scale), the nearest precision below the
bfloat16 the configuration states.  The planted faults, each a program that
forgot one part of the block: ``sequential_block`` (the experts read
``LN_in(x + attn)``: a block made sequential), ``rotate_half`` (the other
rotary layout, the halves ``(i, i + hd/2)`` turned, without the rows'
permutation that would make it the same) and ``window_as_full`` (window
layers attend every earlier key: a pool that forgot the ring).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

FAULTS = (None, "sequential_block", "rotate_half", "window_as_full")
QUERY_BLOCK = 512  # queries attended at a time: scores are [group's heads, QUERY_BLOCK, L], never [heads, L, L]


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


def layer_norm(x, w, eps):
    """LayerNorm with the mean removed, a weight and no bias."""
    import jax.numpy as jnp

    c = x - jnp.mean(x, axis=-1, keepdims=True)
    return c / jnp.sqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps) * w


def rope(x, theta: float, interleaved: bool = True):
    """Rotary embedding of ``x [L, H, hd]`` at positions 0..L-1: GPT-J's pairs
    ``(2i, 2i + 1)``, or (``interleaved=False``, a control) the halves ``(i, i + hd/2)``."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / x.shape[-1]))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if not interleaved:
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def attention(arch: Dict[str, Any], w: Dict[str, Any], a, positions: bool, window: int, precision: str, interleaved: bool):
    """One sequence ``a [L, D]`` through one layer's attention, a key/value
    group of heads (its queries made, attended ``QUERY_BLOCK`` at a time and
    projected out) after another, so that it fits beside the weights."""
    import jax
    import jax.numpy as jnp

    L = a.shape[0]
    H, Hkv, hd = arch["num_attention_heads"], arch["num_key_value_heads"], arch["head_dim"]
    G = H // Hkv
    k = _matmul("ld,ed->le", a, w["wk"], precision).reshape(L, Hkv, hd)
    v = _matmul("ld,ed->le", a, w["wv"], precision).reshape(L, Hkv, hd)
    if positions:
        k = rope(k, arch["rope_theta"], interleaved)
    n_blocks = -(-L // QUERY_BLOCK)
    j = jnp.arange(L)[None, :]

    def group(out, g):
        rows = jax.lax.dynamic_slice_in_dim(w["wq"], g * G * hd, G * hd, axis=0)
        q = _matmul("ld,ed->le", a, rows, precision).reshape(L, G, hd)
        if positions:
            q = rope(q, arch["rope_theta"], interleaved)
        q = jnp.pad(q, ((0, n_blocks * QUERY_BLOCK - L), (0, 0), (0, 0)))
        kg, vg = jax.lax.dynamic_index_in_dim(k, g, axis=1, keepdims=False), jax.lax.dynamic_index_in_dim(v, g, axis=1, keepdims=False)

        def block(at):
            qs = jax.lax.dynamic_slice_in_dim(q, at, QUERY_BLOCK, axis=0)
            s = _matmul("qhd,kd->hqk", qs, kg, precision) / np.sqrt(hd)
            i = at + jnp.arange(QUERY_BLOCK)[:, None]
            seen = (j <= i) & (j > i - window) if window else j <= i
            # the block's padding queries past a window see no key: the least float, never -inf, keeps their rows finite
            p = jax.nn.softmax(jnp.where(seen[None], s, jnp.finfo(jnp.float32).min), axis=-1)
            return _matmul("hqk,kd->qhd", p, vg, precision)

        o = jax.lax.map(block, jnp.arange(n_blocks) * QUERY_BLOCK).reshape(n_blocks * QUERY_BLOCK, G * hd)[:L]
        return out + _matmul("le,ed->ld", o, jax.lax.dynamic_slice_in_dim(w["wo"], g * G * hd, G * hd, axis=0), precision), None

    return jax.lax.scan(group, jnp.zeros_like(a), jnp.arange(Hkv))[0]


def route(arch: Dict[str, Any], a, router, precision: str):
    """Gates ``[L, E]`` of one sequence: the chosen experts' sigmoid scores
    over their sum, zero elsewhere (ties as ``lax.top_k``: the lower id first)."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(_matmul("ld,de->le", a, router, precision))
    top, ids = jax.lax.top_k(s, arch["num_experts_per_tok"])
    rows = jnp.arange(a.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, ids].set(top / jnp.sum(top, axis=-1, keepdims=True)), ids


def mlp(wg, wu, wd, a, precision: str):
    """One expert, gated SiLU: ``(silu(a Wg) * (a Wu)) Wd``."""
    import jax

    return _matmul("lf,fd->ld", jax.nn.silu(_matmul("ld,df->lf", a, wg, precision)) * _matmul("ld,df->lf", a, wu, precision), wd, precision)


class Reference:
    """The model from an architecture (the published keys, ``experts_held``
    and the vocabulary's slice) and the weights of that share."""

    def __init__(self, arch: Dict[str, Any], params: Dict[str, Any], precision: str = "f32", fault: Optional[str] = None):
        import jax

        if fault not in FAULTS:
            raise ValueError(f"unknown planted fault {fault!r}")
        self.arch = {**arch, "head_dim": arch.get("head_dim") or arch["hidden_size"] // arch["num_attention_heads"]}
        self.held = tuple(arch.get("experts_held") or (0, arch["num_experts"]))
        self.params, self.precision, self.fault = params, precision, fault
        eps = float(self.arch["layer_norm_eps"])
        interleaved = fault != "rotate_half"
        self._norm = jax.jit(lambda x, w: layer_norm(x, w, eps))
        self._route = jax.jit(lambda a, router: route(self.arch, a, router, precision))
        self._attend = jax.jit(
            lambda w, a, positions, window: attention(self.arch, w, a, positions, window, precision, interleaved), static_argnums=(2, 3))
        self._mlp = jax.jit(lambda wg, wu, wd, a: mlp(wg, wu, wd, a, precision))
        self._logits = jax.jit(lambda x, at, table: _matmul("nd,vd->nv", x[at], table, precision) * float(self.arch.get("logit_scale", 1.0)))

    def experts(self, l: int, a, m=None, only: Optional[Sequence[int]] = None, shared: bool = True):
        """Layer ``l``'s expert branch: the router reads ``a``, the experts
        ``m`` (``a`` in the parallel block); the routed experts ``only`` (ids;
        the held ones by default) and, asked for, the shared ones averaged in.
        Returns it and the experts chosen ``[L, k]``."""
        layers = self.params["layers"]
        m = a if m is None else m
        gates, chosen = self._route(a, layers["router"][l])
        lo, hi = self.held
        routed = 0.0 * m
        for e in range(lo, hi) if only is None else only:  # weights are filed by the held range: expert e is row e - lo
            routed = routed + gates[:, e : e + 1] * self._mlp(*(layers[n][l][e - lo] for n in ("wg", "wu", "wd")), m)
        n, F = self.arch["num_shared_experts"], self.arch["intermediate_size"]
        if not shared:
            return 0.5 * routed, chosen
        together = 0.0 * m
        for i in range(n):
            cut = slice(i * F, (i + 1) * F)
            together = together + self._mlp(layers["shared_wg"][l][:, cut], layers["shared_wu"][l][:, cut], layers["shared_wd"][l][cut], m)
        return 0.5 * (routed + together / n), chosen

    def layer(self, l: int, x):
        """Layer ``l`` applied to one sequence ``x [L, D]``; returns the new state and the experts chosen ``[L, k]``."""
        arch, layers = self.arch, self.params["layers"]
        a = self._norm(x, layers["in_norm"][l])
        sliding = arch["layer_types"][l] == "sliding_attention"
        window = int(arch["sliding_window"]) if sliding and self.fault != "window_as_full" else 0
        attn = self._attend({n: layers[n][l] for n in ("wq", "wk", "wv", "wo")}, a, sliding, window)
        if self.fault == "sequential_block":
            f, chosen = self.experts(l, a, self._norm(x + attn, layers["in_norm"][l]))
        else:
            f, chosen = self.experts(l, a)
        return x + attn + f, chosen

    def forward(self, ids: np.ndarray, at: np.ndarray):
        """Full forward of one sequence ``ids [L]``: logits ``[N, V]`` at
        positions ``at [N]`` and every layer's choice of experts ``[layers, L, k]``."""
        import jax.numpy as jnp

        x = self.params["embed"][jnp.asarray(ids)].astype(jnp.float32)
        choices: List[Any] = []
        for l in range(self.arch["num_hidden_layers"]):
            x, chosen = self.layer(l, x)
            choices.append(np.asarray(chosen))
        x = self._norm(x, self.params["final_norm"])
        return np.asarray(self._logits(x, jnp.asarray(at), self.params["embed"])), np.stack(choices)

    def score(self, sequences: Sequence[Sequence[int]], n_prompt: Sequence[int], pad_to: int = 128):
        """Per sequence (prompt + emitted ids), the float32 logits ``[n_emit,
        V]`` the model gives at the positions its emitted tokens were chosen
        from (position ``n_prompt - 1 + j`` chose emitted token ``j``), and
        the experts every layer chose for its tokens ``[layers, len, k]``.  One
        sequence a call, all right-padded to the longest (a causal model never
        looks right) and asked for as many positions, so that every call has
        one shape and the programs compile once."""
        L = -(-max(len(s) for s in sequences) // pad_to) * pad_to
        n_emit = [len(s) - n for s, n in zip(sequences, n_prompt)]
        logits: List[np.ndarray] = []
        choices: List[np.ndarray] = []
        for seq, n, m in zip(sequences, n_prompt, n_emit):
            ids = np.zeros(L, np.int32)
            ids[: len(seq)] = seq
            lg, chosen = self.forward(ids, np.minimum(n - 1 + np.arange(max(n_emit)), L - 1))
            logits.append(lg[:m])
            choices.append(chosen[:, : len(seq)])
        return logits, choices

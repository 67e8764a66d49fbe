"""The plain reference of the sparse-expert decoder: its equations, nothing else.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: one
full causal forward over prompt + served tokens, no cache, no scan, no sort,
no kernel.  It imports nothing of the program; it reads token ids (never
text) and the weights the benchmark made from the seed, in the tree
``weights.py`` states, casting bfloat16 matrices to float32 as each is used.

For the residual stream ``x`` of one sequence and layer ``l`` (``R*`` =
RMSNorm with its own weight, no biases anywhere)::

    a      = R_in[l](x)
    r      = a W_router[l]                      # read off the layer's normed input, before attention
    top    = the k largest of r                 # moe_num_active_primary_experts of moe_num_primary_experts
    g      = softmax(r[top])                    # zero for every expert not chosen
    q,k,v  = a Wq[l]^T, a Wk[l]^T, a Wv[l]^T    # num_attention_heads / num_key_value_heads heads of head_dim
    if rope_layout[l]: q, k = rope(q), rope(k)  # rotate-half, rope_theta; else no positions at all
    seen(i, j) = j <= i  and, if sliding_window_layout[l],  j > i - sliding_window_size
    x      = x + softmax(q k^T / sqrt(head_dim), seen) v Wo[l]
    m      = R_post[l](x)
    x      = x + sum_e g[e] * (relu(m Wg[l,e]) * (m Wu[l,e])) Wd[l,e]
    logits = R_final(x) W_head

Every expert is applied to every token, in a Python loop, and weighted by
``g`` (zero where not chosen).  Computed in blocks so that it fits beside the
weights: one sequence, one key/value group of heads, one expert at a time.

Departures from the published model, all stated in the configuration's
``assumed``: the router reads ``a`` and the experts ``m``, ReLU gating, no
query/key norm and no biases follow ``described_as`` and arXiv 2507.20984,
not keys of ``config.json``; weights are random.

``precision="fp8"`` is the control: the same mathematics with both inputs of
every matrix product rounded to float8 (e4m3, per-tensor scale), the nearest
precision below the bfloat16 the configuration states.
``fault="window_as_full"`` is the planted fault the check must catch: window
layers attend every earlier key (what a pool that forgot the ring serves).
``router_input="bf16"`` is a reading, not a control: the router's input
rounded to bfloat16 and nothing else, to tell what a flipped choice of
experts does to a logit from what rounding does.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

FAULTS = (None, "window_as_full")


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
    """Rotate-half rotary embedding of ``x [L, H, hd]`` at positions 0..L-1."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang), b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def route(arch: Dict[str, Any], a, router, precision: str):
    """Gates ``[L, E]`` of one sequence: the softmax over the chosen experts'
    logits, zero elsewhere (ties as ``lax.top_k``: the lower id first)."""
    import jax
    import jax.numpy as jnp

    r = _matmul("ld,de->le", a, router, precision)
    top, ids = jax.lax.top_k(r, arch["moe_num_active_primary_experts"])
    g = jax.nn.softmax(top, axis=-1)
    rows = jnp.arange(a.shape[0])[:, None]
    return jnp.zeros_like(r).at[rows, ids].set(g), ids


def attention(arch: Dict[str, Any], w: Dict[str, Any], a, rope: bool, window: int, precision: str):
    """One sequence ``a [L, D]`` through one layer's attention, a key/value
    group of heads at a time."""
    import jax
    import jax.numpy as jnp

    L = a.shape[0]
    H, Hkv, hd = arch["num_attention_heads"], arch["num_key_value_heads"], arch["head_dim"]
    q = _matmul("ld,ed->le", a, w["wq"], precision).reshape(L, H, hd)
    k = _matmul("ld,ed->le", a, w["wk"], precision).reshape(L, Hkv, hd)
    v = _matmul("ld,ed->le", a, w["wv"], precision).reshape(L, Hkv, hd)
    if rope:
        q, k = _rope(q, arch["rope_theta"]), _rope(k, arch["rope_theta"])
    i, j = jnp.arange(L)[:, None], jnp.arange(L)[None, :]
    seen = j <= i
    if window:
        seen &= j > i - window
    out = []
    for g in range(Hkv):
        qs = q[:, g * (H // Hkv) : (g + 1) * (H // Hkv)]
        s = _matmul("qhd,kd->hqk", qs, k[:, g], precision) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        out.append(_matmul("hqk,kd->qhd", p, v[:, g], precision))
    o = jnp.concatenate(out, axis=1).reshape(L, H * hd)
    return _matmul("le,ed->ld", o, w["wo"], precision)


def expert(w: Dict[str, Any], m, gate, precision: str):
    """One expert's part of the layer: ``gate [L]`` times its gated ReLU."""
    import jax

    h = jax.nn.relu(_matmul("ld,df->lf", m, w["wg"], precision)) * _matmul("ld,df->lf", m, w["wu"], precision)
    return gate[:, None] * _matmul("lf,fd->ld", h, w["wd"], precision)


class Reference:
    """The model from an architecture (the published keys) and the weights."""

    def __init__(self, arch: Dict[str, Any], params: Dict[str, Any], precision: str = "f32",
                 fault: Optional[str] = None, router_input: str = "f32"):
        import jax

        if fault not in FAULTS:
            raise ValueError(f"unknown planted fault {fault!r}")
        self.arch = {**arch, "head_dim": arch.get("head_dim") or arch["hidden_size"] // arch["num_attention_heads"]}
        self.params, self.precision, self.fault, self.router_input = params, precision, fault, router_input
        self._norm = jax.jit(lambda x, w: _rms(x, w, self.arch["rms_norm_eps"]))
        self._route = jax.jit(lambda a, router: route(self.arch, a, router, precision))
        self._attend = jax.jit(
            lambda w, a, rope, window: attention(self.arch, w, a, rope, window, precision), static_argnums=(2, 3))
        self._expert = jax.jit(lambda w, m, gate: expert(w, m, gate, precision))
        self._logits = jax.jit(lambda x, at, head: _matmul("nd,dv->nv", x[at], head, precision))

    def layer(self, l: int, x, only: Optional[Sequence[int]] = None):
        """Layer ``l`` applied to one sequence ``x [L, D]``; returns the new
        state and the experts chosen ``[L, k]``.  ``only`` keeps those
        experts' part of the expert branch (a share of the layer)."""
        import jax.numpy as jnp

        arch, w = self.arch, {n: a[l] for n, a in self.params["layers"].items()}
        a = self._norm(x, w["in_norm"])
        routed = a.astype(jnp.bfloat16).astype(jnp.float32) if self.router_input == "bf16" else a
        gates, chosen = self._route(routed, w["router"])
        window = arch["sliding_window_size"] if arch["sliding_window_layout"][l] and self.fault != "window_as_full" else 0
        x = x + self._attend({n: w[n] for n in ("wq", "wk", "wv", "wo")}, a, bool(arch["rope_layout"][l]), int(window))
        m = self._norm(x, w["post_norm"])
        for e in range(arch["moe_num_primary_experts"]) if only is None else only:
            x = x + self._expert({n: w[n][e] for n in ("wg", "wu", "wd")}, m, gates[:, e])
        return x, chosen

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
        return np.asarray(self._logits(x, jnp.asarray(at), self.params["head"])), np.stack(choices)

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

"""The plain reference of the hybrid decoder: its equations, nothing else.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: one
full causal forward over prompt + served tokens from token 0, no cache, no
chunking, no sort, no kernel.  It imports nothing of the program; it reads
token ids (never text) and the weights the benchmark made from the seed, in
the tree ``weights.py`` states, casting bfloat16 matrices to float32 as each
is used.

Layer ``l`` is full attention where ``(l + 1) mod full_attention_interval ==
0``, else a Gated DeltaNet layer.  ``N*`` is RMSNorm with a zero-centred
weight, ``N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``; no biases.  Every
layer: ``h = x + Mixer(N_in(x))``, ``x' = h + Experts(N_post(h))``::

    # full attention: H query heads over Hkv key/value heads of head_dim
    [q, gate] = a Wq                       # per head a query and an output gate
    k, v      = a Wk, a Wv
    q, k      = Nq(q), Nk(k)               # per head
    q, k      = rope(q), rope(k)           # rotate-half on the first head_dim * partial_rotary_factor dims only
    o         = softmax(q k^T / sqrt(head_dim), j <= i) v
    Mixer     = (o * sigmoid(gate)) Wo

    # Gated DeltaNet: Hk key heads x dk, Hv value heads x dv
    [q,k,v,z] = a Wqkvz ;  [b, al] = a Wba
    [q,k,v]   = silu(conv([q,k,v]))        # depthwise, causal: channel c at t reads t-(taps-1)..t, zeros before 0
    q, k      = q/|q|/sqrt(dk), k/|k|      # per head; value head h reads key head h // (Hv / Hk)
    beta      = sigmoid(b) ;  g = -exp(A_log) * softplus(al + dt_bias)
    per value head, S_0 = 0 [dk, dv], for each token t, one after another (lax.scan):
        S = exp(g_t) S ;  u = beta_t (v_t - S^T k_t) ;  S = S + k_t u^T ;  o_t = S^T q_t
    Mixer     = (o / sqrt(mean(o^2) + eps) * w_o * silu(z)) Wout     # per head, a plain weight

    # experts: num_experts routed (num_experts_per_tok a token) and one shared
    p         = softmax(m Wr) ;  top = the k largest ;  g_e = p_e / sum_top p    (= softmax over the k logits)
    routed    = sum_{e in top, e held} g_e (silu(m Wg_e) * (m Wu_e)) Wd_e
    shared    = sigmoid(m w_sg) (silu(m Wg_s) * (m Wu_s)) Wd_s
    logits    = N_final(x) W_head

**The share.**  The reference is given what the chip holds and nothing else:
the experts ``experts_held = [lo, hi)`` (the router still chooses among all
``num_experts``; a chosen expert that is absent adds nothing, here as in the
program) and the ``vocab_size`` rows of the vocabulary that are held.  Each
held expert is applied to every token in a Python loop and weighted by its
gate (zero where not chosen).

Departures from the published modelling code, all stated in the
configuration's ``assumed``: the ``q k v z`` projection's rows are ordered
``[q | k | v | z]`` and the query projection's ``[head, (query, gate),
head_dim]`` (the published code interleaves them per key head: a permutation
of rows of a random matrix); the pre-convolution rows are NOT rounded (the
program rounds them to bfloat16 once: part of what the limits allow); the
multi-token-prediction module is left out; weights are random.

``precision="fp8"`` is the control: both inputs of every matrix product
rounded to float8 (e4m3, per-tensor scale), the nearest precision below the
bfloat16 the configuration states.  ``fault="no_decay"`` is the kind's own
planted fault, which breaks only the new path: the delta rule without its
decay (``g = 0``), what a scan or a restored state that dropped the gate
would serve.  ``fault="early_snapshot"`` with ``restored`` (a position a
sequence) is the other: at that position the state is replaced by what it
was ``early`` tokens before, what a snapshot filed under the wrong block
would serve.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

FAULTS = (None, "no_decay", "early_snapshot")
QUERY_BLOCK = 512  # queries attended at a time: scores are [heads, QUERY_BLOCK, L], never [heads, L, L]
EARLY = 32  # tokens by which the planted early snapshot is early: one block of the prefix tier


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


def rms0(x, w, eps):
    """RMSNorm with a zero-centred weight."""
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def partial_rope(x, theta: float, rotary: int):
    """Rotate-half on the first ``rotary`` dims of ``x [L, H, hd]`` at positions 0..L-1; the rest pass."""
    import jax.numpy as jnp

    half = rotary // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv
    a, b = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang), b * jnp.cos(ang) + a * jnp.sin(ang), x[..., rotary:]], axis=-1)


def gated_attention(arch: Dict[str, Any], w: Dict[str, Any], a, precision: str = "f32", gate: bool = True, rotary: Optional[int] = None):
    """One sequence ``a [L, D]`` through one full layer's mixer, ``QUERY_BLOCK`` queries at a time."""
    import jax
    import jax.numpy as jnp

    L = a.shape[0]
    H, Hkv, hd, eps = arch["num_attention_heads"], arch["num_key_value_heads"], arch["head_dim"], arch["rms_norm_eps"]
    rotary = int(hd * arch["partial_rotary_factor"]) if rotary is None else rotary
    qg = _matmul("ld,ed->le", a, w["wq"], precision).reshape(L, H, 2, hd)
    q = partial_rope(rms0(qg[:, :, 0], w["q_norm"], eps), arch["rope_theta"], rotary)
    k = partial_rope(rms0(_matmul("ld,ed->le", a, w["wk"], precision).reshape(L, Hkv, hd), w["k_norm"], eps), arch["rope_theta"], rotary)
    v = _matmul("ld,ed->le", a, w["wv"], precision).reshape(L, Hkv, hd)
    k, v = jnp.repeat(k, H // Hkv, axis=1), jnp.repeat(v, H // Hkv, axis=1)
    out = []
    for at in range(0, L, QUERY_BLOCK):
        qs = q[at : at + QUERY_BLOCK]
        s = _matmul("qhd,khd->hqk", qs, k, precision) / np.sqrt(hd)
        seen = jnp.arange(L)[None, :] <= (at + jnp.arange(qs.shape[0]))[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        out.append(_matmul("hqk,khd->qhd", p, v, precision))
    o = jnp.concatenate(out, axis=0)
    if gate:
        o = o * jax.nn.sigmoid(qg[:, :, 1])
    return _matmul("le,ed->ld", o.reshape(L, H * hd), w["wo"], precision)


def delta_rule(q, k, v, beta, g, restored=None, early: int = EARLY):
    """The gated delta rule, token by token: ``q k [L, H, dk]``, ``v [L, H,
    dv]``, ``beta g [L, H]`` -> ``o [L, H, dv]``.  ``restored`` (a position,
    or None) plants the early snapshot: there the state becomes what it was
    ``early`` tokens before."""
    import jax
    import jax.numpy as jnp

    L, H, dk = q.shape
    S0 = jnp.zeros((H, dk, v.shape[-1]), jnp.float32)

    def one(carry, x):
        S, kept = carry
        q, k, v, beta, g, t = x
        if restored is not None:
            kept = jnp.where(t == restored - early, S, kept)
            S = jnp.where(t == restored, kept, S)
        S = S * jnp.exp(g)[:, None, None]
        u = beta[:, None] * (v - jnp.sum(S * k[:, :, None], axis=1))
        S = S + k[:, :, None] * u[:, None, :]
        return (S, kept), jnp.sum(S * q[:, :, None], axis=1)

    _, o = jax.lax.scan(one, (S0, S0), (q, k, v, beta, g, jnp.arange(L)))
    return o


def delta_net(arch: Dict[str, Any], w: Dict[str, Any], a, precision: str = "f32", decay: bool = True, restored=None):
    """One sequence ``a [L, D]`` through one Gated DeltaNet layer's mixer."""
    import jax
    import jax.numpy as jnp

    L = a.shape[0]
    Hk, Hv, dk, dv = arch["linear_num_key_heads"], arch["linear_num_value_heads"], arch["linear_key_head_dim"], arch["linear_value_head_dim"]
    taps, Kd, Vd = arch["linear_conv_kernel_dim"], Hk * dk, Hv * dv
    y = _matmul("ld,ed->le", a, w["wqkvz"], precision)
    ba = _matmul("ld,ed->le", a, w["wba"], precision)
    rows = jnp.pad(y[:, : 2 * Kd + Vd], ((taps - 1, 0), (0, 0)))
    c = jax.nn.silu(sum(rows[i : i + L] * w["conv"][i] for i in range(taps)))
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = jnp.repeat(unit(c[:, :Kd].reshape(L, Hk, dk)) / np.sqrt(dk), Hv // Hk, axis=1)
    k = jnp.repeat(unit(c[:, Kd : 2 * Kd].reshape(L, Hk, dk)), Hv // Hk, axis=1)
    v = c[:, 2 * Kd :].reshape(L, Hv, dv)
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[:, Hv:] + w["dt_bias"])
    o = delta_rule(q, k, v, beta, g if decay else jnp.zeros_like(g), restored)
    n = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + arch["rms_norm_eps"]) * w["o_norm"]
    return _matmul("le,ed->ld", (n * jax.nn.silu(y[:, 2 * Kd + Vd :].reshape(L, Hv, dv))).reshape(L, Vd), w["wout"], precision)


def route(arch: Dict[str, Any], m, router, precision: str):
    """Gates ``[L, E]`` of one sequence: the softmax over all the experts'
    logits renormalised over the chosen ones, zero elsewhere (ties as
    ``lax.top_k``: the lower id first)."""
    import jax
    import jax.numpy as jnp

    p = jax.nn.softmax(_matmul("ld,de->le", m, router, precision), axis=-1)
    top, ids = jax.lax.top_k(p, arch["num_experts_per_tok"])
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, ids].set(top / jnp.sum(top, axis=-1, keepdims=True)), ids


def expert(w: Dict[str, Any], m, gate, precision: str):
    """One expert's part of the layer: ``gate [L]`` times its gated SiLU."""
    import jax

    h = jax.nn.silu(_matmul("ld,df->lf", m, w["wg"], precision)) * _matmul("ld,df->lf", m, w["wu"], precision)
    return gate[:, None] * _matmul("lf,fd->ld", h, w["wd"], precision)


def shared_expert(w: Dict[str, Any], m, precision: str):
    import jax

    return expert({"wg": w["shared_wg"], "wu": w["shared_wu"], "wd": w["shared_wd"]}, m,
                  jax.nn.sigmoid(_matmul("ld,d->l", m, w["shared_gate"], precision)), precision)


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
        eps = self.arch["rms_norm_eps"]
        self._norm = jax.jit(lambda x, w: rms0(x, w, eps))
        self._route = jax.jit(lambda m, router: route(self.arch, m, router, precision))
        self._attend = jax.jit(lambda w, a: gated_attention(self.arch, w, a, precision))
        self._delta = jax.jit(lambda w, a, restored: delta_net(self.arch, w, a, precision, fault != "no_decay", restored))
        self._delta_plain = jax.jit(lambda w, a: delta_net(self.arch, w, a, precision, fault != "no_decay"))
        self._expert = jax.jit(lambda w, m, gate: expert(w, m, gate, precision))
        self._shared = jax.jit(lambda w, m: shared_expert(w, m, precision))
        self._logits = jax.jit(lambda x, at, head: _matmul("nd,dv->nv", x[at], head, precision))

    def experts(self, l: int, m, only: Optional[Sequence[int]] = None, shared: bool = True):
        """Layer ``l``'s expert branch on the normed state ``m [L, D]``: the
        routed experts ``only`` (ids; the held ones by default) and, asked
        for, the shared expert.  Returns it and the experts chosen ``[L, k]``."""
        layers = self.params["layers"]
        every = {n: a[l] for n, a in layers["every"].items()}
        gates, chosen = self._route(m, every["router"])
        lo, hi = self.held
        out = self._shared(every, m) if shared else 0.0 * m
        for e in range(lo, hi) if only is None else only:  # weights are filed by the held range: expert e is row e - lo
            out = out + self._expert({n: layers[n][l][e - lo] for n in ("wg", "wu", "wd")}, m, gates[:, e])
        return out, chosen

    def layer(self, l: int, x, restored=None):
        """Layer ``l`` applied to one sequence ``x [L, D]``; returns the new state and the experts chosen ``[L, k]``."""
        import jax.numpy as jnp

        period = self.arch["full_attention_interval"]
        layers = self.params["layers"]
        every = {n: a[l] for n, a in layers["every"].items()}
        a = self._norm(x, every["in_norm"])
        if (l + 1) % period == 0:
            x = x + self._attend({n: w[l // period] for n, w in layers["full"].items()}, a)
        else:
            w = {n: w[l - l // period] for n, w in layers["linear"].items()}
            x = x + (self._delta_plain(w, a) if restored is None else self._delta(w, a, jnp.int32(restored)))
        f, chosen = self.experts(l, self._norm(x, every["post_norm"]))
        return x + f, chosen

    def forward(self, ids: np.ndarray, at: np.ndarray, restored: Optional[int] = None):
        """Full forward of one sequence ``ids [L]``: logits ``[N, V]`` at
        positions ``at [N]`` and every layer's choice of experts ``[layers, L, k]``."""
        import jax.numpy as jnp

        x = self.params["embed"][jnp.asarray(ids)].astype(jnp.float32)
        choices: List[Any] = []
        for l in range(self.arch["num_hidden_layers"]):
            x, chosen = self.layer(l, x, restored if self.fault == "early_snapshot" else None)
            choices.append(np.asarray(chosen))
        x = self._norm(x, self.params["final_norm"])
        return np.asarray(self._logits(x, jnp.asarray(at), self.params["head"])), np.stack(choices)

    def score(self, sequences: Sequence[Sequence[int]], n_prompt: Sequence[int], restored: Optional[Sequence[int]] = None, pad_to: int = 128):
        """Per sequence (prompt + emitted ids), the float32 logits ``[n_emit,
        V]`` the model gives at the positions its emitted tokens were chosen
        from (position ``n_prompt - 1 + j`` chose emitted token ``j``), and
        the experts every layer chose for its tokens ``[layers, len, k]``.  One
        sequence a call, all right-padded to the longest (a causal model never
        looks right) and asked for as many positions, so that every call has
        one shape and the programs compile once.  ``restored``: per sequence
        the position its join started from (0: cold), for the planted early snapshot."""
        L = -(-max(len(s) for s in sequences) // pad_to) * pad_to
        n_emit = [len(s) - n for s, n in zip(sequences, n_prompt)]
        logits: List[np.ndarray] = []
        choices: List[np.ndarray] = []
        for j, (seq, n, m) in enumerate(zip(sequences, n_prompt, n_emit)):
            ids = np.zeros(L, np.int32)
            ids[: len(seq)] = seq
            at = restored[j] if restored is not None and restored[j] else None
            lg, chosen = self.forward(ids, np.minimum(n - 1 + np.arange(max(n_emit)), L - 1), at)
            logits.append(lg[:m])
            choices.append(chosen[:, : len(seq)])
        return logits, choices

"""The hybrid decoder's weights, made on the device from the seed: the held share only.

The tree is the family's (stated here, so that the reference can read it
without the program).  ``embed [V, D]`` and ``head [D, V]`` over the ``V``
rows of the vocabulary this chip holds; ``final_norm [D]``; under ``layers``:

- ``every``, stacked over all ``L`` layers: ``in_norm post_norm [L, D]``,
  ``router [L, D, E]`` (all ``E`` published experts: the router is not cut),
  the shared expert ``shared_wg shared_wu [L, D, Fs]``, ``shared_wd [L, Fs,
  D]`` and its gate ``shared_gate [L, D]``;
- ``full``, stacked over the full-attention layers: ``wq [n, H * 2 * hd,
  D]`` (per head a query and an output gate), ``wk wv [n, Hkv * hd, D]``,
  ``wo [n, H * hd, D]`` (all applied transposed but ``wo``), ``q_norm k_norm
  [n, hd]``;
- ``linear``, stacked over the Gated DeltaNet layers: ``wqkvz [n, 2 Kd + 2
  Vd, D]`` (rows ``[q | k | v | z]``), ``wba [n, 2 Hv, D]`` (``[b | a]``),
  ``conv [n, taps, 2 Kd + Vd]``, ``A_log dt_bias [n, Hv]``, ``o_norm [n,
  dv]``, ``wout [n, Vd, D]``;
- the routed experts HELD HERE, one array a layer in lists of ``L``: ``wg wu
  [held, D, F]`` and ``wd [held, F, D]``: expert ``e`` of ``experts_held =
  [lo, hi)`` is row ``e - lo``.  No absent expert is ever drawn.

Matrices are bfloat16, uniform with standard deviation ``weight_scale``; the
embedding with ``embedding_scale`` (of order 1, SmallThinker's lesson: at the
matrices' 0.02 a token's own embedding drowns in what the mixers add to every
token alike and all tokens choose the same experts).  Norm weights, float32:
the zero-centred ones uniform of deviation 0.1 about 0, the delta rule's
output norm about 1.  The convolution's taps float32 of deviation
``conv_scale`` (about ``1 / sqrt(taps)``: at 0.02 its output would be a
fiftieth of its input).  ``A_log`` uniform over ``log`` of ``decay_per_token
= [lo, hi]``: with ``dt_bias`` 0 and ``softplus`` near 0.7 a head forgets
over 1 / (0.7 A) tokens, here tens to thousands (the published
initialisation, A uniform in (0, 16) and ``dt_bias`` 1, forgets within one
token: a state no snapshot could get wrong).  Drawn with the chip's own
generator (``rbg`` keys), a leaf a call.  The same arrays go to the program
and to the reference.
"""

from __future__ import annotations

from typing import Any, Dict

F32_LEAVES = ("conv", "A_log", "dt_bias")  # beside the norms: float32 leaves


def weight_shapes(arch: Dict[str, Any]) -> Dict[str, Any]:
    D, V, L, E = arch["hidden_size"], arch["vocab_size"], arch["num_hidden_layers"], arch["num_experts"]
    F, Fs = arch["moe_intermediate_size"], arch["shared_expert_intermediate_size"]
    H, Hkv, hd = arch["num_attention_heads"], arch["num_key_value_heads"], arch["head_dim"]
    Hk, Hv, dk, dv = arch["linear_num_key_heads"], arch["linear_num_value_heads"], arch["linear_key_head_dim"], arch["linear_value_head_dim"]
    lo, hi = arch.get("experts_held") or (0, E)
    nf = L // arch["full_attention_interval"]
    nl, Kd, Vd, held = L - nf, Hk * dk, Hv * dv, hi - lo
    return {
        "embed": (V, D), "head": (D, V), "final_norm": (D,),
        "layers": {
            "every": {
                "in_norm": (L, D), "post_norm": (L, D), "router": (L, D, E),
                "shared_wg": (L, D, Fs), "shared_wu": (L, D, Fs), "shared_wd": (L, Fs, D), "shared_gate": (L, D),
            },
            "full": {
                "wq": (nf, H * 2 * hd, D), "wk": (nf, Hkv * hd, D), "wv": (nf, Hkv * hd, D), "wo": (nf, H * hd, D),
                "q_norm": (nf, hd), "k_norm": (nf, hd),
            },
            "linear": {
                "wqkvz": (nl, 2 * Kd + 2 * Vd, D), "wba": (nl, 2 * Hv, D), "conv": (nl, arch["linear_conv_kernel_dim"], 2 * Kd + Vd),
                "A_log": (nl, Hv), "dt_bias": (nl, Hv), "o_norm": (nl, dv), "wout": (nl, Vd, D),
            },
            "wg": ((held, D, F),) * L, "wu": ((held, D, F),) * L, "wd": ((held, F, D),) * L,
        },
    }


def _is_shape(node) -> bool:
    return isinstance(node, tuple) and all(isinstance(n, int) for n in node)


def make_weights(word: int, arch: Dict[str, Any], assumed: Dict[str, Any]):
    import jax
    import jax.numpy as jnp
    import numpy as np

    lo, hi = (float(np.log(x)) for x in assumed["decay_per_token"])

    def build(key, shape, how, deviation):
        x = jax.random.uniform(key, shape, jnp.float32, -(3.0 ** 0.5), 3.0 ** 0.5)  # deviation 1
        if how == "matrix":
            return (x * deviation).astype(jnp.bfloat16)
        if how == "log_decay":  # uniform over [lo, hi]
            return lo + (hi - lo) * (x / (2 * 3.0 ** 0.5) + 0.5)
        return deviation[0] + deviation[1] * x

    build = jax.jit(build, static_argnums=(1, 2, 3))
    leaves, tree = jax.tree_util.tree_flatten_with_path(weight_shapes(arch), is_leaf=_is_shape)
    root = jax.random.key(word, impl="rbg")
    scale = float(assumed["weight_scale"])
    made = []
    for n, (path, shape) in enumerate(leaves):
        name = str(getattr(path[-1], "key", ""))
        if name == "A_log":
            how, deviation = "log_decay", None
        elif name == "dt_bias":
            how, deviation = "float32", (0.0, 0.0)
        elif name == "conv":
            how, deviation = "float32", (0.0, float(assumed["conv_scale"]))
        elif name.endswith("norm"):
            how, deviation = "float32", (1.0 if name == "o_norm" else 0.0, 0.1)
        else:
            how, deviation = "matrix", float(assumed["embedding_scale"]) if name == "embed" else scale
        made.append(build(jax.random.fold_in(root, n), shape, how, deviation))
    return jax.tree_util.tree_unflatten(tree, made)

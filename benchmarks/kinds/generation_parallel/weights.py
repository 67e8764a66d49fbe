"""The parallel-block decoder's weights, made on the device from the seed: the held share only.

The tree is the family's (stated here, so that the reference can read it
without the program).  ``embed [V, D]`` over the ``V`` rows of the vocabulary
this chip holds, which is the head as well (tied); ``final_norm [D]``; under
``layers``, stacked over all ``L`` layers: ``wq wo [L, H * hd, D]``, ``wk wv
[L, Hkv * hd, D]`` (all applied transposed but ``wo``), ``router [L, D, E]``
(all ``E`` published experts: the router is not cut), ``in_norm [L, D]`` (the
block's one LayerNorm), the shared experts side by side ``shared_wg
shared_wu [L, D, n * F]`` and ``shared_wd [L, n * F, D]`` (shared expert
``i`` is columns ``[i F, (i + 1) F)`` of the first two and rows of the
third); and the routed experts HELD HERE, one array a layer in lists of
``L``: ``wg wu [held, D, F]`` and ``wd [held, F, D]``: expert ``e`` of
``experts_held = [lo, hi)`` is row ``e - lo``.  No absent expert is ever drawn.

Matrices are bfloat16, uniform with standard deviation ``weight_scale``, or
the deviation ``leaf_scale`` gives a leaf by name; the embedding with
``embedding_scale``.  Norm weights float32, uniform of deviation 0.1 about 1.
Drawn with the chip's own generator (``rbg`` keys), a leaf a call.  The same
arrays go to the program and to the reference.
"""

from __future__ import annotations

from typing import Any, Dict


def weight_shapes(arch: Dict[str, Any]) -> Dict[str, Any]:
    D, V, L, E = arch["hidden_size"], arch["vocab_size"], arch["num_hidden_layers"], arch["num_experts"]
    F, Fs = arch["intermediate_size"], arch["num_shared_experts"] * arch["intermediate_size"]
    A, Akv = arch["num_attention_heads"] * arch["head_dim"], arch["num_key_value_heads"] * arch["head_dim"]
    lo, hi = arch.get("experts_held") or (0, E)
    return {
        "embed": (V, D), "final_norm": (D,),
        "layers": {
            "wq": (L, A, D), "wk": (L, Akv, D), "wv": (L, Akv, D), "wo": (L, A, D), "router": (L, D, E), "in_norm": (L, D),
            "shared_wg": (L, D, Fs), "shared_wu": (L, D, Fs), "shared_wd": (L, Fs, D),
            "wg": ((hi - lo, D, F),) * L, "wu": ((hi - lo, D, F),) * L, "wd": ((hi - lo, F, D),) * L,
        },
    }


def _is_shape(node) -> bool:
    return isinstance(node, tuple) and all(isinstance(n, int) for n in node)


def make_weights(word: int, arch: Dict[str, Any], assumed: Dict[str, Any]):
    import jax
    import jax.numpy as jnp

    def build(key, shape, norm, deviation):
        x = jax.random.uniform(key, shape, jnp.float32, -(3.0 ** 0.5), 3.0 ** 0.5)  # deviation 1
        return 1.0 + 0.1 * x if norm else (x * deviation).astype(jnp.bfloat16)

    build = jax.jit(build, static_argnums=(1, 2, 3))
    leaves, tree = jax.tree_util.tree_flatten_with_path(weight_shapes(arch), is_leaf=_is_shape)
    root = jax.random.key(word, impl="rbg")
    deviations = {"embed": assumed["embedding_scale"], **(assumed.get("leaf_scale") or {})}
    made = []
    for n, (path, shape) in enumerate(leaves):
        name = [str(p.key) for p in path if isinstance(p, jax.tree_util.DictKey)][-1]  # an expert list's name, not its index
        deviation = float(deviations.get(name, assumed["weight_scale"]))
        made.append(build(jax.random.fold_in(root, n), shape, name.endswith("norm"), deviation))
    return jax.tree_util.tree_unflatten(tree, made)

"""The sparse-expert decoder's weights, made on the device from the seed.

The tree is the family's (stated here, so that the reference can read it
without the program): ``embed [V, D]`` and ``head [D, V]`` and, under
``layers``, stacked over the layers ``wq wo [L, H*hd, D]`` and ``wk wv [L,
Hkv*hd, D]`` (applied transposed, but for ``wo``) and ``router [L, D, E]``;
the experts one array a layer, in lists of ``L``: ``wg wu [E, D, F]`` and
``wd [E, F, D]`` (a grouped product takes one layer's experts whole, and a
slice of a stack would be a copy), all in bfloat16; the norm weights
``in_norm post_norm [L, D]`` and ``final_norm [D]`` in float32.  Matrices
are uniform with standard deviation ``scale``, the embedding with
``embedding_scale`` (of order 1: at the matrices' 0.02 a token's own
embedding is a tenth of what the first attention layer adds to every token
alike, every later state points one way, and all tokens choose the same
experts: one expert took 5,500 of a prompt's 6,752 tokens, my chip run,
ISSUE 32), norm weights 1 + uniform of deviation 0.1, drawn with the chip's
own generator (``rbg`` keys), a leaf a call.  The same arrays go to the
program and to the reference.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def weight_shapes(arch: Dict[str, Any]) -> Dict[str, Any]:
    D, F, V, L = arch["hidden_size"], arch["moe_ffn_hidden_size"], arch["vocab_size"], arch["num_hidden_layers"]
    E = arch["moe_num_primary_experts"]
    A, Akv = arch["num_attention_heads"] * arch["head_dim"], arch["num_key_value_heads"] * arch["head_dim"]
    return {
        "embed": (V, D), "head": (D, V), "final_norm": (D,),
        "layers": {
            "wq": (L, A, D), "wk": (L, Akv, D), "wv": (L, Akv, D), "wo": (L, A, D), "router": (L, D, E),
            "wg": ((E, D, F),) * L, "wu": ((E, D, F),) * L, "wd": ((E, F, D),) * L,
            "in_norm": (L, D), "post_norm": (L, D),
        },
    }


def _is_shape(node) -> bool:
    return isinstance(node, tuple) and all(isinstance(n, int) for n in node)


def make_weights(word: int, arch: Dict[str, Any], scale: float, embedding_scale: Optional[float] = None):
    import jax
    import jax.numpy as jnp

    def build(key, shape, deviation):
        x = jax.random.uniform(key, shape, jnp.float32, -(3.0 ** 0.5), 3.0 ** 0.5)  # deviation 1
        return 1.0 + 0.1 * x if deviation is None else (x * deviation).astype(jnp.bfloat16)

    build = jax.jit(build, static_argnums=(1, 2))
    leaves, tree = jax.tree_util.tree_flatten_with_path(weight_shapes(arch), is_leaf=_is_shape)
    root = jax.random.key(word, impl="rbg")
    made = []
    for n, (path, shape) in enumerate(leaves):
        name = str(getattr(path[-1], "key", ""))
        deviation = None if name.endswith("norm") else (embedding_scale or scale) if name == "embed" else scale
        made.append(build(jax.random.fold_in(root, n), shape, deviation))
    return jax.tree_util.tree_unflatten(tree, made)

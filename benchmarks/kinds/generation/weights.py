"""The looped decoder's weights, made on the device from the seed.

The tree is the family's (stated here, so that the reference can read it
without the program): ``embed [V, D]`` and ``head [D, V]`` and, stacked over
the layers under ``layers``, ``wq wk wv [L, H*hd, D]`` (applied transposed), ``wo [L, H*hd, D]``,
``wg wu [L, D, F]``, ``wd [L, F, D]`` in bfloat16; the norm weights
``in_norm attn_out_norm post_norm mlp_out_norm [L, D]``, ``final_norm [D]``
and the exit gate ``gate_w [D]``, ``gate_b []`` in float32.  Matrices are uniform with
standard deviation ``scale``, norm weights 1 + uniform of deviation 0.1, the
gate uniform of deviation ``scale`` with a zero bias, drawn with the chip's
own generator (``rbg`` keys: a threefry draw of 2.7 billion normals takes
the chip 100 s): the same arrays go to the program and to the reference.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def weight_shapes(arch: Dict[str, Any]) -> Dict[str, Any]:
    D, F, V, L = arch["hidden_size"], arch["intermediate_size"], arch["vocab_size"], arch["num_hidden_layers"]
    A = arch["num_attention_heads"] * arch["head_dim"]
    return {
        "embed": (V, D), "head": (D, V), "final_norm": (D,), "gate_w": (D,), "gate_b": (),
        "layers": {
            "wq": (L, A, D), "wk": (L, A, D), "wv": (L, A, D), "wo": (L, A, D),
            "wg": (L, D, F), "wu": (L, D, F), "wd": (L, F, D),
            "in_norm": (L, D), "attn_out_norm": (L, D), "post_norm": (L, D), "mlp_out_norm": (L, D),
        },
    }


def _leaves(tree: Dict[str, Any], path: Tuple[str, ...] = ()):
    for name in sorted(tree):
        if isinstance(tree[name], dict):
            yield from _leaves(tree[name], path + (name,))
        else:
            yield path + (name,), tree[name]


def make_weights(word: int, arch: Dict[str, Any], scale: float):
    """One jitted draw per leaf (a leaf of the full model is up to 1.1 GB;
    the whole tree in one float32 draw would not fit beside it)."""
    import jax
    import jax.numpy as jnp

    out: Dict[str, Any] = {}
    for n, (path, shape) in enumerate(_leaves(weight_shapes(arch))):
        kind = path[-1]

        def build(key, shape=shape, kind=kind):
            x = jax.random.uniform(key, shape, jnp.float32, -(3.0 ** 0.5), 3.0 ** 0.5)  # deviation 1
            if kind.endswith("norm"):
                return 1.0 + 0.1 * x
            if kind == "gate_b":
                return jnp.zeros(shape, jnp.float32)
            if kind == "gate_w":
                return x * scale
            return (x * scale).astype(jnp.bfloat16)

        node = out
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = jax.jit(build)(jax.random.fold_in(jax.random.key(word, impl="rbg"), n))
    return out

"""Everything a run is fed, made from ``--seed``: weights, index vectors, texts.

Nothing here imports the program.  The weights are made on the device in
one jitted call, in the layout (names and shapes) of a BERT-family trunk as
``configs/<config>.json`` sizes it; the runner hands them to the program and
the reference reads the very same arrays.  Index vectors are made on the
device too, block by block, so that the reference can make any block again
after the window without keeping the matrix.

Texts follow ``chip_smoke.make_corpus`` (a copy, see PERF.md Open
questions): every text draws a topic with a small word pool of its own and
filler from a shared pool, so the embedding space has clusters an IVF can
find.  The index's vectors are unit vectors around the reference embedding
of each topic's canonical text, so queries (text) land among them.

Every seed gets the same multiset of lengths and of arrival gaps, in
another order: a seed changes which request is long, never how much work a
window holds.
"""

from __future__ import annotations

from statistics import NormalDist
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

TOPIC_WORDS = 16
FILLER_WORDS = 2048
TOPIC_SHARE = 0.6


# ---------------------------------------------------------------------------
# seeds
# ---------------------------------------------------------------------------


def seed_words(seed: int, n: int) -> List[int]:
    """``n`` independent 31-bit words from any whole-number seed (the
    driver's seeds pass 2**31, which a 32-bit PRNG key does not hold)."""
    ss = np.random.SeedSequence(int(seed))
    return [int(w) & 0x7FFFFFFF for w in ss.generate_state(n, dtype=np.uint32)]


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), int(stream)]))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def weight_shapes(model: Dict[str, Any], cross: bool) -> Dict[str, Any]:
    """Nested ``{name: shape}`` of a pre-LN BERT-family trunk; with ``cross``
    the trunk sits under ``trunk`` beside a two-layer regression head."""
    d, ff = model["hidden_size"], model["intermediate_size"]
    dense = lambda i, o: {"kernel": (i, o), "bias": (o,)}  # noqa: E731
    ln = {"scale": (d,), "bias": (d,)}
    trunk: Dict[str, Any] = {
        "tok_embed": {"embedding": (model["vocab_size"], d)},
        "pos_embed": {"embedding": (model["max_length"], d)},
        "final_ln": dict(ln),
    }
    for i in range(model["num_hidden_layers"]):
        trunk[f"block_{i}"] = {
            "LayerNorm_0": dict(ln),
            "LayerNorm_1": dict(ln),
            "SelfAttention_0": {
                n: dense(d, d) for n in ("query", "key", "value", "out")
            },
            "MlpBlock_0": {"Dense_0": dense(d, ff), "Dense_1": dense(ff, d)},
        }
    if not cross:
        return trunk
    return {"trunk": trunk, "head_dense": dense(d, d), "head_out": dense(d, 1)}


def _flatten(tree: Dict[str, Any], prefix: Tuple[str, ...] = ()):
    for name in sorted(tree):
        node = tree[name]
        if isinstance(node, dict):
            yield from _flatten(node, prefix + (name,))
        else:
            yield prefix + (name,), node


def make_weights(word: int, model: Dict[str, Any], cross: bool):
    """The whole parameter tree in one jitted call, float32 (the type the
    program keeps parameters in; it computes in bf16)."""
    import jax
    import jax.numpy as jnp

    leaves = list(_flatten(weight_shapes(model, cross)))
    sizes = [int(np.prod(shape)) for _, shape in leaves]

    @jax.jit
    def build(key):
        # one draw for the whole tree, cut into leaves (a draw per leaf
        # compiles a hundred generators)
        flat = jax.random.normal(key, (sum(sizes),), jnp.float32)
        out: Dict[str, Any] = {}
        at = 0
        for (path, shape), n in zip(leaves, sizes):
            x = jax.lax.dynamic_slice_in_dim(flat, at, n).reshape(shape)
            at += n
            kind = path[-1]
            if kind == "kernel":
                x = x * (1.0 / np.sqrt(shape[0]))
            elif kind == "scale":
                x = 1.0 + 0.1 * x
            elif kind == "embedding":
                x = x * (0.05 if path[-2] == "tok_embed" else 0.02)
            else:  # bias
                x = 0.02 * x
            node = out
            for name in path[:-1]:
                node = node.setdefault(name, {})
            node[path[-1]] = x
        return out

    return build(jax.random.PRNGKey(word))


# ---------------------------------------------------------------------------
# lengths and arrivals: one multiset for every seed
# ---------------------------------------------------------------------------


def lognormal_lengths(n: int, mu: float, sigma: float, lo: int, hi: int) -> np.ndarray:
    """``n`` lengths at the evenly spaced quantiles of a log-normal,
    clipped to ``lo..hi``: the same multiset whatever the seed."""
    inv = NormalDist().inv_cdf
    z = np.array([inv((i + 0.5) / n) for i in range(n)])
    return np.clip(np.exp(mu + sigma * z), lo, hi).astype(np.int64)


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` inter-arrival gaps at the evenly spaced quantiles of an
    exponential of the given rate (their mean is 1/rate to 1/n)."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / float(rate)


# ---------------------------------------------------------------------------
# texts
# ---------------------------------------------------------------------------


class Texts:
    """Topic-structured text from integers alone, so that the text of any
    key can be made again (the rerank stage asks for it by key)."""

    def __init__(self, seed: int, n_topics: int):
        self.n_topics = int(n_topics)
        # one 64-bit mixing constant per run keeps texts seed-dependent
        self._salt = seed_words(seed, 3)[2] | 1

    def topic_text(self, t: int) -> str:
        return " ".join(f"t{t}w{j}" for j in range(TOPIC_WORDS))

    def _words(self, t: int, picks: np.ndarray, is_topic: np.ndarray) -> List[str]:
        return [
            f"t{t}w{p % TOPIC_WORDS}" if it else f"f{p % FILLER_WORDS}"
            for p, it in zip(picks.tolist(), is_topic.tolist())
        ]

    def compose(self, lead: str, t: int, n_words: int, rng: np.random.Generator) -> str:
        """``lead`` (a word no other text has) then ``n_words - 1`` words,
        six in ten of topic ``t``'s pool and the rest filler."""
        m = max(int(n_words) - 1, 1)
        picks = rng.integers(0, 1 << 30, m)
        return lead + " " + " ".join(self._words(t, picks, rng.random(m) < TOPIC_SHARE))

    def doc_topic(self, key: int) -> int:
        return int(key) % self.n_topics

    def doc_text(self, key: int) -> str:
        """Text of bulk document ``key`` (its vector is synthetic and lies
        around its topic): made from the key by integer mixing alone."""
        key = int(key)
        t = self.doc_topic(key)
        h = (key * 0x9E3779B97F4A7C15 + self._salt) & 0xFFFFFFFFFFFFFFFF
        n_words = 8 + (h >> 40) % 56  # 8..63 words
        words = [f"doc{key}"]
        for _ in range(n_words - 1):
            h = (h * 6364136223846793005 + 1442695040888963407) & 0xFFFFFFFFFFFFFFFF
            p = h >> 33
            words.append(
                f"t{t}w{p % TOPIC_WORDS}" if (p >> 8) % 10 < 6 else f"f{p % FILLER_WORDS}"
            )
        return " ".join(words)


def make_queries(texts: Texts, seed: int, n: int, spec: Dict[str, Any]) -> List[str]:
    """``n`` distinct single-query texts: lengths from the fixed multiset in
    an order drawn from the seed, topics drawn from the seed."""
    rng = rng_for(seed, 11)
    lengths = lognormal_lengths(n, spec["mu"], spec["sigma"], spec["min"], spec["max"])
    lengths = lengths[rng.permutation(n)]
    topics = rng.integers(0, texts.n_topics, n)
    return [
        texts.compose(f"q{i}", int(topics[i]), int(lengths[i]), rng) for i in range(n)
    ]


def make_live_docs(
    texts: Texts, seed: int, n: int, first_key: int, spec: Dict[str, Any]
) -> List[Tuple[int, str]]:
    """``n`` live documents ``(key, text)`` with keys from ``first_key``."""
    rng = rng_for(seed, 13)
    lengths = lognormal_lengths(n, spec["mu"], spec["sigma"], spec["min"], spec["max"])
    lengths = lengths[rng.permutation(n)]
    topics = rng.integers(0, texts.n_topics, n)
    return [
        (
            first_key + i,
            texts.compose(f"live{first_key + i}", int(topics[i]), int(lengths[i]), rng),
        )
        for i in range(n)
    ]


# ---------------------------------------------------------------------------
# index vectors
# ---------------------------------------------------------------------------


class VectorSpace:
    """Unit vectors around topic centres, in blocks that can be made again.

    Row ``j`` of shard ``s`` is document key ``j * n_shards + s`` and lies
    around the centre of topic ``key % n_topics``; ``noise`` is the spread
    inside a topic as a share of the spread between topic centres."""

    def __init__(
        self,
        word: int,
        centres,  # [n_topics, d] float32 on the device
        n_rows: int,
        block_rows: int,
        n_shards: int,
        noise: float,
    ):
        import jax
        import jax.numpy as jnp

        assert n_rows % block_rows == 0, (n_rows, block_rows)
        self.n_rows, self.block_rows = int(n_rows), int(block_rows)
        self.n_shards, self.n_blocks = int(n_shards), n_rows // block_rows
        self.centres = centres
        n_topics, d = centres.shape
        self.dim = int(d)
        self._sigma = jnp.std(centres, axis=0).mean() * float(noise)
        self._key = jax.random.PRNGKey(word)
        n_blocks = self.n_blocks

        # centres, spread and key are arguments, never constants: one compiled
        # program for every seed
        def block(key0, centres, sigma, shard, b):
            k = jax.random.fold_in(jax.random.fold_in(key0, shard), b)
            j = b * block_rows + jnp.arange(block_rows)
            topic = (j * n_shards + shard) % n_topics
            x = centres[topic] + sigma * jax.random.normal(k, (block_rows, d), jnp.float32)
            return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

        self._block = jax.jit(block)
        self._shard = jax.jit(
            lambda key0, centres, sigma, shard: jax.lax.map(
                lambda b: block(key0, centres, sigma, shard, b), jnp.arange(n_blocks)
            ).reshape(n_rows, d)
        )

    def block(self, shard: int, b: int):
        """Rows ``b*block_rows ..`` of ``shard``: [block_rows, d] float32."""
        return self._block(self._key, self.centres, self._sigma, shard, b)

    def shard_matrix(self, shard: int):
        """All rows of ``shard`` in one jitted call: [n_rows, d] float32."""
        return self._shard(self._key, self.centres, self._sigma, shard)

    def shard_keys(self, shard: int) -> np.ndarray:
        return np.arange(self.n_rows, dtype=np.int64) * self.n_shards + shard

    def locate(self, key: int) -> Tuple[int, int, int]:
        """``(shard, block, row in block)`` of a bulk document key."""
        shard, j = int(key) % self.n_shards, int(key) // self.n_shards
        return shard, j // self.block_rows, j % self.block_rows

    @property
    def n_keys(self) -> int:
        return self.n_rows * self.n_shards

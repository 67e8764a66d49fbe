"""The plain reference: tokenizer, trunk forward, pair scorer, exact search.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernels, no cache, no batching tricks, no packing.  It imports nothing
of the program and takes nothing the program made; it reads the weights
``corpus.make_weights`` made and makes the index vectors again from the
seed, block by block.

The architecture is the one the program serves (``models/transformer.py``),
written down here as equations.  Departures from published BERT, all the
program's: LayerNorm before each sub-layer (pre-LN) plus a final LayerNorm,
tanh-approximated GELU, learned positions without token types, a hashing
word tokenizer in place of WordPiece, mean pooling over real tokens.

``precision="fp8"`` is the control: the same mathematics with both inputs
of every matrix product rounded to float8 (e4m3, per-tensor scale), the
nearest precision below the bf16 the configurations state.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import xxhash

PAD, CLS, SEP = 0, 1, 2
_RESERVED = 8
_WORD_RE = re.compile(r"[\w']+|[^\w\s]")
LN_EPS = 1e-6


# ---------------------------------------------------------------------------
# tokenizer (hash of each lower-cased word into the vocabulary)
# ---------------------------------------------------------------------------


def word_ids(text: str, vocab_size: int) -> List[int]:
    return [
        _RESERVED + xxhash.xxh3_64_intdigest(w.lower().encode()) % (vocab_size - _RESERVED)
        for w in _WORD_RE.findall(str(text))
    ]


def encode_single(text: str, vocab_size: int, max_length: int) -> List[int]:
    return [CLS] + word_ids(text, vocab_size)[: max_length - 2] + [SEP]


def encode_pair(a: str, b: str, vocab_size: int, max_length: int) -> List[int]:
    """``[CLS] a [SEP] b [SEP]``, truncated longest-first to ``max_length``."""
    ta, tb = word_ids(a, vocab_size), word_ids(b, vocab_size)
    budget = max(max_length - 3, 2)
    while len(ta) + len(tb) > budget:
        if len(ta) >= len(tb) and len(ta) > 1:
            ta.pop()
        elif len(tb) > 1:
            tb.pop()
        else:
            break
    return [CLS] + ta + [SEP] + tb + [SEP]


def pad_batch(seqs: Sequence[Sequence[int]], length: int) -> Tuple[np.ndarray, np.ndarray]:
    ids = np.zeros((len(seqs), length), np.int32)
    mask = np.zeros((len(seqs), length), np.int32)
    for i, s in enumerate(seqs):
        s = list(s)[:length]
        ids[i, : len(s)] = s
        mask[i, : len(s)] = 1
    return ids, mask


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------


def _fp8(x):
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _matmul(spec: str, a, b, precision: str):
    import jax
    import jax.numpy as jnp

    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _layer_norm(x, p):
    import jax.numpy as jnp

    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def trunk_forward(params: Dict[str, Any], ids, mask, n_heads: int, precision: str = "f32"):
    """Token ids and mask [B, L] -> mean-pooled final hidden state [B, d]."""
    import jax
    import jax.numpy as jnp

    B, L = ids.shape
    x = params["tok_embed"]["embedding"][ids] + params["pos_embed"]["embedding"][jnp.arange(L)][None]
    d = x.shape[-1]
    hd = d // n_heads
    key_mask = mask[:, None, None, :] > 0
    n_layers = sum(1 for k in params if k.startswith("block_"))
    for i in range(n_layers):
        blk = params[f"block_{i}"]
        att = blk["SelfAttention_0"]
        h = _layer_norm(x, blk["LayerNorm_0"])

        def proj(name, h=h, att=att):
            y = _matmul("bld,de->ble", h, att[name]["kernel"], precision) + att[name]["bias"]
            return y.reshape(B, L, n_heads, hd)

        q, k, v = proj("query"), proj("key"), proj("value")
        s = _matmul("blhd,bmhd->bhlm", q, k, precision) / np.sqrt(hd)
        s = jnp.where(key_mask, s, jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(s, axis=-1)
        o = _matmul("bhlm,bmhd->blhd", p, v, precision).reshape(B, L, d)
        x = x + _matmul("bld,de->ble", o, att["out"]["kernel"], precision) + att["out"]["bias"]
        h = _layer_norm(x, blk["LayerNorm_1"])
        mlp = blk["MlpBlock_0"]
        h = _matmul("bld,df->blf", h, mlp["Dense_0"]["kernel"], precision) + mlp["Dense_0"]["bias"]
        h = jax.nn.gelu(h, approximate=True)
        x = x + _matmul("blf,fd->bld", h, mlp["Dense_1"]["kernel"], precision) + mlp["Dense_1"]["bias"]
    x = _layer_norm(x, params["final_ln"])
    m = mask[:, :, None].astype(jnp.float32)
    return (x * m).sum(1) / jnp.maximum(m.sum(1), 1.0)


def _blocks(n: int, size: int):
    for start in range(0, n, size):
        yield start, min(start + size, n)


class Reference:
    """The reference over one configuration's weights."""

    def __init__(self, model: Dict[str, Any], enc_params, cross_model=None, cross_params=None,
                 precision: str = "f32", rows_per_call: int = 256):
        import jax

        self.model, self.cross_model = model, cross_model
        self.precision = precision
        self.rows = rows_per_call
        heads = model["num_attention_heads"]
        self.enc_params, self.cross_params = enc_params, cross_params
        # weights are arguments, never constants of the program: a compiled
        # reference is then found again in the compile cache whatever the seed
        self._embed = jax.jit(
            lambda params, ids, mask: _unit(trunk_forward(params, ids, mask, heads, precision))
        )
        if cross_params is not None:
            ch = cross_model["num_attention_heads"]

            def score(params, ids, mask):
                import jax.numpy as jnp

                pooled = trunk_forward(params["trunk"], ids, mask, ch, precision)
                hd, ho = params["head_dense"], params["head_out"]
                h = jnp.tanh(_matmul("bd,de->be", pooled, hd["kernel"], precision) + hd["bias"])
                return (_matmul("bd,de->be", h, ho["kernel"], precision) + ho["bias"])[:, 0]

            self._score = jax.jit(score)

    def embed(self, texts: Sequence[str]):
        """Unit embeddings [n, d] float32 on the device, in blocks of rows."""
        import jax.numpy as jnp

        m = self.model
        seqs = [encode_single(t, m["vocab_size"], m["max_length"]) for t in texts]
        out = []
        # one padded length for the whole call: one compiled shape, not one per block
        length = _round_up(max((len(s) for s in seqs), default=2), 16)
        for a, b in _blocks(len(seqs), self.rows):
            part = seqs[a:b]
            ids, mask = pad_batch(part + [[CLS, SEP]] * (self.rows - len(part)), length)
            out.append(self._embed(self.enc_params, ids, mask)[: len(part)])
        d = m["hidden_size"]
        return jnp.concatenate(out) if out else jnp.zeros((0, d), jnp.float32)

    def score_pairs(self, pairs: Sequence[Tuple[str, str]]) -> np.ndarray:
        """Cross-encoder logits [n] float32 for (query, document) pairs."""
        m = self.cross_model
        seqs = [encode_pair(q, d, m["vocab_size"], m["max_length"]) for q, d in pairs]
        out = []
        for a, b in _blocks(len(seqs), self.rows):
            part = seqs[a:b]
            ids, mask = pad_batch(part + [[CLS, SEP]] * (self.rows - len(part)), m["max_length"])
            out.append(np.asarray(self._score(self.cross_params, ids, mask))[: len(part)])
        return np.concatenate(out) if out else np.zeros(0, np.float32)


_BLOCK_SCORES: Dict[int, Any] = {}


def _block_scores(k: int):
    import jax
    import jax.numpy as jnp

    if k not in _BLOCK_SCORES:

        @jax.jit
        def fn(queries, mat):
            sc = jnp.einsum("sd,nd->sn", queries, mat, precision=jax.lax.Precision.HIGHEST)
            top_s, top_i = jax.lax.top_k(sc, k)
            return sc, top_s, top_i

        _BLOCK_SCORES[k] = fn
    return _BLOCK_SCORES[k]


def _unit(x):
    import jax.numpy as jnp

    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


# ---------------------------------------------------------------------------
# exact search
# ---------------------------------------------------------------------------


def exact_search(
    space,
    queries,  # [S, d] unit float32 on the device
    k: int,
    want_keys: Sequence[Sequence[int]],
    live_keys: Optional[np.ndarray] = None,  # [N] int64
    live_vecs=None,  # [N, d] unit float32 on the device
    live_ok: Optional[np.ndarray] = None,  # [S, N] bool: surely visible to query s
):
    """Brute force over every bulk block made again from the seed, plus the
    live documents visible to each query.

    Returns ``(top_scores [S, k], top_keys [S, k], want_scores)`` where
    ``want_scores[s][j]`` is the reference score of ``want_keys[s][j]``
    (NaN for a key that exists nowhere)."""
    import jax
    import jax.numpy as jnp

    S = int(queries.shape[0])
    hi = jax.lax.Precision.HIGHEST
    want_scores = [np.full(len(row), np.nan, np.float32) for row in want_keys]
    per_block: Dict[Tuple[int, int], List[Tuple[int, int, int]]] = {}
    live_pos = {int(key): i for i, key in enumerate(live_keys)} if live_keys is not None else {}
    live_wants: List[Tuple[int, int, int]] = []
    for s, row in enumerate(want_keys):
        for j, key in enumerate(row):
            key = int(key)
            if key in live_pos:
                live_wants.append((s, j, live_pos[key]))
            elif 0 <= key < space.n_keys:
                shard, b, r = space.locate(key)
                per_block.setdefault((shard, b), []).append((s, j, r))

    block_scores = _block_scores(k)

    best_s = np.full((S, 0), -np.inf, np.float32)
    best_k = np.zeros((S, 0), np.int64)
    for shard in range(space.n_shards):
        for b in range(space.n_blocks):
            sc, top_s, top_i = block_scores(queries, space.block(shard, b))
            keys = (b * space.block_rows + np.asarray(top_i, np.int64)) * space.n_shards + shard
            best_s = np.concatenate([best_s, np.asarray(top_s)], axis=1)
            best_k = np.concatenate([best_k, keys], axis=1)
            wants = per_block.get((shard, b))
            if wants:
                w = np.zeros((_pow2(len(wants)), 3), np.int64)  # few gather shapes
                w[: len(wants)] = wants
                got = np.asarray(sc[jnp.asarray(w[:, 0]), jnp.asarray(w[:, 2])])
                for (s, j, _), val in zip(wants, got.tolist()):
                    want_scores[s][j] = val
            del sc
    if live_keys is not None and len(live_keys):
        sc = np.asarray(jnp.einsum("sd,nd->sn", queries, live_vecs, precision=hi))
        for s, j, i in live_wants:
            want_scores[s][j] = sc[s, i]
        masked = np.where(live_ok, sc, -np.inf) if live_ok is not None else sc
        kk = min(k, masked.shape[1])
        idx = np.argpartition(-masked, kk - 1, axis=1)[:, :kk]
        best_s = np.concatenate([best_s, np.take_along_axis(masked, idx, 1)], axis=1)
        best_k = np.concatenate([best_k, np.asarray(live_keys, np.int64)[idx]], axis=1)
    order = np.argsort(-best_s, axis=1, kind="stable")[:, :k]
    return (
        np.take_along_axis(best_s, order, 1),
        np.take_along_axis(best_k, order, 1),
        want_scores,
    )

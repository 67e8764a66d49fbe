"""TextGenerator — local causal LM for chat-style generation.

TPU-native analog of the reference's HFPipelineChat local generator
(xpacks/llm/llms.py:441).  Decoding is a real **KV-cache decode**: one
jitted function runs the prompt prefill (suffix only, when the prefix
cache below has the leading blocks) and then ``lax.scan``s single-token
steps against persistent per-layer K/V buffers — O(steps × L) attention
instead of the old full re-attend's O(steps × L²), still with no
per-token python round trips (ONE dispatch per generate call, as
before).

**Prefix/KV reuse** (pathway_tpu/cache/prefix.py): prompt token ids are
content-addressed in fixed blocks under a hash chain, and the K/V of
every full block is captured device-resident after the decode.  RAG
prompts sharing a system-prompt + retrieved-chunk prefix prefill only
their tails — prefill cost across a shared-prefix prompt set is
sub-linear, measured by the ``serve_cache`` bench phase via the
``pathway_cache_prefill_tokens_total{kind=reused|computed}`` counters.

Bit-reproducibility: the KV twin (models/transformer.py
``KVTransformerDecoder``) keeps the attention math line-for-line with
the trunk, the K/V buffer width is constant across prefix splits, and
masked slots carry exactly-zero probability — so warm (cached-prefix)
decodes emit the SAME tokens as cold ones, and the KV path matches the
legacy full re-attend decode token-for-token (tests/test_serve_cache.py
parity tests).  ``PATHWAY_GENERATOR_KV=0`` falls back to the legacy
decode.

With random-init weights the output is noise; with a trained checkpoint
it generates — either way the serving path, batching, caching and
compile behavior are the product."""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import config, observe
from ..observe import hbm, profile
from ..robust import retry_call
from ._params import unbox as _unbox

from . import hybrid, looped, moe
from .looped import token_stats
from .tokenizer import HashTokenizer
from .transformer import (
    KVTransformerDecoder,
    SlotKVDecoder,
    TransformerConfig,
    TransformerEncoder,
    resolve_heads,
)

__all__ = [
    "TextGenerator",
    "decode_draft_layers",
    "decode_draft_source",
    "decode_kv_quant",
    "decode_spec_k",
    "decode_step_bucket",
    "eos_id_from_env",
]

# flight recorder: submit→ready latency of a full decode (dispatch
# through host fetch) + batch occupancy per dispatch
_H_READY = observe.histogram("pathway_serve_model_seconds", model="generator")

# sentinel: "use the instance default" for per-call eos_id overrides
_UNSET = object()


def decode_step_bucket() -> int:
    """Decode-step chunk size from ``decode.step_bucket`` (default 8,
    tuner-adjustable): how many single-token decode steps one compiled
    chunk dispatch advances.  Shared by the legacy EOS-chunked decode and
    the continuous engine (serve/decode.py) — ONE knob, one compile shape."""
    return config.get("decode.step_bucket")


def decode_spec_k() -> int:
    """Speculation depth from ``PATHWAY_DECODE_SPEC_K`` (default 0 =
    speculation OFF): how many positions one verify dispatch scores per
    active slot — 1 committed token + up to ``k-1`` accepted draft
    tokens per round.  ``k <= 1`` is the plain one-token-per-step
    continuous decode; the ceiling keeps the verify forward (an
    ``Ln = k`` attention) from dwarfing the steps it replaces."""
    return config.get("decode.spec_k")


def decode_kv_quant() -> str:
    """Slot-pool K/V storage from ``PATHWAY_DECODE_KV_QUANT``: ``bf16``
    (default, bit-identical to solo decode) or ``int8`` (per-(layer,
    head, channel) stored scales, 2x slots×context at fixed HBM,
    bounded token drift — ops/kv_quant.py)."""
    return config.get("decode.kv_quant")


def decode_draft_source() -> str:
    """Draft proposal source from ``PATHWAY_DECODE_DRAFT``: ``auto``
    (default: n-gram mining first, reduced-layer trunk when the n-gram
    well runs dry), ``ngram`` (mining only — lanes without a match
    advance one token per round), or ``trunk`` (always the reduced-
    layer draft dispatch)."""
    return config.get("decode.draft")


def decode_draft_layers(n_layers: int) -> int:
    """Reduced-layer draft-trunk depth from
    ``PATHWAY_DECODE_DRAFT_LAYERS`` (default 0 = half the trunk,
    minimum 1): the draft forwards only the FIRST ``D`` blocks of the
    same params — cheap proposals, exactness restored by the verify."""
    d = config.get("decode.draft_layers")
    if d <= 0:
        d = max(1, n_layers // 2)
    return min(d, n_layers)


def eos_id_from_env() -> Optional[int]:
    """``PATHWAY_GENERATOR_EOS`` (a token id, e.g. 2 for the tokenizer's
    SEP) — unset/empty means no EOS handling, byte-for-byte the
    pre-EOS decode behavior."""
    raw = config.get("generator.eos").strip()
    if not raw or raw in ("0", "none", "off"):
        return None
    try:
        return int(raw)
    except ValueError:
        return None


class TextGenerator:
    def __init__(
        self,
        model: str = "pathway-mini-lm",
        dimension: int = 256,
        n_layers: int = 4,
        n_heads: int = 4,
        max_length: int = 256,
        vocab_size: int = 32768,
        seed: int = 2,
        checkpoint_path: Optional[str] = None,
        dtype=jnp.bfloat16,
        kv_cache: Any = "env",
        eos_id: Any = "env",
        architecture: Optional[Mapping[str, Any]] = None,
        params: Any = None,
    ):
        # ``architecture`` (a published config.json's keys) selects a decoder
        # family at exactly those sizes, and its own keys say which: linear-
        # attention layers among full ones (``linear_num_value_heads`` /
        # ``full_attention_interval``; models/hybrid.py: gated delta-rule
        # layers whose state lives beside the cache rows, routed experts of
        # which this chip holds a range, a shared expert), routed experts
        # (``moe_num_primary_experts``; models/moe.py: grouped-query
        # heads, per-layer rotary and window layouts, a router before
        # attention; or the parallel block, ``use_parallel_block`` /
        # ``model_type`` "cohere2_moe": one LayerNorm feeding attention and
        # sigmoid-routed experts side by side, shared experts, a held range)
        # or else the looped family (models/looped.py: RMSNorm
        # sandwich, rotary, gated SiLU, ``total_ut_steps`` passes over one
        # stack).  ``params`` hands its weights in.  Without it this is the
        # 4 x dimension LayerNorm/GELU trunk, as ever (``family`` None).
        self.family = None if architecture is None else self._family_of(architecture)
        if self.family is not None:
            self.config = self.family.Config.from_architecture(architecture, dtype)
            vocab_size, max_length = self.config.vocab_size, self.config.max_len
        else:
            self.config = TransformerConfig(
                vocab_size=vocab_size,
                d_model=dimension,
                n_heads=resolve_heads(dimension, n_heads),
                n_layers=n_layers,
                d_ff=dimension * 4,
                max_len=max_length,
                dtype=dtype,
                pool="none",
                causal=True,
            )
            self.module = TransformerEncoder(self.config)
            self._kv_module = KVTransformerDecoder(self.config)
            self._slot_module = SlotKVDecoder(self.config)
            # int8 twins (same params; ops/kv_quant.py scales as operands)
            self._kv_module_q = KVTransformerDecoder(self.config, quant=True)
            self._slot_module_q = SlotKVDecoder(self.config, quant=True)
        self.tokenizer = HashTokenizer(vocab_size=vocab_size, max_length=max_length)
        self._kv_scales = None  # lazy (params exist below)
        # EOS handling: a row that emits this token is FINISHED — further
        # sampling work is masked to PAD and the legacy decode returns as
        # soon as every row has finished (chunked dispatch).  None (the
        # env default when PATHWAY_GENERATOR_EOS is unset) preserves the
        # single-dispatch always-`steps` decode exactly.
        if eos_id == "env":
            eos_id = eos_id_from_env()
        if eos_id is not None and int(eos_id) == self.tokenizer.PAD:
            raise ValueError("eos_id must differ from the PAD token id")
        self.eos_id = None if eos_id is None else int(eos_id)
        # decode steps actually executed by the last generate() call —
        # the EOS early-exit regression hook (a batch of short answers
        # must not pay the full `steps` budget)
        self.last_decode_steps = 0
        self._lock = threading.Lock()
        self._fns: Dict[tuple, Any] = {}
        # recompile tripwire (ops/recompile_guard.py): decode shapes are
        # (batch bucket, padded length, prefix bucket, steps); a leak
        # fails under tests
        from ..ops.recompile_guard import RecompileTripwire

        self._tripwire = RecompileTripwire(f"TextGenerator[{model}]")
        if self.family is not None:
            self.params = self._family_params(params, seed)
        else:
            ids = jnp.zeros((1, 16), jnp.int32)
            mask = jnp.ones((1, 16), jnp.int32)
            self.params = self.module.init(jax.random.PRNGKey(seed), ids, mask)["params"]
            self.params = _unbox(self.params)
        # weight-tied readout: logits = h @ tok_embed.T
        self._vocab_table = None
        # tier-2 prefix/KV cache (pathway_tpu/cache): per-generator —
        # K/V blocks are only meaningful against this instance's params
        if kv_cache == "env":
            from ..cache import prefix_kv_cache_from_env

            kv_cache = prefix_kv_cache_from_env()
        self.kv_cache = kv_cache
        self._use_kv = config.get("generator.kv")
        # HBM ledger (observe/hbm.py): parameter tree bytes
        hbm.track_params("generator", self)

    @staticmethod
    def _family_of(architecture: Mapping[str, Any]):
        if "linear_num_value_heads" in architecture or "full_attention_interval" in architecture:
            return hybrid
        parallel = "use_parallel_block" in architecture or architecture.get("model_type") == "cohere2_moe"
        return moe if parallel or "moe_num_primary_experts" in architecture else looped

    def _family_params(self, params, seed: int):
        """A decoder family's weights: handed in (their tree is checked
        against the architecture's), else seeded random."""
        if params is None:
            return self.family.init_params(self.config, seed)

        def shapes(tree):
            return {
                jax.tree_util.keystr(k): (tuple(v.shape), str(v.dtype))
                for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]
            }

        want = shapes(jax.eval_shape(lambda: self.family.init_params(self.config, 0)))
        if shapes(params) != want:
            diff = sorted(set(shapes(params).items()) ^ set(want.items()))[:4]
            raise ValueError(f"params do not fit the architecture: {diff}")
        return params

    def slot_prefix(self, rows, n_blk: int, depth_shape):
        """The cached-prefix operands of a slot prefill from, per row, the
        prefix cache's blocks ``(k, v)``: the trunk takes them stacked
        ``[B, depth, P, H, hd]``; the looped family takes the blocks as
        they are, row by row (no copy); a family with state beside its rows
        (models/hybrid.py) takes per row ``(rows, snapshot)``: the blocks'
        rows joined ``[depth, P, H, hd]`` and the state filed with the last
        block, ``None`` both where there is no prefix."""
        if self.state_layout():
            # 17 MB of rows a 4,096-token prefix here: joined once, a program of a hundred operands a row less
            snapshot = self.kv_cache.snapshot if n_blk else None
            return tuple(
                tuple((jnp.concatenate([b[i] for b in row[:n_blk]], axis=1), snapshot(row[n_blk - 1])[i]) if n_blk else (None, None) for row in rows)
                for i in (0, 1)
            )
        if self.family is not None:
            return (
                tuple(tuple(b[0] for b in row[:n_blk]) for row in rows),
                tuple(tuple(b[1] for b in row[:n_blk]) for row in rows),
            )
        if not n_blk:
            empty = jnp.zeros((len(rows), *depth_shape), self.config.dtype)
            return empty, empty
        return tuple(
            jnp.stack([
                jnp.concatenate([b[i] for b in row[:n_blk]], axis=1) for row in rows
            ]).astype(self.config.dtype)
            for i in (0, 1)
        )

    def check_decode_options(self, spec_k: int, kv_quant: str) -> None:
        """What the slot pool may be asked of this generator.  The decoder
        families have no verify/draft program and no int8 cache rows yet:
        they say so here, at construction, instead of decoding wrongly."""
        if self.family is not None and (spec_k >= 2 or kv_quant == "int8"):
            raise ValueError(
                f"the {self.family.FAMILY} decoder family serves with speculation off (decode.spec_k 0) and a "
                f"bf16 cache (decode.kv_quant bf16); asked for spec_k={spec_k}, kv_quant={kv_quant}"
            )

    def join_attention(self, L_sfx: int, rows: int = 1, prefix: int = 0) -> str:
        """What attends the prompt in a join of ``rows`` rows of ``L_sfx``
        suffix tokens behind ``prefix`` cached ones: "kernel" where a family's
        flash kernel takes it, "blocks" where its query blocks do
        (models/moe.py ``prompt_attention``), "dense" where the scores are one array."""
        choose = getattr(self.family, "prompt_attention", None)
        return choose(self.config, L_sfx, rows, prefix + L_sfx) if choose is not None else "dense"

    def join_rows(self, prefix: int):
        """The most rows one join behind ``prefix`` cached tokens may carry
        (models/moe.py ``join_rows``), or None: no limit of the family's."""
        most = getattr(self.family, "join_rows", None)
        return most(self.config, prefix) if most is not None else None

    def join_ffn_blocks(self, tokens: int) -> int:
        """Blocks of tokens the expert branch of a join of ``tokens`` tokens
        runs its shared experts over (models/moe.py ``ffn_blocks``; 1 elsewhere)."""
        blocks = getattr(self.family, "ffn_blocks", None)
        return blocks(self.config, tokens) if blocks is not None else 1

    def kv_pool_layout(self, T: int):
        """The slot pool's kinds of rows at width ``T`` as ``(kind, cache
        rows deep, rows a layer)``: one rectangle, or what the family states
        (models/moe.py: full layers beside window layers' rings)."""
        layout = getattr(self.config, "pool_layout", None)
        return layout(T) if layout is not None else (("full", self.config.cache_depth, T),)

    def alloc_kv_pool(self, slots: int, T: int, dtype):
        """One of the two pools (keys, or values) for ``slots`` sequences:
        ``[slots, depth, rows, key/value heads, head_dim]`` per kind of row,
        a bare array where there is one kind."""
        cfg = self.config
        heads = getattr(cfg, "n_kv_heads", cfg.n_heads)
        pools = tuple(jnp.zeros((slots, depth, rows, heads, cfg.head_dim), dtype) for _, depth, rows in self.kv_pool_layout(T))
        return pools[0] if len(pools) == 1 else pools

    def state_layout(self):
        """What a slot holds besides rows, whatever its length, as ``(kind,
        layers, shape a layer, dtype)``: nothing, or what the family states
        (models/hybrid.py: the delta rule's state and the convolution's rows)."""
        layout = getattr(self.config, "state_layout", None)
        return layout() if layout is not None else ()

    def alloc_pool(self, slots: int, T: int, dtype):
        """The slot pool: the two trees a join and a step are handed and
        hand back (both donated).  Keys and values are allocated alike; a
        family with state puts one kind beside each, ``[layers, slots, ...]``."""
        pools = tuple(self.alloc_kv_pool(slots, T, dtype) for _ in range(2))
        state = tuple(jnp.zeros((layers, slots) + shape, dt) for _, layers, shape, dt in self.state_layout())
        return tuple(zip(pools, state)) if state else pools

    def blank_snapshot(self):
        """One sequence's state, all zeros: what ``warm`` files with a blank prefix's last block."""
        return tuple(jnp.zeros((layers,) + shape, dt) for _, layers, shape, dt in self.state_layout())

    def snapshot_positions(self, P: int, L_sfx: int) -> tuple:
        """The positions inside a join's suffix whose state the join hands
        back for the prefix tier (none without a tier, or without state)."""
        where = getattr(self.family, "snapshot_positions", None)
        return where(P, L_sfx, self.kv_cache.block) if where is not None and self.kv_cache is not None else ()

    # -- legacy full re-attend decode (parity reference / fallback) ----------
    def _decode_fn(self, B: int, L: int, steps: int):
        """Compiled decode CHUNK of ``steps`` single-token iterations:
        ``(params, ids, mask, pos, temperature, rng, finished, eos) ->
        (tokens [B, steps], ids, mask, pos, rng, finished)``.  The carry
        is explicit so ``generate`` can thread it across chunk dispatches
        and return as soon as every row has finished; with EOS disabled
        (``eos = -1``) one chunk of the full budget reproduces the
        original single-dispatch decode token-for-token.  Per-row
        ``finished`` masks every write/advance (the row is bit-frozen)
        and an all-finished batch skips the forward pass entirely via
        ``lax.cond`` — post-EOS sampling work is zeroed, not just
        discarded."""
        key = (B, L, steps)
        fn = self._fns.get(key)
        if fn is None:
            self._tripwire.observe(key)
            module = self.module
            PAD = self.tokenizer.PAD

            def decode(params, ids, mask, pos, temperature, rng, finished, eos):
                emb = params["tok_embed"]["embedding"]

                def live(carry):
                    ids_c, mask_c, pos, rng_c, fin = carry
                    hidden = module.apply({"params": params}, ids_c, mask_c)
                    logits = jnp.einsum(
                        "bld,vd->blv", hidden.astype(jnp.float32), emb.astype(jnp.float32)
                    )
                    # logits at last real position of each row
                    last = jnp.take_along_axis(
                        logits, (pos - 1)[:, None, None], axis=1
                    )[:, 0, :]
                    rng_c, sub = jax.random.split(rng_c)
                    greedy = jnp.argmax(last, axis=-1)
                    sampled = jax.random.categorical(sub, last / jnp.maximum(temperature, 1e-4))
                    nxt = jnp.where(temperature <= 0.0, greedy, sampled).astype(jnp.int32)
                    nxt = jnp.where(fin, PAD, nxt)
                    ids_c = jnp.take_along_axis(
                        ids_c, jnp.arange(ids_c.shape[1])[None, :], axis=1
                    )
                    ids_w = jax.vmap(lambda row, p, t: row.at[p].set(t))(
                        ids_c, pos, nxt
                    )
                    mask_w = jax.vmap(lambda row, p: row.at[p].set(1))(mask_c, pos)
                    # finished rows are frozen: no ids/mask write, no
                    # position advance — their history stays exactly the
                    # prefix that ended in EOS.  The row emitting EOS
                    # THIS step still writes and advances (the original
                    # unconditional behavior), then freezes.
                    keep = fin[:, None]
                    ids_c = jnp.where(keep, ids_c, ids_w)
                    mask_c = jnp.where(keep, mask_c, mask_w)
                    pos = jnp.where(fin, pos, pos + 1)
                    fin = fin | (nxt == eos)
                    return (ids_c, mask_c, pos, rng_c, fin), nxt

                def dead(carry):
                    return carry, jnp.full((B,), PAD, jnp.int32)

                def step(carry, _):
                    return jax.lax.cond(jnp.all(carry[4]), dead, live, carry)

                (ids_f, mask_f, pos_f, rng_f, fin_f), toks = jax.lax.scan(
                    step, (ids, mask, pos, rng, finished), None, length=steps
                )
                return toks.T, ids_f, mask_f, pos_f, rng_f, fin_f

            # device-time attribution (observe/profile.py)
            fn = profile.wrap("generator.decode", jax.jit(decode))
            self._fns[key] = fn
        return fn

    # -- KV-cache decode -----------------------------------------------------
    def _kv_fn(self, B: int, L_sfx: int, P: int, steps: int):
        """Compiled prefill+decode: ``(params, suffix_ids, n_lens,
        prefix_k, prefix_v, temperature, rng) -> (tokens [B, steps],
        k_buf, v_buf)``.  ``P`` is the static cached-prefix split (the
        batch-min match, bucketed to power-of-two block multiples by
        ``_cached_prefix``) — the K/V buffer width is ``P + L_sfx +
        steps`` == the legacy decode's constant attention width, which
        is what makes warm and cold decodes bit-identical.
        The returned buffers stay device-resident; the capture pass
        slices prompt blocks out of them for the prefix cache."""
        key = ("kv", B, L_sfx, P, steps)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        self._tripwire.observe(key)
        cfg = self.config
        decoder = self._kv_module
        PAD = self.tokenizer.PAD
        H = cfg.n_heads
        hd = cfg.d_model // H
        T = P + L_sfx + steps

        def run(
            params, suffix_ids, n_lens, prefix_k, prefix_v, temperature,
            rng, eos, fin0,
        ):
            emb = params["tok_embed"]["embedding"]
            kbuf = jnp.zeros((B, cfg.n_layers, T, H, hd), cfg.dtype)
            vbuf = jnp.zeros((B, cfg.n_layers, T, H, hd), cfg.dtype)
            if P:
                kbuf = jax.lax.dynamic_update_slice(
                    kbuf, prefix_k.astype(cfg.dtype), (0, 0, 0, 0, 0)
                )
                vbuf = jax.lax.dynamic_update_slice(
                    vbuf, prefix_v.astype(cfg.dtype), (0, 0, 0, 0, 0)
                )
            # prefill: the suffix tokens sit at global positions
            # [P, P + L_sfx); every row shares the static split point
            positions = jnp.broadcast_to(
                (P + jnp.arange(L_sfx, dtype=jnp.int32))[None, :], (B, L_sfx)
            )
            write_pos = jnp.full((B,), P, jnp.int32)
            hidden, kbuf, vbuf = decoder.apply(
                {"params": params}, suffix_ids, positions, kbuf, vbuf,
                write_pos, positions,
            )
            logits = jnp.einsum(
                "bld,vd->blv", hidden.astype(jnp.float32), emb.astype(jnp.float32)
            )
            # first decode logits: the last REAL prompt position, in
            # suffix-local coordinates (the prefix cache always leaves
            # >= 1 real suffix token, so n - 1 - P >= 0 on real rows)
            last0 = jnp.take_along_axis(
                logits,
                jnp.maximum(n_lens - 1 - P, 0)[:, None, None],
                axis=1,
            )[:, 0, :]

            def step(carry, _):
                kbuf_c, vbuf_c, last, pos, rng_c, fin = carry
                greedy = jnp.argmax(last, axis=-1)

                def sample(rng_c):
                    rng2, sub = jax.random.split(rng_c)
                    return rng2, jax.random.categorical(
                        sub, last / jnp.maximum(temperature, 1e-4)
                    )

                def greedy_only(rng_c):
                    # temperature 0: the B×V gumbel draw would be
                    # discarded by the where below — skip it
                    return rng_c, greedy

                rng_c, sampled = jax.lax.cond(
                    temperature <= 0.0, greedy_only, sample, rng_c
                )
                nxt = jnp.where(temperature <= 0.0, greedy, sampled).astype(
                    jnp.int32
                )
                # per-row finished mask: a row that emitted EOS samples
                # PAD from here on; once EVERY row is done the forward
                # pass is skipped outright (lax.cond) — further work is
                # zeroed inside the single decode dispatch
                nxt = jnp.where(fin, PAD, nxt)
                fin_next = fin | (nxt == eos)

                def fwd(args):
                    kbuf_c, vbuf_c, nxt, pos = args
                    h1, kbuf_n, vbuf_n = decoder.apply(
                        {"params": params}, nxt[:, None], pos[:, None],
                        kbuf_c, vbuf_c, pos, pos[:, None],
                    )
                    return kbuf_n, vbuf_n, jnp.einsum(
                        "bld,vd->blv",
                        h1.astype(jnp.float32),
                        emb.astype(jnp.float32),
                    )[:, 0, :]

                def skip(args):
                    kbuf_c, vbuf_c, _nxt, _pos = args
                    return kbuf_c, vbuf_c, last

                kbuf_c, vbuf_c, logits1 = jax.lax.cond(
                    jnp.all(fin_next), skip, fwd, (kbuf_c, vbuf_c, nxt, pos)
                )
                pos = jnp.where(fin, pos, pos + 1)
                return (kbuf_c, vbuf_c, logits1, pos, rng_c, fin_next), nxt

            (kbuf, vbuf, _, _, _, _), toks = jax.lax.scan(
                step, (kbuf, vbuf, last0, n_lens, rng, fin0), None, length=steps
            )
            return toks.T, kbuf, vbuf  # toks [B, steps]

        fn = profile.wrap("generator.kv_decode", jax.jit(run))
        self._fns[key] = fn
        return fn

    def _cached_prefix(self, ids: np.ndarray, n_lens: np.ndarray, n: int):
        """Cache wrapper for the prefix tier: per-row longest cached
        block chain, batched at the row MINIMUM (the static split point
        every row shares — the RAG shape is many prompts over one
        system+chunks prefix, where the minimum IS the shared prefix),
        then rounded DOWN to a power-of-two block multiple
        (``PrefixKVCache.bucket_tokens``) so the split point (a
        compile-shape dimension) takes O(log) values instead of one per
        distinct prefix length — a mix of prompt families must not
        compile one decode program each.  Returns ``(P, matches)``;
        pure host + cache work, no dispatch."""
        matches = [
            self.kv_cache.match(ids[i], int(n_lens[i])) for i in range(n)
        ]
        P = self.kv_cache.bucket_tokens(min((m[0] for m in matches), default=0))
        if self.state_layout():
            # a join can start only where the tier holds the state: cut back
            # to the last split point whose block carries a snapshot in every row
            blk = self.kv_cache.block
            while P and not all(self.kv_cache.snapshot(m[1][P // blk - 1]) for m in matches):
                P = self.kv_cache.bucket_tokens(P - 1)
        return P, matches

    # -- continuous-decode slot pool (serve/decode.py) -----------------------
    def kv_pool_scales(self):
        """Per-(layer, head, channel) int8 K/V scales ``[L, H, hd]``
        for THIS generator's params (ops/kv_quant.py) — computed once,
        shared by every quantized pool over the instance."""
        self.check_decode_options(0, "int8")
        if self._kv_scales is None:
            from ..ops.kv_quant import kv_pool_scales

            # compute OFF the lock (device math must never run under
            # it); the assignment races benignly — both winners hold
            # identical values derived from the same params
            scales = kv_pool_scales(self.params, self.config)
            with self._lock:
                if self._kv_scales is None:
                    self._kv_scales = scales
        return self._kv_scales

    def _slot_prefill_fn(
        self, S: int, T: int, B: int, L_sfx: int, P: int, quant: bool = False
    ):
        """Compiled JOIN batch for ``B`` slots of a ``[S, L, H, T, d]``
        K/V pool: ``(params, pool_k, pool_v, slots [B], suffix_ids
        [B, L_sfx], n_len [B], prefix_k, prefix_v, rngs [B, 2],
        temps [B]) -> (pool_k, pool_v, first_tokens [B], rngs')``.
        Prefills each row's prompt suffix (cached prefix blocks land at
        positions [0, P)) into fresh width-``T`` buffers, samples each
        row's FIRST generated token from its last real prompt position —
        per-row rng chains, consuming each request's first split, the
        same chain position the solo decode uses — and scatters every
        row into the pool at its slot, wiping the previous occupants.
        Joins arriving together batch into ONE dispatch (``B`` bucketed
        to powers of two; a pad row repeats the first row, slot and all,
        and so writes the same values again).  ``T`` is the POOL width:
        masked attention is width-invariant (extra key slots carry
        exact-zero probability), which is what keeps a pooled decode
        bit-identical to a solo one whose buffer is exactly
        prompt+steps wide.

        ``quant=True`` (int8 pool): the fn takes two trailing operands
        ``k_scales``/``v_scales`` ``[L, H, hd]``, prefills through the
        quant KV twin — every attention read is dequant(int8), the SAME
        values a later warm join will read back, which is what keeps
        warm and cold int8 joins deterministic — and scatters int8
        rows; the bf16 prefix rows passed in are (re)quantized on
        insert (idempotent: ops/kv_quant.py)."""
        key = ("slot_prefill_q" if quant else "slot_prefill", S, T, B, L_sfx, P)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        self._tripwire.observe(key)
        cfg = self.config
        if self.family is not None:
            self.check_decode_options(0, "int8" if quant else "bf16")
            # the prefix tier's block: a family may hand the tier its blocks itself (models/moe.py)
            block = self.kv_cache.block if self.kv_cache is not None else 0
            fn = profile.wrap(
                "generator.slot_prefill", self.family.slot_prefill(cfg, S, T, B, L_sfx, P, block)
            )
            self._fns[key] = fn
            return fn
        decoder = self._kv_module_q if quant else self._kv_module
        H = cfg.n_heads
        hd = cfg.d_model // H
        buf_dtype = jnp.int8 if quant else cfg.dtype

        def prefill(
            params, pool_k, pool_v, slots, suffix_ids, n_len,
            prefix_k, prefix_v, rngs, temps, k_scales=None, v_scales=None,
        ):
            from ..ops.kv_quant import quantize_kv

            emb = params["tok_embed"]["embedding"]
            kbuf = jnp.zeros((B, cfg.n_layers, T, H, hd), buf_dtype)
            vbuf = jnp.zeros((B, cfg.n_layers, T, H, hd), buf_dtype)
            if P:
                pfx_k = (
                    quantize_kv(prefix_k, k_scales)
                    if quant else prefix_k.astype(cfg.dtype)
                )
                pfx_v = (
                    quantize_kv(prefix_v, v_scales)
                    if quant else prefix_v.astype(cfg.dtype)
                )
                kbuf = jax.lax.dynamic_update_slice(
                    kbuf, pfx_k, (0, 0, 0, 0, 0)
                )
                vbuf = jax.lax.dynamic_update_slice(
                    vbuf, pfx_v, (0, 0, 0, 0, 0)
                )
            positions = jnp.broadcast_to(
                (P + jnp.arange(L_sfx, dtype=jnp.int32))[None, :], (B, L_sfx)
            )
            write_pos = jnp.full((B,), P, jnp.int32)
            hidden, kbuf, vbuf = decoder.apply(
                {"params": params}, suffix_ids, positions, kbuf, vbuf,
                write_pos, positions, k_scales, v_scales,
            )
            logits = jnp.einsum(
                "bld,vd->blv", hidden.astype(jnp.float32), emb.astype(jnp.float32)
            )
            last0 = jnp.take_along_axis(
                logits,
                jnp.maximum(n_len - 1 - P, 0)[:, None, None],
                axis=1,
            )[:, 0, :]
            greedy = jnp.argmax(last0, axis=-1)

            def sample(rngs):
                pairs = jax.vmap(jax.random.split)(rngs)
                drawn = jax.vmap(jax.random.categorical)(
                    pairs[:, 1], last0 / jnp.maximum(temps, 1e-4)[:, None]
                )
                return pairs[:, 0], jnp.where(temps <= 0.0, greedy, drawn)

            def greedy_only(rngs):
                return rngs, greedy

            rngs, toks = jax.lax.cond(
                jnp.all(temps <= 0.0), greedy_only, sample, rngs
            )
            # ONE scatter per buffer: row i lands at pool slot
            # ``slots[i]``; a pad row repeats row 0 (the engine fills it
            # so), lands on row 0's slot with row 0's values, and can
            # never clobber another slot
            pool_k = pool_k.at[slots].set(kbuf)
            pool_v = pool_v.at[slots].set(vbuf)
            toks = toks.astype(jnp.int32)
            return pool_k, pool_v, toks, rngs, token_stats(last0, toks)

        fn = profile.wrap("generator.slot_prefill", jax.jit(prefill))
        self._fns[key] = fn
        return fn

    def _slot_step_fn(self, S: int, T: int, chunk: int, quant: bool = False):
        """Compiled decode-step CHUNK over the whole slot pool:
        ``(params, pool_k, pool_v, tok [S], pos [S], active [S],
        left [S], rngs [S, 2], temps [S], eos [S]) -> (pool_k, pool_v,
        rngs, emitted [chunk, S])``.  Each of the ``chunk`` scan
        iterations forwards every slot's current token one position
        (``SlotKVDecoder`` — inactive slots' K/V bit-frozen), samples
        the next token PER SLOT with that slot's own rng chain (the solo
        chain: requests are batch-composition-independent), emits ``-1``
        for inactive lanes, and retires lanes that emit their EOS or
        exhaust their budget.  ONE compile signature per engine — the
        shapes are (S, T, chunk), all static per pool.

        ``quant=True``: int8 pool, trailing ``k_scales``/``v_scales``
        operands, reads dequantized in-kernel (ops/kv_quant.py)."""
        key = ("slot_step_q" if quant else "slot_step", S, T, chunk)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        self._tripwire.observe(key)
        if self.family is not None:
            self.check_decode_options(0, "int8" if quant else "bf16")
            fn = profile.wrap(
                "generator.slot_step", self.family.slot_step(self.config, S, T, chunk)
            )
            self._fns[key] = fn
            return fn
        decoder = self._slot_module_q if quant else self._slot_module

        def run(
            params, pool_k, pool_v, tok, pos, active, left, rngs, temps, eos,
            k_scales=None, v_scales=None,
        ):
            emb = params["tok_embed"]["embedding"]

            def one(carry, _):
                pool_k, pool_v, tok, pos, act, left, rngs = carry
                live = act & (left > 0)
                h, pool_k, pool_v = decoder.apply(
                    {"params": params}, tok[:, None], pos[:, None],
                    pool_k, pool_v, pos, pos[:, None], live,
                    k_scales, v_scales,
                )
                logits = jnp.einsum(
                    "bld,vd->blv", h.astype(jnp.float32), emb.astype(jnp.float32)
                )[:, 0, :]
                greedy = jnp.argmax(logits, axis=-1)

                def sample(rngs):
                    # sampling lanes: one split per step per lane (the
                    # solo chain), per-lane categorical over [V]
                    pairs = jax.vmap(jax.random.split)(rngs)
                    subs = pairs[:, 1]
                    drawn = jax.vmap(jax.random.categorical)(
                        subs, logits / jnp.maximum(temps, 1e-4)[:, None]
                    )
                    return pairs[:, 0], jnp.where(
                        temps <= 0.0, greedy, drawn
                    )

                def greedy_only(rngs):
                    # all-greedy pool: tokens are rng-independent, so
                    # the S×V gumbel draw (the dominant per-step cost at
                    # small models) is skipped outright
                    return rngs, greedy

                rngs2, nxt = jax.lax.cond(
                    jnp.all(temps <= 0.0), greedy_only, sample, rngs
                )
                nxt = nxt.astype(jnp.int32)
                emitted = jnp.where(live, nxt, -1)
                act2 = live & (nxt != eos)
                pos2 = jnp.where(live, pos + 1, pos)
                left2 = jnp.where(live, left - 1, left)
                tok2 = jnp.where(live, nxt, tok)
                # rng chains advance only for live lanes: a finished
                # lane's chain state is frozen where the solo decode's
                # chain was when it emitted that request's last token
                rngs3 = jnp.where(live[:, None], rngs2, rngs)
                return (pool_k, pool_v, tok2, pos2, act2, left2, rngs3), (
                    emitted, token_stats(logits, nxt),
                )

            (pool_k, pool_v, _, _, _, _, rngs), (em, extra) = jax.lax.scan(
                one, (pool_k, pool_v, tok, pos, active, left, rngs),
                None, length=chunk,
            )
            return pool_k, pool_v, rngs, em, extra

        fn = profile.wrap("generator.slot_step", jax.jit(run))
        self._fns[key] = fn
        return fn

    def _slot_verify_fn(self, S: int, T: int, k: int, quant: bool = False):
        """Compiled speculative VERIFY over the whole slot pool — the
        single batched dispatch that scores all ``k`` draft positions at
        once: ``(params, pool_k, pool_v, toks [S, k], pos [S],
        active [S], left [S], rngs [S, 2], temps [S], eos [S]) ->
        (pool_k, pool_v, rngs, emitted [k, S])``.

        ``toks[:, 0]`` is each lane's last emitted token (what a plain
        step would forward) and ``toks[:, 1:]`` its k-1 draft proposals.
        One ``SlotKVDecoder`` forward with ``Ln = k`` writes K/V for all
        k positions and yields logits at each; an in-kernel scan then
        walks the positions replaying EXACTLY the plain-step sampling
        (same per-lane rng chain, one split per EMITTED token, the
        pool-level all-greedy gate) and accepts while the sampled token
        agrees with the next forwarded input.  On the first disagreement
        the lane's own sampled token is still emitted (it was drawn from
        the true distribution at a position whose K/V is valid — the
        prefix up to it matched), and later positions emit ``-1``.
        Greedy and temperature>0 are both EXACT: acceptance only keeps
        tokens the plain chain would have drawn with the same splits, so
        spec-on == spec-off == solo bit-for-bit.  Rejected positions'
        K/V rows are garbage but UNREACHABLE: the pool is
        write-before-read (next dispatch re-writes position ``pos``
        before anything attends it) and masked attention zeroes keys
        past each row's ``q_pos``.

        ``quant=True``: int8 pool + trailing scales operands, same as
        the step fn."""
        key = ("slot_verify_q" if quant else "slot_verify", S, T, k)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        self.check_decode_options(k, "int8" if quant else "bf16")
        self._tripwire.observe(key)
        decoder = self._slot_module_q if quant else self._slot_module

        def run(
            params, pool_k, pool_v, toks, pos, active, left, rngs, temps, eos,
            k_scales=None, v_scales=None,
        ):
            emb = params["tok_embed"]["embedding"]
            live0 = active & (left > 0)
            positions = pos[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
            # ONE forward for all k positions pool-wide; write_pos=pos
            # so the k rows land at [pos, pos+k) (inactive lanes'
            # writes are masked off by ``live0`` as in the plain step)
            h, pool_k, pool_v = decoder.apply(
                {"params": params}, toks, positions,
                pool_k, pool_v, pos, positions, live0,
                k_scales, v_scales,
            )
            logits = jnp.einsum(
                "bld,vd->blv", h.astype(jnp.float32), emb.astype(jnp.float32)
            )  # [S, k, V]
            # follow[:, i] = the token forwarded at position i+1 — what
            # the sampled token at i must equal for acceptance to
            # continue; -1 (never a vocab id) past the last draft
            follow = jnp.concatenate(
                [toks[:, 1:], jnp.full((S, 1), -1, jnp.int32)], axis=1
            )

            def one(carry, xs):
                acc, pos_c, left_c, rngs = carry
                lg, fol = xs
                live = acc & (left_c > 0)
                greedy = jnp.argmax(lg, axis=-1)

                def sample(rngs):
                    pairs = jax.vmap(jax.random.split)(rngs)
                    drawn = jax.vmap(jax.random.categorical)(
                        pairs[:, 1], lg / jnp.maximum(temps, 1e-4)[:, None]
                    )
                    return pairs[:, 0], jnp.where(temps <= 0.0, greedy, drawn)

                def greedy_only(rngs):
                    return rngs, greedy

                rngs2, nxt = jax.lax.cond(
                    jnp.all(temps <= 0.0), greedy_only, sample, rngs
                )
                nxt = nxt.astype(jnp.int32)
                emitted = jnp.where(live, nxt, -1)
                # keep accepting only while the draw agrees with the
                # next forwarded draft AND the lane didn't just finish
                acc2 = live & (nxt != eos) & (nxt == fol)
                pos2 = jnp.where(live, pos_c + 1, pos_c)
                left2 = jnp.where(live, left_c - 1, left_c)
                # one split per EMITTED token — the solo chain position
                rngs3 = jnp.where(live[:, None], rngs2, rngs)
                return (acc2, pos2, left2, rngs3), (emitted, token_stats(lg, nxt))

            xs = (jnp.swapaxes(logits, 0, 1), follow.T)
            (_, _, _, rngs), (em, extra) = jax.lax.scan(
                one, (live0, pos, left, rngs), xs
            )
            return pool_k, pool_v, rngs, em, extra

        fn = profile.wrap("generator.slot_verify", jax.jit(run))
        self._fns[key] = fn
        return fn

    def _slot_draft_fn(
        self, S: int, T: int, k_draft: int, D: int, quant: bool = False
    ):
        """Compiled reduced-layer TRUNK draft — the fallback proposer
        when a lane's n-gram well runs dry: ``(params, pool_k, pool_v,
        tok [S], pos [S], active [S]) -> drafts [S, k_draft]``.  Runs
        only the first ``D`` trunk blocks (plus ``final_ln``) over the
        SAME params — no second model — greedily rolling ``k_draft``
        tokens forward on a sliced ``[S, D, T, H, hd]`` view of the
        pool.  The slice is a functional copy: the real pool is NEVER
        written (drafts are proposals; the verify dispatch is what
        commits K/V), so a wrong draft can't poison anything.  Greedy
        on purpose — drafts only seed verification, and the verify
        scan's exact sampling decides acceptance, so draft quality
        affects speed, never tokens."""
        key = ("slot_draft_q" if quant else "slot_draft", S, T, k_draft, D)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        self.check_decode_options(k_draft + 1, "int8" if quant else "bf16")
        self._tripwire.observe(key)
        cfg = self.config
        decoder = SlotKVDecoder(cfg, quant=quant, layers=D)

        def run(
            params, pool_k, pool_v, tok, pos, active,
            k_scales=None, v_scales=None,
        ):
            emb = params["tok_embed"]["embedding"]
            pk = pool_k[:, :D]
            pv = pool_v[:, :D]
            ks = None if k_scales is None else k_scales[:D]
            vs = None if v_scales is None else v_scales[:D]

            def one(carry, _):
                pk, pv, tok, pos_c = carry
                h, pk, pv = decoder.apply(
                    {"params": params}, tok[:, None], pos_c[:, None],
                    pk, pv, pos_c, pos_c[:, None], active,
                    ks, vs,
                )
                logits = jnp.einsum(
                    "bld,vd->blv", h.astype(jnp.float32),
                    emb.astype(jnp.float32),
                )[:, 0, :]
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                pos2 = jnp.where(active, pos_c + 1, pos_c)
                return (pk, pv, nxt, pos2), nxt

            (_, _, _, _), toks = jax.lax.scan(
                one, (pk, pv, tok, pos), None, length=k_draft
            )
            return jnp.swapaxes(toks, 0, 1)

        fn = profile.wrap("generator.slot_draft", jax.jit(run))
        self._fns[key] = fn
        return fn

    def _generate_kv(
        self,
        prompts: Sequence[str],
        max_new_tokens: int,
        temperature: float,
        seed: int,
        eos: Optional[int] = None,
    ) -> List[str]:
        cfg = self.config
        n = len(prompts)
        # tokenize + pad OFF the lock (the tokenizer is stateless), same
        # discipline as the serve/encode paths: concurrent generates
        # overlap their host prep; the lock covers only the compiled-fn
        # cache below
        from .encoder import _bucket

        b = _bucket(n)
        texts = [str(p) for p in prompts] + [""] * (b - n)
        L_budget = cfg.max_len - max_new_tokens
        ids, mask = self.tokenizer.encode_batch(texts, max_length=L_budget)
        ids = np.asarray(ids)
        mask = np.asarray(mask)
        n_lens = mask.sum(axis=1).astype(np.int32)
        # tier-2 lookup OFF the lock (cache traffic, incl. chaos sites,
        # must never stall a concurrent generate)
        P, matches = (0, [])
        if self.kv_cache is not None:
            P, matches = self._cached_prefix(ids, n_lens, n)
        L_sfx = ids.shape[1] - P
        H = cfg.n_heads
        hd = cfg.d_model // H
        if P:
            n_pblk = P // self.kv_cache.block
            rows_k = []
            rows_v = []
            for i in range(b):
                if i < n:
                    blocks = matches[i][1][:n_pblk]
                    rows_k.append(jnp.concatenate([blk[0] for blk in blocks], axis=1))
                    rows_v.append(jnp.concatenate([blk[1] for blk in blocks], axis=1))
                else:
                    rows_k.append(jnp.zeros((cfg.n_layers, P, H, hd), cfg.dtype))
                    rows_v.append(jnp.zeros((cfg.n_layers, P, H, hd), cfg.dtype))
            prefix_k = jnp.stack(rows_k)
            prefix_v = jnp.stack(rows_v)
        else:
            prefix_k = jnp.zeros((b, cfg.n_layers, 0, H, hd), cfg.dtype)
            prefix_v = jnp.zeros((b, cfg.n_layers, 0, H, hd), cfg.dtype)
        with self._lock:
            fn = self._kv_fn(b, L_sfx, P, max_new_tokens)
        t0 = time.perf_counter_ns()
        observe.record_occupancy("generator", n, b)
        # "generator.dispatch" is the retry/fault site: a generator that
        # stays down raises out of here, and the QA layer's ladder rung
        # answers extractively from the retrieved passages instead
        toks, kbuf, vbuf = retry_call(
            "generator.dispatch",
            fn,
            self.params,
            jnp.asarray(ids[:, P:]),
            jnp.asarray(n_lens),
            prefix_k,
            prefix_v,
            jnp.float32(temperature),
            jax.random.PRNGKey(seed),
            jnp.int32(-1 if eos is None else eos),
            # padding rows start finished (output discarded) so the
            # in-scan all-finished compute skip can fire on real batches
            jnp.asarray(np.arange(b) >= n)
            if eos is not None
            else jnp.zeros((b,), bool),
        )
        toks = np.asarray(toks)[:n]
        self.last_decode_steps = max_new_tokens
        _H_READY.observe_ns(time.perf_counter_ns() - t0)
        # capture: admit the prompt's uncached full blocks as async
        # device slices of the returned buffers (prompt region only —
        # block j covers buffer positions [j*blk, (j+1)*blk), identical
        # in global and buffer coordinates since the prefix sits at 0)
        if self.kv_cache is not None:
            blk = self.kv_cache.block
            for i in range(n):
                matched, _blocks, chain = matches[i]
                self.kv_cache.admit(
                    chain,
                    matched // blk,
                    lambda j, row=i: (
                        kbuf[row, :, j * blk : (j + 1) * blk],
                        vbuf[row, :, j * blk : (j + 1) * blk],
                    ),
                )
                self.kv_cache.note_prefill(
                    reused=P, computed=int(n_lens[i]) - P
                )
        return [self.render_tokens(row) for row in toks]

    def _generate_family(
        self, prompts, max_new_tokens: int, temperature: float, seed: int, eos
    ) -> List[str]:
        """Solo decode of a decoder family: the slot pool's own prefill
        and step programs over a private pool, one slot per prompt, as wide
        as the longest prompt plus the budget."""
        from ..ops.dispatch_counter import record_fetch
        from .encoder import _bucket

        cfg = self.config
        n, b = len(prompts), _bucket(len(prompts))
        ids, mask = self.tokenizer.encode_batch(
            [str(p) for p in prompts], max_length=cfg.max_len - max_new_tokens
        )
        ids, n_lens = np.asarray(ids), np.asarray(mask).sum(axis=1).astype(np.int32)
        L = ids.shape[1]
        T = -(-(L + max_new_tokens) // 64) * 64
        chunk = min(max_new_tokens, decode_step_bucket())
        pool_k, pool_v = self.alloc_pool(b, T, cfg.dtype)
        no_prefix = self.slot_prefix([()] * b, 0, None)
        pad = b - n
        with self._lock:
            prefill = self._slot_prefill_fn(b, T, b, L, 0)
            step = self._slot_step_fn(b, T, chunk)
        rng0 = np.stack([np.asarray(jax.random.PRNGKey(seed))] * b)
        temps = jnp.full((b,), temperature, jnp.float32)
        pk, pv, tok, rngs, _ = retry_call(
            "generator.dispatch", prefill, self.params, pool_k, pool_v,
            # a pad row repeats row 0 (same slot, same ids): it writes the same values again
            jnp.asarray(np.r_[np.arange(n), np.zeros(pad)].astype(np.int32)),
            jnp.asarray(np.r_[ids, ids[:1].repeat(pad, 0)]), jnp.asarray(np.r_[n_lens, n_lens[:1].repeat(pad)]),
            *no_prefix, jnp.asarray(rng0), temps,
        )
        # a blocking call by contract: each fetch below is booked
        record_fetch("generator_solo")
        rows = [[int(t)] for t in np.asarray(tok)[:n]]
        pos = np.pad(n_lens, (0, pad))
        active = np.r_[np.ones(n, bool), np.zeros(pad, bool)]
        eos_arr = np.full(b, -1 if eos is None else int(eos), np.int32)
        active[:n] &= np.asarray([r[0] for r in rows]) != eos_arr[:n]
        left = np.where(active, max_new_tokens - 1, 0).astype(np.int32)
        while (active & (left > 0)).any():
            last = np.asarray([rows[i][-1] if i < n else 0 for i in range(b)], np.int32)
            pk, pv, rngs, em, _ = retry_call(
                "generator.dispatch", step, self.params, pk, pv, jnp.asarray(last),
                jnp.asarray(pos), jnp.asarray(active), jnp.asarray(left), rngs, temps,
                jnp.asarray(eos_arr), jnp.int32(chunk),
            )
            record_fetch("generator_solo")
            for col in np.asarray(em):
                for i in np.flatnonzero(col[:n] >= 0):
                    rows[i].append(int(col[i]))
                    pos[i] += 1
                    left[i] -= 1
                    active[i] = col[i] != eos_arr[i]
        self.last_decode_steps = max(len(r) for r in rows)
        return [self.render_tokens(r) for r in rows]

    def render_tokens(self, row: Sequence[int]) -> str:
        """Canonical token-id rendering (the hashing tokenizer is not
        invertible) — shared by every decode path, including the
        continuous engine (serve/decode.py), so per-request token
        identity is comparable as plain strings."""
        return " ".join(
            f"<{int(t)}>" for t in row if int(t) != self.tokenizer.PAD
        )

    def generate(
        self,
        prompts: Sequence[str],
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        seed: int = 0,
        use_kv: Optional[bool] = None,
        eos_id: Any = _UNSET,
    ) -> List[str]:
        """Generate up to ``max_new_tokens`` per prompt.  ``use_kv``
        overrides the decode path (None = the ``PATHWAY_GENERATOR_KV``
        default): the KV path and the legacy full re-attend emit
        identical tokens — the legacy path survives as the parity oracle
        and fallback.  ``eos_id`` (default: the instance's
        ``PATHWAY_GENERATOR_EOS`` setting) marks rows finished when they
        emit it: post-EOS sampling is masked to PAD on both paths, and
        the legacy path runs its decode in ``PATHWAY_DECODE_STEP_BUCKET``
        chunks so the call RETURNS as soon as every row has finished
        instead of paying the full ``steps`` budget."""
        if not prompts:
            return []
        eos = self.eos_id if eos_id is _UNSET else eos_id
        if eos is not None and int(eos) == self.tokenizer.PAD:
            raise ValueError("eos_id must differ from the PAD token id")
        if self.family is not None:
            return self._generate_family(
                prompts, max_new_tokens, temperature, seed, eos
            )
        if use_kv if use_kv is not None else self._use_kv:
            return self._generate_kv(
                prompts, max_new_tokens, temperature, seed, eos=eos
            )
        with self._lock:
            n = len(prompts)
            from .encoder import _bucket

            b = _bucket(n)
            texts = [str(p) for p in prompts] + [""] * (b - n)
            L_budget = self.config.max_len - max_new_tokens
            ids, mask = self.tokenizer.encode_batch(texts, max_length=L_budget)
            pad = np.zeros((ids.shape[0], max_new_tokens), np.int32)
            ids = np.concatenate([ids, pad], axis=1)
            mask_full = np.concatenate([mask, pad], axis=1)
            # without EOS the whole budget is ONE chunk (the original
            # single-dispatch decode, unchanged); with EOS the budget is
            # split into step-bucket chunks so the host can stop as soon
            # as the finished mask covers every row
            chunk = (
                max_new_tokens if eos is None
                else min(max_new_tokens, decode_step_bucket())
            )
        # dispatch + fetch OFF the lock (lock-discipline: holding it across
        # the decode round trip serialized concurrent generates for the
        # full device latency); the lock only guards tokenization and the
        # compiled-fn cache
        t0 = time.perf_counter_ns()
        observe.record_occupancy("generator", n, b)
        ids_d = jnp.asarray(ids)
        mask_d = jnp.asarray(mask_full)
        pos_d = jnp.asarray(mask.sum(axis=1).astype(np.int32))
        rng = jax.random.PRNGKey(seed)
        # bucket-padding rows start FINISHED: their output is discarded,
        # and leaving them live would keep the all-finished early exit
        # from ever firing on a real EOS-heavy batch
        fin = jnp.asarray(np.arange(ids.shape[0]) >= n) if eos is not None \
            else jnp.zeros((ids.shape[0],), bool)
        eos_t = jnp.int32(-1 if eos is None else eos)
        temp_t = jnp.float32(temperature)
        out_chunks: List[np.ndarray] = []
        steps_run = 0
        while steps_run < max_new_tokens:
            # the tail chunk is sized EXACTLY to the remaining budget
            # (one extra compile signature per distinct remainder, both
            # bounded by the step bucket) — the decode never runs, nor
            # reports, more steps than max_new_tokens
            c = min(chunk, max_new_tokens - steps_run)
            with self._lock:
                fn = self._decode_fn(ids.shape[0], ids.shape[1], c)
            toks_c, ids_d, mask_d, pos_d, rng, fin = retry_call(
                "generator.dispatch",
                fn,
                self.params,
                ids_d,
                mask_d,
                pos_d,
                temp_t,
                rng,
                fin,
                eos_t,
            )
            out_chunks.append(np.asarray(toks_c))
            steps_run += c
            # EOS early-exit: every row finished — the remaining budget
            # would be all-PAD no-op iterations, so return now
            if eos is not None and bool(np.asarray(fin).all()):
                break
        self.last_decode_steps = steps_run
        toks = np.concatenate(out_chunks, axis=1)[:n, :max_new_tokens]
        _H_READY.observe_ns(time.perf_counter_ns() - t0)
        return [self.render_tokens(row) for row in toks]

    def __call__(self, prompts: Sequence[str], **kwargs) -> List[str]:
        return self.generate(prompts, **kwargs)

"""Tier 2: generator prefix/KV reuse — content-addressed prefill blocks.

RAG prompts share long prefixes (system prompt + retrieved chunks vary
far more slowly than the trailing question), and under causal attention
a token's K/V depends ONLY on the tokens at or before it — so the K/V
of a shared prefix is a pure function of that prefix's token ids and can
be computed once and reused by every prompt that starts with it (the
paged-KV / prefix-caching design arxiv 2412.15246 credits with the
generator-side RAG speedup).

Storage is BLOCK-granular: prompt token ids are split into fixed-size
blocks (``PATHWAY_CACHE_KV_BLOCK``, default 32) and each block's K/V
``[n_layers, block, heads, head_dim]`` (device-resident, never fetched)
is stored under a hash CHAIN key — ``key[j] = H(key[j-1] || block_j
tokens)`` (cache/keys.py) — so a block's key commits to the entire
prefix before it, two prompts sharing ``m`` blocks share exactly
``m`` entries, and no entry can ever be reused under a different
prefix.  Lookup walks the chain until the first miss; the generator
prefills only the remainder.

Only FULL blocks of real (non-pad) tokens are cached, and at least one
real suffix token is always left for the prefill (the decode needs the
last prompt position's hidden state, which K/V blocks do not carry).

A model whose layers also hold recurrent state (models/hybrid.py) can
restart a prompt only where that state is known, so a block's value may
carry, after ``(k, v)``, a SNAPSHOT of the state at the block's end: further
parts of the same entry, counted in its bytes, evicted with it.  Snapshots
are filed only at the positions ``bucket_tokens`` can return; the generator
cuts a match back to the last such block that carries one.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Tuple

import numpy as np

from .. import config
from .keys import block_chain_keys
from .store import CacheTier, cache_enabled

__all__ = ["PrefixKVCache", "prefix_kv_cache_from_env"]


class PrefixKVCache:
    def __init__(
        self,
        block: Optional[int] = None,
        max_bytes: Optional[int] = None,
        ttl_s: Optional[float] = None,
    ):
        if block is None:
            block = config.get("cache.kv_block")
        if max_bytes is None:
            max_bytes = config.get("cache.kv_bytes")
        if ttl_s is None:
            ttl = config.get("cache.kv_ttl_s")
            ttl_s = ttl if ttl > 0 else None
        self.block = max(1, int(block))
        self._tier = CacheTier("generator_kv", max_bytes=max_bytes, ttl_s=ttl_s)
        # prefill-token accounting for the sub-linearity claim: reused =
        # prompt tokens served from cached K/V, computed = tokens the
        # prefill actually ran the trunk over
        self.stats_tokens = {"reused": 0, "computed": 0}
        # state snapshots admitted beside blocks, and their bytes
        self.stats_state = {"snapshots": 0, "bytes": 0}
        from .. import observe

        observe.register_provider(self)
        # HBM ledger (observe/hbm.py): prefill K/V blocks are device
        # arrays — the tier's byte accounting is resident HBM, and the
        # byte budget is the exhaustion-ETA capacity
        from ..observe import hbm

        hbm.track(
            "cache", self, lambda c: {"prefill_blocks": c._tier.bytes}
        )
        hbm.track_resource(
            "prefill_cache_bytes",
            self,
            lambda c: c._tier.bytes,
            lambda c: c._tier.max_bytes,
        )

    @property
    def stats(self):
        return self._tier.stats

    def __len__(self) -> int:
        return len(self._tier)

    def clear(self) -> None:
        self._tier.clear()

    @staticmethod
    def snapshot(value) -> tuple:
        """The state snapshot a block's value carries after ``(k, v)``: its parts, or ``()``."""
        return tuple(value[2:])

    # -- lookup --------------------------------------------------------------
    def cacheable_blocks(self, n_real: int) -> int:
        """How many full blocks of a prompt with ``n_real`` real tokens
        are cacheable: full real blocks, minus one block if the prompt
        ends exactly on a boundary (the prefill must keep >= 1 real
        token to produce the first decode logits)."""
        n_blocks = n_real // self.block
        if n_blocks and n_blocks * self.block == n_real:
            n_blocks -= 1
        return n_blocks

    def bucket_tokens(self, n_matched: int) -> int:
        """Round a matched-prefix token count DOWN to a power-of-two
        block multiple.  The prefix split is a compile-shape dimension
        in every decode path (the batch KV decode's ``P`` and the
        continuous engine's per-join prefill) — bucketing keeps it at
        O(log) distinct values, so a mix of prompt families cannot
        compile one program per prefix length."""
        bucket = 0
        step = self.block
        while step <= int(n_matched):
            bucket = step
            step *= 2
        return bucket

    def match(
        self, ids_row: np.ndarray, n_real: int, deadline=None
    ) -> Tuple[int, List[Any], List[bytes]]:
        """Longest cached prefix of one prompt row: returns ``(n_tokens,
        blocks, keys)`` — the matched token count (a block multiple),
        the cached block values in order, and the chain keys of EVERY
        cacheable block (matched or not; the capture pass stores the
        missing tail under them)."""
        n_blocks = self.cacheable_blocks(int(n_real))
        keys = block_chain_keys(ids_row, n_blocks, self.block)
        blocks: List[Any] = []
        for key in keys:
            value = self._tier.get(key, deadline=deadline)
            if value is None:
                break
            blocks.append(value)
        return len(blocks) * self.block, blocks, keys

    # -- capture -------------------------------------------------------------
    def admit(
        self,
        keys: List[bytes],
        n_matched_blocks: int,
        get_block: Callable[[int], Any],
        deadline=None,
    ) -> int:
        """Store the blocks beyond the matched prefix.  ``get_block(j)``
        returns block ``j``'s K/V value (the generator slices it from
        the decode's returned buffers — an async device op, no fetch).
        Returns how many blocks were admitted."""
        admitted = 0
        # a chain's later blocks are reachable only through its earlier
        # ones, so it may not evict its own head: the matched blocks are
        # made most recently used, and no more is admitted than the budget
        # holds beside them and beside this chain's earlier admissions (a
        # deep model's block is tens of MB; a long prompt's tail would
        # otherwise push out the prefix it shares with the next prompt)
        room = self._tier.max_bytes - sum(
            self._tier.touch(key) for key in keys[:n_matched_blocks]
        )
        for j in range(n_matched_blocks, len(keys)):
            try:
                value = get_block(j)
            except Exception:
                self._tier._count("failures")
                break
            nbytes = sum(
                int(getattr(part, "nbytes", 64)) for part in value
            )
            room -= nbytes
            if room < 0:
                break
            if self._tier.put(keys[j], value, nbytes=nbytes, deadline=deadline):
                admitted += 1
                if self.snapshot(value):
                    self.stats_state["snapshots"] += 1
                    self.stats_state["bytes"] += sum(int(part.nbytes) for part in self.snapshot(value))
        return admitted

    def state_bytes(self) -> int:
        """Bytes of the state snapshots the tier holds now (a walk over its entries: for a read-out, not a hot path)."""
        return sum(int(part.nbytes) for _, value, _ in self._tier.warm_entries() for part in self.snapshot(value))

    def note_prefill(self, reused: int, computed: int) -> None:
        self.stats_tokens["reused"] += int(reused)
        self.stats_tokens["computed"] += int(computed)

    def observe_metrics(self):
        for kind, value in self.stats_tokens.items():
            yield (
                "counter",
                "pathway_cache_prefill_tokens_total",
                {**self._tier.labels, "kind": kind},
                value,
            )


def prefix_kv_cache_from_env() -> Optional[PrefixKVCache]:
    """Generator default: enabled unless ``PATHWAY_CACHE=0`` or
    ``PATHWAY_CACHE_KV=0`` (pure reuse of bit-reproducible K/V — the
    warm decode is bit-identical to the cold one, see
    models/generator.py)."""
    if not cache_enabled():
        return None
    if not config.get("cache.kv"):
        return None
    return PrefixKVCache()

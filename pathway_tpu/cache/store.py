"""The one bounded, thread-safe, metrics-instrumented cache store.

Every serve-cache tier (result / embedding / generator KV) is a
``CacheTier`` — an LRU dict bounded by a BYTE budget (entry count is a
secondary cap), with optional TTL, integrity fingerprints, and the
``cache.get`` / ``cache.put`` chaos sites wired through
``robust/inject.py``.  Design constraints, in order:

1. **A cache failure is a miss, never a failed or wrong serve.**  Every
   internal error on the lookup path — an armed chaos site, a corrupt
   entry (fingerprint mismatch), an expired TTL, a poisoned value —
   degrades to ``None`` (recompute); every error on the store path drops
   the entry.  The serve path cannot tell a broken cache from a cold one.
2. **Lookups stay off the serve locks** (the analyzer's lock-discipline
   rule): the tier's internal lock guards only dict/int operations —
   never a device dispatch, a fetch, or the chaos sites (``fire`` runs
   BEFORE the lock so an armed ``hang`` wedges only the calling request,
   not every cache user).
3. **Bounded by construction.**  ``max_bytes`` is enforced at put time
   with LRU eviction; values carry their own byte estimate (device
   arrays report ``.nbytes`` without a host sync).  TTL expiry is lazy
   (checked at get) plus opportunistic at put.
4. **One scrape surface.**  Each tier registers as a flight-recorder
   provider: ``pathway_cache_{hits,misses,evictions,insertions,
   corrupt,failures}_total{tier=...}`` counters plus
   ``pathway_cache_{bytes,entries}{tier=...}`` gauges render on the
   existing ``/metrics`` endpoint, and ``/serve_stats`` groups the
   ``tier``-labeled samples into a per-tier cache column.

The motivating numbers are in "Accelerating Retrieval-Augmented
Generation" (arxiv 2412.15246): production RAG query streams are
hot-headed across seconds-to-minutes windows, and the caching layer is
the dominant serving speedup once the dispatch path itself is tight.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional

from .. import config, observe
from ..observe import trace
from ..robust import log_once
from ..robust import inject

__all__ = ["CacheTier", "cache_enabled", "live_tiers"]

# every live tier, weakly: the online tuner (serve/tuner.py) walks this
# to retarget byte budgets on RUNNING tiers — a registry lookup at
# construction time only would strand long-lived caches on stale budgets
_LIVE_TIERS: "weakref.WeakSet[CacheTier]" = weakref.WeakSet()


def live_tiers() -> "List[CacheTier]":
    """Snapshot of every live ``CacheTier`` (tuner discovery surface)."""
    return list(_LIVE_TIERS)


def cache_enabled() -> bool:
    """Global kill switch: ``PATHWAY_CACHE=0`` disables every tier."""
    return config.get("cache.enabled")


def _default_nbytes(value: Any) -> int:
    """Byte estimate for budget accounting: device/numpy arrays report
    exactly (``.nbytes`` is metadata, not a host sync); containers
    recurse one level; everything else pays a flat floor."""
    nbytes = getattr(value, "nbytes", None)
    if nbytes is not None:
        return int(nbytes)
    if isinstance(value, (tuple, list)):
        return 64 + sum(_default_nbytes(v) for v in value)
    if isinstance(value, (bytes, str)):
        return 64 + len(value)
    return 64


class _Entry:
    __slots__ = ("value", "nbytes", "expires_at", "fingerprint")

    def __init__(self, value, nbytes, expires_at, fingerprint):
        self.value = value
        self.nbytes = nbytes
        self.expires_at = expires_at
        self.fingerprint = fingerprint


class CacheTier:
    """One LRU + byte-budget bounded tier behind the shared contract.

    ``fingerprint`` (optional) is a cheap pure function of a value used
    as an integrity check: computed at put, re-checked at get — a
    mismatch means the entry was corrupted in place, and the get
    degrades to a miss (and drops the entry) instead of serving a wrong
    result.  Only use it for host values; fingerprinting a device array
    would be a hidden sync."""

    def __init__(
        self,
        tier: str,
        max_bytes: int,
        ttl_s: Optional[float] = None,
        max_entries: Optional[int] = None,
        fingerprint: Optional[Callable[[Any], Any]] = None,
    ):
        self.tier = str(tier)
        self.max_bytes = int(max_bytes)
        self.ttl_s = float(ttl_s) if ttl_s else None
        self.max_entries = int(max_entries) if max_entries else None
        self._fingerprint = fingerprint
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Any, _Entry]" = OrderedDict()
        self._bytes = 0
        # plain ints under the tier lock; the recorder samples them at
        # scrape time through the provider registry
        self.stats: Dict[str, int] = {
            "hits": 0,
            "misses": 0,
            "insertions": 0,
            "evictions": 0,
            "expirations": 0,
            "corrupt": 0,
            "failures": 0,  # chaos/internal errors degraded to miss/drop
        }
        # per-instance `id` label: two live caches of the SAME tier (two
        # serve stacks, encoder-side + serve-side embedding tiers) must
        # not collapse into one Prometheus label set — duplicate label
        # sets fail the whole scrape (same rule as every other
        # per-instance series; see observe.next_id)
        self.labels = {"tier": self.tier, "id": str(observe.next_id())}
        observe.register_provider(self)
        _LIVE_TIERS.add(self)

    def _trace_note(self, op: str, outcome: str) -> None:
        """Hit/miss annotation on the active trace (observe/trace.py):
        one zero-duration span per cache operation, so a kept trace
        shows which tiers this request touched and how they answered.
        One context-var read when untraced."""
        t = trace.current()
        if t is not None:
            t.add_event("cache." + op, tier=self.tier, outcome=outcome)

    # -- the serve-facing contract ------------------------------------------
    def get(self, key: Any, deadline=None) -> Optional[Any]:
        """The cached value, or None.  EVERY failure mode — armed chaos
        site, expired TTL, corrupt entry, internal error — is a miss;
        the caller recomputes and the serve result stays correct."""
        try:
            # chaos site OUTSIDE the tier lock: an armed hang must wedge
            # only this request, never every cache user behind the lock
            inject.fire("cache.get", deadline=deadline)
        except Exception as exc:
            self._count("failures")
            self._count("misses")
            log_once(
                f"cache.get:{type(exc).__name__}",
                "cache get failed on tier %s (%r); degrading to recompute",
                self.tier,
                exc,
            )
            self._trace_note("get", "error")
            return None
        now = time.monotonic()
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                self.stats["misses"] += 1
                entry_state = "miss"
            elif entry.expires_at is not None and now >= entry.expires_at:
                self._drop_locked(key, entry)
                self.stats["expirations"] += 1
                self.stats["misses"] += 1
                entry_state = "expired"
            else:
                self._entries.move_to_end(key)
                value = entry.value
                fp = entry.fingerprint
                entry_state = "hit"
        if entry_state != "hit":
            self._trace_note("get", entry_state)
            return None
        if fp is not None:
            # integrity re-check OFF the lock (pure host compute): a
            # mutated-in-place entry must never become a wrong serve
            try:
                ok = self._fingerprint(value) == fp
            except Exception:
                ok = False
            if not ok:
                self.discard(key)
                self._count("corrupt")
                self._count("misses")
                log_once(
                    f"cache.corrupt:{self.tier}",
                    "corrupt cache entry on tier %s; dropped and recomputing",
                    self.tier,
                )
                self._trace_note("get", "corrupt")
                return None
        self._count("hits")
        self._trace_note("get", "hit")
        return value

    def put(
        self, key: Any, value: Any, nbytes: Optional[int] = None, deadline=None
    ) -> bool:
        """Insert (last-writer-wins).  A failure — chaos site, byte
        estimate error — drops the entry silently: the cache is an
        optimization, never a correctness dependency.  Values larger
        than the whole budget are refused (they would evict everything
        for one entry that LRU would then immediately rotate out)."""
        try:
            inject.fire("cache.put", deadline=deadline)
            size = int(nbytes) if nbytes is not None else _default_nbytes(value)
            fp = self._fingerprint(value) if self._fingerprint else None
        except Exception as exc:
            self._count("failures")
            log_once(
                f"cache.put:{type(exc).__name__}",
                "cache put failed on tier %s (%r); entry dropped "
                "(next lookup recomputes)",
                self.tier,
                exc,
            )
            self._trace_note("put", "dropped")
            return False
        if self.max_bytes <= 0:
            # a zero/negative budget DISABLES the tier (matching the TTL
            # knobs' `0 = off` convention) — it must never mean
            # "unbounded", which is what skipping the eviction loop
            # below would silently produce
            return False
        if size > self.max_bytes:
            return False
        expires = (
            time.monotonic() + self.ttl_s if self.ttl_s is not None else None
        )
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old.nbytes
            self._entries[key] = _Entry(value, size, expires, fp)
            self._bytes += size
            self.stats["insertions"] += 1
            while self._entries and (
                (self.max_bytes and self._bytes > self.max_bytes)
                or (self.max_entries and len(self._entries) > self.max_entries)
            ):
                k, e = self._entries.popitem(last=False)
                self._bytes -= e.nbytes
                self.stats["evictions"] += 1
        return True

    def touch(self, key: Any) -> int:
        """Make an entry the most recently used without reading it (no hit
        is counted); its bytes, 0 if it is not there."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                return 0
            self._entries.move_to_end(key)
            return entry.nbytes

    def __contains__(self, key: Any) -> bool:
        with self._lock:
            return key in self._entries

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    @property
    def bytes(self) -> int:
        return self._bytes

    def discard(self, key: Any) -> None:
        with self._lock:
            entry = self._entries.pop(key, None)
            if entry is not None:
                self._bytes -= entry.nbytes

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes = 0

    # -- durable warm state (serve/warmstate.py) -----------------------------
    def warm_entries(self) -> List[Any]:
        """LRU-ordered ``(key, value, nbytes)`` triples of the live,
        unexpired entries (oldest first, so a replay preserves eviction
        order).  Values are returned by REFERENCE — callers that need
        host-picklable payloads (the embedding tier's device rows)
        override this in the owning wrapper."""
        now = time.monotonic()
        with self._lock:
            return [
                (k, e.value, e.nbytes)
                for k, e in self._entries.items()
                if e.expires_at is None or now < e.expires_at
            ]

    def load_warm_entries(self, entries: List[Any]) -> int:
        """Replay ``warm_entries()`` triples through ``put`` (fingerprints
        recomputed, TTL clocks restart — a restored entry is as fresh as
        a just-inserted one).  Returns the number of entries accepted;
        a failed put is just a cold key, never an error."""
        loaded = 0
        for k, v, nbytes in entries:
            if self.put(k, v, nbytes=nbytes):
                loaded += 1
        return loaded

    # -- internals -----------------------------------------------------------
    def _drop_locked(self, key: Any, entry: _Entry) -> None:
        self._entries.pop(key, None)
        self._bytes -= entry.nbytes

    def _count(self, stat: str) -> None:
        with self._lock:
            self.stats[stat] += 1

    # -- flight-recorder provider -------------------------------------------
    def observe_metrics(self):
        labels = self.labels
        for stat in (
            "hits", "misses", "evictions", "insertions", "expirations",
            "corrupt", "failures",
        ):
            yield (
                "counter",
                f"pathway_cache_{stat}_total",
                labels,
                self.stats[stat],
            )
        yield ("gauge", "pathway_cache_bytes", labels, self._bytes)
        yield ("gauge", "pathway_cache_entries", labels, len(self._entries))
        yield (
            "gauge", "pathway_cache_max_bytes", labels, self.max_bytes
        )

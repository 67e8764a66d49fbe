"""Fixed-slot latency histogram + bounded event ring — the flight
recorder's storage primitives.

Both are built for the serving hot path: recording is a few integer ops
under a lock held only for the increment itself (never across a timing
section, a dispatch, or any other blocking call — the PR 2 lock-discipline
rules apply to this package too), and neither allocates per request.  The
histogram pre-allocates its count slots once; the ring pre-allocates its
slot list and overwrites in place.

Buckets are powers of two over nanoseconds: bucket ``i`` holds durations
in ``(2^(SHIFT+i-1), 2^(SHIFT+i)]`` ns with ``SHIFT = 10`` — the first
bucket tops out at ~1 µs and the second-to-last at ~2^40 ns ≈ 18 min; the
final bucket is the +Inf overflow.  Power-of-two bounds make the bucket
index one ``bit_length`` call (no search, no float math) and give uniform
relative resolution (every bucket is 2x the last), which is what latency
distributions need: the same histogram covers a 40 ns counter read and a
multi-second first-call compile without configuration.
"""

from __future__ import annotations

import threading
import time
from typing import Iterable, List, Optional, Tuple

from . import _state

__all__ = ["LatencyHistogram", "EventRing", "N_BUCKETS", "bucket_bounds_s"]

N_BUCKETS = 32
_SHIFT = 10  # first bucket upper bound: 2^10 ns = 1.024 us


def _bucket_index(ns: int) -> int:
    """Bucket for a duration in ns: smallest ``i`` with ns <= 2^(SHIFT+i),
    clamped into [0, N_BUCKETS-1] (the last bucket is +Inf)."""
    if ns <= 0:
        return 0
    i = (int(ns) - 1).bit_length() - _SHIFT
    if i < 0:
        return 0
    if i >= N_BUCKETS - 1:
        return N_BUCKETS - 1
    return i


_BOUNDS_S = tuple((1 << (_SHIFT + i)) * 1e-9 for i in range(N_BUCKETS - 1))


def bucket_bounds_s() -> List[float]:
    """Upper bounds of the finite buckets, in seconds (the Prometheus
    ``le`` values; the +Inf bucket is implicit)."""
    return list(_BOUNDS_S)


class LatencyHistogram:
    """Fixed-slot power-of-two-bucket histogram over durations in ns.

    ``observe_ns`` is the hot-path entry: one bucket-index computation and
    three integer increments under the instance lock.  ``snapshot``
    returns a consistent (counts, sum, count) view for rendering —
    cumulative bucket series are computed by the RENDERER from one
    snapshot, so scraped ``_bucket`` values are monotone by construction
    even while concurrent observes land.
    """

    __slots__ = ("_counts", "_sum_ns", "_n", "_lock", "_exemplars")

    def __init__(self) -> None:
        self._counts = [0] * N_BUCKETS
        self._sum_ns = 0
        self._n = 0
        self._lock = threading.Lock()
        # per-bucket exemplar slots (trace_id, value_s, unix_ts) — lazily
        # allocated on the first stamp, so histograms that never carry
        # exemplars (the overwhelming majority) pay one None field
        self._exemplars: Optional[List[Optional[Tuple[str, float, float]]]] = None

    def observe_ns(self, ns: int) -> None:
        if not _state.enabled:
            return
        ns = int(ns)
        # _bucket_index, inlined: this is the hottest line of the recorder
        i = (ns - 1).bit_length() - _SHIFT if ns > 0 else 0
        if i < 0:
            i = 0
        elif i > N_BUCKETS - 1:
            i = N_BUCKETS - 1
        with self._lock:
            self._counts[i] += 1
            self._sum_ns += ns
            self._n += 1

    def observe_s(self, seconds: float) -> None:
        self.observe_ns(int(seconds * 1e9))

    def snapshot(self) -> Tuple[Tuple[int, ...], int, int]:
        """(per-bucket counts, sum_ns, count) — one consistent view."""
        with self._lock:
            return tuple(self._counts), self._sum_ns, self._n

    def set_exemplar(self, ns: int, trace_id: str) -> None:
        """Stamp ``trace_id`` as the exemplar of the bucket a duration of
        ``ns`` lands in (newest-wins).  Called ONLY for traces the tail
        sampler kept, so every exemplar on /metrics resolves on /traces
        — the Dapper-style aggregate↔trace linkage."""
        i = _bucket_index(int(ns))
        with self._lock:
            if self._exemplars is None:
                self._exemplars = [None] * N_BUCKETS
            self._exemplars[i] = (str(trace_id), int(ns) * 1e-9, time.time())

    def exemplars(self) -> Optional[List[Optional[Tuple[str, float, float]]]]:
        """Per-bucket exemplar snapshot (index-aligned with the counts),
        or None when this histogram never carried one."""
        with self._lock:
            if self._exemplars is None:
                return None
            return list(self._exemplars)

    def reset(self) -> None:
        with self._lock:
            self._counts = [0] * N_BUCKETS
            self._sum_ns = 0
            self._n = 0
            self._exemplars = None

    def merge_from(self, other: "LatencyHistogram") -> None:
        """Element-wise accumulate ``other`` into this histogram (shard
        aggregation: per-thread or per-process histograms sum exactly —
        identical buckets make the merge a vector add)."""
        counts, sum_ns, n = other.snapshot()
        with self._lock:
            for i, c in enumerate(counts):
                self._counts[i] += c
            self._sum_ns += sum_ns
            self._n += n

    @property
    def count(self) -> int:
        return self._n

    @property
    def sum_seconds(self) -> float:
        return self._sum_ns * 1e-9

    def quantile_s(self, q: float) -> Optional[float]:
        """Upper-bound estimate of the ``q`` quantile in seconds (the
        bucket boundary where the cumulative count crosses ``q * n``);
        None when empty.  The overflow bucket reports the largest finite
        bound — an explicit floor, not a fabricated value."""
        counts, _sum_ns, n = self.snapshot()
        if n == 0:
            return None
        target = q * n
        cum = 0
        for i, c in enumerate(counts):
            cum += c
            if cum >= target:
                return _BOUNDS_S[min(i, N_BUCKETS - 2)]
        return _BOUNDS_S[-1]


class EventRing:
    """Bounded ring of per-request events: ``capacity`` pre-allocated
    slots overwritten in place (no per-request allocation beyond the
    event tuple itself), newest-wins.  ``snapshot`` returns the retained
    events oldest -> newest plus the total-appended counter, so a reader
    can tell how many were overwritten."""

    __slots__ = ("_slots", "_n", "_lock", "capacity")

    def __init__(self, capacity: int = 256) -> None:
        self.capacity = int(capacity)
        self._slots: List[Optional[tuple]] = [None] * self.capacity
        self._n = 0
        self._lock = threading.Lock()

    def append(self, event: tuple) -> None:
        if not _state.enabled:
            return
        with self._lock:
            self._slots[self._n % self.capacity] = event
            self._n += 1

    def snapshot(self) -> Tuple[List[tuple], int]:
        with self._lock:
            n = self._n
            if n <= self.capacity:
                events = [e for e in self._slots[:n]]
            else:
                head = n % self.capacity
                events = [
                    e
                    for e in self._slots[head:] + self._slots[:head]
                    if e is not None
                ]
            return events, n

    @property
    def dropped(self) -> int:
        """How many appended events have been overwritten (the ring's
        drop count, rendered on pathway_observe_events_dropped_total)."""
        with self._lock:
            return max(0, self._n - self.capacity)

    def reset(self) -> None:
        with self._lock:
            self._slots = [None] * self.capacity
            self._n = 0

    def __len__(self) -> int:
        with self._lock:
            return min(self._n, self.capacity)

"""Dispatch/fetch accounting hook for the serving hot path.

The serve budget is a count of host syncs, not FLOPs ("a retrieve+rerank
serve call issues exactly two device dispatches and two host fetches in
steady state").  Timing can't prove that on CPU CI, so the
serving paths report every compiled-function launch and every device→host
result copy here; tests and bench install a counter around a steady-state
call and assert on ground truth instead of wall clock.

Two consumers share each report:

- the **flight recorder** (``pathway_tpu/observe``) — ALWAYS on: every
  dispatch/fetch increments the ``pathway_serve_dispatches_total`` /
  ``pathway_serve_fetches_total`` counters on the scrape endpoint, so the
  budget is continuously visible in production, not only under a test;
- an **installed ``DispatchCounter``** — the test/bench assertion hook,
  still a no-op dict read when none is installed.

Per-shard-group accounting (the sharded serve path): a scatter-dispatch
fan-out launches one kernel per index shard plus a merge, but the batch
still pays ONE wire round trip — the per-shard launches overlap on their
own devices and only the merged output is fetched.  Reporting sites pass
``shards=N`` for such a group; the counter books it as ONE **logical**
dispatch (what the 2+2 budget is stated in) while ``physical_dispatches``
accumulates the real launch count (``N``), and the recorder exports the
physical count on ``pathway_serve_shard_dispatches_total`` so fan-out
width stays visible in production.  ``mode="physical"`` flips the
headline ``dispatches``/``fetches`` attributes to the physical counts
for tests that want to pin the fan-out width itself.

Thread-safety: each ``DispatchCounter`` carries its OWN lock (the old
module-global lock serialized unrelated counters and the ``_active`` read
happened outside it), and ``events`` is bounded — a long soak under an
installed counter keeps the first ``max_events`` events and counts the
rest in ``events_dropped`` instead of growing without bound.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

from .. import observe
from ..observe import trace as _trace

__all__ = ["DispatchCounter", "install", "uninstall", "record_dispatch", "record_fetch"]

_install_lock = threading.Lock()
_active: Optional["DispatchCounter"] = None

# pre-resolved recorder counters per tag (tags are a small fixed set of
# serve-path literals; the cache makes the always-on path two dict reads
# + one locked increment)
_obs_counters: Dict[Tuple[str, str], observe.Counter] = {}


def _obs_counter(kind: str, tag: str) -> observe.Counter:
    key = (kind, tag)
    c = _obs_counters.get(key)
    if c is None:
        c = _obs_counters[key] = observe.counter(
            f"pathway_serve_{kind}es_total", tag=tag
        )
    return c


def _obs_shard_counter(kind: str, tag: str) -> observe.Counter:
    key = (f"shard_{kind}", tag)
    c = _obs_counters.get(key)
    if c is None:
        c = _obs_counters[key] = observe.counter(
            f"pathway_serve_shard_{kind}es_total", tag=tag
        )
    return c


class DispatchCounter:
    """Counts device dispatches and host fetches on the serving paths.

    ``mode="logical"`` (default): a shard-group fan-out reported with
    ``shards=N`` counts as ONE dispatch/fetch — the number the 2+2
    per-batch budget is asserted against.  ``mode="physical"``: the
    headline counts are the real per-device launch counts.  Both modes
    always keep both views (``dispatches``/``fetches`` honor the mode;
    ``physical_dispatches``/``physical_fetches`` are always physical).
    """

    def __init__(self, max_events: int = 4096, mode: str = "logical") -> None:
        if mode not in ("logical", "physical"):
            raise ValueError(f"unknown accounting mode {mode!r}")
        self.max_events = int(max_events)
        self.mode = mode
        self.dispatches = 0
        self.fetches = 0
        self.physical_dispatches = 0
        self.physical_fetches = 0
        self.events: List[Tuple[str, str]] = []  # ("dispatch"|"fetch", tag)
        self.events_dropped = 0
        self._lock = threading.Lock()

    def _record(self, kind: str, tag: str, shards: int) -> None:
        physical = max(1, int(shards))
        logical = 1
        with self._lock:
            if kind == "dispatch":
                self.physical_dispatches += physical
                self.dispatches += (
                    physical if self.mode == "physical" else logical
                )
            else:
                self.physical_fetches += physical
                self.fetches += physical if self.mode == "physical" else logical
            if len(self.events) < self.max_events:
                self.events.append((kind, tag))
            else:
                self.events_dropped += 1

    def reset(self) -> None:
        with self._lock:
            self.dispatches = 0
            self.fetches = 0
            self.physical_dispatches = 0
            self.physical_fetches = 0
            self.events = []
            self.events_dropped = 0

    def snapshot(self) -> Tuple[int, int]:
        with self._lock:
            return self.dispatches, self.fetches

    def __enter__(self) -> "DispatchCounter":
        install(self)
        return self

    def __exit__(self, *exc) -> None:
        uninstall()


def install(counter: Optional[DispatchCounter] = None) -> DispatchCounter:
    global _active
    with _install_lock:
        _active = counter or DispatchCounter()
        return _active


def uninstall() -> None:
    global _active
    with _install_lock:
        _active = None


def record_dispatch(tag: str, shards: int = 1) -> None:
    """Report one LOGICAL dispatch.  ``shards > 1`` marks a shard-group
    fan-out: ``shards`` physical kernel launches that together cost the
    batch one round trip (scatter + per-shard search + merge).  The
    active trace (observe/trace.py), when one exists, gets the count
    stamped too — a kept trace carries its own 2+2 budget evidence."""
    _obs_counter("dispatch", tag).inc()
    if shards > 1:
        _obs_shard_counter("dispatch", tag).inc(shards)
    t = _trace.current()
    if t is not None:
        t.note_dispatch(tag, shards)
    c = _active
    if c is not None:
        c._record("dispatch", tag, shards)


def record_fetch(tag: str, shards: int = 1) -> None:
    _obs_counter("fetch", tag).inc()
    if shards > 1:
        _obs_shard_counter("fetch", tag).inc(shards)
    t = _trace.current()
    if t is not None:
        t.note_fetch(tag, shards)
    c = _active
    if c is not None:
        c._record("fetch", tag, shards)

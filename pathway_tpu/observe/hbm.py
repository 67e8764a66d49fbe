"""Central HBM attribution ledger — which subsystem owns device memory,
and when does it run out.

Every long-lived device-resident structure in the serve stack registers
here: the IVF resident slabs + exact tail, the forward index's row
buckets, the continuous-decode slot KV pool, the embedding-cache rows
and prefix-cache prefill blocks, the model parameter trees.  The ledger
is PULL-based — registration stores a weakref plus a byte-reporting
callback, and byte counts are read at sample time only (scrape,
``/serve_stats``, bench) — so the serve path pays nothing: absorbing a
batch, joining a slot, or evicting a cache row never touches the
ledger.  ``.nbytes`` on a jax array is metadata, not a host sync, so a
sample never blocks on the device either.

What a sample produces:

- ``pathway_hbm_bytes{subsystem,component}`` — per-structure gauges,
  summed across instances (two indexes both report ``ivf/resident``);
- ``pathway_hbm_total_bytes`` and ``pathway_hbm_watermark_bytes`` — the
  ledger total and its high-water mark (watermark advances at sample
  time: scrape cadence is the resolution);
- ``pathway_hbm_device_bytes`` — the BACKEND's own accounting
  (``device.memory_stats()["bytes_in_use"]`` where the platform
  provides it, the sum over ``jax.live_arrays()`` otherwise), the
  cross-check that catches an unregistered consumer: ledger ≈ device
  within tolerance or something is eating HBM off the books;
- ``pathway_hbm_resource_used/capacity`` and
  ``pathway_hbm_exhaustion_eta_seconds{resource}`` — for registered
  capacity-bounded resources (decode slots, forward-index rows, cache
  byte budgets), the observed growth rate over recent samples projected
  to exhaustion (-1 = not growing).

Degrade-never-fail: the ``hbm.ledger`` chaos site fires on the sample
path under an already-spent deadline — ANY armed fault yields the
last-known (stale) sample, counted on
``pathway_hbm_samples_dropped_total``, and a single misbehaving
registrant (raising callback, collected object) is skipped, never
poisoning the scrape or a serve.
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

from .recorder import counter, register_provider

__all__ = [
    "device_bytes",
    "ledger_stats",
    "reset",
    "sample",
    "track",
    "track_params",
    "track_resource",
    "tree_nbytes",
]

_C_DROPPED = counter("pathway_hbm_samples_dropped_total")

_lock = threading.Lock()
# byte registrants: (subsystem, weakref(obj), fn) with fn(obj) ->
# {component: bytes}
_tracked: List[Tuple[str, "weakref.ref", Callable[[Any], Dict[str, int]]]] = []
# capacity resources: (name, weakref(obj), used_fn, cap_fn)
_resources: List[
    Tuple[str, "weakref.ref", Callable[[Any], float], Callable[[Any], float]]
] = []
# per-resource growth history: name -> (t_s, used) of the previous
# sample, plus an EWMA of the growth rate in units/s
_growth: Dict[str, Tuple[float, float, float]] = {}

_watermark = 0
_last_sample: Optional[Dict[str, Any]] = None
_last_sample_t = 0.0

_inject_mod: Any = None


def _inject():
    global _inject_mod
    if _inject_mod is None:
        try:
            from ..robust import inject as mod
        except Exception:  # pragma: no cover - partial teardown
            return None
        _inject_mod = mod
    return _inject_mod


def _sample_allowed() -> bool:
    """Chaos gate (site ``hbm.ledger``): fired under a spent deadline so
    armed hangs release instantly; any firing = serve the stale sample."""
    inj = _inject()
    if inj is None or not inj.any_armed():
        return True
    try:
        from ..robust.deadline import Deadline

        before = inj.fired_count("hbm.ledger")
        inj.fire("hbm.ledger", deadline=Deadline.after_ms(0.0))
        return inj.fired_count("hbm.ledger") == before
    except Exception:
        return False


def tree_nbytes(tree: Any) -> int:
    """Total ``.nbytes`` over an arbitrary pytree-ish container of
    arrays (params dicts, tuples of buffers) — metadata only, no sync."""
    total = 0
    stack = [tree]
    while stack:
        x = stack.pop()
        nb = getattr(x, "nbytes", None)
        if nb is not None and not isinstance(x, (str, bytes)):
            total += int(nb)
        elif isinstance(x, dict):
            stack.extend(x.values())
        elif isinstance(x, (tuple, list)):
            stack.extend(x)
    return total


def track(
    subsystem: str,
    obj: Any,
    fn: Optional[Callable[[Any], Dict[str, int]]] = None,
) -> None:
    """Register ``obj`` as a device-memory owner under ``subsystem``.

    ``fn(obj)`` returns ``{component: bytes}``; the default calls
    ``obj.hbm_bytes()`` (int -> one ``total`` component, dict passed
    through).  Weakly held: a collected structure leaves the ledger on
    its own."""
    if fn is None:
        def fn(o):  # noqa: E306 - default byte reader
            got = o.hbm_bytes()
            return got if isinstance(got, dict) else {"total": int(got)}

    with _lock:
        _tracked.append((str(subsystem), weakref.ref(obj), fn))


def track_params(name: str, model: Any) -> None:
    """Register a model's parameter tree under ``params/<name>`` —
    params are usually the single largest resident allocation and the
    cross-check is meaningless without them."""
    track(
        "params",
        model,
        lambda m, _n=str(name): {_n: tree_nbytes(getattr(m, "params", None))},
    )


def track_resource(
    name: str,
    obj: Any,
    used_fn: Callable[[Any], float],
    cap_fn: Callable[[Any], float],
) -> None:
    """Register a capacity-bounded resource for exhaustion-ETA tracking
    (decode slots, forward-index rows, cache byte budgets).  Rates are
    derived from successive samples — absorb/join rates as actually
    observed, not as configured."""
    with _lock:
        _resources.append((str(name), weakref.ref(obj), used_fn, cap_fn))


def device_bytes() -> Optional[int]:
    """The backend's own resident-byte accounting, summed over the
    local devices (the ledger's owners may sit on any of them): TPU/GPU
    platforms report ``memory_stats()['bytes_in_use']``; the CPU backend
    doesn't, so fall back to summing ``jax.live_arrays()`` — every live
    buffer the backend still holds.  None when jax is unavailable."""
    try:
        import jax
    except Exception:  # pragma: no cover - jax always present in-tree
        return None
    try:
        total = sum(
            int((dev.memory_stats() or {}).get("bytes_in_use", 0))
            for dev in jax.local_devices()
        )
        if total:
            return total
    except Exception:
        pass
    try:
        return int(
            sum(int(getattr(a, "nbytes", 0)) for a in jax.live_arrays())
        )
    except Exception:
        return None


_EWMA_ALPHA = 0.5  # recent growth dominates: exhaustion is a NOW question
# growth observations closer together than this reuse the previous rate
# instead of updating the EWMA: back-to-back samples (a scrape that
# reads the ledger twice, several registrants landing in one pass) would
# otherwise inject zero-dt/zero-growth updates that halve the rate
_MIN_GROWTH_DT_S = 0.05


def _sample_resources(now_s: float) -> Dict[str, Dict[str, float]]:
    # aggregate used/capacity ACROSS registrants sharing a name first
    # (every shard of a ShardedForwardIndex registers "forward_rows",
    # every embedding cache its byte budget): growth is then derived
    # from ONE total per resource — per-registrant updates would
    # overwrite each other within a single pass and read as a huge
    # instantaneous growth spike
    totals: Dict[str, Tuple[float, float]] = {}
    with _lock:
        live = [
            (name, ref, used_fn, cap_fn)
            for name, ref, used_fn, cap_fn in _resources
            if ref() is not None
        ]
        _resources[:] = live
    for name, ref, used_fn, cap_fn in live:
        obj = ref()
        if obj is None:
            continue
        try:
            used = float(used_fn(obj))
            cap = float(cap_fn(obj))
        except Exception:
            continue  # one bad registrant never poisons the sample
        u0, c0 = totals.get(name, (0.0, 0.0))
        totals[name] = (u0 + used, c0 + cap)
    out: Dict[str, Dict[str, float]] = {}
    for name, (used, cap) in totals.items():
        prev = _growth.get(name)
        rate = 0.0
        if prev is not None:
            t_prev, used_prev, rate_prev = prev
            dt = now_s - t_prev
            if dt < _MIN_GROWTH_DT_S:
                # too soon to say anything about growth: keep the
                # previous observation point and rate untouched
                rate = rate_prev
                used_prev_kept = True
            else:
                inst = max(0.0, (used - used_prev) / dt)  # growth only
                rate = _EWMA_ALPHA * inst + (1 - _EWMA_ALPHA) * rate_prev
                used_prev_kept = False
        else:
            used_prev_kept = False
        if prev is None or not used_prev_kept:
            _growth[name] = (now_s, used, rate)
        headroom = max(0.0, cap - used)
        eta = headroom / rate if rate > 1e-9 else -1.0
        out[name] = {
            "used": used,
            "capacity": cap,
            "growth_per_s": rate,
            "exhaustion_eta_s": eta,
        }
    return out


def sample(max_age_s: float = 0.0) -> Dict[str, Any]:
    """Read every registrant and produce one ledger sample (also cached
    as the stale fallback for the chaos path).  Called at scrape time
    and on demand by tests/bench — never from the serve path.

    ``max_age_s > 0`` reuses the cached sample when it is fresh enough —
    a scrape that renders the provider gauges AND the ``/serve_stats``
    ``hbm`` column must not walk the registry (and, on CPU, sum
    ``jax.live_arrays()``) twice back to back."""
    global _watermark, _last_sample, _last_sample_t
    if (
        max_age_s > 0.0
        and _last_sample is not None
        and time.monotonic() - _last_sample_t < max_age_s
    ):
        return _last_sample
    if not _sample_allowed():
        _C_DROPPED.inc()
        if _last_sample is not None:
            return {**_last_sample, "stale": True}
        return {
            "stale": True, "subsystems": {}, "total_bytes": 0,
            "watermark_bytes": _watermark, "device_bytes": None,
            "resources": {},
        }
    now_s = time.monotonic()
    with _lock:
        live = [
            (subsystem, ref, fn)
            for subsystem, ref, fn in _tracked
            if ref() is not None
        ]
        _tracked[:] = live
    by_key: Dict[Tuple[str, str], int] = {}
    for subsystem, ref, fn in live:
        obj = ref()
        if obj is None:
            continue
        try:
            parts = fn(obj)
        except Exception:
            continue  # half-torn-down registrant: skip, never raise
        for component, nbytes in parts.items():
            key = (subsystem, str(component))
            by_key[key] = by_key.get(key, 0) + int(nbytes)
    total = sum(by_key.values())
    if total > _watermark:
        _watermark = total
    subsystems: Dict[str, Dict[str, int]] = {}
    for (subsystem, component), nbytes in sorted(by_key.items()):
        subsystems.setdefault(subsystem, {})[component] = nbytes
    doc = {
        "stale": False,
        "subsystems": subsystems,
        "total_bytes": total,
        "watermark_bytes": _watermark,
        "device_bytes": device_bytes(),
        "resources": _sample_resources(now_s),
    }
    _last_sample = doc
    _last_sample_t = time.monotonic()
    return doc


def ledger_stats() -> Dict[str, Any]:
    """The ``/serve_stats`` ``hbm`` column — reuses a fraction-of-a-
    second-fresh sample so one snapshot() never walks the ledger twice."""
    return sample(max_age_s=0.25)


class _Provider:
    """Flight-recorder provider: the ledger rendered as gauges on the
    one scrape surface."""

    def observe_metrics(self):
        doc = sample()
        for subsystem, parts in doc["subsystems"].items():
            for component, nbytes in parts.items():
                yield (
                    "gauge",
                    "pathway_hbm_bytes",
                    {"subsystem": subsystem, "component": component},
                    nbytes,
                )
        yield ("gauge", "pathway_hbm_total_bytes", {}, doc["total_bytes"])
        yield (
            "gauge", "pathway_hbm_watermark_bytes", {},
            doc["watermark_bytes"],
        )
        if doc["device_bytes"] is not None:
            yield (
                "gauge", "pathway_hbm_device_bytes", {}, doc["device_bytes"]
            )
        for name, row in doc["resources"].items():
            labels = {"resource": name}
            yield (
                "gauge", "pathway_hbm_resource_used", labels, row["used"]
            )
            yield (
                "gauge", "pathway_hbm_resource_capacity", labels,
                row["capacity"],
            )
            yield (
                "gauge",
                "pathway_hbm_exhaustion_eta_seconds",
                labels,
                row["exhaustion_eta_s"],
            )


_provider = _Provider()
register_provider(_provider)


def reset() -> None:
    """Drop every registration and the watermark (tests only — live
    structures re-register on construction, not on reset)."""
    global _watermark, _last_sample, _last_sample_t
    with _lock:
        _tracked.clear()
        _resources.clear()
    _growth.clear()
    _watermark = 0
    _last_sample = None
    _last_sample_t = 0.0

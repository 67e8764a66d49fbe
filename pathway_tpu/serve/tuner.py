"""The online knob tuner — the profile-guided loop, closed.

Every signal the observe plane exports (queue-wait histograms, pack
occupancy counters, cache hit/eviction counters, profiler share-of-wall)
already *describes* the knob that would fix it; this module is the small
controller that actually turns those knobs, bounded by the declarative
registry (``pathway_tpu/config.py``):

- ``serve.coalesce_us`` — from admission wait vs SLO headroom: a firing
  fast-burn window shrinks the coalescing window (latency pressure
  beats batching efficiency); ample headroom with the window binding
  (holds behind a full launch pipeline ended on it since the last tick,
  and the mean wait for a launch ~= window) grows it.
- ``decode.step_bucket`` — from decode-chunk occupancy: mostly-idle
  chunks halve the bucket, saturated chunks double it.
- ``cache.{result,embed,kv}_bytes`` — from marginal hit rate: a tier
  evicting while hits still climb is budget-bound (grow); a tier whose
  hits flatlined well under budget gives HBM back (shrink).  Applied to
  the registry AND retargeted onto every live ``CacheTier``.
- ``observe.profile_sample`` — from overhead share: sampling cost above
  ~1% of wall halves the fraction; negligible cost doubles it back.

Safety rails, in order:

1. **The registry is the authority.**  Every write goes through
   ``config.set``: clamped to the declared bounds, and ``static``-class
   knobs (everything a bit-identity oracle pins) raise
   ``StaticKnobError`` — the tuner counts the veto and moves on.  A
   controller bug cannot un-pin determinism.
2. **Reversible.**  Every adjustment is journaled; ``revert()`` restores
   the pre-tuner state (env/default layer), including live tier budgets.
3. **Degrade, never fail.**  The ``tuner.adjust`` chaos site is fired
   inside the tick; an injected fault reverts everything, freezes the
   tuner, and counts ``pathway_tuner_faults_total`` — a broken
   controller leaves the system exactly where static config had it.
4. **Observable.**  ``pathway_tuner_adjustments_total{knob,direction}``,
   ``pathway_tuner_vetoed_total``, ``pathway_tuner_faults_total``,
   and ``pathway_tuner_value{knob}`` gauges render on ``/metrics``.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

from .. import config, observe
from ..config import StaticKnobError
from ..robust import inject

__all__ = ["Tuner", "tuner_from_env"]

# knob the tuner writes for each cache tier name (store.py labels)
_TIER_KNOB = {
    "result": "cache.result_bytes",
    "embedding": "cache.embed_bytes",
    "generator_kv": "cache.kv_bytes",
}

# controller constants: gentle multiplicative steps — the registry
# clamps are the hard bounds, these keep single ticks small enough to
# revert cheaply
_GROW = 1.25
_SHRINK = 0.8
_OCC_LOW = 0.5       # decode chunk occupancy below this: bucket too wide
_OCC_HIGH = 0.85     # above this: bucket saturating, room to widen
_PROFILE_OVERHEAD_HIGH = 0.01   # sampling cost > 1% of wall: back off
_PROFILE_OVERHEAD_LOW = 0.001   # < 0.1%: cheap enough to sample more
_PROFILE_SAMPLE_COST_S = 5e-6   # per-sample bookkeeping estimate


class Tuner:
    """Background controller over the registry's ``dynamic`` knobs.

    ``tick()`` is the whole control loop (call it directly in tests);
    ``start()`` runs it on a daemon thread every ``interval_s``."""

    def __init__(self, interval_s: Optional[float] = None):
        if interval_s is None:
            interval_s = config.get("tuner.interval_s")
        self.interval_s = float(interval_s)
        # journal of (knob, had_override, previous_override) in apply
        # order — revert() unwinds it newest-first
        self._journal: List[Tuple[str, bool, Any]] = []
        self._journaled: set = set()
        self._tier_bytes0: Dict[int, Tuple[Any, int]] = {}
        self._frozen = False
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # last-tick signal snapshots (deltas drive the controllers)
        self._last: Dict[str, Any] = {}
        self.stats = {"ticks": 0, "adjustments": 0, "vetoes": 0, "faults": 0}

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "Tuner":
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, name="pathway-tuner", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=self.interval_s + 5.0)
        self._thread = None

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            self.tick()

    @property
    def frozen(self) -> bool:
        return self._frozen

    # -- the control loop ----------------------------------------------------
    def tick(self) -> int:
        """One pass over every controller; returns adjustments applied.
        Never raises: an injected/internal fault reverts all tuner state
        and freezes the loop (static config is the fallback plan)."""
        if self._frozen:
            return 0
        self.stats["ticks"] += 1
        try:
            inject.fire("tuner.adjust")
            n = 0
            n += self._tune_coalesce()
            n += self._tune_step_bucket()
            n += self._tune_cache_budgets()
            n += self._tune_profile_sample()
            return n
        except Exception:
            self.stats["faults"] += 1
            observe.count("pathway_tuner_faults_total")
            self.revert()
            self._frozen = True
            return 0

    def propose(self, knob: str, value: Any, direction: str) -> bool:
        """Route one adjustment through the registry: clamp, journal,
        count.  A ``static`` knob is vetoed (counted, False).  This is
        the ONLY write path controllers use."""
        try:
            before = config.overrides().get(knob)
            had = knob in config.overrides()
            applied = config.set(knob, value)
        except StaticKnobError:
            self.stats["vetoes"] += 1
            observe.count("pathway_tuner_vetoed_total", knob=knob)
            return False
        with self._lock:
            if knob not in self._journaled:
                self._journaled.add(knob)
                self._journal.append((knob, had, before))
        self.stats["adjustments"] += 1
        observe.count(
            "pathway_tuner_adjustments_total", knob=knob, direction=direction
        )
        observe.gauge("pathway_tuner_value", knob=knob).set(float(applied))
        return True

    def revert(self) -> None:
        """Restore the pre-tuner world: unwind every journaled override
        (newest-first) and re-point live tier budgets at their original
        ``max_bytes``."""
        with self._lock:
            journal = list(reversed(self._journal))
            self._journal.clear()
            self._journaled.clear()
            tier_bytes0 = dict(self._tier_bytes0)
            self._tier_bytes0.clear()
        for knob, had, before in journal:
            if had:
                try:
                    config.set(knob, before)
                except StaticKnobError:  # pragma: no cover - journal is dynamic-only
                    pass
            else:
                config.clear_override(knob)
        for ref, max_bytes in tier_bytes0.values():
            tier = ref()
            if tier is not None:
                tier.max_bytes = max_bytes

    # -- signals -------------------------------------------------------------
    def _delta(self, key: str, current: float) -> float:
        prev = self._last.get(key, 0.0)
        self._last[key] = current
        return current - prev

    def _admission_wait_mean_s(self) -> Optional[float]:
        """Mean wait for the launch that took a request (enqueue → its
        launch began: backlog + hold, WITHOUT the launch itself) over the
        last tick window (histogram delta), or None when no requests
        landed."""
        h = observe.histogram("pathway_serve_admission_wait_seconds")
        _, sum_ns, n = h.snapshot()
        d_sum = self._delta("aw_sum_ns", float(sum_ns))
        d_n = self._delta("aw_n", float(n))
        if d_n <= 0:
            return None
        return (d_sum / d_n) * 1e-9

    def _held_window_launches(self) -> float:
        """Shared batches, over every live scheduler, whose hold behind a
        full launch pipeline ended on its cap (the coalescing window or
        half a rider's budget): the only launches the window decides."""
        return sum(
            value
            for name, value in observe.snapshot()["counters"].items()
            if name.startswith("pathway_serve_queue_launches_total{")
            and 'release="held_window"' in name
        )

    def _slo_fast_burn(self) -> float:
        """Worst fast-window burn rate across latency SLOs (0 = all
        headroom, >= 1 = budget burning faster than allotted)."""
        try:
            from ..observe import slo

            report = slo.evaluate()
        except Exception:
            return 0.0
        worst = 0.0
        for row in (report.get("slos") or {}).values():
            fast = (row.get("windows") or {}).get("fast") or {}
            if fast.get("events"):
                worst = max(worst, float(fast.get("burn_rate") or 0.0))
        return worst

    def _occupancy(self, site: str) -> Optional[float]:
        """real/padded pack-row ratio for ``site`` over the last tick."""
        real = observe.counter(
            "pathway_serve_pack_rows_total", site=site, kind="real"
        ).value
        padded = observe.counter(
            "pathway_serve_pack_rows_total", site=site, kind="padded"
        ).value
        d_real = self._delta(f"occ_real_{site}", float(real))
        d_padded = self._delta(f"occ_padded_{site}", float(padded))
        if d_padded <= 0:
            return None
        return d_real / d_padded

    # -- controllers ---------------------------------------------------------
    def _tune_coalesce(self) -> int:
        window_us = float(config.get("serve.coalesce_us"))
        mean_wait = self._admission_wait_mean_s()
        capped = self._delta("held_window", self._held_window_launches())
        burn = self._slo_fast_burn()
        if burn >= 1.0:
            # latency budget burning: the window is the one knob that
            # trades batching for immediate latency — shrink it, floored
            # at 50us (below that coalescing is already off in practice;
            # decaying toward 0 would just journal no-op adjustments)
            if window_us <= 50.0:
                return 0
            return int(
                self.propose(
                    "serve.coalesce_us", max(window_us * 0.7, 50.0), "down"
                )
            )
        if (
            mean_wait is not None
            and burn < 0.5
            and window_us > 0
            and capped > 0
            and mean_wait * 1e6 >= 0.5 * window_us
        ):
            # headroom ample and the window itself is the binding wait
            # (where nothing is held on it, it decides no launch): grow it
            # for denser batches
            return int(
                self.propose(
                    "serve.coalesce_us", max(window_us * 1.3, 100.0), "up"
                )
            )
        return 0

    def _tune_step_bucket(self) -> int:
        occ = self._occupancy("generator")
        if occ is None:
            return 0
        bucket = int(config.get("decode.step_bucket"))
        if occ < _OCC_LOW and bucket > 1:
            return int(
                self.propose("decode.step_bucket", bucket // 2, "down")
            )
        if occ > _OCC_HIGH:
            return int(self.propose("decode.step_bucket", bucket * 2, "up"))
        return 0

    def _tune_cache_budgets(self) -> int:
        import weakref

        from ..cache.store import live_tiers

        n = 0
        for tier in live_tiers():
            knob = _TIER_KNOB.get(tier.tier)
            if knob is None:
                continue
            tag = f"tier_{tier.labels.get('id', tier.tier)}"
            d_hits = self._delta(f"{tag}_hits", float(tier.stats["hits"]))
            d_evict = self._delta(
                f"{tag}_evict", float(tier.stats["evictions"])
            )
            d_miss = self._delta(f"{tag}_miss", float(tier.stats["misses"]))
            budget = int(config.get(knob))
            direction = None
            if d_evict > 0 and d_hits > 0:
                # evicting while hits still climb: every evicted entry
                # was a future hit — the budget is the binding resource
                direction, factor = "up", _GROW
            elif (
                d_hits <= 0
                and d_miss <= 0
                and tier.bytes < budget // 2
                and budget > 1 << 20
            ):
                # idle tier holding a large budget: give the HBM back
                direction, factor = "down", _SHRINK
            if direction is None:
                continue
            if self.propose(knob, int(budget * factor), direction):
                key = id(tier)
                if key not in self._tier_bytes0:
                    self._tier_bytes0[key] = (
                        weakref.ref(tier),
                        tier.max_bytes,
                    )
                tier.max_bytes = int(config.get(knob))
                n += 1
        return n

    def _tune_profile_sample(self) -> int:
        from ..observe import profile

        samples = 0.0
        for row in profile.profile_stats().values():
            samples += float(row.get("samples", 0))
        d_samples = self._delta("profile_samples", samples)
        wall_s = max(self.interval_s, 1e-3)
        overhead = (d_samples * _PROFILE_SAMPLE_COST_S) / wall_s
        fraction = float(config.get("observe.profile_sample"))
        if overhead > _PROFILE_OVERHEAD_HIGH and fraction > 0.0:
            if self.propose(
                "observe.profile_sample", fraction * 0.5, "down"
            ):
                profile.set_sample(config.get("observe.profile_sample"))
                return 1
        elif (
            0.0 < overhead < _PROFILE_OVERHEAD_LOW
            and d_samples > 0
            and fraction < 1.0
        ):
            if self.propose(
                "observe.profile_sample", min(fraction * 2.0, 1.0), "up"
            ):
                profile.set_sample(config.get("observe.profile_sample"))
                return 1
        return 0


def tuner_from_env() -> Optional[Tuner]:
    """Start a background tuner when ``PATHWAY_TUNER=1``; the interval
    comes from ``PATHWAY_TUNER_INTERVAL_S``.  Returns the running tuner
    or None (the default: static config, no background thread)."""
    if not config.get("tuner.enabled"):
        return None
    return Tuner().start()

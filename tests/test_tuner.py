"""The online knob tuner (ISSUE 17): bounded, reversible, vetoed for
static knobs, observable, and degrade-never-fail under chaos.

Controllers are tested by driving their SIGNALS (histograms, pack
counters, tier stats) and asserting the knob moved the right direction
through the registry — no background thread, ``tick()`` is called
directly.
"""

from __future__ import annotations

import pytest

from pathway_tpu import config, observe
from pathway_tpu.cache.store import CacheTier
from pathway_tpu.observe import slo
from pathway_tpu.robust import inject
from pathway_tpu.serve.tuner import Tuner, tuner_from_env


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    import os

    for name in list(os.environ):
        if name.startswith("PATHWAY_"):
            monkeypatch.delenv(name)
    config.clear_overrides()
    observe.reset()
    # the SLO engine keeps its own burn windows: serves of a test file that
    # ran earlier in this process must not burn through the baseline tick
    slo.reset()
    inject.disarm()
    yield
    config.clear_overrides()
    observe.reset()
    inject.disarm()


def _counter_value(name, **labels):
    return observe.counter(name, **labels).value


# -- bounds ------------------------------------------------------------------

def test_propose_clamps_to_registry_bounds():
    t = Tuner(interval_s=0.01)
    assert t.propose("serve.coalesce_us", 10**12, "up")
    assert config.get("serve.coalesce_us") == 100000.0
    assert t.propose("decode.step_bucket", -5, "down")
    assert config.get("decode.step_bucket") == 1
    assert t.propose("decode.step_bucket", 10**6, "up")
    assert config.get("decode.step_bucket") == 128


def test_static_knob_vetoed_and_counted():
    t = Tuner(interval_s=0.01)
    before = _counter_value(
        "pathway_tuner_vetoed_total", knob="decode.kv_quant"
    )
    assert not t.propose("decode.kv_quant", "int8", "up")
    assert t.stats["vetoes"] == 1
    assert _counter_value(
        "pathway_tuner_vetoed_total", knob="decode.kv_quant"
    ) == before + 1
    # the registry is untouched — the veto happened before any write
    assert config.overrides() == {}


def test_adjustments_counted_by_knob_and_direction():
    t = Tuner(interval_s=0.01)
    assert t.propose("serve.coalesce_us", 3000, "up")
    assert t.propose("serve.coalesce_us", 1500, "down")
    assert _counter_value(
        "pathway_tuner_adjustments_total",
        knob="serve.coalesce_us", direction="up",
    ) == 1
    assert _counter_value(
        "pathway_tuner_adjustments_total",
        knob="serve.coalesce_us", direction="down",
    ) == 1
    assert observe.gauge(
        "pathway_tuner_value", knob="serve.coalesce_us"
    ).value == 1500.0


# -- reversal ----------------------------------------------------------------

def test_revert_restores_env_and_default_layer(monkeypatch):
    monkeypatch.setenv("PATHWAY_SERVE_COALESCE_US", "4000")
    t = Tuner(interval_s=0.01)
    assert t.propose("serve.coalesce_us", 9000, "up")
    assert t.propose("decode.step_bucket", 16, "up")
    assert config.get("serve.coalesce_us") == 9000.0
    t.revert()
    assert config.overrides() == {}
    assert config.get("serve.coalesce_us") == 4000.0  # env layer back
    assert config.get("decode.step_bucket") == 8      # default back


def test_revert_restores_live_tier_budgets():
    tier = CacheTier("result", max_bytes=1 << 20)
    tier.stats["hits"] = 50
    tier.stats["evictions"] = 10
    t = Tuner(interval_s=0.01)
    n = t.tick()
    assert n >= 1
    assert tier.max_bytes == config.get("cache.result_bytes") > 1 << 20
    t.revert()
    assert tier.max_bytes == 1 << 20
    assert config.overrides() == {}


# -- controllers -------------------------------------------------------------

def test_cache_budget_grows_on_evictions_with_hits():
    tier = CacheTier("result", max_bytes=1 << 20)
    t = Tuner(interval_s=0.01)
    t.tick()  # baseline snapshot (no deltas yet -> may or may not move)
    t.revert()
    tier.stats["hits"] += 100
    tier.stats["evictions"] += 20
    base = config.get("cache.result_bytes")
    assert t.tick() >= 1
    assert config.get("cache.result_bytes") > base
    assert tier.max_bytes == config.get("cache.result_bytes")


def test_cache_budget_shrinks_when_idle():
    tier = CacheTier("generator_kv", max_bytes=256 << 20)
    t = Tuner(interval_s=0.01)
    t.tick()
    t.revert()
    base = config.get("cache.kv_bytes")
    # no hits, no misses, bytes far under budget: reclaim
    assert t.tick() >= 1
    assert config.get("cache.kv_bytes") < base


def test_step_bucket_shrinks_on_low_occupancy():
    t = Tuner(interval_s=0.01)
    t.tick()  # baseline
    observe.record_occupancy("generator", real=2, padded=8)
    assert config.get("decode.step_bucket") == 8
    t.tick()
    assert config.get("decode.step_bucket") == 4


def test_step_bucket_grows_on_saturation():
    t = Tuner(interval_s=0.01)
    t.tick()
    observe.record_occupancy("generator", real=8, padded=8)
    t.tick()
    assert config.get("decode.step_bucket") == 16


def test_coalesce_shrinks_under_slo_burn(monkeypatch):
    from pathway_tpu.serve import tuner as tuner_mod

    t = Tuner(interval_s=0.01)
    monkeypatch.setattr(Tuner, "_slo_fast_burn", lambda self: 2.0)
    t.tick()
    assert config.get("serve.coalesce_us") < 2000.0


@pytest.mark.parametrize(
    "series, holds_capped, grows",
    [
        # the wait for a launch ~= the full window, holds ended on it, no
        # burn: the window binds
        ("pathway_serve_admission_wait_seconds", 1, True),
        # the same wait where no hold ended on the window (the queue formed
        # behind a busy scheduler thread): the window decided no launch
        ("pathway_serve_admission_wait_seconds", 0, False),
        # queue_wait CONTAINS the launch: a 1.9 ms launch is not a window
        ("pathway_serve_queue_wait_seconds", 1, False),
    ],
)
def test_coalesce_grows_only_when_the_window_binds(
    monkeypatch, series, holds_capped, grows
):
    class _Scheduler:
        """The launch counters a scheduler's provider exports."""

        held_window = 0

        def observe_metrics(self):
            yield (
                "counter", "pathway_serve_queue_launches_total",
                {"scheduler": "s", "release": "held_window"}, self.held_window,
            )

    monkeypatch.setattr(Tuner, "_slo_fast_burn", lambda self: 0.0)
    sched = _Scheduler()
    observe.register_provider(sched)
    t = Tuner(interval_s=0.01)
    t.tick()  # baseline snapshots
    sched.held_window += holds_capped
    h = observe.histogram(series)
    for _ in range(10):
        h.observe_s(0.0019)
    t.tick()
    assert (config.get("serve.coalesce_us") > 2000.0) == grows


def test_profile_sample_backs_off_under_overhead(monkeypatch):
    from pathway_tpu.observe import profile

    t = Tuner(interval_s=0.01)
    t.tick()
    monkeypatch.setattr(
        t, "_delta",
        lambda key, cur, _orig=t._delta: (
            1e6 if key == "profile_samples" else _orig(key, cur)
        ),
    )
    base = config.get("observe.profile_sample")
    t.tick()
    assert config.get("observe.profile_sample") < base
    # the live stride followed the knob
    assert profile.sample_stride() >= int(round(1.0 / base))


# -- chaos: degrade, never fail ---------------------------------------------

def test_injected_fault_freezes_and_reverts():
    tier = CacheTier("result", max_bytes=1 << 20)
    t = Tuner(interval_s=0.01)
    t.tick()
    t.revert()
    tier.stats["hits"] += 100
    tier.stats["evictions"] += 20
    assert t.tick() >= 1
    assert config.overrides() != {}
    before = _counter_value("pathway_tuner_faults_total")
    inject.load_env("tuner.adjust=raise")
    assert t.tick() == 0  # the fault is contained, not raised
    assert t.frozen
    assert config.overrides() == {}          # reverted
    assert tier.max_bytes == 1 << 20         # tier budget restored
    assert _counter_value("pathway_tuner_faults_total") == before + 1
    inject.disarm()
    assert t.tick() == 0  # frozen stays frozen: static config is the plan


# -- lifecycle ---------------------------------------------------------------

def test_tuner_from_env_default_off():
    assert tuner_from_env() is None


def test_tuner_from_env_starts_and_stops(monkeypatch):
    monkeypatch.setenv("PATHWAY_TUNER", "1")
    monkeypatch.setenv("PATHWAY_TUNER_INTERVAL_S", "0.05")
    t = tuner_from_env()
    try:
        assert t is not None and t._thread.is_alive()
        assert t.interval_s == 0.05
    finally:
        t.stop()
    assert t._thread is None

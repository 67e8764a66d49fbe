"""Process-wide flight recorder: the registry behind every
``pathway_serve_*`` / ``pathway_ivf_*`` / ``pathway_recompile_*`` /
``pathway_exchange_*`` series on the scrape endpoint.

Three ways data gets here, by cost profile:

- **histograms / counters** (hot path): instrumentation sites resolve
  their series object ONCE (module/instance scope) and call
  ``observe_ns`` / ``inc`` per event — a dict-free few-integer-ops
  update.  ``count(...)`` is the dynamic-label convenience for cold-ish
  sites (one dict lookup per call);
- **providers** (zero hot-path cost): long-lived objects (an IVF index,
  an exchange plane, a recompile tripwire) register themselves weakly
  and are asked for their current gauge/counter samples AT SCRAPE TIME
  only — live state costs nothing until someone looks;
- **event ring**: a bounded trace of recent serve-path events for the
  ``/serve_stats`` JSON view (capacity slots, overwrite-oldest).

``set_enabled(False)`` (or ``PATHWAY_OBSERVE=0``) turns every record
call into an early-return bool check (what it costs when on, measured on
the chip, is in the package docstring and PERF.md, ISSUE 24).  Rendering snapshots
each series before formatting, so scraped histogram buckets are
cumulative and monotone even under concurrent writes.
"""

from __future__ import annotations

import itertools
import threading
import time
import weakref
from typing import Any, Dict, Iterable, List, Optional, Tuple

from . import _state
from .histogram import EventRing, LatencyHistogram, bucket_bounds_s

__all__ = [
    "Counter",
    "Gauge",
    "count",
    "counter",
    "enabled",
    "gauge",
    "histogram",
    "next_id",
    "record_event",
    "register_provider",
    "render_prometheus",
    "reset",
    "set_enabled",
    "snapshot",
]

_LabelKey = Tuple[Tuple[str, str], ...]


def enabled() -> bool:
    return _state.enabled


def set_enabled(flag: bool) -> None:
    """Flip the recorder globally (bench's on/off A-B switch; production
    opt-out via PATHWAY_OBSERVE=0).  Disabled record calls early-return;
    already-recorded data stays and keeps rendering."""
    _state.enabled = bool(flag)


class Counter:
    """Monotone counter; ``inc`` is the hot-path entry (whole events, or
    seconds for the ``*_seconds_total`` families)."""

    __slots__ = ("_value", "_lock")

    def __init__(self) -> None:
        self._value = 0
        self._lock = threading.Lock()

    def inc(self, n: float = 1) -> None:
        if not _state.enabled:
            return
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return self._value

    def reset(self) -> None:
        with self._lock:
            self._value = 0


class Gauge:
    """Last-write-wins gauge for push-style values (prefer a provider
    for anything derivable from live object state)."""

    __slots__ = ("_value",)

    def __init__(self) -> None:
        self._value = 0.0

    def set(self, value: float) -> None:
        if not _state.enabled:
            return
        self._value = float(value)

    @property
    def value(self) -> float:
        return self._value

    def reset(self) -> None:
        self._value = 0.0


_registry_lock = threading.Lock()
_hists: Dict[str, Dict[_LabelKey, LatencyHistogram]] = {}
_counters: Dict[str, Dict[_LabelKey, Counter]] = {}
_gauges: Dict[str, Dict[_LabelKey, Gauge]] = {}
_providers: "weakref.WeakSet" = weakref.WeakSet()
_ring = EventRing(capacity=512)
_ids = itertools.count()


def next_id() -> int:
    """Process-unique small integer for the ``id`` label that uniquifies
    per-instance series (two encoders with the same model name must not
    collide into one Prometheus label set — duplicate label sets fail
    the whole scrape)."""
    return next(_ids)


def _label_key(labels: Dict[str, Any]) -> _LabelKey:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def histogram(name: str, **labels: Any) -> LatencyHistogram:
    """The (name, labels) histogram, created on first use.  Resolve once
    at module/instance scope and keep the reference — the per-event call
    is then ``h.observe_ns(dt)`` with no registry lookup."""
    key = _label_key(labels)
    with _registry_lock:
        series = _hists.setdefault(name, {})
        h = series.get(key)
        if h is None:
            h = series[key] = LatencyHistogram()
        return h


def counter(name: str, **labels: Any) -> Counter:
    key = _label_key(labels)
    with _registry_lock:
        series = _counters.setdefault(name, {})
        c = series.get(key)
        if c is None:
            c = series[key] = Counter()
        return c


def gauge(name: str, **labels: Any) -> Gauge:
    key = _label_key(labels)
    with _registry_lock:
        series = _gauges.setdefault(name, {})
        g = series.get(key)
        if g is None:
            g = series[key] = Gauge()
        return g


def count(name: str, n: int = 1, **labels: Any) -> None:
    """Dynamic-label counter increment (one registry lookup per call) —
    for sites whose label values vary at runtime (e.g. the batch bucket
    actually chosen)."""
    if not _state.enabled:
        return
    counter(name, **labels).inc(n)


# resolved occupancy-counter trios per (site, bucket): sites and buckets
# are small fixed sets, so this cache keeps the per-dispatch cost at one
# dict read + three locked increments instead of three _registry_lock
# acquisitions (a benign GIL race on first resolution hands back the
# same registered objects — counter() is idempotent)
_occ_cache: Dict[Tuple[str, int], Tuple[Counter, Counter, Counter]] = {}


def record_occupancy(site: str, real: int, padded: int) -> None:
    """Packing/batch occupancy accounting for one dispatch: ``real``
    rows of actual work inside ``padded`` bucketed rows, plus a counter
    on the bucket actually chosen.  Occupancy ratio = real/padded over
    any scrape window; bucket counters expose compile-shape churn."""
    if not _state.enabled:
        return
    key = (site, int(padded))
    trio = _occ_cache.get(key)
    if trio is None:
        trio = _occ_cache[key] = (
            counter("pathway_serve_pack_rows_total", site=site, kind="real"),
            counter("pathway_serve_pack_rows_total", site=site, kind="padded"),
            counter(
                "pathway_serve_batch_bucket_total", site=site, bucket=str(padded)
            ),
        )
    trio[0].inc(int(real))
    trio[1].inc(int(padded))
    trio[2].inc()


def record_event(kind: str, tag: str, dur_ns: int = 0, **extra: Any) -> None:
    """Append one serve-path event to the bounded ring (shown on
    ``/serve_stats``).  ``extra`` must be JSON-able scalars."""
    if not _state.enabled:
        return
    _ring.append((time.time(), kind, tag, int(dur_ns), extra or None))


def register_provider(obj: Any) -> None:
    """Weakly register an object exposing ``observe_metrics() ->
    iterable of (kind, name, labels_dict, value)`` with ``kind`` in
    {"gauge", "counter"}.  Sampled at scrape time only; a collected
    object silently drops out."""
    _providers.add(obj)


def _provider_samples() -> List[Tuple[str, str, _LabelKey, float]]:
    samples: List[Tuple[str, str, _LabelKey, float]] = []
    for obj in list(_providers):
        try:
            for kind, name, labels, value in obj.observe_metrics():
                samples.append((kind, name, _label_key(labels), float(value)))
        except Exception:
            # a half-torn-down provider (closed plane, dropped index)
            # must not take the scrape endpoint down with it
            continue
    samples.sort(key=lambda s: (s[1], s[2]))
    return samples


# -- rendering -----------------------------------------------------------
def _escape(value: str) -> str:
    return (
        str(value)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _fmt_labels(key: _LabelKey, extra: Optional[Tuple[Tuple[str, str], ...]] = None) -> str:
    pairs = list(key) + list(extra or ())
    if not pairs:
        return ""
    return "{" + ",".join(f'{k}="{_escape(v)}"' for k, v in pairs) + "}"


def _fmt_le(bound: float) -> str:
    return repr(bound)


def _fmt_exemplar(exemplars, i: int) -> str:
    """OpenMetrics exemplar suffix for bucket ``i`` ('' when none)."""
    if exemplars is None or exemplars[i] is None:
        return ""
    trace_id, value_s, ts = exemplars[i]
    return f' # {{trace_id="{_escape(trace_id)}"}} {repr(value_s)} {ts:.3f}'


def _ring_health() -> List[Tuple[str, int, int]]:
    """(ring, capacity, dropped) rows for every bounded ring: the serve
    event ring, the trace kept/pending stores, and — when a test/bench
    counter is installed — the dispatch counter's event buffer.  Drop
    counts were previously tracked but never rendered (ISSUE 9)."""
    rows: List[Tuple[str, int, int]] = [
        ("serve_events", _ring.capacity, _ring.dropped)
    ]
    try:
        from . import trace as _trace

        rows.extend(_trace.ring_stats())
    except Exception:  # pragma: no cover - partial teardown
        pass
    try:
        from ..ops import dispatch_counter as _dc

        active = _dc._active
        if active is not None:
            rows.append(
                ("dispatch_counter", active.max_events, active.events_dropped)
            )
    except Exception:  # pragma: no cover - partial teardown
        pass
    return rows


def _fmt_value(value: float) -> str:
    """Exact sample formatting: integral values render as integers
    (``%g`` would truncate to 6 significant digits — a bytes counter
    past ~1e6 would appear frozen across scrapes and rate() would read
    0), floats via repr (shortest exact form)."""
    if float(value).is_integer():
        return str(int(value))
    return repr(float(value))


def render_prometheus(openmetrics: bool = False) -> List[str]:
    """All recorder series in Prometheus text exposition format —
    appended to ``internals/metrics.py``'s ``render_metrics`` output so
    one scrape covers engine, connectors, and the serve flight recorder.
    Deterministic ordering (sorted names, sorted label sets) and one
    consistent snapshot per series.

    ``openmetrics=True`` additionally renders kept-trace exemplars on
    the histogram bucket samples.  Exemplar syntax is ONLY legal in the
    OpenMetrics exposition (negotiated via the Accept header and served
    as ``application/openmetrics-text``); a classic
    ``text/plain; version=0.0.4`` parser errors on the ``#`` token and
    the WHOLE scrape fails — so the classic rendering never carries
    them."""
    lines: List[str] = []
    bounds = bucket_bounds_s()

    with _registry_lock:
        hist_items = {
            name: dict(series) for name, series in _hists.items()
        }
        counter_items = {
            name: dict(series) for name, series in _counters.items()
        }
        gauge_items = {
            name: dict(series) for name, series in _gauges.items()
        }

    for name in sorted(hist_items):
        series = hist_items[name]
        if not series:
            continue
        lines.append(f"# TYPE {name} histogram")
        for key in sorted(series):
            h = series[key]
            counts, sum_ns, n = h.snapshot()
            # OpenMetrics exemplars: kept-trace ids stamped by the tail
            # sampler (observe/trace.py) onto the bucket their span
            # duration landed in — "# {trace_id=...} value ts" appended
            # to the bucket sample, so a p99 bucket links to /traces
            exemplars = h.exemplars() if openmetrics else None
            cum = 0
            for i, bound in enumerate(bounds):
                cum += counts[i]
                line = (
                    f"{name}_bucket"
                    f"{_fmt_labels(key, (('le', _fmt_le(bound)),))} {cum}"
                )
                lines.append(line + _fmt_exemplar(exemplars, i))
            inf_line = (
                f"{name}_bucket{_fmt_labels(key, (('le', '+Inf'),))} {n}"
            )
            lines.append(
                inf_line + _fmt_exemplar(exemplars, len(bounds))
            )
            lines.append(f"{name}_sum{_fmt_labels(key)} {sum_ns * 1e-9:.9f}")
            lines.append(f"{name}_count{_fmt_labels(key)} {n}")

    provider = _provider_samples()
    prov_counters: Dict[str, List[Tuple[_LabelKey, float]]] = {}
    prov_gauges: Dict[str, List[Tuple[_LabelKey, float]]] = {}
    for kind, name, key, value in provider:
        (prov_counters if kind == "counter" else prov_gauges).setdefault(
            name, []
        ).append((key, value))

    counter_names = sorted(set(counter_items) | set(prov_counters))
    for name in counter_names:
        rows = [
            (key, float(c.value)) for key, c in counter_items.get(name, {}).items()
        ] + prov_counters.get(name, [])
        if not rows:
            continue
        lines.append(f"# TYPE {name} counter")
        for key, value in sorted(rows):
            lines.append(f"{name}{_fmt_labels(key)} {_fmt_value(value)}")

    gauge_names = sorted(set(gauge_items) | set(prov_gauges))
    for name in gauge_names:
        rows = [
            (key, g.value) for key, g in gauge_items.get(name, {}).items()
        ] + prov_gauges.get(name, [])
        if not rows:
            continue
        lines.append(f"# TYPE {name} gauge")
        for key, value in sorted(rows):
            lines.append(f"{name}{_fmt_labels(key)} {_fmt_value(value)}")
    # bounded-ring health: the drop counters were tracked (event ring,
    # dispatch counter) but never rendered; a silently-saturating ring
    # reads as "nothing happened" exactly when the most is happening
    rings = _ring_health()
    lines.append("# TYPE pathway_observe_events_dropped_total counter")
    for ring, _capacity, dropped in rings:
        lines.append(
            f'pathway_observe_events_dropped_total{{ring="{ring}"}} {dropped}'
        )
    lines.append("# TYPE pathway_observe_ring_capacity gauge")
    for ring, capacity, _dropped in rings:
        lines.append(
            f'pathway_observe_ring_capacity{{ring="{ring}"}} {capacity}'
        )
    return lines


def _shard_sort_key(shard: str):
    try:
        return (0, int(shard))
    except ValueError:
        return (1, shard)


def snapshot() -> Dict[str, Any]:
    """JSON-able view for ``GET /serve_stats``: per-series histogram
    summaries (count/sum/p50/p95/p99 bucket-bound estimates), counters,
    gauges (provider-sampled), a per-shard column (every provider
    sample labeled ``shard=...`` grouped by shard id), a per-tier cache
    column (samples labeled ``tier=...`` — the pathway_tpu/cache
    hit/miss/evict/bytes families), a per-runner ingest column (samples
    labeled ``ingest=...`` — lag, pending docs, freshness quantiles),
    and the recent event ring."""
    with _registry_lock:
        hist_items = {name: dict(series) for name, series in _hists.items()}
        counter_items = {
            name: dict(series) for name, series in _counters.items()
        }
        gauge_items = {name: dict(series) for name, series in _gauges.items()}

    def series_name(name: str, key: _LabelKey) -> str:
        return name + _fmt_labels(key)

    hists = {}
    for name, series in hist_items.items():
        for key, h in series.items():
            counts, sum_ns, n = h.snapshot()
            hists[series_name(name, key)] = {
                "count": n,
                "sum_s": sum_ns * 1e-9,
                "p50_s": h.quantile_s(0.50),
                "p95_s": h.quantile_s(0.95),
                "p99_s": h.quantile_s(0.99),
            }
    counters = {
        series_name(name, key): c.value
        for name, series in counter_items.items()
        for key, c in series.items()
    }
    gauges = {
        series_name(name, key): g.value
        for name, series in gauge_items.items()
        for key, g in series.items()
    }
    # the shard column: any provider sample carrying a "shard" label is
    # ALSO grouped per shard id, so /serve_stats shows one row per shard
    # (resident vectors, tail size, skips, breaker state, forward docs)
    # without the reader having to parse Prometheus label strings.  The
    # remaining labels stay ON the per-shard key — several sharded
    # structures (two replicas' groups, a 1-shard vs 8-shard bench pair)
    # legitimately report the same metric for the same shard id, and
    # keying by bare metric name would let whichever provider iterates
    # last silently overwrite the others
    shards: Dict[str, Dict[str, float]] = {}
    # the cache column: provider samples labeled tier=... (the
    # pathway_tpu/cache tiers) grouped per tier, same shape as shards —
    # /serve_stats readers get hit/miss/evict/bytes per tier without
    # parsing Prometheus label strings
    caches: Dict[str, Dict[str, float]] = {}
    # the generator column: samples labeled generator=... (the
    # continuous-decode engines, serve/decode.py) grouped per engine —
    # slot occupancy, prefill/decode token counters, finished/evicted
    # requests, quarantined slots, per engine name
    generators: Dict[str, Dict[str, float]] = {}
    # the ingest column: samples labeled ingest=... (the live-ingest
    # runners, serve/ingest.py) grouped per runner — pending docs,
    # oldest-pending age, per-connector lag, freshness p50/p99 — so the
    # one scrape surface stays the single pane of glass for the
    # ingest+serve plane
    ingests: Dict[str, Dict[str, float]] = {}
    for kind, name, key, value in _provider_samples():
        target = counters if kind == "counter" else gauges
        target[series_name(name, key)] = value
        labels = dict(key)
        shard = labels.get("shard")
        if shard is not None:
            rest = tuple(
                (lk, lv) for lk, lv in key if lk != "shard"
            )
            shards.setdefault(shard, {})[series_name(name, rest)] = value
        tier = labels.get("tier")
        if tier is not None:
            rest = tuple((lk, lv) for lk, lv in key if lk != "tier")
            caches.setdefault(tier, {})[series_name(name, rest)] = value
        gen = labels.get("generator")
        if gen is not None:
            rest = tuple((lk, lv) for lk, lv in key if lk != "generator")
            generators.setdefault(gen, {})[series_name(name, rest)] = value
        ing = labels.get("ingest")
        if ing is not None:
            rest = tuple((lk, lv) for lk, lv in key if lk != "ingest")
            ingests.setdefault(ing, {})[series_name(name, rest)] = value
    events, total = _ring.snapshot()
    # the profile column: per-callable device-time attribution from the
    # sampling profiler (observe/profile.py — lazy import: profile
    # resolves its series through this module).  hbm and slo ride along:
    # the ledger sample and the current burn-rate document, so one
    # /serve_stats read answers "who owns device time, who owns HBM,
    # are we in budget" together
    profile_col: Dict[str, Any] = {}
    hbm_col: Dict[str, Any] = {}
    slo_col: Dict[str, Any] = {}
    try:
        from . import hbm as _hbm
        from . import profile as _profile
        from . import slo as _slo

        profile_col = _profile.profile_stats()
        hbm_col = _hbm.ledger_stats()
        slo_col = _slo.evaluate()
    except Exception:  # pragma: no cover - partial teardown
        pass
    return {
        "enabled": _state.enabled,
        "rings": {
            ring: {"capacity": capacity, "dropped": dropped}
            for ring, capacity, dropped in _ring_health()
        },
        "histograms": hists,
        "counters": counters,
        "gauges": gauges,
        "shards": {k: shards[k] for k in sorted(shards, key=_shard_sort_key)},
        "caches": {k: caches[k] for k in sorted(caches)},
        "generators": {k: generators[k] for k in sorted(generators)},
        "ingest": {k: ingests[k] for k in sorted(ingests)},
        "profile": profile_col,
        "hbm": hbm_col,
        "slo": slo_col,
        "events": [
            {
                "ts": e[0],
                "kind": e[1],
                "tag": e[2],
                "dur_ns": e[3],
                **(e[4] or {}),
            }
            for e in events
        ],
        "events_total": total,
    }


def reset() -> None:
    """Zero every registered series and the event ring WITHOUT dropping
    the series objects (instrumentation sites hold direct references;
    replacing the objects would silently detach them from the scrape
    output).  Tests and the bench overhead phase use this between runs."""
    with _registry_lock:
        for series in _hists.values():
            for h in series.values():
                h.reset()
        for series in _counters.values():
            for c in series.values():
                c.reset()
        for series in _gauges.values():
            for g in series.values():
                g.reset()
    _ring.reset()

"""Serve-path flight recorder — always-on, low-overhead observability
for the ML hot path.

PRs 1–2 made the fused retrieve→rerank serve fast (2 dispatches + 2
fetches) and statically safe; this package makes it *visible*: where a
serve call spends time (tokenize/pack on host, stage-1 dispatch→fetch
RTT, stage-2 rescore RTT, post-process), how full the packed batches
are, what the IVF index / recompile tripwires / exchange plane are doing
— without re-running ``bench.py``.  Multi-stage ranking systems live or
die by per-stage accounting (PAPERS.md: "An Exploration of Approaches to
Integrating Neural Reranking Models in Multi-Stage Ranking
Architectures"; "Accelerating Retrieval-Augmented Generation" names the
retrieval-vs-inference stage breakdown as the prerequisite for every
serving optimization).

Design constraints, in order:

1. **Nearly free.**  Fixed-slot power-of-two-bucket histograms (one
   ``bit_length`` + three increments per event), pre-resolved series
   objects on the hot sites, a bounded pre-allocated event ring, and
   scrape-time *providers* for anything derivable from live state.  What
   it costs on the chip (PERF.md, ISSUE 24; ``vs1m-query-open`` at 1,200
   requests/s, ``latency_p50_ms``): 6.15 ms with everything on against
   5.36-5.40 with ``PATHWAY_OBSERVE=0``, about 14% — the host is one GIL
   with the scheduler thread at 77% load, so every microsecond there
   costs several in the median.  One bracket costs 1.5 us (2.4 with a
   histogram) on that host; a thread-CPU clock read 5.9 us, which is why
   the CPU twin is sampled.
2. **Analyzer-clean.**  The recorder itself passes the PR 2
   lock-discipline / hidden-sync / recompile-hazard rules: locks are
   held only for integer updates, instrumentation points sit outside
   dispatch scopes, and nothing here touches jax at all.
3. **One surface.**  Everything renders on the existing scrape endpoint
   (``internals/metrics.py``): ``pathway_serve_*`` stage histograms,
   ``pathway_ivf_*`` index gauges, ``pathway_recompile_*`` census,
   ``pathway_exchange_*`` plane counters — plus a ``/serve_stats`` JSON
   view and OTLP spans via ``internals/telemetry.py`` when an endpoint
   is configured.
4. **One primitive per stage boundary** (``spans.py``): ``observe.span``
   brackets host work on the calling thread and feeds one pair of clock
   reads to the wall histogram, its thread-CPU twin, the active trace
   tree and a ``jax.profiler.TraceAnnotation("pw.<name>")`` on the device
   trace's clock; ``observe.interval`` records what crossed threads or
   was a wait (histogram + tree, no profiler event).

``PATHWAY_OBSERVE=0`` (or ``set_enabled(False)``) reduces every record
call to a bool check.

``trace`` (observe/trace.py) is the per-request layer on top: Dapper-
style span trees across the coalescing scheduler, shards, cascade
stages and cache tiers, tail-sampled into a bounded kept store served
on ``GET /traces``, with kept-trace exemplars stamped onto the
histogram buckets above.

``profile`` / ``hbm`` / ``slo`` (round 15) are the attribution layer:
sampled submit→ready device time per compiled callable
(``pathway_profile_*``), a pull-based HBM ledger cross-checked against
the backend's own byte accounting (``pathway_hbm_*``), and declarative
SLOs evaluated with multi-window burn-rate math (``pathway_slo_*`` +
``GET /slo`` + the scheduler's advisory ``should_shed`` probe).
"""

from .histogram import EventRing, LatencyHistogram, N_BUCKETS, bucket_bounds_s
from . import trace
from . import profile
from . import hbm
from . import slo
from .recorder import (
    Counter,
    Gauge,
    count,
    counter,
    enabled,
    gauge,
    histogram,
    next_id,
    record_event,
    record_occupancy,
    register_provider,
    render_prometheus,
    reset,
    set_enabled,
    snapshot,
)
from .spans import interval, serve_stage, span

__all__ = [
    "Counter",
    "EventRing",
    "Gauge",
    "LatencyHistogram",
    "N_BUCKETS",
    "bucket_bounds_s",
    "count",
    "counter",
    "enabled",
    "gauge",
    "hbm",
    "histogram",
    "interval",
    "next_id",
    "profile",
    "record_event",
    "record_occupancy",
    "register_provider",
    "render_prometheus",
    "reset",
    "serve_stage",
    "set_enabled",
    "slo",
    "snapshot",
    "span",
    "trace",
]

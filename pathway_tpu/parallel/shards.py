"""Serve-shard device groups: placement, routing, and per-shard health.

The single-host serving tier (rounds 5–10) is capped by one chip's HBM
and FLOPs no matter how many callers the scheduler coalesces; the
scale-out design (ROADMAP item 1; chip_smoke.py's four-chip leg checks
it on four real devices, the merge's cost is not measured) partitions
the index by DOCUMENT across a device group and fans the coalesced
stage-1 batch out to every shard:

- ``ShardGroup`` resolves the serve device group (``PATHWAY_SERVE_SHARDS``
  or an explicit count, over the local devices) and owns the one routing
  rule — ``owner_of(key)`` — that the sharded IVF index (ops/ivf.py) and
  the sharded forward index (index/forward.py) both use, so a document's
  postings AND its compressed token rows live on the SAME shard and the
  late-interaction rerank never crosses shards for data it doesn't need;
- per-shard ``CircuitBreaker``s: a shard that keeps failing its stage-1
  dispatch is skipped (degradation rung ``shard_skipped`` — recall on
  its partition is lost, the request never is) and probed back in on the
  breaker's half-open schedule;
- ``shard_skips`` / breaker state export as ``pathway_serve_shard_*``
  on the one scrape surface via the flight-recorder provider registry.

Shards may outnumber physical devices (round-robin reuse): tier-1 runs
on CPU with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so
the shard axis is real in tests, and a 16-way logical sharding over 8
chips is a capacity-planning knob, not an error.
"""

from __future__ import annotations

import threading
from typing import List, Optional, Sequence

import jax

from .. import config, observe
from ..observe import trace
from ..robust import CircuitBreaker

__all__ = ["FleetPartitionMap", "ShardGroup", "serve_shards"]


def serve_shards(default: int = 0) -> int:
    """Shard count from ``serve.shards`` (0 = every local device)."""
    return config.get("serve.shards", fallback=default)


class FleetPartitionMap:
    """The ONE routing rule lifted to fleet scope: ``doc_key %
    n_partitions`` names the fabric HOST that owns a document, exactly
    as ``ShardGroup.owner_of`` names the device shard inside one host.
    The two compose — a fleet of H hosts each running an S-way
    ``ShardGroup`` places a document first by ``FleetPartitionMap``
    (which host's IVF resident/tail slabs and forward-index row bucket
    hold it) and then by the host's own ``ShardGroup`` (which local
    device) — and because both levels spell the same stable modulo
    rule, owner-routed absorb, scatter-gather serve, and per-partition
    warm snapshots all agree on placement with zero coordination.

    Deliberately device-free: the front-end process holds no
    accelerators, only host links.
    """

    def __init__(self, n_partitions: int):
        if int(n_partitions) < 1:
            raise ValueError(
                f"FleetPartitionMap needs >= 1 partition, got {n_partitions}"
            )
        self.n_partitions = int(n_partitions)

    def __len__(self) -> int:
        return self.n_partitions

    def owner_of(self, key: int) -> int:
        """Owning PARTITION (fabric host index) of a document key —
        the fleet-level spelling of the one routing rule."""
        return int(key) % self.n_partitions

    def route(self, keys: Sequence[int]):
        """Positions of ``keys`` grouped by owning partition (the same
        bucket-loop contract as ``ShardGroup.route``; iterate
        ``sorted(...)`` for deterministic per-partition batches)."""
        buckets: dict = {}
        for i, key in enumerate(keys):
            buckets.setdefault(self.owner_of(int(key)), []).append(i)
        return buckets


class ShardGroup:
    """One serve device group: ``n_shards`` logical shards mapped onto
    the local devices (round-robin when shards outnumber devices), the
    document→shard routing rule, and per-shard circuit breakers.

    A group is SHARED by every sharded structure serving one corpus
    (IVF index, forward index, any future posting tier): ``owner_of``
    is the single source of placement truth, so co-partitioned data
    stays co-resident by construction.
    """

    def __init__(
        self,
        n_shards: Optional[int] = None,
        devices: Optional[Sequence] = None,
        name: Optional[str] = None,
    ):
        self.devices = list(devices if devices is not None else jax.devices())
        if not self.devices:
            raise ValueError("ShardGroup needs at least one device")
        n = n_shards or serve_shards() or len(self.devices)
        self.n_shards = max(1, int(n))
        self.name = name or f"shards-{observe.next_id()}"
        self._lock = threading.Lock()
        # per-shard breakers: persistent stage-1 failures on one shard
        # open ITS breaker only — the other shards keep serving, and the
        # half-open probe heals it without operator action
        self._breakers: List[CircuitBreaker] = [
            CircuitBreaker(f"{self.name}.shard{s}")
            for s in range(self.n_shards)
        ]
        # skip accounting per shard (dead dispatch, open breaker): the
        # pathway_serve_shard_skips_total{shard=...} counter family
        self.skips: List[int] = [0] * self.n_shards
        observe.register_provider(self)

    def __len__(self) -> int:
        return self.n_shards

    def device(self, shard: int):
        """The device hosting ``shard`` (round-robin past the physical
        count)."""
        return self.devices[shard % len(self.devices)]

    def owner_of(self, key: int) -> int:
        """Owning shard of a document key — THE routing rule.  Stable
        modulo hash so IVF postings, forward rows, and absorb traffic
        for one document all land on one shard."""
        return int(key) % self.n_shards

    def route(self, keys: Sequence[int]):
        """Positions of ``keys`` grouped by owning shard — the one
        bucket loop every sharded structure's ingest/remove path uses
        (iterate ``sorted(...)`` for deterministic per-shard batches)."""
        buckets: dict = {}
        for i, key in enumerate(keys):
            buckets.setdefault(self.owner_of(int(key)), []).append(i)
        return buckets

    def breaker(self, shard: int) -> CircuitBreaker:
        return self._breakers[shard]

    def record_skip(self, shard: int) -> None:
        with self._lock:
            self.skips[shard] += 1
        # annotate the active trace: a kept slow/degraded serve shows
        # WHICH shard it lost, next to the per-shard dispatch spans
        t = trace.current()
        if t is not None:
            t.add_event("shard.skip", shard=int(shard))

    # -- flight-recorder provider ------------------------------------------
    def observe_metrics(self):
        labels = {"group": self.name}
        yield ("gauge", "pathway_serve_shard_count", labels, self.n_shards)
        for s in range(self.n_shards):
            shard_labels = {**labels, "shard": str(s)}
            yield (
                "counter",
                "pathway_serve_shard_skips_total",
                shard_labels,
                self.skips[s],
            )
            yield (
                "gauge",
                "pathway_serve_shard_breaker_open",
                shard_labels,
                0.0 if self._breakers[s].state == "closed" else 1.0,
            )

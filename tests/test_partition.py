"""Cross-host index partitioning (ISSUE 20, serve/fabric.py).

The clean-path bar: an H=3 partitioned fleet — each host owning
``doc_key % 3`` of the corpus per ``FleetPartitionMap`` — serves
BIT-IDENTICALLY to H=1, exact and IVF-at-full-probe, through the
front-side scheduler at matched composition: the front merge
(``ops/topk.tree_merge_topk_host``) only PICKS among the owners' sorted
rows, never recomputes a score.  The ingest bar: a committed document is
owner-routed to exactly its owning host (absorb fans ×H), retrievable
only via its owner directly and fleet-wide after the merge.  The cache
bar: dedup/result keys carry the fleet generation VECTOR, so an absorb
on host B invalidates results cached via host A even when the fleet MAX
generation does not move.  The budget bar: 2 dispatches + 2 fetches per
batch on EACH host, with the scatter booked 1 logical + H physical.
"""

from __future__ import annotations

import itertools
import time

import jax.numpy as jnp
import pytest

from pathway_tpu import observe
from pathway_tpu.cache import ResultCache, normalize_generation, query_key
from pathway_tpu.models.encoder import SentenceEncoder
from pathway_tpu.ops import dispatch_counter
from pathway_tpu.ops.ivf import IvfKnnIndex
from pathway_tpu.ops.knn import DeviceKnnIndex
from pathway_tpu.ops.serving import FusedEncodeSearch
from pathway_tpu.parallel import FleetPartitionMap
from pathway_tpu.persistence.backends import MemoryBackend
from pathway_tpu.serve import (
    FabricWorker,
    LiveIngestRunner,
    ServeFabric,
    ServeScheduler,
    fabric_token,
)
from pathway_tpu.serve.warmstate import WarmStateManager

DOCS = {
    i: f"partition doc {i} about {topic} case {i % 7}"
    for i, topic in enumerate(
        [
            "key ownership", "vector indexes", "owner routing",
            "scatter gather", "generation vectors", "stream joins",
            "warm snapshots", "absorb throughput", "rag retrieval",
            "sharded state", "commit ticks", "partition maps",
        ]
        * 2
    )
}
QUERIES = ["owner routed absorb", "scatter gather merge",
           "generation vector keys", "warm partition restore"]

_ids = itertools.count()


def _names(n: int):
    """Fresh host names per fleet: fabric breakers live in the
    process-wide registry keyed by host name."""
    tag = next(_ids)
    return [f"part{tag}-{i}" for i in range(n)]


@pytest.fixture(scope="module")
def enc():
    return SentenceEncoder(
        dimension=32, n_layers=2, n_heads=4, max_length=32,
        vocab_size=512, dtype=jnp.float32,
    )


def _wait_gens(fabric, want, timeout=10.0):
    """Poll the fleet generation vector until it reaches ``want`` (a
    first-ever pong can lose a race with a 1s poll window)."""
    t_end = time.monotonic() + timeout
    gens = fabric.poll_generations()
    while gens != want and time.monotonic() < t_end:
        time.sleep(0.05)
        gens = fabric.poll_generations()
    return gens


def _build_index(enc, keys, docs, kind: str):
    if kind == "ivf":
        idx = IvfKnnIndex(dimension=32, metric="cos", n_clusters=2, n_probe=2)
        idx.add(keys, enc.encode([docs[i] for i in keys]))
        idx.build()
    else:
        idx = DeviceKnnIndex(dimension=32, metric="cos", initial_capacity=64)
        idx.add(keys, enc.encode([docs[i] for i in keys]))
    return idx


class _PartFleet:
    """H partition hosts (each: its OWNED slice of the corpus → fused
    search → scheduler → worker, optionally a live ingest runner) + one
    partitioned front fabric."""

    def __init__(self, enc, n, kind="exact", with_ingest=False,
                 indexes=None, docs=None):
        docs = docs if docs is not None else DOCS
        keys = sorted(docs)
        self.token = fabric_token()
        self.names = _names(n)
        self.indexes = []
        self.scheds = []
        self.runners = []
        self.workers = []
        pmap = FleetPartitionMap(n)
        for i in range(n):
            if indexes is not None:
                idx = indexes[i]
            else:
                owned = [k for k in keys if pmap.owner_of(k) == i]
                idx = _build_index(enc, owned, docs, kind)
            self.indexes.append(idx)
            fused = FusedEncodeSearch(enc, idx, k=8)
            sched = ServeScheduler(
                fused, window_us=0, result_cache=None,
                name=f"{self.names[i]}-s",
            )
            self.scheds.append(sched)
            runner = (
                LiveIngestRunner(enc, idx, name=f"{self.names[i]}-ing")
                if with_ingest
                else None
            )
            self.runners.append(runner)
            self.workers.append(
                FabricWorker(
                    sched, token=self.token, name=self.names[i],
                    ingest=runner,
                )
            )
        self.fabric = ServeFabric(
            {w.name: w.address for w in self.workers},
            self.token,
            name=f"pfab-{self.names[0]}",
            partitions=n,
        )

    def stop(self) -> None:
        self.fabric.stop()
        for w in self.workers:
            w.stop()
        for r in self.runners:
            if r is not None:
                r.stop()
        for s in self.scheds:
            s.stop()


# -- the ONE routing rule, lifted to the fleet --------------------------------


def test_fleet_partition_map_is_the_modulo_rule():
    pmap = FleetPartitionMap(3)
    assert len(pmap) == 3
    for key in range(20):
        assert pmap.owner_of(key) == key % 3
    buckets = pmap.route([0, 1, 2, 3, 4, 30, 100])
    assert buckets == {0: [0, 3, 5], 1: [1, 4, 6], 2: [2]}
    with pytest.raises(ValueError):
        FleetPartitionMap(0)


# -- clean-path bit-identity --------------------------------------------------


def _serve_solo(front, queries, k):
    return [front.serve([q], k=k) for q in queries]


def test_h3_exact_bit_identical_to_h1_through_scheduler(enc):
    """Acceptance: H=3 == H=1 on the exact index, each query served
    solo through a front-side scheduler on both sides (matched
    composition)."""
    fleet3 = _PartFleet(enc, 3, kind="exact")
    fleet1 = _PartFleet(enc, 1, kind="exact")
    front3 = ServeScheduler(fleet3.fabric, window_us=0, result_cache=None)
    front1 = ServeScheduler(fleet1.fabric, window_us=0, result_cache=None)
    try:
        assert fleet3.fabric.connect() == 3
        got3 = _serve_solo(front3, QUERIES, k=5)
        got1 = _serve_solo(front1, QUERIES, k=5)
        for q, r3, r1 in zip(QUERIES, got3, got1):
            assert list(r3) == list(r1), q  # floats: bit-equal
            assert r3.degraded == () and r1.degraded == ()
        assert fleet3.fabric.stats["ok"] == len(QUERIES)
        assert fleet3.fabric.stats["partition_lost"] == 0
    finally:
        front3.stop()
        front1.stop()
        fleet3.stop()
        fleet1.stop()


def test_h3_ivf_full_probe_bit_identical_to_h1(enc):
    """IVF at full probe: the per-partition IVF indexes score each owned
    document identically to the H=1 index, so the merge is bit-identical
    too — cluster geometry differs, scores do not."""
    fleet3 = _PartFleet(enc, 3, kind="ivf")
    fleet1 = _PartFleet(enc, 1, kind="ivf")
    try:
        got3 = fleet3.fabric.serve(QUERIES, k=5)
        got1 = fleet1.fabric.serve(QUERIES, k=5)
        assert list(got3) == list(got1)
        assert got3.degraded == ()
        assert got3.meta["fabric_partitions"] == 3
        # add() then build(): every partition sits at generation 2
        assert got3.meta["index_generation"] == (2, 2, 2)
    finally:
        fleet3.stop()
        fleet1.stop()


# -- owner-routed absorb ------------------------------------------------------


def test_absorb_routes_to_owner_only_and_is_fleet_visible(enc):
    new_key = 100  # owner = 100 % 3 = 1
    text = "owner routed absorb lands on its owner"
    fleet = _PartFleet(enc, 3, kind="exact", with_ingest=True)
    try:
        conn = fleet.fabric.connector("src0")
        conn.insert(new_key, text)
        assert conn.commit() == 1
        assert fleet.runners[1].flush(timeout=30.0)
        gens = _wait_gens(fleet.fabric, (1, 2, 1))
        assert gens == (1, 2, 1)  # only the owner absorbed
        # absorb ledger: the owner took the doc, nobody dropped any
        assert fleet.fabric._absorb_docs == [0, 1, 0]
        assert fleet.fabric._absorb_dropped == [0, 0, 0]
        # retrievable ONLY via the owner directly...
        for part, sched in enumerate(fleet.scheds):
            rows = sched.serve([text], k=8)
            has_doc = any(int(k) == new_key for k, _s in rows[0])
            assert has_doc == (part == 1), part
        # ...and fleet-wide through the merge
        got = fleet.fabric.serve([text], k=8)
        assert got.degraded == ()
        assert any(int(k) == new_key for k, _s in got[0])
    finally:
        fleet.stop()


def test_connector_requires_partitioned_fabric(enc):
    from tests.test_fabric import _Fleet  # replica-mode fleet

    fused = FusedEncodeSearch(
        enc, _build_index(enc, sorted(DOCS), DOCS, "exact"), k=8
    )
    replica_fleet = _Fleet(fused, n=1)
    try:
        with pytest.raises(RuntimeError):
            replica_fleet.fabric.connector()
        with pytest.raises(RuntimeError):
            replica_fleet.fabric.absorb([(1, "x", 0)])
    finally:
        replica_fleet.stop()


# -- generation-vector cache keys (satellite: absorb inside an open window) ---


def test_partition_absorb_invalidates_fleet_wide_cache_keys(enc):
    """The regression the VECTOR key exists for: host 0 is at generation
    3, host 1 at 1 — an absorb on host 1 moves the fleet MAX not at all,
    so a scalar max-generation cache key would serve the STALE result.
    The vector key changes on ANY partition's absorb; and an absorb
    landing inside an open coalescing window must keep that window's
    result out of the cache (dispatch-time generation != admission
    generation)."""
    q = "generation vector keys"
    fleet = _PartFleet(enc, 3, kind="exact", with_ingest=True)
    front = ServeScheduler(
        fleet.fabric, window_us=0, result_cache=ResultCache(),
        name="part-front",
    )

    def absorb(key, text):
        conn = fleet.fabric.connector("gen-src")
        conn.insert(key, text)
        assert conn.commit() == 1
        assert fleet.runners[key % 3].flush(timeout=30.0)
        return fleet.fabric.poll_generations()  # callers _wait_gens when exact

    try:
        # host 0 → generation 3 (two separate absorb batches); the fleet
        # max is now pinned by host 0
        absorb(30, "warmup absorb doc one")
        absorb(33, "warmup absorb doc two")
        gens = _wait_gens(fleet.fabric, (3, 1, 1))
        assert gens == (3, 1, 1)
        r1 = front.serve([q], k=5)
        assert not any(int(k) == 100 for k, _s in r1[0])
        # absorb on host 1 (owner of 100): max(gens) stays 3, the VECTOR
        # changes — the cached r1 must not survive.  The fresh documents ARE
        # the query: cosine 1, so they rank first whatever the encoder's
        # random weights make of the other 24 (a near-match text scored 0.69
        # against a fifth-best 0.73 and read as a stale result)
        absorb(100, q)
        gens = _wait_gens(fleet.fabric, (3, 2, 1))
        assert gens == (3, 2, 1)
        assert max(gens) == 3  # a scalar max key would NOT change
        r2 = front.serve([q], k=5)
        assert any(int(k) == 100 for k, _s in r2[0]), r2
        assert front.stats["cache_hits"] == 0
        # the window case: admit under the current vector, land an
        # absorb before the window dispatches — the result crossing the
        # generation boundary is served but never cached
        cache = ResultCache()
        slow_front = ServeScheduler(
            fleet.fabric, window_us=400_000, result_cache=cache,
            name="part-front-w",
        )
        try:
            ticket = slow_front.submit([q], k=5)
            absorb(103, q)  # inside the window
            gens = _wait_gens(fleet.fabric, (3, 3, 1))
            assert gens == (3, 3, 1)
            stale_risk = ticket.result(timeout=30.0)
            assert stale_risk  # served, never raised
            # stored under its admission key only if every owner dispatched
            # it at that generation (an absorb that outlasted the window)
            crossed = stale_risk.meta["index_generation"] != (3, 2, 1)
            stored = cache.get_rows([query_key(q, (3, 2, 1))], 5)
            assert (stored is None) == crossed, (stale_risk.meta, stored)
            r3 = slow_front.serve([q], k=5)
            assert any(int(k) == 103 for k, _s in r3[0]), r3
            assert slow_front.stats.get("cache_hits", 0) == 0
        finally:
            slow_front.stop()
    finally:
        front.stop()
        fleet.stop()


def test_index_generation_vector_normalizes_for_cache_keys(enc):
    fleet = _PartFleet(enc, 2, kind="exact")
    try:
        gens = _wait_gens(fleet.fabric, (1, 1))
        assert gens == (1, 1)
        assert normalize_generation(gens) == (1, 1)
        assert normalize_generation(list(gens)) == (1, 1)
        assert normalize_generation(7) == 7
    finally:
        fleet.stop()


# -- per-partition warm restore ----------------------------------------------


def test_per_partition_warm_restore_is_bit_identical(enc):
    """Each partition snapshots ONLY its owned slabs; a replacement
    fleet restored per-partition serves the same rows at the same
    generation vector."""
    fleet = _PartFleet(enc, 3, kind="ivf")
    backends = []
    try:
        want = fleet.fabric.serve(QUERIES, k=5)
        want_gens = fleet.fabric.index_generation()
        for i, idx in enumerate(fleet.indexes):
            backend = MemoryBackend()
            mgr = WarmStateManager(
                backend, name=f"part-{i}", components={"ivf": idx}
            )
            assert mgr.snapshot() is not None
            backends.append(backend)
    finally:
        fleet.stop()

    replicas = []
    for i, backend in enumerate(backends):
        replica = IvfKnnIndex(
            dimension=32, metric="cos", n_clusters=2, n_probe=2
        )
        report = WarmStateManager(
            backend, name=f"part-{i}", components={"ivf": replica}
        ).restore()
        assert report.restored, (i, report)
        replicas.append(replica)
    fleet2 = _PartFleet(enc, 3, indexes=replicas)
    try:
        got = fleet2.fabric.serve(QUERIES, k=5)
        assert list(got) == list(want)
        assert got.degraded == ()
        assert fleet2.fabric.index_generation() == want_gens
    finally:
        fleet2.stop()


# -- dispatch budget ----------------------------------------------------------


def test_partitioned_serve_keeps_two_plus_two_per_host(enc):
    """Acceptance: with partitioned serve + owner-routed absorb live,
    each host's per-batch budget stays 2 dispatches + 2 fetches, and
    the front books the scatter as ONE logical + H physical."""
    fleet = _PartFleet(enc, 3, kind="exact", with_ingest=True)
    try:
        q = QUERIES[0]
        fleet.fabric.serve([q], k=5)  # warm every host's compile
        conn = fleet.fabric.connector("budget-src")
        conn.insert(102, "absorb rides before the measured serve")
        assert conn.commit() == 1
        assert fleet.runners[0].flush(timeout=30.0)

        # per host: a solo batch through the host's own scheduler
        for sched in fleet.scheds:
            with dispatch_counter.DispatchCounter() as counter:
                sched.serve([q], k=5)
            assert counter.dispatches <= 2, counter.events
            assert counter.fetches <= 2, counter.events

        # fleet-wide: the scatter is 1 logical + H physical; each host
        # spends its own <=2+2 underneath
        with dispatch_counter.DispatchCounter() as counter:
            got = fleet.fabric.serve([q], k=5)
        assert got.degraded == ()
        disp = [t for kind, t in counter.events if kind == "dispatch"]
        fet = [t for kind, t in counter.events if kind == "fetch"]
        assert disp.count("fabric.scatter") == 1
        assert fet.count("fabric.gather") == 1
        # each of the 3 hosts served one solo batch inside its budget
        host_disp = [t for t in disp if t != "fabric.scatter"]
        host_fet = [t for t in fet if t != "fabric.gather"]
        assert len(host_disp) <= 3 * 2, counter.events
        assert len(host_fet) <= 3 * 2, counter.events
        # absorb is ingest routing, not a serve dispatch: never booked
        assert not any(t.startswith("partition.") for t in disp + fet)

        with dispatch_counter.DispatchCounter(mode="physical") as counter:
            fleet.fabric.serve([q], k=5)
        phys_disp = [t for kind, t in counter.events if kind == "dispatch"]
        assert phys_disp.count("fabric.scatter") == 1  # one EVENT ...
        assert counter.physical_dispatches >= 3  # ... H physical sends
    finally:
        fleet.stop()


# -- scrape surface -----------------------------------------------------------


def test_partition_metrics_reach_the_scrape_surface(enc):
    fleet = _PartFleet(enc, 2, kind="exact")
    try:
        fleet.fabric.serve([QUERIES[0]], k=5)
        snap = observe.snapshot()
        names = "\n".join(list(snap["counters"]) + list(snap["gauges"]))
        assert "pathway_partition_count" in names
        assert "pathway_partition_lost_total" in names
        assert "pathway_partition_absorb_docs_total" in names
        assert "pathway_partition_absorb_dropped_total" in names
    finally:
        fleet.stop()

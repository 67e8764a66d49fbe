"""Multi-process / multi-host execution — the comms backend.

Replaces the reference's timely TCP cluster (src/engine/dataflow/config.rs:
104-121 — PATHWAY_PROCESSES/PATHWAY_PROCESS_ID/PATHWAY_FIRST_PORT building a
``CommunicationConfig::Cluster``; zero-copy exchange in external/
timely-dataflow/communication/src/allocator/zero_copy/tcp.rs) with the
jax-native runtime: ``jax.distributed`` for process coordination (gRPC
coordination service hosted by process 0) and XLA collectives over ICI/DCN
for the data plane.

Execution model (the honest jax-native design, documented per-layer):

- **SPMD program, worker-sharded host plane.** Like the reference — where
  the user's script runs once per worker and each worker owns a shard
  (docs/2.developers/4.user-guide/80.advanced/10.worker-architecture.md:
  37-48) — every process builds the identical graph.  The host relational
  plane is SHARDED: each rank ingests its owned-key slice of every source
  (or its file split, for partitioned readers), stateful operators exchange
  rows by group/join key over the TCP exchange plane
  (``parallel/exchange.py``), and sinks gather to rank 0 for exactly-once
  output.  Commit timestamps are agreed per tick: ranks exchange
  (proposed_ts, moved, finished, stop) and deterministically adopt the max
  proposal (engine/executor.py ``_step_dist``).
- **Sharded device data plane.** Device-resident state (the KNN embedding
  matrix, model weights) lives on ONE global mesh spanning every process's
  devices (`global_mesh()`); each process addresses only its local shard.
  Exchange between shards is XLA collectives (all_gather/psum/ppermute)
  inside jit — the analog of timely's exchange channels — riding ICI within
  a slice and DCN across hosts, never the Python layer.  Operators that
  drive a multi-process mesh (external indexes) run REPLICATED on the host
  plane so every rank issues the same jit calls (SPMD discipline).

Topology env vars (set by ``pathway-tpu spawn`` — cli.py):
  PATHWAY_PROCESSES            total process count (default 1 — no-op)
  PATHWAY_PROCESS_ID           this process's rank
  PATHWAY_COORDINATOR_ADDRESS  host:port of process 0's coordination service

On CPU (tests / the virtual mesh) cross-process collectives use the gloo
backend; on TPU pods jax's default (device runtime over ICI/DCN) is used.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Dict, Optional

import jax

from .. import config, observe
from ..robust import log_once
from ..robust import inject as _inject

__all__ = [
    "topology_from_env",
    "maybe_initialize",
    "is_distributed",
    "process_id",
    "process_count",
    "is_coordinator",
    "barrier",
    "broadcast_obj",
]

logger = logging.getLogger(__name__)

_lock = threading.Lock()
_initialized = False


def topology_from_env() -> tuple[int, int, Optional[str]]:
    """(processes, process_id, coordinator_address) from PATHWAY_* env
    (reference: Config::from_env, src/engine/dataflow/config.rs:88-121)."""
    processes = config.get("parallel.processes")
    pid = config.get("parallel.process_id")
    addr = config.get("parallel.coordinator_address") or None
    if addr is None:
        first_port = config.get("parallel.first_port")
        if first_port:
            addr = f"127.0.0.1:{first_port}"
    return processes, pid, addr


def maybe_initialize() -> bool:
    """Join the process cluster if PATHWAY_PROCESSES > 1.  Idempotent; safe
    to call from ``pw.run()`` on every process.  Returns True when running
    distributed (after this call).

    Must run before the first jax backend touch in this process."""
    global _initialized
    with _lock:
        if _initialized:
            return True
        processes, pid, addr = topology_from_env()
        if processes <= 1:
            return False
        if addr is None:
            raise RuntimeError(
                "PATHWAY_PROCESSES > 1 but no PATHWAY_COORDINATOR_ADDRESS / "
                "PATHWAY_FIRST_PORT — launch via `pathway-tpu spawn` or set "
                "the topology env vars explicitly"
            )
        if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
            # cross-process CPU collectives need an explicit implementation
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=addr,
            num_processes=processes,
            process_id=pid,
        )
        logger.info(
            "joined process cluster: rank %d/%d via %s", pid, processes, addr
        )
        _initialized = True
        return True


def is_distributed() -> bool:
    return jax.process_count() > 1


def process_id() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_coordinator() -> bool:
    return jax.process_index() == 0


def _client():
    from jax._src import distributed as _dist

    return _dist.global_state.client


# degraded control-plane operations, by site (barrier / broadcast):
# the coordination service timing out or faulting must cost AGREEMENT,
# never a hung serve — callers get a flagged local-only answer
_degraded_counters: Dict[str, observe.Counter] = {}


def _count_degraded(site: str) -> None:
    c = _degraded_counters.get(site)
    if c is None:
        c = _degraded_counters[site] = observe.counter(
            "pathway_dist_degraded_total", site=site
        )
    c.inc()


def barrier(name: str, timeout_ms: int = 60_000, deadline=None) -> bool:
    """Host-side control-plane barrier over the coordination service — the
    analog of timely's progress frontier sync at commit ticks (workers agree
    a timestamp is closed before results are emitted downstream).

    Returns True when every process reached the barrier, False when the
    sync DEGRADED (chaos site ``dist.barrier`` armed, coordination
    timeout, or service error): the caller proceeds on local knowledge
    with the degradation counted on
    ``pathway_dist_degraded_total{site="barrier"}`` — a serve tier must
    never hang on its own control plane."""
    try:
        _inject.fire("dist.barrier", deadline=deadline)
        if not is_distributed():
            return True
        client = _client()
        if client is None:  # pragma: no cover - initialize() always sets it
            raise RuntimeError("distributed runtime not initialized")
        client.wait_at_barrier(name, timeout_in_ms=timeout_ms)
        return True
    except Exception as exc:
        _count_degraded("barrier")
        log_once(
            f"dist.barrier:{type(exc).__name__}",
            "control-plane barrier %r degraded (%r); proceeding local-only",
            name,
            exc,
        )
        return False


def broadcast_obj(obj=None, *, name: str, timeout_ms: int = 60_000,
                  deadline=None):
    """Broadcast a small picklable control-plane object (config, rendezvous
    info, a per-tick chosen timestamp) from the coordinator to every process
    via the coordination service's KV store.  Call with ``obj`` on the
    coordinator and ``obj=None`` elsewhere; returns the coordinator's value
    everywhere.

    ``name`` must be unique per broadcast (include a tick/sequence number for
    repeated control-plane values: ``name=f"commit/{tick}"``) — the KV store
    rejects overwrites, which makes an accidental reuse fail loudly instead
    of silently serving a stale value to racing followers.

    Degrade semantics (chaos site ``dist.broadcast``, KV timeout, service
    error): returns the LOCAL ``obj`` — the coordinator's own value, or
    None on a follower — counted on
    ``pathway_dist_degraded_total{site="broadcast"}``.  Consumers (e.g.
    warm-state generation agreement, serve/warmstate.py) treat a local-only
    answer as flagged agreement, never as a reason to hang or fail."""
    try:
        _inject.fire("dist.broadcast", deadline=deadline)
        if not is_distributed():
            return obj
        import base64
        import pickle

        client = _client()
        key = f"pathway_tpu/bcast/{name}"
        if is_coordinator():
            client.key_value_set(
                key, base64.b64encode(pickle.dumps(obj)).decode()
            )
            return obj
        raw = client.blocking_key_value_get(key, timeout_ms)
        return pickle.loads(base64.b64decode(raw))
    except Exception as exc:
        _count_degraded("broadcast")
        log_once(
            f"dist.broadcast:{type(exc).__name__}",
            "control-plane broadcast %r degraded (%r); serving local value",
            name,
            exc,
        )
        return obj

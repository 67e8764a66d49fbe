"""chip_smoke.py — the quickest proof that the RAG serve path starts on the chip.

Run from the repo root on a machine with a TPU::

    python chip_smoke.py

It drives the north-star path once through the entry points a user calls,
at the constructors' default widths (SentenceEncoder 384d x 6L,
CrossEncoderModel 256d x 4L, TextGenerator 256d x 4L), weights random
from their seeds, corpus generated from a seed:

- write side: bulk ``encode_to_device`` + ``IvfKnnIndex.build_from_matrix``,
  then ``LiveIngestRunner`` connector commits absorbing under serve traffic;
- read side: ``ServeScheduler(RetrieveRerankPipeline(FusedEncodeSearch(..)))``
  from several threads, then ``ContinuousDecoder(TextGenerator())``;
- the Pallas rescore kernel compiled at the index's real layout;
- with >= 4 devices, the same read path over a four-shard
  ``ShardedIvfIndex`` and a mesh-sharded ``DeviceKnnIndex``;
- the README launcher: ``python -m pathway_tpu.cli run
  templates/adaptive_rag.yaml`` answering ``POST /v1/pw_ai_answer``.

**One process holds the chip.**  The parent (``main()`` without arguments)
never touches JAX.  It runs two children one after another: this file
with ``--leg stack`` (everything in-process, one process driving every
local chip), then the template server as a child of ``cli.py``.  Each has
left the chip before the next starts.  Both share the compile cache
``import pathway_tpu`` places (``JAX_COMPILATION_CACHE_DIR`` if set, else
``<checkout>/.jax_cache``).

The stack leg's first act is to print what JAX found and to exit non-zero
unless that is a TPU: there is no CPU mode.  ``tests/test_chip_smoke.py``
calls the same leg functions at toy sizes on the CPU, which is why they
take their sizes as arguments.  Any failed check raises; nothing catches
it.  Wall times printed here are set-up information, not measurements.
The last line of stdout is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import http.client
import importlib.metadata
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request
from dataclasses import dataclass, field
from typing import Any, Dict, List, Sequence

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
TEMPLATE = "templates/adaptive_rag.yaml"
STACK_TIMEOUT_S = 900
TEMPLATE_READY_S = 180
# ivf_rescore vs a jnp reference at "highest" precision.  Rows are unit
# vectors, so scores lie in [-1, 1]; the kernel multiplies in f32, so the
# bound is a few f32 roundings of a 384-term sum (2.4e-7 seen on a v5e).
KERNEL_ATOL = 1e-5


@dataclass(frozen=True)
class Sizes:
    """Everything the legs size themselves by.  The defaults are the chip
    run: empty model kwargs mean the constructors' own (full) widths."""

    n_docs: int = 131072  # C ~ 1093 clusters, M_pad 256, d_pad 384
    encode_chunk: int = 2048
    n_queries: int = 64
    k: int = 10
    candidates: int = 32
    # live ingest: commits the size of one absorb batch (ingest.batch_docs),
    # and few enough documents that the exact tail stays in one shape bucket
    live_docs: int = 256
    live_commit: int = 32
    absorb_threshold: int = 128
    serve_threads: int = 4
    serve_requests: int = 48
    max_new_tokens: int = 16
    shard_docs: int = 32768
    encoder: Dict[str, Any] = field(default_factory=dict)
    cross: Dict[str, Any] = field(default_factory=dict)
    generator: Dict[str, Any] = field(default_factory=dict)
    ivf: Dict[str, Any] = field(default_factory=dict)


_T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    """A failed check ends the smoke (``assert`` would vanish under -O)."""
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {msg}")


# ---------------------------------------------------------------------------
# device, native library, corpus
# ---------------------------------------------------------------------------


def report_device() -> Dict[str, Any]:
    """Print what JAX found; return the device object of the last line."""
    import jax
    import jaxlib

    import pathway_tpu  # noqa: F401 - places the compile cache

    devices = jax.devices()
    dev = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(
        f"jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu} | "
        f"platform={dev['platform']} device_kind={dev['kind']!r} "
        f"devices={dev['count']} | compile cache: "
        f"{jax.config.jax_compilation_cache_dir}"
    )
    return dev


def load_native() -> None:
    """The native library of this checkout's sources loads (built first
    where this checkout has none yet); its name is the hash of them."""
    from pathway_tpu import native

    check(native.available(), "the native library did not build or does not load")
    log(f"native library of these sources loaded: {native.build().name}")


def make_corpus(n: int, seed: int = 0) -> List[str]:
    """``n`` distinct documents from a seed: each draws a topic (its own
    small word pool, so the embedding space has clusters an IVF can
    find), filler from a shared pool, a unique leading word, and a
    log-normal length of 8..120 words."""
    import numpy as np

    rng = np.random.default_rng(seed)
    n_topics = max(8, n // 512)
    topic = rng.integers(0, n_topics, n)
    n_words = np.clip(rng.lognormal(2.9, 0.7, n), 8, 120).astype(int)
    pick = rng.integers(0, 1 << 30, (n, 120))
    # < 16: a word of the document's topic; >= 16: shared filler
    word = np.where(rng.random((n, 120)) < 0.6, pick % 16, 16 + pick % 2048)
    docs = []
    for i, t in enumerate(topic.tolist()):
        words = [
            f"topic{t}x{w}" if w < 16 else f"word{w - 16}"
            for w in word[i, : n_words[i]].tolist()
        ]
        docs.append(f"doc{i} " + " ".join(words))
    return docs


def _overlap(a, b) -> float:
    hits = sum(
        len({key for key, _ in x} & {key for key, _ in y})
        for x, y in zip(a, b)
    )
    return hits / max(1, sum(len(x) for x in b))


_FAILURE_FAMILIES = (
    "pathway_serve_degraded_total",
    "pathway_robust_breaker_open",
    "pathway_serve_shard_breaker_open",
    "pathway_recompile_tripped",
    "pathway_ingest_failures_total",
    "pathway_ivf_maintenance_failures_total",
)


def failure_metrics() -> Dict[str, float]:
    """The series that say a stage degraded, a breaker opened, a recompile
    tripwire fired or maintenance failed: all zero in a fresh process."""
    from pathway_tpu import observe

    snap = observe.snapshot()
    return {
        name: value
        for kind in ("counters", "gauges")
        for name, value in snap[kind].items()
        if name.startswith(_FAILURE_FAMILIES)
    }


def check_clean_metrics(before: Dict[str, float]) -> None:
    """None of the failure series moved since ``before`` was sampled."""
    moved = {
        name: (before.get(name, 0), value)
        for name, value in failure_metrics().items()
        if value != before.get(name, 0)
    }
    check(not moved, f"failure metrics moved (before, after): {moved}")
    log(
        "metrics clean: no degraded serve, open breaker, recompile tripwire, "
        "ingest or IVF maintenance failure was counted"
    )


# ---------------------------------------------------------------------------
# the single-chip legs
# ---------------------------------------------------------------------------


def build_indexes(sizes: Sizes, docs: Sequence[str]):
    """Bulk load: chunked device encode into the exact index, then the IVF
    built from that same device matrix."""
    from pathway_tpu.models.encoder import SentenceEncoder
    from pathway_tpu.ops.ivf import IvfKnnIndex
    from pathway_tpu.ops.knn import DeviceKnnIndex

    encoder = SentenceEncoder(**sizes.encoder)
    dim = encoder.config.d_model
    n = len(docs)
    exact = DeviceKnnIndex(dimension=dim, metric="cos", initial_capacity=n)
    t0 = time.monotonic()
    for start in range(0, n, sizes.encode_chunk):
        part = docs[start : start + sizes.encode_chunk]
        exact.add_from_device(
            range(start, start + len(part)), encoder.encode_to_device(part)
        )
    exact._matrix.block_until_ready()
    log(f"encoded {n} docs at d={dim} into the exact index ({time.monotonic() - t0:.1f}s wall, compile included)")
    ivf = IvfKnnIndex(
        dimension=dim, metric="cos",
        absorb_threshold=sizes.absorb_threshold, **sizes.ivf,
    )
    t0 = time.monotonic()
    ivf.build_from_matrix(range(n), exact._matrix[:n])
    ivf._slabs.block_until_ready()
    C, M, d_pad = ivf._slabs.shape
    log(
        f"IVF layout: C={ivf._centroids.shape[0]} (C_pad {C}) M_pad={M} "
        f"d_pad={d_pad} default probe {ivf._default_probe()} "
        f"({time.monotonic() - t0:.1f}s wall)"
    )
    return encoder, exact, ivf


def kernel_leg(ivf, n_queries: int, interpret: bool) -> None:
    """``ivf_rescore`` at the built layout's own (B, p, C, M, d) against a
    jnp gather + einsum at ``highest`` precision on the same device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pathway_tpu.ops.ivf_pallas import ivf_rescore

    C = ivf._centroids.shape[0]
    p = min(ivf.n_probe or ivf._default_probe(), C)
    B = ((n_queries + 7) // 8) * 8
    C_pad, M, d_pad = ivf._slabs.shape
    rng = np.random.default_rng(1)
    # queries near real rows, so live slots score high and pad slots -inf
    rows = rng.integers(0, C, B)
    q = ivf._slabs[rows, 0, :].astype(jnp.float32)
    q = q + 0.05 * jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    cscores = q[:, : ivf.dimension] @ ivf._centroids.T
    probe = jax.lax.top_k(cscores, p)[1].astype(jnp.int32)

    t0 = time.monotonic()
    got = np.asarray(
        ivf_rescore(probe, q, ivf._slabs, ivf._bias, interpret=interpret)
    )
    wall = time.monotonic() - t0

    @jax.jit
    def reference(slabs, bias, probe_rows, q_rows):
        return (
            jnp.einsum(
                "bpmd,bd->bpm",
                slabs[probe_rows].astype(jnp.float32),
                q_rows,
                precision=jax.lax.Precision.HIGHEST,
            )
            + bias[probe_rows]
        )

    # 8 queries at a time: the gathered rows are [8, p, M, d] f32
    want = np.concatenate(
        [
            np.asarray(
                reference(ivf._slabs, ivf._bias, probe[b : b + 8], q[b : b + 8])
            )
            for b in range(0, B, 8)
        ]
    )
    check(got.shape == (B, p, M), f"kernel output shape {got.shape}")
    live = np.isfinite(want)
    check(
        bool((np.isfinite(got) == live).all()),
        "kernel and reference disagree on which slots are live",
    )
    check(bool(live.any()), "kernel leg scored no live slot")
    err = float(np.abs(got[live] - want[live]).max())
    check(err <= KERNEL_ATOL, f"ivf_rescore max |err| {err:.3e} > {KERNEL_ATOL}")
    log(
        f"ivf_rescore(interpret={interpret}) at B={B} p={p} C_pad={C_pad} "
        f"M={M} d={d_pad}: max |err| vs highest-precision jnp = {err:.3e} "
        f"(tolerance {KERNEL_ATOL}; {wall:.1f}s wall with compile)"
    )


def retrieval_leg(sizes: Sizes, docs, encoder, exact, ivf, require_pallas: bool):
    """Stage 1 alone: self-retrieval on the exact index, IVF recall against
    it on the same device, and which rescore path the IVF kernel took."""
    import numpy as np

    from pathway_tpu.ops.serving import FusedEncodeSearch

    n = len(docs)
    picks = [(i * 9973) % n for i in range(sizes.n_queries)]
    queries = [docs[i] for i in picks]
    exact_hits = FusedEncodeSearch(encoder, exact, k=sizes.k)(queries)
    retriever = FusedEncodeSearch(encoder, ivf, k=sizes.candidates)
    ivf_hits = retriever(queries, k=sizes.k)
    for name, hits in (("exact", exact_hits), ("ivf", ivf_hits)):
        check(hits.degraded == (), f"{name} stage 1 degraded: {hits.degraded}")
        check(
            all(len(row) == sizes.k for row in hits)
            and all(np.isfinite(s) for row in hits for _, s in row),
            f"{name} stage 1 rows are not {sizes.k} finite scores each",
        )
    self_first = sum(row[0][0] == i for row, i in zip(exact_hits, picks))
    check(
        self_first == len(picks),
        f"exact self-retrieval {self_first}/{len(picks)}",
    )
    margin = min(row[0][1] - row[1][1] for row in exact_hits)
    ivf_self = sum(row[0][0] == i for row, i in zip(ivf_hits, picks))
    recall = _overlap(ivf_hits, exact_hits)
    check(recall >= 0.9, f"IVF top-{sizes.k} overlap with exact {recall:.3f} < 0.9")
    check(ivf_self >= 0.9 * len(picks), f"IVF self-retrieval {ivf_self}/{len(picks)}")
    if require_pallas:
        check(
            retriever.ivf_use_pallas is True,
            f"IVF serve kernel built with use_pallas={retriever.ivf_use_pallas}",
        )
    log(
        f"stage 1: exact self-retrieval {self_first}/{len(picks)} (smallest "
        f"margin to the runner-up {margin:.4f}); IVF top-{sizes.k} overlap "
        f"with exact {recall:.3f}, IVF self-retrieval {ivf_self}/{len(picks)}; "
        f"use_pallas={retriever.ivf_use_pallas}"
    )
    return retriever, queries


def _serve_from_threads(scheduler, requests, n_threads: int, k: int):
    """Issue ``requests`` (one query each) from ``n_threads`` threads that
    start together, so they coalesce into shared batches; returns the
    responses in request order."""
    out: List[Any] = [None] * len(requests)
    errors: List[BaseException] = []
    gate = threading.Barrier(n_threads)

    def worker(t: int) -> None:
        try:
            gate.wait(timeout=60)
            for i in range(t, len(requests), n_threads):
                out[i] = scheduler.serve([requests[i]], k)
        except BaseException as exc:  # noqa: BLE001 - re-raised below
            errors.append(exc)

    threads = [
        threading.Thread(target=worker, args=(t,)) for t in range(n_threads)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=600)
        check(not th.is_alive(), "a serve thread did not finish in 600s")
    if errors:
        raise errors[0]
    return out


def _check_responses(responses, k: int, what: str) -> None:
    import numpy as np

    for i, res in enumerate(responses):
        check(res.degraded == (), f"{what} request {i} degraded: {res.degraded}")
        check(
            len(res) == 1 and len(res[0]) == k,
            f"{what} request {i}: expected 1 row of {k}, got {[len(r) for r in res]}",
        )
        scores = [s for _, s in res[0]]
        check(
            all(np.isfinite(s) for s in scores)
            and scores == sorted(scores, reverse=True),
            f"{what} request {i}: scores not finite and descending: {scores}",
        )


def serve_leg(sizes: Sizes, docs, encoder, ivf, retriever) -> None:
    """The read path from several threads, then the same under live ingest
    with a donated absorb, then a sentinel committed mid-run."""
    import numpy as np

    from pathway_tpu.models.cross_encoder import CrossEncoderModel
    from pathway_tpu.ops.retrieve_rerank import RetrieveRerankPipeline
    from pathway_tpu.serve import LiveIngestRunner, ServeScheduler

    n = len(docs)
    cross = CrossEncoderModel(**sizes.cross)
    doc_text = dict(enumerate(docs))
    pipe = RetrieveRerankPipeline(
        retriever, cross, doc_text, k=sizes.k, candidates=sizes.candidates
    )
    scheduler = ServeScheduler(pipe, k=sizes.k)
    # every request is a different document, so no result-cache hit pulls a
    # thread out of step with the others
    fresh = iter(docs[(i * 7919 + 1) % n] for i in range(n))
    try:
        t0 = time.monotonic()
        requests = [next(fresh) for _ in range(sizes.serve_requests)]
        responses = _serve_from_threads(
            scheduler, requests, sizes.serve_threads, sizes.k
        )
        _check_responses(responses, sizes.k, "serve")
        # the rerank reorders stage 1's shortlist.  Stage 1 in another
        # batch shape rounds bf16 differently, so rows at the shortlist's
        # edge may swap: compare against a shortlist twice as wide.
        wide = retriever(requests, k=2 * sizes.candidates)
        inside = [
            key in {key for key, _ in row}
            for res, row in zip(responses, wide)
            for key, _ in res[0]
        ]
        share = sum(inside) / len(inside)
        check(share >= 0.9, f"only {share:.3f} of reranked keys come from the stage-1 shortlist")
        # ... and its scores are the cross-encoder's own unpacked predict()
        ref = np.asarray(
            cross.predict(
                [(requests[0], doc_text[key]) for key, _ in responses[0][0]]
            ),
            np.float32,
        )
        got = np.asarray([s for _, s in responses[0][0]], np.float32)
        diff, scale = float(np.abs(ref - got).max()), float(np.abs(ref).max())
        check(
            diff <= 0.05 * max(scale, 1.0),
            f"packed rerank scores differ from predict() by {diff:.4f} (scale {scale:.3f})",
        )
        log(
            f"serve: {len(responses)} requests from {sizes.serve_threads} threads, all "
            f"degraded == (); {share:.3f} of reranked keys inside the wide stage-1 "
            f"shortlist; packed rerank vs predict() max |diff| {diff:.4f} at score "
            f"scale {scale:.3f} ({time.monotonic() - t0:.1f}s wall, compile included); "
            f"scheduler stats {scheduler.stats}"
        )

        # live ingest: connector commits absorb while the threads keep serving
        live = [
            (n + i, f"live{i} " + text.split(" ", 1)[1])
            for i, text in enumerate(make_corpus(sizes.live_docs - 1, seed=7))
        ]
        sentinel_key = n + sizes.live_docs
        sentinel = "sentinel zebra quartz committed while serving " + " ".join(
            f"sentinelword{j}" for j in range(24)
        )
        live.insert(len(live) // 2, (sentinel_key, sentinel))
        doc_text.update(live)
        gen0 = ivf.generation
        runner = LiveIngestRunner(encoder, ivf, name="chip-smoke")
        conn = runner.connector("smoke-connector")

        def ingest() -> None:
            for start in range(0, len(live), sizes.live_commit):
                conn.insert_rows(live[start : start + sizes.live_commit])
                conn.commit()
                time.sleep(0.05)

        feeder = threading.Thread(target=ingest)
        served: List[Any] = []
        t0 = time.monotonic()
        try:
            feeder.start()
            while feeder.is_alive() or not served:
                wave = [next(fresh) for _ in range(2 * sizes.serve_threads)]
                served += _serve_from_threads(
                    scheduler, wave, sizes.serve_threads, sizes.k
                )
            feeder.join(timeout=120)
            check(not feeder.is_alive(), "the ingest feeder did not finish")
            check(runner.flush(timeout=120.0), "runner.flush() timed out")
            deadline = time.monotonic() + 60
            while (
                ivf.stats["absorbs"] < 1 or ivf._absorbing
            ) and time.monotonic() < deadline:
                time.sleep(0.05)
        finally:
            runner.stop()
        _check_responses(served, sizes.k, "serve-under-ingest")
        stats = runner.stats
        check(stats["dropped"] == 0, f"live ingest dropped documents: {stats}")
        check(stats["docs"] == len(live), f"live ingest absorbed {stats} of {len(live)}")
        check(ivf.generation > gen0, "index.generation did not move")
        check(
            ivf.stats["absorbs"] >= 1 and ivf.stats["absorb_failures"] == 0,
            f"no donated absorb ran under serve: {ivf.stats}",
        )
        # the sentinel rides in a batch of the usual width and depth, so
        # whatever the tail holds by now the kernel shape is one seen above
        batch = [sentinel] + [next(fresh) for _ in range(sizes.serve_threads - 1)]
        hit = retriever(batch, k=sizes.candidates)
        check(hit.degraded == (), f"sentinel query degraded: {hit.degraded}")
        check(
            bool(hit[0]) and hit[0][0][0] == sentinel_key,
            f"sentinel not retrieved first: {hit[0][:3]}",
        )
        reranked = scheduler.serve(batch, sizes.candidates)
        check(reranked.degraded == (), f"sentinel serve degraded: {reranked.degraded}")
        check(
            sentinel_key in {key for key, _ in reranked[0]},
            "sentinel missing from the scheduler's reranked rows",
        )
        log(
            f"live ingest: {stats['docs']} docs in {stats['batches']} batches, dropped 0, "
            f"flush ok, generation {gen0} -> {ivf.generation}, donated absorbs "
            f"{ivf.stats['absorbs']}, {len(served)} serves meanwhile all degraded == (), "
            f"sentinel retrieved first ({time.monotonic() - t0:.1f}s wall)"
        )
    finally:
        scheduler.stop()


def decode_leg(sizes: Sizes) -> None:
    """Slot-engine greedy tokens equal ``TextGenerator.generate`` on the
    same device, one request at a time and then all at once; two prompts
    share a long prefix."""
    from pathway_tpu.models.generator import TextGenerator
    from pathway_tpu.serve.decode import ContinuousDecoder

    gen = TextGenerator(**sizes.generator)
    budget = gen.config.max_len - sizes.max_new_tokens
    context = " ".join(f"ctx{j % 37}" for j in range(max(8, budget - 24)))
    prompts = [
        context + " question one about streams",
        context + " question two about indexes",
        "a short prompt with no shared prefix",
    ]
    t0 = time.monotonic()
    want = [
        gen.generate([p], max_new_tokens=sizes.max_new_tokens)[0] for p in prompts
    ]
    decoder = ContinuousDecoder(gen)
    try:
        alone = [
            decoder.submit(p, max_new_tokens=sizes.max_new_tokens).result(timeout=600)
            for p in prompts
        ]
        tickets = [
            decoder.submit(p, max_new_tokens=sizes.max_new_tokens) for p in prompts
        ]
        together = [t.result(timeout=600) for t in tickets]
    finally:
        decoder.stop()
    for what, got in (("alone", alone), ("together", together)):
        for i, (g, w) in enumerate(zip(got, want)):
            check(g.degraded == (), f"decode {what} {i} degraded: {g.degraded}")
            check(
                len(str(g).split()) == sizes.max_new_tokens,
                f"decode {what} {i} returned {len(str(g).split())} tokens",
            )
            check(
                str(g) == w,
                f"decode {what} {i}: slot engine {str(g)!r} != generate() {w!r}",
            )
    check(
        decoder.pool_stats["evicted"] == 0,
        f"decoder evicted requests: {decoder.pool_stats}",
    )
    log(
        f"decode: {len(prompts)} prompts x {sizes.max_new_tokens} greedy tokens, alone and "
        f"together: slot engine == generate() for all, degraded == () "
        f"({time.monotonic() - t0:.1f}s wall, compile included)"
    )


def run_single_chip(sizes: Sizes, interpret: bool, require_pallas: bool):
    """Every single-chip leg in order; returns what the four-chip leg
    reuses (encoder, corpus, exact index, queries)."""
    before = failure_metrics()
    t0 = time.monotonic()
    docs = make_corpus(sizes.n_docs)
    log(f"corpus: {len(docs)} documents from seed 0 ({time.monotonic() - t0:.1f}s wall)")
    encoder, exact, ivf = build_indexes(sizes, docs)
    kernel_leg(ivf, sizes.n_queries, interpret)
    retriever, queries = retrieval_leg(
        sizes, docs, encoder, exact, ivf, require_pallas
    )
    serve_leg(sizes, docs, encoder, ivf, retriever)
    decode_leg(sizes)
    check_clean_metrics(before)
    return encoder, exact, queries


# ---------------------------------------------------------------------------
# the four-chip leg
# ---------------------------------------------------------------------------


def four_chip_leg(sizes: Sizes, encoder, exact, queries, require_pallas: bool) -> None:
    """The stage-1 read path over four shards on four devices equals the
    one-shard answer (full probe: the candidate set is then independent of
    the partition), and the mesh-sharded exact index agrees with the
    single-device one."""
    import jax
    import numpy as np

    from pathway_tpu.ops.ivf import ShardedIvfIndex
    from pathway_tpu.ops.knn import DeviceKnnIndex
    from pathway_tpu.ops.serving import FusedEncodeSearch
    from pathway_tpu.parallel.mesh import make_mesh
    from pathway_tpu.parallel.shards import ShardGroup

    before = failure_metrics()
    devices = jax.devices()[:4]
    n = min(sizes.shard_docs, len(exact.key_to_slot))
    keys = list(range(n))
    vecs = np.asarray(exact._matrix[:n], np.float32)
    dim = vecs.shape[1]

    def sharded(group):
        idx = ShardedIvfIndex(dim, metric="cos", group=group, n_probe=65536, **sizes.ivf)
        idx.add(keys, vecs)
        idx.build()
        return FusedEncodeSearch(encoder, idx, k=sizes.k)

    four = sharded(ShardGroup(n_shards=4, devices=devices))
    one = sharded(ShardGroup(n_shards=1, devices=devices[:1]))
    homes = [next(iter(c._slabs.devices())) for c in four.index.shards]
    check(len(set(homes)) == 4, f"four shards' slabs sit on {homes}")
    got = four(queries)
    want = one(queries)
    check(got.degraded == () and want.degraded == (), f"sharded serve degraded: {got.degraded} / {want.degraded}")
    for qi, (g, w) in enumerate(zip(got, want)):
        check(
            [key for key, _ in g] == [key for key, _ in w],
            f"query {qi}: 4-shard keys {[k for k, _ in g]} != 1-shard {[k for k, _ in w]}",
        )
        np.testing.assert_allclose(
            [s for _, s in g], [s for _, s in w], rtol=0, atol=1e-5
        )
    # every shard's search ran where its slabs are, and they have not moved
    for s, child in enumerate(four.index.shards):
        home = four.index.group.device(s)
        check(child._slabs.devices() == {home}, f"shard {s} slabs moved to {child._slabs.devices()}")
        # the launch FusedEncodeSearch._submit_sharded makes for this shard
        with jax.default_device(home):
            _, tail_dev, tail_valid, t_pad = child._tail_snapshot_device()
            fn, _ = four._shard_search_fn(child, len(queries), sizes.k, t_pad)
            z = jax.device_put(np.zeros((len(queries), dim), np.float32), home)
            out = fn(z, child._slabs, child._bias, child._centroids, tail_dev, tail_valid)
        check(out.devices() == {home}, f"shard {s} search ran on {out.devices()}, slabs on {home}")
    if require_pallas:
        check(four.ivf_use_pallas is True, f"sharded IVF built with use_pallas={four.ivf_use_pallas}")

    mesh_index = DeviceKnnIndex(dimension=dim, metric="cos", initial_capacity=n, mesh=make_mesh(4, devices=devices))
    mesh_index.add(keys, vecs)
    q = np.asarray(encoder.encode(queries), np.float32)
    check(
        len(mesh_index._matrix.devices()) == 4,
        f"mesh index matrix on {mesh_index._matrix.devices()}",
    )
    solo = DeviceKnnIndex(dimension=dim, metric="cos", initial_capacity=n)
    solo.add(keys, vecs)
    mesh_hits, solo_hits = mesh_index.search(q, sizes.k), solo.search(q, sizes.k)
    # the two matmuls tile differently, so near-ties at the tail may swap
    check(
        all(m[0][0] == s[0][0] for m, s in zip(mesh_hits, solo_hits))
        and _overlap(mesh_hits, solo_hits) >= 0.99,
        "mesh-sharded DeviceKnnIndex disagrees with the single-device index",
    )
    in_use = {
        str(d): (d.memory_stats() or {}).get("bytes_in_use") for d in devices
    }
    log(
        f"four-chip leg: shards on {[str(h) for h in homes]}, 4-shard == 1-shard on "
        f"{len(queries)} queries over {n} docs, mesh DeviceKnnIndex agrees with "
        f"single-device (overlap {_overlap(mesh_hits, solo_hits):.3f}); "
        f"bytes_in_use per device {in_use}"
    )
    check_clean_metrics(before)


# ---------------------------------------------------------------------------
# the template leg (a child of cli.py; shared with tests/test_templates.py)
# ---------------------------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class TemplateServer:
    """``python -m pathway_tpu.cli run <template>`` as a child process on
    a free port, its output kept in a temporary file.  ``with`` waits for
    readiness and always stops the child."""

    def __init__(self, template: str, env: Dict[str, str], ready_s: float = TEMPLATE_READY_S,
                 probe: Any = None):
        self.template = template
        self.env = env
        self.ready_s = ready_s
        self.probe = probe or {"query": "cats", "k": 1}
        self.port = free_port()
        self._log = tempfile.TemporaryFile(mode="w+")
        self.proc: subprocess.Popen | None = None

    def output(self) -> str:
        self._log.flush()
        self._log.seek(0)
        return self._log.read()

    def post(self, route: str, payload: Any, timeout: float = 180) -> Any:
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{route}",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            if resp.status != 200:
                raise RuntimeError(f"{route} answered HTTP {resp.status}")
            return json.loads(resp.read())

    def __enter__(self) -> "TemplateServer":
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "pathway_tpu.cli", "run", self.template,
             "--port", str(self.port)],
            cwd=REPO_ROOT, env=self.env, stdout=self._log,
            stderr=subprocess.STDOUT, text=True,
        )
        try:
            deadline = time.monotonic() + self.ready_s
            while True:
                if self.proc.poll() is not None:
                    raise RuntimeError(
                        f"template app exited {self.proc.returncode}:\n{self.output()[-3000:]}"
                    )
                try:
                    self.post("/v1/retrieve", self.probe, timeout=5)
                    return self
                except (OSError, http.client.HTTPException, ValueError):
                    pass  # not listening yet, or still warming up
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"template server not ready in {self.ready_s}s:\n{self.output()[-3000:]}"
                    )
                time.sleep(1.0)
        except BaseException:
            self.__exit__(None, None, None)
            raise

    def __exit__(self, *exc) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()


def template_leg(expect_platform: str) -> None:
    """The README's launcher answers prompts, on the expected platform."""
    t0 = time.monotonic()
    with TemplateServer(TEMPLATE, dict(os.environ)) as server:
        for prompt in ("What do cats do?", "What is a TPU?", "How does dataflow work?"):
            answer = server.post("/v1/pw_ai_answer", {"prompt": prompt})
            check(
                isinstance(answer, str) and bool(answer.strip()),
                f"template answered {answer!r} to {prompt!r}",
            )
    out = server.output()
    check(
        f"platform={expect_platform} " in out,
        f"template child's log does not show platform={expect_platform}:\n{out[-2000:]}",
    )
    log(
        f"template leg: cli run {TEMPLATE} answered 3 prompts with HTTP 200 on "
        f"platform={expect_platform}, child stopped ({time.monotonic() - t0:.1f}s wall)"
    )


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def stack_main() -> int:
    """The in-process leg: everything that needs the chip in this process."""
    dev = report_device()
    if dev["platform"] != "tpu":
        print(
            f"chip_smoke: JAX found platform {dev['platform']!r}, not a TPU. "
            "This script has no CPU mode; run it on the chip machine.",
            file=sys.stderr,
        )
        return 2
    load_native()
    sizes = Sizes()
    encoder, exact, queries = run_single_chip(sizes, interpret=False, require_pallas=True)
    if dev["count"] >= 4:
        four_chip_leg(sizes, encoder, exact, queries, require_pallas=True)
    else:
        log(f"four-chip leg NOT RUN: {dev['count']} device(s) here, it needs 4")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


def main(argv: Sequence[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--leg", choices=["stack"], help="internal: run one leg in-process")
    args = parser.parse_args(argv)
    if args.leg == "stack":
        return stack_main()

    # parent: never imports JAX, so each child finds the chip free
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--leg", "stack"],
        cwd=REPO_ROOT, stdout=subprocess.PIPE, text=True,
    )
    watchdog = threading.Timer(STACK_TIMEOUT_S, child.kill)
    watchdog.start()
    last = ""
    try:
        for line in child.stdout:
            if last:
                print(last, end="", flush=True)
            last = line
        rc = child.wait()
    finally:
        watchdog.cancel()
        if child.poll() is None:
            child.kill()
            child.wait()
    if rc != 0:
        if last:
            print(last, end="", flush=True)
        print(f"chip_smoke: the stack leg exited {rc}", file=sys.stderr)
        return rc or 1
    result = json.loads(last)
    template_leg(expect_platform=result["device"]["platform"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

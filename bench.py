"""Headline benchmarks — streams one complete JSON record line per phase.

Three measurements (BASELINE.md / VERDICT round-1 #1):
  1. retrieval_p50_ms   — live-retrieval latency: query text -> on-device
     SentenceEncoder -> sharded DeviceKnnIndex over 1M docs in HBM, fused
     into one dispatch (SURVEY.md §3.3 north-star path).
  2. ingest_docs_per_sec — streaming ingest: tokenize + embed + index
     (the docs/sec embedded+indexed target).
  3. wordcount_rows_per_sec — relational engine throughput: rows through
     source -> groupby(word).count (streaming wordcount shape,
     reference README.md:245 benchmark workload).

Every phase that can touch a device runs in a SUBPROCESS with a hard
timeout, one at a time: a chip belongs to one process, and a hang inside
the runtime is out of a signal handler's reach.  The parent process never
imports jax.  The backend is probed first; no accelerator is an error
unless ``BENCH_FORCE_CPU`` asks for the CPU (records then carry
``"backend": "cpu"``).  A failed phase is recorded in ``extras["errors"]``,
is never retried on the CPU, and makes the run exit non-zero.

Output: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
         "backend": ..., "extras": {...}}
vs_baseline > 1.0 beats the driver target of 50 ms p50 (BASELINE.md).

A COMPLETE record (with every extra measured so far, ``"partial": true``)
is printed and FLUSHED after every phase, and the final record is the last
line — the driver parses the tail, so a wall-budget kill at any point
still leaves the most complete measured record instead of an empty tail
(the round-5 ``rc: 124`` failure mode; VERDICT r5 #1).  Phases run in
importance order (retrieval → rerank → late_interaction → ingest →
wordcount → exchange → rag_eval → scaling) and ``BENCH_WALL_BUDGET``
(seconds) skips remaining
phases once the budget is spent rather than dying mid-measurement.
"""

from __future__ import annotations

import itertools
import json
import os
import subprocess
import sys
import threading
import time
import traceback
from typing import Optional

import numpy as np


def probe_backend() -> str:
    """The jax backend a phase child will find, probed in a subprocess (the
    parent never imports jax, so each child finds the chip free).  No
    accelerator is an error, not a CPU run: only ``BENCH_FORCE_CPU`` asks
    for the CPU, and its records say ``"backend": "cpu"``."""
    if os.environ.get("BENCH_FORCE_CPU"):
        return "cpu"
    out = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.default_backend())"],
        capture_output=True,
        timeout=180,
        text=True,
    )
    backend = out.stdout.strip().splitlines()[-1].strip() if out.stdout.strip() else ""
    if out.returncode != 0 or backend in ("", "cpu"):
        sys.stderr.write(out.stderr)
        raise SystemExit(
            f"[bench] no accelerator: jax.default_backend() gave {backend!r} "
            f"(rc={out.returncode}); set BENCH_FORCE_CPU=1 to run on the CPU"
        )
    return backend


# --------------------------------------------------------------------------
# phases — each runs in its own subprocess (BENCH_PHASE=<name>) and prints
# one JSON line {"value": N, "extras": {...}} (or {"error": ...})


def _init_jax(backend: str):
    """``backend == "cpu"`` comes from ``BENCH_FORCE_CPU``: the parent pins
    the children with ``JAX_PLATFORMS=cpu`` in their environment."""
    import jax

    return jax


def _corpus_texts(n: int):
    topics = [
        "incremental dataflow", "vector index", "exactly once", "stream join",
        "window aggregation", "schema registry", "kafka offsets",
        "snapshot replay", "rag retrieval", "sharded state", "commit ticks",
        "key ownership", "mesh collectives", "tokenizer ingest",
    ]
    return [
        f"document {i} covers {topics[i % len(topics)]} case {i % 97} with "
        f"{topics[(i // 7) % len(topics)]} updates and live serving"
        for i in range(n)
    ]


def phase_retrieval(backend: str, extras: dict) -> float:
    """Fused encode+search p50 latency over an HBM-resident index of REAL
    text embeddings (ms), with bf16-storage and IVF approximate tiers."""
    jax = _init_jax(backend)
    import jax.numpy as jnp
    import numpy as _np

    from pathway_tpu.models.encoder import SentenceEncoder
    from pathway_tpu.ops.knn import DeviceKnnIndex
    from pathway_tpu.ops.serving import FusedEncodeSearch

    backend = jax.default_backend()
    extras["backend"] = backend
    n_docs = int(
        os.environ.get("BENCH_N_DOCS", "1000000" if backend == "tpu" else "100000")
    )
    dim, n_queries, k = 384, 64, 10

    encoder = SentenceEncoder(dimension=dim, n_layers=6, max_length=128)
    index = DeviceKnnIndex(dimension=dim, metric="cos", initial_capacity=n_docs)
    index_bf16 = DeviceKnnIndex(
        dimension=dim, metric="cos", initial_capacity=n_docs, dtype=jnp.bfloat16
    )

    # REAL text corpus encoded on device (round-3 critique: random normals
    # say nothing about recall); fully device-to-device — no host fetch in
    # the loop (r4 Weak #5: a per-chunk np.asarray is a host sync per chunk
    # and made index_build_s a bench artifact)
    docs = _corpus_texts(n_docs)
    chunk = 4096
    t0 = time.perf_counter()
    for start in range(0, n_docs, chunk):
        part = docs[start : start + chunk]
        vecs = encoder.encode_to_device(part)
        keys = range(start, start + len(part))
        index.add_from_device(keys, vecs)
        index_bf16.add_from_device(keys, vecs)
    index._matrix.block_until_ready()
    extras["index_build_s"] = round(time.perf_counter() - t0, 2)
    extras["index_docs"] = n_docs

    queries = [docs[(i * 9973) % n_docs] for i in range(n_queries)]
    serve = FusedEncodeSearch(encoder, index, k=k)
    hits = serve(queries)  # warmup: compiles the fused kernel
    assert len(hits) == n_queries and len(hits[0]) == k
    # self-retrieval sanity: each query IS a document; its key must win
    self_hits = sum(
        1 for i, row in enumerate(hits) if row and row[0][0] == (i * 9973) % n_docs
    )
    extras["self_hit_rate"] = round(self_hits / n_queries, 3)

    latencies = []
    for _ in range(int(os.environ.get("BENCH_ITERS", "30"))):
        t0 = time.perf_counter()
        serve(queries)
        latencies.append((time.perf_counter() - t0) * 1e3)
    p50_e2e = float(np.percentile(latencies, 50))
    extras["p50_e2e_ms"] = round(p50_e2e, 3)
    extras["retrieval_p95_ms"] = round(float(np.percentile(latencies, 95)), 3)

    # pipelined serving (VERDICT r2 #3): keep the device queue full so
    # per-batch wall time approaches pure device time instead of paying one
    # host round trip per call — this is the QPS a concurrent server sees,
    # and per-batch time under pipelining is the device-side p50 (the <50 ms
    # target is a device+ICI number; the dispatch floor is reported separately)
    depth = int(os.environ.get("BENCH_PIPELINE_DEPTH", "4"))
    iters = int(os.environ.get("BENCH_QPS_ITERS", "40"))
    pending = []
    completions = []
    t0 = time.perf_counter()
    for _ in range(iters):
        pending.append(serve.submit(queries))
        if len(pending) > depth:
            pending.pop(0)()
            completions.append(time.perf_counter())
    while pending:
        pending.pop(0)()
        completions.append(time.perf_counter())
    elapsed = time.perf_counter() - t0
    # a real median: per-batch device time = inter-completion gap with the
    # queue kept full (diff also drops the pipeline-fill first completion)
    gaps_ms = np.diff(np.asarray(completions)) * 1e3
    p50_device = (
        float(np.percentile(gaps_ms, 50)) if len(gaps_ms) else elapsed / iters * 1e3
    )
    extras["p50_device_ms"] = round(p50_device, 3)
    extras["p95_device_ms"] = (
        round(float(np.percentile(gaps_ms, 95)), 3) if len(gaps_ms) else None
    )
    extras["qps"] = round(iters * n_queries / elapsed, 1)
    extras["qps_batch"] = n_queries
    extras["pipeline_depth"] = depth

    def pipelined_p50(serve_fn, iters=24, depth=4):
        pend, comps = [], []
        for _ in range(iters):
            pend.append(serve_fn.submit(queries))
            if len(pend) > depth:
                pend.pop(0)()
                comps.append(time.perf_counter())
        while pend:
            pend.pop(0)()
            comps.append(time.perf_counter())
        gaps = np.diff(np.asarray(comps)) * 1e3
        return float(np.percentile(gaps, 50)) if len(gaps) else None

    # --- bf16 vector-storage tier: halves the HBM sweep (usearch f16
    # analog, usearch_integration.rs:37) -----------------------------------
    serve_bf16 = FusedEncodeSearch(encoder, index_bf16, k=k)
    hits_bf16 = serve_bf16(queries)
    overlap = sum(
        len({kk for kk, _ in a} & {kk for kk, _ in b})
        for a, b in zip(hits, hits_bf16)
    ) / (k * n_queries)
    extras["bf16_p50_device_ms"] = round(pipelined_p50(serve_bf16), 3)
    extras["bf16_recall_vs_f32"] = round(overlap, 4)

    # --- IVF approximate tier in the SERVING path -------------------------
    from pathway_tpu.ops.ivf import IvfKnnIndex

    # device-to-device bulk build: k-means + layout read the exact
    # index's HBM matrix directly; only the training sample and the
    # assignment indices cross the host link (r4 Weak #5 / task #7)
    ivf = IvfKnnIndex(dimension=dim, metric="cos")
    t0 = time.perf_counter()
    ivf.build_from_matrix(range(n_docs), index._matrix[:n_docs])
    ivf._slabs.block_until_ready()
    extras["ivf_build_s"] = round(time.perf_counter() - t0, 2)
    serve_ivf = FusedEncodeSearch(encoder, ivf, k=k)
    hits_ivf = serve_ivf(queries)
    recall = sum(
        len({kk for kk, _ in a} & {kk for kk, _ in b})
        for a, b in zip(hits, hits_ivf)
    ) / (k * n_queries)
    extras["ivf_p50_device_ms"] = round(pipelined_p50(serve_ivf), 3)
    extras["ivf_recall_at_10"] = round(recall, 4)
    extras["ivf_flops_fraction"] = round(ivf.score_flops_fraction(), 4)

    # --- serving UNDER STREAMING (VERDICT r4 #2 'Done' at bench
    # scale): stream adds into the live IVF index between serve
    # batches; p50 during streaming must stay near steady state — no
    # rebuild ever runs on the serve path (absorb + exact tail only)
    # steady-state SYNCHRONOUS p50 (one dispatch + fetch per call) — the honest
    # baseline for the streaming loop below, which serves the same way
    sync_lat = []
    for _ in range(12):
        t0 = time.perf_counter()
        serve_ivf(queries)
        sync_lat.append((time.perf_counter() - t0) * 1e3)
    steady_ivf = float(np.percentile(sync_lat, 50))
    extras["ivf_p50_e2e_ms"] = round(steady_ivf, 3)
    builds_before = ivf.stats["sync_builds"]
    stream_n = int(os.environ.get("BENCH_STREAM_ADDS", "16384"))
    stream_chunk = 1024
    fresh = [f"fresh update {t}" for t in _corpus_texts(stream_n)]
    lat = []
    for start in range(0, stream_n, stream_chunk):
        part = fresh[start : start + stream_chunk]
        vecs = _np.asarray(
            encoder.encode_to_device(part), dtype=_np.float32
        )
        ivf.add(range(n_docs + start, n_docs + start + len(part)), vecs)
        t0 = time.perf_counter()
        serve_ivf(queries)
        lat.append((time.perf_counter() - t0) * 1e3)
    extras["ivf_streaming_adds"] = stream_n
    extras["ivf_serving_streaming_p50_ms"] = round(
        float(np.percentile(lat, 50)), 3
    )
    extras["ivf_serving_streaming_p95_ms"] = round(
        float(np.percentile(lat, 95)), 3
    )
    extras["ivf_rebuilds_during_streaming"] = (
        ivf.stats["sync_builds"] - builds_before
    )
    extras["ivf_absorbs_during_streaming"] = ivf.stats["absorbs"]
    if steady_ivf:
        extras["ivf_streaming_vs_steady"] = round(
            extras["ivf_serving_streaming_p50_ms"] / max(steady_ivf, 1e-9), 2
        )

    # dispatch-latency floor: one tiny jitted call round trip (serving is
    # exactly ONE such round trip per batch)
    tiny = jax.jit(lambda a: a + 1)
    x = jax.device_put(np.ones((8,), np.float32))
    tiny(x).block_until_ready()
    rtts = []
    for _ in range(5):
        t0 = time.perf_counter()
        tiny(x).block_until_ready()
        rtts.append((time.perf_counter() - t0) * 1e3)
    extras["dispatch_rtt_floor_ms"] = round(float(np.percentile(rtts, 50)), 2)
    return p50_device


def _build_rr_pipeline(n_docs: int, n_queries: int, k: int, candidates: int,
                       small: bool = False):
    """Shared serve-stack setup for the retrieve_rerank and
    observe_overhead phases: models, chunked device ingest into an exact
    index, fused retriever + rerank pipeline, query set.  ``small`` picks
    scaled-down models (the observe phase's CPU arm measures host-side
    recorder overhead, which is model-size blind)."""
    from pathway_tpu.models.cross_encoder import CrossEncoderModel
    from pathway_tpu.models.encoder import SentenceEncoder
    from pathway_tpu.ops.knn import DeviceKnnIndex
    from pathway_tpu.ops.retrieve_rerank import RetrieveRerankPipeline
    from pathway_tpu.ops.serving import FusedEncodeSearch

    if small:
        encoder = SentenceEncoder(dimension=64, n_layers=2, max_length=64)
        cross = CrossEncoderModel(dimension=64, n_layers=2, max_length=128)
        dim = 64
    else:
        encoder = SentenceEncoder(dimension=384, n_layers=6, max_length=128)
        cross = CrossEncoderModel(dimension=256, n_layers=4, max_length=256)
        dim = 384
    index = DeviceKnnIndex(dimension=dim, metric="cos", initial_capacity=n_docs)
    # variable-length prose, log-normal lengths — the packing win is real
    # row sharing, not an artifact of uniform short docs
    docs = _realistic_corpus(n_docs)
    chunk = 4096
    for start in range(0, n_docs, chunk):
        part = docs[start : start + chunk]
        index.add_from_device(
            range(start, start + len(part)), encoder.encode_to_device(part)
        )
    index._matrix.block_until_ready()
    queries = [docs[(i * 9973) % n_docs] for i in range(n_queries)]
    pipe = RetrieveRerankPipeline(
        FusedEncodeSearch(encoder, index, k=candidates), cross,
        doc_text=dict(enumerate(docs)), k=k, candidates=candidates,
    )
    return pipe, cross, docs, queries


def phase_retrieve_rerank(backend: str, extras: dict) -> float:
    """Fused two-stage serving (ops/retrieve_rerank.py): encode+search is
    dispatch #1, packed cross-encoder rescoring is dispatch #2 — a full
    retrieve→rerank serve is two device round trips, and consecutive calls
    pipeline (stage 2 of call N overlaps stage 1 of call N+1).  Reports
    cross-encoder pairs/s (the phase value), per-call latency sync and
    pipelined, the packing row compression, and the measured dispatch/fetch
    budget."""
    jax = _init_jax(backend)

    from pathway_tpu.ops import dispatch_counter

    backend = jax.default_backend()
    extras["backend"] = backend
    # CPU fallback runs the full-size models at a fraction of the corpus
    # and iteration count (one serve call is ~8 s of CPU cross-encoder
    # work; the phase must fit its 900 s subprocess budget)
    n_docs = int(
        os.environ.get("BENCH_RR_DOCS", "100000" if backend == "tpu" else "2000")
    )
    n_queries, k, candidates = 16, 10, 32
    pipe, cross, docs, queries = _build_rr_pipeline(
        n_docs, n_queries, k, candidates
    )
    hits = pipe(queries)  # warmup: compiles both stages
    assert len(hits) == n_queries and all(len(row) == k for row in hits)

    # steady-state dispatch/fetch budget — ground truth, not timing
    with dispatch_counter.DispatchCounter() as counter:
        pipe(queries)
    extras["dispatches_per_serve"] = counter.dispatches
    extras["fetches_per_serve"] = counter.fetches

    # synchronous per-call latency (what one caller sees)
    iters = int(
        os.environ.get("BENCH_RR_ITERS", "20" if backend == "tpu" else "4")
    )
    pairs0 = pipe.stats["stage2_pairs"]
    lat = []
    t_all = time.perf_counter()
    for _ in range(iters):
        t0 = time.perf_counter()
        pipe(queries)
        lat.append((time.perf_counter() - t0) * 1e3)
    sync_elapsed = time.perf_counter() - t_all
    extras["p50_e2e_ms"] = round(float(np.percentile(lat, 50)), 3)
    extras["p95_e2e_ms"] = round(float(np.percentile(lat, 95)), 3)
    pairs_per_s = (pipe.stats["stage2_pairs"] - pairs0) / sync_elapsed
    extras["pairs_per_s_sync"] = round(pairs_per_s, 1)

    # pipelined serving: advance() dispatches stage 2 of call N while
    # stage 1 of call N+1 is queued behind it; per-call wall time is the
    # inter-completion gap with the queue kept full
    depth = int(os.environ.get("BENCH_PIPELINE_DEPTH", "4"))
    pend, comps = [], []
    pairs0 = pipe.stats["stage2_pairs"]
    t_all = time.perf_counter()
    for _ in range(2 * iters):
        pend.append(pipe.submit(queries))
        if len(pend) >= 2:
            pend[-2].advance()
        if len(pend) > depth:
            pend.pop(0)()
            comps.append(time.perf_counter())
    while pend:
        pend.pop(0)()
        comps.append(time.perf_counter())
    piped_elapsed = time.perf_counter() - t_all
    gaps_ms = np.diff(np.asarray(comps)) * 1e3
    if len(gaps_ms):
        extras["p50_pipelined_ms"] = round(float(np.percentile(gaps_ms, 50)), 3)
    pairs_per_s_piped = (pipe.stats["stage2_pairs"] - pairs0) / piped_elapsed
    extras["pairs_per_s_pipelined"] = round(pairs_per_s_piped, 1)
    extras["pipeline_depth"] = depth
    extras["rerank_candidates"] = candidates
    extras["queries_per_call"] = n_queries

    # packing effectiveness: rows actually dispatched vs one max_length row
    # per pair (the unpacked cost this PR removes)
    pairs_total = max(pipe.stats["stage2_pairs"], 1)
    extras["packing_rows_per_pair"] = round(
        pipe.stats["stage2_rows"] / pairs_total, 3
    )

    # packed vs unpacked cross-encoder scoring on one serve's pair batch
    pairs = [
        (q, docs[key]) for q, row in zip(queries, hits) for key, _ in row
    ]
    reps = 5 if backend == "tpu" else 2
    cross.predict(pairs, packed=True)  # warm both jit caches
    cross.predict(pairs, packed=False)
    t0 = time.perf_counter()
    for _ in range(reps):
        cross.predict(pairs, packed=True)
    t_packed = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        cross.predict(pairs, packed=False)
    t_unpacked = time.perf_counter() - t0
    extras["packed_speedup_vs_unpacked"] = round(t_unpacked / max(t_packed, 1e-9), 2)

    return round(max(pairs_per_s, pairs_per_s_piped), 1)


def phase_late_interaction(backend: str, extras: dict) -> float:
    """Late-interaction rerank tier (ISSUE 6, pathway_tpu/index): price
    stage 2 as cross-encoder vs MaxSim-over-forward-index vs the
    MaxSim→CE cascade at MATCHED over-fetch.  Reports per-mode serve
    p50 + stage-2 pairs/s, the analytic per-pair device-FLOPs reduction
    (the acceptance bar is >= 8x), forward-index ingest rate, HBM
    footprint + compression ratio, a known-item retrieval quality delta
    (cascade must stay within ~2% of the full cross-encoder), and the
    2-dispatch + 2-fetch happy-path budget via ``dispatch_counter``."""
    jax = _init_jax(backend)

    from pathway_tpu.index import ForwardIndex
    from pathway_tpu.ops import dispatch_counter
    from pathway_tpu.ops.retrieve_rerank import RetrieveRerankPipeline
    from pathway_tpu.ops.serving import FusedEncodeSearch

    backend = jax.default_backend()
    extras["backend"] = backend
    on_tpu = backend == "tpu"
    n_docs = int(
        os.environ.get("BENCH_LI_DOCS", "100000" if on_tpu else "1500")
    )
    n_queries, k, candidates = 16, 10, 32
    pipe_ce, cross, docs, queries = _build_rr_pipeline(
        n_docs, n_queries, k, candidates
    )
    encoder = pipe_ce.retriever.encoder
    index = pipe_ce.retriever.index
    doc_text = dict(enumerate(docs))

    # -- forward-index ingest: docs/s, HBM, compression ---------------------
    fwd = ForwardIndex(encoder)
    chunk = 1024 if on_tpu else 256
    t0 = time.perf_counter()
    for start in range(0, n_docs, chunk):
        part = docs[start : start + chunk]
        fwd.add(range(start, start + len(part)), part)
    ingest_s = time.perf_counter() - t0
    extras["forward_ingest_docs_per_s"] = round(n_docs / max(ingest_s, 1e-9), 1)
    extras["forward_hbm_bytes"] = fwd.hbm_bytes()
    extras["forward_tokens_per_doc"] = fwd.tokens_per_doc
    extras["forward_quant"] = fwd.quant
    extras["forward_compression_ratio"] = round(fwd.compression_ratio(), 2)
    if fwd._quant_abs_err is not None:
        extras["forward_quant_abs_err"] = round(fwd._quant_abs_err, 5)

    pipe_li = RetrieveRerankPipeline(
        FusedEncodeSearch(encoder, index, k=candidates), doc_text=doc_text,
        k=k, candidates=candidates, forward_index=fwd,
    )
    pipe_cas = RetrieveRerankPipeline(
        FusedEncodeSearch(encoder, index, k=candidates), cross, doc_text,
        k=k, candidates=candidates, forward_index=fwd, cascade=k,
    )

    # -- happy-path budget: gather+MaxSim+top-k fused into dispatch #2 ------
    pipe_li(queries)  # warmup compiles stage 1 (with token export) + gather
    with dispatch_counter.DispatchCounter() as counter:
        got = pipe_li(queries)
    assert got and all(got) and not got.degraded, got.degraded
    extras["li_dispatches_per_serve"] = counter.dispatches
    extras["li_fetches_per_serve"] = counter.fetches
    assert counter.dispatches == 2 and counter.fetches == 2, counter.events

    # -- per-mode latency + stage-2 pairs/s at matched over-fetch -----------
    iters = int(os.environ.get("BENCH_LI_ITERS", "20" if on_tpu else "3"))

    # per-mode stage-1 baseline: the LI/cascade retrievers run with
    # query-token export ON (an extra [B, L, d] f32 output in the fused
    # kernel), the cross-encoder pipeline's runs without — subtracting
    # one shared baseline would understate the CE mode's stage-2 cost
    def stage1_ms_of(pipe):
        retr = pipe.retriever
        retr(queries, candidates)  # warm
        t_s1 = time.perf_counter()
        for _ in range(iters):
            retr(queries, candidates)
        return (time.perf_counter() - t_s1) / iters * 1e3

    stage1_ms = {
        "cross_encoder": stage1_ms_of(pipe_ce),
        "maxsim": stage1_ms_of(pipe_li),
    }
    stage1_ms["cascade"] = stage1_ms["maxsim"]  # same export-on kernel
    extras["stage1_only_p50_ms"] = round(stage1_ms["cross_encoder"], 3)
    extras["stage1_export_p50_ms"] = round(stage1_ms["maxsim"], 3)
    modes = {"cross_encoder": pipe_ce, "maxsim": pipe_li, "cascade": pipe_cas}
    pairs_per_call = n_queries * candidates
    for name, pipe in modes.items():
        pipe(queries)  # warm
        lat = []
        t_all = time.perf_counter()
        for _ in range(iters):
            t1 = time.perf_counter()
            pipe(queries)
            lat.append((time.perf_counter() - t1) * 1e3)
        elapsed = time.perf_counter() - t_all
        p50 = float(np.percentile(lat, 50))
        extras[f"{name}_p50_e2e_ms"] = round(p50, 3)
        extras[f"{name}_stage2_ms"] = round(max(p50 - stage1_ms[name], 0.0), 3)
        extras[f"{name}_pairs_per_s"] = round(
            iters * pairs_per_call / elapsed, 1
        )

    # -- analytic per-pair device FLOPs at matched over-fetch ---------------
    # cross-encoder: a full transformer forward over the packed pair —
    # per token per layer ~ 12 d^2 (qkv/out/mlp matmuls) + 2 L d
    # (attention) MACs.  MaxSim: Lq x T' x d MACs per pair.  Both use
    # the shapes actually dispatched (packed pair tokens; padded Lq).
    sample = [(queries[i % n_queries], docs[i * 37 % n_docs]) for i in range(64)]
    ids, _m = cross.tokenizer.encode_batch(
        [q for q, _ in sample], pairs=[d for _, d in sample]
    )
    pair_tokens = float(np.asarray(_m).sum() / len(sample))
    d_ce, l_ce = cross.config.d_model, cross.config.n_layers
    flops_ce = 2.0 * pair_tokens * l_ce * (12.0 * d_ce * d_ce + 2.0 * pair_tokens * d_ce)
    q_ids, _qm = encoder.tokenizer.encode_batch(list(queries))
    lq = float(np.asarray(q_ids).shape[1])  # padded serve width
    flops_ms = 2.0 * lq * fwd.tokens_per_doc * encoder.config.d_model
    reduction = flops_ce / max(flops_ms, 1.0)
    extras["ce_flops_per_pair"] = round(flops_ce, 0)
    extras["maxsim_flops_per_pair"] = round(flops_ms, 0)
    extras["stage2_flop_reduction_x"] = round(reduction, 1)
    assert reduction >= 8.0, f"FLOP reduction {reduction:.1f}x < 8x"

    # -- known-item retrieval quality at matched over-fetch -----------------
    # noisy queries with a known target doc: every other word dropped.
    # MRR over the served top-k per mode; the MaxSim->CE cascade must
    # stay within ~2% of the full cross-encoder.
    n_eval = int(os.environ.get("BENCH_LI_EVAL", "64" if on_tpu else "16"))
    eval_ids = [(i * 9973 + 1) % n_docs for i in range(n_eval)]
    eval_qs = [" ".join(docs[i].split()[::2]) for i in eval_ids]
    mrr = {}
    for name, pipe in modes.items():
        total = 0.0
        rows = pipe(eval_qs)
        for target, row in zip(eval_ids, rows):
            keys = [key for key, _ in row]
            if target in keys:
                total += 1.0 / (keys.index(target) + 1)
        mrr[name] = total / max(n_eval, 1)
        extras[f"{name}_known_item_mrr"] = round(mrr[name], 4)
    base = max(mrr["cross_encoder"], 1e-9)
    extras["maxsim_quality_delta_pct"] = round(
        (mrr["cross_encoder"] - mrr["maxsim"]) / base * 100.0, 2
    )
    extras["cascade_quality_delta_pct"] = round(
        (mrr["cross_encoder"] - mrr["cascade"]) / base * 100.0, 2
    )
    return round(reduction, 1)


def phase_observe_overhead(backend: str, extras: dict) -> float:
    """Price of the always-on flight recorder (pathway_tpu/observe): the
    SAME steady-state fused retrieve→rerank serve measured with the
    recorder enabled vs forcibly disabled, interleaved A/B/A/B so clock
    drift and cache effects hit both arms equally.  The phase value is the
    added p50 latency in percent — the acceptance budget is < 3%.  Also
    re-asserts the 2-dispatch + 2-fetch budget WITH the recorder on."""
    jax = _init_jax(backend)

    from pathway_tpu import observe
    from pathway_tpu.ops import dispatch_counter

    backend = jax.default_backend()
    extras["backend"] = backend
    on_tpu = backend == "tpu"
    n_docs = int(os.environ.get("BENCH_OBS_DOCS", "20000" if on_tpu else "1000"))
    n_queries, k, candidates = 16, 10, 32
    pipe, _cross, _docs, queries = _build_rr_pipeline(
        n_docs, n_queries, k, candidates, small=not on_tpu
    )
    pipe(queries)  # warmup: compiles both stages

    # budget with the recorder ON: observability must not add round trips.
    # Force it on (a PATHWAY_OBSERVE=0 environment must not kill the
    # phase — the A/B loop flips the switch both ways regardless) and
    # restore the environment-derived state afterwards.
    env_enabled = observe.enabled()
    observe.set_enabled(True)
    with dispatch_counter.DispatchCounter() as counter:
        pipe(queries)
    extras["dispatches_with_recorder"] = counter.dispatches
    extras["fetches_with_recorder"] = counter.fetches
    assert counter.dispatches == 2 and counter.fetches == 2, counter.events

    rounds = int(os.environ.get("BENCH_OBS_ROUNDS", "6"))
    per_round = int(
        os.environ.get("BENCH_OBS_ITERS", "10" if on_tpu else "4")
    )
    lat = {True: [], False: []}
    try:
        for _ in range(rounds):
            for mode in (True, False):
                observe.set_enabled(mode)
                pipe(queries)  # settle: the first call after a flip is warm-up
                for _ in range(per_round):
                    t0 = time.perf_counter()
                    pipe(queries)
                    lat[mode].append((time.perf_counter() - t0) * 1e3)
    finally:
        observe.set_enabled(env_enabled)
    p50_on = float(np.percentile(lat[True], 50))
    p50_off = float(np.percentile(lat[False], 50))
    overhead_pct = (p50_on - p50_off) / max(p50_off, 1e-9) * 100.0
    extras["observe_p50_on_ms"] = round(p50_on, 3)
    extras["observe_p50_off_ms"] = round(p50_off, 3)
    extras["observe_overhead_pct"] = round(overhead_pct, 3)
    # series actually populated by the workload (sanity: the recorder the
    # overhead was measured against is the one /metrics would scrape)
    stats = observe.snapshot()
    extras["observe_series"] = len(stats["histograms"])
    # ISSUE 9 satellite: with the recorder off, trace creation is a
    # single flag check — start_trace returns None, no context ever
    # activates, and no trace state moves across a full serve
    from pathway_tpu.observe import trace as trace_mod

    observe.set_enabled(False)
    try:
        t_before = trace_mod.stats()
        assert trace_mod.start_trace("bench.noop") is None
        assert trace_mod.current() is None
        pipe(queries)
        t_after = trace_mod.stats()
        assert t_after["started"] == t_before["started"], (t_before, t_after)
        assert t_after["spans_dropped"] == t_before["spans_dropped"]
    finally:
        observe.set_enabled(env_enabled)
    extras["trace_noop_verified"] = True
    return round(overhead_pct, 3)


def phase_tracing_overhead(backend: str, extras: dict) -> float:
    """Price of end-to-end serve tracing (ISSUE 9, observe/trace.py):
    the SAME coalescing serve stack driven by 16 concurrent single-query
    callers, head-sampling 1.0 (every request gets a full span tree) vs
    0.0 (tracing off), interleaved A/B so drift hits both arms equally.
    The phase value is the added p50 latency in percent — the acceptance
    budget is < 3% (BENCH_TRACE_MAX_OVERHEAD_PCT overrides).  Also
    asserts the per-batch 2+2 dispatch budget with tracing ON: span
    recording must never add a device round trip."""
    jax = _init_jax(backend)

    from pathway_tpu import observe
    from pathway_tpu.observe import trace as trace_mod
    from pathway_tpu.ops import dispatch_counter
    from pathway_tpu.serve import ServeScheduler

    backend = jax.default_backend()
    extras["backend"] = backend
    on_tpu = backend == "tpu"
    n_docs = int(os.environ.get("BENCH_TR_DOCS", "20000" if on_tpu else "1000"))
    k, candidates = 10, 32
    pipe, _cross, docs, _queries = _build_rr_pipeline(
        n_docs, 16, k, candidates, small=not on_tpu
    )
    pool = [
        " ".join(docs[(i * 9973) % n_docs].split()[:8]) for i in range(32)
    ]
    # warm every compile shape both arms touch (solo + coalesced comps)
    for q in pool:
        pipe([q], k)
    for b in range(2, 17):
        pipe(sorted(set(pool))[:b], k)

    conc = 16
    env_enabled = observe.enabled()
    observe.set_enabled(True)
    sample0 = trace_mod.sample_rate()
    window_us = float(os.environ.get("BENCH_TR_WINDOW_US", "5000"))
    max_batch = int(os.environ.get("BENCH_TR_MAX_BATCH", "16" if on_tpu else "4"))

    def burst(sched, queries, k_arg):
        res, errs = [], []
        barrier = threading.Barrier(len(queries))

        def w(q):
            try:
                barrier.wait(timeout=30)
                res.append(sched.serve([q], k_arg))
            except Exception as exc:
                errs.append(repr(exc))

        threads = [threading.Thread(target=w, args=(q,)) for q in queries]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errs:
            raise RuntimeError(f"tracing_overhead burst failed: {errs[:3]}")
        return res

    def drive(sample: float, n_req: int):
        trace_mod.set_sample(sample)
        lats: list = [None] * n_req
        errs: list = []
        sched = ServeScheduler(
            pipe, window_us=window_us, max_batch=max_batch, result_cache=None
        )
        barrier = threading.Barrier(conc)

        def worker(t: int):
            try:
                barrier.wait(timeout=30)
                for i in range(t, n_req, conc):
                    t0 = time.perf_counter()
                    rows = sched.serve([pool[(i * 7) % len(pool)]], k)
                    lats[i] = (time.perf_counter() - t0) * 1e3
                    assert rows and rows[0]
            except Exception as exc:
                errs.append(repr(exc))

        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(conc)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        sched.stop()
        if errs:
            raise RuntimeError(f"tracing_overhead c{conc} failed: {errs[:3]}")
        return np.asarray([l for l in lats if l is not None])

    try:
        # per-batch 2+2 budget with every request traced: one coalesced
        # burst of 8 distinct queries; dispatches/fetches per batch <= 2
        trace_mod.set_sample(1.0)
        trace_mod.reset()
        with ServeScheduler(
            pipe, window_us=200_000, result_cache=None
        ) as sched:
            with dispatch_counter.DispatchCounter() as counter:
                burst(sched, pool[:8], k)
            batches = max(
                1, sched.stats["batches"] + sched.stats["solo"]
            )
        extras["trace_dispatches_per_batch"] = round(
            counter.dispatches / batches, 2
        )
        extras["trace_fetches_per_batch"] = round(
            counter.fetches / batches, 2
        )
        assert counter.dispatches <= 2 * batches, (counter.events, batches)
        assert counter.fetches <= 2 * batches, (counter.events, batches)
        extras["trace_started"] = trace_mod.stats()["started"]

        # paired A/B: per-round on/off p50 RATIOS with the arm order
        # alternated, summarized by the median — at c16 on a contended
        # host the round-to-round p50 drifts by far more than the span
        # cost, and only the paired ratio cancels it
        rounds = int(os.environ.get("BENCH_TR_ROUNDS", "5"))
        n_req = int(os.environ.get("BENCH_TR_REQUESTS", str(conc * 8)))
        lat = {1.0: [], 0.0: []}
        ratios = []
        for r in range(rounds):
            order = (1.0, 0.0) if r % 2 == 0 else (0.0, 1.0)
            round_p50 = {}
            for mode in order:
                drive(mode, 2 * conc)  # settle after the sample flip
                arm = drive(mode, n_req)
                lat[mode].append(arm)
                round_p50[mode] = float(np.percentile(arm, 50))
            ratios.append(round_p50[1.0] / max(round_p50[0.0], 1e-9))
    finally:
        trace_mod.set_sample(sample0)
        observe.set_enabled(env_enabled)
    p50_on = float(np.percentile(np.concatenate(lat[1.0]), 50))
    p50_off = float(np.percentile(np.concatenate(lat[0.0]), 50))
    overhead_pct = (float(np.median(ratios)) - 1.0) * 100.0
    extras["trace_p50_on_ms"] = round(p50_on, 3)
    extras["trace_p50_off_ms"] = round(p50_off, 3)
    extras["trace_round_ratios"] = [round(x, 4) for x in ratios]
    extras["tracing_overhead_pct"] = round(overhead_pct, 3)
    t_stats = trace_mod.stats()
    extras["trace_kept"] = t_stats["kept"]
    extras["trace_spans_dropped"] = t_stats["spans_dropped"]
    max_pct = float(os.environ.get("BENCH_TRACE_MAX_OVERHEAD_PCT", "3.0"))
    assert overhead_pct < max_pct, (
        f"tracing overhead {overhead_pct:.2f}% exceeds the {max_pct}% "
        f"budget (p50 on {p50_on:.3f} ms vs off {p50_off:.3f} ms)"
    )
    return round(overhead_pct, 3)


def phase_profiling_overhead(backend: str, extras: dict) -> float:
    """Price of the attribution layer (ISSUE 12: device-time profiler +
    HBM ledger + SLO engine): the SAME coalescing serve stack driven by
    16 concurrent callers with ALL THREE on (profiler sampling every
    call, a 10 Hz scraper thread pulling the ledger + SLO document —
    harsher than any real scrape cadence) vs all off, paired-ratio A/B.
    The phase value is the added p50 latency in percent; the acceptance
    budget is < 3% (BENCH_PROF_MAX_OVERHEAD_PCT overrides).  Also
    asserts the per-batch 2+2 dispatch budget with stride-1 sampling
    (attribution never adds a round trip), checks the HBM ledger total
    against the backend's own byte accounting (within
    BENCH_HBM_TOLERANCE, default 10%), and records the per-callable
    device-second attribution the profiler produced."""
    jax = _init_jax(backend)

    from pathway_tpu import observe
    from pathway_tpu.observe import hbm, profile
    from pathway_tpu.observe import slo as slo_mod
    from pathway_tpu.ops import dispatch_counter
    from pathway_tpu.serve import ServeScheduler

    backend = jax.default_backend()
    extras["backend"] = backend
    on_tpu = backend == "tpu"
    n_docs = int(os.environ.get("BENCH_PROF_DOCS", "20000" if on_tpu else "1000"))
    k, candidates = 10, 32
    pipe, _cross, docs, _queries = _build_rr_pipeline(
        n_docs, 16, k, candidates, small=not on_tpu
    )
    pool = [
        " ".join(docs[(i * 9973) % n_docs].split()[:8]) for i in range(32)
    ]
    for q in pool:
        pipe([q], k)
    for b in range(2, 17):
        pipe(sorted(set(pool))[:b], k)

    conc = 16
    env_enabled = observe.enabled()
    observe.set_enabled(True)
    stride0 = profile.sample_stride()
    shed0 = slo_mod.shed_advisory_enabled()
    window_us = float(os.environ.get("BENCH_PROF_WINDOW_US", "5000"))
    max_batch = int(
        os.environ.get("BENCH_PROF_MAX_BATCH", "16" if on_tpu else "4")
    )

    # HBM cross-check at a quiesced point: the ledger total (params,
    # index, caches, pools) vs the backend's own resident accounting
    import gc

    gc.collect()
    ledger = hbm.sample()
    device_b = ledger["device_bytes"]
    extras["hbm_ledger_bytes"] = ledger["total_bytes"]
    extras["hbm_device_bytes"] = device_b
    extras["hbm_watermark_bytes"] = ledger["watermark_bytes"]
    extras["hbm_subsystems"] = {
        sub: sum(parts.values())
        for sub, parts in ledger["subsystems"].items()
    }
    tol = float(os.environ.get("BENCH_HBM_TOLERANCE", "0.10"))
    if device_b:
        agreement = abs(device_b - ledger["total_bytes"]) / max(device_b, 1)
        extras["hbm_agreement_pct"] = round(agreement * 100.0, 2)
        assert agreement < tol, (
            f"HBM ledger {ledger['total_bytes']} vs device {device_b} "
            f"disagree by {agreement:.1%} (> {tol:.0%}) — a consumer is "
            "off the books"
        )

    def drive(arm_on: bool, n_req: int):
        lats: list = [None] * n_req
        errs: list = []
        sched = ServeScheduler(
            pipe, window_us=window_us, max_batch=max_batch, result_cache=None
        )
        stop_scrape = threading.Event()
        scraper = None
        if arm_on:
            profile.set_sample(1.0)
            slo_mod.set_shed_advisory(True)

            def scrape_loop():
                while not stop_scrape.is_set():
                    hbm.sample()
                    slo_mod.evaluate(max_age_s=0.0)
                    profile.profile_stats()
                    stop_scrape.wait(0.1)

            scraper = threading.Thread(target=scrape_loop, daemon=True)
            scraper.start()
        else:
            profile.set_sample(0.0)
            slo_mod.set_shed_advisory(False)
        barrier = threading.Barrier(conc)

        def worker(t: int):
            try:
                barrier.wait(timeout=30)
                for i in range(t, n_req, conc):
                    t0 = time.perf_counter()
                    rows = sched.serve([pool[(i * 7) % len(pool)]], k)
                    lats[i] = (time.perf_counter() - t0) * 1e3
                    assert rows and rows[0]
            except Exception as exc:
                errs.append(repr(exc))

        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(conc)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        sched.stop()
        stop_scrape.set()
        if scraper is not None:
            scraper.join(timeout=5)
        if errs:
            raise RuntimeError(f"profiling_overhead c{conc} failed: {errs[:3]}")
        return np.asarray([l for l in lats if l is not None])

    try:
        # per-batch 2+2 with stride-1 sampling: attribution must never
        # add a device round trip
        profile.set_sample(1.0)
        with ServeScheduler(
            pipe, window_us=200_000, result_cache=None
        ) as sched:
            with dispatch_counter.DispatchCounter() as counter:
                res, errs = [], []
                barrier = threading.Barrier(8)

                def w(q):
                    try:
                        barrier.wait(timeout=30)
                        res.append(sched.serve([q], k))
                    except Exception as exc:
                        errs.append(repr(exc))

                threads = [
                    threading.Thread(target=w, args=(q,)) for q in pool[:8]
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                assert not errs, errs[:3]
            batches = max(1, sched.stats["batches"] + sched.stats["solo"])
        extras["profile_dispatches_per_batch"] = round(
            counter.dispatches / batches, 2
        )
        assert counter.dispatches <= 2 * batches, (counter.events, batches)
        assert counter.fetches <= 2 * batches, (counter.events, batches)

        # paired A/B: per-round on/off p50 ratios, arm order alternated
        rounds = int(os.environ.get("BENCH_PROF_ROUNDS", "5"))
        n_req = int(os.environ.get("BENCH_PROF_REQUESTS", str(conc * 8)))
        lat = {True: [], False: []}
        ratios = []
        for r in range(rounds):
            order = (True, False) if r % 2 == 0 else (False, True)
            round_p50 = {}
            for mode in order:
                drive(mode, 2 * conc)  # settle after the flip
                arm = drive(mode, n_req)
                lat[mode].append(arm)
                round_p50[mode] = float(np.percentile(arm, 50))
            ratios.append(round_p50[True] / max(round_p50[False], 1e-9))
    finally:
        profile.set_sample(1.0 / stride0 if stride0 else 0.0)
        slo_mod.set_shed_advisory(shed0)
        observe.set_enabled(env_enabled)
    p50_on = float(np.percentile(np.concatenate(lat[True]), 50))
    p50_off = float(np.percentile(np.concatenate(lat[False]), 50))
    overhead_pct = (float(np.median(ratios)) - 1.0) * 100.0
    extras["profile_p50_on_ms"] = round(p50_on, 3)
    extras["profile_p50_off_ms"] = round(p50_off, 3)
    extras["profile_round_ratios"] = [round(x, 4) for x in ratios]
    extras["profiling_overhead_pct"] = round(overhead_pct, 3)
    # the attribution the layer exists for: per-callable device seconds
    profile.drain()
    stats = profile.profile_stats()
    extras["profile_attribution"] = {
        name: {
            "device_s": round(row["device_s"], 4),
            "share_of_wall": round(row["share_of_wall"], 4),
            "samples": int(row["samples"]),
        }
        for name, row in sorted(stats.items())
        if row["samples"]
    }
    doc = slo_mod.evaluate(max_age_s=0.0)
    extras["slo_states"] = {
        name: row["state"] for name, row in doc["slos"].items()
    }
    max_pct = float(os.environ.get("BENCH_PROF_MAX_OVERHEAD_PCT", "3.0"))
    assert overhead_pct < max_pct, (
        f"profiling overhead {overhead_pct:.2f}% exceeds the {max_pct}% "
        f"budget (p50 on {p50_on:.3f} ms vs off {p50_off:.3f} ms)"
    )
    return round(overhead_pct, 3)


def phase_sanitizer_overhead(backend: str, extras: dict) -> float:
    """Price of the runtime lock-order sanitizer (ISSUE 13): the SAME
    c16 coalescing serve driven over a sanitizer-wrapped stack (every
    lock an order-recording proxy: held stacks, edge set, cycle check)
    vs the raw-primitive stack, paired-ratio A/B with arm order
    alternated.  The phase value is the added p50 latency in percent;
    the budget is < 3% (BENCH_SAN_MAX_OVERHEAD_PCT overrides).  Also
    asserts the 2+2 per-batch dispatch budget WITH the proxies
    installed, and that the whole run records ZERO violations (the
    sanitizer must price in clean, not by firing)."""
    jax = _init_jax(backend)

    from pathway_tpu.analysis import sanitizer
    from pathway_tpu.ops import dispatch_counter
    from pathway_tpu.serve import ServeScheduler

    backend = jax.default_backend()
    extras["backend"] = backend
    on_tpu = backend == "tpu"
    n_docs = int(os.environ.get("BENCH_SAN_DOCS", "20000" if on_tpu else "1000"))
    k, candidates = 10, 32
    conc = 16
    window_us = float(os.environ.get("BENCH_SAN_WINDOW_US", "5000"))
    max_batch = int(
        os.environ.get("BENCH_SAN_MAX_BATCH", "16" if on_tpu else "4")
    )

    # two identical stacks: one built with raw primitives, one with the
    # sanitizer installed so EVERY lock in it is a proxy (uninstalling
    # later never unwraps existing proxies, so each arm keeps its kind)
    sanitizer.uninstall()
    pipe_off, _c0, docs, _q0 = _build_rr_pipeline(
        n_docs, 16, k, candidates, small=not on_tpu
    )
    sanitizer.install()
    try:
        pipe_on, _c1, _d1, _q1 = _build_rr_pipeline(
            n_docs, 16, k, candidates, small=not on_tpu
        )
    finally:
        sanitizer.uninstall()
    pool = [
        " ".join(docs[(i * 9973) % n_docs].split()[:8]) for i in range(32)
    ]
    for pipe in (pipe_off, pipe_on):
        for q in pool[:8]:
            pipe([q], k)
        for b in (2, 4, 8, 16):
            pipe(sorted(set(pool))[:b], k)

    def drive(pipe, armed: bool, n_req: int):
        """One c16 burst; the install state is toggled around the burst
        so runtime-created locks (per-batch handoff locks) follow the
        arm being measured."""
        if armed:
            sanitizer.install()
        else:
            sanitizer.uninstall()
        lats: list = [None] * n_req
        errs: list = []
        sched = ServeScheduler(
            pipe, window_us=window_us, max_batch=max_batch, result_cache=None
        )
        barrier = threading.Barrier(conc)

        def worker(t: int):
            try:
                barrier.wait(timeout=30)
                for i in range(t, n_req, conc):
                    t0 = time.perf_counter()
                    rows = sched.serve([pool[(i * 7) % len(pool)]], k)
                    lats[i] = (time.perf_counter() - t0) * 1e3
                    assert rows and rows[0]
            except Exception as exc:
                errs.append(repr(exc))

        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(conc)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        sched.stop()
        if errs:
            raise RuntimeError(f"sanitizer_overhead c{conc} failed: {errs[:3]}")
        return np.asarray([l for l in lats if l is not None])

    try:
        # per-batch 2+2 with the proxies installed: order recording must
        # never add a device round trip
        sanitizer.install()
        with ServeScheduler(
            pipe_on, window_us=200_000, result_cache=None
        ) as sched:
            with dispatch_counter.DispatchCounter() as counter:
                res, errs = [], []
                barrier = threading.Barrier(8)

                def w(q):
                    try:
                        barrier.wait(timeout=30)
                        res.append(sched.serve([q], k))
                    except Exception as exc:
                        errs.append(repr(exc))

                threads = [
                    threading.Thread(target=w, args=(q,)) for q in pool[:8]
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                assert not errs, errs[:3]
            batches = max(1, sched.stats["batches"] + sched.stats["solo"])
        extras["sanitizer_dispatches_per_batch"] = round(
            counter.dispatches / batches, 2
        )
        assert counter.dispatches <= 2 * batches, (counter.events, batches)
        assert counter.fetches <= 2 * batches, (counter.events, batches)

        # paired A/B: per-round on/off p50 ratios, arm order alternated
        rounds = int(os.environ.get("BENCH_SAN_ROUNDS", "5"))
        n_req = int(os.environ.get("BENCH_SAN_REQUESTS", str(conc * 8)))
        lat = {True: [], False: []}
        ratios = []
        for r in range(rounds):
            order = (True, False) if r % 2 == 0 else (False, True)
            round_p50 = {}
            for mode in order:
                pipe = pipe_on if mode else pipe_off
                drive(pipe, mode, 2 * conc)  # settle after the flip
                arm = drive(pipe, mode, n_req)
                lat[mode].append(arm)
                round_p50[mode] = float(np.percentile(arm, 50))
            ratios.append(round_p50[True] / max(round_p50[False], 1e-9))
    finally:
        if sanitizer.enabled_from_env():
            sanitizer.install()
        else:
            sanitizer.uninstall()
    p50_on = float(np.percentile(np.concatenate(lat[True]), 50))
    p50_off = float(np.percentile(np.concatenate(lat[False]), 50))
    overhead_pct = (float(np.median(ratios)) - 1.0) * 100.0
    stats = sanitizer.stats()
    extras["sanitizer_p50_on_ms"] = round(p50_on, 3)
    extras["sanitizer_p50_off_ms"] = round(p50_off, 3)
    extras["sanitizer_round_ratios"] = [round(x, 4) for x in ratios]
    extras["sanitizer_overhead_pct"] = round(overhead_pct, 3)
    extras["sanitizer_locks_tracked"] = stats["locks_tracked"]
    extras["sanitizer_edges_observed"] = stats["edges_observed"]
    extras["sanitizer_violations"] = stats["violations"]
    assert all(v == 0 for v in stats["violations"].values()), (
        f"sanitizer recorded violations on the clean serve stack: "
        f"{stats['violations']}"
    )
    max_pct = float(os.environ.get("BENCH_SAN_MAX_OVERHEAD_PCT", "3.0"))
    assert overhead_pct < max_pct, (
        f"sanitizer overhead {overhead_pct:.2f}% exceeds the {max_pct}% "
        f"budget (p50 on {p50_on:.3f} ms vs off {p50_off:.3f} ms)"
    )
    return round(overhead_pct, 3)


def phase_analysis_runtime(backend: str, extras: dict) -> float:
    """ISSUE 15: (a) whole-repo analyzer wall time COLD vs WARM through
    the per-family incremental cache (``PATHWAY_ANALYSIS_CACHE``) — the
    warm run must re-parse only changed modules, asserted at < 25% of
    cold wall time (BENCH_ANALYSIS_WARM_MAX_PCT overrides); (b) the
    runtime donation guard's serve overhead: the SAME c16 coalescing
    serve driven with ``PATHWAY_DONATION_GUARD=1`` (production mode) vs
    off, paired-ratio A/B, < 3% p50 budget with the per-batch 2+2
    dispatch budget asserted under the armed guard.  Phase value = the
    donation-guard overhead in percent."""
    import shutil
    import tempfile

    # -- (a) analyzer cold vs warm ------------------------------------
    from pathway_tpu.analysis import analyze_paths

    repo_pkg = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "pathway_tpu")
    cache_dir = tempfile.mkdtemp(prefix="pathway_analysis_cache_")
    os.environ["PATHWAY_ANALYSIS_CACHE"] = cache_dir
    try:
        t0 = time.perf_counter()
        cold = analyze_paths([repo_pkg])
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        warm = analyze_paths([repo_pkg])
        warm_s = time.perf_counter() - t0
    finally:
        os.environ.pop("PATHWAY_ANALYSIS_CACHE", None)
        shutil.rmtree(cache_dir, ignore_errors=True)
    assert [f.__dict__ for f in warm] == [f.__dict__ for f in cold], (
        "warm analyzer findings drifted from cold"
    )
    live = [f for f in cold if not f.suppressed]
    assert live == [], f"analyzer tree not clean: {live[:3]}"
    warm_pct = 100.0 * warm_s / max(cold_s, 1e-9)
    extras["analysis_cold_s"] = round(cold_s, 3)
    extras["analysis_warm_s"] = round(warm_s, 3)
    extras["analysis_warm_over_cold_pct"] = round(warm_pct, 2)
    extras["analysis_findings_suppressed"] = len(cold) - len(live)
    warm_max = float(os.environ.get("BENCH_ANALYSIS_WARM_MAX_PCT", "25"))
    assert warm_pct < warm_max, (
        f"warm analyzer run at {warm_pct:.1f}% of cold exceeds the "
        f"{warm_max:.0f}% budget (cold {cold_s:.2f}s, warm {warm_s:.2f}s)"
    )

    # -- (b) donation-guard serve overhead at c16 ----------------------
    jax = _init_jax(backend)

    from pathway_tpu.ops import dispatch_counter, donation_guard
    from pathway_tpu.serve import ServeScheduler

    backend = jax.default_backend()
    extras["backend"] = backend
    on_tpu = backend == "tpu"
    n_docs = int(os.environ.get("BENCH_DG_DOCS", "20000" if on_tpu else "1000"))
    k, candidates = 10, 32
    conc = 16
    window_us = float(os.environ.get("BENCH_DG_WINDOW_US", "5000"))
    max_batch = int(os.environ.get("BENCH_DG_MAX_BATCH", "16" if on_tpu else "4"))

    os.environ.pop("PATHWAY_DONATION_GUARD", None)
    os.environ["PATHWAY_DONATION_GUARD_STRICT"] = "0"  # production mode
    pipe, _c0, docs, _q0 = _build_rr_pipeline(
        n_docs, 16, k, candidates, small=not on_tpu
    )
    pool = [
        " ".join(docs[(i * 9973) % n_docs].split()[:8]) for i in range(32)
    ]
    for q in pool[:8]:
        pipe([q], k)
    for b in (2, 4, 8, 16):
        pipe(sorted(set(pool))[:b], k)

    def drive(armed: bool, n_req: int):
        if armed:
            os.environ["PATHWAY_DONATION_GUARD"] = "1"
        else:
            os.environ.pop("PATHWAY_DONATION_GUARD", None)
        lats: list = [None] * n_req
        errs: list = []
        sched = ServeScheduler(
            pipe, window_us=window_us, max_batch=max_batch, result_cache=None
        )
        barrier = threading.Barrier(conc)

        def worker(t: int):
            try:
                barrier.wait(timeout=30)
                for i in range(t, n_req, conc):
                    t0 = time.perf_counter()
                    rows = sched.serve([pool[(i * 7) % len(pool)]], k)
                    lats[i] = (time.perf_counter() - t0) * 1e3
                    assert rows and rows[0]
            except Exception as exc:
                errs.append(repr(exc))

        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(conc)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        sched.stop()
        if errs:
            raise RuntimeError(f"analysis_runtime c{conc} failed: {errs[:3]}")
        return np.asarray([l for l in lats if l is not None])

    try:
        # per-batch 2+2 with the guard armed: poisoning bookkeeping must
        # never add a device round trip
        os.environ["PATHWAY_DONATION_GUARD"] = "1"
        with ServeScheduler(
            pipe, window_us=200_000, result_cache=None
        ) as sched:
            with dispatch_counter.DispatchCounter() as counter:
                res, errs = [], []
                barrier = threading.Barrier(8)

                def w(q):
                    try:
                        barrier.wait(timeout=30)
                        res.append(sched.serve([q], k))
                    except Exception as exc:
                        errs.append(repr(exc))

                threads = [
                    threading.Thread(target=w, args=(q,)) for q in pool[:8]
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                assert not errs, errs[:3]
            batches = max(1, sched.stats["batches"] + sched.stats["solo"])
        extras["donation_guard_dispatches_per_batch"] = round(
            counter.dispatches / batches, 2
        )
        assert counter.dispatches <= 2 * batches, (counter.events, batches)
        assert counter.fetches <= 2 * batches, (counter.events, batches)

        rounds = int(os.environ.get("BENCH_DG_ROUNDS", "5"))
        n_req = int(os.environ.get("BENCH_DG_REQUESTS", str(conc * 8)))
        lat = {True: [], False: []}
        ratios = []
        for r in range(rounds):
            order = (True, False) if r % 2 == 0 else (False, True)
            round_p50 = {}
            for mode in order:
                drive(mode, 2 * conc)  # settle after the flip
                arm = drive(mode, n_req)
                lat[mode].append(arm)
                round_p50[mode] = float(np.percentile(arm, 50))
            ratios.append(round_p50[True] / max(round_p50[False], 1e-9))
    finally:
        os.environ.pop("PATHWAY_DONATION_GUARD", None)
        os.environ.pop("PATHWAY_DONATION_GUARD_STRICT", None)
    p50_on = float(np.percentile(np.concatenate(lat[True]), 50))
    p50_off = float(np.percentile(np.concatenate(lat[False]), 50))
    overhead_pct = (float(np.median(ratios)) - 1.0) * 100.0
    stats = donation_guard.stats()
    extras["donation_guard_p50_on_ms"] = round(p50_on, 3)
    extras["donation_guard_p50_off_ms"] = round(p50_off, 3)
    extras["donation_guard_round_ratios"] = [round(x, 4) for x in ratios]
    extras["donation_guard_overhead_pct"] = round(overhead_pct, 3)
    extras["donation_guard_poisoned"] = stats["poisoned"]
    extras["donation_guard_violations"] = stats["violations"]
    assert all(v == 0 for v in stats["violations"].values()), (
        f"donation guard recorded violations on the clean serve stack: "
        f"{stats['violations']}"
    )
    max_pct = float(os.environ.get("BENCH_DG_MAX_OVERHEAD_PCT", "3.0"))
    assert overhead_pct < max_pct, (
        f"donation-guard overhead {overhead_pct:.2f}% exceeds the "
        f"{max_pct}% budget (p50 on {p50_on:.3f} ms vs off {p50_off:.3f} ms)"
    )
    return round(overhead_pct, 3)


def phase_fault_tolerance(backend: str, extras: dict) -> float:
    """Price and prove the serve-path fault-tolerance layer (ISSUE 4,
    pathway_tpu/robust): the SAME steady-state fused retrieve→rerank
    serve measured clean vs with a 1% seeded dispatch-failure rate
    injected at the stage-1 and stage-2 fault sites.  Every faulted
    serve must complete as a successful retry or a flagged degraded
    response — NEVER an exception — within 1.5x the deadline (the
    explicit grace covers retry backoff + host scheduling jitter around
    the post-deadline degrade decision), and the phase value is the
    added p50 latency in percent.  Also re-asserts the 2-dispatch +
    2-fetch budget with deadlines and retry wrappers live."""
    jax = _init_jax(backend)

    from pathway_tpu import observe
    from pathway_tpu.ops import dispatch_counter
    from pathway_tpu.robust import Deadline, inject

    backend = jax.default_backend()
    extras["backend"] = backend
    on_tpu = backend == "tpu"
    n_docs = int(os.environ.get("BENCH_FT_DOCS", "20000" if on_tpu else "1000"))
    n_queries, k, candidates = 16, 10, 32
    pipe, _cross, _docs, queries = _build_rr_pipeline(
        n_docs, n_queries, k, candidates, small=not on_tpu
    )
    pipe(queries)  # warmup: compiles both stages

    # deadline sized from a clean probe (env-overridable): generous
    # enough that the clean arm never degrades, tight enough that the
    # "degraded serves stay under the deadline" assertion means something
    probe = []
    for _ in range(3):
        t0 = time.perf_counter()
        pipe(queries)
        probe.append((time.perf_counter() - t0) * 1e3)
    deadline_ms = float(
        os.environ.get(
            "BENCH_FT_DEADLINE_MS",
            max(100.0, min(5000.0, 8.0 * float(np.percentile(probe, 50)))),
        )
    )
    extras["deadline_ms"] = round(deadline_ms, 1)

    # budget with deadlines + retry wrappers live: fault tolerance must
    # not add round trips
    with dispatch_counter.DispatchCounter() as counter:
        got = pipe(queries, deadline=Deadline.after_ms(deadline_ms))
    assert got.ok and counter.dispatches == 2 and counter.fetches == 2, (
        counter.events, got.degraded
    )

    iters = int(os.environ.get("BENCH_FT_ITERS", "30" if on_tpu else "10"))

    def run_serves(n: int):
        lats = []
        degraded = 0
        for _ in range(n):
            t0 = time.perf_counter()
            got = pipe(queries, deadline=Deadline.after_ms(deadline_ms))
            lats.append((time.perf_counter() - t0) * 1e3)
            assert len(got) == n_queries  # a serve NEVER raises or shrinks
            if getattr(got, "degraded", ()):
                degraded += 1
        return np.asarray(lats), degraded

    clean, clean_degraded = run_serves(iters)
    retries0 = observe.counter(
        "pathway_robust_retries_total", site="serve.dispatch"
    ).value + observe.counter(
        "pathway_robust_retries_total", site="rerank.dispatch"
    ).value
    fault_rate = float(os.environ.get("BENCH_FT_FAULT_RATE", "0.01"))
    inject.arm("serve.dispatch", "raise", p=fault_rate, seed=7)
    inject.arm("rerank.dispatch", "raise", p=fault_rate, seed=8)
    try:
        faulted, fault_degraded = run_serves(2 * iters)
    finally:
        inject.disarm()
    retries = observe.counter(
        "pathway_robust_retries_total", site="serve.dispatch"
    ).value + observe.counter(
        "pathway_robust_retries_total", site="rerank.dispatch"
    ).value - retries0

    # the contract under fault: completes within the deadline plus the
    # stated 1.5x grace, degrading instead of blowing through it
    grace = 1.5
    extras["deadline_grace"] = grace
    assert float(faulted.max()) < deadline_ms * grace, (
        f"faulted serve p100 {faulted.max():.1f}ms vs deadline "
        f"{deadline_ms}ms (grace {grace}x)"
    )
    p50_clean = float(np.percentile(clean, 50))
    p50_fault = float(np.percentile(faulted, 50))
    extras["p50_clean_ms"] = round(p50_clean, 3)
    extras["p99_clean_ms"] = round(float(np.percentile(clean, 99)), 3)
    extras["p50_faulted_ms"] = round(p50_fault, 3)
    extras["p99_faulted_ms"] = round(float(np.percentile(faulted, 99)), 3)
    extras["fault_rate"] = fault_rate
    extras["serves_clean"] = int(iters)
    extras["serves_faulted"] = int(2 * iters)
    extras["degraded_serves_clean"] = clean_degraded
    extras["degraded_serves_faulted"] = fault_degraded
    extras["dispatch_retries"] = int(retries)
    overhead_pct = (p50_fault - p50_clean) / max(p50_clean, 1e-9) * 100.0
    return round(overhead_pct, 3)


def phase_concurrent_serve(backend: str, extras: dict) -> float:
    """Continuous cross-request batching (pathway_tpu/serve/scheduler.py):
    the SAME steady-state retrieve→rerank stack driven by concurrent
    single-query callers at concurrency {1, 4, 16}, scheduler OFF
    (each caller pays its own 2+2 serve, serializing on the pipeline)
    vs scheduler ON (callers coalesce into shared bucketed batches with
    double-buffered stage pipelining + in-window dedup).  The workload
    has a hot query head (~1/3 of requests hit 4 hot queries — the
    serving-traffic shape dedup exists for).  Reports QPS and p50/p99
    per cell plus coalesce occupancy and dedup rate; the phase value is
    the QPS speedup at concurrency 16 (acceptance bar: >= 2x, with
    p99_on within 1.5x of the solo p50)."""
    jax = _init_jax(backend)

    from pathway_tpu.ops import dispatch_counter
    from pathway_tpu.serve import ServeScheduler

    backend = jax.default_backend()
    extras["backend"] = backend
    on_tpu = backend == "tpu"
    n_docs = int(os.environ.get("BENCH_CS_DOCS", "20000" if on_tpu else "1000"))
    k, candidates = 10, 32
    pipe, _cross, docs, _queries = _build_rr_pipeline(
        n_docs, 16, k, candidates, small=not on_tpu
    )

    # short queries against long docs (the serving shape: questions are a
    # few words, passages are paragraphs) — uniform tokenized length, so
    # the stage-1 compile shapes are the handful the warmup covers
    pool = [
        " ".join(docs[(i * 9973) % n_docs].split()[:8]) for i in range(64)
    ]
    hot = pool[:4]
    hot_every = int(os.environ.get("BENCH_CS_HOT_EVERY", "2"))

    def workload(n: int):
        # deterministic hot-head mix: every ``hot_every``-th request hits
        # one of 4 hot queries (zipf-ish serving traffic — what in-window
        # dedup exists for)
        return [
            hot[i % len(hot)]
            if i % hot_every == 0
            else pool[(i * 7) % len(pool)]
            for i in range(n)
        ]

    # warm the compile shapes both arms touch: every pool query solo
    # (the scheduler-off arm serves B=1 batches) and coalesced batch
    # compositions at every unique-count the scheduler can form (stage-2
    # row/segment buckets shift with composition; an in-measurement
    # compile would charge ~seconds to one arm's p99)
    for q in pool:
        pipe([q], k)
    for b in range(2, 17):
        pipe(sorted(set(workload(3 * b)))[:b], k)

    window_us = float(os.environ.get("BENCH_CS_WINDOW_US", "5000"))
    # bucket-aligned cap on UNIQUE queries per device batch: on CPU the
    # device compute scales with the padded bucket, so a small full
    # bucket beats a large half-empty one; on TPU bigger batches
    # amortize the dispatch + fetch further
    cs_max_batch = int(
        os.environ.get("BENCH_CS_MAX_BATCH", "16" if on_tpu else "4")
    )

    def drive(conc: int, scheduler_on: bool):
        n_req = int(
            os.environ.get("BENCH_CS_REQUESTS", str(max(32, conc * 12)))
        )
        reqs = workload(n_req)
        lats: list = [None] * n_req
        errors: list = []
        sched = (
            # result_cache=None: this phase prices COALESCING alone; the
            # serve_cache phase owns the cache-on/off A/B
            ServeScheduler(
                pipe, window_us=window_us, max_batch=cs_max_batch,
                result_cache=None,
            )
            if scheduler_on
            else None
        )
        barrier = threading.Barrier(conc)

        def worker(t: int):
            try:
                barrier.wait(timeout=30)
                for i in range(t, n_req, conc):
                    t0 = time.perf_counter()
                    if sched is not None:
                        rows = sched.serve([reqs[i]], k)
                    else:
                        rows = pipe([reqs[i]], k)
                    lats[i] = (time.perf_counter() - t0) * 1e3
                    assert rows and rows[0]
            except Exception as exc:  # surfaces in the cell's stats
                errors.append(repr(exc))

        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(conc)
        ]
        t_all = time.perf_counter()
        with dispatch_counter.DispatchCounter(max_events=16) as counter:
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        elapsed = time.perf_counter() - t_all
        stats = dict(sched.stats) if sched is not None else {}
        if sched is not None:
            sched.stop()
        if errors:
            raise RuntimeError(f"concurrent_serve c{conc} failed: {errors[:3]}")
        done = np.asarray([l for l in lats if l is not None])
        # device round trips per request: the hardware-independent count
        # behind the speedup (every dispatch/fetch pair is a host sync)
        stats["round_trips_per_request"] = round(
            (counter.dispatches + counter.fetches) / (2 * n_req), 3
        )
        return n_req / elapsed, done, stats

    speedup_c16 = 0.0
    solo_p50 = None
    for conc in (1, 4, 16):
        qps = {}
        for mode in (False, True):
            tag = "on" if mode else "off"
            # unmeasured pre-pass: the scheduler's batch compositions are
            # timing-dependent, so their stage-2 compile shapes can only
            # be warmed by actually running the arm once — a mid-
            # measurement compile would charge ~seconds to one p99
            drive(conc, mode)
            qps[tag], lat, stats = drive(conc, mode)
            extras[f"qps_{tag}_c{conc}"] = round(qps[tag], 2)
            extras[f"p50_{tag}_c{conc}_ms"] = round(float(np.percentile(lat, 50)), 3)
            extras[f"p99_{tag}_c{conc}_ms"] = round(float(np.percentile(lat, 99)), 3)
            extras[f"rtt_per_request_{tag}_c{conc}"] = stats.get(
                "round_trips_per_request"
            )
            if mode and stats.get("batches"):
                extras[f"coalesce_occupancy_c{conc}"] = round(
                    stats["items"] / stats["batches"], 2
                )
                extras[f"dedup_rate_c{conc}"] = round(
                    stats["dedup_hits"] / max(stats["items"], 1), 3
                )
        if conc == 1:
            solo_p50 = extras["p50_off_c1_ms"]
        if conc == 16:
            speedup_c16 = qps["on"] / max(qps["off"], 1e-9)
            extras["serve_coalesce_speedup_c16"] = round(speedup_c16, 3)
            extras["rtt_reduction_c16"] = round(
                extras["rtt_per_request_off_c16"]
                / max(extras["rtt_per_request_on_c16"], 1e-9), 2
            )
            if solo_p50:
                # the acceptance bar's latency arm: coalesced p99 vs the
                # uncontended solo p50
                extras["p99_on_c16_vs_solo_p50"] = round(
                    extras["p99_on_c16_ms"] / solo_p50, 3
                )
    extras["coalesce_window_us"] = window_us
    return round(speedup_c16, 3)


def phase_self_tuning(backend: str, extras: dict) -> float:
    """The closed tuning loop (ISSUE 17: serve/tuner.py + the knob
    registry): the concurrent_serve stack at c16 with the LIVE
    registry-backed coalescing window (``window_us=None``), driven
    through a SHIFTING workload — a hot query head for the first half
    of requests, then a cold long-tail over a 96-query pool — static
    registry defaults vs a background ``Tuner`` adjusting the dynamic
    knobs mid-run.  Reports QPS/p50/p99 per arm, the knob trajectory
    the tuner actually walked, the config-lookup A/B (registry ``get``
    vs a raw env parse, asserted < 1% of the tuned p50), and the
    steady-state 2+2 dispatch/fetch budget re-asserted with the tuner
    thread live.  Phase value: tuned/static QPS ratio at c16."""
    jax = _init_jax(backend)

    from pathway_tpu import config
    from pathway_tpu.cache import ResultCache
    from pathway_tpu.ops import dispatch_counter
    from pathway_tpu.serve import ServeScheduler
    from pathway_tpu.serve.tuner import Tuner

    backend = jax.default_backend()
    extras["backend"] = backend
    on_tpu = backend == "tpu"
    n_docs = int(os.environ.get("BENCH_ST_DOCS", "20000" if on_tpu else "1000"))
    k, candidates = 10, 32
    pipe, _cross, docs, _queries = _build_rr_pipeline(
        n_docs, 16, k, candidates, small=not on_tpu
    )

    pool = [
        " ".join(docs[(i * 9973) % n_docs].split()[:8]) for i in range(96)
    ]
    hot = pool[:4]

    def workload(n: int):
        # the SHIFT the tuner exists for: 2/3 of the first half hits 4
        # hot queries (dedup/result-cache traffic), then the second half
        # walks a cold long-tail over the full 96-query pool — the
        # profitable window/budget settings move mid-run
        return [
            (hot[i % 4] if i % 3 else pool[(i * 7) % 64])
            if i < n // 2
            else pool[(i * 11 + 5) % len(pool)]
            for i in range(n)
        ]

    # warm the compile shapes both arms touch (solo serves + coalesced
    # batch compositions) — a mid-measurement compile would charge
    # ~seconds to one arm's p99
    for q in pool:
        pipe([q], k)
    for b in range(2, 17):
        pipe(sorted(set(workload(3 * b)))[:b], k)

    conc = 16
    max_batch = int(
        os.environ.get("BENCH_ST_MAX_BATCH", "16" if on_tpu else "4")
    )
    n_req = int(os.environ.get("BENCH_ST_REQUESTS", str(conc * 16)))
    tick_s = float(os.environ.get("BENCH_ST_TICK_S", "0.05"))

    def drive(tuned: bool):
        config.clear_overrides()  # each arm starts from declared defaults
        reqs = workload(n_req)
        lats: list = [None] * n_req
        errors: list = []
        cache = ResultCache()
        sched = ServeScheduler(
            # window_us=None: the batcher re-reads serve.coalesce_us from
            # the registry every batch window — the surface the tuner's
            # adjustments land on while the arm is RUNNING
            pipe, window_us=None, max_batch=max_batch, result_cache=cache,
        )
        tuner = None
        traj: list = []
        if tuned:
            tuner = Tuner(interval_s=tick_s)
            orig_tick = tuner.tick

            def tick_and_log():
                applied = orig_tick()
                if applied:
                    traj.append({
                        "tick": tuner.stats["ticks"],
                        "overrides": dict(config.overrides()),
                    })
                return applied

            tuner.tick = tick_and_log
            tuner.start()
        barrier = threading.Barrier(conc)

        def worker(t: int):
            try:
                barrier.wait(timeout=30)
                for i in range(t, n_req, conc):
                    t0 = time.perf_counter()
                    rows = sched.serve([reqs[i]], k)
                    lats[i] = (time.perf_counter() - t0) * 1e3
                    assert rows and rows[0]
            except Exception as exc:  # surfaces in the arm's stats
                errors.append(repr(exc))

        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(conc)
        ]
        t_all = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t_all
        stats = dict(sched.stats)
        sched.stop()
        s = cache.stats
        stats["result_hit_rate"] = round(
            s["hits"] / max(s["hits"] + s["misses"], 1), 3
        )
        if tuner is not None:
            # 2+2 budget with the tuner LIVE: adaptation must never cost
            # device round trips on the steady-state serve path
            with dispatch_counter.DispatchCounter() as counter:
                pipe([pool[7]], k)
            assert counter.dispatches <= 2, counter.dispatches
            assert counter.fetches <= 2, counter.fetches
            stats["budget_dispatches_tuner_live"] = counter.dispatches
            stats["budget_fetches_tuner_live"] = counter.fetches
            stats["tuner_ticks"] = tuner.stats["ticks"]
            stats["tuner_adjustments"] = tuner.stats["adjustments"]
            stats["final_overrides"] = dict(config.overrides())
            stats["knob_trajectory"] = traj
            tuner.stop()
            tuner.revert()
            config.clear_overrides()
        if errors:
            raise RuntimeError(f"self_tuning failed: {errors[:3]}")
        done = np.asarray([l for l in lats if l is not None])
        return n_req / elapsed, done, stats

    qps = {}
    tuned_stats: dict = {}
    for tuned in (False, True):
        tag = "tuned" if tuned else "static"
        # unmeasured pre-pass: batch compositions (and, tuned, the knob
        # path itself) are timing-dependent — warm them by running the
        # arm once before the measured drive
        drive(tuned)
        qps[tag], lat, stats = drive(tuned)
        extras[f"qps_{tag}_c{conc}"] = round(qps[tag], 2)
        extras[f"p50_{tag}_c{conc}_ms"] = round(float(np.percentile(lat, 50)), 3)
        extras[f"p99_{tag}_c{conc}_ms"] = round(float(np.percentile(lat, 99)), 3)
        extras[f"result_hit_rate_{tag}"] = stats["result_hit_rate"]
        if tuned:
            tuned_stats = stats
            extras["tuner_ticks"] = stats["tuner_ticks"]
            extras["tuner_adjustments"] = stats["tuner_adjustments"]
            extras["knob_trajectory"] = stats["knob_trajectory"]
            extras["tuned_final_overrides"] = stats["final_overrides"]
            extras["budget_dispatches_tuner_live"] = stats[
                "budget_dispatches_tuner_live"
            ]
            extras["budget_fetches_tuner_live"] = stats[
                "budget_fetches_tuner_live"
            ]
            # "demonstrably adapts": the measured tuned arm must have
            # ticked and moved at least one knob on this workload
            assert stats["tuner_ticks"] >= 1
            assert stats["tuner_adjustments"] >= 1, "tuner never adjusted"

    # config-lookup overhead A/B: the registry's cached typed get vs the
    # raw env parse it replaced, priced against the tuned p50 at the
    # registry-read rate the serve path ACTUALLY pays — one live
    # ``coalesce_window_s()`` read per batch window, amortized over the
    # requests that window serves (cache/dedup hits never reach it)
    n_lk = int(os.environ.get("BENCH_ST_LOOKUPS", "50000"))
    t0 = time.perf_counter()
    for _ in range(n_lk):
        config.get("serve.coalesce_us")
    get_s = (time.perf_counter() - t0) / n_lk
    t0 = time.perf_counter()
    for _ in range(n_lk):
        float(os.environ.get("PATHWAY_SERVE_COALESCE_US") or 2000.0)
    raw_s = (time.perf_counter() - t0) / n_lk
    extras["config_get_ns"] = round(get_s * 1e9, 1)
    extras["raw_env_parse_ns"] = round(raw_s * 1e9, 1)
    reads_per_req = tuned_stats.get("batches", n_req) / max(n_req, 1)
    extras["registry_reads_per_request"] = round(reads_per_req, 3)
    share = (get_s * reads_per_req) / max(
        extras[f"p50_tuned_c{conc}_ms"] * 1e-3, 1e-9
    )
    extras["config_lookup_share_of_p50"] = round(share, 5)
    assert share < 0.01, f"config.get overhead {share:.2%} of tuned p50"

    speedup = qps["tuned"] / max(qps["static"], 1e-9)
    extras["self_tuning_speedup_c16"] = round(speedup, 3)
    return round(speedup, 3)


def phase_sharded_serve(backend: str, extras: dict) -> float:
    """Sharded serving (ISSUE 7 / ROADMAP item 1): the SAME coalescing
    serve stack over a 1-shard vs an N-shard ``ShardedIvfIndex`` (N = 8
    forced host devices on CPU, the physical chip count on TPU), driven
    by 16 concurrent single-query callers.  Reports QPS + p50/p99 per
    shard count, the on-device hierarchical merge's share of serve
    latency (A/B against the host-merge probe, the MULTICHIP_r05
    methodology: ``merge_share = (global_topk - per_shard_only) /
    global_topk``, clamped at 0), and the dead-shard ladder (one shard
    down ⇒ every serve flagged ``shard_skipped``, zero errors).  Phase
    value: merge share as a percentage of serve latency (acceptance bar
    < 5%)."""
    if backend == "cpu" and "xla_force_host_platform_device_count" not in (
        os.environ.get("XLA_FLAGS", "")
    ):
        # the shard axis must be real before the first backend init
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
    jax = _init_jax(backend)

    from pathway_tpu import observe
    from pathway_tpu.models.encoder import SentenceEncoder
    from pathway_tpu.ops.ivf import ShardedIvfIndex
    from pathway_tpu.ops.serving import FusedEncodeSearch
    from pathway_tpu.robust import SHARD_SKIPPED, inject
    from pathway_tpu.serve import ServeScheduler

    backend = jax.default_backend()
    extras["backend"] = backend
    on_tpu = backend == "tpu"
    n_devices = len(jax.devices())
    n_shards = min(8, n_devices)
    extras["n_devices"] = n_devices
    n_docs = int(os.environ.get("BENCH_SS_DOCS", "40000" if on_tpu else "2000"))
    docs = _corpus_texts(n_docs)
    dims = dict(dimension=128, n_layers=2, n_heads=4, max_length=64,
                vocab_size=2048)
    if on_tpu:
        dims = dict(dimension=384, n_layers=4, n_heads=8, max_length=64,
                    vocab_size=8192)
    enc = SentenceEncoder(**dims)
    keys = list(range(n_docs))
    vecs = enc.encode(docs)
    pool = [" ".join(docs[(i * 9973) % n_docs].split()[:8]) for i in range(64)]
    k = 10
    conc = int(os.environ.get("BENCH_SS_CONC", "16"))
    n_req = int(os.environ.get("BENCH_SS_REQUESTS", str(conc * 12)))

    def build(shards: int) -> FusedEncodeSearch:
        idx = ShardedIvfIndex(
            int(enc.config.d_model), metric="cos", n_shards=shards,
            absorb_threshold=100_000,
        )
        idx.add(keys, vecs)
        idx.build()
        return FusedEncodeSearch(enc, idx, k=k)

    def drive(serve: FusedEncodeSearch, tag: str):
        # result_cache=None: the phase prices the sharded dispatch path;
        # a tier-0 hit on the repeating pool would skip it entirely
        sched = ServeScheduler(
            serve, window_us=5000, max_batch=16, result_cache=None
        )
        lats: list = [None] * n_req
        errors: list = []
        barrier = threading.Barrier(conc)

        def worker(t: int):
            try:
                barrier.wait(timeout=30)
                for i in range(t, n_req, conc):
                    t0 = time.perf_counter()
                    rows = sched.serve([pool[(i * 7) % len(pool)]], k)
                    lats[i] = (time.perf_counter() - t0) * 1e3
                    assert rows and rows[0]
            except Exception as exc:
                errors.append(repr(exc))

        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(conc)
        ]
        t_all = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t_all
        sched.stop()
        if errors:
            raise RuntimeError(f"sharded_serve {tag} failed: {errors[:3]}")
        done = np.asarray([l for l in lats if l is not None])
        extras[f"qps_{tag}_c{conc}"] = round(n_req / elapsed, 2)
        extras[f"p50_{tag}_c{conc}_ms"] = round(float(np.percentile(done, 50)), 3)
        extras[f"p99_{tag}_c{conc}_ms"] = round(float(np.percentile(done, 99)), 3)
        return n_req / elapsed

    serve1 = build(1)
    serveN = build(n_shards)
    for q in pool:  # warm both arms' compile shapes
        serve1([q], k)
        serveN([q], k)
    for b in (2, 4, 8, 16):
        batch = sorted(set(pool))[:b]
        serve1(batch, k)
        serveN(batch, k)
    drive(serve1, "shards1")  # unmeasured pre-pass per arm, then measured
    qps1 = drive(serve1, "shards1")
    drive(serveN, f"shards{n_shards}")
    qpsN = drive(serveN, f"shards{n_shards}")
    extras["sharded_qps_ratio"] = round(qpsN / max(qps1, 1e-9), 3)

    # merge share: global-topk (device tree merge, one fetch) vs
    # per-shard-only (skip the merge kernel, fetch every shard's list,
    # merge on host) — the MULTICHIP_r05 dryrun methodology
    probe = pool[:16]
    reps = int(os.environ.get("BENCH_SS_MERGE_REPS", "30"))
    serveN(probe, k)
    times = {}
    for mode in ("device", "host"):
        serveN.shard_host_merge = mode == "host"
        serveN(probe, k)  # warm this arm
        samples = []
        for _ in range(reps):
            t0 = time.perf_counter()
            serveN(probe, k)
            samples.append((time.perf_counter() - t0) * 1e3)
        times[mode] = float(np.percentile(samples, 50))
    serveN.shard_host_merge = False
    merge_share = max(0.0, (times["device"] - times["host"]) / times["device"])
    extras["global_topk_p50_ms"] = round(times["device"], 3)
    extras["per_shard_only_p50_ms"] = round(times["host"], 3)
    extras["merge_share_pct"] = round(merge_share * 100.0, 2)
    observe.gauge("pathway_serve_shard_merge_share").set(merge_share)

    # dead-shard ladder: one shard down for a whole serve burst — every
    # serve flagged shard_skipped, zero exceptions
    dead = n_shards - 1
    degraded = 0
    with inject.armed(f"shard.dispatch.{dead}", "raise"):
        for i in range(16):
            rows = serveN([pool[i % len(pool)]], k)
            assert rows and rows[0]
            degraded += SHARD_SKIPPED in rows.degraded
    extras["dead_shard_degraded_serves"] = degraded
    extras["dead_shard_errors"] = 0
    clean = serveN([pool[0]], k)
    assert clean.degraded == ()
    extras["n_shards"] = n_shards
    return extras["merge_share_pct"]


_PEAK_BF16_FLOPS = {
    # per-chip peak dense bf16 FLOP/s by jax device_kind (Google Cloud
    # documentation, "TPU v5e"); a kind that is not here is an error
    "TPU v5 lite": 197e12,
}


def _peak_flops(jax) -> float | None:
    """Peak of the device the phase runs on; None only on the CPU, where
    a utilization is not a device metric."""
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    if dev.device_kind not in _PEAK_BF16_FLOPS:
        raise KeyError(
            f"no peak FLOP/s entry for device_kind {dev.device_kind!r}; "
            f"known: {sorted(_PEAK_BF16_FLOPS)}"
        )
    return _PEAK_BF16_FLOPS[dev.device_kind]


def _realistic_corpus(n: int, seed: int = 0):
    """Variable-length documents with a log-normal word-count distribution
    (r4 Weak #1: the old corpus was uniform synthetic, every doc padding to
    T=32 — flattering and unrealistic).  Sentences are natural-ish prose
    assembled from a topic vocabulary; token lengths span ~8..128."""
    rng = np.random.default_rng(seed)
    subjects = [
        "the connector", "a worker", "the scheduler", "this index",
        "the pipeline", "each shard", "the snapshot", "a reducer",
        "the tokenizer", "that stream",
    ]
    verbs = [
        "commits", "retracts", "ingests", "reshards", "compacts",
        "replays", "serves", "joins", "windows", "deduplicates",
    ]
    objects = [
        "late events", "update deltas", "offset antichains", "key ranges",
        "document chunks", "embedding rows", "commit ticks", "upsert chains",
        "window panes", "probe tables",
    ]
    tails = [
        "under backpressure", "during recovery", "at the frontier",
        "across the mesh", "with exactly once delivery", "on the hot path",
        "before the deadline", "in the steady state",
    ]
    # log-normal word counts, clipped: median ~18 words, tail to ~110
    n_words = np.clip(
        rng.lognormal(mean=2.9, sigma=0.7, size=n), 6, 110
    ).astype(int)
    docs = []
    for i in range(n):
        words = []
        while len(words) < n_words[i]:
            words.extend(
                (
                    subjects[rng.integers(len(subjects))],
                    verbs[rng.integers(len(verbs))],
                    objects[rng.integers(len(objects))],
                    tails[rng.integers(len(tails))],
                )
            )
        docs.append(f"document {i}: " + " ".join(words[: n_words[i]]) + ".")
    return docs


def phase_serve_cache(backend: str, extras: dict) -> float:
    """Multi-tier serve cache (ISSUE 8, pathway_tpu/cache): the SAME
    hot-head mix ``concurrent_serve`` uses, driven at concurrency 8
    through the coalescing scheduler with the cache OFF, RESULT-tier
    only, and ALL serve tiers (result + embedding).  Reports QPS and
    p50/p99 per arm, per-tier hit rates, and the zero-dispatch fraction
    (requests resolved with no device work at all), plus the generator
    prefix/KV tier's prefill-token savings over a shared-prefix RAG
    prompt set.  Phase value: QPS speedup, all tiers vs cache off
    (arxiv 2412.15246 reports this caching layer as the dominant RAG
    serving speedup — here it is measured, not assumed)."""
    jax = _init_jax(backend)

    from pathway_tpu.cache import EmbeddingCache, PrefixKVCache, ResultCache
    from pathway_tpu.models.generator import TextGenerator
    from pathway_tpu.ops import dispatch_counter
    from pathway_tpu.serve import ServeScheduler

    backend = jax.default_backend()
    extras["backend"] = backend
    on_tpu = backend == "tpu"
    n_docs = int(os.environ.get("BENCH_SC_DOCS", "20000" if on_tpu else "1000"))
    k, candidates = 10, 32
    pipe, _cross, docs, _queries = _build_rr_pipeline(
        n_docs, 16, k, candidates, small=not on_tpu
    )
    pool = [
        " ".join(docs[(i * 9973) % n_docs].split()[:8]) for i in range(64)
    ]
    hot = pool[:4]

    def workload(n: int):
        # the concurrent_serve hot-head mix: every 2nd request hits one
        # of 4 hot queries — the repeat traffic the cache tiers absorb
        return [
            hot[i % len(hot)] if i % 2 == 0 else pool[(i * 7) % len(pool)]
            for i in range(n)
        ]

    for q in pool:
        pipe([q], k)  # warm the solo compile shapes
    for b in range(2, 9):
        pipe(sorted(set(workload(3 * b)))[:b], k)

    conc = int(os.environ.get("BENCH_SC_CONC", "8"))
    n_req = int(os.environ.get("BENCH_SC_REQUESTS", str(conc * 16)))

    def drive(arm: str, result_cache, embed):
        pipe.retriever.embed_cache = embed
        # the embedding tier persists across the warm pre-pass, so its
        # rate must come from THIS drive's deltas (the scheduler stats
        # below are per-drive already — the two rates must be comparable)
        embed0 = dict(embed.stats) if embed is not None else {}
        sched = ServeScheduler(
            pipe, window_us=5000, max_batch=8, result_cache=result_cache
        )
        reqs = workload(n_req)
        lats: list = [None] * n_req
        errors: list = []
        barrier = threading.Barrier(conc)

        def worker(t: int):
            try:
                barrier.wait(timeout=30)
                for i in range(t, n_req, conc):
                    t0 = time.perf_counter()
                    rows = sched.serve([reqs[i]], k)
                    lats[i] = (time.perf_counter() - t0) * 1e3
                    assert rows and rows[0]
            except Exception as exc:
                errors.append(repr(exc))

        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(conc)
        ]
        t_all = time.perf_counter()
        with dispatch_counter.DispatchCounter(max_events=16) as counter:
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        elapsed = time.perf_counter() - t_all
        stats = dict(sched.stats)
        sched.stop()
        pipe.retriever.embed_cache = None
        if errors:
            raise RuntimeError(f"serve_cache arm {arm} failed: {errors[:3]}")
        done = np.asarray([l for l in lats if l is not None])
        qps = n_req / elapsed
        extras[f"qps_{arm}"] = round(qps, 2)
        extras[f"p50_{arm}_ms"] = round(float(np.percentile(done, 50)), 3)
        extras[f"p99_{arm}_ms"] = round(float(np.percentile(done, 99)), 3)
        if result_cache is not None:
            hits = stats.get("cache_hits", 0)
            extras[f"result_hit_rate_{arm}"] = round(hits / n_req, 3)
            # a tier-0 hit is a serve with ZERO device work
            extras[f"zero_dispatch_fraction_{arm}"] = round(hits / n_req, 3)
        if embed is not None:
            hits = embed.stats["hits"] - embed0.get("hits", 0)
            misses = embed.stats["misses"] - embed0.get("misses", 0)
            extras["embed_hit_rate_all"] = round(
                hits / max(hits + misses, 1), 3
            )
        extras[f"dispatches_{arm}"] = counter.dispatches
        return qps

    qps_by_arm = {}
    enc = pipe.retriever.encoder
    for i, arm in enumerate(("off", "result", "all")):
        # per-arm caches persist across the pre-pass and the measured
        # pass, and an index ADD lands in between: the measurement is
        # the honest production shape — a mutation just invalidated
        # every tier-0 entry (generation keying), so the result tier
        # earns only its IN-PASS repeat hits, while the embedding tier
        # (keyed on token ids, mutation-immune) still skips the encode
        # for every query the pre-pass saw.
        result_cache = None if arm == "off" else ResultCache()
        embed = EmbeddingCache() if arm == "all" else None
        drive(arm, result_cache, embed)  # unmeasured warm pre-pass
        pipe.retriever.index.add(
            [10**7 + i], enc.encode([f"invalidation probe document {i}"])
        )
        qps_by_arm[arm] = drive(arm, result_cache, embed)
    speedup = qps_by_arm["all"] / max(qps_by_arm["off"], 1e-9)
    extras["serve_cache_speedup"] = round(speedup, 3)
    extras["serve_cache_speedup_result_only"] = round(
        qps_by_arm["result"] / max(qps_by_arm["off"], 1e-9), 3
    )

    # -- generator prefix/KV tier: prefill-token savings --------------------
    kv = PrefixKVCache(block=16)
    gen = TextGenerator(
        dimension=64 if not on_tpu else 256,
        n_layers=2 if not on_tpu else 4,
        n_heads=4,
        max_length=192,
        vocab_size=4096,
        kv_cache=kv,
    )
    shared = (
        "answer strictly from the retrieved context. "
        + " ".join(docs[0].split()[:60])
        + " "
    )
    prompts = [shared + q for q in pool[:8]]
    gen.generate([prompts[0]], max_new_tokens=8)  # cold: seeds the prefix
    kv.stats_tokens.update(reused=0, computed=0)
    t0 = time.perf_counter()
    for p in prompts[1:]:
        gen.generate([p], max_new_tokens=8)
    extras["kv_generate_s"] = round(time.perf_counter() - t0, 3)
    reused = kv.stats_tokens["reused"]
    computed = kv.stats_tokens["computed"]
    extras["kv_prefill_tokens_reused"] = int(reused)
    extras["kv_prefill_tokens_computed"] = int(computed)
    # sub-linearity: the shared prefix is reused, so the marginal prompt
    # prefills strictly less than its full length
    extras["kv_prefill_savings_fraction"] = round(
        reused / max(reused + computed, 1), 3
    )
    assert reused > 0, "shared-prefix prompts reused no prefill blocks"
    return round(speedup, 3)


def phase_continuous_decode(backend: str, extras: dict) -> float:
    """Continuous token-level batching for generator decode (ISSUE 10,
    pathway_tpu/serve/decode.py): aggregate tokens/s and p99
    time-to-last-token at concurrency {1, 4, 16} for the slotted
    continuous engine vs CALL-level batching (each request a solo
    ``generate()`` — the KV-cache decode, the strongest per-call
    baseline), over a mixed workload: short EOS-heavy requests (each
    prompt's own early greedy token used as its EOS, so it genuinely
    finishes at ~4 of its 32-token budget) + long answers, half the
    prompts sharing a rerank-style prefix (the PrefixKVCache warms both
    arms equally).  Outputs are token-identical across arms, so the
    tokens/s ratio IS the wall-clock ratio.  Also reports average slot
    occupancy per step chunk and the bounded compile census.  Phase
    value: tokens/s speedup at concurrency 16 (acceptance: >= 2x)."""
    jax = _init_jax(backend)

    from pathway_tpu.cache import PrefixKVCache
    from pathway_tpu.models.generator import TextGenerator
    from pathway_tpu.serve import ContinuousDecoder

    backend = jax.default_backend()
    extras["backend"] = backend
    on_tpu = backend == "tpu"
    kv = PrefixKVCache(block=16)
    gen = TextGenerator(
        dimension=256 if on_tpu else 64,
        n_layers=4 if on_tpu else 2,
        n_heads=4,
        max_length=192,
        vocab_size=4096,
        kv_cache=kv,
    )
    shared = (
        "rerank the following passages for the query about incremental "
        "dataflow serving latency and freshness guarantees "
    )
    topics = [
        "vector index maintenance", "stream joins", "exactly once",
        "window aggregation", "kafka offsets", "snapshot replay",
        "sharded state", "commit ticks", "mesh collectives",
        "tokenizer ingest", "cross encoders", "packing rows",
    ]
    n_prompts = 16
    prompts = [
        (shared if i % 2 == 0 else "standalone question about ")
        + topics[i % len(topics)]
        + f" variant {i}"
        for i in range(n_prompts)
    ]
    budget = 32
    # EOS-heavy short half: each short prompt's own 4th greedy token is
    # its EOS, so rerun with that EOS finishes honestly at ~4 tokens
    eos_of: dict = {}
    for i, p in enumerate(prompts):
        out = gen.generate([p], max_new_tokens=budget)[0]
        toks = [int(t.strip("<>")) for t in out.split()]
        if i % 2 == 0 and len(toks) > 4:
            eos_of[i] = toks[3]

    def requests(n: int):
        return [
            (prompts[j % n_prompts], eos_of.get(j % n_prompts))
            for j in range(n)
        ]

    def drive_call_level(conc: int, n_req: int):
        lats: list = [None] * n_req
        outs: list = [None] * n_req
        reqs = requests(n_req)
        barrier = threading.Barrier(conc)
        errors: list = []

        def worker(t: int):
            try:
                barrier.wait(timeout=30)
                for i in range(t, n_req, conc):
                    p, eos = reqs[i]
                    t0 = time.perf_counter()
                    outs[i] = gen.generate(
                        [p], max_new_tokens=budget, eos_id=eos
                    )[0]
                    lats[i] = (time.perf_counter() - t0) * 1e3
            except Exception as exc:  # noqa: BLE001
                errors.append(repr(exc))

        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(conc)
        ]
        t_all = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t_all
        if errors:
            raise RuntimeError(f"call-level arm failed: {errors[:3]}")
        return wall, lats, outs

    def drive_continuous(conc: int, n_req: int, eng):
        lats: list = [None] * n_req
        outs: list = [None] * n_req
        reqs = requests(n_req)
        barrier = threading.Barrier(conc)
        errors: list = []

        def worker(t: int):
            try:
                barrier.wait(timeout=30)
                for i in range(t, n_req, conc):
                    p, eos = reqs[i]
                    t0 = time.perf_counter()
                    outs[i] = eng.submit(
                        p, max_new_tokens=budget, eos_id=eos
                    )()
                    lats[i] = (time.perf_counter() - t0) * 1e3
            except Exception as exc:  # noqa: BLE001
                errors.append(repr(exc))

        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(conc)
        ]
        t_all = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t_all
        if errors:
            raise RuntimeError(f"continuous arm failed: {errors[:3]}")
        return wall, lats, outs

    def tokens_of(outs) -> int:
        return sum(len(str(o).split()) for o in outs)

    speedup_c16 = 0.0
    # ONE engine for every concurrency level: slot count and chunk are
    # compile-shape dimensions, so reusing the pool keeps the step loop
    # at one compiled program across the whole phase
    eng = ContinuousDecoder(
        # kv_width: the workload is known-short (prompt+budget <= 64
        # tokens), so the pool attends 96 wide instead of max_len=192 —
        # tokens are width-invariant, step cost is not
        gen, slots=16, step_bucket=32, name="bench-decode", kv_width=96,
    )
    try:
        # warm BOTH arms' compile shapes (and the prefix cache) off the
        # clock: every prompt at its measured eos/budget, both paths —
        # then two concurrent warm drives so the BATCHED join-prefill
        # shapes (cohort buckets) compile before anything is timed
        for p, eos in requests(n_prompts):
            gen.generate([p], max_new_tokens=budget, eos_id=eos)
            eng.submit(p, max_new_tokens=budget, eos_id=eos)()
        for _ in range(2):
            drive_continuous(16, 64, eng)
        for conc in (1, 4, 16):
            n_req = conc * (8 if conc >= 16 else 4)
            # the headline c16 cell takes the best of three rounds PER ARM
            # (both arms equally): the engine's single loop thread is
            # sensitive to scheduler noise on a shared CPU host, and one
            # descheduled quantum should not masquerade as throughput
            rounds = 3 if conc >= 16 else 1
            w_call, l_call, o_call = drive_call_level(conc, n_req)
            for _ in range(rounds - 1):
                w2, l2, o2 = drive_call_level(conc, n_req)
                if w2 < w_call:
                    w_call, l_call, o_call = w2, l2, o2
            chunks0 = eng.pool_stats["chunks"]
            occ0 = eng.pool_stats["occupancy_sum"]
            fin0 = eng.pool_stats["finished"]
            w_cont, l_cont, o_cont = drive_continuous(conc, n_req, eng)
            for _ in range(rounds - 1):
                w2, l2, o2 = drive_continuous(conc, n_req, eng)
                if w2 < w_cont:
                    w_cont, l_cont, o_cont = w2, l2, o2
            # token identity across arms — the speedup is not bought
            # with different (or truncated) outputs
            assert [str(o) for o in o_call] == [str(o) for o in o_cont]
            tok = tokens_of(o_cont)
            tps_call = tok / max(w_call, 1e-9)
            tps_cont = tok / max(w_cont, 1e-9)
            extras[f"decode_tokens_per_s_call_c{conc}"] = round(tps_call, 1)
            extras[f"decode_tokens_per_s_cont_c{conc}"] = round(tps_cont, 1)
            extras[f"decode_p99_ttlt_call_c{conc}_ms"] = round(
                float(np.percentile(np.asarray(l_call), 99)), 2
            )
            extras[f"decode_p99_ttlt_cont_c{conc}_ms"] = round(
                float(np.percentile(np.asarray(l_cont), 99)), 2
            )
            if conc == 16:
                speedup_c16 = tps_cont / max(tps_call, 1e-9)
                chunks = eng.pool_stats["chunks"] - chunks0
                occ = eng.pool_stats["occupancy_sum"] - occ0
                extras["decode_slot_occupancy_avg_c16"] = round(
                    occ / max(chunks, 1), 2
                )
                extras["decode_requests_finished_c16"] = (
                    eng.pool_stats["finished"] - fin0
                )
    finally:
        eng.stop()
    extras["decode_compile_signatures"] = gen._tripwire.signatures
    extras["decode_prefill_reused_fraction"] = round(
        kv.stats_tokens["reused"]
        / max(kv.stats_tokens["reused"] + kv.stats_tokens["computed"], 1),
        3,
    )
    extras["continuous_decode_speedup_c16"] = round(speedup_c16, 3)
    extras["continuous_decode_speedup_ok"] = bool(speedup_c16 >= 2.0)
    return round(speedup_c16, 3)


def phase_speculative_decode(backend: str, extras: dict) -> float:
    """Speculative decode + int8 KV slot pool (ISSUE 16,
    serve/decode.py): the continuous engine's self-speculative
    draft→verify rounds vs its own plain step chunks — IDENTICAL pool
    shapes, one knob apart — over the continuous_decode RAG workload
    (half the prompts share a rerank-style prefix; requests repeat the
    prompt set the way serving traffic repeats popular queries — the
    cross-request suffix corpus's regime; the EOS-heavy short half
    finishes INSIDE a verify chunk, exercising the truncation path).
    Both arms run SATURATED: the whole request queue is submitted
    up-front so the 16 slots stay occupied and the ratio measures
    decode throughput, not closed-loop ticket latency (the
    continuous_decode phase owns that).  Outputs are token-identical
    across arms — speculation is a dispatch-count optimisation, not a
    different sampler — so the tokens/s ratio IS the wall-clock ratio.
    Also proves the int8 pool's capacity claim in the HBM ledger's own
    units: a 2x-slot int8 pool (dequant scales included) fits the bf16
    pool's byte budget and still serves speculatively.  Phase value:
    aggregate tokens/s speedup at 16 occupied slots, spec-on vs
    spec-off (acceptance: >= 1.3x with accepted-tokens/round > 1)."""
    jax = _init_jax(backend)

    from pathway_tpu.cache import PrefixKVCache
    from pathway_tpu.models.generator import TextGenerator
    from pathway_tpu.serve import ContinuousDecoder

    backend = jax.default_backend()
    extras["backend"] = backend
    on_tpu = backend == "tpu"
    gen = TextGenerator(
        dimension=256 if on_tpu else 64,
        n_layers=4 if on_tpu else 2,
        n_heads=4,
        max_length=192,
        vocab_size=4096,
        kv_cache=PrefixKVCache(block=16),
    )
    shared = (
        "rerank the following passages for the query about incremental "
        "dataflow serving latency and freshness guarantees "
    )
    topics = [
        "vector index maintenance", "stream joins", "exactly once",
        "window aggregation", "kafka offsets", "snapshot replay",
        "sharded state", "commit ticks", "mesh collectives",
        "tokenizer ingest", "cross encoders", "packing rows",
    ]
    n_prompts = 16
    prompts = [
        (shared if i % 2 == 0 else "standalone question about ")
        + topics[i % len(topics)]
        + f" variant {i}"
        for i in range(n_prompts)
    ]
    # budget 64 with a 96-wide pool: prompt + budget fits every lane,
    # and pos + k <= 96 holds right up to the last verify round
    budget = 64
    spec_k = 16
    eos_of: dict = {}
    for i, p in enumerate(prompts):
        out = gen.generate([p], max_new_tokens=budget)[0]
        toks = [int(t.strip("<>")) for t in out.split()]
        if i % 2 == 0 and len(toks) > 4:
            eos_of[i] = toks[3]

    def requests(n: int):
        return [
            (prompts[j % n_prompts], eos_of.get(j % n_prompts))
            for j in range(n)
        ]

    def drive(n_req: int, eng):
        """Saturated drive: submit the whole queue, then resolve —
        the pool stays at full occupancy until the tail drains."""
        reqs = requests(n_req)
        t0 = time.perf_counter()
        tickets = [
            eng.submit(p, max_new_tokens=budget, eos_id=eos)
            for p, eos in reqs
        ]
        outs = [t() for t in tickets]
        return time.perf_counter() - t0, outs

    def tokens_of(outs) -> int:
        return sum(len(str(o).split()) for o in outs)

    eng_plain = ContinuousDecoder(
        gen, slots=16, step_bucket=32, name="bench-spec-off",
        kv_width=96, spec_k=0,
    )
    eng_spec = ContinuousDecoder(
        gen, slots=16, step_bucket=32, name="bench-spec-on",
        kv_width=96, spec_k=spec_k,
    )
    speedup = 0.0
    bf_pool_bytes = 0
    try:
        # warm both arms' compile shapes, the prefix cache, and the
        # spec arm's suffix corpus off the clock: every prompt once,
        # then two saturated warm drives per arm
        for eng in (eng_plain, eng_spec):
            for p, eos in requests(n_prompts):
                eng.submit(p, max_new_tokens=budget, eos_id=eos)()
            for _ in range(2):
                drive(128, eng)
        n_req, rounds = 256, 3
        w_pl, o_pl = drive(n_req, eng_plain)
        for _ in range(rounds - 1):
            w2, o2 = drive(n_req, eng_plain)
            if w2 < w_pl:
                w_pl, o_pl = w2, o2
        sp0 = dict(eng_spec.pool_stats)
        w_sp, o_sp = drive(n_req, eng_spec)
        for _ in range(rounds - 1):
            w2, o2 = drive(n_req, eng_spec)
            if w2 < w_sp:
                w_sp, o_sp = w2, o2
        # token identity across arms — the speedup is not bought with
        # different outputs (the unit matrix's oracle, re-proven in situ)
        assert [str(o) for o in o_pl] == [str(o) for o in o_sp]
        tok = tokens_of(o_sp)
        tps_pl = tok / max(w_pl, 1e-9)
        tps_sp = tok / max(w_sp, 1e-9)
        speedup = tps_sp / max(tps_pl, 1e-9)
        st = eng_spec.pool_stats
        d_acc = st["draft_accepted"] - sp0["draft_accepted"]
        d_off = st["draft_offered"] - sp0["draft_offered"]
        # lane-rounds = offered / (k-1); committed tokens per lane per
        # speculative round = 1 (the always-emitted verify sample) +
        # accepted draft tokens — the >1 acceptance criterion
        lane_rounds = d_off / max(spec_k - 1, 1)
        acc_per_round = 1.0 + d_acc / max(lane_rounds, 1e-9)
        extras["spec_tokens_per_s_off_c16"] = round(tps_pl, 1)
        extras["spec_tokens_per_s_on_c16"] = round(tps_sp, 1)
        extras["spec_accepted_tokens_per_round"] = round(acc_per_round, 2)
        extras["spec_draft_accept_rate"] = round(
            d_acc / max(d_off, 1), 3
        )
        extras["spec_rounds_c16"] = st["spec_rounds"] - sp0["spec_rounds"]
        extras["spec_fallbacks_total"] = st["spec_fallbacks"]
        extras["spec_draft_sources"] = dict(eng_spec._draft_sources)
        bf_pool_bytes = sum(eng_spec.hbm_components().values())
    finally:
        eng_plain.stop()
        eng_spec.stop()
    # int8 capacity at fixed HBM: double the slots, quantize the pool —
    # the ledger components (scales included) must fit the bf16 budget,
    # and the doubled pool must still serve speculative rounds
    eng_i8 = ContinuousDecoder(
        gen, slots=32, step_bucket=32, name="bench-spec-int8",
        kv_width=96, spec_k=spec_k, kv_quant="int8",
    )
    try:
        i8_pool_bytes = sum(eng_i8.hbm_components().values())
        for p, eos in requests(8):
            eng_i8.submit(p, max_new_tokens=budget, eos_id=eos)()
        w_i8, o_i8 = drive(64, eng_i8)
        assert tokens_of(o_i8) > 0
        assert eng_i8.pool_stats["spec_rounds"] > 0
        extras["spec_int8_tokens_per_s_c32"] = round(
            tokens_of(o_i8) / max(w_i8, 1e-9), 1
        )
    finally:
        eng_i8.stop()
    cap_x = (eng_i8.slots * 96) / (16 * 96)  # slots x attended context
    hbm_ratio = i8_pool_bytes / max(bf_pool_bytes, 1)
    extras["int8_slot_context_x"] = round(cap_x, 2)
    extras["int8_hbm_ratio_vs_bf16"] = round(hbm_ratio, 4)
    extras["spec_compile_signatures"] = gen._tripwire.signatures
    acc_per_round = extras.get("spec_accepted_tokens_per_round", 0.0)
    extras["speculative_decode_speedup_c16"] = round(speedup, 3)
    extras["speculative_decode_speedup_ok"] = bool(
        speedup >= 1.3
        and acc_per_round > 1.0
        and cap_x >= 2.0
        and hbm_ratio <= 1.02
    )
    return round(speedup, 3)


def phase_ingest(backend: str, extras: dict) -> float:
    """Streaming embed+index ingest rate on a REALISTIC variable-length
    corpus: docs/sec end to end with LENGTH-BUCKETED batching, and MFU
    reported per sequence bucket + aggregate (r4 Weak #1 / task #3)."""
    jax = _init_jax(backend)

    from pathway_tpu.models.encoder import SentenceEncoder
    from pathway_tpu.ops.knn import DeviceKnnIndex

    backend = jax.default_backend()
    extras["backend"] = backend
    n_docs = int(
        os.environ.get("BENCH_INGEST_DOCS", "131072" if backend == "tpu" else "4096")
    )
    dim = 384
    batch = int(os.environ.get("BENCH_INGEST_BATCH", "1024"))
    n_docs = max(n_docs - n_docs % batch, batch)
    encoder = SentenceEncoder(dimension=dim, n_layers=6, max_length=128)
    # headroom for ragged-tail pad rows and the high-range warmup keys
    index = DeviceKnnIndex(
        dimension=dim, metric="cos", initial_capacity=n_docs + 300_000
    )
    docs = _realistic_corpus(n_docs)

    # LENGTH-BUCKETED BATCHING: tokenize once on host (the native batch
    # tokenizer), order docs by token length, and emit fixed-size batches
    # of consecutive sorted docs — each batch pads to its own /16 bucket,
    # so padding waste is the within-batch spread, not max_len.  The
    # sort is the batcher's job in the streaming engine too (documents
    # arrive unordered; the ingest operator buffers one batch window).
    t_tok0 = time.perf_counter()
    tok_lens = np.empty(n_docs, np.int64)
    for s in range(0, n_docs, 8192):
        _ids, mask = encoder.tokenizer.encode_batch(docs[s : s + 8192])
        tok_lens[s : s + mask.shape[0]] = np.asarray(mask).sum(axis=1)
    tokenize_s = time.perf_counter() - t_tok0
    order = np.argsort(tok_lens, kind="stable")
    max_len = encoder.tokenizer.max_length
    docs_sorted = [docs[j] for j in order]
    lens_sorted = tok_lens[order]
    bucket_of = np.clip(((lens_sorted + 15) // 16) * 16, 16, max_len)

    # TOKEN-BUDGET batching: a constant docs-per-batch starves the MXU on
    # short sequences (B=1024 at T=16 is a 16k-token batch vs 131k at
    # T=128), so batch size scales inversely with the sequence bucket —
    # ~constant tokens per dispatch, power-of-two B for a small compile set
    budget = batch * 256  # ~256k tokens/dispatch at the default batch=1024
    runs = []  # (T_bucket, [docs...], [true lens...])
    start = 0
    for i in range(1, n_docs + 1):
        if i == n_docs or bucket_of[i] != bucket_of[start]:
            runs.append(
                (int(bucket_of[start]), docs_sorted[start:i], lens_sorted[start:i])
            )
            start = i
    batches = []  # (texts_padded_to_B, T_padded, n_real)
    for T, run, run_lens in runs:
        B_T = min(16384, max(256, budget // T))
        B_T = 1 << (B_T.bit_length() - 1)
        for s in range(0, len(run), B_T):
            chunk = run[s : s + B_T]
            n_real = len(chunk)
            T_pad = int(
                min(max_len, ((int(run_lens[s : s + B_T].max()) + 15) // 16) * 16)
            )
            if n_real < B_T:  # ragged tail padded with empty docs
                chunk = chunk + [""] * (B_T - n_real)
            batches.append((chunk, T_pad, n_real))

    # warmup: compile each (B, T) shape outside the timed loop.  Warmup
    # keys live in a HIGH range so the timed loop's keys never collide —
    # a collision flips add_from_device onto the upsert path (mask old
    # slot + realloc), which is much slower than plain insert
    seen_shapes = set()
    warm_key = n_docs + 200_000
    for part, T, _real in batches:
        if (len(part), T) not in seen_shapes:
            seen_shapes.add((len(part), T))
            index.add_from_device(
                range(warm_key, warm_key + len(part)),
                encoder.encode_to_device(part),
            )
            warm_key += len(part)
    # drain the warmup COMPLETELY before starting the clock: each fresh
    # executable's first run carries one-time costs (program upload etc.)
    # that must not leak into the timed region
    index._matrix.block_until_ready()
    np.asarray(index._matrix[:1, :1])

    # device-to-device pipeline: encode leaves embeddings in HBM,
    # add_from_device scatters them without a host fetch, so tokenization
    # overlaps device compute and the one host sync is paid at the end
    t0 = time.perf_counter()
    key0 = 0
    enc_host_s = add_host_s = 0.0
    for part, _T, n_real in batches:
        t1 = time.perf_counter()
        vecs = encoder.encode_to_device(part)
        t2 = time.perf_counter()
        index.add_from_device(range(key0, key0 + len(part)), vecs)
        enc_host_s += t2 - t1
        add_host_s += time.perf_counter() - t2
        key0 += len(part)
    index._matrix.block_until_ready()
    # a 1-element fetch: the timed region ends with the data on the host
    _np_fence = np.asarray(index._matrix[:1, :1])
    elapsed = time.perf_counter() - t0
    extras["ingest_encode_host_s"] = round(enc_host_s, 2)
    extras["ingest_add_host_s"] = round(add_host_s, 2)
    extras["ingest_drain_s"] = round(elapsed - enc_host_s - add_host_s, 2)
    extras["ingest_corpus"] = n_docs
    rate = n_docs / elapsed

    # MFU: per-batch FLOPs = B * (2*P_matmul*T_b + 4*layers*d*T_b^2) with
    # T_b the batch's ACTUAL padded length; embedding-table params excluded
    # (lookups are not matmul FLOPs).  Aggregate = sum over batches.
    leaves = jax.tree_util.tree_leaves_with_path(encoder.params)
    n_params = sum(int(np.prod(p.shape)) for _, p in leaves)
    n_embed = sum(
        int(np.prod(p.shape))
        for path, p in leaves
        if "embed" in jax.tree_util.keystr(path).lower()
    )
    cfg = encoder.config
    p_mm = n_params - n_embed

    def flops_at(T: int) -> float:
        return 2.0 * p_mm * T + 4.0 * cfg.n_layers * cfg.d_model * T * T

    total_flops = float(
        sum(n_real * flops_at(T) for _part, T, n_real in batches)
    )
    extras["encoder_params"] = n_params
    extras["tokenize_s"] = round(tokenize_s, 2)
    lens = tok_lens.astype(float)
    extras["tokens_per_doc"] = {
        "p10": float(np.percentile(lens, 10)),
        "p50": float(np.percentile(lens, 50)),
        "p90": float(np.percentile(lens, 90)),
        "max": float(lens.max()),
    }
    extras["batch_shapes"] = sorted(
        {(len(part), T) for part, T, _r in batches}
    )
    extras["docs_per_sec_per_chip"] = round(rate, 1)  # single-chip phase
    peak = _peak_flops(jax)
    if peak is not None:
        extras["mfu"] = round(total_flops / elapsed / peak, 4)
        extras["peak_bf16_flops"] = float(f"{peak:.3g}")
        # per-bucket MFU: re-time one full-size batch per distinct shape.
        # Completion is a 1-element HOST FETCH; its cost amortizes over
        # the reps.
        per_bucket = {}
        by_T: dict = {}
        for part, T, n_real in batches:
            if n_real == len(part):  # only full batches represent the shape
                by_T.setdefault(T, part)
        for T, part in sorted(by_T.items()):
            np.asarray(encoder.encode_to_device(part)[:1, :1])  # warm
            reps = 6
            t0 = time.perf_counter()
            for _ in range(reps):
                out = encoder.encode_to_device(part)
            np.asarray(out[:1, :1])  # real completion fence
            dt = (time.perf_counter() - t0) / reps
            per_bucket[str(T)] = round(
                len(part) * flops_at(T) / dt / peak, 4
            )
        extras["mfu_per_bucket"] = per_bucket
    else:
        extras["mfu"] = None  # no peak table entry for this backend (cpu)

    # --- SEQUENCE-PACKED ingest: the TPU-idiomatic variable-length path
    # (models/encoder.py encode_packed_to_device — short docs share rows
    # under block-diagonal attention, so the MXU always sees full-length
    # matmuls).  Useful FLOPs are counted at each doc's TRUE length, so
    # the cross-segment attention waste the packing pays is excluded —
    # the packed MFU below is conservative.
    try:
        avg_tok = float(np.mean(lens))
        chunk_docs = max(256, int(batch * max_len * 0.96 / max(avg_tok, 1.0)))
        n_packed = n_docs - (n_docs % chunk_docs)
        pchunks = [
            docs[s : s + chunk_docs] for s in range(0, n_packed, chunk_docs)
        ]
        # a dedicated index so warmup + timed keys can never force a
        # mid-measurement capacity grow; each best-of-2 attempt gets its
        # own key range so attempt 2 measures plain inserts, not upserts
        index_p = DeviceKnnIndex(
            dimension=dim, metric="cos", initial_capacity=3 * n_packed + 131072
        )
        warm_p = 2 * n_packed + 65536
        for c in pchunks:  # warm every (rows, segment) shape
            index_p.add_from_device(
                range(warm_p, warm_p + chunk_docs),
                encoder.encode_packed_to_device(c),
            )
            warm_p += chunk_docs
        index_p._matrix.block_until_ready()
        np.asarray(index_p._matrix[:1, :1])
        # best-of-2: the better pass is the closer estimate of the
        # machine's capability
        p_elapsed = float("inf")
        for attempt in range(2):
            t0 = time.perf_counter()
            key0 = attempt * n_packed
            for c in pchunks:
                vecs = encoder.encode_packed_to_device(c)
                index_p.add_from_device(range(key0, key0 + chunk_docs), vecs)
                key0 += chunk_docs
            index_p._matrix.block_until_ready()
            np.asarray(index_p._matrix[:1, :1])
            p_elapsed = min(p_elapsed, time.perf_counter() - t0)
        packed_rate = n_packed / p_elapsed
        useful = float(
            np.sum(2.0 * p_mm * lens[:n_packed])
            + np.sum(4.0 * cfg.n_layers * cfg.d_model * lens[:n_packed] ** 2)
        )
        extras["docs_per_sec_packed"] = round(packed_rate, 1)
        if peak is not None:
            extras["mfu_packed"] = round(useful / p_elapsed / peak, 4)
        if packed_rate > rate:
            # headline = best real e2e configuration; keep the bucketed
            # number under its own key so the two never contradict
            extras["docs_per_sec_bucketed"] = extras["docs_per_sec_per_chip"]
            extras["docs_per_sec_per_chip"] = round(packed_rate, 1)
            rate = packed_rate
    except Exception as exc:  # noqa: BLE001 - packing must not sink the phase
        extras["packed_error"] = f"{type(exc).__name__}: {exc}"

    # --- pipeline headroom demo: the same packed ingest with an
    # MXU-friendly encoder size (BERT-base class).  The flagship 384-dim
    # model's device ceiling is ~0.39 MFU (small-d matmuls); this shows
    # the FRAMEWORK sustains >0.5 when the model is wide enough.
    if peak is not None and os.environ.get("BENCH_LARGE_ENCODER", "1") == "1":
        try:
            from pathway_tpu.models.encoder import SentenceEncoder as _SE

            big = _SE(dimension=768, n_layers=12, n_heads=12, max_length=128)
            bleaves = jax.tree_util.tree_leaves_with_path(big.params)
            bp = sum(int(np.prod(p.shape)) for _, p in bleaves)
            bemb = sum(
                int(np.prod(p.shape))
                for path, p in bleaves
                if "embed" in jax.tree_util.keystr(path).lower()
            )
            bp_mm = bp - bemb
            n_big = min(16384, n_packed) or chunk_docs
            bchunk = max(256, int(512 * 128 * 0.96 / max(avg_tok, 1.0)))
            n_big -= n_big % bchunk
            bchunks = [
                docs[s : s + bchunk] for s in range(0, n_big, bchunk)
            ]
            for c in bchunks:
                big.encode_packed_to_device(c)
            out = big.encode_packed_to_device(bchunks[-1])
            np.asarray(out[:1, :1])
            b_el = float("inf")
            for _attempt in range(2):
                t0 = time.perf_counter()
                for c in bchunks:
                    out = big.encode_packed_to_device(c)
                np.asarray(out[:1, :1])
                b_el = min(b_el, time.perf_counter() - t0)
            useful_b = float(
                np.sum(2.0 * bp_mm * lens[:n_big])
                + np.sum(4.0 * 12 * 768 * lens[:n_big] ** 2)
            )
            extras["mfu_large_packed"] = round(useful_b / b_el / peak, 4)
            extras["large_encoder"] = {
                "d_model": 768, "n_layers": 12, "params": bp,
                "docs_per_sec": round(n_big / b_el, 1), "corpus": n_big,
            }
        except Exception as exc:  # noqa: BLE001
            extras["large_encoder_error"] = f"{type(exc).__name__}: {exc}"
    return rate


def phase_live_ingest(backend: str, extras: dict) -> float:
    """Ingest→retrievable freshness under live serve traffic (ISSUE 18:
    serve/ingest.py + the real load-shed decision): the concurrent_serve
    stack at c16 with a ``LiveIngestRunner`` absorbing connector commits
    into the SAME index the fused retriever reads.  Measures staleness
    (arrival → retrievable commit) p50/p99 and serve p50/p99 under the
    combined load with a mid-run sentinel doc proven retrievable and its
    ingest trace force-kept; asserts the per-batch 2+2 serve dispatch
    budget with ingest absorbing around the burst (the counter hooks
    only the serve sites, so any ingest work leaking onto the serve
    dispatch path would trip it); A/Bs the freshness plane on/off
    (budget < 3% added serve p50 — attribution must be free at the
    serve path); and A/Bs shed-on vs shed-off under a REAL freshness
    burn (a paused absorber's overdue backlog): low-priority load
    turned away at admission must protect the surviving high-priority
    p99, with every high-priority request served clean.  The phase
    value is the staleness p99 in ms."""
    jax = _init_jax(backend)

    from pathway_tpu import observe
    from pathway_tpu.observe import slo as slo_mod
    from pathway_tpu.observe import trace as trace_mod
    from pathway_tpu.ops import dispatch_counter
    from pathway_tpu.serve import LiveIngestRunner, ServeScheduler
    from pathway_tpu.serve import ingest as ingest_mod

    backend = jax.default_backend()
    extras["backend"] = backend
    on_tpu = backend == "tpu"
    n_docs = int(os.environ.get("BENCH_LI_DOCS", "20000" if on_tpu else "1000"))
    k, candidates = 10, 32
    pipe, _cross, docs, _queries = _build_rr_pipeline(
        n_docs, 16, k, candidates, small=not on_tpu
    )
    encoder = pipe.retriever.encoder
    index = pipe.retriever.index

    pool = [
        " ".join(docs[(i * 9973) % n_docs].split()[:8]) for i in range(32)
    ]
    # warm every compile shape the arms touch: solo + coalesced comps +
    # the single-row ingest-embed shape (absorb batches re-bucket rows)
    for q in pool:
        pipe([q], k)
    for b in range(2, 17):
        pipe(sorted(set(pool))[:b], k)

    conc = 16
    window_us = float(os.environ.get("BENCH_LI_WINDOW_US", "5000"))
    max_batch = int(
        os.environ.get("BENCH_LI_MAX_BATCH", "16" if on_tpu else "4")
    )
    n_req = int(os.environ.get("BENCH_LI_REQUESTS", str(conc * 8)))
    per_commit = 8
    next_key = [n_docs]

    def fresh_rows(n: int):
        # new (key, text) rows in the corpus shape, registered with the
        # pipeline up front so reranking can score them once retrievable
        rows = []
        for _ in range(n):
            key = next_key[0]
            next_key[0] += 1
            text = f"fresh update {key} " + docs[key % n_docs]
            pipe.doc_text[key] = text
            rows.append((key, text))
        return rows

    def drive(sched, n: int, priority_of=None, feeder=None):
        """c16 barrier workers (+ optional ingest feeder sharing the
        barrier); returns (lats list indexed by request, shed flags,
        priorities)."""
        lats: list = [None] * n
        sheds = [False] * n
        prios = [priority_of(i) if priority_of else None for i in range(n)]
        errs: list = []
        barrier = threading.Barrier(conc + (1 if feeder is not None else 0))

        def worker(t: int):
            try:
                barrier.wait(timeout=60)
                for i in range(t, n, conc):
                    t0 = time.perf_counter()
                    res = sched.serve([pool[(i * 7) % len(pool)]], k,
                                      priority=prios[i])
                    lats[i] = (time.perf_counter() - t0) * 1e3
                    shed = bool(getattr(res, "meta", {}).get("shed"))
                    sheds[i] = shed
                    assert shed or (res and res[0])
            except Exception as exc:
                errs.append(repr(exc))

        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(conc)
        ]
        if feeder is not None:
            threads.append(threading.Thread(target=feeder, args=(barrier,)))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errs:
            raise RuntimeError(f"live_ingest drive failed: {errs[:3]}")
        return lats, sheds, prios

    env_enabled = observe.enabled()
    observe.set_enabled(True)
    staleness_p99_ms = 0.0
    try:
        # -- combined load: staleness + serve latency + mid-run sentinel --
        runner = LiveIngestRunner(encoder, index, name="bench-live")
        conn = runner.connector("bench-live-0")
        sentinel = {"key": None}
        sentinel_text = (
            "the zanzibar quorum ledger reconciles nightly freshness audits"
        )
        ingest_commits = max(2, n_req // 16)
        gen0 = index.generation

        def feeder(barrier):
            barrier.wait(timeout=60)
            for i in range(ingest_commits):
                conn.insert_rows(fresh_rows(per_commit))
                conn.commit(offsets={"0": (i + 1) * per_commit})
                if i == ingest_commits // 2:
                    # mid-run sentinel: unique text; a 1 ms freshness
                    # threshold around just this commit force-keeps the
                    # batch's ingest trace
                    key = next_key[0]
                    next_key[0] += 1
                    pipe.doc_text[key] = sentinel_text
                    prev = os.environ.get("PATHWAY_SLO_FRESHNESS_MS")
                    os.environ["PATHWAY_SLO_FRESHNESS_MS"] = "1"
                    try:
                        conn.insert(key, sentinel_text)
                        conn.commit()
                        runner.flush(timeout=30.0)
                    finally:
                        if prev is None:
                            os.environ.pop("PATHWAY_SLO_FRESHNESS_MS", None)
                        else:
                            os.environ["PATHWAY_SLO_FRESHNESS_MS"] = prev
                    sentinel["key"] = key
                time.sleep(0.01)

        sched = ServeScheduler(
            pipe, window_us=window_us, max_batch=max_batch, result_cache=None
        )
        try:
            drive(sched, 2 * conc)  # settle the scheduler's compositions
            lats, _sheds, _prios = drive(sched, n_req, feeder=feeder)
        finally:
            sched.stop()
        assert runner.flush(timeout=60.0), runner.stats
        r_stats = runner.stats
        assert r_stats["dropped"] == 0, r_stats
        assert index.generation > gen0
        done = np.asarray([l for l in lats if l is not None])
        extras["live_serve_p50_ms"] = round(float(np.percentile(done, 50)), 3)
        extras["live_serve_p99_ms"] = round(float(np.percentile(done, 99)), 3)
        extras["live_ingest_docs"] = r_stats["docs"]
        extras["live_ingest_batches"] = r_stats["batches"]
        p50_s = ingest_mod._H_FRESH.quantile_s(0.5)
        p99_s = ingest_mod._H_FRESH.quantile_s(0.99)
        assert p99_s is not None, "no freshness observations landed"
        staleness_p99_ms = p99_s * 1e3
        extras["live_staleness_p50_ms"] = round((p50_s or 0.0) * 1e3, 3)
        extras["live_staleness_p99_ms"] = round(staleness_p99_ms, 3)

        # the sentinel committed mid-run is retrievable and its ingest
        # trace was kept (keep_reason "forced" via the 1 ms threshold)
        assert sentinel["key"] is not None
        got = pipe([sentinel_text], k)
        assert sentinel["key"] in [key for key, _score in got[0]], got[0]
        kept_ingest = [
            t for t in trace_mod.snapshot_traces()["traces"]
            if t.get("kind") == "ingest"
        ]
        assert kept_ingest, "no kept ingest trace for the sentinel batch"
        extras["live_sentinel_trace_kept"] = len(kept_ingest)

        # -- 2+2 budget with ingest absorbing around the burst --
        b0 = runner.stats["batches"]
        with ServeScheduler(
            pipe, window_us=200_000, result_cache=None
        ) as bsched:
            conn.insert_rows(fresh_rows(per_commit))
            conn.commit()
            res: list = []
            errs: list = []
            barrier = threading.Barrier(8)

            def w(q):
                try:
                    barrier.wait(timeout=60)
                    res.append(bsched.serve([q], k))
                except Exception as exc:
                    errs.append(repr(exc))

            with dispatch_counter.DispatchCounter() as counter:
                threads = [
                    threading.Thread(target=w, args=(q,)) for q in pool[:8]
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
            if errs:
                raise RuntimeError(f"live_ingest burst failed: {errs[:3]}")
            batches = max(1, bsched.stats["batches"] + bsched.stats["solo"])
        assert runner.flush(timeout=60.0), runner.stats
        extras["live_dispatches_per_batch"] = round(
            counter.dispatches / batches, 2
        )
        extras["live_fetches_per_batch"] = round(counter.fetches / batches, 2)
        extras["live_ingest_batches_during_burst"] = (
            runner.stats["batches"] - b0
        )
        assert counter.dispatches <= 2 * batches, (counter.events, batches)
        assert counter.fetches <= 2 * batches, (counter.events, batches)
        runner.stop()

        # -- freshness-plane overhead A/B: serve p50 with the plane on
        # (histograms + stage spans + provider) vs a plane-off runner,
        # interleaved paired rounds, median ratio, < 3% budget --
        rounds = int(os.environ.get("BENCH_LI_ROUNDS", "3"))
        lat_arm = {True: [], False: []}
        ratios = []
        for r in range(rounds):
            order = (True, False) if r % 2 == 0 else (False, True)
            round_p50 = {}
            for plane in order:
                arm_runner = LiveIngestRunner(
                    encoder, index, name=f"ab-{r}-{int(plane)}",
                    freshness_plane=plane,
                )
                arm_conn = arm_runner.connector("ab-0")

                def ab_feeder(barrier, arm_conn=arm_conn):
                    barrier.wait(timeout=60)
                    for _ in range(6):
                        arm_conn.insert_rows(fresh_rows(per_commit))
                        arm_conn.commit()
                        time.sleep(0.005)

                asched = ServeScheduler(
                    pipe, window_us=window_us, max_batch=max_batch,
                    result_cache=None,
                )
                try:
                    drive(asched, 2 * conc)  # settle after the flip
                    arm, _s, _p = drive(asched, n_req, feeder=ab_feeder)
                finally:
                    asched.stop()
                    arm_runner.flush(timeout=60.0)
                    arm_runner.stop()
                arm = np.asarray([l for l in arm if l is not None])
                lat_arm[plane].append(arm)
                round_p50[plane] = float(np.percentile(arm, 50))
            ratios.append(round_p50[True] / max(round_p50[False], 1e-9))
        overhead_pct = (float(np.median(ratios)) - 1.0) * 100.0
        extras["live_plane_p50_on_ms"] = round(
            float(np.percentile(np.concatenate(lat_arm[True]), 50)), 3
        )
        extras["live_plane_p50_off_ms"] = round(
            float(np.percentile(np.concatenate(lat_arm[False]), 50)), 3
        )
        extras["live_plane_round_ratios"] = [round(x, 4) for x in ratios]
        extras["freshness_plane_overhead_pct"] = round(overhead_pct, 3)
        max_pct = float(os.environ.get("BENCH_LI_MAX_OVERHEAD_PCT", "3.0"))
        assert overhead_pct < max_pct, (
            f"freshness plane adds {overhead_pct:.2f}% serve p50 "
            f"(budget {max_pct}%)"
        )

        # -- shed A/B under a REAL freshness burn: a paused absorber's
        # backlog ages past a 50 ms threshold, the freshness objective
        # fires, and the admission decision (serve.shed + priority
        # classes) turns low-priority load away — the surviving
        # high-priority p99 is the number the decision protects --
        env_prev = {
            kk: os.environ.get(kk)
            for kk in ("PATHWAY_SLO_FRESHNESS_MS", "PATHWAY_SERVE_SHED")
        }
        backlog = None
        try:
            os.environ["PATHWAY_SLO_FRESHNESS_MS"] = "50"
            engine = slo_mod.set_engine(None)
            engine.evaluate(max_age_s=0.0)  # baseline ring snapshot
            backlog = LiveIngestRunner(
                encoder, index, name="backlog", autostart=False
            )
            bconn = backlog.connector("backlog-0")
            bconn.insert_rows(fresh_rows(32))
            bconn.commit()
            time.sleep(0.12)  # age the backlog past the threshold
            engine.evaluate(max_age_s=0.0)
            assert "freshness" in slo_mod.firing_specs(), (
                slo_mod.firing_specs()
            )
            assert slo_mod.should_shed()

            def priority_of(i: int) -> str:
                return "low" if i % 2 else "high"

            pairs = []
            shed_total = 0
            for r in range(rounds):
                order = (True, False) if r % 2 == 0 else (False, True)
                round_hi = {}
                for shed_on in order:
                    if shed_on:
                        os.environ.pop("PATHWAY_SERVE_SHED", None)
                    else:
                        os.environ["PATHWAY_SERVE_SHED"] = "0"
                    ssched = ServeScheduler(
                        pipe, window_us=window_us, max_batch=max_batch,
                        result_cache=None,
                    )
                    try:
                        drive(ssched, 2 * conc, priority_of=priority_of)
                        lats, sheds, prios = drive(
                            ssched, n_req, priority_of=priority_of
                        )
                        n_shed = ssched.stats.get("shed", 0)
                    finally:
                        ssched.stop()
                    hi = [
                        lats[i] for i in range(n_req)
                        if prios[i] == "high" and lats[i] is not None
                    ]
                    assert not any(
                        sheds[i] for i in range(n_req) if prios[i] == "high"
                    ), "a high-priority request was shed"
                    if shed_on:
                        assert any(sheds), "burn firing but nothing shed"
                        shed_total += n_shed
                    else:
                        assert not any(sheds) and n_shed == 0
                    round_hi[shed_on] = float(np.percentile(hi, 99))
                pairs.append((round_hi[True], round_hi[False]))
            protection = float(
                np.median([off / max(on, 1e-9) for on, off in pairs])
            )
            extras["live_shed_high_p99_on_ms"] = round(
                float(np.median([on for on, _ in pairs])), 3
            )
            extras["live_shed_high_p99_off_ms"] = round(
                float(np.median([off for _, off in pairs])), 3
            )
            extras["live_shed_requests_shed"] = shed_total
            extras["live_shed_p99_protection_x"] = round(protection, 3)
            assert protection > 1.0, (
                f"shedding low-priority load did not protect the "
                f"high-priority p99 (ratio {protection:.3f})"
            )
        finally:
            for kk, vv in env_prev.items():
                if vv is None:
                    os.environ.pop(kk, None)
                else:
                    os.environ[kk] = vv
            slo_mod.reset()
            if backlog is not None:
                backlog.stop()
    finally:
        observe.set_enabled(env_enabled)
    return round(staleness_p99_ms, 3)


def phase_serve_fabric(backend: str, extras: dict) -> float:
    """Multi-host serve fabric (ISSUE 19: serve/fabric.py +
    serve/warmstate.py): a 3-worker replica group (each worker its own
    ServeScheduler over the shared retrieve→rerank stack) behind one
    ``ServeFabric`` front-end, driven at c16.  Measures the healthy
    baseline, then a KILL-ONE-HOST burst (every affected request flagged
    ``host_failover`` with rows from a survivor, zero exceptions,
    breaker open, re-route within one heartbeat budget), the 2+2
    per-batch dispatch budget on the SURVIVING hosts, p99 during a full
    rolling bounce of every worker (the zero-downtime bar), and the
    warm-restore vs cold-ingest bring-up ratio (a replacement replica
    restoring the writer's snapshot vs re-embedding the corpus).  The
    phase value is the rolling-bounce p99 in ms."""
    jax = _init_jax(backend)

    from pathway_tpu import robust
    from pathway_tpu.ops import dispatch_counter
    from pathway_tpu.ops.ivf import IvfKnnIndex
    from pathway_tpu.persistence.backends import MemoryBackend
    from pathway_tpu.robust import HOST_FAILOVER
    from pathway_tpu.serve import (
        FabricWorker,
        ServeFabric,
        ServeScheduler,
        WarmStateManager,
        fabric_token,
    )

    backend = jax.default_backend()
    extras["backend"] = backend
    on_tpu = backend == "tpu"
    n_docs = int(os.environ.get("BENCH_SF_DOCS", "20000" if on_tpu else "1000"))
    k, candidates = 10, 32
    pipe, _cross, docs, _queries = _build_rr_pipeline(
        n_docs, 16, k, candidates, small=not on_tpu
    )
    encoder = pipe.retriever.encoder
    dim = 384 if on_tpu else 64

    pool = [
        " ".join(docs[(i * 9973) % n_docs].split()[:8]) for i in range(32)
    ]
    window_us = float(os.environ.get("BENCH_SF_WINDOW_US", "5000"))
    max_batch = int(
        os.environ.get("BENCH_SF_MAX_BATCH", "16" if on_tpu else "4")
    )
    # warm every compile shape the fleet touches: solo serves plus every
    # coalesced composition a per-host scheduler can form
    for q in pool:
        pipe([q], k)
    for b in range(2, max_batch + 1):
        pipe(sorted(set(pool))[:b], k)

    conc = 16
    n_req = int(os.environ.get("BENCH_SF_REQUESTS", str(conc * 6)))
    n_hosts = 3
    hb_s, hb_timeout_s = 0.1, 0.5
    env_prev = {
        kk: os.environ.get(kk)
        for kk in ("PATHWAY_FABRIC_HEARTBEAT", "PATHWAY_FABRIC_HEARTBEAT_TIMEOUT")
    }
    os.environ["PATHWAY_FABRIC_HEARTBEAT"] = str(hb_s)
    os.environ["PATHWAY_FABRIC_HEARTBEAT_TIMEOUT"] = str(hb_timeout_s)

    token = fabric_token()
    names = [f"bench-sf-{i}" for i in range(n_hosts)]

    def make_host(i: int):
        sched = ServeScheduler(
            pipe, window_us=window_us, max_batch=max_batch,
            result_cache=None, name=f"{names[i]}-s",
        )
        worker = FabricWorker(sched, token=token, name=names[i])
        return sched, worker

    scheds, workers = [], []
    for i in range(n_hosts):
        s, w = make_host(i)
        scheds.append(s)
        workers.append(w)
    fabric = ServeFabric(
        {w.name: w.address for w in workers}, token, name="bench-fabric"
    )

    def crash(i: int) -> None:
        """Unplanned death: listener + live streams die with NO bye."""
        workers[i].kill()
        scheds[i].stop()

    def drive(n: int, on_each=None):
        """c16 barrier drive through the fabric; returns per-request
        (latency ms, degraded flags, rows-landed) plus raised errors."""
        reqs = [pool[(i * 7) % len(pool)] for i in range(n)]
        lats: list = [None] * n
        flags: list = [()] * n
        rows_ok = [False] * n
        errs: list = []
        barrier = threading.Barrier(conc)

        def worker(t: int):
            try:
                barrier.wait(timeout=60)
                for i in range(t, n, conc):
                    t0 = time.perf_counter()
                    res = fabric.serve([reqs[i]], k)
                    lats[i] = (time.perf_counter() - t0) * 1e3
                    flags[i] = tuple(res.degraded)
                    rows_ok[i] = bool(res and res[0])
                    if on_each is not None:
                        on_each(i)
            except Exception as exc:  # the contract: NEVER an exception
                errs.append(repr(exc))

        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(conc)
        ]
        t_all = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t_all
        return lats, flags, rows_ok, errs, elapsed

    bounce_p99_ms = 0.0
    try:
        assert fabric.connect() == n_hosts

        # -- healthy baseline: c16, no failures, no degraded flags --
        drive(conc * 2)  # settle the per-host batch compositions
        lats, flags, rows_ok, errs, elapsed = drive(n_req)
        assert errs == [], errs[:3]
        assert all(rows_ok), "healthy fleet must serve every request"
        assert not any(flags), f"healthy fleet degraded: {flags}"
        done = np.asarray([l for l in lats if l is not None])
        p99_healthy = float(np.percentile(done, 99))
        extras["fabric_hosts"] = n_hosts
        extras["fabric_qps_healthy_c16"] = round(n_req / elapsed, 2)
        extras["fabric_p50_healthy_ms"] = round(float(np.percentile(done, 50)), 3)
        extras["fabric_p99_healthy_ms"] = round(p99_healthy, 3)

        # -- kill-one-host burst: crash host 0 while it holds in-flight
        # requests; every affected request re-routes to a survivor --
        killed = threading.Event()

        def killer():
            t0 = time.monotonic()
            while time.monotonic() - t0 < 30:
                if fabric._links[0].inflight > 0:
                    break
                time.sleep(0.002)
            crash(0)
            killed.set()

        kt = threading.Thread(target=killer)
        kt.start()
        lats, flags, rows_ok, errs, _elapsed = drive(n_req)
        kt.join()
        assert killed.is_set()
        assert errs == [], errs[:3]
        assert all(rows_ok), "survivors must serve every request"
        failover_lats = [
            lats[i] for i in range(n_req)
            if HOST_FAILOVER in flags[i] and lats[i] is not None
        ]
        assert failover_lats, "the kill burst never caught an in-flight request"
        # re-route within one heartbeat: a dead socket fails in-flights
        # immediately and heartbeat silence is bounded by the timeout —
        # the affected request pays at most one heartbeat timeout plus a
        # normal (contended) serve on the survivor
        reroute_budget_ms = hb_timeout_s * 1e3 + max(2000.0, 5 * p99_healthy)
        extras["fabric_kill_failovers"] = len(failover_lats)
        extras["fabric_reroute_max_ms"] = round(max(failover_lats), 3)
        extras["fabric_reroute_budget_ms"] = round(reroute_budget_ms, 1)
        assert max(failover_lats) < reroute_budget_ms, (
            max(failover_lats), reroute_budget_ms,
        )
        breaker0 = robust.breaker(f"fabric:{names[0]}")
        assert breaker0.state != "closed", breaker0.state
        assert not fabric._links[0].up()
        extras["fabric_breaker_after_kill"] = breaker0.state

        # -- 2+2 per-batch dispatch budget on the SURVIVING hosts --
        def fleet_batches():
            return sum(
                scheds[i].stats["batches"] + scheds[i].stats["solo"]
                for i in range(1, n_hosts)
            )

        b0 = fleet_batches()
        res: list = []
        burst_errs: list = []
        barrier = threading.Barrier(8)

        def burst_worker(q):
            try:
                barrier.wait(timeout=60)
                res.append(fabric.serve([q], k))
            except Exception as exc:
                burst_errs.append(repr(exc))

        with dispatch_counter.DispatchCounter() as counter:
            threads = [
                threading.Thread(target=burst_worker, args=(q,))
                for q in pool[:8]
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        assert burst_errs == [], burst_errs[:3]
        assert all(r and r[0] for r in res)
        batches = max(1, fleet_batches() - b0)
        extras["fabric_dispatches_per_batch_survivors"] = round(
            counter.dispatches / batches, 2
        )
        extras["fabric_fetches_per_batch_survivors"] = round(
            counter.fetches / batches, 2
        )
        assert counter.dispatches <= 2 * batches, (counter.events, batches)
        assert counter.fetches <= 2 * batches, (counter.events, batches)

        # -- rolling bounce of the FULL fleet under continuous load --
        def restart(i: int) -> None:
            """A restarting process re-binds the bounced listener's port
            (retrying until TIME_WAIT clears) and re-joins the fabric."""
            port = workers[i].port
            workers[i].stop()
            scheds[i].stop()
            scheds[i] = ServeScheduler(
                pipe, window_us=window_us, max_batch=max_batch,
                result_cache=None, name=f"{names[i]}-s2",
            )
            t0 = time.monotonic()
            while True:
                try:
                    workers[i] = FabricWorker(
                        scheds[i], host="127.0.0.1", port=port,
                        token=token, name=names[i],
                    )
                    break
                except OSError:
                    if time.monotonic() - t0 > 15:
                        raise
                    time.sleep(0.05)
            # the breaker half-opens after one heartbeat timeout; an
            # affinity-routed probe closes it again
            q = next(
                q for q in (f"rejoin probe {j}" for j in itertools.count())
                if fabric._affinity(q) == i
            )
            t0 = time.monotonic()
            while time.monotonic() - t0 < 30:
                got = fabric.serve([q], k)
                if got.meta.get("fabric_host") == names[i]:
                    return
                time.sleep(0.05)
            raise RuntimeError(f"worker {i} never re-joined the fabric")

        restart(0)  # bring the killed host back before the bounce
        stop_serving = threading.Event()
        bounce_lats: list = []
        bounce_errs: list = []
        bounce_lock = threading.Lock()

        def bounce_driver(qi: int):
            while not stop_serving.is_set():
                try:
                    t0 = time.perf_counter()
                    got = fabric.serve([pool[qi % len(pool)]], k)
                    lat = (time.perf_counter() - t0) * 1e3
                    with bounce_lock:
                        bounce_lats.append(lat)
                        if not (len(got) == 1 and got[0]):
                            bounce_errs.append(("empty", got.degraded))
                except Exception as exc:
                    with bounce_lock:
                        bounce_errs.append(("raise", repr(exc)))
                time.sleep(0.002)

        drivers = [
            threading.Thread(target=bounce_driver, args=(i,)) for i in range(8)
        ]
        for t in drivers:
            t.start()
        try:
            for i in range(n_hosts):
                restart(i)
        finally:
            stop_serving.set()
            for t in drivers:
                t.join(30)
        assert bounce_errs == [], bounce_errs[:5]
        assert len(bounce_lats) > 20, "the bounce drive never ramped"
        bounce_p99_ms = float(np.percentile(np.asarray(bounce_lats), 99))
        bounce_budget_ms = float(
            os.environ.get("BENCH_SF_BOUNCE_BUDGET_MS", "0") or 0
        ) or (hb_timeout_s * 1e3 + 10 * p99_healthy)
        extras["fabric_bounce_requests"] = len(bounce_lats)
        extras["fabric_bounce_p99_ms"] = round(bounce_p99_ms, 3)
        extras["fabric_bounce_p99_vs_healthy_x"] = round(
            bounce_p99_ms / max(p99_healthy, 1e-9), 3
        )
        extras["fabric_bounce_budget_ms"] = round(bounce_budget_ms, 1)
        assert bounce_p99_ms < bounce_budget_ms, (
            f"rolling-bounce p99 {bounce_p99_ms:.0f} ms exceeds the "
            f"{bounce_budget_ms:.0f} ms budget"
        )
        for nm in names:
            assert robust.breaker(f"fabric:{nm}").state == "closed", nm
    finally:
        fabric.stop()
        for w in workers:
            w.stop()
        for s in scheds:
            s.stop()
        for kk, vv in env_prev.items():
            if vv is None:
                os.environ.pop(kk, None)
            else:
                os.environ[kk] = vv

    # -- warm-restore vs cold-ingest bring-up: a replacement replica
    # restores the writer's snapshot instead of re-embedding the corpus --
    keys = list(range(n_docs))
    t0 = time.perf_counter()
    cold_index = IvfKnnIndex(
        dimension=dim, metric="cos", n_clusters=16, n_probe=16
    )
    cold_index.add(keys, encoder.encode(docs))
    q_emb = encoder.encode(pool[:2])
    want = cold_index.search(q_emb, k=k)
    t_cold = time.perf_counter() - t0

    mgr = WarmStateManager(
        MemoryBackend(), name="bench-sf", components={"ivf": cold_index}
    )
    assert mgr.snapshot() is not None
    t0 = time.perf_counter()
    replica = IvfKnnIndex(
        dimension=dim, metric="cos", n_clusters=16, n_probe=16
    )
    report = WarmStateManager(
        mgr.backend, name="bench-sf", components={"ivf": replica}
    ).restore()
    got = replica.search(q_emb, k=k)
    t_warm = time.perf_counter() - t0
    assert report.restored, report
    # bit-identity: the warm-restored replica serves the writer's rows
    assert np.array_equal(np.asarray(want[0]), np.asarray(got[0]))
    assert np.array_equal(np.asarray(want[1]), np.asarray(got[1]))
    warm_vs_cold = t_cold / max(t_warm, 1e-9)
    extras["fabric_cold_ingest_s"] = round(t_cold, 3)
    extras["fabric_warm_restore_s"] = round(t_warm, 3)
    extras["fabric_warm_vs_cold_x"] = round(warm_vs_cold, 2)
    assert warm_vs_cold > 1.0, (t_cold, t_warm)

    return round(bounce_p99_ms, 3)


def phase_partitioned_fabric(backend: str, extras: dict) -> float:
    """Cross-host index sharding (ISSUE 20: ``FleetPartitionMap`` +
    ``ServeFabric(partitions=H)``): H partition hosts each own the
    ``doc_key % H`` slice of one corpus and the front serves by
    scatter-gather.  Measures the POINT of partitioning — per-host HBM
    at H=3 vs H=1 (the 0.45× acceptance bar), owner-routed absorb
    throughput ×H A/B, scatter-gather p50/p99 at c16 for both fleet
    sizes, the 1-logical + H-physical scatter booking next to the 2+2
    per-host budget, and a KILL-ONE-PARTITION burst (affected requests
    flagged ``partition_lost`` with the survivors' rows, recall lost on
    the dead partition's keys ONLY, zero exceptions).  The phase value
    is the H=3 scatter-gather p99 in ms."""
    jax = _init_jax(backend)
    import jax.numpy as jnp

    from pathway_tpu import robust
    from pathway_tpu.models.encoder import SentenceEncoder
    from pathway_tpu.ops import dispatch_counter
    from pathway_tpu.ops.ivf import IvfKnnIndex
    from pathway_tpu.ops.serving import FusedEncodeSearch
    from pathway_tpu.parallel import FleetPartitionMap
    from pathway_tpu.robust import PARTITION_LOST
    from pathway_tpu.serve import (
        FabricWorker,
        LiveIngestRunner,
        ServeFabric,
        ServeScheduler,
        fabric_token,
    )

    backend = jax.default_backend()
    extras["backend"] = backend
    on_tpu = backend == "tpu"
    dim = 384 if on_tpu else 64
    n_docs = int(os.environ.get("BENCH_PF_DOCS", "12000" if on_tpu else "900"))
    k = 10
    conc = 16
    n_req = int(os.environ.get("BENCH_PF_REQUESTS", str(conc * 6)))
    hb_s, hb_timeout_s = 0.1, 0.5
    env_prev = {
        kk: os.environ.get(kk)
        for kk in ("PATHWAY_FABRIC_HEARTBEAT", "PATHWAY_FABRIC_HEARTBEAT_TIMEOUT")
    }
    os.environ["PATHWAY_FABRIC_HEARTBEAT"] = str(hb_s)
    os.environ["PATHWAY_FABRIC_HEARTBEAT_TIMEOUT"] = str(hb_timeout_s)

    enc = SentenceEncoder(
        dimension=dim, n_layers=2, n_heads=4, max_length=32,
        vocab_size=2048, dtype=jnp.float32,
    )
    docs = dict(enumerate(_corpus_texts(n_docs)))
    pool = [
        " ".join(docs[(i * 9973) % n_docs].split()[:8]) for i in range(32)
    ]

    class _Fleet:
        """H partition hosts (owned IVF slice → fused search →
        scheduler → worker + ingest runner) + the partitioned front."""

        def __init__(self, n_parts: int, tag: str):
            self.names = [f"bench-pf{tag}-{i}" for i in range(n_parts)]
            self.token = fabric_token()
            pmap = FleetPartitionMap(n_parts)
            self.indexes, self.scheds = [], []
            self.runners, self.workers = [], []
            for i in range(n_parts):
                owned = [kk for kk in range(n_docs) if pmap.owner_of(kk) == i]
                # cluster count scales with the owned slice so the slab
                # capacity (max cluster size, padded) shrinks with it —
                # that shrink IS the per-host HBM win being measured
                nc = max(8, len(owned) // 48)
                idx = IvfKnnIndex(
                    dimension=dim, metric="cos", n_clusters=nc, n_probe=nc
                )
                idx.add(owned, enc.encode([docs[kk] for kk in owned]))
                idx.build()
                self.indexes.append(idx)
                sched = ServeScheduler(
                    FusedEncodeSearch(enc, idx, k=k),
                    window_us=0, result_cache=None,
                    name=f"{self.names[i]}-s",
                )
                self.scheds.append(sched)
                runner = LiveIngestRunner(enc, idx, name=f"{self.names[i]}-ing")
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
                name=f"bench-pfab{tag}",
                partitions=n_parts,
            )

        def per_host_hbm(self) -> int:
            return max(
                sum(idx.hbm_bytes().values()) for idx in self.indexes
            )

        def stop(self) -> None:
            self.fabric.stop()
            for w in self.workers:
                w.stop()
            for r in self.runners:
                r.stop()
            for s in self.scheds:
                s.stop()

    def drive(fabric, n: int):
        """c16 barrier drive; (latency ms, degraded flags, rows, errors)."""
        reqs = [pool[(i * 7) % len(pool)] for i in range(n)]
        lats: list = [None] * n
        flags: list = [()] * n
        rows: list = [None] * n
        errs: list = []
        barrier = threading.Barrier(conc)

        def worker(t: int):
            try:
                barrier.wait(timeout=60)
                for i in range(t, n, conc):
                    t0 = time.perf_counter()
                    res = fabric.serve([reqs[i]], k)
                    lats[i] = (time.perf_counter() - t0) * 1e3
                    flags[i] = tuple(res.degraded)
                    rows[i] = list(res[0]) if res else []
            except Exception as exc:  # the contract: NEVER an exception
                errs.append(repr(exc))

        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(conc)
        ]
        t_all = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return lats, flags, rows, errs, time.perf_counter() - t_all

    def absorb_rate(fleet) -> float:
        """Commit a fresh batch through the owner-routed path, flush
        every owner, confirm every partition's generation bumped;
        docs/s from commit to fleet-wide retrievability."""
        before = fleet.fabric.poll_generations()
        n_fresh = int(os.environ.get("BENCH_PF_ABSORB", "120"))
        t_ns = time.time_ns()
        batch = [
            (n_docs + j, f"absorbed fleet doc {n_docs + j} fresh", t_ns)
            for j in range(n_fresh)
        ]
        t0 = time.perf_counter()
        accepted = fleet.fabric.absorb(batch)
        for r in fleet.runners:
            assert r.flush(timeout=60), "ingest flush wedged"
        elapsed = time.perf_counter() - t0
        assert accepted == n_fresh, (accepted, n_fresh)
        t_end = time.monotonic() + 30
        gens = fleet.fabric.poll_generations()
        while time.monotonic() < t_end and not all(
            g > b for g, b in zip(gens, before)
        ):
            time.sleep(0.05)
            gens = fleet.fabric.poll_generations()
        assert all(g > b for g, b in zip(gens, before)), (before, gens)
        return n_fresh / elapsed

    p99_h3 = 0.0
    fleet1 = _Fleet(1, "a")
    fleet3 = _Fleet(3, "b")
    try:
        # -- per-host HBM: the point of partitioning (measured before
        # any serve so no exact-tail upload cache inflates either side) --
        hbm1 = fleet1.per_host_hbm()
        hbm3 = fleet3.per_host_hbm()
        extras["partition_hbm_per_host_h1_mb"] = round(hbm1 / 2**20, 3)
        extras["partition_hbm_per_host_h3_mb"] = round(hbm3 / 2**20, 3)
        hbm_ratio = hbm3 / max(hbm1, 1)
        extras["partition_hbm_h3_vs_h1_x"] = round(hbm_ratio, 3)
        assert hbm_ratio <= 0.45, (
            f"per-host HBM at H=3 is {hbm_ratio:.2f}x H=1 — the "
            "partitioned fleet must shed ~1/H per host (bar: 0.45x)"
        )

        assert fleet1.fabric.connect() == 1
        assert fleet3.fabric.connect() == 3
        for q in pool:  # warm every per-host compile shape
            fleet1.fabric.serve([q], k)
            fleet3.fabric.serve([q], k)

        # -- scatter-gather latency at c16, both fleet sizes --
        lats, flags, _rows, errs, elapsed = drive(fleet1.fabric, n_req)
        assert errs == [] and not any(flags), (errs[:3], flags[:3])
        done = np.asarray([l for l in lats if l is not None])
        extras["partition_p50_h1_c16_ms"] = round(float(np.percentile(done, 50)), 3)
        extras["partition_p99_h1_c16_ms"] = round(float(np.percentile(done, 99)), 3)
        extras["partition_qps_h1_c16"] = round(n_req / elapsed, 2)
        lats, flags, _rows, errs, elapsed = drive(fleet3.fabric, n_req)
        assert errs == [] and not any(flags), (errs[:3], flags[:3])
        done = np.asarray([l for l in lats if l is not None])
        p99_h3 = float(np.percentile(done, 99))
        extras["partition_p50_h3_c16_ms"] = round(float(np.percentile(done, 50)), 3)
        extras["partition_p99_h3_c16_ms"] = round(p99_h3, 3)
        extras["partition_qps_h3_c16"] = round(n_req / elapsed, 2)

        # -- the scatter booking: 1 logical + H physical, hosts at 2+2 --
        with dispatch_counter.DispatchCounter() as counter:
            res = fleet3.fabric.serve([pool[0]], k)
        assert res and res[0] and not res.degraded
        disp = [t for kind, t in counter.events if kind == "dispatch"]
        fet = [t for kind, t in counter.events if kind == "fetch"]
        assert disp.count("fabric.scatter") == 1, counter.events
        assert fet.count("fabric.gather") == 1, counter.events
        host_disp = [t for t in disp if t != "fabric.scatter"]
        host_fet = [t for t in fet if t != "fabric.gather"]
        assert len(host_disp) <= 3 * 2, counter.events
        assert len(host_fet) <= 3 * 2, counter.events
        extras["partition_scatter_logical_dispatches"] = disp.count("fabric.scatter")
        extras["partition_host_dispatches_per_serve"] = len(host_disp)

        # -- owner-routed absorb throughput: H=1 vs H=3 on the same
        # fresh batch (each H=3 owner ingests 1/3 of the stream) --
        rate1 = absorb_rate(fleet1)
        rate3 = absorb_rate(fleet3)
        absorb_x = rate3 / max(rate1, 1e-9)
        extras["partition_absorb_docs_per_s_h1"] = round(rate1, 2)
        extras["partition_absorb_docs_per_s_h3"] = round(rate3, 2)
        extras["partition_absorb_h3_vs_h1_x"] = round(absorb_x, 2)
        # owners ingest concurrently; CPU thread contention bounds the
        # win well short of 3x, but partitioning must never SERIALIZE
        # the fleet below the single host
        assert absorb_x > 0.9, (rate1, rate3)

        # -- kill-one-partition burst: crash partition 0 mid-flight --
        killed = threading.Event()

        def killer():
            t0 = time.monotonic()
            while time.monotonic() - t0 < 30:
                if fleet3.fabric._links[0].inflight > 0:
                    break
                time.sleep(0.002)
            fleet3.workers[0].kill()
            fleet3.scheds[0].stop()
            killed.set()

        kt = threading.Thread(target=killer)
        kt.start()
        lats, flags, rows, errs, _elapsed = drive(fleet3.fabric, n_req)
        kt.join()
        assert killed.is_set()
        assert errs == [], errs[:3]
        lost = [i for i in range(n_req) if PARTITION_LOST in flags[i]]
        assert lost, "the kill burst never caught a scatter in flight"
        for i in lost:
            # survivors still serve rows; recall is lost ONLY on the
            # dead partition's keys
            assert rows[i], f"request {i} lost its survivors' merge"
            assert all(int(kk) % 3 != 0 for kk, _s in rows[i]), rows[i]
        extras["partition_kill_lost_requests"] = len(lost)
        extras["partition_kill_requests"] = n_req
        breaker0 = robust.breaker(f"fabric:{fleet3.names[0]}")
        extras["partition_breaker_after_kill"] = breaker0.state
        assert breaker0.state != "closed", breaker0.state
    finally:
        fleet3.stop()
        fleet1.stop()
        for kk, vv in env_prev.items():
            if vv is None:
                os.environ.pop(kk, None)
            else:
                os.environ[kk] = vv

    return round(p99_h3, 3)


def phase_wordcount(backend: str, extras: dict) -> float:
    """Relational engine throughput: rows/sec through groupby-count."""
    _init_jax("cpu")  # host-side engine bench; never needs the device

    import pathway_tpu as pw
    from pathway_tpu.engine.executor import Executor
    from pathway_tpu.engine.operators.io import InputSession, SourceOperator
    from pathway_tpu.internals import dtype as dt
    from pathway_tpu.internals.table import Table
    from pathway_tpu.internals.universe import Universe

    n_rows = int(os.environ.get("BENCH_WORDCOUNT_ROWS", "500000"))
    batch = 50000
    rng = np.random.default_rng(0)
    vocab = np.array([f"word{i:04d}" for i in range(2000)], dtype=object)
    words = vocab[rng.zipf(1.3, size=n_rows).clip(max=len(vocab)) - 1]

    session = InputSession(upsert=False)
    et = pw.G.engine_graph.add_table(["word"], "wc_in")
    pw.G.engine_graph.add_operator(
        SourceOperator(et, session, {"word": dt.wrap(str)}, name="wc_in")
    )
    t = Table(et, {"word": dt.wrap(str)}, Universe(), short_name="wc_in")
    out = t.groupby(pw.this.word).reduce(
        word=pw.this.word, count=pw.reducers.count()
    )
    ex = Executor(pw.G.engine_graph)
    pw.G.engine_graph.finalize()

    t0 = time.perf_counter()
    for start in range(0, n_rows, batch):
        part = words[start : start + batch]
        session.insert_columnar(
            np.arange(start, start + len(part), dtype=np.uint64),
            {"word": part},
        )
        ex.step()
    elapsed = time.perf_counter() - t0
    n_groups = len(out._engine_table.store)
    assert n_groups > 0
    extras["wordcount_rows"] = n_rows
    extras["wordcount_groups"] = n_groups
    return n_rows / elapsed


def phase_scaling(backend: str, extras: dict) -> float:
    """Strong-scaling curve for sharded retrieval, measured on the REAL
    chip (VERDICT r3 #8: the 'QPS scaling 1->N chips' axis had no
    shard-count>1 measurement).  With the index row-sharded over N chips,
    each chip scores its N-th of the corpus and all-gathers k candidates
    (64*k*N values — microseconds over ICI), so per-batch time on N chips
    ≈ measured per-batch time at corpus/N on one chip.  A virtual CPU mesh
    cannot measure this (fake devices share one host's cores — measured
    flat 1.0x); the multi-chip EXECUTION itself is checked by
    chip_smoke.py's four-chip leg."""
    jax = _init_jax(backend)
    import jax.numpy as jnp

    from pathway_tpu.ops.knn import DeviceKnnIndex

    backend = jax.default_backend()
    extras["backend"] = backend
    full = int(
        os.environ.get("BENCH_SCALING_DOCS", "1048576" if backend == "tpu" else "131072")
    )
    dim, n_queries, k = 384, 64, 10
    rkey = jax.random.PRNGKey(0)
    queries = np.random.default_rng(0).normal(size=(n_queries, dim)).astype(np.float32)
    curve_ms = {}
    for shards in (1, 2, 4, 8):
        n = full // shards
        index = DeviceKnnIndex(dimension=dim, metric="cos", initial_capacity=n)
        for start in range(0, n, 65536):
            m = min(65536, n - start)
            rkey, sub = jax.random.split(rkey)
            index.add_from_device(
                range(start, start + m),
                jax.random.normal(sub, (m, dim), jnp.float32),
            )
        index._matrix.block_until_ready()
        qd = index._to_mesh(queries)
        np.asarray(index._run_search(qd, k)[0])  # compile + real sync
        # completion-gap timing with async host copies queued at dispatch
        # (the retrieval phase's method): gaps between consecutive
        # completions with the queue kept full are pure device time —
        # sequential sync fetches would each add a host sync instead
        iters = 28
        outs = []
        comps = []
        for _ in range(iters):
            o = index._run_search(qd, k)
            for a in o:
                if hasattr(a, "copy_to_host_async"):
                    a.copy_to_host_async()
            outs.append(o)
            if len(outs) > 4:
                np.asarray(outs.pop(0)[0])
                comps.append(time.perf_counter())
        while outs:
            np.asarray(outs.pop(0)[0])
            comps.append(time.perf_counter())
        gaps = np.diff(np.asarray(comps)) * 1e3
        curve_ms[shards] = round(float(np.percentile(gaps, 50)), 3)
        del index
    extras["shard_scaling_corpus"] = full
    extras["shard_scaling_per_batch_ms"] = curve_ms
    speedup = round(curve_ms[1] / curve_ms[8], 2)
    extras["shard_scaling_speedup_8x"] = speedup
    extras["qps_projected_8_chips"] = round(
        n_queries / (curve_ms[8] / 1e3), 1
    )
    return speedup


def phase_exchange(backend: str, extras: dict) -> float:
    """Host exchange-plane microbench (r4 Weak #6 / task #8): 2 processes
    push realistic Delta-shaped shards through ``all_to_all`` and measure
    rows/s, MB/s, and the pickle share of a tick — the number that bounds
    the BSP plane before any multi-core deployment."""
    import pickle
    import tempfile

    _init_jax("cpu")  # host-only phase

    n_rounds = int(os.environ.get("BENCH_EXCHANGE_ROUNDS", "60"))
    rows_per_shard = int(os.environ.get("BENCH_EXCHANGE_ROWS", "20000"))

    # file-based rendezvous KV (the real plane rides the jax coordination
    # service; the microbench isolates the exchange itself)
    kv_dir = tempfile.mkdtemp(prefix="pw_exch_bench_")
    worker = f"""
import os, pickle, time, sys
import numpy as np
sys.path.insert(0, {os.path.dirname(os.path.abspath(__file__))!r})
from pathway_tpu.parallel.exchange import ExchangePlane

kv_dir = {kv_dir!r}
def kv_set(k, v):
    p = os.path.join(kv_dir, k.replace('/', '_'))
    with open(p + '.tmp', 'w') as f:
        f.write(v)
    os.rename(p + '.tmp', p)
def kv_get(k):
    p = os.path.join(kv_dir, k.replace('/', '_'))
    deadline = time.time() + 60
    while time.time() < deadline:
        try:
            with open(p) as f:
                return f.read()
        except FileNotFoundError:
            time.sleep(0.01)
    raise TimeoutError(k)

rank = int(os.environ['BENCH_RANK'])
plane = ExchangePlane(rank, 2, kv_set, kv_get)
n_rounds = {n_rounds}
rows = {rows_per_shard}
rng = np.random.default_rng(rank)
# a realistic wordcount-shaped Delta shard: uint64 keys + object words + counts
shard = (
    rng.integers(0, 2**63, rows).astype(np.uint64),
    np.array(['word%04d' % (i % 2000) for i in range(rows)], dtype=object),
    rng.integers(1, 100, rows),
)
blob = pickle.dumps(shard, protocol=pickle.HIGHEST_PROTOCOL)
payload_bytes = len(blob)
t_p0 = time.perf_counter()
for _ in range(10):
    pickle.dumps(shard, protocol=pickle.HIGHEST_PROTOCOL)
pickle_s = (time.perf_counter() - t_p0) / 10
t_u0 = time.perf_counter()
for _ in range(10):
    pickle.loads(blob)
unpickle_s = (time.perf_counter() - t_u0) / 10
t0 = time.perf_counter()
for seq in range(n_rounds):
    got = plane.all_to_all('bench', seq, [shard, shard])
    assert len(got) == 2
elapsed = time.perf_counter() - t0
if rank == 0:
    import json
    per_tick = elapsed / n_rounds
    print('RESULT ' + json.dumps({{
        'exchange_rows_per_s': round(2 * rows / per_tick, 1),
        'exchange_mb_per_s': round(2 * payload_bytes / per_tick / 1e6, 1),
        'exchange_tick_ms': round(per_tick * 1e3, 2),
        'exchange_pickle_share': round((pickle_s + unpickle_s) / per_tick, 3),
        'exchange_shard_rows': rows,
        'exchange_shard_mb': round(payload_bytes / 1e6, 2),
    }}))
plane.close()
"""
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env["BENCH_RANK"] = str(rank)
        env["JAX_PLATFORMS"] = "cpu"
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", worker],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    result = None
    for p in procs:
        out, err = p.communicate(timeout=300)
        if p.returncode != 0:
            raise RuntimeError(f"exchange bench rank failed:\n{err[-2000:]}")
        for line in out.splitlines():
            if line.startswith("RESULT "):
                result = json.loads(line[len("RESULT "):])
    assert result, "rank 0 produced no RESULT"
    extras.update(result)
    return result["exchange_rows_per_s"]


def phase_rag_eval(backend: str, extras: dict) -> float:
    """Offline RAG answer-quality eval (r4 Missing #2 / task #4): BM25
    retrieval over a scripted fact corpus + deterministic extractive
    reader; reports adaptive-RAG accuracy, the accuracy-vs-doc-count curve
    (the reference's headline chart, docs/.adaptive-rag/article.py:85),
    and the one-round answer fraction (its >60%-with-1-doc claim)."""
    import tempfile

    _init_jax("cpu")  # host-side pipeline; the reader is deterministic

    import pathway_tpu as pw
    from pathway_tpu.stdlib.indexing import TantivyBM25Factory
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.evals import (
        ExtractiveReaderChat,
        accuracy_vs_doc_count,
        make_fact_corpus,
        run_eval,
    )
    from pathway_tpu.xpacks.llm.question_answering import (
        answer_with_geometric_rag_strategy,
    )

    corpus_dir = tempfile.mkdtemp(prefix="pw_rag_eval_")
    cases = make_fact_corpus(corpus_dir, n_docs=24, seed=7)
    docs = pw.io.fs.read(
        corpus_dir, format="plaintext_by_file", with_metadata=True, mode="static"
    )
    store = DocumentStore(docs, retriever_factory=TantivyBM25Factory())
    chat = ExtractiveReaderChat()
    rounds: list = []

    # ONE retrieval table over every eval question and a single pw.run()
    # (ADVICE r5 #3: the old per-question table rebuilt the shared global
    # graph each call, so pw.run() #N re-executed the full ingest pipeline
    # N times — quadratic in the number of questions).  Every consumer
    # needs at most max_k docs; BM25 top-k is a ranked prefix, so smaller
    # k is a slice of the same retrieval.
    # dedup: results are keyed by question text, and one retrieval serves
    # every case asking the same question
    questions = list(dict.fromkeys(c.question for c in cases))
    max_k = 8
    q = pw.debug.table_from_rows(
        pw.schema_from_types(
            query=str, k=int, metadata_filter=type(None),
            filepath_globpattern=type(None),
        ),
        [(question, max_k, None, None) for question in questions],
    )
    out = store.retrieve_query(q)
    # retrieve_query keeps the query table's universe; join question and
    # result rows on the row key
    key_to_q: dict = {}
    key_to_docs: dict = {}
    pw.io.subscribe(
        q, on_change=lambda key, row, time, is_addition: key_to_q.update(
            {key: row["query"]}
        )
    )
    pw.io.subscribe(
        out, on_change=lambda key, row, time, is_addition: key_to_docs.update(
            {key: row["result"]}
        )
    )
    pw.run(monitoring_level=None)
    retrieved = {
        key_to_q[key]: [d["text"] for d in docs_k]
        for key, docs_k in key_to_docs.items()
        if key in key_to_q
    }
    assert len(retrieved) == len(questions), (
        f"batched retrieval covered {len(retrieved)}/{len(questions)} questions"
    )

    def retrieve_texts(question, k):
        # the one-shot retrieval above only fetched max_k docs per
        # question; a larger k here would silently return fewer docs than
        # asked
        assert k <= max_k, f"retrieve_texts(k={k}) exceeds batched max_k={max_k}"
        return retrieved[question][:k]

    def answer_fn(question):
        docs_k = retrieve_texts(question, 8)
        calls0 = chat.calls
        pred = answer_with_geometric_rag_strategy(
            question, docs_k, chat, n_starting_documents=1, factor=2,
            max_iterations=4,
        )
        rounds.append(chat.calls - calls0)
        return pred

    result = run_eval(answer_fn, cases)
    curve = accuracy_vs_doc_count(
        retrieve_texts, chat, cases, doc_counts=(1, 2, 4)
    )
    one_round = sum(1 for c in rounds if c == 1) / max(len(rounds), 1)
    extras["rag_eval_accuracy"] = round(result.accuracy, 3)
    extras["rag_eval_cases"] = result.cases
    extras["rag_eval_accuracy_vs_docs"] = {str(k): round(v, 3) for k, v in curve.items()}
    extras["rag_eval_one_round_fraction"] = round(one_round, 3)
    return result.accuracy


_PHASES = {
    "retrieval": (phase_retrieval, 1800),
    "retrieve_rerank": (phase_retrieve_rerank, 900),
    "late_interaction": (phase_late_interaction, 900),
    "observe_overhead": (phase_observe_overhead, 450),
    "tracing_overhead": (phase_tracing_overhead, 450),
    "profiling_overhead": (phase_profiling_overhead, 450),
    "sanitizer_overhead": (phase_sanitizer_overhead, 450),
    "analysis_runtime": (phase_analysis_runtime, 450),
    "fault_tolerance": (phase_fault_tolerance, 450),
    "concurrent_serve": (phase_concurrent_serve, 600),
    "self_tuning": (phase_self_tuning, 600),
    "sharded_serve": (phase_sharded_serve, 600),
    "serve_cache": (phase_serve_cache, 450),
    "continuous_decode": (phase_continuous_decode, 450),
    "speculative_decode": (phase_speculative_decode, 450),
    "ingest": (phase_ingest, 900),
    "live_ingest": (phase_live_ingest, 600),
    "serve_fabric": (phase_serve_fabric, 600),
    "partitioned_fabric": (phase_partitioned_fabric, 600),
    "wordcount": (phase_wordcount, 450),
    "scaling": (phase_scaling, 900),
    "exchange": (phase_exchange, 450),
    "rag_eval": (phase_rag_eval, 450),
}


def run_phase_child(name: str, backend: str) -> None:
    extras: dict = {}
    try:
        value = _PHASES[name][0](backend, extras)
        print(json.dumps({"value": value, "extras": extras}))
    except Exception:
        traceback.print_exc()
        print(json.dumps({"error": traceback.format_exc(limit=3).splitlines()[-1]}))
        sys.exit(1)


def run_phase(name: str, backend: str, extras: dict, errors: dict):
    """Run one phase in a subprocess with a hard timeout; parse its JSON."""
    timeout = int(_PHASES[name][1] * float(os.environ.get("BENCH_TIMEOUT_SCALE", "1")))
    env = dict(os.environ)
    env["BENCH_PHASE"] = name
    env["BENCH_BACKEND"] = backend
    if backend == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            capture_output=True,
            timeout=timeout,
            text=True,
            env=env,
        )
    except subprocess.TimeoutExpired:
        errors[name] = f"timeout after {timeout}s"
        return None
    except OSError as exc:
        errors[name] = str(exc)
        return None
    sys.stderr.write(out.stderr)
    for line in reversed(out.stdout.strip().splitlines()):
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if not isinstance(rec, dict):
            continue
        if "error" in rec:
            errors[name] = rec["error"]
            return None
        extras.update(rec.get("extras", {}))
        return rec.get("value")
    errors[name] = f"no JSON from phase (rc={out.returncode})"
    return None


def build_record(state: dict, extras: dict, errors: dict, backends: dict, backend: str) -> dict:
    """The headline record from whatever has been measured SO FAR —
    callable after every phase, so a partial run still yields a complete,
    parseable artifact (the round-5 rc:124 left an empty tail because the
    single record only printed after all ~5,000 s of phases)."""
    p50 = state.get("retrieval")
    docs_per_sec = state.get("ingest")
    rows_per_sec = state.get("wordcount")
    ex = dict(extras)
    if errors:
        ex["errors"] = dict(errors)
    if p50 is not None:
        ndocs = ex.get("index_docs", 0)
        tag = "1M" if ndocs >= 10**6 else str(ndocs)
        record = {
            # device-side p50 under pipelining — the <50 ms target is a
            # device+ICI number; extras carries p50_e2e_ms + the dispatch floor
            "metric": f"retrieval_p50_device_ms_{tag}",
            "value": round(p50, 3),
            "unit": "ms",
            "vs_baseline": round(50.0 / p50, 3),
            "backend": backends.get("retrieval", backend),
        }
    elif docs_per_sec is not None:
        record = {
            "metric": "ingest_docs_per_sec",
            "value": round(docs_per_sec, 1),
            "unit": "docs/s",
            "vs_baseline": None,
            "backend": backends.get("ingest", backend),
        }
    elif rows_per_sec is not None:
        record = {
            "metric": "wordcount_rows_per_sec",
            "value": round(rows_per_sec, 1),
            "unit": "rows/s",
            "vs_baseline": None,
            "backend": backends.get("wordcount", backend),
        }
    else:
        record = {
            "metric": "bench_failed",
            "value": 0.0,
            "unit": "none",
            "vs_baseline": None,
            "backend": backend,
        }
    record["extras"] = ex
    return record


_trajectory_target: "Optional[tuple]" = None  # (path, round) once resolved


def _resolve_trajectory_target() -> tuple:
    """(path, round) for this RUN's trajectory record, resolved ONCE:
    ``BENCH_ROUND`` pins the round explicitly; otherwise the next free
    round after the highest existing ``BENCH_<n>.json`` — a later
    session's run must never silently overwrite an earlier round's
    baseline (every streamed emit within one run still rewrites the
    same file)."""
    global _trajectory_target
    if _trajectory_target is not None:
        return _trajectory_target
    here = os.path.dirname(os.path.abspath(__file__))
    round_raw = os.environ.get("BENCH_ROUND")
    if round_raw:
        round_no: object = (
            int(round_raw) if round_raw.isdigit() else round_raw
        )
    else:
        import glob
        import re as _re

        existing = [
            int(m.group(1))
            for p in glob.glob(os.path.join(here, "BENCH_*.json"))
            for m in [_re.fullmatch(r"BENCH_(\d+)\.json", os.path.basename(p))]
            if m
        ]
        round_no = max(existing) + 1 if existing else 12
    path = os.environ.get("BENCH_RECORD_FILE") or os.path.join(
        here, f"BENCH_{round_no}.json"
    )
    _trajectory_target = (path, round_no)
    return _trajectory_target


def write_trajectory_record(record: dict, state: dict) -> Optional[str]:
    """Persist the versioned trajectory record ``BENCH_<round>.json``
    (ISSUE 12: the bench-trajectory bootstrap).  ``BENCH_ROUND`` pins
    the round (auto: next free round number); ``BENCH_RECORD_FILE``
    overrides the path; ``BENCH_RECORD=0`` disables.  Overwritten on
    every streamed emit so a driver timeout still leaves the latest
    partial record — ``python -m pathway_tpu.bench_compare
    BENCH_*.json`` diffs records across rounds and flags >10%
    regressions."""
    if os.environ.get("BENCH_RECORD", "1") in ("0", "false", "off"):
        return None
    path, round_no = _resolve_trajectory_target()
    doc = {
        "schema": 1,
        "round": round_no,
        "created_unix": round(time.time(), 1),
        "phases_measured": sorted(
            name for name, value in state.items() if value is not None
        ),
        **record,
    }
    try:
        with open(path, "w") as fh:
            json.dump(doc, fh, indent=1, sort_keys=True)
            fh.write("\n")
    except OSError as exc:  # the record is best-effort, the run is not
        print(f"[bench] trajectory record write failed: {exc}", file=sys.stderr)
        return None
    return path


def main() -> None:
    phase = os.environ.get("BENCH_PHASE")
    if phase:
        run_phase_child(phase, os.environ.get("BENCH_BACKEND", "cpu"))
        return

    backend = probe_backend()
    extras: dict = {}
    errors: dict = {}
    backends: dict = {}
    state: dict = {}
    t_start = time.monotonic()
    # global wall budget (seconds; 0 = off): when the remaining phases
    # would outlive the driver's budget, SKIP them and keep the partial
    # record instead of dying mid-phase with nothing on stdout
    wall_budget = float(os.environ.get("BENCH_WALL_BUDGET", "0") or 0)

    def emit(partial: bool) -> None:
        """Stream the current best record to the BENCH artifact: a full,
        parseable result line after EVERY phase (flushed), so a driver
        timeout at any point still captures everything measured so far —
        the tail-most complete record wins."""
        record = build_record(state, extras, errors, backends, backend)
        if partial:
            record["partial"] = True
            record["elapsed_s"] = round(time.monotonic() - t_start, 1)
        write_trajectory_record(record, state)
        print(json.dumps(record), flush=True)

    def device_phase(name: str):
        """Run a device phase on the probed backend.  A failure is recorded
        and fails the run; it is never retried on the CPU."""
        value = run_phase(name, backend, extras, errors)
        backends[name] = extras.pop("backend", backend)
        return value

    # importance order (VERDICT r5 #1): headline retrieval first, the
    # strong-scaling curve last — a budget kill loses the least-load-
    # bearing numbers first
    plan = [
        ("retrieval", lambda: device_phase("retrieval")),
        ("retrieve_rerank", lambda: device_phase("retrieve_rerank")),
        ("late_interaction", lambda: device_phase("late_interaction")),
        ("observe_overhead", lambda: device_phase("observe_overhead")),
        ("tracing_overhead", lambda: device_phase("tracing_overhead")),
        ("profiling_overhead", lambda: device_phase("profiling_overhead")),
        ("sanitizer_overhead", lambda: device_phase("sanitizer_overhead")),
        ("analysis_runtime", lambda: device_phase("analysis_runtime")),
        ("fault_tolerance", lambda: device_phase("fault_tolerance")),
        ("concurrent_serve", lambda: device_phase("concurrent_serve")),
        ("self_tuning", lambda: device_phase("self_tuning")),
        ("sharded_serve", lambda: device_phase("sharded_serve")),
        ("serve_cache", lambda: device_phase("serve_cache")),
        ("continuous_decode", lambda: device_phase("continuous_decode")),
        ("speculative_decode", lambda: device_phase("speculative_decode")),
        ("ingest", lambda: device_phase("ingest")),
        ("live_ingest", lambda: device_phase("live_ingest")),
        ("serve_fabric", lambda: device_phase("serve_fabric")),
        ("partitioned_fabric", lambda: device_phase("partitioned_fabric")),
        ("wordcount", lambda: run_phase("wordcount", backend, extras, errors)),
        # host BSP plane microbench + offline answer-quality eval (cpu)
        ("exchange", lambda: run_phase("exchange", "cpu", extras, errors)),
        ("rag_eval", lambda: run_phase("rag_eval", "cpu", extras, errors)),
        ("scaling", lambda: device_phase("scaling")),
    ]
    # BENCH_PHASES=a,b,c runs a subset (trajectory seeding, quick local
    # A/Bs) — unlisted phases are skipped without an error entry
    only_raw = os.environ.get("BENCH_PHASES", "").strip()
    only = {p.strip() for p in only_raw.split(",") if p.strip()} or None
    for name, run in plan:
        if only is not None and name not in only:
            continue
        if wall_budget and time.monotonic() - t_start > wall_budget:
            errors[name] = f"skipped: wall budget {wall_budget:.0f}s exhausted"
            continue
        value = run()
        if name == "wordcount":
            backends["wordcount"] = extras.pop("backend", "cpu")
        state[name] = value
        if name == "retrieve_rerank" and value is not None:
            extras["rerank_pairs_per_sec"] = round(value, 1)
        elif name == "late_interaction" and value is not None:
            extras["stage2_flop_reduction_x"] = round(value, 1)
        elif name == "observe_overhead" and value is not None:
            extras["observe_overhead_pct"] = round(value, 3)
        elif name == "tracing_overhead" and value is not None:
            extras["tracing_overhead_pct"] = round(value, 3)
        elif name == "profiling_overhead" and value is not None:
            extras["profiling_overhead_pct"] = round(value, 3)
        elif name == "sanitizer_overhead" and value is not None:
            extras["sanitizer_overhead_pct"] = round(value, 3)
        elif name == "analysis_runtime" and value is not None:
            extras["donation_guard_overhead_pct"] = round(value, 3)
        elif name == "fault_tolerance" and value is not None:
            extras["fault_overhead_pct"] = round(value, 3)
        elif name == "concurrent_serve" and value is not None:
            extras["serve_coalesce_speedup_c16"] = round(value, 3)
        elif name == "self_tuning" and value is not None:
            extras["self_tuning_speedup_c16"] = round(value, 3)
        elif name == "sharded_serve" and value is not None:
            extras["sharded_merge_share_pct"] = round(value, 2)
        elif name == "continuous_decode" and value is not None:
            extras["continuous_decode_speedup_c16"] = round(value, 3)
        elif name == "speculative_decode" and value is not None:
            extras["speculative_decode_speedup_c16"] = round(value, 3)
        elif name == "ingest" and value is not None:
            extras["ingest_docs_per_sec"] = round(value, 1)
        elif name == "live_ingest" and value is not None:
            extras["live_staleness_p99_ms"] = round(value, 3)
        elif name == "serve_fabric" and value is not None:
            extras["fabric_bounce_p99_ms"] = round(value, 3)
        elif name == "wordcount" and value is not None:
            extras["wordcount_rows_per_sec"] = round(value, 1)
        emit(partial=True)

    record = build_record(state, extras, errors, backends, backend)
    write_trajectory_record(record, state)
    for k, v in errors.items():
        print(f"[bench] {k} FAILED: {v}", file=sys.stderr)
    print(f"[bench] {record}", file=sys.stderr)
    print(json.dumps(record), flush=True)
    if errors:
        sys.exit(1)


if __name__ == "__main__":
    main()

"""One run of one cell: load, warm up, measure, check, print one line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and per-layer metrics are files found
by the names in ``BENCHMARK.json``; nothing about a cell lives in this
file.  Without a TPU holding the chips the cell asks for, the run prints no
result and exits non-zero.  ``--rehearse`` (with ``--config``/``--traffic``
naming files directly) lets the tiny rehearsal configurations run on the
CPU; such a run prints ``"platform": "cpu"`` and is never a measurement.
"""

from __future__ import annotations

import time

_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import re  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

TRACE_START_S = 1.0
TRACE_SECONDS = 3.0
CHECK_SAMPLE = 512
CHECK_SAMPLE_RERANK = 128


def _load(path: str):
    with open(path) as fh:
        return json.load(fh)


def resolve(args):
    """The cell: its configuration, its mix, its metrics, from BENCHMARK.json."""
    bench = _load(os.path.join(ROOT, "BENCHMARK.json")) if os.path.exists(os.path.join(ROOT, "BENCHMARK.json")) else None
    if args.config and args.traffic:
        config = _load(os.path.join(HERE, "configs", f"{args.config}.json"))
        traffic = _load(os.path.join(HERE, "traffic", f"{args.traffic}.json"))
        cell = {"name": args.workload or f"{args.config}.{args.traffic}", "config": args.config,
                "traffic": args.traffic, "chips": config["chips"]}
    else:
        if bench is None:
            raise SystemExit("no BENCHMARK.json at the root of this checkout")
        cells = {w["name"]: w for w in bench["workloads"]}
        if args.workload not in cells:
            raise SystemExit(f"unknown workload {args.workload!r}; BENCHMARK.json has {sorted(cells)}")
        cell = cells[args.workload]
        files = {c["name"]: c["file"] for c in bench["configs"]}
        config = _load(os.path.join(ROOT, files[cell["config"]]))
        traffic = _load(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))

    def applies(metric):
        return "workloads" not in metric or cell["name"] in metric["workloads"]

    if bench is not None and not (args.config and args.traffic):
        e2e = [m for m in bench["end_to_end"] if applies(m)]
        layer = [m["name"] for m in bench["per_layer"] if applies(m)]
    else:  # rehearsal: every end-to-end number, every metric file
        e2e = [{"name": n, "unit": u} for n, u in (
            ("latency_p50_ms", "ms"), ("latency_p95_ms", "ms"), ("throughput_rps", "requests/s"),
            ("freshness_p50_ms", "ms"), ("setup_s", "s"))]
        layer = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "metrics")) if f.endswith(".json"))
    return cell, config, traffic, e2e, layer


def find_devices(chips: int, rehearse: bool):
    import jax

    devices = jax.local_devices()
    platform = devices[0].platform
    if platform != "tpu" and not rehearse:
        raise SystemExit(f"JAX found platform {platform!r}, not a TPU: the benchmark has no CPU mode")
    if len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chip(s), JAX found {len(devices)}")
    return devices


class GcTimer:
    """Total pause of generation-2 collections, through ``gc.callbacks``."""

    def __init__(self):
        self.pause_s, self.count, self._t = 0.0, 0, None

    def __call__(self, phase, info):
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pause_s += time.perf_counter() - self._t
            self.count += 1
            self._t = None


class CompileCounter(logging.Filter):
    """Counts compilations (``jax.monitoring``) and keeps their names
    (``jax_log_compiles`` records, swallowed here)."""

    def __init__(self):
        import jax
        import jax.monitoring

        super().__init__()
        self.n, self.names = 0, []
        self.hits = self.misses = 0
        self.seconds = {"backend_compile": 0.0, "cache_retrieval": 0.0}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)
        jax.config.update("jax_log_compiles", True)
        for name in ("jax._src.interpreters.pxla", "jax._src.dispatch", "jax._src.compiler"):
            logging.getLogger(name).addFilter(self)

    def _on(self, event, duration, **kw):
        if "backend_compile" in event:
            self.n += 1
            self.seconds["backend_compile"] += duration
        elif "cache_retrieval_time" in event:
            self.seconds["cache_retrieval"] += duration

    def _on_event(self, event, **kw):
        if event.endswith("/cache_hits"):
            self.hits += 1
        elif event.endswith("/cache_misses"):
            self.misses += 1

    def summary(self) -> str:
        return (f"{self.n} compile requests: {self.hits} cache hits, {self.misses} misses, "
                f"{self.seconds['backend_compile']:.1f}s compiling or loading, {self.seconds['cache_retrieval']:.1f}s reading the cache")

    def filter(self, record):
        msg = record.getMessage()
        if msg.startswith(("Compiling ", "Finished ", "Not writing", "Writing ", "Persistent compilation cache")):
            if msg.startswith("Compiling "):
                shapes = re.findall(r"(?:int32|float32|bfloat16)\[[\d,]+\]", msg)
                self.names.append(msg[10:].split(" with ")[0] + " " + " ".join(shapes[:3] + shapes[-3:]))
            return False
        return True


class WindowTrace:
    """A few seconds of profiler trace inside the window: host spans and
    device operations, no Python call tracing (it slows the host it is
    measuring).  ``tick`` is called from the main thread while the load runs."""

    def __init__(self, seconds: float):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.length = min(TRACE_SECONDS, seconds / 2)
        self.t0 = self.t1 = 0.0  # host clock at the traced part's start and end
        self._span = None
        self.done = False

    def tick(self, t: float) -> None:
        import jax

        from benchmarks import reduce_trace

        if self.done:
            return
        if self._span is None and t >= TRACE_START_S:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level, opts.host_tracer_level = 0, 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation(reduce_trace.WINDOW_SPAN)
            self._span.__enter__()
            self.t0 = time.perf_counter()
        elif self._span is not None and time.perf_counter() - self.t0 >= self.length:
            self.close()

    def close(self) -> None:
        import jax

        if self._span is None or self.done:
            return
        self.t1 = time.perf_counter()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.done = True

    def reduce(self, keep_as=None):
        from benchmarks import reduce_trace

        try:
            events = reduce_trace.read_xplane(reduce_trace.find_xplane(self.dir))
            if keep_as:
                reduce_trace.save_events(events, keep_as)
            return reduce_trace.reduce_events(events)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def needed_work(config, layout, n_shards, plan, window, a: float, b: float):
    """Operations and bytes that the requests served inside ``a..b`` of the
    window needed (real tokens, probed slabs): ``flops.py`` from shapes."""
    from benchmarks import flops

    inside = np.flatnonzero(window.ok & (window.sent >= a) & (window.done <= b))
    enc = config["encoder"]
    q_tok = [min(len(plan.texts[i].split()) + 2, enc["max_length"]) for i in inside]
    nq = len(inside) * n_shards  # every shard probes and rescores for every query
    work = {
        "requests": int(len(inside)),
        "rescore_flops": flops.rescore_flops(nq, layout["probe"], layout["M_pad"], enc["hidden_size"]),
        "rescore_bytes": flops.rescore_bytes(nq, layout["probe"], layout["M_pad"], layout["d_pad"], layout["slab_bytes"]),
    }
    model = flops.encoder_flops(q_tok, enc) + flops.probe_flops(nq, layout["C"], enc["hidden_size"]) + work["rescore_flops"]
    if config.get("cross_encoder"):
        ce, wide = config["cross_encoder"], int(config["serve"]["candidates"])
        doc_tok = 37  # a bulk document has 8..63 words, evenly: 35.5 on average, plus its lead word
        model += flops.cross_encoder_flops(
            [min(t + doc_tok + 1, ce["max_length"]) for t in q_tok for _ in range(wide)], ce)
    for c, cm in enumerate(plan.commits):
        if a <= window.commit_called[c] <= b:
            model += flops.encoder_flops([min(len(t.split()) + 2, enc["max_length"]) for _, t in cm.rows], enc)
    work["model_flops"] = model
    return work


def compare_window(config, ref, space, doc_text, plan, window, sample):
    """The numbers ``correct`` is decided by, for the sampled requests."""
    from benchmarks import check

    live_rows, live_visible = list(plan.setup_rows), [-np.inf] * len(plan.setup_rows)
    for c, cm in enumerate(plan.commits):
        live_rows += cm.rows
        vis = window.commit_visible[c]
        live_visible += [vis if np.isfinite(vis) else np.inf] * len(cm.rows)
    probe_keys = None
    if plan.probes:
        # a probe is judged only if it was sent after its commit became visible
        probe_keys = []
        for i in sample:
            c = plan.probes.get(i)
            seen = c is not None and window.commit_visible[c] <= window.sent[i]
            probe_keys.append(plan.commits[c].probe_key if seen else -1)
    return check.compare(
        config, ref, space, doc_text, [plan.texts[i] for i in sample], [window.kept[i][0] for i in sample],
        sent_s=window.sent[sample], live_rows=live_rows,
        live_visible_s=np.asarray(live_visible, np.float64), probe_keys=probe_keys,
    )


def _burst(system, texts, k):
    tickets = [system.scheduler.submit([t], k) for t in texts]
    return [t() for t in tickets]


def warm_up(system, plan, traffic, seed, label):
    """Serve every shape the window's traffic can make: each length bucket
    at each batch bucket (a burst of B requests coalesces into one batch),
    then mixed bursts at the sizes in between (stage 2 packs by content)."""
    from benchmarks import corpus

    t0 = time.monotonic()
    rng = corpus.rng_for(seed, 23)
    cap = plan.callers or plan.batch_cap
    sizes = [b for b in (1, 4, 16, 64) if b <= max(cap, 1)]
    if cap not in sizes:
        sizes.append(cap)
    n = 0
    for length in plan.length_buckets:
        for b in sizes:
            for rep in range(2):
                texts = [
                    system.texts.compose(f"warm{label}x{n + i}", int(rng.integers(0, system.texts.n_topics)), length - 2, rng)
                    for i in range(b)
                ]
                _burst(system, texts, plan.k)
                n += b
    if system.cross is not None:
        # stage 2 packs (query, document) pairs by content, so its row bucket
        # follows the lengths: at each batch size serve all-short, all-long
        # and mixed queries, which reach the fewest and the most rows it takes
        spec = traffic["query_words"]
        for b in sorted(set(range(1, min(cap, 8) + 1)) | set(range(8, cap + 1, 2))):
            mixed = corpus.lognormal_lengths(b, spec["mu"], spec["sigma"], spec["min"], spec["max"])
            for lengths in ([spec["min"]] * b, [spec["max"]] * b, mixed):
                texts = [
                    system.texts.compose(f"warm{label}y{n + i}", int(rng.integers(0, system.texts.n_topics)), int(lengths[(i * 7) % b]), rng)
                    for i in range(b)
                ]
                _burst(system, texts, plan.k)
                n += b
    return time.monotonic() - t0


def setup_commits(system, plan, per):
    """Fill the index's tail as the window will find it: commit the set-up
    documents through the connector, let the absorb they trigger land, then
    leave ``tail_fill`` documents waiting."""
    # the shortest documents first, the longest second: the two commits pack
    # into the fewest and the most encoder rows a commit can take, so both of
    # the packed encoder's shapes are compiled here and not in the window
    rows = sorted(plan.setup_rows, key=lambda r: len(r[1].split()))
    rows = rows[:per] + rows[-per:] + rows[per:-per] if len(rows) >= 3 * per else rows
    first = len(rows) - plan.tail_fill
    base = system.docs_visible()

    done = 0
    for phase, part in enumerate((rows[:first], rows[first:])):
        for a in range(0, len(part), per):
            system.commit(part[a : a + per])
        done += len(part)
        limit = time.monotonic() + 120
        while system.docs_visible() < base + done and time.monotonic() < limit:
            time.sleep(0.002)
        if phase == 0 and first > 0:
            while (system.absorbs() < 1 or system.absorbing()) and time.monotonic() < limit:
                time.sleep(0.005)
    if system.docs_visible() < base + len(rows):
        raise SystemExit("set-up commits did not become visible in time")


def prepare(system, plan, traffic, seed) -> float:
    """Warm every shape, then (where the mix commits) start ingest, fill the
    tail and warm the shapes a filled tail adds.  Returns the seconds spent
    warming."""
    warm_s = warm_up(system, plan, traffic, seed, "a")
    if traffic.get("commits"):
        system.quiet()  # the first serves compiled: without this the ingest loop yields to them
        system.start_ingest()
        setup_commits(system, plan, int(traffic["commits"]["docs_per_commit"]))
        warm_s += warm_up(system, plan, traffic, seed, "b")
    return warm_s


def main(argv=None, sabotage=None) -> int:
    """``sabotage(system)`` is for the fault tests only: it breaks the timed
    path underneath before the warm-up."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default=None)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--config", default=None, help="rehearsal: a file under configs/, with --traffic")
    ap.add_argument("--traffic", default=None)
    ap.add_argument("--rehearse", action="store_true", help="allow a CPU run (never a measurement)")
    ap.add_argument("--control", default=None, help="readings only: also put the reference at this precision (fp8) in the program's place")
    ap.add_argument("--keep-trace", default=None, help="also save the reduced trace's events here (.json.gz)")
    args = ap.parse_args(argv)

    cell, config, traffic, e2e, layer_names = resolve(args)
    bench_path = os.path.join(ROOT, "BENCHMARK.json")
    seconds = args.seconds or (_load(bench_path)["run_seconds"] if os.path.exists(bench_path) else 10)
    devices = find_devices(int(cell["chips"]), args.rehearse)

    import jax

    from benchmarks import check, flops, loadgen, metrics, peaks, reduce_trace
    from benchmarks.reference import Reference
    from benchmarks.system import System, log

    compiles = CompileCounter()
    system = System(config, args.seed)
    if sabotage is not None:
        sabotage(system)
    space, texts = system.space, system.texts
    p = loadgen.plan(traffic, texts, args.seed, seconds, space.n_keys)
    warm_s = prepare(system, p, traffic, args.seed)
    per = bool(traffic.get("commits"))
    # a short rehearsal of the mix itself (other texts, no commits)
    warm_traffic = {k: v for k, v in traffic.items() if k != "commits"}
    wp = loadgen.plan(warm_traffic, texts, args.seed ^ 0x5BD1E995, 2.0, space.n_keys)
    wp.texts = ["w " + t for t in wp.texts]
    loadgen.run_window(system.scheduler.serve, wp, 2.0)
    log(f"warm-up {warm_s:.1f}s + 2s rehearsal; {compiles.summary()}")

    # the window opens on a quiet SLO engine and empty histograms
    gc.collect()
    system.quiet()
    timer = GcTimer()
    gc.callbacks.append(timer)
    before = system.program_state()
    cache_before = system.cache_tier("result")
    compiles_before, names_before = compiles.n, len(compiles.names)
    tracer = WindowTrace(seconds) if args.trace else None

    rerank = system.cross is not None
    pool = len(p.texts) if p.loop == "open" else int(traffic["min_rps"] * seconds)
    sample_size = CHECK_SAMPLE_RERANK if rerank else CHECK_SAMPLE
    keep = check.choose_sample(pool, p.texts, args.seed, sample_size, always=sorted(p.probes))

    setup_s = time.monotonic() - _PROCESS_START
    window = loadgen.run_window(
        system.scheduler.serve, p, seconds, keep=keep,
        commit=system.commit if per else None,
        docs_visible=system.docs_visible if per else None,
        annotate=jax.profiler.TraceAnnotation if args.trace else None,
        on_tick=tracer.tick if tracer else None,
    )
    if tracer:
        tracer.close()  # a window shorter than the trace
    gc.callbacks.remove(timer)
    compiled_in_window = compiles.n - compiles_before
    after = system.program_state()
    cache_after = system.cache_tier("result")

    # ---- end-to-end numbers, over all requests of the window ----------------
    summary = loadgen.summarise(window.due, window.done, window.ok, seconds)
    fresh = window.commit_visible - window.commit_called
    if fresh.size:
        worst = float(np.nanmax(fresh)) if np.isfinite(fresh).any() else loadgen.POST_WINDOW_WAIT_S
        summary["freshness_p50_ms"] = loadgen.percentile(np.where(np.isfinite(fresh), fresh, worst), 50) * 1e3
        summary["commits"] = int(fresh.size)
        summary["commits_never_visible"] = int((~np.isfinite(fresh)).sum())
    summary["setup_s"] = setup_s
    stats = {k: after["scheduler"].get(k, 0) - before["scheduler"].get(k, 0) for k in after["scheduler"]}
    log(
        f"window: attempted {summary['attempted']} failed {summary['failed']} backlog at close {summary['backlog_at_close']}; "
        f"compilations inside the window {compiled_in_window} {compiles.names[names_before:][:8]}; SLO objectives firing {after['slo_firing']}; "
        f"shed {after['shed'] - before['shed']}; ingest yields {after['ingest_yields'] - before['ingest_yields']}; "
        f"gen-2 collections {timer.count} ({timer.pause_s * 1e3:.1f} ms); failure series {after['failure_series']}; "
        f"load generator {window.notes}"
    )

    # ---- device: memory first, then the program's state is freed ------------
    mem = [d.memory_stats() or {} for d in devices[: int(cell["chips"])]]
    memory = {
        "peak": max((m.get("peak_bytes_in_use", 0) for m in mem), default=0),
        "resident": max((m.get("bytes_in_use", 0) for m in mem), default=0),
    }
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": int(cell["chips"]), "memory_peak_bytes": int(memory["peak"]),
    }

    hist_read = {}

    def hist(family, **labels):
        key = (family, tuple(sorted(labels.items())))
        if key not in hist_read:
            h = system.histogram(family, **labels)
            hist_read[key] = (h.count, h.sum_seconds)
        return hist_read[key]

    out_metrics, breakdown = {}, None
    if tracer:
        reduced = tracer.reduce(args.keep_trace)
        work = needed_work(config, system.layout, system.n_shards, p, window,
                           tracer.t0 - window.t0, tracer.t1 - window.t0)
        ctx = {
            "hist": hist, "stats": stats, "window": window, "summary": summary, "plan": p,
            "cache": {k.split("_total")[0].replace("pathway_cache_", ""): cache_after.get(k, 0) - cache_before.get(k, 0)
                      for k in cache_after if k.startswith("pathway_cache_")},
            "trace": reduced, "work": work, "peaks": peaks.peaks_for(device["kind"]) if device["platform"] == "tpu" else None,
            "chips": int(cell["chips"]), "n_shards": system.n_shards, "memory": memory,
            "gc_pause_ms": timer.pause_s * 1e3,
            "kernel_seconds": reduce_trace.kernel_seconds, "roofline": flops.roofline_seconds,
        }
        if ctx["peaks"] is None:  # a rehearsal has no peaks: trace-derived shares are not reported
            ctx["trace"] = None
        for name in layer_names:
            spec = metrics.load(name)
            value = metrics.read(spec, ctx)
            if value is not None:
                out_metrics[name] = {"value": float(value), "unit": spec["unit"]}
        if reduced is not None:
            device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
            log(f"traced {reduced['window_s']:.2f}s on {reduced['chips']} chip(s): busy {reduced['busy_s']:.3f}s, "
                f"{work['requests']} requests inside, kernel ivf_rescore {reduce_trace.kernel_seconds(reduced, 'ivf_rescore'):.4f}s")
    else:
        for m in e2e:
            if m["name"] in summary:
                out_metrics[m["name"]] = {"value": float(summary[m["name"]]), "unit": m["unit"]}

    # ---- correct: the reference, once the program's state is freed ----------
    t_check = time.monotonic()
    enc_params, cross_params = system.enc_params, system.cross_params
    doc_text = system.doc_text
    system.free()
    ref = Reference(config["encoder"], enc_params, config.get("cross_encoder"), cross_params)
    wanted = set(keep)
    sample = [i for i in sorted(window.kept) if window.ok[i] and i in wanted]
    numbers = compare_window(config, ref, space, doc_text, p, window, sample) if sample else {}
    numbers["requests_not_clean"] = float(summary["failed"])
    numbers["commits_never_visible"] = float(summary.get("commits_never_visible", 0))
    numbers["sample_missing"] = float(0 if sample else 1)
    limits = {**config["limits"], **(traffic.get("limits") or {})}
    limits = {k: v for k, v in limits.items() if v is not None}
    good, table = check.judge(numbers, limits)
    control_line = None
    if args.control and sample:
        # readings only: the reference in the precision below, in the program's place
        control = Reference(config["encoder"], enc_params, config.get("cross_encoder"), cross_params, precision=args.control)
        queries = [p.texts[i] for i in sample]
        answers = check.control_answers(config, control, space, doc_text, queries)
        c_numbers = check.compare(config, ref, space, doc_text, queries, answers)
        c_good, c_table = check.judge(c_numbers, limits)
        control_line = {"precision": args.control, "correct": bool(c_good), "numbers": c_numbers}
        for name, value in c_numbers.items():
            print(f"control[{args.control}] {name} = {value!r}", file=sys.stderr)
    extra = {k: v for k, v in numbers.items() if k not in table}
    extra["sampled_requests"] = float(len(sample))
    extra["check_seconds"] = time.monotonic() - t_check
    log(f"check done; {compiles.summary()}")

    check.print_table(table, extra)

    line = {
        "correct": bool(good),
        "attempted": int(summary["attempted"]),
        "failed": int(summary["failed"]),
        "metrics": out_metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["workload"] = cell["name"]
    line["seed"] = args.seed
    line["window"] = {k: summary[k] for k in ("backlog_at_close", "throughput_rps", "latency_p50_ms", "latency_p95_ms", "setup_s") if k in summary}
    line["window"].update({
        "compilations": compiled_in_window, "slo_firing": after["slo_firing"],
        "shed": after["shed"] - before["shed"], "ingest_yields": after["ingest_yields"] - before["ingest_yields"],
        "gc2": timer.count, "gc2_pause_ms": timer.pause_s * 1e3,
        "freshness_p50_ms": summary.get("freshness_p50_ms"),
        "absorbs": after["absorbs"] - before["absorbs"],
    })
    if control_line is not None:
        line["control"] = control_line
    line["compared"] = {name: {"value": v, "limit": lim} for name, (v, lim) in table.items()}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

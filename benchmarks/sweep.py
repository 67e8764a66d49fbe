"""Find a mix's knee, once, on the chip: one set-up, then a short window at
each rate (open loop) in one process.  Prints one JSON line per rate.

    python3 benchmarks/sweep.py --workload <name> --rates 300,450,600 --seconds 6

The knee is the highest rate at which the backlog does not grow over the
window and nothing fails; the traffic file then states three fifths of it.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=12345)
    args = ap.parse_args()
    from benchmarks import loadgen, run
    from benchmarks.system import System, log

    args.trace = 0
    cell, config, traffic, _, _ = run.resolve(args)
    run.find_devices(int(cell["chips"]), args.rehearse)
    compiles = run.CompileCounter()
    system = System(config, args.seed)
    base = loadgen.plan(traffic, system.texts, args.seed, args.seconds, system.space.n_keys)
    run.prepare(system, base, traffic, args.seed)
    per = bool(traffic.get("commits"))
    first_key = system.space.n_keys + 10_000_000
    for n, rate in enumerate(float(r) for r in args.rates.split(",")):
        p = loadgen.plan(traffic, system.texts, args.seed + 1 + n, args.seconds, first_key + n * 1_000_000, rate=rate)
        p.texts = [f"s{n} " + t if i not in p.probes else t for i, t in enumerate(p.texts)]
        p.setup_rows = []
        gc.collect()
        system.quiet()
        timer, c0 = run.GcTimer(), compiles.n
        gc.callbacks.append(timer)
        w = loadgen.run_window(
            system.scheduler.serve, p, args.seconds,
            commit=system.commit if per else None, docs_visible=system.docs_visible if per else None,
        )
        gc.callbacks.remove(timer)
        s = loadgen.summarise(w.due, w.done, w.ok, args.seconds)
        late = (w.sent - w.due)[np.isfinite(w.sent)]
        half = w.due >= args.seconds / 2
        lat = (w.done - w.due)
        fresh = w.commit_visible - w.commit_called
        state = system.program_state()
        print(json.dumps({
            "rate": rate, **{k: s[k] for k in ("attempted", "failed", "backlog_at_close", "latency_p50_ms", "latency_p95_ms", "throughput_rps")},
            "p50_first_half_ms": float(np.nanmedian(lat[~half]) * 1e3), "p50_second_half_ms": float(np.nanmedian(lat[half]) * 1e3),
            "gen_late_p95_ms": float(np.percentile(late, 95) * 1e3) if late.size else None,
            "freshness_p50_ms": float(np.nanmedian(fresh) * 1e3) if fresh.size else None,
            "compilations": compiles.n - c0, "gc2": timer.count, "gc2_pause_ms": timer.pause_s * 1e3, "worst_ms": float(np.nanmax(lat) * 1e3),
            "slo_firing": state["slo_firing"], "absorbs": state["absorbs"], "yields": state["ingest_yields"],
        }), flush=True)
    system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The generation kind: a decoder-only language model behind the continuous
decode engine, a closed loop of callers asking for answers of a few dozen
tokens to prompts that share an instruction.

``system.py`` is the only module that imports the program; ``plan.py`` makes
a window's work from a mix, ``weights.py`` the model's weights from the seed,
``flops.py`` counts what the requests needed, ``reference.py`` is the plain
reference and ``check.py`` the comparison.  ``Served`` is what the runner
calls (``benchmarks/kinds/__init__.py`` states the interface).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ...loadgen import percentile
from . import check, flops, plan as planning
from .reference import Reference
from .system import System

SAMPLE = 16  # requests the reference runs again: at least 12, the heaviest among them
_STATS = ("logit", "lse", "top_ids", "top_logits")
_REQUEST_SPAN, _SLICE_S = "bench.request", 0.25  # the runner's own span name for a request in flight


def _thirds(window, seconds: float) -> Tuple[Optional[float], Optional[float]]:
    """Median latency of the clean requests sent in the window's first and last third."""
    lat, out = window.done - window.due, []
    for lo, hi in ((0.0, seconds / 3), (2 * seconds / 3, seconds)):
        part = lat[window.ok & np.isfinite(lat) & (window.sent >= lo) & (window.sent < hi) & (window.done <= seconds)]
        out.append(percentile(part, 50) * 1e3 if part.size else None)
    return out[0], out[1]


class Served:
    """One generator deployment under one mix: ``system.decoder.submit`` is the entry."""

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any], seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.system = System(config, seed)
        self._reference_logits: List[np.ndarray] = []
        self._annotate = None  # the runner's trace annotation, when a run is traced (``actions``)

    # -- the work -------------------------------------------------------------
    def plan(self, seconds: float, rate: Optional[float] = None, nth: Optional[int] = None):
        if nth is None:
            return planning.plan(self.traffic, self.seed, seconds)
        return planning.plan(self.traffic, self.seed + 1 + nth, seconds, label=f"s{nth}x")

    def rehearsal_plan(self, seconds: float):
        return planning.plan(self.traffic, self.seed, seconds, label="h")

    def prepare(self, plan) -> float:
        """Every program the plan can reach, run once: each join batch bucket
        at each suffix bucket, cold and behind the shared instruction's cached
        block, and the step chunk (``ContinuousDecoder.warm``); then one burst
        of real requests as wide as the slot pool, so that the host side of a
        join (prefix capture and restore) has run too."""
        t0 = time.monotonic()
        dec, block = self.system.decoder, self.system.block
        lo, hi = self.traffic["prompt_tokens"]
        shared = int(self.traffic["instruction_tokens"])
        prefixes = (0,) + ((block,) if block and shared >= block else ())
        dec.warm((int(lo), int(hi)), prefixes)
        burst = planning.plan(self.traffic, self.seed, 1.0, label="p")
        for rep in range(2):
            tickets = [
                dec.submit(burst.texts[i], max_new_tokens=int(burst.budgets[i]), temperature=0.0)
                for i in range(rep * dec.slots, (rep + 1) * dec.slots)
            ]
            for t in tickets:
                t()
        return time.monotonic() - t0

    def requests(self, plan):
        submit, texts, budgets = self.system.decoder.submit, plan.texts, plan.budgets

        def send(i: int):
            ticket = submit(texts[i], max_new_tokens=int(budgets[i]), temperature=0.0)
            while self._annotate is not None:
                # a request outlasts the traced part of a window, and the profiler keeps only spans that
                # begin and end inside it: while a run is traced, the wait is marked in slices, so that the
                # trace shows a request in flight (``device_idle_inflight_share``); an untraced run waits once
                with self._annotate(_REQUEST_SPAN):
                    try:
                        return ticket.result(timeout=_SLICE_S)
                    except TimeoutError:
                        pass
            return ticket()

        def outcome(i: int, res):
            meta = getattr(res, "meta", None) or {}
            tokens = meta.get("token_ids") or []
            clean = not getattr(res, "degraded", ()) and len(tokens) == int(budgets[i]) and bool(meta.get("logprobs"))
            kept = {"prompt_ids": meta.get("prompt_ids"), "token_ids": tokens, "choice": tokens,
                    **{k: (meta.get("logprobs") or {}).get(k) for k in _STATS}} if clean else None
            marks = {"first_token": meta["t_first_token"], "tokens": len(tokens)} if meta.get("t_first_token") else None
            return clean, kept, marks

        return send, outcome

    def actions(self, plan, annotate=None) -> List[Any]:
        self._annotate = annotate
        return []

    def sample(self, plan, seconds: float) -> List[int]:
        expected = int(float(self.traffic["min_rps"]) * seconds)
        return check.sample_for(expected, plan.prompt_tokens + plan.budgets, self.seed, SAMPLE - 1)

    # -- the program's state ----------------------------------------------------
    def quiet(self) -> None:
        self.system.quiet()

    def state(self) -> Dict[str, Any]:
        return self.system.program_state()

    def window_summary(self, plan, window) -> Dict[str, float]:
        first, n_out = window.marks["first_token"], window.marks["tokens"]
        ok = window.ok & np.isfinite(first) & np.isfinite(window.done)
        if not ok.any():
            return {}
        ttft = (first - window.due)[ok]
        tpot = ((window.done - first) / np.maximum(n_out - 1, 1))[ok]
        inside = ok & (window.done <= window.seconds)
        out = {
            "ttft_p50_ms": percentile(ttft, 50) * 1e3,
            "tpot_p50_ms": percentile(tpot, 50) * 1e3,
            "decode_tokens_per_s": float(n_out[inside].sum()) / float(window.seconds),
            "completions": int(inside.sum()),
        }
        early, late = _thirds(window, window.seconds)
        if early is not None and late is not None:
            out["latency_p50_ms_first_third"], out["latency_p50_ms_last_third"] = early, late
        return out

    def readings(self, before: Dict[str, Any], after: Dict[str, Any]):
        system = self.system
        hist_read: Dict[Any, Tuple[int, float]] = {}

        def hist(family, **labels):
            key = (family, tuple(sorted(labels.items())))
            if key not in hist_read:
                h = system.histogram(family, **labels)
                hist_read[key] = (h.count, h.sum_seconds)
            return hist_read[key]

        pool = {k: after["pool"][k] - before["pool"].get(k, 0) for k in after["pool"]}
        pre = {k: after["prefill_tokens"][k] - before["prefill_tokens"].get(k, 0) for k in after["prefill_tokens"]}
        tier = {k: after["prefix_tier"].get(k, 0) - before["prefix_tier"].get(k, 0) for k in after["prefix_tier"]}
        gen: Dict[str, float] = {"kv_bytes_per_token": float(after["kv_bytes_per_token"]), "slots": float(after["slots"])}
        if pre["reused"] + pre["computed"]:
            gen["prefix_reused_share"] = 100.0 * pre["reused"] / (pre["reused"] + pre["computed"])
        if pool["chunks"]:
            gen["slot_occupancy_share"] = 100.0 * pool["occupancy_sum"] / (pool["chunks"] * after["slots"])
        if pool.get("tokens_forwarded"):
            gen["loop_passes_per_token"] = pool["loop_passes"] / pool["tokens_forwarded"]
        n_step, step_s = hist("pathway_generator_phase_seconds", phase="step")
        n_pre, pre_s = hist("pathway_generator_phase_seconds", phase="prefill")
        if step_s + pre_s > 0:
            # of the engine's time in device round trips, the part in which live lanes waited on a prefill
            gen["decode_stalled_share"] = 100.0 * (after["stalled_s"] - before["stalled_s"]) / (step_s + pre_s)
        ctx = {
            "hist": hist, "counter": system.counter, "gen": gen,
            # what the retrieval kind's readers ask for: nothing of it is served here
            "stats": {}, "cache": {}, "n_shards": 1,
        }
        extras = {
            "slo_firing": after["slo_firing"], "failure_series": after["failure_series"],
            "pool": {k: v for k, v in pool.items() if v}, "prefix_tokens": pre,
            "prefix_tier": {k: v for k, v in tier.items() if v}, "exit_mass": after["exit_mass"],
            "stalled_s": after["stalled_s"] - before["stalled_s"],
        }
        return ctx, extras

    def needed_work(self, plan, window, a: float, b: float) -> Dict[str, Any]:
        return flops.needed_work(self.system.arch, plan.prompt_tokens, window, a, b)

    # -- correct ----------------------------------------------------------------
    def free(self) -> None:
        self.system.free()

    def _score(self, window, sample: Sequence[int], precision: str = "f32", fault: Optional[str] = None):
        ref = Reference(self.system.arch, self.system.params, precision=precision, fault=fault)
        kept = [window.kept[i] for i in sample]
        return ref.score([k["prompt_ids"] + k["token_ids"] for k in kept], [len(k["prompt_ids"]) for k in kept])

    def compare(self, plan, window, sample: Sequence[int]) -> Dict[str, float]:
        short = sum(1 for i in np.flatnonzero(window.ok) if int(window.marks["tokens"][i]) != int(plan.budgets[i]))
        numbers: Dict[str, float] = {"tokens_short": float(short)}
        if sample:
            self._reference_logits = self._score(window, sample)
            numbers.update(check.compare([window.kept[i] for i in sample], self._reference_logits))
        return numbers

    def control(self, plan, window, sample: Sequence[int], precision: str) -> Dict[str, float]:
        """The reference in the program's place, judged like the program:
        at a precision below the configuration's (``fp8``), or with the
        planted fault (``stale_cache``: a loop step reads the last one's
        cache) that the check has to catch."""
        how = {"fault": precision} if precision == "stale_cache" else {"precision": precision}
        logits = self._score(window, sample, **how)
        records = [
            {**check.record_of(lg, window.kept[i]["token_ids"], len(window.kept[i]["top_ids"][0])),
             "token_ids": window.kept[i]["token_ids"]}
            for i, lg in zip(sample, logits)
        ]
        return check.compare(records, self._reference_logits)

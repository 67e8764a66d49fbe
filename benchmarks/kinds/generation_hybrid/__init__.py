"""The hybrid generation kind: a decoder-only language model whose layers are
mostly gated delta-rule (linear-attention) layers with a full-attention layer
among every few, routed experts of which this chip holds a range beside a
shared expert, behind the continuous decode engine, under a closed loop of
callers who ask several questions of the same documents.

The comparison is the generation kind's (``kinds/generation/check.py``), and
so is every method of its ``Served`` that does not change; this kind's own are
``system.py`` (the only module that imports the program), ``weights.py`` (the
held share only), ``plan.py`` (sessions over documents), ``flops.py`` and
``reference.py``.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..generation import Served as _Served, check
from ..generation_moe import Served as _SparseServed
from . import flops, plan as planning
from .reference import FAULTS, Reference
from .system import System

WARM, COLD = 9, 3  # requests the reference runs again: behind a restored snapshot, and from token 0
WARM_LEAST, COLD_LEAST = 8, 2  # of them, how many must have been served so, or the check has not seen both paths


class Served(_Served):
    """One hybrid generator deployment under one mix of document sessions."""

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any], seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.system = System(config, seed)
        self._reference_logits: List[np.ndarray] = []
        self._annotate = None

    # -- the work -------------------------------------------------------------
    def plan(self, seconds: float, rate: Optional[float] = None, nth: Optional[int] = None):
        if nth is None:
            return planning.plan(self.traffic, self.seed, seconds)
        return planning.plan(self.traffic, self.seed + 1 + nth, seconds, label=f"s{nth}x")

    def rehearsal_plan(self, seconds: float):
        return planning.plan(self.traffic, self.seed, seconds, label="h", rehearsal=True)

    def prepare(self, plan) -> float:
        """Every program the plan can reach, run once (``ContinuousDecoder.warm``:
        each join bucket under the token budget, from token 0 and behind the
        split point a live document's questions start from, and the step
        chunk); then one question of each live document, a slot pool's worth at
        a time, so that the window opens on a warm tier."""
        t0 = time.monotonic()
        dec, block = self.system.decoder, self.system.block
        lo = int(self.traffic["instruction_tokens"]) + int(self.traffic["document_tokens"][0])
        hi = int(self.traffic["instruction_tokens"]) + int(self.traffic["document_tokens"][1])
        splits = sorted({0} | {flops.split_point(n, block) for n in (lo, hi)})
        dec.warm((lo + int(self.traffic["question_tokens"][0]), hi + int(self.traffic["question_tokens"][1])), tuple(splits))
        for a in range(0, len(plan.setup), dec.slots):
            tickets = [dec.submit(text, max_new_tokens=budget, temperature=0.0) for text, budget in plan.setup[a : a + dec.slots]]
            for t in tickets:
                t()
        return time.monotonic() - t0

    def requests(self, plan):
        send, outcome = super().requests(plan)

        def outcome_with_start(i: int, res):
            clean, kept, marks = outcome(i, res)
            if kept is not None:  # where its join started: 0, or the position of the snapshot it restored
                kept["prefix_tokens"] = int((getattr(res, "meta", None) or {}).get("prefix_tokens", 0))
            return clean, kept, marks

        return send, outcome_with_start

    def sample(self, plan, seconds: float) -> List[int]:
        """Of the requests the window is sure to reach: ``COLD`` that bring a
        document and ``WARM`` that ask a live one, drawn from the seed, the
        heaviest of the latter among them."""
        n = max(min(int(float(self.traffic["min_rps"]) * seconds), plan.n), 1)
        rng = np.random.default_rng(np.random.SeedSequence([int(self.seed), 41]))
        cold, warm = np.flatnonzero(plan.fresh[:n]), np.flatnonzero(~plan.fresh[:n])
        weight = plan.prompt_tokens + plan.budgets
        pick = set(rng.choice(cold, size=min(COLD, cold.size), replace=False).tolist())
        pick |= set(rng.choice(warm, size=min(WARM - 1, warm.size), replace=False).tolist())
        if warm.size:
            pick.add(int(warm[np.argmax(weight[warm])]))
        return sorted(pick)

    # -- the program's state ----------------------------------------------------
    def readings(self, before: Dict[str, Any], after: Dict[str, Any]):
        ctx, extras = super().readings(before, after)
        pool = {k: after["pool"][k] - before["pool"].get(k, 0) for k in after["pool"]}
        Ly = self.system.arch["num_hidden_layers"]
        hybrid: Dict[str, float] = {
            "state_bytes_per_slot": float(after["state_bytes_per_slot"]),
            "prefix_state_tier_bytes": float(after["prefix_state_tier_bytes"]),
        }
        if pool["state_restored_tokens"] + pool["tokens_prefill"]:  # of the prompts' tokens, those a restored snapshot stood for (0 with no tier)
            hybrid["prefix_state_reused_share"] = 100.0 * pool["state_restored_tokens"] / (pool["state_restored_tokens"] + pool["tokens_prefill"])
        pairs = pool["expert_tokens_prefill"] + pool["expert_tokens_decode"]
        if pairs:
            hybrid["held_pairs_share"] = 100.0 * (pool["expert_pairs_held_prefill"] + pool["expert_pairs_held_decode"]) / pairs
        if pool.get("steps"):
            hybrid["held_experts_touched_per_step"] = pool["experts_touched_decode"] / (pool["steps"] * Ly)
        for start in ("warm", "cold"):
            count = after["join_hist"][start][0] - before["join_hist"][start][0]
            if count:
                hybrid[f"join_{start}_ms"] = 1e3 * (after["join_hist"][start][1] - before["join_hist"][start][1]) / count
                extras[f"join_{start}_n"] = count
        ctx["hybrid"] = hybrid
        # what ``moe_ffn_roofline``'s reader asks for, of the held experts
        ctx["moe"] = {"experts_touched_per_step": hybrid.get("held_experts_touched_per_step")}
        for phase in ("prefill", "step"):
            count, seconds = ctx["hist"]("pathway_generator_phase_seconds", phase=phase)
            if count:
                extras[f"{phase}_ms"], extras[f"{phase}_n"] = 1e3 * seconds / count, count
        extras["hybrid"] = hybrid
        return ctx, extras

    def needed_work(self, plan, window, a: float, b: float) -> Dict[str, Any]:
        return flops.needed_work(self.system.arch, plan, self.system.block, window, a, b)

    # -- correct ----------------------------------------------------------------
    def _score(self, window, sample: Sequence[int], **how):
        ref = Reference(self.system.arch, self.system.params, **how)
        kept = [window.kept[i] for i in sample]
        return ref.score([k["prompt_ids"] + k["token_ids"] for k in kept], [len(k["prompt_ids"]) for k in kept],
                         restored=[k["prefix_tokens"] for k in kept])[0]

    # the generation kind's numbers and ``first_logit_err_p50``, as the sparse-expert kind has them
    _numbers = staticmethod(_SparseServed._numbers)

    def compare(self, plan, window, sample: Sequence[int]) -> Dict[str, float]:
        short = sum(1 for i in np.flatnonzero(window.ok) if int(window.marks["tokens"][i]) != int(plan.budgets[i]))
        numbers: Dict[str, float] = {"tokens_short": float(short)}
        if sample:
            starts = [window.kept[i]["prefix_tokens"] for i in sample]
            numbers["sample_warm"], numbers["sample_cold"] = float(sum(p > 0 for p in starts)), float(sum(p == 0 for p in starts))
            numbers["sample_warm_short"] = max(0.0, WARM_LEAST - numbers["sample_warm"])
            numbers["sample_cold_short"] = max(0.0, COLD_LEAST - numbers["sample_cold"])
            self._reference_logits = self._score(window, sample)
            numbers.update(self._numbers([window.kept[i] for i in sample], self._reference_logits))
        return numbers

    def control(self, plan, window, sample: Sequence[int], precision: str) -> Dict[str, float]:
        """The reference in the program's place, judged like the program: at a
        precision below the configuration's (``fp8``), or with a planted fault
        of the new path: ``no_decay`` (the delta rule without its gate) or
        ``early_snapshot`` (where a request's join restored a snapshot, the
        state of one block earlier in its place)."""
        how = {"fault": precision} if precision in FAULTS else {"precision": precision}
        logits = self._score(window, sample, **how)
        records = [
            {**check.record_of(lg, window.kept[i]["token_ids"], len(window.kept[i]["top_ids"][0])),
             "token_ids": window.kept[i]["token_ids"]}
            for i, lg in zip(sample, logits)
        ]
        return self._numbers(records, self._reference_logits)

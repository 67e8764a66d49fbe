"""The parallel-block generation kind: a decoder-only language model whose
layers feed one LayerNorm to attention and to sigmoid-routed experts side by
side, of which this chip holds a range beside shared experts every chip
computes, behind the continuous decode engine, under a closed loop of callers
who ask several questions of the same long documents.

The plan (sessions over documents) and the sample of warm and cold joins are
the hybrid kind's, the comparison the generation kind's (``check.py``), and
so is every method of their ``Served`` that does not change; this kind's own
are ``system.py`` (the only module that imports the program), ``weights.py``
(the held share only), ``flops.py`` and ``reference.py``.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Sequence

import numpy as np

from ..generation import check
from ..generation_hybrid import COLD_LEAST, WARM_LEAST, Served as _HybridServed
from . import flops
from .reference import FAULTS, Reference
from .system import System


def _token_gaps(record: Dict[str, Any], reference: np.ndarray) -> np.ndarray:
    """Each position's gap, as ``check.compare`` reads a request's first one."""
    ref = np.asarray(reference, np.float32)
    keys = ("logit", "lse", "top_ids", "top_logits", "token_ids", "choice")
    return np.array([
        check.compare([{k: np.asarray(record[k])[j : j + 1] for k in keys}], [ref[j : j + 1]])["first_logit_err"]
        for j in range(ref.shape[0])
    ])


class Served(_HybridServed):
    """One parallel-block generator deployment under one mix of document sessions."""

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any], seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.system = System(config, seed)
        self._reference_logits: List[np.ndarray] = []
        self._annotate = None

    def prepare(self, plan) -> float:
        """The hybrid kind's warm-up, with one more split point: a request
        that brings its document joins behind the instruction's cached block
        (a cache of rows alone can start a join at any block), so that join
        starts at ``block`` where the hybrid family's starts at 0."""
        t0 = time.monotonic()
        dec, block = self.system.decoder, self.system.block
        shared = int(self.traffic["instruction_tokens"])
        lo, hi = (shared + int(n) for n in self.traffic["document_tokens"])
        splits = sorted({0} | ({block} if block and shared >= block else set()) | {flops.split_point(n, block) for n in (lo, hi)})
        dec.warm((lo + int(self.traffic["question_tokens"][0]), hi + int(self.traffic["question_tokens"][1])), tuple(splits))
        for a in range(0, len(plan.setup), dec.slots):
            tickets = [dec.submit(text, max_new_tokens=budget, temperature=0.0) for text, budget in plan.setup[a : a + dec.slots]]
            for t in tickets:
                t()
        return time.monotonic() - t0

    def compare(self, plan, window, sample: Sequence[int]) -> Dict[str, float]:
        """The hybrid kind's numbers, with a join counted warm where it
        started inside the document (behind more than the instruction's
        block) and cold where it computed the document from its first token."""
        short = sum(1 for i in np.flatnonzero(window.ok) if int(window.marks["tokens"][i]) != int(plan.budgets[i]))
        numbers: Dict[str, float] = {"tokens_short": float(short)}
        if sample:
            shared = int(self.traffic["instruction_tokens"])
            starts = [window.kept[i]["prefix_tokens"] for i in sample]
            numbers["sample_warm"], numbers["sample_cold"] = float(sum(p > shared for p in starts)), float(sum(p <= shared for p in starts))
            numbers["sample_warm_short"] = max(0.0, WARM_LEAST - numbers["sample_warm"])
            numbers["sample_cold_short"] = max(0.0, COLD_LEAST - numbers["sample_cold"])
            self._reference_logits = self._score(window, sample)
            numbers.update(self._numbers([window.kept[i] for i in sample], self._reference_logits))
        return numbers

    @staticmethod
    def _numbers(records, reference_logits) -> Dict[str, float]:
        """The sparse-expert kind's numbers and ``logit_err_p50``: per sampled
        request the median of its decode tokens' gaps, the widest of those
        over the sample.  A flipped choice of expert moves one token's logits
        by more than rounding does, and ``logit_err`` (the widest token)
        reads that; a lane decoded from a wrong state or a wrong token is
        off at most of its tokens, and this reads that."""
        numbers = _HybridServed._numbers(records, reference_logits)
        medians = [float(np.median(gaps[1:])) for gaps in map(_token_gaps, records, reference_logits) if gaps.size > 1]
        numbers["logit_err_p50"] = max(medians, default=0.0)
        return numbers

    def window_summary(self, plan, window) -> Dict[str, float]:
        """The generation kind's summary, and how varied the sampled answers
        are: the share of distinct tokens in an answer, and of tokens that
        repeat the one before (greedy decoding of random weights can settle
        on one token a lane, and then a step touches few experts)."""
        out = super().window_summary(plan, window)
        answers = [np.asarray(k["token_ids"]) for k in window.kept.values() if k and len(k["token_ids"]) > 1]
        if answers:
            out["answer_distinct_share"] = float(np.mean([len(set(t.tolist())) / len(t) for t in answers]))
            out["answer_repeat_share"] = float(np.mean([np.mean(t[1:] == t[:-1]) for t in answers]))
        return out

    def readings(self, before: Dict[str, Any], after: Dict[str, Any]):
        """The hybrid kind's readings (joins by start, the held experts a step
        touches, the held pairs' share) and the rows the expert layers carried
        through sort, gather and products over the pairs the routers made."""
        ctx, extras = super().readings(before, after)
        pool = {k: after["pool"][k] - before["pool"].get(k, 0) for k in after["pool"]}
        parallel: Dict[str, float] = {}
        touched = ctx["hybrid"].get("held_experts_touched_per_step")
        if touched is not None:
            parallel["held_experts_touched_per_step"] = touched
        routed = pool["expert_tokens_prefill"] + pool["expert_tokens_decode"]
        if routed and "expert_pairs_carried_prefill" in pool:
            parallel["pairs_carried_share"] = (pool["expert_pairs_carried_prefill"] + pool["expert_pairs_carried_decode"]) / routed
        ctx["parallel"] = extras["parallel"] = parallel
        return ctx, extras

    def needed_work(self, plan, window, a: float, b: float) -> Dict[str, Any]:
        return flops.needed_work(self.system.arch, plan, self.system.block, window, a, b)

    # -- correct ----------------------------------------------------------------
    def _score(self, window, sample: Sequence[int], **how):
        ref = Reference(self.system.arch, self.system.params, **how)
        kept = [window.kept[i] for i in sample]
        return ref.score([k["prompt_ids"] + k["token_ids"] for k in kept], [len(k["prompt_ids"]) for k in kept])[0]

    def control(self, plan, window, sample: Sequence[int], precision: str) -> Dict[str, float]:
        """The reference in the program's place, judged like the program: at a
        precision below the configuration's (``fp8``), or with a planted fault
        of the block: ``sequential_block``, ``rotate_half`` or
        ``window_as_full`` (``reference.py``)."""
        how = {"fault": precision} if precision in FAULTS else {"precision": precision}
        logits = self._score(window, sample, **how)
        records = [
            {**check.record_of(lg, window.kept[i]["token_ids"], len(window.kept[i]["top_ids"][0])),
             "token_ids": window.kept[i]["token_ids"]}
            for i, lg in zip(sample, logits)
        ]
        return self._numbers(records, self._reference_logits)

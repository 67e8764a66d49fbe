"""The sparse-expert generation kind: a decoder-only language model with
routed experts, grouped-query heads and a cache of two kinds of rows, behind
the continuous decode engine, under a closed loop of callers whose prompts
pass the sliding window.

The plan of a window's work and the comparison are the generation kind's
(``kinds/generation/plan.py``, ``check.py``), and so is every method of its
``Served`` that does not change; this kind's own are ``system.py`` (the only
module that imports the program), ``weights.py``, ``flops.py`` and
``reference.py``.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List, Sequence

import numpy as np

from ..generation import Served as _Served, check, plan as planning
from . import flops
from .reference import FAULTS, Reference
from .system import System

SAMPLE = 12  # requests the reference runs again: the heaviest among them


class Served(_Served):
    """One sparse-expert generator deployment under one mix."""

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any], seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.system = System(config, seed)
        self._reference_logits: List[np.ndarray] = []
        self._reference_choices: List[np.ndarray] = []
        self._annotate = None

    def prepare(self, plan) -> float:
        """Every program the plan can reach, run once (``ContinuousDecoder.warm``:
        each join bucket under the token budget, cold and behind the shared
        instruction's cached block, and the step chunk); then two bursts of
        real requests as wide as the slot pool, the second behind what the
        first left in the prefix tier."""
        t0 = time.monotonic()
        dec, block = self.system.decoder, self.system.block
        lo, hi = self.traffic["prompt_tokens"]
        shared = int(self.traffic["instruction_tokens"])
        dec.warm((int(lo), int(hi)), (0,) + ((block,) if block and shared >= block else ()))
        seconds = math.ceil(2 * dec.slots / float(self.traffic["max_rps"]))
        burst = planning.plan(self.traffic, self.seed, seconds, label="p")
        for rep in range(2):
            tickets = [
                dec.submit(burst.texts[i], max_new_tokens=int(burst.budgets[i]), temperature=0.0)
                for i in range(rep * dec.slots, (rep + 1) * dec.slots)
            ]
            for t in tickets:
                t()
        return time.monotonic() - t0

    def sample(self, plan, seconds: float) -> List[int]:
        expected = int(float(self.traffic["min_rps"]) * seconds)
        return check.sample_for(expected, plan.prompt_tokens + plan.budgets, self.seed, SAMPLE - 1)

    def readings(self, before: Dict[str, Any], after: Dict[str, Any]):
        ctx, extras = super().readings(before, after)
        pool = {k: after["pool"][k] - before["pool"].get(k, 0) for k in after["pool"]}
        arch = self.system.arch
        Ly, E = arch["num_hidden_layers"], arch["moe_num_primary_experts"]
        moe: Dict[str, float] = {}
        if pool.get("steps"):
            moe["experts_touched_per_step"] = pool["experts_touched_decode"] / (pool["steps"] * Ly)
        if pool.get("expert_tokens_prefill"):
            # over (join, layer): the busiest expert's tokens against the mean expert's
            moe["expert_load_max_over_mean"] = pool["expert_load_max_prefill"] / (pool["expert_tokens_prefill"] / E)
        if pool.get("joins"):
            moe["join_tokens_mean"] = pool["join_tokens"] / pool["joins"]
        rows = after.get("kv_rows") or {}
        if rows:
            moe["kv_pool_bytes_per_slot"] = float(sum(rows.values()) * flops.kv_bytes_per_row(arch))
        ctx["moe"] = moe
        # for the record (PERF.md section 5), not a metric: a join's and a step chunk's mean round trip
        for phase in ("prefill", "step"):
            count, seconds = ctx["hist"]("pathway_generator_phase_seconds", phase=phase)
            if count:
                extras[f"{phase}_ms"], extras[f"{phase}_n"] = 1e3 * seconds / count, count
        return ctx, extras

    def state(self) -> Dict[str, Any]:
        out = self.system.program_state()
        out["kv_rows"] = {
            m[2]["kind"]: m[3] for m in self.system.decoder.observe_metrics() if m[1] == "pathway_generator_kv_rows"
        }
        return out

    def needed_work(self, plan, window, a: float, b: float) -> Dict[str, Any]:
        return flops.needed_work(self.system.arch, plan.prompt_tokens, window, a, b)

    # -- correct ----------------------------------------------------------------
    def _score(self, window, sample: Sequence[int], **how):
        ref = Reference(self.system.arch, self.system.params, **how)
        kept = [window.kept[i] for i in sample]
        return ref.score([k["prompt_ids"] + k["token_ids"] for k in kept], [len(k["prompt_ids"]) for k in kept])

    @staticmethod
    def _numbers(records, reference_logits) -> Dict[str, float]:
        """The generation kind's numbers and, beside its ``first_logit_err``
        (the widest first-token gap of the sample: one position a request,
        where a single flipped choice of expert reads as much as a control's
        typical token), ``first_logit_err_p50``: the median over the sample's
        requests of the same gap, which a flip at one request cannot move."""
        numbers = check.compare(records, reference_logits)
        firsts = [check.compare([rec], [ref])["first_logit_err"] for rec, ref in zip(records, reference_logits)]
        numbers["first_logit_err_p50"] = float(np.median(firsts))
        return numbers

    def compare(self, plan, window, sample: Sequence[int]) -> Dict[str, float]:
        short = sum(1 for i in np.flatnonzero(window.ok) if int(window.marks["tokens"][i]) != int(plan.budgets[i]))
        numbers: Dict[str, float] = {"tokens_short": float(short)}
        if sample:
            self._reference_logits, self._reference_choices = self._score(window, sample)
            numbers.update(self._numbers([window.kept[i] for i in sample], self._reference_logits))
        return numbers

    def control(self, plan, window, sample: Sequence[int], precision: str) -> Dict[str, float]:
        """The reference in the program's place, judged like the program: at a
        precision below the configuration's (``fp8``), or with the planted fault
        (``window_as_full``: window layers attend every earlier key).
        ``router_bf16`` is a reading, not a control: only the router's input
        rounded to bfloat16, with the share of (token, layer) choices of
        experts it changes beside the logit gaps that makes."""
        how = {"fault": precision} if precision in FAULTS else {"router_input": "bf16"} if precision == "router_bf16" else {"precision": precision}
        logits, choices = self._score(window, sample, **how)
        records = [
            {**check.record_of(lg, window.kept[i]["token_ids"], len(window.kept[i]["top_ids"][0])),
             "token_ids": window.kept[i]["token_ids"]}
            for i, lg in zip(sample, logits)
        ]
        numbers = self._numbers(records, self._reference_logits)
        changed = [np.any(np.sort(a, axis=-1) != np.sort(b, axis=-1), axis=-1) for a, b in zip(choices, self._reference_choices)]
        numbers["choices_changed_share"] = float(np.concatenate([c.reshape(-1) for c in changed]).mean())
        return numbers

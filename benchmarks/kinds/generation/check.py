"""What the generation kind compares, on the scale of the reference's logits.

The check reads logits, never tokens: with random weights the largest logit
changes on rounding.  For every sampled request the reference runs one full
forward over the prompt's ids as prefilled plus the ids served, and gives
the logits each served token was chosen from.  Compared:

- ``first_logit_err`` (the prefill path) and ``logit_err`` (decode through
  the cache): the widest gap between a number the program read off its own
  logits (the chosen token's logit, the log-sum-exp, each of the top ids'
  logits) and the reference's at the same position and id;
- ``top1_regret_p50``: the reference's best logit minus its logit of the
  token the system chose, median over the sample's tokens;
- ``tokens_short``: requests whose answer is not exactly its budget.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np


def sample_for(n_expected: int, weight: np.ndarray, seed: int, size: int) -> List[int]:
    """``size`` of the first ``n_expected`` requests, drawn from the seed,
    the heaviest (longest prompt plus budget) among them."""
    n = max(min(int(n_expected), len(weight)), 1)
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 41]))
    pick = set(rng.choice(n, size=min(size, n), replace=False).tolist())
    pick.add(int(np.argmax(weight[:n])))
    return sorted(pick)


def _logsumexp(x: np.ndarray) -> np.ndarray:
    top = x.max(axis=-1)
    return top + np.log(np.exp(x - top[..., None]).sum(axis=-1))


def record_of(logits: np.ndarray, tokens: Sequence[int], top: int) -> Dict[str, Any]:
    """What a system that computed ``logits [n, V]`` would report of them
    (the control puts the reference at a lower precision in the program's
    place through this): it reads the served tokens' logits, and chooses
    its own best."""
    logits = np.asarray(logits, np.float32)
    tokens = np.asarray(tokens)
    ids = np.argsort(-logits, axis=-1)[:, :top]
    return {
        "logit": logits[np.arange(len(tokens)), tokens],
        "lse": _logsumexp(logits),
        "top_ids": ids,
        "top_logits": np.take_along_axis(logits, ids, axis=-1),
        "choice": ids[:, 0],
    }


def compare(records: Sequence[Dict[str, Any]], reference_logits: Sequence[np.ndarray]) -> Dict[str, float]:
    first, later, regret = 0.0, 0.0, []
    for rec, ref in zip(records, reference_logits):
        ref = np.asarray(ref, np.float32)
        rows = np.arange(ref.shape[0])
        gap = np.maximum.reduce([
            np.abs(np.asarray(rec["logit"]) - ref[rows, np.asarray(rec["token_ids"])]),
            np.abs(np.asarray(rec["lse"]) - _logsumexp(ref)),
            np.abs(np.asarray(rec["top_logits"]) - np.take_along_axis(ref, np.asarray(rec["top_ids"]), axis=-1)).max(axis=-1),
        ])
        first = max(first, float(gap[0]))
        later = max(later, float(gap[1:].max()) if gap.size > 1 else 0.0)
        regret.append(ref.max(axis=-1) - ref[rows, np.asarray(rec["choice"])])
    flat = np.concatenate(regret) if regret else np.zeros(1)
    return {
        "first_logit_err": first,
        "logit_err": later,
        "top1_regret_p50": float(np.median(flat)),
        "top1_regret_max": float(flat.max()),
        "top1_disagree_share": float((flat > 0).mean()),
    }

"""A window's work for a generator, from a mix's file and the seed.

A closed loop of ``callers`` workers.  A request is a prompt and an answer
budget: the prompt opens with the mix's fixed instruction (with the leading
``[CLS]`` exactly ``instruction_tokens`` tokens: what a RAG template shares
between requests) and goes on with retrieved chunks and a question that are
this request's own from the first word on.  Every seed gets the same
multiset of (prompt tokens, answer budget) pairs, both uniform over the
mix's ranges, in another order: the order differs inside consecutive blocks
of ``shuffle_block`` requests, so any stretch of a window holds nearly the
same pairs whatever the seed.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

MARKS = {"first_token": "time", "tokens": "count"}
_WORD_RE = re.compile(r"[\w']+|[^\w\s]")  # the hashing word tokenizer's split: one token a word
_ENVELOPE = 2  # [CLS] ... [SEP]


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), int(stream)]))


def seed_words(seed: int, n: int) -> List[int]:
    """``n`` independent 31-bit words from any whole-number seed."""
    ss = np.random.SeedSequence(int(seed))
    return [int(w) & 0x7FFFFFFF for w in ss.generate_state(n, dtype=np.uint32)]


@dataclass
class Plan:
    loop: str
    n: int
    due: Optional[np.ndarray]
    callers: int
    waiters: int
    marks: Dict[str, str]
    texts: List[str]
    prompt_tokens: np.ndarray  # as the program's tokenizer will count them
    budgets: np.ndarray


def pairs(traffic: Dict[str, Any], n: int) -> np.ndarray:
    """The multiset, in its base order: both coordinates walk their range
    with a stride coprime to its size, so every stretch is spread evenly."""
    (p_lo, p_hi), (b_lo, b_hi) = traffic["prompt_tokens"], traffic["max_new_tokens"]
    j = np.arange(n)

    def walk(lo, hi, stride):
        size = hi - lo + 1
        while math.gcd(stride, size) != 1:
            stride += 1
        return lo + (j * stride) % size

    return np.stack([walk(p_lo, p_hi, 73), walk(b_lo, b_hi, 5)], axis=1)


def plan(traffic: Dict[str, Any], seed: int, seconds: float, label: str = "w") -> Plan:
    if traffic["loop"] != "closed":
        raise SystemExit("the generation kind's mixes are closed loops")
    callers = int(traffic["callers"])
    n = callers + int(math.ceil(float(traffic["max_rps"]) * seconds))
    order = rng_for(seed, 31)
    block = int(traffic.get("shuffle_block", 32))
    index = np.concatenate([a + order.permutation(min(block, n - a)) for a in range(0, n, block)])
    chosen = pairs(traffic, n)[index]
    instruction = _WORD_RE.findall(traffic["instruction"])
    if len(instruction) + 1 != int(traffic["instruction_tokens"]):
        raise SystemExit(f"the mix's instruction is {len(instruction) + 1} tokens with [CLS], not {traffic['instruction_tokens']}")
    words = rng_for(seed, 37)
    head = " ".join(instruction)
    texts = []
    for i, (n_tokens, _) in enumerate(chosen):
        own = int(n_tokens) - _ENVELOPE - len(instruction)
        # the first word is this request's alone; the rest are drawn from a vocabulary of 2**20 words
        body = [f"{label}{seed}r{i}"] + [f"t{w:x}" for w in words.integers(0, 1 << 20, own - 1)]
        texts.append(head + " " + " ".join(body))
    return Plan("closed", n, None, callers, 0, dict(MARKS), texts, chosen[:, 0].copy(), chosen[:, 1].copy())

"""A window's work for a generator that answers questions about documents.

A closed loop of ``callers`` workers.  A request is a prompt and an answer
budget; the prompt is the mix's fixed instruction (with the leading ``[CLS]``
exactly ``instruction_tokens`` tokens), then a DOCUMENT, then a question that
is this request's own from its first word on.  Documents are asked about more
than once: ``live_documents`` of them are live at any time; of every
``fresh_every`` consecutive requests one brings a document no request has
carried before, which takes the place of the least recently asked live one;
the others ask the least recently asked live document, so the live set is
walked evenly: a queue, served one document a request.  A document that came
at request ``r`` is next in turn at ``r + live_documents``, and is asked
again or retired there.  Were the newcomer's place among its ``fresh_every``
requests fixed, a queue of 24 under a cadence of 8 would retire the same
three places for ever (every newcomer asked once, 21 documents immortal); so
the place walks the block with a stride coprime to it (``FRESH_STRIDE``), and
every document is then asked exactly ``fresh_every`` times, ``live_documents``
requests apart, and retired at its next turn.  The schedule is the same for
every seed.

Every seed gets the same documents' lengths (document ``d`` of a seed is as
long as document ``d`` of any other: a walk over ``document_tokens`` with a
stride coprime to its size) and the same multiset of (question tokens, answer
budget) pairs, both walked the same way, in another order: the order differs
inside consecutive blocks of ``shuffle_block`` requests, as the generation
kind's plan has it.  The words differ with the seed too.

``setup`` holds one question for each of the first ``live_documents``
documents, asked once before the window opens, so that it opens on a warm
tier; a rehearsal asks those documents again and brings none.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from ..generation.plan import MARKS, _ENVELOPE, _WORD_RE, rng_for

_SEP = _ENVELOPE - 1  # the closing [SEP] is counted with the question
FRESH_STRIDE = 5  # the newcomer's place in block b of ``fresh_every`` requests is ``b * FRESH_STRIDE mod fresh_every``


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
    fresh: np.ndarray  # bool: the request brings a document of its own
    document: np.ndarray  # which document it asks about
    shared_tokens: np.ndarray  # tokens of the prompt an earlier request carried too (instruction + document), 0 if fresh
    setup: List[Any] = field(default_factory=list)  # (text, budget): one question a live document, for the warm-up


def _walk(lo: int, hi: int, stride: int, j: np.ndarray) -> np.ndarray:
    size = hi - lo + 1
    while math.gcd(stride, size) != 1:
        stride += 1
    return lo + (j * stride) % size


def plan(traffic: Dict[str, Any], seed: int, seconds: float, label: str = "w", rehearsal: bool = False) -> Plan:
    if traffic["loop"] != "closed":
        raise SystemExit("the hybrid generation kind's mixes are closed loops")
    callers, live, every = int(traffic["callers"]), int(traffic["live_documents"]), int(traffic["fresh_every"])
    n = callers + int(math.ceil(float(traffic["max_rps"]) * seconds))
    (d_lo, d_hi), (q_lo, q_hi), (b_lo, b_hi) = traffic["document_tokens"], traffic["question_tokens"], traffic["max_new_tokens"]
    instruction = _WORD_RE.findall(traffic["instruction"])
    if len(instruction) + 1 != int(traffic["instruction_tokens"]):
        raise SystemExit(f"the mix's instruction is {len(instruction) + 1} tokens with [CLS], not {traffic['instruction_tokens']}")
    head = " ".join(instruction)

    # who asks what: a queue of the live documents, least recently asked first
    if math.gcd(FRESH_STRIDE, every) != 1:
        raise SystemExit(f"the mix's fresh_every = {every} shares a factor with the stride {FRESH_STRIDE} its newcomers' places walk by")
    queue, nxt = list(range(live)), live
    document, fresh = np.zeros(n, np.int64), np.zeros(n, bool)
    for r in range(n):
        front = queue.pop(0)  # asked again, or retired by the document that takes its place
        if not rehearsal and r % every == (r // every) * FRESH_STRIDE % every:
            front, fresh[r], nxt = nxt, True, nxt + 1
        document[r] = front
        queue.append(front)

    # the questions' lengths and budgets: one multiset, reordered inside blocks
    order = rng_for(seed, 31)
    block = int(traffic.get("shuffle_block", 32))
    index = np.concatenate([a + order.permutation(min(block, n - a)) for a in range(0, n, block)])
    q_tokens, budgets = _walk(q_lo, q_hi, 29, index), _walk(b_lo, b_hi, 5, index)
    d_tokens = _walk(d_lo, d_hi, 577, np.arange(nxt))  # by document, the same for every seed

    words = rng_for(seed, 37)
    # a document's first word is its own; the rest are drawn from a vocabulary of 2**20 words
    bodies = [" ".join([f"d{seed}x{d}"] + [f"t{w:x}" for w in words.integers(0, 1 << 20, int(d_tokens[d]) - 1)]) for d in range(nxt)]

    def ask(tag: str, d: int, q: int) -> str:
        own = [f"{tag}{seed}r"] + [f"t{w:x}" for w in words.integers(0, 1 << 20, q - _SEP - 1)]
        return f"{head} {bodies[d]} {' '.join(own)}"

    setup = [] if rehearsal else [(ask(f"s{d}", d, int(q_lo)), int(b_lo)) for d in range(live)]
    texts = [ask(f"{label}{i}", int(document[i]), int(q_tokens[i])) for i in range(n)]
    shared = np.where(fresh, 0, len(instruction) + 1 + d_tokens[document])
    return Plan("closed", n, None, callers, 0, dict(MARKS), texts, len(instruction) + 1 + d_tokens[document] + q_tokens,
                budgets.copy(), fresh, document, shared, setup)

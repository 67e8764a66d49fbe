"""The comparison that decides ``correct``.

It compares what the timed path answered in the window, for a sample of the
window's requests drawn from the seed (the longest among them, and every
probe of a committed document), with the plain reference run once over the
same texts.  Every number compared is a gap on the reference's own scale:

- ``score_err``: the widest gap between a served score and the reference's
  score of the same (query, document) pair;
- ``regret_p50``: per request, the widest gap by which the reference's
  score of the j-th served document lies below the reference's j-th best
  document; the median over the sample (the mean and the maximum are moved
  by the IVF's own rare probe misses, which no precision explains, and are
  recorded, not compared);
- with a rerank stage, ``rerank_err`` and ``rerank_regret_p50``: the same
  two on the cross-encoder's logits over the reference's own shortlist
  (``shortlist_regret_*``, how far a served document lies below the edge
  of the reference's stage-1 shortlist, is recorded);
- exact counts, limit 0: requests that failed, were shed, degraded or came
  back short; probes whose committed document was not served first; commits
  that never became visible.

The limits sit in the configuration file, with the readings they were set
from in PERF.md.
"""

from __future__ import annotations

import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import corpus
from .reference import Reference, exact_search


def choose_sample(n: int, texts: Sequence[str], seed: int, size: int, always: Sequence[int] = ()) -> List[int]:
    """``size`` request indices drawn from the seed, the longest text and
    every index in ``always`` among them."""
    rng = corpus.rng_for(seed, 19)
    if n <= 0:
        return []
    pick = set(int(i) for i in rng.choice(n, size=min(size, n), replace=False))
    pick.add(int(np.argmax([len(t) for t in texts[:n]])))
    pick.update(int(i) for i in always if i < n)
    return sorted(pick)


def _regret(ref_top: np.ndarray, served_ref: np.ndarray) -> float:
    """Widest gap, rank by rank, of the served documents' reference scores
    below the reference's best ones (0 where the served set is as good)."""
    got = np.sort(served_ref)[::-1]
    j = min(len(got), len(ref_top))
    if j == 0:
        return float(ref_top[0] + 1.0) if len(ref_top) else 0.0
    return float(max(np.max(ref_top[:j] - got[:j]), 0.0))


def compare(
    config: Dict[str, Any],
    ref: Reference,
    space,
    doc_text,
    queries: Sequence[str],  # the sampled requests' texts
    served: Sequence[Sequence[Tuple[int, float]]],  # what the window answered
    sent_s: Optional[np.ndarray] = None,  # when each sampled request was sent
    live_rows: Sequence[Tuple[int, str]] = (),
    live_visible_s: Optional[np.ndarray] = None,  # per live row; -inf: before the window
    probe_keys: Optional[Sequence[int]] = None,  # per sampled request, -1: no probe
) -> Dict[str, float]:
    """The numbers compared, from served answers and the reference."""
    k = int(config["serve"]["k"])
    rerank = bool(config.get("cross_encoder"))
    wide = int(config["serve"].get("candidates") or k) if rerank else k
    S = len(queries)
    q = ref.embed(queries)
    live_keys = live_vecs = live_ok = None
    if live_rows:
        live_keys = np.asarray([key for key, _ in live_rows], np.int64)
        live_vecs = ref.embed([text for _, text in live_rows])
        live_ok = live_visible_s[None, :] <= np.asarray(sent_s)[:, None]
    want = [[key for key, _ in row] for row in served]
    top_s, top_k, want_s = exact_search(space, q, wide, want, live_keys, live_vecs, live_ok)
    out: Dict[str, float] = {}
    short = sum(1 for row in served if len(row) != k)
    unknown = sum(int(np.isnan(w).sum()) for w in want_s)
    out["short_or_unknown"] = float(short + unknown)
    want_s = [np.nan_to_num(w, nan=-1.0) for w in want_s]
    if probe_keys is not None:
        judged = [
            (row[0][0] if row else None, key)
            for row, key in zip(served, probe_keys)
            if key >= 0
        ]
        out["probes_judged"] = float(len(judged))
        out["probe_missed"] = float(sum(1 for got, key in judged if got != key))
    if not rerank:
        out["score_err"] = float(max(
            (np.max(np.abs(np.asarray([s for _, s in row], np.float32) - w)) if len(row) else 0.0)
            for row, w in zip(served, want_s)
        )) if S else 0.0
        regrets = [_regret(top_s[i, :k], want_s[i]) for i in range(S)] or [0.0]
        out["regret_mean"] = float(np.mean(regrets))
        out["regret_p50"] = float(np.percentile(regrets, 50))
        out["regret_p90"] = float(np.percentile(regrets, 90))
        out["regret_max"] = float(np.max(regrets))
        out["regret_zero_share"] = float(np.mean(np.asarray(regrets) <= 0.0))
        return out
    # rerank: stage 1 is judged by the shortlist's edge, stage 2 by the logits
    edge = top_s[:, wide - 1]
    short_r = [max(float(np.max(edge[i] - want_s[i])), 0.0) if len(want_s[i]) else 1.0 for i in range(S)]
    out["shortlist_regret_mean"] = float(np.mean(short_r))
    out["shortlist_regret_p90"] = float(np.percentile(short_r, 90))
    pairs: List[Tuple[str, str]] = []
    for i in range(S):
        pairs += [(queries[i], doc_text(int(key))) for key in top_k[i]]
        pairs += [(queries[i], doc_text(int(key))) for key in want[i]]
    logits = ref.score_pairs(pairs)
    errs, regrets, pos = [], [], 0
    for i in range(S):
        cand = logits[pos : pos + wide]
        pos += wide
        mine = logits[pos : pos + len(want[i])]
        pos += len(want[i])
        got = np.asarray([s for _, s in served[i]], np.float32)
        errs.append(float(np.max(np.abs(got - mine))) if len(mine) else 0.0)
        regrets.append(_regret(np.sort(cand)[::-1][:k], mine))
        order_ok = bool(np.all(np.diff(got) <= 0))
        out["misordered"] = out.get("misordered", 0.0) + (0.0 if order_ok else 1.0)
    out["rerank_err"] = float(max(errs)) if errs else 0.0
    out["rerank_regret_mean"] = float(np.mean(regrets)) if regrets else 0.0
    out["rerank_regret_p50"] = float(np.percentile(regrets, 50)) if regrets else 0.0
    out["rerank_err_mean"] = float(np.mean(errs)) if errs else 0.0
    out["rerank_regret_zero_share"] = float(np.mean(np.asarray(regrets) <= 0.0)) if regrets else 0.0
    return out


def control_answers(
    config: Dict[str, Any], control: Reference, space, doc_text, queries: Sequence[str],
) -> List[List[Tuple[int, float]]]:
    """The control put in the program's place: the same path in the
    precision below (exact search, so that only the precision differs)."""
    k = int(config["serve"]["k"])
    rerank = bool(config.get("cross_encoder"))
    wide = int(config["serve"].get("candidates") or k) if rerank else k
    q = control.embed(queries)
    top_s, top_k, _ = exact_search(space, q, wide, [[] for _ in queries])
    if not rerank:
        return [list(zip(top_k[i].tolist(), top_s[i].tolist())) for i in range(len(queries))]
    pairs = [(queries[i], doc_text(int(key))) for i in range(len(queries)) for key in top_k[i]]
    logits = control.score_pairs(pairs).reshape(len(queries), wide)
    out = []
    for i in range(len(queries)):
        order = np.argsort(-logits[i], kind="stable")[:k]
        out.append([(int(top_k[i][j]), float(logits[i][j])) for j in order])
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> Tuple[bool, Dict[str, List[float]]]:
    """Each number beside its limit; ``correct`` only if every limited
    number is at or under its limit (and none is missing)."""
    table: Dict[str, List[float]] = {}
    good = True
    for name, limit in limits.items():
        if name not in numbers:
            continue
        value = float(numbers[name])
        table[name] = [value, float(limit)]
        if not (value <= float(limit)):
            good = False
    return good, table


def print_table(table: Dict[str, List[float]], extra: Dict[str, float]) -> None:
    for name, (value, limit) in table.items():
        print(f"compared {name} = {value!r} limit {limit!r}", file=sys.stderr)
    for name, value in extra.items():
        print(f"recorded {name} = {value!r}", file=sys.stderr)
    sys.stderr.flush()

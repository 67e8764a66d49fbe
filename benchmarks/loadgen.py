"""The one traffic generator: a mix is a file of parameters, never code.

``plan()`` turns ``traffic/<mix>.json`` and a seed into a fixed amount of
work: the texts, the due time of every request (open loop) or the number of
callers (closed loop), and, where the mix commits documents, the commits
with their times and the probe queries that ask for them afterwards.
``run_window()`` offers that work to the system from one process: one
dispatcher thread and a fixed pool of waiters (open loop), or the callers
themselves (closed loop), and one committer.  It records, on the host's
clock, when each request was due, sent and answered.

``percentile`` and ``summarise`` are the arithmetic behind every end-to-end
metric: all requests of the window, from the due time; a request that
failed, was shed or was answered from a degraded rung counts as the
longest.
"""

from __future__ import annotations

import itertools
import math
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import corpus

POST_WINDOW_WAIT_S = 60.0


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the sample at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("percentile of no values")
    rank = max(int(math.ceil(q / 100.0 * v.size)), 1)
    return float(v[rank - 1])


def summarise(
    due: np.ndarray, done: np.ndarray, ok: np.ndarray, window_s: float
) -> Dict[str, float]:
    """End-to-end numbers of one window.  ``due``/``done`` in seconds from
    the window's start (``done`` NaN where no answer came), ``ok`` False
    for a failed, shed or degraded request."""
    lat = done - due
    clean = ok & np.isfinite(lat)
    worst = float(np.nanmax(lat)) if np.isfinite(lat).any() else POST_WINDOW_WAIT_S
    lat = np.where(clean, lat, worst)
    in_window = clean & (done <= window_s)
    return {
        "attempted": int(due.size),
        "failed": int((~clean).sum()),
        "latency_p50_ms": percentile(lat, 50) * 1e3,
        "latency_p95_ms": percentile(lat, 95) * 1e3,
        "throughput_rps": float(in_window.sum()) / float(window_s),
        "backlog_at_close": int((~(done <= window_s)).sum()),
    }


# ---------------------------------------------------------------------------
# the plan: a fixed amount of work from the seed
# ---------------------------------------------------------------------------


@dataclass
class Commit:
    at_s: float
    rows: List[Tuple[int, str]]
    probe_key: int = -1


@dataclass
class Plan:
    loop: str
    k: int
    texts: List[str]
    due: Optional[np.ndarray]  # open loop: seconds from the window's start
    callers: int
    waiters: int
    commits: List[Commit] = field(default_factory=list)
    setup_rows: List[Tuple[int, str]] = field(default_factory=list)  # committed at set-up
    tail_fill: int = 0
    probes: Dict[int, int] = field(default_factory=dict)  # request index -> commit index
    length_buckets: List[int] = field(default_factory=list)
    batch_cap: int = 64


def plan(traffic: Dict[str, Any], texts: corpus.Texts, seed: int, seconds: float,
         first_live_key: int, rate: Optional[float] = None) -> Plan:
    qspec = traffic["query_words"]
    loop = traffic["loop"]
    buckets = sorted({((w + 2 + 15) // 16) * 16 for w in range(qspec["min"], qspec["max"] + 1)})
    if loop == "open":
        rate = float(rate or traffic["rate_rps"])
        n = int(math.ceil(rate * seconds))
        rng = corpus.rng_for(seed, 17)
        gaps = corpus.exponential_gaps(n, rate)[rng.permutation(n)]
        due = np.cumsum(gaps)
        due = due[due < seconds]
        n = int(due.size)
        callers, waiters = 0, int(traffic["waiters"])
    else:
        callers = int(traffic["callers"])
        # more texts than any window can answer; the callers stop at the close
        n = int(traffic["max_rps"] * seconds)
        due, waiters = None, 0
    queries = corpus.make_queries(texts, seed, n, qspec)
    out = Plan(loop, int(traffic["k"]), queries, due, callers, waiters,
               length_buckets=buckets, batch_cap=int(traffic.get("batch_cap", 64)))
    cm = traffic.get("commits")
    if cm:
        per, period = int(cm["docs_per_commit"]), float(cm["period_s"])
        n_commits = int(math.floor((seconds - cm["phase_s"]) / period)) + 1
        n_setup = int(cm["setup_docs"])
        docs = corpus.make_live_docs(
            texts, seed, n_setup + n_commits * per, first_live_key, cm["doc_words"]
        )
        out.setup_rows, out.tail_fill = docs[:n_setup], int(cm["tail_fill_docs"])
        qmax = qspec["max"]
        taken: set = set()
        for c in range(n_commits):
            rows = docs[n_setup + c * per : n_setup + (c + 1) * per]
            commit = Commit(cm["phase_s"] + c * period, rows)
            out.commits.append(commit)
            if due is None:
                continue
            # the probe: the commit's shortest document, asked for by its own
            # text once it must be visible
            i = int(np.searchsorted(due, commit.at_s + cm["probe_delay_s"]))
            while i in taken:
                i += 1
            if i >= n:
                continue
            key, text = min(rows, key=lambda r: len(r[1].split()))
            if len(text.split()) > qmax:
                text = " ".join(text.split()[:qmax])
            taken.add(i)
            out.texts[i], out.probes[i], commit.probe_key = text, c, key
    return out


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


@dataclass
class Window:
    t0: float  # perf_counter at the window's start
    seconds: float
    due: np.ndarray
    sent: np.ndarray
    done: np.ndarray
    ok: np.ndarray
    kept: Dict[int, Any]  # request index -> rows, for the sampled requests
    commit_called: np.ndarray
    commit_visible: np.ndarray
    notes: Dict[str, Any]


def _valid(res, k: int) -> bool:
    return (
        getattr(res, "degraded", ()) == ()
        and len(res) == 1
        and len(res[0]) == k
    )


def run_window(
    serve: Callable[[Sequence[str], int], Any],
    p: Plan,
    seconds: float,
    keep: Sequence[int] = (),
    commit: Optional[Callable[[Sequence], None]] = None,
    docs_visible: Optional[Callable[[], int]] = None,
    annotate: Optional[Callable[[str], Any]] = None,
    on_tick: Optional[Callable[[float], None]] = None,
) -> Window:
    """Offer the plan's work for ``seconds`` and wait for every answer due
    in the window (a minute past the close at most)."""
    n = len(p.texts)
    due = np.full(n, np.nan) if p.due is None else p.due.copy()
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    ok = np.zeros(n, bool)
    keep_set = set(int(i) for i in keep)
    kept: Dict[int, Any] = {}
    errors: List[BaseException] = []
    k = p.k
    clock = time.perf_counter

    def one(i: int, t0: float) -> None:
        sent[i] = clock() - t0
        try:
            if annotate is not None:
                with annotate("bench.request"):
                    res = serve([p.texts[i]], k)
            else:
                res = serve([p.texts[i]], k)
            done[i] = clock() - t0
            ok[i] = _valid(res, k)
            if i in keep_set or not ok[i]:
                kept[i] = (list(res[0]) if len(res) else [], tuple(getattr(res, "degraded", ())))
        except Exception as exc:  # noqa: BLE001 - a request that raised is a failed request, reported below
            errors.append(exc)

    threads: List[threading.Thread] = []
    n_commits = len(p.commits)
    commit_called = np.full(n_commits, np.nan)
    commit_visible = np.full(n_commits, np.nan)
    t0 = clock() + 0.05
    t_end = t0 + seconds

    if p.loop == "open":
        q: "queue.SimpleQueue[Optional[int]]" = queue.SimpleQueue()

        def dispatcher() -> None:
            for i in range(n):
                wait = t0 + p.due[i] - clock()
                if wait > 0:
                    time.sleep(wait)
                q.put(i)
            for _ in range(p.waiters):
                q.put(None)

        def waiter() -> None:
            while True:
                i = q.get()
                if i is None:
                    return
                one(i, t0)

        threads += [threading.Thread(target=waiter, daemon=True, name=f"bench-waiter-{w}") for w in range(p.waiters)]
        threads.append(threading.Thread(target=dispatcher, daemon=True, name="bench-dispatcher"))
    else:
        counter = itertools.count()

        def caller() -> None:
            while clock() < t_end:
                i = next(counter)
                if i >= n:
                    return
                one(i, t0)
                due[i] = sent[i]

        threads += [threading.Thread(target=caller, daemon=True, name=f"bench-caller-{c}") for c in range(p.callers)]

    if n_commits:
        base = docs_visible()

        def committer() -> None:
            total = base
            for c, cm in enumerate(p.commits):
                wait = t0 + cm.at_s - clock()
                if wait > 0:
                    time.sleep(wait)
                total += len(cm.rows)
                if annotate is not None:
                    with annotate("bench.commit"):
                        commit_called[c] = commit(cm.rows) - t0
                else:
                    commit_called[c] = commit(cm.rows) - t0
                limit = clock() + POST_WINDOW_WAIT_S
                while docs_visible() < total and clock() < limit:
                    time.sleep(0.0003)
                if docs_visible() >= total:
                    commit_visible[c] = clock() - t0

        threads.append(threading.Thread(target=committer, daemon=True, name="bench-committer"))

    for th in threads:
        th.start()
    while clock() < t_end:
        if on_tick is not None:
            on_tick(clock() - t0)
        time.sleep(min(0.25, max(t_end - clock(), 0.0)))
    deadline = t_end + POST_WINDOW_WAIT_S
    for th in threads:
        th.join(timeout=max(deadline - clock(), 0.1))
    stuck = [th.name for th in threads if th.is_alive()]
    if p.loop == "closed":
        started = np.isfinite(sent)
        due, sent, done, ok = due[started], sent[started], done[started], ok[started]
        index = np.flatnonzero(started)
        kept = {int(np.searchsorted(index, i)): v for i, v in kept.items() if started[i]}
    return Window(
        t0, seconds, due, sent, done, ok, kept, commit_called, commit_visible,
        {"stuck_threads": stuck, "errors": [repr(e) for e in errors[:5]], "n_errors": len(errors)},
    )

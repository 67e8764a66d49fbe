"""The open-loop schedule and the percentile arithmetic, on synthetic
completions: a stalled window must move the tail and the throughput."""

import numpy as np

from benchmarks import corpus, loadgen


def test_percentile_nearest_rank():
    v = list(range(1, 101))
    assert loadgen.percentile(v, 50) == 50
    assert loadgen.percentile(v, 95) == 95
    assert loadgen.percentile([7.0], 95) == 7.0
    assert loadgen.percentile([1, 2, 3, 4], 50) == 2


def test_same_work_for_every_seed():
    texts = corpus.Texts(1, 64)
    traffic = {"loop": "open", "rate_rps": 200, "waiters": 4, "k": 10,
               "query_words": {"mu": 2.3, "sigma": 0.6, "min": 4, "max": 32}}
    a = loadgen.plan(traffic, texts, 1, 5.0, 10**6)
    b = loadgen.plan(traffic, texts, 4_000_000_007, 5.0, 10**6)
    assert abs(len(a.texts) - len(b.texts)) <= 12  # the same gaps in another order
    assert sorted(len(t.split()) for t in a.texts)[:900] == sorted(len(t.split()) for t in b.texts)[:900]
    assert a.texts != b.texts and len(set(a.texts)) == len(a.texts)
    gaps = np.diff(a.due)
    assert abs(gaps.mean() - 1 / 200) < 2e-4 and (gaps > 0).all()
    assert a.length_buckets == [16, 32, 48]


def _window(stall_at=None, stall_s=0.0, n=2000, seconds=10.0, service=0.005):
    due = np.linspace(0, seconds, n, endpoint=False)
    done = due + service
    if stall_at is not None:  # one server: nothing completes during the stall
        hit = (due >= stall_at) & (due < stall_at + stall_s)
        done[hit] = stall_at + stall_s + service
    return due, done, np.ones(n, bool)


def test_a_stall_moves_tail_and_throughput():
    calm = loadgen.summarise(*_window(), 10.0)
    stalled = loadgen.summarise(*_window(stall_at=9.2, stall_s=1.5), 10.0)
    assert calm["latency_p95_ms"] < 5.1 and calm["backlog_at_close"] <= 1
    assert stalled["latency_p95_ms"] > 100 * calm["latency_p95_ms"]
    assert stalled["throughput_rps"] < 0.93 * calm["throughput_rps"]
    assert stalled["backlog_at_close"] > 0
    # the latency counts from the due time, so the median hardly moves
    assert abs(stalled["latency_p50_ms"] - calm["latency_p50_ms"]) < 1e-6


def test_failed_requests_count_as_the_longest():
    due, done, ok = _window(n=100)
    ok[:10] = False
    done[10:12] = np.nan
    s = loadgen.summarise(due, done, ok, 10.0)
    assert s["failed"] == 12 and s["attempted"] == 100
    assert abs(s["latency_p95_ms"] - s["latency_p50_ms"]) < 1e-9  # 12% at the worst, which is the common 5 ms here
    done[20] = due[20] + 3.0
    s = loadgen.summarise(due, done, ok, 10.0)
    assert abs(s["latency_p95_ms"] - 3000.0) < 1e-6


def test_commits_and_probes_are_planned():
    texts = corpus.Texts(3, 64)
    traffic = {"loop": "open", "rate_rps": 100, "waiters": 4, "k": 10,
               "query_words": {"mu": 2.3, "sigma": 0.6, "min": 4, "max": 32},
               "commits": {"docs_per_commit": 8, "period_s": 0.5, "phase_s": 0.1, "probe_delay_s": 0.2,
                           "setup_docs": 24, "tail_fill_docs": 8,
                           "doc_words": {"mu": 2.9, "sigma": 0.7, "min": 8, "max": 120}}}
    p = loadgen.plan(traffic, texts, 3, 4.0, 5000)
    assert len(p.commits) == 8 and len(p.setup_rows) == 24 and p.tail_fill == 8
    assert [round(c.at_s, 3) for c in p.commits[:3]] == [0.1, 0.6, 1.1]
    keys = [k for c in p.commits for k, _ in c.rows] + [k for k, _ in p.setup_rows]
    assert len(set(keys)) == 24 + 64 and min(keys) == 5000
    for i, c in p.probes.items():
        assert p.due[i] >= p.commits[c].at_s + 0.2
        assert p.commits[c].probe_key in {k for k, _ in p.commits[c].rows}


def test_run_window_open_loop_counts_a_late_server():
    import time

    texts = corpus.Texts(5, 16)
    traffic = {"loop": "open", "rate_rps": 200, "waiters": 8, "k": 2,
               "query_words": {"mu": 2.3, "sigma": 0.6, "min": 4, "max": 32}}
    p = loadgen.plan(traffic, texts, 5, 1.0, 100)

    class Res(list):
        degraded = ()

    def serve(batch, k):
        time.sleep(0.002)
        return Res([[(1, 0.5), (2, 0.4)]])

    w = loadgen.run_window(serve, p, 1.0, keep=[0, 1])
    s = loadgen.summarise(w.due, w.done, w.ok, 1.0)
    assert s["failed"] == 0 and s["attempted"] == len(p.texts)
    assert 2.0 <= s["latency_p50_ms"] < 20 and set(w.kept) == {0, 1}

"""The trace reducer against a small recorded trace (0.12 s of a chip run
of ``vs1m-query-open``, TPU v5 lite, kept beside this file as plain events;
the ``bench.trace`` span was cut to the slice) and against events made by
hand."""

import os

import numpy as np

from benchmarks import reduce_trace as rt

HERE = os.path.dirname(os.path.abspath(__file__))


def _raster(intervals, lo, hi, step=1e-7):
    grid = np.zeros(int(round((hi - lo) / step)), bool)
    for a, b in intervals:
        grid[max(int((a - lo) / step), 0) : max(int((b - lo) / step), 0)] = True
    return grid


def test_recorded_trace_busy_and_kernel_time():
    ev = rt.load_events(os.path.join(HERE, "trace_small.json.gz"))
    r = rt.reduce_events(ev)
    span = [e for e in ev["host"] if e[0] == rt.WINDOW_SPAN][0]
    lo, hi = span[1], span[1] + span[2]
    dev = ev["devices"]["/device:TPU:0"]
    busy = _raster([(s, s + d) for _, s, d in dev], lo, hi)
    assert r["chips"] == 1 and abs(r["window_s"] - 0.12) < 1e-9
    assert abs(r["busy_s"] - busy.sum() * 1e-7) < 2e-5  # the union, not the sum
    assert r["busy_s"] <= sum(d for _, s, d in dev) + 1e-9
    kernel = sum(min(s + d, hi) - max(s, lo) for n, s, d in dev if "ivf_rescore" in n and min(s + d, hi) > max(s, lo))
    assert abs(rt.kernel_seconds(r, "ivf_rescore") - kernel) < 1e-9
    assert r["device_ops"][0][0].startswith("ivf_rescore f32[69,")
    assert abs(sum(v for _, v in r["idle_gaps"]) - (r["window_s"] - r["busy_s"])) < 1e-6
    inflight = _raster([(s, s + d) for n, s, d in ev["host"] if n == rt.REQUEST_SPAN], lo, hi)
    assert abs(r["inflight_s"] - inflight.sum() * 1e-7) < 2e-5
    assert abs(r["idle_inflight_s"] - (inflight & ~busy).sum() * 1e-7) < 4e-5
    assert all(label.startswith(("in-flight: ", "no-request: ")) for label, _ in r["idle_gaps"])


def test_by_hand():
    ev = {
        "devices": {"/device:TPU:0": [("%fusion.1 = f32[8]{0} fusion()", 1.0, 0.2), ("%fusion.2 = f32[8]{0} fusion()", 1.1, 0.2),
                                       ("%ivf_rescore.1 = f32[69,1,8,256]{3} custom-call()", 2.0, 0.5), ("late", 9.0, 1.0)]},
        "host": [(rt.WINDOW_SPAN, 0.0, 4.0), (rt.REQUEST_SPAN, 0.4, 1.3), ("tokenize", 0.0, 0.9), ("fetch", 1.3, 0.7),
                 ("$python noise", 0.0, 4.0)],
    }
    r = rt.reduce_events(ev)
    assert abs(r["busy_s"] - 0.8) < 1e-12 and r["window_s"] == 4.0
    ops = dict(map(tuple, r["device_ops"]))
    assert sorted(ops) == ["fusion f32[8]", "ivf_rescore f32[69,1,8,256]"]
    assert abs(ops["fusion f32[8]"] - 0.4) < 1e-12 and abs(ops["ivf_rescore f32[69,1,8,256]"] - 0.5) < 1e-12
    gaps = dict(map(tuple, r["idle_gaps"]))
    # idle 0..1 (tokenize overlaps it most), 1.3..2 (fetch), 2.5..4 (nothing but Python noise);
    # the request 0.4..1.7 is open at the middle of the first two
    assert abs(gaps["in-flight: tokenize"] - 1.0) < 1e-12
    assert abs(gaps["in-flight: fetch"] - 0.7) < 1e-12
    assert abs(gaps["no-request: (no host event)"] - 1.5) < 1e-12
    assert abs(r["inflight_s"] - 1.3) < 1e-12 and abs(r["idle_inflight_s"] - 1.0) < 1e-12


def test_no_device_event_gives_nothing_to_read():
    r = rt.reduce_events({"devices": {}, "host": []})
    assert r["busy_s"] == 0.0 and r["device_ops"] == []

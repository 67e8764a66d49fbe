"""From a ``jax.profiler`` trace (``.xplane.pb``) to device metrics.

Two layers, so that the arithmetic can be checked on a small recorded trace
(``tests/trace_small.json.gz``) without the profiler: ``read_xplane`` turns
the file into plain event lists, ``reduce_events`` turns those into

- ``busy_s``: per chip, the union of the intervals in which an operation
  ran on the device, averaged over the chips used; ``window_s``: the traced
  window (the runner's ``bench.trace`` span);
- ``device_ops``: device seconds by operation name (digits that only number
  an instance are folded), largest first;
- ``idle_gaps``: the device's idle time inside the window by what the host
  was doing meanwhile: the host event that overlaps a gap the most, with
  ``in-flight:`` before it when a request was being served (a
  ``bench.request`` span was open) and ``no-request:`` when none was;
- ``inflight_s`` / ``idle_inflight_s``: the time with a request in flight,
  and the idle part of it.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Any, Dict, Iterable, List, Sequence, Tuple

import numpy as np

WINDOW_SPAN = "bench.trace"
REQUEST_SPAN = "bench.request"
_ENVELOPES = (WINDOW_SPAN, REQUEST_SPAN)
# host events that only say a thread exists or sleeps tell nothing about a gap
_SKIP_HOST = re.compile(r"^(ThreadpoolListener|\$|Thread )")
_DEVICE_LINE = "XLA Ops"

Event = Tuple[str, float, float]  # name, start seconds, duration seconds


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_xplane(path: str) -> Dict[str, Any]:
    """``{"devices": {plane name: [event]}, "host": [event], "lines": {...}}``
    with times in seconds on the trace's own clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    lines: Dict[str, List[str]] = {}
    for plane in data.planes:
        plane_lines = list(plane.lines)
        lines[plane.name] = [ln.name for ln in plane_lines]
        if plane.name.startswith("/device:TPU:"):
            chosen = [ln for ln in plane_lines if ln.name == _DEVICE_LINE]
            for ln in chosen:
                devices.setdefault(plane.name, []).extend(
                    (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9) for ev in ln.events
                )
        elif plane.name.startswith("/host:"):
            for ln in plane_lines:
                host.extend(
                    (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                    for ev in ln.events
                    if ev.duration_ns > 0
                )
    return {"devices": devices, "host": host, "lines": lines}


def save_events(events: Dict[str, Any], path: str, limit: int = 0) -> None:
    """Keep a trace as plain JSON (``limit`` > 0 keeps the first events of
    each list only: a small recorded trace for the tests)."""
    cut = (lambda xs: xs[:limit]) if limit else (lambda xs: xs)
    doc = {
        "devices": {k: cut(sorted(v, key=lambda e: e[1])) for k, v in events["devices"].items()},
        "host": cut(sorted(events["host"], key=lambda e: e[1])),
        "lines": events.get("lines", {}),
    }
    with gzip.open(path, "wt") as fh:
        json.dump(doc, fh)


def load_events(path: str) -> Dict[str, Any]:
    with gzip.open(path, "rt") as fh:
        doc = json.load(fh)
    doc["devices"] = {k: [tuple(e) for e in v] for k, v in doc["devices"].items()}
    doc["host"] = [tuple(e) for e in doc["host"]]
    return doc


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Sequence[Tuple[float, float]], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def total(intervals: Sequence[Tuple[float, float]]) -> float:
    return float(sum(b - a for a, b in intervals))


def complement(intervals: Sequence[Tuple[float, float]], lo: float, hi: float):
    out, at = [], lo
    for a, b in intervals:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def intersect(xs: Sequence[Tuple[float, float]], ys: Sequence[Tuple[float, float]]):
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


_HLO = re.compile(r"^%?([^ =]+?)(?:\.\d+)* = (?:\()?([a-z0-9]+\[[0-9,]*\])")


def fold_name(name: str) -> str:
    """A device event is named by its whole HLO instruction: keep the
    operation and its (first) result shape, drop the instance number, so
    that ``%fusion.123 = f32[16,384]{...} fusion(...)`` and ``%fusion.124
    = f32[16,384]...`` are one kind, ``fusion f32[16,384]``."""
    m = _HLO.match(name)
    if m:
        return f"{m.group(1)} {m.group(2)}"
    return re.sub(r"(\.\d+)+$", "", name.lstrip("%"))[:80]


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------


def reduce_events(events: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    host: List[Event] = sorted(events["host"], key=lambda e: e[1])
    spans = [e for e in host if e[0] == WINDOW_SPAN]
    all_dev = [e for evs in events["devices"].values() for e in evs]
    if spans:
        lo, hi = spans[0][1], spans[0][1] + spans[0][2]
    elif all_dev:
        lo = min(e[1] for e in all_dev)
        hi = max(e[1] + e[2] for e in all_dev)
    else:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [], "idle_gaps": [], "chips": 0,
                "inflight_s": 0.0, "idle_inflight_s": 0.0, "op_seconds": {}}
    window_s = hi - lo
    busy_each, op_s = [], {}
    busy_all: List[Tuple[float, float]] = []
    for evs in events["devices"].values():
        ivs = clip([(s, s + d) for _, s, d in evs], lo, hi)
        u = union(ivs)
        busy_each.append(total(u))
        busy_all.append(u)
        for name, s, d in evs:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                key = fold_name(name)
                op_s[key] = op_s.get(key, 0.0) + (b - a)
    chips = len(busy_each)
    inflight = union(clip([(s, s + d) for n, s, d in host if n == REQUEST_SPAN], lo, hi))
    # a gap is idle on the fullest-used chip's clock: where several chips are
    # traced, the device is idle when the first one is
    first = busy_all[0] if busy_all else []
    idle = complement(first, lo, hi)
    idle_inflight = intersect(idle, inflight)
    gaps: Dict[str, float] = {}
    cand = [e for e in host if e[0] not in _ENVELOPES and not _SKIP_HOST.match(e[0])]
    starts = np.asarray([e[1] for e in cand])
    ends = np.asarray([e[1] + e[2] for e in cand])
    # events sorted by start: those that can overlap a gap (a, b) start before b;
    # a running maximum of ends bounds how far back to look
    run_max = np.maximum.accumulate(ends) if len(cand) else ends
    for a, b in idle:
        if b - a <= 0:
            continue
        hi_i = int(np.searchsorted(starts, b, "left"))
        lo_i = int(np.searchsorted(run_max, a, "right"))
        label, best = "(no host event)", 0.0
        for i in range(lo_i, hi_i):
            ov = min(ends[i], b) - max(starts[i], a)
            if ov > best:
                best, label = ov, cand[i][0]
        mid = 0.5 * (a + b)
        k = int(np.searchsorted([iv[0] for iv in inflight], mid, "right")) - 1
        busy_host = k >= 0 and inflight[k][1] > mid
        key = ("in-flight: " if busy_host else "no-request: ") + label
        gaps[key] = gaps.get(key, 0.0) + (b - a)
    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]  # noqa: E731
    return {
        "busy_s": float(np.mean(busy_each)) if busy_each else 0.0,
        "window_s": float(window_s),
        "chips": chips,
        "device_ops": rank(op_s),
        "op_seconds": op_s,
        "idle_gaps": rank(gaps),
        "inflight_s": total(inflight),
        "idle_inflight_s": total(idle_inflight),
    }


def kernel_seconds(reduced: Dict[str, Any], needle: str) -> float:
    """Device seconds of every operation whose name contains ``needle``."""
    return float(sum(v for k, v in reduced["op_seconds"].items() if needle in k))

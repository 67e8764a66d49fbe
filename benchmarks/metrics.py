"""Per-layer metrics: one file each under ``metrics/``, found by name.

``metrics/<name>.json`` states the metric (layer, unit, source, the one
end-to-end metric it moves) and how it is read: a generic kind handled
here, or ``"kind": "python"`` with a reader ``metrics/<name>.py`` beside it
whose ``read(ctx)`` returns the number, or ``None`` where it finds nothing
to read (the harness then leaves the metric out of the line).  Generic
kinds: ``histogram_mean_ms`` (sum of the means of the listed series),
``stat_ratio`` (two scheduler counters), ``value`` (a path into ``ctx``).

``ctx`` is what a traced run collected: ``hist(family, **labels)`` ->
``(count, sum_seconds)`` of a program histogram over the window,
``stats`` (scheduler counters over the window), ``cache`` (cache-tier
counters over the window), ``window`` and ``summary`` (the load
generator's record), ``trace`` (``reduce_trace.reduce_events`` of the
traced part), ``work`` (operations and bytes the traced requests needed),
``peaks``, ``chips``, ``memory``, ``gc_pause_ms``.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, Optional

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "metrics")


def load(name: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, f"{name}.json")) as fh:
        spec = json.load(fh)
    spec["name"] = name
    return spec


def _hist_mean_ms(ctx, series) -> Optional[float]:
    total, seen = 0.0, False
    for s in series:
        count, sum_s = ctx["hist"](s["family"], **s.get("labels", {}))
        if count:
            total += sum_s / count * 1e3
            seen = True
    return total if seen else None


def read(spec: Dict[str, Any], ctx: Dict[str, Any]) -> Optional[float]:
    how = spec["read"]
    kind = how["kind"]
    if kind == "histogram_mean_ms":
        return _hist_mean_ms(ctx, how["series"])
    if kind == "stat_ratio":
        num, den = ctx["stats"].get(how["numerator"], 0), ctx["stats"].get(how["denominator"], 0)
        return float(num) / float(den) if den else None
    if kind == "value":  # a number the run already holds, by its path in ctx
        node = ctx
        for key in how["path"]:
            node = node.get(key) if isinstance(node, dict) else None
            if node is None:
                return None
        return float(node)
    if kind == "python":
        path = os.path.join(HERE, f"{spec['name']}.py")
        mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{abs(hash(path))}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read(ctx)
    raise SystemExit(f"metric {spec['name']}: unknown reader kind {kind!r}")

"""Share of the scheduler thread's accounted time spent on batches: launching
one or advancing the previous one, over those plus idle and the coalescing
window (``pathway_serve_dispatcher_seconds_total``, seconds since the window
opened on reset counters)."""

PHASES = ("idle", "window", "launch", "advance")


def read(ctx):
    from pathway_tpu import observe  # ctx carries histograms only, not counters

    s = {p: float(observe.counter("pathway_serve_dispatcher_seconds_total", phase=p).value) for p in PHASES}
    total = sum(s.values())
    if total <= 0:
        return None
    return 100.0 * (s["launch"] + s["advance"]) / total

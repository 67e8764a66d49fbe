"""Share of the decode engine thread's working time in which the device waits
on the host: every phase of ``pathway_generator_engine_seconds_total`` but
``idle`` (nothing live, nothing queued) and the two fetches (the thread waits
for the device there), over every phase but ``idle``; seconds since the window
opened on reset counters.  The engine is one synchronous thread, so this is
the device's idle share as the program itself accounts for it."""

PHASES = (
    "join_host", "prefill_operands", "prefill_prefix", "prefill_call", "prefill_fetch", "prefill_settle",
    "step_operands", "step_dispatch", "step_fetch", "step_replay",
)
WAITS_FOR_THE_DEVICE = ("prefill_fetch", "step_fetch")


def read(ctx):
    counter = ctx.get("counter")
    if counter is None:
        return None
    s = {p: float(counter("pathway_generator_engine_seconds_total", phase=p)) for p in PHASES}
    total = sum(s.values())
    if total <= 0:
        return None
    return 100.0 * (total - sum(s[p] for p in WAITS_FOR_THE_DEVICE)) / total

"""The decode steps' needed reads over what the chip could have read: the
bytes the steps of the traced window had to read (every layer's weights once
per loop step, the head, the cached keys and values of each emitted token's
context: ``kinds/generation/flops.py``), over traced wall time x chips x the
HBM peak."""


def read(ctx):
    tr, work = ctx.get("trace"), ctx.get("work")
    if not tr or not work or tr["window_s"] <= 0 or not work.get("step_bytes"):
        return None
    return 100.0 * work["step_bytes"] / (tr["window_s"] * ctx["chips"] * ctx["peaks"]["hbm_bytes_per_s"])

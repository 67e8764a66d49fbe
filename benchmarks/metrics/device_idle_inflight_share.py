"""The device's idle share of the time in which a request was in flight."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["inflight_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * tr["idle_inflight_s"] / tr["inflight_s"]

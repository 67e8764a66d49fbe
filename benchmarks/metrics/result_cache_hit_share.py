"""Result-tier hits over look-ups inside the window (0 in an all-miss mix)."""


def read(ctx):
    hits = ctx["cache"].get("hits", 0.0) + ctx["stats"].get("cache_hits", 0)
    looks = hits + ctx["cache"].get("misses", 0.0)
    if not looks:
        return None
    return 100.0 * hits / looks

"""Thread CPU time over wall time of the ingest runner's embed bracket
(tokenise + pack + encode of a commit): the rest it waited, for the GIL it
shares with serving or for the device.  The CPU twin is sampled, so this is
the mean CPU of the sampled embeds over the mean wall of all of them."""


def read(ctx):
    n_cpu, cpu_s = ctx["hist"]("pathway_freshness_stage_cpu_seconds", stage="embed")
    n_wall, wall_s = ctx["hist"]("pathway_freshness_stage_seconds", stage="embed")
    if not n_cpu or not n_wall or wall_s <= 0:
        return None
    return 100.0 * (cpu_s / n_cpu) / (wall_s / n_wall)

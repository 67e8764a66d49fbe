"""The slowest shard's mean search stage (dispatch to fetched) in ms."""


def read(ctx):
    means = []
    for shard in range(ctx["n_shards"]):
        count, sum_s = ctx["hist"]("pathway_serve_shard_stage_seconds", stage="dispatch", shard=str(shard))
        if count:
            means.append(sum_s / count * 1e3)
    return max(means) if means else None

"""The whole step's share of the chip's peak: model operations that the
requests served in the traced window needed (encoder and cross-encoder
forward over real tokens, centroid probe and rescore), over traced wall
time x chips x the bf16 peak."""


def read(ctx):
    tr, work = ctx.get("trace"), ctx.get("work")
    if not tr or not work or tr["window_s"] <= 0:
        return None
    return 100.0 * work["model_flops"] / (tr["window_s"] * ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])

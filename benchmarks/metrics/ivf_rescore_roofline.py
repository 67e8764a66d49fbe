"""The rescore kernel's share of its roofline over the traced window: the
least time the chip could take for the slab reads and multiply-adds that
the traced requests needed, over the kernel's device time.  Bound by HBM
bandwidth (half an operation per byte read)."""


def read(ctx):
    tr, work = ctx.get("trace"), ctx.get("work")
    if not tr or not work:
        return None
    seconds = ctx["kernel_seconds"](tr, "ivf_rescore")
    if seconds <= 0 or not work["rescore_bytes"]:
        return None
    least = ctx["roofline"](work["rescore_flops"], work["rescore_bytes"], ctx["peaks"])
    return 100.0 * least["seconds"] / seconds

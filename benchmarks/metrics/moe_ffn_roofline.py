"""The grouped expert product's share of its roofline over the traced window:
the least time the chip could take for the expert products that the traced
joins and steps needed (``work["moe"]``, ``kinds/generation_moe/flops.py``:
a join's are bound by operations, 197 TFLOP/s, a step's by the bytes of the
distinct experts its lanes touch, 819 GB/s; each kind of program takes the
longer of its two), over the device time of the grouped product.

The product is a kernel of its own in the trace under either of two names:
``gmm…`` (the Pallas grouped matmul the program calls on a TPU, named by its
jitted function) or ``ragged-dot…`` (what the chip's compiler makes of
``jax.lax.ragged_dot``, its group-layout program ``ragged-dot-metadata``
included); the reader sums both, so the share errs low, never high.  A
step's bytes are counted for the experts uniform routing is expected to
touch; where the program's own counter says its steps touched fewer
(``moe_experts_touched_per_step``), the bytes are those fewer experts'.  The
gathers that sort the tokens by expert and sum the results back are not in
it."""

KERNELS = ("gmm", "ragged-dot")


def read(ctx):
    tr, work = ctx.get("trace"), ctx.get("work")
    if not tr or not work or not work.get("moe"):
        return None
    seconds = sum(ctx["kernel_seconds"](tr, name) for name in KERNELS)
    if seconds <= 0:
        return None
    touched = (ctx.get("moe") or {}).get("experts_touched_per_step")
    least = 0.0
    for w in work["moe"].values():
        fewer = min(1.0, touched / w["experts_expected"]) if touched and w.get("experts_expected") else 1.0
        least += ctx["roofline"](w["flops"], w["bytes"] * fewer, ctx["peaks"])["seconds"]
    return 100.0 * least / seconds

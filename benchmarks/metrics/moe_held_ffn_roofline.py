"""The grouped product over the HELD experts' share of its roofline over the
traced window: ``moe_ffn_roofline``'s reading (its reader is called as it
is: the same kernels ``gmm`` / ``ragged-dot``, the same rule for a step's
bytes), of a work count that holds only what this chip's range of the experts
needed: ``work["moe"]`` as ``kinds/generation_hybrid/flops.py`` counts it (a
join's pairs that fall to a held expert under even routing, a step's distinct
held experts), and ``ctx["moe"]["experts_touched_per_step"]`` the held
experts the program's own counter says its steps touched."""

import importlib.util
import os


def read(ctx):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "moe_ffn_roofline.py")
    spec = importlib.util.spec_from_file_location("bench_metric_moe_ffn_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)

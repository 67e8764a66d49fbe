"""The step program's share of its roofline over the traced window: the
least time the chip could take to read what the traced steps had to read
(``work["step_bytes"]``, ``kinds/generation/flops.py``; a step's operations
are a hundredth of what its bytes allow, so HBM bandwidth bounds it), over
the device time of the step program's operations.

The trace names an operation by its result's shape, not by its program.  A
step's arrays have one row per slot (a leading dimension of ``slots``) or
are one layer's slice of a stacked weight (a leading 1); a join's have a row
per joining request (1, 4 or 16, squeezed away when 1) and its suffix's
length.  The step's time is the sum of the first kind: weight slices are
counted for the step wherever they ran, so the share errs low, never high.
The ``while`` and ``conditional`` events enclose the others (a step chunk is
a loop over steps, each a conditional around a loop over loop steps around a
loop over layers) and are left out."""

import re

ENCLOSING = ("while", "conditional", "call")


def read(ctx):
    tr, work, gen = ctx.get("trace"), ctx.get("work"), ctx.get("gen")
    if not tr or not work or not gen or not work.get("step_bytes") or not gen.get("slots"):
        return None
    lead = re.compile(r" [a-z0-9]+\[(%d|1)[,\]]" % int(gen["slots"]))
    seconds = sum(v for k, v in tr["op_seconds"].items() if lead.search(k) and not k.startswith(ENCLOSING))
    if seconds <= 0:
        return None
    least = ctx["roofline"](0.0, work["step_bytes"], ctx["peaks"])
    return 100.0 * least["seconds"] / seconds

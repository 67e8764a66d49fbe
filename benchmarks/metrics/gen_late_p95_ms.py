"""How late the load generator ran: sent minus due, 95th percentile."""
import numpy as np


def read(ctx):
    w = ctx["window"]
    late = (w.sent - w.due)[np.isfinite(w.sent)]
    if late.size == 0 or ctx["plan"].loop != "open":
        return None
    return float(np.sort(late)[max(int(np.ceil(0.95 * late.size)), 1) - 1] * 1e3)

"""Share of the traced window in which no operation runs on the device:
1 - (union of the device's operation intervals) / window, averaged over
the cell's chips."""
from bench import trace as T


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.ops:
        return None
    lo, hi = tr.window()
    busy = [T.length(T.busy(tr, d)) for d in tr.ops]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))

"""The whole step's share of the chips' bf16 peak while the device works:
model FLOPs of the traced steps (``bench/flops.py``) over the union of the
device's operation intervals in the trace, averaged over the chips, times
the chips' peak. Read from the device trace alone, it leaves out the idle
share that the host-clock ``mfu`` carries; it bounds every kernel roofline
of the step, so a kernel taken off the path cannot hide a slower step."""
from bench import trace as T


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.ops:
        return None
    busy = sum(T.length(T.busy(tr, d)) for d in tr.ops) / len(tr.ops) / 1e9
    if busy <= 0:
        return None
    peak = ctx.chips * ctx.peak["bf16_flops_per_s"]
    return 100.0 * ctx.flops_step * ctx.steps / (busy * peak)

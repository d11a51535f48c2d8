"""Per step, the time in which a collective (all-gather, reduce-scatter,
all-reduce, all-to-all, collective-permute) runs on a chip while no other
operation runs there: the EP token exchange and the EPSO gathers that
compute does not hide. Averaged over the chips."""
from bench import trace as T


def read(ctx):
    tr = ctx.trace
    if tr is None or len(tr.ops) < 2:
        return None
    if not any(T.is_collective(n) for ops in tr.ops.values()
               for n, _, _ in ops):
        return None
    t = [T.exposed_collective(tr, d) for d in tr.ops]
    return sum(t) / len(t) / ctx.steps / 1e6

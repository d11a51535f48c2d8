"""Device time per step of the MoE path's Pallas kernels (grouped matmul
forward and transposed, fused SwiGLU, combine forward and backward, token
counts), found by kernel name in the trace, averaged over the chips."""
from bench import trace as T

KERNELS = ("_gmm_kernel", "_tgmm_kernel", "_swiglu_kernel",
           "_combine_fwd_kernel", "_combine_bwd_kernel", "_count_kernel")


def is_moe_kernel(label: str) -> bool:
    return any(k in label for k in KERNELS)


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.ops:
        return None
    t = [T.op_time(tr, d, is_moe_kernel) for d in tr.ops]
    if not any(t):
        return None
    return sum(t) / len(t) / ctx.steps / 1e6

"""Share of the bf16 peak that the expert GEMMs reach: the routed rows'
FLOPs (T*K rows x 3 matrices x 2*d*f, forward and backward, counted from
the configuration's shapes by ``bench/flops.py``) over the device time of
the kernels that compute them, summed over the chips. Padding of the
dropless pool, the float32 cast inside the kernel and the forward that
remat recomputes all show as lost share; the count stays the same
whatever implements the GEMMs."""
from bench import trace as T

KERNELS = ("_gmm_kernel", "_tgmm_kernel")


def is_gemm(label: str) -> bool:
    return any(k in label for k in KERNELS)


def read(ctx):
    tr = ctx.trace
    flops = ctx.flops_expert_gemm
    if tr is None or not tr.ops or not flops:
        return None
    t = sum(T.op_time(tr, d, is_gemm) for d in tr.ops) / 1e9 / ctx.steps
    if t <= 0:
        return None
    return 100.0 * flops / (t * ctx.peak["bf16_flops_per_s"])

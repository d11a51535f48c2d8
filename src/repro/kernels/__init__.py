"""Pallas TPU kernels for the compute hot-spots (DESIGN §2, §3).

Each kernel: <name>.py (pl.pallas_call + explicit BlockSpec VMEM tiling),
a jit'd wrapper (+custom VJP where trained) in ops.py, and a pure-jnp
oracle in ref.py; all validated on CPU via interpret=True with
shape/dtype sweeps (tests/test_kernels.py, test_flash_attention.py,
test_ssd_kernel.py).

  gmm.py              Stage-4 grouped matmul (ragged, scalar-prefetched
                      tile->group map) + tgmm weight-gradient kernel
  moe_dispatch.py     Stage-2 token-count histogram
  combine.py          Stage-5 output reduction, forward + fused backward
  swiglu.py           fused SwiGLU activation
  flash_attention.py  blockwise online-softmax attention (causal + SWA)
  ssd.py              Mamba-2 SSD intra-chunk stage (hybrid archs)
"""
import jax


def out_struct(shape, dtype, *operands):
    """A ``pallas_call`` out_shape that varies over the manual mesh axes its
    operands vary over. Inside a ``check_vma`` shard_map region (the EP MoE
    body) a kernel output must declare them; elsewhere the set is empty."""
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)

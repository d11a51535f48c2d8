"""Fused SwiGLU elementwise Pallas kernel: silu(gate) * up.

Fuses the two Stage-4 activation reads into one VMEM pass between the
gate/up grouped GEMMs and the down-projection GEMM (on GPU the paper fuses
this into its expert-computation stage; on TPU it saves one HBM round-trip
of the (pool_rows × d_ff) activation).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import out_struct


def _swiglu_kernel(g_ref, u_ref, out_ref):
    g = g_ref[...].astype(jnp.float32)
    out_ref[...] = (g * jax.lax.logistic(g) *
                    u_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


def swiglu_pallas(gate: jax.Array, up: jax.Array, *, tile_m: int = 512,
                  tile_n: int = 512, interpret: bool = False) -> jax.Array:
    M, N = gate.shape
    tm, tn = min(tile_m, M), min(tile_n, N)
    assert M % tm == 0 and N % tn == 0
    return pl.pallas_call(
        _swiglu_kernel,
        grid=(M // tm, N // tn),
        in_specs=[pl.BlockSpec((tm, tn), lambda i, j: (i, j)),
                  pl.BlockSpec((tm, tn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j: (i, j)),
        out_shape=out_struct((M, N), gate.dtype, gate, up),
        interpret=interpret,
    )(gate, up)

"""Pallas TPU output-reduction kernels (paper §3.1 Stage 5, fwd + bwd).

Forward (paper ExpertOutputReductionForward, lines 82-96): each output
element out[t, h] = sum_k weights[t, k] * rows[t, k, h]. The GPU kernel maps
one thread per (t, h) element; the TPU kernel tiles (t, h) into VMEM blocks
and reduces over the K axis with a vectorized multiply-add.

Backward (paper ExpertOutputReductionBackward, lines 98-113): produces
d_rows[t, k, h] = weights[t, k] * dout[t, h] and
d_weights[t, k] = sum_h rows[t, k, h] * dout[t, h] in one pass, mirroring
the paper's fused backward kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import out_struct


def _combine_fwd_kernel(rows_ref, w_ref, out_ref):
    rows = rows_ref[...].astype(jnp.float32)     # (TT, K, TD)
    w = w_ref[...].astype(jnp.float32)           # (TT, K)
    # broadcast multiply + K-reduction on the VPU: Mosaic has no batched
    # matmul lowering for a (TT, K, TD) x (TT, K) contraction
    out_ref[...] = (rows * w[:, :, None]).sum(axis=1).astype(out_ref.dtype)


def combine_fwd_pallas(rows: jax.Array, weights: jax.Array, *,
                       tile_t: int = 256, tile_d: int = 512,
                       interpret: bool = False) -> jax.Array:
    T, K, D = rows.shape
    tt, td = min(tile_t, T), min(tile_d, D)
    assert T % tt == 0 and D % td == 0
    return pl.pallas_call(
        _combine_fwd_kernel,
        grid=(T // tt, D // td),
        in_specs=[pl.BlockSpec((tt, K, td), lambda t, d: (t, 0, d)),
                  pl.BlockSpec((tt, K), lambda t, d: (t, 0))],
        out_specs=pl.BlockSpec((tt, td), lambda t, d: (t, d)),
        out_shape=out_struct((T, D), rows.dtype, rows, weights),
        interpret=interpret,
    )(rows, weights)


def _combine_bwd_kernel(rows_ref, w_ref, dout_ref, drows_ref, dw_ref, *,
                        n_d: int):
    d = pl.program_id(1)
    rows = rows_ref[...].astype(jnp.float32)     # (TT, K, TD)
    w = w_ref[...].astype(jnp.float32)           # (TT, K)
    dout = dout_ref[...].astype(jnp.float32)     # (TT, TD)
    drows_ref[...] = (w[:, :, None] * dout[:, None, :]).astype(drows_ref.dtype)

    @pl.when(d == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dw_ref[...] += jnp.einsum("tkd,td->tk", rows, dout).astype(dw_ref.dtype)


def combine_bwd_pallas(rows: jax.Array, weights: jax.Array, dout: jax.Array,
                       *, tile_t: int = 256, tile_d: int = 512,
                       interpret: bool = False):
    T, K, D = rows.shape
    tt, td = min(tile_t, T), min(tile_d, D)
    assert T % tt == 0 and D % td == 0
    import functools
    return pl.pallas_call(
        functools.partial(_combine_bwd_kernel, n_d=D // td),
        grid=(T // tt, D // td),
        in_specs=[pl.BlockSpec((tt, K, td), lambda t, d: (t, 0, d)),
                  pl.BlockSpec((tt, K), lambda t, d: (t, 0)),
                  pl.BlockSpec((tt, td), lambda t, d: (t, d))],
        out_specs=[pl.BlockSpec((tt, K, td), lambda t, d: (t, 0, d)),
                   pl.BlockSpec((tt, K), lambda t, d: (t, 0))],
        out_shape=[out_struct((T, K, D), rows.dtype, rows, weights, dout),
                   out_struct((T, K), jnp.float32, rows, weights, dout)],
        interpret=interpret,
    )(rows, weights, dout)

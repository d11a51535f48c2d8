"""Pallas TPU token-counting kernel (paper §3.1 Stage 2).

The paper's GPU kernel maps threads to row blocks of the routing-indices
tensor and bumps per-(expert, thread) counters with atomics, then reduces.
TPU has no atomics; the adaptation processes the flattened indices in grid
tiles, forms a one-hot (tile × experts) matrix in VMEM, row-reduces it and
accumulates into the (experts,) output block — the output block is revisited
by every grid step (index map is constant), which Pallas TPU supports for
sequential grids. The partial-counts-then-reduce structure of the paper
becomes the grid-step accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import out_struct


def _count_kernel(idx_ref, out_ref, *, num_local: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    idx = idx_ref[...]                                   # (1, TB) int32
    eye = jax.lax.broadcasted_iota(jnp.int32, (num_local, idx.shape[1]), 0)
    onehot = (idx == eye).astype(jnp.int32)              # (E, TB)
    out_ref[...] += onehot.sum(axis=1)


def token_counts_pallas(indices: jax.Array, num_local: int, offset, *,
                        tile: int = 1024, interpret: bool = False) -> jax.Array:
    """indices: (F,) flat global expert ids; returns (num_local,) int32
    counts of ids in [offset, offset + num_local)."""
    F = indices.shape[0]
    tb = min(tile, F)
    pad = (-F) % tb
    local = indices.astype(jnp.int32) - offset
    local = jnp.where((local >= 0) & (local < num_local), local, num_local)
    local = jnp.pad(local, (0, pad), constant_values=num_local)
    local = local.reshape(1, F + pad)                    # 2-D for TPU layout

    return pl.pallas_call(
        functools.partial(_count_kernel, num_local=num_local),
        grid=((F + pad) // tb,),
        in_specs=[pl.BlockSpec((1, tb), lambda t: (0, t))],
        out_specs=pl.BlockSpec((num_local,), lambda t: (0,)),
        out_shape=out_struct((num_local,), jnp.int32, local),
        interpret=interpret,
    )(local)

"""Pallas TPU grouped matmul (paper §3.1 Stage 4: Grouped_mm).

The paper merges per-rank expert weights into single tensors and runs one
grouped GEMM over the routed-token rows. On TPU the pointer-chasing GPU
grouped GEMM becomes a *tile→group map*: row tiles are group-aligned (the
dispatch pads each expert's rows to ``tile_m``), a scalar-prefetched
``group_ids`` array tells each m-tile which expert's weight block to stream
into VMEM, and the MXU sees plain (tm × tk) @ (tk × tn) tiles.

VMEM working set per grid step: tm*tk (lhs) + tk*tn (rhs) + tm*tn (acc f32),
e.g. 128*512*2B + 512*128*2B + 128*128*4B ≈ 0.3 MB — far under the ~16 MB
v5e VMEM budget, leaving room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import out_struct


def _gmm_kernel(group_ids_ref, lhs_ref, rhs_ref, out_ref, acc_ref, *,
                n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(lhs_ref[...].astype(jnp.float32),
                            rhs_ref[0].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def gmm_pallas(lhs: jax.Array, rhs: jax.Array, group_ids: jax.Array, *,
               tile_m: int, tile_k: int, tile_n: int,
               interpret: bool = False) -> jax.Array:
    """lhs: (M, K) with M % tile_m == 0 and every m-tile belonging to exactly
    one group (group-aligned layout); rhs: (G, K, N); group_ids: (M/tile_m,)
    int32 tile→group map (scalar-prefetched)."""
    from jax.experimental.pallas import tpu as pltpu
    M, K = lhs.shape
    G, K2, N = rhs.shape
    assert K == K2 and M % tile_m == 0 and K % tile_k == 0 and N % tile_n == 0
    n_m, n_k, n_n = M // tile_m, K // tile_k, N // tile_n

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_m, n_n, n_k),
        in_specs=[
            pl.BlockSpec((tile_m, tile_k), lambda m, n, k, gid: (m, k)),
            pl.BlockSpec((1, tile_k, tile_n),
                         lambda m, n, k, gid: (gid[m], k, n)),
        ],
        out_specs=pl.BlockSpec((tile_m, tile_n), lambda m, n, k, gid: (m, n)),
        scratch_shapes=[pltpu.VMEM((tile_m, tile_n), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, n_k=n_k),
        grid_spec=grid_spec,
        out_shape=out_struct((M, N), lhs.dtype, lhs, rhs, group_ids),
        interpret=interpret,
    )(group_ids, lhs, rhs)


# ----------------------------------------------------------------------------
# tgmm: per-group weight gradient  out[g] = lhs_g^T @ rhs_g
# ----------------------------------------------------------------------------

def _tgmm_kernel(group_ids_ref, lhs_ref, rhs_ref, out_ref, acc_ref, *,
                 n_m: int):
    m = pl.program_id(2)
    first = jnp.logical_or(
        m == 0, group_ids_ref[jnp.maximum(m, 1) - 1] != group_ids_ref[m])

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(lhs_ref[...].astype(jnp.float32).T,
                            rhs_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    last = jnp.logical_or(
        m == n_m - 1,
        group_ids_ref[jnp.minimum(m + 1, n_m - 1)] != group_ids_ref[m])

    @pl.when(last)
    def _flush():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def tgmm_pallas(lhs: jax.Array, rhs: jax.Array, group_ids: jax.Array,
                num_groups: int, *, tile_m: int, tile_k: int, tile_n: int,
                interpret: bool = False) -> jax.Array:
    """lhs: (M, K); rhs: (M, N); group-aligned m-tiles; out: (G, K, N).

    Grid order (k, n, m): for a fixed (k, n) output tile the m-sweep visits
    each group's tiles consecutively, so the output block for group g is
    initialized at the group's first tile and flushed at its last — the
    sequential-grid accumulation pattern Pallas TPU guarantees.
    """
    from jax.experimental.pallas import tpu as pltpu
    M, K = lhs.shape
    N = rhs.shape[1]
    assert M % tile_m == 0 and K % tile_k == 0 and N % tile_n == 0
    n_m, n_k, n_n = M // tile_m, K // tile_k, N // tile_n

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_k, n_n, n_m),
        in_specs=[
            pl.BlockSpec((tile_m, tile_k), lambda k, n, m, gid: (m, k)),
            pl.BlockSpec((tile_m, tile_n), lambda k, n, m, gid: (m, n)),
        ],
        out_specs=pl.BlockSpec((1, tile_k, tile_n),
                               lambda k, n, m, gid: (gid[m], k, n)),
        scratch_shapes=[pltpu.VMEM((tile_k, tile_n), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, n_m=n_m),
        grid_spec=grid_spec,
        out_shape=out_struct((num_groups, K, N), lhs.dtype, lhs, rhs,
                             group_ids),
        interpret=interpret,
    )(group_ids, lhs, rhs)

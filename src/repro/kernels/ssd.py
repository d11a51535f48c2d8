"""Pallas TPU kernel for the Mamba-2 SSD intra-chunk computation.

The chunked SSD formulation (models/ssm.py::_ssd_chunked) splits the
selective scan into dense intra-chunk matmuls + a short inter-chunk
recurrence. This kernel fuses the intra-chunk stage per (batch, chunk,
head) grid cell so the (L,L) decay/score matrices never leave VMEM:

    la      = cumsum(dt * A)                       (L,)
    decay   = tril(exp(la_i - la_j))               (L,L)  — VMEM only
    y_diag  = ((C B^T) ∘ decay) @ (dt * x)         (L,P)
    states  = (exp(la_L - la) * dt * x)^T @ B      (P,N)  — chunk final
    cdecay  = exp(la_L)                            ()

VMEM per grid step ≈ L·P + 2·L·N (bf16) + 2·L·L f32 ≈ 0.7 MiB at
(L,P,N) = (256, 64, 64). The inter-chunk recurrence and off-diagonal
read-out stay in jnp (matmul-light). Forward-only (training uses the jnp
path — same math; this is the serving/prefill hot loop for hybrid archs).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import out_struct


def _ssd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref,
                y_ref, st_ref, cd_ref):
    h = pl.program_id(2)
    x = x_ref[0, 0, :, 0, :].astype(jnp.float32)      # (L, P)
    dt = dt_ref[0, 0, :, 0].astype(jnp.float32)       # (L,)
    bm = b_ref[0, 0].astype(jnp.float32)              # (L, N)
    cm = c_ref[0, 0].astype(jnp.float32)              # (L, N)
    a = a_ref[h]                                      # scalar (negative)

    L = x.shape[0]
    la = jnp.cumsum(dt * a)                           # (L,)
    seg = la[:, None] - la[None, :]
    causal = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    decay = jnp.where(causal, jnp.exp(seg), 0.0)      # (L, L) VMEM-resident
    cb = jnp.dot(cm, bm.T, preferred_element_type=jnp.float32)
    dtx = dt[:, None] * x                             # (L, P)
    y = jnp.dot(cb * decay, dtx, preferred_element_type=jnp.float32)
    w = jnp.exp(la[-1] - la)                          # (L,)
    st = jnp.dot((w[:, None] * dtx).T, bm,
                 preferred_element_type=jnp.float32)  # (P, N)

    y_ref[0, 0, :, 0, :] = y.astype(y_ref.dtype)
    st_ref[0, 0, 0] = st.astype(st_ref.dtype)
    cd_ref[0, 0, 0] = jnp.exp(la[-1]).astype(cd_ref.dtype)


def ssd_intra_chunk_pallas(x, dt, Bm, Cm, A, *, interpret: bool = False):
    """x: (B, C, L, H, P); dt: (B, C, L, H); Bm/Cm: (B, C, L, N); A: (H,).
    Returns (y_diag (B,C,L,H,P), states (B,C,H,P,N), chunk_decay (B,C,H))."""
    B, C, L, H, P = x.shape
    N = Bm.shape[-1]
    ops = (x, dt, Bm, Cm, A)
    y, st, cd = pl.pallas_call(
        _ssd_kernel,
        grid=(B, C, H),
        in_specs=[
            pl.BlockSpec((1, 1, L, 1, P), lambda b, c, h: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, L, 1), lambda b, c, h: (b, c, 0, h)),
            pl.BlockSpec((1, 1, L, N), lambda b, c, h: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, c, h: (b, c, 0, 0)),
            pl.BlockSpec((H,), lambda b, c, h: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, 1, P), lambda b, c, h: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, 1, P, N), lambda b, c, h: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, c, h: (b, c, h)),
        ],
        out_shape=[
            out_struct((B, C, L, H, P), jnp.float32, *ops),
            out_struct((B, C, H, P, N), jnp.float32, *ops),
            out_struct((B, C, H), jnp.float32, *ops),
        ],
        interpret=interpret,
    )(x, dt, Bm, Cm, A.astype(jnp.float32))
    return y, st, cd

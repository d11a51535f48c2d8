"""Public jit'd wrappers for the Pallas kernels, with custom VJPs.

* ``gmm(x, w, group_sizes)``      — Stage 4 grouped matmul. Backward:
      dx = gmm(dy, swap(w)),  dw = tgmm(x, dy)  (both Pallas kernels).
* ``combine(rows, weights)``      — Stage 5 output reduction; backward uses
      the paper's fused backward kernel.
* ``fused_swiglu(gate, up)``      — fused activation; analytic VJP.
* ``token_counts(idx, n, off)``   — Stage 2 histogram (no gradient).

Tile sizes (MXU-aligned 128/512 defaults) and the interpret flag (True on
CPU: kernels execute their Python bodies — how this container validates TPU
kernels) come from the *active* ``parallel.plan.KernelPlan`` — plan-scoped
via ``use_kernel_plan`` (leak-free), read at trace time. Under
``KernelPlan(tiles='auto')`` each wrapper first consults the measured
tuning table (kernels/autotune.py) for its shape bucket and falls back to
the plan's explicit tiles on a miss.
Wrappers pad K/N dims up to tile multiples (zero-padding is exact for
matmul) and slice back.

Tombstone: the PR 4 dict-view compatibility alias over the process-default
plan is deleted (lint rule SL004 forbids the symbol repo-wide). Scope a
plan with ``use_kernel_plan`` / set the process default with
``set_default_kernel_plan`` instead.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.parallel.plan import (KernelPlan, current_kernel_plan,
                                 default_kernel_plan,
                                 set_default_kernel_plan, use_kernel_plan)

from .gmm import gmm_pallas, tgmm_pallas
from .combine import combine_fwd_pallas, combine_bwd_pallas
from .swiglu import swiglu_pallas
from .moe_dispatch import token_counts_pallas

__all__ = ["KernelPlan", "current_kernel_plan", "default_kernel_plan",
           "set_default_kernel_plan", "use_kernel_plan",
           "gmm", "combine", "fused_swiglu", "token_counts",
           "flash_attention", "gmm_align", "ssd_intra_chunk",
           "vma_checkable"]


def _interpret() -> bool:
    flag = current_kernel_plan().interpret
    if flag is None:
        return jax.default_backend() == "cpu"
    return bool(flag)


def vma_checkable() -> bool:
    """Whether the Pallas kernels may run inside a ``check_vma`` shard_map
    region. Compiled kernels may. Pallas's CPU interpreter cannot evaluate
    a kernel whose scalar-prefetched tile->group index map varies over a
    manual axis (jax 0.9 raises in its index-map evaluation), so interpret
    mode may not: callers turn the check off there."""
    return not _interpret()


def gmm_align() -> int:
    """Group alignment the dispatch must honor for the Pallas backend."""
    return current_kernel_plan().tile_m


def _pad_to(x, mult, axis):
    r = (-x.shape[axis]) % mult
    if r == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, r)
    return jnp.pad(x, pads)


def _tile_group_ids(group_sizes: jax.Array, n_tiles: int, tile_m: int):
    """tile -> group map (scalar prefetch). Requires group_sizes % tile_m == 0
    (ensured by the dispatch's alignment). Tiles past sum(group_sizes) are
    clamped to the last group; their rows are masked out by the callers."""
    G = group_sizes.shape[0]
    offsets = jnp.cumsum(group_sizes)
    tile_starts = jnp.arange(n_tiles, dtype=jnp.int32) * tile_m
    gids = jnp.searchsorted(offsets, tile_starts, side="right")
    return jnp.minimum(gids, G - 1).astype(jnp.int32)


# ----------------------------------------------------------------------------
# gmm with custom VJP
# ----------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=())
def gmm(x: jax.Array, w: jax.Array, group_sizes: jax.Array) -> jax.Array:
    return _gmm_fwd_impl(x, w, group_sizes)


def _resolved_gmm_tiles(kp, G, M, K, N):
    """Plan tiles, overridden by the tuning table under ``tiles='auto'``.
    An auto tile_m only applies when it divides the plan's tile_m (the
    dispatch pads group sizes to ``plan.tile_m``, so any divisor keeps the
    ``group_sizes % tile_m == 0`` kernel contract) and divides M."""
    tm, tk, tn = kp.tile_m, kp.tile_k, kp.tile_n
    auto = kp.resolve_tiles("gmm", {"g": G, "m": M, "k": K, "n": N})
    if auto is not None:
        atm, atk, atn = auto
        if atm and kp.tile_m % atm == 0 and M % atm == 0:
            tm = atm
        tk = atk or tk
        tn = atn or tn
    return tm, tk, tn


def _gmm_fwd_impl(x, w, group_sizes):
    kp = current_kernel_plan()
    M, K = x.shape
    G, _, N = w.shape
    tm, tk, tn = _resolved_gmm_tiles(kp, G, M, K, N)
    tk = min(tk, K)
    tn = min(tn, N)
    xp = _pad_to(x, tk, 1)
    wp = _pad_to(_pad_to(w, tk, 1), tn, 2)
    n_tiles = M // tm
    gids = _tile_group_ids(group_sizes, n_tiles, tm)
    out = gmm_pallas(xp, wp, gids, tile_m=tm, tile_k=tk, tile_n=tn,
                     interpret=_interpret())
    # rows past sum(group_sizes) belong to no group -> zero (ref semantics)
    total = jnp.sum(group_sizes)
    out = out * (jnp.arange(M) < total)[:, None].astype(out.dtype)
    return out[:, :N]


def _gmm_fwd(x, w, group_sizes):
    return _gmm_fwd_impl(x, w, group_sizes), (x, w, group_sizes)


def _gmm_bwd(res, dy):
    x, w, group_sizes = res
    kp = current_kernel_plan()
    M, K = x.shape
    G, _, N = w.shape
    # dx = gmm(dy, w^T) — resolves its own (k=N, n=K) bucket under 'auto'
    dx = _gmm_fwd_impl(dy, jnp.swapaxes(w, 1, 2), group_sizes)
    # dw[g] = x_g^T dy_g  (tgmm kernel: lhs = x (M,K), rhs = dy (M,N)
    # -> out (G,K,N)); tile defaults 512/512, table-overridable
    tm = kp.tile_m
    tkk = min(512, K)
    tnn = min(512, N)
    auto = kp.resolve_tiles("tgmm", {"g": G, "m": M, "k": K, "n": N})
    if auto is not None:
        atm, atk, atn = auto
        if atm and kp.tile_m % atm == 0 and M % atm == 0:
            tm = atm
        tkk = min(atk or tkk, K)
        tnn = min(atn or tnn, N)
    total = jnp.sum(group_sizes)
    row_mask = (jnp.arange(M) < total)[:, None]
    xp = _pad_to(x * row_mask.astype(x.dtype), tkk, 1)
    dyp = _pad_to(dy * row_mask.astype(dy.dtype), tnn, 1)
    gids = _tile_group_ids(group_sizes, M // tm, tm)
    dw = tgmm_pallas(xp, dyp, gids, G, tile_m=tm, tile_k=tkk, tile_n=tnn,
                     interpret=_interpret())
    # groups with zero rows have no tiles -> their output block is never
    # written (uninitialized); their true gradient is zero.
    dw = jnp.where((group_sizes > 0)[:, None, None], dw, 0)
    dw = dw[:, :K, :N].astype(w.dtype)
    return dx.astype(x.dtype), dw, None


gmm.defvjp(_gmm_fwd, _gmm_bwd)


# ----------------------------------------------------------------------------
# combine with the paper's fused backward kernel
# ----------------------------------------------------------------------------

@jax.custom_vjp
def combine(rows: jax.Array, weights: jax.Array) -> jax.Array:
    return _combine_fwd_impl(rows, weights)


def _tile_t(T):
    for t in (256, 128, 64, 32, 16, 8, 4, 2, 1):
        if T % t == 0:
            return t
    return 1


def _tile_d(D):
    for t in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        if D % t == 0:
            return t
    return 1


# Largest (tt, K, td) rows block: the backward kernel double-buffers it in
# and out (4 blocks), which must fit the 16 MiB of scoped VMEM a TPU kernel
# gets by default. 2 MiB is a (256, 8, 512) bf16 block; a float32 one halves tt.
_COMBINE_BLOCK_BYTES = 2 << 20


def _combine_tiles(T, K, D, itemsize):
    """Divisor-scan defaults, overridden by the tuning table under
    ``tiles='auto'`` when the table tiles divide the actual dims (these
    wrappers don't pad, so non-divisors fall back); then tt halves (it stays
    a power-of-two divisor of T) until the rows block fits the VMEM budget."""
    tt, td = _tile_t(T), _tile_d(D)
    auto = current_kernel_plan().resolve_tiles(
        "combine", {"t": T, "k": K, "d": D})
    if auto is not None:
        at, ad = auto
        if at and T % at == 0:
            tt = at
        if ad and D % ad == 0:
            td = ad
    while tt > 8 and tt % 2 == 0 and tt * K * td * itemsize > \
            _COMBINE_BLOCK_BYTES:
        tt //= 2
    return tt, td


def _combine_fwd_impl(rows, weights):
    T, K, D = rows.shape
    tt, td = _combine_tiles(T, K, D, rows.dtype.itemsize)
    return combine_fwd_pallas(rows, weights, tile_t=tt, tile_d=td,
                              interpret=_interpret())


def _combine_fwd(rows, weights):
    return _combine_fwd_impl(rows, weights), (rows, weights)


def _combine_bwd(res, dout):
    rows, weights = res
    T, K, D = rows.shape
    tt, td = _combine_tiles(T, K, D, rows.dtype.itemsize)
    drows, dw = combine_bwd_pallas(rows, weights, dout, tile_t=tt,
                                   tile_d=td, interpret=_interpret())
    return drows, dw.astype(weights.dtype)


combine.defvjp(_combine_fwd, _combine_bwd)


# ----------------------------------------------------------------------------
# fused swiglu
# ----------------------------------------------------------------------------

@jax.custom_vjp
def fused_swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return _swiglu_impl(gate, up)


def _swiglu_impl(gate, up):
    M, N = gate.shape
    tm, tn = _tile_t(M), _tile_d(N)
    auto = current_kernel_plan().resolve_tiles(
        "fused_swiglu", {"m": M, "n": N})
    if auto is not None:
        am, an = auto
        if am and M % am == 0:
            tm = am
        if an and N % an == 0:
            tn = an
    return swiglu_pallas(gate, up, tile_m=tm, tile_n=tn,
                         interpret=_interpret())


def _swiglu_fwd(gate, up):
    return _swiglu_impl(gate, up), (gate, up)


def _swiglu_bwd(res, dout):
    gate, up = res
    g = gate.astype(jnp.float32)
    sig = jax.lax.logistic(g)
    silu = g * sig
    dsilu = sig * (1 + g * (1 - sig))
    dout32 = dout.astype(jnp.float32)
    dgate = (dout32 * up.astype(jnp.float32) * dsilu).astype(gate.dtype)
    dup = (dout32 * silu).astype(up.dtype)
    return dgate, dup


fused_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


# ----------------------------------------------------------------------------
# token counts (Stage 2) — integer output, no gradient
# ----------------------------------------------------------------------------

def token_counts(indices: jax.Array, num_local: int, offset) -> jax.Array:
    return token_counts_pallas(indices, num_local, offset,
                               interpret=_interpret())


# ----------------------------------------------------------------------------
# flash attention (forward; training uses the pure-JAX blockwise path)
# ----------------------------------------------------------------------------

def ssd_intra_chunk(x, dt, Bm, Cm, A):
    """Mamba-2 SSD intra-chunk stage (see kernels/ssd.py)."""
    from .ssd import ssd_intra_chunk_pallas
    return ssd_intra_chunk_pallas(x, dt, Bm, Cm, A, interpret=_interpret())


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_block: int = 512, kv_block: int = 512) -> jax.Array:
    """q: (B, Sq, nh, hd); k/v: (B, Skv, nkv, hd). GQA kv heads are
    broadcast to nh; heads fold into the batch for the kernel."""
    from .flash_attention import flash_attention_pallas
    B, Sq, nh, hd = q.shape
    Skv, nkv = k.shape[1], k.shape[2]
    if nkv != nh:
        rep = nh // nkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    fold = lambda a: a.transpose(0, 2, 1, 3).reshape(B * nh, a.shape[1], hd)
    out = flash_attention_pallas(fold(q), fold(k), fold(v), causal=causal,
                                 window=window, q_block=q_block,
                                 kv_block=kv_block, interpret=_interpret())
    return out.reshape(B, nh, Sq, hd).transpose(0, 2, 1, 3)

"""Pallas TPU flash attention (forward): blockwise online-softmax attention
with causal and sliding-window masking.

Grid (batch·heads, q_blocks, kv_blocks), kv innermost; the (m, l, acc)
online-softmax state lives in VMEM scratch and persists across the kv sweep
(the output block is revisited consecutively — the sequential-grid pattern
Pallas TPU guarantees). VMEM per step: qb·hd + kb·hd (bf16) + qb·(hd+2) f32
≈ 0.4 MiB at (512, 128) tiles — ample room for double buffering.

This is the TPU-native replacement for the pure-JAX blockwise attention in
repro/models/layers.py (same math — that function doubles as the oracle
harness; ref.py holds the dense reference).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import out_struct

NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, qb: int, kb: int,
                  n_k: int, sq: int, skv: int):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale            # (qb, hd)
    k = k_ref[0].astype(jnp.float32)                    # (kb, hd)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)

    q_pos = i * qb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
    k_pos = j * kb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
    mask = k_pos < skv                                  # kv padding
    if causal:
        mask &= q_pos >= k_pos
    if window > 0:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
        p, v_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == n_k - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           q_block: int = 512, kv_block: int = 512,
                           interpret: bool = False):
    """q: (BH, Sq, hd); k/v: (BH, Skv, hd) — heads pre-flattened (GQA kv
    heads pre-broadcast). Returns (BH, Sq, hd)."""
    from jax.experimental.pallas import tpu as pltpu
    BH, Sq, hd = q.shape
    Skv = k.shape[1]
    qb, kb = min(q_block, Sq), min(kv_block, Skv)
    pq, pk = (-Sq) % qb, (-Skv) % kb
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    n_q, n_k = (Sq + pq) // qb, (Skv + pk) // kb

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=1.0 / math.sqrt(hd),
                          causal=causal, window=window, qb=qb, kb=kb,
                          n_k=n_k, sq=Sq, skv=Skv),
        grid=(BH, n_q, n_k),
        in_specs=[pl.BlockSpec((1, qb, hd), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, kb, hd), lambda b, i, j: (b, j, 0)),
                  pl.BlockSpec((1, kb, hd), lambda b, i, j: (b, j, 0))],
        out_specs=pl.BlockSpec((1, qb, hd), lambda b, i, j: (b, i, 0)),
        out_shape=out_struct((BH, Sq + pq, hd), q.dtype, qp, kp, vp),
        scratch_shapes=[pltpu.VMEM((qb,), jnp.float32),
                        pltpu.VMEM((qb,), jnp.float32),
                        pltpu.VMEM((qb, hd), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :Sq]

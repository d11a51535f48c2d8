"""Core transformer layers: norms, RoPE, GQA attention (full / sliding-window,
train / prefill / decode with KV cache), MLPs.

Functional style: params are plain dict pytrees; every layer is
``init_*(rng, ...) -> params`` + a pure apply function. Activation sharding
constraints are threaded via an optional ``constrain`` callable (see
repro.parallel.sharding).
"""
from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.kernels.ref import slot_decode_attention_ref
from repro.parallel.plan import current_kernel_plan

Constrain = Callable[[jax.Array, str], jax.Array]  # (x, logical_spec_name)

# Probe mode (launch/costmodel.py): forces single-block attention so the
# blockwise scans have trip count 1 and XLA cost analysis (which counts
# while bodies once) is exact. None = use the q_block/kv_block arguments.
ATTN_BLOCK_OVERRIDE = None


# The attention implementation — 'blockwise' (pure-JAX online-softmax; has a
# backward, used for training) | 'pallas' (repro/kernels/flash_attention.py,
# forward-only — serving/prefill on TPU; interpret mode on CPU) — is the
# active KernelPlan's ``attn_impl`` (plan-scoped; no module-global state).
# Tombstone: the PR 4 module-global alias (and its __getattr__ shim) is
# deleted; lint rule SL004 forbids the symbol repo-wide. Scope a plan with
# use_kernel_plan to select an implementation.
def _attn_impl() -> str:
    return current_kernel_plan().attn_impl


def no_constrain(x, _name):
    return x


# ----------------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------------

def init_norm(kind: str, d: int):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def apply_norm(params, x, kind: str = "rmsnorm", eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    if kind == "rmsnorm":
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        out = x * params["scale"]
    else:
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        out = (x - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return checkpoint_name(out.astype(dtype), "norm_out")


# ----------------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------------
# Attention (GQA, full or sliding window)
# ----------------------------------------------------------------------------

def init_attention(rng, cfg) -> dict:
    d, nh, nkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    s = 1.0 / math.sqrt(d)
    return {
        "wq": jax.random.normal(k1, (d, nh * hd), jnp.float32) * s,
        "wk": jax.random.normal(k2, (d, nkv * hd), jnp.float32) * s,
        "wv": jax.random.normal(k3, (d, nkv * hd), jnp.float32) * s,
        "wo": jax.random.normal(k4, (nh * hd, d), jnp.float32) * s,
    }


def _blockwise_attention(q, k, v, *, causal: bool, window: int,
                         q_offset: int | jax.Array = 0,
                         q_block: int = 512, kv_block: int = 512):
    """Flash-style double-blocked attention in pure JAX (online softmax).

    q: (B, Sq, nh, hd); k/v: (B, Skv, nkv, hd). Memory O(B*nh*q_block*kv_block).
    ``q_offset`` is the absolute position of q[0] relative to k[0] (for
    prefill-with-cache / cross-chunk cases). ``window``>0 => sliding window
    (each query attends to keys in (pos-window, pos]).
    """
    B, Sq, nh, hd = q.shape
    Skv, nkv = k.shape[1], k.shape[2]
    groups = nh // nkv
    scale = 1.0 / math.sqrt(hd)

    if ATTN_BLOCK_OVERRIDE is not None:
        q_block = kv_block = ATTN_BLOCK_OVERRIDE
    qb = min(q_block, Sq)
    kb = min(kv_block, Skv)
    nq = -(-Sq // qb)
    nk = -(-Skv // kb)
    Sq_pad, Skv_pad = nq * qb, nk * kb
    q = jnp.pad(q, ((0, 0), (0, Sq_pad - Sq), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, Skv_pad - Skv), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, Skv_pad - Skv), (0, 0), (0, 0)))

    # (B, nkv, groups, nq, qb, hd)
    qr = q.reshape(B, nq, qb, nkv, groups, hd).transpose(0, 3, 4, 1, 2, 5)
    kr = k.reshape(B, nk, kb, nkv, hd).transpose(0, 3, 1, 2, 4)   # (B,nkv,nk,kb,hd)
    vr = v.reshape(B, nk, kb, nkv, hd).transpose(0, 3, 1, 2, 4)

    q_pos = q_offset + jnp.arange(Sq_pad).reshape(nq, qb)
    kv_pos = jnp.arange(Skv_pad).reshape(nk, kb)

    neg = jnp.float32(-1e30)

    # NOTE: both block scans walk a *carried* int32 counter instead of
    # scanning over a jnp.arange xs: an iota-valued scan operand has
    # check-failed the SPMD partitioner inside partial-auto shard_map
    # regions (the per-stage pipeline executor) — "Check failed:
    # sharding.IsManualSubgroup()". A carried counter is bit-identical.
    # The scan bodies open the caller's scope again: JAX re-emits parts of
    # a scan under remat (the recompute, the hoisted masks) with the name
    # stack of where it does so, which drops the scopes around the scan.
    def q_step(qi, _):
        with jax.named_scope("attn"):
            qt = qr[:, :, :, qi].astype(jnp.float32) * scale   # (B,nkv,g,qb,hd)
            qp = q_pos[qi]                                     # (qb,)

            def kv_step(carry, _):
                with jax.named_scope("attn"):
                    m, l, acc, ki = carry
                    kt = kr[:, :, ki].astype(jnp.float32)      # (B,nkv,kb,hd)
                    vt = vr[:, :, ki].astype(jnp.float32)
                    s = jnp.einsum("bngqh,bnkh->bngqk", qt, kt)  # (B,nkv,g,qb,kb)
                    kp = kv_pos[ki]
                    mask = jnp.ones((qb, kb), bool)
                    if causal:
                        mask &= qp[:, None] >= kp[None, :]
                    if window > 0:
                        mask &= qp[:, None] - kp[None, :] < window
                    mask &= (kp < Skv)[None, :]                # kv padding
                    s = jnp.where(mask[None, None, None], s, neg)
                    m_new = jnp.maximum(m, s.max(-1))
                    p = jnp.exp(s - m_new[..., None])
                    corr = jnp.exp(m - m_new)
                    l_new = l * corr + p.sum(-1)
                    acc_new = acc * corr[..., None] + jnp.einsum(
                        "bngqk,bnkh->bngqh", p, vt)
                    return (m_new, l_new, acc_new, ki + 1), None

            m0 = jnp.full((B, nkv, groups, qb), neg)
            l0 = jnp.zeros((B, nkv, groups, qb))
            a0 = jnp.zeros((B, nkv, groups, qb, hd))
            (m, l, acc, _), _ = jax.lax.scan(
                kv_step, (m0, l0, a0, jnp.int32(0)), None, length=nk)
            out = acc / jnp.maximum(l[..., None], 1e-30)
            return qi + 1, out

    _, o = jax.lax.scan(q_step, jnp.int32(0), None,
                        length=nq)                         # (nq,B,nkv,g,qb,hd)
    o = o.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq_pad, nh, hd)
    return o[:, :Sq].astype(q.dtype)


def attention(params, x, cfg, *, constrain: Constrain = no_constrain,
              memory: Optional[jax.Array] = None, causal: bool = True,
              positions: Optional[jax.Array] = None,
              return_kv: bool = False,
              q_block: int = 512, kv_block: int = 512):
    """Self- (or cross-, if ``memory`` given) attention for train/prefill.

    x: (B, S, d). Cross-attention is non-causal over ``memory``.
    ``return_kv`` additionally returns the (k, v) tensors for cache prefill.
    """
    B, S, d = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if positions is None:
        positions = jnp.arange(S)[None, :]
    src = x if memory is None else memory
    q = (x @ params["wq"].astype(x.dtype)).reshape(B, S, nh, hd)
    k = (src @ params["wk"].astype(x.dtype)).reshape(B, src.shape[1], nkv, hd)
    v = (src @ params["wv"].astype(x.dtype)).reshape(B, src.shape[1], nkv, hd)
    q = constrain(q, "act_heads")
    k = constrain(k, "act_kv_heads")
    v = constrain(v, "act_kv_heads")
    if memory is None:  # RoPE only for self-attention
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if _attn_impl() == "pallas":
        from repro.kernels.ops import flash_attention
        o = flash_attention(q, k, v, causal=(causal and memory is None),
                            window=cfg.sliding_window if memory is None else 0,
                            q_block=q_block, kv_block=kv_block).astype(x.dtype)
    else:
        o = _blockwise_attention(
            q, k, v, causal=(causal and memory is None),
            window=cfg.sliding_window if memory is None else 0,
            q_block=q_block, kv_block=kv_block)
    o = checkpoint_name(o, "attn_out")
    out = o.reshape(B, S, nh * hd) @ params["wo"].astype(x.dtype)
    out = constrain(out, "act_btd")
    # post-TP-allreduce activation: saving it under the 'block_sc' SAC policy
    # keeps the backward recompute from replaying the collective
    out = checkpoint_name(out, "attn_proj_out")
    if return_kv:
        return out, (k, v)
    return out


# ---- decode with KV cache ----------------------------------------------------

def init_kv_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    """Ring-buffer cache when sliding_window > 0 (window-sized), else full."""
    size = min(max_len, cfg.sliding_window) if cfg.sliding_window > 0 else max_len
    shape = (batch, size, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def decode_attention(params, x, cache, index, cfg,
                     *, constrain: Constrain = no_constrain):
    """One-token decode. x: (B, 1, d); index: scalar absolute position, or a
    (B,) int32 vector of per-row positions (continuous batching: every cache
    row advances independently; see repro/serve/engine.py).

    Returns (out (B,1,d), new_cache). Sliding-window caches are ring buffers
    indexed by ``position % window`` per row. Writes whose position falls
    outside a full cache are dropped (the row's slot budget is exhausted).
    """
    B, _, d = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (B,))
    q = (x @ params["wq"].astype(x.dtype)).reshape(B, 1, nh, hd)
    k = (x @ params["wk"].astype(x.dtype)).reshape(B, 1, nkv, hd)
    v = (x @ params["wv"].astype(x.dtype)).reshape(B, 1, nkv, hd)
    pos = idx[:, None]
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)

    size = cache["k"].shape[1]
    ring = cfg.sliding_window > 0
    slot = idx % size if ring else idx
    rows = jnp.arange(B)
    ck = cache["k"].at[rows, slot].set(k[:, 0].astype(cache["k"].dtype),
                                       mode="drop")
    cv = cache["v"].at[rows, slot].set(v[:, 0].astype(cache["v"].dtype),
                                       mode="drop")
    new_cache = {"k": ck, "v": cv}

    o = slot_decode_attention_ref(q[:, 0], ck, cv, idx, ring=ring)
    o = o.reshape(B, 1, nh * hd).astype(x.dtype)
    out = o @ params["wo"].astype(x.dtype)
    return constrain(out, "act_btd"), new_cache


# ----------------------------------------------------------------------------
# MLP
# ----------------------------------------------------------------------------

def init_mlp(rng, d: int, d_ff: int, activation: str) -> dict:
    ks = jax.random.split(rng, 3)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(d_ff)
    p = {"up": jax.random.normal(ks[0], (d, d_ff), jnp.float32) * s_in,
         "down": jax.random.normal(ks[1], (d_ff, d), jnp.float32) * s_out}
    if activation == "swiglu":
        p["gate"] = jax.random.normal(ks[2], (d, d_ff), jnp.float32) * s_in
    return p


def apply_mlp(params, x, activation: str,
              constrain: Constrain = no_constrain):
    up = x @ params["up"].astype(x.dtype)
    up = constrain(up, "act_ff")
    if activation == "swiglu":
        gate = constrain(x @ params["gate"].astype(x.dtype), "act_ff")
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up)
    h = checkpoint_name(h, "mlp_hidden")
    out = h @ params["down"].astype(x.dtype)
    return constrain(out, "act_btd")


# ----------------------------------------------------------------------------
# Embedding / LM head
# ----------------------------------------------------------------------------

def init_embedding(rng, vocab: int, d: int) -> dict:
    return {"table": jax.random.normal(rng, (vocab, d), jnp.float32) * 0.02}


def embed(params, tokens, dtype):
    with jax.named_scope("embed"):
        return params["table"].astype(dtype)[tokens]


def unembed(params, x):
    return x @ params["table"].T.astype(x.dtype)

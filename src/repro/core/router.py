"""MoE router: top-k softmax routing, load-balance aux loss, router z-loss,
and FUR (Forced Uniform Routing, paper §2.3).

The router is replicated across EP ranks (paper §3.1: "the experts and the
router ... are divided and replicated among the EP ranks respectively").
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class RouterOut(NamedTuple):
    weights: jax.Array      # (T, K) combine weights
    indices: jax.Array      # (T, K) int32 expert ids
    aux_loss: jax.Array     # scalar: load-balance loss (OLMoE-style)
    z_loss: jax.Array       # scalar: router z-loss


def route(x: jax.Array, router_w: jax.Array, *, num_experts: int, top_k: int,
          forced_uniform: bool = False) -> RouterOut:
    """x: (T, d); router_w: (d, E)."""
    with jax.named_scope("router"):
        T = x.shape[0]
        logits = (x @ router_w.astype(x.dtype)).astype(jnp.float32)   # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)

        if forced_uniform:
            # FUR: every expert receives the same number of tokens in the same
            # pattern — isolates load-imbalance effects from scaling studies.
            t = jnp.arange(T, dtype=jnp.int32)[:, None]
            k = jnp.arange(top_k, dtype=jnp.int32)[None, :]
            indices = (t * top_k + k) % num_experts
            weights = jnp.full((T, top_k), 1.0 / top_k, jnp.float32)
        else:
            weights, indices = jax.lax.top_k(probs, top_k)
            indices = indices.astype(jnp.int32)

        # load-balance auxiliary loss: E * sum_e f_e * p_e  (Switch/OLMoE form)
        one_hot = jax.nn.one_hot(indices, num_experts, dtype=jnp.float32)  # (T,K,E)
        f = one_hot.sum(axis=(0, 1)) / (T * top_k)        # fraction dispatched
        p = probs.mean(axis=0)                            # mean router prob
        aux = num_experts * jnp.sum(f * p)
        z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
        return RouterOut(weights, indices, aux, z)

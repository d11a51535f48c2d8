"""Weights from the seed, made by the benchmark and not by the program.

The layout is the training program's parameter tree (its checkpoint
format): ``embed``/``head`` tables padded to a multiple of 256 rows,
``final_norm``, and one stacked ``layers`` group whose leading axis is the
layer. The harness checks this layout against the program's own before a
run, so a change of format stops the run instead of feeding it wrong
weights. Every leaf is drawn from its own key, so one leaf can be made
again on its own (``leaf``) and equals the one that ``make`` gives.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

VOCAB_ALIGN = 256


def padded_vocab(c: dict) -> int:
    return -(-c["vocab_size"] // VOCAB_ALIGN) * VOCAB_ALIGN


def layout(c: dict) -> list:
    """[(path, shape, scale)] in the order of ``jax.tree.leaves`` of the
    nested tree; scale None means ones."""
    d, L, vp = c["d_model"], c["num_layers"], padded_vocab(c)
    q = c["num_heads"] * c["head_dim"]
    kv = c["num_kv_heads"] * c["head_dim"]
    s = 1.0 / math.sqrt(d)
    out = [
        (("embed", "table"), (vp, d), 0.02),
        (("final_norm", "scale"), (d,), None),
        (("head", "table"), (vp, d), 0.02),
        (("layers", "attn", "wk"), (L, d, kv), s),
        (("layers", "attn", "wo"), (L, q, d), s),
        (("layers", "attn", "wq"), (L, d, q), s),
        (("layers", "attn", "wv"), (L, d, kv), s),
        (("layers", "ln1", "scale"), (L, d), None),
        (("layers", "ln2", "scale"), (L, d), None),
    ]
    if c["arch_type"] == "moe":
        e, f = c["num_experts"], c["d_ff_expert"]
        out += [
            (("layers", "moe", "down"), (L, e, f, d), 1.0 / math.sqrt(f)),
            (("layers", "moe", "gate"), (L, e, d, f), s),
            (("layers", "moe", "router"), (L, d, e), s),
            (("layers", "moe", "up"), (L, e, d, f), s),
        ]
    else:
        f = c["d_ff"]
        out += [
            (("layers", "mlp", "down"), (L, f, d), 1.0 / math.sqrt(f)),
            (("layers", "mlp", "gate"), (L, d, f), s),
            (("layers", "mlp", "up"), (L, d, f), s),
        ]
    return out


def seed_words(seed: int) -> jax.Array:
    """Any integer seed (64-bit included) as two uint32 words, passed to
    the jitted makers as an argument so that one program serves every
    seed."""
    s = int(seed) & (2 ** 64 - 1)
    return jnp.array([s & 0xFFFFFFFF, s >> 32], jnp.uint32)


def _key(words, index: int):
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, words[0])
    k = jax.random.fold_in(k, words[1])
    return jax.random.fold_in(k, index)


def leaf(c: dict, words, index: int) -> jax.Array:
    """The float32 leaf number ``index`` of ``layout(c)``."""
    _, shape, scale = layout(c)[index]
    if scale is None:
        return jnp.ones(shape, jnp.float32)
    return jax.random.normal(_key(words, index), shape, jnp.float32) * scale


def nest(c: dict, leaves) -> dict:
    """Nested dict of the program's tree from leaves in ``layout`` order."""
    tree: dict = {}
    for (path, _, _), x in zip(layout(c), leaves):
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = x
    return tree


def make(c: dict, words) -> dict:
    """Every float32 leaf, nested as the program's tree."""
    return nest(c, [leaf(c, words, i) for i in range(len(layout(c)))])

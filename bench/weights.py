"""Weights from the seed, made by the benchmark and not by the program.

The layout is the training program's parameter tree (its checkpoint
format), as the configuration's plain reference states it (``layout`` of
``bench/configs/<reference>.py``). The harness checks this layout against
the program's own before a run, so a change of format stops the run
instead of feeding it wrong weights. Every leaf is drawn from its own key,
so one leaf can be made again on its own (``leaf``) and equals the one
that ``make`` gives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import configs


def layout(c: dict) -> list:
    """[(path, shape, scale)] in the order of ``jax.tree.leaves`` of the
    nested tree; scale None means ones. The one place that takes the layout
    from the reference: ``make``, the program's layout check and the
    parameter count of ``bench/scopes.py`` all read it here."""
    return configs.reference(c).layout(c)


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

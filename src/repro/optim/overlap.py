"""Overlapped SO/EPSO optimizer update — the EPSO step-time fix.

The eager path (train/trainer.py tail + optim/adamw.py) leaves the paper's
reduce-scatter/all-gather entirely to GSPMD: the global-norm clip forces a
full gradient reduction, every state leaf gets its own derived reshard, and
the updated-param all-gathers land one-per-leaf on the critical path after
the last backward op — the committed ``BENCH_epso.json`` regression (EPSO
~17% slower than unsharded despite the 4.9x state-bytes win).

This module replaces that tail with an explicit bucket schedule executed in
one fully-manual ``shard_map`` region over the whole mesh:

* gradients enter the region under the *state* specs — GSPMD lowers the
  placement mismatch to a reduce-scatter, so each device receives exactly
  its 1/N update shard and never materializes replicated gradients;
* the global grad-norm is computed from the shards: per-leaf local square
  sums, one scalar ``psum`` per distinct state-axis set — the full-tensor
  norm compute and its implied all-reduce disappear;
* each shard runs the identical elementwise AdamW (``adamw_leaf``) on its
  slice of every leaf in the bucket;
* the updated master shards are cast to the param dtype, flattened, and
  concatenated into ONE buffer per bucket, which is all-gathered over the
  bucket's extra axes — either a hierarchical ``ppermute`` ring
  (``impl='ring'``: n-1 neighbor exchanges per axis, the pattern async
  backends pipeline bucket-by-bucket against backward compute) or a single
  ``lax.all_gather`` (``impl='xla'``: the fallback where the ring pattern is
  unsupported or the backend's native all-gather is already async);
* the gathered buffer is split and reassembled into the param-local leaves.

Because buckets only depend on their own leaves' gradient shards (plus the
one clip scalar), the scheduler is free to start a bucket's gather while
other buckets (and, on async backends, the tail of backward) are still
computing — nothing serializes on a single whole-tree gather.

Expert placement (parallel/placement.py): a live EP rebalance permutes the
expert stacks (and, via ``epso.permute_expert_states``, master/m/v) along
their existing expert dim — shapes and specs are unchanged, so the bucket
schedule (``UpdatePlan``) and this region's lowering are placement-
invariant; the rebuilt step after a rebalance re-plans to the identical
buckets (pinned by tests/test_placement.py). Expert-stack leaves take a
*canonical* grad-norm path (``expert_norm``): per-(layer, expert) slice
sums gathered into a replicated (L, E) table, reordered to global-id
order, reduced in fixed order — so the clip scale is bit-identical across
a rebalance even though the shard-local partials regroup. The update
math is ``adamw_leaf`` with the same clip/LR scalars as the eager path; the
only numerical difference is the non-expert grad-norm's reduction order
(shard-wise partial sums instead of whole-leaf sums), so eager and
overlapped updates agree to ~1 ulp and checkpoint resume stays
bit-identical.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.optim.adamw import AdamWState, adamw_leaf
from repro.optim.epso import (DEFAULT_BUCKET_BYTES, UpdatePlan, _entry_axes,
                              optimizer_state_specs, plan_update_buckets,
                              update_axis_order)
from repro.parallel.sharding import param_specs

OVERLAP_IMPLS = ("off", "ring", "xla")


def resolve_opt_overlap(setting: Optional[str], mode: str, mesh) -> str:
    """Resolve an ``opt_overlap`` request to 'off' | 'ring' | 'xla'.

    ``None``/'auto' turns the overlap on (ring) for ``epso`` on a real mesh
    with update axes — the mode whose collectives regressed — and leaves
    'so' eager as the parity baseline. Explicit 'ring'/'xla' require a
    sharded optimizer mode and a mesh; explicit 'off' always wins.
    """
    s = "auto" if setting is None else str(setting)
    if s == "off":
        return "off"
    has_axes = mesh is not None and bool(update_axis_order(mesh))
    if s == "auto":
        return "ring" if (mode == "epso" and has_axes) else "off"
    if s not in ("ring", "xla"):
        raise ValueError(f"opt_overlap must be one of "
                         f"{('auto',) + OVERLAP_IMPLS}, got {setting!r}")
    if mode not in ("so", "epso"):
        raise ValueError(f"opt_overlap={s!r} needs opt_shard in "
                         f"{{'so','epso'}} (got {mode!r}): the overlap "
                         f"schedules the sharded-state collectives")
    if not has_axes:
        raise ValueError(f"opt_overlap={s!r} needs a mesh with update axes "
                         f"(pod/data/model/ep/tp)")
    return s


def _ring_all_gather(flat, axes, coords, axis_sizes):
    """Hierarchical ppermute ring over ``axes`` (canonical rank order).

    Gathers the minor-most axis first; after each level every shard holds
    that level's full ring reordered to rank order (roll by own coord), so
    the final leading dim enumerates shards major-to-minor over ``axes`` —
    the same linearization a GSPMD tuple spec uses.
    """
    cur = flat[None]                            # (1, S)
    for a in reversed(axes):
        n = axis_sizes[a]
        if n == 1:
            continue
        perm = [(s, (s - 1) % n) for s in range(n)]
        parts = [cur]
        p = cur
        for _ in range(n - 1):
            p = jax.lax.ppermute(p, a, perm)
            parts.append(p)                     # parts[k] = shard (r+k) % n
        stacked = jnp.roll(jnp.stack(parts), coords[a], axis=0)
        cur = stacked.reshape((n * cur.shape[0],) + cur.shape[1:])
    return cur                                  # (prod(axes), S)


def _assemble_leaf(seg, bucket_axes, leaf, blk_shape, axis_sizes):
    """Post-gather reassembly: (N, *blk) -> param-local leaf, moving each
    rank-index axis next to the dim it split (spec major-to-minor order,
    matching the state spec's tiling) and merging."""
    sizes = tuple(axis_sizes[a] for a in bucket_axes)
    t = seg.reshape(sizes + blk_shape)
    k = len(sizes)
    added = dict(leaf.added)
    perm, out_shape = [], []
    for d in range(len(blk_shape)):
        mult = 1
        for a in added.get(d, ()):
            perm.append(bucket_axes.index(a))
            mult *= axis_sizes[a]
        perm.append(k + d)
        out_shape.append(mult * blk_shape[d])
    return t.transpose(perm).reshape(out_shape)


def overlapped_adamw_update(grads, state: AdamWState, *, rules, mode: str,
                            impl: str = "ring", lr, beta1=0.9, beta2=0.99,
                            eps=1e-8, weight_decay=0.1, grad_clip=1.0,
                            clip_enabled=None, param_dtype=jnp.float32,
                            update_plan: Optional[UpdatePlan] = None,
                            max_bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                            expert_norm=None):
    """Drop-in replacement for ``adamw_update`` with bucketed, overlappable
    collectives. Same signature plus ``rules``/``mode``/``impl`` and an
    optional precomputed ``update_plan`` (built once at step-build time).
    ``expert_norm`` is the ``(mask, inv)`` pair from
    ``adamw.global_norm``: flagged expert-stack leaves contribute to the
    grad-norm via per-(layer, expert) slice sums gathered to a replicated
    (L, E) table, reordered to global-id order, and reduced in fixed order —
    the same association the eager path uses, and invariant under live
    expert placement, so the clip scale cannot drift across a rebalance.
    Returns (new_params(param_dtype), new_state, metrics) with identical
    semantics; see the module docstring for the one numerical difference
    (grad-norm reduction order on non-expert leaves)."""
    if impl not in ("ring", "xla"):
        raise ValueError(f"impl must be 'ring' or 'xla', got {impl!r}")
    mesh = rules.mesh
    if update_plan is None:
        update_plan = plan_update_buckets(grads, rules, mode,
                                          max_bucket_bytes=max_bucket_bytes)
    axis_sizes = dict(mesh.shape)

    step = state.step + 1
    t = step.astype(jnp.float32)
    bc1 = 1.0 - beta1 ** t
    bc2 = 1.0 - beta2 ** t

    flat_g, treedef = jax.tree.flatten(grads)
    flat_ma = jax.tree.leaves(state.master)
    flat_m = jax.tree.leaves(state.m)
    flat_v = jax.tree.leaves(state.v)
    pspecs = tuple(jax.tree.leaves(param_specs(grads, rules)))
    ospecs = tuple(jax.tree.leaves(
        optimizer_state_specs(grads, rules, mode)))
    n = len(flat_g)
    assert update_plan.n_leaves == n, (update_plan.n_leaves, n)

    ex_mask = expert_norm[0] if expert_norm is not None else ()
    expert_ids = frozenset(i for i, m in enumerate(ex_mask) if m)
    inv_const = None
    if expert_norm is not None and expert_norm[1] is not None:
        inv_const = jnp.asarray(expert_norm[1], jnp.int32)

    all_leaves = [lf for b in update_plan.buckets for lf in b.leaves]
    norm_groups = {}          # psum axis set -> leaf indices (non-expert)
    expert_leaves = []        # canonical slice-sum norm path (global order)
    for lf in all_leaves:
        if lf.index in expert_ids:
            expert_leaves.append(lf)
        else:
            norm_groups.setdefault(lf.psum_axes, []).append(lf.index)
    expert_leaves.sort(key=lambda lf: lf.index)

    def region(gs, ma, mo, vo, scalars):
        lrv, b1c, b2c, clip_on = scalars
        coords = {a: jax.lax.axis_index(a) for a in update_plan.axes} \
            if impl == "ring" else {}
        # global grad norm from the shards: one scalar psum per distinct
        # state-axis set (shards tile the tensor exactly over those axes)
        total = jnp.zeros((), jnp.float32)
        for axes, idxs in sorted(norm_groups.items()):
            loc = jnp.zeros((), jnp.float32)
            for i in idxs:
                loc = loc + jnp.sum(jnp.square(gs[i].astype(jnp.float32)))
            total = total + (jax.lax.psum(loc, axes) if axes else loc)
        # expert stacks: per-(L, E)-slice sums, un-sharded to a replicated
        # (L, E) table (gather over the axes tiling dims 0/1, psum over the
        # axes tiling the trailing dims), reordered to global-id order, then
        # one fixed-order reduction — placement moves slices between ranks
        # but never changes the association, so gnorm (and the clip scale)
        # is bit-identical across a live rebalance
        for lf in expert_leaves:
            i = lf.index
            s = jnp.sum(jnp.square(gs[i].astype(jnp.float32)),
                        axis=tuple(range(2, gs[i].ndim)))
            spec = ospecs[i]
            lead = []
            for d in (0, 1):
                ent = spec[d] if d < len(spec) else None
                for a in reversed(_entry_axes(ent)):
                    s = jax.lax.all_gather(s, a, axis=d, tiled=True)
                    lead.append(a)
            trail = tuple(a for a in lf.psum_axes if a not in lead)
            if trail:
                s = jax.lax.psum(s, trail)
            if inv_const is not None:
                s = jnp.take_along_axis(s, inv_const, axis=1)
            total = total + jnp.sum(s)
        gnorm = jnp.sqrt(total)
        if grad_clip <= 0:
            sc = jnp.float32(1.0)
        else:
            sc = jnp.where(gnorm > grad_clip,
                           grad_clip / (gnorm + 1e-12), 1.0)
            sc = jnp.where(clip_on, sc, 1.0)

        new_p = [None] * n
        new_ma = [None] * n
        new_m = [None] * n
        new_v = [None] * n
        for bucket in update_plan.buckets:
            pieces, blk_shapes = [], []
            for leaf in bucket.leaves:
                i = leaf.index
                nma, nm2, nv2 = adamw_leaf(
                    gs[i], ma[i], mo[i], vo[i], scale=sc, lr=lrv, bc1=b1c,
                    bc2=b2c, beta1=beta1, beta2=beta2, eps=eps,
                    weight_decay=weight_decay)
                new_ma[i], new_m[i], new_v[i] = nma, nm2, nv2
                if bucket.axes:
                    pieces.append(nma.astype(param_dtype).reshape(-1))
                    blk_shapes.append(nma.shape)
                else:
                    new_p[i] = nma.astype(param_dtype)
            if not bucket.axes:
                continue
            flat = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
            if impl == "ring":
                full = _ring_all_gather(flat, bucket.axes, coords, axis_sizes)
            else:
                full = jax.lax.all_gather(flat, bucket.axes)
            off = 0
            for leaf, blk in zip(bucket.leaves, blk_shapes):
                sz = 1
                for d in blk:
                    sz *= d
                seg = full[:, off:off + sz].reshape((full.shape[0],) + blk)
                new_p[leaf.index] = _assemble_leaf(
                    seg, bucket.axes, leaf, blk, axis_sizes)
                off += sz
        return (tuple(new_p), tuple(new_ma), tuple(new_m), tuple(new_v),
                gnorm, sc)

    scal_specs = (P(), P(), P(), P())
    # grads enter under the STATE specs: GSPMD lowers the mismatch against
    # the backward's partial sums to a reduce-scatter (the paper's grad RS)
    # check_vma=False: the ring all-gather's outputs are replicated by
    # construction, but ppermute chains cannot be typed invariant; the
    # overlapped-vs-eager goldens in tests/test_opt_overlap.py are the check
    fn = jax.shard_map(
        region, mesh=mesh,
        in_specs=(ospecs, ospecs, ospecs, ospecs, scal_specs),
        out_specs=(pspecs, ospecs, ospecs, ospecs, P(), P()),
        check_vma=False)
    clip_arg = jnp.asarray(True if clip_enabled is None else clip_enabled)
    scalars = (jnp.asarray(lr, jnp.float32),
               jnp.asarray(bc1, jnp.float32),
               jnp.asarray(bc2, jnp.float32), clip_arg)
    new_p, new_ma, new_m, new_v, gnorm, scale = fn(
        tuple(flat_g), tuple(flat_ma), tuple(flat_m), tuple(flat_v), scalars)
    new_params = treedef.unflatten(list(new_p))
    new_state = AdamWState(step, treedef.unflatten(list(new_ma)),
                           treedef.unflatten(list(new_m)),
                           treedef.unflatten(list(new_v)))
    metrics = {"grad_norm": gnorm, "clip_scale": scale}
    return new_params, new_state, metrics

"""FastSparseMoE — the paper's §3.1 five-stage MoE block, adapted to TPU.

Three execution paths (DESIGN §4), all computing the same math:

* ``naive``          — HF-OLMoE-equivalent baseline: every expert processes
                       every token, one-hot combine. O(E/K) extra compute.
* ``dense_capacity`` — sort-based dispatch into a shared capacity pool,
                       grouped expert compute. Pure XLA, auto-shardable.
* ``fsmoe``          — the paper-faithful five-stage pipeline under EP:
      Stage 1  token communication: all_gather(x, weights, indices) over the
               EP mesh axis (paper: allgather beats all2all thanks to the
               regular communication pattern); its backward is the paper's
               reduce-scatter.
      Stage 2  token counting: per-local-expert histogram (Pallas kernel or
               XLA bincount).
      Stage 3  index generation: argsort of the flattened local expert ids
               reproduces the paper's (input_indices, output_indices) with
               static shapes — the TPU adaptation of the atomic-counter GPU
               kernels (DESIGN §3).
      Stage 4  expert computation: merged expert weights + grouped matmul
               over a ragged-aligned slot pool (Pallas gmm, or an
               expert-masked batched contraction on the XLA path).
      Stage 5  output reduction: weighted combine of the K expert rows per
               token (Pallas combine kernel or XLA einsum), then
               psum_scatter over the EP axis.

Dispatch modes (``MoEConfig.dispatch``): routed-token buffers are always
static.

* ``capacity`` — ``capacity_factor`` sizes a shared slot pool; per-expert
  group offsets are count-aligned, so imbalance is absorbed by the pool
  rather than per-expert truncation; tokens past the pool are dropped.
  cf >= E/K guarantees zero drops; FUR is dropless at cf >= 1.
* ``dropless`` — the pool is sized for the worst-case routing
  (``dropless_pool_rows``: all T*K pairs to one expert still fit), groups
  are always count-aligned ragged (the grouped-matmul layout), and the
  result is exactly the naive math for ANY routing — independent of
  capacity_factor and of pool-geometry knobs like ``c_align``, which is
  what makes pp=1 and pp>1 losses bit-comparable at any batch shape. The
  Pallas grouped matmuls stop at the routed rows (``sum(group_sizes)``),
  so the worst-case tail costs them nothing; the dispatch gather, the
  SwiGLU and the combine still span the whole pool.

Every path reports ``MoeStats`` (per-expert activation counts, drop
count, and the rows of its slot pool and of its grouped matmuls) so the
train step can surface routing telemetry.

Expert placement (``parallel/placement.py``): every path takes an
optional ``placement`` — the (E,) *inverse* permutation row mapping
global expert id -> placed position. The stacked expert weights are
stored in placed order (position p holds global expert perm[p]), the
router keeps producing global ids, and dispatch translates
``indices -> placement[indices]`` so each token reaches the position
hosting its expert; reported ``MoeStats.counts`` are translated back
(``counts_pos[placement]``) so telemetry stays in global expert order.
Router weights and shared experts are never permuted.
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .router import RouterOut, route


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def stage45_backend(moe_cfg) -> str:
    """Stage-4/5 (grouped FFN + combine) backend. The active KernelPlan
    wins when it names a concrete backend ('xla' | 'pallas'); under its
    'ref' default the per-config ``kernel_backend`` knob decides — so a
    plan can retarget the kernels without touching the model config."""
    from repro.parallel.plan import current_kernel_plan
    kp = current_kernel_plan()
    if kp.backend != "ref":
        return kp.moe_backend
    return moe_cfg.kernel_backend


# ----------------------------------------------------------------------------
# params
# ----------------------------------------------------------------------------

def init_moe_block(rng, cfg) -> dict:
    """Stacked (merged) expert weights — paper Stage 4 merges per-rank expert
    weights into single tensors to enable grouped GEMM."""
    d, m = cfg.d_model, cfg.moe
    e, f = m.num_experts, m.d_ff_expert
    ks = jax.random.split(rng, 5)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * s_in,
        "gate": jax.random.normal(ks[1], (e, d, f), jnp.float32) * s_in,
        "up": jax.random.normal(ks[2], (e, d, f), jnp.float32) * s_in,
        "down": jax.random.normal(ks[3], (e, f, d), jnp.float32) * s_out,
    }
    if m.num_shared_experts:
        fs = f * m.num_shared_experts
        kss = jax.random.split(ks[4], 3)
        p["shared"] = {
            "gate": jax.random.normal(kss[0], (d, fs), jnp.float32) * s_in,
            "up": jax.random.normal(kss[1], (d, fs), jnp.float32) * s_in,
            "down": jax.random.normal(kss[2], (fs, d), jnp.float32) * s_out,
        }
    return p


def _shared_expert(p, x):
    sp = p["shared"]
    h = jax.nn.silu(x @ sp["gate"].astype(x.dtype)) * (x @ sp["up"].astype(x.dtype))
    return h @ sp["down"].astype(x.dtype)


# ----------------------------------------------------------------------------
# naive baseline (HF-style: all experts compute all tokens)
# ----------------------------------------------------------------------------

def moe_naive(p, x, moe_cfg, placement=None) -> tuple[jax.Array, RouterOut]:
    r = route(x, p["router"], num_experts=moe_cfg.num_experts,
              top_k=moe_cfg.experts_per_token,
              forced_uniform=moe_cfg.forced_uniform_routing)

    def one(gate, up, down):
        h = jax.nn.silu(x @ gate) * (x @ up)
        return h @ down

    ys = jax.vmap(one)(p["gate"].astype(x.dtype), p["up"].astype(x.dtype),
                       p["down"].astype(x.dtype))           # (E, T, d)
    # combine indexes stored (placed) positions; r keeps global ids
    idx = r.indices if placement is None else placement[r.indices]
    one_hot = jax.nn.one_hot(idx, moe_cfg.num_experts, dtype=x.dtype)
    cw = (one_hot * r.weights[..., None].astype(x.dtype)).sum(1)  # (T, E)
    out = jnp.einsum("te,etd->td", cw, ys)
    if moe_cfg.num_shared_experts:
        out = out + _shared_expert(p, x)
    return out, r


# ----------------------------------------------------------------------------
# Stages 2+3: token counting + sort-based index generation
# ----------------------------------------------------------------------------

class DispatchPlan(NamedTuple):
    slot: jax.Array          # (T*K,) destination row in the slot pool (OOB=pool_rows)
    valid: jax.Array         # (T*K,) bool — False = dropped or non-local
    counts: jax.Array        # (EL,) exact tokens routed per local expert
    group_sizes: jax.Array   # (EL,) aligned slot-pool group sizes
    pool_rows: int           # static slot-pool size
    drops: jax.Array         # scalar: number of dropped (over-capacity) pairs


class MoeStats(NamedTuple):
    """Per-layer routing telemetry. Every field is float32 (not int) so it
    rides through vjp/scan/psum alongside the loss scalars with zero
    cotangents. The row counts are summed over the chips that dispatch
    distinct tokens."""
    counts: jax.Array        # (E,) routed (t, k) pairs per global expert
    drops: jax.Array         # () pairs dropped over capacity (0 when dropless)
    pool_rows: jax.Array     # () slot-pool rows (dispatch, SwiGLU, combine)
    gemm_rows: jax.Array     # () rows the grouped matmuls compute

    @classmethod
    def zero(cls, num_experts: int) -> "MoeStats":
        z = jnp.zeros((), jnp.float32)
        return cls(jnp.zeros((num_experts,), jnp.float32), z, z, z)

    def __add__(self, other: "MoeStats") -> "MoeStats":
        return MoeStats(*(a + b for a, b in zip(self, other)))


def plan_stats(plan: "DispatchPlan", backend: str) -> MoeStats:
    """One dispatch's MoeStats, before any reduction over chips: the
    Pallas grouped matmuls visit only the routed groups' tiles
    (``sum(group_sizes)``), the XLA backends compute every pool row."""
    gemm = jnp.sum(plan.group_sizes) if backend == "pallas" else \
        plan.pool_rows
    return MoeStats(plan.counts.astype(jnp.float32),
                    plan.drops.astype(jnp.float32),
                    jnp.float32(plan.pool_rows),
                    jnp.asarray(gemm, jnp.float32))


def make_dispatch_plan(indices: jax.Array, *, num_experts: int,
                       pool_rows: int, align: int = 8,
                       expert_offset=0, local_experts: int = 0,
                       uniform_capacity: bool = False) -> DispatchPlan:
    """Sort-based index generation (paper Stage 3, DESIGN §3).

    indices: (T, K) global expert ids. When ``local_experts`` > 0, only
    experts in [expert_offset, expert_offset + local_experts) are dispatched
    (the EP case); others sort to the sentinel end and are masked out.
    ``expert_offset`` may be a traced scalar (lax.axis_index under EP).

    ``uniform_capacity``: every expert gets exactly pool_rows/EL slots
    (GShard-style — the XLA backend reshapes the pool to (EL, C, d) for a
    batched einsum). False: count-aligned ragged offsets sharing the pool
    (the Pallas gmm backend's group-aligned layout — absorbs imbalance).
    """
    with jax.named_scope("dispatch"):
        T, K = indices.shape
        EL = local_experts or num_experts
        flat = indices.reshape(-1).astype(jnp.int32) - expert_offset
        local = (flat >= 0) & (flat < EL)
        key = jnp.where(local, flat, EL).astype(jnp.int32)    # non-local -> sentinel
        order = jnp.argsort(key, stable=True)                 # (T*K,)
        sorted_key = key[order]

        counts_all = jnp.bincount(key, length=EL + 1)         # Stage 2 histogram
        counts = counts_all[:EL].astype(jnp.int32)
        if uniform_capacity:
            cap = pool_rows // EL
            group_sizes = jnp.full((EL,), cap, jnp.int32)
            offsets = (jnp.arange(EL + 1) * cap).astype(jnp.int32)
        else:
            gs_aligned = ((counts + align - 1) // align) * align
            cum = jnp.minimum(jnp.cumsum(gs_aligned), pool_rows)
            offsets = jnp.concatenate([jnp.zeros((1,), cum.dtype), cum])  # (EL+1,)
            group_sizes = (offsets[1:] - offsets[:-1]).astype(jnp.int32)

        # position of each sorted element within its expert group
        starts = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             jnp.cumsum(counts_all)[:-1].astype(jnp.int32)])             # (EL+1,)
        pos_sorted = jnp.arange(T * K, dtype=jnp.int32) - starts[sorted_key]

        safe_key = jnp.minimum(sorted_key, EL - 1)
        slot_sorted = offsets[safe_key].astype(jnp.int32) + pos_sorted
        valid_sorted = (sorted_key < EL) & (pos_sorted < group_sizes[safe_key])
        slot_sorted = jnp.where(valid_sorted, slot_sorted, pool_rows)    # OOB

        slot = jnp.zeros((T * K,), jnp.int32).at[order].set(slot_sorted)
        valid = jnp.zeros((T * K,), bool).at[order].set(valid_sorted)
        drops = jnp.sum(local) - jnp.sum(valid_sorted)
        return DispatchPlan(slot, valid, counts, group_sizes, int(pool_rows), drops)


def pool_size(tokens: int, top_k: int, num_experts: int, local_experts: int,
              capacity_factor: float, align: int = 8) -> int:
    """Static slot-pool rows for one EP shard."""
    expected = tokens * top_k * local_experts / num_experts
    return round_up(int(math.ceil(capacity_factor * expected)) + align *
                    local_experts, align)


def dropless_pool_rows(tokens: int, top_k: int, local_experts: int,
                       align: int = 8) -> int:
    """Slot-pool rows guaranteeing zero drops for ANY routing: even if one
    expert receives every local (t, k) pair its aligned group still fits,
    and the ``align * EL`` slack absorbs per-group alignment padding
    (each group rounds up by < align rows)."""
    return round_up(tokens * top_k, align) + align * local_experts


# ----------------------------------------------------------------------------
# Stage 4: grouped expert FFN — XLA and Pallas backends
# ----------------------------------------------------------------------------

def grouped_ffn(gate_w, up_w, down_w, pool_x, group_sizes, backend: str,
                constrain=None):
    """pool_x: (M, d) rows grouped by expert; w: (EL, d, f)/(EL, f, d).

    backend 'pallas': ragged grouped-matmul kernels (paper Stage 4).
    backend 'xla'   : uniform-capacity batched einsum (GShard-style) —
                      reshape (EL, C, d); exact-FLOP XLA lowering.
    backend 'ragged': count-ragged groups via an expert-masked batched
                      contraction (costs EL dense matmuls, same as XLA's
                      CPU lowering of lax.ragged_dot).
    """
    with jax.named_scope("ffn"):
        cons = constrain or (lambda x, n: x)
        if backend == "pallas":
            from repro.kernels.ops import gmm, fused_swiglu
            g = gmm(pool_x, gate_w.astype(pool_x.dtype), group_sizes)
            u = gmm(pool_x, up_w.astype(pool_x.dtype), group_sizes)
            h = fused_swiglu(g, u)
            h = checkpoint_name(h, "moe_hidden")
            return gmm(h, down_w.astype(pool_x.dtype), group_sizes)
        if backend == "ragged":
            # NOT lax.ragged_dot: XLA's SPMD partitioner rewrites ragged_dot's
            # group_sizes operand into per-shard windows when the expert dim is
            # sharded, and the rewritten values leak into every OTHER consumer
            # of group_sizes (negative sizes -> phantom drops, diverged loss on
            # any mesh with an ep/tp axis). A 0/1 expert mask partitions like
            # any einsum and adds exact zeros, so the values are unchanged.
            EL = gate_w.shape[0]
            ends = jnp.cumsum(group_sizes)
            e_row = jnp.searchsorted(ends, jnp.arange(pool_x.shape[0]),
                                     side="right")          # slack rows -> EL
            oh = jax.nn.one_hot(e_row, EL, dtype=pool_x.dtype)      # (M, EL)

            def masked(h, w, sub):                          # h:(M,a) w:(EL,a,b)
                return jnp.einsum(f"em{sub[-1]},me->m{sub[-1]}",
                                  jnp.einsum(f"m{sub[0]},e{sub}->em{sub[-1]}",
                                             h, w.astype(pool_x.dtype)), oh)

            g = masked(pool_x, gate_w, "df")
            u = masked(pool_x, up_w, "df")
            h = jax.nn.silu(g) * u
            h = checkpoint_name(h, "moe_hidden")
            return masked(h, down_w, "fd")
        # 'xla': uniform capacity — (EL, C, d) batched matmul
        EL = gate_w.shape[0]
        M, d = pool_x.shape
        C = M // EL
        xb = cons(pool_x.reshape(EL, C, d), "moe_pool")
        g = jnp.einsum("ecd,edf->ecf", xb, gate_w.astype(pool_x.dtype))
        u = jnp.einsum("ecd,edf->ecf", xb, up_w.astype(pool_x.dtype))
        h = cons(jax.nn.silu(g) * u, "moe_hidden")
        h = checkpoint_name(h, "moe_hidden")
        out = jnp.einsum("ecf,efd->ecd", h, down_w.astype(pool_x.dtype))
        return out.reshape(M, d)


# ----------------------------------------------------------------------------
# Stages 2-5 on one shard
# ----------------------------------------------------------------------------

def dispatch_compute_combine(gate_w, up_w, down_w, x, r: RouterOut, moe_cfg,
                             *, expert_offset=0, local_experts: int = 0,
                             backend: str = "xla", constrain=None,
                             c_align: int = 1, pool_rows=None,
                             dropless: bool = False):
    """x: (T, d) tokens (already gathered under EP); expert weights are the
    *local* slices (EL experts). Returns (partial out (T, d), plan).

    ``c_align``: make the per-expert capacity C divisible by this (the
    batch-shard count, so the (EL, C, d) pool can shard its C dim).
    ``pool_rows``: explicit slot-pool size (a2a path supplies its own).
    ``dropless``: size the pool for the worst-case routing and use the
    count-aligned ragged layout — no drops, and the pool geometry knobs
    (capacity_factor, c_align, pool_rows) are ignored, so the result is
    naive-exact regardless of executor."""
    T, d = x.shape
    K = moe_cfg.experts_per_token
    E = moe_cfg.num_experts
    EL = local_experts or E
    align = 8
    if backend == "pallas":
        from repro.kernels.ops import gmm_align
        align = gmm_align()   # Pallas gmm needs tile_m-aligned groups
    if dropless:
        # worst-case pool; the uniform-capacity (EL, C, d) reshape cannot be
        # statically dropless, so the XLA backend computes through the
        # ragged (expert-masked) grouped matmul
        rows = dropless_pool_rows(T, K, EL, align=align)
        uniform = False
    else:
        rows = pool_rows if pool_rows is not None else \
            pool_size(T, K, E, EL, moe_cfg.capacity_factor, align=align)
        rows = round_up(rows, EL * align * max(c_align, 1))  # EL uniform groups
        uniform = backend == "xla"
    plan = make_dispatch_plan(r.indices, num_experts=E, pool_rows=rows,
                              expert_offset=expert_offset, local_experts=EL,
                              align=align, uniform_capacity=uniform)
    if dropless and backend == "xla":
        backend = "ragged"
    if backend == "pallas":
        # Stage 2 on the Pallas path: histogram computed in-kernel; checked
        # against the plan's bincount by tests. (Same values; plan drives
        # index generation either way.)
        pass

    # inverse map: pool row -> source token (paper: mlp_in = input[input_indices])
    with jax.named_scope("dispatch"):
        tok_flat = jnp.arange(T * K, dtype=jnp.int32) // K
        inv_token = jnp.zeros((rows,), jnp.int32).at[plan.slot].set(
            tok_flat, mode="drop")
        pool_valid = jnp.zeros((rows,), bool).at[plan.slot].set(
            plan.valid, mode="drop")
        pool_x = x[inv_token] * pool_valid[:, None].astype(x.dtype)
        pool_x = checkpoint_name(pool_x, "moe_dispatch")

    pool_y = grouped_ffn(gate_w, up_w, down_w, pool_x, plan.group_sizes,
                         backend, constrain=constrain)

    # ---- Stage 5: weighted combine --------------------------------------
    with jax.named_scope("combine"):
        safe_slot = jnp.minimum(plan.slot, rows - 1)
        yk = pool_y[safe_slot] * plan.valid[:, None].astype(pool_y.dtype)
        yk = yk.reshape(T, K, d)
        if backend == "pallas":
            from repro.kernels.ops import combine as combine_kernel
            out = combine_kernel(yk, r.weights.astype(pool_y.dtype))
        else:
            out = jnp.einsum("tkd,tk->td", yk, r.weights.astype(yk.dtype))
    return out, plan


# ----------------------------------------------------------------------------
# dense_capacity (no EP shard_map; pjit auto-shards)
# ----------------------------------------------------------------------------

def _moe_dense(p, x, moe_cfg, *, backend: str, constrain=None,
               c_align: int = 1, dropless: bool = False, placement=None):
    """Shared core of the auto-sharded (no shard_map) paths. Returns
    (out, router_out, MoeStats)."""
    r = route(x, p["router"], num_experts=moe_cfg.num_experts,
              top_k=moe_cfg.experts_per_token,
              forced_uniform=moe_cfg.forced_uniform_routing)
    rd = r if placement is None else \
        RouterOut(r.weights, placement[r.indices], r.aux_loss, r.z_loss)
    out, plan = dispatch_compute_combine(p["gate"], p["up"], p["down"], x, rd,
                                         moe_cfg, backend=backend,
                                         constrain=constrain, c_align=c_align,
                                         dropless=dropless)
    if moe_cfg.num_shared_experts:
        out = out + _shared_expert(p, x)
    stats = plan_stats(plan, backend)
    if placement is not None:
        stats = stats._replace(counts=stats.counts[placement])
    return out, r, stats


def moe_dense_capacity(p, x, moe_cfg, backend: str = "xla", constrain=None,
                       c_align: int = 1):
    out, r, _ = _moe_dense(p, x, moe_cfg, backend=backend,
                           constrain=constrain, c_align=c_align)
    return out, r


def moe_dropless(p, x, moe_cfg, backend: str = "xla", constrain=None,
                 placement=None):
    """Dropless dispatch (tentpole): true per-expert counts feed the grouped
    matmul's ragged ``group_sizes`` and the worst-case pool guarantees
    stats.drops == 0 for any routing. Returns (out, router_out, MoeStats)."""
    return _moe_dense(p, x, moe_cfg, backend=backend, constrain=constrain,
                      dropless=True, placement=placement)


# ----------------------------------------------------------------------------
# fsmoe under EP: the five-stage pipeline inside shard_map
# ----------------------------------------------------------------------------

def _reduce_stats(stats: MoeStats, axes, batch_axes) -> MoeStats:
    """Sum ``stats`` over the token-partitioning (batch) axes among
    ``axes`` and average it over the token-replicating ones (expert-TP,
    where every rank ran the identical dispatch) — NOT psum over
    everything, which would multiply-count under expert-TP."""
    for ax in axes:
        red = jax.lax.psum if ax in batch_axes else jax.lax.pmean
        stats = MoeStats(*(red(v, ax) for v in stats))
    return stats


def _fsmoe_stats(local: MoeStats, *, ep_axis, ep, batch_axes, manual):
    """Global MoeStats from one EP rank's ``plan_stats``.

    counts: each rank holds its (EL,) local-expert histogram over the
    ep-gathered tokens — written at its rank's offset of a zero (E,) vector
    and psum'd over ep, which concatenates them in rank order == expert
    order (exact: integer counts plus zeros) and, unlike an all_gather,
    types the result as replicated over ep for shard_map's check_vma.
    drops and rows: psum over ep (each rank drops its own experts' overflow
    and computes its own pool). Then ``_reduce_stats`` over the other
    axes."""
    el = local.counts.shape[0]
    counts = jax.lax.dynamic_update_slice(
        jnp.zeros((el * ep,), jnp.float32), local.counts,
        (jax.lax.axis_index(ep_axis) * el,))
    stats = MoeStats(*(jax.lax.psum(v, ep_axis)
                       for v in local._replace(counts=counts)))
    return _reduce_stats(stats, sorted(manual - {ep_axis}), batch_axes)


def moe_fsmoe_ep(p, x, moe_cfg, *, mesh, ep_axis: str = "model",
                 batch_axes=("data",), tp_axis=None, dropless: bool = False,
                 placement=None):
    """Paper Algorithm 1 under EP. Tokens x: (N, d) sharded over
    (batch_axes..., ep_axis) on dim 0; expert weights sharded over ep_axis on
    the stacked expert dim. The body is fully manual so the dispatch sort
    stays local to each (pod, data) group (no cross-DP communication).

    ``tp_axis`` composes expert-TP on top of EP (the ParallelPlan ep x tp
    mesh): each expert's d_ff is additionally sharded over ``tp_axis``
    (gate/up column-sharded, down row-sharded), every tp rank runs the same
    dispatch on replicated tokens, and the partial expert outputs are
    psum'd over ``tp_axis`` before the Stage-5 reduce-scatter — one extra
    all-reduce per MoE layer, like a Megatron MLP.
    """
    from jax.sharding import PartitionSpec as P

    E = moe_cfg.num_experts
    ep = mesh.shape[ep_axis]
    assert E % ep == 0, f"{E} experts not divisible by EP={ep}"
    EL = E // ep
    if tp_axis is not None and tp_axis not in mesh.shape:
        raise ValueError(
            f"tp_axis {tp_axis!r} is not a mesh axis "
            f"(mesh has {tuple(mesh.shape)}): expert-TP needs a real axis — "
            f"drop tp_axis for plain EP, or add the axis to the plan")
    if tp_axis is not None and moe_cfg.d_ff_expert % mesh.shape[tp_axis]:
        raise ValueError(
            f"expert d_ff={moe_cfg.d_ff_expert} not divisible by "
            f"tp={mesh.shape[tp_axis]} (axis {tp_axis!r})")
    if dropless and moe_cfg.stage1 == "a2a":
        raise ValueError(
            "dispatch='dropless' does not compose with stage1='a2a': the "
            "all-to-all send buffers are capacity-bounded by construction. "
            "Use the allgather Stage 1 (stage1='allgather') for dropless.")
    # manual over ALL mesh axes: leaving an axis (e.g. 'pod') auto at the
    # shard_map boundary trips an XLA SPMD repartitioning bug ("Invalid
    # binary instruction opcode copy") on multi-pod meshes.
    manual = set(mesh.shape.keys())
    batch_axes = tuple(a for a in batch_axes if a in mesh.shape)
    token_spec = P(tuple(batch_axes) + (ep_axis,), None)

    def body(router_w, gate, up, down, xl, pl=None):
        if moe_cfg.stage1 == "a2a":
            if tp_axis is not None:
                raise NotImplementedError(
                    "stage1='a2a' does not compose with expert-TP yet; use "
                    "the allgather Stage 1 for ep x tp plans")
            return _fsmoe_a2a_body(
                gate, up, down, router_w, xl, moe_cfg, ep_axis=ep_axis,
                ep=ep, manual=manual, batch_axes=batch_axes, placement=pl)
        # Router on local tokens (router replicated — paper §3.1).
        r = route(xl, router_w, num_experts=E,
                  top_k=moe_cfg.experts_per_token,
                  forced_uniform=moe_cfg.forced_uniform_routing)
        # placed-order dispatch: global ids -> stored positions (aux/z losses
        # already computed on global ids inside route)
        idx = r.indices if pl is None else pl[r.indices]
        # ---- Stage 1: allgather tokens + routing over the EP axis -------
        with jax.named_scope("exchange"):
            x_g = jax.lax.all_gather(xl, ep_axis, tiled=True)
            w_g = jax.lax.all_gather(r.weights, ep_axis, tiled=True)
            i_g = jax.lax.all_gather(idx, ep_axis, tiled=True)
        r_g = RouterOut(w_g, i_g, r.aux_loss, r.z_loss)
        # ---- Stages 2-5 on the local expert (and d_ff) slice -------------
        rank = jax.lax.axis_index(ep_axis)
        backend = stage45_backend(moe_cfg)
        out_partial, plan = dispatch_compute_combine(
            gate, up, down, x_g, r_g, moe_cfg,
            expert_offset=rank * EL, local_experts=EL,
            backend=backend, dropless=dropless)
        with jax.named_scope("exchange"):
            if tp_axis is not None:
                # expert-TP: sum the per-d_ff-shard partial outputs (the
                # combine is linear in the expert rows, so summing after it
                # is exact)
                out_partial = jax.lax.psum(out_partial, tp_axis)
            # ---- Stage 5 tail: reduce-scatter to local tokens ------------
            out_local = jax.lax.psum_scatter(out_partial, ep_axis,
                                             scatter_dimension=0, tiled=True)
            aux = r.aux_loss
            z = r.z_loss
            for ax in manual:
                aux = jax.lax.pmean(aux, ax)
                z = jax.lax.pmean(z, ax)
            stats = _fsmoe_stats(plan_stats(plan, backend), ep_axis=ep_axis,
                                 ep=ep, batch_axes=batch_axes, manual=manual)
        if pl is not None:     # report counts back in global expert order
            stats = stats._replace(counts=stats.counts[pl])
        return out_local, aux, z, stats

    operands = [p["router"], p["gate"], p["up"], p["down"], x]
    in_specs = [P(), P(ep_axis, None, tp_axis), P(ep_axis, None, tp_axis),
                P(ep_axis, tp_axis, None), token_spec]
    if placement is not None:
        operands.append(jnp.asarray(placement, jnp.int32))
        in_specs.append(P(None))
    # The replicated outputs type-check: the stats are psum'd over ep, aux
    # and z pmean'd over every axis. Interpret-mode Pallas kernels
    # cannot run under the check (ops.vma_checkable); there the EP goldens
    # against the naive MoE (tests/test_distributed.py) are the check.
    from repro.kernels import ops
    check = stage45_backend(moe_cfg) != "pallas" or ops.vma_checkable()
    out, aux, z, stats = jax.shard_map(
        body, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(token_spec, P(), P(), MoeStats(P(None), P(), P(), P())),
        axis_names=manual, check_vma=check)(*operands)
    out = checkpoint_name(out, "moe_out")
    if moe_cfg.num_shared_experts:
        out = out + _shared_expert(p, x)
    return out, RouterOut(None, None, aux, z), stats


# ----------------------------------------------------------------------------
# beyond-paper: Stage-1 all-to-all dispatch variant
# ----------------------------------------------------------------------------

def _fsmoe_a2a_body(gate, up, down, router_w, xl, moe_cfg, *, ep_axis, ep,
                    manual, batch_axes=(), placement=None):
    """Capacity-bounded all-to-all dispatch (EXPERIMENTS §Perf, dbrx
    hillclimb). The paper sends *all* tokens to *all* EP ranks (allgather,
    chosen because oneCCL's allgather beats its irregular all-to-all). On
    TPU ICI the bytes roofline favors sending each token only to the ranks
    owning its K chosen experts: per-chip traffic drops from (EP-1)/EP·T·d
    to ~cf·K/EP·T·d each way.

    Pipeline: local route -> sort tokens by destination rank into uniform
    (EP, Cd) send buffers -> all_to_all -> local Stage 2/3 dispatch of the
    received rows among the EL local experts (each row is a single (t,k)
    pair, so K'=1) -> Stage 4 grouped FFN + Stage 5 weighting -> reverse
    all_to_all -> per-token sum over the K slots at the source."""
    E = moe_cfg.num_experts
    EL = E // ep
    K = moe_cfg.experts_per_token
    T_loc, d = xl.shape

    r = route(xl, router_w, num_experts=E, top_k=K,
              forced_uniform=moe_cfg.forced_uniform_routing)
    # placed-order dispatch: translate global ids to stored positions
    idx = r.indices if placement is None else placement[r.indices]

    # --- build per-destination send buffers (dest rank = expert // EL) ----
    dest = (idx // EL).astype(jnp.int32)                     # (T,K)
    Cd = round_up(int(math.ceil(moe_cfg.capacity_factor * T_loc * K / ep)), 8)
    plan = make_dispatch_plan(dest, num_experts=ep, pool_rows=ep * Cd,
                              uniform_capacity=True)
    with jax.named_scope("dispatch"):
        tok_flat = jnp.arange(T_loc * K, dtype=jnp.int32) // K
        inv_tok = jnp.zeros((ep * Cd,), jnp.int32).at[plan.slot].set(
            tok_flat, mode="drop")
        pool_valid = jnp.zeros((ep * Cd,), bool).at[plan.slot].set(
            plan.valid, mode="drop")
        send_x = xl[inv_tok] * pool_valid[:, None].astype(xl.dtype)
        flat_idx = idx.reshape(-1)
        flat_w = r.weights.reshape(-1)
        send_e = jnp.full((ep * Cd,), -1, jnp.int32).at[plan.slot].set(
            flat_idx, mode="drop")
        send_w = jnp.zeros((ep * Cd,), jnp.float32).at[plan.slot].set(
            flat_w, mode="drop")
        send_e = jnp.where(pool_valid, send_e, -1)

    # --- all-to-all ------------------------------------------------------
    def a2a(a):
        with jax.named_scope("exchange"):
            return jax.lax.all_to_all(
                a.reshape((ep, Cd) + a.shape[1:]), ep_axis, 0, 0, tiled=False
            ).reshape((ep * Cd,) + a.shape[1:])

    recv_x = a2a(send_x)
    recv_e = a2a(send_e)
    recv_w = a2a(send_w)

    # --- local Stages 2-5 on received rows (K'=1) -------------------------
    rank = jax.lax.axis_index(ep_axis)
    local_e = jnp.where(recv_e >= 0, recv_e - rank * EL, EL)   # sentinel EL
    r2 = RouterOut(recv_w[:, None], local_e[:, None].astype(jnp.int32),
                   r.aux_loss, r.z_loss)
    inner_cfg = dataclasses.replace(moe_cfg, experts_per_token=1)
    # expected local rows ~ T_loc*K (uniform routing); pool sized with the
    # same capacity slack
    inner_pool = round_up(int(math.ceil(
        moe_cfg.capacity_factor * T_loc * K)), 8)
    backend = stage45_backend(moe_cfg)
    out_rows, inner_plan = dispatch_compute_combine(
        gate, up, down, recv_x, r2, inner_cfg, expert_offset=0,
        local_experts=EL, backend=backend, pool_rows=inner_pool)

    # --- reverse all-to-all + per-token sum over K slots ------------------
    back = a2a(out_rows)
    with jax.named_scope("combine"):
        safe_slot = jnp.minimum(plan.slot, ep * Cd - 1)
        yk = back[safe_slot] * plan.valid[:, None].astype(back.dtype)
        out_local = yk.reshape(T_loc, K, d).sum(axis=1)

    with jax.named_scope("exchange"):
        aux, z = r.aux_loss, r.z_loss
        for ax in manual:
            aux = jax.lax.pmean(aux, ax)
            z = jax.lax.pmean(z, ax)
        # send-side capacity drops (outer plan) + receive-side pool overflow
        # (inner plan); counts come from the received rows each rank
        # dispatched among its local experts
        local = plan_stats(inner_plan, backend)
        local = local._replace(drops=local.drops + plan.drops)
        stats = _fsmoe_stats(local, ep_axis=ep_axis, ep=ep,
                             batch_axes=batch_axes, manual=manual)
    if placement is not None:  # back to global expert order
        stats = stats._replace(counts=stats.counts[placement])
    return out_local, aux, z, stats


# ----------------------------------------------------------------------------
# beyond-paper: explicit expert-tensor-parallel path (shard_map)
# ----------------------------------------------------------------------------

def moe_etp_shard_map(p, x, moe_cfg, *, mesh, tp_axis: str = "model",
                      batch_axes=("data",), dropless: bool = False,
                      placement=None):
    """Beyond-paper optimization (EXPERIMENTS §Perf, mixtral hillclimb).

    When E < the model-axis size (mixtral: 8 experts on a 16-way axis), the
    auto-partitioned capacity path reshards tokens *and* the slot pool across
    the mesh, generating TB-scale gather/scatter collectives. This explicit
    path exploits that under expert-TP the expert weights are *replicated*
    across 'model' except for their d_ff shard: every rank can dispatch its
    own data shard locally (sort + pool stay rank-local) and compute partial
    expert outputs with its f-shard; the ONLY cross-rank communication is a
    psum over 'model' of the combined (T_local, d) output — exactly one
    all-reduce per MoE layer, like a Megatron MLP.
    """
    from jax.sharding import PartitionSpec as P

    manual = set(mesh.shape.keys())
    batch_axes = tuple(a for a in batch_axes if a in mesh.shape)
    token_spec = P(tuple(batch_axes), None) if batch_axes else P(None, None)

    def body(router_w, gate, up, down, xl, pl=None):
        r = route(xl, router_w, num_experts=moe_cfg.num_experts,
                  top_k=moe_cfg.experts_per_token,
                  forced_uniform=moe_cfg.forced_uniform_routing)
        rd = r if pl is None else \
            RouterOut(r.weights, pl[r.indices], r.aux_loss, r.z_loss)
        out_partial, plan = dispatch_compute_combine(
            gate, up, down, xl, rd, moe_cfg, backend="xla",
            dropless=dropless)
        with jax.named_scope("exchange"):
            out = jax.lax.psum(out_partial, tp_axis)
        aux, z = r.aux_loss, r.z_loss
        for ax in manual:
            aux = jax.lax.pmean(aux, ax)
            z = jax.lax.pmean(z, ax)
        # all E experts are local here (EP=1): the stats are per token
        # shard
        stats = plan_stats(plan, "xla")
        if pl is not None:
            stats = stats._replace(counts=stats.counts[pl])
        return out, aux, z, _reduce_stats(stats, sorted(manual), batch_axes)

    operands = [p["router"], p["gate"], p["up"], p["down"], x]
    in_specs = [P(), P(None, None, tp_axis), P(None, None, tp_axis),
                P(None, tp_axis, None), token_spec]
    if placement is not None:
        operands.append(jnp.asarray(placement, jnp.int32))
        in_specs.append(P(None))
    out, aux, z, stats = jax.shard_map(
        body, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(token_spec, P(), P(), MoeStats(P(None), P(), P(), P())),
        axis_names=manual)(*operands)
    out = checkpoint_name(out, "moe_out")
    if moe_cfg.num_shared_experts:
        out = out + _shared_expert(p, x)
    return out, RouterOut(None, None, aux, z), stats


# ----------------------------------------------------------------------------
# top-level block entry
# ----------------------------------------------------------------------------

def sparse_moe_block(p, x, cfg, *, mesh=None, ep_axis: str = "model",
                     batch_axes=("data",), constrain=None, c_align: int = 1,
                     tp_mesh=None, tp_axis=None, placement=None):
    """x: (B, S, d) -> (out (B,S,d), aux_loss, z_loss, MoeStats). The
    dispatch mode comes from ``cfg.moe.dispatch``; ``tp_axis`` (a plan
    mesh's dedicated TP axis) composes expert-TP with the EP shard_map.
    ``placement``: optional (E,) inverse placement row (global expert id
    -> stored position) when the stacked expert weights are re-placed."""
    B, S, d = x.shape
    m = cfg.moe
    dropless = m.dispatch == "dropless"
    xt = x.reshape(B * S, d)
    if m.moe_impl == "naive":
        out, r = moe_naive(p, xt, m, placement=placement)
        # stats from the router's global ids — already placement-free
        one_hot = jax.nn.one_hot(r.indices, m.num_experts, dtype=jnp.float32)
        rows = jnp.float32(B * S * m.num_experts)   # every expert, every token
        stats = MoeStats(one_hot.sum((0, 1)), jnp.zeros((), jnp.float32),
                         rows, rows)
        return out.reshape(B, S, d), r.aux_loss, r.z_loss, stats
    use_ep = (m.moe_impl == "fsmoe" and mesh is not None
              and ep_axis in mesh.shape
              and m.num_experts % mesh.shape[ep_axis] == 0)
    if use_ep:
        out, r, stats = moe_fsmoe_ep(p, xt, m, mesh=mesh, ep_axis=ep_axis,
                                     batch_axes=batch_axes, tp_axis=tp_axis,
                                     dropless=dropless, placement=placement)
        return out.reshape(B, S, d), r.aux_loss, r.z_loss, stats
    if m.etp_shard_map and tp_mesh is not None:
        out, r, stats = moe_etp_shard_map(p, xt, m, mesh=tp_mesh,
                                          tp_axis=tp_axis or "model",
                                          batch_axes=batch_axes,
                                          dropless=dropless,
                                          placement=placement)
        return out.reshape(B, S, d), r.aux_loss, r.z_loss, stats
    backend = stage45_backend(m) if m.moe_impl == "fsmoe" else "xla"
    out, r, stats = _moe_dense(p, xt, m, backend=backend, constrain=constrain,
                               c_align=c_align, dropless=dropless,
                               placement=placement)
    return out.reshape(B, S, d), r.aux_loss, r.z_loss, stats

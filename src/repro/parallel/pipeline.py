"""Pipeline parallelism: gpipe and 1f1b microbatch schedules (paper §1 PP).

The paper trains Mula-100B (PP=4) and Mula-220B (PP=8) with 1f1b microbatch
scheduling across nodes. This module implements PP as:

  * ``split_stages``      — vertical partition of a scanned layer stack into
                            PP contiguous stage subtrees.
  * ``gpipe_schedule`` / ``one_f_one_b_schedule`` — the *exact* time-ordered
    (clock, stage, microbatch, F|B) tick tables of the two schedules,
    including their bubble structure (validated by tests + used by the
    scaling benchmark to account PP bubbles).
  * ``pipeline_train_step`` — a functional executor that runs the ticks in
    schedule order, carrying activations stage-to-stage and accumulating
    per-stage gradients. On a stage-sharded deployment each tick's compute
    runs on its stage's devices and the activation hand-off is a
    ``ppermute``; on this container the ticks execute sequentially but in
    the *same order and dataflow*, so gradient equivalence and schedule
    correctness are what gets tested.

Bubble fractions (per schedule theory, used in §Perf accounting):
  gpipe : (pp-1) / (mb + pp - 1)
  1f1b  : same bubble, but peak activation memory drops from mb to pp
          in-flight microbatches — the reason the paper uses it.
"""
from __future__ import annotations

from typing import Callable, List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


class Tick(NamedTuple):
    clock: int
    stage: int
    mb: int
    kind: str      # 'F' | 'B'


def _check_stage_divisible(L: int, pp: int, name: str = "") -> None:
    if pp < 1:
        raise ValueError(f"pp_stages must be >= 1, got {pp}")
    if L % pp != 0:
        who = f"config {name!r}: " if name else ""
        raise ValueError(
            f"{who}{L} layers do not divide evenly into pp_stages={pp} "
            f"pipeline stages (each stage needs L/pp whole layers)")


def split_stages(stacked_layer_params, pp: int, *, name: str = "") -> list:
    """Split a layer-stacked pytree (leading dim = L) into pp subtrees."""
    L = jax.tree.leaves(stacked_layer_params)[0].shape[0]
    _check_stage_divisible(L, pp, name)
    per = L // pp
    return [jax.tree.map(lambda a: a[s * per:(s + 1) * per],
                         stacked_layer_params) for s in range(pp)]


def stack_stages(stacked_layer_params, pp: int, *, name: str = ""):
    """Mesh-native stage view: reshape each (L, ...) leaf to (pp, L/pp, ...).

    With the stacked layer dim sharded over the 'pp' mesh axis
    (``parallel.sharding.param_specs``) this reshape is device-local — each
    stage's devices already hold exactly their contiguous L/pp layer slice,
    so the stage view introduces no data movement."""
    L = jax.tree.leaves(stacked_layer_params)[0].shape[0]
    _check_stage_divisible(L, pp, name)
    per = L // pp
    return jax.tree.map(lambda a: a.reshape((pp, per) + a.shape[1:]),
                        stacked_layer_params)


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------

def gpipe_schedule(n_mb: int, pp: int) -> List[Tick]:
    """All forwards (staggered), then all backwards (reverse-staggered)."""
    ticks = []
    for m in range(n_mb):
        for s in range(pp):
            ticks.append(Tick(m + s, s, m, "F"))
    fwd_end = (n_mb - 1) + (pp - 1) + 1
    for m in range(n_mb):
        for s in reversed(range(pp)):
            ticks.append(Tick(fwd_end + m + (pp - 1 - s), s, m, "B"))
    return sorted(ticks, key=lambda t: (t.clock, t.stage))


def one_f_one_b_schedule(n_mb: int, pp: int) -> List[Tick]:
    """PipeDream-flush (1f1b), generated by event-driven simulation of the
    policy: each stage keeps at most (pp - stage) microbatches in flight and
    prefers a backward over a forward once one is ready. Same bubble as
    gpipe; O(pp) in-flight activations instead of O(n_mb)."""
    ticks = []
    ftime = {}    # (stage, mb) -> clock of completed F
    btime = {}
    fwd_next = [0] * pp
    bwd_next = [0] * pp
    clock = 0
    while sum(bwd_next) < n_mb * pp:
        progressed = False
        for s in range(pp):
            # backward ready?
            m = bwd_next[s]
            b_ready = (m < n_mb and (s, m) in ftime and ftime[(s, m)] < clock
                       and (s == pp - 1 or
                            ((s + 1, m) in btime and btime[(s + 1, m)] < clock)))
            # forward ready?
            f = fwd_next[s]
            inflight = fwd_next[s] - bwd_next[s]
            f_ready = (f < n_mb and inflight < pp - s
                       and (s == 0 or ((s - 1, f) in ftime
                                       and ftime[(s - 1, f)] < clock)))
            if b_ready:
                ticks.append(Tick(clock, s, m, "B"))
                btime[(s, m)] = clock
                bwd_next[s] += 1
                progressed = True
            elif f_ready:
                ticks.append(Tick(clock, s, f, "F"))
                ftime[(s, f)] = clock
                fwd_next[s] += 1
                progressed = True
        clock += 1
        assert clock < 10 * (n_mb + pp) * 2 + 64, "1f1b scheduler stuck"
    return sorted(ticks, key=lambda t: (t.clock, t.stage))


def interleaved_1f1b_schedule(n_mb: int, pp: int, v: int) -> List[Tick]:
    """Interleaved 1f1b (Megatron; paper §1 lists it as Optimus' third PP
    schedule): each device hosts ``v`` model chunks; virtual stage s lives
    on device s % pp. Generated by the same event-driven greedy (prefer
    backward; bound in-flight work), over pp*v virtual stages. Interleaving
    shrinks the bubble ~v-fold: (pp-1)/(v*n_mb + pp - 1).

    Tick.stage is the *virtual* stage id; device = stage % pp.
    """
    vs = pp * v
    ticks = []
    ftime, btime = {}, {}
    fwd_next = [0] * vs
    bwd_next = [0] * vs
    clock = 0
    while sum(bwd_next) < n_mb * vs:
        busy = set()
        # prefer backwards (latest virtual stage first), then forwards
        for s in sorted(range(vs), key=lambda x: -x):
            dev = s % pp
            if dev in busy:
                continue
            m = bwd_next[s]
            if (m < n_mb and (s, m) in ftime and ftime[(s, m)] < clock
                    and (s == vs - 1 or ((s + 1, m) in btime
                                         and btime[(s + 1, m)] < clock))):
                ticks.append(Tick(clock, s, m, "B"))
                btime[(s, m)] = clock
                bwd_next[s] += 1
                busy.add(dev)
        # deepest-ready-first forwards: pushes the lead microbatch to the
        # last virtual stage quickly so backwards start early (bounds the
        # in-flight window without an explicit cap deadlocking the greedy)
        for s in sorted(range(vs), key=lambda x: -x):
            dev = s % pp
            if dev in busy:
                continue
            f = fwd_next[s]
            if (f < n_mb
                    and (s == 0 or ((s - 1, f) in ftime
                                    and ftime[(s - 1, f)] < clock))):
                ticks.append(Tick(clock, s, f, "F"))
                ftime[(s, f)] = clock
                fwd_next[s] += 1
                busy.add(dev)
        clock += 1
        assert clock < 20 * (n_mb + vs) + 64, "interleaved scheduler stuck"
    return sorted(ticks, key=lambda t: (t.clock, t.stage))


def validate_schedule(ticks: Sequence[Tick], n_mb: int, pp: int,
                      v: int = 1) -> None:
    """Dependency check: F(s,m) after F(s-1,m); B(s,m) after B(s+1,m) and
    F(s,m); one op per (device, clock). ``v`` > 1: stages are virtual,
    device = stage % pp."""
    vs = pp * v
    ftime, btime, busy = {}, {}, set()
    for t in sorted(ticks, key=lambda x: x.clock):
        dev = t.stage % pp
        assert (dev, t.clock) not in busy, "device double-booked"
        busy.add((dev, t.clock))
        if t.kind == "F":
            if t.stage > 0:
                assert ftime[(t.stage - 1, t.mb)] < t.clock, \
                    f"F dep violated at {t}"
            ftime[(t.stage, t.mb)] = t.clock
        else:
            assert ftime[(t.stage, t.mb)] < t.clock, f"B-after-F at {t}"
            if t.stage < vs - 1:
                assert btime[(t.stage + 1, t.mb)] < t.clock, \
                    f"B dep violated at {t}"
            btime[(t.stage, t.mb)] = t.clock
    assert len(ftime) == len(btime) == n_mb * vs


def bubble_fraction(n_mb: int, pp: int, schedule: str = "1f1b") -> float:
    return (pp - 1) / (n_mb + pp - 1)


def peak_inflight(ticks: Sequence[Tick], stage: int = 0) -> int:
    """Max simultaneously-alive forward activations on a stage (1f1b wins)."""
    alive = peak = 0
    for t in sorted(ticks, key=lambda x: x.clock):
        if t.stage != stage:
            continue
        if t.kind == "F":
            alive += 1
            peak = max(peak, alive)
        else:
            alive -= 1
    return peak


# ---------------------------------------------------------------------------
# functional executor
# ---------------------------------------------------------------------------

def pipeline_train_step(stage_fwd: Callable, loss_fn: Callable,
                        stage_params: list, microbatches: list,
                        schedule: str = "1f1b", v: int = 1):
    """Run one PP train step over ``microbatches`` in schedule order.

    stage_fwd(params_s, x) -> x_out              (per-stage forward)
    loss_fn(x_last, mb) -> scalar                (applied after last stage)

    ``schedule='interleaved-1f1b'`` treats stage_params as pp*v virtual
    stages (v chunks per device, device = stage % pp).

    Returns (loss_mean, per-stage grads) — bit-identical to running the
    stages sequentially, since the schedule only reorders independent work.
    """
    n_mb = len(microbatches)
    if schedule == "interleaved-1f1b":
        assert len(stage_params) % v == 0
        pp = len(stage_params) // v
        ticks = interleaved_1f1b_schedule(n_mb, pp, v)
        validate_schedule(ticks, n_mb, pp, v)
        pp = pp * v    # executor below walks virtual stages
    else:
        pp = len(stage_params)
        ticks = (gpipe_schedule if schedule == "gpipe"
                 else one_f_one_b_schedule)(n_mb, pp)
        validate_schedule(ticks, n_mb, pp)

    # forward saves, backward accumulators
    acts = {}                       # (stage, mb) -> input activation
    grads = [jax.tree.map(jnp.zeros_like, sp) for sp in stage_params]
    dacts = {}                      # (stage, mb) -> cotangent flowing back
    losses = []

    def fwd_vjp(s, m):
        x = acts[(s, m)]
        y, vjp = jax.vjp(lambda p, xx: stage_fwd(p, xx), stage_params[s], x)
        return y, vjp

    vjps = {}
    for t in ticks:
        s, m = t.stage, t.mb
        if t.kind == "F":
            if s == 0:
                acts[(0, m)] = microbatches[m]["x"]
            y, vjp = fwd_vjp(s, m)
            vjps[(s, m)] = vjp
            if s < pp - 1:
                acts[(s + 1, m)] = y     # ppermute on a stage mesh
            else:
                loss, dlast = jax.value_and_grad(
                    lambda yy: loss_fn(yy, microbatches[m]))(y)
                losses.append(loss)
                dacts[(s, m)] = dlast
        else:
            dp, dx = vjps.pop((s, m))(dacts.pop((s, m)))
            grads[s] = jax.tree.map(jnp.add, grads[s], dp)
            if s > 0:
                dacts[(s - 1, m)] = dx   # ppermute (reverse direction)

    loss = jnp.mean(jnp.stack(losses))
    grads = [jax.tree.map(lambda g: g / n_mb, gs) for gs in grads]
    return loss, grads


# ---------------------------------------------------------------------------
# mesh-native jitted executors (the real training path)
# ---------------------------------------------------------------------------

def _make_cons_buf(mesh, batch_axes):
    """Constraint fn for (pp, n_mb, mbB, ...) buffers: stage dim pinned to
    'pp', microbatch rows on the batch axes when divisible."""
    def cons_buf(a):
        if mesh is None or "pp" not in mesh.shape:
            return a
        spec = [None] * a.ndim
        spec[0] = "pp"
        if batch_axes and a.ndim >= 3:
            n = 1
            for ax in batch_axes:
                n *= mesh.shape[ax]
            if n > 1 and a.shape[2] % n == 0:
                spec[2] = (tuple(batch_axes) if len(batch_axes) > 1
                           else batch_axes[0])
        return jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, P(*spec)))

    return cons_buf

def schedule_masks(schedule: str, n_mb: int, pp: int) -> dict:
    """Compile a tick table into dense per-clock mask arrays for the jitted
    executor: ``do_f``/``do_b`` (T, pp) bool and ``f_mb``/``b_mb`` (T, pp)
    int32. Both schedules put at most one op per (clock, stage)."""
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"pp_schedule must be 'gpipe' or '1f1b', "
                         f"got {schedule!r}")
    ticks = (gpipe_schedule if schedule == "gpipe"
             else one_f_one_b_schedule)(n_mb, pp)
    validate_schedule(ticks, n_mb, pp)
    T = max(t.clock for t in ticks) + 1
    do_f = np.zeros((T, pp), bool)
    do_b = np.zeros((T, pp), bool)
    f_mb = np.zeros((T, pp), np.int32)
    b_mb = np.zeros((T, pp), np.int32)
    for t in ticks:
        assert not (do_f[t.clock, t.stage] or do_b[t.clock, t.stage])
        if t.kind == "F":
            do_f[t.clock, t.stage] = True
            f_mb[t.clock, t.stage] = t.mb
        else:
            do_b[t.clock, t.stage] = True
            b_mb[t.clock, t.stage] = t.mb
    return {"do_f": do_f, "f_mb": f_mb, "do_b": do_b, "b_mb": b_mb,
            "ticks": T}


def check_pp_microbatches(n_mb: int, pp: int) -> None:
    """Per-stage executor guardrail: microbatches must divide into pp_stages.

    Balanced waves keep every stage's F/B tick counts uniform per schedule
    round (and the steady-state 1f1b window full); a ragged count silently
    degrades into extra bubble that the analytic accounting does not model.
    The masked executor tolerates any n_mb >= 1 for compatibility."""
    if n_mb < 1 or n_mb % pp != 0:
        raise ValueError(
            f"pp_impl='shardmap' needs microbatches divisible by pp_stages, "
            f"got microbatches={n_mb}, pp_stages={pp}: pick microbatches = "
            f"k*{pp} (e.g. {2 * pp}) so the 1f1b/gpipe waves stay balanced "
            f"across stages, or use pp_impl='masked'")


def pipelined_loss_and_grads(stage_fn, io_params, stage_params, mbs,
                             scalar_cots, *, act_shape, act_dtype,
                             schedule: str = "1f1b", mesh=None,
                             batch_axes=()):
    """Run one pipelined loss+grad computation in schedule order, inside jit.

    The executor walks the exact gpipe/1f1b tick table as a ``lax.scan`` over
    per-clock masks. All per-stage work is a single op batched over a leading
    stage dim; with that dim sharded over the 'pp' mesh axis, GSPMD runs each
    stage on its own devices and lowers the stage-dim ``jnp.roll`` hand-offs
    to collective-permutes (``ppermute``) over 'pp' — forward activations
    shift stage s -> s+1, backward cotangents s -> s-1.

    * ``stage_fn(io_params, lp, x, mb, sid) -> (y, scalars)`` — one stage's
      forward. ``y`` must have ``x``'s shape/dtype (homogeneous stages);
      ``scalars`` is a pytree of f32 scalars or small f32 vectors (loss
      pieces / aux terms / telemetry like per-expert counts).
    * ``scalar_cots`` — pytree matching ``scalars``; each leaf a (pp,) + leaf
      shape f32 array of per-stage cotangent seeds (e.g. CE seeded only on
      the last stage, MoE aux coefficients on every stage, zeros for
      non-differentiable telemetry channels).
    * Backward ticks recompute the stage forward from the saved stage input
      and pull a ``jax.vjp`` through it — the same recompute-from-block-input
      semantics as the SAC 'block' remat policy.

    Activations are buffered per (stage, microbatch) — the gpipe memory
    profile. On real stage-local memory 1f1b's O(pp) in-flight window is the
    point (see ``peak_inflight``); on this single-program simulation the
    buffers are global and only the schedule order is observable.

    Known single-program cost: every stage executes the full ``stage_fn``
    (including any embed/head work the caller masks with ``sid``) because
    SPMD devices all run the same program — per-stage branches would need a
    shard_map + cond restructure. Correctness is unaffected (masks/cotangent
    seeds zero the unused paths); step times on the sim mesh include that
    redundant compute.

    Returns ``(scalar_sums, io_grads, stage_grads)`` — raw sums over
    microbatches (f32); the caller normalizes by n_mb exactly like the
    non-PP microbatch-accumulation path. ``scalar_sums`` leaves are (pp,) +
    leaf shape per-stage sums; ``io_grads`` is summed over stages (at most one stage
    contributes per leaf thanks to the cotangent seeds).
    """
    pp = jax.tree.leaves(stage_params)[0].shape[0]
    n_mb = jax.tree.leaves(mbs)[0].shape[0]
    masks = schedule_masks(schedule, n_mb, pp)
    sids = jnp.arange(pp)
    f32 = jnp.float32
    cons_buf = _make_cons_buf(mesh, batch_axes)

    def fwd_one(lp, x, mb, sid):
        return stage_fn(io_params, lp, x, mb, sid)

    def bwd_one(lp, x, mb, sid, dy, dscal):
        def f(io, lp_, x_):
            return stage_fn(io, lp_, x_, mb, sid)
        _, pull = jax.vjp(f, io_params, lp, x)
        return pull((dy, dscal))          # (dio, dlp, dx)

    vfwd = jax.vmap(fwd_one)
    vbwd = jax.vmap(bwd_one)

    take = jax.vmap(lambda buf, i: buf[i])                     # buf[s, i[s]]
    put = jax.vmap(lambda buf, i, v, m:
                   buf.at[i].set(jnp.where(m, v, buf[i])))

    def smask(m, g):                      # broadcast (pp,) mask over a leaf
        return jnp.where(m.reshape((pp,) + (1,) * (g.ndim - 1)), g, 0)

    acts = cons_buf(jnp.zeros((pp, n_mb) + tuple(act_shape), act_dtype))
    dacts = cons_buf(jnp.zeros((pp, n_mb) + tuple(act_shape), act_dtype))
    g_stage = jax.tree.map(lambda p: jnp.zeros(p.shape, f32), stage_params)
    g_io = jax.tree.map(lambda p: jnp.zeros(p.shape, f32), io_params)
    # per-stage sums, (pp,) + leaf shape (vector channels ride along)
    ssum = jax.tree.map(lambda c: jnp.zeros(c.shape, f32), scalar_cots)

    def tick(carry, msk):
        acts, dacts, g_io, g_stage, ssum = carry
        do_f, f_mb, do_b, b_mb = msk
        # ---- forward wave: one masked F per stage -------------------------
        x_f = take(acts, f_mb)
        mb_f = jax.tree.map(lambda a: a[f_mb], mbs)
        y, scal = vfwd(stage_params, x_f, mb_f, sids)
        ssum = jax.tree.map(
            lambda s, v: s + smask(do_f, v.astype(f32)), ssum, scal)
        # activation hand-off (ppermute over 'pp'): y[s] -> acts[s+1, mb]
        ok_f = jnp.roll(do_f, 1) & (sids > 0)
        acts = cons_buf(put(acts, jnp.roll(f_mb, 1),
                            jnp.roll(y, 1, axis=0), ok_f))
        # ---- backward wave: one masked B per stage ------------------------
        x_b = take(acts, b_mb)
        mb_b = jax.tree.map(lambda a: a[b_mb], mbs)
        dy = take(dacts, b_mb)
        dio, dlp, dx = vbwd(stage_params, x_b, mb_b, sids, dy, scalar_cots)
        g_stage = jax.tree.map(
            lambda a, g: a + smask(do_b, g.astype(f32)), g_stage, dlp)
        g_io = jax.tree.map(
            lambda a, g: a + smask(do_b, g.astype(f32)).sum(axis=0),
            g_io, dio)
        # cotangent hand-off (reverse ppermute): dx[s] -> dacts[s-1, mb]
        ok_b = jnp.roll(do_b, -1) & (sids < pp - 1)
        dacts = cons_buf(put(dacts, jnp.roll(b_mb, -1),
                             jnp.roll(dx, -1, axis=0), ok_b))
        return (acts, dacts, g_io, g_stage, ssum), None

    xs = (jnp.asarray(masks["do_f"]), jnp.asarray(masks["f_mb"]),
          jnp.asarray(masks["do_b"]), jnp.asarray(masks["b_mb"]))
    (acts, dacts, g_io, g_stage, ssum), _ = jax.lax.scan(
        tick, (acts, dacts, g_io, g_stage, ssum), xs)
    return ssum, g_io, g_stage


# ---------------------------------------------------------------------------
# shard_map-per-stage executor (pp_impl='shardmap', the default)
# ---------------------------------------------------------------------------

def pipelined_loss_and_grads_per_stage(embed_fn, block_fn, head_fn,
                                       io_params, stage_params, mbs,
                                       scalar_cots, *, act_shape, act_dtype,
                                       schedule: str = "1f1b", mesh=None,
                                       batch_axes=(), ce_key: str = "ce"):
    """Stage-specialized pipelined loss+grads: shard_map over the 'pp' axis.

    Same tick tables, hand-off dataflow, block-input recompute and
    microbatch-ordered grad accumulation as ``pipelined_loss_and_grads`` —
    but the stage-heterogeneous work runs inside ``shard_map`` regions over
    'pp' where a ``lax.cond`` on the stage id makes each stage execute a
    *different program at runtime*:

      * only stage 0 runs ``embed_fn`` (every other stage forwards its
        received activation untouched);
      * only the last stage runs ``head_fn`` (final norm + the vocab-sized
        unembed matmul + CE) — the single largest matmul at real vocab
        sizes, which the masked SPMD executor pays on *every* stage every
        tick because single-program SPMD cannot branch per stage;
      * the backward additionally reuses the stage outputs saved at F ticks
        (``outs`` buffer) for the head vjp, so the last stage skips the
        head *recompute* the masked executor performs on every B tick.

    The homogeneous interior work (the per-stage block stacks) stays a
    single vmapped op batched over the stage dim exactly like the masked
    executor — it is uniform across stages, so there is nothing to
    specialize, and keeping it in GSPMD-auto land lets expert-parallel /
    expert-TP placements inside a stage compose unchanged.

    * ``embed_fn(io_params, mb) -> x0`` — stage-0 input from the microbatch.
    * ``block_fn(stage_lp, x, mb) -> (y, scalars)`` — one stage's block
      stack; ``y`` must have ``x``'s shape/dtype; ``scalars`` is a pytree of
      f32 scalars or small f32 vectors (MoE aux terms / telemetry; must NOT
      contain ``ce_key``).
    * ``head_fn(io_params, y, mb) -> ce`` — last-stage tail (scalar f32).
    * ``scalar_cots`` — per-stage (pp,) + leaf shape f32 cotangent seeds
      keyed like the combined ``{ce_key: ..., **scalars}`` dict.

    Activation hand-offs happen outside the regions, in GSPMD-auto land,
    via the same stage-dim ``jnp.roll`` as the masked executor — on a
    'pp'-sharded buffer XLA lowers the roll to a collective-permute
    (``ppermute``) between neighbouring stages.

    Gradient contract matches ``pipelined_loss_and_grads``:
    ``(scalar_sums, io_grads, stage_grads)``, raw sums over microbatches.
    Loss scalars are bit-identical to the masked executor; gradients agree
    to ~1 ulp (XLA fuses the head->blocks backward chain differently when
    the vjp is factored at the stage-output boundary — see
    tests/test_pp_train.py::test_shardmap_executor_golden_parity_mesh8).

    Requires a mesh with a 'pp' axis (shard_map needs real stage shards)
    and ``n_mb % pp == 0`` (``check_pp_microbatches``).
    """
    if mesh is None or "pp" not in mesh.shape:
        raise ValueError(
            "pipelined_loss_and_grads_per_stage needs a mesh with a 'pp' "
            "axis: per-stage programs are shard_map shards over pipeline "
            "stages (use pp_impl='masked' for off-mesh simulation)")
    pp = mesh.shape["pp"]
    got = jax.tree.leaves(stage_params)[0].shape[0]
    if got != pp:
        raise ValueError(f"stage_params leading dim {got} != mesh pp={pp}")
    n_mb = jax.tree.leaves(mbs)[0].shape[0]
    check_pp_microbatches(n_mb, pp)
    masks = schedule_masks(schedule, n_mb, pp)
    sids = jnp.arange(pp)
    f32 = jnp.float32
    cons_buf = _make_cons_buf(mesh, batch_axes)

    # -- shard_map regions: the per-stage-specialized programs ---------------
    # The stage id travels as a P('pp')-sharded data array, not
    # lax.axis_index: axis_index inside a partial-auto region lowers to a
    # PartitionId op this XLA's SPMD partitioner rejects. Region bodies are
    # scan-free on purpose — iota-xs scans and scan-under-cond patterns
    # check-fail the partial-auto partitioner (see models/layers.py note).
    # The replicated io params are cast to 'pp'-varying at region entry,
    # outside the cond: the transpose of that cast is the psum over 'pp'
    # that sums io cotangents across stages. Left to the branches, the
    # implicit cast (and so its psum) lands inside the cond, where only
    # one stage runs it, and the collective deadlocks.
    def _varying_io(io):
        return jax.tree.map(lambda a: jax.lax.pcast(a, "pp", to="varying"),
                            io)

    def _in_region(sid_blk, io, x_blk, mb_blk):
        sid = sid_blk[0]
        io = _varying_io(io)
        mb1 = jax.tree.map(lambda a: a[0], mb_blk)
        h = jax.lax.cond(
            sid == 0,
            lambda: embed_fn(io, mb1).astype(act_dtype),
            lambda: x_blk[0])
        return h[None]

    def _head_region(sid_blk, io, y_blk, mb_blk):
        sid = sid_blk[0]
        io = _varying_io(io)
        mb1 = jax.tree.map(lambda a: a[0], mb_blk)
        # both branches vary over 'pp' (check_vma needs matching types):
        # the head reads this stage's activation, the zero is cast to match
        ce = jax.lax.cond(
            sid == pp - 1,
            lambda: head_fn(io, y_blk[0], mb1).astype(f32),
            lambda: jax.lax.pcast(jnp.zeros((), f32), "pp", to="varying"))
        return ce[None]

    stage_in = jax.shard_map(
        _in_region, mesh=mesh, in_specs=(P("pp"), P(), P("pp"), P("pp")),
        out_specs=P("pp"), axis_names={"pp"})
    stage_head = jax.shard_map(
        _head_region, mesh=mesh, in_specs=(P("pp"), P(), P("pp"), P("pp")),
        out_specs=P("pp"), axis_names={"pp"})

    vblocks = jax.vmap(block_fn)

    def bwd_blocks_one(lp, h, mb1, dyy, seeds):
        # per-stage block vjp (block-input recompute), vmapped over the
        # stage dim — the identical vmap-of-vjp structure the masked
        # executor uses, so the block backward bits can only differ through
        # the head-path cotangent
        _, pull = jax.vjp(lambda l, hh: block_fn(l, hh, mb1), lp, h)
        return pull((dyy, seeds))

    vbwd_blocks = jax.vmap(bwd_blocks_one)

    take = jax.vmap(lambda buf, i: buf[i])
    put = jax.vmap(lambda buf, i, v, m:
                   buf.at[i].set(jnp.where(m, v, buf[i])))

    def smask(m, g):
        return jnp.where(m.reshape((pp,) + (1,) * (g.ndim - 1)), g, 0)

    block_keys = [k for k in scalar_cots if k != ce_key]

    acts = cons_buf(jnp.zeros((pp, n_mb) + tuple(act_shape), act_dtype))
    outs = cons_buf(jnp.zeros((pp, n_mb) + tuple(act_shape), act_dtype))
    dacts = cons_buf(jnp.zeros((pp, n_mb) + tuple(act_shape), act_dtype))
    g_stage = jax.tree.map(lambda p: jnp.zeros(p.shape, f32), stage_params)
    g_io = jax.tree.map(lambda p: jnp.zeros(p.shape, f32), io_params)
    # per-stage sums, (pp,) + leaf shape (vector channels ride along)
    ssum = jax.tree.map(lambda c: jnp.zeros(c.shape, f32), scalar_cots)

    def tick(carry, msk):
        acts, outs, dacts, g_io, g_stage, ssum = carry
        do_f, f_mb, do_b, b_mb = msk
        # ---- forward wave: embed region -> vmapped blocks -> head region --
        x_f = take(acts, f_mb)
        mb_f = jax.tree.map(lambda a: a[f_mb], mbs)
        h_in = stage_in(sids, io_params, x_f, mb_f)
        y, scal = vblocks(stage_params, h_in, mb_f)
        ce = stage_head(sids, io_params, y, mb_f)
        ssum = jax.tree.map(
            lambda s, v: s + smask(do_f, v.astype(f32)),
            ssum, {ce_key: ce, **scal})
        # stage outputs saved for the backward head vjp (the masked
        # executor recomputes the head forward instead)
        outs = cons_buf(put(outs, f_mb, y, do_f))
        # activation hand-off: y[s] -> acts[s+1, mb] (collective permute
        # over 'pp', same stage-dim roll as the masked executor)
        ok_f = jnp.roll(do_f, 1) & (sids > 0)
        acts = cons_buf(put(acts, jnp.roll(f_mb, 1),
                            jnp.roll(y, 1, axis=0), ok_f))
        # ---- backward wave ------------------------------------------------
        x_b = take(acts, b_mb)
        y_b = take(outs, b_mb)
        mb_b = jax.tree.map(lambda a: a[b_mb], mbs)
        # inactive stages must contribute exactly zero: io-grads come out of
        # the region vjps already stage-summed, so the masking happens on
        # the cotangent seeds (dy + scalar seeds), not on grad outputs
        dy = smask(do_b, take(dacts, b_mb))
        cots_t = jax.tree.map(lambda c: smask(do_b, c), scalar_cots)
        # head vjp (last stage only, from the saved stage output)
        _, pull_h = jax.vjp(
            lambda io, y_: stage_head(sids, io, y_, mb_b), io_params, y_b)
        dio_h, dy_h = pull_h(cots_t[ce_key])
        # embed region forward (recompute) + blocks vjp + embed vjp
        h_in_b, pull_in = jax.vjp(
            lambda io, x_: stage_in(sids, io, x_, mb_b), io_params, x_b)
        dlp, dh_in = vbwd_blocks(stage_params, h_in_b, mb_b, dy + dy_h,
                                 {k: cots_t[k] for k in block_keys})
        dio_e, dx = pull_in(dh_in)
        g_stage = jax.tree.map(
            lambda a, g: a + smask(do_b, g.astype(f32)), g_stage, dlp)
        g_io = jax.tree.map(
            lambda a, ge, gh: a + (ge.astype(f32) + gh.astype(f32)),
            g_io, dio_e, dio_h)
        # cotangent hand-off: dx[s] -> dacts[s-1, mb]
        ok_b = jnp.roll(do_b, -1) & (sids < pp - 1)
        dacts = cons_buf(put(dacts, jnp.roll(b_mb, -1),
                             jnp.roll(smask(do_b, dx), -1, axis=0), ok_b))
        return (acts, outs, dacts, g_io, g_stage, ssum), None

    xs = (jnp.asarray(masks["do_f"]), jnp.asarray(masks["f_mb"]),
          jnp.asarray(masks["do_b"]), jnp.asarray(masks["b_mb"]))
    (acts, outs, dacts, g_io, g_stage, ssum), _ = jax.lax.scan(
        tick, (acts, outs, dacts, g_io, g_stage, ssum), xs)
    return ssum, g_io, g_stage

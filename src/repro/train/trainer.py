"""Training / serving steps.

``train_step`` implements the paper's recipe (§2.1): bf16 fwd/bwd on bf16
params, bf16 gradient reduction, fp32 master weights + AdamW states (held in
the optimizer state, sharded per SO/EPSO), warmup+cosine LR, global-norm
clipping enabled only after warmup, gradient accumulation over microbatches
via ``lax.scan``, SAC remat policies.

``serve_step`` is single-token decode against a KV/SSM cache (the lowering
target for decode_32k / long_500k) — with ``sample=True`` it becomes the
serve engine's decode lowering (per-slot positions + per-request sampling;
repro/serve/engine.py). ``prefill_step`` is the forward pass for prefill_32k;
with ``into_cache=True`` it writes prompt K/V straight into cache slots (the
engine's admission path).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig, TrainConfig
from repro.models import (init_params, loss_fn, forward,
                          decode_step, prefill_with_cache, embed_tokens,
                          pipeline_stage_forward, lm_head_ce, PP_ARCH_TYPES)
from repro.optim import adamw_init, adamw_update, warmup_cosine, AdamWState
from repro.optim.epso import optimizer_state_shardings, plan_update_buckets
from repro.optim.overlap import overlapped_adamw_update, resolve_opt_overlap
from repro.parallel.placement import expert_leaf_mask
from repro.parallel.pipeline import (check_pp_microbatches,
                                     pipelined_loss_and_grads,
                                     pipelined_loss_and_grads_per_stage,
                                     stack_stages)
from repro.parallel.plan import ResolvedPlan, use_kernel_plan
from repro.parallel.sharding import make_rules, shardings as param_shardings


class TrainState(NamedTuple):
    params: dict          # compute-precision params (bf16 in production)
    opt: AdamWState       # fp32 master + moments


def train_state_shardings(params, rules, mode: str = "none"):
    """TrainState-shaped NamedSharding pytree: params per ``param_specs``,
    AdamW master/m/v per ``optimizer_state_specs(mode)`` (paper §3.2 SO/EPSO
    placement), the step counter replicated. ``params`` may be concrete
    arrays or ShapeDtypeStructs — only shapes are read. Returns None off-mesh.
    """
    if rules is None or rules.mesh is None:
        return None
    psh = param_shardings(params, rules)
    osh = optimizer_state_shardings(params, rules, mode)
    rep = NamedSharding(rules.mesh, P())
    return TrainState(psh, AdamWState(rep, osh, osh, osh))


def _resolve_rules(cfg, train, rules, mesh):
    if rules is None and mesh is not None:
        rules = make_rules(cfg, mesh, kind="train",
                           global_batch=train.global_batch)
    return rules


def _unpack_plan(plan: Optional[ResolvedPlan], rules, mesh,
                 opt_sharding_mode):
    """A ResolvedPlan supplies rules/mesh/opt mode in one object; explicit
    kwargs (the legacy threading, now deprecated) win when both are given —
    an explicit ``opt_sharding_mode='none'`` disables sharding even
    alongside an EPSO plan (only ``None`` means 'take the plan's mode')."""
    if rules is not None or mesh is not None:
        warnings.warn(
            "passing rules=/mesh= to the step builders is deprecated; "
            "resolve a ParallelPlan and pass plan= instead "
            "(ParallelPlan.parse('dp=...').resolve(cfg, ...)). Legacy mesh "
            "strings are covered by ParallelPlan.from_legacy.",
            DeprecationWarning, stacklevel=3)
    if plan is not None:
        rules = rules if rules is not None else plan.rules
        mesh = mesh if mesh is not None else plan.mesh
        if opt_sharding_mode is None:
            opt_sharding_mode = plan.opt_shard
    return rules, mesh, opt_sharding_mode


def init_state(rng, cfg: ModelConfig, train: TrainConfig, *,
               plan: Optional[ResolvedPlan] = None, rules=None,
               mesh=None,
               opt_sharding_mode: Optional[str] = None) -> TrainState:
    """Initialize params + AdamW state. With a ``plan`` (or legacy
    ``rules``/``mesh``), every leaf is device_put onto its SO/EPSO sharding
    right after host init, so the first jitted step sees exactly the
    placement it was compiled for. (The state is still materialized on one
    device first — models that only fit sharded would jit init with these
    shardings as ``out_shardings``.)"""
    rules, mesh, opt_sharding_mode = _unpack_plan(
        plan, rules, mesh, opt_sharding_mode)
    if opt_sharding_mode is None:     # no plan, nothing requested
        opt_sharding_mode = "none"
    rules = _resolve_rules(cfg, train, rules, mesh)
    params = init_params(rng, cfg)
    opt = adamw_init(params)
    pd = jnp.dtype(train.param_dtype)
    # a copy where pd is the master's float32: params and master that share
    # a buffer could not be donated to the step
    params = jax.tree.map(
        lambda p: p.astype(pd) if p.dtype != pd else jnp.copy(p), params)
    state = TrainState(params, opt)
    sh = train_state_shardings(params, rules, opt_sharding_mode)
    if sh is not None:
        state = jax.tree.map(jax.device_put, state, sh)
    return state


def make_train_step(cfg: ModelConfig, parallel: Optional[ParallelConfig],
                    train: TrainConfig, *, plan: Optional[ResolvedPlan] = None,
                    rules=None, mesh=None,
                    opt_sharding_mode: Optional[str] = None,
                    state_shardings=None):
    """Build the train step.

    The canonical call passes a resolved ``plan`` (parallel/plan.py), which
    supplies rules + mesh + optimizer-sharding mode + pipeline schedule in
    one object and scopes its KernelPlan over the step's trace (so tile
    sizes / attention impl never leak across differently-planned steps);
    ``parallel`` may then be None (derived via ``plan.parallel_config()``).

    With ``opt_sharding_mode`` set ('none'|'so'|
    'epso') the step is returned jitted with the optimizer-state shardings as
    ``out_shardings`` — XLA derives the paper's reduce-scatter (grads into
    state shards) and all-gather (updated params) from the placement
    mismatch. A caller that already holds the ``train_state_shardings`` tree
    can pass it as ``state_shardings`` to skip the abstract init re-trace.
    With ``opt_sharding_mode=None`` (default) and no plan the raw function is
    returned and the caller jits it (legacy single-device path). Whatever is
    returned carries the resolved optimizer-overlap impl
    ('off'|'ring'|'xla') as ``.opt_overlap_impl``.

    With ``parallel.pp_stages > 1`` the loss/grad computation runs through
    the jitted 1f1b/gpipe pipeline executor instead of the microbatch
    accumulation scan: the layer stack is stage-sharded over the 'pp' mesh
    axis, ``parallel.microbatches`` become the pipeline microbatches, and
    activations/cotangents hand off between stages via ppermute
    (``parallel.pipeline.pipelined_loss_and_grads``). The optimizer tail
    (cast, LR, clip, AdamW, SO/EPSO placement) is shared with the non-PP
    path."""
    rules, mesh, opt_sharding_mode = _unpack_plan(
        plan, rules, mesh, opt_sharding_mode)
    if parallel is None:
        if plan is None:
            raise ValueError("make_train_step needs a ParallelConfig or a "
                             "resolved plan")
        parallel = plan.parallel_config()
    kplan = plan.kernel if plan is not None else None
    # live expert placement (parallel/placement.py): baked into the trace as
    # an (L, E) inverse-permutation constant; identity stays None so the
    # lowering (and census baselines) are untouched without rebalancing
    pl_rows = None
    pl_obj = plan.placement if plan is not None else None
    if pl_obj is not None and not pl_obj.is_identity:
        pl_rows = jnp.asarray(pl_obj.inverse_array(), jnp.int32)
    if (parallel.moe_dispatch is not None and cfg.moe is not None
            and cfg.moe.dispatch != parallel.moe_dispatch):
        # ParallelConfig is authoritative in the step builder, so every
        # executor the step composes runs one MoE dispatch mode
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, dispatch=parallel.moe_dispatch))
    rules = _resolve_rules(cfg, train, rules, mesh)
    if mesh is None and rules is not None:
        mesh = rules.mesh
    cd = jnp.dtype(train.compute_dtype)
    pd = jnp.dtype(train.param_dtype)
    rd = jnp.dtype(train.grad_reduce_dtype)
    nmb = parallel.microbatches
    pp = parallel.pp_stages
    if pp > 1 and cfg.arch_type not in PP_ARCH_TYPES:
        raise ValueError(f"pp_stages={pp} needs arch_type in {PP_ARCH_TYPES},"
                         f" not {cfg.arch_type!r}")
    if pp > 1 and pl_rows is not None:
        raise NotImplementedError(
            "a non-identity expert placement is not threaded through the "
            "pipeline executors yet (rebalance requires pp=1)")
    if (pp > 1 and parallel.pp_impl == "shardmap" and mesh is not None
            and "pp" in getattr(mesh, "shape", {})):
        # surface the wave-balance guardrail at build time, not first call
        check_pp_microbatches(max(nmb, 1), pp)

    # overlapped SO/EPSO update (optim/overlap.py): resolved and bucket-
    # planned once at build time. 'auto' (the default) turns the bucketed
    # ring schedule on for epso on a real mesh — the mode whose eager
    # GSPMD-derived collectives regressed — and keeps 'so'/'none' eager.
    # The request follows the _unpack_plan precedence: an explicit
    # ParallelConfig.opt_overlap wins, a None defers to the plan's
    # ``overlap=`` token. Off-mesh, 'auto' degrades to 'off' but an explicit
    # ring/xla request still errors (same behavior as launch/train.py).
    ov_req = getattr(parallel, "opt_overlap", None)
    if ov_req is None and plan is not None:
        ov_req = plan.opt_overlap
    on_mesh = rules is not None and rules.mesh is not None
    ov_impl = resolve_opt_overlap(ov_req, opt_sharding_mode or "none",
                                  mesh if on_mesh else None)
    update_plan = None
    if ov_impl != "off":
        _shapes = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg))
        update_plan = plan_update_buckets(_shapes, rules, opt_sharding_mode)

    # canonical expert grad-norm (optim/adamw.expert_slice_sumsq): expert
    # stacks contribute per-(L, E)-slice sums reduced in global-id order, so
    # the clip scale — the one scalar a rebalance could otherwise perturb
    # through shard-partial reassociation — is placement-invariant. Always
    # on for MoE configs so identity and placed traces share the association.
    expert_norm = None
    if cfg.moe is not None:
        _shapes = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg))
        _mask = expert_leaf_mask(_shapes, cfg.num_layers,
                                 cfg.moe.num_experts)
        if any(_mask):
            expert_norm = (_mask, pl_rows)

    def loss_for(params, mb):
        return loss_fn(params, mb, cfg, rules=rules, mesh=mesh,
                       sac=parallel.remat_policy, compute_dtype=cd,
                       placement=pl_rows)

    def split_mb(batch, n):
        """(B, ...) -> (n, B/n, ...) microbatch view — shared by the PP and
        acc_step paths so their splits can never diverge."""
        return jax.tree.map(
            lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch)

    def pp_uses_shardmap():
        """The per-stage executor needs real stage shards: a mesh with a
        'pp' axis. Off-mesh (the single-device PP simulation) falls back to
        the masked executor, which bit-matches the non-PP step."""
        return (parallel.pp_impl == "shardmap" and mesh is not None
                and "pp" in getattr(mesh, "shape", {}))

    def pp_loss_and_grads(params, batch):
        """Pipelined loss+grads in 1f1b/gpipe schedule order. Both executors
        share the same model pieces (embed_tokens / pipeline_stage_forward /
        lm_head_ce), tick tables and grad contract:

        * 'masked' — single-program SPMD; bit-equal math to running the
          stage slices sequentially per microbatch and summing grads in
          microbatch order (the acc_step contract), at the cost of every
          stage computing the masked embed/head+CE each tick.
        * 'shardmap' (default on a 'pp' mesh) — shard_map-per-stage: only
          stage 0 embeds and only the last stage runs the vocab-sized
          head+CE; loss is bit-equal to 'masked', grads to ~1 ulp."""
        n_mb = max(nmb, 1)
        mbs = split_mb(batch, n_mb)
        io_params = {k: v for k, v in params.items() if k != "layers"}
        stage_params = stack_stages(params["layers"], pp, name=cfg.name)

        def embed_fn(io, mb):
            return embed_tokens(io, mb["tokens"], cfg, compute_dtype=cd)

        stage_rows = []     # a stage's static PoolRows for one microbatch

        def block_fn(lp, h, mb):
            # NOTE: PP stages run the MoE dense path (c_align=1), not the
            # non-PP EP shard_map variant — GSPMD still shards the expert
            # compute via the param placement. Under dispatch='capacity'
            # the pool geometry matches the single-device reference but may
            # differ from an on-mesh non-PP step (c_align=dp) at shapes
            # that overflow; dispatch='dropless' is geometry-independent,
            # which closes that parity gap.
            h, aux, z, stats = pipeline_stage_forward(
                lp, h, cfg, sac=parallel.remat_policy)
            stage_rows.append(stats.rows)
            scal = {"aux": aux, "z": z}
            if cfg.is_moe:
                scal["counts"] = stats.counts
                scal["drops"] = stats.drops
            return h, scal

        def head_fn(io, h, mb):
            return lm_head_ce(io, h, mb["labels"], cfg)

        ca = cfg.moe.router_aux_coef if cfg.is_moe else 0.0
        cz = cfg.moe.router_z_coef if cfg.is_moe else 0.0
        nl = max(cfg.num_layers, 1)
        cots = {"ce": (jnp.arange(pp) == pp - 1).astype(jnp.float32),
                "aux": jnp.full((pp,), ca / nl, jnp.float32),
                "z": jnp.full((pp,), cz / nl, jnp.float32)}
        if cfg.is_moe:
            # telemetry channels: zero cotangents (counts/drops are derived
            # from integer routing decisions — no gradient flows through)
            cots["counts"] = jnp.zeros((pp, cfg.moe.num_experts), jnp.float32)
            cots["drops"] = jnp.zeros((pp,), jnp.float32)
        mb_b = batch["tokens"].shape[0] // n_mb
        seq = batch["tokens"].shape[1]
        baxes = tuple(rules.batch_axes) if rules is not None else ()
        if pp_uses_shardmap():
            ssum, g_io, g_stage = pipelined_loss_and_grads_per_stage(
                embed_fn, block_fn, head_fn, io_params, stage_params, mbs,
                cots, act_shape=(mb_b, seq, cfg.d_model), act_dtype=cd,
                schedule=parallel.pp_schedule, mesh=mesh, batch_axes=baxes)
        else:
            def stage_fn(io, lp, x, mb, sid):
                emb = embed_fn(io, mb)
                h = jnp.where(sid == 0, emb, x)      # stage 0 ingests tokens
                h, scal = block_fn(lp, h, mb)
                ce = head_fn(io, h, mb)              # masked off-last-stage
                return h, {"ce": ce, **scal}

            ssum, g_io, g_stage = pipelined_loss_and_grads(
                stage_fn, io_params, stage_params, mbs, cots,
                act_shape=(mb_b, seq, cfg.d_model), act_dtype=cd,
                schedule=parallel.pp_schedule, mesh=mesh, batch_axes=baxes)
        grads = dict(g_io)
        grads["layers"] = jax.tree.map(lambda g, p: g.reshape(p.shape),
                                       g_stage, params["layers"])
        grads = jax.tree.map(lambda g: g / n_mb, grads)
        ce = ssum["ce"][pp - 1] / n_mb
        aux = ssum["aux"].sum() / n_mb
        z = ssum["z"].sum() / n_mb
        loss = ce + (ca * aux + cz * z) / nl
        metrics = {"ce": ce}
        if cfg.is_moe:
            # sum over stages = sum over all layers and microbatches; the
            # per-layer mean makes counts sum to the whole-step T*K
            metrics["moe_counts"] = ssum["counts"].sum(axis=0) / nl
            metrics["moe_drops"] = ssum["drops"].sum()
            # a stage holds nl / pp layers; per layer, over all microbatches
            metrics["moe_rows_computed"] = np.float32(
                stage_rows[-1].n * pp / nl * n_mb)
        return loss, metrics, grads

    def _train_step(state: TrainState, batch: dict):
        params = state.params

        if pp > 1:
            loss, metrics, grads = pp_loss_and_grads(params, batch)
        elif nmb > 1:
            mbs = split_mb(batch, nmb)
            m0 = {"ce": jnp.zeros(())}
            if cfg.is_moe:
                m0["moe_counts"] = jnp.zeros((cfg.moe.num_experts,),
                                             jnp.float32)
                m0["moe_drops"] = jnp.zeros((), jnp.float32)

            mb_rows = []    # a microbatch's static moe_rows_computed

            def acc_step(carry, mb):
                gacc, lacc, macc = carry
                (loss, metrics), grads = jax.value_and_grad(
                    loss_for, has_aux=True)(params, mb)
                if cfg.is_moe:
                    mb_rows.append(metrics["moe_rows_computed"])
                gacc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                    gacc, grads)
                macc = {k: macc[k] + metrics[k] for k in macc}
                return (gacc, lacc + loss, macc), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            (grads, loss, macc), _ = jax.lax.scan(
                acc_step, (g0, jnp.zeros(()), m0), mbs)
            grads = jax.tree.map(lambda g: g / nmb, grads)
            loss = loss / nmb
            metrics = {"ce": macc["ce"] / nmb}
            if cfg.is_moe:
                # counts/drops are totals, not means: summed over
                # microbatches they cover the whole global batch
                metrics["moe_counts"] = macc["moe_counts"]
                metrics["moe_drops"] = macc["moe_drops"]
                metrics["moe_rows_computed"] = mb_rows[-1] * nmb
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_for, has_aux=True)(params, batch)

        with jax.named_scope("optim"):
            # paper: bf16 gradient reduction (cast before the DP reduction
            # that XLA derives from the state shardings), fp32 update
            grads = jax.tree.map(lambda g: g.astype(rd).astype(jnp.float32),
                                 grads)

            lr = warmup_cosine(state.opt.step, lr_peak=train.lr_peak,
                               lr_min=train.lr_min,
                               warmup_steps=train.warmup_steps,
                               total_steps=train.total_steps)
            clip_on = None
            if train.clip_after_warmup_only:
                clip_on = state.opt.step >= train.warmup_steps
            if ov_impl != "off":
                new_params, new_opt, om = overlapped_adamw_update(
                    grads, state.opt, rules=rules, mode=opt_sharding_mode,
                    impl=ov_impl, update_plan=update_plan, lr=lr,
                    beta1=train.beta1, beta2=train.beta2, eps=train.eps,
                    weight_decay=train.weight_decay,
                    grad_clip=train.grad_clip, clip_enabled=clip_on,
                    param_dtype=pd, expert_norm=expert_norm)
            else:
                new_params, new_opt, om = adamw_update(
                    grads, state.opt, lr=lr, beta1=train.beta1,
                    beta2=train.beta2, eps=train.eps,
                    weight_decay=train.weight_decay,
                    grad_clip=train.grad_clip, clip_enabled=clip_on,
                    param_dtype=pd, expert_norm=expert_norm)
        out_metrics = {"loss": loss, "lr": lr, **metrics, **om}
        return TrainState(new_params, new_opt), out_metrics

    def train_step(state: TrainState, batch: dict):
        # the body runs at trace time, so scoping the plan's kernel config
        # here pins tile sizes / attention impl for this step's lowering
        with use_kernel_plan(kplan):
            return _train_step(state, batch)

    if opt_sharding_mode is None:
        fn = train_step
    elif rules is None or rules.mesh is None:
        fn = jax.jit(train_step)
    else:
        ssh = state_shardings
        if ssh is None:
            shapes = jax.eval_shape(
                lambda: init_params(jax.random.PRNGKey(0), cfg))
            ssh = train_state_shardings(shapes, rules, opt_sharding_mode)
        # metrics subtree: None = unconstrained (scalars; XLA replicates)
        fn = jax.jit(train_step, out_shardings=(ssh, None))
    # the resolved overlap impl, for callers that record/assert what the
    # built step actually runs (bench_epso.py, test_opt_overlap.py)
    fn.opt_overlap_impl = ov_impl
    return fn


def make_prefill_step(cfg: ModelConfig, *, plan: Optional[ResolvedPlan] = None,
                      rules=None, mesh=None,
                      compute_dtype=jnp.bfloat16, into_cache: bool = False):
    """``into_cache=False``: the prefill_32k lowering — forward over the
    batch, last-position logits. ``into_cache=True``: the serve engine's
    admission lowering — ``prefill_step(params, tokens, cache, slots,
    lengths)`` writes the prompts' K/V into the given cache slots and
    returns (last_logits, new_cache); see models.prefill_with_cache."""
    rules, mesh, _ = _unpack_plan(plan, rules, mesh, "none")
    kplan = plan.kernel if plan is not None else None
    if into_cache:
        from repro.serve.engine import dropless_cfg
        scfg = dropless_cfg(cfg)   # serving must be batching-transparent

        def prefill_step(params, tokens, cache, slots, lengths):
            with use_kernel_plan(kplan):
                return prefill_with_cache(params, tokens, cache, slots,
                                          lengths, scfg, rules=rules,
                                          mesh=mesh,
                                          compute_dtype=compute_dtype)

        return prefill_step

    def prefill_step(params, batch):
        with use_kernel_plan(kplan):
            logits, _ = forward(params, batch, cfg, rules=rules, mesh=mesh,
                                sac="", compute_dtype=compute_dtype)
            return logits[:, -1]

    return prefill_step


def make_serve_step(cfg: ModelConfig, *, plan: Optional[ResolvedPlan] = None,
                    rules=None, compute_dtype=jnp.bfloat16,
                    sample: bool = False):
    """``index`` may be a scalar (lockstep batch, the decode_32k shape) or a
    (B,) vector of per-slot positions (continuous batching). With
    ``sample=True`` returns the serve engine's full decode lowering —
    ``(params, tokens, cache, positions, seeds, temperature, top_k, top_p)
    -> (next_tokens, new_cache)`` — built by serve.make_decode_fn."""
    rules, _, _ = _unpack_plan(plan, rules, None, "none")
    kplan = plan.kernel if plan is not None else None
    if sample:
        from repro.serve.engine import make_decode_fn
        return make_decode_fn(cfg, rules=rules, compute_dtype=compute_dtype,
                              kernel_plan=kplan)

    def serve_step(params, tokens, cache, index):
        with use_kernel_plan(kplan):
            return decode_step(params, tokens, cache, index, cfg, rules=rules,
                               compute_dtype=compute_dtype)

    return serve_step

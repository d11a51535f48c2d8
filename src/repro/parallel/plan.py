"""ParallelPlan — the declarative named-axis parallelism API.

One frozen object is the single source of truth for how a run maps onto
hardware, replacing the positional ``--mesh dp,pp,model`` spec + scattered
kwargs (``rules`` / ``mesh`` / ``opt_sharding_mode`` / ``pp_stages``) and
the retired module-global kernel knobs (the PR 4 compatibility aliases are
deleted; lint rule SL004 tombstones the symbols repo-wide).

Axes and their roles (every axis is explicit — no role inference on a
shared 'model' axis):

  ====  =========================================================
  axis  role
  ====  =========================================================
  pod   outermost data-parallel replication (multi-pod runs)
  dp    data parallelism — batch rows; FSDP/ZeRO-3 when ``fsdp``
  pp    pipeline stages (1f1b / gpipe over the stacked layer dim)
  ep    expert parallelism — MoE expert stacks sharded on dim 0
  tp    tensor parallelism — attention heads / MLP d_ff; composed
        with ``ep`` it shards the *experts'* d_ff (expert-TP), the
        mesh shape the legacy role-inferred API could not express
  ====  =========================================================

``ParallelPlan.parse("dp=2,pp=2,ep=2")`` / ``str(plan)`` round-trip;
``plan.resolve(cfg, train)`` builds the Mesh + ``ShardingRules`` exactly
once, and the resulting ``ResolvedPlan`` is threaded through
``train.init_state`` / ``make_train_step``, the launcher, ``Checkpointer``
(plan serialized into checkpoint metadata), ``serve.ServeEngine`` and the
dry-run tooling.

``KernelPlan`` scopes the kernel backend (tile sizes, interpret flag,
attention impl) to a plan instead of process-global mutable state:
``use_kernel_plan(plan.kernel)`` installs it for the current (tracing)
context and restores the previous one on exit — no cross-test leakage.
"""
from __future__ import annotations

import contextlib
import contextvars
from dataclasses import dataclass, field
from typing import Optional, Tuple

# ----------------------------------------------------------------------------
# KernelPlan — plan-scoped replacement for the retired module-global knobs
# ----------------------------------------------------------------------------

_BACKENDS = ("ref", "pallas", "xla")
_ATTN_IMPLS = ("blockwise", "pallas")


@dataclass(frozen=True)
class KernelPlan:
    """Kernel execution knobs, scoped to a plan (not a process).

    ``backend``   'ref' — pure-JAX reference paths everywhere (CPU default);
                  'xla' — XLA-optimized lowerings (uniform-capacity MoE);
                  'pallas' — the Pallas kernels (gmm/combine/swiglu; flash
                  attention for forward-only paths).
    ``tile_*``    Pallas grouped-matmul tile sizes (MXU-aligned defaults).
    ``tiles``     None — always use the explicit ``tile_*`` fields;
                  'auto' — resolve tiles per (kernel, shape bucket) from
                  the active measured tuning table (kernels/autotune.py) at
                  trace time, falling back to the ``tile_*`` fields on any
                  miss. An auto tile_m is only applied when it divides
                  ``tile_m`` (the dispatch pads groups to ``tile_m``, so a
                  non-divisor would break the gmm alignment contract).
    ``interpret`` None -> auto (True on CPU): kernels execute their Python
                  bodies — how this container validates TPU kernels.
    ``attn_impl`` 'blockwise' (pure-JAX online softmax, has a backward) |
                  'pallas' (forward-only flash kernel, serving/prefill).
    ``hw``        HardwareSpec name (launch/roofline.py registry) whose
                  VMEM budget the tile guardrail checks and whose roofline
                  the per-kernel attribution predicts against.
    ``strict``    guardrail escalation: a tile triple whose double-buffered
                  working set exceeds the ``hw`` VMEM budget warns by
                  default; with ``strict=True`` it raises.
    """
    backend: str = "ref"
    tile_m: int = 128
    tile_k: int = 512
    tile_n: int = 512
    interpret: Optional[bool] = None
    attn_impl: str = "blockwise"
    tiles: Optional[str] = None
    hw: str = "tpu-v5e"
    strict: bool = False

    def __post_init__(self):
        if self.backend not in _BACKENDS:
            raise ValueError(f"KernelPlan.backend must be one of {_BACKENDS},"
                             f" got {self.backend!r}")
        if self.attn_impl not in _ATTN_IMPLS:
            raise ValueError(f"KernelPlan.attn_impl must be one of "
                             f"{_ATTN_IMPLS}, got {self.attn_impl!r}")
        for k in ("tile_m", "tile_k", "tile_n"):
            if getattr(self, k) < 1:
                raise ValueError(f"KernelPlan.{k} must be >= 1, "
                                 f"got {getattr(self, k)}")
        if self.tiles not in (None, "auto"):
            raise ValueError(f"KernelPlan.tiles must be None or 'auto', "
                             f"got {self.tiles!r} (explicit tiles go in "
                             f"tile_m/tile_k/tile_n)")
        # VMEM-budget guardrail: the double-buffered working set of the
        # explicit tile triple must fit the target hardware's fast memory
        # (bf16 inputs, f32 accumulator — launch/roofline.py owns the math)
        from repro.launch.roofline import (get_hardware,
                                           gmm_working_set_bytes)
        spec = get_hardware(self.hw)     # validates the name too
        ws = gmm_working_set_bytes(self.tile_m, self.tile_k, self.tile_n)
        if ws > spec.vmem_bytes:
            msg = (f"KernelPlan tiles {self.tile_m}/{self.tile_k}/"
                   f"{self.tile_n}: double-buffered working set "
                   f"{ws / 2**20:.1f}MiB exceeds {spec.name} fast memory "
                   f"{spec.vmem_bytes / 2**20:.0f}MiB — the kernel would "
                   f"spill (shrink tile_k/tile_n or pick tiles='auto')")
            if self.strict:
                raise ValueError(msg)
            import warnings
            warnings.warn(msg, stacklevel=2)

    @property
    def moe_backend(self) -> str:
        """Stage-4/5 grouped-FFN backend this kernel plan selects."""
        return "pallas" if self.backend == "pallas" else "xla"

    def resolve_tiles(self, kernel: str, dims) -> Optional[tuple]:
        """Tile tuple for ``kernel`` at ``dims`` (a dim dict, e.g.
        ``{"g": G, "m": M, "k": K, "n": N}``) from the active tuning table,
        or None — the caller keeps its built-in defaults. Only consults the
        table under ``tiles='auto'``; reads happen at trace time, so the
        resolved tiles are baked into the jaxpr like the explicit fields."""
        if self.tiles != "auto":
            return None
        from repro.kernels.autotune import lookup_tiles
        return lookup_tiles(kernel, self.backend, dims)


# The active kernel plan: a contextvar (scoped, restores on exit) over a
# mutable process default (set_default_kernel_plan).
_DEFAULT_KERNEL_PLAN = [KernelPlan()]
_ACTIVE_KERNEL_PLAN: contextvars.ContextVar[Optional[KernelPlan]] = \
    contextvars.ContextVar("repro_kernel_plan", default=None)


def current_kernel_plan() -> KernelPlan:
    """The kernel plan in effect for the current (tracing) context."""
    p = _ACTIVE_KERNEL_PLAN.get()
    return p if p is not None else _DEFAULT_KERNEL_PLAN[0]


def default_kernel_plan() -> KernelPlan:
    """The process-default kernel plan (what applies outside any
    ``use_kernel_plan`` scope)."""
    return _DEFAULT_KERNEL_PLAN[0]


def set_default_kernel_plan(plan: KernelPlan) -> None:
    """Replace the process-default kernel plan (prefer the scoped
    ``use_kernel_plan``)."""
    _DEFAULT_KERNEL_PLAN[0] = plan


@contextlib.contextmanager
def use_kernel_plan(plan: Optional[KernelPlan]):
    """Scope ``plan`` as the active kernel plan; always restores the previous
    one — the leak-free replacement for the retired mutable module globals.
    ``None`` is a no-op scope (callers can pass a maybe-plan through)."""
    if plan is None:
        yield None
        return
    tok = _ACTIVE_KERNEL_PLAN.set(plan)
    try:
        yield plan
    finally:
        _ACTIVE_KERNEL_PLAN.reset(tok)


def _apply_tiles_token(kernel: KernelPlan, value: str,
                       spec: str = "") -> KernelPlan:
    """Apply a ``tiles=`` token ('auto' or 'TMxTKxTN') to a KernelPlan —
    shared by ``ParallelPlan.parse`` and ``launch/train.py --kernel-tiles``."""
    import dataclasses
    v = value.strip()
    if v == "auto":
        return dataclasses.replace(kernel, tiles="auto")
    try:
        tm, tk, tn = (int(x) for x in v.split("x"))
    except ValueError:
        where = f" in parallel spec {spec!r}" if spec else ""
        raise ValueError(f"tiles={value!r}{where}: want 'auto' or an "
                         f"explicit 'TMxTKxTN' triple, e.g. "
                         f"tiles=128x512x512") from None
    return dataclasses.replace(kernel, tiles=None, tile_m=tm, tile_k=tk,
                               tile_n=tn)


# ----------------------------------------------------------------------------
# ParallelPlan
# ----------------------------------------------------------------------------

# canonical axis order == mesh-major order (pod outermost, tp innermost) and
# the mesh axis name each plan axis maps to.
AXES: Tuple[Tuple[str, str], ...] = (
    ("pod", "pod"), ("dp", "data"), ("pp", "pp"), ("ep", "ep"), ("tp", "tp"))
_AXIS_KEYS = tuple(k for k, _ in AXES)
_OPT_MODES = ("none", "so", "epso")
_OPT_OVERLAPS = ("auto", "off", "ring", "xla")
_PP_SCHEDULES = ("gpipe", "1f1b")
_PP_IMPLS = ("shardmap", "masked")
_MOE_DISPATCH = ("capacity", "dropless")


@dataclass(frozen=True)
class ParallelPlan:
    """Declarative parallel-execution plan. See module docstring."""
    dp: int = 1
    pp: int = 1
    ep: int = 1
    tp: int = 1
    pod: int = 1
    opt_shard: str = "none"          # none | so | epso  (paper §3.2)
    # overlapped optimizer collectives (optim/overlap.py): None/'auto' = on
    # (ring) for epso on a real mesh, off otherwise; 'ring'/'xla' force an
    # impl; 'off' keeps the eager GSPMD-derived update tail.
    opt_overlap: Optional[str] = None    # None | auto | off | ring | xla
    pp_schedule: str = "1f1b"        # gpipe | 1f1b      (paper §2.2)
    pp_impl: str = "shardmap"        # shardmap (per-stage programs) | masked
    microbatches: int = 1
    fsdp: bool = False
    # MoE dispatch the plan pins across train/serve/dryrun/checkpoints:
    # None defers to the model's MoEConfig.dispatch
    moe_dispatch: Optional[str] = None   # None | capacity | dropless
    # live EP rebalancing policy (parallel/placement.py): None/'off' = static
    # identity placement; 'N:threshold' = every N steps, re-place experts
    # when the windowed max/mean rank load exceeds threshold.
    rebalance: Optional[str] = None      # None | off | '<int>:<float>'
    kernel: KernelPlan = field(default_factory=KernelPlan)

    def __post_init__(self):
        for k in _AXIS_KEYS + ("microbatches",):
            v = getattr(self, k)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"ParallelPlan.{k} must be a positive int, "
                                 f"got {v!r}")
        if self.opt_shard not in _OPT_MODES:
            raise ValueError(f"opt_shard must be one of {_OPT_MODES}, "
                             f"got {self.opt_shard!r}")
        if self.opt_overlap not in (None,) + _OPT_OVERLAPS:
            raise ValueError(f"opt_overlap must be None or one of "
                             f"{_OPT_OVERLAPS}, got {self.opt_overlap!r}")
        if self.pp_schedule not in _PP_SCHEDULES:
            raise ValueError(f"pp_schedule must be one of {_PP_SCHEDULES}, "
                             f"got {self.pp_schedule!r}")
        if self.pp_impl not in _PP_IMPLS:
            raise ValueError(f"pp_impl must be one of {_PP_IMPLS}, "
                             f"got {self.pp_impl!r}")
        if self.moe_dispatch is not None and \
                self.moe_dispatch not in _MOE_DISPATCH:
            raise ValueError(f"moe_dispatch must be None or one of "
                             f"{_MOE_DISPATCH}, got {self.moe_dispatch!r}")
        self.rebalance_params()          # validates the token's shape

    def rebalance_params(self) -> Optional[Tuple[int, float]]:
        """The parsed ``rebalance=`` policy: ``(interval_steps, threshold)``,
        or None when rebalancing is off (token absent or 'off')."""
        r = self.rebalance
        if r is None or r == "off":
            return None
        try:
            n_s, t_s = str(r).split(":", 1)
            n, t = int(n_s), float(t_s)
        except ValueError:
            raise ValueError(
                f"rebalance={r!r}: want 'off' or '<interval>:<threshold>' "
                f"(e.g. rebalance=50:1.25 — every 50 steps, re-place when "
                f"max/mean rank load exceeds 1.25)") from None
        if n < 1 or t < 1.0:
            raise ValueError(f"rebalance={r!r}: interval must be >= 1 and "
                             f"threshold >= 1.0 (a max/mean ratio)")
        return n, t

    # ---- spec string <-> plan ------------------------------------------------
    @classmethod
    def parse(cls, spec: str, **overrides) -> "ParallelPlan":
        """``'dp=2,pp=2,ep=2'`` -> ParallelPlan. Options ride along in the
        same spec: ``opt=epso``, ``schedule=gpipe``, ``mb=4``, ``fsdp``.
        Raises a descriptive ValueError on unknown roles or bad sizes."""
        if not str(spec).strip():
            raise ValueError("empty parallel spec (want e.g. 'dp=2,pp=2,ep=2')")
        kw: dict = {}

        def put(key, val):
            if key in kw:
                raise ValueError(f"duplicate {key!r} in parallel spec "
                                 f"{spec!r} (each axis/option once)")
            kw[key] = val

        for tok in str(spec).split(","):
            tok = tok.strip()
            if not tok:
                continue
            if tok == "fsdp":
                put("fsdp", True)
                continue
            if "=" not in tok:
                raise ValueError(
                    f"bad token {tok!r} in parallel spec {spec!r}: want "
                    f"axis=size (axes: {', '.join(_AXIS_KEYS)}) or an option "
                    f"(opt=, schedule=, mb=, fsdp)")
            k, v = (s.strip() for s in tok.split("=", 1))
            if k in _AXIS_KEYS or k in ("mb", "microbatches"):
                try:
                    n = int(v)
                except ValueError:
                    raise ValueError(f"{k}={v!r} in parallel spec {spec!r}: "
                                     f"size must be an integer") from None
                if n < 1:
                    raise ValueError(f"{k}={n} in parallel spec {spec!r}: "
                                     f"axis sizes must be >= 1")
                put("microbatches" if k in ("mb", "microbatches") else k, n)
            elif k in ("opt", "opt_shard"):
                put("opt_shard", v)
            elif k in ("overlap", "opt_overlap"):
                put("opt_overlap", v)
            elif k in ("schedule", "pp_schedule", "sched"):
                put("pp_schedule", v)
            elif k in ("impl", "pp_impl"):
                put("pp_impl", v)
            elif k in ("moe", "moe_dispatch"):
                put("moe_dispatch", v)
            elif k == "rebalance":
                put("rebalance", v)
            elif k == "tiles":
                put("tiles", v)
            elif k == "fsdp":
                put("fsdp", v not in ("0", "false", "False"))
            else:
                raise ValueError(
                    f"unknown role {k!r} in parallel spec {spec!r}; valid "
                    f"axes: {', '.join(_AXIS_KEYS)}; options: opt={{none|so|"
                    f"epso}}, overlap={{auto|off|ring|xla}}, "
                    f"schedule={{gpipe|1f1b}}, "
                    f"impl={{shardmap|masked}}, moe={{capacity|dropless}}, "
                    f"rebalance={{off|N:threshold}}, "
                    f"tiles={{auto|TMxTKxTN}}, mb=<int>, fsdp")
        kw.update(overrides)
        tiles = kw.pop("tiles", None)
        if tiles is not None:
            kern = kw.get("kernel", KernelPlan())
            kw["kernel"] = _apply_tiles_token(kern, tiles, spec)
        return cls(**kw)

    def __str__(self) -> str:
        """Canonical spec; ``ParallelPlan.parse(str(p)) == p`` (modulo
        kernel-plan fields other than the tile selection, which round-trips
        via the ``tiles=`` token)."""
        parts = [f"{k}={getattr(self, k)}" for k in ("dp", "pp", "ep", "tp",
                                                     "pod")
                 if getattr(self, k) != 1]
        if not parts:
            parts = ["dp=1"]
        if self.opt_shard != "none":
            parts.append(f"opt={self.opt_shard}")
        if self.opt_overlap is not None:
            parts.append(f"overlap={self.opt_overlap}")
        if self.pp_schedule != "1f1b":
            parts.append(f"schedule={self.pp_schedule}")
        if self.pp_impl != "shardmap":
            parts.append(f"impl={self.pp_impl}")
        if self.moe_dispatch is not None:
            parts.append(f"moe={self.moe_dispatch}")
        if self.rebalance is not None:
            parts.append(f"rebalance={self.rebalance}")
        k = self.kernel
        if k.tiles == "auto":
            parts.append("tiles=auto")
        elif (k.tile_m, k.tile_k, k.tile_n) != (128, 512, 512):
            parts.append(f"tiles={k.tile_m}x{k.tile_k}x{k.tile_n}")
        if self.microbatches != 1:
            parts.append(f"mb={self.microbatches}")
        if self.fsdp:
            parts.append("fsdp")
        return ",".join(parts)

    # ---- legacy translation --------------------------------------------------
    @classmethod
    def from_legacy(cls, mesh_spec: str, *, cfg=None, opt_shard: str = "none",
                    pp_schedule: str = "1f1b", microbatches: int = 1,
                    fsdp: bool = False) -> "ParallelPlan":
        """Translate the positional ``--mesh dp[,pp][,model]`` spec (+ the
        old role inference on the 'model' axis) into an explicit plan:
        MoE configs whose expert count divides the model-axis size get
        ``ep=<model>``; everything else (dense archs, non-divisible expert
        counts — the old 'etp' fallback) gets ``tp=<model>``."""
        from repro.launch.mesh import parse_mesh_spec
        dims, axes = parse_mesh_spec(mesh_spec)
        sizes = dict(zip(axes, dims))
        model = sizes.get("model", 1)
        ep, tp = 1, 1
        if model > 1:
            if (cfg is not None and getattr(cfg, "is_moe", False)
                    and cfg.moe.num_experts % model == 0):
                ep = model
            else:
                tp = model
        return cls(dp=sizes.get("data", 1), pp=sizes.get("pp", 1),
                   ep=ep, tp=tp, pod=sizes.get("pod", 1),
                   opt_shard=opt_shard, pp_schedule=pp_schedule,
                   microbatches=microbatches, fsdp=fsdp)

    # ---- derived -------------------------------------------------------------
    @property
    def num_devices(self) -> int:
        return self.pod * self.dp * self.pp * self.ep * self.tp

    def mesh_axes(self) -> Tuple[Tuple[str, int], ...]:
        """(mesh_axis_name, size) pairs, mesh-major order, size-1 axes
        dropped (a plan that is all ones has no mesh)."""
        return tuple((name, getattr(self, key)) for key, name in AXES
                     if getattr(self, key) > 1)

    def apply_to_model(self, cfg):
        """Fold plan-pinned model options into ``cfg``. Today that is the MoE
        dispatch mode: ``moe=...`` in the spec overrides ``MoEConfig.dispatch``
        so every consumer of the plan (train, serve, dryrun, checkpoints)
        agrees on one path. Returns ``cfg`` unchanged when nothing is pinned
        or the model has no MoE block."""
        import dataclasses
        if (self.moe_dispatch is None or getattr(cfg, "moe", None) is None
                or cfg.moe.dispatch == self.moe_dispatch):
            return cfg
        return dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch=self.moe_dispatch))

    def contracts(self) -> Tuple[str, ...]:
        """Sharding-contract ids (repro.analysis.contracts registry) the
        lowered step must satisfy under this plan. The plan declares its
        own invariants so the census (``repro.analysis.census``), the
        ``dryrun --analyze`` report and the CI gate all check the same
        set; contract-id strings are stable — they are stored in
        ANALYSIS_census.json baselines."""
        ids = ["no-host-transfer"]
        if self.num_devices > 1:
            ids.append("coll-vs-costmodel")
        if self.ep > 1 or self.tp > 1:
            # the ragged_dot GSPMD hazard only bites when expert buffers
            # are actually sharded (see core/moe.py's dropless notes)
            ids.append("no-gspmd-ragged-dot")
        if self.opt_shard == "epso":
            ids.append("epso-no-full-param-gather")
        if self.rebalance_params() is not None:
            # live placements must stay valid bijections (the census
            # records the placement metadata the contract checks)
            ids.append("placement-consistency")
        return tuple(ids)

    # ---- resolution ----------------------------------------------------------
    def validate_model(self, cfg) -> None:
        """Plan-vs-model divisibility checks, with errors that say what to
        change. Called by ``resolve`` (and usable standalone pre-flight)."""
        if self.pp > 1:
            if cfg.num_layers % self.pp != 0:
                raise ValueError(
                    f"plan pp={self.pp} does not divide {cfg.name}'s "
                    f"{cfg.num_layers} layers: each pipeline stage needs "
                    f"L/pp whole layers")
        if self.rebalance_params() is not None:
            if not getattr(cfg, "is_moe", False):
                raise ValueError(
                    f"plan rebalance={self.rebalance!r} but {cfg.name} has "
                    f"no experts: rebalancing permutes MoE expert stacks")
            if self.pp > 1:
                raise NotImplementedError(
                    f"rebalance={self.rebalance!r} with pp={self.pp}: live "
                    f"placement is not threaded through the pipeline "
                    f"executors yet (stage-sharded layer stacks would need "
                    f"per-stage placement rows)")
        if self.ep > 1:
            if not getattr(cfg, "is_moe", False):
                raise ValueError(
                    f"plan ep={self.ep} but {cfg.name} has no experts: "
                    f"expert parallelism needs a MoE config (use tp/dp)")
            if cfg.moe.num_experts % self.ep != 0:
                raise ValueError(
                    f"plan ep={self.ep} does not divide {cfg.name}'s "
                    f"{cfg.moe.num_experts} experts (ep x tp = "
                    f"{self.ep}x{self.tp}): pick ep | num_experts, or move "
                    f"the ways onto tp (expert-TP shards d_ff instead)")
        if self.tp > 1:
            if getattr(cfg, "is_moe", False):
                f = cfg.moe.d_ff_expert
                if f and f % self.tp != 0:
                    raise ValueError(
                        f"plan tp={self.tp} does not divide {cfg.name}'s "
                        f"expert d_ff={f} (ep x tp = {self.ep}x{self.tp}): "
                        f"expert-TP shards each expert's d_ff {self.tp}-way")
            elif cfg.d_ff and cfg.d_ff % self.tp != 0:
                raise ValueError(
                    f"plan tp={self.tp} does not divide {cfg.name}'s "
                    f"d_ff={cfg.d_ff}")

    def resolve(self, cfg, train=None, *, global_batch=None,
                devices=None) -> "ResolvedPlan":
        """Build the Mesh and ShardingRules ONCE for this plan + model.

        Token/batch rows shard over (pod, data[, ep]) — EP gathers tokens
        over its own axis exactly as the legacy 'ep' role did over 'model'.
        ``devices`` overrides the device pool (tests); by default the mesh
        spans the default backend's devices (``launch.mesh.make_forced_mesh``:
        on the CPU platform it asks for ``num_devices`` host devices, which
        only takes effect before backend init)."""
        import jax
        from jax.sharding import AxisType
        from repro.parallel.sharding import (ShardingRules, ep_batch_axes,
                                             resolve_batch_axes)

        self.validate_model(cfg)
        if global_batch is None and train is not None:
            global_batch = getattr(train, "global_batch", None)

        axes = self.mesh_axes()
        if not axes:
            return ResolvedPlan(plan=self, mesh=None, rules=None)
        shape = tuple(s for _, s in axes)
        names = tuple(n for n, _ in axes)
        if devices is None:
            from repro.launch.mesh import make_forced_mesh
            mesh = make_forced_mesh(shape, names, what=f"plan '{self}'")
        else:
            mesh = jax.make_mesh(shape, names, devices=devices,
                                 axis_types=(AxisType.Auto,) * len(shape))

        ep_axis = "ep" if self.ep > 1 else None
        tp_axis = "tp" if self.tp > 1 else None
        pp_axis = "pp" if self.pp > 1 else None
        data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
        if ep_axis is not None:
            # EP shards tokens over its axis too (paper §1: tokens over
            # (pod, data, ep)), falling back to pure-DP rows when the batch
            # cannot span data x ep — same helper as the legacy role path
            batch = ep_batch_axes(mesh, ep_axis, global_batch, data_axes)
        else:
            batch = resolve_batch_axes(global_batch, mesh, data_axes)
        rules = ShardingRules(mesh, batch, tp_axis, ep_axis,
                              fsdp=self.fsdp, pp_axis=pp_axis, cfg=cfg)
        return ResolvedPlan(plan=self, mesh=mesh, rules=rules)


# ----------------------------------------------------------------------------
# ResolvedPlan
# ----------------------------------------------------------------------------

@dataclass(frozen=True)
class ResolvedPlan:
    """A ParallelPlan bound to a Mesh + ShardingRules (built exactly once).
    This is the object threaded through init_state / make_train_step /
    Checkpointer / ServeEngine / dryrun — replacing the per-call
    rules/mesh/opt_sharding_mode kwarg threading."""
    plan: ParallelPlan
    mesh: object = None           # jax.sharding.Mesh | None (single device)
    rules: object = None          # ShardingRules | None
    # live ExpertPlacement (parallel/placement.py) baked into the step as a
    # trace-time constant; None = identity. Rebalance events swap it via
    # ``with_placement`` and rebuild the step (rare, so the recompile is
    # cheaper than carrying the permutation as a traced input every step).
    placement: object = None      # ExpertPlacement | None

    def with_placement(self, placement) -> "ResolvedPlan":
        """This plan with a different live placement (same mesh/rules —
        a placement never changes shardings, only which expert lives at
        which position)."""
        import dataclasses
        return dataclasses.replace(self, placement=placement)

    # ---- forwarding ----------------------------------------------------------
    @property
    def opt_shard(self) -> str:
        return self.plan.opt_shard

    @property
    def opt_overlap(self) -> Optional[str]:
        return self.plan.opt_overlap

    @property
    def pp_stages(self) -> int:
        return self.plan.pp

    @property
    def microbatches(self) -> int:
        return self.plan.microbatches

    @property
    def pp_schedule(self) -> str:
        return self.plan.pp_schedule

    @property
    def pp_impl(self) -> str:
        return self.plan.pp_impl

    @property
    def kernel(self) -> KernelPlan:
        return self.plan.kernel

    def parallel_config(self, *, remat_policy: str = "block"):
        """The ParallelConfig this plan implies for make_train_step."""
        from repro.configs.base import ParallelConfig
        return ParallelConfig(microbatches=self.microbatches,
                              remat_policy=remat_policy,
                              optimizer_sharding=self.opt_shard,
                              opt_overlap=self.plan.opt_overlap,
                              pp_stages=self.pp_stages,
                              pp_schedule=self.pp_schedule,
                              pp_impl=self.pp_impl,
                              moe_dispatch=self.plan.moe_dispatch)

    # ---- checkpoint metadata -------------------------------------------------
    def layout_signature(self) -> dict:
        """The axis layout a checkpoint records: what must agree between the
        saving and restoring plan for shardings to be interchangeable."""
        return {"axes": [[n, s] for n, s in self.plan.mesh_axes()],
                "opt_shard": self.plan.opt_shard,
                "fsdp": bool(self.plan.fsdp)}

    def spec(self) -> str:
        return str(self.plan)

    # ---- dry-run description -------------------------------------------------
    def describe(self, cfg, train=None, *, params=None) -> str:
        """Human-readable resolution report: axis table, per-param placement
        and projected bytes/device. Shape-only (jax.eval_shape) — zero
        allocation, safe for CI smoke."""
        import jax
        import numpy as np
        from repro.parallel.sharding import param_specs
        from repro.optim.epso import (optimizer_state_specs,
                                      state_bytes_per_device)

        lines = [f"plan     : {self.plan}",
                 f"devices  : {self.plan.num_devices}"]
        if self.mesh is None:
            lines.append("mesh     : none (single device)")
            return "\n".join(lines)
        lines.append("mesh     : " + " x ".join(
            f"{n}={s}" for n, s in self.plan.mesh_axes()))
        r = self.rules
        lines.append(f"batch    : rows over {tuple(r.batch_axes) or '(replicated)'}"
                     f"  tp={r.tp_axis or '-'} ep={r.ep_axis or '-'} "
                     f"pp={r.pp_axis or '-'} fsdp={r.fsdp}")
        if params is None:
            from repro.models import init_params
            params = jax.eval_shape(
                lambda: init_params(jax.random.PRNGKey(0), cfg))
        pspecs = param_specs(params, r)
        ospecs = optimizer_state_specs(params, r, self.plan.opt_shard)

        def ndev(spec):
            n = 1
            for e in spec:
                for a in (e if isinstance(e, tuple) else (e,)):
                    if a is not None:
                        n *= self.mesh.shape[a]
            return n

        lines.append(f"{'param':44s} {'shape':>20s} {'placement':24s} "
                     f"opt({self.plan.opt_shard})")
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        pflat = jax.tree.leaves(pspecs)
        oflat = jax.tree.leaves(ospecs)
        param_bytes = 0
        for (path, leaf), ps, os_ in zip(flat, pflat, oflat):
            key = jax.tree_util.keystr(path)
            param_bytes += int(np.prod(leaf.shape)) * 4 // ndev(ps)
            lines.append(f"{key:44s} {str(tuple(leaf.shape)):>20s} "
                         f"{str(ps):24s} {os_}")
        opt_bytes = state_bytes_per_device(params, r, self.plan.opt_shard)
        lines.append(f"projected bytes/device: params(fp32)="
                     f"{param_bytes / 2**20:.1f}MiB  "
                     f"opt-states={opt_bytes / 2**20:.1f}MiB")
        return "\n".join(lines)

"""End-to-end training launcher.

Wires together the full substrate: data pipeline (tokenize/shuffle/shard +
mmap loader), model zoo, FSMOE, AdamW with SO/EPSO state sharding jitted as
``out_shardings``, SAC, dual + model-only checkpointing with reshard-on-
restore, and the paper §4 failure-handling loop (NaN monitor + buffer-node
ClusterManager) as the main loop. Reduced-scale runs reproduce the paper's
Figure 1 training curves (see examples/train_mula.py).

Usage (single device):
  PYTHONPATH=src python -m repro.launch.train --arch mula-7b-a1b --scale smoke \
      --steps 100 --batch 8 --seq 128 --out runs/mula7b

Usage (simulated 8-device mesh, EP-aware sharded optimizer, survives an
injected hard node failure at step 12 via buffer-node swap + restore):
  PYTHONPATH=src python -m repro.launch.train --arch mula-7b-a1b --scale smoke \
      --mesh 4,2 --opt-shard epso --steps 20 --inject-hard-at 12

Usage (declarative plan: 2-way DP x 2 pipeline stages x 2-way EP, jitted
1f1b schedule composed with EPSO + fault tolerance):
  PYTHONPATH=src python -m repro.launch.train --arch mula-7b-a1b --scale smoke \
      --parallel dp=2,pp=2,ep=2 --opt-shard epso --steps 20

Usage (expert-TP: EP and TP as *distinct* axes — each expert's d_ff sharded
2-way on top of 2-way expert parallelism; inexpressible with --mesh):
  PYTHONPATH=src python -m repro.launch.train --arch mula-7b-a1b --scale smoke \
      --parallel dp=2,ep=2,tp=2 --steps 10

Usage (published widths, depth cut to one layer — the 50,304-token vocab
is kept; byte-tokenizer ids are valid ids in it):
  python -m repro.launch.train --arch mula-7b-a1b --scale full --layers 1 \
      --batch 2 --seq 2048 --steps 5

Usage (profile steps 3 and 4 into runs/mula7b/profile, for TensorBoard or
``jax.profiler.ProfileData``; device ops carry the step's named scopes in
their HLO metadata, host spans are train.input, train.fetch and ckpt.save):
  PYTHONPATH=src python -m repro.launch.train --arch mula-7b-a1b --scale smoke \
      --steps 10 --out runs/mula7b --profile 3:5

The legacy ``--mesh dp[,pp][,model]`` spec still works: it is translated to
a ParallelPlan via ``ParallelPlan.from_legacy`` (the old role inference on
the 'model' axis — EP when the expert count divides it, TP otherwise).
Both paths build the mesh over the default backend's devices; on the CPU
platform the plan's device product is requested as host devices through
XLA_FLAGS (see launch/mesh, parallel/plan).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.configs import (ParallelConfig, TrainConfig, get_config, reduced)
from repro.data import ByteTokenizer, ShardedDataLoader, preprocess_corpus
from repro.checkpoint import Checkpointer
from repro.ft import (ClusterManager, NaNMonitor, NodeFailure,
                      run_with_failure_handling)
from repro.parallel.plan import ParallelPlan
from repro.parallel.sharding import batch_sharding
from repro.train import init_state, make_train_step, train_state_shardings
from repro.models import padded_vocab


class RunResult(list):
    """History list (one dict per executed step, in step order) plus
    fault-tolerance bookkeeping from the launcher loop."""
    relaunches: int = 0
    replaced: list = ()


def synthetic_corpus(n_files: int = 4, docs_per_file: int = 64,
                     seed: int = 0):
    """Procedural text corpus: Zipf-ish word soup with structure, so the
    loss curve has signal (byte-level models learn digraph statistics)."""
    rng = np.random.default_rng(seed)
    words = ["the", "model", "expert", "router", "token", "aurora", "tile",
             "pipeline", "gradient", "optimizer", "state", "shard", "mixture",
             "attention", "scan", "chunk", "loss", "batch", "step", "node"]
    probs = 1.0 / np.arange(1, len(words) + 1)
    probs /= probs.sum()
    files = []
    for _ in range(n_files):
        docs = []
        for _ in range(docs_per_file):
            n = int(rng.integers(30, 120))
            docs.append(" ".join(rng.choice(words, size=n, p=probs)) + ".")
        files.append(docs)
    return files


def prepare_data(out_dir: str, *, context: int, seed: int = 0,
                 n_files: int = 4, docs_per_file: int = 256):
    data_dir = os.path.join(out_dir, "data")
    if not os.path.exists(os.path.join(data_dir, "meta.json")):
        preprocess_corpus(synthetic_corpus(n_files, docs_per_file, seed),
                          data_dir, context=context, seed=seed)
    return data_dir


def _env_int(name: str):
    v = os.environ.get(name)
    return int(v) if v else None


def _profile_steps(spec: str | None):
    """'START:STOP' -> (START, STOP), the steps [START, STOP) to profile."""
    if spec is None:
        return None
    try:
        start, stop = (int(x) for x in spec.split(":"))
    except ValueError:
        raise ValueError(f"--profile wants START:STOP, not {spec!r}")
    if not 0 <= start < stop:
        raise ValueError(f"--profile {spec}: need 0 <= START < STOP")
    return start, stop


def run(arch: str, *, scale: str = "smoke", steps: int = 100, batch: int = 8,
        seq: int = 128, out: str = "runs/default", lr: float = 1e-3,
        moe_impl: str = None, fur: bool = False, ckpt_interval: int = 50,
        microbatches: int = 1, sac: str = "block", seed: int = 0,
        log_every: int = 10, d_model: int = 256, layers: int = None,
        d_ff: int = 0, moe_dff: int = 0, mesh: str = None,
        parallel: str = None,
        opt_shard: str = None, opt_overlap: str = None,
        pp_schedule: str = None,
        pp_impl: str = None, moe_dispatch: str = None,
        kernel_tiles: str = None,
        rebalance: str = None, rebalance_force_at: int = None,
        n_buffer: int = 2,
        inject_hard_at: int = None, inject_soft_at: int = None,
        max_relaunches: int = 8, profile: str = None) -> RunResult:
    # opt_shard/pp_schedule: None = not passed (the --parallel spec's opt=/
    # schedule= options apply); an explicit value — including the defaults
    # 'none'/'1f1b' — overrides the spec.
    if opt_shard not in (None, "none") and not (mesh or parallel):
        raise ValueError(f"--opt-shard {opt_shard} needs --parallel (or the "
                         f"legacy --mesh): optimizer-state sharding is a "
                         f"placement over mesh axes")
    if mesh and parallel:
        raise ValueError("--mesh and --parallel are mutually exclusive "
                         "(--mesh is the legacy spelling of --parallel)")
    prof_steps = _profile_steps(profile)
    os.makedirs(out, exist_ok=True)

    # cfg is pure python — build it before the plan resolves (on the CPU
    # platform the resolve requests host devices, which must precede JAX
    # backend initialization)
    cfg = get_config(arch)
    if scale == "smoke":
        cfg = reduced(cfg, layers=layers or 2, d_model=d_model,
                      vocab=ByteTokenizer.VOCAB)
    elif layers:
        # published widths and vocabulary; --layers cuts depth only
        cfg = dataclasses.replace(cfg, num_layers=layers)
    if d_ff:
        cfg = dataclasses.replace(cfg, d_ff=d_ff)
    if cfg.moe is not None and (moe_impl or fur or moe_dff):
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, moe_impl=moe_impl or cfg.moe.moe_impl,
            forced_uniform_routing=fur,
            d_ff_expert=moe_dff or cfg.moe.d_ff_expert))

    # ---- the ParallelPlan: --parallel spec, or the legacy --mesh shim ----
    if parallel:
        pplan = ParallelPlan.parse(parallel)
        if opt_shard is not None:               # CLI flag overrides the spec
            pplan = dataclasses.replace(pplan, opt_shard=opt_shard)
        if opt_overlap is not None:
            pplan = dataclasses.replace(pplan, opt_overlap=opt_overlap)
        if pp_schedule is not None:
            pplan = dataclasses.replace(pplan, pp_schedule=pp_schedule)
        if pp_impl is not None:
            pplan = dataclasses.replace(pplan, pp_impl=pp_impl)
        if moe_dispatch is not None:
            pplan = dataclasses.replace(pplan, moe_dispatch=moe_dispatch)
    elif mesh:
        pplan = ParallelPlan.from_legacy(mesh, cfg=cfg,
                                         opt_shard=opt_shard or "none",
                                         pp_schedule=pp_schedule or "1f1b")
        if opt_overlap is not None:
            pplan = dataclasses.replace(pplan, opt_overlap=opt_overlap)
        if pp_impl is not None:
            pplan = dataclasses.replace(pplan, pp_impl=pp_impl)
        if moe_dispatch is not None:
            pplan = dataclasses.replace(pplan, moe_dispatch=moe_dispatch)
    else:
        pplan = None
    # one MoE dispatch path everywhere: fold the plan-pinned (or --moe-
    # dispatch) mode into the model config before anything resolves on it
    if pplan is not None:
        cfg = pplan.apply_to_model(cfg)
    elif moe_dispatch is not None and cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, dispatch=moe_dispatch))
    if kernel_tiles is not None:
        # 'auto' resolves tiles per shape bucket from the measured tuning
        # table (kernels/autotune.py); 'TMxTKxTN' pins an explicit triple.
        # Overrides a --parallel spec's tiles= option.
        from repro.parallel.plan import _apply_tiles_token
        if pplan is None:
            pplan = ParallelPlan()
        pplan = dataclasses.replace(
            pplan, kernel=_apply_tiles_token(pplan.kernel, kernel_tiles))
    if rebalance is not None:           # CLI flag overrides the spec token
        if pplan is None:
            raise ValueError("--rebalance needs --parallel (or --mesh): "
                             "rebalancing re-places experts over the EP axis")
        pplan = dataclasses.replace(pplan, rebalance=rebalance)
    opt_shard = pplan.opt_shard if pplan is not None else (opt_shard
                                                           or "none")

    # a pp plan axis > 1 turns on the jitted 1f1b/gpipe pipeline:
    # microbatches become pipeline microbatches.
    pp_stages = pplan.pp if pplan is not None else 1
    if microbatches == 1 and pplan is not None and pplan.microbatches > 1:
        microbatches = pplan.microbatches       # spec-supplied mb=
    if pp_stages > 1 and microbatches == 1:
        # only the untouched default is bumped; an explicit --microbatches
        # is honored as-is (any value >= 1 pipelines, just with more bubble).
        # The default must divide the batch — prefer 2*pp, fall back to pp.
        for cand in (2 * pp_stages, pp_stages):
            if batch % cand == 0:
                microbatches = cand
                print(f"pp={pp_stages}: pipeline microbatches defaulted to "
                      f"{microbatches}")
                break
    if pp_stages > 1 and batch % microbatches != 0:
        raise ValueError(f"--batch {batch} must divide into --microbatches "
                         f"{microbatches} pipeline microbatches")
    if pplan is not None:
        pplan = dataclasses.replace(pplan, microbatches=microbatches)
    pp_schedule = pplan.pp_schedule if pplan is not None \
        else (pp_schedule or "1f1b")
    pp_impl = pplan.pp_impl if pplan is not None else (pp_impl or "shardmap")

    # resolve once: builds the mesh (host devices requested first on CPU)
    plan = pplan.resolve(cfg, global_batch=batch) if pplan is not None \
        else None
    rules = plan.rules if plan is not None else None

    data_dir = prepare_data(out, context=seq, seed=seed)
    loader = ShardedDataLoader(data_dir, global_batch=batch)

    train = TrainConfig(param_dtype="float32", compute_dtype="float32",
                        grad_reduce_dtype="float32", lr_peak=lr,
                        lr_min=lr / 10, warmup_steps=max(steps // 20, 5),
                        total_steps=steps, seq_len=seq, global_batch=batch,
                        seed=seed)
    par = ParallelConfig(microbatches=microbatches, remat_policy=sac,
                         optimizer_sharding=opt_shard,
                         opt_overlap=pplan.opt_overlap
                         if pplan is not None else opt_overlap,
                         pp_stages=pp_stages, pp_schedule=pp_schedule,
                         pp_impl=pp_impl,
                         moe_dispatch=pplan.moe_dispatch
                         if pplan is not None else moe_dispatch)
    # resolve the overlap up front so the header/summary record what the
    # step will actually run (and bad combinations fail with the same error
    # make_train_step would raise)
    from repro.optim.overlap import resolve_opt_overlap
    ov_impl = resolve_opt_overlap(
        par.opt_overlap, opt_shard,
        plan.mesh if plan is not None else None)

    state = init_state(jax.random.PRNGKey(seed), cfg, train, plan=plan,
                       opt_sharding_mode=opt_shard)
    state_sh = train_state_shardings(state.params, rules, opt_shard)

    def build_step(plan_live):
        if plan_live is not None and plan_live.mesh is not None:
            return make_train_step(cfg, par, train, plan=plan_live,
                                   state_shardings=state_sh)
        if plan_live is not None:
            # meshless plan (all axes 1): no shardings to install, but the
            # plan still carries the KernelPlan (backend/tiles) that must
            # scope the step trace — dropping it here would silently ignore
            # --kernel-tiles
            return jax.jit(make_train_step(cfg, par, train, plan=plan_live))
        return jax.jit(make_train_step(cfg, par, train))

    # live state for the rebalance loop: the resolved plan (placement rides
    # on it) and the step compiled against it — a rebalance swaps both
    live = {"plan": plan, "step_fn": build_step(plan)}
    bsh = batch_sharding(rules)

    # ---- telemetry-driven EP rebalancing (parallel/placement.py) ---------
    reb = pplan.rebalance_params() if pplan is not None else None
    controller = None
    if (reb is not None or rebalance_force_at is not None) \
            and cfg.moe is not None:
        from repro.parallel.placement import RebalanceController
        interval, threshold = reb if reb is not None else (steps + 1, 1.0)
        ep_ax = rules.ep_axis if rules is not None else None
        ep = rules.mesh.shape[ep_ax] if (rules is not None and ep_ax
                                         and rules.mesh is not None) else 1
        controller = RebalanceController(
            num_layers=cfg.num_layers, num_experts=cfg.moe.num_experts,
            ep=ep, interval=interval, threshold=threshold)

    def set_placement(placement, state=None, *, prev=None):
        """Swap the live placement: optionally move the state arrays
        (prev -> placement), rebuild the jitted step against it, and keep
        the checkpointer manifest current."""
        if prev is not None and state is not None:
            from repro.parallel.placement import apply_placement
            mv = lambda s: apply_placement(s, prev, placement,
                                           cfg.num_layers,
                                           cfg.moe.num_experts)
            if state_sh is not None:
                mv = jax.jit(mv, donate_argnums=0, out_shardings=state_sh)
            else:
                mv = jax.jit(mv, donate_argnums=0)
            state = mv(state)
        live["plan"] = live["plan"].with_placement(
            None if placement is None or placement.is_identity
            else placement)
        live["step_fn"] = build_step(live["plan"])
        ckpt.placement = None if placement is None or placement.is_identity \
            else placement
        if controller is not None and placement is not None:
            controller.placement = placement
        return state

    inject_hard_at = inject_hard_at if inject_hard_at is not None \
        else _env_int("REPRO_INJECT_HARD_AT")
    inject_soft_at = inject_soft_at if inject_soft_at is not None \
        else _env_int("REPRO_INJECT_SOFT_AT")
    # failure-injection demos checkpoint often enough that the injected
    # failure has something newer than step 0 to restore; explicit intervals
    # on ordinary runs are honored as-is
    if (inject_hard_at is not None or inject_soft_at is not None) \
            and ckpt_interval >= steps:
        ckpt_interval = max(1, steps // 4)
        print(f"injection requested: ckpt interval clamped to {ckpt_interval}")
    ckpt = Checkpointer(os.path.join(out, "ckpt"), interval=ckpt_interval,
                        shardings=state_sh, plan=plan)
    n_nodes = max(2, len(jax.devices()))
    cluster = ClusterManager(n_active=n_nodes, n_buffer=n_buffer)

    # resume if a valid checkpoint exists (resharded onto the jitted placement)
    restored, ck_step = ckpt.restore(state)
    start = 0
    if restored is not None:
        state, start = restored, ck_step + 1   # ckpt holds post-step state
        print(f"resumed from step {start}")
        if ckpt.restored_placement is not None:
            # arrays on disk are already in placed order — adopt the manifest
            # placement without moving anything, rebuild the step against it
            set_placement(ckpt.restored_placement)
            print(f"resumed expert placement (non-identity) from manifest")
    # the loop consumes the loader's iterator; point it at the first step to
    # run so a resumed run replays the exact batch sequence an uninterrupted
    # one would have seen (never batch 0 again)
    loader.load_state_dict({"step": start})
    batches = iter(loader)

    nparams = sum(l.size for l in jax.tree.leaves(state.params))
    print(f"arch={cfg.name} params={nparams/1e6:.1f}M "
          f"vocab={padded_vocab(cfg)} "
          f"plan={pplan if pplan is not None else 'single'} "
          f"opt_shard={opt_shard} opt_overlap={ov_impl} pp={pp_stages}"
          + (f":{pp_schedule}:{pp_impl}" if pp_stages > 1 else ""))

    injected = {"hard": False, "soft": False}
    history = {}          # keyed by step: replays after restore overwrite
    t0 = time.time()

    profiling = [False]

    def profiler(step):
        """Start the trace at the first profiled step, stop it at the
        step after the last (so that step's checkpoint save is in it)."""
        if prof_steps is None:
            return
        if step == prof_steps[0] and not profiling[0]:
            jax.profiler.start_trace(os.path.join(out, "profile"))
            profiling[0] = True
        elif step >= prof_steps[1] and profiling[0]:
            jax.profiler.stop_trace()
            profiling[0] = False

    def train_one_step(state, step):
        if step == inject_hard_at and not injected["hard"]:
            injected["hard"] = True
            print(f"  !! injected HARD failure on node 0 @ step {step}")
            raise NodeFailure(cluster.active[0].node_id, "hard")
        profiler(step)
        with StepTraceAnnotation("train", step_num=step):
            return step_body(state, step)

    def step_body(state, step):
        with TraceAnnotation("train.input"):
            batch_np = next(batches)     # == loader.batch(step): pure in step
            if cfg.arch_type == "vlm":
                batch_np["image_embeds"] = np.zeros(
                    (batch, cfg.num_prefix_embeds, cfg.d_model), np.float32)
            if cfg.arch_type == "audio":
                half = seq // 2
                batch_np = {
                    "frame_embeds": np.random.default_rng(step).normal(
                        size=(batch, half, cfg.d_model)).astype(np.float32),
                    "tokens": batch_np["tokens"][:, :half],
                    "labels": batch_np["labels"][:, :half]}
            batch_dev = jax.tree.map(
                lambda a: jax.device_put(a, bsh) if bsh is not None
                else jnp.asarray(a), batch_np)
        state, metrics = live["step_fn"](state, batch_dev)
        # one host sync per step: batch every fetched metric into a single
        # device_get — per-metric float()/np.asarray() calls would each
        # block and serialize the overlapped step. The MoE telemetry (a
        # scalar + num_experts floats) rides the same batched transfer, so
        # the history artifact keeps its per-step moe_drops/moe_load_max
        # fields at no extra sync cost
        will_log = step % log_every == 0 or step == steps - 1
        fetch = {"loss": metrics["loss"], "lr": metrics["lr"],
                 "grad_norm": metrics["grad_norm"]}
        if "moe_drops" in metrics:
            fetch["moe_drops"] = metrics["moe_drops"]
            fetch["moe_counts"] = metrics["moe_counts"]
        with TraceAnnotation("train.fetch"):
            vals = jax.device_get(fetch)
        loss = float(vals["loss"])
        gnorm = float(vals["grad_norm"])
        per_rank = [loss]
        if step == inject_soft_at and not injected["soft"]:
            injected["soft"] = True
            print(f"  !! injected SOFT failure (NaN) on node 1 @ step {step}")
            per_rank = [loss, float("nan")]
        history[step] = {"step": step, "loss": loss,
                         "lr": float(vals["lr"]), "grad_norm": gnorm}
        moe_line = ""
        if "moe_drops" in vals:        # per-expert routing telemetry
            drops = float(vals["moe_drops"])
            counts = np.asarray(vals["moe_counts"])
            history[step]["moe_drops"] = drops
            history[step]["moe_load_max"] = float(
                counts.max() / max(counts.sum(), 1.0)) if counts.size else 0.0
            moe_line = (f" drops {drops:.0f} "
                        f"load_max {history[step]['moe_load_max']:.3f}")
        if controller is not None:
            # telemetry-driven EP rebalancing: feed the windowed counts to
            # the controller; at a window boundary (or the forced step) move
            # the expert stacks + EPSO states and rebuild the step. The
            # mutated state returns from this step, so the checkpointer
            # saves placed arrays together with the manifest placement.
            imb = controller.observe(np.asarray(vals["moe_counts"]))
            history[step]["moe_imbalance"] = imb
            moe_line += f" imb {imb:.2f}"
            do_force = (rebalance_force_at is not None
                        and step == rebalance_force_at)
            if controller.window_full() or do_force:
                prev = controller.placement
                new_pl = controller.propose(force=do_force)
                if new_pl is not None:
                    state = set_placement(new_pl, state, prev=prev)
                    history[step]["rebalanced"] = True
                    print(f"step {step:5d} rebalanced expert placement "
                          f"(imbalance {imb:.2f}, ep={controller.ep}, "
                          f"event #{controller.rebalances})")
        if will_log:
            dt = time.time() - t0
            print(f"step {step:5d} loss {loss:.4f} gnorm {gnorm:.3f} "
                  f"lr {float(vals['lr']):.2e}{moe_line} ({dt:.1f}s)")
        return state, {"loss": loss, "per_rank_losses": per_rank,
                       "per_rank_grad_norms": [gnorm]}

    def on_relaunch(state, failure, step):
        # rewind the batch stream to the restore point: the iterator re-reads
        # the shared step cursor on every next(), so this re-points it
        loader.load_state_dict({"step": step})
        if controller is not None:
            # re-sync the live placement to whatever the restored checkpoint
            # was written under (identity when the manifest carries none) —
            # the relaunch may roll back across a rebalance event
            from repro.parallel.placement import ExpertPlacement
            target = ckpt.restored_placement or ExpertPlacement.identity(
                cfg.num_layers, cfg.moe.num_experts)
            if target != controller.placement:
                set_placement(target)
            controller.reset_window()
        return state

    try:
        state, end_step, relaunches = run_with_failure_handling(
            train_one_step, state=state, checkpointer=ckpt, cluster=cluster,
            num_steps=steps, monitor=NaNMonitor(), start_step=start,
            max_relaunches=max_relaunches, on_relaunch=on_relaunch)
    finally:
        if profiling[0]:           # the run ended inside the profiled steps
            jax.profiler.stop_trace()

    result = RunResult(history[s] for s in sorted(history))
    result.relaunches = relaunches
    result.replaced = list(cluster.replaced)
    with open(os.path.join(out, "history.json"), "w") as f:
        json.dump(list(result), f)
    summary = {"arch": cfg.name, "steps": end_step, "mesh": mesh,
               "parallel": str(pplan) if pplan is not None else None,
               "opt_shard": opt_shard, "opt_overlap": ov_impl,
               "pp_stages": pp_stages,
               "moe_dispatch": cfg.moe.dispatch if cfg.moe is not None
               else None,
               "pp_schedule": pp_schedule if pp_stages > 1 else None,
               "pp_impl": pp_impl if pp_stages > 1 else None,
               "relaunches": relaunches,
               "replaced": result.replaced,
               "rebalance": pplan.rebalance if pplan is not None else None,
               "rebalances": controller.rebalances if controller is not None
               else 0,
               "final_imbalance": next(
                   (history[s].get("moe_imbalance")
                    for s in sorted(history, reverse=True)
                    if "moe_imbalance" in history[s]), None),
               "final_loss": result[-1]["loss"] if result else None}
    with open(os.path.join(out, "summary.json"), "w") as f:
        json.dump(summary, f)
    if relaunches:
        print(f"completed with {relaunches} relaunch(es); node swaps: "
              f"{result.replaced}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mula-1b")
    ap.add_argument("--scale", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--out", default="runs/default")
    ap.add_argument("--moe-impl", default=None,
                    choices=[None, "naive", "dense_capacity", "fsmoe"])
    ap.add_argument("--fur", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--sac", default="block")
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=None,
                    help="depth: smoke default 2; with --scale full, cuts "
                         "the published depth (widths and vocab unchanged)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--parallel", default=None,
                    help="declarative ParallelPlan spec, e.g. "
                         "'dp=2,pp=2,ep=2' or 'dp=2,ep=2,tp=2' (expert-TP); "
                         "axes: dp, pp, ep, tp, pod; options: opt=, "
                         "schedule=, moe=, tiles=, mb=, fsdp. The mesh "
                         "spans the default backend's devices (on CPU the "
                         "device product is requested as host devices); "
                         "pp>1 enables the jitted pipeline schedule")
    ap.add_argument("--mesh", default=None,
                    help="LEGACY device mesh: '4,2' = (data, "
                         "model), '2,2,2' = (data, pp, model); translated "
                         "to a ParallelPlan (MoE: model axis -> ep when "
                         "divisible, else tp). Prefer --parallel")
    ap.add_argument("--opt-shard", default=None,
                    choices=["none", "so", "epso"],
                    help="optimizer-state sharding (paper §3.2); overrides "
                         "a --parallel spec's opt= option (unset = spec "
                         "decides, default none)")
    ap.add_argument("--opt-overlap", default=None,
                    choices=["auto", "off", "ring", "xla"],
                    help="overlapped optimizer collectives (optim/overlap): "
                         "'auto' (default) runs the bucketed ppermute-ring "
                         "update for epso on a real mesh; 'ring'/'xla' force "
                         "an impl for so/epso; 'off' keeps the eager "
                         "GSPMD-derived update. Overrides a --parallel "
                         "spec's overlap= option")
    ap.add_argument("--pp-schedule", default=None,
                    choices=["gpipe", "1f1b"],
                    help="pipeline microbatch schedule when the plan has a "
                         "pp axis (paper §2.2: Mula-100B/220B train 1f1b); "
                         "overrides a --parallel spec's schedule= option")
    ap.add_argument("--pp-impl", default=None,
                    choices=["shardmap", "masked"],
                    help="pipeline executor: 'shardmap' (default) runs "
                         "per-stage programs over the 'pp' axis — only "
                         "stage 0 embeds, only the last stage runs the "
                         "vocab-sized head+CE; 'masked' is the legacy "
                         "single-program SPMD executor. Overrides a "
                         "--parallel spec's impl= option")
    ap.add_argument("--moe-dispatch", default=None,
                    choices=["capacity", "dropless"],
                    help="MoE token dispatch: 'capacity' (slot pool sized by "
                         "capacity_factor, over-capacity tokens dropped) or "
                         "'dropless' (pool sized for the worst-case routing, "
                         "no drops, naive-exact math). Overrides both the "
                         "model's MoEConfig.dispatch and a --parallel spec's "
                         "moe= option")
    ap.add_argument("--kernel-tiles", default=None,
                    help="Pallas kernel tile selection: 'auto' resolves "
                         "tiles per (kernel, shape bucket) from the "
                         "committed tuning table "
                         "(src/repro/kernels/tuning_table.json; regenerate "
                         "with benchmarks/bench_kernels.py --write-table), "
                         "or an explicit 'TMxTKxTN' triple, e.g. "
                         "128x512x512. Overrides a --parallel spec's "
                         "tiles= option")
    ap.add_argument("--rebalance", default=None,
                    help="telemetry-driven EP rebalancing "
                         "(parallel/placement.py): 'off' or 'N:threshold' "
                         "(e.g. 50:1.25 — every 50 steps, re-place the "
                         "experts over the EP axis when the windowed "
                         "max/mean rank load exceeds 1.25). Numerics-"
                         "preserving data movement: losses are unchanged "
                         "across a rebalance event. Overrides a --parallel "
                         "spec's rebalance= option")
    ap.add_argument("--rebalance-force-at", type=int, default=None,
                    help="force one rebalance event after this step "
                         "regardless of threshold (tests/goldens)")
    ap.add_argument("--log-every", type=int, default=10,
                    help="print the step line (loss/gnorm/lr + MoE routing "
                         "telemetry: drops, max expert load) every N steps")
    ap.add_argument("--n-buffer", type=int, default=2,
                    help="buffer nodes for hard-failure replacement")
    ap.add_argument("--inject-hard-at", type=int, default=None,
                    help="inject one hard node failure at this step "
                         "(also REPRO_INJECT_HARD_AT)")
    ap.add_argument("--inject-soft-at", type=int, default=None,
                    help="inject one soft (NaN) failure at this step "
                         "(also REPRO_INJECT_SOFT_AT)")
    ap.add_argument("--profile", default=None, metavar="START:STOP",
                    help="take a jax.profiler trace of steps [START, STOP) "
                         "into <out>/profile (off by default)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(args.arch, scale=args.scale, steps=args.steps, batch=args.batch,
        seq=args.seq, out=args.out, lr=args.lr, moe_impl=args.moe_impl,
        fur=args.fur, microbatches=args.microbatches, sac=args.sac,
        d_model=args.d_model, layers=args.layers, seed=args.seed,
        ckpt_interval=args.ckpt_interval, mesh=args.mesh,
        parallel=args.parallel,
        opt_shard=args.opt_shard, opt_overlap=args.opt_overlap,
        pp_schedule=args.pp_schedule,
        pp_impl=args.pp_impl, moe_dispatch=args.moe_dispatch,
        kernel_tiles=args.kernel_tiles,
        rebalance=args.rebalance,
        rebalance_force_at=args.rebalance_force_at,
        log_every=args.log_every, n_buffer=args.n_buffer,
        inject_hard_at=args.inject_hard_at,
        inject_soft_at=args.inject_soft_at, profile=args.profile)


if __name__ == "__main__":
    main()

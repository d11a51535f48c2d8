"""Chip smoke run: the Mula-7B-A1B train step on TPU, through the library's
own entry points (a resolved ``ParallelPlan``, ``init_state``,
``make_train_step``) with the paper's Pallas MoE kernels compiled for the
chip.

    python chip_smoke.py                # one chip (the default)
    python chip_smoke.py --four-chips   # EP=4 over four chips, vs one chip

One chip. Mula-7B-A1B at its published widths (d_model 2048, 16 heads of
128, 64 experts of d_ff 1024, top-8, vocab 50,304), depth cut to one layer
(625 M parameters, ~10 GB of bf16 params + fp32 master/m/v; two layers do
not fit 16 GB), random weights from ``--seed``, the paper's dtypes (bf16
params and compute over an fp32 master), dropless dispatch, and
``KernelPlan(backend="pallas")`` with explicit tiles. Phases:

1. kernels: one full-width MoE layer on a few hundred tokens, Pallas
   backend against the ``naive`` reference in float32 (output and every
   gradient), plus the token-count and flash-attention kernels against
   their references;
2. train: compile the step once, then 6 steps on one repeated batch of
   token ids drawn from ``--seed`` over the whole vocabulary. The loss must
   stay finite and fall, and the compiled step must contain the Pallas
   kernels (``tpu_custom_call``).

Four chips (``--four-chips``, that phase only): the same model, with the
load-balance loss off, under ``ep=4,opt=epso,moe=dropless`` for 3 steps,
against the same seed, batch and steps on one device, in the paper's
dtypes and again in float32 (params, compute, gradient reduction and XLA
matmuls). Dropless EP computes the single-device math, so every loss and
every grad norm agree, to bf16 rounding and to float32 accumulation order:
the check of the EP forward, backward and EPSO update together.

Every line but the last is progress. The last line, printed only when every
phase passed, is one JSON object: ``{"ok": true, "device": {...}}``. With no
TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEQ = 2048
LAYERS = 1
BATCH = 4            # from the step's memory_analysis at 1 layer (PERF.md)
TRAIN_STEPS = 6
EP_STEPS = 3
F32_SEQ = 512        # float32 state is 10 GB: shorter sequences to fit one chip
CHECK_TOKENS = 512   # tokens in the full-width MoE layer check
# max |pallas - naive| <= MOE_TOL * max |naive|, per tensor, both sides at
# float32 "highest" matmul precision. A routing or grouping error moves
# whole rows (an O(1) share); one bf16 pass per product would show as
# ~4e-3; what is left at f32 is accumulation order.
MOE_TOL = 1e-3
FLASH_TOL = 2e-2     # bf16 output (2**-8) over an f32 reference
# four chips vs one, every step's loss and grad norm, relative: one bf16
# ulp with the paper's dtypes (measured on a v5e: 5.4e-4 at most); 2**-16
# in float32, where what is left is accumulation order (2.7e-7 at most)
EP_RTOL = 2.0 ** -8
EP_F32_RTOL = 2.0 ** -16


def log(msg: str) -> None:
    print(msg, flush=True)


def model_config(layers: int = LAYERS):
    from repro.configs import get_config
    return dataclasses.replace(get_config("mula-7b-a1b"), num_layers=layers)


def train_config(seed: int, seq: int = SEQ, dtype: str = "bfloat16"):
    """The paper's recipe; ``dtype="bfloat16"`` gives its dtypes (bf16
    params, compute and gradient reduction over the fp32 master), float32
    puts all three in float32. Warmup 2, so that step 1 already moves the
    weights (the schedule gives lr 0 at step 0)."""
    from repro.configs import TrainConfig
    dt = {} if dtype == "bfloat16" else dict(compute_dtype=dtype,
                                             grad_reduce_dtype=dtype)
    return TrainConfig(seq_len=seq, global_batch=BATCH, warmup_steps=2,
                       total_steps=100, param_dtype=dtype, seed=seed, **dt)


def kernel_plan():
    from repro.parallel.plan import KernelPlan
    return KernelPlan(backend="pallas", tile_m=128, tile_k=512, tile_n=512)


def make_batch(cfg, batch: int, seq: int, seed: int) -> dict:
    import numpy as np
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}


def build_step(cfg, train, plan):
    """The library's train step for a resolved plan, with the state
    donated: at one layer the state is ~10 GB, so an input copy and an
    output copy of it do not both fit one chip."""
    import jax
    from repro.train import make_train_step
    return jax.jit(make_train_step(cfg, None, train, plan=plan),
                   donate_argnums=0)


def _rel_err(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


# ----------------------------------------------------------------------------
# kernels against their references
# ----------------------------------------------------------------------------

def check_moe_layer(cfg, seed: int, tag: str, mesh=None) -> None:
    """One full-width MoE layer, dropless, Pallas backend (under EP over
    ``mesh``'s 'ep' axis when given) against the naive MoE, in float32:
    the output and the gradients of every input."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.moe import init_moe_block, sparse_moe_block
    from repro.parallel.plan import use_kernel_plan

    m = dataclasses.replace(cfg.moe, dispatch="dropless")
    cfg_pallas = dataclasses.replace(cfg, moe=dataclasses.replace(
        m, moe_impl="fsmoe"))
    cfg_naive = dataclasses.replace(cfg, moe=dataclasses.replace(
        m, moe_impl="naive"))
    k_p, k_x, k_c = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = init_moe_block(k_p, cfg)
    x = jax.random.normal(k_x, (1, CHECK_TOKENS, cfg.d_model), jnp.float32)
    ct = jax.random.normal(k_c, x.shape, jnp.float32)
    if mesh is not None:
        params, x, ct = jax.device_put((params, x, ct),
                                       NamedSharding(mesh, P()))

    def out_and_grads(c, mesh_):
        def f(p, xx):
            out = sparse_moe_block(p, xx, c, mesh=mesh_, ep_axis="ep",
                                   batch_axes=())[0]
            return jnp.sum(out * ct), out
        return jax.jit(jax.grad(f, argnums=(0, 1), has_aux=True))

    with jax.default_matmul_precision("highest"):
        with use_kernel_plan(kernel_plan()):
            (gp_k, gx_k), out_k = out_and_grads(cfg_pallas, mesh)(params, x)
        (gp_n, gx_n), out_n = out_and_grads(cfg_naive, None)(params, x)
    errs = {"out": _rel_err(out_k, out_n), "d_x": _rel_err(gx_k, gx_n)}
    for name in ("router", "gate", "up", "down"):
        errs[f"d_{name}"] = _rel_err(gp_k[name], gp_n[name])
    worst = max(errs.values())
    log(f"{tag}: MoE layer (dropless, {CHECK_TOKENS} tokens, E="
        f"{m.num_experts}, K={m.experts_per_token}, d={cfg.d_model}, "
        f"f={m.d_ff_expert}) pallas vs naive, max|diff|/max|ref|: "
        + " ".join(f"{k}={v:.3e}" for k, v in errs.items())
        + f" (tolerance {MOE_TOL:g})")
    if not worst <= MOE_TOL:
        raise SystemExit(f"MoE check failed: {worst:.3e} > {MOE_TOL:g}")


def check_kernels(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.parallel.plan import use_kernel_plan

    check_moe_layer(cfg, seed, "kernels")
    m = cfg.moe
    k_x, k_c = jax.random.split(jax.random.PRNGKey(seed + 1))

    # Stage-2 token counts: exact integers
    idx = jax.random.randint(k_x, (CHECK_TOKENS * m.experts_per_token,), 0,
                             m.num_experts)
    with use_kernel_plan(kernel_plan()):
        counts = jax.jit(lambda i: ops.token_counts(i, m.num_experts, 0))(idx)
    want = ref.token_counts_ref(idx, m.num_experts, 0)
    exact = bool(jnp.array_equal(counts, want))
    log(f"kernels: token_counts ({idx.shape[0]} ids over {m.num_experts} "
        f"experts) exact={exact}")
    if not exact:
        raise SystemExit("token_counts check failed")

    # flash attention forward at the model's heads and sequence
    kq, kk, kv = jax.random.split(k_c, 3)
    shape = (1, SEQ, cfg.num_heads, cfg.head_dim)
    q, k, v = (jax.random.normal(kx, shape, jnp.bfloat16)
               for kx in (kq, kk, kv))
    with use_kernel_plan(kernel_plan()):
        o = jax.jit(ops.flash_attention)(q, k, v)
    fold = lambda a: a.transpose(0, 2, 1, 3).reshape(-1, SEQ, cfg.head_dim)
    with jax.default_matmul_precision("highest"):
        o_ref = ref.flash_attention_ref(fold(q), fold(k), fold(v))
    err = _rel_err(fold(o), o_ref)
    log(f"kernels: flash_attention fwd {shape} bf16 max|diff|/max|ref|="
        f"{err:.3e} (tolerance {FLASH_TOL:g})")
    if not err <= FLASH_TOL:
        raise SystemExit(f"flash attention check failed: {err:.3e}")


# ----------------------------------------------------------------------------
# phase 2: the one-chip train step
# ----------------------------------------------------------------------------

def run_steps(step, state, batch, steps: int, tag: str):
    """Run ``steps`` steps on one batch; returns (state, losses, gnorms)."""
    import jax
    losses, gnorms = [], []
    for i in range(steps):
        t = time.perf_counter()
        state, metrics = step(state, batch)
        jax.block_until_ready((state, metrics))
        dt = time.perf_counter() - t
        loss = float(metrics["loss"])
        gnorm = float(metrics["grad_norm"])
        losses.append(loss)
        gnorms.append(gnorm)
        log(f"{tag}: step {i} loss {loss:.6f} grad_norm {gnorm:.6f} "
            f"lr {float(metrics['lr']):.3e} wall {dt:.4f}s")
    return state, losses, gnorms


def train_one_chip(cfg, seed: int) -> None:
    import jax
    from repro.parallel.plan import ParallelPlan
    from repro.train import init_state

    train = train_config(seed)
    plan = ParallelPlan(moe_dispatch="dropless",
                        kernel=kernel_plan()).resolve(cfg, train)
    t = time.perf_counter()
    state = init_state(jax.random.PRNGKey(seed), cfg, train, plan=plan)
    jax.block_until_ready(state)
    n = sum(x.size for x in jax.tree.leaves(state.params))
    log(f"train: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"experts={cfg.moe.num_experts} top_k={cfg.moe.experts_per_token} "
        f"d_ff_expert={cfg.moe.d_ff_expert} vocab={cfg.vocab_size} "
        f"params={n} batch={BATCH}x{SEQ} plan='{plan.spec()}' "
        f"init {time.perf_counter() - t:.2f}s")
    batch = jax.device_put(make_batch(cfg, BATCH, SEQ, seed))
    step = build_step(cfg, train, plan)
    t = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    log(f"train: compile {time.perf_counter() - t:.2f}s")
    log(f"train: memory_analysis {compiled.memory_analysis()}")
    has_kernel = "tpu_custom_call" in compiled.as_text()
    log(f"train: compiled step contains tpu_custom_call: {has_kernel}")
    if not has_kernel:
        raise SystemExit("the compiled step holds no Pallas kernel")
    state, losses, _ = run_steps(compiled, state, batch, TRAIN_STEPS,
                                 "train")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"train: peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    if not all(math.isfinite(v) for v in losses):
        raise SystemExit(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise SystemExit(f"loss did not fall: {losses}")
    log(f"train: loss {losses[0]:.6f} -> {losses[-1]:.6f}")


# ----------------------------------------------------------------------------
# four chips: EP=4 dropless + EPSO against one device
# ----------------------------------------------------------------------------

def ep_vs_one_device(cfg, seed: int, dtype: str):
    """EP=4 + EPSO and one device, same seed, batch and steps. Returns
    {"one-device"|"ep=4": (losses, grad_norms)} and the EP mesh."""
    import contextlib
    import jax
    from repro.parallel.plan import ParallelPlan
    from repro.parallel.sharding import batch_sharding
    from repro.train import init_state

    seq = SEQ if dtype == "bfloat16" else F32_SEQ
    train = train_config(seed, seq, dtype)
    host_batch = make_batch(cfg, BATCH, seq, seed)
    # float32 XLA matmuls run at full precision, not one bf16 pass
    precision = (contextlib.nullcontext() if dtype == "bfloat16"
                 else jax.default_matmul_precision("highest"))
    runs, mesh = {}, None
    for name, pplan in (
            ("one-device", ParallelPlan(moe_dispatch="dropless",
                                        kernel=kernel_plan())),
            ("ep=4", ParallelPlan(ep=4, opt_shard="epso",
                                  moe_dispatch="dropless",
                                  kernel=kernel_plan()))):
        tag = f"{name} {dtype}"
        plan = pplan.resolve(cfg, train)
        mesh = plan.mesh or mesh
        state = init_state(jax.random.PRNGKey(seed), cfg, train, plan=plan)
        bsh = batch_sharding(plan.rules)
        batch = jax.device_put(host_batch, bsh) if bsh is not None \
            else jax.device_put(host_batch)
        t = time.perf_counter()
        with precision:
            compiled = build_step(cfg, train, plan).lower(state,
                                                          batch).compile()
        log(f"{tag}: plan '{plan.spec()}' batch {BATCH}x{seq} compile "
            f"{time.perf_counter() - t:.2f}s")
        if plan.mesh is not None and dtype == "bfloat16":
            gate = state.params["layers"]["moe"]["gate"]
            for sh in sorted(gate.addressable_shards,
                             key=lambda s: s.device.id):
                log(f"{tag}: device {sh.device.id} expert stack 'gate' "
                    f"shard {tuple(sh.data.shape)} of {tuple(gate.shape)}")
            per_dev = {}
            for leaf in jax.tree.leaves((state.opt.master, state.opt.m,
                                         state.opt.v)):
                for sh in leaf.addressable_shards:
                    per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                        + sh.data.nbytes
            for d in sorted(per_dev):
                log(f"{tag}: device {d} EPSO state bytes {per_dev[d]}")
        state, losses, gnorms = run_steps(compiled, state, batch, EP_STEPS,
                                          tag)
        runs[name] = (losses, gnorms)
        del state, compiled
    return runs, mesh


def four_chips(cfg, seed: int) -> None:
    # EP computes the load-balance loss over each rank's tokens and averages
    # it over the ranks (Switch-style); one device computes it over the
    # whole batch. The two are different objectives (at step 2 the grad
    # norms differ by 7% on a v5e), so both runs turn it off
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, router_aux_coef=0.0))
    for dtype, rtol in (("bfloat16", EP_RTOL), ("float32", EP_F32_RTOL)):
        runs, mesh = ep_vs_one_device(cfg, seed, dtype)
        (l1, g1), (le, ge) = runs["one-device"], runs["ep=4"]
        dl = [abs(a - b) / abs(b) for a, b in zip(le, l1)]
        dg = [abs(a - b) / abs(b) for a, b in zip(ge, g1)]
        log(f"ep=4 vs one-device, {dtype}: loss rel diff per step "
            + " ".join(f"{v:.3e}" for v in dl) + ", grad-norm rel diff "
            "per step " + " ".join(f"{v:.3e}" for v in dg)
            + f" (tolerance {rtol:g})")
        if not all(math.isfinite(v) for v in le + l1 + ge + g1):
            raise SystemExit(f"non-finite loss or grad norm ({dtype})")
        if not max(dl + dg) <= rtol:
            raise SystemExit(f"EP=4 does not agree with the one-device run "
                             f"({dtype})")
    check_moe_layer(cfg, seed, "ep=4", mesh=mesh)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the EP=4 phase (needs four chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    need = 4 if args.four_chips else 1
    if platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chip(s); jax sees "
              f"{len(devices)} {platform} device(s)", file=sys.stderr)
        return 1
    limit = (devices[0].memory_stats() or {}).get("bytes_limit")
    log(f"device: {platform} {devices[0].device_kind} x{len(devices)}, "
        f"bytes_limit {limit}, jax {jax.__version__}, compile cache "
        f"{cache_dir}")
    cfg = model_config()
    if args.four_chips:
        four_chips(cfg, args.seed)
    else:
        check_kernels(cfg, args.seed)
        train_one_chip(cfg, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

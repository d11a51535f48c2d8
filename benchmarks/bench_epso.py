"""Paper Table 3, EPSO column + Figure 6: SO vs EPSO.

Two parts:

* spec-level (``run(report)``, used by benchmarks/run.py): per MoE model on
  the 16x16 production AbstractMesh, analytic per-device optimizer-state
  bytes (master+m+v fp32) under SO and EPSO — the memory mechanism of
  Figure 6 and, via the update-step roofline, the paper's optimizer-step
  speedup mechanism (1.07-1.36x wall-clock on PVC);

* measured (``python benchmarks/bench_epso.py``): a subprocess with 8 forced
  CPU host devices trains a reduced Mula-7B-A1B on a (4,2) (data, model)
  mesh under ``opt_shard`` in {none, so, epso}, recording *placed* per-device
  optimizer-state bytes (summed over the shards resident on device 0) and
  the post-compile per-step median over ``n_iters`` timed steps (the
  bench_scaling.py shape — a single averaged loop was too flaky to gate on),
  into ``BENCH_epso.json`` at the repo root.

``--overlap`` controls the overlapped optimizer update (optim/overlap.py);
the default 'auto' runs epso with the bucketed ring overlap and keeps
none/so eager, so the recorded epso-vs-so delta is overlapped-vs-eager —
the step-time parity check_regression.py::check_epso_time gates on. Each
mode records the resolved ``opt_overlap`` impl it ran.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "src") not in sys.path:      # direct-script invocation
    sys.path.insert(0, os.path.join(ROOT, "src"))

import jax
import numpy as np
from jax.sharding import AbstractMesh, AxisType

from repro.configs import get_config
from repro.models import init_params
from repro.optim.epso import state_bytes_per_device
from repro.parallel.sharding import make_rules

MODELS = ["mula-7b-a1b", "mula-20b-a2b", "mula-100b-a7b", "mula-220b-a10b",
          "dbrx-132b", "mixtral-8x7b", "moonshot-v1-16b-a3b"]

MEASURE_MODES = ("none", "so", "epso")


def run(report):
    mesh = AbstractMesh((16, 16), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)
    for name in MODELS:
        cfg = get_config(name)
        shapes = jax.eval_shape(
            lambda c=cfg: init_params(jax.random.PRNGKey(0), c))
        rules = make_rules(cfg, mesh, kind="train", global_batch=256)
        so = state_bytes_per_device(shapes, rules, "so")
        epso = state_bytes_per_device(shapes, rules, "epso")
        report(f"epso_state_bytes_so[{name}]", so / 2**20)
        report(f"epso_state_bytes_epso[{name}]", epso / 2**20,
               derived=f"bytes_ratio={so / epso:.2f}x "
                       f"(paper optimizer speedups: 1.07-1.36x)")


# ---------------------------------------------------------------------------
# measured: simulated 8-device mesh
# ---------------------------------------------------------------------------

def measure(mesh_spec: str = "4,2", steps: int = 10, d_model: int = 64,
            seq: int = 32, batch: int = 8, overlap: str = "auto",
            modes=MEASURE_MODES) -> dict:
    """Runs inside a process whose backend sees enough devices.

    The orchestrating ``main()`` calls this once per mode in its own
    subprocess: timing the modes back-to-back in one process lets the
    earlier modes' compiled executables and allocator state skew the later
    ones (epso, timed last, measured up to ~25% slow purely from ordering).
    """
    import dataclasses
    import time

    from repro.configs import TrainConfig, reduced
    from repro.optim.overlap import resolve_opt_overlap
    from repro.parallel.plan import ParallelPlan
    from repro.train import init_state, make_train_step

    cfg = reduced(get_config("mula-7b-a1b"), d_model=d_model)
    tc = TrainConfig(param_dtype="float32", compute_dtype="float32",
                     grad_reduce_dtype="float32", lr_peak=1e-3, lr_min=1e-4,
                     warmup_steps=2, total_steps=steps + 1, seq_len=seq,
                     global_batch=batch)
    toks = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1), 0,
                              cfg.vocab_size)
    b = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    dev0 = jax.devices()[0]
    out = {}
    rules = None
    for mode in modes:
        pplan = ParallelPlan.from_legacy(mesh_spec, cfg=cfg, opt_shard=mode)
        ov_setting = overlap
        if overlap in ("ring", "xla") and mode == "none":
            # unsharded has no optimizer collectives to overlap; forcing an
            # impl would be rejected by resolve_opt_overlap
            ov_setting = "off"
        if ov_setting != "auto":
            pplan = dataclasses.replace(pplan, opt_overlap=ov_setting)
        plan = pplan.resolve(cfg, global_batch=batch)
        rules = plan.rules
        state = init_state(jax.random.PRNGKey(0), cfg, tc, plan=plan)
        # parallel=None: the plan supplies the ParallelConfig, including the
        # overlap= token, so the built step runs exactly what we record
        step_fn = make_train_step(cfg, None, tc, plan=plan)
        ov = step_fn.opt_overlap_impl
        assert ov == resolve_opt_overlap(plan.opt_overlap, mode, plan.mesh), \
            (mode, ov, plan.opt_overlap)
        # explicit warmup: compile + place, block on the whole output so no
        # async dispatch leaks into the first timed step
        state, m = step_fn(state, b)
        jax.block_until_ready((jax.tree.leaves(state.opt.m)[0], m["loss"]))
        placed = 0
        for leaf in (jax.tree.leaves(state.opt.master)
                     + jax.tree.leaves(state.opt.m)
                     + jax.tree.leaves(state.opt.v)):
            placed += sum(s.data.nbytes for s in leaf.addressable_shards
                          if s.device == dev0)
        # per-step median over n_iters (the bench_scaling.py shape): the
        # forced-host-device simulation shares CPU cores, so a single
        # averaged loop is too flaky for the CI parity gate
        ts = []
        for _ in range(steps):
            t0 = time.perf_counter()
            state, m = step_fn(state, b)
            jax.block_until_ready(m["loss"])
            ts.append(time.perf_counter() - t0)
        dt = sorted(ts)[len(ts) // 2]
        out[mode] = {
            "state_bytes_per_device": int(placed),
            "state_bytes_per_device_analytic": int(
                state_bytes_per_device(state.params, rules, mode)),
            "step_time_ms": dt * 1e3,
            "n_iters": steps,
            "opt_overlap": ov,
        }
    return {"mesh": mesh_spec, "devices": len(jax.devices()),
            "arch": cfg.name, "d_model": d_model, "seq": seq, "batch": batch,
            "n_iters": steps, "overlap": overlap, "modes": out}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="4,2")
    ap.add_argument("--steps", type=int, default=10,
                    help="timed steps per mode (median is recorded)")
    ap.add_argument("--overlap", default="auto",
                    choices=["auto", "off", "ring", "xla"],
                    help="opt_overlap plan option: 'auto' overlaps epso "
                         "(ring) and keeps none/so eager")
    ap.add_argument("--tiny", action="store_true",
                    help="CI bench-smoke mode: median-of-3")
    ap.add_argument("--out", default=os.path.join(ROOT, "BENCH_epso.json"))
    ap.add_argument("--_measure", choices=list(MEASURE_MODES),
                    help=argparse.SUPPRESS)   # child-process mode: one mode
    args = ap.parse_args(argv)
    if args.tiny:
        args.steps = min(args.steps, 3)

    if args._measure:
        print(json.dumps(measure(args.mesh, steps=args.steps,
                                 overlap=args.overlap,
                                 modes=(args._measure,))))
        return

    from repro.launch.mesh import forced_device_env
    shape = [int(x) for x in args.mesh.split(",")]
    env = forced_device_env(int(np.prod(shape)))
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    result = None
    for mode in MEASURE_MODES:          # one subprocess per mode (see measure)
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--_measure", mode,
             "--mesh", args.mesh, "--steps", str(args.steps),
             "--overlap", args.overlap],
            capture_output=True, text=True, env=env, timeout=1800)
        if r.returncode != 0:
            sys.stderr.write(r.stdout + r.stderr)
            raise SystemExit(f"bench_epso measured run failed (mode={mode})")
        part = json.loads(r.stdout.strip().splitlines()[-1])
        if result is None:
            result = part
        else:
            result["modes"].update(part["modes"])
    modes = result["modes"]
    assert modes["epso"]["state_bytes_per_device"] \
        < modes["so"]["state_bytes_per_device"], modes
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    for mode in MEASURE_MODES:
        m = modes[mode]
        print(f"{mode:5s} state_bytes/dev={m['state_bytes_per_device']:>10d} "
              f"step={m['step_time_ms']:.1f}ms (median of {m['n_iters']}, "
              f"overlap={m['opt_overlap']})")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()

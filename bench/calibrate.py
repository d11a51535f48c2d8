"""Readings that the limits of the correctness check are set from, for one
cell, on the chip at the cell's own size, in one process:

- the program's numbers (``bench/check.py``) on each seed: the lower
  readings;
- the control on the first ``--faults`` seeds: the plain reference in
  the nearest precision below the configuration's bfloat16 (every matrix
  product in float8, ``quant="fp8"`` of the reference) put in the
  program's place;
- the faults a training cell can have, planted in the reference put in
  the program's place: half of the batch left out (the mean over the
  rest), and on more than one chip each chip's tokens reaching only its
  own experts (the exchange left out). A step that returns its state
  unchanged reads 1 on ``change`` by construction and needs no run.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,...,12 --faults 3

Prints one JSON line per reading and a summary line last. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def as_program(readings: dict, beta1: float) -> dict:
    """Reference-shaped readings in the place of the program's."""
    return {"losses": readings["losses"],
            "m_norms": [g * (1 - beta1) for g in readings["grad_norms"]],
            "change_norms": readings["change_norms"]}


def calibrate(workload: str, seeds: list, faults: int, *,
              require_chip: bool = True, sizes: dict | None = None,
              emit=print) -> dict:
    import jax
    from bench import check, harness
    from bench.system import Program, ShardedDataLoader
    cell = harness.load_cell(workload, sizes)
    devices = (harness.find_devices(cell.chips) if require_chip
               else jax.devices())
    beta1 = cell.c["beta1"]
    prog = Program(cell.c, cell.spec, cell.seq_len, cell.batch)
    compiled = None
    rows = {"program": [], "control": [], "half_batch": [],
            "no_exchange": []}
    for n, seed in enumerate(seeds):
        t = time.perf_counter()
        with tempfile.TemporaryDirectory() as d:
            harness.write_shards(cell, seed, harness.CHECK_STEPS, d)
            loader = ShardedDataLoader(d, global_batch=cell.batch)
            batches = [loader.batch(i) for i in range(harness.CHECK_STEPS)]
            state = prog.init_state(seed, harness.first_step(cell.c))
            if compiled is None:
                compiled = prog.compile(prog.step_fn(), state,
                                        prog.put(batches[0]))
            state, readings, _ = harness.checked_steps(prog, compiled, state,
                                                       loader, seed)
        del state
        gc.collect()
        ref = harness.reference_readings(cell, seed, batches, devices)
        runs = [("program", readings)]
        if n < faults:
            runs.append(("control", as_program(harness.reference_readings(
                cell, seed, batches, devices, quant="fp8"), beta1)))
            runs.append(("half_batch", as_program(harness.reference_readings(
                cell, seed, batches, devices, drop_half=True), beta1)))
            if cell.chips > 1 and cell.c["arch_type"] == "moe":
                runs.append(("no_exchange", as_program(
                    harness.reference_readings(cell, seed, batches, devices,
                                               local_experts=True), beta1)))
        for kind, r in runs:
            nums = check.numbers(r, ref, beta1)
            rows[kind].append(nums)
            leaves = {k: [float(x) for x in v]
                      for k, v in check.leaf_gaps(r, ref, beta1).items()}
            emit(json.dumps({"seed": seed, "kind": kind, **nums,
                             "losses": list(r["losses"]),
                             "ref_losses": ref["losses"],
                             "leaves": leaves}))
        emit(json.dumps({"seed": seed, "seconds":
                         time.perf_counter() - t}))
    summary = {}
    for kind, vals in rows.items():
        if vals:
            agg = max if kind == "program" else min
            summary[kind] = {k: agg(v[k] for v in vals) for k in check.NAMES}
    summary["frozen"] = {"change": 1.0}
    emit(json.dumps({"summary": summary, "workload": workload,
                     "seeds": seeds}))
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", type=int, default=3)
    args = ap.parse_args(argv)
    from bench import harness, system
    system.enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        calibrate(args.workload, seeds, args.faults)
    except harness.NoChip as e:
        print(f"calibrate.py: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())

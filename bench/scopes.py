"""The program's named scopes in a profiler trace: device time by scope and
phase, and the per-layer readings that rest on it.

A trace does not carry the program's ``jax.named_scope`` names. They are
in the op_name metadata of the compiled program's HLO text, which
``scope_names`` reads into ``{instruction: (scope, phase)}``. The
reduction here matches a trace's operations to that map by instruction
name, so it reads a trace loaded without kernel names
(``bench.trace.load(path)``), whose labels all begin with the instruction.

The harness does not hand its metric readers the compiled program's text
or a step's metrics, so these readings are not metrics of
``BENCHMARK.json`` yet. On the chip,

    python3 bench/scopes.py --workload <cell> --seed <n>

traces a few steps of a cell as the harness's traced run does, and prints
the scope x phase table and the unscoped share to standard error and the
readings as one JSON line.
"""
from __future__ import annotations

import math
import os
import re
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import trace as T  # noqa: E402

# The program's scopes (PERF.md section 3): the top-level names, and the
# stages under ``moe``. A scope is read from the op_name path; the phase
# from JAX's own wrappers on it: ``jvp(`` forward, ``transpose(``
# backward, ``rematted_computation`` the backward's recompute, none the
# update after the gradients (the optimizer tail).
SCOPES = ("embed", "attn", "mlp", "moe", "head", "optim")
MOE_STAGES = ("router", "dispatch", "ffn", "combine", "exchange")
PHASES = ("forward", "backward", "recompute", "optimizer")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPER = re.compile(r"^[\w.-]+\((.*)\)$")

# Device times per step, by the scopes each sums. ``moe_dispatch_ms`` is
# the MoE layer's data movement around the expert GEMMs, without the EP
# exchange.
TIMES = {"attention_ms": ("attn",), "head_ce_ms": ("head",),
         "moe_dispatch_ms": ("moe/router", "moe/dispatch", "moe/combine"),
         "optimizer_ms": ("optim",)}


def scope_of(op_name: str) -> tuple:
    """(scope or None, phase) of an op_name path such as
    ``jit(train_step)/transpose(jvp())/while/body/moe/ffn/dot_general``:
    the innermost top-level scope on the path, refined by the innermost
    MoE stage below ``moe``. The last component is the primitive."""
    parts = []
    for p in op_name.split("/")[:-1]:
        while (m := _WRAPPER.match(p)):
            p = m.group(1)
        parts.append(p)
    scope, at = None, -1
    for i, p in enumerate(parts):
        if p in SCOPES:
            scope, at = p, i
    if scope == "moe":
        stages = [p for p in parts[at + 1:] if p in MOE_STAGES]
        if stages:
            scope = f"moe/{stages[-1]}"
    if "rematted_computation" in op_name:
        phase = "recompute"
    elif "transpose(" in op_name:
        phase = "backward"
    elif "jvp(" in op_name:
        phase = "forward"
    else:
        phase = "optimizer"
    return scope, phase


def scope_names(hlo_text: str) -> dict:
    """{instruction: (scope, phase)} for the instructions of a compiled
    program's HLO text whose op_name metadata lies under a named scope."""
    out = {}
    for line in hlo_text.splitlines():
        m = T._INSTR.match(line.strip().removeprefix("ROOT "))
        n = _OP_NAME.search(line)
        if not (m and n):
            continue
        scope, phase = scope_of(n.group(1))
        if scope is not None:
            out[m.group(1)] = (scope, phase)
    return out


def instruction(label: str) -> str:
    """The instruction of an operation's label ``"<instruction> <opcode>"``."""
    return label.split(" ", 1)[0]


def scope_time(trace: T.Trace, device: str, scopes: dict, match,
               phase: str | None = None) -> int:
    """Device time, clipped to the window and with nested operations
    counted once, of the operations whose scope ``match`` accepts (and in
    ``phase``, if given)."""
    lo, hi = trace.window()
    sel = []
    for name, a, b in trace.ops[device]:
        s = scopes.get(instruction(name))
        if s and match(s[0]) and (phase is None or s[1] == phase):
            sel.append((a, b))
    return T.length(T.clip(sel, lo, hi))


def scope_ms(trace: T.Trace, scopes: dict, steps: int,
             match) -> float | None:
    """Device time in ms per step of the operations whose scope ``match``
    accepts, averaged over the devices; None where there is no trace or no
    instruction of the program lies under such a scope."""
    if trace is None or not trace.ops or steps <= 0:
        return None
    if not any(match(s) for s, _ in scopes.values()):
        return None
    t = [scope_time(trace, d, scopes, match) for d in trace.ops]
    return sum(t) / len(t) / steps / 1e6


def scope_table(trace: T.Trace, scopes: dict, steps: int) -> dict:
    """{scope: {phase: ms per step}}, averaged over the devices."""
    nd = max(len(trace.ops), 1)
    out = {}
    for name in sorted({s for s, _ in scopes.values()}):
        row = {ph: sum(scope_time(trace, d, scopes,
                                  lambda s, n=name: s == n, ph)
                       for d in trace.ops) / nd / steps / 1e6
               for ph in PHASES}
        out[name] = {ph: v for ph, v in row.items() if v > 0}
    return out


def unscoped(trace: T.Trace, scopes: dict, k: int = 6) -> tuple:
    """(share of busy time under no scope, [[opcode, ms]] of that time by
    the innermost operation running in it), averaged over the devices. A
    moment counts as unscoped when no scoped operation runs, so an
    unscoped operation nested in a scoped one (a copy in a scoped loop)
    is the scope's time."""
    lo, hi = trace.window()
    share, tot = 0.0, {}
    for d, ops in trace.ops.items():
        edges = []
        for j, (_, a, b) in enumerate(ops):
            a, b = max(a, lo), min(b, hi)
            if b > a:
                edges += [(a, 1, j), (b, -1, j)]
        edges.sort()
        active, n_scoped, free, prev = {}, 0, 0, None
        for t, kind, j in edges:
            if active and not n_scoped and t > prev:
                inner = max(active, key=lambda i: (ops[i][1], -ops[i][2]))
                op = ops[inner][0].split(" ")[-1]
                tot[op] = tot.get(op, 0) + t - prev
                free += t - prev
            scoped = instruction(ops[j][0]) in scopes
            if kind > 0:
                active[j] = True
                n_scoped += scoped
            else:
                del active[j]
                n_scoped -= scoped
            prev = t
        busy_ns = T.length(T.busy(trace, d))
        share += free / busy_ns if busy_ns else 0.0
    nd = max(len(trace.ops), 1)
    rows = sorted(tot.items(), key=lambda x: -x[1])[:k]
    return share / nd, [[o, v / nd / 1e6] for o, v in rows]


def scope_line(trace: T.Trace, scopes: dict, steps: int) -> str:
    """One line: the scope x phase table in ms per step, and the unscoped
    share of busy time with its largest opcodes (per step)."""
    table = scope_table(trace, scopes, steps)
    cells = "; ".join(
        f"{s} " + " ".join(f"{ph}={v:.3f}" for ph, v in row.items())
        for s, row in table.items())
    share, ops = unscoped(trace, scopes)
    return (f"scopes (ms/step): {cells}; unscoped {100 * share:.2f}% of "
            f"busy: " + " ".join(f"{o}={v / steps:.3f}" for o, v in ops))


def optimizer_bytes(c: dict) -> int:
    """HBM bytes the AdamW update must move per step: for each parameter of
    the program's layout (``bench/weights.layout``, padded vocabulary rows
    included) the float32 master, m and v each read and written (24 B) and
    the bf16 parameter written (2 B). Gradient reads are left out, so a
    time over these bytes gives a lower bound on the update's share of the
    HBM roofline."""
    from bench import weights
    n = sum(math.prod(shape) for _, shape, _ in weights.layout(c))
    return 26 * n


def readings(trace: T.Trace, scopes: dict, steps: int, *, chips: int,
             opt_bytes: float, hbm_bytes_per_s: float,
             metrics: dict | None = None) -> dict:
    """The per-layer readings of a traced run, leaving out each one that
    finds nothing to read: the ``TIMES``; ``optimizer_hbm_roofline``, the
    share of the HBM roofline ``opt_bytes`` in ``optimizer_ms`` reach on
    one chip (under EPSO each chip updates its shard while the gathers run
    in the same scope, so the count is left undefined on several); and
    ``moe_pool_occupancy``, sum(``moe_counts``) / ``moe_rows_computed``
    of a step's ``metrics``, the share of the rows the expert GEMMs
    compute that carry a routed token."""
    out = {}
    for name, names in TIMES.items():
        v = scope_ms(trace, scopes, steps, lambda s, n=names: s in n)
        if v is not None:
            out[name] = v
    if chips == 1 and opt_bytes and out.get("optimizer_ms"):
        out["optimizer_hbm_roofline"] = 100.0 * opt_bytes / (
            out["optimizer_ms"] / 1e3 * hbm_bytes_per_s)
    m = metrics or {}
    if "moe_counts" in m and float(m.get("moe_rows_computed", 0)) > 0:
        out["moe_pool_occupancy"] = 100.0 * float(
            np.sum(m["moe_counts"])) / float(m["moe_rows_computed"])
    return out


WARMUP_STEPS = 3


def main(argv=None) -> int:
    import argparse
    import json
    import tempfile

    import jax

    from bench import harness, system
    ap = argparse.ArgumentParser(description="Trace a benchmark cell's "
                                 "steps and split their device time by "
                                 "the program's named scopes.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    system.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.find_devices(cell.chips)
    except harness.NoChip as e:
        print(f"scopes.py: {e}", file=sys.stderr)
        return 3
    peak = harness.peak_table(devices[0].device_kind)
    prog = system.Program(cell.c, cell.spec, cell.seq_len, cell.batch)
    with tempfile.TemporaryDirectory() as d:
        harness.write_shards(cell, args.seed,
                             WARMUP_STEPS + harness.TRACE_STEPS, d)
        loader = system.ShardedDataLoader(d, global_batch=cell.batch)
        state = prog.init_state(args.seed, harness.first_step(cell.c))
        compiled = prog.compile(prog.step_fn(), state,
                                prog.put(loader.batch(0)))
        scopes = scope_names(compiled.as_text())
        for k in range(WARMUP_STEPS):
            state, met = compiled(state, prog.put(loader.batch(k)))
        metrics = jax.device_get(met)
        # no kernel names, so that every label keeps its instruction
        ctx = harness.Context(cell, cell.chips, peak, 0.0, 0.0)
        harness.traced_window(ctx, prog, compiled, state, loader,
                              WARMUP_STEPS)
    tr = ctx.trace
    harness.log(scope_line(tr, scopes, ctx.steps))
    busy = [T.length(T.busy(tr, d)) for d in tr.ops]
    share, ops = unscoped(tr, scopes)
    out = {"workload": args.workload, "steps": ctx.steps,
           "busy_ms": sum(busy) / len(busy) / ctx.steps / 1e6,
           "unscoped_share": share,
           "readings": readings(tr, scopes, ctx.steps, chips=cell.chips,
                                opt_bytes=optimizer_bytes(cell.c),
                                hbm_bytes_per_s=peak["hbm_bytes_per_s"],
                                metrics=metrics),
           "scopes": scope_table(tr, scopes, ctx.steps)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The benchmark harness: one run of one cell.

Everything is found by name. The cell file ``bench/cells/<workload>.json``
names its configuration (``bench/configs/<config>.json``, with the plain
reference ``bench/configs/<reference>.py`` beside it), its traffic mix
(``bench/traffic/<traffic>.json``, read by the generator of its token law
``bench/laws/<law>.py``), its chips, the program's plan and the limits of
the correctness check. Each metric of ``BENCHMARK.json`` that applies to
the cell is read by ``bench/metrics/<metric>.py``. Peaks come from
``bench/peaks.json`` by ``device_kind``.

The reference module owns its architecture: it exports ``make_step``
(the float32 training step, with the ``quant`` control and the
``drop_half`` and ``local_experts`` faults), ``layout`` (the weight
layout, the program's parameter tree), ``flops_per_token`` and
``expert_gemm_flops`` (the FLOP counts of ``mfu``, ``step_mfu`` and
``expert_gemm_roofline``), and may declare ``REFERENCE_KEYS``, the
configuration keys it alone reads. The configuration's other keys reach
the program's config objects by field name (``bench/system.py``), so a new
architecture comes as new files: a configuration, its reference module, a
traffic mix, a cell and any metric readers it needs.

A run: set-up (find the chips, write the cell's token shards, make the
state on the device from the seed at the step counter ``first_step``,
compile the step with the state donated, drive the first three steps
through the window's own step and loader and record what the correctness
check compares, then warm up until two step times agree); the window
(``--trace 0``: as many steps as ``--seconds`` holds, each reading a new
batch through the loader; ``--trace 1``: a few steps under the profiler);
then, with the program's state freed, the plain reference over the first
three batches, and the comparison.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import math
import os
import sys
import tempfile
import time

import numpy as np

from bench import configs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CHECK_STEPS = 3
TRACE_STEPS = 8
WARMUP_AGREE = 0.02          # two warm-up step times within 2% of each other
WARMUP_MIN, WARMUP_MAX = 2, 30
BATCHES_PER_SECOND = 20      # batches written per second of window
EXTRA_BATCHES = 40           # for the checked and warm-up steps


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def read_json(*parts) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    spec: dict           # the cell file
    c: dict              # the configuration
    mix: dict            # the traffic mix

    @property
    def chips(self) -> int:
        return self.spec["chips"]

    @property
    def seq_len(self) -> int:
        return self.mix["seq_len"]

    @property
    def batch(self) -> int:
        return self.mix["sequences_per_step"]

    @property
    def tokens(self) -> int:
        return self.batch * self.seq_len

    def reference(self):
        return configs.reference(self.c)


def load_cell(workload: str, sizes: dict | None = None) -> Cell:
    """The cell's files; ``sizes`` overrides configuration and traffic
    keys (tests run the harness at a size the CPU can hold)."""
    spec = read_json("cells", f"{workload}.json")
    c = read_json("configs", f"{spec['config']}.json")
    mix = read_json("traffic", f"{spec['traffic']}.json")
    sizes = sizes or {}
    c.update(sizes.get("config", {}))
    mix.update(sizes.get("traffic", {}))
    spec = {**spec, **sizes.get("cell", {})}
    return Cell(workload, spec, c, mix)


def metric_specs(workload: str) -> tuple:
    """(end_to_end, per_layer) entries of BENCHMARK.json for this cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cells = {w["name"] for w in b["workloads"]}

    def applies(m, reported=None):
        if "workloads" in m:
            return workload in m["workloads"]
        return reported is None or m["moves"] in reported

    e2e = [m for m in b["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in b["per_layer"] if applies(m, names)]
    if workload not in cells:
        raise KeyError(f"{workload} is not a workload of BENCHMARK.json")
    return e2e, layer


def first_step(c: dict) -> int:
    """The step counter the state starts from: the last step of the
    recipe's warm-up, so that the checked steps move the weights at the
    recipe's learning rate, the first of them unclipped (the recipe clips
    from ``warmup_steps`` on) and the next two clipped."""
    return c["warmup_steps"] - 1


def find_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX sees "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs


class CompileCounter:
    """Counts lowerings and backend compiles while ``active``."""

    def __init__(self):
        import jax
        self.active = False
        self.lowered = self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if not self.active:
            return
        if event.endswith("jaxpr_to_mlir_module_duration"):
            self.lowered += 1
        elif event.endswith("backend_compile_duration"):
            self.compiled += 1


def write_shards(cell: Cell, seed: int, steps: int, directory: str) -> None:
    """The cell's token stream for ``steps`` steps, as the loader's
    shards: instances of seq_len + 1 ids."""
    law = importlib.import_module(f"bench.laws.{cell.mix['law']}")
    ids = law.generate(cell.mix, cell.c["vocab_size"], steps * cell.batch,
                       seed)
    names = []
    for i, part in enumerate(np.array_split(ids, 4)):
        names.append(f"shard_{i:02d}.npy")
        np.save(os.path.join(directory, names[-1]), part)
    with open(os.path.join(directory, "meta.json"), "w") as f:
        json.dump({"shards": names, "seq_len": cell.seq_len,
                   "num_instances": int(ids.shape[0])}, f)


def leaf_norms(tree):
    import jax
    import jax.numpy as jnp
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def change_norms(c: dict):
    """jitted (tree, seed words) -> per-leaf norms of tree - initial."""
    import jax
    import jax.numpy as jnp
    from bench import weights

    def f(tree, words):
        return jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(x - weights.leaf(c, words, i))))
            for i, x in enumerate(jax.tree.leaves(tree))])

    return jax.jit(f)


def checked_steps(prog, compiled, state, loader, seed: int):
    """The first CHECK_STEPS steps, through the window's compiled step and
    loader. Returns (state, readings, seconds spent reading)."""
    import jax
    from bench import weights
    losses, m_norms, spent = [], None, 0.0
    for i in range(CHECK_STEPS):
        state, met = compiled(state, prog.put(loader.batch(i)))
        losses.append(float(met["loss"]))
        t = time.perf_counter()
        if i == 0:
            m_norms = np.asarray(jax.jit(leaf_norms)(state.opt.m))
        if i == CHECK_STEPS - 1:
            ch = np.asarray(change_norms(prog.c)(state.opt.master,
                                                 weights.seed_words(seed)))
        spent += time.perf_counter() - t
    return state, {"losses": losses, "m_norms": m_norms,
                   "change_norms": ch}, spent


def reference_readings(cell: Cell, seed: int, batches: list, devices,
                       **fault) -> dict:
    """The plain reference's three steps on the same weights and batches:
    losses, the first step's per-leaf gradient norms, and the per-leaf
    change of the weights after the three steps."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from bench import weights
    c = cell.c
    ref = cell.reference()
    mesh, groups = None, 1
    if cell.chips > 1:
        mesh = Mesh(np.array(devices[:cell.chips]), ("g",))
        groups = cell.chips
        rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("g"))
    else:
        rep = rows = jax.sharding.SingleDeviceSharding(devices[0])
    words = weights.seed_words(seed)
    with jax.default_matmul_precision("highest"):
        w = jax.jit(lambda s: weights.make(c, s), out_shardings=rep)(words)
        zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                        out_shardings=rep)
        m, v = zeros(w), zeros(w)
        step = ref.make_step(c, groups=groups, mesh=mesh, **fault)
        t0 = first_step(c)
        losses, gnorms = [], None
        for t, b in enumerate(batches):
            tok = jax.device_put(b["tokens"], rows)
            lab = jax.device_put(b["labels"], rows)
            loss, norms, w, m, v = step(w, m, v, jnp.int32(t0 + t), tok,
                                        lab)
            losses.append(float(loss))
            if t == 0:
                gnorms = np.asarray(norms)
        del m, v
        ch = np.asarray(change_norms(c)(w, words))
    return {"losses": losses, "grad_norms": gnorms, "change_norms": ch}


@dataclasses.dataclass
class Context:
    """What the metric readers read."""
    cell: Cell
    chips: int
    peak: dict
    flops_step: float
    flops_expert_gemm: float
    setup_s: float = 0.0
    window_s: float = 0.0
    steps: int = 0
    tokens: int = 0
    memory: object = None
    kernels: dict = dataclasses.field(default_factory=dict)
    trace: object = None
    host_spans: dict = dataclasses.field(default_factory=dict)


def _span(ctx: Context, name: str, seconds: float) -> None:
    ctx.host_spans.setdefault(name, []).append(seconds)


def timed_window(ctx, prog, compiled, state, loader, first: int,
                 seconds: float):
    """Steps until ``seconds`` have passed, at most two in flight, each
    reading a new batch. Returns (state, losses)."""
    import jax
    losses, prev, done = [], None, []
    k = first
    t0 = time.perf_counter()
    while True:
        batch = prog.put(loader.batch(k))
        state, met = compiled(state, batch)
        losses.append(met["loss"])
        if prev is not None:
            jax.block_until_ready(prev)
            done.append(time.perf_counter())
        prev = met
        k += 1
        if time.perf_counter() - t0 >= seconds:
            break
    jax.block_until_ready((state, prev))
    ctx.window_s = time.perf_counter() - t0
    ctx.steps = k - first
    gaps = np.diff(done) if len(done) > 1 else np.zeros(1)
    log(f"window step completions (s): min {gaps.min():.4f} median "
        f"{np.median(gaps):.4f} max {gaps.max():.4f}")
    return state, losses


def traced_window(ctx, prog, compiled, state, loader, first: int):
    """TRACE_STEPS steps under the profiler, with the harness's host spans
    (``bench.input``, ``bench.dispatch``, ``bench.wait``) in the trace."""
    import jax
    from jax.profiler import TraceAnnotation
    from bench import trace as T
    losses, prev = [], None
    with tempfile.TemporaryDirectory() as td:
        jax.profiler.start_trace(td)
        with TraceAnnotation("bench.window"):
            for k in range(first, first + TRACE_STEPS):
                t = time.perf_counter()
                with TraceAnnotation("bench.input"):
                    batch = prog.put(loader.batch(k))
                _span(ctx, "bench.input", time.perf_counter() - t)
                with TraceAnnotation("bench.dispatch"):
                    state, met = compiled(state, batch)
                losses.append(met["loss"])
                if prev is not None:
                    with TraceAnnotation("bench.wait"):
                        jax.block_until_ready(prev)
                prev = met
            with TraceAnnotation("bench.wait"):
                jax.block_until_ready((state, prev))
        jax.profiler.stop_trace()
        ctx.trace = T.load(T.find(td), ctx.kernels)
    ctx.steps = TRACE_STEPS
    lo, hi = ctx.trace.window()
    ctx.window_s = (hi - lo) / 1e9
    return state, losses


def peak_table(kind: str) -> dict:
    peaks = read_json("peaks.json")
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        start: float | None = None, require_chip: bool = True,
        sizes: dict | None = None, fault=None) -> dict:
    """One run of a cell; returns the result line's object. ``fault``
    wraps the program's step function (tests plant faults with it);
    ``require_chip=False`` lets tests run on the CPU."""
    start = time.perf_counter() if start is None else start
    cell = load_cell(workload, sizes)
    e2e, layer = metric_specs(workload)
    import jax
    split = {"import": time.perf_counter() - start}
    t = time.perf_counter()
    devices = find_devices(cell.chips) if require_chip else jax.devices()
    kind = devices[0].device_kind
    split["devices"] = time.perf_counter() - t
    peak = peak_table(kind) if require_chip else {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    log(f"device: {devices[0].platform} {kind} x{len(devices)}; cell "
        f"{workload} on {cell.chips} chip(s)")
    from bench import flops
    t = time.perf_counter()
    from bench.system import Program, ShardedDataLoader
    split["program_import"] = time.perf_counter() - t
    fl = flops.per_step(cell.c, cell.seq_len, cell.tokens)
    ctx = Context(cell, cell.chips, peak, fl["total"],
                  flops.expert_gemm(cell.c, cell.tokens))
    counter = CompileCounter()

    with tempfile.TemporaryDirectory() as data_dir:
        t = time.perf_counter()
        steps_cap = EXTRA_BATCHES + int(BATCHES_PER_SECOND * seconds)
        write_shards(cell, seed, steps_cap, data_dir)
        loader = ShardedDataLoader(data_dir, global_batch=cell.batch)
        split["shards"] = time.perf_counter() - t

        t = time.perf_counter()
        prog = Program(cell.c, cell.spec, cell.seq_len, cell.batch)
        state = prog.init_state(seed, first_step(cell.c))
        jax.block_until_ready(state)
        split["init"] = time.perf_counter() - t

        t = time.perf_counter()
        step_fn = prog.step_fn()
        if fault is not None:
            step_fn = fault(step_fn)
        compiled = prog.compile(step_fn, state, prog.put(loader.batch(0)))
        ctx.memory = compiled.memory_analysis()
        split["compile"] = time.perf_counter() - t
        if trace:
            from bench import trace as T
            ctx.kernels = T.kernel_names(compiled.as_text())

        t = time.perf_counter()
        state, readings, spent = checked_steps(prog, compiled, state, loader,
                                               seed)
        split["check_steps"] = time.perf_counter() - t - spent

        t = time.perf_counter()
        k, times = CHECK_STEPS, []
        while len(times) < WARMUP_MAX:
            t1 = time.perf_counter()
            state, met = compiled(state, prog.put(loader.batch(k)))
            jax.block_until_ready((state, met))
            times.append(time.perf_counter() - t1)
            k += 1
            if (len(times) >= WARMUP_MIN and abs(times[-1] - times[-2])
                    <= WARMUP_AGREE * times[-2]):
                break
        split["warmup"] = time.perf_counter() - t
        ctx.setup_s = time.perf_counter() - start - spent
        log("setup split (s): " + " ".join(
            f"{n}={v:.3f}" for n, v in split.items())
            + f" total={ctx.setup_s:.3f}; warm-up step times "
            + " ".join(f"{x:.4f}" for x in times))

        counter.active = True
        if trace:
            state, losses = traced_window(ctx, prog, compiled, state, loader,
                                          k)
        else:
            state, losses = timed_window(ctx, prog, compiled, state, loader,
                                         k, seconds)
        counter.active = False
        log(f"window: {ctx.steps} steps in {ctx.window_s:.3f} s; "
            f"lowerings {counter.lowered}, compiles {counter.compiled} "
            f"in the window")
        ctx.tokens = ctx.steps * cell.tokens
        failed = sum(not math.isfinite(float(x)) for x in losses)
        stats = [d.memory_stats() or {} for d in prog.devices]
        peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)
        batches = [loader.batch(i) for i in range(CHECK_STEPS)]
    del state, compiled, losses, met
    gc.collect()

    t = time.perf_counter()
    ref = reference_readings(cell, seed, batches, prog.devices)
    log(f"reference: {time.perf_counter() - t:.3f} s")
    from bench import check
    nums = check.numbers(readings, ref, cell.c["beta1"])
    correct, checks = check.judge(nums, cell.spec.get("limits"))
    log("losses: program " + " ".join(f"{x:.6f}" for x in readings["losses"])
        + ", reference " + " ".join(f"{x:.6f}" for x in ref["losses"]))

    metrics = {}
    for m in (layer if trace else e2e):
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        v = mod.read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": cell.chips, "memory_peak_bytes": peak_bytes}
    out = {"correct": correct and failed == 0, "attempted": ctx.steps,
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        from bench import trace as T
        tr = ctx.trace
        busy = [T.length(T.busy(tr, d)) / 1e9 for d in tr.ops]
        device["busy_s"] = sum(busy) / max(len(busy), 1)
        device["window_s"] = ctx.window_s
        out["breakdown"] = {"device_ops": T.top_ops(tr),
                            "idle_gaps": T.idle_gaps(tr)}
    out["checks"] = checks
    for n, v in checks.items():
        log(f"check {n} {v['value']:.6e} limit {v['limit']}")
    return out

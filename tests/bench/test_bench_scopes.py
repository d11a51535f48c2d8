"""The program's named scopes and its MoE pool counter, read the way the
benchmark's scope reduction reads them: ``bench.scopes.scope_names`` over
the real train step compiled on the CPU at a tiny size (EP on four forced
CPU devices in a child process), the per-layer readings on a hand-made
trace and scope map, and the optimizer's byte count against a hand
count."""
import json
import os

import numpy as np
import pytest

from bench import harness, scopes as S, trace as T

TINY = {
    "config": {"d_model": 64, "num_heads": 2, "num_kv_heads": 2,
               "head_dim": 32, "num_experts": 4, "experts_per_token": 2,
               "d_ff_expert": 32, "d_ff": 128, "vocab_size": 256},
    "traffic": {"seq_len": 16, "sequences_per_step": 4},
    "cell": {"kernel": {"backend": "pallas", "tile_m": 8, "tile_k": 32,
                        "tile_n": 32}},
}
# every instruction of these opcodes must lie under a scope of the table
MUST_SCOPE = ("dot", "custom-call", "gather", "scatter", "all-gather",
              "reduce-scatter", "all-reduce", "all-to-all",
              "collective-permute", "all-gather-start", "all-reduce-start",
              "collective-permute-start")
MOE = {"embed", "attn", "moe/router", "moe/dispatch", "moe/ffn",
       "moe/combine", "head", "optim"}
DENSE = {"embed", "attn", "mlp", "head", "optim"}


def tiny_step(workload: str, sequences: int) -> dict:
    """The cell's step at TINY sizes, compiled on the CPU and run once:
    its scope map, the opcodes of its instructions outside any scope, and
    the MoE metrics beside what they should read."""
    import jax
    from bench.system import Program
    from repro.core.moe import dropless_pool_rows
    sizes = dict(TINY, traffic=dict(TINY["traffic"],
                                    sequences_per_step=sequences))
    cell = harness.load_cell(workload, sizes)
    prog = Program(cell.c, cell.spec, cell.seq_len, cell.batch)
    state = prog.init_state(7, harness.first_step(cell.c))
    toks = np.random.default_rng(0).integers(
        0, cell.c["vocab_size"], (cell.batch, cell.seq_len + 1), np.int32)
    batch = prog.put({"tokens": toks[:, :-1], "labels": toks[:, 1:]})
    compiled = prog.compile(prog.step_fn(), state, batch)
    hlo = compiled.as_text()
    scopes = S.scope_names(hlo)
    unscoped = []
    for line in hlo.splitlines():
        m = T._INSTR.match(line.strip())
        if m and m.group(2) in MUST_SCOPE and m.group(1) not in scopes:
            unscoped.append(line.strip()[:200])
    out = {"scopes": sorted({s for s, _ in scopes.values()}),
           "phases": sorted({p for _, p in scopes.values()}),
           "unscoped": unscoped}
    if cell.c["arch_type"] == "moe":
        _, met = compiled(state, batch)
        met = jax.device_get(met)
        k = cell.c["experts_per_token"]
        chips = cell.chips
        out["rows"] = float(met["moe_rows_computed"])
        out["counts"] = float(np.sum(met["moe_counts"]))
        out["want_rows"] = chips * dropless_pool_rows(
            cell.tokens, k, cell.c["num_experts"] // chips,
            align=cell.spec["kernel"]["tile_m"])
        out["want_counts"] = cell.tokens * k
    return out


@pytest.fixture(scope="module")
def ep_step(mesh8_start):
    """EP=4 with EPSO on four of the forced CPU devices, compiled and run
    in a child process that starts with the module's first test."""
    here = os.path.dirname(os.path.abspath(__file__))
    return mesh8_start(f"""
        import json, sys
        sys.path[:0] = {[os.path.join(harness.ROOT, "src"), harness.ROOT,
                         here]!r}
        import test_bench_scopes as t
        print(json.dumps(t.tiny_step("mula-7b-a1b.ep4.train.zipf-2k", 8)))
    """)


@pytest.mark.parametrize("workload,want", [
    ("mula-7b-a1b.train.zipf-2k", MOE), ("mula-1b.train.zipf-2k", DENSE)])
def test_cpu_step_is_scoped(ep_step, workload, want):
    got = tiny_step(workload, 4)
    assert got["unscoped"] == []
    assert set(got["scopes"]) <= set(S.SCOPES) | {
        f"moe/{s}" for s in S.MOE_STAGES}
    assert want <= set(got["scopes"])
    assert {"forward", "backward", "optimizer"} <= set(got["phases"])
    if "rows" in got:
        assert got["rows"] == got["want_rows"] == 4 * 16 * 2 + 8 * 4
        assert got["counts"] == got["want_counts"] == 4 * 16 * 2


def test_ep_step_is_scoped(ep_step):
    """The exchange is under ``moe/exchange``, and the pool counter sums
    the four ranks' pools, each of which holds every gathered row."""
    got = json.loads(ep_step().strip().splitlines()[-1])
    assert got["unscoped"] == []
    assert MOE | {"moe/exchange"} <= set(got["scopes"])
    assert {"forward", "backward", "optimizer"} <= set(got["phases"])
    # 8 x 16 tokens gathered on each rank: T*K rows + 8 of alignment for
    # each of its 1 local expert, on 4 ranks
    assert got["rows"] == got["want_rows"] == 4 * (8 * 16 * 2 + 8 * 1)
    assert got["counts"] == got["want_counts"] == 8 * 16 * 2


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/jvp()/while/body/closed_call/attn/dot_general",
     ("attn", "forward")),
    ("jit(train_step)/jvp(head)/reduce_sum", ("head", "forward")),
    ("jit(train_step)/transpose(jvp(head))/mul", ("head", "backward")),
    ("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/moe/ffn/pallas_call", ("moe/ffn", "recompute")),
    ("jit(f)/transpose(jvp())/while/body/moe/shard_map/exchange/all_gather",
     ("moe/exchange", "backward")),
    ("jit(f)/jvp()/while/body/moe/apply_norm/mul", ("moe", "forward")),
    ("jit(train_step)/optim/sub", ("optim", "optimizer")),
    ("jit(train_step)/jvp()/while/body/dynamic_slice", (None, "forward")),
    ("jit(train_step)/transpose(jvp())/while/body/mlp/dot_general",
     ("mlp", "backward")),
    # a stage name counts only below ``moe``; the innermost stage wins
    ("jit(f)/jvp()/while/body/dispatch/dot_general", (None, "forward")),
    ("jit(f)/jvp()/while/body/moe/dispatch/ffn/pallas_call",
     ("moe/ffn", "forward")),
    ("jit(f)/transpose(jvp(moe))/router/reduce_max",
     ("moe/router", "backward")),
    ("jit(f)/transpose(jvp())/while/body/checkpoint/rematted_computation/"
     "attn/while/body/attn/exp", ("attn", "recompute")),
    ("jit(train_step)/optim/while/body/all_gather", ("optim", "optimizer")),
])
def test_scope_of_op_names(op_name, want):
    assert S.scope_of(op_name) == want


def test_scope_names_read_the_metadata():
    hlo = "\n".join([
        '  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, calls=%c, '
        'metadata={op_type="mul" op_name="jit(s)/optim/mul"}',
        '  ROOT %dot.2 = f32[4]{0} dot(%a, %b), metadata={op_type="dot" '
        'op_name="jit(s)/jvp()/while/body/attn/dot_general"}',
        '  %copy.3 = f32[4]{0} copy(%a)',
        '  %add.4 = f32[4]{0} add(%a, %b), metadata={op_name="jit(s)/add"}'])
    assert S.scope_names(hlo) == {"fusion.1": ("optim", "optimizer"),
                                  "dot.2": ("attn", "forward")}


def _hand_made():
    """Two devices, a 0-100 ns window, two steps. Device 0: attention
    10-30 and (nested in a scoped loop) 20-25, router 30-40, dispatch
    35-45, head 50-60, optimizer 60-80, an unscoped copy 80-90. Device 1:
    attention 0-20, combine 20-30, optimizer 30-90 with an unscoped copy
    nested in it at 40-45."""
    tr = T.Trace()
    tr.spans = [("bench.window", 0, 100)]
    ops = {"/device:TPU:0": [("while.1", 10, 30), ("fusion.2", 20, 25),
                             ("fusion.3", 30, 40), ("gather.4", 35, 45),
                             ("fusion.5", 50, 60), ("fusion.6", 60, 80),
                             ("copy.7", 80, 90)],
           "/device:TPU:1": [("fusion.2", 0, 20), ("fusion.8", 20, 30),
                             ("fusion.6", 30, 90), ("copy-done.9", 40, 45)]}
    tr.ops = {d: [(f"{n} {n.split('.')[0]}", a, b) for n, a, b in v]
              for d, v in ops.items()}
    scopes = {"while.1": ("attn", "forward"), "fusion.2": ("attn", "forward"),
              "fusion.3": ("moe/router", "forward"),
              "gather.4": ("moe/dispatch", "backward"),
              "fusion.5": ("head", "forward"),
              "fusion.6": ("optim", "optimizer"),
              "fusion.8": ("moe/combine", "recompute")}
    return tr, scopes


def _read(tr, scopes, steps=2, opt_bytes=0.0, metrics=None):
    return S.readings(tr, scopes, steps, chips=len(tr.ops),
                      opt_bytes=opt_bytes, hbm_bytes_per_s=1e9,
                      metrics=metrics)


def test_scope_readers_on_a_hand_made_trace():
    tr, scopes = _hand_made()
    got = _read(tr, scopes)
    ms = 1e-6 / 2 / 2                  # ns summed over 2 devices, 2 steps
    assert got["attention_ms"] == pytest.approx((20 + 20) * ms)
    assert got["head_ce_ms"] == pytest.approx(10 * ms)
    # device 0: router 30-40 and dispatch 35-45 overlap: 15; device 1: 10
    assert got["moe_dispatch_ms"] == pytest.approx((15 + 10) * ms)
    assert got["optimizer_ms"] == pytest.approx((20 + 60) * ms)
    table = S.scope_table(tr, scopes, 2)
    assert table["moe/dispatch"] == {"backward": pytest.approx(10 * ms)}
    assert table["moe/combine"] == {"recompute": pytest.approx(10 * ms)}
    share, ops = S.unscoped(tr, scopes)
    # busy 10-45, 50-90 = 75 with 10 unscoped on device 0; 90 on device 1,
    # whose copy runs inside the optimizer's time
    assert share == pytest.approx((10 / 75 + 0) / 2)
    assert ops == [["copy", pytest.approx(10 / 2 / 1e6)]]
    assert "unscoped 6.67% of busy: copy=0.000" in S.scope_line(tr, scopes, 2)


def test_optimizer_roofline_on_a_hand_made_trace():
    tr, scopes = _hand_made()
    tr.ops.pop("/device:TPU:1")
    # optim 20 ns a step over 2 steps on one device: 10 ns = 1e-8 s a
    # step; 3 B at 1e9 B/s take 3e-9 s: 30% of the roofline
    got = _read(tr, scopes, opt_bytes=3.0)
    assert got["optimizer_hbm_roofline"] == pytest.approx(30.0)
    assert "optimizer_hbm_roofline" not in _read(*_hand_made(),
                                                  opt_bytes=3.0)


def test_readers_find_nothing_in_an_unscoped_program():
    """A program without the scopes, or a step without the pool counter,
    gives no reading, and no error."""
    tr, _ = _hand_made()
    tr.ops.pop("/device:TPU:1")
    assert _read(tr, {}, opt_bytes=3.0,
                 metrics={"moe_counts": np.ones(4)}) == {}
    assert "unscoped 100.00% of busy" in S.scope_line(tr, {}, 2)


def test_pool_occupancy_reads_the_step_metrics():
    got = _read(T.Trace(), {}, metrics={
        "moe_counts": np.array([40000.0, 25536.0]),
        "moe_rows_computed": np.float32(73728)})
    assert got == {"moe_pool_occupancy": pytest.approx(100 * 65536 / 73728)}


def test_optimizer_bytes_against_a_hand_count():
    c = harness.read_json("configs", "mula-7b-a1b.json")
    vocab, d = 50432, 2048                 # 50,304 rows padded to 256
    params = (2 * vocab * d + d            # embed, head, final norm
              + 4 * d * d + 2 * d          # q, k, v, o (16 x 128), ln1, ln2
              + 3 * 64 * d * 1024 + d * 64)  # experts and router, 1 layer
    assert params == 626_137_088
    assert S.optimizer_bytes(c) == 26 * params
    dense = harness.read_json("configs", "mula-1b.json")
    params = 2 * vocab * d + d + 6 * (4 * d * d + 2 * d + 3 * d * 8192)
    assert S.optimizer_bytes(dense) == 26 * params


DATA = os.path.join(os.path.dirname(T.__file__), "testdata")


def test_recorded_cell1_is_scoped():
    """A traced cell-1 run of this program on a TPU v5e, loaded without
    kernel names, with the scope map of its compiled step (the
    instructions the trace holds): at least 90% of the device's busy time
    lies under a scope, and the four scope times, which do not overlap,
    sum to no more than the busy time."""
    with open(os.path.join(DATA, "cell1.scopes.json")) as f:
        scopes = {k: tuple(v) for k, v in json.load(f).items()}
    tr = T.load(os.path.join(DATA, "cell1.scoped.xplane.pb"))
    steps = sum(n == "bench.dispatch" for n, _, _ in tr.spans)
    (dev,) = tr.ops
    busy = T.length(T.busy(tr, dev))
    assert S.scope_time(tr, dev, scopes, bool) >= 0.9 * busy
    got = _read(tr, scopes, steps=steps)
    parts = [got[m] for m in S.TIMES]
    assert all(p > 0 for p in parts)
    assert sum(parts) <= busy / steps / 1e6

"""The correctness check against its control and against the faults a
training cell can have, at a size the CPU holds.

Each test skips the harness's look for a chip and drives the rest of a
run (shards, loader, state, the program's compiled step, the checked
steps, the window, the plain reference and the comparison with the
cell's own limits): unbroken it comes out correct; with the timed path
broken underneath it comes out not correct. The control, the plain
reference in float8 put in the program's place, comes out not correct
too."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from bench import calibrate, check, harness

CELL = "mula-7b-a1b.train.zipf-2k"
SMALL = {
    "config": {"d_model": 128, "num_heads": 2, "num_kv_heads": 2,
               "head_dim": 64, "num_experts": 8, "experts_per_token": 2,
               "d_ff_expert": 64, "d_ff": 256, "vocab_size": 1000},
    "traffic": {"seq_len": 64, "sequences_per_step": 4},
    "cell": {"kernel": {"backend": "pallas", "tile_m": 16, "tile_k": 32,
                        "tile_n": 32}},
}


def _run(fault=None, workload=CELL, sizes=SMALL):
    return harness.run(workload, 2 ** 31 + 11, 0.2, False,
                       require_chip=False, sizes=sizes, fault=fault)


def frozen(step):
    """A step that returns its state unchanged."""
    def f(state, batch):
        return state, step(state, batch)[1]
    return f


def half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    def f(state, batch):
        return step(state, jax.tree.map(lambda x: x[: x.shape[0] // 2],
                                        batch))
    return f


@pytest.mark.parametrize("fault", [None, frozen, half_batch],
                         ids=["unbroken", "frozen", "half_batch"])
def test_program_and_faults(fault):
    out = _run(fault)
    assert out["correct"] is (fault is None), out["checks"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("workload", [CELL, "mula-1b.train.zipf-2k"])
def test_control_is_not_correct(workload):
    cell = harness.load_cell(workload, SMALL)
    seed = 5
    with harness.tempfile.TemporaryDirectory() as d:
        harness.write_shards(cell, seed, harness.CHECK_STEPS, d)
        from bench.system import ShardedDataLoader
        loader = ShardedDataLoader(d, global_batch=cell.batch)
        batches = [loader.batch(i) for i in range(harness.CHECK_STEPS)]
    devs = jax.devices()
    ref = harness.reference_readings(cell, seed, batches, devs)
    ctrl = calibrate.as_program(harness.reference_readings(
        cell, seed, batches, devs, quant="fp8"), cell.c["beta1"])
    nums = check.numbers(ctrl, ref, cell.c["beta1"])
    correct, _ = check.judge(nums, cell.spec["limits"])
    assert not correct, nums


def test_ep_exchange_left_out_is_not_correct():
    """EP=4 on four virtual CPU devices, with the token all-gather of the
    MoE layer replaced by each chip's own tokens."""
    code = textwrap.dedent(f"""
        import json, sys
        sys.path[:0] = {[os.path.join(harness.ROOT, "src"), harness.ROOT]!r}
        import jax, jax.numpy as jnp
        from bench import harness
        sizes = {dict(SMALL, traffic={"seq_len": 64,
                                       "sequences_per_step": 8})!r}
        W = "mula-7b-a1b.ep4.train.zipf-2k"
        ok = harness.run(W, 7, 0.2, False, require_chip=False, sizes=sizes)
        real = jax.lax.all_gather
        def own_tokens(x, axis, tiled=False, **kw):
            n = jax.lax.axis_size(axis)
            return jnp.concatenate([x] * n, 0) if tiled else real(
                x, axis, tiled=tiled, **kw)
        import types
        import repro.core.moe as moe
        lax = types.SimpleNamespace(**vars(jax.lax))
        lax.all_gather = own_tokens
        moe.jax = types.SimpleNamespace(**vars(jax))
        moe.jax.lax = lax
        bad = harness.run(W, 7, 0.2, False, require_chip=False, sizes=sizes)
        print(json.dumps([ok["correct"], bad["correct"], bad["checks"]]))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    import json
    ok, bad, checks = json.loads(r.stdout.strip().splitlines()[-1])
    assert ok is True and bad is False, checks

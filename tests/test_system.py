"""End-to-end behaviour tests: full launcher runs (data pipeline -> train ->
checkpoint -> resume), dry-run roofline plumbing, serve loop."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_train_launcher_end_to_end(tmp_path):
    from repro.launch.train import run
    hist = run("mula-7b-a1b", steps=12, batch=4, seq=64,
               out=str(tmp_path / "run"), ckpt_interval=5, d_model=64)
    assert len(hist) == 12
    losses = [h["loss"] for h in hist]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    # dual checkpoints + model-only exist
    ckdir = tmp_path / "run" / "ckpt"
    assert (ckdir / "ckpt-1").exists() or (ckdir / "ckpt-2").exists()


def test_train_launcher_resume(tmp_path):
    from repro.launch.train import run
    out = str(tmp_path / "run")
    run("mula-1b", steps=10, batch=4, seq=64, out=out, ckpt_interval=5,
        d_model=64)
    hist2 = run("mula-1b", steps=14, batch=4, seq=64, out=out,
                ckpt_interval=5, d_model=64)
    # first run checkpointed after step 5 (10 steps, interval 5) => resume
    # continues at 6 and trains to 13
    steps = [h["step"] for h in hist2]
    assert steps[0] == 6 and steps[-1] == 13


def test_train_launcher_profile_writes_host_spans(tmp_path):
    """--profile START:STOP traces those steps into <out>/profile: the
    trace holds the launcher's host spans around the batch read, the
    per-step metrics fetch and the checkpoint save."""
    from jax.profiler import ProfileData
    from repro.launch.train import run
    out = tmp_path / "run"
    run("mula-1b", steps=3, batch=2, seq=32, out=str(out), d_model=64,
        ckpt_interval=2, profile="1:3")
    paths = sorted((out / "profile").rglob("*.xplane.pb"))
    assert len(paths) == 1
    names = {e.name for p in ProfileData.from_file(str(paths[0])).planes
             for line in p.lines for e in line.events}
    assert {"train.input", "train.fetch", "ckpt.save"} <= names


def test_serve_loop_generates():
    """Batched greedy decode over a prompt — the serving path end-to-end."""
    from repro.configs import get_config, reduced
    from repro.models import init_params, init_cache, decode_step
    cfg = reduced(get_config("falcon-mamba-7b"), d_model=64)
    p = init_params(jax.random.PRNGKey(0), cfg)
    B, steps = 4, 12
    cache = init_cache(cfg, B, steps, jnp.float32)
    tok = jnp.ones((B, 1), jnp.int32)
    step = jax.jit(lambda p, t, c, i: decode_step(p, t, c, i, cfg,
                                                  compute_dtype=jnp.float32))
    outs = []
    for i in range(steps):
        logits, cache = step(p, tok, cache, i)
        tok = jnp.argmax(logits[:, :, :cfg.vocab_size], axis=-1).astype(
            jnp.int32)
        outs.append(tok)
    gen = jnp.concatenate(outs, axis=1)
    assert gen.shape == (B, steps)
    assert bool((gen >= 0).all()) and bool((gen < cfg.vocab_size).all())


def test_roofline_collective_parser():
    from repro.launch.roofline import collective_bytes
    hlo = """
      %ag = bf16[16,128]{1,0} all-gather(bf16[2,128] %x), replica_groups=[8,8]<=[64], dimensions={0}
      %ar = f32[256]{0} all-reduce(f32[256] %y), replica_groups={{0,1,2,3}}, to_apply=%add
      %rs = f32[32]{0} reduce-scatter(f32[256] %z), replica_groups=[4,8]<=[32], dimensions={0}
      %cp = bf16[64]{0} collective-permute(bf16[64] %w), source_target_pairs={{0,1}}
    """
    c = collective_bytes(hlo)
    assert c["all-gather"] == pytest.approx(16 * 128 * 2 * 7 / 8)
    assert c["all-reduce"] == pytest.approx(2 * 256 * 4 * 3 / 4)
    assert c["reduce-scatter"] == pytest.approx(32 * 4 * 7)
    assert c["collective-permute"] == pytest.approx(64 * 2)
    assert c["unknown_dtypes"] == []
    assert c["total"] == sum(v for k, v in c.items()
                             if k not in ("total", "unknown_dtypes"))


def test_nan_failure_aborts_training():
    """Soft-failure wiring in the launcher: NaN loss raises NodeFailure."""
    from repro.ft import NaNMonitor, NodeFailure
    mon = NaNMonitor()
    with pytest.raises(NodeFailure):
        mon.check([float("nan")])

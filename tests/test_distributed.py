"""Distributed semantics tests — run in a subprocess with 8 forced host
devices (the ``mesh8`` conftest fixture) so the main pytest process keeps
its single-device view."""
import pytest

pytestmark = pytest.mark.distributed


def test_parse_mesh_spec():
    from repro.launch.mesh import parse_mesh_spec
    assert parse_mesh_spec("8") == ((8,), ("data",))
    assert parse_mesh_spec("4,2") == ((4, 2), ("data", "model"))
    assert parse_mesh_spec("2,2,2") == ((2, 2, 2), ("data", "pp", "model"))
    assert parse_mesh_spec("2,2,2,2") == ((2, 2, 2, 2),
                                          ("pod", "data", "pp", "model"))
    with pytest.raises(ValueError):
        parse_mesh_spec("1,2,3,4,5")
    with pytest.raises(ValueError):
        parse_mesh_spec("")


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_forced_mesh_too_few_devices_names_platform(monkeypatch, platform):
    """A plan that needs more devices than the backend has fails with both
    counts; only on the CPU does the message point at the host-device flag
    (an accelerator has the chips it has)."""
    import types
    from repro.launch import mesh as M
    monkeypatch.setenv("XLA_FLAGS", "")
    monkeypatch.setattr(M.jax, "devices",
                        lambda: [types.SimpleNamespace(platform=platform)])
    with pytest.raises(RuntimeError) as e:
        M.make_forced_mesh((2, 2), ("data", "model"), what="plan ep=4")
    msg = str(e.value)
    assert msg.startswith("plan ep=4 needs 4 devices")
    assert (M._FORCE_FLAG in msg) == (platform == "cpu")
    if platform == "tpu":
        assert msg == "plan ep=4 needs 4 devices, tpu has 1"


@pytest.mark.slow
def test_fsmoe_ep_matches_naive_with_grads(mesh8):
    """Paper Algorithm 1 under a real 2x4 (data, model) mesh: forward and
    gradients equal the naive single-device reference; the collective
    schedule contains Stage-1 all-gather + Stage-5 reduce-scatter and no
    all-to-all."""
    out = mesh8("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from jax.sharding import AxisType
        from repro.configs.base import ModelConfig, MoEConfig
        from repro.core import moe as M
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,)*2)
        cfg = ModelConfig(name="t", arch_type="moe", num_layers=1, d_model=32,
                          num_heads=2, num_kv_heads=2, d_ff=0, vocab_size=64,
                          moe=MoEConfig(num_experts=8, experts_per_token=2,
                                        d_ff_expert=16, capacity_factor=4.0,
                                        moe_impl="fsmoe"))
        p = M.init_moe_block(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
        ref, _ = M.moe_naive(p, x, cfg.moe)
        pspec = {"router": P(), "gate": P("model", None, None),
                 "up": P("model", None, None), "down": P("model", None, None)}
        ps = jax.tree.map(lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                          p, pspec)
        xs = jax.device_put(x, NamedSharding(mesh, P(("data", "model"), None)))
        def f(p, x):
            out, r, stats = M.moe_fsmoe_ep(p, x, cfg.moe, mesh=mesh)
            return out
        out = jax.jit(f)(ps, xs)
        assert np.allclose(ref, out, atol=1e-4), "forward mismatch"
        g1 = jax.jit(jax.grad(lambda p, x: (f(p, x)**2).sum()))(ps, xs)
        g2 = jax.grad(lambda p: (M.moe_naive(p, x, cfg.moe)[0]**2).sum())(p)
        for k in ("router", "gate", "up", "down"):
            assert np.allclose(g1[k], g2[k], atol=1e-3), k
        txt = jax.jit(f).lower(ps, xs).compile().as_text()
        assert "all-gather" in txt and "reduce-scatter" in txt
        assert "all-to-all" not in txt
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_fsmoe_a2a_dispatch_matches_naive(mesh8):
    """Beyond-paper Stage-1 variant (EXPERIMENTS §Perf): capacity-bounded
    all-to-all dispatch is numerically identical to the allgather path and
    the naive reference in the dropless regime."""
    out = mesh8("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from jax.sharding import AxisType
        from repro.configs.base import ModelConfig, MoEConfig
        from repro.core import moe as M
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,)*2)
        cfg = ModelConfig(name="t", arch_type="moe", num_layers=1, d_model=32,
                          num_heads=2, num_kv_heads=2, d_ff=0, vocab_size=64,
                          moe=MoEConfig(num_experts=8, experts_per_token=2,
                                        d_ff_expert=16, capacity_factor=8.0,
                                        moe_impl="fsmoe", stage1="a2a"))
        p = M.init_moe_block(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
        ref, _ = M.moe_naive(p, x, cfg.moe)
        pspec = {"router": P(), "gate": P("model", None, None),
                 "up": P("model", None, None), "down": P("model", None, None)}
        ps = jax.tree.map(lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                          p, pspec)
        xs = jax.device_put(x, NamedSharding(mesh, P(("data", "model"), None)))
        def f(p, x):
            out, r, stats = M.moe_fsmoe_ep(p, x, cfg.moe, mesh=mesh)
            return out, stats
        out, stats = jax.jit(f)(ps, xs)
        assert int(stats.drops) == 0
        assert int(stats.counts.sum()) > 0
        assert np.allclose(ref, out, atol=1e-4)
        g1 = jax.jit(jax.grad(lambda p, x: (f(p, x)[0]**2).sum()))(ps, xs)
        g2 = jax.grad(lambda p: (M.moe_naive(p, x, cfg.moe)[0]**2).sum())(p)
        for k in ("router", "gate", "up", "down"):
            assert np.allclose(g1[k], g2[k], atol=1e-3), k
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_moe_etp_shard_map_matches_naive(mesh8):
    """Beyond-paper ETP path (mixtral hillclimb): local dispatch + one psum
    over the model axis; exact vs the naive reference."""
    out = mesh8("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from jax.sharding import AxisType
        from repro.configs.base import ModelConfig, MoEConfig
        from repro.core import moe as M
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,)*2)
        cfg = ModelConfig(name="t", arch_type="moe", num_layers=1, d_model=32,
                          num_heads=2, num_kv_heads=2, d_ff=0, vocab_size=64,
                          moe=MoEConfig(num_experts=2, experts_per_token=1,
                                        d_ff_expert=16, capacity_factor=2.0,
                                        moe_impl="fsmoe", etp_shard_map=True))
        p = M.init_moe_block(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
        ref, _ = M.moe_naive(p, x, cfg.moe)
        pspec = {"router": P(), "gate": P(None, None, "model"),
                 "up": P(None, None, "model"), "down": P(None, "model", None)}
        ps = jax.tree.map(lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                          p, pspec)
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
        def f(p, x):
            out, r, stats = M.moe_etp_shard_map(p, x, cfg.moe, mesh=mesh,
                                                batch_axes=("data",))
            return out
        out = jax.jit(f)(ps, xs)
        assert np.allclose(ref, out, atol=1e-4)
        g1 = jax.jit(jax.grad(lambda p, x: (f(p, x)**2).sum()))(ps, xs)
        g2 = jax.grad(lambda p: (M.moe_naive(p, x, cfg.moe)[0]**2).sum())(p)
        for k in ("router", "gate", "up", "down"):
            assert np.allclose(g1[k], g2[k], atol=1e-3), k
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_train_step_matches_single_device(mesh8):
    """pjit train_step on a (2,4) mesh == single-device train_step."""
    out = mesh8("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, reduced, TrainConfig, ParallelConfig
        from repro.train import init_state, make_train_step
        from repro.parallel.plan import ParallelPlan
        from repro.parallel.sharding import shardings
        from repro.optim.epso import optimizer_state_shardings

        cfg = reduced(get_config("deepseek-7b"), d_model=64)
        tc = TrainConfig(param_dtype="float32", compute_dtype="float32",
                         grad_reduce_dtype="float32", warmup_steps=2,
                         total_steps=10, lr_peak=1e-3, lr_min=1e-4)
        state = init_state(jax.random.PRNGKey(0), cfg, tc)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

        s1, m1 = jax.jit(make_train_step(cfg, ParallelConfig(), tc))(state,
                                                                     batch)

        plan = ParallelPlan.from_legacy("2,4", cfg=cfg, opt_shard="epso") \
            .resolve(cfg, global_batch=8)
        rules, mesh = plan.rules, plan.mesh
        psh = shardings(state.params, rules)
        osh = optimizer_state_shardings(state.params, rules, "epso")
        sp = state._replace(
            params=jax.tree.map(jax.device_put, state.params, psh),
            opt=state.opt._replace(
                master=jax.tree.map(jax.device_put, state.opt.master, osh),
                m=jax.tree.map(jax.device_put, state.opt.m, osh),
                v=jax.tree.map(jax.device_put, state.opt.v, osh)))
        bsh = NamedSharding(mesh, P("data", None))
        bp = jax.tree.map(lambda a: jax.device_put(a, bsh), batch)
        step2 = make_train_step(cfg, ParallelConfig(), tc, plan=plan)
        s2, m2 = step2(sp, bp)
        assert np.allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
            assert np.allclose(np.asarray(a), np.asarray(b), atol=2e-4)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_epso_state_placement_on_devices(mesh8):
    """EPSO states occupy fewer bytes per device than SO on a real mesh."""
    out = mesh8("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.configs import get_config, reduced
        from repro.models import init_params
        from repro.optim import adamw_init
        from repro.optim.epso import optimizer_state_shardings
        from repro.parallel.sharding import make_rules
        import dataclasses
        cfg = reduced(get_config("mixtral-8x7b"), d_model=128, max_experts=4)
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                               num_experts=4))
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,)*2)
        rules = make_rules(cfg, mesh, kind="train", global_batch=8)
        params = init_params(jax.random.PRNGKey(0), cfg)
        opt = adamw_init(params)
        sizes = {}
        for mode in ("so", "epso"):
            sh = optimizer_state_shardings(params, rules, mode)
            placed = jax.tree.map(jax.device_put, opt.m, sh)
            dev0 = jax.devices()[0]
            per_dev = sum(sum(s.data.nbytes for s in l.addressable_shards
                              if s.device == dev0)
                          for l in jax.tree.leaves(placed))
            sizes[mode] = per_dev
        assert sizes["epso"] < sizes["so"], sizes
        print("OK", sizes)
    """)
    assert "OK" in out

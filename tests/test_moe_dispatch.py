"""Dropless dispatch + per-expert telemetry: the routing-parity suite.

The dispatch mode (``MoEConfig.dispatch``) selects how the slot pool is
sized: 'capacity' (paper default — capacity_factor bounds the pool, tokens
over capacity are dropped) or 'dropless' (the pool covers the worst-case
routing, every (token, expert) pair is computed). Dropless is exactly the
naive math for ANY routing, independent of pool-geometry knobs like
``c_align`` — which is what makes pp=1 and pp>1 losses comparable at
shapes where the capacity path's different pool geometries diverge
(the c_align parity test at the bottom pins that).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import ParallelConfig, TrainConfig, get_config, reduced
from repro.configs.base import ModelConfig, MoEConfig
from repro.core import moe as M
from repro.core.router import route
from repro.train import init_state, make_train_step


def make_cfg(E=8, K=2, d=32, f=16, cf=None, **kw):
    return ModelConfig(
        name="t", arch_type="moe", num_layers=1, d_model=d, num_heads=2,
        num_kv_heads=2, d_ff=0, vocab_size=64,
        moe=MoEConfig(num_experts=E, experts_per_token=K, d_ff_expert=f,
                      capacity_factor=cf if cf is not None else E / K, **kw))


# ---------------------------------------------------------------------------
# make_dispatch_plan properties (Stages 2+3)
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(2, 16), st.integers(1, 4), st.integers(8, 96),
       st.integers(0, 3))
def test_dispatch_plan_conservation(E, K, T, seed):
    """routed + dropped == T*K, for any pool size — nothing is silently
    lost even when the pool is far too small."""
    K = min(K, E)
    idx = jax.random.randint(jax.random.PRNGKey(seed), (T, K), 0, E)
    for rows in (8, M.pool_size(T, K, E, E, 1.0),
                 M.dropless_pool_rows(T, K, E)):
        plan = M.make_dispatch_plan(idx, num_experts=E, pool_rows=rows)
        assert int(plan.valid.sum()) + int(plan.drops) == T * K
        assert int(plan.counts.sum()) == T * K   # counts are pre-drop


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 16), st.integers(1, 4), st.integers(8, 96),
       st.integers(0, 3))
def test_dispatch_plan_group_sizes_cover_pool(E, K, T, seed):
    """Ragged groups tile the pool: offsets are monotone, fit in pool_rows,
    and every valid slot lands inside the occupied prefix."""
    K = min(K, E)
    idx = jax.random.randint(jax.random.PRNGKey(seed), (T, K), 0, E)
    rows = M.dropless_pool_rows(T, K, E)
    plan = M.make_dispatch_plan(idx, num_experts=E, pool_rows=rows)
    gs = np.array(plan.group_sizes)
    assert (gs >= 0).all()
    occupied = int(gs.sum())
    assert occupied <= rows
    slot = np.array(plan.slot)
    valid = np.array(plan.valid)
    if valid.any():
        assert slot[valid].max() < occupied


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 8), st.integers(1, 2), st.integers(8, 64),
       st.integers(0, 3))
def test_dispatch_plan_uniform_capacity_shape(E, K, T, seed):
    """uniform_capacity: every group is exactly pool_rows // EL (the
    (EL, C, d) reshape contract of the XLA backend)."""
    K = min(K, E)
    idx = jax.random.randint(jax.random.PRNGKey(seed), (T, K), 0, E)
    rows = M.pool_size(T, K, E, E, float(E))
    rows = (rows // E) * E          # divisible, as dispatch_compute_combine
    plan = M.make_dispatch_plan(idx, num_experts=E, pool_rows=rows,
                                uniform_capacity=True)
    gs = np.array(plan.group_sizes)
    assert (gs == rows // E).all()
    assert int(gs.sum()) == rows


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 8), st.integers(1, 2), st.integers(0, 4))
def test_dropless_pool_survives_adversarial_routing(E, K, seed):
    """The dropless bound holds at its worst case: ALL (t, k) pairs routed
    to a single expert still produce zero drops."""
    T = 48
    e = seed % E
    idx = jnp.full((T, K), e, jnp.int32)
    rows = M.dropless_pool_rows(T, K, E)
    plan = M.make_dispatch_plan(idx, num_experts=E, pool_rows=rows)
    assert int(plan.drops) == 0
    assert int(plan.counts[e]) == T * K


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 4))
def test_dropless_combine_permutation_invariance(seed):
    """Permuting the token order permutes the output rows and nothing else:
    the sort-based dispatch has no order-dependent drop behavior under
    dropless."""
    cfg = make_cfg(E=4, K=2, cf=0.1)      # cf ignored by dropless
    p = M.init_moe_block(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 10), (32, 32))
    perm = jax.random.permutation(jax.random.PRNGKey(seed + 20), 32)
    out, _, stats = M.moe_dropless(p, x, cfg.moe)
    out_p, _, stats_p = M.moe_dropless(p, x[perm], cfg.moe)
    np.testing.assert_allclose(np.asarray(out)[np.asarray(perm)],
                               np.asarray(out_p), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(stats.counts),
                                  np.asarray(stats_p.counts))


# ---------------------------------------------------------------------------
# golden parity: dropless == naive, capacity == dropless when nothing drops
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("E", [4, 8])
@pytest.mark.parametrize("K", [1, 2])
def test_dropless_matches_naive_golden(E, K):
    """moe_dropless == moe_naive (forward + every gradient) at a tight
    capacity_factor where the capacity path would drop — the tentpole's
    correctness contract."""
    cfg = make_cfg(E=E, K=K, cf=0.25)
    p = M.init_moe_block(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    ref, _ = M.moe_naive(p, x, cfg.moe)
    out, _, stats = M.moe_dropless(p, x, cfg.moe)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    assert float(stats.drops) == 0.0
    assert int(stats.counts.sum()) == 64 * K
    g1 = jax.grad(lambda p: (M.moe_dropless(p, x, cfg.moe)[0] ** 2).sum())(p)
    g2 = jax.grad(lambda p: (M.moe_naive(p, x, cfg.moe)[0] ** 2).sum())(p)
    for k in ("router", "gate", "up", "down"):
        np.testing.assert_allclose(g1[k], g2[k], atol=1e-4, err_msg=k)


def test_capacity_equals_dropless_at_full_capacity():
    """At capacity_factor = E/K the capacity pool also fits every pair, so
    both dispatch modes compute the identical function."""
    cfg = make_cfg(E=8, K=2, cf=4.0)
    p = M.init_moe_block(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    out_c, _ = M.moe_dense_capacity(p, x, cfg.moe)
    out_d, _, stats = M.moe_dropless(p, x, cfg.moe)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_d),
                               atol=1e-5)
    assert float(stats.drops) == 0.0


def test_sparse_moe_block_dispatch_modes():
    """cfg.moe.dispatch drives the block: dropless reports zero drops at a
    capacity_factor where the capacity path demonstrably drops."""
    base = make_cfg(E=8, K=2, cf=0.25)
    p = M.init_moe_block(jax.random.PRNGKey(0), base)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 32)).reshape(4, 16, 32)
    _, _, _, st_cap = M.sparse_moe_block(p, x, base)
    assert float(st_cap.drops) > 0
    drop = dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, dispatch="dropless"))
    out, aux, z, st_dl = M.sparse_moe_block(p, x, drop)
    assert float(st_dl.drops) == 0.0
    assert int(st_dl.counts.sum()) == 4 * 16 * 2
    # dropless through the block == naive reference
    ref, _ = M.moe_naive(p, x.reshape(64, 32), base.moe)
    np.testing.assert_allclose(np.asarray(out).reshape(64, 32),
                               np.asarray(ref), atol=1e-5)


def test_moe_config_validates_dispatch():
    with pytest.raises(ValueError, match="dispatch"):
        make_cfg(dispatch="sometimes")
    with pytest.raises(ValueError, match="moe_dispatch"):
        ParallelConfig(moe_dispatch="sometimes")


def test_fsmoe_a2a_rejects_dropless():
    """stage1='a2a' send buffers are capacity-bounded by construction —
    dropless must fail loudly, never silently drop."""
    cfg = make_cfg(E=4, K=2, moe_impl="fsmoe", stage1="a2a",
                   dispatch="dropless")
    p = M.init_moe_block(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
    mesh = jax.make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="a2a"):
        M.sparse_moe_block(p, x, cfg, mesh=mesh)


# ---------------------------------------------------------------------------
# train-step telemetry (moe_stats -> metrics)
# ---------------------------------------------------------------------------

def _tc(seq=32, batch=4):
    return TrainConfig(param_dtype="float32", compute_dtype="float32",
                       grad_reduce_dtype="float32", lr_peak=1e-3,
                       lr_min=1e-4, warmup_steps=2, total_steps=10,
                       seq_len=seq, global_batch=batch)


def _moe_train_cfg(cf=None):
    cfg = reduced(get_config("mula-7b-a1b"), layers=2, d_model=64)
    if cf is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=cf))
    return cfg


def _run_step(cfg, par, batch=4, seq=32, seed=1):
    tc = _tc(seq, batch)
    state = init_state(jax.random.PRNGKey(0), cfg, tc)
    toks = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0,
                              cfg.vocab_size)
    b = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    return jax.jit(make_train_step(cfg, par, tc))(state, b)


@pytest.mark.parametrize("nmb", [1, 2])
def test_train_step_moe_stats_counts_conserve(nmb):
    """metrics['moe_counts'] sums to tokens*top_k for the whole batch, for
    both the single-shot and microbatch-accumulation paths."""
    cfg = _moe_train_cfg()
    B, S, K = 4, 32, cfg.moe.experts_per_token
    _, m = _run_step(cfg, ParallelConfig(microbatches=nmb,
                                         moe_dispatch="dropless"),
                     batch=B, seq=S)
    assert m["moe_counts"].shape == (cfg.moe.num_experts,)
    np.testing.assert_allclose(float(m["moe_counts"].sum()), B * S * K,
                               atol=1e-3)
    assert float(m["moe_drops"]) == 0.0
    # a per-layer mean of whole routed pairs
    summed = np.asarray(m["moe_counts"]) * cfg.num_layers
    np.testing.assert_array_equal(summed, np.round(summed))
    # the pool counter: each microbatch's dropless pool, every row of
    # which this (XLA) backend's grouped matmuls compute
    E = cfg.moe.num_experts
    assert float(m["moe_rows_computed"]) == \
        nmb * M.dropless_pool_rows(B * S // nmb, K, E)
    assert float(m["moe_gemm_rows"]) == float(m["moe_rows_computed"])


def _aligned_rows(counts, align):
    """Rows of a pool's routed groups: each expert's count rounded up to
    the grouped matmul's tile."""
    c = np.rint(np.asarray(counts)).astype(np.int64)
    return int(np.sum(-(-c // align) * align))


def test_train_step_gemm_rows_count_the_routed_tiles():
    """On the Pallas backend the grouped matmuls stop at the routed rows:
    ``moe_gemm_rows`` is the layer's tile-aligned group sizes, a device
    value below the worst-case pool that ``moe_rows_computed`` still
    reports, and every row of it but the alignment carries a routed
    pair."""
    from repro.parallel.plan import KernelPlan, use_kernel_plan
    cfg = dataclasses.replace(_moe_train_cfg(), num_layers=1)
    B, S, K, E, tm = 4, 32, cfg.moe.experts_per_token, \
        cfg.moe.num_experts, 8
    with use_kernel_plan(KernelPlan(backend="pallas", tile_m=tm)):
        _, m = _run_step(cfg, ParallelConfig(moe_dispatch="dropless"),
                         batch=B, seq=S)
    gemm = float(m["moe_gemm_rows"])
    assert gemm == _aligned_rows(m["moe_counts"], tm)
    assert float(m["moe_rows_computed"]) == \
        M.dropless_pool_rows(B * S, K, E, align=tm) > gemm
    assert 0 < float(np.sum(m["moe_counts"])) / gemm <= 1


def test_train_step_capacity_reports_drops():
    """A starved capacity pool surfaces real drop counts; the same model
    under dispatch='dropless' reports zero."""
    cfg = _moe_train_cfg(cf=0.1)
    _, m_cap = _run_step(cfg, ParallelConfig(moe_dispatch="capacity"))
    assert float(m_cap["moe_drops"]) > 0
    _, m_dl = _run_step(cfg, ParallelConfig(moe_dispatch="dropless"))
    assert float(m_dl["moe_drops"]) == 0.0


def test_parallel_config_dispatch_overrides_model():
    """ParallelConfig.moe_dispatch is authoritative over MoEConfig.dispatch
    inside make_train_step — the plan pins one path for the whole run."""
    cfg = _moe_train_cfg(cf=0.1)      # model says capacity + starved pool
    assert cfg.moe.dispatch == "capacity"
    _, m = _run_step(cfg, ParallelConfig(moe_dispatch="dropless"))
    assert float(m["moe_drops"]) == 0.0    # dropless won


def test_pp_train_step_moe_stats():
    """The pipeline executors thread per-expert counts through the
    (pp,)-leaf scalar channels: pp=2 telemetry == non-pp telemetry."""
    cfg = _moe_train_cfg()
    B, S, K = 8, 16, cfg.moe.experts_per_token
    _, m_ref = _run_step(cfg, ParallelConfig(microbatches=4,
                                             moe_dispatch="dropless"),
                         batch=B, seq=S)
    _, m_pp = _run_step(cfg, ParallelConfig(microbatches=4, pp_stages=2,
                                            moe_dispatch="dropless"),
                        batch=B, seq=S)
    np.testing.assert_allclose(np.asarray(m_ref["moe_counts"]),
                               np.asarray(m_pp["moe_counts"]), atol=1e-3)
    np.testing.assert_allclose(float(m_pp["moe_counts"].sum()), B * S * K,
                               atol=1e-3)
    assert float(m_pp["moe_drops"]) == 0.0
    assert float(m_pp["moe_rows_computed"]) == \
        float(m_ref["moe_rows_computed"])


# ---------------------------------------------------------------------------
# mesh8: dropless under EP x TP, and the c_align parity gap
# ---------------------------------------------------------------------------

@pytest.mark.distributed
@pytest.mark.slow
def test_dropless_ep_tp_matches_naive_mesh8(mesh8):
    """Dropless through the EP shard_map path on a (data=2, ep=2, tp=2)
    mesh: forward == naive, stats.drops == 0, counts conserve — at a
    capacity_factor that would starve the capacity path."""
    out = mesh8("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from jax.sharding import AxisType
        from repro.configs.base import ModelConfig, MoEConfig
        from repro.core import moe as M
        mesh = jax.make_mesh((2, 2, 2), ("data", "ep", "tp"),
                             axis_types=(AxisType.Auto,)*3)
        cfg = ModelConfig(name="t", arch_type="moe", num_layers=1, d_model=32,
                          num_heads=2, num_kv_heads=2, d_ff=0, vocab_size=64,
                          moe=MoEConfig(num_experts=4, experts_per_token=2,
                                        d_ff_expert=16, capacity_factor=0.25,
                                        moe_impl="fsmoe",
                                        dispatch="dropless"))
        p = M.init_moe_block(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
        ref, _ = M.moe_naive(p, x, cfg.moe)
        pspec = {"router": P(), "gate": P("ep", None, "tp"),
                 "up": P("ep", None, "tp"), "down": P("ep", "tp", None)}
        ps = jax.tree.map(lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                          p, pspec)
        xs = jax.device_put(x, NamedSharding(mesh, P(("data", "ep"), None)))
        def f(p, x):
            out, aux, z, stats = M.sparse_moe_block(
                p, x.reshape(4, 16, 32), cfg, mesh=mesh, ep_axis="ep",
                tp_axis="tp", batch_axes=("data",))
            return out.reshape(64, 32), stats
        out, stats = jax.jit(f)(ps, xs)
        assert np.allclose(ref, out, atol=1e-4), "forward mismatch"
        assert float(stats.drops) == 0.0, stats.drops
        assert int(stats.counts.sum()) == 64 * 2, stats.counts
        g1 = jax.jit(jax.grad(lambda p, x: (f(p, x)[0]**2).sum()))(ps, xs)
        g2 = jax.grad(lambda p: (M.moe_naive(p, x, cfg.moe)[0]**2).sum())(p)
        for k in ("router", "gate", "up", "down"):
            assert np.allclose(g1[k], g2[k], atol=1e-3), k
        print("DROPLESS-EP-TP-OK")
    """, timeout=1200)
    assert "DROPLESS-EP-TP-OK" in out


@pytest.mark.distributed
@pytest.mark.slow
def test_dropless_ep_gemm_rows_mesh8(mesh8):
    """The grouped-matmul row counter under EP=2 on the Pallas kernels, on
    a (data=2, ep=2, tp=2) mesh: each (data, ep) rank computes its local
    experts' tile-aligned groups over the tokens its data shard gathered;
    the counter sums those over data and ep, and counts once over tp,
    whose ranks dispatch the same tokens. The pool counter sums the four
    dispatching ranks' worst-case pools."""
    out = mesh8("""
        import jax, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from jax.sharding import AxisType
        from repro.configs.base import ModelConfig, MoEConfig
        from repro.core import moe as M
        from repro.parallel.plan import KernelPlan, use_kernel_plan
        mesh = jax.make_mesh((2, 2, 2), ("data", "ep", "tp"),
                             axis_types=(AxisType.Auto,)*3)
        cfg = ModelConfig(name="t", arch_type="moe", num_layers=1, d_model=32,
                          num_heads=2, num_kv_heads=2, d_ff=0, vocab_size=64,
                          moe=MoEConfig(num_experts=4, experts_per_token=2,
                                        d_ff_expert=16, moe_impl="fsmoe",
                                        dispatch="dropless"))
        p = M.init_moe_block(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
        pspec = {"router": P(), "gate": P("ep", None, "tp"),
                 "up": P("ep", None, "tp"), "down": P("ep", "tp", None)}
        ps = jax.tree.map(lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                          p, pspec)
        xs = jax.device_put(x, NamedSharding(mesh, P(("data", "ep"), None)))
        with use_kernel_plan(KernelPlan(backend="pallas", tile_m=8)):
            stats = jax.jit(lambda p, x: M.sparse_moe_block(
                p, x.reshape(4, 16, 32), cfg, mesh=mesh, ep_axis="ep",
                tp_axis="tp", batch_axes=("data",))[3])(ps, xs)
            # each data shard's 32 tokens, dispatched over all 4 experts
            shard = [M.moe_dropless(p, x[32 * d:32 * (d + 1)], cfg.moe,
                                    backend="pallas")[2].counts
                     for d in range(2)]
        counts = np.rint(np.asarray(shard)).astype(int)
        np.testing.assert_array_equal(counts.sum(0), np.asarray(stats.counts))
        want = int(np.sum(-(-counts // 8) * 8))
        assert float(stats.gemm_rows) == want, (stats.gemm_rows, want)
        assert float(stats.pool_rows) == 4 * M.dropless_pool_rows(
            32, 2, 2, align=8) > want
        assert 0 < 64 * 2 / float(stats.gemm_rows) <= 1
        print("EP-GEMM-ROWS-OK")
    """, timeout=900)
    assert "EP-GEMM-ROWS-OK" in out


@pytest.mark.distributed
@pytest.mark.slow
def test_c_align_parity_gap_closed_by_dropless_mesh8(mesh8):
    """THE parity test this PR exists for. A non-PP on-mesh step pads the
    capacity pool to c_align = batch-shard count; the PP executors run the
    blocks with c_align = 1. At a starved capacity_factor the two pool
    geometries drop different tokens and the losses diverge — that shape
    was previously unblessed. Under dispatch='dropless' the pool geometry
    is irrelevant and the losses agree."""
    out = mesh8("""
        import dataclasses
        import jax, numpy as np
        from repro.configs import get_config, reduced, TrainConfig, ParallelConfig
        from repro.train import init_state, make_train_step, train_state_shardings
        from repro.parallel.plan import ParallelPlan
        from repro.parallel.sharding import batch_sharding

        cfg0 = reduced(get_config("mula-7b-a1b"), layers=2, d_model=64)
        cfg0 = dataclasses.replace(cfg0, moe=dataclasses.replace(
            cfg0.moe, capacity_factor=0.25))    # starved: capacity drops
        tc = TrainConfig(param_dtype="float32", compute_dtype="float32",
                         grad_reduce_dtype="float32", lr_peak=1e-3,
                         lr_min=1e-4, warmup_steps=2, total_steps=10,
                         seq_len=32, global_batch=8)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                                  cfg0.vocab_size)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

        def run(mesh_spec, pp, dispatch):
            plan = ParallelPlan.from_legacy(mesh_spec, cfg=cfg0) \
                .resolve(cfg0, global_batch=8)
            rules = plan.rules
            state = init_state(jax.random.PRNGKey(0), cfg0, tc, plan=plan)
            ssh = train_state_shardings(state.params, rules, "none")
            par = ParallelConfig(microbatches=4, pp_stages=pp,
                                 pp_schedule="1f1b",
                                 pp_impl="masked" if pp > 1 else "shardmap",
                                 moe_dispatch=dispatch)
            step = make_train_step(cfg0, par, tc, plan=plan,
                                   state_shardings=ssh)
            bdev = jax.tree.map(
                lambda a: jax.device_put(a, batch_sharding(rules)), batch)
            _, m = step(state, bdev)
            return float(m["loss"]), float(m["moe_drops"])

        # non-PP on an 8-way data mesh (c_align=8) vs PP=2 (c_align=1)
        l_cap_nopp, d_cap_nopp = run("8", 1, "capacity")
        l_cap_pp, d_cap_pp = run("2,2,2", 2, "capacity")
        l_dl_nopp, d_dl_nopp = run("8", 1, "dropless")
        l_dl_pp, d_dl_pp = run("2,2,2", 2, "dropless")
        print("capacity:", l_cap_nopp, l_cap_pp,
              "drops:", d_cap_nopp, d_cap_pp)
        print("dropless:", l_dl_nopp, l_dl_pp)
        # the starved capacity path drops on at least one geometry and the
        # two geometries disagree on the loss
        assert max(d_cap_nopp, d_cap_pp) > 0
        assert abs(l_cap_nopp - l_cap_pp) > 1e-6, "gap vanished: retune cf"
        # dropless: geometry-independent -> pp=1 and pp=2 agree
        assert d_dl_nopp == 0.0 and d_dl_pp == 0.0
        assert abs(l_dl_nopp - l_dl_pp) <= 1e-6, (l_dl_nopp, l_dl_pp)
        print("CALIGN-PARITY-OK")
    """, timeout=1800)
    assert "CALIGN-PARITY-OK" in out

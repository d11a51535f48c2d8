"""ParallelPlan: spec round-trip, validation, kernel-plan scoping,
checkpoint plan metadata, and the golden legacy-vs-plan parity +
expert-TP (dedicated ep x tp axes) mesh tests."""
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import ModelConfig, MoEConfig
from repro.parallel.plan import (KernelPlan, ParallelPlan, ResolvedPlan,
                                 current_kernel_plan, use_kernel_plan)


def moe_cfg(E=4, f=32, name="t-moe"):
    return ModelConfig(name=name, arch_type="moe", num_layers=2, d_model=64,
                       num_heads=2, num_kv_heads=2, d_ff=0, vocab_size=64,
                       moe=MoEConfig(num_experts=E, experts_per_token=2,
                                     d_ff_expert=f, moe_impl="fsmoe"))


def dense_cfg(d_ff=128):
    return ModelConfig(name="t-dense", arch_type="dense", num_layers=2,
                       d_model=64, num_heads=2, num_kv_heads=2, d_ff=d_ff,
                       vocab_size=64)


# ---------------------------------------------------------------------------
# parse / str round-trip
# ---------------------------------------------------------------------------

def test_parse_basic():
    p = ParallelPlan.parse("dp=2,pp=2,ep=2")
    assert (p.dp, p.pp, p.ep, p.tp, p.pod) == (2, 2, 2, 1, 1)
    assert p.num_devices == 8
    assert p.mesh_axes() == (("data", 2), ("pp", 2), ("ep", 2))
    # options ride along in the same spec
    q = ParallelPlan.parse("dp=2,ep=2,tp=2,opt=epso,schedule=gpipe,mb=4,fsdp")
    assert q.opt_shard == "epso" and q.pp_schedule == "gpipe"
    assert q.microbatches == 4 and q.fsdp


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 8), st.integers(1, 4), st.integers(1, 4),
       st.integers(1, 4), st.integers(1, 2),
       st.sampled_from(["none", "so", "epso"]),
       st.sampled_from(["gpipe", "1f1b"]),
       st.sampled_from(["shardmap", "masked"]),
       st.integers(1, 8), st.booleans(),
       st.sampled_from([None, "capacity", "dropless"]))
def test_parse_str_roundtrip(dp, pp, ep, tp, pod, opt, sched, impl, mb,
                             fsdp, moe):
    p = ParallelPlan(dp=dp, pp=pp, ep=ep, tp=tp, pod=pod, opt_shard=opt,
                     pp_schedule=sched, pp_impl=impl, microbatches=mb,
                     fsdp=fsdp, moe_dispatch=moe)
    assert ParallelPlan.parse(str(p)) == p


def test_parse_moe_dispatch_option():
    p = ParallelPlan.parse("dp=2,ep=2,moe=dropless")
    assert p.moe_dispatch == "dropless"
    assert "moe=dropless" in str(p)
    assert ParallelPlan.parse("dp=2").moe_dispatch is None   # defers to cfg
    with pytest.raises(ValueError, match="moe_dispatch"):
        ParallelPlan.parse("dp=2,moe=sometimes")
    # the plan's ParallelConfig carries the pinned mode to make_train_step
    rp = ResolvedPlan(plan=ParallelPlan.parse("dp=2,moe=dropless"))
    assert rp.parallel_config().moe_dispatch == "dropless"
    assert ResolvedPlan(
        plan=ParallelPlan.parse("dp=2")).parallel_config().moe_dispatch is None


def test_plan_apply_to_model():
    plan = ParallelPlan.parse("dp=2,ep=2,moe=dropless")
    cfg = moe_cfg(E=4)
    assert cfg.moe.dispatch == "capacity"
    cfg2 = plan.apply_to_model(cfg)
    assert cfg2.moe.dispatch == "dropless"
    assert cfg.moe.dispatch == "capacity"          # original untouched
    # nothing pinned, or no MoE block: config passes through unchanged
    assert ParallelPlan.parse("dp=2").apply_to_model(cfg) is cfg
    dense = dense_cfg()
    assert plan.apply_to_model(dense) is dense


def test_parse_errors_are_descriptive():
    with pytest.raises(ValueError, match="unknown role 'qq'"):
        ParallelPlan.parse("dp=2,qq=3")
    with pytest.raises(ValueError, match="sizes must be >= 1"):
        ParallelPlan.parse("dp=0")
    with pytest.raises(ValueError, match="must be an integer"):
        ParallelPlan.parse("dp=x")
    with pytest.raises(ValueError, match="empty parallel spec"):
        ParallelPlan.parse("  ")
    with pytest.raises(ValueError, match="opt_shard"):
        ParallelPlan.parse("dp=2,opt=zorp")
    with pytest.raises(ValueError, match="pp_schedule"):
        ParallelPlan.parse("dp=2,schedule=zigzag")
    with pytest.raises(ValueError, match="pp_impl"):
        ParallelPlan.parse("dp=2,pp=2,impl=telepathy")
    with pytest.raises(ValueError, match="duplicate 'dp'"):
        ParallelPlan.parse("dp=2,ep=4,dp=8")   # typo'd spec, never last-wins


def test_parse_rebalance_token():
    p = ParallelPlan.parse("dp=2,ep=2,opt=epso,rebalance=50:1.25")
    assert p.rebalance == "50:1.25"
    assert p.rebalance_params() == (50, 1.25)
    assert "rebalance=50:1.25" in str(p)
    assert ParallelPlan.parse(str(p)) == p
    # off / absent both mean 'no policy'
    assert ParallelPlan.parse("dp=2,ep=2,rebalance=off").rebalance_params() \
        is None
    assert ParallelPlan.parse("dp=2,ep=2").rebalance_params() is None
    with pytest.raises(ValueError, match="interval"):
        ParallelPlan.parse("dp=2,ep=2,rebalance=0:1.25")
    with pytest.raises(ValueError, match="threshold"):
        ParallelPlan.parse("dp=2,ep=2,rebalance=50:0.5")
    with pytest.raises(ValueError, match="rebalance="):
        ParallelPlan.parse("dp=2,ep=2,rebalance=always")


def test_rebalance_contracts_and_validation():
    # the plan declares the placement contract only when the policy is live
    p = ParallelPlan.parse("dp=2,ep=2,opt=epso,rebalance=50:1.25")
    assert "placement-consistency" in p.contracts()
    assert "placement-consistency" not in \
        ParallelPlan.parse("dp=2,ep=2,opt=epso").contracts()
    # rebalancing permutes expert stacks: dense models have none
    with pytest.raises(ValueError, match="no experts"):
        ParallelPlan.parse("dp=2,rebalance=50:1.25").validate_model(
            dense_cfg())
    # pp>1 is explicitly unimplemented (stage-sharded layer stacks)
    with pytest.raises(NotImplementedError, match="pipeline"):
        ParallelPlan.parse("dp=2,pp=2,ep=2,rebalance=50:1.25") \
            .validate_model(moe_cfg(E=4))
    ParallelPlan.parse("dp=2,ep=2,rebalance=50:1.25").validate_model(
        moe_cfg(E=4))


def test_validate_model_divisibility():
    # ep on a dense model
    with pytest.raises(ValueError, match="has no experts"):
        ParallelPlan(ep=2).validate_model(dense_cfg())
    # ep not dividing num_experts
    with pytest.raises(ValueError, match="does not divide .* 4 experts"):
        ParallelPlan(ep=3).validate_model(moe_cfg(E=4))
    # tp not dividing the experts' d_ff (the ep x tp contract)
    with pytest.raises(ValueError, match="expert d_ff=33"):
        ParallelPlan(ep=2, tp=2).validate_model(moe_cfg(E=4, f=33))
    # tp not dividing a dense d_ff
    with pytest.raises(ValueError, match="d_ff=130"):
        ParallelPlan(tp=4).validate_model(dense_cfg(d_ff=130))
    # valid combinations pass
    ParallelPlan(ep=2, tp=2).validate_model(moe_cfg(E=4, f=32))
    ParallelPlan(pp=2).validate_model(dense_cfg())
    with pytest.raises(ValueError, match="pipeline stage"):
        ParallelPlan(pp=3).validate_model(dense_cfg())


def test_from_legacy_role_inference():
    # MoE + divisible expert count -> the model axis becomes ep
    p = ParallelPlan.from_legacy("4,2", cfg=moe_cfg(E=4), opt_shard="epso")
    assert (p.dp, p.ep, p.tp, p.opt_shard) == (4, 2, 1, "epso")
    # MoE + non-divisible expert count -> the old 'etp' fallback = tp
    p = ParallelPlan.from_legacy("2,4", cfg=moe_cfg(E=6))
    assert (p.dp, p.ep, p.tp) == (2, 1, 4)
    # dense -> tp; 3-dim spec carries pp
    p = ParallelPlan.from_legacy("2,2,2", cfg=dense_cfg())
    assert (p.dp, p.pp, p.ep, p.tp) == (2, 2, 1, 2)
    # and the same 3-dim spec on a MoE maps model -> ep
    p = ParallelPlan.from_legacy("2,2,2", cfg=moe_cfg(E=4))
    assert (p.dp, p.pp, p.ep, p.tp) == (2, 2, 2, 1)


def test_single_device_plan_resolves_to_no_mesh():
    plan = ParallelPlan().resolve(moe_cfg())
    assert plan.mesh is None and plan.rules is None
    assert plan.parallel_config().pp_stages == 1


# ---------------------------------------------------------------------------
# KernelPlan scoping (the KERNEL_CONFIG / ATTN_IMPL replacement)
# ---------------------------------------------------------------------------

def test_kernel_plan_scoping_restores():
    from repro.kernels import ops
    base = ops.gmm_align()
    with use_kernel_plan(dataclasses.replace(current_kernel_plan(),
                                             tile_m=8)):
        assert ops.gmm_align() == 8
        # nested scopes stack
        with use_kernel_plan(dataclasses.replace(current_kernel_plan(),
                                                 tile_m=16)):
            assert ops.gmm_align() == 16
        assert ops.gmm_align() == 8
    assert ops.gmm_align() == base


def test_retired_aliases_are_tombstoned():
    """The PR 4 compatibility aliases are deleted, not just deprecated:
    the symbols no longer exist (lint rule SL004 forbids them repo-wide)."""
    from repro.kernels import ops
    from repro.models import layers as L
    # getattr with string names: SL004 forbids the bare identifiers even here
    assert not hasattr(ops, "KERNEL_CONFIG")
    with pytest.raises(AttributeError):
        getattr(L, "ATTN_IMPL")
    # the replacement path still answers the same question
    assert L._attn_impl() == current_kernel_plan().attn_impl == "blockwise"
    with use_kernel_plan(dataclasses.replace(current_kernel_plan(),
                                             attn_impl="pallas")):
        assert L._attn_impl() == "pallas"
    assert L._attn_impl() == "blockwise"


def test_default_kernel_plan_swap_and_scope_precedence():
    """set_default_kernel_plan replaces the process default; a scoped
    use_kernel_plan always outranks it and restores on exit."""
    from repro.kernels import ops
    from repro.parallel.plan import (default_kernel_plan,
                                     set_default_kernel_plan)
    old = default_kernel_plan()
    try:
        set_default_kernel_plan(dataclasses.replace(old, tile_m=8))
        assert ops.gmm_align() == 8 == current_kernel_plan().tile_m
        with use_kernel_plan(dataclasses.replace(current_kernel_plan(),
                                                 tile_m=16)):
            assert ops.gmm_align() == 16
        assert ops.gmm_align() == 8
    finally:
        set_default_kernel_plan(old)
    assert ops.gmm_align() == old.tile_m


def test_kernel_plan_validation():
    with pytest.raises(ValueError, match="backend"):
        KernelPlan(backend="cuda")
    with pytest.raises(ValueError, match="attn_impl"):
        KernelPlan(attn_impl="vanilla")


def test_kernel_plan_backend_drives_moe_stage_backend():
    """KernelPlan.backend retargets the MoE stage-4/5 kernels: a
    'pallas'-backend plan produces the same numbers as the xla reference
    through sparse_moe_block (dense-capacity path, dropless regime)."""
    import jax
    import numpy as np
    from repro.core import moe as M

    cfg = moe_cfg(E=4, f=32)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=2.0))     # dropless: backends must agree
    assert M.stage45_backend(cfg.moe) == cfg.moe.kernel_backend  # 'ref' plan
    p = M.init_moe_block(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64))
    ref, _, _, _ = M.sparse_moe_block(p, x, cfg)
    with use_kernel_plan(KernelPlan(backend="pallas", tile_m=8)):
        assert M.stage45_backend(cfg.moe) == "pallas"
        out, _, _, _ = M.sparse_moe_block(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


# ---------------------------------------------------------------------------
# Checkpointer plan metadata (the silent-reshard bugfix)
# ---------------------------------------------------------------------------

def _resolved(spec: str) -> ResolvedPlan:
    # layout metadata only — no mesh needed off-device
    return ResolvedPlan(plan=ParallelPlan.parse(spec))


def test_checkpointer_plan_mismatch_errors(tmp_path):
    import jax.numpy as jnp
    from repro.checkpoint import Checkpointer

    state = {"w": jnp.arange(4, dtype=jnp.float32)}
    ck = Checkpointer(str(tmp_path), interval=1,
                      plan=_resolved("dp=2,ep=2,opt=epso"))
    ck.save(state, 3)

    # same layout -> restores fine
    same = Checkpointer(str(tmp_path), interval=1,
                        plan=_resolved("dp=2,ep=2,opt=epso"))
    restored, step = same.restore(state)
    assert step == 3 and np.array_equal(restored["w"], state["w"])

    # different axis layout -> hard error instead of silent reshard
    other = Checkpointer(str(tmp_path), interval=1,
                         plan=_resolved("dp=4,opt=so"))
    with pytest.raises(ValueError, match="refusing to silently reshard"):
        other.restore(state)

    # explicit re-plan opt-in
    replan = Checkpointer(str(tmp_path), interval=1,
                          plan=_resolved("dp=4,opt=so"),
                          on_plan_mismatch="reshard")
    restored, step = replan.restore(state)
    assert step == 3 and np.array_equal(restored["w"], state["w"])

    # legacy caller (no plan) keeps working against a plan-stamped ckpt
    legacy = Checkpointer(str(tmp_path), interval=1)
    restored, step = legacy.restore(state)
    assert step == 3


# ---------------------------------------------------------------------------
# mesh tests: golden parity + the dedicated ep x tp axis pair
# ---------------------------------------------------------------------------

@pytest.mark.distributed
@pytest.mark.slow
def test_plan_matches_legacy_mesh_bit_identical(mesh8, tmp_path):
    """Golden parity: a plan-built (2,2,2) EPSO+PP run is bit-identical
    (loss history + final checkpointed state) to the legacy
    --mesh 2,2,2 --opt-shard epso path."""
    out = mesh8(f"""
        import json, os
        import numpy as np
        from repro.launch.train import run

        base = {str(tmp_path)!r}
        KW = dict(steps=8, batch=8, seq=32, d_model=64, ckpt_interval=5,
                  opt_shard="epso", log_every=100)
        legacy = run("mula-7b-a1b", out=f"{{base}}/legacy", mesh="2,2,2",
                     **KW)
        plan = run("mula-7b-a1b", out=f"{{base}}/plan",
                   parallel="dp=2,pp=2,ep=2", **KW)
        la = [h["loss"] for h in legacy]
        lb = [h["loss"] for h in plan]
        assert la == lb, (la, lb)

        def newest(d, want):
            for slot in ("ckpt-1", "ckpt-2"):
                man = os.path.join(d, "ckpt", slot, "MANIFEST.json")
                if os.path.exists(man):
                    with open(man) as f:
                        m = json.load(f)
                    if m.get("valid") and int(m["step"]) == want:
                        return (dict(np.load(os.path.join(d, "ckpt", slot,
                                                          "state.npz"))), m)
            raise AssertionError(f"no valid ckpt @ {{want}} in {{d}}")

        sa, ma = newest(f"{{base}}/legacy", 5)
        sb, mb = newest(f"{{base}}/plan", 5)
        assert sorted(sa) == sorted(sb)
        for k in sa:
            assert sa[k].dtype == sb[k].dtype, k
            assert np.array_equal(sa[k], sb[k]), k
        # both manifests carry the plan layout (the legacy path goes
        # through the from_legacy shim, so it records the same axes)
        assert ma["plan"]["layout"] == mb["plan"]["layout"], (ma, mb)
        assert ma["plan"]["layout"]["axes"] == [["data", 2], ["pp", 2],
                                               ["ep", 2]]
        print("PARITY-OK")
    """, timeout=1800)
    assert "PARITY-OK" in out


@pytest.mark.distributed
@pytest.mark.slow
def test_ep_tp_axis_pair_through_sparse_moe_block(mesh8):
    """Expert-TP: a dedicated ep=2 x tp=2 axis pair (inexpressible on the
    legacy shared 'model' axis) through sparse_moe_block — forward and
    gradients match the naive single-device reference."""
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
                                        d_ff_expert=16, capacity_factor=2.0,
                                        moe_impl="fsmoe"))
        p = M.init_moe_block(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
        ref, _ = M.moe_naive(p, x, cfg.moe)
        pspec = {"router": P(), "gate": P("ep", None, "tp"),
                 "up": P("ep", None, "tp"), "down": P("ep", "tp", None)}
        ps = jax.tree.map(lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                          p, pspec)
        xs = jax.device_put(x, NamedSharding(mesh, P(("data", "ep"), None)))
        # a tp_axis that is not a mesh axis fails loudly, never silently
        try:
            M.moe_fsmoe_ep(p, x, cfg.moe, mesh=mesh, ep_axis="ep",
                           tp_axis="nope")
            raise AssertionError("expected ValueError for bad tp_axis")
        except ValueError as e:
            assert "not a mesh axis" in str(e)
        def f(p, x):
            out, aux, z, stats = M.sparse_moe_block(
                p, x.reshape(4, 16, 32), cfg, mesh=mesh, ep_axis="ep",
                tp_axis="tp", batch_axes=("data",))
            return out.reshape(64, 32)
        out = jax.jit(f)(ps, xs)
        assert np.allclose(ref, out, atol=1e-4), "forward mismatch"
        g1 = jax.jit(jax.grad(lambda p, x: (f(p, x)**2).sum()))(ps, xs)
        g2 = jax.grad(lambda p: (M.moe_naive(p, x, cfg.moe)[0]**2).sum())(p)
        for k in ("router", "gate", "up", "down"):
            assert np.allclose(g1[k], g2[k], atol=1e-3), k
        print("EP-TP-OK")
    """)
    assert "EP-TP-OK" in out


@pytest.mark.distributed
@pytest.mark.slow
def test_ep_tp_plan_trains(mesh8, tmp_path):
    """A dp=2,ep=2,tp=2 plan — EP and TP as distinct axes — trains a MoE
    config for 10 steps with finite, decreasing loss."""
    out = mesh8(f"""
        import numpy as np
        from repro.launch.train import run
        r = run("mula-7b-a1b", steps=10, batch=8, seq=32, d_model=64,
                out={str(tmp_path)!r} + "/eptp", parallel="dp=2,ep=2,tp=2",
                ckpt_interval=50, log_every=100)
        losses = [h["loss"] for h in r]
        assert len(losses) == 10
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses
        print("EP-TP-TRAIN-OK")
    """, timeout=1800)
    assert "EP-TP-TRAIN-OK" in out


@pytest.mark.distributed
@pytest.mark.slow
def test_plan_resolution_on_mesh(mesh8):
    """resolve() builds the mesh + rules once with dedicated axes; the
    dry-run description renders placement without allocating."""
    out = mesh8("""
        from repro.configs import get_config, reduced
        from repro.parallel.plan import ParallelPlan
        cfg = reduced(get_config("mula-7b-a1b"), d_model=64)
        plan = ParallelPlan.parse("dp=2,ep=2,tp=2,opt=epso").resolve(
            cfg, global_batch=8)
        assert tuple(plan.mesh.shape.keys()) == ("data", "ep", "tp")
        assert plan.rules.ep_axis == "ep" and plan.rules.tp_axis == "tp"
        assert "ep" in plan.rules.batch_axes
        text = plan.describe(cfg)
        assert "moe" in text and "ep" in text and "bytes/device" in text
        print("RESOLVE-OK")
    """)
    assert "RESOLVE-OK" in out

"""Telemetry-driven expert placement + live EP rebalancing
(parallel/placement.py, ISSUE 10 tentpole).

Host-side units (permutation algebra, greedy LPT, windowed controller),
single-device numerics preservation (a placement is pure data movement:
losses and global-id telemetry are bit-identical under a permuted expert
stack), manifest/checkpoint round-trips, and the mesh8 goldens — a forced
rebalance event mid-run leaves the loss history bit-identical to the
static run, and a mid-schedule resume across the event stays bit-exact.
"""
import json
import os

import numpy as np
import pytest

from repro.parallel.placement import (ExpertPlacement, RebalanceController,
                                      greedy_perm, imbalance, is_expert_stack,
                                      permute_expert_tree, rank_loads)


# ---------------------------------------------------------------------------
# ExpertPlacement: permutation algebra + manifest round-trip
# ---------------------------------------------------------------------------

def test_identity_and_broadcast():
    pl = ExpertPlacement.identity(3, 4)
    assert pl.is_identity
    assert pl.perm == (tuple(range(4)),) * 3
    b = ExpertPlacement.broadcast((2, 3, 0, 1), 3)
    assert not b.is_identity
    assert b.num_layers == 3 and b.num_experts == 4
    assert b.perm == ((2, 3, 0, 1),) * 3


def test_inverse_is_argsort_round_trip():
    pl = ExpertPlacement(2, 4, ((2, 0, 3, 1), (1, 3, 0, 2)))
    fwd, inv = pl.perm_array(), pl.inverse_array()
    assert fwd.dtype == np.int32 and inv.dtype == np.int32
    for l in range(2):
        # inv[global id] = position holding it: fwd[inv[g]] == g
        assert list(fwd[l][inv[l]]) == [0, 1, 2, 3]
        assert list(inv[l][fwd[l]]) == [0, 1, 2, 3]


def test_relative_to_moves_live_arrays():
    """rel = cur.relative_to(new) must satisfy W_new[pos] = W_live[rel[pos]]
    where W_live[p] = W_global[cur.perm[p]]."""
    cur = ExpertPlacement.broadcast((2, 0, 3, 1), 2)
    new = ExpertPlacement.broadcast((3, 1, 2, 0), 2)
    rel = cur.relative_to(new)
    w_global = np.arange(4) * 10
    w_live = w_global[cur.perm_array()[0]]
    w_new = w_live[rel[0]]
    assert list(w_new) == list(w_global[new.perm_array()[0]])
    # identity -> new is just new's forward row
    ident = ExpertPlacement.identity(2, 4)
    assert np.array_equal(ident.relative_to(new), new.perm_array())
    # round trip: moving there and back is the identity gather
    back = new.relative_to(cur)
    assert np.array_equal(rel[0][back[0]], np.arange(4))


def test_manifest_round_trip_and_none():
    pl = ExpertPlacement(2, 4, ((2, 0, 3, 1), (0, 1, 2, 3)))
    assert ExpertPlacement.from_manifest(pl.to_manifest()) == pl
    # JSON-clean (what rides in the checkpoint MANIFEST)
    assert ExpertPlacement.from_manifest(
        json.loads(json.dumps(pl.to_manifest()))) == pl
    assert ExpertPlacement.from_manifest(None) is None


def test_validation_errors():
    with pytest.raises(ValueError, match="rows"):
        ExpertPlacement(3, 4, ((0, 1, 2, 3),) * 2)
    with pytest.raises(ValueError, match="not a permutation"):
        ExpertPlacement(1, 4, ((0, 1, 2, 2),))
    with pytest.raises(ValueError, match="shape mismatch"):
        ExpertPlacement.identity(2, 4).relative_to(
            ExpertPlacement.identity(2, 8))


# ---------------------------------------------------------------------------
# load metrics + greedy LPT
# ---------------------------------------------------------------------------

def test_rank_loads_and_imbalance():
    counts = [100, 50, 10, 40]            # global-id space
    assert list(rank_loads(counts, (0, 1, 2, 3), 2)) == [150, 50]
    assert imbalance(counts, (0, 1, 2, 3), 2) == pytest.approx(1.5)
    # pairing hot with cold balances: ranks (100+40, 50+10)=(140,60)? no —
    # (0,3 | 1,2) -> (140, 60); (0,2 | 1,3) -> (110, 90)
    assert imbalance(counts, (0, 2, 1, 3), 2) == pytest.approx(1.1)
    assert imbalance(np.zeros(4), (0, 1, 2, 3), 2) == 1.0


def test_greedy_perm_balances_skew():
    rng = np.random.default_rng(0)
    for ep in (2, 4):
        counts = rng.zipf(1.4, size=8).astype(np.float64)
        row = greedy_perm(counts, ep)
        assert sorted(row) == list(range(8))
        assert imbalance(counts, row, ep) <= imbalance(
            counts, tuple(range(8)), ep) + 1e-12
        assert row == greedy_perm(counts, ep)     # deterministic
    # textbook LPT: hottest goes to rank 0, next to rank 1, ...
    assert greedy_perm([100, 50, 10, 40], 2) == (0, 2, 1, 3)
    with pytest.raises(ValueError, match="does not divide"):
        greedy_perm([1.0, 2.0, 3.0], 2)
    with pytest.raises(ValueError, match="does not divide"):
        rank_loads([1.0, 2.0, 3.0], (0, 1, 2), 2)


def test_is_expert_stack_selects_routed_stacks_only():
    L, E = 2, 4
    assert is_expert_stack("layers/moe/gate", (L, E, 8, 16), L, E)
    assert is_expert_stack("layers/moe/down", (L, E, 16, 8), L, E)
    assert not is_expert_stack("layers/moe/router", (L, 8, E), L, E)
    assert not is_expert_stack("layers/moe/shared/gate", (L, E, 8, 16), L, E)
    assert not is_expert_stack("layers/attn/wq", (L, E, 8, 16), L, E)
    assert not is_expert_stack("layers/moe/gate", (L, E), L, E)  # no tail dim


# ---------------------------------------------------------------------------
# RebalanceController: windowed host loop
# ---------------------------------------------------------------------------

def test_controller_windowing_and_threshold():
    c = RebalanceController(num_layers=2, num_experts=4, ep=2,
                            interval=3, threshold=1.2)
    # balanced counts: observe returns the live per-step imbalance
    assert c.observe([10, 10, 10, 10]) == pytest.approx(1.0)
    assert not c.window_full()
    c.observe([10, 10, 10, 10])
    c.observe([10, 10, 10, 10])
    assert c.window_full()
    assert c.propose() is None                 # below threshold: no event
    assert not c.window_full()                 # propose resets the window
    assert c.rebalances == 0
    # skewed window above threshold: adopts the greedy placement
    for _ in range(3):
        assert c.observe([100, 50, 10, 40]) == pytest.approx(1.5)
    new = c.propose()
    assert new is not None and new.perm[0] == (0, 2, 1, 3)
    assert c.placement == new and c.rebalances == 1
    # same skew again: greedy reproposes the already-live row -> no event
    for _ in range(3):
        c.observe([100, 50, 10, 40])
    assert c.propose() is None and c.rebalances == 1


def test_controller_force_and_reset():
    c = RebalanceController(num_layers=1, num_experts=4, ep=2,
                            interval=100, threshold=10.0)
    c.observe([100, 50, 10, 40])
    # forced mid-window, threshold never reached: still adopts
    new = c.propose(force=True)
    assert new is not None and c.rebalances == 1
    assert c.steps_in_window == 0
    # empty window: force is a no-op
    assert c.propose(force=True) is None
    c.observe([1, 1, 1, 1])
    c.reset_window()                           # relaunch rollback path
    assert c.steps_in_window == 0 and c.window.sum() == 0
    assert c.propose(force=True) is None       # nothing observed
    with pytest.raises(ValueError, match="interval"):
        RebalanceController(num_layers=1, num_experts=4, ep=2,
                            interval=0, threshold=1.5)
    with pytest.raises(ValueError, match="threshold"):
        RebalanceController(num_layers=1, num_experts=4, ep=2,
                            interval=5, threshold=0.5)


# ---------------------------------------------------------------------------
# numerics preservation, single device: a placement is pure data movement
# ---------------------------------------------------------------------------

def test_placed_train_step_bit_identical_and_counts_conserved():
    """Permute the expert stacks (params AND optimizer state) to a
    non-identity placement, train with the placement threaded through the
    plan: losses and the global-id ``moe_counts`` telemetry are bit-equal
    to the identity run (top_k=2: see placement.py docstring), and
    un-permuting the trained stacks recovers the identity run's params and
    optimizer state to 16 float32 ulps of each leaf's largest magnitude.

    Not bitwise, on jax 0.9: the MoE backward accumulates each token's
    cotangent in expert-position order, which a placement changes, and the
    way XLA's CPU backend fuses it lets that order reach the rounding (a
    two-term sum alone would commute). From step 0 every gradient
    below the head moves by an ulp (expert stacks included; two different
    non-identity placements disagree the same way), and after four steps
    the largest drift is 6.1e-7 of a leaf's scale, about 5 ulps. A wrong
    expert mapping, or an optimizer state left behind by a move, is off by
    a whole slice."""
    import jax
    import jax.numpy as jnp
    from repro.configs import (ParallelConfig, TrainConfig, get_config,
                               reduced)
    from repro.parallel.placement import apply_placement
    from repro.parallel.plan import ParallelPlan
    from repro.train import init_state, make_train_step

    cfg = reduced(get_config("mula-7b-a1b"), d_model=32)
    L, E = cfg.num_layers, cfg.moe.num_experts
    assert cfg.moe.experts_per_token <= 2     # bit-identity precondition
    tc = TrainConfig(param_dtype="float32", compute_dtype="float32",
                     grad_reduce_dtype="float32", lr_peak=1e-3, lr_min=1e-4,
                     warmup_steps=2, total_steps=4, seq_len=16,
                     global_batch=4)
    base = ParallelPlan().resolve(cfg, global_batch=4)   # meshless
    assert base.mesh is None
    ident = ExpertPlacement.identity(L, E)
    placed = ExpertPlacement.broadcast(tuple(reversed(range(E))), L)

    batches = []
    for s in range(4):
        t = jax.random.randint(jax.random.PRNGKey(100 + s), (4, 17), 0,
                               cfg.vocab_size)
        batches.append({"tokens": t[:, :-1], "labels": t[:, 1:]})

    def train(plan, state):
        fn = make_train_step(cfg, ParallelConfig(), tc, plan=plan)
        losses, counts = [], []
        for b in batches:
            state, m = fn(state, b)
            losses.append(float(m["loss"]))
            counts.append(np.asarray(m["moe_counts"]))
        return state, losses, counts

    state0 = init_state(jax.random.PRNGKey(0), cfg, tc, plan=base)
    sa, la, ca = train(base, state0)

    state0 = init_state(jax.random.PRNGKey(0), cfg, tc, plan=base)
    state_p = apply_placement(state0, ident, placed, L, E)
    # the router is never permuted; the expert stacks are
    assert np.array_equal(np.asarray(state_p.params["layers"]["moe"]["router"]),
                          np.asarray(state0.params["layers"]["moe"]["router"]))
    rel = ident.relative_to(placed)
    g0 = np.asarray(state0.params["layers"]["moe"]["gate"])
    gp = np.asarray(state_p.params["layers"]["moe"]["gate"])
    for l in range(L):
        assert np.array_equal(gp[l], g0[l][rel[l]])
    sb, lb, cb = train(base.with_placement(placed), state_p)

    assert la == lb, (la, lb)                  # bit-identical losses
    for a, b in zip(ca, cb):                   # telemetry in global-id space
        assert np.array_equal(a, b)
    # moving the trained state back to identity recovers the base run
    sb_back = apply_placement(sb, placed, ident, L, E)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(sa.params),
            jax.tree_util.tree_leaves_with_path(sb_back.params)):
        assert jax.tree_util.keystr(pa) == jax.tree_util.keystr(pb)
        assert _ulp_close(a, b), pa
    for a, b in zip(jax.tree_util.tree_leaves(sa.opt),
                    jax.tree_util.tree_leaves(sb_back.opt)):
        assert _ulp_close(a, b)


def _ulp_close(a, b, rel=16 * 2.0 ** -23):
    """Largest |a - b| within ``rel`` (16 float32 ulps) of the leaf's
    largest |a|."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.abs(a - b).max()) <= rel * float(np.abs(a).max())


def test_update_plan_invariant_under_placement():
    """optim/epso.py claims the bucket schedule can't see a placement (it
    reads only shapes and specs) — pin it: the plan computed from permuted
    shapes is identical."""
    import jax
    from jax.sharding import AxisType
    from jax.sharding import AbstractMesh
    from repro.configs import get_config, reduced
    from repro.models import init_params
    from repro.optim.epso import plan_update_buckets
    from repro.parallel.sharding import make_rules

    cfg = reduced(get_config("mula-7b-a1b"), d_model=64)
    L, E = cfg.num_layers, cfg.moe.num_experts
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = AbstractMesh((2, 4), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)
    rules = make_rules(cfg, mesh, kind="train", global_batch=8)
    before = plan_update_buckets(params, rules, "epso")
    rel = ExpertPlacement.identity(L, E).relative_to(
        ExpertPlacement.broadcast(tuple(reversed(range(E))), L))
    permuted = permute_expert_tree(params, rel, L, E)
    assert jax.tree.map(lambda a: a.shape, permuted) \
        == jax.tree.map(lambda a: a.shape, params)
    assert plan_update_buckets(permuted, rules, "epso") == before


# ---------------------------------------------------------------------------
# checkpoint: placement rides the MANIFEST
# ---------------------------------------------------------------------------

def test_checkpointer_placement_round_trip(tmp_path):
    import jax.numpy as jnp
    from repro.checkpoint.checkpointer import Checkpointer

    state = {"w": jnp.arange(6.0).reshape(2, 3), "step": jnp.int32(3)}
    pl = ExpertPlacement.broadcast((2, 0, 3, 1), 2)
    ck = Checkpointer(str(tmp_path / "ck"), interval=1, placement=pl)
    ck.save(state, 3)
    ck2 = Checkpointer(str(tmp_path / "ck"), interval=1)
    restored, step = ck2.restore(state)
    assert step == 3
    assert ck2.restored_placement == pl
    assert np.array_equal(np.asarray(restored["w"]), np.asarray(state["w"]))
    # identity-placement runs write no placement key and restore None
    ck3 = Checkpointer(str(tmp_path / "ck0"), interval=1)
    ck3.save(state, 1)
    ck3.restore(state)
    assert ck3.restored_placement is None


# ---------------------------------------------------------------------------
# KV pool bookkeeping (satellite: O(1) free + double-free guard)
# ---------------------------------------------------------------------------

def test_kv_pool_free_is_guarded_and_constant_time():
    from repro.configs import get_config, reduced
    from repro.serve.kv_pool import SlotKVPool

    cfg = reduced(get_config("mula-7b-a1b"), d_model=32)
    pool = SlotKVPool(cfg, 4, 8)
    a, b = pool.alloc(), pool.alloc()
    pool.free(a)
    with pytest.raises(ValueError, match="bad free"):
        pool.free(a)                     # double free
    with pytest.raises(ValueError, match="bad free"):
        pool.free(99)                    # out of range
    # the mirror set stays consistent with the deque through churn
    pool.free(b)
    seen = [pool.alloc() for _ in range(pool.num_free)]
    assert sorted(seen) == sorted(set(seen))
    assert pool.num_free == 0 and pool._free_set == set()
    for s in seen:
        pool.free(s)
    assert pool._free_set == set(pool._free) and pool.num_free == 4


# ---------------------------------------------------------------------------
# mesh8 goldens: forced rebalance event + mid-schedule resume
# ---------------------------------------------------------------------------

@pytest.mark.distributed
@pytest.mark.slow
def test_forced_rebalance_bit_identical_losses(mesh8, tmp_path):
    """ISSUE 10 acceptance: on dp=2,ep=2,tp=2 with epso + ring overlap, a
    forced rebalance at step 3 moves the expert stacks and optimizer state
    across EP ranks mid-run — and the loss history stays bit-identical to
    the static run."""
    out = mesh8(f"""
        import json, os
        from repro.launch.train import run

        base = {str(tmp_path)!r}
        KW = dict(batch=8, seq=32, d_model=64, steps=8, ckpt_interval=100,
                  parallel="dp=2,ep=2,tp=2,opt=epso,overlap=ring",
                  log_every=100)

        static = run("mula-7b-a1b", out=f"{{base}}/static", **KW)
        forced = run("mula-7b-a1b", out=f"{{base}}/forced",
                     rebalance_force_at=3, **KW)
        la = [h["loss"] for h in static]
        lb = [h["loss"] for h in forced]
        assert la == lb, (la, lb)
        assert [h["step"] for h in forced] == list(range(8))
        assert forced[3].get("rebalanced") is True, forced[3]
        assert not any(h.get("rebalanced") for h in static)
        with open(f"{{base}}/forced/summary.json") as f:
            s = json.load(f)
        assert s["rebalances"] >= 1, s
        with open(f"{{base}}/static/summary.json") as f:
            s0 = json.load(f)
        assert s0["rebalances"] in (0, None), s0
        print("REBALANCE-GOLDEN-OK")
    """, timeout=1800)
    assert "REBALANCE-GOLDEN-OK" in out


@pytest.mark.distributed
@pytest.mark.slow
def test_rebalance_mid_schedule_resume_bit_identical(mesh8, tmp_path):
    """Resume after the rebalance event: the checkpoint at step 5 holds
    *placed* arrays plus the MANIFEST placement; restoring must rebuild the
    step against that placement and continue bit-identically."""
    out = mesh8(f"""
        import json, os
        import numpy as np
        from repro.launch.train import run

        base = {str(tmp_path)!r}
        KW = dict(batch=8, seq=32, d_model=64, ckpt_interval=5,
                  parallel="dp=2,ep=2,tp=2,opt=epso,overlap=ring",
                  rebalance_force_at=3, log_every=100)

        straight = run("mula-7b-a1b", steps=8, out=f"{{base}}/straight", **KW)
        run("mula-7b-a1b", steps=6, out=f"{{base}}/resumed", **KW)
        resumed = run("mula-7b-a1b", steps=8, out=f"{{base}}/resumed", **KW)
        assert [h["step"] for h in resumed] == [6, 7]
        la = [h["loss"] for h in straight if h["step"] >= 6]
        lb = [h["loss"] for h in resumed]
        assert la == lb, (la, lb)

        # the step-5 checkpoints carry a non-identity manifest placement and
        # identical placed arrays (the event happened before the save)
        def slot5(d):
            for slot in ("ckpt-1", "ckpt-2"):
                man = os.path.join(d, "ckpt", slot, "MANIFEST.json")
                if os.path.exists(man):
                    with open(man) as f:
                        m = json.load(f)
                    if m.get("valid") and int(m["step"]) == 5:
                        return m, dict(np.load(os.path.join(
                            d, "ckpt", slot, "state.npz")))
            raise AssertionError(f"no valid ckpt @ 5 in {{d}}")

        ma, sa = slot5(f"{{base}}/straight")
        mb, sb = slot5(f"{{base}}/resumed")
        assert ma.get("placement") is not None
        assert ma["placement"] == mb["placement"]
        ident = [list(range(ma["placement"]["num_experts"]))] \
            * ma["placement"]["num_layers"]
        assert ma["placement"]["perm"] != ident
        assert sorted(sa) == sorted(sb)
        for k in sa:
            assert np.array_equal(sa[k], sb[k]), k
        print("REBALANCE-RESUME-OK")
    """, timeout=1800)
    assert "REBALANCE-RESUME-OK" in out

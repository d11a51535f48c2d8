"""Per-kernel validation vs ref.py oracles (interpret mode) with
shape/dtype sweeps + hypothesis property tests (spec deliverable (c))."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


@pytest.fixture(autouse=True)
def small_tiles():
    # plan-scoped: restores automatically, nothing leaks across tests
    with ops.use_kernel_plan(dataclasses.replace(ops.current_kernel_plan(),
                                                 tile_m=8)):
        yield


def _groups(rng, G, M, align):
    """Random aligned group sizes summing <= M."""
    cuts = np.sort(rng.integers(0, M // align + 1, size=G - 1)) * align
    sizes = np.diff(np.concatenate([[0], cuts, [M]]))
    return jnp.array(sizes, jnp.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("G,M,K,N", [(4, 64, 24, 40), (2, 32, 128, 128),
                                     (8, 128, 16, 8)])
def test_gmm_forward_sweep(dtype, G, M, K, N):
    rng = np.random.default_rng(0)
    gs = _groups(rng, G, M, 8)
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (G, K, N), dtype)
    out = ops.gmm(x, w, gs)
    expect = ref.gmm_ref(x, w, gs)
    tol = 1e-4 if dtype == jnp.float32 else 6e-2
    np.testing.assert_allclose(np.array(out, np.float32),
                               np.array(expect, np.float32),
                               atol=tol, rtol=tol)


def test_gmm_gradients_match_ref():
    rng = np.random.default_rng(1)
    gs = _groups(rng, 4, 64, 8)
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 24))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 24, 40))
    g1 = jax.grad(lambda x, w: (ops.gmm(x, w, gs) ** 2).sum(),
                  argnums=(0, 1))(x, w)
    g2 = jax.grad(lambda x, w: (ref.gmm_ref(x, w, gs) ** 2).sum(),
                  argnums=(0, 1))(x, w)
    np.testing.assert_allclose(g1[0], g2[0], atol=1e-3)
    np.testing.assert_allclose(g1[1], g2[1], atol=1e-3)


def test_gmm_empty_group_grad_is_zero():
    gs = jnp.array([0, 32, 0, 32], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16))
    dw = jax.grad(lambda w: (ops.gmm(x, w, gs) ** 2).sum())(w)
    assert np.all(np.isfinite(dw))
    np.testing.assert_allclose(dw[0], 0.0)
    np.testing.assert_allclose(dw[2], 0.0)


# 8 tiles of 8 rows: the routed rows end one tile before the pool does,
# three quarters of the pool before it, at its start, and at its end with
# the last group empty
ROUTED_LAYOUTS = {"tail_one_tile": [16, 8, 24, 8],
                  "tail_three_quarters": [8, 0, 8, 0],
                  "no_rows": [0, 0, 0, 0],
                  "last_group_empty": [32, 16, 16, 0]}


@pytest.mark.parametrize("layout", ROUTED_LAYOUTS)
def test_gmm_visits_only_routed_tiles(layout):
    """The grids stop at sum(group_sizes): forward and both gradients
    match the reference with a cotangent that is not zero past the routed
    rows, the rows past them are exactly zero, and the routed rows are
    bit-identical to a run of the same kernel over every tile of the pool.
    The interpreter leaves rows the grid does not visit NaN, so a row the
    wrapper failed to mask would show."""
    from repro.kernels.gmm import gmm_pallas
    M, K, N, tm = 64, 16, 24, 8
    gs = jnp.array(ROUTED_LAYOUTS[layout], jnp.int32)
    total = int(gs.sum())
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, K, N))
    c = jax.random.normal(jax.random.PRNGKey(2), (M, N))
    out = np.asarray(ops.gmm(x, w, gs))
    np.testing.assert_allclose(out, ref.gmm_ref(x, w, gs), atol=1e-4)
    assert (out[total:] == 0).all()
    gids, _ = ops._tile_group_ids(gs, M // tm, tm)
    full = gmm_pallas(x, w, gids, jnp.int32(M // tm), tile_m=tm, tile_k=K,
                      tile_n=N, interpret=True)
    np.testing.assert_array_equal(out[:total], np.asarray(full)[:total])
    g1 = jax.grad(lambda x, w: (ops.gmm(x, w, gs) * c).sum(),
                  argnums=(0, 1))(x, w)
    g2 = jax.grad(lambda x, w: (ref.gmm_ref(x, w, gs) * c).sum(),
                  argnums=(0, 1))(x, w)
    np.testing.assert_allclose(g1[0], g2[0], atol=1e-4)
    np.testing.assert_allclose(g1[1], g2[1], atol=1e-4)
    assert (np.asarray(g1[0])[total:] == 0).all()
    assert (np.asarray(g1[1])[np.asarray(gs) == 0] == 0).all()


@pytest.mark.parametrize("layout", ROUTED_LAYOUTS)
def test_gmm_kernels_leave_unvisited_blocks_unwritten(layout):
    """Under the TPU interpreter, whose fresh buffers hold NaN, the two
    kernels write exactly the routed rows' output tiles and the weight
    blocks of the groups with rows (and the last group's, for a pool with
    no rows, which still visits one tile), and write them right."""
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels.gmm import gmm_pallas, tgmm_pallas
    if not hasattr(pltpu, "InterpretParams"):
        pytest.skip("this jax has no TPU interpreter")
    nan_mem = pltpu.InterpretParams(uninitialized_memory="nan")
    M, K, N, tm = 64, 16, 24, 8
    gs = jnp.array(ROUTED_LAYOUTS[layout], jnp.int32)
    total = int(gs.sum())
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, K, N))
    dy = jax.random.normal(jax.random.PRNGKey(2), (M, N))
    gids, n_active = ops._tile_group_ids(gs, M // tm, tm)
    assert int(n_active) == max(1, total // tm)
    out = np.asarray(gmm_pallas(x, w, gids, n_active, tile_m=tm, tile_k=K,
                                tile_n=8, interpret=nan_mem))
    visited = int(n_active) * tm
    assert np.isnan(out[visited:]).all()
    np.testing.assert_allclose(out[:total], ref.gmm_ref(x, w, gs)[:total],
                               atol=1e-4)
    dw = np.asarray(tgmm_pallas(x, dy, gids, n_active, 4, tile_m=tm,
                                tile_k=8, tile_n=8, interpret=nan_mem))
    want = np.asarray(ref.tgmm_ref(x, dy, gs, 4))
    written = np.asarray(gs) > 0
    written[-1] |= total == 0
    for g in range(4):
        if written[g]:
            assert np.isfinite(dw[g]).all()
            if gs[g]:
                np.testing.assert_allclose(dw[g], want[g], atol=1e-4)
        else:
            assert np.isnan(dw[g]).all()


@pytest.mark.parametrize("T,K,D", [(32, 2, 48), (64, 8, 16), (16, 1, 512)])
def test_combine_kernel(T, K, D):
    rows = jax.random.normal(jax.random.PRNGKey(0), (T, K, D))
    w = jax.random.normal(jax.random.PRNGKey(1), (T, K))
    np.testing.assert_allclose(ops.combine(rows, w),
                               ref.combine_ref(rows, w), atol=1e-4)
    g1 = jax.grad(lambda r, w: (ops.combine(r, w) ** 2).sum(),
                  argnums=(0, 1))(rows, w)
    g2 = jax.grad(lambda r, w: (ref.combine_ref(r, w) ** 2).sum(),
                  argnums=(0, 1))(rows, w)
    np.testing.assert_allclose(g1[0], g2[0], atol=1e-3)
    np.testing.assert_allclose(g1[1], g2[1], atol=1e-3)


def test_combine_bwd_matches_paper_formulas():
    """Stage 5 backward (paper lines 98-113): explicit formula check."""
    rows = jax.random.normal(jax.random.PRNGKey(0), (8, 2, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 2))
    dout = jax.random.normal(jax.random.PRNGKey(2), (8, 16))
    _, vjp = jax.vjp(ops.combine, rows, w)
    drows, dw = vjp(dout)
    drows_ref, dw_ref = ref.combine_bwd_ref(rows, w, dout)
    np.testing.assert_allclose(drows, drows_ref, atol=1e-5)
    np.testing.assert_allclose(dw, dw_ref, atol=1e-5)


@pytest.mark.parametrize("M,N", [(32, 48), (8, 512), (128, 16)])
def test_swiglu_kernel(M, N):
    g = jax.random.normal(jax.random.PRNGKey(0), (M, N))
    u = jax.random.normal(jax.random.PRNGKey(1), (M, N))
    np.testing.assert_allclose(ops.fused_swiglu(g, u), ref.swiglu_ref(g, u),
                               atol=1e-5)
    s1 = jax.grad(lambda g, u: (ops.fused_swiglu(g, u) ** 2).sum(),
                  argnums=(0, 1))(g, u)
    s2 = jax.grad(lambda g, u: (ref.swiglu_ref(g, u) ** 2).sum(),
                  argnums=(0, 1))(g, u)
    np.testing.assert_allclose(s1[0], s2[0], atol=1e-4)
    np.testing.assert_allclose(s1[1], s2[1], atol=1e-4)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 500), st.integers(1, 16), st.integers(0, 20))
def test_token_counts_property(n, e, off):
    """Histogram == bincount for arbitrary index streams/offsets."""
    idx = jax.random.randint(jax.random.PRNGKey(n), (n,), 0, e + off + 3)
    got = ops.token_counts(idx, e, off)
    expect = ref.token_counts_ref(idx, e, off)
    assert np.array_equal(np.array(got), np.array(expect))
    assert int(got.sum()) <= n


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 6), st.integers(2, 8))
def test_gmm_matches_blockdiag_property(G, nblk):
    """gmm == block-diagonal dense matmul for any aligned group layout."""
    rng = np.random.default_rng(G * 31 + nblk)
    M = nblk * 8 * G
    gs = _groups(rng, G, M, 8)
    x = jax.random.normal(jax.random.PRNGKey(0), (M, 12))
    w = jax.random.normal(jax.random.PRNGKey(1), (G, 12, 20))
    np.testing.assert_allclose(ops.gmm(x, w, gs), ref.gmm_ref(x, w, gs),
                               atol=1e-4)

"""The Mula MoE path's Pallas kernels compile for a TPU v5e.

Each case lowers one kernel (through the ``kernels.ops`` wrappers, the way
the MoE layer calls them) at Mula-7B-A1B widths (d_model 2048, 64 experts
of d_ff 1024, top-8, 16 heads of 128, one 2048-token sequence) and compiles
it for one chip of a described ``v5e:2x2`` topology — no chip needed, the
TPU compiler refuses here what the chip would refuse: misaligned blocks,
blocks that do not fit the scoped VMEM, layouts Mosaic cannot lower.
Interpret-mode tests cannot see any of that.

The topology is described only inside the module fixture below: only one
process may load the TPU library at a time, so describing it on import
would break a multi-worker test run. All compiles stay in this file so
they share the worker that loaded it.
"""
import os

import pytest

T, SEQ = 2048, 2048          # routed tokens per call; attention sequence


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:      # else libtpu logs to /tmp
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU lib
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compiled_plan():
    # the ops wrappers pick interpret mode from the backend, which is the
    # CPU here: the plan forces the compiled (Mosaic) lowering
    from repro.parallel.plan import KernelPlan
    return KernelPlan(backend="pallas", interpret=False, tile_m=128,
                      tile_k=512, tile_n=512)


def _widths():
    from repro.configs import get_config
    cfg = get_config("mula-7b-a1b")
    m = cfg.moe
    return dict(d=cfg.d_model, e=m.num_experts, f=m.d_ff_expert,
                k=m.experts_per_token, h=cfg.num_heads, hd=cfg.head_dim)


def _cases():
    """name -> (fn, [(shape, dtype)]) at Mula-7B-A1B widths."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    w = _widths()
    d, e, f, k = w["d"], w["e"], w["f"], w["k"]
    bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    # dropless slot pool: every (token, k) pair plus one tile of alignment
    # slack per expert (core/moe.py dropless_pool_rows at tile_m=128)
    m = T * k + 128 * e

    def gmm_grads(x, wt, gs):
        return jax.grad(lambda a, b: ops.gmm(a, b, gs).astype(f32).sum(),
                        argnums=(0, 1))(x, wt)

    def combine_grads(rows, wts):
        return jax.grad(lambda r, c: ops.combine(r, c).astype(f32).sum(),
                        argnums=(0, 1))(rows, wts)

    # one EP=4 chip's dropless pool at the benchmark's 3x2048 tokens per
    # chip: all 4 chips' T*K rows plus a tile per local expert, of which
    # the grid visits only the routed tiles
    el = e // 4
    m_ep = 4 * 3 * T * k + 128 * el
    attn = [((1, SEQ, w["h"], w["hd"]), bf)] * 3
    return {
        "gmm_fwd_gate_up": (ops.gmm, [((m, d), bf), ((e, d, f), bf),
                                      ((e,), i32)]),
        "gmm_fwd_down": (ops.gmm, [((m, f), bf), ((e, f, d), bf),
                                   ((e,), i32)]),
        # backward: dx through gmm on the transposed stack, dw through tgmm
        "gmm_bwd": (gmm_grads, [((m, d), bf), ((e, d, f), bf), ((e,), i32)]),
        "gmm_fwd_ep4_pool": (ops.gmm, [((m_ep, d), bf), ((el, d, f), bf),
                                       ((el,), i32)]),
        "gmm_bwd_ep4_pool": (gmm_grads, [((m_ep, d), bf), ((el, d, f), bf),
                                         ((el,), i32)]),
        "fused_swiglu": (ops.fused_swiglu, [((m, f), bf), ((m, f), bf)]),
        "combine_fwd": (ops.combine, [((T, k, d), bf), ((T, k), bf)]),
        "combine_bwd": (combine_grads, [((T, k, d), bf), ((T, k), bf)]),
        # float32 rows double the block bytes: the tile must shrink to fit
        # the scoped VMEM (the float32 MoE-layer check takes this path)
        "combine_bwd_f32": (combine_grads, [((512, k, d), f32),
                                            ((512, k), f32)]),
        "token_counts": (lambda ids: ops.token_counts(ids, e, 0),
                         [((T * k,), i32)]),
        "flash_attention_fwd": (ops.flash_attention, attn),
    }


@pytest.mark.parametrize("name", ["gmm_fwd_gate_up", "gmm_fwd_down",
                                  "gmm_bwd", "gmm_fwd_ep4_pool",
                                  "gmm_bwd_ep4_pool", "fused_swiglu",
                                  "combine_fwd",
                                  "combine_bwd", "combine_bwd_f32",
                                  "token_counts", "flash_attention_fwd"])
def test_kernel_compiles_for_v5e(one_chip, name):
    import jax
    from repro.parallel.plan import use_kernel_plan

    fn, specs = _cases()[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in specs]
    with use_kernel_plan(_compiled_plan()):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def test_ep_moe_layer_compiles_for_v5e_2x2(topo):
    """The dropless MoE layer under EP over the four described chips, its
    Pallas kernels compiled inside the ``check_vma`` shard_map region
    (``core/moe.py moe_fsmoe_ep``) — the combination interpret-mode runs
    cannot type-check (``ops.vma_checkable``): forward and gradients."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.core.moe import init_moe_block, sparse_moe_block
    from repro.parallel.plan import ParallelPlan, use_kernel_plan

    cfg = get_config("mula-7b-a1b")
    cfg = dataclasses.replace(cfg, num_layers=1, moe=dataclasses.replace(
        cfg.moe, dispatch="dropless", moe_impl="fsmoe"))
    mesh = ParallelPlan(ep=4).resolve(cfg, devices=topo.devices).mesh
    params = jax.eval_shape(lambda: init_moe_block(jax.random.PRNGKey(0),
                                                   cfg))
    shard = {"gate": P("ep"), "up": P("ep"), "down": P("ep")}
    params = {k: jax.ShapeDtypeStruct(v.shape, jnp.bfloat16,
                                      sharding=NamedSharding(
                                          mesh, shard.get(k, P())))
              for k, v in params.items()}
    x = jax.ShapeDtypeStruct((1, T, cfg.d_model), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P()))

    def loss(p, xx):
        out = sparse_moe_block(p, xx, cfg, mesh=mesh, ep_axis="ep",
                               batch_axes=())[0]
        return out.astype(jnp.float32).sum()

    with use_kernel_plan(_compiled_plan()):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, x).compile()
    assert "tpu_custom_call" in compiled.as_text()

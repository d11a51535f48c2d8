"""Probe-based roofline cost accounting (spec §ROOFLINE ANALYSIS).

XLA's ``cost_analysis()`` counts a ``while`` body once, so a scanned-layers
module under-reports FLOPs/bytes by ~the trip count. This module therefore
derives the roofline terms from *scan-free probes* — each structural
component is lowered and compiled on the real production mesh with its real
shardings, its HLO parsed exactly, and the totals composed with the known
structural trip counts:

    total = Σ_component  probe_cost(component) × trips(component)

Components per step kind:
  train    : per-layer fwd+bwd probe (with SAC remat, so recompute FLOPs are
             included) × L × microbatches; embed/head+CE probe × microbatches;
             optimizer-update probe × 1 (captures the paper's all-gather of
             updated params; the DP gradient reduce-scatter is added
             analytically per leaf — see _dp_grad_reduce_bytes).
  prefill  : per-layer fwd probe × L; embed/head fwd probe.
  decode   : per-layer decode probe × L; embed/head probe.

Probes run with ``layers.ATTN_BLOCK_OVERRIDE`` = full sequence, making the
flash-attention scans single-trip (FLOPs exact — the blockwise kernel
computes the same masked S² products). The memory term for attention is
corrected analytically: the probe materializes the (S×S) score tensor that
the real blockwise kernel keeps in VMEM, so we subtract the score traffic
and add the flash K/V re-read traffic (documented approximation; FLOPs and
collective terms are exact). Mamba recurrences get analytic scan-body
corrections (their in-scan flops are tiny relative to the matmuls).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, InputShape
from repro.models import model as M
from repro.models import layers as L
from repro.models import ssm as S
from repro.optim import adamw_init, adamw_update
from repro.optim.epso import optimizer_state_shardings
from repro.parallel.sharding import ShardingRules, shardings, param_specs
from repro.launch import roofline as RL


def _probe(fn, args, out_shardings=None):
    """Lower+compile a scan-free probe; return per-chip (flops, bytes, coll)."""
    jitted = jax.jit(fn, out_shardings=out_shardings) if out_shardings \
        else jax.jit(fn)
    compiled = jitted.lower(*args).compile()
    cost = compiled.cost_analysis()
    coll = RL.collective_bytes(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)), coll)


def _merge(acc, probe, mult=1.0):
    f, b, c = probe
    acc["flops"] += f * mult
    acc["bytes"] += b * mult
    for k, v in c.items():
        if k == "unknown_dtypes":      # list of dtype tokens, not a count
            cur = acc["coll"].get(k, [])
            acc["coll"][k] = sorted(set(cur) | set(v))
            continue
        acc["coll"][k] = acc["coll"].get(k, 0.0) + v * mult
    return acc


def _zero():
    return {"flops": 0.0, "bytes": 0.0, "coll": {}}


def _sds_tree(tree, shard_tree, mesh):
    if shard_tree is None:
        return jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype),
                            tree)
    return jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        tree, shard_tree)


def _layer_params_shapes(cfg: ModelConfig, kind: str):
    """eval_shape one layer's params (unstacked)."""
    rng = jax.random.PRNGKey(0)
    if kind == "dense":
        return jax.eval_shape(lambda: M._init_dense_layer(rng, cfg))
    if kind == "moe":
        return jax.eval_shape(lambda: M._init_moe_layer(rng, cfg))
    if kind == "ssm":
        return jax.eval_shape(lambda: M._init_ssm_layer(rng, cfg))
    if kind == "xattn":
        return jax.eval_shape(lambda: M._init_xattn_layer(rng, cfg))
    raise ValueError(kind)


def _layer_shardings(cfg, lp_shapes, rules, prefix="layers"):
    """Reuse param_specs by faking the stacked path (specs are stack-aware,
    so wrap under the expected key with no leading dim shift needed)."""
    if rules.mesh is None:
        return None
    fake = {prefix: jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((1,) + l.shape, l.dtype), lp_shapes)}
    specs = param_specs(fake, rules)[prefix]
    # drop the leading stacked None entry
    def unstack(s, l):
        entries = list(s)[1:]
        return NamedSharding(rules.mesh, P(*entries))
    return jax.tree.map(unstack, specs, lp_shapes)


# ----------------------------------------------------------------------------
# attention memory-term corrections (analytic, per probe application)
# ----------------------------------------------------------------------------

def _flash_attn_bytes(cfg, rules, Bmb, Sq, Skv, *, train: bool) -> float:
    """Analytic per-chip HBM traffic of a blockwise (flash) attention — what
    a fused TPU kernel actually moves: Q/K/V/O streams + K/V re-reads per
    extra q-block. Replaces the probe's materialized-score traffic (an
    artifact of the probe's single-block XLA lowering)."""
    bshards = _tp_shards(rules)
    tp = 1
    if rules.mesh is not None and rules.tp_axis:
        n = rules.mesh.shape[rules.tp_axis]
        if cfg.num_heads % n == 0:
            tp = n
    B_loc = max(Bmb // max(bshards, 1), 1)
    nh_loc = cfg.num_heads // tp
    nkv_loc = max(cfg.num_kv_heads // tp, 1) if cfg.num_kv_heads else 1
    t = 2.0 * cfg.head_dim          # bf16 per (token, head)
    q = B_loc * Sq * nh_loc * t
    o = q
    k = B_loc * Skv * nkv_loc * t
    v = k
    nq = max(1, Sq // 512)
    base = q + k + v + o
    rereads = (nq - 1) * (k + v)
    if train:
        return 10.0 * base + 3.0 * rereads
    return base + rereads


def _ssm_scan_correction(cfg, B, Sq) -> tuple[float, float]:
    """(flops, bytes) under-counted by the recurrence scans (per layer)."""
    if cfg.ssm is None:
        return 0.0, 0.0
    # NOTE on bytes: the scan's stacked vjp-residual buffers live *outside*
    # the while loop, so the probe's "bytes accessed" already counts the
    # trajectory traffic; only the in-scan FLOPs are under-counted. The
    # per-step carry itself fits VMEM on the target (e.g. falcon-mamba:
    # B_loc*di*ds*4 = 8 MB < 16 MB v5e VMEM).
    if cfg.ssm.variant == "mamba1":
        di = cfg.ssm.expand * cfg.d_model
        ds = cfg.ssm.d_state
        body_f = 8.0 * B * di * ds   # decay+update+readout per step
        return body_f * (Sq - 1), 0.0
    d, di, H, Pd, N, _ = S.mamba2_dims(cfg)
    Lc = cfg.ssm.chunk
    C = max(1, Sq // Lc)
    body_f = 3.0 * B * Lc * H * Pd * N + 3.0 * B * H * Pd * N
    return body_f * (C - 1), 0.0


# ----------------------------------------------------------------------------
# per-arch structural decomposition
# ----------------------------------------------------------------------------

def _block_fn(cfg, kind, rules, mesh, sac):
    if kind == "dense":
        return lambda lp, x: M._dense_block(lp, x, cfg, rules, sac)
    if kind == "moe":
        return lambda lp, x: M._moe_block(lp, x, cfg, rules, sac, mesh)[0]
    if kind == "ssm":
        return lambda lp, x: M._ssm_block(lp, x, cfg, rules, sac)
    if kind == "xattn":
        mem_shape = None  # bound later
        raise RuntimeError("use _xattn_fn")
    raise ValueError(kind)


def structure(cfg: ModelConfig):
    """[(layer_kind, count)] per arch."""
    at = cfg.arch_type
    if at in ("dense", "vlm"):
        return [("dense", cfg.num_layers)]
    if at == "moe":
        return [("moe", cfg.num_layers)]
    if at == "ssm":
        return [("ssm", cfg.num_layers)]
    if at == "hybrid":
        n_shared = cfg.num_layers // cfg.shared_attn_every
        return [("ssm", cfg.num_layers), ("dense", n_shared)]
    if at == "audio":
        return [("enc", cfg.num_encoder_layers), ("xattn", cfg.num_layers)]
    raise ValueError(at)


def analyze(cfg: ModelConfig, shape: InputShape, rules: ShardingRules,
            *, opt_mode: str = "epso", sac: str = "block",
            microbatches: int = 1, compute_dtype=jnp.bfloat16) -> dict:
    """Compose probe costs into per-chip totals {flops, bytes, coll}."""
    mesh = rules.mesh
    chips = mesh.size if mesh else 1
    acc = _zero()
    B = shape.global_batch
    train = shape.kind == "train"
    nmb = microbatches if train else 1
    Bmb = max(B // nmb, 1)
    Sq = shape.seq_len
    if cfg.arch_type == "audio":
        Sq = shape.seq_len // 2
    if cfg.arch_type == "vlm":
        Sq = shape.seq_len

    bspec = P(rules.batch_axes if len(rules.batch_axes) != 1
              else rules.batch_axes[0], None, None) if mesh else None
    x_sds = (jax.ShapeDtypeStruct((Bmb, Sq, cfg.d_model), compute_dtype,
                                  sharding=NamedSharding(mesh, bspec))
             if mesh else
             jax.ShapeDtypeStruct((Bmb, Sq, cfg.d_model), compute_dtype))

    old_override = L.ATTN_BLOCK_OVERRIDE
    L.ATTN_BLOCK_OVERRIDE = max(Sq, 1)
    try:
        if shape.kind in ("train", "prefill"):
            _analyze_fwd(cfg, acc, rules, mesh, x_sds, Bmb, Sq, train, sac,
                         nmb, compute_dtype, shape)
        else:
            _analyze_decode(cfg, acc, rules, mesh, shape, compute_dtype)
    finally:
        L.ATTN_BLOCK_OVERRIDE = old_override

    if train:
        _analyze_optimizer(cfg, acc, rules, opt_mode)
    return {"flops_per_chip": acc["flops"], "bytes_per_chip": acc["bytes"],
            "coll_per_chip": acc["coll"], "chips": chips}


def _tp_shards(rules):
    if rules.mesh is None:
        return 1
    n = 1
    for a in rules.batch_axes:
        n *= rules.mesh.shape[a]
    return n


def _analyze_fwd(cfg, acc, rules, mesh, x_sds, Bmb, Sq, train, sac, nmb,
                 cd, shape):
    mult_batch_shards = _tp_shards(rules)

    def probe_block(kind, count, fn, extra_args=()):
        lp_shapes = _layer_params_shapes(
            cfg, "dense" if kind in ("enc", "dense") else kind)
        lsh = _layer_shardings(cfg, lp_shapes, rules)
        lp_sds = _sds_tree(lp_shapes, lsh, mesh)

        def wrap(f):
            body = f
            if train:
                body = M.block_remat(f, sac)  # count the SAC recompute
            if train:
                def loss_like(lp, x, *rest):
                    return (body(lp, x, *rest).astype(jnp.float32) ** 2).sum()
                return jax.grad(loss_like, argnums=(0, 1))
            return body

        pr = _probe(wrap(fn), (lp_sds, x_sds) + extra_args)
        _merge(acc, pr, count * nmb)

        # attention memory correction: swap the probe's materialized-score
        # traffic for the analytic flash-kernel traffic (FLOPs untouched)
        if kind in ("dense", "moe", "enc", "xattn") and cfg.num_heads:
            attn_pr = _probe(
                wrap(lambda lp, x: L.attention(
                    lp["attn"], x, cfg, constrain=rules.constrain,
                    causal=(kind != "enc"))), (lp_sds, x_sds))
            delta = _flash_attn_bytes(cfg, rules, Bmb, Sq, Sq,
                                      train=train) - attn_pr[1]
            if kind == "xattn":   # self + cross attention
                xpr = _probe(
                    wrap(lambda lp, x: L.attention(
                        lp["xattn"], x, cfg, constrain=rules.constrain,
                        memory=x)), (lp_sds, x_sds))
                delta += _flash_attn_bytes(cfg, rules, Bmb, Sq, Sq,
                                           train=train) - xpr[1]
            acc["bytes"] += delta * count * nmb

        # corrections for the recurrence scans (XLA counts bodies once)
        if kind == "ssm":
            cf, cb = _ssm_scan_correction(cfg, Bmb, Sq)
            f = (3.0 if train else 1.0)
            acc["flops"] += cf * f * count * nmb / mult_batch_shards
            acc["bytes"] += cb * f * count * nmb / mult_batch_shards

    for kind, count in structure(cfg):
        if kind == "dense":
            probe_block("dense", count,
                        lambda lp, x: M._dense_block(lp, x, cfg, rules, sac))
        elif kind == "enc":
            probe_block("enc", count,
                        lambda lp, x: M._dense_block(lp, x, cfg, rules, sac,
                                                     causal=False))
        elif kind == "moe":
            probe_block("moe", count,
                        lambda lp, x: M._moe_block(lp, x, cfg, rules, sac,
                                                   mesh)[0])
        elif kind == "ssm":
            probe_block("ssm", count,
                        lambda lp, x: M._ssm_block(lp, x, cfg, rules, sac))
        elif kind == "xattn":
            probe_block("xattn", count,
                        lambda lp, x, m: M._xattn_block(lp, x, m, cfg, rules,
                                                        sac),
                        extra_args=(x_sds,))

    # embed + head (+ CE loss when training)
    vp = M.padded_vocab(cfg)
    emb_shapes = jax.eval_shape(
        lambda: {"embed": L.init_embedding(jax.random.PRNGKey(0), vp,
                                           cfg.d_model),
                 "final_norm": L.init_norm(cfg.norm, cfg.d_model)})
    esh = shardings(emb_shapes, rules)
    emb_sds = _sds_tree(emb_shapes, esh, mesh)
    bspec1 = (NamedSharding(mesh, P(rules.batch_axes
                                    if len(rules.batch_axes) != 1
                                    else rules.batch_axes[0], None))
              if mesh else None)
    tok_sds = (jax.ShapeDtypeStruct((Bmb, Sq), jnp.int32, sharding=bspec1)
               if mesh else jax.ShapeDtypeStruct((Bmb, Sq), jnp.int32))

    def emb_head(p, tokens, h):
        e = L.embed(p["embed"], tokens, cd)
        hh = L.apply_norm(p["final_norm"], h + 0 * e, cfg.norm)
        logits = L.unembed(p["embed"], hh).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
        return (lse - ll).sum()

    if train:
        pr = _probe(jax.grad(emb_head, argnums=(0, 2)),
                    (emb_sds, tok_sds, x_sds))
    else:
        pr = _probe(emb_head, (emb_sds, tok_sds, x_sds))
    _merge(acc, pr, nmb)


def _analyze_decode(cfg, acc, rules, mesh, shape, cd):
    from repro.launch.specs import decode_input_specs
    B = shape.global_batch
    bspec = P(rules.batch_axes if len(rules.batch_axes) != 1
              else (rules.batch_axes[0] if rules.batch_axes else None),
              None, None)
    x_sds = (jax.ShapeDtypeStruct((B, 1, cfg.d_model), cd,
                                  sharding=NamedSharding(mesh, bspec))
             if mesh else jax.ShapeDtypeStruct((B, 1, cfg.d_model), cd))
    tokens, cache, index = decode_input_specs(cfg, shape, rules)

    def one_layer_cache(tree, kind="kv"):
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(
                l.shape[1:], l.dtype,
                sharding=NamedSharding(
                    mesh, P(*list(l.sharding.spec)[1:])) if mesh else None),
            tree)

    at = cfg.arch_type
    if at in ("dense", "vlm", "moe"):
        lp_shapes = _layer_params_shapes(cfg, "moe" if at == "moe" else "dense")
        lsh = _layer_shardings(cfg, lp_shapes, rules)
        lp_sds = _sds_tree(lp_shapes, lsh, mesh)
        kv = one_layer_cache(cache["kv"])

        def dec(lp, x, kv):
            a, kv2 = L.decode_attention(
                lp["attn"], L.apply_norm(lp["ln1"], x, cfg.norm), kv,
                jnp.int32(17), cfg)
            h = x + a
            x2 = L.apply_norm(lp["ln2"], h, cfg.norm)
            if at == "moe":
                from repro.core import moe as moe_lib
                mo, _, _, _ = moe_lib.sparse_moe_block(lp["moe"], x2, cfg,
                                                       mesh=None)
                return h + mo, kv2
            return h + L.apply_mlp(lp["mlp"], x2, cfg.mlp_activation), kv2

        _merge(acc, _probe(dec, (lp_sds, x_sds, kv)), cfg.num_layers)
    elif at == "ssm":
        lp_shapes = _layer_params_shapes(cfg, "ssm")
        lsh = _layer_shardings(cfg, lp_shapes, rules)
        lp_sds = _sds_tree(lp_shapes, lsh, mesh)
        c = one_layer_cache(cache["ssm"])
        stepf = (S.mamba1_decode_step if cfg.ssm.variant == "mamba1"
                 else S.mamba2_decode_step)

        def dec(lp, x, c):
            y, c2 = stepf(lp["mixer"], L.apply_norm(lp["ln"], x, cfg.norm),
                          c, cfg)
            return x + y, c2

        _merge(acc, _probe(dec, (lp_sds, x_sds, c)), cfg.num_layers)
    elif at == "hybrid":
        lp_shapes = _layer_params_shapes(cfg, "ssm")
        lsh = _layer_shardings(cfg, lp_shapes, rules)
        lp_sds = _sds_tree(lp_shapes, lsh, mesh)
        c = one_layer_cache(cache["groups"])

        def dec(lp, x, c):
            y, c2 = S.mamba2_decode_step(
                lp["mixer"], L.apply_norm(lp["ln"], x, cfg.norm), c, cfg)
            return x + y, c2

        _merge(acc, _probe(dec, (lp_sds, x_sds, c)), cfg.num_layers)
        # shared attention blocks
        sh_shapes = _layer_params_shapes(cfg, "dense")
        ssh = _layer_shardings(cfg, sh_shapes, rules)
        sh_sds = _sds_tree(sh_shapes, ssh, mesh)
        skv = one_layer_cache(cache["shared_kv"])

        def dec_sh(lp, x, kv):
            a, kv2 = L.decode_attention(
                lp["attn"], L.apply_norm(lp["ln1"], x, cfg.norm), kv,
                jnp.int32(17), cfg)
            h = x + a
            return h + L.apply_mlp(lp["mlp"],
                                   L.apply_norm(lp["ln2"], h, cfg.norm),
                                   cfg.mlp_activation), kv2

        _merge(acc, _probe(dec_sh, (sh_sds, x_sds, skv)),
               cfg.num_layers // cfg.shared_attn_every)
    elif at == "audio":
        lp_shapes = _layer_params_shapes(cfg, "xattn")
        lsh = _layer_shardings(cfg, lp_shapes, rules)
        lp_sds = _sds_tree(lp_shapes, lsh, mesh)
        kv = one_layer_cache(cache["kv"])
        mem = jax.ShapeDtypeStruct(
            cache["memory"].shape, cd,
            sharding=cache["memory"].sharding if mesh else None)

        def dec(lp, x, kv, mem):
            a, kv2 = L.decode_attention(
                lp["attn"], L.apply_norm(lp["ln1"], x, cfg.norm), kv,
                jnp.int32(17), cfg)
            h = x + a
            h = h + L.attention(lp["xattn"], L.apply_norm(lp["lnx"], h,
                                                          cfg.norm),
                                cfg, memory=mem)
            return h + L.apply_mlp(lp["mlp"],
                                   L.apply_norm(lp["ln2"], h, cfg.norm),
                                   cfg.mlp_activation), kv2

        _merge(acc, _probe(dec, (lp_sds, x_sds, kv, mem)), cfg.num_layers)

    # head
    vp = M.padded_vocab(cfg)
    emb_shapes = jax.eval_shape(
        lambda: {"embed": L.init_embedding(jax.random.PRNGKey(0), vp,
                                           cfg.d_model),
                 "final_norm": L.init_norm(cfg.norm, cfg.d_model)})
    esh = shardings(emb_shapes, rules)
    emb_sds = _sds_tree(emb_shapes, esh, mesh)

    def head(p, h):
        return L.unembed(L.apply_norm(p["final_norm"], h, cfg.norm),
                         p["embed"]) if False else \
            L.unembed(p["embed"], L.apply_norm(p["final_norm"], h, cfg.norm))

    _merge(acc, _probe(head, (emb_sds, x_sds)), 1)


def _dp_grad_reduce_bytes(params_shapes, rules) -> float:
    """Analytic per-device bytes for the DP gradient reduction (bf16,
    ring reduce-scatter): each leaf reduces over the batch axes it is
    replicated on."""
    if rules.mesh is None:
        return 0.0
    specs = param_specs(params_shapes, rules)
    total = 0.0
    for spec, leaf in zip(jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)),
                          jax.tree.leaves(params_shapes)):
        used = set()
        for e in spec:
            for a in (e if isinstance(e, tuple) else (e,)):
                if a is not None:
                    used.add(a)
        n = 1
        for a in rules.batch_axes:
            if a not in used:
                n *= rules.mesh.shape[a]
        if n > 1:
            shard = leaf.size
            for e in spec:
                for a in (e if isinstance(e, tuple) else (e,)):
                    if a is not None:
                        shard //= rules.mesh.shape[a]
            total += shard * 2.0 * (n - 1) / n    # bf16 reduction
    return total


def _analyze_optimizer(cfg, acc, rules, opt_mode):
    mesh = rules.mesh
    params_shapes = jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    params_bf16 = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16), params_shapes)
    psh = shardings(params_bf16, rules)
    osh = optimizer_state_shardings(params_bf16, rules, opt_mode)
    opt_shapes = jax.eval_shape(adamw_init, params_bf16)

    def mk(tree, sh):
        return _sds_tree(tree, sh, mesh)

    grads = mk(jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.float32), params_bf16),
        psh)
    state = opt_shapes._replace(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        master=mk(opt_shapes.master, osh),
        m=mk(opt_shapes.m, osh),
        v=mk(opt_shapes.v, osh))

    def upd(grads, state):
        new_p, new_s, _ = adamw_update(grads, state, lr=1e-4,
                                       param_dtype=jnp.bfloat16)
        return new_p, new_s

    out_sh = (psh, state._replace(
        step=None, master=osh, m=osh, v=osh)) if mesh else None
    try:
        pr = _probe(upd, (grads, state), out_shardings=out_sh)
    except Exception:
        pr = _probe(upd, (grads, state))
    _merge(acc, pr, 1.0)
    acc["coll"]["dp-grad-reduce"] = acc["coll"].get("dp-grad-reduce", 0.0) + \
        _dp_grad_reduce_bytes(params_bf16, rules)
    acc["coll"]["total"] = sum(v for k, v in acc["coll"].items()
                               if k not in ("total", "unknown_dtypes"))


# ----------------------------------------------------------------------------
# analytic per-stage pipeline accounting (no compile — dryrun + bench_scaling)
# ----------------------------------------------------------------------------

def per_stage_costs(cfg: ModelConfig, *, pp: int, microbatches: int,
                    seq: int, global_batch: int,
                    pp_impl: str = "shardmap",
                    schedule: str = "1f1b") -> dict:
    """Projected per-stage FLOPs/bytes of one pipelined train step.

    Shape-only analytics (nothing is lowered or compiled): per-layer FLOPs
    come from the active per-layer parameter count plus the attention
    quadratic term; the embed/head/CE terms are attributed per stage
    according to the executor:

    * ``pp_impl='masked'`` — single-program SPMD: *every* stage pays the
      masked head+CE on every tick (fwd on F waves; recompute + backward on
      B waves) because SPMD cannot branch per stage.
    * ``pp_impl='shardmap'`` — per-stage programs: only stage 0 embeds,
      only the last stage runs head+CE, and the backward reuses the saved
      stage output (no head recompute on B waves).

    Both executors compute a masked F-wave and B-wave on every clock tick,
    so totals scale with the tick count T(n_mb, pp) — bubble ticks included
    (that is the honest simulated-mesh cost; on real stage-local hardware
    bubble ticks idle instead).

    Returns {"ticks", "stages": [{stage, role, block_gflops, embed_gflops,
    head_gflops, total_gflops, act_gbytes}, ...]}.
    """
    from repro.models.model import padded_vocab
    from repro.parallel.pipeline import schedule_masks

    n_mb = max(microbatches, 1)
    if pp > 1:
        T = schedule_masks(schedule, n_mb, pp)["ticks"]
    else:
        T = n_mb                                   # plain microbatch scan
    mb_rows = max(global_batch // n_mb, 1)
    t = mb_rows * seq                              # tokens per microbatch
    d = cfg.d_model
    vp = padded_vocab(cfg)

    # per-layer active params: active total minus embed/head tables
    emb_params = vp * d * (1 if cfg.tie_embeddings else 2)
    p_layer = max((cfg.active_param_count() - emb_params)
                  / max(cfg.num_layers, 1), 0.0)
    # fwd flops: 2*p*t matmuls + 4*t*S*d attention scores/values (causal
    # not discounted); one tick's work = 1x fwd (F wave) + 3x fwd-equiv
    # (B wave: block-input recompute + backward)
    f_layer = 2.0 * p_layer * t + 4.0 * t * seq * d
    f_head = 2.0 * t * d * vp                      # unembed matmul fwd
    layers_per_stage = max(cfg.num_layers // max(pp, 1), 1)

    stages = []
    for s in range(pp):
        first, last = s == 0, s == pp - 1
        block = T * 4.0 * f_layer * layers_per_stage
        if pp_impl == "masked" or pp == 1:
            head = T * 4.0 * f_head                # every stage, every tick
            embed_b = T * 2.0 * t * d * 4.0        # masked embed gather r/w
            role = "embed+blocks+head_ce (masked)" if pp > 1 else "all"
        else:
            head = T * 3.0 * f_head if last else 0.0   # saved-output bwd
            embed_b = T * 2.0 * t * d * 4.0 if first else 0.0
            role = ("embed+blocks" if first else
                    "blocks+head_ce" if last else "blocks")
        act_bytes = T * 2.0 * t * d * 4.0 + embed_b    # hand-off + embed
        stages.append({
            "stage": s, "role": role,
            "block_gflops": block / 1e9,
            "head_gflops": head / 1e9,
            "total_gflops": (block + head) / 1e9,
            "act_gbytes": act_bytes / 1e9,
        })
    return {"ticks": int(T), "pp": pp, "impl": pp_impl if pp > 1 else "-",
            "microbatches": n_mb, "stages": stages}


# ----------------------------------------------------------------------------
# analytic per-kernel attribution (no compile — dryrun --parallel)
# ----------------------------------------------------------------------------

def per_kernel_costs(cfg: ModelConfig, pplan, *, global_batch: int,
                     seq: int = 2048, hw: str | None = None,
                     table=None) -> dict:
    """Per-kernel roofline attribution of one MoE layer's forward pass,
    per device, under ``pplan``'s axis sizes. Shape-only analytics.

    Each row: analytic FLOPs/bytes (bf16 streams), arithmetic intensity,
    the ``hw`` roofline's predicted time and bound; plus — when the tuning
    ``table`` has a matching (kernel, backend, bucket) entry — the
    measured tiles/time and achieved-vs-peak fraction stamped at bench
    time. Predicted-vs-measured divergence per kernel is the number CI
    tracks (check_regression.py::check_kernels).
    """
    from repro.launch import roofline as RL

    spec = RL.get_hardware(hw or pplan.kernel.hw)
    moe = cfg.moe
    if moe is None:
        return {"hw": spec.name, "rows": [], "note": f"{cfg.name} has no "
                f"MoE block — per-kernel attribution covers expert kernels"}
    d = cfg.d_model
    f = moe.d_ff_expert
    E = moe.num_experts
    topk = moe.experts_per_token
    dp_ways = pplan.pod * pplan.dp * pplan.ep       # token rows shard here
    ep, tp = pplan.ep, pplan.tp
    t_loc = max(global_batch * seq // dp_ways, 1)   # tokens per device
    m = t_loc * topk                                # assigned rows/device
    g_loc = max(E // ep, 1)                         # experts per device
    f_loc = max(f // tp, 1) if f else f             # expert d_ff per device
    bb = 2.0                                        # bf16 stream bytes

    def row(kernel, dims, flops, byts):
        ai = flops / byts if byts else 0.0
        pred = spec.roofline_time(flops, byts)
        r = {"kernel": kernel, "dims": dims, "flops": flops, "bytes": byts,
             "ai": ai, "pred_ms": pred * 1e3,
             "bound": ("compute" if flops / spec.peak_flops
                       >= byts / spec.hbm_bw else "memory")}
        if table is not None:
            e = table.find(kernel, pplan.kernel.backend
                           if pplan.kernel.backend != "ref" else "pallas",
                           dims)
            if e is not None:
                r.update({"tiles": tuple(e["tiles"]),
                          "measured_ms": e["time_ms"],
                          "default_ms": e.get("default_time_ms"),
                          "measured_bucket": "_".join(
                              f"{k}{v}" for k, v in sorted(
                                  e["bucket"].items())),
                          "measured_hw": e.get("measured_hw", e.get("hw")),
                          "achieved_frac": e.get("achieved_frac")})
        return r

    rows = []
    # gate and up projections: one gmm each over the local expert stack
    gmm_b = bb * (m * d + g_loc * d * f_loc + m * f_loc)
    for name in ("gmm[gate]", "gmm[up]"):
        rows.append(row("gmm", {"g": g_loc, "m": m, "k": d, "n": f_loc},
                        2.0 * m * d * f_loc, gmm_b))
        rows[-1]["kernel_instance"] = name
    rows.append(row("gmm", {"g": g_loc, "m": m, "k": f_loc, "n": d},
                    2.0 * m * f_loc * d,
                    bb * (m * f_loc + g_loc * f_loc * d + m * d)))
    rows[-1]["kernel_instance"] = "gmm[down]"
    # fused SwiGLU: silu(gate) * up, ~5 flops/element in f32
    rows.append(row("fused_swiglu", {"m": m, "n": f_loc},
                    5.0 * m * f_loc, bb * 3.0 * m * f_loc))
    rows[-1]["kernel_instance"] = "fused_swiglu"
    # combine: weighted top-k reduction back to token order
    rows.append(row("combine", {"t": t_loc, "k": topk, "d": d},
                    2.0 * t_loc * topk * d,
                    bb * (t_loc * topk * d + t_loc * d) + 4.0 * t_loc * topk))
    rows[-1]["kernel_instance"] = "combine"
    # dispatch: histogram + gather into expert order (bandwidth only)
    rows.append(row("moe_dispatch", {"t": t_loc, "k": topk, "d": d},
                    0.0, bb * 2.0 * m * d))
    rows[-1]["kernel_instance"] = "moe_dispatch"
    if cfg.num_heads:
        nh_loc = max(cfg.num_heads // tp, 1)
        hd = cfg.head_dim
        rows.append(row("flash_attention",
                        {"t": t_loc, "s": seq, "h": nh_loc, "hd": hd},
                        4.0 * t_loc * seq * nh_loc * hd,
                        bb * 4.0 * t_loc * nh_loc * hd
                        + bb * 2.0 * t_loc * nh_loc * hd
                        * max(seq // 512 - 1, 0)))
        rows[-1]["kernel_instance"] = "flash_attention"
    return {"hw": spec.name, "per": "MoE layer fwd, per device",
            "tokens_per_device": t_loc, "rows": rows}

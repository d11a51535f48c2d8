"""Unified model builder for all supported architecture families.

``init_params`` / ``forward`` / ``loss_fn`` / ``init_cache`` / ``decode_step``
dispatch on ``cfg.arch_type`` in {dense, moe, vlm, ssm, hybrid, audio}.

Layer stacks are *scanned* (stacked params with a leading layer dim +
``lax.scan``) so that HLO size and compile time stay flat in depth — the
standard large-model JAX pattern. The zamba2-style hybrid scans over
"macro-groups" of ``shared_attn_every`` mamba layers followed by one
application of the shared-weight attention+MLP block.

Selective activation checkpointing (paper §1 SAC) wraps the selected
sub-modules (norm / attn / moe / mlp / block) in ``jax.checkpoint``: only the
module inputs are saved, its internals recomputed in backward — exactly the
paper's semantics.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import moe as moe_lib
from . import layers as L
from . import ssm as S

VOCAB_ALIGN = 256


def padded_vocab(cfg: ModelConfig) -> int:
    return -(-cfg.vocab_size // VOCAB_ALIGN) * VOCAB_ALIGN


# ----------------------------------------------------------------------------
# init
# ----------------------------------------------------------------------------

def _init_dense_layer(rng, cfg):
    k1, k2 = jax.random.split(rng)
    return {"ln1": L.init_norm(cfg.norm, cfg.d_model),
            "attn": L.init_attention(k1, cfg),
            "ln2": L.init_norm(cfg.norm, cfg.d_model),
            "mlp": L.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.mlp_activation)}


def _init_moe_layer(rng, cfg):
    k1, k2 = jax.random.split(rng)
    return {"ln1": L.init_norm(cfg.norm, cfg.d_model),
            "attn": L.init_attention(k1, cfg),
            "ln2": L.init_norm(cfg.norm, cfg.d_model),
            "moe": moe_lib.init_moe_block(k2, cfg)}


def _init_ssm_layer(rng, cfg):
    mixer = (S.init_mamba1 if cfg.ssm.variant == "mamba1" else S.init_mamba2)
    return {"ln": L.init_norm(cfg.norm, cfg.d_model), "mixer": mixer(rng, cfg)}


def _init_xattn_layer(rng, cfg):
    k1, k2, k3 = jax.random.split(rng, 3)
    return {"ln1": L.init_norm(cfg.norm, cfg.d_model),
            "attn": L.init_attention(k1, cfg),
            "lnx": L.init_norm(cfg.norm, cfg.d_model),
            "xattn": L.init_attention(k2, cfg),
            "ln2": L.init_norm(cfg.norm, cfg.d_model),
            "mlp": L.init_mlp(k3, cfg.d_model, cfg.d_ff, cfg.mlp_activation)}


def _stack(init_fn, rng, n, cfg):
    return jax.vmap(lambda r: init_fn(r, cfg))(jax.random.split(rng, n))


def init_params(rng, cfg: ModelConfig) -> dict:
    ks = jax.random.split(rng, 8)
    vp = padded_vocab(cfg)
    p = {"embed": L.init_embedding(ks[0], vp, cfg.d_model),
         "final_norm": L.init_norm(cfg.norm, cfg.d_model)}
    if not cfg.tie_embeddings:
        p["head"] = L.init_embedding(ks[1], vp, cfg.d_model)

    at = cfg.arch_type
    if at in ("dense", "vlm"):
        p["layers"] = _stack(_init_dense_layer, ks[2], cfg.num_layers, cfg)
    elif at == "moe":
        p["layers"] = _stack(_init_moe_layer, ks[2], cfg.num_layers, cfg)
    elif at == "ssm":
        p["layers"] = _stack(_init_ssm_layer, ks[2], cfg.num_layers, cfg)
    elif at == "hybrid":
        every = cfg.shared_attn_every
        n_group = cfg.num_layers // every
        rem = cfg.num_layers - n_group * every
        p["groups"] = jax.vmap(lambda r: _stack(_init_ssm_layer, r, every, cfg))(
            jax.random.split(ks[2], n_group))
        if rem:
            p["rem"] = _stack(_init_ssm_layer, ks[3], rem, cfg)
        k1, k2 = jax.random.split(ks[4])
        p["shared"] = {"ln1": L.init_norm(cfg.norm, cfg.d_model),
                       "attn": L.init_attention(k1, cfg),
                       "ln2": L.init_norm(cfg.norm, cfg.d_model),
                       "mlp": L.init_mlp(k2, cfg.d_model, cfg.d_ff,
                                         cfg.mlp_activation)}
    elif at == "audio":
        p["enc_layers"] = _stack(_init_dense_layer, ks[2],
                                 cfg.num_encoder_layers, cfg)
        p["enc_norm"] = L.init_norm(cfg.norm, cfg.d_model)
        p["dec_layers"] = _stack(_init_xattn_layer, ks[3], cfg.num_layers, cfg)
    else:
        raise ValueError(f"unknown arch_type {at}")
    if at == "vlm":
        p["img_proj"] = {"w": jax.random.normal(
            ks[5], (cfg.d_model, cfg.d_model), jnp.float32) / math.sqrt(cfg.d_model)}
    return p


# ----------------------------------------------------------------------------
# SAC wrappers
# ----------------------------------------------------------------------------

def _sac(fn, name: str, policy: str):
    """Wrap ``fn`` in jax.checkpoint when its module is selected by the SAC
    policy (comma-separated set, e.g. 'attn,moe')."""
    selected = set(policy.split(",")) if policy else set()
    if name in selected:
        return jax.checkpoint(fn)
    return fn


def block_remat(fn, sac: str):
    """Whole-block remat variants:
    'block'    — save only block inputs (paper SAC; collectives replayed);
    'block_sc' — like 'block' but *save collective outputs* (attn_proj_out,
                 moe_out), so backward recompute does not re-run the TP/EP
                 all-reduces (beyond-paper §Perf lever)."""
    modes = set(sac.split(",")) if sac else set()
    if "block_sc" in modes:
        policy = jax.checkpoint_policies.save_only_these_names(
            "attn_proj_out", "moe_out")
        return jax.checkpoint(fn, policy=policy)
    if "block" in modes:
        return jax.checkpoint(fn)
    return fn


# ----------------------------------------------------------------------------
# blocks
# ----------------------------------------------------------------------------

def _dense_block(lp, h, cfg, rules, sac: str, causal=True):
    cons = rules.constrain if rules else (lambda x, n: x)
    attn = _sac(lambda q, x: L.attention(q, x, cfg, constrain=cons,
                                         causal=causal), "attn", sac)
    mlp = _sac(lambda q, x: L.apply_mlp(q, x, cfg.mlp_activation, cons),
               "mlp", sac)
    with jax.named_scope("attn"):
        h = h + attn(lp["attn"], L.apply_norm(lp["ln1"], h, cfg.norm))
    with jax.named_scope("mlp"):
        h = h + mlp(lp["mlp"], L.apply_norm(lp["ln2"], h, cfg.norm))
    return cons(h, "act_btd")


def _moe_block(lp, h, cfg, rules, sac: str, mesh, placement=None):
    cons = rules.constrain if rules else (lambda x, n: x)
    ep_axis = rules.ep_axis if rules else None
    tp_axis = rules.tp_axis if rules else None
    # token/batch axes for the MoE dispatch exclude the EP axis itself
    # (tokens reshard over it inside the block)
    batch_axes = tuple(a for a in (rules.batch_axes if rules else ())
                       if a != ep_axis)
    # EP shard_map path only when the rules assign an EP axis; under
    # 'etp'/'tp'-only placements the capacity path auto-shards instead.
    mesh_eff = mesh if ep_axis else None
    attn = _sac(lambda q, x: L.attention(q, x, cfg, constrain=cons),
                "attn", sac)
    c_align = 1
    if rules is not None and rules.mesh is not None and rules.batch_axes:
        c_align = rules._axis_size(tuple(rules.batch_axes))
    tp_mesh = mesh if tp_axis else None
    moe = _sac(lambda q, x: moe_lib.sparse_moe_block(
        q, x, cfg, mesh=mesh_eff, ep_axis=ep_axis or "model",
        batch_axes=batch_axes, constrain=cons,
        c_align=c_align, tp_mesh=tp_mesh, tp_axis=tp_axis,
        placement=placement), "moe", sac)
    with jax.named_scope("attn"):
        h = h + attn(lp["attn"], L.apply_norm(lp["ln1"], h, cfg.norm))
    with jax.named_scope("moe"):
        mo, aux, z, stats = moe(lp["moe"],
                                L.apply_norm(lp["ln2"], h, cfg.norm))
        h = h + mo
    return cons(h, "act_btd"), aux, z, stats


def _ssm_block(lp, h, cfg, rules, sac: str):
    cons = rules.constrain if rules else (lambda x, n: x)
    mixer = S.mamba1_block if cfg.ssm.variant == "mamba1" else S.mamba2_block
    fn = _sac(lambda q, x: mixer(q, x, cfg), "ssm", sac)
    h = h + fn(lp["mixer"], L.apply_norm(lp["ln"], h, cfg.norm))
    return cons(h, "act_btd")


def _xattn_block(lp, h, mem, cfg, rules, sac: str):
    cons = rules.constrain if rules else (lambda x, n: x)
    attn = _sac(lambda q, x: L.attention(q, x, cfg, constrain=cons),
                "attn", sac)
    xatt = _sac(lambda q, x, m: L.attention(q, x, cfg, constrain=cons,
                                            memory=m), "attn", sac)
    mlp = _sac(lambda q, x: L.apply_mlp(q, x, cfg.mlp_activation, cons),
               "mlp", sac)
    with jax.named_scope("attn"):
        h = h + attn(lp["attn"], L.apply_norm(lp["ln1"], h, cfg.norm))
        h = h + xatt(lp["xattn"], L.apply_norm(lp["lnx"], h, cfg.norm), mem)
    with jax.named_scope("mlp"):
        h = h + mlp(lp["mlp"], L.apply_norm(lp["ln2"], h, cfg.norm))
    return cons(h, "act_btd")


def _scan_layers(stacked, h, body, sac: str):
    """lax.scan over a stacked layer pytree. body(lp, h) -> h."""
    fn = block_remat(body, sac)

    def step(carry, lp):
        return fn(lp, carry), None

    h, _ = jax.lax.scan(step, h, stacked)
    return h


def _scan_layers_aux(stacked, h, body, sac: str, num_experts: int,
                     placement=None):
    """Like _scan_layers but body(lp, h, pl) returns (h, aux, z, MoeStats)
    — aux losses and routing telemetry accumulated (summed) across layers.
    ``placement``: optional (L, E) int32 inverse placement rows scanned
    alongside the stacked params, so each layer dispatches against its own
    row (None — an empty pytree — scans through untouched)."""
    fn = block_remat(body, sac)
    rows = []      # a layer's static PoolRows, kept out of the carry

    def step(carry, xs):
        lp, pl = xs
        h, aux, z, st = carry
        h, a, zz, s = fn(lp, h, pl)
        rows.append(s.rows)
        return (h, aux + a, z + zz, st + s._replace(rows=st.rows)), None

    (h, aux, z, st), _ = jax.lax.scan(
        step, (h, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
               moe_lib.MoeStats.zero(num_experts)),
        (stacked, placement))
    n_layers = jax.tree.leaves(stacked)[0].shape[0]
    return h, aux, z, st._replace(
        rows=moe_lib.PoolRows(rows[-1].n * n_layers))


# ----------------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------------

def forward(params, batch: dict, cfg: ModelConfig, *,
            rules=None, mesh=None, sac: str = "block",
            compute_dtype=jnp.bfloat16, placement=None):
    """Returns (logits (B, S_out, V_pad), aux_losses dict).

    ``placement``: optional (L, E) int32 inverse expert-placement rows
    (global expert id -> stored position per layer; parallel/placement.py)
    when the stacked MoE expert weights live in a re-placed order."""
    cons = rules.constrain if rules else (lambda x, n: x)
    aux = {"moe_aux": jnp.zeros((), jnp.float32),
           "moe_z": jnp.zeros((), jnp.float32)}
    at = cfg.arch_type

    if at == "audio":
        enc_h = batch["frame_embeds"].astype(compute_dtype)
        enc_h = cons(enc_h, "act_btd")
        enc_h = _scan_layers(
            params["enc_layers"], enc_h,
            lambda lp, h: _dense_block(lp, h, cfg, rules, sac, causal=False),
            sac)
        mem = L.apply_norm(params["enc_norm"], enc_h, cfg.norm)
        h = L.embed(params["embed"], batch["tokens"], compute_dtype)
        h = cons(h, "act_btd")
        h = _scan_layers(
            params["dec_layers"], h,
            lambda lp, hh: _xattn_block(lp, hh, mem, cfg, rules, sac), sac)
    else:
        h = L.embed(params["embed"], batch["tokens"], compute_dtype)
        if at == "vlm":
            img = batch["image_embeds"].astype(compute_dtype)
            img = img @ params["img_proj"]["w"].astype(compute_dtype)
            h = jnp.concatenate([img, h], axis=1)
        h = cons(h, "act_btd")
        if at in ("dense", "vlm"):
            h = _scan_layers(params["layers"], h,
                             lambda lp, hh: _dense_block(lp, hh, cfg, rules, sac),
                             sac)
        elif at == "moe":
            h, a, z, st = _scan_layers_aux(
                params["layers"], h,
                lambda lp, hh, pl: _moe_block(lp, hh, cfg, rules, sac, mesh,
                                              placement=pl),
                sac, cfg.moe.num_experts, placement=placement)
            aux["moe_aux"], aux["moe_z"] = a, z
            aux["moe_stats"] = st
        elif at == "ssm":
            h = _scan_layers(params["layers"], h,
                             lambda lp, hh: _ssm_block(lp, hh, cfg, rules, sac),
                             sac)
        elif at == "hybrid":
            def group_body(gp, hh):
                hh = _scan_layers(
                    gp, hh, lambda lp, x: _ssm_block(lp, x, cfg, rules, sac),
                    sac)
                return _dense_block(params["shared"], hh, cfg, rules, sac)

            def gstep(carry, gp):
                return group_body(gp, carry), None

            h, _ = jax.lax.scan(gstep, h, params["groups"])
            if "rem" in params:
                h = _scan_layers(
                    params["rem"], h,
                    lambda lp, x: _ssm_block(lp, x, cfg, rules, sac), sac)
        else:
            raise ValueError(at)

    return _logits(params, h, cfg, cons), aux


def _logits(params, h, cfg: ModelConfig, cons=L.no_constrain):
    """The head up to the logits: final norm and unembedding."""
    with jax.named_scope("head"):
        h = L.apply_norm(params["final_norm"], h, cfg.norm)
        return cons(L.unembed(params.get("head", params["embed"]), h),
                    "logits")


# ----------------------------------------------------------------------------
# loss
# ----------------------------------------------------------------------------

def masked_ce(logits, labels, cfg: ModelConfig):
    """Masked next-token CE over padded-vocab logits. Returns (ce, ntok)."""
    with jax.named_scope("head"):
        vp = padded_vocab(cfg)
        logits = logits.astype(jnp.float32)
        if vp != cfg.vocab_size:     # mask padded vocab columns out of the lse
            pad_mask = jnp.arange(vp) >= cfg.vocab_size
            logits = jnp.where(pad_mask, -1e9, logits)
        mask = labels >= 0
        safe = jnp.maximum(labels, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        nll = jnp.where(mask, lse - ll, 0.0)
        ntok = jnp.maximum(mask.sum(), 1)
        return nll.sum() / ntok, ntok


def loss_fn(params, batch, cfg: ModelConfig, *, rules=None, mesh=None,
            sac: str = "block", compute_dtype=jnp.bfloat16, placement=None):
    """Next-token cross entropy (+ MoE aux losses). labels = -100 masked."""
    logits, aux = forward(params, batch, cfg, rules=rules, mesh=mesh,
                          sac=sac, compute_dtype=compute_dtype,
                          placement=placement)
    labels = batch["labels"]
    if cfg.arch_type == "vlm":   # prefix image positions produce no loss
        logits = logits[:, cfg.num_prefix_embeds:]
    ce, ntok = masked_ce(logits, labels, cfg)
    total = ce
    if cfg.is_moe:
        total = total + cfg.moe.router_aux_coef * aux["moe_aux"] / cfg.num_layers
        total = total + cfg.moe.router_z_coef * aux["moe_z"] / cfg.num_layers
    metrics = {"ce": ce, "moe_aux": aux["moe_aux"] / max(cfg.num_layers, 1),
               "moe_z": aux["moe_z"] / max(cfg.num_layers, 1), "ntok": ntok}
    if "moe_stats" in aux:
        st = aux["moe_stats"]
        nl = max(cfg.num_layers, 1)
        metrics["moe_counts"] = st.counts / nl        # per-layer mean -> T*K
        metrics["moe_drops"] = st.drops               # summed over layers
        # static, so a constant output: the pool rows the grouped matmul
        # covers in a layer, in the units of moe_counts
        metrics["moe_rows_computed"] = np.float32(st.rows.n / nl)
    return total, metrics


# ----------------------------------------------------------------------------
# pipeline-stage pieces (the jitted PP train path; parallel/pipeline.py)
# ----------------------------------------------------------------------------

PP_ARCH_TYPES = ("dense", "moe", "ssm")   # uniform scanned 'layers' stacks


def embed_tokens(params, tokens, cfg: ModelConfig, *,
                 compute_dtype=jnp.bfloat16):
    """Stage-0 input: token embedding, exactly as ``forward`` computes it."""
    return L.embed(params["embed"], tokens, compute_dtype)


def pipeline_stage_forward(stage_lp, h, cfg: ModelConfig, *, sac: str = ""):
    """Apply one pipeline stage's (L/pp, ...)-stacked layer slice to ``h``.

    The same block functions and scan the full ``forward`` uses, so running
    the pp stage slices back-to-back reproduces the sequential model
    bit-for-bit. Blocks run without sharding-rule constraints (the PP
    executor pins placement at stage granularity instead); MoE stages
    therefore always take the auto-shardable dense path (``c_align=1``,
    capacity or dropless per ``cfg.moe.dispatch``), never the EP shard_map
    path. Returns (h, moe_aux, moe_z, MoeStats)."""
    at = cfg.arch_type
    if at not in PP_ARCH_TYPES:
        raise ValueError(
            f"pipeline parallelism supports arch_type in {PP_ARCH_TYPES}, "
            f"not {at!r} (non-uniform layer stacks)")
    if at == "moe":
        return _scan_layers_aux(
            stage_lp, h,
            lambda lp, hh, pl: _moe_block(lp, hh, cfg, None, sac, None,
                                          placement=pl),
            sac, cfg.moe.num_experts)
    if at == "dense":
        h = _scan_layers(stage_lp, h,
                         lambda lp, hh: _dense_block(lp, hh, cfg, None, sac),
                         sac)
    else:
        h = _scan_layers(stage_lp, h,
                         lambda lp, hh: _ssm_block(lp, hh, cfg, None, sac),
                         sac)
    return (h, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
            moe_lib.MoeStats.zero(0))


def lm_head_ce(params, h, labels, cfg: ModelConfig):
    """Last-stage tail: final norm + unembed + masked CE — the same ops
    ``forward`` + ``loss_fn`` apply after the layer stack. Returns ce."""
    ce, _ = masked_ce(_logits(params, h, cfg), labels, cfg)
    return ce


# ----------------------------------------------------------------------------
# decode (serve_step)
# ----------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> dict:
    """Per-layer stacked caches (leading dim = layer)."""
    at = cfg.arch_type

    def stack(make, n):
        one = make()
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape), one)

    if at in ("dense", "vlm", "moe"):
        return {"kv": stack(lambda: L.init_kv_cache(cfg, batch, max_len, dtype),
                            cfg.num_layers)}
    if at == "ssm":
        mk = (S.init_mamba1_cache if cfg.ssm.variant == "mamba1"
              else S.init_mamba2_cache)
        return {"ssm": stack(lambda: mk(cfg, batch), cfg.num_layers)}
    if at == "hybrid":
        every = cfg.shared_attn_every
        n_group = cfg.num_layers // every
        rem = cfg.num_layers - n_group * every
        c = {"groups": stack(lambda: S.init_mamba2_cache(cfg, batch),
                             n_group * every),
             "shared_kv": stack(lambda: L.init_kv_cache(cfg, batch, max_len,
                                                        dtype), n_group)}
        if rem:
            c["rem"] = stack(lambda: S.init_mamba2_cache(cfg, batch), rem)
        return c
    if at == "audio":
        return {"kv": stack(lambda: L.init_kv_cache(cfg, batch, max_len, dtype),
                            cfg.num_layers),
                "memory": jnp.zeros((batch, max_len, cfg.d_model), dtype)}
    raise ValueError(at)


def decode_step(params, tokens, cache: dict, index, cfg: ModelConfig, *,
                rules=None, compute_dtype=jnp.bfloat16):
    """One decode step. tokens: (B, 1) int32; index: scalar position, or a
    (B,) int32 vector of per-row positions (continuous batching — each cache
    row advances independently; see repro/serve/engine.py).
    Returns (logits (B, 1, V_pad), new_cache)."""
    cons = rules.constrain if rules else (lambda x, n: x)
    at = cfg.arch_type
    h = L.embed(params["embed"], tokens, compute_dtype)
    new_cache = dict(cache)

    def attn_step(lp, hh, kv):
        with jax.named_scope("attn"):
            a, kv2 = L.decode_attention(
                lp["attn"], L.apply_norm(lp["ln1"], hh, cfg.norm), kv, index,
                cfg, constrain=cons)
            return hh + a, kv2

    if at in ("dense", "vlm", "moe"):
        def step(carry, xs):
            hh = carry
            lp, kv = xs
            hh, kv2 = attn_step(lp, hh, kv)
            with jax.named_scope("moe" if at == "moe" else "mlp"):
                x2 = L.apply_norm(lp["ln2"], hh, cfg.norm)
                if at == "moe":
                    mo, _, _, _ = moe_lib.sparse_moe_block(lp["moe"], x2, cfg,
                                                           mesh=None)
                    hh = hh + mo
                else:
                    hh = hh + L.apply_mlp(lp["mlp"], x2, cfg.mlp_activation,
                                          cons)
            return hh, kv2

        h, kv_new = jax.lax.scan(step, h, (params["layers"], cache["kv"]))
        new_cache["kv"] = kv_new
    elif at == "ssm":
        mixer_step = (S.mamba1_decode_step if cfg.ssm.variant == "mamba1"
                      else S.mamba2_decode_step)

        def step(carry, xs):
            hh = carry
            lp, c = xs
            y, c2 = mixer_step(lp["mixer"], L.apply_norm(lp["ln"], hh, cfg.norm),
                               c, cfg)
            return hh + y, c2

        h, ssm_new = jax.lax.scan(step, h, (params["layers"], cache["ssm"]))
        new_cache["ssm"] = ssm_new
    elif at == "hybrid":
        every = cfg.shared_attn_every
        n_group = params["groups"]["ln"]["scale"].shape[0]

        def mamba_step(carry, xs):
            hh = carry
            lp, c = xs
            y, c2 = S.mamba2_decode_step(lp["mixer"],
                                         L.apply_norm(lp["ln"], hh, cfg.norm),
                                         c, cfg)
            return hh + y, c2

        def group_step(carry, xs):
            hh = carry
            gp, gc, skv = xs
            hh, gc2 = jax.lax.scan(mamba_step, hh, (gp, gc))
            a, skv2 = L.decode_attention(
                params["shared"]["attn"],
                L.apply_norm(params["shared"]["ln1"], hh, cfg.norm),
                skv, index, cfg, constrain=cons)
            hh = hh + a
            hh = hh + L.apply_mlp(params["shared"]["mlp"],
                                  L.apply_norm(params["shared"]["ln2"], hh,
                                               cfg.norm),
                                  cfg.mlp_activation, cons)
            return hh, (gc2, skv2)

        gc = jax.tree.map(
            lambda a: a.reshape((n_group, every) + a.shape[1:]),
            cache["groups"])
        h, (gc2, skv2) = jax.lax.scan(group_step, h,
                                      (params["groups"], gc,
                                       cache["shared_kv"]))
        new_cache["groups"] = jax.tree.map(
            lambda a: a.reshape((-1,) + a.shape[2:]), gc2)
        new_cache["shared_kv"] = skv2
        if "rem" in params:
            h, rem2 = jax.lax.scan(mamba_step, h,
                                   (params["rem"], cache["rem"]))
            new_cache["rem"] = rem2
    elif at == "audio":
        mem = cache["memory"].astype(compute_dtype)

        def step(carry, xs):
            hh = carry
            lp, kv = xs
            hh, kv2 = attn_step(lp, hh, kv)
            x = L.apply_norm(lp["lnx"], hh, cfg.norm)
            hh = hh + L.attention(lp["xattn"], x, cfg, constrain=cons,
                                  memory=mem)
            hh = hh + L.apply_mlp(lp["mlp"],
                                  L.apply_norm(lp["ln2"], hh, cfg.norm),
                                  cfg.mlp_activation, cons)
            return hh, kv2

        h, kv_new = jax.lax.scan(step, h, (params["dec_layers"], cache["kv"]))
        new_cache["kv"] = kv_new
    else:
        raise ValueError(at)

    return _logits(params, h, cfg, cons), new_cache


# ----------------------------------------------------------------------------
# prefill into cache slots (serve admission path)
# ----------------------------------------------------------------------------

def prefill_with_cache(params, tokens, cache: dict, slots, lengths,
                       cfg: ModelConfig, *, rules=None, mesh=None,
                       compute_dtype=jnp.bfloat16):
    """Prefill right-padded prompts directly into KV-cache rows.

    tokens: (B', P) int32, right-padded; slots: (B',) int32 cache rows to
    fill; lengths: (B',) valid prompt lengths (1 <= length <= P). Causal
    masking keeps padded columns from contaminating real positions, and the
    K/V of padded (or window-expired) positions are dropped by the scatter.
    Ring (sliding-window) caches keep only the last ``window`` positions,
    laid out at ``position % window`` — exactly the layout ``decode_step``
    expects to find.

    Returns (last_logits (B', V_pad) — the logits at position length-1 of
    each row, i.e. the distribution of the first generated token — and the
    updated cache). Attention-KV archs only (dense, moe); recurrent-state
    archs prefill by stepping ``decode_step`` over the prompt instead.
    """
    at = cfg.arch_type
    if at not in ("dense", "moe"):
        raise NotImplementedError(
            f"prefill_with_cache supports attention-KV archs, not {at!r}")
    cons = rules.constrain if rules else (lambda x, n: x)
    h = L.embed(params["embed"], tokens, compute_dtype)
    h = cons(h, "act_btd")

    def step(carry, lp):
        hh = carry
        with jax.named_scope("attn"):
            a, kv = L.attention(lp["attn"],
                                L.apply_norm(lp["ln1"], hh, cfg.norm),
                                cfg, constrain=cons, return_kv=True)
            hh = hh + a
        with jax.named_scope("moe" if at == "moe" else "mlp"):
            x2 = L.apply_norm(lp["ln2"], hh, cfg.norm)
            if at == "moe":
                # single-host capacity path, matching decode_step; ``mesh`` is
                # accepted for signature parity but EP dispatch is not wired
                # into serving yet (multi-host serve is a ROADMAP item)
                mo, _, _, _ = moe_lib.sparse_moe_block(lp["moe"], x2, cfg,
                                                       mesh=None)
                hh = hh + mo
            else:
                hh = hh + L.apply_mlp(lp["mlp"], x2, cfg.mlp_activation, cons)
        return hh, kv

    h, (ks, vs) = jax.lax.scan(step, h, params["layers"])  # (L, B', P, ...)

    ck, cv = cache["kv"]["k"], cache["kv"]["v"]            # (L, B, W, n, hd)
    W = ck.shape[2]
    P = tokens.shape[1]
    lengths = jnp.asarray(lengths, jnp.int32)
    slots = jnp.asarray(slots, jnp.int32)
    pos = jnp.arange(P)[None, :]                           # (1, P)
    keep = (pos < lengths[:, None]) & (pos >= lengths[:, None] - W)
    if cfg.sliding_window > 0:
        dest = jnp.where(keep, pos % W, W)                 # W => dropped
    else:
        dest = jnp.where(keep & (pos < W), pos, W)
    rows = jnp.broadcast_to(slots[:, None], dest.shape)
    new_cache = dict(cache)
    new_cache["kv"] = {
        "k": ck.at[:, rows, dest].set(ks.astype(ck.dtype), mode="drop"),
        "v": cv.at[:, rows, dest].set(vs.astype(cv.dtype), mode="drop"),
    }

    logits = _logits(params, h, cfg, cons)                 # (B', P, V_pad)
    last = jnp.take_along_axis(logits, (lengths - 1)[:, None, None], axis=1)
    return last[:, 0], new_cache

"""Plain float32 reference of the Mula family (paper Table 1: an OLMoE-style
decoder), for training: loss, gradients and AdamW, in straightforward
``jax.numpy`` at float32 with every matrix product at ``HIGHEST``
precision. It imports nothing of the program and takes its weights from
``bench/weights.py``, drawn in this module's ``layout``; its FLOP counts
(``flops_per_token``, ``expert_gemm_flops``) feed ``mfu``, ``step_mfu``
and ``expert_gemm_roofline``.

The model, as the configuration file states it:

- token embedding, then per layer ``x + attn(rmsnorm(x))`` and
  ``x + ffn(rmsnorm(x))``, a final RMSNorm (eps 1e-6, learned scale) and an
  untied head over the vocabulary's ``vocab_size`` ids;
- attention: full causal softmax, ``num_heads`` heads of ``head_dim``,
  rotary embedding on the two halves of each head (theta ``rope_theta``);
- ``arch_type`` moe: softmax router over ``num_experts``, the top
  ``experts_per_token`` probabilities weight the experts' SwiGLU outputs
  (not renormalised), every routed token computed (no capacity drop); a
  load-balance loss ``E * sum_e f_e p_e`` (``f`` the share of routed pairs,
  ``p`` the mean probability) and a z-loss ``mean(logsumexp(logits)**2)``,
  each summed over layers, divided by the depth and weighted by
  ``router_aux_coef`` / ``router_z_coef``. With ``groups`` > 1 the
  load-balance loss is taken over each group of sequences (one group per
  chip under expert parallelism, as the program's EP ranks do) and
  averaged;
- ``arch_type`` dense: a SwiGLU MLP of width ``d_ff``;
- loss: mean next-token cross entropy plus the router losses;
- AdamW: linear warmup then cosine decay, global-norm clipping only from
  ``warmup_steps`` on, bias-corrected moments, decoupled weight decay on
  every leaf.

Every expert is computed for every token and weighted by its combine
weight (zero where not routed), one expert at a time. Attention runs one
head at a time and the head one block of rows at a time, so that the
reference fits one chip at the cell's own size.

``quant="fp8"`` is the control: the same reference with every matrix
product in float8, as float8 training computes it: the forward product's
operands rounded to e4m3 and the backward products' incoming gradient to
e5m2, each tensor with its own scale. ``drop_half`` and
``local_experts`` plant two faults the correctness check must catch: half
of the batch left out (the mean over the rest), and each group's tokens
reaching only the experts on its own chip (the exchange between chips
left out).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 1024
VOCAB_ALIGN = 256
# read by this reference alone: the program's norms fix eps at 1e-6 and
# have no field for it
REFERENCE_KEYS = ("norm_eps",)


def padded_vocab(c: dict) -> int:
    return -(-c["vocab_size"] // VOCAB_ALIGN) * VOCAB_ALIGN


def layout(c: dict) -> list:
    """The training program's parameter tree (its checkpoint format) as
    [(path, shape, scale)] in the order of ``jax.tree.leaves`` of the
    nested tree; scale None means ones. ``embed``/``head`` tables padded
    to a multiple of 256 rows, ``final_norm``, and one stacked ``layers``
    group whose leading axis is the layer."""
    d, L, vp = c["d_model"], c["num_layers"], padded_vocab(c)
    q = c["num_heads"] * c["head_dim"]
    kv = c["num_kv_heads"] * c["head_dim"]
    s = 1.0 / math.sqrt(d)
    out = [
        (("embed", "table"), (vp, d), 0.02),
        (("final_norm", "scale"), (d,), None),
        (("head", "table"), (vp, d), 0.02),
        (("layers", "attn", "wk"), (L, d, kv), s),
        (("layers", "attn", "wo"), (L, q, d), s),
        (("layers", "attn", "wq"), (L, d, q), s),
        (("layers", "attn", "wv"), (L, d, kv), s),
        (("layers", "ln1", "scale"), (L, d), None),
        (("layers", "ln2", "scale"), (L, d), None),
    ]
    if c["arch_type"] == "moe":
        e, f = c["num_experts"], c["d_ff_expert"]
        out += [
            (("layers", "moe", "down"), (L, e, f, d), 1.0 / math.sqrt(f)),
            (("layers", "moe", "gate"), (L, e, d, f), s),
            (("layers", "moe", "router"), (L, d, e), s),
            (("layers", "moe", "up"), (L, e, d, f), s),
        ]
    else:
        f = c["d_ff"]
        out += [
            (("layers", "mlp", "down"), (L, f, d), 1.0 / math.sqrt(f)),
            (("layers", "mlp", "gate"), (L, d, f), s),
            (("layers", "mlp", "up"), (L, d, f), s),
        ]
    return out


def flops_per_token(c: dict, seq_len: int) -> dict:
    """Training FLOPs per token, by part: 6 x the matrix-product parameters
    a token uses (forward 2, backward 4), plus causal attention scores
    (6 x heads x head_dim x seq_len: the QK^T and PV products over on
    average seq_len / 2 keys, forward and backward). Embedding lookups,
    elementwise work and what remat recomputes are not counted. The head
    counts the vocabulary's ``vocab_size`` rows, not the padded table."""
    d, L = c["d_model"], c["num_layers"]
    q = c["num_heads"] * c["head_dim"]
    kv = c["num_kv_heads"] * c["head_dim"]
    parts = {
        "head": 6 * d * c["vocab_size"],
        "attention_projections": 6 * L * d * (2 * q + 2 * kv),
        "attention_scores": 6 * L * q * seq_len,
    }
    if c["arch_type"] == "moe":
        parts["experts"] = 6 * L * c["experts_per_token"] * 3 * d * \
            c["d_ff_expert"]
        parts["router"] = 6 * L * d * c["num_experts"]
    else:
        parts["mlp"] = 6 * L * 3 * d * c["d_ff"]
    return parts


def expert_gemm_flops(c: dict, tokens: int) -> int:
    """FLOPs of the routed rows' expert GEMMs in a training step: T*K rows
    through three d x f matrices, forward and backward."""
    if c["arch_type"] != "moe":
        return 0
    return (tokens * c["experts_per_token"] * 3 * 2 * c["d_model"]
            * c["d_ff_expert"] * 3 * c["num_layers"])


def _round(x, dtype):
    """x rounded to a float8 type, scaled so that its largest magnitude
    lands on the type's largest finite value."""
    top = float(jnp.finfo(dtype).max)
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def _matmul(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


@jax.custom_vjp
def _mm8(a, b):
    return _matmul(_round(a, jnp.float8_e4m3fn), _round(b, jnp.float8_e4m3fn))


def _mm8_fwd(a, b):
    qa, qb = _round(a, jnp.float8_e4m3fn), _round(b, jnp.float8_e4m3fn)
    return _matmul(qa, qb), (qa, qb)


def _mm8_bwd(res, g):
    _, vjp = jax.vjp(_matmul, *res)
    return vjp(_round(g, jnp.float8_e5m2))


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def _mm(a, b, quant):
    if quant == "fp8":
        return _mm8(a, b)
    return _matmul(a, b)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, S, H, hd); rotate the two halves of each head."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(p, x, c, quant):
    B, S, _ = x.shape
    nh, nkv, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    q = _rope(_mm(x, p["wq"], quant).reshape(B, S, nh, hd), c["rope_theta"])
    k = _rope(_mm(x, p["wk"], quant).reshape(B, S, nkv, hd), c["rope_theta"])
    v = _mm(x, p["wv"], quant).reshape(B, S, nkv, hd)
    rep = nh // nkv
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def head(args):
        qh, kh, vh = args                                  # (B, S, hd)
        s = _mm(qh, kh.swapaxes(1, 2), quant) / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        return _mm(jax.nn.softmax(s, axis=-1), vh, quant)

    o = jax.lax.map(head, tuple(t.transpose(2, 0, 1, 3) for t in (q, k, v)))
    return _mm(o.transpose(1, 2, 0, 3).reshape(B, S, nh * hd), p["wo"], quant)


def _moe(p, x, c, quant, local_experts):
    """x: (G, T, d) tokens in groups. Returns (out, aux, z)."""
    E, K = c["num_experts"], c["experts_per_token"]
    G = x.shape[0]
    logits = _mm(x, p["router"], quant)                    # (G, T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, K)
    hot = jax.nn.one_hot(topi, E, dtype=jnp.float32)       # (G, T, K, E)
    comb = jnp.einsum("gtke,gtk->gte", hot, topw)
    if local_experts:
        owner = jnp.arange(E) // (E // G)
        comb = comb * (owner[None, :] == jnp.arange(G)[:, None])[:, None]
    f = hot.sum((1, 2)) / (x.shape[1] * K)                 # (G, E)
    aux = jnp.mean(E * jnp.sum(f * probs.mean(1), -1))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1)))

    @jax.checkpoint
    def expert(gate, up, down, cw):
        h = jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant)
        return cw[..., None] * _mm(h, down, quant)

    def body(out, xs):
        return out + expert(*xs), None

    out, _ = jax.lax.scan(body, jnp.zeros_like(x),
                          (p["gate"], p["up"], p["down"],
                           jnp.moveaxis(comb, -1, 0)))
    return out, aux, z


def _mlp(p, x, quant):
    h = jax.nn.silu(_mm(x, p["gate"], quant)) * _mm(x, p["up"], quant)
    return _mm(h, p["down"], quant)


def _cross_entropy(h, head, labels, V, quant):
    """Mean next-token cross entropy over the first V rows of the head,
    one block of rows at a time. h: (G, T, d); labels: (G, T)."""
    G, T, d = h.shape
    blk = min(ROW_BLOCK, T)
    nb = T // blk
    hb = h.reshape(G, nb, blk, d).swapaxes(0, 1)
    lb = labels.reshape(G, nb, blk).swapaxes(0, 1)
    w = head[:V].T

    @jax.checkpoint
    def block(tot, xs):
        hh, ll = xs
        logits = _mm(hh, w, quant)                         # (G, blk, V)
        lse = jax.nn.logsumexp(logits, -1)
        ll = jnp.take_along_axis(logits, ll[..., None], -1)[..., 0]
        return tot + jnp.sum(lse - ll), None

    tot, _ = jax.lax.scan(block, jnp.zeros((), jnp.float32), (hb, lb))
    return tot / (G * T)


def loss(w, tokens, labels, c, *, groups=1, quant=None,
         local_experts=False, constrain=None):
    """Scalar training loss. tokens, labels: (B, S) int32, B divisible by
    ``groups``; w: the float32 weight tree."""
    B, S = tokens.shape
    d, L, eps = c["d_model"], c["num_layers"], c["norm_eps"]
    cons = constrain or (lambda t: t)
    x = cons(w["embed"]["table"][tokens])                  # (B, S, d)
    aux = z = 0.0
    for i in range(L):
        lp = jax.tree.map(lambda t: t[i], w["layers"])

        @jax.checkpoint
        def layer(lp, x):
            x = x + _attention(lp["attn"],
                               _rmsnorm(x, lp["ln1"]["scale"], eps), c,
                               quant)
            hn = _rmsnorm(x, lp["ln2"]["scale"], eps)
            if c["arch_type"] == "moe":
                g = hn.reshape(groups, B // groups * S, d)
                out, a, zz = _moe(lp["moe"], g, c, quant, local_experts)
                return cons(x + out.reshape(B, S, d)), a, zz
            return cons(x + _mlp(lp["mlp"], hn, quant)), 0.0, 0.0

        x, a, zz = layer(lp, x)
        aux, z = aux + a, z + zz
    h = _rmsnorm(x, w["final_norm"]["scale"], eps)
    G = groups
    ce = _cross_entropy(h.reshape(G, B // G * S, d), w["head"]["table"],
                        labels.reshape(G, B // G * S), c["vocab_size"], quant)
    if c["arch_type"] == "moe":
        ce = ce + (c["router_aux_coef"] * aux + c["router_z_coef"] * z) / L
    return ce


def learning_rate(c, t):
    t = jnp.asarray(t, jnp.float32)
    warm, total = c["warmup_steps"], c["total_steps"]
    prog = jnp.clip((t - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = c["lr_min"] + 0.5 * (c["lr_peak"] - c["lr_min"]) * (
        1 + jnp.cos(jnp.pi * prog))
    return jnp.where(t < warm, c["lr_peak"] * t / max(warm, 1), cos)


def train_step(c, w, m, v, t, tokens, labels, *, groups=1, quant=None,
               drop_half=False, local_experts=False, constrain=None):
    """One AdamW step from step index ``t`` (0-based). Returns
    (loss, per-leaf gradient norms in tree order, w, m, v)."""
    if drop_half:
        half = tokens.shape[0] // 2
        tokens, labels = tokens[:half], labels[:half]
        groups = math.gcd(half, groups)
    val, g = jax.value_and_grad(loss)(
        w, tokens, labels, c, groups=groups, quant=quant,
        local_experts=local_experts, constrain=constrain)
    norms = jnp.stack([jnp.sqrt(jnp.sum(x * x)) for x in jax.tree.leaves(g)])
    gnorm = jnp.sqrt(jnp.sum(norms ** 2))
    scale = jnp.where(gnorm > c["grad_clip"],
                      c["grad_clip"] / (gnorm + 1e-12), 1.0)
    if c["clip_after_warmup_only"]:
        scale = jnp.where(t >= c["warmup_steps"], scale, 1.0)
    b1, b2 = c["beta1"], c["beta2"]
    tt = jnp.asarray(t + 1, jnp.float32)
    lr = learning_rate(c, t)

    def upd(x, gx, mx, vx):
        gx = gx * scale
        mx = b1 * mx + (1 - b1) * gx
        vx = b2 * vx + (1 - b2) * gx * gx
        step = (mx / (1 - b1 ** tt)) / (jnp.sqrt(vx / (1 - b2 ** tt))
                                        + c["eps"])
        return x - lr * (step + c["weight_decay"] * x), mx, vx

    out = jax.tree.map(upd, w, g, m, v)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return val, norms, pick(0), pick(1), pick(2)


def make_step(c, *, groups=1, mesh=None, quant=None, drop_half=False,
              local_experts=False):
    """The jitted reference step. With ``mesh`` (one axis 'g' over the
    cell's chips) the batch is split over the chips by sequence and the
    weights and moments are replicated."""
    constrain = None
    kw = {}
    if mesh is not None:
        rep = NamedSharding(mesh, P())
        rows = NamedSharding(mesh, P("g"))
        constrain = lambda t: jax.lax.with_sharding_constraint(t, rows)
        kw = dict(in_shardings=(rep, rep, rep, rep, rows, rows),
                  out_shardings=(rep, rep, rep, rep, rep))

    def step(w, m, v, t, tokens, labels):
        return train_step(c, w, m, v, t, tokens, labels, groups=groups,
                          quant=quant, drop_half=drop_half,
                          local_experts=local_experts, constrain=constrain)

    return jax.jit(step, donate_argnums=(0, 1, 2), **kw)

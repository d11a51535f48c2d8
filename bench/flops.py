"""Model FLOPs counted from a configuration's shapes.

Training FLOPs per token are 6 x the matrix-product parameters a token
uses (forward 2, backward 4), plus causal attention scores
(6 x heads x head_dim x seq_len: the QK^T and PV products over on average
seq_len / 2 keys, forward and backward). Embedding lookups, elementwise
work and what remat recomputes are not counted. The head counts the
vocabulary's ``vocab_size`` rows, not the padded table.
"""
from __future__ import annotations


def per_token(c: dict, seq_len: int) -> dict:
    """Training FLOPs per token, by part."""
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


def per_step(c: dict, seq_len: int, tokens: int) -> dict:
    """Training FLOPs of a step of ``tokens`` tokens, by part and total."""
    parts = {k: v * tokens for k, v in per_token(c, seq_len).items()}
    parts["total"] = sum(parts.values())
    return parts


def expert_gemm(c: dict, tokens: int) -> int:
    """FLOPs of the routed rows' expert GEMMs in a training step: T*K rows
    through three d x f matrices, forward and backward."""
    if c["arch_type"] != "moe":
        return 0
    return (tokens * c["experts_per_token"] * 3 * 2 * c["d_model"]
            * c["d_ff_expert"] * 3 * c["num_layers"])

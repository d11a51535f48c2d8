"""Model FLOPs of a training step, as the configuration's plain reference
counts them from its shapes (``flops_per_token`` and ``expert_gemm_flops``
of ``bench/configs/<reference>.py``). The harness and the hand-figure tests
ask this module for both counts, so neither needs to know which reference
a configuration names."""
from __future__ import annotations

from bench import configs


def per_step(c: dict, seq_len: int, tokens: int) -> dict:
    """Training FLOPs of a step of ``tokens`` tokens, by part and total."""
    parts = {k: v * tokens for k, v in
             configs.reference(c).flops_per_token(c, seq_len).items()}
    parts["total"] = sum(parts.values())
    return parts


def expert_gemm(c: dict, tokens: int) -> int:
    """FLOPs of the routed rows' expert GEMMs in a training step."""
    return configs.reference(c).expert_gemm_flops(c, tokens)

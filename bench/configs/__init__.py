"""Model configurations (``<name>.json``, the sizes as run) and their plain
references (``<reference>.py``, named by each configuration's
``reference`` key).

A reference module owns everything the harness must know of its
architecture, so that a new architecture comes as new files alone:

- ``make_step(c, *, groups, mesh, quant, drop_half, local_experts)``: the
  jitted float32 training step, with the control (``quant="fp8"``) and the
  faults the correctness check must catch (half of the batch left out,
  each group's tokens reaching only its own chip's experts);
- ``layout(c)``: the program's parameter tree as ``[(path, shape,
  scale)]`` in the order of ``jax.tree.leaves``, from which
  ``bench/weights.py`` draws the weights;
- ``flops_per_token(c, seq_len)``: training FLOPs per token, by part;
- ``expert_gemm_flops(c, tokens)``: FLOPs of a step's routed expert GEMMs
  (0 where there are none);
- ``REFERENCE_KEYS`` (optional): keys of the configuration that the
  reference reads and the program has no field for.
"""
import importlib

EXPORTS = ("make_step", "layout", "flops_per_token", "expert_gemm_flops")


def reference(c: dict):
    """The plain reference module that configuration ``c`` names."""
    return importlib.import_module(f"bench.configs.{c['reference']}")

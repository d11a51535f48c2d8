"""A configuration reaches the program and the weights through its own
files: its keys by name, its weight layout and FLOP counts from its plain
reference module. Golden copies of the mapping and the layout that were
written by hand for the Mula shapes hold the generic path to the same
values, and a stand-in architecture with a parameter tree Mula lacks runs
through the harness's files unchanged."""
import math
import os
import re
import sys
import types

import jax
import pytest

from bench import flops, harness, weights
from bench.configs import mula

CONFIGS = ["mula-7b-a1b", "mula-1b"]
CELL = "mula-7b-a1b.train.zipf-2k"
SMALL = {"d_model": 64, "num_heads": 2, "num_kv_heads": 2, "head_dim": 32,
         "num_experts": 4, "experts_per_token": 2, "d_ff_expert": 16,
         "vocab_size": 300}


def _seed_model_config(c):
    """The hand mapping the harness used for the Mula files."""
    from repro.configs.base import ModelConfig, MoEConfig
    moe = None
    if c["arch_type"] == "moe":
        moe = MoEConfig(num_experts=c["num_experts"],
                        experts_per_token=c["experts_per_token"],
                        d_ff_expert=c["d_ff_expert"],
                        router_aux_coef=c["router_aux_coef"],
                        router_z_coef=c["router_z_coef"],
                        moe_impl="fsmoe")
    return ModelConfig(
        name=c["name"], arch_type=c["arch_type"], num_layers=c["num_layers"],
        d_model=c["d_model"], num_heads=c["num_heads"],
        num_kv_heads=c["num_kv_heads"], head_dim=c["head_dim"],
        d_ff=c["d_ff"], vocab_size=c["vocab_size"], moe=moe,
        rope_theta=c["rope_theta"], norm=c["norm"],
        tie_embeddings=c["tie_embeddings"])


def _seed_train_config(c, seq_len, global_batch):
    from repro.configs.base import TrainConfig
    return TrainConfig(
        seq_len=seq_len, global_batch=global_batch, lr_peak=c["lr_peak"],
        lr_min=c["lr_min"], warmup_steps=c["warmup_steps"],
        total_steps=c["total_steps"], weight_decay=c["weight_decay"],
        beta1=c["beta1"], beta2=c["beta2"], eps=c["eps"],
        grad_clip=c["grad_clip"],
        clip_after_warmup_only=c["clip_after_warmup_only"],
        grad_reduce_dtype=c["grad_reduce_dtype"],
        param_dtype=c["param_dtype"], compute_dtype=c["compute_dtype"])


def _seed_layout(c):
    """The Mula weight layout as the harness wrote it by hand."""
    d, L = c["d_model"], c["num_layers"]
    vp = -(-c["vocab_size"] // 256) * 256
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


@pytest.mark.parametrize("config", CONFIGS)
def test_configuration_reaches_the_program_as_by_hand(config):
    from bench.system import model_config, train_config
    c = harness.read_json("configs", f"{config}.json")
    assert model_config(c) == _seed_model_config(c)
    assert train_config(c, 2048, 4) == _seed_train_config(c, 2048, 4)


@pytest.mark.parametrize("config", CONFIGS)
def test_weight_layout_is_the_hand_written_one(config):
    c = harness.read_json("configs", f"{config}.json")
    assert weights.layout(c) == _seed_layout(c)


def test_unknown_key_stops_the_program_with_its_name():
    from bench.system import Program
    cell = harness.load_cell(CELL, {"config": dict(SMALL, kv_lora_rank=512)})
    with pytest.raises(KeyError, match="kv_lora_rank"):
        Program(cell.c, cell.spec, 16, 2)


def test_master_other_than_float32_stops_the_program():
    from bench.system import Program
    cell = harness.load_cell(CELL, {"config": dict(SMALL,
                                                   master_dtype="bfloat16")})
    with pytest.raises(ValueError, match="master_dtype"):
        Program(cell.c, cell.spec, 16, 2)


def _standin_layout(c):
    """Mula's layout with a shared expert in each MoE layer, at its sorted
    place in the tree (after ``router``, before ``up``)."""
    d, L = c["d_model"], c["num_layers"]
    fs = c["d_ff_expert"] * c["num_shared_experts"]
    s = 1.0 / math.sqrt(d)
    out = mula.layout(c)
    i = [p for p, _, _ in out].index(("layers", "moe", "up"))
    return out[:i] + [
        (("layers", "moe", "shared", "down"), (L, fs, d), 1.0 / math.sqrt(fs)),
        (("layers", "moe", "shared", "gate"), (L, d, fs), s),
        (("layers", "moe", "shared", "up"), (L, d, fs), s),
    ] + out[i:]


def _standin_flops(c, seq_len):
    parts = mula.flops_per_token(c, seq_len)
    parts["shared_experts"] = (6 * c["num_layers"] * 3 * c["d_model"]
                               * c["d_ff_expert"] * c["num_shared_experts"])
    return parts


@pytest.fixture
def standin(monkeypatch):
    """A reference module of a new architecture, registered under
    ``bench.configs`` as a new file would be, and a configuration that
    names it."""
    mod = types.ModuleType("bench.configs.standin_shared")
    mod.make_step = mula.make_step
    mod.layout = _standin_layout
    mod.flops_per_token = _standin_flops
    mod.expert_gemm_flops = mula.expert_gemm_flops
    mod.REFERENCE_KEYS = mula.REFERENCE_KEYS
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    cell = harness.load_cell(CELL, {"config": dict(
        SMALL, reference="standin_shared", num_shared_experts=1)})
    return cell


def test_a_new_architecture_needs_no_edit_of_the_harness(standin):
    from bench.system import Program
    c = standin.c
    prog = Program(c, standin.spec, 16, 2)        # checks the layout
    assert prog.cfg.moe.num_shared_experts == 1
    with pytest.raises(RuntimeError, match="weight layout"):
        Program(dict(c, reference="mula"), standin.spec, 16, 2)
    tree = jax.eval_shape(lambda: weights.make(c, weights.seed_words(3)))
    shared = tree["layers"]["moe"]["shared"]
    assert {k: v.shape for k, v in shared.items()} == {
        "down": (1, 16, 64), "gate": (1, 64, 16), "up": (1, 64, 16)}
    step = flops.per_step(c, 16, 32)
    assert step["shared_experts"] == 32 * 6 * 3 * 64 * 16
    assert step["total"] == 32 * sum(_standin_flops(c, 16).values())
    assert flops.expert_gemm(c, 32) == mula.expert_gemm_flops(c, 32)


@pytest.mark.parametrize("module", ["system", "weights", "flops"])
def test_harness_files_name_no_architecture(module):
    with open(os.path.join(harness.BENCH, f"{module}.py")) as f:
        words = set(re.findall(r"[A-Za-z0-9_]+", f.read()))
    assert not words & {"wq", "wk", "wv", "wo", "d_ff_expert", "num_experts",
                        "head_dim", "shared"}

"""Every configuration, cell, traffic mix and metric of BENCHMARK.json
parses and names only things that exist, and the benchmark's weight layout
is the program's parameter tree."""
import importlib
import json
import os
import re

import jax
import pytest

from bench import check, configs, harness, weights

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


B = _bench()
WORKLOADS = [w["name"] for w in B["workloads"]]
CONFIGS = [c["name"] for c in B["configs"]]
METRICS = [m["name"] for m in B["end_to_end"] + B["per_layer"]]


def test_manifest_names_and_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "bench/run.py"]
    for p in B["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    names = WORKLOADS + CONFIGS + METRICS
    assert all(NAME.match(n) for n in names)
    assert len(set(WORKLOADS)) == len(WORKLOADS)
    assert len(set(CONFIGS)) == len(CONFIGS)
    assert len(set(METRICS)) == len(METRICS)
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in B["workloads"]) <= max(
        1, len(WORKLOADS) // 2)
    e2e = {m["name"] for m in B["end_to_end"]}
    assert "setup_s" in e2e
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", WORKLOADS)) <= set(WORKLOADS)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_files_agree_with_manifest(workload):
    w = next(x for x in B["workloads"] if x["name"] == workload)
    cell = harness.load_cell(workload)
    assert cell.spec["config"] == w["config"]
    assert cell.spec["traffic"] == w["traffic"]
    assert cell.chips == w["chips"]
    assert cell.batch % cell.chips == 0
    conf = next(x for x in B["configs"] if x["name"] == w["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        assert json.load(f) == cell.c
    for k in conf["reduced"]:
        assert k in cell.c
    importlib.import_module(f"bench.laws.{cell.mix['law']}")
    ref = cell.reference()
    assert all(callable(getattr(ref, n, None)) for n in configs.EXPORTS)
    lim = cell.spec["limits"]
    assert lim and set(lim) <= set(check.NAMES)
    assert all(0 < v < 1 for v in lim.values())
    e2e, layer = harness.metric_specs(workload)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert layer


@pytest.mark.parametrize("metric", METRICS)
def test_every_metric_has_a_reader(metric):
    mod = importlib.import_module(f"bench.metrics.{metric}")
    assert callable(mod.read)


@pytest.mark.parametrize("config", CONFIGS)
def test_weight_layout_is_the_program_tree(config):
    from repro.models.model import init_params
    from bench.system import model_config
    c = harness.read_json("configs", f"{config}.json")
    got = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                             model_config(c)))
    want = weights.nest(c, [jax.ShapeDtypeStruct(s, "float32")
                            for _, s, _ in weights.layout(c)])
    assert jax.tree.map(lambda x: x.shape, got) == \
        jax.tree.map(lambda x: x.shape, want)


def test_peaks_are_keyed_by_device_kind():
    assert harness.peak_table("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peak_table("cpu")


def test_weights_one_leaf_equals_the_whole():
    c = dict(harness.read_json("configs", "mula-7b-a1b.json"), d_model=64,
             num_heads=2, num_kv_heads=2, head_dim=32, num_experts=4,
             d_ff_expert=16, vocab_size=300)
    words = weights.seed_words(2 ** 31 + 7)
    leaves = jax.tree.leaves(weights.make(c, words))
    for i in (0, 3, len(leaves) - 1):
        assert (weights.leaf(c, words, i) == leaves[i]).all()
    other = jax.tree.leaves(weights.make(c, weights.seed_words(7)))
    assert not (other[0] == leaves[0]).all()

"""The benchmark's FLOP counts against the figures worked out by hand for
the Mula cells."""
import pytest

from bench import flops, harness


def _tflop(x):
    return round(x / 1e12, 3)


def test_mula_7b_a1b_cell_step_flops():
    cell = harness.load_cell("mula-7b-a1b.train.zipf-2k")
    assert cell.tokens == 8192
    p = flops.per_step(cell.c, cell.seq_len, cell.tokens)
    assert {k: _tflop(v) for k, v in p.items()} == {
        "head": 5.064, "experts": 2.474, "attention_projections": 0.825,
        "attention_scores": 0.206, "router": 0.006, "total": 8.575}
    assert _tflop(flops.expert_gemm(cell.c, cell.tokens)) == 2.474
    assert flops.expert_gemm(cell.c, cell.tokens) == p["experts"]


def test_ep_cell_counts_every_chips_tokens():
    one = harness.load_cell("mula-7b-a1b.train.zipf-2k")
    ep = harness.load_cell("mula-7b-a1b.ep4.train.zipf-2k")
    assert ep.tokens == 3 * one.tokens == 24576
    assert flops.per_step(ep.c, ep.seq_len, ep.tokens)["total"] == \
        3 * flops.per_step(one.c, one.seq_len, one.tokens)["total"]


def test_mula_1b_layer_and_head():
    cell = harness.load_cell("mula-1b.train.zipf-2k")
    one = dict(cell.c, num_layers=1)
    two = dict(cell.c, num_layers=2)
    per_layer = (flops.per_step(two, 2048, 8192)["total"]
                 - flops.per_step(one, 2048, 8192)["total"])
    assert _tflop(per_layer) == pytest.approx(3.505, abs=1e-3)
    assert flops.expert_gemm(cell.c, 8192) == 0
    assert "experts" not in flops.per_step(cell.c, 2048, 8192)

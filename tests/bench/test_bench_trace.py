"""The trace reduction: interval arithmetic on hand-made intervals, and
the busy, idle, kernel and collective sums of a small trace recorded on a
TPU v5e and committed under bench/testdata."""
import json
import os

import numpy as np
import pytest

from bench import trace as T


def _synthetic():
    tr = T.Trace()
    tr.spans = [("bench.window", 0, 100), ("bench.input", 0, 12),
                ("bench.wait", 60, 100)]
    tr.ops = {
        "/device:TPU:0": [("fusion.1", 10, 30), ("_gmm_kernel", 25, 40),
                          ("all-gather-start", 50, 70),
                          ("fusion.2", 65, 80), ("copy", 95, 120)],
        "/device:TPU:1": [("fusion.1", 0, 50), ("all-reduce.3", 40, 90)],
    }
    return tr


def test_interval_arithmetic():
    assert T.merge([(5, 7), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 7)]
    assert T.length([(0, 3), (2, 4), (5, 7)]) == 6
    assert T.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert T.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert T.gaps([(10, 20), (30, 40)], 0, 50) == [(0, 10), (20, 30),
                                                   (40, 50)]


def test_synthetic_trace_sums():
    tr = _synthetic()
    # device 0: busy 10-40, 50-80, 95-100 of the 0-100 window
    assert T.length(T.busy(tr, "/device:TPU:0")) == 65
    assert T.length(T.busy(tr, "/device:TPU:1")) == 90
    assert T.op_time(tr, "/device:TPU:0", lambda n: "gmm" in n) == 15
    # all-gather 50-70 overlaps fusion.2 from 65: 15 exposed
    assert T.exposed_collective(tr, "/device:TPU:0") == 15
    # all-reduce 40-90 overlaps fusion.1 until 50: 40 exposed
    assert T.exposed_collective(tr, "/device:TPU:1") == 40
    gaps = T.idle_gaps(tr)
    assert gaps[0] == ["bench.wait", 15e-9]      # 80-95
    assert sorted(g[1] for g in gaps) == [10e-9, 10e-9, 15e-9]
    assert gaps[1:] == [["bench.input", 10e-9], ["host.other", 10e-9]]
    top = T.top_ops(tr)
    # self time: fusion.1 less the overlapping ops, (15 + 40) / 2 devices
    assert top[0] == ["fusion.1", 27.5e-9]


DATA = os.path.join(os.path.dirname(T.__file__), "testdata")
RECORDED = ["cell1", "ep4"]


def _recorded(name):
    with open(os.path.join(DATA, f"{name}.kernels.json")) as f:
        kernels = json.load(f)
    return T.load(os.path.join(DATA, f"{name}.xplane.pb"), kernels)


def _timeline(intervals, lo, hi, ns=100):
    """A boolean timeline of ``ns`` bins: a second, independent way to
    measure a union of intervals."""
    line = np.zeros((hi - lo) // ns + 1, bool)
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            line[(a - lo) // ns:(b - lo) // ns] = True
    return line


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_trace_against_a_timeline(name):
    """Busy, idle, kernel and collective sums of a trace recorded on a TPU
    v5e, against boolean timelines of the same events."""
    tr = _recorded(name)
    lo, hi = tr.window()
    assert tr.ops and all(d.startswith(T.DEVICE_PREFIX) for d in tr.ops)
    for dev, ops in tr.ops.items():
        busy = T.length(T.busy(tr, dev))
        line = _timeline([(a, b) for _, a, b in ops], lo, hi)
        assert busy == pytest.approx(line.sum() * 100, rel=5e-3)
        assert 0 < busy < hi - lo
        gemm = T.op_time(tr, dev, lambda n: n in ("_gmm_kernel",
                                                  "_tgmm_kernel"))
        assert gemm > 0
        assert gemm == pytest.approx(_timeline(
            [(a, b) for n, a, b in ops if n in ("_gmm_kernel",
                                                "_tgmm_kernel")],
            lo, hi).sum() * 100, rel=5e-3)
        coll = _timeline([(a, b) for n, a, b in ops if T.is_collective(n)],
                         lo, hi)
        other = _timeline([(a, b) for n, a, b in ops
                           if not T.is_collective(n)], lo, hi)
        exposed = T.exposed_collective(tr, dev)
        assert exposed == pytest.approx((coll & ~other).sum() * 100,
                                        rel=5e-3, abs=2e4)
        if len(tr.ops) == 1:
            assert exposed == 0
        else:
            assert 0 < exposed < busy


def test_recorded_kernel_names():
    with open(os.path.join(DATA, "cell1.kernels.json")) as f:
        names = set(json.load(f).values())
    assert {"_gmm_kernel", "_tgmm_kernel", "_swiglu_kernel",
            "_combine_fwd_kernel", "_combine_bwd_kernel"} <= names


@pytest.mark.parametrize("name", RECORDED)
def test_step_mfu_reads_device_busy_time(name):
    """``step_mfu`` divides by the device's busy time in the trace, not by
    the host window: against the busy time of a boolean timeline."""
    import types
    from bench.metrics import step_mfu
    tr = _recorded(name)
    lo, hi = tr.window()
    busy = np.mean([_timeline([(a, b) for _, a, b in ops], lo, hi).sum()
                    * 100e-9 for ops in tr.ops.values()])
    n = len(tr.ops)
    ctx = types.SimpleNamespace(trace=tr, chips=n, steps=8,
                                flops_step=8.575e12 * n,
                                peak={"bf16_flops_per_s": 197e12})
    got = step_mfu.read(ctx)
    want = 100.0 * 8.575e12 * 8 / (busy * 197e12)
    assert got == pytest.approx(want, rel=5e-3)
    assert got > 100.0 * 8.575e12 * 8 / ((hi - lo) * 1e-9 * 197e12)

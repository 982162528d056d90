"""bench/trace.py on synthetic intervals and on a small trace recorded on a
TPU v5e (``data/small_trace.xplane.pb``: two ``bench.train`` spans of five
2048x2048 f32 matmuls each, around a 50 ms ``bench.plan`` span in which the
device is idle, all inside ``bench.window``)."""
import pathlib

import numpy as np
import pytest

from bench import trace

DATA = pathlib.Path(__file__).parent / "data" / "small_trace.xplane.pb"


def test_union_overlap_gaps():
    m = trace.union([(5, 7, "b"), (0, 2, "a"), (1, 3, "c"), (9, 10, "d")])
    np.testing.assert_array_equal(m, [[0, 3], [5, 7], [9, 10]])
    assert trace.overlap(m, 1, 6) == 2 + 1
    assert trace.overlap(m, 3, 5) == 0
    assert trace.gaps(m, 0, 10) == [(3, 5), (7, 9)]
    assert trace.gaps(m, -1, 11) == [(-1, 0), (3, 5), (7, 9), (10, 11)]
    assert trace.gaps(trace.union([]), 0, 1) == [(0, 1)]


def test_host_activity_picks_the_innermost_span():
    t = trace.Trace(ops={}, modules={}, spans=[
        ("window", 0, 100), ("epoch", 0, 50), ("plan", 0, 10),
        ("train", 10, 40), ("epoch", 50, 100)])
    at = trace.host_activity(t)
    assert at(5) == "plan"
    assert at(20) == "train"
    assert at(45) == "epoch"
    assert at(60) == "epoch"
    assert at(200) == "outside spans"


def test_op_label():
    assert (trace.op_label("jit_block(123)", "%fusion.4 = f32[8] fusion(x)")
            == "jit_block/fusion.4")


@pytest.fixture(scope="module")
def small():
    return trace.load(str(DATA))


def test_small_trace_planes_and_spans(small):
    assert list(small.ops) == ["/device:TPU:0"]
    assert len(small.ops["/device:TPU:0"]) > 10
    assert [n for n, _, _ in small.spans] == ["window", "train", "plan",
                                              "train"]
    lo, hi = small.window()
    assert hi - lo > 50e6


def test_small_trace_busy_and_idle(small):
    lo, hi = small.window()
    ops = small.ops["/device:TPU:0"]
    first, last = ops[0][0], ops[-1][1]
    # Ten matmuls of 17.2 GFLOP at most at the 197 TFLOP/s peak: >= 0.87 ms.
    busy = trace.busy_ns(small, first, last)
    assert 0.87e6 <= busy < 0.1 * (hi - lo)
    # The profiler aligns the device clock to the host's within a few ms:
    # the ops of each train span lie within 5 ms of it.
    for (s, e), block in zip(small.span_list("train"), (ops[:15], ops[15:])):
        assert abs(block[0][0] - s) < 5e6 and abs(block[-1][1] - e) < 5e6
    b = trace.breakdown(small)
    idle = dict(b["idle_gaps"])
    assert idle["plan"] >= 0.045
    assert sum(idle.values()) == pytest.approx(
        (hi - lo - trace.busy_ns(small, lo, hi)) * 1e-9)
    assert b["device_ops"][0][0].startswith("jit__lambda/fusion")

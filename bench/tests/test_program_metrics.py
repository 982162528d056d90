"""The readers of the program's own record (``bench/program.py`` and the
four metrics on it) on synthetic traces and epoch statistics: clipping to
the window, division by the epochs that refreshed, and nothing read where
the program declares no such module or records no spans."""
import types

import pytest

from bench import program, run
from bench.trace import Trace

MS = 1e6   # ns


def trace(modules, window=(0.0, 100 * MS)):
    return Trace(ops={"/device:TPU:0": [(window[0], window[1], "op")]},
                 modules={"/device:TPU:0": modules},
                 spans=[("window", *window)])


def epoch(fwd, bwd, spans=None):
    s = types.SimpleNamespace(fwd_samples=fwd, bwd_samples=bwd)
    if spans is not None:
        s.spans = spans
    return s


def spans(assemble=None, fetch=None):
    intervals = [("epoch", None, 0.0, 1.0, {})]
    if fetch is not None:
        intervals.insert(0, ("plan.fetch", "plan", 0.1, 0.1 + fetch, {}))
    aggregates = ({} if assemble is None else
                  {"refresh.assemble": ("refresh", 3, assemble, assemble / 2)})
    return {"epoch": 0, "intervals": intervals, "aggregates": aggregates}


def record(modules=(), epochs=(), window=(0.0, 100 * MS)):
    return types.SimpleNamespace(trace=trace(list(modules), window),
                                 epochs=list(epochs))


def metric(name):
    return run.load_module(run.BENCH / "metrics" / f"{name}.py").read


def test_module_time_is_clipped_to_the_window():
    t = trace([(-5 * MS, 5 * MS, "jit_plan_step(1)"),
               (50 * MS, 52 * MS, "jit_plan_step(1)"),
               (98 * MS, 110 * MS, "jit_plan_step(1)"),
               (60 * MS, 70 * MS, "jit_train_block(2)")])
    assert program.module_ns(t, ("jit_plan_step",)) == 5 * MS + 2 * MS + 2 * MS
    assert program.module_ns(t, ("jit_eval_forward",)) is None
    outside = trace([(200 * MS, 210 * MS, "jit_plan_step(1)")])
    assert program.module_ns(outside, ("jit_plan_step",)) is None


def test_refresh_device_ms_divides_by_refreshing_epochs():
    rec = record([(10 * MS, 40 * MS, "jit_eval_forward(3)"),
                  (40 * MS, 46 * MS, "jit_observe_scatter(4)"),
                  (50 * MS, 60 * MS, "jit_train_block(2)")],
                 [epoch(256, 180), epoch(256, 180), epoch(256, 256)])
    assert metric("refresh_device_ms")(rec) == pytest.approx(36.0 / 2)


@pytest.mark.parametrize("name", ["refresh_device_ms", "plan_device_ms"])
def test_device_readers_read_nothing_without_the_declared_modules(name):
    # The parent's programs: the same work under the functions' own names.
    rec = record([(10 * MS, 40 * MS, "jit_eval_step(3)"),
                  (50 * MS, 51 * MS, "jit__plan_step(5)")],
                 [epoch(256, 180)])
    assert metric(name)(rec) is None
    assert metric(name)(types.SimpleNamespace(trace=None,
                                              epochs=rec.epochs)) is None


def test_plan_device_ms_per_window_epoch():
    rec = record([(10 * MS, 10.5 * MS, "jit_plan_step(5)"),
                  (60 * MS, 60.3 * MS, "jit_plan_step(5)")],
                 [epoch(256, 180), epoch(256, 256)])
    assert metric("plan_device_ms")(rec) == pytest.approx(0.8 / 2)


def test_refresh_assemble_ms_divides_by_refreshing_epochs():
    rec = record(epochs=[epoch(256, 180, spans(assemble=0.030)),
                         epoch(256, 180, spans(assemble=0.050)),
                         epoch(256, 256, spans())])
    assert metric("refresh_assemble_ms")(rec) == pytest.approx(40.0)


def test_plan_fetch_ms_averages_the_window_epochs():
    rec = record(epochs=[epoch(256, 180, spans(fetch=0.002)),
                         epoch(256, 256, spans(fetch=0.004))])
    assert metric("plan_fetch_ms")(rec) == pytest.approx(3.0)


@pytest.mark.parametrize("name", ["refresh_assemble_ms", "plan_fetch_ms"])
def test_host_readers_read_nothing_without_spans(name):
    # EpochStats of a program that records no spans, and of one whose
    # epochs recorded none of these.
    assert metric(name)(record(epochs=[epoch(256, 180)])) is None
    assert metric(name)(record(epochs=[epoch(256, 180, spans())])) is None
    assert metric(name)(record(epochs=[])) is None

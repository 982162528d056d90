"""The trainer's own spans, counters and program names (``repro/tracing.py``).

- spans nest under the innermost open span and share their epoch's record;
- every epoch records each phase of ``Trainer.run_epoch``, and step D's
  spans only where samples were hidden;
- recording adds no host sync (the per-strategy sync counts hold);
- a profiler trace carries the ``repro.`` annotations on its host plane;
- the compile counter names the phase a compile fell in;
- the jitted programs carry their declared XLA module names, and the named
  scopes their op names;
- params, losses and plans are bit-identical with every span, count and
  named scope made a no-op.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs.registry import get_arch
from repro.core import KakurenboConfig, LRSchedule
from repro.core import kakurenbo as kakurenbo_mod
from repro.core.state import TrainCarry
from repro.data import SyntheticLM
from repro.models import build_model
from repro.train import Trainer, TrainConfig
from repro.train.engines import scan_block_sizes

from tests.test_scan_engine import _mk

PHASES = ("epoch",) + tracing.PHASES
REFRESH_SPANS = {"refresh.assemble", "refresh.dispatch", "refresh.observe"}


def _intervals(stats):
    return {name: (parent, start, end, counts)
            for name, parent, start, end, counts in stats.spans["intervals"]}


def test_spans_nest_under_the_innermost_open_span():
    with tracing.span("outside"):      # no epoch open: nothing recorded
        tracing.count("rows", 5)
    with tracing.epoch(7) as rec:
        with tracing.span("plan"):
            with tracing.span("plan.fetch"):
                tracing.count("rows", 2)
        with tracing.span("train"):
            for _ in range(3):
                with tracing.span("train.dispatch"):
                    tracing.count("rows")
        with pytest.raises(ValueError):
            with tracing.span("refresh"):
                raise ValueError("a failing phase still closes its span")
    spans = rec.spans()
    assert spans["epoch"] == 7
    got = {n: (p, c) for n, p, _, _, c in spans["intervals"]}
    assert got == {"plan.fetch": ("plan", {"rows": 2}), "plan": ("epoch", {}),
                   "train": ("epoch", {"rows": 3}), "refresh": ("epoch", {}),
                   "epoch": (None, {})}
    parent, n, total, longest = spans["aggregates"]["train.dispatch"]
    assert (parent, n) == ("train", 3) and 0 <= longest <= total
    assert rec.counters == {"rows": 5}
    (_, _, start, end, _), = [i for i in spans["intervals"] if i[0] == "epoch"]
    assert (rec.start, rec.end) == (start, end)
    assert tracing._state.stack == [] and tracing._state.record is None


@pytest.mark.parametrize("strategy", ["kakurenbo", "baseline"])
def test_epoch_stats_hold_every_phase(strategy):
    tr = _mk("scan", strategy=strategy, epochs=3)
    hist = tr.run()
    for stats in hist:
        got = _intervals(stats)
        assert stats.spans["epoch"] == stats.epoch
        assert set(PHASES) <= set(got)
        assert {"train.layout", "train.fetch"} <= set(got)
        assert all(got[p][0] == "epoch" for p in tracing.PHASES)
        assert got["train.fetch"][0] == "train"
        if strategy == "kakurenbo":
            assert got["plan.dispatch"][0] == got["plan.fetch"][0] == "plan"
        _, start, end, _ = got["epoch"]
        assert stats.wall_time == end - start
        assert all(start <= s <= e <= end for _, s, e, _ in got.values())
        aggs = stats.spans["aggregates"]
        steps = stats.bwd_samples // tr.cfg.batch_size
        assert aggs["train.dispatch"][:2] == (
            "train", len(scan_block_sizes(steps, tr.cfg.scan_steps)))
        hid = stats.hidden_fraction > 0
        assert (REFRESH_SPANS <= set(aggs)) == hid
        assert not hid or all(aggs[k][0] == "refresh" for k in REFRESH_SPANS)
        rows = stats.counters.get("refresh_rows", 0)
        assert rows == stats.fwd_samples - stats.bwd_samples
    assert any(h.hidden_fraction > 0 for h in hist) == (strategy == "kakurenbo")


def test_host_syncs_per_epoch_unchanged():
    from benchmarks.selection_overhead import strategy_sync_counts
    records = strategy_sync_counts(num_samples=128, batch=32, epochs=2)
    assert {r["strategy"] for r in records} >= {"baseline", "kakurenbo"}
    assert all(r["host_syncs_per_epoch"] == 1 for r in records
               if r["strategy"] in ("baseline", "kakurenbo"))


def test_profiler_trace_carries_the_annotations(tmp_path):
    tr = _mk("scan", strategy="kakurenbo", epochs=2)
    tr.run_epoch(0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        stats = tr.run_epoch(1)
    finally:
        jax.profiler.stop_trace()
    assert stats.hidden_fraction > 0
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    want = {"repro." + n for n in _intervals(stats)}
    want |= {"repro." + n for n in stats.spans["aggregates"]}
    assert want <= names
    assert "repro.refresh.assemble" in want
    assert any(n.startswith("train") for n in names)   # the step annotation


def test_compiles_counted_under_their_phase():
    tr = _mk("scan", strategy="baseline", epochs=2)
    first, warmed = tr.run()
    assert first.counters["compiles"] >= 1
    assert first.counters["compile_s"] > 0
    assert _intervals(first)["train"][3].get("compiles", 0) >= 1
    assert warmed.counters.get("compiles", 0) == 0


def _program_lowerings():
    def train_block(tr):
        bs = tr.cfg.batch_size
        carry = TrainCarry(tr.params, tr.opt_state, tr.ef_state,
                           tr.strategy.get_device_state(), tr.guard_state)
        xs = {"idx": jnp.zeros((1, bs), jnp.int32)}
        return tr.engine._block.lower(carry, xs, jnp.int32(0), 0.0)

    def eval_forward(tr):
        return tr._eval_step.lower(
            tr.params, tr.dataset.get(np.arange(tr.cfg.batch_size)))

    def observe_scatter(tr):
        n = 4
        return tr.strategy._inner._observe.lower(
            tr.strategy.get_device_state(), jnp.arange(n),
            jnp.zeros(n), jnp.zeros(n, bool), jnp.zeros(n), 0)

    def plan_step(tr):
        return kakurenbo_mod._plan_step.lower(
            tr.strategy.get_device_state(), jax.random.key(0),
            jnp.float32(0.3), method="sort", tau=0.7, drop_top=0.0,
            moveback=True, adjust_lr=True, mesh=None)

    return [pytest.param(tracing.TRAIN_BLOCK, None, train_block,
                         id="train_block"),
            pytest.param(tracing.EVAL_FORWARD, None, eval_forward,
                         id="eval_forward"),
            pytest.param(tracing.EVAL_FORWARD, (1,), eval_forward,
                         id="eval_forward_mesh"),
            pytest.param(tracing.OBSERVE_SCATTER, None, observe_scatter,
                         id="observe_scatter"),
            pytest.param(tracing.PLAN_STEP, None, plan_step, id="plan_step")]


@pytest.mark.parametrize("name,mesh_shape,lower", _program_lowerings())
def test_programs_carry_their_declared_module_names(name, mesh_shape, lower):
    tr = _mk("scan", strategy="kakurenbo", epochs=1, mesh_shape=mesh_shape)
    tr.engine._build_block()
    assert f"module @{name} " in lower(tr).as_text()


def _lm_trainer(arch: str) -> Trainer:
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    ds = SyntheticLM(num_samples=64, seq_len=16, vocab_size=48, order=1,
                     easy_fraction=0.7, seed=0)
    tc = TrainConfig(epochs=3, batch_size=16, strategy="kakurenbo",
                     optimizer="adamw", optimizer_hp={},
                     lr=LRSchedule(1e-2, "cosine", 3, 1),
                     kakurenbo=KakurenboConfig(max_fraction=0.3,
                                               fraction_milestones=(0, 1, 2, 3),
                                               moveback=False),
                     engine="scan", scan_steps=1, seed=0)
    return Trainer(tc, model.init, model.loss_and_metrics, ds, None)


SCOPES = {"smollm-135m": ("attention", "mlp", "scoring", "optimizer",
                          "observe"),
          "mamba2-130m": ("ssd_mixer", "scoring", "optimizer", "observe")}


@pytest.mark.parametrize("arch", sorted(SCOPES))
def test_named_scopes_reach_the_step(arch):
    tr = _lm_trainer(arch)
    tr.engine._build_block()
    carry = TrainCarry(tr.params, tr.opt_state, tr.ef_state,
                       tr.strategy.get_device_state(), tr.guard_state)
    text = tr.engine._block.lower(
        carry, {"idx": jnp.zeros((1, 16), jnp.int32)}, jnp.int32(0),
        0.0).as_text(debug_info=True)
    # Op locations are name stacks: "jit(train_block)/jvp(scoring)/sub".
    parts = {p for loc in re.findall(r'loc\("([^"]*)"', text)
             for p in re.split(r"[/()]", loc)}
    assert set(SCOPES[arch]) <= parts


class _NoSpan:
    def __init__(self, name, step=None):
        pass

    def __enter__(self):
        self.t0 = 0.0
        return self

    def __exit__(self, *exc):
        return False


@contextlib.contextmanager
def _no_epoch(number):
    yield tracing.EpochRecord(number)


def _run(arch):
    tr = _lm_trainer(arch)
    plans = []
    plan = tr.strategy.plan
    tr.strategy.plan = lambda e: plans.append(plan(e)) or plans[-1]
    hist = tr.run()
    return tr, hist, plans


@pytest.mark.parametrize("arch", sorted(SCOPES))
def test_spans_and_scopes_change_no_result(arch, monkeypatch):
    tr_a, hist_a, plans_a = _run(arch)
    with monkeypatch.context() as m:
        m.setattr(tracing, "span", _NoSpan)
        m.setattr(tracing, "count", lambda name, n=1: None)
        m.setattr(tracing, "epoch", _no_epoch)
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        tr_b, hist_b, plans_b = _run(arch)
    assert hist_b[0].spans == {"epoch": 0, "intervals": [], "aggregates": {}}
    assert hist_a[0].spans["intervals"]
    assert any(p.hidden_indices.size for p in plans_a)
    assert [h.train_loss for h in hist_a] == [h.train_loss for h in hist_b]
    for pa, pb in zip(plans_a, plans_b):
        np.testing.assert_array_equal(pa.visible_indices, pb.visible_indices)
        np.testing.assert_array_equal(pa.hidden_indices, pb.hidden_indices)
    for a, b in zip(jax.tree.leaves((tr_a.params, tr_a.opt_state,
                                     tr_a.strategy.get_device_state())),
                    jax.tree.leaves((tr_b.params, tr_b.opt_state,
                                     tr_b.strategy.get_device_state()))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

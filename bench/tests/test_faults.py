"""A run whose timed path is broken underneath must come out not correct.

Each fault is planted in the program before the trainer is built, then the
rest of a run is driven at tiny widths on the CPU (the look for a chip is
skipped): a step that returns its state unchanged, half of the batch left
out with the mean taken over the rest, and an answer (the per-sample and
batch loss) altered where it is produced; in KAKURENBO cells also the
fused observe writing to the wrong rows and a step D that refreshes
nothing.  A cell on several chips runs on as many virtual CPU devices in a
process of its own (``bench/tests/cpu_run.py``), with one fault more: the
exchange of gradients between the chips left out.
"""
import json

import jax.numpy as jnp
import pytest

from bench import run
from bench.tests.cpu_run import subprocess_result
from bench.tests.tiny import tiny


def state_unchanged(monkeypatch):
    from repro.optim import optimizers
    real = optimizers.adamw

    def frozen(**hp):
        opt = real(**hp)
        return optimizers.Optimizer(
            opt.init, lambda grads, state, params, lr: (params, state),
            "adamw")
    monkeypatch.setattr(optimizers, "adamw", frozen)


def half_batch(monkeypatch):
    from repro.models.model import Model
    real = Model.loss_and_metrics

    def first_half(self, params, batch):
        _, (lv, pa, pc) = real(self, params, batch)
        return jnp.mean(lv[: lv.shape[0] // 2]), (lv, pa, pc)
    monkeypatch.setattr(Model, "loss_and_metrics", first_half)


def answer_altered(monkeypatch):
    from repro.models.model import Model
    real = Model.loss_and_metrics

    def altered(self, params, batch):
        scalar, (lv, pa, pc) = real(self, params, batch)
        return scalar * 1.05, (lv * 1.05, pa, pc)
    monkeypatch.setattr(Model, "loss_and_metrics", altered)


def observed_elsewhere(monkeypatch):
    from repro.core.kakurenbo import KakurenboStrategy
    real = KakurenboStrategy.fused_observe

    def shifted(state, indices, *args, **kw):
        return real(state, (indices + 1) % state.loss.shape[0], *args, **kw)
    monkeypatch.setattr(KakurenboStrategy, "fused_observe",
                        staticmethod(shifted))


def refresh_skipped(monkeypatch):
    from repro.core.kakurenbo import KakurenboStrategy
    monkeypatch.setattr(KakurenboStrategy, "on_epoch_end",
                        lambda self, plan, eval_forward, batch_size: 0)


def exchange_left_out(monkeypatch):
    """Each chip folds only its own chunks: the all-gather of the fold
    hands every chip its own partials in each chip's place."""
    import jax

    def own_only(x, axis_name, **_):
        n = jax.lax.axis_size(axis_name)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), x)
    monkeypatch.setattr(jax.lax, "all_gather", own_only)


def not_correct(capsys, workload):
    rc = run.main(["--workload", workload, "--seed", "11", "--seconds", "0",
                   "--trace", "0"], require_tpu=False, arch_override=tiny)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   answer_altered])
@pytest.mark.parametrize("workload", ["smollm-135m.kakurenbo",
                                      "smollm-135m.baseline",
                                      "mamba2-130m.kakurenbo"])
def test_fault_is_not_correct(monkeypatch, capsys, fault, workload):
    fault(monkeypatch)
    not_correct(capsys, workload)


@pytest.mark.parametrize("fault", [observed_elsewhere, refresh_skipped])
@pytest.mark.parametrize("workload", ["smollm-135m.kakurenbo",
                                      "mamba2-130m.kakurenbo"])
def test_strategy_fault_is_not_correct(monkeypatch, capsys, fault, workload):
    fault(monkeypatch)
    not_correct(capsys, workload)



@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered", "exchange_left_out",
                                   "observed_elsewhere", "refresh_skipped"])
def test_mesh_fault_is_not_correct(fault):
    res, _ = subprocess_result(
        ["--devices", "4", "--fault", f"test_faults.{fault}", "--",
         "--workload", "smollm-135m.kakurenbo.dp4", "--seed", "11",
         "--seconds", "0", "--trace", "0"])
    assert res["device"]["count"] == 4
    assert res["correct"] is False, res["checks"]

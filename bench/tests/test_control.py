"""The control must come out not correct.

The configurations state float32 parameters at the default matmul
precision; the next precision below is bfloat16.  Here the program itself
computes its forward and backward passes in bfloat16 (the parameters cast
before the model reads them) and the rest of a run is driven on the CPU,
at the published widths and 2 layers deep (the cell on four chips on as
many virtual devices; mamba2 at its own 512 tokens, where its control
fails the gradient numbers as on the chip, and not at 64, where it fails
none): the comparison against the float32 reference must fail at least
one number.  On the chip the same
control is read at full size by ``bench/calibrate.py`` (PERF.md).
"""
import json

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.tests.cpu_run import subprocess_result
from bench.tests.tiny import shallow, shallow_full_length

SIZES = {"mamba2-130m.kakurenbo": shallow_full_length}


def bfloat16_program(monkeypatch):
    from repro.models.model import Model
    real = Model.loss_and_metrics

    def bf16(self, params, batch):
        return real(self, jax.tree.map(
            lambda p: p.astype(jnp.bfloat16), params), batch)
    monkeypatch.setattr(Model, "loss_and_metrics", bf16)


@pytest.mark.parametrize("workload", ["smollm-135m.kakurenbo",
                                      "mamba2-130m.kakurenbo"])
def test_bfloat16_program_is_not_correct(monkeypatch, capsys, workload):
    bfloat16_program(monkeypatch)
    rc = run.main(["--workload", workload, "--seed", "21", "--seconds", "0",
                   "--trace", "0"], require_tpu=False,
                  arch_override=SIZES.get(workload, shallow))
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is False, res["checks"]


def test_bfloat16_program_on_four_devices_is_not_correct():
    res, _ = subprocess_result(
        ["--devices", "4", "--size", "shallow", "--fault",
         "test_control.bfloat16_program", "--", "--workload",
         "smollm-135m.kakurenbo.dp4", "--seed", "21", "--seconds", "0",
         "--trace", "0"])
    assert res["device"]["count"] == 4
    assert res["correct"] is False, res["checks"]

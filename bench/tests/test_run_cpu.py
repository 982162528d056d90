"""The harness's own behaviour on the CPU, at tiny widths.

The full run path (set-up, window, checks, result line) is driven here with
``require_tpu=False``, which only the tests pass: from the command line a
run without a TPU refuses to report.
"""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import run
from bench.tests.tiny import tiny

ROOT = pathlib.Path(__file__).resolve().parents[2]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def cli(args, cwd=ROOT):
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=ENV,
        capture_output=True, text=True, timeout=300)


def test_refuses_to_report_without_a_tpu():
    p = cli(["--workload", "smollm-135m.baseline", "--seed", "1",
             "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU: JAX found cpu" in p.stderr
    assert "platform=cpu kind=cpu count=1" in p.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = cli(["--workload", "smollm-135m.baseline", "--seed", "1",
             "--seconds", "1", "--trace", "0"], cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def result_of(capsys, workload, seed, trace):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "1", "--trace", str(trace)],
                  require_tpu=False, arch_override=tiny)
    out = capsys.readouterr()
    assert rc == 0
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize("workload,trace", [
    ("smollm-135m.kakurenbo", 0), ("smollm-135m.baseline", 1),
    ("mamba2-130m.kakurenbo", 1)])
def test_result_line(capsys, workload, trace):
    res, err = result_of(capsys, workload, 2**32 + 7, trace)
    assert list(res)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(res)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    cell = run.Cell(workload)
    want = cell.per_layer if trace else cell.end_to_end
    assert set(res["metrics"]) <= {m["name"] for m in want}
    if not trace:
        assert set(res["metrics"]) == {m["name"] for m in want}
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    if trace:
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "compiles_in_window=0" in err
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("check ") for line in tail)

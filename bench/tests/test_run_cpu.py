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
from bench.tests.cpu_run import subprocess_result
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


@pytest.mark.parametrize("trace", [0, 1])
def test_four_chip_cell_on_four_cpu_devices(trace):
    res, err = subprocess_result(
        ["--devices", "4", "--peak", "1e12", "--", "--workload",
         "smollm-135m.kakurenbo.dp4", "--seed", str(2**33 + 3),
         "--seconds", "1", "--trace", str(trace)])
    assert res["correct"] is True and res["failed"] == 0, res["checks"]
    assert res["checks"]["plan_diff"]["value"] == 0
    assert res["checks"]["seen_diff"]["value"] == 0
    assert res["device"]["count"] == 4
    assert "compiles_in_window=0" in err
    assert "batch=16x32" in err and "samples=512" in err
    # Every chip's memory was read for the peak.
    assert all(f"memory_stats {i}:" in err for i in range(4))
    cell = run.Cell("smollm-135m.kakurenbo.dp4")
    want = cell.per_layer if trace else cell.end_to_end
    if trace:
        assert {"mfu", "refresh_ms", "plan_fetch_ms"} <= set(res["metrics"])
        assert set(res["metrics"]) <= {m["name"] for m in want}
    else:
        assert set(res["metrics"]) == {m["name"] for m in want}


def test_a_configuration_of_a_new_family_from_new_files_alone(tmp_path):
    """A configuration that ``bench/flops.py`` cannot count (a mixture of
    experts) runs through the harness of a copy of the benchmark to which
    only its own files and entries were added, and ``mfu`` reads the count
    its module gives."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    data = ROOT / "bench" / "tests" / "data"
    for name in ("tiny-moe.json", "tiny-moe.py", "tiny-moe.limits.json"):
        shutil.copy(data / name, tmp_path / "bench" / "configs")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-moe", "source": "a test fixture",
        "file": "bench/configs/tiny-moe.json", "reduced": [],
        "why": "a family the harness's own files do not know"})
    bench["workloads"].append({
        "name": "tiny-moe.kakurenbo", "config": "tiny-moe",
        "traffic": "kakurenbo", "chips": 1, "why": "the fixture's cell"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    res, err = subprocess_result(
        ["--peak", "1e12", "--", "--workload", "tiny-moe.kakurenbo",
         "--seed", str(2**32 + 9), "--seconds", "1", "--trace", "1"],
        cwd=tmp_path, env=env)
    assert res["correct"] is True, res["checks"]
    (line,) = [ln for ln in err.splitlines()
               if ln.startswith("window_flops ")]
    got = json.loads(line.split(" ", 1)[1])
    assert got["trained"] > 0 and got["chips"] == 1
    assert res["metrics"]["mfu"]["value"] == pytest.approx(
        100.0 * (got["trained"] + got["refreshed"])
        / (got["window_s"] * got["chips"] * 1e12), rel=1e-12)

"""One benchmark run on the CPU at its configuration's CPU sizes, in a
process of its own, for tests that need what a test process cannot give
itself: virtual devices for a cell on several chips, a benchmark in
another checkout, or a CPU peak.

    python3 bench/tests/cpu_run.py [--devices N] [--size tiny|shallow] \\
        [--fault MODULE.NAME] [--peak FLOPS] -- <bench/run.py's arguments>

``--devices`` makes the CPU backend show N devices, so a cell's mesh runs;
``--size`` picks ``bench/tests/tiny.py``'s sizes; ``--fault`` plants a
fault of a test module of ``bench/tests`` (``test_faults.half_batch``,
``test_control.bfloat16_program``) before the run; ``--peak`` gives the
CPU a bf16 peak, so that ``mfu`` reads a number.  The benchmark and the tests are those of this file's checkout;
the program is found on ``PYTHONPATH`` where the checkout has no ``src``.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def subprocess_result(args, cwd=ROOT, env=None) -> tuple[dict, str]:
    """Runs this file of the checkout at ``cwd`` with ``args`` in a process
    of its own; returns the run's result line and its standard error."""
    p = subprocess.run(
        [sys.executable, "bench/tests/cpu_run.py", *args], cwd=cwd,
        env=dict(env or os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--size", choices=("tiny", "shallow"), default="tiny")
    ap.add_argument("--fault")
    ap.add_argument("--peak", type=float)
    ap.add_argument("run_args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.devices}")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import importlib

    import pytest
    from bench import peaks, run
    from bench.tests import tiny
    if args.peak:
        peaks.PEAKS["cpu"] = {"bf16_flops_per_s": args.peak}
    run_args = [a for a in args.run_args if a != "--"]
    with pytest.MonkeyPatch.context() as mp:
        if args.fault:
            module, name = args.fault.rsplit(".", 1)
            getattr(importlib.import_module(f"bench.tests.{module}"),
                    name)(mp)
        return run.main(run_args, require_tpu=False,
                        arch_override=getattr(tiny, args.size))


if __name__ == "__main__":
    sys.exit(main())

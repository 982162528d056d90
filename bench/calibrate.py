"""Readings that the limits of ``bench/configs/<name>.limits.json`` are set
from.  Not part of a benchmark run.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --first-seed <n>

For each seed, in one process: the cell's set-up and one window epoch, then
every number ``bench/check.py`` computes, read three ways —

- ``program``: the program against the float32 reference (the lower
  reading);
- ``control``: the reference computed in bfloat16, put in the program's
  place (the configuration states float32 at the default matmul precision,
  so bfloat16 is the next precision below): its three steps, and its
  (loss, PC) of the probe's rows at the float32 reference's parameters
  after its steps; for ``plan_diff`` the plan of the sample state with its
  losses rounded to bfloat16;
- ``half_batch``: the reference with half of each batch left out and the
  mean taken over the rest, put in the program's place (a fault);
- ``state_unchanged``: the reference with a learning rate of 0, put in the
  program's place: every step sees the initial parameters (a fault; it
  reads 1 on ``update_norm_rel`` by construction).

One JSON line per seed goes to standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys

from run import ROOT, Cell, Run, checks, device_info, enable_compile_cache  # noqa: E402


def half(batches):
    return [(t[: len(t) // 2], l[: len(l) // 2]) for t, l in batches]


def readings(cell: Cell, seed: int, arch_override=None) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import check

    r = Run(cell, seed, arch_override)
    r.setup()
    r.window(0.0)
    states = [check.state_dict_of(s) for s in r.states]
    program, extra = checks(r, None)
    tap, ref = extra["tap"], extra["ref"]
    out = {"seed": seed, "program": program, "control": {}, "half_batch": {},
           "state_unchanged": {}, "setup_hidden": r.setup_hidden,
           "hidden": [len(p.hidden_indices) for p in r.plans]}
    batches = r.tap_batches(tap)
    if cell.kakurenbo:
        rounded = [dict(s, loss=np.asarray(jnp.asarray(s["loss"]).astype(
            jnp.bfloat16).astype(jnp.float32))) for s in states]
        out["control"]["plan_diff"] = check.plan_diff(
            r.plans, rounded, cell.traffic["kakurenbo"], r.n)
        # The probe's rows at the float32 reference's parameters, which the
        # program's probe consumed: the same three steps again.
        params = check.reference_steps(
            r.forward, r.initial_weights, batches, tap["lrs"], r.hp,
            rows=r.reference_rows)["params"]
        data = r.corpus.get(extra["rows"])
        loss, pc = check.reference_signals(
            r.forward, jax.tree.map(lambda p: p.astype(jnp.bfloat16), params),
            data["tokens"], data["labels"], r.reference_rows)
        del params
        f_loss, f_pc = extra["signals"]
        b = extra["train"]
        for name, sl in (("observe_rel", slice(None, b)),
                         ("refresh_rel", slice(b, None))):
            out["control"][name] = max(check.sample_gap(loss[sl], f_loss[sl]),
                                       check.sample_gap(pc[sl], f_pc[sl]))
    gc.collect()
    leaves = {"names": [jax.tree_util.keystr(p) for p, _ in
                        jax.tree_util.tree_leaves_with_path(ref["grad_tree"])],
              "program": check.grad_diffs(tap, ref).tolist()}
    out["grad_diff_leaves"] = leaves
    for name, steps in (
            ("control", dict(batches=batches, dtype=jnp.bfloat16)),
            ("half_batch", dict(batches=half(batches),
                                rows=max(r.reference_rows // 2, 1))),
            ("state_unchanged", dict(batches=batches,
                                     lrs=[0.0] * len(batches)))):
        kw = dict(batches=batches, lrs=tap["lrs"], rows=r.reference_rows)
        kw.update(steps)
        got = check.reference_steps(r.forward, r.initial_weights,
                                    kw.pop("batches"), kw.pop("lrs"), r.hp,
                                    **kw)
        del got["params"]
        out[name].update(check.step_numbers(got, ref))
        leaves[name] = check.grad_diffs(got, ref).tolist()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    enable_compile_cache()
    device_info(cell.chips)
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        out = readings(cell, seed)
        print(json.dumps(out, default=lambda x: None if x is None else float(x)
                         if not math.isinf(x) else "inf"), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    sys.exit(main())

"""Readings that the limits of ``bench/configs/<name>.limits.json`` are set
from.  Not part of a benchmark run.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --first-seed <n>
                               [--seed <m> ...]

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
  reads 1 on ``update_norm_rel`` by construction);
- ``answer_altered``, in KAKURENBO cells: the program's step-D losses
  5% high (``refresh_rel``, ``refresh_mean_rel``; a fault);
- ``exchange_left_out``, in a cell on several chips: the reference on the
  rows of the first chip alone, put in the program's place (a fault: the
  gradients are not exchanged, and each chip keeps its own).

One JSON line per seed goes to standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys

from run import ROOT, Cell, Run, checks, device_info, enable_compile_cache  # noqa: E402


def first_share(batches, parts: int):
    """The first of ``parts`` equal shares of each batch's rows."""
    return [(t[: len(t) // parts], l[: len(l) // parts]) for t, l in batches]


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
           "state_unchanged": {}, "exchange_left_out": {},
           "answer_altered": {},
           "setup_hidden": r.setup_hidden,
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
        out["refresh_rows"] = {
            "reference": {"loss": f_loss[b:].tolist(),
                          "pc": f_pc[b:].tolist()},
            "program": extra["refreshed"],
            "control": {"loss": np.asarray(loss[b:]).tolist(),
                        "pc": np.asarray(pc[b:]).tolist()}}
        for name, sl, reduce in (
                ("observe_rel", slice(None, b), np.max),
                ("refresh_rel", slice(b, None), np.max),
                ("refresh_mean_rel", slice(b, None), np.mean)):
            out["control"][name] = max(
                check.sample_gap(loss[sl], f_loss[sl], reduce),
                check.sample_gap(pc[sl], f_pc[sl], reduce))
        # An answer altered where it is produced: step D's losses (the
        # program's, read above) 5% high, as bench/tests plants it.
        got = extra["refreshed"]
        for name, reduce in (("refresh_rel", np.max),
                             ("refresh_mean_rel", np.mean)):
            out["answer_altered"][name] = max(
                check.sample_gap(1.05 * np.asarray(got["loss"]), f_loss[b:],
                                 reduce),
                check.sample_gap(got["pc"], f_pc[b:], reduce))
    gc.collect()
    leaves = {"names": [jax.tree_util.keystr(p) for p, _ in
                        jax.tree_util.tree_leaves_with_path(ref["grad_tree"])],
              "program": check.grad_diffs(tap, ref).tolist()}
    out["grad_diff_leaves"] = leaves
    faults = [
        ("control", dict(batches=batches, dtype=jnp.bfloat16)),
        ("half_batch", dict(batches=first_share(batches, 2),
                            rows=max(r.reference_rows // 2, 1))),
        ("state_unchanged", dict(batches=batches, lrs=[0.0] * len(batches)))]
    if cell.chips > 1:
        faults.append(("exchange_left_out", dict(
            batches=first_share(batches, cell.chips),
            rows=min(r.reference_rows, r.batch // cell.chips))))
    for name, steps in faults:
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
    ap.add_argument("--seed", type=int, action="append", default=[],
                    help="a seed to read before the range (repeatable)")
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    enable_compile_cache()
    device_info(cell.chips)
    for seed in [*args.seed,
                 *range(args.first_seed, args.first_seed + args.seeds)]:
        out = readings(cell, seed)
        print(json.dumps(out, default=lambda x: None if x is None else float(x)
                         if not math.isinf(x) else "inf"), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    sys.exit(main())

"""Where the device idles in one traced run of a cell, by the trainer's own
phases, and where the trace carries the program's named scopes.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does (set-up, then the window
under the profiler, here with the programs' HLO kept in the trace), and
prints one JSON object:

- ``by_phase``: each idle gap of the device in the window, given to the
  innermost interval of the window epochs' ``EpochStats.spans`` holding its
  midpoint; where that is the interval's own time, also the two child
  intervals it lies between (``epoch: plan > train``).  The intervals are
  on ``time.perf_counter``; they are placed on the trace's clock through
  the ``bench.window`` annotation, whose trace start is taken against
  ``rec.window[0]``.  A gap in no epoch is the benchmark's own loop between
  epochs.  Per holder: seconds, gaps, longest.
- ``align_us``: the largest distance between an aligned ``epoch`` interval
  and the trace's own ``repro.epoch`` annotation.
- ``by_annotation``: the same gaps by the innermost ``repro.`` annotation in
  the trace, which also splits the per-batch spans (``refresh.assemble``,
  ``refresh.dispatch``, ``refresh.observe``, ``train.dispatch``).
- ``epoch_s``: per window epoch, the seconds of ``epoch`` and of each of
  its phases (a stall shows in the phase it fell in).
- ``by_module``: device seconds and executions of each XLA module in the
  window (the programs' declared names).
- ``scopes``: for each named scope, the trace's planes, lines and stats
  whose text holds it; and ``scope_s``, the device self time of the HLO ops
  whose framework op name (the JAX name stack, which xprof's ``hlo_stats``
  reads from the HLO kept in the trace) holds the scope.
"""
from __future__ import annotations

import argparse
import collections
import json
import pathlib
import re
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run, trace  # noqa: E402
from repro import tracing  # noqa: E402

SCOPES = ("attention", "mlp", "ssd_mixer", "scoring", "optimizer", "observe")


def innermost(spans, t: float):
    """The shortest (name, start, end, parent) span holding ``t``, by name;
    in its own time, with the child intervals before and after ``t``."""
    best = min((s for s in spans if s[1] <= t <= s[2]),
               key=lambda s: s[2] - s[1], default=None)
    if best is None:
        return None
    kids = [s for s in spans if s[3] == best[0]
            and best[1] <= s[1] and s[2] <= best[2]]
    if not kids:
        return best[0]
    before = max((s for s in kids if s[2] <= t), key=lambda s: s[2],
                 default=None)
    after = min((s for s in kids if s[1] >= t), key=lambda s: s[1],
                default=None)
    return (f"{best[0]}: {before[0] if before else 'start'} > "
            f"{after[0] if after else 'end'}")


def idle_by(spans, tr: trace.Trace, outside: str) -> dict:
    """{holder: [seconds, gaps, longest gap]} over the first device plane's
    idle gaps in the window (none without a device plane)."""
    lo, hi = tr.window()
    out = collections.defaultdict(lambda: [0.0, 0, 0.0])
    merged = [tr.merged()[p] for p in sorted(tr.ops)[:1]]
    for a, b in (g for m in merged for g in trace.gaps(m, lo, hi)):
        v = out[innermost(spans, (a + b) / 2) or outside]
        d = (b - a) * 1e-9
        v[0] += d
        v[1] += 1
        v[2] = max(v[2], d)
    return dict(sorted(out.items(), key=lambda kv: -kv[1][0]))


def epoch_intervals(r, tr: trace.Trace) -> list[tuple]:
    """The window epochs' intervals on the trace's clock (ns)."""
    offset = tr.window()[0] - r.t0 * 1e9   # r.t0 is rec.window[0]
    return [(name, start * 1e9 + offset, end * 1e9 + offset, parent)
            for s in r.epochs
            for name, parent, start, end, _ in s.spans.get("intervals", [])]


def annotations(data) -> list[tuple]:
    return [(e.name[len("repro."):], e.start_ns, e.end_ns, None)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


def scope_places(data) -> dict:
    """{scope: {"<plane>|<line>|<stat>": count}} over every string stat."""
    found = {s: collections.Counter() for s in SCOPES}

    def look(where: str, stats) -> None:
        for name, value in stats:
            if isinstance(value, bytes):
                value = value.decode("latin-1")
            if not isinstance(value, str):
                continue
            for s in SCOPES:
                if f"/{s}/" in value or f"({s})" in value:
                    found[s][f"{where}|{name}"] += 1

    for plane in data.planes:
        look(plane.name + "|plane", plane.stats)
        for line in plane.lines:
            for e in line.events:
                look(f"{plane.name}|{line.name}",
                     [("name", e.name), *e.stats])
    return {s: dict(c.most_common(8)) for s, c in found.items()}


def epoch_seconds(epochs) -> dict:
    names = ("epoch",) + tracing.PHASES
    rows = []
    for s in epochs:
        own = {n: end - start for n, _, start, end, _ in
               s.spans.get("intervals", [])}
        rows.append([own.get(n) for n in names])
    return {"names": names, "rows": rows}


def by_module(tr: trace.Trace) -> dict:
    lo, hi = tr.window()
    out = collections.defaultdict(lambda: [0.0, 0])
    for plane in sorted(tr.modules)[:1]:
        for s, e, name in tr.modules[plane]:
            if e > lo and s < hi:
                v = out[name.split("(", 1)[0]]
                v[0] += (min(e, hi) - max(s, lo)) * 1e-9
                v[1] += 1
    return dict(sorted(out.items(), key=lambda kv: -kv[1][0]))


def scope_seconds(path: str) -> dict:
    """Device self seconds by named scope from xprof's ``hlo_stats`` (the
    whole trace, not only the window); ``None`` where xprof is absent."""
    try:
        from xprof.convert import raw_to_tool_data
    except ImportError:
        return None
    data, _ = raw_to_tool_data.xspace_to_tool_data([path], "hlo_stats", {})
    table = json.loads(data)
    cols = [c["id"] for c in table["cols"]]
    name_at, self_at = cols.index("tf_op_name"), cols.index("total_self_time")
    out = collections.Counter()
    for row in table["rows"]:
        v = [c.get("v") if c else None for c in row["c"]]
        parts = set(re.split(r"[/()]", v[name_at] or ""))
        hit = [s for s in SCOPES if s in parts] or ["(none)"]
        out[hit[-1]] += (v[self_at] or 0.0) * 1e-6
    return dict(out.most_common())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = run.Cell(args.workload)
    run.enable_compile_cache()
    import jax
    run.device_info(cell.chips)
    r = run.Run(cell, args.seed)
    r.setup()
    log_dir = tempfile.mkdtemp(prefix="bench_phases_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = True
        r.spans.annotate = r.spans.sync = True
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            r.window(args.seconds)
        finally:
            jax.profiler.stop_trace()
        path = trace.find_xplane(log_dir)
        tr = trace.load(path)
        data = jax.profiler.ProfileData.from_file(path)
        ours = epoch_intervals(r, tr)
        theirs = annotations(data)
        starts = sorted(s[1] for s in ours if s[0] == "epoch")
        marks = sorted(s[1] for s in theirs if s[0] == "epoch")
        out = {
            "workload": cell.name, "seed": args.seed,
            "epochs": len(r.epochs),
            "window_s": (tr.window()[1] - tr.window()[0]) * 1e-9,
            "by_phase": idle_by(ours, tr, "outside epochs"),
            "align_us": max((abs(a - b) * 1e-3
                             for a, b in zip(starts, marks)), default=None),
            "by_annotation": idle_by(theirs, tr, "outside repro spans"),
            "epoch_s": epoch_seconds(r.epochs),
            "by_module": by_module(tr),
            "scopes": scope_places(data),
            "scope_s": scope_seconds(path),
        }
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

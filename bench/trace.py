"""Reduction of a profiler trace (``.xplane.pb``) to device busy time.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane.  Busy time is the union of their intervals;
idle gaps are what the union leaves out of a window.  Host spans are the
``bench.<name>`` annotations the benchmark writes (``bench/spans.py``), on
the same clock as the device events.  Nothing here depends on the
program's jit or kernel names.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    # Per device plane: (start_ns, end_ns, name) of every device op, sorted.
    ops: dict[str, list[tuple[float, float, str]]]
    # Per device plane: (start_ns, end_ns, name) of every program execution.
    modules: dict[str, list[tuple[float, float, str]]]
    # (name without the prefix, start_ns, end_ns) of every benchmark span.
    spans: list[tuple[str, float, float]]
    _merged: dict | None = None

    def span_list(self, name: str) -> list[tuple[float, float]]:
        return [(s, e) for n, s, e in self.spans if n == name]

    def window(self) -> tuple[float, float]:
        (w,) = self.span_list("window")
        return w

    def merged(self) -> dict[str, np.ndarray]:
        """Per device plane, the union of its op intervals (cached)."""
        if self._merged is None:
            self._merged = {p: union(evs) for p, evs in self.ops.items()}
        return self._merged


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {paths}")
    return paths[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: dict[str, list] = {}
    modules: dict[str, list] = {}
    spans = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((e.start_ns, e.end_ns, e.name)
                               for e in line.events)
                elif line.name == MODULES_LINE:
                    mods.extend((e.start_ns, e.end_ns, e.name)
                                for e in line.events)
            ops[plane.name] = sorted(evs)
            modules[plane.name] = sorted(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):], e.start_ns,
                                      e.end_ns))
    return Trace(ops, modules, sorted(spans, key=lambda s: s[1]))


def union(intervals) -> np.ndarray:
    """Merge (start, end, ...) intervals into a sorted disjoint (k, 2) array."""
    out: list[list[float]] = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64).reshape(-1, 2)


def overlap(merged: np.ndarray, lo: float, hi: float) -> float:
    """Length of the part of disjoint ``merged`` intervals inside [lo, hi]."""
    i = np.searchsorted(merged[:, 1], lo, side="right")
    j = np.searchsorted(merged[:, 0], hi, side="left")
    m = merged[i:j]
    return float(np.sum(np.minimum(m[:, 1], hi) - np.maximum(m[:, 0], lo)))


def gaps(merged: np.ndarray, lo: float, hi: float) -> list[tuple[float, float]]:
    """Sub-intervals of [lo, hi] that disjoint ``merged`` leaves uncovered."""
    out, t = [], lo
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def busy_ns(trace: Trace, lo: float, hi: float) -> float:
    """Busy time in [lo, hi], averaged over the device planes."""
    merged = trace.merged()
    if not merged:
        return 0.0
    return sum(overlap(m, lo, hi) for m in merged.values()) / len(merged)


def busy_in_spans(trace: Trace, name: str) -> float:
    """Device busy ns inside every ``name`` span, averaged over devices."""
    return sum(busy_ns(trace, s, e) for s, e in trace.span_list(name))


def host_activity(trace: Trace):
    """A function of an instant: the innermost benchmark span holding it."""
    groups = collections.defaultdict(list)
    for name, s, e in trace.spans:
        if name != "window":
            groups[name].append((s, e))
    index = {n: (np.array([s for s, _ in v]), v) for n, v in groups.items()}

    def at(t: float) -> str:
        best = None
        for name, (starts, spans) in index.items():
            i = int(np.searchsorted(starts, t, side="right")) - 1
            if i >= 0 and spans[i][1] >= t:
                d = spans[i][1] - spans[i][0]
                if best is None or d < best[1]:
                    best = (name, d)
        return best[0] if best else "outside spans"

    return at


def op_label(module: str, op: str) -> str:
    """``program/op`` from an XLA module event name (``jit_f(<hash>)``) and
    an op event name (its HLO text, ``%fusion.12 = f32[...] ...``)."""
    return module.split("(", 1)[0] + "/" + op.split(" = ", 1)[0].lstrip("%")


def breakdown(trace: Trace, top: int = 10) -> dict:
    """Top device ops (by program and op) by total time, and idle time by
    host activity, both in seconds over the window (first device plane)."""
    lo, hi = trace.window()
    if not trace.ops:
        return {"device_ops": [], "idle_gaps": []}
    plane = sorted(trace.ops)[0]
    mods = trace.modules.get(plane, [])
    mod_starts = np.array([m[0] for m in mods])
    per_op = collections.Counter()
    for s, e, name in trace.ops[plane]:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            i = int(np.searchsorted(mod_starts, s, side="right")) - 1
            module = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
            per_op[op_label(module, name)] += d * 1e-9
    idle = collections.Counter()
    at = host_activity(trace)
    for s, e in gaps(trace.merged()[plane], lo, hi):
        idle[at((s + e) / 2)] += (e - s) * 1e-9
    return {"device_ops": [[n, v] for n, v in per_op.most_common(top)],
            "idle_gaps": [[n, v] for n, v in idle.most_common(top)]}

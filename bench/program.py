"""What the program records about itself, as the per-layer readers of
``bench/metrics/`` read it.

- Device time of a program: the trainer's jitted programs carry declared
  XLA module names (``repro/tracing.py``: ``jit_train_block``,
  ``jit_eval_forward``, ``jit_observe_scatter``, ``jit_plan_step``); their
  executions are the events of the trace's ``XLA Modules`` line.
- Host spans: ``EpochStats.spans`` of each window epoch, the trainer's own
  intervals ``(name, parent, start, end, counts)`` and aggregates
  ``{name: (parent, count, total_s, longest_s)}`` on ``time.perf_counter``.

A program that declares no such name, or an ``EpochStats`` without
``spans``, reads as nothing (``None``), never as zero.
"""
from __future__ import annotations


def module_ns(trace, names) -> float | None:
    """Device ns of the executions of the XLA modules ``names`` inside the
    trace's window, averaged over the device planes; None where no such
    module ran in the window."""
    if trace is None or not trace.modules:
        return None
    lo, hi = trace.window()
    total, found = 0.0, False
    for mods in trace.modules.values():
        for s, e, name in mods:
            if name.split("(", 1)[0] in names and e > lo and s < hi:
                total += min(e, hi) - max(s, lo)
                found = True
    return total / len(trace.modules) if found else None


def epoch_spans(epochs) -> list[dict]:
    """The ``EpochStats.spans`` of the window's epochs that have them."""
    return [s.spans for s in epochs if getattr(s, "spans", None)]


def interval_s(spans: dict, name: str) -> float | None:
    """Seconds of the epoch's intervals named ``name``; None if none."""
    got = [end - start for n, _, start, end, _ in spans["intervals"]
           if n == name]
    return sum(got) if got else None


def aggregate_s(spans: dict, name: str) -> float | None:
    """Total seconds of the epoch's aggregate ``name``; None if absent."""
    agg = spans["aggregates"].get(name)
    return None if agg is None else agg[2]


def refreshed(epochs) -> list:
    """The window's epochs that ran step D (forwards beyond the trained)."""
    return [s for s in epochs if s.fwd_samples > s.bwd_samples]

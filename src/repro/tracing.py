"""Spans and counters the trainer records about its own work.

``span(name)`` times a block on the host clock (``time.perf_counter``) and,
while a ``jax.profiler`` trace runs, writes it into the trace as the
annotation ``repro.<name>`` beside the device events.  ``count(name, n)``
adds to a counter.  Both are recorded into the ``EpochRecord`` of the
epoch that is open (``epoch(e)``, entered by ``Trainer.run_epoch``); outside
an epoch only the annotation is written.

- Each span records its parent, the innermost span open when it started,
  and belongs to its epoch's record: the epoch number is the identifier
  every span of one epoch shares.
- Phase spans and their direct children are kept as intervals
  ``(name, parent, start, end, counts)``.  A span in ``REPEATED`` opens once
  per dispatch or per refresh batch and is kept as one aggregate
  ``(parent, count, total_s, longest_s)``, so an epoch's record grows with
  its phases and not with its steps.
- A count goes to its epoch's totals and to the ``counts`` of the innermost
  open interval.  Every compile (or load from the persistent compile cache)
  is counted as ``compiles`` and ``compile_s`` by one ``jax.monitoring``
  listener, so a recompile names its phase and epoch.

The record is always on: a span costs two clock reads, a trace annotation
that does nothing while no profiler runs, and a list push and pop.  It adds
no host sync and no device work.

The jitted programs whose device time the benchmark reads carry the XLA
module names declared below (``jit(fun, name)``), not names that follow
their Python functions.
"""
from __future__ import annotations

import contextlib
import functools
import time

import jax

# Declared XLA module names of the programs the trainer dispatches.
TRAIN_BLOCK = "jit_train_block"          # the scan engine's train block
EVAL_FORWARD = "jit_eval_forward"        # the forward-only metrics step
OBSERVE_SCATTER = "jit_observe_scatter"  # KAKURENBO's host-dispatched observe
PLAN_STEP = "jit_plan_step"              # KAKURENBO's epoch plan

# Spans opened once per dispatch or per refresh batch.
REPEATED = frozenset({"train.dispatch", "refresh.assemble",
                      "refresh.dispatch", "refresh.observe"})

# The phases of ``Trainer.run_epoch``, in order, children of ``epoch``.
PHASES = ("prepare", "plan", "train", "refresh", "eval", "finish")

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# Counted once per trace of ``models.attention.attend``, by the path taken;
# they land in the epoch that compiles.
ATTENTION_PATHS = ("attention.kernel", "attention.jnp")


def jit(fun, name: str, **kwargs):
    """``jax.jit(fun, **kwargs)`` whose XLA module is ``name`` (one of the
    constants above) instead of ``jit_<fun.__name__>``.  ``fun`` itself is
    not renamed; arguments, donation and sharding are ``jax.jit``'s."""
    named = functools.partial(fun)
    named.__name__ = named.__qualname__ = name.removeprefix("jit_")
    return jax.jit(named, **kwargs)


class EpochRecord:
    """The spans and counters of one epoch, in plain tuples and dicts."""

    def __init__(self, epoch: int):
        self.epoch = epoch
        self.start = self.end = 0.0
        self.intervals: list[tuple] = []    # (name, parent, start, end, counts)
        self.aggregates: dict[str, list] = {}  # name -> [parent, n, total, longest]
        self.counters: dict[str, float] = {}

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def spans(self) -> dict:
        """What ``EpochStats.spans`` holds."""
        return {"epoch": self.epoch, "intervals": list(self.intervals),
                "aggregates": {k: tuple(v)
                               for k, v in self.aggregates.items()}}


class _State:
    def __init__(self):
        self.record: EpochRecord | None = None
        # Open spans, innermost last: (name, counts of the innermost
        # interval, which an aggregate shares with its parent).
        self.stack: list[tuple[str, dict]] = []


_state = _State()


class span:
    """``with span(name):`` — see the module docstring.  With ``step``, the
    block is also a ``jax.profiler.StepTraceAnnotation`` named after the
    span's phase, so a profiler groups device time by step."""

    __slots__ = ("name", "step", "anns", "t0")

    def __init__(self, name: str, step: int | None = None):
        self.name, self.step = name, step

    def __enter__(self):
        stack = _state.stack
        counts = stack[-1][1] if stack and self.name in REPEATED else {}
        stack.append((self.name, counts))
        # A TraceAnnotation starts its event when it is constructed.
        self.anns = [jax.profiler.TraceAnnotation("repro." + self.name)]
        if self.step is not None:
            self.anns.append(jax.profiler.StepTraceAnnotation(
                self.name.split(".", 1)[0], step_num=self.step))
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        for ann in reversed(self.anns):
            ann.__exit__(*exc)
        _, counts = _state.stack.pop()
        stack, rec = _state.stack, _state.record
        if rec is None:
            return False
        parent = stack[-1][0] if stack else None
        if self.name in REPEATED:
            agg = rec.aggregates.get(self.name)
            d = t1 - self.t0
            if agg is None:
                rec.aggregates[self.name] = [parent, 1, d, d]
            else:
                agg[1] += 1
                agg[2] += d
                agg[3] = max(agg[3], d)
        else:
            rec.intervals.append((self.name, parent, self.t0, t1, counts))
        return False


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name`` of the open epoch and of the innermost
    open interval (nothing outside an epoch)."""
    rec = _state.record
    if rec is None:
        return
    rec.counters[name] = rec.counters.get(name, 0) + n
    if _state.stack:
        counts = _state.stack[-1][1]
        counts[name] = counts.get(name, 0) + n


@contextlib.contextmanager
def epoch(number: int):
    """The root span ``epoch``; yields the ``EpochRecord`` its spans and
    counts fill, whose ``start``/``end`` are the span's."""
    outer = _state.record
    rec = _state.record = EpochRecord(number)
    try:
        with span("epoch") as s:
            rec.start = s.t0
            yield rec
    finally:
        rec.end = rec.intervals[-1][3]   # the epoch span's own end
        _state.record = outer


def _on_duration(event: str, duration: float, **_) -> None:
    if event == COMPILE_EVENT:
        count("compiles")
        count("compile_s", duration)


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def summary(rec: EpochRecord) -> str:
    """The per-epoch log line: phase seconds, the longest train dispatch,
    compiles with the phases they fell in, and step D's work."""
    own = {name: end - start for name, parent, start, end, _ in rec.intervals
           if parent == "epoch"}
    phases = " ".join(f"{p}={own.get(p, 0.0):.4f}" for p in PHASES)
    agg = rec.aggregates
    longest = agg["train.dispatch"][3] if "train.dispatch" in agg else 0.0
    where = ",".join(f"{name}:{counts['compiles']}"
                     for name, _, _, _, counts in rec.intervals
                     if counts.get("compiles"))
    refresh = " ".join(f"{k.split('.', 1)[1]}={agg[k][2]:.4f}"
                       for k in ("refresh.assemble", "refresh.dispatch",
                                 "refresh.observe") if k in agg)
    c = rec.counters
    paths = "".join(f"{k}={int(c[k])} " for k in ATTENTION_PATHS if k in c)
    return (f"epoch {rec.epoch}: {rec.seconds:.4f} s ({phases}) "
            f"longest_dispatch={longest:.4f} "
            f"compiles={int(c.get('compiles', 0))} "
            f"compile_s={c.get('compile_s', 0.0):.3f} [{where}] {paths}"
            f"refresh_rows={int(c.get('refresh_rows', 0))} "
            f"padded_rows={int(c.get('refresh_padded_rows', 0))}"
            + (f" ({refresh})" if refresh else ""))

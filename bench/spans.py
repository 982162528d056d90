"""Host spans the benchmark records around its calls into the program.

The benchmark wraps four calls of the trainer it drives, from outside:

- ``epoch``   — ``Trainer.run_epoch`` (the bench loop calls it);
- ``plan``    — ``strategy.plan``, which ends in the plan's ``device_get``;
- ``train``   — ``engine.run_epoch``, which ends in the epoch's loss fetch;
- ``refresh`` — ``strategy.on_epoch_end`` (step D), closed in traced runs by
  ``block_until_ready`` on the strategy's device state.

Each span is kept on the host clock and, while the profiler runs, also
written into the trace as a ``jax.profiler.TraceAnnotation`` named
``bench.<name>`` so that device time can be attributed to it.  Spans are
keyed by these names, never by the program's jit names.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time

import jax


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Spans:
    def __init__(self):
        self.spans: list[Span] = []
        self.annotate = False   # also write TraceAnnotations (traced runs)
        self.sync = False       # close refresh spans with block_until_ready

    @contextlib.contextmanager
    def span(self, name: str):
        ann = (jax.profiler.TraceAnnotation("bench." + name)
               if self.annotate else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            yield
        self.spans.append(Span(name, t0, time.perf_counter()))

    def between(self, name: str, start: float, end: float) -> list[Span]:
        return [s for s in self.spans
                if s.name == name and s.start >= start and s.end <= end]

    def wrap(self, tr) -> None:
        """Record plan, train and refresh spans of trainer ``tr``."""
        strategy, engine = tr.strategy, tr.engine
        plan, train, refresh = (strategy.plan, engine.run_epoch,
                                strategy.on_epoch_end)

        def timed_plan(epoch):
            with self.span("plan"):
                return plan(epoch)

        def timed_train(*args, **kw):
            with self.span("train"):
                return train(*args, **kw)

        def timed_refresh(*args, **kw):
            with self.span("refresh"):
                n = refresh(*args, **kw)
                if self.sync:
                    jax.block_until_ready(strategy.get_device_state())
            return n

        strategy.plan = timed_plan
        engine.run_epoch = timed_train
        strategy.on_epoch_end = timed_refresh


class HostClock:
    """The host's side of the window, to find where it stops dispatching:
    each call of the engine's train block (dispatch start and return), and
    each garbage collection with its generation.  Installed around
    ``engine._block`` for the window only; two clock reads per step."""

    def __init__(self, engine):
        self.engine, self.inner = engine, engine._block
        self.calls: list[tuple[float, float]] = []
        self.gcs: list[tuple[int, float]] = []
        self._gc_start = 0.0
        engine._block = self
        gc.callbacks.append(self._on_gc)

    def __call__(self, *args):
        t0 = time.perf_counter()
        out = self.inner(*args)
        self.calls.append((t0, time.perf_counter()))
        return out

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_start = time.perf_counter()
        else:
            self.gcs.append((info["generation"],
                             time.perf_counter() - self._gc_start))

    def remove(self) -> None:
        self.engine._block = self.inner
        gc.callbacks.remove(self._on_gc)

    def summary(self, train: list[Span]) -> str:
        """Longest dispatch, longest host gap inside a ``train`` span before
        its first dispatch or between two (after the last one the host
        waits for the device), and the collections."""
        gaps, longest = [0.0], max((b - a for a, b in self.calls),
                                   default=0.0)
        for s in train:
            marks = [s.start] + [t for c in self.calls
                                 if s.start <= c[0] <= s.end for t in c]
            gaps += [marks[i + 1] - marks[i]
                     for i in range(0, len(marks) - 1, 2)]
        worst = max(self.gcs, key=lambda g: g[1], default=(None, 0.0))
        return (f"dispatches={len(self.calls)} longest_dispatch_s="
                f"{longest!r} longest_gap_s={max(gaps)!r} gc={len(self.gcs)} "
                f"longest_gc_s={worst[1]!r} longest_gc_gen={worst[0]}")

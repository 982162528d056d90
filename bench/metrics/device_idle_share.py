"""Share of the window in which no operation ran on the device (%).

1 - (union of the device op intervals / window), from the profiler trace,
averaged over the chips used.
"""
from bench.trace import busy_ns


def read(rec):
    if rec.trace is None or not rec.trace.ops:
        return None
    lo, hi = rec.trace.window()
    return 100.0 * (1.0 - busy_ns(rec.trace, lo, hi) / (hi - lo))

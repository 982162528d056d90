"""Device busy time per train step (ms).

The union of device op intervals inside the benchmark's ``train`` spans
(around the engine's ``run_epoch``, which ends in the epoch's loss fetch),
divided by the window's train steps.  Steps are the backward samples over
the batch size, padded final batches included.
"""
from bench.trace import busy_in_spans


def read(rec):
    steps = sum(s.bwd_samples for s in rec.epochs) // rec.batch
    if rec.trace is None or not rec.trace.ops or steps == 0:
        return None
    return busy_in_spans(rec.trace, "train") * 1e-6 / steps

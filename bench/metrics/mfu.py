"""Model FLOP/s utilization of the window (%).

Model FLOPs executed in the window (trained tokens x forward-plus-backward
FLOPs per token, plus refreshed tokens x forward FLOPs per token, from the
configuration's shapes by ``bench/flops.py``, no recomputation counted)
over the window's seconds times the chip's bf16 peak (``bench/peaks.py``).
Refreshed samples exclude the refresh's padding rows.
"""
from bench import flops


def read(rec):
    trained = sum(s.bwd_samples for s in rec.epochs) * rec.seq_len
    refreshed = sum(s.fwd_samples - s.bwd_samples
                    for s in rec.epochs) * rec.seq_len
    if trained == 0 or rec.peak is None:
        return None
    work = (trained * flops.train_flops_per_token(rec.arch, rec.seq_len)
            + refreshed * flops.forward_flops_per_token(rec.arch, rec.seq_len))
    return 100.0 * work / (rec.window_s * rec.peak["bf16_flops_per_s"])

"""Model FLOP/s utilization of the window (%).

Model FLOPs executed in the window over the window's seconds times the
cell's chips times one chip's bf16 peak (``bench/peaks.py``).  The FLOPs
are the configuration's own count where its module
(``bench/configs/<name>.py``) defines ``window_flops(rec) -> (trained,
refreshed)``; otherwise trained tokens x forward-plus-backward FLOPs per
token plus refreshed tokens x forward FLOPs per token, from the
configuration's shapes by ``bench/flops.py``.  No recomputation is counted,
and refreshed samples exclude the refresh's padding rows.
"""
from bench import flops


def read(rec):
    trained = sum(s.bwd_samples for s in rec.epochs) * rec.seq_len
    refreshed = sum(s.fwd_samples - s.bwd_samples
                    for s in rec.epochs) * rec.seq_len
    if trained == 0 or rec.peak is None:
        return None
    count = getattr(rec.model_ref, "window_flops", None)
    if count is not None:
        work = sum(count(rec))
    else:
        work = (trained * flops.train_flops_per_token(rec.arch, rec.seq_len)
                + refreshed * flops.forward_flops_per_token(rec.arch,
                                                            rec.seq_len))
    return 100.0 * work / (rec.window_s * rec.chips
                           * rec.peak["bf16_flops_per_s"])

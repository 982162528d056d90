"""Host time of one step-D refresh (ms): the mean span around
``strategy.on_epoch_end`` over the window's epochs that refreshed, closed
by ``block_until_ready`` on the strategy's device state (traced runs only).
"""


def read(rec):
    refreshed = [s for s in rec.epochs if s.fwd_samples > s.bwd_samples]
    spans = rec.spans.between("refresh", *rec.window)
    if not refreshed or not spans:
        return None
    return 1e3 * sum(s.seconds for s in spans) / len(spans)

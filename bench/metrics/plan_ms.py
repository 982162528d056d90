"""Host time of one epoch plan (ms): the mean span around
``strategy.plan`` over the window's epochs; the span ends in the plan's own
``device_get``."""


def read(rec):
    spans = rec.spans.between("plan", *rec.window)
    if not spans:
        return None
    return 1e3 * sum(s.seconds for s in spans) / len(spans)

"""Host time of the plan's one ``device_get`` (ms): the trainer's
``plan.fetch`` span, averaged over the window's epochs.  In a traced run
the benchmark has already waited for the refresh's device work, so the
fetch waits for the plan step alone."""
from bench import program


def read(rec):
    got = [program.interval_s(s, "plan.fetch")
           for s in program.epoch_spans(rec.epochs)]
    got = [t for t in got if t is not None]
    if not got:
        return None
    return 1e3 * sum(got) / len(got)

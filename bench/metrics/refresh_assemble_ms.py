"""Host time spent building step D's batches (ms): the trainer's
``refresh.assemble`` spans around the corpus's ``get`` of each refresh
batch, summed per epoch, averaged over the window's epochs that refreshed."""
from bench import program


def read(rec):
    totals = [program.aggregate_s(s, "refresh.assemble")
              for s in program.epoch_spans(program.refreshed(rec.epochs))]
    totals = [t for t in totals if t is not None]
    if not totals:
        return None
    return 1e3 * sum(totals) / len(totals)

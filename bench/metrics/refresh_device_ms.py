"""Device time of one step-D refresh (ms): the executions of the trainer's
forward-only step (``jit_eval_forward``) and of KAKURENBO's observe scatter
(``jit_observe_scatter``) inside the window, over the window's epochs that
refreshed.  The cells evaluate no test set, so every forward-only step in
the window is step D's."""
from bench import program


def read(rec):
    epochs = program.refreshed(rec.epochs)
    ns = program.module_ns(rec.trace, ("jit_eval_forward",
                                       "jit_observe_scatter"))
    if not epochs or ns is None:
        return None
    return ns * 1e-6 / len(epochs)

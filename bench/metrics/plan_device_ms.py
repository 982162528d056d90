"""Device time of one epoch plan (ms): the executions of KAKURENBO's plan
step (``jit_plan_step``: selection, move-back and the epoch's shuffle)
inside the window, over the window's epochs."""
from bench import program


def read(rec):
    ns = program.module_ns(rec.trace, ("jit_plan_step",))
    if not rec.epochs or ns is None:
        return None
    return ns * 1e-6 / len(rec.epochs)

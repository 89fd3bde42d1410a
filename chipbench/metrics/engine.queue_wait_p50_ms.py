"""Median wait of a request in the engine's queue: from its due time on
the arrival schedule to the start of the step that admitted it (host
clock), over the requests due in the window."""
import numpy as np


def read(ctx):
    w = ctx.window
    waits = [(s.admitted_step - (w.t0 + s.due)) * 1e3 for s in w.served
             if s.due < w.seconds and s.admitted_step is not None]
    return float(np.median(waits)) if waits else None

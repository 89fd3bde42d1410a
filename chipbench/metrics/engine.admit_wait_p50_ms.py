"""Median wait of a request for its own prefill: from its due time to the
start of its first prefill call on the engine's stamp
(``Request.t_prefill``; both on the host clock), over the requests due in
the window.  Unlike engine.queue_wait_p50_ms, which ends at the start of
the admitting step, it also counts the admission and the prefills that
ran before the request's own in that step.  None where the program sets
no such stamp."""
import numpy as np


def read(ctx):
    w = ctx.window
    waits = [(s.request.t_prefill - (w.t0 + s.due)) * 1e3
             for s in w.served if s.due < w.seconds
             and getattr(s.request, "t_prefill", None) is not None]
    return float(np.median(waits)) if waits else None

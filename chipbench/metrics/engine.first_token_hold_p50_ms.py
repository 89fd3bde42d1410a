"""Median time a request's first token waits on the host before the
caller gets it: from the engine's stamp of the token's arrival on the
host (``Request.t_first``) to the end of the step that returned it (host
clock), over the requests due in the window.  The token arrives during
admission and is returned after that step's inserts and decode.  None
where the program sets no such stamp."""
import numpy as np


def read(ctx):
    w = ctx.window
    holds = [(s.token_times[0] - s.request.t_first) * 1e3
             for s in w.served if s.due < w.seconds and s.token_times
             and getattr(s.request, "t_first", None) is not None]
    return float(np.median(holds)) if holds else None

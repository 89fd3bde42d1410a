"""Share of the prefill tokens dispatched that are padding: the engine's
counters (``prefill_tokens_padded`` and ``prefill_tokens_true`` of
``Engine.stats()``) from the start of the window to the end of its
drain.  None where the program keeps no such counters."""


def read(ctx):
    w = ctx.window
    try:
        padded = (w.traces_after["prefill_tokens_padded"]
                  - w.traces_before["prefill_tokens_padded"])
        true = (w.traces_after["prefill_tokens_true"]
                - w.traces_before["prefill_tokens_true"])
    except KeyError:
        return None
    return 100.0 * (padded - true) / padded if padded else None

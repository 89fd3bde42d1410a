"""Share of the chip's peak that the prefills' needed work took: needed
operations of the window's prompts (every layer for every prompt token,
causal attention, the head for the last token; pruned weights' nonzero
tiles, activations dense) over the prefill program's device time x peak."""
import work


def read(ctx):
    p = ctx.trace["programs"]["prefill"]
    w = ctx.window
    lengths = [n for t in work.in_window(w.ticks, w.t0, w.seconds)
               for n in t.prefill_lengths]
    if not p["device_s"] or not lengths:
        return None
    wk = ctx.work()
    flops = sum(wk.prefill_flops(n) for n in lengths)
    return 100.0 * flops / (p["device_s"] * ctx.peaks["bf16_flops_per_s"])

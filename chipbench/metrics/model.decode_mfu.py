"""Share of the chip's peak that the decode steps' needed work took:
needed operations of the window's decode steps (every layer and the head
for each decoded token, attention over its keys; pruned weights' nonzero
tiles, activations dense) over the decode program's device time x peak."""
import work


def read(ctx):
    p = ctx.trace["programs"]["decode"]
    w = ctx.window
    ticks = [t for t in work.in_window(w.ticks, w.t0, w.seconds)
             if t.decode_keys]
    if not p["device_s"] or not ticks:
        return None
    wk = ctx.work()
    flops = sum(wk.decode_flops(t.decode_keys) for t in ticks)
    return 100.0 * flops / (p["device_s"] * ctx.peaks["bf16_flops_per_s"])

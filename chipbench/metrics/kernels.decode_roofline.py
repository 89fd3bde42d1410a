"""Roofline share of the Pallas kernels in the decode steps: the least
time of the work of the sites the decode program runs as kernels (each
the larger of operations over peak and bytes over bandwidth: projections
over all serving slots, cache attention over each decoded token's keys)
over the device time of the kernel ops inside the decode program."""
import work


def read(ctx):
    p = ctx.trace["programs"]["decode"]
    w = ctx.window
    ticks = [t for t in work.in_window(w.ticks, w.t0, w.seconds)
             if t.decode_keys]
    if not p["kernel_s"] or not ticks:
        return None
    wk = ctx.work()
    sites = ctx.program["kernel_sites"]["decode"]
    slots = ctx.cell.spec["engine"]["slots"]
    least = sum(wk.kernels_least_s(sites, slots, t.decode_keys, ctx.peaks)
                for t in ticks)
    return 100.0 * least / p["kernel_s"]

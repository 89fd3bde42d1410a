"""Roofline share of the Pallas kernels in the prefills: the least time
of the work of the sites the prefill program runs as kernels, over every
dispatched (batch x padded length) row, over the device time of the
kernel ops inside the prefill program."""
import work


def read(ctx):
    p = ctx.trace["programs"]["prefill"]
    w = ctx.window
    eng = ctx.cell.spec["engine"]
    calls = [c for t in work.in_window(w.ticks, w.t0, w.seconds)
             for c in work.prefill_calls(t.prefill_lengths,
                                         eng["prefill_bucket"],
                                         eng["max_prefill_batch"])]
    if not p["kernel_s"] or not calls:
        return None
    wk = ctx.work()
    sites = ctx.program["kernel_sites"]["prefill"]
    least = sum(wk.kernels_least_s(sites, n * lpad, [], ctx.peaks)
                for n, lpad in calls)
    return 100.0 * least / p["kernel_s"]

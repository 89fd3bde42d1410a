"""Device time of one decode step: the decode program's device time in
the traced window over its calls there."""


def read(ctx):
    p = ctx.trace["programs"]["decode"]
    return p["device_s"] / p["calls"] * 1e3 if p["calls"] else None

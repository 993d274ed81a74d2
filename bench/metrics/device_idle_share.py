"""Share of the traced window in which no op ran on the device."""

from benchlib import trace


def read(ctx):
    if ctx.trace is None:
        return None
    busy = trace.busy_ns(ctx.trace["device"], ctx.lo, ctx.hi)
    return 100.0 * (1.0 - busy / (ctx.hi - ctx.lo))

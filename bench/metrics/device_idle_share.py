"""Share of the traced window in which no op ran on a chip, averaged
over the cell's chips."""

from benchlib import trace


def read(ctx):
    if ctx.trace is None:
        return None
    busy = trace.busy_ns_per_device(ctx.trace["by_device"], ctx.lo, ctx.hi)
    return 100.0 * (1.0 - busy / (ctx.hi - ctx.lo))

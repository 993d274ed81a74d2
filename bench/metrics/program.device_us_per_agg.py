"""Device busy time in the traced window (one chip's, averaged over the
cell's chips) per aggregation completed."""

from benchlib import trace


def read(ctx):
    if ctx.trace is None or not ctx.aggs:
        return None
    busy = trace.busy_ns_per_device(ctx.trace["by_device"], ctx.lo,
                                    ctx.hi) / 1e3
    return busy / ctx.aggs if busy > 0 else None

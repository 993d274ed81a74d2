"""Device busy time in the traced window per aggregation completed."""

from benchlib import trace


def read(ctx):
    if ctx.trace is None or not ctx.aggs:
        return None
    busy = trace.busy_ns(ctx.trace["device"], ctx.lo, ctx.hi) / 1e3
    return busy / ctx.aggs if busy > 0 else None

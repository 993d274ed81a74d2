"""Required model operations per second of the traced window over the
chip's peak: forward and backward of every local step within its chosen
interval, the aggregation and the per-aggregation eval metric, counted
from shapes (``benchlib.counts``)."""

from benchlib import counts


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    flops = sum(counts.required_flops(ctx.cfg, ctx.ref, r["run"]["mode"],
                                      r["record"]["interval"])
                for r in ctx.rows)
    window_s = (ctx.hi - ctx.lo) / 1e9
    share = 100.0 * flops / window_s / float(ctx.peak["flops_per_s"])
    return share if share > 0 else None

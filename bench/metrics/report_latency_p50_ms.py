"""Median time from when each tenant was due (open loop) to its
``ReportReady``, over every tenant due in the window."""

import numpy as np


def read(ctx):
    if ctx.latencies_ms is None or not len(ctx.latencies_ms):
        return None
    return float(np.percentile(ctx.latencies_ms, 50))

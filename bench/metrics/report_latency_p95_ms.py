"""95th percentile of the due-to-report time over every tenant due in
the window; a tenant with no report counts as late as the run's end."""

import numpy as np


def read(ctx):
    if ctx.latencies_ms is None or not len(ctx.latencies_ms):
        return None
    return float(np.percentile(ctx.latencies_ms, 95))

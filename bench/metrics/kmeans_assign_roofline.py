"""The ``kmeans_assign`` kernel's share of its roofline.

Every launch of the kernel in the traced window is found by what a TPU
trace shows of it: a Pallas kernel runs as a TPU custom call (the op's
HLO text holds ``custom-call(``), and the E-step kernel's operands are
points ``[..., n, D]`` and centroids ``[..., K, D]`` in float32, at the
configuration's ``D`` and ``K``.  Those shapes give what the launch needs
(``benchlib.counts.kmeans_assign_launch``: the points it reads, the
centroids, the two ``[..., n]`` outputs); the least time the v5e could
take for that is the larger of operations over the FLOP peak and bytes
over the HBM peak; at these shapes the bytes bound it.  The share is
that least time, summed over launches, over the launches' device time.
A custom call without those operands is another kernel and is not
counted; a window with no launch leaves the metric out.
"""

import math
import re

from benchlib import counts, trace

KERNEL = " custom-call("


def _launch(text, d, k):
    dims = [tuple(int(x) for x in m.split(","))
            for m in re.findall(r"f32\[([0-9,]+)\]", text)]
    points = [s for s in dims if len(s) >= 2 and s[-1] == d and s[-2] != k]
    cents = [s for s in dims if len(s) >= 2 and s[-1] == d and s[-2] == k]
    if not points or not cents:
        return None
    rows = max(math.prod(s[:-1]) for s in points)
    return counts.kmeans_assign_launch(rows, d, k,
                                       math.prod(cents[0][:-2]))


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    d, k = ctx.cfg["features"], ctx.cfg["classes"]
    meta = ctx.trace["meta"]
    evs = trace.kernel_events(ctx.trace["device"], meta, KERNEL, ctx.lo,
                              ctx.hi)
    least = spent = 0.0
    for s, e, name in evs:
        need = _launch(name + " " + meta.get(name, ""), d, k)
        if need is None:
            continue
        least += counts.roofline_seconds(need["flops"], need["bytes"],
                                         ctx.peak)[0]
        spent += (e - s) / 1e9
    return 100.0 * least / spent if spent > 0 else None

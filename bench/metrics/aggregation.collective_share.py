"""Share of a chip's busy time in the traced window spent in cross-chip
collectives (all-to-all, all-gather, all-reduce, reduce-scatter,
collective-permute, and their asynchronous start/done halves), averaged
over the cell's chips: what the aggregation's exchange costs the chips
beside their local steps.  A trace with no collective leaves the metric
out (a one-chip program has none)."""

import re

from benchlib import trace

#: a collective's instruction name: XLA's own (``all-gather.3``,
#: ``all-reduce-start.1``) or JAX's (``all_to_all.2168``)
COLLECTIVE = re.compile(r"all[-_]to[-_]all|all[-_]gather|all[-_]reduce"
                        r"|reduce[-_]scatter|collective[-_]permute")


def read(ctx):
    if ctx.trace is None:
        return None
    shares, found = [], False
    for evs in ctx.trace["by_device"]:
        busy = trace.busy_ns(evs, ctx.lo, ctx.hi)
        coll = trace.union([ev for ev in trace.leaves(evs)
                            if COLLECTIVE.search(trace.op_name(ev[2]))],
                           ctx.lo, ctx.hi)
        found |= bool(coll)
        if busy > 0:
            shares.append(sum(e - s for s, e in coll) / busy)
    if not found or not shares:
        return None
    return 100.0 * sum(shares) / len(shares)

"""Device busy time in the traced window (one chip's, averaged over the
cell's chips) per local step a chip ran: the steps are the program's own
counter, the local steps each edge trained, which the session's
``session.records`` span carries (``edge_steps``), summed over the
window's calls and divided over the chips.  A program without the
counter, or a trace with no device op, leaves the metric out."""

from benchlib import trace


def read(ctx):
    if ctx.trace is None:
        return None
    steps = sum(sum(s["edge_steps"]) for s in ctx.spans
                if s.get("name") == "session.records" and "edge_steps" in s)
    if not steps:
        return None
    busy = trace.busy_ns_per_device(ctx.trace["by_device"], ctx.lo, ctx.hi)
    return busy / 1e6 / (steps / ctx.cell["chips"]) if busy > 0 else None

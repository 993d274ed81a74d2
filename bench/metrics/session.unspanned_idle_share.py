"""Share of the traced window's device-idle time in which no stage span
of the session is open: the idle that the program's spans leave
unexplained.  A stage span is a host span named ``session.*`` other
than the root ``session.call``, so idle inside a call but between its
stages counts as unexplained, as does idle outside any call.  Idle is
each chip's own, summed over the cell's chips.  A program without the
root span has no stage spans to read, and reads nothing."""

from benchlib import trace

ROOT = "session.call"


def overlap_ns(a, b):
    """Length of the intersection of two sorted lists of disjoint
    ``(start, end)`` intervals."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, e - s)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(ctx):
    if ctx.trace is None:
        return None
    host = ctx.trace["host"]
    if not any(name == ROOT for _, _, name in host):
        return None
    stages = trace.union([h for h in host if h[2].startswith("session.")
                          and h[2] != ROOT], ctx.lo, ctx.hi)
    staged = sum(e - s for s, e in stages)
    idle = covered = 0
    for device in ctx.trace["by_device"]:
        busy = trace.union(device, ctx.lo, ctx.hi)
        idle += ctx.hi - ctx.lo - sum(e - s for s, e in busy)
        covered += staged - overlap_ns(stages, busy)
    if idle <= 0:
        return None
    return 100.0 * (idle - covered) / idle

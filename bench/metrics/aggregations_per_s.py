"""Aggregations completed per second of the window: every sync round,
async merge event and sweep-cell round of every call in the window, over
the window from the first call's start to the end of the last call begun
before ``--seconds`` (host work between calls included)."""


def read(ctx):
    return ctx.aggs / ctx.window_s

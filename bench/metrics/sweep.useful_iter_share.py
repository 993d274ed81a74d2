"""Aggregations the sweep completed over the rounds its vmapped loop ran
for every cell (cells x iterations of the longest cell): the loop's
padding waste under unequal budgets."""


def read(ctx):
    ran = sum(c.get("cells", 0) * c.get("iters", 0) for c in ctx.calls)
    return 100.0 * ctx.aggs / ran if ran else None

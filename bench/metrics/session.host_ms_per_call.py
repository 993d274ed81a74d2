"""Mean host time of one ``run_*_ingraph`` / ``sweep`` call outside its
``session.dispatch`` span(s): history records, ``ex.evaluate``, the
report, the sweep's host ``score_final_params``."""


def read(ctx):
    if not ctx.calls:
        return None
    total = sum(c["t1"] - c["t0"] for c in ctx.calls)
    dispatch = sum(s["dur_us"] for s in ctx.spans
                   if s.get("name") == "session.dispatch") / 1e6
    return (total - dispatch) / len(ctx.calls) * 1e3

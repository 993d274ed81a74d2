"""Mean host time per call in the session's ``session.evaluate`` stage:
``ex.evaluate`` of a run's final params, or the sweep's host scoring of
every cell's final params."""


def read(ctx):
    spans = [s["dur_us"] for s in ctx.spans
             if s.get("name") == "session.evaluate"]
    if not ctx.calls or not spans:
        return None
    return sum(spans) / len(ctx.calls) / 1e3

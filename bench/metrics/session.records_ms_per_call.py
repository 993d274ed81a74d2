"""Mean host time per call in the session's ``session.records`` stage:
the ``RoundRecord`` of every round or event read back from the run's
history arrays, and the round callbacks (single runs only)."""


def read(ctx):
    spans = [s["dur_us"] for s in ctx.spans
             if s.get("name") == "session.records"]
    if not ctx.calls or not spans:
        return None
    return sum(spans) / len(ctx.calls) / 1e3

"""Serving layer: 95th percentile of the query:queue-wait spans of the
window (admitted-to-scheduled wait behind the executor threads)."""


def read(ctx):
    waits = [d for s in ctx["request_spans"].values()
             for d in s.get("query:queue-wait", ())]
    return ctx["percentile"](waits, 0.95) if waits else None

"""Planner layer: the query:plan spans of the window over its queries."""


def read(ctx):
    per = ctx["request_spans"]
    plans = [sum(s["query:plan"]) for s in per.values() if "query:plan" in s]
    return sum(plans) / len(plans) if plans else None

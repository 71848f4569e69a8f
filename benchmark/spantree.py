"""The program's spans as one tree per request, for the span readers of
layer_metrics/ that need more than a duration per name (spanred.by_request
gives that): intervals, parents, and what a parent's children leave
uncovered.

A request of the window is tied to its trace as in spanred: by the name the
harness gave it, which ``attrs.query`` of its spans carries. A program that
records none of the spans a reader asks for (the parent of the PR that
added them) yields empty lists, and the reader returns None."""
import statistics

from tracered import union

IDS = ("trace_id", "span_id", "parent_id")


def by_request(spans: list, requests: list) -> dict:
    """{request name: [span]} for requests of the window that answered;
    a span is {"name", "start", "end" (ns), "id", "parent", "attrs"}."""
    traces, named = {}, {}
    for e in spans:
        args = e.get("args") or {}
        tid = args.get("trace_id")
        if tid is None:
            continue
        traces.setdefault(tid, []).append({
            "name": e["name"], "start": e["start_ns"],
            "end": e["start_ns"] + e["dur_ns"], "id": args.get("span_id"),
            "parent": args.get("parent_id"),
            "attrs": {k: v for k, v in args.items() if k not in IDS}})
        if args.get("query") is not None:
            named[tid] = args["query"]
    wanted = {r["name"] for r in requests}
    return {named[t]: tree for t, tree in traces.items()
            if named.get(t) in wanted}


def named(tree: list, name: str) -> list:
    return [s for s in tree if s["name"] == name]


def total_ms(tree: list, name: str) -> float:
    return sum(s["end"] - s["start"] for s in named(tree, name)) / 1e6


def self_ms(span: dict, others: list) -> float:
    """The part of ``span`` that none of ``others`` covers."""
    lo, hi = span["start"], span["end"]
    covered = union([(max(o["start"], lo), min(o["end"], hi))
                     for o in others if o["end"] > lo and o["start"] < hi])
    return (hi - lo - sum(e - s for s, e in covered)) / 1e6


def per_request(ctx, name: str, value) -> list:
    """[value(tree)] over the window's requests whose trace holds a span
    called ``name``."""
    trees = by_request(ctx["spans"], ctx["requests"]).values()
    return [value(t) for t in trees if named(t, name)]


def mean(values: list):
    return sum(values) / len(values) if values else None


def median(values: list):
    return statistics.median(values) if values else None

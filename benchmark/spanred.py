"""Reduction of the program's spans (obs/span.py, captured through
utils/tracing while the window runs) for the span readers of layer_metrics/:
the spans of the window, grouped
by request. A request is tied to its trace by the name the harness gave it
(``attrs.query`` of net:accept, query:submit, query:queue-wait and
query:execute carry it)."""


def by_request(spans: list, requests: list) -> dict:
    """{request name: {span name: [dur_ms, ...]}} for requests of the
    window that answered."""
    traces, named = {}, {}
    for e in spans:
        args = e.get("args") or {}
        tid = args.get("trace_id")
        if tid is None:
            continue
        traces.setdefault(tid, {}).setdefault(e["name"], []).append(
            e["dur_ns"] / 1e6)
        if "query" in args:
            named[tid] = args["query"]
    wanted = {r["name"] for r in requests}
    return {named[t]: spans for t, spans in traces.items()
            if named.get(t) in wanted}

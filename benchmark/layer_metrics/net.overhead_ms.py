"""Wire layer: median over the window's requests of the client's submit
wall minus that request's query:queue-wait and query:execute spans: what
framing, pickling the plan, the front-end's threads and streaming the Arrow
result add around the server's own work."""
import statistics


def read(ctx):
    spans = ctx["request_spans"]
    over = []
    for r in ctx["requests"]:
        s = spans.get(r["name"])
        if s and "query:execute" in s and "query:queue-wait" in s:
            over.append((r["t1"] - r["t0"]) * 1e3
                        - sum(s["query:execute"]) - sum(s["query:queue-wait"]))
    return statistics.median(over) if over else None

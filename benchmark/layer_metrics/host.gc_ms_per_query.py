"""Host (process) layer: the garbage-collection pauses of the window (the
program's host:gc events, which belong to no request's trace: a pause stops
every thread) summed, over the requests answered in the window. A program
whose spans carry no cpu_ns has no such events and reads nothing; one that
has them and collected nothing reads 0."""


def read(ctx):
    spans = ctx["spans"]
    if not ctx["requests"] or not any(
            "cpu_ns" in (e.get("args") or {}) for e in spans):
        return None
    return (sum(e["dur_ns"] for e in spans if e["name"] == "host:gc")
            / 1e6 / len(ctx["requests"]))

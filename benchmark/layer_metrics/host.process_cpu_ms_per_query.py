"""Host (process) layer: CPU time of every thread of the process per
request (JAX's runtime threads, the client's, the front-end's and the
executor's): the last minus the first proc_cpu_ns that the window's
query:execute spans took at their close, over the requests that closed
after the first and up to the last. A program whose query:execute carries no
proc_cpu_ns reads nothing."""
import spantree


def read(ctx):
    trees = spantree.by_request(ctx["spans"], ctx["requests"]).values()
    closes = sorted((s["end"], s["attrs"]["proc_cpu_ns"]) for t in trees
                    for s in spantree.named(t, "query:execute")
                    if "proc_cpu_ns" in s["attrs"])
    if len(closes) < 2:
        return None
    return (closes[-1][1] - closes[0][1]) / (len(closes) - 1) / 1e6

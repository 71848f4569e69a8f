"""Host (process) layer: the part of query:execute's wall in which the
executor thread neither ran nor waited on the device, mean over the
window's requests: waiting for the GIL, a lock or a core. Of the wall, take
out the thread's CPU time (cpu_ns) and the union of the exec:host-sync spans
below it, at any depth; a sync's own CPU time (its cpu_ns: the call and the
copy) lies in both, so it is given back once:

    off-CPU = wall - cpu_ns - union(syncs) + sum(syncs' cpu_ns)

A program whose spans carry no cpu_ns reads nothing."""
import spantree
from tracered import union


def _below(tree, root):
    """The spans under ``root``, at any depth."""
    kids = {}
    for s in tree:
        kids.setdefault(s["parent"], []).append(s)
    out, todo = [], [root["id"]]
    while todo:
        for s in kids.get(todo.pop(), ()):
            out.append(s)
            todo.append(s["id"])
    return out


def _offcpu(tree):
    total = 0.0
    for ex in spantree.named(tree, "query:execute"):
        cpu = ex["attrs"].get("cpu_ns")
        if cpu is None:
            return None
        lo, hi = ex["start"], ex["end"]
        syncs = [s for s in _below(tree, ex) if s["name"] == "exec:host-sync"
                 and s["end"] > lo and s["start"] < hi]
        waited = union([(max(s["start"], lo), min(s["end"], hi))
                        for s in syncs])
        total += (hi - lo - cpu - sum(e - s for s, e in waited)
                  + sum(s["attrs"].get("cpu_ns", 0) for s in syncs)) / 1e6
    return total


def read(ctx):
    return spantree.mean([v for v in spantree.per_request(
        ctx, "query:execute", _offcpu) if v is not None])

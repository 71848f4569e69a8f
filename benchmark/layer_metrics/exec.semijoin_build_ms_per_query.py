"""Operator layer: milliseconds a request spends under exec:join-build
spans whose path is exists: the build side of a semi or anti join taken
into its fused stage, executed batch by batch (Q4: the late lineitem's 58
batches), its key range read in one sync and its keys marked in a presence
map of a byte a possible key; the union of such spans, as
exec.join_build_ms_per_query takes it. Read over the requests that hold
such a span: a program without the exists form (the parent of the change
that added it) records none, and the reader reads nothing."""
import spantree
from tracered import union


def _exists(tree):
    return [s for s in spantree.named(tree, "exec:join-build")
            if s["attrs"].get("path") == "exists"]


def _covered_ms(tree):
    return sum(e - s for s, e in union(
        [(x["start"], x["end"]) for x in _exists(tree)])) / 1e6


def read(ctx):
    trees = spantree.by_request(ctx["spans"], ctx["requests"]).values()
    return spantree.mean([_covered_ms(t) for t in trees if _exists(t)])

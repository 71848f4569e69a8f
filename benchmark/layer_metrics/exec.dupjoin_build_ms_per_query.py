"""Operator layer: milliseconds a request spends under exec:join-build
spans whose path is runs: the build side of a join whose keys repeat,
executed (Q12: the filtered lineitem's 58 batches), refused by the dense
and unique tables, sorted into one run a key and its heads' table made,
host syncs included; the union of such spans, as
exec.join_build_ms_per_query takes it. Read over the requests that hold
such a span: a program without the runs rung (the parent of the PR that
added it) records none, and the reader reads nothing."""
import spantree
from tracered import union


def _runs(tree):
    return [s for s in spantree.named(tree, "exec:join-build")
            if s["attrs"].get("path") == "runs"]


def _covered_ms(tree):
    return sum(e - s for s, e in union(
        [(x["start"], x["end"]) for x in _runs(tree)])) / 1e6


def read(ctx):
    trees = spantree.by_request(ctx["spans"], ctx["requests"]).values()
    return spantree.mean([_covered_ms(t) for t in trees if _runs(t)])

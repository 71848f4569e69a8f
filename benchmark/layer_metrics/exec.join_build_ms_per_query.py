"""Operator layer: milliseconds a request spends under exec:join-build
spans, over the window's requests: executing each hash join's build side
and constructing its probe structure (dense table, bucketed unique table),
host syncs included. A build in another join's build subtree lies inside
that join's span, so the spans' union is measured, not their sum."""
import spantree
from tracered import union


def _covered_ms(tree):
    spans = spantree.named(tree, "exec:join-build")
    return sum(e - s for s, e in union([(x["start"], x["end"])
                                        for x in spans])) / 1e6


def read(ctx):
    return spantree.mean(spantree.per_request(
        ctx, "exec:join-build", _covered_ms))

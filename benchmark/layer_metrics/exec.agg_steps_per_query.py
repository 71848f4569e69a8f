"""Operator layer: exec:agg-step spans per request of the window: window
dispatches of the fused stage's streaming aggregate (exec/fused.py), each
one launch of a step program over up to a window of batches. Beside
exec.launches_per_query it says how many of a query's launches the
aggregate's windows are."""
import spantree


def read(ctx):
    return spantree.mean(spantree.per_request(
        ctx, "exec:agg-step",
        lambda t: len(spantree.named(t, "exec:agg-step"))))

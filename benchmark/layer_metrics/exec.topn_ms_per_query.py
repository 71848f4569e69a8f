"""Operator layer: summed duration of a request's exec:topn spans, over the
window's requests: the host's side of the top-N dispatches of an ORDER BY
... LIMIT (one per input batch of the operator, one more where several
partial results are reduced); the device's side is in the trace."""
import spantree


def read(ctx):
    return spantree.mean(spantree.per_request(
        ctx, "exec:topn", lambda t: spantree.total_ms(t, "exec:topn")))

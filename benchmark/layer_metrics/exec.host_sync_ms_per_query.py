"""Operator layer: summed duration of a request's exec:host-sync spans, over
the window's requests: how long the host thread sat blocked on the device
(waiting for what the value depends on, then the copy), not dispatching."""
import spantree


def read(ctx):
    return spantree.mean(spantree.per_request(
        ctx, "exec:host-sync",
        lambda t: spantree.total_ms(t, "exec:host-sync")))

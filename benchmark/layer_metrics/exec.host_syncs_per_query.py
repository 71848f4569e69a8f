"""Operator layer: exec:host-sync spans per request of the window: blocking
device->host reads on the query path (utils/sync.host_get), each a point
where the host thread stops dispatching."""
import spantree


def read(ctx):
    return spantree.mean(spantree.per_request(
        ctx, "exec:host-sync",
        lambda t: len(spantree.named(t, "exec:host-sync"))))

"""Wire layer: median over the window's requests of net:request's self
time: the client's whole submit minus the union of every other span of the
request's trace. What the spans still do not cover: 0 when the request is
tiled, and the size of the hole when it is not."""
import spantree


def _self(tree):
    root = spantree.named(tree, "net:request")[0]
    return spantree.self_ms(root, [s for s in tree if s is not root])


def read(ctx):
    return spantree.median(spantree.per_request(ctx, "net:request", _self))

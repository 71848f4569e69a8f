"""Operator layer: query:readback minus its exec:host-sync children, per
request of the window: the host-side Arrow assembly of the result
(batch_to_arrow after the copy has landed)."""
import spantree


def _assembly(tree):
    syncs = spantree.named(tree, "exec:host-sync")
    return sum(spantree.self_ms(r, [s for s in syncs if s["parent"] == r["id"]])
               for r in spantree.named(tree, "query:readback"))


def read(ctx):
    return spantree.mean(spantree.per_request(ctx, "query:readback",
                                              _assembly))

"""Planner layer: query:finish per request of the window: profile close-out,
autotune feedback and its file write, cleanup walk, leak audit, all on the
executor thread after the last batch, while the client waits."""
import spantree


def read(ctx):
    return spantree.mean(spantree.per_request(
        ctx, "query:finish", lambda t: spantree.total_ms(t, "query:finish")))

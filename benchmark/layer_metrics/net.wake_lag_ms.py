"""Wire layer: median over the window's requests of net:wake-lag, the time
between the ticket resolving on the executor thread and the front-end's
_await_result noticing (its poll): measured by the program, not inferred
from latencies that come in ticks."""
import spantree


def read(ctx):
    return spantree.median(spantree.per_request(
        ctx, "net:wake-lag", lambda t: spantree.total_ms(t, "net:wake-lag")))

"""Host (process) layer: the executor thread's own CPU time inside
query:execute (its cpu_ns attr, time.thread_time_ns), mean over the window's
requests: planning, dispatch, readback and close-out, and none of the time
the thread waited. A program whose spans carry no cpu_ns reads nothing."""
import spantree


def _cpu(tree):
    cpu = [s["attrs"].get("cpu_ns") for s in
           spantree.named(tree, "query:execute")]
    return None if None in cpu else sum(cpu) / 1e6


def read(ctx):
    return spantree.mean([v for v in spantree.per_request(
        ctx, "query:execute", _cpu) if v is not None])

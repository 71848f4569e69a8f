"""Host (process) layer: the CPU time of the harness's in-process client
thread inside net:request (its cpu_ns attr), mean over the window's
requests: pickling the plan and decoding the Arrow result. A deployed server
does not pay it, but here it competes with the executor for the GIL. A
program whose spans carry no cpu_ns reads nothing."""
import spantree


def _cpu(tree):
    cpu = [s["attrs"].get("cpu_ns") for s in
           spantree.named(tree, "net:request")]
    return None if None in cpu else sum(cpu) / 1e6


def read(ctx):
    return spantree.mean([v for v in spantree.per_request(
        ctx, "net:request", _cpu) if v is not None])

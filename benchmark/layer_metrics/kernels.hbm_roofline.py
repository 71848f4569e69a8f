"""Kernel layer: the least time the chip could take for the traced
queries, which is the bytes each must read and write (queries/<q>.py
least_bytes: pruned input columns once at device width, plus the result;
from table shapes only) over the chip's HBM peak, as a share of the time
the device was busy in the traced window. Bandwidth bounds every query of
this engine: the arithmetic is a few operations per value read."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["busy_s"] or not tr["queries"]:
        return None
    need = sum(f * ctx["queries"][q].least_bytes(ctx["rows"], ctx["width"])
               for q, f in tr["fractions"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / tr["busy_s"]

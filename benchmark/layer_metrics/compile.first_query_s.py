"""Compile layer: summed wall of the warm-up's first submit of each of the
cell's queries (upload of its tables, trace, compile or cache load)."""


def read(ctx):
    return sum(ctx["first_query_s"].values())

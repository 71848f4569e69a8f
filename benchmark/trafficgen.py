"""The one general traffic generator. A traffic mix is a data file
(traffic/<name>.json); this module turns it and ``--seed`` into what each
client sends. Nothing here knows a cell by name.

Parameters of a mix:
  source      where the mix comes from (a clause of the benchmark's spec).
  tenants     [{name, weight, priority}]: who sends. A tenant's token is
              "tok-" + name; weights go to the fair-share conf.
  streams     [{tenant, queries, params}]: one closed-loop client each, on
              a connection of its own, which sends its next query when the
              last one answered. ``queries`` are names under queries/, in
              the stream's own order; ``params`` gives a query its
              substitution parameters for this stream (absent: the query
              file's validation values). Every seed sends the same cycle of
              work; the seed picks where in its cycle each stream starts.
  warm_rounds   how often every stream sends each of its queries before the
              window, at the least (the harness goes on while a round still
              compiles something).
  trace_seconds length of the profiler's window inside a --trace 1 run.
"""

import json
import os
import random


def load(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    mix.setdefault("warm_rounds", 2)
    mix.setdefault("trace_seconds", 5.0)
    names = {t["name"] for t in mix["tenants"]}
    for s in mix["streams"]:
        if s["tenant"] not in names:
            raise ValueError(f"{path}: stream of unknown tenant {s['tenant']}")
        s.setdefault("params", {})
    return mix


def query_names(mix: dict) -> list:
    """The query files the mix needs, each once, in order of first use."""
    return list(dict.fromkeys(q for s in mix["streams"]
                              for q in s["queries"]))


def instance_key(query: str, params: dict) -> str:
    """Names one query with one set of parameters: what an answer is
    compared against."""
    return query + json.dumps(params or {}, sort_keys=True)


def stream_cycle(mix: dict, seed: int, index: int):
    """Endless iterator over one stream's queries, in the stream's order,
    from a seeded place in the cycle."""
    order = list(mix["streams"][index]["queries"])
    k = random.Random(seed * 1000003 + index).randrange(len(order))
    while True:
        yield from order[k:] + order[:k]


def traffic_path(root: str, name: str) -> str:
    return os.path.join(root, "benchmark", "traffic", name + ".json")

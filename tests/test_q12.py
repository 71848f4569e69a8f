"""TPC-H Q12 as the benchmark submits it (benchmark/queries/q12.py; cell
``sf10_q12_dupjoin1``): ``orders`` probes the filtered ``lineitem``, whose
order keys repeat where an order has several qualifying lines, grouped by
the dictionary-coded ship mode with two CASE counts.

What is held: through the planner and over the wire the answer equals the
plain numpy reference exactly (``compare.judge``), on 3 seeds and two
parameter sets, with ``lineitem`` in FULL batches of 8,192 rows whose last
row qualifies (``In`` over a string column once lost it, PERF.md PR 38),
an order planted with two qualifying lines and ``orders`` probing in
several batches; the orders stage stays fused on the runs rung: one
``exec:join-build`` span with path runs and probe merge a request, no
``exec:fused-fallback`` span, no ``join.candidate_stats`` sync (the
general hash table's sync a probe batch). Over the wire the keys are
shifted past ``join.denseKey.maxDomain`` as SF10's are, so the unique
rung, not the dense table, is what refuses the build first."""

import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import compare  # noqa: E402  (benchmark/compare.py: pyarrow only)
import datagen  # noqa: E402  (benchmark/datagen.py: numpy and pyarrow only)

from spark_rapids_tpu.config import conf as C  # noqa: E402
from spark_rapids_tpu.obs import gauges, span  # noqa: E402
from spark_rapids_tpu.plan import from_arrow  # noqa: E402
from spark_rapids_tpu.utils import tracing  # noqa: E402

SF = 0.03
SEEDS = (7, 2147483693, 314159265)
PARAMS = (None, {"shipmodes": ["AIR", "TRUCK"], "date": [1996, 1, 1]})
BATCH = 8192  # both tables: lineitem's 179,991 rows fill 21 batches
SHIFT = 1 << 26  # past join.denseKey.maxDomain (2^25): SF10's key range
REQUIRES = "agg.boundedStepPrograms,join.fusedDuplicateKeys"


def _q12():
    import harness
    return harness.load_by_path("queries", "q12")


def _data(seed: int, p, shift: int = 0):
    """The seed's two tables with an order planted that has two qualifying
    lines, and the last row of lineitem's first full batch qualifying."""
    q12 = _q12()
    raw = datagen.make(list(q12.TABLES), SF, seed)
    li = raw["lineitem"]
    lo, _ = q12._year(p)
    mode = datagen.labels("l_shipmode").index(p["shipmodes"][-1])
    _, first, count = np.unique(li["l_orderkey"], return_index=True,
                                return_counts=True)
    two = int(first[np.flatnonzero(count >= 2)[0]])
    for row in (two, two + 1, BATCH - 1):
        li["l_shipmode"][row] = mode
        li["l_shipdate"][row], li["l_commitdate"][row] = lo + 20, lo + 30
        li["l_receiptdate"][row] = lo + 40
    keep = q12._qualifying(raw, p)
    assert keep[two] and keep[two + 1] and keep[BATCH - 1]
    if shift:
        li["l_orderkey"] += shift
        raw["orders"]["o_orderkey"] += shift
    return raw


def _assert_equals_reference(tables, raw, p):
    q12 = _q12()
    verdict = compare.judge([("q12", t) for t in tables],
                            {"q12": q12.reference(raw, p)}, {"q12": q12})
    assert verdict["answers_wrong"] == 0 and verdict["whys"] == [], verdict
    assert verdict["distinct_answers"] == 1
    assert tables[0].num_rows == 2


def _assert_runs(before, after, events, requests):
    """One runs build a request, its probes merging offsets (the key is
    one ranged integer and the heads fit a probe batch), and nothing the
    fused probe avoids."""
    assert after["join_build_path_runs_total"] - before[
        "join_build_path_runs_total"] == requests
    assert after["join_build_path_total"] - before[
        "join_build_path_total"] == requests
    assert after["join_run_probe_merge_total"] - before[
        "join_run_probe_merge_total"] == requests
    assert after["join_run_probe_table_total"] == before[
        "join_run_probe_table_total"]
    assert after["fused_fallback_total"] == before["fused_fallback_total"]
    builds = [e["args"] for e in events if e["name"] == "exec:join-build"]
    assert [(b["path"], b["probe"]) for b in builds] == [
        ("runs", "merge")] * requests
    assert all(b["longest"] >= 2 for b in builds)
    names = [e["name"] for e in events]
    assert "exec:fused-fallback" not in names
    assert names.count("exec:join-pairs") == requests
    sites = [e["args"]["site"] for e in events
             if e["name"] == "exec:host-sync"]
    assert "join.candidate_stats" not in sites


@pytest.mark.parametrize("params", PARAMS, ids=["validation", "other"])
@pytest.mark.parametrize("seed", SEEDS)
def test_q12_through_the_planner_equals_the_reference(seed, params):
    """Overrides.apply (DataFrame.to_arrow) over plain string columns (the
    ``In`` of ``_string_eq``): the dense table refuses the repeated keys,
    the runs rung takes them. ``shrinkToLive.minCapacity`` is the batch's,
    so the build stage learns the capacity its output is packed to."""
    q12 = _q12()
    p = params or q12.PARAMS
    raw = _data(seed, p)
    conf = C.RapidsConf({C.SHRINK_TO_LIVE_MIN_CAPACITY.key: BATCH})
    d = {t: from_arrow(datagen.arrow(raw[t]), conf, batch_rows=BATCH,
                       partitions=1)
         for t in q12.TABLES}
    df = q12.build(d, params) if params else q12.build(d)
    assert "TpuFusedStage" in df.physical_plan().explain()
    before = gauges.snapshot()
    was = C._ACTIVE
    tracing.set_capture(True, clear=True)
    try:
        with span.activate(span.new_trace()):
            out = df.to_arrow()
        events = tracing.trace_events(clear=True)
    finally:
        tracing.set_capture(False, clear=True)
        C._ACTIVE = was
    _assert_equals_reference([out], raw, p)
    after = gauges.snapshot()
    _assert_runs(before, after, events, 1)
    # the build stage (the filtered lineitem) packs its 22 batches to what
    # its first one kept: no shrink_to_live sync, one fused.short
    assert after["fused_plain_learned_total"] - before[
        "fused_plain_learned_total"] == 1
    assert after["fused_plain_unlearned_total"] == before[
        "fused_plain_unlearned_total"]
    sites = [e["args"]["site"] for e in events
             if e["name"] == "exec:host-sync"]
    assert "shrink_to_live" not in sites, sites


@pytest.mark.parametrize("params", PARAMS, ids=["validation", "other"])
@pytest.mark.parametrize("seed", SEEDS)
def test_q12_over_the_wire_equals_the_reference(seed, params):
    """NetClient.submit() -> QueryFrontend -> QueryServer, twice (the second
    request finds the plan memoized), over dictionary-coded uploads with
    SF10's key range: the presence table, then the runs."""
    from spark_rapids_tpu.net import NetClient, QueryFrontend
    from spark_rapids_tpu.serve import QueryServer
    q12 = _q12()
    p = params or q12.PARAMS
    raw = _data(seed, p, SHIFT)
    conf = C.RapidsConf({C.REQUIRES.key: REQUIRES})
    srv = QueryServer(conf)
    fe = QueryFrontend(srv, tables={t: datagen.arrow(raw[t])
                                    for t in q12.TABLES},
                       host="127.0.0.1", port=0)
    before = gauges.snapshot()
    tracing.set_capture(True, clear=True)
    try:
        cl = NetClient(fe.host, fe.port, conf=conf, timeout_s=600)
        try:
            d = {t: cl.table(t, batch_rows=BATCH, partitions=1)
                 for t in q12.TABLES}
            df = q12.build(d, params) if params else q12.build(d)
            outs = [cl.submit(df, name=f"q12-{i}", timeout_s=600)
                    for i in range(2)]
        finally:
            cl.close()
        events = tracing.trace_events(clear=True)
    finally:
        tracing.set_capture(False, clear=True)
        fe.close()
        srv.close()
    _assert_equals_reference(outs, raw, p)
    _assert_runs(before, gauges.snapshot(), events, 2)


def test_the_configuration_names_the_duplicate_key_rung():
    """``tpch_sf10_join12`` names ``join.fusedDuplicateKeys`` in
    ``spark.rapids.tpu.requires``: this build takes it, a build without the
    rung (the parent of the PR that added it) refuses where the conf is
    made, before any data."""
    import json
    path = os.path.join(BENCH, "configs", "tpch_sf10_join12.json")
    with open(path) as f:
        conf = json.load(f)["conf"]
    assert conf == {C.REQUIRES.key: REQUIRES}
    assert C.RapidsConf(conf)[C.REQUIRES] == REQUIRES
    with pytest.raises(ValueError, match="this build lacks"):
        C.RapidsConf({C.REQUIRES.key: REQUIRES + ",join.fusedTripleKeys"})


def test_the_build_reader_reads_runs_builds_alone():
    """``exec.dupjoin_build_ms_per_query`` takes the union of a request's
    ``exec:join-build`` spans whose path is runs; a request whose builds
    took another rung, or a program that records no such span (the
    parent), reads nothing."""
    import harness
    reader = harness.load_by_path("layer_metrics",
                                  "exec.dupjoin_build_ms_per_query")

    def ev(name, start, dur, query=None, **attrs):
        args = dict(trace_id="t" + (query or "x"), span_id=name + str(start),
                    parent_id=None, **attrs)
        if query:
            args["query"] = query
        return {"name": name, "start_ns": start, "dur_ns": dur, "args": args}
    spans = [ev("net:request", 0, 10_000_000, query="r1"),
             ev("exec:join-build", 1_000_000, 4_000_000, path="runs"),
             ev("exec:join-build", 2_000_000, 1_000_000, path="dense")]
    for s in spans[1:]:
        s["args"]["trace_id"] = "tr1"
    ctx = {"spans": spans, "requests": [{"name": "r1"}]}
    assert reader.read(ctx) == 4.0
    spans[1]["args"]["path"] = "unique"
    assert reader.read(ctx) is None
    assert reader.read({"spans": [], "requests": [{"name": "r1"}]}) is None

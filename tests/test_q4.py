"""TPC-H Q4 as the benchmark submits it (benchmark/queries/q4.py; cell
``sf10_q4_semi1``): the quarter's ``orders`` left-semi-joined to the
``lineitem`` rows received after their commit date (EXISTS as Spark plans
it), counted by the dictionary-coded order priority.

What is held: through the planner and over the wire the answer equals the
plain numpy reference exactly (``compare.judge``), on 3 seeds and two
quarters, with ``lineitem`` in FULL batches of 8,192 rows and an order in
the quarter whose one late line is the last row of the first batch; the
plan is one fused stage over ``orders`` that holds the semi join, whose
build (the filtered ``lineitem``, a fused stage of its own) ends in the
exists form: one ``exec:join-build`` span with path exists a request, no
``exec:fused-fallback``, one ``join.exists_stats`` sync and no
``shrink_to_live`` or ``join.candidate_stats`` sync. Over the wire the keys
are shifted past ``join.denseKey.maxDomain`` as SF10's are."""

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

SF = 0.01
SEEDS = (7, 2147483693, 314159265)
PARAMS = (None, {"date": [1996, 11, 1]})  # the second crosses a year
BATCH = 8192  # both tables: lineitem's 59,997 rows fill 7 batches
SHIFT = 1 << 26  # past join.denseKey.maxDomain (2^25): SF10's key range
REQUIRES = "agg.boundedStepPrograms,join.fusedSemi"


def _q4():
    import harness
    return harness.load_by_path("queries", "q4")


def _data(seed: int, p, shift: int = 0):
    """The seed's two tables with the order of lineitem's row BATCH - 1
    moved into the quarter, that row its only late line."""
    q4 = _q4()
    raw = datagen.make(list(q4.TABLES), SF, seed)
    li, od = raw["lineitem"], raw["orders"]
    lo, _ = q4._quarter(p)
    key = li["l_orderkey"][BATCH - 1]
    mine = li["l_orderkey"] == key
    li["l_commitdate"][mine] = li["l_receiptdate"][mine]  # none late...
    li["l_commitdate"][BATCH - 1] -= 1                   # ...but this one
    od["o_orderdate"][od["o_orderkey"] == key] = lo + 5
    if shift:
        li["l_orderkey"] += shift
        od["o_orderkey"] += shift
    return raw


def _assert_equals_reference(tables, raw, p):
    q4 = _q4()
    want = q4.reference(raw, p)
    verdict = compare.judge([("q4", t) for t in tables], {"q4": want},
                            {"q4": q4})
    assert verdict["answers_wrong"] == 0 and verdict["whys"] == [], verdict
    assert verdict["distinct_answers"] == 1
    assert tables[0].num_rows == 5
    # the planted order counts: without its one late line it would not
    gone = q4.reference(dict(raw, lineitem=datagen.with_row(
        raw["lineitem"], q4.COLUMNS["lineitem"], BATCH - 1, 0)), p)
    assert sum(gone["order_count"]) == sum(want["order_count"]) - 1


def _assert_exists(before, after, events, requests):
    """One exists build a request, semi, read in one sync, and nothing
    the fused probe avoids."""
    assert after["join_build_path_exists_total"] - before[
        "join_build_path_exists_total"] == requests
    assert after["join_build_path_total"] - before[
        "join_build_path_total"] == requests
    assert after["fused_fallback_total"] == before["fused_fallback_total"]
    builds = [e["args"] for e in events if e["name"] == "exec:join-build"]
    assert [(b["join"], b["path"]) for b in builds] == [
        ("left_semi", "exists")] * requests
    names = [e["name"] for e in events]
    assert "exec:fused-fallback" not in names
    sites = [e["args"]["site"] for e in events
             if e["name"] == "exec:host-sync"]
    assert sites.count("join.exists_stats") == requests
    assert "join.candidate_stats" not in sites
    assert "shrink_to_live" not in sites


@pytest.mark.parametrize("params", PARAMS, ids=["validation", "other"])
@pytest.mark.parametrize("seed", SEEDS)
def test_q4_through_the_planner_equals_the_reference(seed, params):
    """Overrides.apply (DataFrame.to_arrow): one fused stage over orders
    holds the semi join; its build is a fused stage of lineitem."""
    from spark_rapids_tpu.exec.fused import TpuFusedStageExec
    from spark_rapids_tpu.exec.join import HashJoinExec
    q4 = _q4()
    p = params or q4.PARAMS
    raw = _data(seed, p)
    d = {t: from_arrow(datagen.arrow(raw[t]), batch_rows=BATCH,
                       partitions=1)
         for t in q4.TABLES}
    df = q4.build(d, params) if params else q4.build(d)
    stage = df.physical_plan().children[0]
    assert isinstance(stage, TpuFusedStageExec)
    join = next(op for op in stage.segments if isinstance(op, HashJoinExec))
    assert join.join_type == "left_semi"
    assert isinstance(join.children[1], TpuFusedStageExec)
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
    _assert_exists(before, after, events, 1)
    # the probe's rows: the quarter's orders tested, the answer's kept
    assert after["join_semi_probe_hits_total"] - before[
        "join_semi_probe_hits_total"] == sum(
            out["order_count"].to_pylist())
    lo, hi = q4._quarter(p)
    date = raw["orders"]["o_orderdate"]
    assert after["join_semi_probe_rows_total"] - before[
        "join_semi_probe_rows_total"] == int(((date >= lo)
                                              & (date < hi)).sum())


@pytest.mark.parametrize("params", PARAMS, ids=["validation", "other"])
@pytest.mark.parametrize("seed", SEEDS)
def test_q4_over_the_wire_equals_the_reference(seed, params):
    """NetClient.submit() -> QueryFrontend -> QueryServer, twice (the second
    request finds the plan memoized), over dictionary-coded uploads with
    SF10's key range."""
    from spark_rapids_tpu.net import NetClient, QueryFrontend
    from spark_rapids_tpu.serve import QueryServer
    q4 = _q4()
    p = params or q4.PARAMS
    raw = _data(seed, p, SHIFT)
    conf = C.RapidsConf({C.REQUIRES.key: REQUIRES})
    srv = QueryServer(conf)
    fe = QueryFrontend(srv, tables={t: datagen.arrow(raw[t])
                                    for t in q4.TABLES},
                       host="127.0.0.1", port=0)
    before = gauges.snapshot()
    tracing.set_capture(True, clear=True)
    try:
        cl = NetClient(fe.host, fe.port, conf=conf, timeout_s=600)
        try:
            d = {t: cl.table(t, batch_rows=BATCH, partitions=1)
                 for t in q4.TABLES}
            df = q4.build(d, params) if params else q4.build(d)
            outs = [cl.submit(df, name=f"q4-{i}", timeout_s=600)
                    for i in range(2)]
        finally:
            cl.close()
        events = tracing.trace_events(clear=True)
    finally:
        tracing.set_capture(False, clear=True)
        fe.close()
        srv.close()
    _assert_equals_reference(outs, raw, p)
    _assert_exists(before, gauges.snapshot(), events, 2)


def test_the_configuration_names_the_fused_semi_join():
    """``tpch_sf10_semi4`` names ``join.fusedSemi`` in
    ``spark.rapids.tpu.requires``: this build takes it, a build without the
    exists form (the parent of the change that added it) refuses where the
    conf is made, before any data."""
    import json
    path = os.path.join(BENCH, "configs", "tpch_sf10_semi4.json")
    with open(path) as f:
        conf = json.load(f)["conf"]
    assert conf == {C.REQUIRES.key: REQUIRES}
    assert C.RapidsConf(conf)[C.REQUIRES] == REQUIRES
    with pytest.raises(ValueError, match="this build lacks"):
        C.RapidsConf({C.REQUIRES.key: "join.fusedNullAwareAnti"})


def test_the_build_reader_reads_exists_builds_alone():
    """``exec.semijoin_build_ms_per_query`` takes the union of a request's
    ``exec:join-build`` spans whose path is exists; a request whose builds
    took another path, or a program that records no such span (the
    parent), reads nothing."""
    import harness
    reader = harness.load_by_path("layer_metrics",
                                  "exec.semijoin_build_ms_per_query")

    def ev(name, start, dur, query=None, **attrs):
        args = dict(trace_id="t" + (query or "x"), span_id=name + str(start),
                    parent_id=None, **attrs)
        if query:
            args["query"] = query
        return {"name": name, "start_ns": start, "dur_ns": dur, "args": args}
    spans = [ev("net:request", 0, 10_000_000, query="r1"),
             ev("exec:join-build", 1_000_000, 4_000_000, path="exists"),
             ev("exec:join-build", 3_000_000, 3_000_000, path="exists"),
             ev("exec:join-build", 2_000_000, 1_000_000, path="dense")]
    for s in spans[1:]:
        s["args"]["trace_id"] = "tr1"
    ctx = {"spans": spans, "requests": [{"name": "r1"}]}
    assert reader.read(ctx) == 5.0  # the union of 1-5 ms and 3-6 ms
    for s in spans[1:3]:
        s["args"]["path"] = "runs"
    assert reader.read(ctx) is None
    assert reader.read({"spans": [], "requests": [{"name": "r1"}]}) is None


def test_the_reference_counts_what_the_controls_break():
    """The reference and each control on one seed: EXISTS counts an order
    once, the inner join once a late line, NOT EXISTS the rest of the
    quarter, a closed window more; the dropped line's order leaves."""
    q4 = _q4()
    raw = _data(SEEDS[0], q4.PARAMS)
    want = q4.reference(raw)["order_count"]
    got = {n: f(raw, q4.PARAMS)["order_count"]
           for n, f in q4.CONTROLS.items()}
    lo, hi = q4._quarter(q4.PARAMS)
    od = raw["orders"]
    quarter = int(((od["o_orderdate"] >= lo)
                   & (od["o_orderdate"] < hi)).sum())
    assert sum(want) + sum(got["anti_for_semi"]) == quarter
    assert all(np.greater_equal(got["counted_per_line"], want))
    assert sum(got["counted_per_line"]) > sum(want)
    assert sum(got["window_closed"]) >= sum(want)
    assert sum(got["dropped_match"]) == sum(want) - 1

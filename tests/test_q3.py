"""TPC-H Q3 as the benchmark submits it (benchmark/queries/q3.py; cell
``sf10_q3_join1``): customer, orders and lineitem joined, a three-column
group-by with a DECIMAL(38,4) sum, ORDER BY ... LIMIT 10.

What is held: through the planner and through the served path the answer
equals the plain numpy reference exactly (every key, date, DECIMAL digit and
the row order), the whole query stays in its fused stages (no fallback, by
the counter and by the span), and both rungs of the join ladder the cell
works are met: the dense direct-address table (``customer``) and, once the
order keys pass ``join.denseKey.maxDomain`` as they do at SF10, the
bucketed unique table (``orders``). The second is forced here by the data
(order keys shifted past 2^25), never by a conf key."""

import os
import sys

import pytest

import conftest

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

TABLES = ("lineitem", "orders", "customer")
BATCH = 8192
SEEDS = (7, 2147483693, 314159265)
PARAMS = (None, {"segment": "MACHINERY", "date": [1995, 3, 29]})
SHIFT = 1 << 26  # past join.denseKey.maxDomain (2^25): SF10's key range


@pytest.fixture(scope="module", autouse=True)
def _release_programs():
    conftest.drop_programs()


def _q3():
    import harness
    return harness.load_by_path("queries", "q3")


def _data(seed: int, sf: float, shift: int = 0):
    raw = datagen.make(list(TABLES), sf, seed)
    if shift:
        raw["lineitem"]["l_orderkey"] = raw["lineitem"]["l_orderkey"] + shift
        raw["orders"]["o_orderkey"] = raw["orders"]["o_orderkey"] + shift
    return raw, {t: datagen.arrow(raw[t]) for t in TABLES}


def _assert_equals_reference(table, raw, params):
    q3 = _q3()
    want = q3.reference(raw, params) if params else q3.reference(raw)
    r = compare.answer_readings(table, want, q3)
    assert (r["wrong"], r["units_off"]) == (0, 0), r["why"]
    assert table.num_rows == 10


def _paths(before, after):
    return {p: after[f"join_build_path_{p}_total"]
            - before[f"join_build_path_{p}_total"]
            for p in ("dense", "unique", "ht", "sorted")}


@pytest.mark.parametrize("params", PARAMS, ids=["validation", "other"])
@pytest.mark.parametrize("seed", SEEDS)
def test_q3_through_the_planner_equals_the_reference(seed, params):
    """Overrides.apply (DataFrame.to_arrow) at SF0.03 with SF10's key
    range: fused end to end, dense and unique builds, zero fallbacks."""
    raw, tables = _data(seed, 0.03, SHIFT)
    d = {t: from_arrow(tables[t], batch_rows=BATCH, partitions=1)
         for t in TABLES}
    df = _q3().build(d, params) if params else _q3().build(d)
    plan = df.physical_plan().explain()
    assert "TpuTopN 10" in plan and "TpuSort" not in plan, plan
    assert plan.count("TpuFusedStage") == 3, plan
    before = gauges.snapshot()
    out = df.to_arrow()
    after = gauges.snapshot()
    _assert_equals_reference(out, raw, params)
    assert after["fused_fallback_total"] == before["fused_fallback_total"]
    assert _paths(before, after) == {"dense": 1, "unique": 1, "ht": 0,
                                     "sorted": 0}
    assert after["join_build_path_total"] - before[
        "join_build_path_total"] == 2


def test_small_order_keys_take_the_dense_table_twice():
    """The generator's own keys at a small scale lie under 2^25: both
    builds are direct-address tables (SF1's path, not the cell's)."""
    raw, tables = _data(SEEDS[0], 0.02)
    d = {t: from_arrow(tables[t], batch_rows=BATCH, partitions=1)
         for t in TABLES}
    before = gauges.snapshot()
    out = _q3().build(d).to_arrow()
    after = gauges.snapshot()
    _assert_equals_reference(out, raw, None)
    assert _paths(before, after) == {"dense": 2, "unique": 0, "ht": 0,
                                     "sorted": 0}
    assert after["fused_fallback_total"] == before["fused_fallback_total"]


@pytest.mark.parametrize("params", PARAMS, ids=["validation", "other"])
@pytest.mark.parametrize("seed", SEEDS)
def test_q3_over_the_wire_equals_the_reference(seed, params):
    """NetClient.submit() -> QueryFrontend -> QueryServer at SF0.05, twice
    (the second request finds the plan memoized): the reference's answer,
    one exec:join-build span per build side per request with its path, one
    exec:topn, and no exec:fused-fallback."""
    from spark_rapids_tpu.net import NetClient, QueryFrontend
    from spark_rapids_tpu.serve import QueryServer
    raw, tables = _data(seed, 0.05, SHIFT)
    conf = C.RapidsConf({C.REQUIRES.key:
                         "agg.boundedStepPrograms,sort.boundedTopN"})
    srv = QueryServer(conf)
    fe = QueryFrontend(srv, tables=tables, host="127.0.0.1", port=0)
    before = gauges.snapshot()
    tracing.set_capture(True, clear=True)
    try:
        cl = NetClient(fe.host, fe.port, conf=conf, timeout_s=600)
        try:
            d = {t: cl.table(t, batch_rows=BATCH, partitions=1)
                 for t in TABLES}
            df = _q3().build(d, params) if params else _q3().build(d)
            outs = [cl.submit(df, name=f"q3-{i}", timeout_s=600)
                    for i in range(2)]
        finally:
            cl.close()
        events = tracing.trace_events(clear=True)
    finally:
        tracing.set_capture(False, clear=True)
        fe.close()
        srv.close()
    after = gauges.snapshot()
    for out in outs:
        _assert_equals_reference(out, raw, params)
    assert after["fused_fallback_total"] == before["fused_fallback_total"]
    assert _paths(before, after) == {"dense": 2, "unique": 2, "ht": 0,
                                     "sorted": 0}
    traces = span.assemble_traces({"driver": events})
    served = [t for t in traces.values()
              if any(e["name"] == "net:request" for e in t)]
    assert len(served) == 2
    for t in served:
        names = [e["name"] for e in t]
        assert "exec:fused-fallback" not in names
        assert names.count("exec:topn") == 1
        by_path = {e["attrs"]["path"]: e for e in t
                   if e["name"] == "exec:join-build"}
        assert sorted(by_path) == ["dense", "unique"]
        assert names.count("exec:join-build") == 2
        dense, unique = by_path["dense"], by_path["unique"]
        # customer's build runs inside orders' build side: a span of its
        # own, started later and ended earlier
        assert unique["start_ns"] < dense["start_ns"]
        assert (dense["start_ns"] + dense["dur_ns"]
                <= unique["start_ns"] + unique["dur_ns"])
        assert dense["attrs"]["rows"] > 0 < unique["attrs"]["rows"]
        assert unique["attrs"]["capacity"] >= unique["attrs"]["rows"]
        syncs = {}  # a build's span -> the sites it waited at
        for e in t:
            if e["name"] == "exec:host-sync":
                syncs.setdefault(e["parent_id"], []).append(
                    e["attrs"]["site"])
        assert syncs[dense["span_id"]] == ["join.dense_key_stats",
                                           "join.dense_dup"]
        # the order keys' range is read first, and is past the dense
        # bound; the build side's own stage waits under the span too
        assert [s for s in syncs[unique["span_id"]]
                if s.startswith("join.")] == [
                    "join.dense_key_stats", "join.table_stats",
                    "join.table_dup"]


@pytest.mark.parametrize("far,asked", [
    ((), "join.dense_dup"), ((1 << 40,), "join.table_dup")],
    ids=["dense", "unique"])
def test_a_fallback_is_a_span_and_a_count(far, asked):
    """A build with duplicate keys refuses the fused probe: the partition
    re-runs unfused, the answer stays right, and the request's trace says
    so (exec:fused-fallback, cause join-refused) beside the counter. With
    a key far past ``join.denseKey.maxDomain`` the build is the unique
    rung's, and what refuses it is the row table's own twin flag."""
    import pyarrow as pa
    from spark_rapids_tpu.exprs.expr import Sum, col
    left = pa.table({"k": pa.array([1, 2, 3, 4] * 50, pa.int64()),
                     "v": pa.array(range(200), pa.int64())})
    right = pa.table({"rk": pa.array([1, 1, 2, 3, *far], pa.int64()),
                      "w": pa.array([10, 20, 30, 40, *far], pa.int64())})
    df = (from_arrow(left, batch_rows=64, partitions=1)
          .join(from_arrow(right, partitions=1), left_on="k", right_on="rk")
          .group_by("k").agg(Sum(col("w")).alias("s")))
    before = gauges.snapshot()
    tracing.set_capture(True, clear=True)
    try:
        with span.activate(span.new_trace()):
            out = df.to_arrow()
        events = tracing.trace_events(clear=True)
    finally:
        tracing.set_capture(False, clear=True)
    after = gauges.snapshot()
    assert sorted(zip(*out.to_pydict().values())) == [
        (1, 1500), (2, 1500), (3, 2000)]
    if "TpuFusedStage" not in df.physical_plan().explain():
        pytest.skip("the planner did not fuse this join")
    falls = [e["args"] for e in events if e["name"] == "exec:fused-fallback"]
    assert [f["cause"] for f in falls] == ["join-refused"]
    sites = [e["args"]["site"] for e in events
             if e["name"] == "exec:host-sync"]
    assert asked in sites
    assert after["fused_fallback_total"] - before[
        "fused_fallback_total"] == 1
    assert after["fused_fallback_join_refused_total"] - before[
        "fused_fallback_join_refused_total"] == 1


@pytest.mark.parametrize("value,error", [
    ("agg.boundedStepPrograms,sort.boundedTopN", None),
    ("sort.boundedTopN", None),
    ("sort.boundedTopN,sort.radixTopN", "this build lacks"),
])
def test_the_configuration_names_the_bounded_topn(value, error, monkeypatch):
    """``tpch_sf10_join3`` names ``sort.boundedTopN`` in
    ``spark.rapids.tpu.requires``: a build that has it takes the conf and
    changes nothing for it; one that lacks it (the parent of the PR that
    added the top-N) refuses where the conf is made."""
    if error:
        with pytest.raises(ValueError, match=error):
            C.RapidsConf({C.REQUIRES.key: value})
        return
    assert C.RapidsConf({C.REQUIRES.key: value})[C.REQUIRES] == value
    assert "sort.boundedTopN" in C.CAPABILITIES
    monkeypatch.setattr(C, "CAPABILITIES", {
        k: v for k, v in C.CAPABILITIES.items() if k != "sort.boundedTopN"})
    with pytest.raises(ValueError, match="this build lacks"):
        C.RapidsConf({C.REQUIRES.key: value})


def _skewed_join(agg: bool):
    """A probe side whose first batch of 8,192 rows hits the build ten
    times and whose second hits it every row: what the first batch
    promised (a capacity of 1,024) does not hold the second's hits."""
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.exprs.expr import Sum, col
    n = 2 * BATCH
    k = np.arange(n, dtype=np.int64) % 500
    k[:BATCH] += 10_000          # misses...
    k[:10] = np.arange(10)       # ...but ten
    left = pa.table({"k": pa.array(k), "v": pa.array(np.ones(n, np.int64))})
    right = pa.table({"rk": pa.array(np.arange(500, dtype=np.int64)),
                      "w": pa.array(np.arange(500, dtype=np.int64))})
    from spark_rapids_tpu.exprs.expr import GreaterThan, lit
    df = (from_arrow(left, batch_rows=BATCH, partitions=1)
          .filter(GreaterThan(col("v"), lit(0)))  # a mask for the probe
          .join(from_arrow(right, partitions=1), left_on="k", right_on="rk"))
    if agg:
        df = df.group_by("k").agg(Sum(col("w")).alias("s"))
    hits = np.concatenate([k[:10], k[BATCH:]])
    return df, hits


def _stages(node, out):
    from spark_rapids_tpu.exec.fused import TpuFusedStageExec
    if isinstance(node, TpuFusedStageExec):
        out.append(node)
    for c in node.children:
        _stages(c, out)
    return out


@pytest.mark.parametrize("agg", [False, True], ids=["plain", "aggregate"])
def test_hits_that_outgrow_the_learned_capacity_run_again_unshrunk(agg):
    """The stage learns the probe's output capacity from its first batch;
    a later batch whose hits do not fit says so, and what was cut runs
    again at the probe's own capacity: the same rows, no fallback to the
    unfused chain, and the stage stops shrinking that partition."""
    import numpy as np
    df, hits = _skewed_join(agg)
    before = gauges.snapshot()["fused_fallback_total"]
    out = df.to_arrow()
    stage = _stages(df.physical_plan(), [])[0]
    assert stage._learned == {0: None}
    assert stage.metrics["numFallbacks"].value == 0
    assert gauges.snapshot()["fused_fallback_total"] == before
    if agg:
        got = dict(zip(out["k"].to_pylist(), out["s"].to_pylist()))
        keys, counts = np.unique(hits, return_counts=True)
        assert got == {int(k): int(k) * int(c) for k, c in zip(keys, counts)}
    else:
        assert sorted(out["k"].to_pylist()) == sorted(hits.tolist())
        assert out["w"].to_pylist() == out["k"].to_pylist()
    again = df.to_arrow()  # straight to the unshrunk programs
    assert again.num_rows == out.num_rows
    assert stage._learned == {0: None}


def test_hits_that_fit_keep_the_learned_capacity():
    """Q3's `lineitem` stage at SF0.03: the probe's hits fit 1,024 slots of
    an 8,192-row batch, the aggregate behind it defers its merge, and the
    plan keeps what it learned for the next request."""
    raw, tables = _data(SEEDS[0], 0.03, SHIFT)
    d = {t: from_arrow(tables[t], batch_rows=BATCH, partitions=1)
         for t in TABLES}
    df = _q3().build(d)
    df.to_arrow()
    stages = _stages(df.physical_plan(), [])
    top = stages[0]  # filter -> project -> join -> aggregate
    assert top.agg is not None and top._learned == {0: 1024}
    from spark_rapids_tpu.exec import fused
    assert any("partial" in key for key in fused._STEP_KEYS)
    _assert_equals_reference(df.to_arrow(), raw, None)
    assert top._learned == {0: 1024}

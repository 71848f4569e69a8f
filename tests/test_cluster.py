"""Multi-process TCP-shuffle execution (shuffle/cluster.py).

VERDICT r3 item 1(a): a planned TPC-H query must run end-to-end across
executor PROCESSES with the reduce side fetching map outputs over the TCP
transport — not the in-process shuffle manager. Differential-checked
against the single-process engine.
"""

import numpy as np
import pyarrow as pa
import pytest

import conftest

from spark_rapids_tpu.config.conf import RapidsConf
from spark_rapids_tpu.exprs import expr as E
from spark_rapids_tpu.exprs.expr import col, lit
from spark_rapids_tpu.plan import from_arrow
from spark_rapids_tpu.shuffle.cluster import TcpShuffleCluster

pytestmark = pytest.mark.skipif(
    conftest.TPU_LANE, reason="multi-process workers run the host platform")


def _conf():
    return RapidsConf({"spark.rapids.tpu.sql.enabled": True})


def _rows(table: pa.Table):
    cols = [c.to_pylist() for c in table.columns]
    return [tuple(r) for r in zip(*cols)] if cols else []


def _canon(rows):
    return sorted(
        [tuple(round(v, 6) if isinstance(v, float) else v for v in r)
         for r in rows], key=repr)


@pytest.fixture(scope="module")
def cluster():
    with TcpShuffleCluster(n_workers=2) as c:
        yield c


def test_cluster_groupby(cluster, rng):
    n = 4000
    t = pa.table({
        "k": pa.array(rng.integers(0, 23, n), pa.int64()),
        "v": pa.array(rng.uniform(0, 10, n)),
        "q": pa.array(rng.integers(1, 9, n).astype(np.int64), pa.int64()),
    })
    df = (from_arrow(t, _conf(), batch_rows=512, partitions=4)
          .filter(E.GreaterThan(col("v"), lit(2.0)))
          .group_by("k")
          .agg(E.Sum(col("q")).alias("sq"), E.Count().alias("c"),
               E.Average(col("v")).alias("av")))
    df.shuffle_partitions = 4
    local = [tuple(r.values()) for r in df.collect()]
    out = cluster.run_query(df)
    assert _canon(_rows(out)) == _canon(local)


def test_cluster_tpch_q1(cluster):
    from spark_rapids_tpu.bench import tpch

    tables = tpch.tables_for(0.002)
    d = tpch.df_tables(tables, _conf(), shuffle_partitions=3, partitions=4,
                       batch_rows=2048)
    df = tpch.DF_QUERIES["q1"](d)
    local = [tuple(r.values()) for r in df.collect()]
    out = cluster.run_query(df)
    # q1 ends in an order-by: compare ordered
    got = [tuple(round(v, 6) if isinstance(v, float) else v for v in r)
           for r in _rows(out)]
    want = [tuple(round(v, 6) if isinstance(v, float) else v for v in r)
            for r in local]
    assert got == want


def test_cluster_tpcds_q42(cluster):
    from spark_rapids_tpu.bench import tpcds_queries as Q
    from spark_rapids_tpu.bench.tpcds_schema import tables_for

    tables = tables_for(0.01)
    d = {}
    for k, v in tables.items():
        df = from_arrow(v, _conf(), batch_rows=4096, partitions=2)
        df.shuffle_partitions = 3
        d[k] = df
    q = Q.QUERIES["q42"](d)
    local = [tuple(r.values()) for r in q.collect()]
    out = cluster.run_query(q)
    got = [tuple(round(v, 6) if isinstance(v, float) else v for v in r)
           for r in _rows(out)]
    want = [tuple(round(v, 6) if isinstance(v, float) else v for v in r)
            for r in local]
    assert got == want


def test_cluster_heartbeat_discovery(cluster):
    # both workers registered through the driver-mediated heartbeat manager
    peers = cluster.heartbeats.peers()
    assert len(peers) == 2
    cluster.heartbeat_round()  # sweep keeps live peers
    assert len(cluster.heartbeats.peers()) == 2


def test_cluster_health_view(cluster):
    """Driver polls every executor for its gauge snapshot and merges the
    per-worker records into one health view."""
    view = cluster.collect_health()
    wids = [w["worker_id"] for w in view["workers"]]
    assert set(cluster.workers) <= set(wids)
    assert view["alive"] >= 2
    by_id = {w["worker_id"]: w for w in view["workers"]}
    for wid in cluster.workers:
        w = by_id[wid]
        assert w["kind"] == "cluster" and w["heartbeats"] >= 1
        # the poll carried the executor's gauge snapshot across the wire
        assert "pool_used_bytes" in w["gauges"]
    assert "jit_cache_hit_total" in view["merged_gauges"]


def test_cluster_stalled_worker_raises_journal_event(cluster, rng):
    """A worker that heartbeats but makes no task progress is flagged stale
    (worker-stale journal event, once per episode) and joins the soft avoid
    set; completing a task recovers it."""
    from spark_rapids_tpu.obs import events as journal

    cluster.collect_health()       # heartbeats alone are NOT progress
    journal.clear()
    stalled = cluster.heartbeat_round(progress_timeout_s=0.0)
    assert set(cluster.workers) <= set(stalled)
    flagged = {e["worker"] for e in journal.recent("worker-stale")}
    assert set(cluster.workers) <= flagged
    assert set(cluster.workers) <= cluster._suspect
    # once per stall episode: a second sweep is silent
    assert set(cluster.heartbeat_round(progress_timeout_s=0.0)) \
        .isdisjoint(cluster.workers)
    view = cluster.collect_health()
    assert view["stale"] >= 2
    # a completed task is progress: the worker recovers and leaves the
    # avoid set (the or-alive fallback kept the query runnable throughout)
    t = pa.table({"k": pa.array(rng.integers(0, 5, 500), pa.int64()),
                  "v": pa.array(rng.integers(0, 9, 500), pa.int64())})
    df = from_arrow(t, _conf(), batch_rows=256, partitions=2)
    df.shuffle_partitions = 2
    cluster.run_query(df.group_by("k").agg(E.Sum(col("v")).alias("s")))
    assert not (set(cluster.workers) & cluster._suspect)
    assert cluster.collect_health()["stale"] == 0
    journal.clear()


def test_cluster_merged_multiworker_trace(cluster, rng, tmp_path):
    """A traceCapture query produces per-worker captures the driver merges
    into ONE Chrome trace with a distinct process track per executor."""
    import json

    from spark_rapids_tpu.utils import tracing
    from tools.trace_viewer_check import check_file, validate_trace

    trace_conf = RapidsConf({
        "spark.rapids.tpu.sql.enabled": True,
        "spark.rapids.tpu.profile.traceCapture": True,
    })
    n = 3000
    t = pa.table({
        "k": pa.array(rng.integers(0, 17, n), pa.int64()),
        "v": pa.array(rng.integers(0, 100, n), pa.int64()),
    })
    df = from_arrow(t, trace_conf, batch_rows=512, partitions=4)
    df.shuffle_partitions = 3
    q = df.group_by("k").agg(E.Sum(col("v")).alias("s"))
    tracing.set_capture(True, clear=True)
    try:
        cluster.run_query(q)
        obj = cluster.merged_chrome_trace()
    finally:
        tracing.set_capture(False)
        tracing.trace_events(clear=True)
    assert validate_trace(obj) == []
    spans = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    # both executors contributed map/reduce task spans on their own tracks
    task_pids = {e["pid"] for e in spans
                 if e["name"].startswith(("task:map:", "task:reduce:"))}
    assert len(task_pids) == 2
    names = [e["name"] for e in spans]
    assert any(n.startswith("task:map:") for n in names)
    assert any(n.startswith("task:reduce:") for n in names)
    # every process track is labeled; driver sorts first
    labels = {e["args"]["name"]: e["pid"] for e in obj["traceEvents"]
              if e["ph"] == "M" and e["name"] == "process_name"}
    assert labels["driver"] == 1
    assert len(labels) == 3  # driver + 2 executors
    # worker identity is stamped on the spans themselves too
    assert all("worker" in e.get("args", {}) for e in spans
               if e["name"].startswith("task:"))
    path = tmp_path / "merged_cluster_trace.json"
    path.write_text(json.dumps(obj))
    assert check_file(str(path)) == []


def test_cluster_executor_sigkill_recovery(rng):
    """One executor SIGKILLed mid-query: its map blocks recompute on
    survivors (lineage) and its reduce tasks reschedule — the query still
    returns correct results (VERDICT r4 missing #6; reference:
    Plugin.scala:560-568 hard-exit + Spark task retry)."""
    import os
    import signal
    import threading
    import time

    n = 4000
    t = pa.table({
        "k": pa.array(rng.integers(0, 50, n), pa.int64()),
        "v": pa.array(rng.integers(0, 1000, n), pa.int64()),
    })
    with TcpShuffleCluster(n_workers=3) as c:
        df = from_arrow(t, _conf(), batch_rows=512, partitions=6)
        df.shuffle_partitions = 4
        q = df.group_by("k").agg(E.Sum(col("v")).alias("s"),
                                 E.Count(col("v")).alias("n"))
        local = _canon([tuple(r.values()) for r in q.collect()])

        victim = c.workers[1]
        pid = c._proc_by[victim].pid
        result = {}

        def run():
            result["table"] = c.run_query(q)

        th = threading.Thread(target=run)
        th.start()
        time.sleep(0.35)  # land the kill mid-query (any phase is handled)
        os.kill(pid, signal.SIGKILL)
        th.join(timeout=180)
        assert not th.is_alive(), "query hung after executor death"
        assert "table" in result
        assert _canon(_rows(result["table"])) == local
        # the cluster keeps working with survivors; if the first query won
        # the race against the kill, the dead worker is detected here
        out2 = c.run_query(q)
        assert _canon(_rows(out2)) == local
        assert victim in c._dead


def test_cluster_executor_kill_fault_recovery(rng):
    """Satellite: the conf-driven ``executor:kill`` fault hard-exits one
    executor mid-query (os._exit(137), the Plugin.scala:560 analog) and the
    query still returns results bit-identical to a fault-free run."""
    n = 4000
    t = pa.table({
        "k": pa.array(rng.integers(0, 50, n), pa.int64()),
        "v": pa.array(rng.integers(0, 1000, n), pa.int64()),
    })
    # worker 1 dies on its SECOND task (skip=1): it completes one map task
    # first, so its written blocks are LOST and must recompute via lineage.
    # The small-query fast path would plan ONE reduce partition for 4,000
    # rows, worker 1 would get no second task and nobody would die: the
    # four reduce partitions asked for below are what reaches it.
    wide = {"spark.rapids.tpu.sql.enabled": True,
            "spark.rapids.tpu.fastpath.enabled": False}
    fault_conf = RapidsConf({
        **wide,
        "spark.rapids.tpu.test.faults": "executor:kill@id=1,skip=1",
    })
    df_clean = from_arrow(t, RapidsConf(wide), batch_rows=512, partitions=6)
    df_clean.shuffle_partitions = 4
    q_clean = df_clean.group_by("k").agg(E.Sum(col("v")).alias("s"),
                                         E.Count(col("v")).alias("n"))
    local = _canon([tuple(r.values()) for r in q_clean.collect()])

    df = from_arrow(t, fault_conf, batch_rows=512, partitions=6)
    df.shuffle_partitions = 4
    q = df.group_by("k").agg(E.Sum(col("v")).alias("s"),
                             E.Count(col("v")).alias("n"))
    with TcpShuffleCluster(n_workers=3) as c:
        victim = c.workers[1]
        out = c.run_query(q)
        assert _canon(_rows(out)) == local
        assert victim in c._dead
        # survivors keep serving queries after the loss
        out2 = c.run_query(q_clean)
        assert _canon(_rows(out2)) == local


def test_cluster_corrupt_block_refetch_then_recompute(rng):
    """Blocks served corrupt by one executor are detected by the integrity
    trailer on the reduce side; persistent corruption triggers recompute of
    that executor's map outputs on OTHER executors (refetch-then-recompute)
    and the query completes bit-identically."""
    n = 4000
    t = pa.table({
        "k": pa.array(rng.integers(0, 50, n), pa.int64()),
        "v": pa.array(rng.integers(0, 1000, n), pa.int64()),
    })
    # worker 0 serves every block corrupted (p=1, unbounded): refetch can
    # never clean it, so the driver must recompute its maps elsewhere
    fault_conf = RapidsConf({
        "spark.rapids.tpu.sql.enabled": True,
        "spark.rapids.tpu.test.faults":
            "shuffle.block:corrupt@id=0,p=1.0,seed=5",
    })
    df_clean = from_arrow(t, _conf(), batch_rows=512, partitions=4)
    df_clean.shuffle_partitions = 3
    q_clean = df_clean.group_by("k").agg(E.Sum(col("v")).alias("s"))
    local = _canon([tuple(r.values()) for r in q_clean.collect()])

    df = from_arrow(t, fault_conf, batch_rows=512, partitions=4)
    df.shuffle_partitions = 3
    q = df.group_by("k").agg(E.Sum(col("v")).alias("s"))
    with TcpShuffleCluster(n_workers=2) as c:
        out = c.run_query(q)
        assert _canon(_rows(out)) == local


def test_cluster_trace_context_propagates(cluster, rng):
    """The tentpole acceptance: one query run under an activated
    TraceContext produces ONE merged trace whose cluster:map/cluster:reduce
    spans were recorded by >= 2 distinct worker processes, all parented on
    the driver's root span — and the merged Chrome trace still validates
    in the trace-viewer checker."""
    from spark_rapids_tpu.obs import span as _span
    from spark_rapids_tpu.obs import trace_export as _te
    from spark_rapids_tpu.utils import tracing
    from tools.trace_viewer_check import validate_trace

    trace_conf = RapidsConf({
        "spark.rapids.tpu.sql.enabled": True,
        "spark.rapids.tpu.profile.traceCapture": True,
    })
    n = 3000
    t = pa.table({
        "k": pa.array(rng.integers(0, 29, n), pa.int64()),
        "v": pa.array(rng.integers(0, 100, n), pa.int64()),
    })
    df = from_arrow(t, trace_conf, batch_rows=512, partitions=4)
    df.shuffle_partitions = 4
    q = df.group_by("k").agg(E.Sum(col("v")).alias("s"))
    tracing.set_capture(True, clear=True)
    tctx = _span.new_trace()
    try:
        with _span.activate(tctx):
            cluster.run_query(q)
        per_process = cluster.collect_traces()
    finally:
        tracing.set_capture(False)
        tracing.trace_events(clear=True)

    traces = _span.assemble_traces(per_process)
    assert tctx.trace_id in traces, sorted(traces)
    spans = traces[tctx.trace_id]
    names = {s["name"] for s in spans}
    assert "cluster:map" in names and "cluster:reduce" in names
    # the ONE trace holds spans recorded by >= 2 distinct worker processes
    worker_procs = {s["process"] for s in spans if s["process"] != "driver"}
    assert len(worker_procs) >= 2, worker_procs
    # every task span parents on the driver's root span id — the wire
    # context, not a fabricated per-worker trace
    for s in spans:
        if s["name"] in ("cluster:map", "cluster:reduce"):
            assert s["parent_id"] == tctx.span_id, s
    # sub-spans recorded inside a task (shuffle:write under cluster:map)
    # parent on the task span, one level down
    by_id = {s["span_id"]: s for s in spans}
    writes = [s for s in spans if s["name"] == "shuffle:write"]
    assert writes, names
    for s in writes:
        assert by_id[s["parent_id"]]["name"] == "cluster:map", s
    # the merged multi-process Chrome trace still validates for viewers
    merged = _te.merge_process_traces(per_process)
    assert validate_trace(merged) == []
    traced = [e for e in merged["traceEvents"]
              if e.get("ph") == "X"
              and (e.get("args") or {}).get("trace_id") == tctx.trace_id]
    assert {e["pid"] for e in traced} >= {
        e["pid"] for e in merged["traceEvents"]
        if e.get("ph") == "X" and e["name"] == "cluster:map"}


def test_cluster_untraced_query_records_no_task_spans(cluster, rng):
    """Without an activated context the workers must not fabricate orphan
    single-span traces: task_span() is a no-op when nothing propagated."""
    from spark_rapids_tpu.obs import span as _span
    from spark_rapids_tpu.utils import tracing

    trace_conf = RapidsConf({
        "spark.rapids.tpu.sql.enabled": True,
        "spark.rapids.tpu.profile.traceCapture": True,
    })
    was_enabled = _span.enabled()
    _span.set_enabled(False)   # simulate spans.enabled=false on the driver
    t = pa.table({
        "k": pa.array(rng.integers(0, 7, 800), pa.int64()),
        "v": pa.array(rng.integers(0, 9, 800), pa.int64()),
    })
    df = from_arrow(t, trace_conf, batch_rows=256, partitions=2)
    df.shuffle_partitions = 2
    tracing.set_capture(True, clear=True)
    try:
        cluster.run_query(df.group_by("k").agg(E.Sum(col("v")).alias("s")))
        per_process = cluster.collect_traces()
    finally:
        _span.set_enabled(was_enabled)
        tracing.set_capture(False)
        tracing.trace_events(clear=True)
    assert _span.assemble_traces(per_process) == {}

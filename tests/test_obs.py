"""Observability layer tests: QueryProfile aggregation, explain_analyze
rendering, Chrome trace_event export validity, Prometheus exposition,
metrics-level filtering, task-metrics registry bounds, and trace-window
hygiene (docs/observability.md).
"""

import json
import pathlib
import sys
import threading

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.config.conf import RapidsConf
from spark_rapids_tpu.exec import base as B
from spark_rapids_tpu.exprs.expr import Count, Sum, col
from spark_rapids_tpu.obs import (
    QueryProfile,
    collect_node_stats,
    gauge_snapshot,
    get_profile,
    health,
    histo,
    journal,
    merge_process_traces,
    render_prometheus,
    to_chrome_trace,
)
from spark_rapids_tpu.plan import from_arrow
from spark_rapids_tpu.utils import task_metrics as TM
from spark_rapids_tpu.utils import tracing

_ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))
from tools.trace_viewer_check import validate_trace  # noqa: E402


def sample_table(n=500, seed=3):
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": pa.array(rng.integers(0, 4, n), pa.int64()),
        "v": pa.array(rng.random(n) * 10, pa.float64()),
    })


def _run_profiled(conf=None):
    df = (from_arrow(sample_table(), conf)
          .filter(col("v") > 1.0)
          .group_by("k")
          .agg(Sum(col("v")).alias("sv"), Count().alias("n")))
    rows = df.collect()
    return df, rows


# -- QueryProfile aggregation ---------------------------------------------

def test_query_profile_aggregates_everything():
    df, rows = _run_profiled()
    prof = df.last_profile()
    assert prof is not None and prof.finished
    d = prof.to_dict()
    assert d["wall_ms"] > 0
    # the plan tree made it in: aggregate root over the source leaf
    names = [n["name"] for n in d["nodes"]]
    assert any("Aggregate" in n for n in names)
    assert d["nodes"][0]["parent"] is None
    # root row count matches what collect() returned
    assert d["nodes"][0]["metrics"]["numOutputRows"] == len(rows)
    # every layer is represented in the one structured dict
    assert any(k.endswith(".opTime") for k in d["metrics"])
    assert "pool_used_bytes" in d["gauges"]
    assert "filecache_hit_total" in d["gauges"]
    assert "retry_count" in d["task_metrics"]
    assert d["plan_explain"]  # static explain captured at plan time
    # registered and retrievable by id
    assert get_profile(prof.query_id) is prof


def test_profile_disabled_by_conf():
    conf = RapidsConf({"spark.rapids.tpu.profile.enabled": False})
    df, _ = _run_profiled(conf)
    assert df.last_profile() is None
    # explain_analyze degrades to the static plan instead of raising
    assert "Aggregate" in df.explain_analyze()


# -- explain_analyze -------------------------------------------------------

def test_explain_analyze_renders_metrics_inline():
    df, rows = _run_profiled()
    text = df.last_profile().explain_analyze()
    lines = text.splitlines()
    assert lines[0].startswith("== Query Profile #")
    assert lines[1].startswith("phases: ")  # phase attribution header
    assert f"rows={len(rows)}" in lines[2]  # root line carries its rows
    assert "opTime=" in lines[2] and "batches=" in lines[2]
    # children are indented under the root with the explain-style prefix
    assert any(l.lstrip().startswith("+- ") for l in lines[3:])
    # ns-suffixed metrics are rendered as milliseconds
    assert "Ns=" not in text


def test_dataframe_explain_analyze_executes():
    df, _ = _run_profiled()
    text = df.explain_analyze()
    assert "rows=" in text and "opTime=" in text


# -- Chrome trace export ---------------------------------------------------

def test_chrome_trace_schema_valid(tmp_path):
    conf = RapidsConf({"spark.rapids.tpu.profile.traceCapture": True})
    df, _ = _run_profiled(conf)
    prof = df.last_profile()
    assert prof.events, "trace capture was on: operator spans expected"
    path = prof.dump_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        obj = json.load(f)
    assert validate_trace(obj) == []
    assert obj["displayTimeUnit"] == "ms"
    evs = obj["traceEvents"]
    spans = [e for e in evs if e["ph"] == "X"]
    assert spans and all(
        isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        and e["dur"] >= 0 and e["name"] for e in spans)
    # per-operator batch spans AND per-node summary spans are both present
    assert any(e.get("args", {}).get("partition") is not None for e in spans)
    meta = [e for e in evs if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in meta)


def test_trace_viewer_check_rejects_garbage():
    assert validate_trace({"no": "traceEvents"})
    assert validate_trace({"traceEvents": []})
    bad = {"traceEvents": [{"ph": "X", "name": "a", "ts": -1, "dur": 2}]}
    assert any("negative ts" in e for e in validate_trace(bad))
    good = {"traceEvents": [{"ph": "X", "name": "a", "ts": 0, "dur": 2,
                             "pid": 1, "tid": 1}]}
    assert validate_trace(good) == []


def test_trace_export_rebases_timestamps():
    events = [
        {"name": "b", "start_ns": 2_000_000, "dur_ns": 1000, "thread": 7},
        {"name": "a", "start_ns": 1_000_000, "dur_ns": 1000, "thread": 7},
    ]
    obj = to_chrome_trace(events)
    spans = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert min(e["ts"] for e in spans) == 0  # rebased to window start
    assert {e["name"] for e in spans} == {"a", "b"}


# -- Prometheus exposition -------------------------------------------------

def test_prometheus_exposition():
    text = render_prometheus()
    for family in ("srtpu_pool_used_bytes", "srtpu_spill_to_host_total",
                   "srtpu_semaphore_wait_ns_total", "srtpu_filecache_hit_total",
                   "srtpu_shuffle_bytes_written_total"):
        assert f"# HELP {family} " in text
        assert f"# TYPE {family} " in text
        assert any(l.startswith(family + " ")
                   for l in text.splitlines()), family
    # snapshot keys and catalog stay in lockstep
    snap = gauge_snapshot()
    from spark_rapids_tpu.obs.gauges import CATALOG
    assert set(snap) == {name for name, _, _ in CATALOG}


# -- metrics levels --------------------------------------------------------

def test_metrics_level_filters_collection():
    prev = B.get_metrics_level()
    try:
        conf = RapidsConf(
            {"spark.rapids.tpu.sql.metrics.level": "ESSENTIAL"})
        df, rows = _run_profiled(conf)
        snap = df.last_profile().nodes[0]["metrics"]
        assert snap["numOutputRows"] == len(rows)   # ESSENTIAL stays
        assert "numOutputBatches" not in snap       # MODERATE filtered
        # back to MODERATE: batches are collected again
        df2, _ = _run_profiled(RapidsConf({}))
        assert "numOutputBatches" in df2.last_profile().nodes[0]["metrics"]
    finally:
        B.set_metrics_level(prev)


def test_metrics_level_disabled_metric_still_addable():
    prev = B.get_metrics_level()
    try:
        B.set_metrics_level("ESSENTIAL")

        class _Op(B.LeafExec):
            pass

        op = _Op()
        # operator code paths add/time unconditionally; placeholders absorb
        op.metrics["numOutputBatches"].add(5)
        with op.timer("numOutputBatches"):
            pass
        assert op.metrics["numOutputBatches"].value == 5  # timer no-oped
        assert "numOutputBatches" not in op.metrics_snapshot()
        with pytest.raises(ValueError):
            B.set_metrics_level("VERBOSE")
    finally:
        B.set_metrics_level(prev)


# -- task-metrics registry bounds ------------------------------------------

def test_task_registry_bounded():
    base = TM.registry_sizes()["active"]
    for i in range(TM.FINISHED_CAPACITY + 100):
        TM.start_task(1_000_000 + i)
        TM.add("retry_count", 1)
        TM.finish_task()
    sizes = TM.registry_sizes()
    assert sizes["active"] == base          # finish_task evicts from active
    assert sizes["finished"] <= TM.FINISHED_CAPACITY
    # most recent attempts survive, the oldest were evicted
    assert TM.get_task(1_000_000 + TM.FINISHED_CAPACITY + 99) is not None
    assert TM.get_task(1_000_000) is None


def test_task_aggregate_snapshot_sums_and_maxes():
    TM.start_task(2_000_001)
    TM.add("spill_to_host_bytes", 100)
    TM.watermark("max_device_bytes", 7)
    TM.finish_task()
    TM.start_task(2_000_002)
    TM.add("spill_to_host_bytes", 50)
    TM.watermark("max_device_bytes", 3)
    TM.finish_task()
    agg = TM.aggregate_snapshot()
    assert agg["spill_to_host_bytes"] >= 150   # summed
    assert agg["max_device_bytes"] >= 7        # high-water, not summed


# -- trace window hygiene --------------------------------------------------

def test_back_to_back_windows_do_not_mix(tmp_path):
    # stale events recorded outside any window must not leak into the next
    tracing.set_capture(True)
    tracing.record_event("stale", 0, 1)
    tracing.set_capture(False)
    with tracing.Profiler(str(tmp_path / "w1")):
        with tracing.TraceRange("first"):
            pass
    w1 = [e["name"] for e in tracing.trace_events()]
    assert "first" in w1 and "stale" not in w1
    with tracing.Profiler(str(tmp_path / "w2")):
        with tracing.TraceRange("second"):
            pass
    w2 = [e["name"] for e in tracing.trace_events(clear=True)]
    assert "second" in w2 and "first" not in w2


def test_record_event_off_window_dropped():
    tracing.set_capture(False)
    before = len(tracing.trace_events())
    tracing.record_event("dropped", 0, 1)
    assert len(tracing.trace_events()) == before


def test_query_profile_owns_capture_only_when_free(tmp_path):
    # a user-managed Profiler window must not be clobbered by a profile
    with tracing.Profiler(str(tmp_path / "user")):
        p = QueryProfile(capture_trace=True).start()
        assert not p._owned_capture
        p.finish()
        assert tracing.capturing()  # user window still open
    assert not tracing.capturing()
    tracing.trace_events(clear=True)


# -- event journal ---------------------------------------------------------

def test_journal_records_query_lifecycle():
    journal.clear()
    df, _ = _run_profiled()
    qid = df.last_profile().query_id
    kinds = [e["kind"] for e in journal.recent(query_id=qid)]
    assert kinds[0] == "submit" and kinds[-1] == "finish"
    phases = [e["phase"] for e in journal.recent("phase", query_id=qid)]
    assert {"plan-rewrite", "reuse", "fusion"} <= set(phases)
    fin = journal.recent("finish", query_id=qid)[0]
    assert fin["wall_ms"] > 0 and "compile_ms" in fin
    # phase attribution also lands in the profile itself
    d = df.last_profile().to_dict()
    assert {"plan-rewrite", "compile", "execute"} <= set(d["phases"])
    assert "phases:" in df.last_profile().explain_analyze()
    assert {"p50", "p95", "p99"} == set(d["latency"]["query_wall"])


def test_journal_bounded_eviction():
    journal.clear()
    old_cap = journal.capacity()
    try:
        journal.set_capacity(16)
        for i in range(50):
            journal.emit("evict-test", seq=i)
        evs = journal.recent("evict-test")
        assert len(evs) == 16
        assert evs[-1]["seq"] == 49          # newest retained
        assert journal.counters()["journal_evicted_total"] >= 34
    finally:
        journal.set_capacity(old_cap)
        journal.clear()


def test_journal_disabled_is_silent():
    journal.clear()
    try:
        journal.set_enabled(False)
        assert journal.emit("off-test") is None
        assert journal.recent("off-test") == []
        assert journal.counters()["journal_events_total"] == 0
    finally:
        journal.set_enabled(True)


def test_journal_concurrent_emits_no_lost_updates():
    journal.clear()
    n_threads, per_thread = 8, 500
    barrier = threading.Barrier(n_threads)

    def worker(t):
        barrier.wait()
        for i in range(per_thread):
            journal.emit("conc-test", thread=t, seq=i)

    ts = [threading.Thread(target=worker, args=(t,)) for t in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert journal.counters()["journal_events_total"] == n_threads * per_thread
    # the bounded ring holds min(capacity, emitted), never more
    assert len(journal.recent("conc-test")) <= journal.capacity()
    journal.clear()


def test_journal_dump_jsonl_roundtrips(tmp_path):
    journal.clear()
    journal.emit("dump-test", query_id=7, note="hello")
    path = journal.dump_jsonl(str(tmp_path / "journal.jsonl"))
    lines = [json.loads(l) for l in open(path)]
    assert any(e["kind"] == "dump-test" and e["query_id"] == 7 for e in lines)
    journal.clear()


# -- latency histograms ----------------------------------------------------

def test_histogram_percentile_within_bucket_resolution():
    h = histo.Histogram("t")
    for _ in range(1000):
        h.record(10_000_000)  # 10ms
    for p in ("p50", "p95", "p99"):
        v = h.percentiles_ms()[p]
        assert 5.0 <= v <= 20.0, (p, v)  # log2 buckets: within 2x


def test_histogram_concurrent_records_no_lost_updates():
    h = histo.Histogram("conc")
    n_threads, per_thread = 8, 2000
    barrier = threading.Barrier(n_threads)

    def worker():
        barrier.wait()
        for _ in range(per_thread):
            h.record(1_000_000)

    ts = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    s = h.snapshot()
    assert s["count"] == n_threads * per_thread
    assert s["sum"] == n_threads * per_thread * 1_000_000


def test_histogram_window_diff():
    h = histo.get("shuffle_fetch_ns")
    s0 = h.snapshot()
    for _ in range(100):
        h.record(2_000_000)
    win = histo.diff(s0, h.snapshot())
    assert win["count"] == 100
    assert 1.0 <= h.percentiles_ms(win)["p50"] <= 4.0


def test_histogram_disabled_and_undeclared():
    try:
        histo.set_enabled(False)
        before = histo.get("retry_backoff_ns").snapshot()["count"]
        histo.record("retry_backoff_ns", 123)
        assert histo.get("retry_backoff_ns").snapshot()["count"] == before
    finally:
        histo.set_enabled(True)
    with pytest.raises(KeyError):
        histo.get("not_declared_ns")


def test_prometheus_histogram_families():
    histo.record("query_wall_ns", 50_000_000)
    text = render_prometheus()
    assert "# TYPE srtpu_query_wall_seconds histogram" in text
    lines = text.splitlines()
    buckets = [l for l in lines
               if l.startswith("srtpu_query_wall_seconds_bucket")]
    assert buckets and buckets[-1].startswith(
        'srtpu_query_wall_seconds_bucket{le="+Inf"}')
    # cumulative: counts never decrease along the le ladder
    counts = [int(l.rsplit(" ", 1)[1]) for l in buckets]
    assert counts == sorted(counts)
    assert any(l.startswith("srtpu_query_wall_seconds_sum ") for l in lines)
    assert any(l.startswith("srtpu_query_wall_seconds_count ") for l in lines)


# -- worker health registry ------------------------------------------------

def test_health_registry_stall_flag_and_recovery():
    reg = health.HealthRegistry()
    journal.clear()
    reg.report("w0", kind="cluster", progress=True)
    reg.report("w1", kind="cluster", progress=True)
    assert reg.sweep_stalled(60.0) == []          # fresh progress
    stalled = reg.sweep_stalled(0.0)
    assert sorted(stalled) == ["w0", "w1"]
    assert reg.sweep_stalled(0.0) == []           # flagged once per episode
    assert {e["worker"] for e in journal.recent("worker-stale")} == \
        {"w0", "w1"}
    v = reg.view()
    assert v["stale"] == 2 and v["alive"] == 0
    # a heartbeat recovers the worker; the next sweep may re-flag it
    reg.report("w0", progress=True)
    assert reg.view()["alive"] == 1
    assert reg.sweep_stalled(0.0) == ["w0"]
    assert reg.counters()["worker_stale_total"] == 3
    journal.clear()


def test_health_registry_merged_gauges_and_lost():
    reg = health.HealthRegistry()
    journal.clear()
    reg.report("a", gauges={"pool_used_bytes": 100, "oom": 1})
    reg.report("b", gauges={"pool_used_bytes": 50})
    v = reg.view()
    assert v["merged_gauges"]["pool_used_bytes"] == 150
    assert [w["worker_id"] for w in v["workers"]] == ["a", "b"]
    reg.remove("a", lost=True)
    reg.remove("never-registered", lost=True)     # no-op, no event
    assert reg.counters()["worker_lost_total"] == 1
    assert [e["worker"] for e in journal.recent("worker-lost")] == ["a"]
    journal.clear()


# -- merged multi-worker traces --------------------------------------------

def test_merge_process_traces_multiworker(tmp_path):
    per = {
        "worker-1": [{"name": "task:map:s1", "start_ns": 2_000_000,
                      "dur_ns": 500_000, "thread": 11,
                      "args": {"worker": "worker-1"}}],
        "driver": [{"name": "plan", "start_ns": 1_000_000,
                    "dur_ns": 200_000, "thread": 1}],
        "worker-0": [{"name": "task:reduce:s1", "start_ns": 3_000_000,
                      "dur_ns": 400_000, "thread": 12}],
    }
    obj = merge_process_traces(per)
    assert validate_trace(obj) == []
    spans = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert len({e["pid"] for e in spans}) == 3    # one track per process
    # driver gets pid 1 and the earliest event rebases to ts 0
    names = {e["args"]["name"]: e["pid"]
             for e in obj["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert names["driver"] == 1
    assert {"worker-0", "worker-1"} <= set(names)
    assert min(e["ts"] for e in spans) == 0
    path = tmp_path / "merged.json"
    path.write_text(json.dumps(obj))
    from tools.trace_viewer_check import check_file
    assert check_file(str(path)) == []


def test_tracing_process_label_stamps_events():
    prev = tracing.process_label()
    try:
        tracing.set_process_label("worker-7")
        tracing.set_capture(True, clear=True)
        tracing.record_event("labeled", 0, 10)
        tracing.record_event("labeled2", 0, 10, args={"x": 1})
        evs = tracing.trace_events(clear=True)
        assert all(e["args"]["worker"] == "worker-7" for e in evs)
        assert evs[1]["args"]["x"] == 1
    finally:
        tracing.set_capture(False)
        tracing.set_process_label(prev)


# -- gauge catalog static guard --------------------------------------------

def test_gauge_catalog_guard_passes_on_tree():
    from tools.lint import gauge_catalog as G
    assert G.run_pass(str(_ROOT)) == []


def test_gauge_catalog_guard_catches_undeclared(tmp_path):
    from tools.lint import gauge_catalog as G
    declared = G.catalog_names(str(_ROOT))
    assert "pool_oom_total" in declared
    bad = tmp_path / "bad.py"
    bad.write_text(
        "def counters():\n"
        "    return {'made_up_thing_total': 1}\n"
        "_C = {}\n"
        "_C['other_unknown_total'] = 2\n"
        "def f(note):\n"
        "    note('third_unknown_total', 1)\n"
        "    alias('year_total')\n"   # SQL alias shape: must NOT be flagged
    )
    violations = []
    G.check_file(str(bad), declared, violations, root=str(_ROOT))
    flagged = " ".join(violations)
    assert "made_up_thing_total" in flagged
    assert "other_unknown_total" in flagged
    assert "third_unknown_total" in flagged
    assert "year_total" not in flagged


# -- span model + trace reassembly (obs/span.py) ---------------------------

def test_span_wire_roundtrip_and_ids():
    from spark_rapids_tpu.obs import span as sp

    ctx = sp.new_trace()
    back = sp.TraceContext.from_wire(ctx.to_wire())
    assert (back.trace_id, back.span_id) == (ctx.trace_id, ctx.span_id)
    assert sp.TraceContext.from_wire(None) is None
    # ids are fresh per trace
    other = sp.new_trace()
    assert other.trace_id != ctx.trace_id


def test_span_undeclared_name_raises():
    from spark_rapids_tpu.obs import span as sp

    with pytest.raises(KeyError):
        sp.Span("not:declared")
    with pytest.raises(KeyError):
        sp.record_span("also:not-declared", 0, 1, ctx=sp.new_trace())


def test_span_parenting_and_activation():
    from spark_rapids_tpu.obs import span as sp

    tracing.set_capture(True, clear=True)
    root = sp.new_trace()
    try:
        with sp.activate(root):
            assert sp.current() is root
            with sp.span("query:plan", attrs={"q": "q1"}) as outer:
                assert outer.parent_id == root.span_id
                # the child context is installed for nested spans
                inner_id = sp.record_span(
                    "query:compile", 0, 1000)
                assert inner_id is not None
            # context restored after the with-block
            assert sp.current() is root
        assert sp.current() is None
        events = tracing.trace_events(clear=True)
    finally:
        tracing.set_capture(False)
        tracing.trace_events(clear=True)
    spans = {e["args"]["span_id"]: e for e in sp.span_events(events)}
    inner = spans[inner_id]["args"]
    assert inner["trace_id"] == root.trace_id
    assert inner["parent_id"] == outer.span_id


def test_task_span_noop_without_context():
    """Worker-side sites must not fabricate orphan traces."""
    from spark_rapids_tpu.obs import span as sp

    tracing.set_capture(True, clear=True)
    try:
        with sp.task_span("cluster:map") as s:
            assert s is None
        with sp.activate(sp.new_trace()):
            with sp.task_span("cluster:map") as s:
                assert s is not None
        events = tracing.trace_events(clear=True)
    finally:
        tracing.set_capture(False)
        tracing.trace_events(clear=True)
    assert len(sp.span_events(events)) == 1


def test_span_disabled_records_nothing():
    from spark_rapids_tpu.obs import span as sp

    tracing.set_capture(True, clear=True)
    try:
        sp.set_enabled(False)
        assert sp.record_span("query:plan", 0, 1,
                              ctx=sp.new_trace()) is None
        with sp.span("query:plan") as s:
            assert s is None
        events = tracing.trace_events(clear=True)
    finally:
        sp.set_enabled(True)
        tracing.set_capture(False)
        tracing.trace_events(clear=True)
    assert sp.span_events(events) == []


def test_assemble_traces_merges_processes():
    from spark_rapids_tpu.obs import span as sp

    root = sp.new_trace()

    def ev(name, span_id, parent_id, start, proc_extra=None):
        args = {"trace_id": root.trace_id, "span_id": span_id,
                "parent_id": parent_id}
        args.update(proc_extra or {})
        return {"name": name, "start_ns": start, "dur_ns": 10,
                "thread": 1, "args": args}

    per = {
        "driver": [ev("query:submit", "s1", root.span_id, 100),
                   {"name": "not-a-span", "start_ns": 0, "dur_ns": 1,
                    "thread": 1, "args": {}}],
        "worker-0": [ev("cluster:map", "m1", "s1", 200, {"shuffle": 3})],
        "worker-1": [ev("cluster:reduce", "r1", "s1", 300)],
    }
    traces = sp.assemble_traces(per)
    assert set(traces) == {root.trace_id}
    spans = traces[root.trace_id]
    assert [s["name"] for s in spans] == [
        "query:submit", "cluster:map", "cluster:reduce"]  # start_ns order
    assert {s["process"] for s in spans} == {
        "driver", "worker-0", "worker-1"}
    m = [s for s in spans if s["span_id"] == "m1"][0]
    assert m["parent_id"] == "s1" and m["attrs"]["shuffle"] == 3


def test_span_catalog_lint_shape():
    """obs/span.CATALOG stays a statically-parseable literal of 2-tuples
    (tools/lint/span_catalog.py and docs render both depend on it)."""
    import ast as _ast
    from spark_rapids_tpu.obs import span as sp

    src = pathlib.Path(sp.__file__).read_text()
    lit = None
    for node in _ast.walk(_ast.parse(src)):
        if (isinstance(node, _ast.AnnAssign)
                and getattr(node.target, "id", None) == "CATALOG"):
            lit = _ast.literal_eval(node.value)
    assert lit is not None
    assert lit == sp.CATALOG
    assert all(isinstance(n, str) and isinstance(h, str) for n, h in lit)


# -- one span model: event + profiler annotation, real intervals -----------

class _CountingLock:
    """Stands in for a threading.Lock and counts how often it is taken."""

    def __init__(self):
        self._lock = threading.Lock()
        self.acquired = 0

    def __enter__(self):
        self.acquired += 1
        return self._lock.__enter__()

    def __exit__(self, *exc):
        return self._lock.__exit__(*exc)


def test_span_with_capture_off_takes_no_lock_and_draws_no_ids(monkeypatch):
    """With capture off and no profiler running, spans that stay in their
    process cost no lock and no os.urandom; the same spans with capture on
    take the event log's lock once each and still draw nothing."""
    import os as _os
    from spark_rapids_tpu.obs import span as sp

    lock = _CountingLock()
    monkeypatch.setattr(tracing, "_events_lock", lock)
    drawn = []
    real = _os.urandom
    monkeypatch.setattr(sp.os, "urandom",
                        lambda n: drawn.append(n) or real(n))

    def work():
        with sp.span("query:execute", attrs={"query": "q"}) as outer:
            with sp.span("query:plan"):
                sp.record_span("query:queue-wait", 0, 10)
            with sp.task_span("query:finish"):
                pass
            with tracing.TraceRange("SomeExec"):
                pass
        return outer

    tracing.set_capture(False)
    base = lock.acquired
    outer = work()
    assert lock.acquired == base and drawn == []
    # ids exist all the same, unique and ready for a wire that asks
    assert outer.span_id and outer.trace_id != outer.span_id
    assert sp.TraceContext.from_wire(outer.context().to_wire()).span_id \
        == outer.span_id
    tracing.set_capture(True, clear=True)
    try:
        base = lock.acquired
        work()
        assert lock.acquired - base == 5  # four spans and the plain range
        assert drawn == []
        assert len(sp.span_events(tracing.trace_events(clear=True))) == 4
    finally:
        tracing.set_capture(False)
        tracing.trace_events(clear=True)


def test_span_ids_are_unique_across_threads_and_reseeded_processes():
    from spark_rapids_tpu.obs import span as sp

    ids, lock = set(), threading.Lock()

    def draw():
        mine = [sp._new_id() for _ in range(2000)]
        with lock:
            ids.update(mine)
    ts = [threading.Thread(target=draw) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert len(ids) == 16000
    prefix = sp._id_prefix
    sp._reseed_ids()  # what a forked child runs
    try:
        assert sp._id_prefix != prefix
        assert sp._new_id() not in ids
    finally:
        sp._reseed_ids()


def test_jax_profiler_trace_holds_an_annotation_per_live_span(tmp_path):
    """Every live span (and the per-batch operator range) is also a
    jax.profiler.TraceAnnotation of the same name: a profiler trace taken
    on the CPU carries them on its own clock, each as long as the event the
    program logged. The two spans stamped after the fact carry none."""
    import glob

    import jax
    from jax.profiler import ProfileData
    from spark_rapids_tpu.obs import span as sp
    from spark_rapids_tpu.serve import QueryServer

    conf = RapidsConf()
    df = (from_arrow(sample_table(), conf, partitions=1)
          .group_by("k").agg(Sum(col("v")).alias("s")).sort("k"))
    srv = QueryServer(conf)
    try:
        srv.submit(df, name="unprofiled").result(timeout_s=120)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        tracing.set_capture(True, clear=True)
        try:
            with sp.span("net:request"):
                with sp.span("net:client-send"):
                    pass
                srv.submit(df, name="profiled",
                           trace=sp.current()).result(timeout_s=120)
            events = tracing.trace_events(clear=True)
        finally:
            tracing.set_capture(False)
            tracing.trace_events(clear=True)
            jax.profiler.stop_trace()
    finally:
        srv.close()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    annotated = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    annotated.setdefault(e.name, []).append(e.duration_ns)
    logged = {}
    for e in events:
        logged.setdefault(e["name"], []).append(e["dur_ns"])
    stamped = {"query:queue-wait", "net:wake-lag"}
    live = set(logged) - stamped
    assert {"net:request", "net:client-send", "query:submit", "query:admit",
            "query:execute", "query:plan", "query:readback", "query:finish",
            "exec:host-sync", "SortExec"} <= live
    assert "query:queue-wait" in logged
    declared = {name for name, _ in sp.CATALOG}
    for name in live:
        got = sorted(annotated.get(name, ()), reverse=True)
        want = sorted(logged[name], reverse=True)
        if name in declared:
            assert len(got) == len(want), name
        else:
            # an operator's last next() (StopIteration) is annotated too,
            # and logs no event
            assert len(got) >= len(want), name
        # the annotation encloses the logged interval, by microseconds
        for a, e in zip(got, want):
            assert 0 <= a - e < 2_000_000, (name, a, e)
    assert not stamped & set(annotated)


def test_known_plan_yields_its_host_syncs_as_spans_and_counts():
    """A plan whose blocking device->host reads are known by site yields
    exactly those exec:host-sync spans under a trace, and the always-on
    counter pair rises by the same number with tracing on or off."""
    from spark_rapids_tpu.obs import span as sp

    conf = RapidsConf()
    df = (from_arrow(sample_table(), conf, partitions=1)
          .group_by("k").agg(Sum(col("v")).alias("s")).sort("k"))
    df.to_arrow()  # bind the programs

    def run(traced):
        before = gauge_snapshot()
        tracing.set_capture(traced, clear=True)
        try:
            with sp.activate(sp.new_trace() if traced else None):
                df.to_arrow()
            events = tracing.trace_events(clear=True)
        finally:
            tracing.set_capture(False)
            tracing.trace_events(clear=True)
        after = gauge_snapshot()
        return (events,
                after["exec_host_sync_total"] - before["exec_host_sync_total"],
                after["exec_host_sync_ns_total"]
                - before["exec_host_sync_ns_total"])

    def operators(n):
        return (1 + len(getattr(n, "fused_ops", ()))
                + sum(operators(c) for c in n.children))

    n_ops = operators(df.physical_plan())  # the memo's tree: what ran
    assert n_ops == 4  # sort, fused stage, its aggregate, batch source
    events, rose, ns = run(traced=True)
    syncs = [e for e in sp.span_events(events) if e["name"] == "exec:host-sync"]
    sites = sorted(e["args"]["site"] for e in syncs)
    # one result batch read back, and at finish one fold of the pending
    # device row counts per operator of the plan: nothing else blocks
    assert sites == ["batch_to_arrow"] + ["metrics.rows"] * n_ops
    assert rose == len(syncs)
    assert ns == sum(e["dur_ns"] for e in syncs) > 0
    _, rose_off, ns_off = run(traced=False)
    assert rose_off == rose and ns_off > 0


def test_spans_stay_out_of_the_lifecycle_journal():
    """Spans go to the event log and the profiler; the bounded journal
    keeps its room for lifecycle events."""
    from spark_rapids_tpu.obs import span as sp

    journal.clear()
    tracing.set_capture(True, clear=True)
    try:
        with sp.span("query:execute"):
            sp.record_span("query:queue-wait", 0, 5)
        assert len(sp.span_events(tracing.trace_events(clear=True))) == 2
    finally:
        tracing.set_capture(False)
        tracing.trace_events(clear=True)
    assert [e for e in journal.recent() if e["kind"] == "span"] == []


def test_trace_range_is_the_one_primitive():
    """TraceRange: args may come late, a dropped range logs nothing, and
    Span is built on it (same start, same name)."""
    from spark_rapids_tpu.obs import span as sp

    tracing.set_capture(True, clear=True)
    try:
        rng = tracing.TraceRange("SomeExec").open()
        rng.close(args={"partition": 3})
        tracing.TraceRange("Dropped").open().close(record=False)
        s = sp.Span("query:plan")
        assert isinstance(s._range, tracing.TraceRange)
        assert s.start_ns == s._range.start_ns > rng.start_ns
        s.finish()
        s.finish()  # inert after the first
        events = tracing.trace_events(clear=True)
    finally:
        tracing.set_capture(False)
        tracing.trace_events(clear=True)
    assert [e["name"] for e in events] == ["SomeExec", "query:plan"]
    assert events[0]["args"] == {"partition": 3}


def test_span_clock_matches_each_event_to_its_nearest_annotation():
    """tools/span_clock.py: an event's shifted start against the nearest
    annotation of its name; a name with no annotation is missing unless it
    is one of the two stamped spans."""
    from tools import span_clock

    annotations = {"query:plan": [1_000, 5_000, 9_000],
                   "SortExec": [2_000]}
    events = [{"name": "query:plan", "start_ns": 40},     # -> 1040: +(-40)
              {"name": "query:plan", "start_ns": 8_100},  # -> 9100: -100
              {"name": "SortExec", "start_ns": 1_500},    # -> 2500: -500
              {"name": "net:wake-lag", "start_ns": 7},
              {"name": "query:finish", "start_ns": 9}]
    diffs = span_clock.match(annotations, events, shift=1_000)
    assert diffs["query:plan"] == [-40, -100]
    assert diffs["SortExec"] == [-500]
    rep = span_clock.summarize(diffs)
    assert rep["missing"] == ["query:finish"]
    assert rep["stamped_absent"] == ["net:wake-lag"]
    assert (rep["n"], rep["median_ns"], rep["max_abs_ns"]) == (3, -100, 500)
    assert rep["names"]["query:plan"] == {"n": 2, "median_ns": -70.0,
                                          "max_abs_ns": 100}


def test_named_scopes_are_metadata_only():
    """The kernels' jax.named_scope shows in the lowered program's debug
    info (what an op profile groups by) and changes no op: the program
    text without locations equals the unscoped function's."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.batch import batch_from_arrow
    from spark_rapids_tpu.exec import kernels as K

    b = batch_from_arrow(pa.table({"a": pa.array(np.arange(10), pa.int64())}))
    keys = [jnp.arange(64, dtype=jnp.uint64), jnp.ones(64, jnp.uint64)]
    for fn, args, static, scope in [
            (K.concat_device, ([b, b], 2 * b.capacity, (0,)), (1, 2),
             "jit(concat_device)/concat/"),
            (K.lexsort_chain, (keys,), (),
             "jit(lexsort_chain)/sort.lexsort/")]:
        scoped = jax.jit(fn, static_argnums=static).lower(*args)
        assert scope in scoped.as_text(debug_info=True)
        bare = jax.jit(fn.__wrapped__, static_argnums=static).lower(*args)
        assert scope not in bare.as_text(debug_info=True)
        assert scoped.as_text() == bare.as_text()


# -- labeled histogram families (per-tenant SLOs) --------------------------

def test_histo_labeled_families_and_reset():
    histo.reset_all()
    histo.record_labeled("serve_queue_wait_ns", 5_000_000,
                         tenant="acme", priority=1)
    histo.record_labeled("serve_queue_wait_ns", 9_000_000,
                         tenant="acme", priority=1)
    histo.record_labeled("serve_queue_wait_ns", 1_000_000,
                         tenant="zed", priority=0)
    fam = histo.family("serve_queue_wait_ns")
    key_acme = (("priority", "1"), ("tenant", "acme"))
    assert fam[key_acme].snapshot()["count"] == 2
    assert fam[(("priority", "0"), ("tenant", "zed"))].snapshot()[
        "count"] == 1
    # the base (unlabeled) histogram aggregates every labeled record
    assert histo.get("serve_queue_wait_ns").snapshot()["count"] == 3
    with pytest.raises(KeyError):
        histo.record_labeled("not_declared_ns", 1, tenant="x")
    histo.reset_all()
    assert histo.family("serve_queue_wait_ns") == {}


def test_prometheus_tenant_slo_exposition():
    from spark_rapids_tpu.serve import metrics as sm

    histo.reset_all()
    sm.reset_tenants()
    sm.note_outcome("acme", 1, "completed")
    sm.observe_queue_wait("acme", 1, 4_000_000)
    text = render_prometheus()
    assert ('srtpu_serve_queue_wait_seconds_bucket{priority="1",'
            'tenant="acme",le=') in text
    assert ('srtpu_serve_tenant_outcome_total{tenant="acme",priority="1",'
            'outcome="completed"} 1') in text
    histo.reset_all()
    sm.reset_tenants()


def test_obs_report_demo_writes_a_complete_bundle(tmp_path):
    """``tools/obs_report.py --demo``: the diagnostics bundle an operator
    asks for holds every file its manifest names, non-empty, and a trace
    the viewer accepts. In a process of its own: the demo plants a
    synthetic OOM post-mortem that this process should not carry."""
    import os
    import subprocess

    out = tmp_path / "bundle"
    proc = subprocess.run(
        [sys.executable, str(_ROOT / "tools" / "obs_report.py"), "--demo",
         "--out", str(out)],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    for name in ("profiles.json", "journal.jsonl", "metrics.prom",
                 "trace.json", "config.json", "health.json", "memory.json",
                 "memory.txt", "MANIFEST.json"):
        assert (out / name).stat().st_size > 0, name
    assert validate_trace(json.loads((out / "trace.json").read_text())) == []
    conf = json.loads((out / "config.json").read_text())
    assert "spark.rapids.tpu.sql.enabled" in conf

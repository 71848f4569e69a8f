"""The cell ``sf10_q3_join1`` (configuration ``tpch_sf10_join3``), the three
readers that came with it, and controls that fail: a rehearsal of the whole
run at SF0.01 on the CPU; the configuration against its sibling; the
readers on made-up spans and on a program that records none of them; and,
because money as float64 passes Q3 and Q6 (the scan-only cell kept for a
later issue, PERF.md), the run's own comparison against the controls the
two query files declare (queries/q3.py, q6.py: what control.py runs on the
chip): float32 money for both queries and, for Q3, a dropped probe match, a
duplicated one, two rows swapped and a top ten that misses a true
member."""
import json
import os
import subprocess
import sys

import pytest

import compare
import datagen
import harness
from test_span_readers import _ctx, _ev, _read, _request

NEW = ("exec.join_build_ms_per_query", "exec.topn_ms_per_query",
       "exec.fused_fallbacks_per_query")


def _rehearse(workload):
    p = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         workload, "--seed", "2147483999", "--seconds", "2", "--trace", "1",
         "--rehearse-sf", "0.01"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=900)
    assert p.returncode == 4, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    line = [ln for ln in p.stderr.splitlines()
            if ln.startswith("[bench] rehearsal (no result line): ")][-1]
    out = json.loads(line.split(": ", 1)[1])
    assert out["correct"] is True and out["failed"] == 0, out["compared"]
    for stage in ("import_s", "data_s", "first_queries_s", "warm_s",
                  "check_s"):
        assert stage in out["notes"]["stages_s"]
    return out


def test_rehearsal_of_the_q3_cell_reads_the_new_metrics():
    out = _rehearse("sf10_q3_join1")
    assert out["notes"]["rows"] == {"lineitem": 59997, "orders": 15000,
                                    "customer": 1500}
    assert set(NEW) <= set(out["metrics_read"])


def test_rehearsal_of_a_q1_cell_does_not_list_them():
    out = _rehearse("sf10_q1_agg1")
    assert out["notes"]["rows"] == {"lineitem": 59997}
    assert not set(NEW) & set(out["metrics_read"])
    assert "exec.agg_steps_per_query" in out["metrics_read"]


def _config(name):
    with open(os.path.join(harness.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_the_configuration_is_tpch_sf10_with_two_capabilities():
    """Key for key the sibling of tpch_sf10.json: only the text that names
    the query, the guard in ``conf`` and what is assumed differ; nothing is
    cut."""
    ten, join3 = _config("tpch_sf10"), _config("tpch_sf10_join3")
    assert list(ten) == list(join3)
    same = set(ten) - {"source", "conf", "conf_why", "assumed"}
    assert all(ten[k] == join3[k] for k in same)
    assert join3["conf"] == {"spark.rapids.tpu.requires":
                             "agg.boundedStepPrograms,sort.boundedTopN"}
    assert join3["reduced"] == [] and join3["scale_factor"] == 10.0
    assert join3["rows"] == {t: datagen.rows(t, 10.0)
                             for t in join3["tables"]}
    assert set(ten["assumed"][:3]) <= set(join3["assumed"])
    assert any("l_orderkey" in a for a in join3["assumed"])
    assert any("rebuilt by every request" in a for a in join3["assumed"])
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == "tpch_sf10_join3"][0]
    assert entry["source"] == join3["source"] and entry["reduced"] == []


def test_the_traffic_is_one_closed_stream_of_the_validation_query():
    spec = harness.load_cell("sf10_q3_join1")
    mix, q1 = spec["mix"], harness.load_cell("sf10_q1_agg1")["mix"]
    assert spec["cell"]["traffic"] == "q3_join1"
    assert spec["cell"]["chips"] == 1
    assert mix["streams"] == [{"tenant": "report", "queries": ["q3"],
                               "params": {}}]
    for k in ("tenants", "warm_rounds"):
        assert mix[k] == q1[k]
    # a request takes 1.5 s: a 3 s trace would hold two (ISSUE 35)
    assert mix["trace_seconds"] == 6.0


def test_the_new_metrics_are_declared_for_the_q3_cell_alone():
    for cell in ("sf1_q1_agg1", "sf10_q1_agg1", "sf10_q3_join1"):
        declared = {m["name"]: m for m in
                    harness.load_cell(cell)["per_layer"]}
        assert (set(NEW) <= set(declared)) == (cell == "sf10_q3_join1")
        assert len(declared) == 17 + 3 * (cell == "sf10_q3_join1")
    declared = {m["name"]: m for m in
                harness.load_cell("sf10_q3_join1")["per_layer"]}
    for name in NEW:
        m = declared[name]
        assert (m["layer"], m["moves"], m["source"], m["better"]) == (
            "operators (exec/)", "query_rate", "program_span", "lower")
        assert m["workloads"] == ["sf10_q3_join1"]
        assert os.path.exists(os.path.join(
            harness.HERE, "layer_metrics", name + ".py"))


def _joined(trace, t0, fallbacks=0):
    """A request's operator spans: the orders build (40 ms) with the
    customer build (10 ms) inside it, one top-N, and fallbacks."""
    ex = trace + ":ex"
    ev = [_ev("exec:join-build", t0 + 5, 40, trace, trace + ":jb1", ex,
              path="unique", rows=1460000, capacity=1 << 21),
          _ev("exec:join-build", t0 + 8, 10, trace, trace + ":jb2",
              trace + ":jb1", path="dense", rows=300000, capacity=1 << 19),
          _ev("exec:topn", t0 + 50, 1.5, trace, trace + ":tn", ex, k=10,
              rows=1 << 17, capacity=1024)]
    for i in range(fallbacks):
        ev.append(_ev("exec:fused-fallback", t0 + 46 + i, 1, trace,
                      f"{trace}:fb{i}", ex, cause="carry-overflow"))
    return ev


def test_the_readers_on_made_up_spans():
    spans = (_request("a", "r1", 100) + _joined("a", 100)
             + _request("b", "r2", 200) + _joined("b", 200, fallbacks=2)
             + _request("c", "warm-up", 0) + _joined("c", 0, fallbacks=7))
    ctx = _ctx(spans, ["r1", "r2"])
    # the inner build lies inside the outer: 40 ms a request, not 50
    assert _read("exec.join_build_ms_per_query", ctx) == 40.0
    assert _read("exec.topn_ms_per_query", ctx) == 1.5
    assert _read("exec.fused_fallbacks_per_query", ctx) == 1.0
    # no fallback in any request of the window reads 0, not nothing
    ctx = _ctx(_request("a", "r1", 100) + _joined("a", 100), ["r1"])
    assert _read("exec.fused_fallbacks_per_query", ctx) == 0.0


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_spans_reads_nothing_and_does_not_raise(
        metric):
    """The parent records none of the three spans."""
    assert _read(metric, _ctx(_request("a", "r1", 100), ["r1"])) is None
    assert _read(metric, _ctx([], ["r1"])) is None


def test_a_program_without_the_topn_refuses_the_configuration(monkeypatch):
    """The parent of the PR that added the cell lacks ``sort.boundedTopN``:
    it refuses when the harness makes the conf, before any program runs."""
    from spark_rapids_tpu.config import conf as C
    spec = harness.load_cell("sf10_q3_join1")
    assert harness.program_conf(spec["config"], spec["mix"], "/nowhere")[
        C.REQUIRES] == "agg.boundedStepPrograms,sort.boundedTopN"
    monkeypatch.setattr(C, "CAPABILITIES", {
        "agg.boundedStepPrograms": C.CAPABILITIES["agg.boundedStepPrograms"]})
    with pytest.raises(ValueError, match="this build lacks"):
        harness.program_conf(spec["config"], spec["mix"], "/nowhere")


# -- controls that fail -------------------------------------------------------

SF = 0.05
SEEDS = (3, 2147483651, 77)


def _q(name):
    return harness.load_by_path("queries", name)


def _readings(mod, got, want):
    return compare.answer_readings(compare.control_table(got, mod), want, mod)


def test_q3_reference_imports_nothing_of_the_program():
    src = open(os.path.join(harness.HERE, "queries", "q3.py")).read()
    ref = src[src.index("def reference("):]
    assert "spark_rapids_tpu" not in ref
    assert "spark_rapids_tpu" not in src[:src.index("def build(")]


@pytest.mark.parametrize("seed", SEEDS)
def test_float64_money_passes_both_queries(seed):
    """Why control.py's one control cannot fail these cells: Q6 is one sum
    of some 10^5 products and Q3's sums hold at most seven."""
    raw = datagen.make(["lineitem", "orders", "customer"], SF, seed)
    for name in ("q3", "q6"):
        mod = _q(name)
        assert mod.reference(raw, money=float) == mod.reference(raw)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", ["q6", "q3"])
def test_float32_money_is_not_correct(name, seed):
    """The precision below the one the configuration states that does
    separate: money as float32 reads answers_wrong 1 in either cell."""
    mod = _q(name)
    raw = datagen.make(list(mod.TABLES), SF, seed)
    want = mod.reference(raw)
    r = _readings(mod, mod.CONTROLS["float32_money"](raw, mod.PARAMS), want)
    assert r["wrong"] == 1 and r["units_off"] >= 1, r
    assert _readings(mod, want, want) == {"wrong": 0, "units_off": 0,
                                          "why": ""}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("fault", ["dropped-match", "duplicated-match",
                                   "rows-swapped", "member-missed"])
def test_a_q3_answer_with_a_join_or_order_fault_is_not_correct(fault, seed):
    """What a probe that loses or repeats a match, a sort that misplaces
    two rows, or an approximate top-k would hand back: every one reads
    answers_wrong 1 through the run's own comparison."""
    mod = _q("q3")
    raw = datagen.make(list(mod.TABLES), SF, seed)
    want = mod.reference(raw)
    got = mod.CONTROLS[fault.replace("-", "_")](raw, mod.PARAMS)
    r = _readings(mod, got, want)
    assert r["wrong"] == 1, (fault, r)
    if fault in ("dropped-match", "duplicated-match"):
        assert r["units_off"] > 0 or "differs" in r["why"]
    if fault == "rows-swapped":  # the first nine stand, the tenth does not
        assert all(got[c][:9] == want[c][:9] for c in want)
        assert got["l_orderkey"][9] != want["l_orderkey"][9]
    if fault == "member-missed":
        assert want["l_orderkey"][4] not in got["l_orderkey"]
        assert len(got["l_orderkey"]) == mod.LIMIT

"""The cell ``sf10_q1_agg1`` (configuration ``tpch_sf10``) and the three
readers that came with it: a rehearsal of the whole run at SF0.01 on the
CPU, the readers on made-up spans and counters, and what they give for a
program that records none of it (the parent of the PR that added them)."""
import json
import os
import subprocess
import sys

import pytest

import harness
from test_span_readers import _ctx, _ev, _read, _request

NEW = {"exec.agg_steps_per_query": ("operators (exec/)", "query_rate",
                                    "program_span"),
       "compile.step_programs": ("compile", "setup_s", "program_counter"),
       "ingest.upload_s": ("ingest", "setup_s", "program_counter")}


def test_rehearsal_runs_every_phase_and_reads_the_new_metrics():
    """--rehearse-sf 0.01: priming children, set-up, a traced window, the
    check; exit 4, no result line, and the three new readers among those
    that returned a number."""
    p = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         "sf10_q1_agg1", "--seed", "2147483999", "--seconds", "2", "--trace",
         "1", "--rehearse-sf", "0.01"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=600)
    assert p.returncode == 4, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    line = [ln for ln in p.stderr.splitlines()
            if ln.startswith("[bench] rehearsal (no result line): ")][-1]
    out = json.loads(line.split(": ", 1)[1])
    assert out["correct"] is True and out["failed"] == 0, out["compared"]
    assert out["notes"]["rows"] == {"lineitem": 59997}
    assert set(NEW) <= set(out["metrics_read"])
    for stage in ("import_s", "data_s", "first_queries_s", "warm_s",
                  "check_s"):
        assert stage in out["notes"]["stages_s"]


def test_the_configuration_is_tpch_sf1_at_scale_ten():
    """Key for key the sibling of tpch_sf1.json: only the scale, the rows
    it gives, the text that names them and the guard in ``conf`` differ."""
    def load(name):
        with open(os.path.join(harness.HERE, "configs", name + ".json")) as f:
            return json.load(f)
    one, ten = load("tpch_sf1"), load("tpch_sf10")
    assert list(one) == list(ten)
    same = set(one) - {"source", "scale_factor", "rows", "assumed", "conf",
                       "conf_why"}
    assert all(one[k] == ten[k] for k in same)
    assert ten["conf"] == {
        "spark.rapids.tpu.requires": "agg.boundedStepPrograms"}
    import datagen
    assert ten["scale_factor"] == 10.0 and ten["reduced"] == []
    assert ten["rows"] == {t: datagen.rows(t, 10.0) for t in ten["tables"]}
    spec = harness.load_cell("sf10_q1_agg1")
    assert spec["mix"] == harness.load_cell("sf1_q1_agg1")["mix"]


def _steps(trace, t0, n):
    return [_ev("exec:agg-step", t0 + 5 + i, 0.5, trace, f"{trace}:w{i}",
                trace + ":ex", batches=7, rows=7 << 20)
            for i in range(n)]


def test_agg_steps_are_counted_per_request():
    spans = (_request("a", "r1", 100) + _steps("a", 100, 9)
             + _request("b", "r2", 200) + _steps("b", 200, 10)
             + _request("c", "warm-up", 0) + _steps("c", 0, 50))
    assert _read("exec.agg_steps_per_query", _ctx(spans, ["r1", "r2"])) == 9.5


def test_counters_are_read_from_the_program_s_gauges(monkeypatch):
    from spark_rapids_tpu.obs import gauges
    real = gauges.snapshot()
    monkeypatch.setattr(gauges, "snapshot", lambda: dict(
        real, fused_step_programs_total=2,
        ingest_upload_ns_total=41_500_000_000))
    assert _read("compile.step_programs", {}) == 2
    assert _read("ingest.upload_s", {}) == 41.5


@pytest.mark.parametrize("metric", sorted(NEW))
def test_a_program_without_them_reads_nothing_and_does_not_raise(
        metric, monkeypatch):
    """The parent records no exec:agg-step span and has neither gauge."""
    from spark_rapids_tpu.obs import gauges
    old = {k: v for k, v in gauges.snapshot().items()
           if k not in ("fused_step_programs_total",
                        "ingest_upload_ns_total")}
    monkeypatch.setattr(gauges, "snapshot", lambda: dict(old))
    assert _read(metric, _ctx(_request("a", "r1", 100), ["r1"])) is None
    assert _read(metric, _ctx([], ["r1"])) is None


def test_every_new_metric_is_declared_for_every_cell():
    for cell in ("sf1_q1_agg1", "sf10_q1_agg1"):
        declared = {m["name"]: m for m in
                    harness.load_cell(cell)["per_layer"]}
        for name, (layer, moves, source) in NEW.items():
            m = declared[name]
            assert (m["layer"], m["moves"], m["source"], m["better"]) == (
                layer, moves, source, "lower")
            assert "workloads" not in m
            assert os.path.exists(os.path.join(
                harness.HERE, "layer_metrics", name + ".py"))


def test_a_program_without_the_capability_refuses_the_configuration(
        monkeypatch):
    """What the configuration's ``conf`` is for: the parent of the PR that
    added the cell has no such key, a later build may lack the capability;
    both refuse when the harness makes the conf, before any program runs
    (at SF10 the parent's cold set-up is longer than a run may take)."""
    from spark_rapids_tpu.config import conf as C
    spec = harness.load_cell("sf10_q1_agg1")
    assert harness.program_conf(spec["config"], spec["mix"], "/nowhere")[
        C.REQUIRES] == "agg.boundedStepPrograms"
    monkeypatch.setattr(C, "CAPABILITIES", {})
    with pytest.raises(ValueError, match="this build lacks"):
        harness.program_conf(spec["config"], spec["mix"], "/nowhere")
    monkeypatch.delitem(C._REGISTRY, C.REQUIRES.key)  # the parent
    with pytest.raises(KeyError, match="unknown config"):
        harness.program_conf(spec["config"], spec["mix"], "/nowhere")

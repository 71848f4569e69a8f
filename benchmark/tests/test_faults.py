"""Drives a whole run (all but the harness's look for a chip) with the
timed path broken underneath, and sees ``correct`` come out false: half of
the rows left out; an answer altered where it is produced; an answer that
never comes. A sound run of the same cell comes out true."""
import time
from decimal import Decimal

import pyarrow as pa
import pytest

import harness

SF = 0.01


def _run(workload, tamper=None, seed=2147483700):
    return harness.run_cell(workload, seed, 1.0, False,
                            t_process=time.perf_counter(), scale=SF,
                            require_chip=False, tamper=tamper,
                            log=lambda *a: None)


@pytest.fixture(params=["sf1_q1_agg1", "streams2_mix"])
def workload(request, monkeypatch):
    """The cell of BENCHMARK.json, and the same cell under the two-stream
    mix (traffic/streams2_mix.json, kept for a later PR): streams with
    parameters of their own go through the same run."""
    if request.param == "streams2_mix":
        real = harness.load_cell

        def with_mix(name):
            spec = real(name)
            spec["mix"] = harness.traffic_mod.load(
                harness.traffic_mod.traffic_path(harness.ROOT, request.param))
            return spec
        monkeypatch.setattr(harness, "load_cell", with_mix)
    return "sf1_q1_agg1"


def test_sound_run_is_correct(workload):
    out = _run(workload)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "compared"


def test_half_of_the_rows_left_out(workload):
    def half(tables):
        return {k: (v.slice(0, v.num_rows // 2) if k == "lineitem" else v)
                for k, v in tables.items()}
    out = _run(workload, tamper=half)
    assert out["correct"] is False, out["compared"]


def _altered(monkeypatch, how):
    from spark_rapids_tpu.plan.dataframe import DataFrame
    real = DataFrame.to_arrow

    def to_arrow(self, *a, **kw):
        return how(real(self, *a, **kw))
    monkeypatch.setattr(DataFrame, "to_arrow", to_arrow)


def test_answer_altered_where_it_is_produced(monkeypatch):
    def nudge(table):  # one unit in the last place of the first DECIMAL
        i = next(i for i, f in enumerate(table.schema)
                 if pa.types.is_decimal(f.type))
        unit = Decimal(1).scaleb(-table.schema.field(i).type.scale)
        col = pa.array([v + unit for v in table.column(i).to_pylist()],
                       table.schema.field(i).type)
        return table.set_column(i, table.schema.field(i), col)
    _altered(monkeypatch, nudge)
    out = _run("sf1_q1_agg1")
    assert out["correct"] is False
    assert out["compared"]["decimal_units_off_max"]["value"] == 1
    assert out["compared"]["answers_wrong"]["value"] == out["attempted"]


def test_row_dropped_where_it_is_produced(monkeypatch):
    _altered(monkeypatch, lambda t: t.slice(0, max(t.num_rows - 1, 0)))
    out = _run("sf1_q1_agg1")
    assert out["correct"] is False
    assert out["compared"]["answers_wrong"]["value"] > 0


def test_answer_that_never_comes(monkeypatch):
    state = {"window": False}
    real_run = harness.Window.run_closed

    def run_closed(self, seconds, annotate):
        state["window"] = True  # warm-up passes, the window's requests fail
        return real_run(self, seconds, annotate)

    def boom(table):
        if state["window"]:
            raise RuntimeError("device lost")
        return table
    monkeypatch.setattr(harness.Window, "run_closed", run_closed)
    _altered(monkeypatch, boom)
    out = _run("sf1_q1_agg1")
    assert out["correct"] is False and out["failed"] > 0
    assert out["compared"]["answers_never_came"]["value"] == out["failed"]

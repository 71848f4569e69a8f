"""The controls of ``correct``: every control a query declares (the plain
reference degraded in one way: money in a precision below DECIMAL, a probe
match dropped or repeated, rows misplaced, a CASE arm lost) put in the
program's place has to come out as not correct, on several seeds; the exact
reference itself has to pass; money as float64 passes Q3 and Q6 by design,
as recorded; control.py says so when a control reads correct. And the data
are the specification's shapes."""
import json
import os
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pytest

import compare
import datagen
import harness

# Q1's largest sum passes 2**53 units of 1e-6 from about SF0.2
SF = {"q1": 0.25, "q3": 0.05, "q6": 0.05, "q12": 0.05}
SEEDS = (3, 2147483651, 77)
OTHER = {"q1": {"delta": 75},
         "q3": {"segment": "MACHINERY", "date": [1995, 3, 29]},
         "q6": {"year": 1996, "discount": 3, "quantity": 25},
         "q12": {"shipmodes": ["AIR", "TRUCK"], "date": [1996, 1, 1]}}


def _declared():
    for q in sorted(SF):
        for name in harness.load_by_path("queries", q).CONTROLS:
            yield q, name


_RAW = {}


def _raw(q, seed):
    """One data set per query and seed, shared by the cases of a control
    (the largest, Q1's at SF0.25, is 1.5M rows)."""
    key = (q, seed)
    if key not in _RAW:
        _RAW.clear()
        mod = harness.load_by_path("queries", q)
        _RAW[key] = datagen.make(list(mod.TABLES), SF[q], seed)
    return _RAW[key]


def test_every_query_declares_its_controls():
    assert dict.fromkeys(q for q, _ in _declared()) == dict.fromkeys(
        ["q1", "q12", "q3", "q6"])
    assert sorted(_declared()) == sorted([
        ("q1", "float64_money"), ("q6", "float32_money"),
        ("q3", "float32_money"), ("q3", "dropped_match"),
        ("q3", "duplicated_match"), ("q3", "rows_swapped"),
        ("q3", "member_missed"), ("q12", "dropped_match"),
        ("q12", "duplicated_match"), ("q12", "priority_miscounted")])


@pytest.mark.parametrize("params", ["validation", "other"])
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("query,control", list(_declared()))
def test_a_declared_control_is_not_correct(query, control, seed, params):
    mod = harness.load_by_path("queries", query)
    raw = _raw(query, seed)
    p = mod.PARAMS if params == "validation" else OTHER[query]
    want = mod.reference(raw, p)
    low = mod.CONTROLS[control](raw, p)
    r = compare.answer_readings(compare.control_table(low, mod), want, mod)
    assert r["wrong"] == 1 and r["why"], r
    if "money" in control:
        assert r["units_off"] >= 1, r
    same = compare.answer_readings(compare.control_table(want, mod), want, mod)
    assert same == {"wrong": 0, "units_off": 0, "why": ""}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("query", ["q3", "q6"])
def test_float64_money_passes_by_design(query, seed):
    """Why Q1's control cannot fail these two: Q6 is one sum of some 10^5
    products and Q3's sums hold at most seven (PERF.md section 2)."""
    mod = harness.load_by_path("queries", query)
    raw = _raw(query, seed)
    assert "float64_money" not in mod.CONTROLS
    fn, why = mod.PASSES_BY_DESIGN["float64_money"]
    assert why and fn(raw, mod.PARAMS) == mod.reference(raw)


def _control_py(workload):
    p = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "control.py"),
         "--workload", workload, "--seeds", "2147483999", "--seconds", "1",
         "--rehearse-sf", "0.01"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=900)
    return p.returncode, json.loads(p.stdout.strip().splitlines()[-1]), \
        p.stderr


def test_control_py_runs_every_control_of_the_cell_and_exits_0():
    rc, line, err = _control_py("sf10_q3_join1")
    assert rc == 0, err[-2000:]
    assert line["program_correct"] is True and line["limit"] == 0
    assert sorted(line["controls"]) == sorted(
        "q3." + c for c in harness.load_by_path("queries", "q3").CONTROLS)
    for r in line["controls"].values():
        assert r["answers_wrong"] == 1
    assert line["controls_that_read_correct"] == []
    by_design = line["passes_by_design"]["q3.float64_money"]
    assert by_design["answers_wrong"] == 0 and by_design["reason"]


def test_control_py_exits_1_where_a_control_reads_correct():
    """At SF0.01 Q1's sums stay under 2**53 units: money as float64 reads
    the exact answer, and the run says so and fails."""
    rc, line, err = _control_py("sf1_q1_agg1")
    assert rc == 1
    assert line["program_correct"] is True
    assert line["controls_that_read_correct"] == ["q1.float64_money"]
    assert "FINDING: control q1.float64_money reads correct" in err


def test_exact_columns_are_held_to_zero():
    mod = harness.load_by_path("queries", "q1")
    raw = datagen.make(["lineitem"], 0.01, 5)
    want = mod.reference(raw)
    off = dict(want)
    off["count_order"] = [want["count_order"][0] + 1] + want["count_order"][1:]
    r = compare.answer_readings(compare.control_table(off, mod), want, mod)
    assert r["wrong"] == 1


@pytest.mark.parametrize("seed", [1, 2147483659])
def test_data_have_the_specification_s_shapes(seed):
    sf = 0.02
    raw = datagen.make(["lineitem", "orders", "customer"], sf, seed)
    tabs = {t: datagen.arrow(c) for t, c in raw.items()}
    assert [tabs[t].num_columns for t in ("lineitem", "orders", "customer")
            ] == [16, 9, 8]
    for t, tb in tabs.items():
        tb.validate(full=True)
        assert tb.num_rows == datagen.rows(t, sf)  # the scale's, not the seed's
        assert not any(f.nullable for f in tb.schema)
    li, od, cu = raw["lineitem"], raw["orders"], raw["customer"]
    for c in ("l_quantity", "l_extendedprice", "l_discount", "l_tax"):
        assert tabs["lineitem"].schema.field(c).type == pa.decimal128(15, 2)
        assert li[c].dtype == np.int64  # the references multiply these
    keys, per_order = np.unique(li["l_orderkey"], return_counts=True)
    assert np.array_equal(keys, od["o_orderkey"])  # every line joins an order
    assert per_order.min() == 1 and per_order.max() == 7
    assert np.all(od["o_orderkey"] % 32 < 8)
    date_of = dict(zip(od["o_orderkey"].tolist(), od["o_orderdate"].tolist()))
    lag = li["l_shipdate"] - np.array([date_of[k] for k in
                                       li["l_orderkey"].tolist()])
    assert lag.min() >= 1 and lag.max() <= 121
    lag = li["l_receiptdate"] - li["l_shipdate"]
    assert lag.min() >= 1 and lag.max() <= 30
    assert np.all(od["o_custkey"] % 3 != 0)
    assert od["o_custkey"].max() <= len(cu["c_custkey"])
    assert np.all((li["l_linestatus"] == 1)
                  == (li["l_shipdate"] > datagen.CURRENTDATE))
    assert np.all((li["l_returnflag"] == 1)
                  == (li["l_receiptdate"] > datagen.CURRENTDATE))
    retail = (90000 + (li["l_partkey"] // 10) % 20001
              + 100 * (li["l_partkey"] % 1000))
    assert np.array_equal(li["l_extendedprice"],
                          li["l_quantity"] // 100 * retail)
    first = tabs["customer"].slice(0, 1).to_pylist()[0]
    assert first["c_name"] == "Customer#000000001"
    assert len(first["c_phone"]) == 15 and first["c_phone"][2] == "-"

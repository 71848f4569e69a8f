"""The control of ``correct``: the plain reference computed with money as
float64 dollars and put in the program's place has to come out as not
correct through Q1, which every cell runs, on several seeds; the exact
reference itself has to pass. And the data are the specification's shapes."""
import numpy as np
import pyarrow as pa
import pytest

import compare
import datagen
import harness

SF = 0.25  # Q1's largest sum passes 2**53 units of 1e-6 from about SF0.2


@pytest.mark.parametrize("seed", [3, 2147483651, 77])
@pytest.mark.parametrize("params", [None, {"delta": 75}])
def test_float_money_control_is_not_correct(seed, params):
    mod = harness.load_by_path("queries", "q1")
    raw = datagen.make(["lineitem"], SF, seed)
    p = params or mod.PARAMS
    want = mod.reference(raw, p)
    low = mod.reference(raw, p, money=float)
    r = compare.answer_readings(compare.control_table(low, mod), want, mod)
    assert r["wrong"] == 1 and r["units_off"] >= 1, r
    same = compare.answer_readings(compare.control_table(want, mod), want, mod)
    assert same == {"wrong": 0, "units_off": 0, "why": ""}


def test_q6_alone_would_not_separate():
    """Q6's one sum of some 10**5 products rounds to the exact answer in
    float64: a cell of Q6 alone needs another control (PERF.md)."""
    mod = harness.load_by_path("queries", "q6")
    raw = datagen.make(["lineitem"], SF, 5)
    assert mod.reference(raw, money=float) == mod.reference(raw)


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

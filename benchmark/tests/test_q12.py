"""TPC-H Q12 (queries/q12.py), the first answer with no DECIMAL: a string
and two counts. Through the planner and through the served path the answer
equals the plain numpy reference exactly, judged by the run's own
comparison (``compare.judge``), on 3 seeds and two parameter sets, over
data in which one order is planted with two qualifying lines (the build
side has duplicate keys: a build that keeps one row a key would lose one)
and one whose only line fails ``l_shipdate < l_commitdate``.

``orders`` probes in batches of 8192 rows; ``lineitem`` goes in ONE batch
that it does not fill, because the program's ``In`` over a string column
reads a full batch's last row as no match (PERF.md section 7, first entry):
the last test here is the witness, and is expected to fail until a PR that
may touch the program cures it."""
import numpy as np
import pytest

import compare
import datagen
import harness

SF = 0.03
SEEDS = (7, 2147483693, 314159265)
PARAMS = (None, {"shipmodes": ["AIR", "TRUCK"], "date": [1996, 1, 1]})
PROBE_ROWS = 8192  # orders: 45,000 rows, six batches
BUILD_ROWS = 1 << 19  # lineitem: 179,991 rows, one batch of capacity 2^18


def _q12():
    return harness.load_by_path("queries", "q12")


def _planted(seed: int, p) -> tuple:
    """(raw, the two planted rows that qualify, the one that does not)."""
    q12 = _q12()
    raw = datagen.make(list(q12.TABLES), SF, seed)
    li = raw["lineitem"]
    lo, _ = q12._year(p)
    mode = datagen.labels("l_shipmode").index(p["shipmodes"][0])
    _, first, count = np.unique(li["l_orderkey"], return_index=True,
                                return_counts=True)
    two = int(first[np.flatnonzero(count >= 2)[0]])
    one = int(first[np.flatnonzero(count == 1)[0]])
    for row, ship in ((two, lo + 20), (two + 1, lo + 25), (one, lo + 30)):
        li["l_shipmode"][row] = mode
        li["l_receiptdate"][row] = lo + 40
        li["l_commitdate"][row] = lo + 30
        li["l_shipdate"][row] = ship  # row ``one``: not before the commit
    keep = q12._qualifying(raw, p)
    assert keep[two] and keep[two + 1] and not keep[one]
    assert li["l_orderkey"][two] == li["l_orderkey"][two + 1]
    return raw, (two, two + 1), one


def _judge(table, raw, p):
    q12 = _q12()
    return compare.judge([("q12", table)], {"q12": q12.reference(raw, p)},
                         {"q12": q12})


@pytest.mark.parametrize("params", PARAMS, ids=["validation", "other"])
@pytest.mark.parametrize("seed", SEEDS)
def test_q12_through_the_planner_equals_the_reference(seed, params):
    from spark_rapids_tpu.obs import gauges
    from spark_rapids_tpu.plan import from_arrow
    q12 = _q12()
    p = params or q12.PARAMS
    raw, _, _ = _planted(seed, p)
    d = {"orders": from_arrow(datagen.arrow(raw["orders"]),
                              batch_rows=PROBE_ROWS, partitions=1),
         "lineitem": from_arrow(datagen.arrow(raw["lineitem"]),
                                batch_rows=BUILD_ROWS, partitions=1)}
    df = q12.build(d, params) if params else q12.build(d)
    plan = df.physical_plan().explain()
    assert "TpuHashJoin inner" in plan and "TpuHashAggregate" in plan, plan
    before = gauges.snapshot()
    out = df.to_arrow()
    after = gauges.snapshot()
    assert _judge(out, raw, p) == {
        "answers_wrong": 0, "decimal_units_off_max": 0,
        "distinct_answers": 1, "whys": []}
    assert out.schema.names == list(q12.EXACT_COLUMNS)
    assert out.num_rows == 2 and out["l_shipmode"].to_pylist() == sorted(
        p["shipmodes"])
    # the build has duplicate keys: not the dense or the unique table
    built = {k: after[f"join_build_path_{k}_total"]
             - before[f"join_build_path_{k}_total"]
             for k in ("dense", "unique", "ht", "sorted")}
    assert built["dense"] == built["unique"] == 0 and sum(
        built.values()) == 1, built


@pytest.mark.parametrize("params", PARAMS, ids=["validation", "other"])
@pytest.mark.parametrize("seed", SEEDS)
def test_q12_over_the_wire_equals_the_reference(seed, params):
    """NetClient.submit() -> QueryFrontend -> QueryServer, twice (the second
    request finds the plan memoized)."""
    from spark_rapids_tpu.config import conf as C
    from spark_rapids_tpu.net import NetClient, QueryFrontend
    from spark_rapids_tpu.serve import QueryServer
    q12 = _q12()
    p = params or q12.PARAMS
    raw, _, _ = _planted(seed, p)
    conf = C.RapidsConf({})
    srv = QueryServer(conf)
    fe = QueryFrontend(srv, tables={t: datagen.arrow(raw[t])
                                    for t in q12.TABLES},
                       host="127.0.0.1", port=0)
    try:
        cl = NetClient(fe.host, fe.port, conf=conf, timeout_s=600)
        try:
            d = {"orders": cl.table("orders", batch_rows=PROBE_ROWS,
                                    partitions=1),
                 "lineitem": cl.table("lineitem", batch_rows=BUILD_ROWS,
                                      partitions=1)}
            df = q12.build(d, params) if params else q12.build(d)
            outs = [cl.submit(df, name=f"q12-{i}", timeout_s=600)
                    for i in range(2)]
        finally:
            cl.close()
    finally:
        fe.close()
        srv.close()
    want = q12.reference(raw, p)
    verdict = compare.judge([("q12", t) for t in outs], {"q12": want},
                            {"q12": q12})
    assert verdict["answers_wrong"] == 0 and verdict["whys"] == [], verdict
    assert verdict["distinct_answers"] == 1


@pytest.mark.parametrize("seed", SEEDS)
def test_the_planted_lines_count_and_the_controls_find_them(seed):
    """The reference counts both lines of the planted order and not the line
    that shipped on its commit date; an answer that lost one of the two (the
    ``dropped_match`` control drops exactly it: the first order with two
    qualifying lines) reads wrong through ``judge``."""
    q12 = _q12()
    p = q12.PARAMS
    raw, (a, b), one = _planted(seed, p)
    want = q12.reference(raw, p)
    total = sum(want["high_line_count"]) + sum(want["low_line_count"])
    assert total == int(q12._qualifying(raw, p).sum())
    li = raw["lineitem"]
    for rows, less in (((a,), 1), ((a, b), 2), ((one,), 0)):
        cut = dict(raw, lineitem={c: np.delete(li[c], rows)
                                  for c in q12.COLUMNS["lineitem"]})
        got = q12.reference(cut, p)
        assert sum(got["high_line_count"]) + sum(
            got["low_line_count"]) == total - less
    lost = q12.CONTROLS["dropped_match"](raw, p)
    assert lost == q12.reference(dict(raw, lineitem={
        c: np.delete(li[c], b) for c in q12.COLUMNS["lineitem"]}), p)
    verdict = compare.judge([("q12", compare.control_table(lost, q12))],
                            {"q12": want}, {"q12": q12})
    assert verdict["answers_wrong"] == 1


def test_q12_reference_imports_nothing_of_the_program():
    import os
    src = open(os.path.join(harness.HERE, "queries", "q12.py")).read()
    assert "spark_rapids_tpu" not in src[src.index("def least_bytes("):]
    assert "spark_rapids_tpu" not in src[:src.index("def build(")]


@pytest.mark.xfail(reason="the program's In over a string column misses a "
                   "full batch's last row (PERF.md section 7): exprs/eval.py",
                   strict=False)
def test_in_over_strings_keeps_a_full_batch_s_last_row():
    """Seed 7 at SF0.02: row 8191 of lineitem's batches of 8192 holds AIR
    or TRUCK five times; ``In`` finds 34,285 of 34,290 rows where two
    ``EqualTo`` find them all. With Q12 as the specification writes it, a
    lineitem batch that is full loses its last row."""
    from spark_rapids_tpu.exprs.expr import In, Or, col, lit
    from spark_rapids_tpu.plan import from_arrow
    raw = datagen.make(["lineitem"], 0.02, 7)["lineitem"]
    modes = [datagen.labels("l_shipmode").index(m) for m in ("AIR", "TRUCK")]
    want = int(np.isin(raw["l_shipmode"], modes).sum())
    table = datagen.arrow(raw).select(["l_orderkey", "l_shipmode"])

    def rows(pred):
        return from_arrow(table, batch_rows=8192, partitions=1).filter(
            pred).select("l_orderkey").to_arrow().num_rows
    assert rows(Or(col("l_shipmode").eq("AIR"),
                   col("l_shipmode").eq("TRUCK"))) == want
    assert rows(In(col("l_shipmode"), [lit("AIR"), lit("TRUCK")])) == want

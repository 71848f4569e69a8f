"""The comparison takes any answer shape the specification's queries have:
the readings of Q1, Q3 and Q6 answers are what they were before it did
(recorded with compare.py of commit eedea33), and an answer of a string and
counts alone, with no DECIMAL, is decided by its exact columns."""
import base64
import json
import os
import types

import pyarrow as pa
import pytest

import compare
import harness

with open(os.path.join(harness.HERE, "testdata",
                       "recorded.answers.json")) as f:
    RECORDED = json.load(f)


def _table(b64: str) -> pa.Table:
    return pa.ipc.open_stream(base64.b64decode(b64)).read_all()


@pytest.mark.parametrize(
    "case", RECORDED["cases"],
    ids=[f"{c['query']}-{c['case']}" for c in RECORDED["cases"]])
def test_readings_of_recorded_answers_are_unchanged(case):
    mod = harness.load_by_path("queries", case["query"])
    got = compare.answer_readings(_table(case["table"]),
                                  RECORDED["want"][case["query"]], mod)
    assert got == case["reading"]


def test_the_recorded_answers_hold_sound_and_unsound_ones_of_each_query():
    for q in ("q1", "q3", "q6"):
        wrong = [c["reading"]["wrong"] for c in RECORDED["cases"]
                 if c["query"] == q]
        assert wrong.count(0) >= 3 and wrong.count(1) >= 4


@pytest.mark.parametrize("query", ["q1", "q3", "q6", "q12"])
def test_control_table_has_the_types_the_program_returns(query):
    """int64 counts and keys, strings, date32 and int32 where the query
    says so; DECIMAL columns at their scale."""
    mod = harness.load_by_path("queries", query)
    want = {"q12": {"l_shipmode": ["MAIL"], "high_line_count": [3],
                    "low_line_count": [4]}}.get(query) or \
        RECORDED["want"][query]
    table = compare.control_table(want, mod)
    for c in mod.EXACT_COLUMNS:
        kind = getattr(mod, "EXACT_TYPES", {}).get(c)
        typ = table.schema.field(c).type
        if kind:
            assert typ == getattr(pa, kind)()
        else:
            assert pa.types.is_string(typ) or typ == pa.int64()
    for c, scale in mod.DECIMAL_COLUMNS.items():
        assert table.schema.field(c).type == pa.decimal128(38, scale)
    program = [c for c in RECORDED["cases"]
               if c["query"] == query and c["case"] == "program"]
    if program:  # the types the program did return, column by column
        got = _table(program[0]["table"])
        for c in mod.EXACT_COLUMNS:
            assert table.schema.field(c).type == got.schema.field(c).type
    assert compare.answer_readings(table, want, mod) == {
        "wrong": 0, "units_off": 0, "why": ""}


# -- an answer with no DECIMAL ------------------------------------------------

COUNTS = types.SimpleNamespace(
    EXACT_COLUMNS=("l_shipmode", "high_line_count", "low_line_count"),
    DECIMAL_COLUMNS={})
WANT = {"l_shipmode": ["MAIL", "SHIP"], "high_line_count": [6202, 6200],
        "low_line_count": [9324, 9262]}


def _counts(**changed) -> pa.Table:
    return compare.control_table(dict(WANT, **changed), COUNTS)


def test_an_answer_of_strings_and_counts_is_compared():
    assert compare.answer_readings(_counts(), WANT, COUNTS) == {
        "wrong": 0, "units_off": 0, "why": ""}
    verdict = compare.judge([("k", _counts()), ("k", _counts())],
                            {"k": WANT}, {"k": COUNTS})
    assert verdict == {"answers_wrong": 0, "decimal_units_off_max": 0,
                       "distinct_answers": 1, "whys": []}


@pytest.mark.parametrize("fault,got", [
    ("a wrong count", lambda: _counts(low_line_count=[9324, 9263])),
    ("a wrong string", lambda: _counts(l_shipmode=["MAIL", "SHIp"])),
    ("a missing row", lambda: _counts().slice(0, 1)),
    ("swapped rows", lambda: _counts().take([1, 0])),
    ("a column too many", lambda: _counts().append_column(
        "revenue", pa.array([1, 2]))),
    ("a column missing", lambda: _counts().drop_columns(["low_line_count"])),
    ("a null count", lambda: _counts().set_column(
        1, "high_line_count", pa.array([6202, None], pa.int64()))),
])
def test_a_fault_in_an_answer_without_decimals_reads_wrong(fault, got):
    r = compare.answer_readings(got(), WANT, COUNTS)
    assert r["wrong"] == 1 and r["units_off"] == 0 and r["why"], (fault, r)
    verdict = compare.judge([("k", got()), ("k", _counts())], {"k": WANT},
                            {"k": COUNTS})
    assert verdict["answers_wrong"] == 1
    assert verdict["decimal_units_off_max"] == 0


def test_an_answer_of_decimals_alone_takes_its_row_count_from_them():
    """Q6's shape: no exact column."""
    q6 = harness.load_by_path("queries", "q6")
    want = {"revenue": [1234567]}
    assert compare.answer_readings(compare.control_table(want, q6), want,
                                   q6)["wrong"] == 0
    two = compare.control_table({"revenue": [1234567, 1]}, q6)
    assert compare.answer_readings(two, want, q6)["why"] == "2 rows, want 1"


def test_an_empty_answer_to_an_empty_reference_is_sound():
    q3 = harness.load_by_path("queries", "q3")
    none = {c: [] for c in q3.EXACT_COLUMNS + tuple(q3.DECIMAL_COLUMNS)}
    assert compare.answer_readings(compare.control_table(none, q3), none,
                                   q3) == {"wrong": 0, "units_off": 0,
                                           "why": ""}


def test_no_row_count_is_taken_from_the_decimal_columns():
    src = open(os.path.join(harness.HERE, "compare.py")).read()
    assert "DECIMAL_COLUMNS))" not in src

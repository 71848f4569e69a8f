"""String kernel + regex engine tests, differential vs Python oracles.

Mirrors the reference's string/regex coverage (reference:
tests/.../CastOpSuite, RegularExpressionTranspilerSuite fuzzing,
integration_tests string_test.py) at unit scale.
"""

import re

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import batch_from_arrow, batch_to_arrow
from spark_rapids_tpu.exprs import expr as E
from spark_rapids_tpu.exprs import regex as RX
from spark_rapids_tpu.exprs.eval import (
    bind_projection, compile_projection, output_schema,
)
from spark_rapids_tpu.exprs.expr import col, lit


def pylist(table, exprs):
    schema = T.Schema.from_arrow(table.schema)
    fn = compile_projection(exprs, schema)
    out_schema = output_schema(bind_projection(exprs, schema))
    out = batch_to_arrow(fn(batch_from_arrow(table)), out_schema)
    return [out.column(i).to_pylist() for i in range(out.num_columns)]


STRS = ["hello world", "", "  padded  ", "a", None, "xyzxyzxyz", "Mixed Case"]


def stab(values=STRS):
    return pa.table({"s": pa.array(values, pa.string())})


# ---------------------------------------------------------------------------
# concat family
# ---------------------------------------------------------------------------


def test_concat_null_intolerant():
    t = pa.table({
        "a": pa.array(["x", None, "", "ab"]),
        "b": pa.array(["y", "z", "w", None]),
    })
    (r,) = pylist(t, [E.Concat(col("a"), col("b"))])
    assert r == ["xy", None, "w", None]


def test_concat_three():
    t = pa.table({"a": pa.array(["x", "q"]), "b": pa.array(["y", "r"])})
    (r,) = pylist(t, [E.Concat(col("a"), lit("-"), col("b"))])
    assert r == ["x-y", "q-r"]


def test_concat_ws_skips_nulls():
    t = pa.table({
        "a": pa.array(["x", None, None, ""]),
        "b": pa.array(["y", "z", None, "w"]),
    })
    (r,) = pylist(t, [E.ConcatWs(col("a"), col("b"), sep="-")])
    assert r == ["x-y", "z", "", "-w"]


# ---------------------------------------------------------------------------
# trim / pad / case
# ---------------------------------------------------------------------------


def test_trim_family():
    t = stab(["  hi  ", "xx", "", None, "   ", "a b"])
    trim, ltrim, rtrim = pylist(t, [
        E.StringTrim(col("s")), E.StringTrimLeft(col("s")),
        E.StringTrimRight(col("s")),
    ])
    assert trim == ["hi", "xx", "", None, "", "a b"]
    assert ltrim == ["hi  ", "xx", "", None, "", "a b"]
    assert rtrim == ["  hi", "xx", "", None, "", "a b"]


def test_trim_custom_chars():
    t = stab(["xxhixx", "xyhix", "hi"])
    (r,) = pylist(t, [E.StringTrim(col("s"), "xy")])
    assert r == ["hi", "hi", "hi"]


def test_pad():
    t = stab(["abc", "abcdef", "", None])
    lp, rp, lpe = pylist(t, [
        E.StringLPad(col("s"), 5, "#"),
        E.StringRPad(col("s"), 5, "xy"),
        E.StringLPad(col("s"), 2, "#"),
    ])
    assert lp == ["##abc", "abcde", "#####", None]
    assert rp == ["abcxy", "abcde", "xyxyx", None]
    assert lpe == ["ab", "ab", "##", None]


def test_pad_empty_pad_string():
    t = stab(["hi", "hello"])
    lp, = pylist(t, [E.StringLPad(col("s"), 4, "")])
    assert lp == ["hi", "hell"]


def test_initcap():
    t = stab(["hello world", "HELLO", "a  b", "", None])
    (r,) = pylist(t, [E.InitCap(col("s"))])
    assert r == ["Hello World", "Hello", "A  B", "", None]


# ---------------------------------------------------------------------------
# replace / translate / repeat / reverse
# ---------------------------------------------------------------------------


def test_replace_basic():
    t = stab(["aaa", "banana", "", None, "abcabc"])
    (r,) = pylist(t, [E.StringReplace(col("s"), "a", "XY")])
    assert r == ["XYXYXY", "bXYnXYnXY", "", None, "XYbcXYbc"]


def test_replace_greedy_non_overlapping():
    t = stab(["aaa", "aaaa", "aa"])
    (r,) = pylist(t, [E.StringReplace(col("s"), "aa", "b")])
    assert r == ["ba", "bb", "b"]


def test_replace_delete():
    t = stab(["a-b-c", "---"])
    (r,) = pylist(t, [E.StringReplace(col("s"), "-", "")])
    assert r == ["abc", ""]


def test_translate():
    t = stab(["AaBbCc", "translate", None])
    (r,) = pylist(t, [E.StringTranslate(col("s"), "abc", "12")])
    # a->1, b->2, c deleted
    assert r == ["A1B2C", "tr1nsl1te", None]


def test_repeat_reverse():
    t = stab(["ab", "", None, "xyz"])
    rep, rev = pylist(t, [E.StringRepeat(col("s"), 3), E.StringReverse(col("s"))])
    assert rep == ["ababab", "", None, "xyzxyzxyz"]
    assert rev == ["ba", "", None, "zyx"]


# ---------------------------------------------------------------------------
# find / substring_index / ascii / chr
# ---------------------------------------------------------------------------


def test_instr_locate():
    t = stab(["hello", "xhix", "", None, "aXbXc"])
    ins, loc = pylist(t, [
        E.StringInstr(col("s"), "h"),
        E.StringLocate(col("s"), "X", 3),
    ])
    assert ins == [1, 2, 0, None, 0]
    assert loc == [0, 0, 0, None, 4]


def test_substring_index():
    t = stab(["a.b.c", "abc", "", None, "a..b"])
    p2, m1, m2 = pylist(t, [
        E.SubstringIndex(col("s"), ".", 2),
        E.SubstringIndex(col("s"), ".", -1),
        E.SubstringIndex(col("s"), ".", -2),
    ])
    assert p2 == ["a.b", "abc", "", None, "a."]
    assert m1 == ["c", "abc", "", None, "b"]
    assert m2 == ["b.c", "abc", "", None, ".b"]


def test_ascii_chr():
    t = pa.table({
        "s": pa.array(["Abc", "", None]),
        "n": pa.array([65, 97, 322], pa.int32()),
    })
    a, c = pylist(t, [E.Ascii(col("s")), E.Chr(col("n"))])
    assert a == [65, 0, None]
    assert c == ["A", "a", "B"]  # Spark chr uses n % 256


def test_left_right():
    t = stab(["hello", "ab", "", None])
    l2, r2 = pylist(t, [E.Left(col("s"), 3), E.Right(col("s"), 3)])
    assert l2 == ["hel", "ab", "", None]
    assert r2 == ["llo", "ab", "", None]


# ---------------------------------------------------------------------------
# LIKE / RLIKE
# ---------------------------------------------------------------------------


def test_like():
    t = stab(["abc", "aXc", "ab", "xabc", "", None])
    starts, contains, under, esc = pylist(t, [
        E.Like(col("s"), "a%"),
        E.Like(col("s"), "%b%"),
        E.Like(col("s"), "a_c"),
        E.Like(col("s"), "ab"),
    ])
    assert starts == [True, True, True, False, False, None]
    assert contains == [True, False, True, True, False, None]
    assert under == [True, True, False, False, False, None]
    assert esc == [False, False, True, False, False, None]


def test_like_escape():
    t = stab(["50%", "50x", "%"])
    (r,) = pylist(t, [E.Like(col("s"), "50\\%")])
    assert r == [True, False, False]


RLIKE_CASES = [
    (r"^[a-z]+$", ["abc", "Abc", "abc1", ""]),
    (r"\d{3}-\d{4}", ["555-1234", "55-1234", "x555-9999y"]),
    (r"(cat|dog)s?", ["cat", "dogs", "dot", "catsup"]),
    (r"a.c", ["abc", "ac", "a\nc", "axc"]),
]


@pytest.mark.parametrize("pat,strs", RLIKE_CASES)
def test_rlike_vs_re(pat, strs):
    t = stab(strs)
    (got,) = pylist(t, [E.RLike(col("s"), pat)])
    want = [re.search(pat, s) is not None for s in strs]
    assert got == want


def test_rlike_fuzz_vs_re(rng):
    """Random ASCII strings x a pile of patterns, vs Python re."""
    alphabet = list("abc01 .x-")
    strs = ["".join(rng.choice(alphabet, rng.integers(0, 12)))
            for _ in range(64)]
    for pat in [r"a+b", r"[0-9]+", r"^a", r"x$", r"a.*c", r"(ab|ba)+",
                r"a{2,3}", r"\s", r"[^abc]+$"]:
        t = stab(strs)
        (got,) = pylist(t, [E.RLike(col("s"), pat)])
        want = [re.search(pat, s) is not None for s in strs]
        assert got == want, f"pattern {pat!r}"


def test_regex_unsupported_raises():
    for pat in [r"(?=look)", r"\bword\b", r"back\1ref", r"a{999}",
                r"a*+a", r"a++", r"[é]"]:
        with pytest.raises(RX.RegexUnsupported):
            RX.compile_rlike(pat)


def test_regex_utf8_literals():
    t = stab(["café", "cafe", "caf"])
    rl, lk = pylist(t, [E.RLike(col("s"), "café"),
                        E.Like(col("s"), "%é")])
    assert rl == [True, False, False]
    assert lk == [True, False, False]


def test_regex_bad_hex_escape_falls_back():
    with pytest.raises(RX.RegexUnsupported):
        RX.compile_rlike(r"\x{41}")


def test_regex_literal_brace():
    t = stab(["a{x}", "ax"])
    (r,) = pylist(t, [E.RLike(col("s"), r"a{x}")])
    assert r == [True, False]


def test_unsupported_regex_falls_back_in_plan():
    from spark_rapids_tpu.plan.overrides import check_expr

    schema = T.Schema([T.Field("s", T.STRING, True)])
    reasons = check_expr(E.RLike(col("s"), r"\bword\b"), schema)
    assert any("regex" in r for r in reasons)
    assert check_expr(E.RLike(col("s"), r"^ab+c$"), schema) == []


# ---------------------------------------------------------------------------
# IN over a string column (exprs/eval.py _string_eq, _dict_in_literals)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows", [1024, 8192])
@pytest.mark.parametrize("encoded", [False, True], ids=["plain", "dict"])
def test_in_over_strings_keeps_a_full_batch_s_last_row(rows, encoded):
    """A batch that its rows fill: the last row matches an item of the
    list, as the rows before it do. The byte space past the last row's
    end belongs to the last row, and was read as a mismatch (PERF.md
    PR 38: Q12's ``l_shipmode in ('MAIL', 'SHIP')`` lost row 8,191 of
    every full batch of 8,192)."""
    from spark_rapids_tpu.columnar.batch import dictionary_encode_table
    from spark_rapids_tpu.exprs import eval as EV
    modes = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
    rng = np.random.default_rng(rows)
    vals = [modes[i] for i in rng.integers(0, len(modes), rows)]
    vals[-1], vals[-2] = "TRUCK", "AIR"
    t = pa.table({"m": pa.array(vals, pa.string())})
    batch = batch_from_arrow(dictionary_encode_table(t) if encoded else t)
    assert batch.capacity == rows and batch.columns[0].is_dict == encoded
    bound = E.resolve(E.In(col("m"), [lit("AIR"), lit("TRUCK")]),
                      T.Schema.from_arrow(t.schema))
    got = EV.eval_expr(bound, EV.EvalContext(batch))
    want = np.isin(vals, ["AIR", "TRUCK"])
    assert np.asarray(got.validity).all()
    assert np.array_equal(np.asarray(got.data), want)
    assert bool(got.data[-1]) and bool(got.data[-2])


def test_string_equality_ignores_the_bytes_past_the_last_row():
    """``=`` of two plain string columns over a full batch, the last rows
    equal: the comparison the IN fix lives in."""
    a = ["x" * (i % 5) for i in range(1023)] + ["MAIL"]
    t = pa.table({"a": pa.array(a, pa.string()),
                  "b": pa.array(list(a), pa.string())})
    (eq,) = pylist(t, [E.EqualTo(col("a"), col("b")).alias("eq")])
    assert eq == [True] * 1024

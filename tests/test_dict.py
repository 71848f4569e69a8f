"""Dictionary-encoded columns: ingest, grouping (dense MXU path), sort,
joins, filter-fused aggregation, and decode fallbacks.

Differential oracles in pandas/pyarrow, mirroring the reference's
CPU-vs-accelerator testing (SURVEY.md section 4)."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import (
    batch_from_arrow, batch_to_arrow, dictionary_encode_table,
)
from spark_rapids_tpu.exec import (
    BatchSourceExec, FilterExec, HashAggregateExec, HashJoinExec, SortExec,
    SortOrder,
)
from spark_rapids_tpu.exec import kernels as K
from spark_rapids_tpu.exprs.expr import (
    Average, Count, GreaterThan, Max, Min, Sum, col, lit,
)


def _table(n=500, seed=0, nulls=True):
    rng = np.random.default_rng(seed)
    keys = np.array(["apple", "pear", "zig", "a", ""])[rng.integers(0, 5, n)]
    kmask = rng.random(n) < 0.1 if nulls else np.zeros(n, bool)
    v = rng.integers(-100, 100, n)
    vmask = rng.random(n) < 0.1 if nulls else np.zeros(n, bool)
    f = np.round(rng.uniform(-10, 10, n), 3)
    return pa.table({
        "k": pa.array(keys, pa.string(), mask=kmask),
        "v": pa.array(v, pa.int64(), mask=vmask),
        "f": pa.array(f, pa.float64()),
    })


def _src(t, batch_rows=200):
    enc = dictionary_encode_table(t)
    cache = {}
    batches = [batch_from_arrow(enc.slice(i, batch_rows), dict_cache=cache)
               for i in range(0, max(t.num_rows, 1), batch_rows)]
    return BatchSourceExec([batches], T.Schema.from_arrow(t.schema))


def test_dict_roundtrip():
    t = _table()
    enc = dictionary_encode_table(t)
    b = batch_from_arrow(enc)
    assert b.columns[0].is_dict
    assert b.columns[0].dict_size == 5
    back = batch_to_arrow(b, T.Schema.from_arrow(t.schema))
    assert back.column("k").to_pylist() == t.column("k").to_pylist()


def test_dict_encode_skips_high_cardinality():
    n = 100
    t = pa.table({"s": pa.array([f"u{i}" for i in range(n)], pa.string())})
    enc = dictionary_encode_table(t)
    assert not pa.types.is_dictionary(enc.column("s").type)


def test_decode_dictionary_kernel():
    t = _table(100)
    b = batch_from_arrow(dictionary_encode_table(t))
    plain = K.decode_dictionary(b.columns[0])
    assert plain.offsets is not None
    out = batch_to_arrow(
        type(b)([plain], b.num_rows), T.Schema([T.Field("k", T.STRING, True)]))
    assert out.column("k").to_pylist() == t.column("k").to_pylist()


def _agg_oracle(t, filt=None):
    df = t.to_pandas()
    if filt is not None:
        df = df[filt(df)]
    g = df.groupby("k", dropna=False, sort=True).agg(
        s=("v", "sum"), c=("v", "count"), n=("v", "size"),
        fs=("f", "sum"), mn=("v", "min"), mx=("v", "max"))
    return g


def _run_agg(node):
    from spark_rapids_tpu.columnar.batch import batch_to_arrow as b2a

    rows = []
    for b in node.execute_all():
        rows.extend(b2a(b, node.output_schema).to_pylist())
    return rows


def _check_agg(t, pre_filter=None, oracle_filt=None):
    src = _src(t)
    child = FilterExec(pre_filter, src) if pre_filter is not None else src
    agg = HashAggregateExec(
        [col("k")],
        [Sum(col("v")).alias("s"), Count(col("v")).alias("c"),
         Count().alias("n"), Sum(col("f")).alias("fs"),
         Min(col("v")).alias("mn"), Max(col("v")).alias("mx")],
        child)
    node = SortExec([SortOrder(col("k"))], agg)
    rows = _run_agg(node)
    oracle = _agg_oracle(t, oracle_filt)
    # pandas sorts NaN (null key) last; engine default NULLS FIRST asc
    orows = list(oracle.reset_index().to_dict("records"))
    orows.sort(key=lambda r: (not (isinstance(r["k"], float) and np.isnan(r["k"])
                                   if not isinstance(r["k"], str) else False),))
    null_first = [r for r in orows if not isinstance(r["k"], str)] + \
                 [r for r in orows if isinstance(r["k"], str)]
    assert len(rows) == len(null_first)
    for got, exp in zip(rows, null_first):
        ek = exp["k"] if isinstance(exp["k"], str) else None
        assert got["k"] == ek
        assert got["n"] == exp["n"]
        if exp["c"] == 0:
            assert got["s"] is None
        else:
            assert got["s"] == exp["s"]
            assert got["mn"] == exp["mn"]
            assert got["mx"] == exp["mx"]
        assert abs(got["fs"] - exp["fs"]) < 1e-9


def test_dense_agg_dict_keys():
    _check_agg(_table())


def test_dense_agg_filter_fused():
    t = _table()
    _check_agg(t, pre_filter=GreaterThan(col("v"), lit(0)),
               oracle_filt=lambda df: df.v > 0)


def test_filter_fusion_absorbs_child():
    src = _src(_table())
    agg = HashAggregateExec([col("k")], [Count().alias("n")],
                            FilterExec(GreaterThan(col("v"), lit(0)), src))
    assert agg.pre_filter is not None
    assert agg.child is src  # FilterExec absorbed


def test_global_agg_dense_with_filter():
    t = _table(nulls=False)
    src = _src(t)
    agg = HashAggregateExec(
        [], [Sum(col("v")).alias("s"), Count().alias("n"),
             Average(col("f")).alias("af")],
        FilterExec(GreaterThan(col("v"), lit(10)), src))
    rows = _run_agg(agg)
    df = t.to_pandas()
    df = df[df.v > 10]
    assert rows[0]["n"] == len(df)
    assert rows[0]["s"] == df.v.sum()
    assert abs(rows[0]["af"] - df.f.mean()) < 1e-12


def test_global_agg_empty_after_filter():
    t = _table(nulls=False)
    agg = HashAggregateExec(
        [], [Sum(col("v")).alias("s"), Count().alias("n")],
        FilterExec(GreaterThan(col("v"), lit(10_000)), _src(t)))
    rows = _run_agg(agg)
    assert rows == [{"s": None, "n": 0}]


def test_int_sum_wraps_like_int64():
    big = (1 << 62) + 12345
    t = pa.table({
        "k": pa.array(["a", "a", "a", "b"], pa.string()),
        "v": pa.array([big, big, big, 7], pa.int64()),
        "f": pa.array([0.0, 0.0, 0.0, 0.0], pa.float64()),
    })
    agg = HashAggregateExec([col("k")], [Sum(col("v")).alias("s")], _src(t))
    rows = sorted(_run_agg(agg), key=lambda r: r["k"])
    expect = (3 * big) % (1 << 64)
    if expect >= (1 << 63):
        expect -= 1 << 64
    assert rows[0]["s"] == expect
    assert rows[1]["s"] == 7


def test_min_max_dict_strings():
    t = _table()
    agg = HashAggregateExec(
        [], [Min(col("k")).alias("mn"), Max(col("k")).alias("mx"),
             Count().alias("n")], _src(t))
    rows = _run_agg(agg)
    ks = [k for k in t.column("k").to_pylist() if k is not None]
    assert rows[0]["mn"] == min(ks)
    assert rows[0]["mx"] == max(ks)


def test_sort_dict_strings():
    t = _table()
    node = SortExec([SortOrder(col("k"), ascending=False, nulls_first=False)],
                    _src(t))
    rows = [r["k"] for r in _run_agg(node)]
    exp = sorted([k for k in t.column("k").to_pylist() if k is not None],
                 reverse=True) + [None] * sum(
                     1 for k in t.column("k").to_pylist() if k is None)
    assert rows == exp


def test_join_dict_vs_plain_keys():
    rng = np.random.default_rng(3)
    left = pa.table({
        "k": pa.array(np.array(["x", "y", "z"])[rng.integers(0, 3, 50)]),
        "a": pa.array(np.arange(50), pa.int64()),
    })
    right = pa.table({
        "k2": pa.array(["x", "z", "w"], pa.string()),
        "b": pa.array([10, 30, 40], pa.int64()),
    })
    # left side dict-encoded, right side plain
    lsrc = _src(pa.table({"k": left.column("k"), "a": left.column("a"),
                          "f": pa.array(np.zeros(50))}))
    rsrc = BatchSourceExec(
        [[batch_from_arrow(right)]], T.Schema.from_arrow(right.schema))
    j = HashJoinExec([col("k")], [col("k2")], "inner", lsrc, rsrc)
    rows = _run_agg(j)
    ldf = left.to_pandas()
    exp = ldf.merge(right.to_pandas(), left_on="k", right_on="k2")
    assert len(rows) == len(exp)
    assert sorted(r["a"] for r in rows) == sorted(exp.a.tolist())


def test_mixed_dict_plain_key_batches():
    # batch 1 dict-encodes the key, batch 2 keeps it plain (high cardinality
    # or separate ingest): layouts must still concat/merge correctly
    t1 = pa.table({"k": pa.array(["a"] * 200, pa.string()),
                   "v": pa.array(np.ones(200, np.int64)),
                   "f": pa.array(np.zeros(200))})
    t2 = pa.table({"k": pa.array(["a"] * 200, pa.string()),
                   "v": pa.array(np.ones(200, np.int64)),
                   "f": pa.array(np.zeros(200))})
    b1 = batch_from_arrow(dictionary_encode_table(t1))
    b2 = batch_from_arrow(t2)  # plain
    assert b1.columns[0].is_dict and not b2.columns[0].is_dict
    src = BatchSourceExec([[b1, b2]], T.Schema.from_arrow(t1.schema))
    agg = HashAggregateExec([col("k")], [Sum(col("v")).alias("s")], src)
    rows = _run_agg(agg)
    assert rows == [{"k": "a", "s": 400}]


def test_presorted_user_dictionary_resorted():
    # a user-provided DictionaryArray with an UNSORTED dictionary must be
    # re-sorted at ingest (kernels assume code order == byte order)
    darr = pa.DictionaryArray.from_arrays(
        pa.array([0, 1, 0, 1], pa.int32()),
        pa.array(["zz", "aa"], pa.string()))
    t = pa.table({"k": darr, "v": pa.array([1, 2, 3, 4], pa.int64()),
                  "f": pa.array(np.zeros(4))})
    b = batch_from_arrow(t)
    src = BatchSourceExec([[b]], T.Schema.from_arrow(
        pa.schema([("k", pa.string()), ("v", pa.int64()), ("f", pa.float64())])))
    node = SortExec([SortOrder(col("k"))], src)
    rows = [r["k"] for r in _run_agg(node)]
    assert rows == ["aa", "aa", "zz", "zz"]
    agg = HashAggregateExec(
        [], [Min(col("k")).alias("mn"), Max(col("k")).alias("mx")], src)
    r = _run_agg(agg)[0]
    assert r == {"mn": "aa", "mx": "zz"}


def test_all_null_string_column_ingest():
    t = pa.table({"s": pa.array([None, None, None], pa.string()),
                  "v": pa.array([1, 2, 3], pa.int64())})
    enc = dictionary_encode_table(t)
    b = batch_from_arrow(enc)
    out = batch_to_arrow(b, T.Schema.from_arrow(t.schema))
    assert out.column("s").to_pylist() == [None, None, None]
    # and via a direct all-null DictionaryArray
    darr = pa.DictionaryArray.from_arrays(
        pa.array([None, None], pa.int32()), pa.array([], pa.string()))
    t2 = pa.table({"s": darr})
    b2 = batch_from_arrow(t2)
    out2 = batch_to_arrow(b2, T.Schema([T.Field("s", T.STRING, True)]))
    assert out2.column("s").to_pylist() == [None, None]


def test_count_over_dict_string_multibatch():
    t = _table(400, seed=9)
    src = _src(t, batch_rows=100)
    agg = HashAggregateExec([col("k")], [Count(col("k")).alias("n")], src)
    rows = _run_agg(agg)
    df = t.to_pandas()
    exp = df.groupby("k", dropna=False).k.count()
    got = {r["k"]: r["n"] for r in rows}
    for k, n in exp.items():
        kk = None if not isinstance(k, str) else k
        if kk is None:
            assert got[kk] == 0  # count(k) excludes nulls
        else:
            assert got[kk] == n


def test_min_max_dict_single_batch_final_project():
    # single input batch: the dict min/max buffer reaches _final_project
    # without any concat/merge decode
    t = _table(100, seed=11)
    src = _src(t, batch_rows=1000)  # one batch
    agg = HashAggregateExec(
        [], [Min(col("k")).alias("mn"), Max(col("k")).alias("mx")], src)
    rows = _run_agg(agg)
    ks = [k for k in t.column("k").to_pylist() if k is not None]
    assert rows[0] == {"mn": min(ks), "mx": max(ks)}


def test_group_concat_across_shared_dict_batches():
    # multiple batches sharing one dictionary: sort-path merge on codes
    t = _table(997, seed=5)
    src = _src(t, batch_rows=100)  # 10 batches
    agg = HashAggregateExec([col("k")], [Count().alias("n")], src)
    rows = _run_agg(agg)
    df = t.to_pandas()
    exp = df.groupby("k", dropna=False).size()
    got = {r["k"]: r["n"] for r in rows}
    for k, n in exp.items():
        kk = None if not isinstance(k, str) else k
        assert got[kk] == n


@pytest.mark.parametrize("literal", ["BUILDING", "MACHINERY", "NO SUCH"])
@pytest.mark.parametrize("null_safe", [False, True])
@pytest.mark.parametrize("more", [0, 100], ids=["compared", "gathered"])
def test_equality_with_a_literal_is_decided_on_the_codes(literal, null_safe,
                                                         more):
    """``dict-coded column = 'literal'`` compares the literal with the
    dictionary's entries and the rows' codes with those (exprs/eval.py
    ``_dict_equals_literal``), and gives what the comparison of the decoded
    strings gives: nulls, an empty entry, a literal the dictionary lacks;
    a dictionary of more entries than ``DICT_COMPARE_ENTRIES`` reads each
    row's answer by one gather."""
    import jax
    from spark_rapids_tpu.exprs import eval as EV
    from spark_rapids_tpu.exprs import expr as E
    from spark_rapids_tpu.exprs.expr import EqualNullSafe, EqualTo, col, lit
    words = ["AUTOMOBILE", "BUILDING", "", "MACHINERY", None] + [
        f"SEGMENT#{i}" for i in range(more)]
    assert (len(words) > EV.DICT_COMPARE_ENTRIES) == bool(more)
    rng = np.random.default_rng(4)
    vals = [words[i] for i in rng.integers(0, len(words), 300)]
    t = pa.table({"s": pa.array(vals, pa.string())})
    enc = batch_from_arrow(dictionary_encode_table(t))
    plain = batch_from_arrow(t)
    assert enc.columns[0].is_dict and not plain.columns[0].is_dict
    schema = T.Schema.from_arrow(t.schema)
    op = EqualNullSafe if null_safe else EqualTo
    bound = E.resolve(op(col("s"), lit(literal)), schema)
    got = EV.eval_expr(bound, EV.EvalContext(enc))
    want = EV.eval_expr(bound, EV.EvalContext(plain))
    n = len(vals)
    assert np.array_equal(np.asarray(got.validity)[:n],
                          np.asarray(want.validity)[:n])
    live = np.asarray(want.validity)[:n]
    assert np.array_equal(np.asarray(got.data)[:n][live],
                          np.asarray(want.data)[:n][live])
    # on the codes: the rows' bytes are never made (the decode's output,
    # rows x longest entry bytes, is in no equation of the program)
    text = str(jax.make_jaxpr(
        lambda b: EV.eval_expr(bound, EV.EvalContext(b)).data)(enc))
    assert f"u8[{enc.capacity * enc.columns[0].dict_max_len}]" not in text

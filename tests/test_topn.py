"""The top-N (exec/sort.py ``TopNExec``, exec/kernels.py ``topn_indices``)
against the form it replaced: ``SortExec`` over the whole partition, then a
limit. Rows AND their order must be equal on every input, ties at rank
k / k+1, nulls and NaNs included: the selection reads the sort's own key
words and breaks ties by arrival, as the stable sort does."""

import math

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import batch_from_arrow, batch_to_arrow
from spark_rapids_tpu.exec import (
    BatchSourceExec, GlobalLimitExec, SortExec, SortOrder,
    take_ordered_and_project,
)
from spark_rapids_tpu.exec import kernels as K
from spark_rapids_tpu.exec.misc import _Gather
from spark_rapids_tpu.exec.sort import TopNExec
from spark_rapids_tpu.exprs.expr import col


def _source(table, batch_rows, parts=1, min_bucket=16):
    schema = T.Schema.from_arrow(table.schema)
    per = -(-max(table.num_rows, 1) // parts)
    out = []
    for p in range(parts):
        piece = table.slice(p * per, per)
        out.append([batch_from_arrow(piece.slice(i, batch_rows), min_bucket)
                    for i in range(0, piece.num_rows, batch_rows)])
    return BatchSourceExec(out, schema)


def _rows(node):
    schema = node.output_schema
    rows = []
    for b in node.execute_all():
        rows.extend(batch_to_arrow(b, schema).to_pylist())
    return [tuple("NaN" if isinstance(v, float) and math.isnan(v) else v
                  for v in r.values()) for r in rows]


def _sort_then_limit(orders, k, table, batch_rows, parts=1):
    """The deleted form: a full sort of the gathered partition(s), then the
    first k rows."""
    return GlobalLimitExec(k, SortExec(orders, _Gather(
        _source(table, batch_rows, parts))))


def _table(seed, n, with_ties=True):
    rng = np.random.default_rng(seed)
    span = max(n // 8, 2) if with_ties else 10 ** 9  # many equal keys
    a = rng.integers(-span, span, n).astype(object)
    f = rng.normal(size=n).round(1).astype(object)
    s = np.array(["", "a", "ab", "abc", "b", "ba", "zebra-crossing-long-key"],
                 object)[rng.integers(0, 7, n)]
    d = rng.integers(0, 5, n).astype(object)
    for arr, share in ((a, 0.1), (f, 0.1), (s, 0.1)):
        arr[rng.random(n) < share] = None
    f[rng.random(n) < 0.05] = float("nan")
    f[rng.random(n) < 0.05] = -0.0
    return pa.table({
        "a": pa.array(a, pa.int64()), "f": pa.array(f, pa.float64()),
        "s": pa.array(s, pa.string()), "d": pa.array(d, pa.int32()),
        "row": pa.array(np.arange(n), pa.int64())})  # tells ties apart


ORDERS = {
    "int-desc": [SortOrder(col("a"), ascending=False)],
    "int-asc-nulls-last": [SortOrder(col("a"), nulls_first=False)],
    "float-desc-then-int": [SortOrder(col("f"), ascending=False),
                            SortOrder(col("a"))],
    "string-asc-then-float-desc": [SortOrder(col("s")),
                                   SortOrder(col("f"), ascending=False)],
    "string-desc-nulls-first": [SortOrder(col("s"), ascending=False,
                                          nulls_first=True)],
    "few-values": [SortOrder(col("d"))],  # rank k always falls in a tie
}


@pytest.mark.parametrize("k", [1, 10, 100])
@pytest.mark.parametrize("name", sorted(ORDERS))
def test_topn_equals_sort_then_limit(name, k):
    """Several batches a partition, ties across and inside batches."""
    table = _table(11, 700)
    want = _rows(_sort_then_limit(ORDERS[name], k, table, 256))
    got = _rows(take_ordered_and_project(ORDERS[name], k,
                                         _source(table, 256)))
    assert got == want and len(got) == k


@pytest.mark.parametrize("parts", [1, 3])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_topn_over_partitions_and_seeds(seed, parts):
    table = _table(seed, 900)
    orders = ORDERS["float-desc-then-int"]
    want = _rows(_sort_then_limit(orders, 10, table, 200, parts))
    node = take_ordered_and_project(orders, 10, _source(table, 200, parts))
    assert _rows(node) == want
    assert isinstance(node, TopNExec)
    # several partitions: a partial top-N each, one more over their gather
    assert (parts > 1) == isinstance(node.children[0], _Gather)


@pytest.mark.parametrize("n, k", [(5, 10), (0, 10), (1, 1), (300, 0)])
def test_topn_with_k_past_the_rows_and_empty_input(n, k):
    table = _table(5, n)
    orders = ORDERS["int-desc"]
    want = _rows(_sort_then_limit(orders, k, table, 128))
    got = _rows(take_ordered_and_project(orders, k, _source(table, 128)))
    assert got == want and len(got) == min(n, k)


def test_ties_at_rank_k_go_to_the_earlier_row():
    """Twenty rows of one key: the k kept are the first k that arrived,
    whichever batch they came in."""
    table = pa.table({"a": pa.array([7] * 20, pa.int64()),
                      "row": pa.array(np.arange(20), pa.int64())})
    got = _rows(take_ordered_and_project(
        [SortOrder(col("a"), ascending=False)], 6, _source(table, 4)))
    assert got == [(7, i) for i in range(6)]


def test_selection_and_sort_sides_of_the_bound_agree():
    """k past the bound the batch's capacity gives takes the full sort of
    the batch; both sides return the same rows. The bound is derived."""
    assert [K.topn_select_max_k(1 << c) for c in (10, 14, 17, 20)] == [
        55, 105, 153, 210]
    table = _table(9, 900, with_ties=True)
    orders = ORDERS["float-desc-then-int"]
    for k in (55, 56):  # 1024-row batches: the last selected, the first sorted
        want = _rows(_sort_then_limit(orders, k, table, 900))
        node = take_ordered_and_project(orders, k,
                                        _source(table, 900, min_bucket=1024))
        assert _rows(node) == want


def test_topn_records_its_span_and_metric():
    from spark_rapids_tpu.obs import span as _span
    from spark_rapids_tpu.utils import tracing
    table = _table(4, 600)
    node = take_ordered_and_project(ORDERS["int-desc"], 10,
                                    _source(table, 256))
    tracing.set_capture(True, clear=True)
    try:
        with _span.activate(_span.new_trace()):
            _rows(node)
        events = [e for e in tracing.trace_events(clear=True)
                  if e["name"] == "exec:topn"]
    finally:
        tracing.set_capture(False, clear=True)
    # three batches, then the three partial results
    assert len(events) == 4 == node.metrics["numTopNDispatches"].value
    assert events[0]["args"]["k"] == 10
    assert events[0]["args"]["rows"] == 256
    assert events[-1]["args"]["rows"] == 1024  # 3 x 10 rows, packed
    assert events[0]["args"]["capacity"] == 1024
    # with no trace propagated nothing is recorded
    tracing.set_capture(True, clear=True)
    try:
        _rows(take_ordered_and_project(ORDERS["int-desc"], 10,
                                       _source(table, 256)))
        assert not [e for e in tracing.trace_events(clear=True)
                    if e["name"] == "exec:topn"]
    finally:
        tracing.set_capture(False, clear=True)


def test_the_planner_plans_a_sort_with_a_limit_as_a_topn():
    from spark_rapids_tpu.plan import from_arrow
    table = _table(6, 500)
    df = from_arrow(table, batch_rows=128, partitions=2)
    plan = df.sort(SortOrder(col("a"), ascending=False), limit=7
                   ).physical_plan()
    names = []

    def walk(n):
        names.append(type(n).__name__)
        for c in n.children:
            walk(c)
    walk(plan)
    assert "TopNExec" in names and "SortExec" not in names, names
    # and a sort without a limit keeps its operator
    plan = df.sort(SortOrder(col("a"))).physical_plan()
    names.clear()
    walk(plan)
    assert "SortExec" in names and "TopNExec" not in names, names

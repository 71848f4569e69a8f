"""TPC-H Q4, order priority checking (cl. 2.4.4).

    select o_orderpriority, count(*) as order_count
    from orders
    where o_orderdate >= date ':1'
      and o_orderdate < date ':1' + interval '3' month
      and exists (select * from lineitem
                  where l_orderkey = o_orderkey
                    and l_commitdate < l_receiptdate)
    group by o_orderpriority order by o_orderpriority

Substitution parameter: ``date`` as [year, month, 1], the first day of a
month from 1993-01 to 1997-10. ``PARAMS`` holds the validation value
(cl. 2.4.4.3). The answer is a string and a count: no DECIMAL. EXISTS is
planned as Spark plans it (``RewritePredicateSubquery``): a left semi join
of the quarter's ``orders`` against the ``lineitem`` rows received after
their commit date, whose order keys repeat (an order has up to seven
lines); the filtered ``lineitem`` is the build side.
"""

import numpy as np

TABLES = ("lineitem", "orders")
COLUMNS = {
    "lineitem": ("l_orderkey", "l_commitdate", "l_receiptdate"),
    "orders": ("o_orderkey", "o_orderdate", "o_orderpriority"),
}
PARAMS = {"date": [1993, 7, 1]}
DECIMAL_COLUMNS = {}
EXACT_COLUMNS = ("o_orderpriority", "order_count")
GROUPS = 5  # o_orderpriority's values: the answer's rows at most


def _quarter(p) -> tuple:
    """The first day of the quarter and the first day after it."""
    from datagen import date_i
    y, m, d = p["date"]
    y2, m2 = (y, m + 3) if m <= 9 else (y + 1, m - 9)
    return date_i(y, m, d), date_i(y2, m2, d)


def build(d, p=PARAMS):
    """The DataFrame a client submits, over the table handles ``d``."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.exprs.expr import (
        And, Count, GreaterThanOrEqual, LessThan, col, lit)
    lo, hi = _quarter(p)
    late = (d["lineitem"]
            .filter(LessThan(col("l_commitdate"), col("l_receiptdate")))
            .select("l_orderkey"))
    ords = (d["orders"]
            .filter(And(GreaterThanOrEqual(col("o_orderdate"),
                                           lit(lo, T.DATE)),
                        LessThan(col("o_orderdate"), lit(hi, T.DATE))))
            .select("o_orderkey", "o_orderpriority"))
    return (ords.join(late, left_on="o_orderkey", right_on="l_orderkey",
                      how="left_semi")
            .group_by("o_orderpriority")
            .agg(Count().alias("order_count"))
            .sort("o_orderpriority"))


def least_bytes(rows: dict, width: dict) -> int:
    """Each pruned column of the two tables once at device width (16 B a
    row of either), and at most five rows of a dictionary code and a
    count. From table shapes only."""
    read = sum(rows[t] * sum(width[c] for c in cols)
               for t, cols in COLUMNS.items())
    return read + GROUPS * (4 + 8)


def reference(raw: dict, p=PARAMS, how="semi", closed=False) -> dict:
    """Plain numpy, exact: boolean masks, ``np.isin`` of the quarter's
    order keys in the late lines' keys, ``np.bincount`` over the priority
    code. ``how``: ``semi`` counts an order once if it has a late line
    (EXISTS), ``anti`` if it has none (NOT EXISTS), ``inner`` once a late
    line (a join in place of EXISTS). ``closed`` takes the quarter's upper
    bound as ``<=``."""
    from datagen import labels
    li, od = raw["lineitem"], raw["orders"]
    lo, hi = _quarter(p)
    date = od["o_orderdate"]
    keep = (date >= lo) & ((date <= hi) if closed else (date < hi))
    late = li["l_orderkey"][li["l_commitdate"] < li["l_receiptdate"]]
    okey, prio = od["o_orderkey"][keep], od["o_orderpriority"][keep]
    if how == "inner":
        keys, times = np.unique(late, return_counts=True)
        at = np.minimum(np.searchsorted(keys, okey), max(len(keys) - 1, 0))
        weight = np.where(keys[at] == okey, times[at], 0) if len(keys) \
            else np.zeros(len(okey), np.int64)
    else:
        found = np.isin(okey, late)
        weight = (found if how == "semi" else ~found).astype(np.int64)
    names = labels("o_orderpriority")
    counts = np.bincount(prio.astype(np.int64), weights=weight,
                         minlength=len(names))  # exact: far under 2**53
    # codes ascend with the strings: the list of priorities is sorted
    groups = np.flatnonzero(counts > 0)
    return {"o_orderpriority": [names[g] for g in groups],
            "order_count": [int(counts[g]) for g in groups]}


def _dropped_match(raw: dict, p) -> dict:
    """The reference over a lineitem without the one late line of an order
    in the quarter that has exactly one: that order no longer counts."""
    from datagen import with_row
    li, od = raw["lineitem"], raw["orders"]
    lo, hi = _quarter(p)
    late = np.flatnonzero(li["l_commitdate"] < li["l_receiptdate"])
    keys, first, times = np.unique(li["l_orderkey"][late], return_index=True,
                                   return_counts=True)
    inq = od["o_orderkey"][(od["o_orderdate"] >= lo)
                           & (od["o_orderdate"] < hi)]
    once = np.flatnonzero((times == 1) & np.isin(keys, inq))
    at = int(late[first[once[0]]])
    return reference(dict(raw, lineitem=with_row(
        li, COLUMNS["lineitem"], at, 0)), p)


# name -> (raw, p) -> a degraded answer in the reference's form; put in the
# program's place each has to read answers_wrong >= 1
CONTROLS = {
    # an order counted once a late line, not once
    "counted_per_line": lambda raw, p: reference(raw, p, how="inner"),
    "dropped_match": _dropped_match,
    # the orders of the first day after the quarter counted too
    "window_closed": lambda raw, p: reference(raw, p, closed=True),
    "anti_for_semi": lambda raw, p: reference(raw, p, how="anti"),
}

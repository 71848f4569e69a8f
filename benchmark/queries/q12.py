"""TPC-H Q12, shipping modes and order priority (cl. 2.4.12).

    select l_shipmode,
      sum(case when o_orderpriority = '1-URGENT' or o_orderpriority = '2-HIGH'
          then 1 else 0 end) as high_line_count,
      sum(case when o_orderpriority <> '1-URGENT'
          and o_orderpriority <> '2-HIGH' then 1 else 0 end) as low_line_count
    from orders, lineitem
    where o_orderkey = l_orderkey and l_shipmode in (':1', ':2')
      and l_commitdate < l_receiptdate and l_shipdate < l_commitdate
      and l_receiptdate >= date ':3'
      and l_receiptdate < date ':3' + interval '1' year
    group by l_shipmode order by l_shipmode

Substitution parameters: ``shipmodes`` (two of l_shipmode's seven values)
and ``date`` as [year, 1, 1] (1993..1997). ``PARAMS`` holds the validation
values (cl. 2.4.12.3). The answer is a string and two counts: no DECIMAL.
``orders`` probes; the filtered ``lineitem`` is the build side, and an order
can have several qualifying lines: a build with duplicate keys.

No cell runs it yet (PERF.md, Open questions): it is here so that the
comparison is proved on an answer without a DECIMAL, through a real plan.
"""

import numpy as np

TABLES = ("lineitem", "orders")
COLUMNS = {
    "lineitem": ("l_orderkey", "l_shipmode", "l_shipdate", "l_commitdate",
                 "l_receiptdate"),
    "orders": ("o_orderkey", "o_orderpriority"),
}
PARAMS = {"shipmodes": ["MAIL", "SHIP"], "date": [1994, 1, 1]}
DECIMAL_COLUMNS = {}
EXACT_COLUMNS = ("l_shipmode", "high_line_count", "low_line_count")
HIGH = ("1-URGENT", "2-HIGH")


def _year(p) -> tuple:
    from datagen import date_i
    y, m, d = p["date"]
    return date_i(y, m, d), date_i(y + 1, m, d)


def build(d, p=PARAMS):
    """The DataFrame a client submits, over the table handles ``d``."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.exprs.expr import (
        And, GreaterThanOrEqual, If, In, LessThan, Not, Or, Sum, col, lit)
    lo, hi = _year(p)
    line = (d["lineitem"].filter(And(
        And(In(col("l_shipmode"), [lit(m) for m in p["shipmodes"]]),
            And(LessThan(col("l_commitdate"), col("l_receiptdate")),
                LessThan(col("l_shipdate"), col("l_commitdate")))),
        And(GreaterThanOrEqual(col("l_receiptdate"), lit(lo, T.DATE)),
            LessThan(col("l_receiptdate"), lit(hi, T.DATE)))))
        .select("l_orderkey", "l_shipmode"))
    ords = d["orders"].select("o_orderkey", "o_orderpriority")
    j = ords.join(line, left_on="o_orderkey", right_on="l_orderkey")
    urgent, high = (col("o_orderpriority").eq(v) for v in HIGH)
    one, zero = lit(1, T.LONG), lit(0, T.LONG)
    return (j.group_by("l_shipmode")
            .agg(Sum(If(Or(urgent, high), one, zero))
                 .alias("high_line_count"),
                 Sum(If(And(Not(urgent), Not(high)), one, zero))
                 .alias("low_line_count"))
            .sort("l_shipmode"))


def least_bytes(rows: dict, width: dict) -> int:
    """Each pruned column of the two tables once at device width, and at
    most seven rows of a dictionary code and two counts. From table shapes
    only."""
    read = sum(rows[t] * sum(width[c] for c in cols)
               for t, cols in COLUMNS.items())
    return read + 7 * (4 + 8 + 8)


def _qualifying(raw: dict, p) -> np.ndarray:
    """Mask of the lineitem rows the predicates keep."""
    from datagen import labels
    li = raw["lineitem"]
    lo, hi = _year(p)
    modes = [labels("l_shipmode").index(m) for m in p["shipmodes"]]
    return (np.isin(li["l_shipmode"], modes)
            & (li["l_commitdate"] < li["l_receiptdate"])
            & (li["l_shipdate"] < li["l_commitdate"])
            & (li["l_receiptdate"] >= lo) & (li["l_receiptdate"] < hi))


def reference(raw: dict, p=PARAMS, high=HIGH) -> dict:
    """Plain numpy, exact: boolean masks, ``np.isin`` for the ship modes,
    ``searchsorted`` on the sorted (unique) order keys for the join, one
    ``np.bincount`` a count. ``high`` names the priorities the first count
    takes; the second takes every line whose priority is not among HIGH."""
    from datagen import labels
    li, od = raw["lineitem"], raw["orders"]
    keep = _qualifying(raw, p)
    lkey, mode = li["l_orderkey"][keep], li["l_shipmode"][keep]
    by_key = np.argsort(od["o_orderkey"], kind="stable")
    okey = od["o_orderkey"][by_key]
    at = np.minimum(np.searchsorted(okey, lkey), max(len(okey) - 1, 0))
    hit = (okey[at] == lkey) if len(okey) else np.zeros(len(lkey), bool)
    prio = od["o_orderpriority"][by_key][at[hit]]  # of each line's one order
    mode = mode[hit].astype(np.int64)
    names, priorities = labels("l_shipmode"), labels("o_orderpriority")
    is_high = np.isin(prio, [priorities.index(v) for v in high])
    is_low = ~np.isin(prio, [priorities.index(v) for v in HIGH])
    lines = np.bincount(mode, minlength=len(names))
    highs = np.bincount(mode[is_high], minlength=len(names))
    lows = np.bincount(mode[is_low], minlength=len(names))
    # codes ascend with the strings: the list of ship modes is sorted
    groups = np.flatnonzero(lines > 0)
    return {"l_shipmode": [names[g] for g in groups],
            "high_line_count": [int(highs[g]) for g in groups],
            "low_line_count": [int(lows[g]) for g in groups]}


def _match(raw: dict, p, times: int) -> dict:
    """The reference over a lineitem in which one qualifying line is there
    ``times`` times: the second of an order's qualifying lines where an
    order has two (what a build that keeps one row a key drops), else the
    first qualifying line of all."""
    from datagen import with_row
    li = raw["lineitem"]
    rows = np.flatnonzero(_qualifying(raw, p))
    keys = li["l_orderkey"][rows]  # lineitem is clustered by order
    again = np.flatnonzero(keys[1:] == keys[:-1])
    at = int(rows[again[0] + 1] if len(again) else rows[0])
    return reference(dict(raw, lineitem=with_row(
        li, COLUMNS["lineitem"], at, times)), p)


# name -> (raw, p) -> a degraded answer in the reference's form; put in the
# program's place each has to read answers_wrong >= 1
CONTROLS = {
    "dropped_match": lambda raw, p: _match(raw, p, 0),
    "duplicated_match": lambda raw, p: _match(raw, p, 2),
    # the CASE's second arm lost: a 2-HIGH line is counted in neither sum
    "priority_miscounted": lambda raw, p: reference(raw, p, high=HIGH[:1]),
}

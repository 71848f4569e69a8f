"""TPC-H Q3, shipping priority (cl. 2.4.3).

    select l_orderkey, sum(l_extendedprice*(1-l_discount)) as revenue,
      o_orderdate, o_shippriority
    from customer, orders, lineitem
    where c_mktsegment = ':1' and c_custkey = o_custkey
      and l_orderkey = o_orderkey and o_orderdate < date ':2'
      and l_shipdate > date ':2'
    group by l_orderkey, o_orderdate, o_shippriority
    order by revenue desc, o_orderdate limit 10

Substitution parameters: ``segment`` (one of c_mktsegment's five values) and
``date`` as [year, month, day] (a day of March 1995). ``PARAMS`` holds the
validation values (cl. 2.4.3.3). The specification leaves ties in (revenue,
o_orderdate) open; ``build`` and ``reference`` both break them by
l_orderkey ascending (the configuration lists that under ``assumed``).
"""

import numpy as np

TABLES = ("lineitem", "orders", "customer")
COLUMNS = {
    "lineitem": ("l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"),
    "orders": ("o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"),
    "customer": ("c_custkey", "c_mktsegment"),
}
PARAMS = {"segment": "BUILDING", "date": [1995, 3, 15]}
DECIMAL_COLUMNS = {"revenue": 4}
EXACT_COLUMNS = ("l_orderkey", "o_orderdate", "o_shippriority")
EXACT_TYPES = {"o_orderdate": "date32", "o_shippriority": "int32"}
LIMIT = 10


def _day(p) -> int:
    from datagen import date_i
    return date_i(*p["date"])


def build(d, p=PARAMS):
    """The DataFrame a client submits, over the table handles ``d``: the
    fact table probes, the filtered dimension tables are the build sides."""
    from decimal import Decimal
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.exec.sort import SortOrder
    from spark_rapids_tpu.exprs.expr import (
        GreaterThan, LessThan, Multiply, Subtract, Sum, col, lit)
    day = lit(_day(p), T.DATE)
    cust = (d["customer"].filter(col("c_mktsegment").eq(p["segment"]))
            .select("c_custkey"))
    ords = (d["orders"].filter(LessThan(col("o_orderdate"), day))
            .select("o_orderkey", "o_custkey", "o_orderdate",
                    "o_shippriority"))
    line = (d["lineitem"].filter(GreaterThan(col("l_shipdate"), day))
            .select("l_orderkey", "l_extendedprice", "l_discount"))
    oc = ords.join(cust, left_on="o_custkey", right_on="c_custkey")
    j = line.join(oc, left_on="l_orderkey", right_on="o_orderkey")
    one = lit(Decimal("1.00"), T.DecimalType(15, 2))
    return (j.group_by("l_orderkey", "o_orderdate", "o_shippriority")
            .agg(Sum(Multiply(col("l_extendedprice"),
                              Subtract(one, col("l_discount"))))
                 .alias("revenue"))
            .sort(SortOrder(col("revenue"), ascending=False),
                  SortOrder(col("o_orderdate")), SortOrder(col("l_orderkey")),
                  limit=LIMIT))


def least_bytes(rows: dict, width: dict) -> int:
    """Each pruned column of the three tables once at device width, and the
    ten rows of the result. From table shapes only."""
    read = sum(rows[t] * sum(width[c] for c in cols)
               for t, cols in COLUMNS.items())
    return read + LIMIT * (8 + 16 + 4 + 4)


def reference(raw: dict, p=PARAMS, money=int, limit=LIMIT) -> dict:
    """Plain numpy, exact: boolean masks, membership by ``np.isin`` and
    ``searchsorted`` on sorted keys for the two joins, revenue in whole
    units of 1e-4 as int64 (price_cents x (100 - discount_cents); an
    order's at most seven products stay far under 2**63), ``np.lexsort``
    for the order, the first ``limit``. ``money=float``: money as float64
    dollars, products and sums in float64, rounded at the end."""
    from datagen import labels
    cu, od, li = raw["customer"], raw["orders"], raw["lineitem"]
    day = _day(p)
    seg = labels("c_mktsegment").index(p["segment"])
    buyers = cu["c_custkey"][cu["c_mktsegment"] == seg]
    o_keep = (od["o_orderdate"] < day) & np.isin(od["o_custkey"], buyers)
    okey = od["o_orderkey"][o_keep]
    odate = od["o_orderdate"][o_keep]
    oprio = od["o_shippriority"][o_keep]
    by_key = np.argsort(okey, kind="stable")
    okey, odate, oprio = okey[by_key], odate[by_key], oprio[by_key]
    l_keep = li["l_shipdate"] > day
    lkey = li["l_orderkey"][l_keep]
    at = np.minimum(np.searchsorted(okey, lkey), max(len(okey) - 1, 0))
    hit = (okey[at] == lkey) if len(okey) else np.zeros(len(lkey), bool)
    at = at[hit]  # the one order each surviving line joins
    price = li["l_extendedprice"][l_keep][hit]
    disc = li["l_discount"][l_keep][hit]
    if money is float:
        rev = np.bincount(at, (price / 100.0) * (1.0 - disc / 100.0),
                          minlength=len(okey))
        rev = np.round(rev * 10 ** 4).astype(np.int64)
    else:
        rev = np.zeros(len(okey), np.int64)
        np.add.at(rev, at, price * (100 - disc))
    groups = np.flatnonzero(np.bincount(at, minlength=len(okey)) > 0)
    # revenue desc, o_orderdate, l_orderkey (lexsort: last key is primary)
    top = groups[np.lexsort((okey[groups], odate[groups], -rev[groups]))
                 ][:limit]
    return {"l_orderkey": [int(v) for v in okey[top]],
            "o_orderdate": [int(v) for v in odate[top]],
            "o_shippriority": [int(v) for v in oprio[top]],
            "revenue": [int(v) for v in rev[top]]}


def _float32_money(raw: dict, p) -> dict:
    """Money as float32 dollars: the keys of the exact top ten with their
    revenue summed in float32 (float32 revenue can also reorder the rows;
    the kindest case is kept)."""
    want = reference(raw, p)
    li = raw["lineitem"]
    keep = li["l_shipdate"] > _day(p)
    out = dict(want, revenue=[])
    for key in want["l_orderkey"]:
        m = keep & (li["l_orderkey"] == key)
        price = (li["l_extendedprice"][m] / 100.0).astype(np.float32)
        disc = (li["l_discount"][m] / 100.0).astype(np.float32)
        rev = np.sum(price * (np.float32(1.0) - disc), dtype=np.float32)
        out["revenue"].append(int(round(float(rev) * 10 ** 4)))
    return out


def _match(raw: dict, p, times: int) -> dict:
    """The reference over a lineitem in which one line that joins the fifth
    order of the answer is there ``times`` times: a probe match dropped (0)
    or emitted twice (2)."""
    from datagen import with_row
    li = raw["lineitem"]
    key = reference(raw, p)["l_orderkey"][4]
    at = int(np.flatnonzero((li["l_orderkey"] == key)
                            & (li["l_shipdate"] > _day(p)))[0])
    return reference(dict(raw, lineitem=with_row(
        li, COLUMNS["lineitem"], at, times)), p)


def _rows_swapped(raw: dict, p) -> dict:
    """Rows 10 and 11 of the full order swapped: the tenth place goes to
    the eleventh group, as a sort that misplaces two rows would leave it."""
    full = reference(raw, p, limit=LIMIT + 1)
    return {c: v[:LIMIT - 1] + v[LIMIT:] for c, v in full.items()}


def _member_missed(raw: dict, p) -> dict:
    """A true member (the fifth) missed and the eleventh let in at the end:
    what an approximate top-k hands back."""
    full = reference(raw, p, limit=LIMIT + 1)
    return {c: v[:4] + v[5:] for c, v in full.items()}


# name -> (raw, p) -> a degraded answer in the reference's form; put in the
# program's place each has to read answers_wrong >= 1 (control.py, on the chip)
CONTROLS = {
    "float32_money": _float32_money,
    "dropped_match": lambda raw, p: _match(raw, p, 0),
    "duplicated_match": lambda raw, p: _match(raw, p, 2),
    "rows_swapped": _rows_swapped,
    "member_missed": _member_missed,
}
# name -> (the same kind of function, why it cannot fail): run and reported,
# never counted against the comparison
PASSES_BY_DESIGN = {
    "float64_money": (
        lambda raw, p: reference(raw, p, money=float),
        "a group's sum holds at most seven products, each under 2**53 "
        "units of 1e-4: float64 rounds every revenue to the exact one"),
}

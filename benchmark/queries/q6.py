"""TPC-H Q6, forecasting revenue change (cl. 2.4.6).

    select sum(l_extendedprice * l_discount) as revenue from lineitem
    where l_shipdate >= date ':1' and l_shipdate < date ':1' + 1 year
      and l_discount between :2 - 0.01 and :2 + 0.01 and l_quantity < :3

Substitution parameters: ``year`` (1993..1997), ``discount`` in cents
(2..9), ``quantity`` (24 or 25). ``PARAMS`` holds the validation values.
"""

import numpy as np

TABLES = ("lineitem",)
COLUMNS = {"lineitem": ("l_shipdate", "l_discount", "l_quantity",
                        "l_extendedprice")}
PARAMS = {"year": 1994, "discount": 6, "quantity": 24}
# answer column -> its scale: DECIMAL columns compared exactly, unscaled
DECIMAL_COLUMNS = {"revenue": 4}
EXACT_COLUMNS = ()


def build(d, p=PARAMS):
    """The DataFrame a client submits, over the table handles ``d``."""
    from decimal import Decimal
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.exprs.expr import (
        And, GreaterThanOrEqual, LessThan, LessThanOrEqual, Multiply, Sum,
        col, lit)
    from datagen import date_i

    def money(cents):
        return lit(Decimal(cents).scaleb(-2), T.DecimalType(15, 2))
    li = d["lineitem"].filter(And(
        And(And(GreaterThanOrEqual(col("l_shipdate"),
                                   lit(date_i(p["year"], 1, 1), T.DATE)),
                LessThan(col("l_shipdate"),
                         lit(date_i(p["year"] + 1, 1, 1), T.DATE))),
            And(GreaterThanOrEqual(col("l_discount"),
                                   money(p["discount"] - 1)),
                LessThanOrEqual(col("l_discount"),
                                money(p["discount"] + 1)))),
        LessThan(col("l_quantity"), money(p["quantity"] * 100))))
    return li.agg(Sum(Multiply(col("l_extendedprice"), col("l_discount")))
                  .alias("revenue"))


def least_bytes(rows: dict, width: dict) -> int:
    """The least a device must read and write to answer: each input column
    once at device width, and the result. From table shapes only."""
    read = rows["lineitem"] * sum(width[c] for c in COLUMNS["lineitem"])
    return read + 16


def _kept(raw: dict, p) -> tuple:
    """(price, discount) in whole cents of the rows the predicates keep."""
    from datagen import date_i
    li = raw["lineitem"]
    m = ((li["l_shipdate"] >= date_i(p["year"], 1, 1))
         & (li["l_shipdate"] < date_i(p["year"] + 1, 1, 1))
         & (li["l_discount"] >= p["discount"] - 1)
         & (li["l_discount"] <= p["discount"] + 1)
         & (li["l_quantity"] < p["quantity"] * 100))
    return li["l_extendedprice"][m], li["l_discount"][m]


def reference(raw: dict, p=PARAMS, money=int) -> dict:
    """Plain numpy over whole cents, exact. ``money=float`` is the same
    query with money as float64 dollars, as an engine without DECIMAL would
    hold it, rounded to the answer's scale at the end."""
    price, disc = _kept(raw, p)
    if money is float:
        total = float(np.sum((price / 100.0) * (disc / 100.0)))
        return {"revenue": [int(round(total * 10 ** 4))]}
    return {"revenue": [sum(int(c.sum()) for c in
                            np.array_split(price * disc, 16))]}


def _float32_money(raw: dict, p) -> dict:
    """Money as float32 dollars, products and the sum in float32; the
    predicates stay exact, which is the kindest float engine."""
    price, disc = ((v / 100.0).astype(np.float32) for v in _kept(raw, p))
    total = float(np.sum(price * disc, dtype=np.float32))
    return {"revenue": [int(round(total * 10 ** 4))]}


# name -> (raw, p) -> a degraded answer in the reference's form; put in the
# program's place each has to read answers_wrong >= 1 (control.py, on the chip)
CONTROLS = {"float32_money": _float32_money}
# name -> (the same kind of function, why it cannot fail): run and reported,
# never counted against the comparison
PASSES_BY_DESIGN = {
    "float64_money": (
        lambda raw, p: reference(raw, p, money=float),
        "one sum of some 10^5..10^6 products of two-decimal numbers, far "
        "under 2**53 units of 1e-4: float64 rounds to the exact answer"),
}

"""TPC-H Q1, pricing summary report (cl. 2.4.1).

    select l_returnflag, l_linestatus, sum(l_quantity), sum(l_extendedprice),
      sum(l_extendedprice*(1-l_discount)),
      sum(l_extendedprice*(1-l_discount)*(1+l_tax)), avg(l_quantity),
      avg(l_extendedprice), avg(l_discount), count(*)
    from lineitem where l_shipdate <= date '1998-12-01' - :1 days
    group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus

Substitution parameter: ``delta`` (60..120 days). ``PARAMS`` holds the
validation value. Averages of DECIMAL(15,2) are DECIMAL(19,6), rounded half
up, as Spark defines them.
"""

import numpy as np

TABLES = ("lineitem",)
COLUMNS = {"lineitem": ("l_shipdate", "l_returnflag", "l_linestatus",
                        "l_quantity", "l_extendedprice", "l_discount",
                        "l_tax")}
PARAMS = {"delta": 90}
DECIMAL_COLUMNS = {"sum_qty": 2, "sum_base_price": 2, "sum_disc_price": 4,
                   "sum_charge": 6, "avg_qty": 6, "avg_price": 6,
                   "avg_disc": 6}
EXACT_COLUMNS = ("l_returnflag", "l_linestatus", "count_order")


def _last_day(p) -> int:
    from datagen import date_i
    return date_i(1998, 12, 1) - int(p["delta"])


def build(d, p=PARAMS):
    from decimal import Decimal
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.exprs.expr import (
        Add, Average, Count, LessThanOrEqual, Multiply, Subtract, Sum, col,
        lit)
    one = lit(Decimal("1.00"), T.DecimalType(15, 2))
    li = d["lineitem"].filter(
        LessThanOrEqual(col("l_shipdate"), lit(_last_day(p), T.DATE)))
    disc_price = Multiply(col("l_extendedprice"),
                          Subtract(one, col("l_discount")))
    charge = Multiply(disc_price, Add(one, col("l_tax")))
    return (li.group_by("l_returnflag", "l_linestatus")
            .agg(Sum(col("l_quantity")).alias("sum_qty"),
                 Sum(col("l_extendedprice")).alias("sum_base_price"),
                 Sum(disc_price).alias("sum_disc_price"),
                 Sum(charge).alias("sum_charge"),
                 Average(col("l_quantity")).alias("avg_qty"),
                 Average(col("l_extendedprice")).alias("avg_price"),
                 Average(col("l_discount")).alias("avg_disc"),
                 Count().alias("count_order"))
            .sort("l_returnflag", "l_linestatus"))


def least_bytes(rows: dict, width: dict) -> int:
    read = rows["lineitem"] * sum(width[c] for c in COLUMNS["lineitem"])
    return read + 4 * (2 * 4 + 7 * 16 + 8)  # 4 groups of 10 values


def _total(v) -> int:
    """Exact sum of int64 values whose total may pass 2**63."""
    return sum(int(c.sum()) for c in np.array_split(v, 64))


def _half_up(num: int, den: int) -> int:
    return (2 * num + den) // (2 * den)


def reference(raw: dict, p=PARAMS, money=int) -> dict:
    """Plain numpy over whole cents, exact; answers as unscaled integers at
    the scales of DECIMAL_COLUMNS. ``money=float`` is the control: money as
    float64 dollars, products and sums in float64, rounded at the end."""
    from datagen import labels
    li = raw["lineitem"]
    keep = li["l_shipdate"] <= _last_day(p)
    code = li["l_returnflag"].astype(np.int16) * 2 + li["l_linestatus"]
    out = {k: [] for k in EXACT_COLUMNS + tuple(DECIMAL_COLUMNS)}
    # codes ascend with (returnflag, linestatus), both lists being sorted
    for c in range(6):
        m = keep & (code == c)
        n = int(np.count_nonzero(m))
        if n == 0:
            continue
        qty, price = li["l_quantity"][m], li["l_extendedprice"][m]
        disc, tax = li["l_discount"][m], li["l_tax"][m]
        if money is float:
            fq, fp, fd, ft = (v / 100.0 for v in (qty, price, disc, tax))
            dp = fp * (1.0 - fd)
            sums = [float(np.sum(v)) for v in
                    (fq, fp, dp, dp * (1.0 + ft), fd)]
            vals = [round(sums[0] * 1e2), round(sums[1] * 1e2),
                    round(sums[2] * 1e4), round(sums[3] * 1e6),
                    round(sums[0] / n * 1e6), round(sums[1] / n * 1e6),
                    round(sums[4] / n * 1e6)]
        else:
            dp = price * (100 - disc)
            s_qty, s_price, s_disc = _total(qty), _total(price), _total(disc)
            vals = [s_qty, s_price, _total(dp), _total(dp * (100 + tax)),
                    _half_up(s_qty * 10 ** 4, n),
                    _half_up(s_price * 10 ** 4, n),
                    _half_up(s_disc * 10 ** 4, n)]
        out["l_returnflag"].append(labels("l_returnflag")[c // 2])
        out["l_linestatus"].append(labels("l_linestatus")[c % 2])
        out["count_order"].append(n)
        for k, v in zip(DECIMAL_COLUMNS, vals):
            out[k].append(int(v))
    return out


# name -> (raw, p) -> a degraded answer in the reference's form; put in the
# program's place each has to read answers_wrong >= 1 (control.py, on the chip)
CONTROLS = {
    # the step below DECIMAL that would tempt a later PR: sum_charge passes
    # 2**53 units of 1e-6 from about SF0.2
    "float64_money": lambda raw, p: reference(raw, p, money=float),
}

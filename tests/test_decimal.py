"""Decimal semantics, differential device-vs-CPU.

The reference treats Spark-exact decimal as core surface (GpuCast.scala:288,
jni DecimalUtils, DecimalPrecision rules); TPC-DS money columns are
decimal(7,2) with wide intermediates.  DECIMAL64 (p<=18) runs on device as
scaled int64; wider types run on the CPU engine with Python-int exactness
until the two-limb device path lands.
"""

import decimal

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.config.conf import RapidsConf
from spark_rapids_tpu.exprs import expr as E
from spark_rapids_tpu.exprs.expr import (Add, Average, Cast, Count, Divide,
                                         EqualTo, GreaterThan, Max, Min,
                                         Multiply, Subtract, Sum, col, lit)
from spark_rapids_tpu.plan import from_arrow

D = decimal.Decimal


def table():
    return pa.table({
        "k": pa.array([1, 2, 1, 2, 1], type=pa.int32()),
        "m": pa.array([D("12.34"), D("-5.00"), D("0.01"), None,
                       D("99999.99")], type=pa.decimal128(7, 2)),
        "n": pa.array([D("1.5"), D("2.25"), None, D("-0.75"), D("10.00")],
                      type=pa.decimal128(9, 4)),
        "w": pa.array(
            [D("12345678901234567890.123456789012345678"),
             D("-0.000000000000000001"), None,
             D("99999999999999999999.999999999999999999"),
             D("1.000000000000000000")], type=pa.decimal128(38, 18)),
        "q": pa.array([2, 3, 4, 5, 6], type=pa.int32()),
        "f": pa.array([1.5, 2.0, 0.5, -1.0, 3.0]),
    })


def both(build, make=table):
    out = []
    for enabled in (True, False):
        conf = RapidsConf({"spark.rapids.tpu.sql.enabled": enabled})
        t = make()
        df = from_arrow(t, conf)
        df.shuffle_partitions = 2
        out.append(build(df).collect())
    return out


def assert_same(build, make=table):
    import math

    dev, cpu = both(build, make)
    assert len(dev) == len(cpu), f"dev={dev}\ncpu={cpu}"
    for ra, rb in zip(dev, cpu):
        assert ra.keys() == rb.keys()
        for kk in ra:
            va, vb = ra[kk], rb[kk]
            if isinstance(va, float) and isinstance(vb, float):
                # the real-TPU f64 is a double-double emulation: ULP-level
                # float divergence is expected (reference approximate_float)
                same = (math.isnan(va) and math.isnan(vb)) or \
                    abs(va - vb) <= 1e-9 * max(1.0, abs(va), abs(vb))
                assert same, f"{kk}: {va!r} vs {vb!r}"
            else:
                assert va == vb, f"{kk}: {va!r} vs {vb!r}\n{ra}\n{rb}"
    return dev


def test_roundtrip_ingest_egest():
    dev = assert_same(lambda df: df.select("m", "n", "w"))
    assert dev[0]["m"] == D("12.34")
    assert dev[3]["w"] == D("99999999999999999999.999999999999999999")


def test_arithmetic_mixed_operands():
    dev = assert_same(lambda df: df.select(
        Add(col("m"), col("n")).alias("a"),
        Subtract(col("m"), lit(D("0.05"), T.DecimalType(3, 2))).alias("s"),
        Multiply(col("m"), col("q")).alias("mq"),
        Multiply(col("m"), col("f")).alias("mf"),
        Multiply(col("m"), col("n")).alias("mn"),
    ))
    assert dev[0]["a"] == D("13.8400")
    assert dev[0]["mq"] == D("24.68")
    assert dev[0]["mf"] == pytest.approx(18.51)
    assert dev[0]["mn"] == D("18.510000")


def test_divide_exact_half_up():
    dev = assert_same(lambda df: df.select(
        Divide(col("m"), col("n")).alias("d"),
        Divide(col("m"), col("q")).alias("di"),
    ))
    # 12.34 / 1.5 at scale 12, HALF_UP
    assert dev[0]["d"] == D("8.226666666667")
    assert dev[1]["d"] == D("-2.222222222222")
    # divide-by-null and null/x stay null
    assert dev[2]["d"] is None and dev[3]["d"] is None


def test_compare_mixed():
    assert_same(lambda df: df.filter(GreaterThan(col("m"), col("n")))
                .select("k"))
    assert_same(lambda df: df.filter(GreaterThan(col("m"), col("q")))
                .select("k"))
    assert_same(lambda df: df.filter(GreaterThan(col("m"), col("f")))
                .select("k"))
    assert_same(lambda df: df.filter(EqualTo(col("w"), col("w")))
                .select("k"))


def test_cast_matrix():
    dev = assert_same(lambda df: df.select(
        Cast(col("m"), T.DecimalType(9, 4)).alias("up"),
        Cast(col("m"), T.DecimalType(6, 1)).alias("down"),
        Cast(col("m"), T.DOUBLE).alias("dbl"),
        Cast(col("m"), T.INT).alias("i"),
        Cast(col("q"), T.DecimalType(5, 2)).alias("fromint"),
        Cast(col("f"), T.DecimalType(5, 2)).alias("fromf"),
    ))
    assert dev[0]["up"] == D("12.3400")
    assert dev[0]["down"] == D("12.3")  # HALF_UP at scale 1
    assert dev[1]["down"] == D("-5.0")
    assert dev[0]["i"] == 12
    assert dev[0]["fromint"] == D("2.00")
    assert dev[0]["fromf"] == D("1.50")


def test_agg_exact():
    dev = assert_same(lambda df: df.group_by("k").agg(
        Sum(col("m")).alias("s"),
        Average(col("m")).alias("a"),
        Min(col("m")).alias("lo"),
        Max(col("m")).alias("hi"),
        Count(col("m")).alias("c"),
    ).sort("k"))
    assert dev[0]["s"] == D("100012.34")
    # avg = 100012.34/3 at scale 6, HALF_UP
    assert dev[0]["a"] == D("33337.446667")
    assert dev[1]["s"] == D("-5.00")


def test_agg_precision38_cpu_path():
    """sum over decimal(38,18) exceeds DECIMAL64 -> exact CPU fallback;
    the total here passes 10^38 scaled units -> Spark overflow NULL."""
    dev = assert_same(lambda df: df.agg(
        Sum(col("w")).alias("s"), Average(col("w")).alias("a")))
    assert dev[0]["s"] is None  # 1.12e20 at scale 18 = 39 digits: overflow
    # narrower wide sum stays exact
    dev2 = assert_same(lambda df: df.filter(
        E.LessThan(col("w"), lit(D("2"), T.DecimalType(38, 18)))).agg(
        Sum(col("w")).alias("s")))
    assert dev2[0]["s"] == D("0.999999999999999999")


def test_wide_arith_cpu_path():
    dev = assert_same(lambda df: df.select(
        Add(col("w"), col("w")).alias("a2"),
        Multiply(col("w"), col("q")).alias("wq"),
    ))
    assert dev[0]["a2"] == D("24691357802469135780.246913578024691356")
    assert dev[3]["a2"] is None  # 2e20 at scale 18: overflow -> NULL


def test_integral_divide_remainder_pmod():
    dev = assert_same(lambda df: df.select(
        E.IntegralDivide(col("m"), col("n")).alias("idiv"),
        E.Remainder(col("m"), col("n")).alias("rem"),
        E.Pmod(col("m"), col("n")).alias("pm"),
    ))
    # 12.34 div 1.5 = trunc(8.22...) = 8; -5.00 div 2.25 = -2
    assert dev[0]["idiv"] == 8
    assert dev[1]["idiv"] == -2
    # 12.34 % 1.5 = 0.34 at scale 4; Java sign rules
    assert dev[0]["rem"] == D("0.3400")
    assert dev[1]["rem"] == D("-0.5000")
    assert dev[1]["pm"] == D("1.7500")


def test_compare_decimal_vs_large_long():
    """rescale-up would overflow int64 (review finding): 2^62 * 100 wraps."""
    t = pa.table({
        "m": pa.array([D("12.34"), D("-5.00")], type=pa.decimal128(7, 2)),
        "big": pa.array([2 ** 62, -2 ** 62], type=pa.int64()),
    })
    for enabled in (True, False):
        df = from_arrow(t, RapidsConf(
            {"spark.rapids.tpu.sql.enabled": enabled}))
        assert df.filter(GreaterThan(col("m"), col("big"))).collect() == [
            {"m": D("-5.00"), "big": -2 ** 62}], f"enabled={enabled}"
        assert df.filter(E.LessThan(col("m"), col("big"))).collect() == [
            {"m": D("12.34"), "big": 2 ** 62}], f"enabled={enabled}"


def test_grouped_wide_agg():
    """decimal128 sum/avg/min/max grouped — dense + shuffled partial/final
    paths with (hi, lo) buffers riding the wire format."""
    dev = assert_same(lambda df: df.group_by("k").agg(
        Sum(col("w")).alias("s"), Min(col("w")).alias("lo"),
        Max(col("w")).alias("hi"), Average(col("w")).alias("a"),
    ).sort("k"))
    assert dev[0]["lo"] == D("1.000000000000000000")
    assert dev[0]["hi"] == D("12345678901234567890.123456789012345678")
    assert dev[1]["lo"] == D("-0.000000000000000001")
    assert dev[1]["hi"] == D("99999999999999999999.999999999999999999")


def test_wide_sum_of_products():
    """sum(m * n): the decimal64 x decimal64 -> decimal128 product feeds a
    128-bit device sum — the TPC-DS sum(price*qty) shape."""
    dev = assert_same(lambda df: df.agg(
        Sum(Multiply(col("m"), col("n"))).alias("s")))
    # 12.34*1.5 + (-5)*2.25 + 99999.99*10 = 1000007.16 at scale 6
    assert dev[0]["s"] == D("1000007.160000")


def test_group_by_decimal_key():
    assert_same(lambda df: df.group_by("m").agg(Count().alias("c"))
                .sort("m"))


def test_sort_by_decimal():
    dev = assert_same(lambda df: df.sort("m"))
    vals = [r["m"] for r in dev if r["m"] is not None]
    assert vals == sorted(vals)


def test_window_decimal_aggs():
    from spark_rapids_tpu.exprs.window import over, window_spec

    from spark_rapids_tpu.exec.sort import SortOrder

    def build(df):
        spec = window_spec(partition_by=[col("k")],
                           order_by=[SortOrder(col("q"))])
        return df.with_window(
            over(Sum(col("m")), spec).alias("rs"),
            over(Average(col("m")), spec).alias("ra"),
            over(Min(col("m")), spec).alias("rmin"),
        )
    assert_same(build)


def test_device_placement():
    """DECIMAL128 storage + sum/avg/min/max/compare AND (round 4) wide
    multiply/divide run on device via the 16-bit-limb Knuth-D kernels."""
    t = table()
    df = from_arrow(t, RapidsConf({}))
    stats = (df.group_by("k").agg(Sum(col("w")).alias("s"))
             .device_plan_stats())
    assert stats["device_fraction"] == 1.0, stats
    stats_div = (df.select(Divide(col("w"), col("w")).alias("d"),
                           Multiply(col("w"), col("m")).alias("m2"))
                 .device_plan_stats())
    assert stats_div["device_fraction"] == 1.0, stats_div
    # the differential value check rides both engines
    dev = assert_same(lambda df: df.select(
        Divide(col("w"), col("n")).alias("d"),
        Multiply(col("w"), col("m")).alias("m2"),
        Divide(col("m"), col("w")).alias("d2")))
    assert dev[0]["d"] is not None


def test_variance_stddev_aggs():
    """stddev/variance family, device vs CPU, grouped + global,
    int/double/decimal inputs."""
    import math

    def build(df):
        return df.group_by("k").agg(
            E.StddevSamp(col("f")).alias("ss"),
            E.StddevPop(col("f")).alias("sp"),
            E.VarianceSamp(col("q")).alias("vs"),
            E.VariancePop(col("m")).alias("vp"),
        ).sort("k")
    dev, cpu = both(build)
    assert len(dev) == len(cpu)
    for a, b in zip(dev, cpu):
        for kcol in ("ss", "sp", "vs", "vp"):
            va, vb = a[kcol], b[kcol]
            if va is None or vb is None:
                assert va == vb, (kcol, a, b)
            elif math.isnan(va) or math.isnan(vb):
                assert math.isnan(va) and math.isnan(vb), (kcol, a, b)
            else:
                assert abs(va - vb) <= 1e-9 * max(1.0, abs(va)), (kcol, a, b)


def test_collect_list_set():
    """collect_list/collect_set run on the CPU engine (array results),
    tagged off-device like the reference pre-GpuCollectList versions."""
    t = pa.table({
        "k": pa.array([1, 1, 2, 1, 2], type=pa.int64()),
        "v": pa.array([3, 1, 5, 3, 5], type=pa.int64()),
    })
    df = from_arrow(t, RapidsConf({}))
    rows = (df.group_by("k")
            .agg(E.CollectList(col("v")).alias("cl"),
                 E.CollectSet(col("v")).alias("cs"))
            .sort("k")).collect()
    assert rows[0]["cl"] == [3, 1, 3] and rows[0]["cs"] == [1, 3]
    assert rows[1]["cl"] == [5, 5] and rows[1]["cs"] == [5]
    stats = (df.group_by("k").agg(E.CollectList(col("v")).alias("cl"))
             .device_plan_stats())
    assert stats["cpu_nodes"], stats


def test_skewness_kurtosis():
    import math

    def build(df):
        return df.group_by("k").agg(
            E.Skewness(col("f")).alias("sk"),
            E.Kurtosis(col("f")).alias("ku")).sort("k")
    dev, cpu = both(build)
    for a, b in zip(dev, cpu):
        for kk in ("sk", "ku"):
            va, vb = a[kk], b[kk]
            if va is None or vb is None:
                assert va == vb
            elif math.isnan(va) or math.isnan(vb):
                assert math.isnan(va) and math.isnan(vb)
            else:
                # raw-power-sum (device) vs centered-sum (CPU): same math,
                # different FP conditioning — tolerance per perf notes
                assert abs(va - vb) <= 1e-6 * max(1.0, abs(va)), (kk, a, b)


def test_greatest_least_mixed_scale():
    # ADVICE r3 (medium): operands must be rescaled to the common decimal
    # type before comparing; greatest(decimal(10,2) 1.50, decimal(10,0) 2)
    # is 2.00, not 1.50.
    t = pa.table({
        "a": pa.array([D("1.50"), D("3.25"), None], type=pa.decimal128(10, 2)),
        "b": pa.array([D("2"), D("3"), D("7")], type=pa.decimal128(10, 0)),
        "i": pa.array([2, 1, None], type=pa.int32()),
    })

    def both_t(build):
        out = []
        for enabled in (True, False):
            conf = RapidsConf({"spark.rapids.tpu.sql.enabled": enabled})
            df = from_arrow(t, conf)
            out.append(build(df).collect())
        return out

    dev, cpu = both_t(lambda df: df.select(
        E.Greatest(col("a"), col("b")).alias("g"),
        E.Least(col("a"), col("b")).alias("l"),
        E.Greatest(col("a"), col("i")).alias("gi"),
    ))
    assert dev == cpu, f"{dev}\n{cpu}"
    assert dev[0]["g"] == D("2.00")
    assert dev[0]["l"] == D("1.50")
    assert dev[1]["g"] == D("3.25")
    assert dev[1]["l"] == D("3.00")
    assert dev[2]["g"] == D("7.00") and dev[2]["l"] == D("7.00")
    assert dev[0]["gi"] == D("2.00")
    assert dev[2]["gi"] is None


def test_greatest_least_wide_decimal128():
    # ADVICE r4 (high): Greatest/Least over decimal128 (>18 digits) operands
    # — and narrow operands widened to a >18-digit result — must run on
    # device (they are in _WIDE_OK), not crash at execute time.
    t = pa.table({
        "w": pa.array([D("123456789012345678901.50"), D("-2.75"), None],
                      type=pa.decimal128(23, 2)),
        "x": pa.array([D("9.99"), D("88888888888888888888.25"), D("4.50")],
                      type=pa.decimal128(23, 2)),
        "n18a": pa.array([D("999999999999999.12"), D("1.00"), None],
                         type=pa.decimal128(17, 2)),
        "n18b": pa.array([D("5.5000"), D("777777777777777.2500"), D("3.2500")],
                         type=pa.decimal128(19, 4)),
    })

    def both_t(build):
        out = []
        for enabled in (True, False):
            conf = RapidsConf({"spark.rapids.tpu.sql.enabled": enabled})
            df = from_arrow(t, conf)
            out.append(build(df).collect())
        return out

    dev, cpu = both_t(lambda df: df.select(
        E.Greatest(col("w"), col("x")).alias("g"),
        E.Least(col("w"), col("x")).alias("l"),
        E.Greatest(col("n18a"), col("n18b")).alias("gn"),
    ))
    assert dev == cpu, f"{dev}\n{cpu}"
    assert dev[0]["g"] == D("123456789012345678901.50")
    assert dev[0]["l"] == D("9.99")
    assert dev[1]["g"] == D("88888888888888888888.25")
    assert dev[1]["l"] == D("-2.75")
    assert dev[2]["g"] == D("4.50") and dev[2]["l"] == D("4.50")
    assert dev[1]["gn"] == D("777777777777777.2500")


def _wide_narrow_table():
    i64 = np.iinfo(np.int64)
    return pa.table({
        # Q1's disc_price type; rows: 28 integer digits, the type's extremes,
        # one unit either side of zero, NULL, a lo limb with its top bit set
        # (2^63 units), zero
        "w": pa.array(
            [D("1234567890123456789012345678.1234"),
             D("-9999999999999999999999999999.9999"), None,
             D("9999999999999999999999999999.9999"), D("0.0001"),
             D("-0.0001"), D("922337203685477.5808"), D("0.0000"),
             D("-31415926535897932384.6264")], type=pa.decimal128(32, 4)),
        "m": pa.array(
            [D("1.07"), D("-99999999999999.99"), D("3.00"), None,
             D("99999999999999.99"), D("-0.01"), D("-1.00"), D("5.55"),
             D("0.00")], type=pa.decimal128(16, 2)),
        "q32": pa.array([7, -2147483648, 1, 2147483647, None, -1, 0, 12, 3],
                        type=pa.int32()),
        "q64": pa.array([i64.min, i64.max, 5, None, i64.min, i64.max, -1,
                         10**12, 0], type=pa.int64()),
        # a second wide column: wide x wide stays on mul_128_exact
        "v": pa.array([D("-3"), D("99999999999999999999"), None, D("2"),
                       D("-99999999999999999999"), D("10000000000"),
                       D("18446744073709551615"), D("1"), D("7")],
                      type=pa.decimal128(20, 0)),
    })


@pytest.mark.parametrize("other", ["m", "q32", "q64", "v"])
@pytest.mark.parametrize("order", ["wide_first", "wide_second"])
def test_wide_times_narrow(order, other):
    """One wide and one narrow operand, in either order, decimal and
    integral: the 128x64 multiply (exec/int128.mul_128x64) through the
    expression path equals the CPU engine, NULLs on either side stay NULL
    and a product of 10^38 or more comes out NULL. ``v`` is the control:
    a second wide operand, the limb multiply, the same demands."""
    pair = (col("w"), col(other))
    prod = Multiply(*(pair if order == "wide_first" else pair[::-1]))
    build = lambda df: df.select(prod.alias("p"), *pair)  # noqa: E731
    stats = build(from_arrow(_wide_narrow_table(),
                             RapidsConf({}))).device_plan_stats()
    assert stats["device_fraction"] == 1.0, stats
    dev = assert_same(build, _wide_narrow_table)
    null_in = sum(r["w"] is None or r[other] is None for r in dev)
    got = [r["p"] for r in dev]
    assert sum(g is None for g in got) > null_in, got  # overflow -> NULL
    assert sum(g is not None for g in got) >= 3, got
    with decimal.localcontext() as ctx:
        ctx.prec = 80
        for g, r in zip(got, dev):
            if g is not None:
                assert g == r["w"] * r[other]

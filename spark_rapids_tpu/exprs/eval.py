"""Expression evaluation: bound expression trees -> fused XLA computations.

The reference dispatches one cudf kernel per expression node (reference:
GpuExpressions.scala columnarEval; arithmetic.scala etc.). TPU-first design:
the whole projection is traced once and jit-compiled, letting XLA fuse every
elementwise op into a handful of kernels — this subsumes the reference's
tiered-projection CSE machinery (basicPhysicalOperators.scala:806).

Spark-exact semantics implemented here (reference spends ~30% of its LoC on
these; SURVEY.md section 7 "hard parts"):
- integral arithmetic wraps (Java two's-complement); ANSI mode is handled at
  plan time (fallback) in round 1
- x/0, x%0  -> null (non-ANSI)
- Java truncated division/remainder (jnp // is floor -> corrected)
- NaN: NaN == NaN is true, NaN is greater than every value (Spark ordering)
- three-valued logic for And/Or
- log(x<=0) -> null, like Spark's Logarithm
- casts follow Spark's Cast.scala (GpuCast.scala:288 on the reference side)
"""

from __future__ import annotations

import functools
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.column import ColVal, DeviceColumn
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exprs import expr as E

# imported at module scope deliberately: cast_strings builds module-level
# jnp constants, and a first import from inside a jitted body (the fused
# path traces _cast_to_string) would capture them as tracers that leak
# into every later use
from spark_rapids_tpu.exprs import cast_strings as CS

from spark_rapids_tpu.exprs.strings import StringVal, row_ids as _string_row_ids

class WideVal(NamedTuple):
    """A DECIMAL128 expression value: (hi, lo) int64 limbs + validity
    (exec/int128.py arithmetic; cudf decimal128 analog)."""

    hi: jax.Array
    lo: jax.Array
    validity: jax.Array


class NestedVal(NamedTuple):
    """A struct/map/array expression value: the DeviceColumn itself (its
    struct-of-columns / offsets+children layout IS the value)."""

    col: "DeviceColumn"

    @property
    def validity(self):
        return self.col.validity


Val = Union[ColVal, StringVal, WideVal, NestedVal]


class EvalContext:
    def __init__(self, batch: ColumnarBatch, ansi: bool = False):
        self.batch = batch
        self.capacity = batch.capacity
        self.num_rows = batch.num_rows
        self.ansi = ansi

    def column(self, i: int) -> Val:
        return _column_to_val(self.batch.columns[i])


def _column_to_val(c: "DeviceColumn") -> Val:
    if c.children is not None or isinstance(c.dtype, T.ArrayType):
        return NestedVal(c)
    if c.is_dict:
        # expressions work on raw bytes: decode dict-encoded columns on
        # read (group-by/sort/gather paths consume codes directly and
        # never come through here)
        from spark_rapids_tpu.exec.kernels import decode_dictionary

        p = decode_dictionary(c)
        return StringVal(p.data, p.offsets, p.validity)
    if c.offsets is not None:
        return StringVal(c.data, c.offsets, c.validity)
    if c.is_wide_decimal:
        return WideVal(c.data2, c.data, c.validity)
    return ColVal(c.data, c.validity)


def _val_to_column(v: Val, dt: T.DataType) -> "DeviceColumn":
    """Expression value -> DeviceColumn (project materialization)."""
    if isinstance(v, NestedVal):
        return v.col
    if isinstance(v, StringVal):
        return DeviceColumn(T.STRING if dt != T.BINARY else T.BINARY,
                            v.data, v.validity, v.offsets)
    if isinstance(v, WideVal):
        return DeviceColumn(dt, v.lo, v.validity, data2=v.hi)
    out_t = dt if dt != T.NULL else T.BOOLEAN
    return DeviceColumn(out_t, v.data.astype(T.numpy_dtype(out_t)),
                        v.validity)


def _all_valid(capacity: int) -> jax.Array:
    return jnp.ones((capacity,), dtype=jnp.bool_)


def _is_wide(dt: T.DataType) -> bool:
    return (isinstance(dt, T.DecimalType)
            and dt.precision > T.DecimalType.MAX_LONG_DIGITS)


def _as_wide(v: Val, dt: T.DataType, to_scale: int) -> "WideVal":
    """Promote a decimal/integral value to (hi, lo) limbs at ``to_scale``."""
    if isinstance(v, WideVal):
        h, l = v.hi, v.lo
    else:
        from spark_rapids_tpu.exec import int128 as I128
        h, l = I128.from_i64(v.data)
    s = dt.scale if isinstance(dt, T.DataType) and isinstance(
        dt, T.DecimalType) else 0
    if to_scale > s:
        from spark_rapids_tpu.exec import int128 as I128
        h, l = I128.rescale10(h, l, to_scale - s)
    return WideVal(h, l, v.validity)


def _as_wide_checked(v: Val, dt: T.DataType, to_scale: int,
                     precision: int):
    """_as_wide with overflow detection on the rescale (a wrapped rescale
    would dodge the result-level overflow mask)."""
    from spark_rapids_tpu.exec import int128 as I128

    if isinstance(v, WideVal):
        h, l = v.hi, v.lo
    else:
        h, l = I128.from_i64(v.data)
    s = dt.scale if isinstance(dt, T.DecimalType) else 0
    if to_scale > s:
        h, l, ovf = I128.rescale10_checked(h, l, to_scale - s, precision)
    else:
        ovf = jnp.zeros_like(h, dtype=jnp.bool_)
    return WideVal(h, l, v.validity), ovf


def _broadcast_literal(value, dtype: T.DataType, capacity: int) -> Val:
    if dtype == T.STRING:
        if value is None:
            return StringVal(
                jnp.zeros((8,), jnp.uint8),
                jnp.zeros((capacity + 1,), jnp.int32),
                jnp.zeros((capacity,), jnp.bool_),
            )
        raw = np.frombuffer(str(value).encode("utf-8"), dtype=np.uint8)
        n = len(raw)
        data = jnp.asarray(np.tile(raw, capacity) if n else np.zeros(0, np.uint8))
        offsets = jnp.arange(capacity + 1, dtype=jnp.int32) * n
        return StringVal(data, offsets, _all_valid(capacity))
    if _is_wide(dtype):
        from spark_rapids_tpu.exec import int128 as I128

        if value is None:
            z = jnp.zeros((capacity,), jnp.int64)
            return WideVal(z, z, jnp.zeros((capacity,), jnp.bool_))
        import decimal
        with decimal.localcontext() as _c:
            _c.prec = 50
            v = int(decimal.Decimal(value).scaleb(dtype.scale))
        hi_np, lo_np = I128.from_py_ints([v])
        return WideVal(jnp.full((capacity,), int(hi_np[0]), jnp.int64),
                       jnp.full((capacity,), int(lo_np[0]), jnp.int64),
                       _all_valid(capacity))
    np_dtype = T.numpy_dtype(dtype if dtype != T.NULL else T.BOOLEAN)
    if value is None:
        return ColVal(
            jnp.zeros((capacity,), np_dtype), jnp.zeros((capacity,), jnp.bool_)
        )
    if isinstance(dtype, T.DecimalType):
        import decimal

        value = int(decimal.Decimal(value).scaleb(dtype.scale))
    elif dtype == T.DATE:
        import datetime

        if isinstance(value, datetime.date):
            value = (value - datetime.date(1970, 1, 1)).days
    elif dtype == T.TIMESTAMP:
        import datetime

        if isinstance(value, datetime.datetime):
            # naive datetimes are session-timezone (UTC in round 1); integer
            # delta from epoch, never float-seconds round trips
            if value.tzinfo is None:
                value = value.replace(tzinfo=datetime.timezone.utc)
            epoch = datetime.datetime(1970, 1, 1, tzinfo=datetime.timezone.utc)
            value = (value - epoch) // datetime.timedelta(microseconds=1)
    return ColVal(
        jnp.full((capacity,), value, np_dtype), _all_valid(capacity)
    )


# ---------------------------------------------------------------------------
# Java/Spark arithmetic helpers
# ---------------------------------------------------------------------------


def _trunc_div(a, b):
    """Java integer division: truncates toward zero; caller guards b==0."""
    safe_b = jnp.where(b == 0, jnp.ones_like(b), b)
    q = a // safe_b
    r = a - q * safe_b
    fix = (r != 0) & ((a < 0) != (b < 0))
    return jnp.where(fix, q + 1, q)


def _java_rem(a, b):
    safe_b = jnp.where(b == 0, jnp.ones_like(b), b)
    if jnp.issubdtype(a.dtype, jnp.floating):
        return jnp.fmod(a, safe_b)
    return a - _trunc_div(a, safe_b) * safe_b


def _nan_safe_eq(a, b):
    if jnp.issubdtype(a.dtype, jnp.floating):
        return (a == b) | (jnp.isnan(a) & jnp.isnan(b))
    return a == b


def _nan_aware_lt(a, b):
    """Spark ordering: NaN greater than everything."""
    if jnp.issubdtype(a.dtype, jnp.floating):
        return jnp.where(
            jnp.isnan(a), jnp.zeros_like(a, jnp.bool_),
            jnp.where(jnp.isnan(b), ~jnp.isnan(a), a < b),
        )
    return a < b


def _string_select_n(takes, vals) -> "StringVal":
    """Per-row k-way select between string columns.

    ``takes[i]`` is the per-row mask for choosing ``vals[i]``; the first True
    wins, ``vals[-1]`` is the default (its take mask is ignored). Output byte
    capacity is the sum over inputs — linear in k, computed once for the whole
    CASE/COALESCE rather than per fold level.
    """
    assert len(takes) == len(vals) and len(vals) >= 2
    k = len(vals)
    # choice[r] = index of the winning source for row r
    choice = jnp.full(vals[0].validity.shape, k - 1, jnp.int32)
    taken = jnp.zeros_like(takes[0])
    for i in range(k - 1):
        win = takes[i] & ~taken
        choice = jnp.where(win, i, choice)
        taken = taken | takes[i]
    lens = jnp.stack([v.offsets[1:] - v.offsets[:-1] for v in vals])  # (k, cap)
    valids = jnp.stack([v.validity for v in vals])
    out_len = jnp.take_along_axis(lens, choice[None, :], axis=0)[0]
    valid = jnp.take_along_axis(valids, choice[None, :], axis=0)[0]
    new_off = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(out_len).astype(jnp.int32)]
    )
    nbytes_out = sum(v.data.shape[0] for v in vals)
    rows = _string_row_ids(new_off, nbytes_out)
    rel = jnp.arange(nbytes_out, dtype=jnp.int32) - new_off[rows]
    row_choice = choice[rows]
    out = jnp.zeros((nbytes_out,), jnp.uint8)
    for i, v in enumerate(vals):
        src = jnp.clip(v.offsets[rows] + rel, 0, v.data.shape[0] - 1)
        out = jnp.where(row_choice == i, v.data[src], out)
    return StringVal(out, new_off, valid)


def _string_select(take: jax.Array, t: "StringVal", f: "StringVal") -> "StringVal":
    return _string_select_n([take, jnp.ones_like(take)], [t, f])


def _string_eq(a: StringVal, b: StringVal, capacity: int) -> jax.Array:
    """Byte-exact string equality (vectorized over the byte buffers)."""
    len_a = a.offsets[1:] - a.offsets[:-1]
    len_b = b.offsets[1:] - b.offsets[:-1]
    # compare byte-by-byte up to the shorter buffer via gather per row
    max_len = a.data.shape[0]  # static bound
    j = jnp.arange(max_len, dtype=jnp.int32)
    rows = _string_row_ids(a.offsets, max_len)
    rel = j - a.offsets[rows]
    b_idx = jnp.clip(b.offsets[rows] + rel, 0, b.data.shape[0] - 1)
    within = rel < len_b[rows]
    byte_neq = (a.data != b.data[b_idx]) | ~within
    neq_any = jax.ops.segment_max(
        byte_neq.astype(jnp.int32), rows, num_segments=capacity,
        indices_are_sorted=True,
    )
    # empty segments yield the identity (INT32_MIN), which means "no mismatch"
    return (len_a == len_b) & (neq_any <= 0)


# ---------------------------------------------------------------------------
# Date kernels (civil calendar; Howard Hinnant's algorithms, int32)
# ---------------------------------------------------------------------------


def _civil_from_days(days):
    z = days.astype(jnp.int32) + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y.astype(jnp.int32), m.astype(jnp.int32), d.astype(jnp.int32)


def _day_of_week(days):
    """Spark dayofweek: 1 = Sunday ... 7 = Saturday. 1970-01-01 was a Thursday."""
    return ((days.astype(jnp.int32) + 4) % 7 + 7) % 7 + 1


def _day_of_year(days):
    y, _, _ = _civil_from_days(days)
    jan1 = _days_from_civil(y, jnp.ones_like(y), jnp.ones_like(y))
    return (days.astype(jnp.int32) - jan1 + 1).astype(jnp.int32)


def _days_from_civil(y, m, d):
    y = y - (m <= 2)
    era = jnp.where(y >= 0, y, y - 399) // 400
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return (era * 146097 + doe - 719468).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Cast (Spark Cast.scala semantics; reference GpuCast.scala:288)
# ---------------------------------------------------------------------------


def cast_val(cv: Val, src: T.DataType, dst: T.DataType, ansi: bool,
             capacity: int) -> Val:
    if src == dst:
        return cv
    if dst in (T.STRING, T.BINARY) and not isinstance(cv, StringVal):
        return _cast_to_string(cv, src)
    if isinstance(cv, StringVal):
        return _cast_from_string(cv, dst, capacity)
    if isinstance(cv, WideVal) or _is_wide(dst):
        return _cast_wide(cv, src, dst)
    assert isinstance(cv, ColVal), f"device cast from {src} not supported"
    data, valid = cv
    if dst == T.BOOLEAN:
        return ColVal(data != 0, valid)
    if src == T.BOOLEAN:
        return ColVal(data.astype(T.numpy_dtype(dst)), valid)
    if dst == T.TIMESTAMP and src == T.DATE:
        return ColVal(data.astype(jnp.int64) * 86_400_000_000, valid)
    if dst == T.DATE and src == T.TIMESTAMP:
        return ColVal((data // 86_400_000_000).astype(jnp.int32), valid)
    if dst == T.TIMESTAMP and src in T.INTEGRAL_TYPES:
        return ColVal(data.astype(jnp.int64) * 1_000_000, valid)
    if src == T.TIMESTAMP and dst == T.LONG:
        return ColVal(jnp.floor_divide(data, 1_000_000), valid)
    if isinstance(dst, T.DecimalType):
        return _cast_to_decimal(data, valid, src, dst, ansi)
    if isinstance(src, T.DecimalType):
        if dst in (T.FLOAT, T.DOUBLE):
            return ColVal(
                (data.astype(jnp.float64) / (10.0 ** src.scale)).astype(
                    T.numpy_dtype(dst)
                ),
                valid,
            )
        if dst in T.INTEGRAL_TYPES:
            whole = _trunc_div(data, jnp.int64(10 ** src.scale))
            return _float_or_int_to_int(whole, valid, dst)
        raise NotImplementedError(f"cast {src} -> {dst}")
    if dst in T.INTEGRAL_TYPES:
        return _float_or_int_to_int(data, valid, dst)
    if dst in (T.FLOAT, T.DOUBLE):
        return ColVal(data.astype(T.numpy_dtype(dst)), valid)
    raise NotImplementedError(f"cast {src} -> {dst}")


def _cast_to_string(cv: Val, src: T.DataType) -> StringVal:
    """value -> string on device (reference GpuCast.scala:1713 + jni
    CastStrings; float->string stays on CPU — gated in check_expr)."""
    if isinstance(cv, WideVal):
        assert isinstance(src, T.DecimalType)
        return CS.decimal_to_string(cv.lo, cv.hi, src.scale, cv.validity)
    data, valid = cv.data, cv.validity
    if isinstance(src, T.DecimalType):
        return CS.decimal_to_string(data, None, src.scale, valid)
    if src == T.BOOLEAN:
        return CS.bool_to_string(data, valid)
    if src in T.INTEGRAL_TYPES:
        return CS.long_to_string(data, valid)
    if src == T.DATE:
        return CS.date_to_string(data, valid)
    if src == T.TIMESTAMP:
        return CS.timestamp_to_string(data, valid)
    raise NotImplementedError(f"cast {src} -> string not on device")


def _cast_from_string(cv: "StringVal", dst: T.DataType, capacity: int) -> Val:
    """string -> value on device (reference GpuCast.scala:288 + jni
    CastStrings; string->decimal and ANSI-mode stay on CPU)."""
    if dst in (T.STRING, T.BINARY):
        return cv
    if dst in T.INTEGRAL_TYPES:
        return CS.string_to_integral(cv, capacity, dst)
    if dst == T.BOOLEAN:
        return CS.string_to_bool(cv, capacity)
    if dst == T.DATE:
        return CS.string_to_date(cv, capacity)
    if dst == T.TIMESTAMP:
        return CS.string_to_timestamp(cv, capacity)
    if dst in (T.FLOAT, T.DOUBLE):
        return CS.string_to_float(cv, capacity, dst)
    raise NotImplementedError(f"cast string -> {dst} not on device")


def _float_or_int_to_int(data, valid, dst: T.DataType) -> ColVal:
    np_dtype = T.numpy_dtype(dst)
    if jnp.issubdtype(data.dtype, jnp.floating):
        # Java (long)/(int) cast: NaN -> 0, saturate at min/max, truncate.
        # float(info.max) rounds UP to 2^63 for int64, so saturation must be
        # done with explicit comparisons against exact powers of two, not clip.
        info = jnp.iinfo(np_dtype)
        hi = float(2 ** (info.bits - 1))  # exactly representable
        trunc = jnp.trunc(data).astype(np_dtype)
        out = jnp.where(
            jnp.isnan(data),
            0,
            jnp.where(
                data >= hi, info.max, jnp.where(data < -hi, info.min, trunc)
            ),
        ).astype(np_dtype)
        return ColVal(out, valid)
    return ColVal(data.astype(np_dtype), valid)  # wraps like Java


def _wide_div_pow10_half_up(h, l, k: int):
    """(hi, lo) / 10^k with a single ROUND_HALF_UP at the full divisor.

    Chained small divides keep the exact remainder (sum of step remainders
    at their place values fits int64 for k <= 18), so rounding applies once.
    """
    from spark_rapids_tpu.exec import int128 as I128

    assert 0 < k <= 18, "scale reduction beyond 18 digits not on device"
    ah, al = I128.abs_(h, l)
    neg = I128.is_neg(h, l)
    rem = jnp.zeros_like(h)
    place = 1
    kk = k
    while kk > 0:
        step = min(kk, 9)
        d = jnp.full_like(h, 10 ** step)
        ah, al, rr = I128._udivmod_small(ah, al, d)
        rem = rem + rr * jnp.int64(place)
        place *= 10 ** step
        kk -= step
    div = jnp.int64(10 ** k)
    up = (2 * rem >= div).astype(jnp.int64)
    qh, ql = I128.add(ah, al, jnp.zeros_like(up), up)
    nh, nl = I128.neg(qh, ql)
    return jnp.where(neg, nh, qh), jnp.where(neg, nl, ql)


def _cast_wide(cv: Val, src: T.DataType, dst: T.DataType) -> Val:
    """Casts involving DECIMAL128 (reference GpuCast decimal paths via
    jni DecimalUtils; here: exact (hi, lo) limb arithmetic)."""
    from spark_rapids_tpu.exec import int128 as I128

    if _is_wide(dst):
        assert isinstance(dst, T.DecimalType)
        pre_ovf = None
        if isinstance(cv, WideVal):
            assert isinstance(src, T.DecimalType)
            diff = dst.scale - src.scale
            h, l, valid = cv.hi, cv.lo, cv.validity
            if diff >= 0:
                h, l, pre_ovf = I128.rescale10_checked(h, l, diff,
                                                       dst.precision)
            else:
                h, l = _wide_div_pow10_half_up(h, l, -diff)
        elif src in T.INTEGRAL_TYPES or isinstance(src, T.DecimalType):
            s = src.scale if isinstance(src, T.DecimalType) else 0
            diff = dst.scale - s
            if diff >= 0:
                h, l = I128.from_i64(cv.data)
                h, l, pre_ovf = I128.rescale10_checked(h, l, diff,
                                                       dst.precision)
            else:
                # reduce scale in int64 first (value shrinks), then widen
                nv = _cast_to_decimal(cv.data, cv.validity, src,
                                      T.DecimalType(18, dst.scale), False)
                h, l = I128.from_i64(nv.data)
                return WideVal(h, l, nv.validity)
            valid = cv.validity
        elif src in (T.FLOAT, T.DOUBLE):
            # double -> decimal128: scale in f64, split at 2^64 (f64 has 53
            # significant bits — approximation inherent to the source type)
            x = cv.data.astype(jnp.float64) * (10.0 ** dst.scale)
            bad = jnp.isnan(x) | jnp.isinf(x) | (jnp.abs(x) >= 2.0 ** 127)
            xs = jnp.where(bad, 0.0, x)
            sign = jnp.sign(xs)
            ax = jnp.abs(xs)
            ax = jnp.floor(ax + 0.5)  # HALF_UP at target scale
            hi_f = jnp.floor(ax / (2.0 ** 64))
            lo_f = ax - hi_f * (2.0 ** 64)
            lo_u = lo_f.astype(jnp.uint64).astype(jnp.int64)
            hpos = hi_f.astype(jnp.int64)
            nh, nl = I128.neg(hpos, lo_u)
            h = jnp.where(sign < 0, nh, hpos)
            l = jnp.where(sign < 0, nl, lo_u)
            valid = cv.validity & ~bad
        else:
            raise NotImplementedError(f"cast {src} -> {dst}")
        ovf = I128.overflow_mask(h, l, dst.precision)
        if pre_ovf is not None:
            ovf = ovf | pre_ovf
        z = jnp.zeros_like(h)
        return WideVal(jnp.where(ovf, z, h), jnp.where(ovf, z, l),
                       valid & ~ovf)

    # source is wide
    assert isinstance(cv, WideVal) and isinstance(src, T.DecimalType)
    if dst in (T.FLOAT, T.DOUBLE):
        return ColVal((_wide_to_f64(cv) / (10.0 ** src.scale)).astype(
            T.numpy_dtype(dst)), cv.validity)
    if isinstance(dst, T.DecimalType) or dst in T.INTEGRAL_TYPES:
        s_dst = dst.scale if isinstance(dst, T.DecimalType) else 0
        diff = s_dst - src.scale
        h, l = cv.hi, cv.lo
        fits_extra = None
        if diff > 0:
            h, l, fits_extra = I128.rescale10_checked(h, l, diff, 38)
        elif diff < 0:
            if isinstance(dst, T.DecimalType):
                h, l = _wide_div_pow10_half_up(h, l, -diff)
            else:
                # integral cast truncates toward zero
                ah, al = I128.abs_(h, l)
                kk = -diff
                while kk > 0:
                    step = min(kk, 9)
                    d = jnp.full_like(h, 10 ** step)
                    ah, al, _ = I128._udivmod_small(ah, al, d)
                    kk -= step
                nh, nl = I128.neg(ah, al)
                m = I128.is_neg(h, l)
                h = jnp.where(m, nh, ah)
                l = jnp.where(m, nl, al)
        # narrow: value must fit the destination representation
        fits = h == jnp.where(l < 0, jnp.int64(-1), jnp.int64(0))
        valid = cv.validity & fits
        if fits_extra is not None:
            valid = valid & ~fits_extra
        if isinstance(dst, T.DecimalType):
            bound = jnp.int64(10 ** min(dst.precision, 18))
            ovf = jnp.abs(l) >= bound
            return ColVal(jnp.where(valid & ~ovf, l, 0), valid & ~ovf)
        return _float_or_int_to_int(jnp.where(valid, l, 0), valid, dst)
    raise NotImplementedError(f"cast {src} -> {dst}")


def _cast_to_decimal(data, valid, src: T.DataType, dst: T.DecimalType, ansi):
    bound = jnp.int64(10 ** min(dst.precision, 18))
    if isinstance(src, T.DecimalType):
        diff = dst.scale - src.scale
        if diff >= 0:
            scaled = data.astype(jnp.int64) * jnp.int64(10**diff)
        else:
            # reduce scale: round HALF_UP (Spark Decimal.changePrecision)
            div = jnp.int64(10 ** (-diff))
            q = _trunc_div(data.astype(jnp.int64), div)
            r = data.astype(jnp.int64) - q * div
            scaled = q + jnp.where(2 * jnp.abs(r) >= div, jnp.sign(r), 0)
    elif src in T.INTEGRAL_TYPES:
        scaled = data.astype(jnp.int64) * jnp.int64(10**dst.scale)
    else:
        # float -> decimal: round HALF_UP (away from zero) at target scale,
        # Spark Decimal(double).changePrecision — not banker's rounding
        shifted = data.astype(jnp.float64) * (10.0**dst.scale)
        half_up = jnp.sign(shifted) * jnp.floor(jnp.abs(shifted) + 0.5)
        scaled = jnp.where(
            jnp.isnan(shifted) | jnp.isinf(shifted),
            jnp.int64(0),
            half_up.astype(jnp.int64),
        )
        overflow_f = jnp.isnan(shifted) | (jnp.abs(shifted) >= 2.0**63)
        valid = valid & ~overflow_f
    overflow = jnp.abs(scaled) >= bound
    return ColVal(jnp.where(overflow, 0, scaled), valid & ~overflow)


# ---------------------------------------------------------------------------
# The evaluator
# ---------------------------------------------------------------------------


def eval_expr(expr: E.Expression, ctx: EvalContext) -> Val:
    cap = ctx.capacity

    if isinstance(expr, E.Alias):
        return eval_expr(expr.child, ctx)
    if isinstance(expr, E.ColumnRef):
        return ctx.column(expr.index)
    if isinstance(expr, E.Literal):
        return _broadcast_literal(expr.value, expr.dtype, cap)
    if isinstance(expr, E.Cast):
        child = eval_expr(expr.child, ctx)
        return cast_val(child, expr.child.dtype, expr.to, ctx.ansi or expr.ansi, cap)

    if hasattr(expr, "eval_columnar"):
        # columnar UDF protocol (RapidsUDF.evaluateColumnar analog): the
        # user kernel traces into this same XLA computation
        vals = [eval_expr(c, ctx) for c in expr.children]
        data, validity = expr.eval_columnar(vals)
        return ColVal(data, validity)

    if isinstance(expr, E.BinaryArithmetic):
        return _eval_arith(expr, ctx)
    if isinstance(expr, E.BinaryComparison):
        return _eval_compare(expr, ctx)

    if isinstance(expr, E.And):
        l = eval_expr(expr.left, ctx)
        r = eval_expr(expr.right, ctx)
        data = l.data & r.data
        # 3VL: valid if (both valid) or (either side is a valid False)
        valid = (l.validity & r.validity) | (l.validity & ~l.data) | (
            r.validity & ~r.data
        )
        return ColVal(data & l.validity & r.validity, valid)
    if isinstance(expr, E.Or):
        l = eval_expr(expr.left, ctx)
        r = eval_expr(expr.right, ctx)
        data = (l.data & l.validity) | (r.data & r.validity)
        valid = (l.validity & r.validity) | (l.validity & l.data) | (
            r.validity & r.data
        )
        return ColVal(data, valid)
    if isinstance(expr, E.Not):
        c = eval_expr(expr.child, ctx)
        return ColVal(~c.data, c.validity)

    if isinstance(expr, E.IsNull):
        c = eval_expr(expr.child, ctx)
        return ColVal(~c.validity, _all_valid(cap))
    if isinstance(expr, E.IsNotNull):
        c = eval_expr(expr.child, ctx)
        return ColVal(c.validity, _all_valid(cap))
    if isinstance(expr, E.IsNaN):
        c = eval_expr(expr.child, ctx)
        return ColVal(jnp.isnan(c.data) & c.validity, _all_valid(cap))

    if isinstance(expr, E.Coalesce):
        vals = [eval_expr(c, ctx) for c in expr.children]
        if isinstance(vals[0], StringVal):
            return _string_select_n([v.validity for v in vals], vals)
        if isinstance(vals[0], WideVal):
            hi, lo = vals[-1].hi, vals[-1].lo
            valid = vals[-1].validity
            for v in reversed(vals[:-1]):
                hi = jnp.where(v.validity, v.hi, hi)
                lo = jnp.where(v.validity, v.lo, lo)
                valid = v.validity | valid
            return WideVal(hi, lo, valid)
        data = vals[-1].data
        valid = vals[-1].validity
        for v in reversed(vals[:-1]):
            data = jnp.where(v.validity, v.data, data)
            valid = v.validity | valid
        return ColVal(data, valid)

    if isinstance(expr, E.If):
        p = eval_expr(expr.children[0], ctx)
        t = eval_expr(expr.children[1], ctx)
        f = eval_expr(expr.children[2], ctx)
        take_t = p.data & p.validity
        if isinstance(t, StringVal):
            assert isinstance(f, StringVal)
            return _string_select(take_t, t, f)
        if isinstance(t, WideVal) or isinstance(f, WideVal):
            assert isinstance(t, WideVal) and isinstance(f, WideVal)
            return WideVal(
                jnp.where(take_t, t.hi, f.hi),
                jnp.where(take_t, t.lo, f.lo),
                jnp.where(take_t, t.validity, f.validity),
            )
        return ColVal(
            jnp.where(take_t, t.data, f.data),
            jnp.where(take_t, t.validity, f.validity),
        )

    if isinstance(expr, E.CaseWhen):
        else_v = (
            eval_expr(expr.else_value, ctx)
            if expr.else_value is not None
            else _broadcast_literal(None, expr.dtype, cap)
        )
        if expr.dtype == T.STRING:
            takes, vals = [], []
            for p_ex, v_ex in expr.branches:
                p = eval_expr(p_ex, ctx)
                takes.append(p.data & p.validity)
                vals.append(eval_expr(v_ex, ctx))
            takes.append(jnp.ones_like(takes[0]))
            vals.append(else_v)
            return _string_select_n(takes, vals)
        if _is_wide(expr.dtype):
            hi, lo, valid = else_v.hi, else_v.lo, else_v.validity
            for p_ex, v_ex in reversed(expr.branches):
                p = eval_expr(p_ex, ctx)
                v = eval_expr(v_ex, ctx)
                take = p.data & p.validity
                hi = jnp.where(take, v.hi, hi)
                lo = jnp.where(take, v.lo, lo)
                valid = jnp.where(take, v.validity, valid)
            return WideVal(hi, lo, valid)
        data, valid = else_v.data, else_v.validity
        for p_ex, v_ex in reversed(expr.branches):
            p = eval_expr(p_ex, ctx)
            v = eval_expr(v_ex, ctx)
            take = p.data & p.validity
            data = jnp.where(take, v.data, data)
            valid = jnp.where(take, v.validity, valid)
        return ColVal(data, valid)

    if isinstance(expr, E.In):
        v = eval_expr(expr.value, ctx)
        hit = jnp.zeros((cap,), jnp.bool_)
        any_null = jnp.zeros((cap,), jnp.bool_)
        for item in expr.items:
            iv = eval_expr(item, ctx)
            if isinstance(v, StringVal):
                assert isinstance(iv, StringVal)
                eq = _string_eq(v, iv, cap)
            else:
                eq = _nan_safe_eq(v.data, iv.data)
            hit = hit | (eq & iv.validity)
            any_null = any_null | ~iv.validity
        # Spark: no match + some null item -> NULL; match -> TRUE; else FALSE
        valid = v.validity & (hit | ~any_null)
        return ColVal(hit, valid)

    if isinstance(expr, E.UnaryMinus):
        c = eval_expr(expr.child, ctx)
        if isinstance(c, WideVal):
            from spark_rapids_tpu.exec import int128 as I128
            h, l = I128.neg(c.hi, c.lo)
            return WideVal(h, l, c.validity)
        return ColVal(-c.data, c.validity)
    if isinstance(expr, E.Abs):
        c = eval_expr(expr.child, ctx)
        if isinstance(c, WideVal):
            from spark_rapids_tpu.exec import int128 as I128
            h, l = I128.abs_(c.hi, c.lo)
            return WideVal(h, l, c.validity)
        return ColVal(jnp.abs(c.data), c.validity)

    if isinstance(expr, E.Sqrt):
        c = eval_expr(expr.child, ctx)
        d = c.data.astype(jnp.float64)
        return ColVal(jnp.sqrt(d), c.validity)
    if isinstance(expr, E.Exp):
        c = eval_expr(expr.child, ctx)
        return ColVal(jnp.exp(c.data.astype(jnp.float64)), c.validity)
    if isinstance(expr, E.Log):
        c = eval_expr(expr.child, ctx)
        d = c.data.astype(jnp.float64)
        ok = d > 0
        return ColVal(jnp.log(jnp.where(ok, d, 1.0)), c.validity & ok)
    if isinstance(expr, E.Pow):
        l = eval_expr(expr.left, ctx)
        r = eval_expr(expr.right, ctx)
        return ColVal(
            jnp.power(l.data.astype(jnp.float64), r.data.astype(jnp.float64)),
            l.validity & r.validity,
        )
    if isinstance(expr, (E.Log10, E.Log2)):
        c = eval_expr(expr.child, ctx)
        d = c.data.astype(jnp.float64)
        ok = d > 0
        f = jnp.log10 if isinstance(expr, E.Log10) else jnp.log2
        return ColVal(f(jnp.where(ok, d, 1.0)), c.validity & ok)
    if isinstance(expr, E.Log1p):
        c = eval_expr(expr.child, ctx)
        d = c.data.astype(jnp.float64)
        ok = d > -1.0
        return ColVal(jnp.log1p(jnp.where(ok, d, 0.0)), c.validity & ok)
    if isinstance(expr, E.Expm1):
        c = eval_expr(expr.child, ctx)
        return ColVal(jnp.expm1(c.data.astype(jnp.float64)), c.validity)
    if isinstance(expr, E.Cbrt):
        c = eval_expr(expr.child, ctx)
        return ColVal(jnp.cbrt(c.data.astype(jnp.float64)), c.validity)
    if type(expr) in _TRIG:
        c = eval_expr(expr.child, ctx)
        return ColVal(_TRIG[type(expr)](c.data.astype(jnp.float64)),
                      c.validity)
    if isinstance(expr, E.Signum):
        c = eval_expr(expr.child, ctx)
        return ColVal(jnp.sign(c.data.astype(jnp.float64)), c.validity)
    if isinstance(expr, E.Atan2):
        l = eval_expr(expr.left, ctx)
        r = eval_expr(expr.right, ctx)
        return ColVal(jnp.arctan2(l.data.astype(jnp.float64),
                                  r.data.astype(jnp.float64)),
                      l.validity & r.validity)
    if isinstance(expr, E.Hypot):
        l = eval_expr(expr.left, ctx)
        r = eval_expr(expr.right, ctx)
        return ColVal(jnp.hypot(l.data.astype(jnp.float64),
                                r.data.astype(jnp.float64)),
                      l.validity & r.validity)
    if isinstance(expr, E.Positive):
        return eval_expr(expr.child, ctx)
    if isinstance(expr, E.BitCount):
        c = eval_expr(expr.child, ctx)
        d = c.data
        if d.dtype == jnp.bool_:
            pc = d.astype(jnp.int32)
        else:
            # popcount the two u32 words: the real-TPU backend cannot
            # lower 64-bit bitcasts (see kernels._u64_from_words)
            w = jax.lax.bitcast_convert_type(d.astype(jnp.int64), jnp.uint32)
            pc = (jax.lax.population_count(w[..., 0])
                  + jax.lax.population_count(w[..., 1])).astype(jnp.int32)
        return ColVal(pc, c.validity)
    if isinstance(expr, E.BitGet):
        l = eval_expr(expr.left, ctx)
        r = eval_expr(expr.right, ctx)
        bits = 8 * T.numpy_dtype(expr.left.dtype).itemsize
        pos = r.data.astype(jnp.int32)
        ok = (pos >= 0) & (pos < bits)
        d = (l.data.astype(jnp.int64)
             >> jnp.clip(pos, 0, 63).astype(jnp.int64)) & 1
        return ColVal(d.astype(jnp.int8), l.validity & r.validity & ok)
    if isinstance(expr, E.Factorial):
        c = eval_expr(expr.child, ctx)
        import math as _math
        tbl = jnp.asarray([_math.factorial(i) for i in range(21)],
                          jnp.int64)
        n = c.data.astype(jnp.int32)
        ok = (n >= 0) & (n <= 20)
        return ColVal(tbl[jnp.clip(n, 0, 20)], c.validity & ok)
    if isinstance(expr, (E.Murmur3Hash, E.XxHash64)):
        from spark_rapids_tpu.exec import kernels as K
        variant = 1 if isinstance(expr, E.XxHash64) else 0
        salt = jnp.uint64(K._INT_SALT[variant])
        h = jnp.zeros(cap, jnp.uint64)
        for ch in expr.children:
            v = eval_expr(ch, ctx)
            if isinstance(v, StringVal):
                col = DeviceColumn(T.STRING, v.data, v.validity, v.offsets)
                chh = K._string_hash(col, variant)
            elif ch.dtype in T.FRACTIONAL_TYPES:
                chh = K._splitmix64(K._float_hash_key(v.data) ^ salt)
            else:
                chh = K._splitmix64(K._int_sortable(v.data) ^ salt)
            chh = jnp.where(v.validity, chh,
                            jnp.uint64(0xDEADBEEFCAFEBABE))
            h = K._splitmix64(h * jnp.uint64(K._COMBINE_MULT[variant]) + chh)
        return ColVal(h.astype(jnp.int64), _all_valid(cap))
    if isinstance(expr, E.Rand):
        # deterministic per-row stream: splitmix of (seed, row index) — the
        # engine contract (Spark rand is per-partition-seeded; both engines
        # here agree exactly)
        from spark_rapids_tpu.exec import kernels as K
        idx = jnp.arange(cap, dtype=jnp.uint64)
        h = K._splitmix64(idx + jnp.uint64(expr.seed) * jnp.uint64(
            0x9E3779B97F4A7C15))
        u = (h >> jnp.uint64(11)).astype(jnp.float64) / float(1 << 53)
        return ColVal(u, _all_valid(cap))
    if isinstance(expr, E.BRound):
        c = eval_expr(expr.child, ctx)
        ct = expr.child.dtype
        if isinstance(ct, T.DecimalType):
            raise NotImplementedError("decimal bround on device")
        if ct in T.FRACTIONAL_TYPES:
            s = 10.0 ** expr.scale
            d = c.data.astype(jnp.float64)
            # HALF_EVEN at the scale: numpy/jnp rint is half-even
            return ColVal(jnp.rint(d * s) / s, c.validity)
        if expr.scale >= 0:
            return ColVal(c.data, c.validity)
        s = 10 ** (-expr.scale)
        d = c.data.astype(jnp.int64)
        # round to the nearest multiple of s, HALF_EVEN: floor-divide keeps
        # rem in [0, s) so the tie decision is a single parity check
        q = jnp.floor_divide(d, s)
        rem = d - q * s
        tie = 2 * rem == s
        take_hi = (2 * rem > s) | (tie & (q % 2 != 0))
        out = ((q + take_hi.astype(jnp.int64)) * s).astype(
            T.numpy_dtype(expr.dtype))
        return ColVal(out, c.validity)
    if isinstance(expr, E.GetJsonObject):
        from spark_rapids_tpu.exprs import json_device as JD

        s = eval_expr(expr.child, ctx)
        assert isinstance(s, StringVal)
        return JD.get_json_object(s, expr.path, cap)

    if isinstance(expr, E.GetStructField):
        v = eval_expr(expr.child, ctx)
        st = expr.child.dtype
        c = v.col.children[st.field_index(expr.field)]
        validity = c.validity & v.col.validity
        return _column_to_val(DeviceColumn(
            c.dtype, c.data, validity, c.offsets, c.dictionary, c.dict_size,
            c.dict_max_len, c.data2, c.children))
    if isinstance(expr, E.CreateNamedStruct):
        kids = tuple(_val_to_column(eval_expr(c, ctx), c.dtype)
                     for c in expr.children)
        return NestedVal(DeviceColumn(
            expr.dtype, jnp.zeros(0, jnp.int32), _all_valid(cap),
            children=kids))
    if isinstance(expr, E.MapKeys):
        v = eval_expr(expr.child, ctx)
        keys = v.col.children[0]
        return NestedVal(DeviceColumn(expr.dtype, keys.data, v.col.validity,
                                      v.col.offsets))
    if isinstance(expr, E.Size):
        v = eval_expr(expr.child, ctx)
        lens = (v.col.offsets[1:] - v.col.offsets[:-1]).astype(jnp.int32)
        if expr.legacy_null:
            return ColVal(jnp.where(v.col.validity, lens, jnp.int32(-1)),
                          _all_valid(cap))
        return ColVal(jnp.where(v.col.validity, lens, 0), v.col.validity)
    if isinstance(expr, E.ElementAt) and isinstance(expr.left.dtype,
                                                    T.MapType):
        v = eval_expr(expr.left, ctx)
        probe = eval_expr(expr.right, ctx)
        mcol = v.col
        keys, vals = mcol.children
        ecap = keys.capacity
        rows = jnp.clip(_string_row_ids(mcol.offsets, ecap), 0, cap - 1)
        in_range = jnp.arange(ecap, dtype=jnp.int32) < mcol.offsets[-1]
        eq = (in_range & keys.validity
              & (keys.data == probe.data[rows]) & probe.validity[rows])
        sel = jax.ops.segment_min(
            jnp.where(eq, jnp.arange(ecap, dtype=jnp.int32), ecap),
            rows, num_segments=cap)
        found = sel < ecap
        sel_c = jnp.clip(sel, 0, ecap - 1)
        validity = (found & mcol.validity & probe.validity
                    & vals.validity[sel_c])
        data = jnp.where(validity, vals.data[sel_c],
                         jnp.zeros((), vals.data.dtype))
        if vals.data2 is not None:
            d2 = jnp.where(validity, vals.data2[sel_c],
                           jnp.zeros((), vals.data2.dtype))
            return WideVal(d2, data, validity)
        return ColVal(data, validity)
    if isinstance(expr, E.ElementAt):  # array, 1-based index (neg = from end)
        v = eval_expr(expr.left, ctx)
        idx = eval_expr(expr.right, ctx)
        acol = v.col
        off = acol.offsets
        lens = off[1:] - off[:-1]
        i64 = idx.data.astype(jnp.int64)
        pos = jnp.where(i64 > 0, i64 - 1, lens.astype(jnp.int64) + i64)
        ok = (pos >= 0) & (pos < lens) & (i64 != 0)
        src = jnp.clip(off[:-1].astype(jnp.int64) + pos, 0,
                       acol.data.shape[0] - 1).astype(jnp.int32)
        validity = acol.validity & idx.validity & ok
        data = jnp.where(validity, acol.data[src],
                         jnp.zeros((), acol.data.dtype))
        return ColVal(data, validity)
    if isinstance(expr, E.ArrayContains):
        v = eval_expr(expr.left, ctx)
        probe = eval_expr(expr.right, ctx)
        acol = v.col
        ecap = acol.data.shape[0]
        rows = jnp.clip(_string_row_ids(acol.offsets, ecap), 0, cap - 1)
        in_range = jnp.arange(ecap, dtype=jnp.int32) < acol.offsets[-1]
        eq = in_range & (acol.data == probe.data[rows]) & probe.validity[rows]
        hit = jax.ops.segment_max(eq.astype(jnp.int32), rows,
                                  num_segments=cap) > 0
        return ColVal(hit, acol.validity & probe.validity)

    if isinstance(expr, (E.Greatest, E.Least)):
        vals = [eval_expr(c, ctx) for c in expr.children]
        out_t = expr.dtype
        is_max = not isinstance(expr, E.Least)

        if (isinstance(out_t, T.DecimalType)
                and (out_t.precision > T.DecimalType.MAX_LONG_DIGITS
                     or any(isinstance(v, WideVal) for v in vals))):
            # decimal128 path: rescale every operand to the result scale as
            # (hi, lo) limbs, compare with int128 ordering (ADVICE r4:
            # Greatest/Least are in _WIDE_OK so this must exist)
            from spark_rapids_tpu.exec import int128 as I128

            acc_h = acc_l = av = None
            for v, c in zip(vals, expr.children):
                w = _as_wide(v, c.dtype, out_t.scale)
                if acc_h is None:
                    acc_h, acc_l, av = w.hi, w.lo, w.validity
                    continue
                both = av & w.validity
                newer = (I128.cmp_lt(acc_h, acc_l, w.hi, w.lo) if is_max
                         else I128.cmp_lt(w.hi, w.lo, acc_h, acc_l))
                take = jnp.where(both, newer, w.validity)
                acc_h = jnp.where(take, w.hi, acc_h)
                acc_l = jnp.where(take, w.lo, acc_l)
                av = av | w.validity
            return WideVal(acc_h, acc_l, av)

        def conv(d, cd):
            # Operands must be rescaled to the common type before comparing:
            # raw unscaled int64 values of different scales are not ordered
            # the same way as the decimals they represent.
            if isinstance(out_t, T.DecimalType):
                cs = cd.scale if isinstance(cd, T.DecimalType) else 0
                return d.astype(jnp.int64) * (10 ** (out_t.scale - cs))
            if isinstance(cd, T.DecimalType):
                return d.astype(jnp.float64) / (10 ** cd.scale)
            return d.astype(T.numpy_dtype(out_t))

        def ckey(d):
            # Spark total order: NaN sorts ABOVE every value
            if jnp.issubdtype(d.dtype, jnp.floating):
                return jnp.where(jnp.isnan(d), jnp.inf, d)
            return d

        acc, av = None, None
        for v, c in zip(vals, expr.children):
            d = conv(v.data, c.dtype)
            if acc is None:
                acc, av = d, v.validity
                continue
            both = av & v.validity
            newer = ckey(d) > ckey(acc) if is_max else ckey(d) < ckey(acc)
            acc = jnp.where(both, jnp.where(newer, d, acc),
                            jnp.where(v.validity, d, acc))
            av = av | v.validity
        return ColVal(acc, av)
    if isinstance(expr, E.NullIf):
        l = eval_expr(expr.left, ctx)
        r = eval_expr(expr.right, ctx)
        if isinstance(l, StringVal):
            eq = _string_eq(l, r, cap)
        else:
            ct = _numeric_common(expr.left.dtype, expr.right.dtype)
            np_ct = T.numpy_dtype(ct) if ct is not None else l.data.dtype
            eq = _nan_safe_eq(l.data.astype(np_ct), r.data.astype(np_ct))
        keep = ~(eq & l.validity & r.validity)
        if isinstance(l, StringVal):
            return StringVal(l.data, l.offsets, l.validity & keep)
        return ColVal(l.data, l.validity & keep)
    if isinstance(expr, E.Nvl2):
        ref = eval_expr(expr.children[0], ctx)
        a = eval_expr(expr.children[1], ctx)
        b = eval_expr(expr.children[2], ctx)
        take = ref.validity
        if isinstance(a, StringVal):
            return _string_select(take, a, b)
        return ColVal(jnp.where(take, a.data, b.data),
                      jnp.where(take, a.validity, b.validity))
    if isinstance(expr, (E.BitwiseAnd, E.BitwiseOr, E.BitwiseXor)):
        l = eval_expr(expr.left, ctx)
        r = eval_expr(expr.right, ctx)
        np_t = T.numpy_dtype(expr.dtype)
        a, b = l.data.astype(np_t), r.data.astype(np_t)
        out = (a & b if isinstance(expr, E.BitwiseAnd)
               else a | b if isinstance(expr, E.BitwiseOr) else a ^ b)
        return ColVal(out, l.validity & r.validity)
    if isinstance(expr, E.BitwiseNot):
        c = eval_expr(expr.child, ctx)
        return ColVal(~c.data, c.validity)
    if isinstance(expr, E.ShiftLeft):  # covers Right/RightUnsigned
        l = eval_expr(expr.left, ctx)
        r = eval_expr(expr.right, ctx)
        bits = 64 if expr.left.dtype == T.LONG else 32
        sh = (r.data.astype(jnp.int32) & (bits - 1))
        valid = l.validity & r.validity
        if isinstance(expr, E.ShiftRightUnsigned):
            u = l.data.astype(jnp.uint64 if bits == 64 else jnp.uint32)
            out = (u >> sh.astype(u.dtype)).astype(l.data.dtype)
        elif isinstance(expr, E.ShiftRight) and not isinstance(
                expr, E.ShiftRightUnsigned):
            out = l.data >> sh.astype(l.data.dtype)
        else:
            out = l.data << sh.astype(l.data.dtype)
        return ColVal(out, valid)
    if isinstance(expr, (E.Hour, E.Minute, E.Second)):
        c = eval_expr(expr.child, ctx)
        us = c.data.astype(jnp.int64)
        # timestamps are negative before the epoch: floor-mod keeps
        # time-of-day in [0, 24h)
        day_us = jnp.int64(86_400_000_000)
        tod = ((us % day_us) + day_us) % day_us
        if type(expr) is E.Hour:
            out = tod // 3_600_000_000
        elif type(expr) is E.Minute:
            out = (tod // 60_000_000) % 60
        else:
            out = (tod // 1_000_000) % 60
        return ColVal(out.astype(jnp.int32), c.validity)
    if isinstance(expr, E.WeekOfYear):
        c = eval_expr(expr.child, ctx)
        days = (c.data // 86_400_000_000
                if expr.child.dtype == T.TIMESTAMP else c.data
                ).astype(jnp.int32)
        doy = _day_of_year(days)
        # ISO weekday: Mon=1..Sun=7; 1970-01-01 was a Thursday (=4)
        wd = ((days.astype(jnp.int32) + 3) % 7 + 7) % 7 + 1
        w = (doy - wd + 10) // 7
        y, _, _ = _civil_from_days(days)

        def _weeks_in(yy):
            jan1 = _days_from_civil(yy, jnp.ones_like(yy), jnp.ones_like(yy))
            jan1_wd = ((jan1 + 3) % 7 + 7) % 7 + 1
            leap = ((yy % 4 == 0) & (yy % 100 != 0)) | (yy % 400 == 0)
            return jnp.where((jan1_wd == 4) | (leap & (jan1_wd == 3)),
                             53, 52)
        w = jnp.where(w < 1, _weeks_in(y - 1),
                      jnp.where(w > _weeks_in(y), 1, w))
        return ColVal(w.astype(jnp.int32), c.validity)
    if isinstance(expr, E.LastDay):
        c = eval_expr(expr.child, ctx)
        days = c.data.astype(jnp.int32)
        y, m, _ = _civil_from_days(days)
        ny = jnp.where(m == 12, y + 1, y)
        nm = jnp.where(m == 12, 1, m + 1)
        out = _days_from_civil(ny, nm, jnp.ones_like(ny)) - 1
        return ColVal(out.astype(jnp.int32), c.validity)
    if isinstance(expr, E.MonthsBetween):
        l = eval_expr(expr.left, ctx)
        r = eval_expr(expr.right, ctx)

        def ymds(v, dt):
            if dt == T.TIMESTAMP:
                days = jnp.floor_divide(v.data, 86_400_000_000)
                # Spark truncates to whole seconds (MICROSECONDS.toSeconds)
                secs = jnp.floor_divide(
                    v.data - days * 86_400_000_000,
                    1_000_000).astype(jnp.float64)
            else:
                days = v.data
                secs = jnp.zeros(v.data.shape, jnp.float64)
            y, m, d = _civil_from_days(days.astype(jnp.int32))
            return y, m, d, secs
        y1, m1, d1, s1 = ymds(l, expr.left.dtype)
        y2, m2, d2, s2 = ymds(r, expr.right.dtype)
        months = (y1 - y2) * 12 + (m1 - m2)

        def month_len(y, m):
            ny = jnp.where(m == 12, y + 1, y)
            nm = jnp.where(m == 12, 1, m + 1)
            first_next = _days_from_civil(ny, nm, jnp.ones_like(ny))
            return first_next - _days_from_civil(y, m, jnp.ones_like(y))

        # Spark: same day-of-month OR both dates on their month's last day
        # -> whole months, else add the seconds-precise day fraction over a
        # 31-day month; result rounds HALF_UP to 8 decimals (roundOff=true)
        both_ends = (d1 == month_len(y1, m1)) & (d2 == month_len(y2, m2))
        sec_diff = ((d1 - d2).astype(jnp.float64) * 86400.0 + s1 - s2)
        frac = sec_diff / (31.0 * 86400.0)
        out = months.astype(jnp.float64) + jnp.where(
            (d1 == d2) | both_ends, 0.0, frac)
        out = jnp.sign(out) * jnp.floor(jnp.abs(out) * 1e8 + 0.5) / 1e8
        return ColVal(out, l.validity & r.validity)
    if isinstance(expr, E.FromUTCTimestamp):
        from spark_rapids_tpu.utils import tzdb
        c = eval_expr(expr.child, ctx)
        if isinstance(expr, E.ToUTCTimestamp):
            lstarts, offs, prev = tzdb.local_transitions(expr.tz)
            ustarts, _ = tzdb.utc_transitions(expr.tz)
            ls = jnp.asarray(lstarts)
            j = jnp.clip(jnp.searchsorted(ls, c.data, side="right") - 1,
                         0, ls.shape[0] - 1)
            offj = jnp.asarray(offs)[j]
            prevj = jnp.asarray(prev)[j]
            # DST overlap: if the earlier offset still lands before the
            # transition instant, java (and Spark) keep it
            cand = c.data - prevj
            use_prev = cand < jnp.asarray(ustarts)[j]
            out = jnp.where(use_prev, cand, c.data - offj)
            return ColVal(out, c.validity)
        starts, offs = tzdb.utc_transitions(expr.tz)
        st = jnp.asarray(starts)
        j = jnp.clip(jnp.searchsorted(st, c.data, side="right") - 1,
                     0, st.shape[0] - 1)
        return ColVal(c.data + jnp.asarray(offs)[j], c.validity)
    if isinstance(expr, E.MakeDate):
        y = eval_expr(expr.children[0], ctx)
        m = eval_expr(expr.children[1], ctx)
        d = eval_expr(expr.children[2], ctx)
        yy = y.data.astype(jnp.int32)
        mm = m.data.astype(jnp.int32)
        dd = d.data.astype(jnp.int32)
        mc = jnp.clip(mm, 1, 12)
        ny = jnp.where(mc == 12, yy + 1, yy)
        nm = jnp.where(mc == 12, 1, mc + 1)
        mlen = (_days_from_civil(ny, nm, jnp.ones_like(yy))
                - _days_from_civil(yy, mc, jnp.ones_like(yy)))
        ok = ((mm >= 1) & (mm <= 12) & (dd >= 1) & (dd <= mlen)
              & (yy >= 1) & (yy <= 9999))
        days = _days_from_civil(yy, mc, jnp.clip(dd, 1, 31))
        return ColVal(jnp.where(ok, days, 0).astype(jnp.int32),
                      y.validity & m.validity & d.validity & ok)
    if isinstance(expr, E.MakeTimestamp):
        vs = [eval_expr(c, ctx) for c in expr.children]
        yy, mm, dd, hh, mi = [v.data.astype(jnp.int32) for v in vs[:5]]
        sec = vs[5].data.astype(jnp.float64)
        mc = jnp.clip(mm, 1, 12)
        ny = jnp.where(mc == 12, yy + 1, yy)
        nm = jnp.where(mc == 12, 1, mc + 1)
        mlen = (_days_from_civil(ny, nm, jnp.ones_like(yy))
                - _days_from_civil(yy, mc, jnp.ones_like(yy)))
        ok = ((mm >= 1) & (mm <= 12) & (dd >= 1) & (dd <= mlen)
              & (hh >= 0) & (hh <= 23) & (mi >= 0) & (mi <= 59)
              & (sec >= 0) & (sec < 60) & (yy >= 1) & (yy <= 9999))
        days = _days_from_civil(yy, mc, jnp.clip(dd, 1, 31)).astype(jnp.int64)
        micros = (days * 86_400_000_000
                  + hh.astype(jnp.int64) * 3_600_000_000
                  + mi.astype(jnp.int64) * 60_000_000
                  + jnp.round(sec * 1e6).astype(jnp.int64))
        valid = ok
        for v in vs:
            valid = valid & v.validity
        return ColVal(jnp.where(valid, micros, 0), valid)
    if isinstance(expr, E.TimestampSeconds):  # + Millis/Micros subclasses
        c = eval_expr(expr.child, ctx)
        return ColVal(c.data.astype(jnp.int64) * expr.SCALE, c.validity)
    if isinstance(expr, E.UnixSeconds):  # + Millis/Micros subclasses
        c = eval_expr(expr.child, ctx)
        return ColVal(jnp.floor_divide(c.data.astype(jnp.int64), expr.DIV),
                      c.validity)
    if isinstance(expr, E.UnixDate):
        c = eval_expr(expr.child, ctx)
        return ColVal(c.data.astype(jnp.int32), c.validity)
    if isinstance(expr, E.DateFromUnixDate):
        c = eval_expr(expr.child, ctx)
        return ColVal(c.data.astype(jnp.int32), c.validity)
    if isinstance(expr, E.TruncDate):
        c = eval_expr(expr.children[0], ctx)
        days = c.data.astype(jnp.int32)
        y, m, d = _civil_from_days(days)
        fmt = expr.fmt
        if fmt in ("year", "yyyy", "yy"):
            out = _days_from_civil(y, jnp.ones_like(m), jnp.ones_like(d))
        elif fmt in ("quarter",):
            qm = ((m - 1) // 3) * 3 + 1
            out = _days_from_civil(y, qm, jnp.ones_like(d))
        elif fmt in ("month", "mon", "mm"):
            out = _days_from_civil(y, m, jnp.ones_like(d))
        elif fmt in ("week",):
            wd = ((days + 3) % 7 + 7) % 7  # 0 = Monday
            out = days - wd
        else:
            raise NotImplementedError(f"trunc format {fmt}")
        return ColVal(out.astype(jnp.int32), c.validity)
    if isinstance(expr, E.NextDay):
        c = eval_expr(expr.children[0], ctx)
        days = c.data.astype(jnp.int32)
        target = E.NextDay._DOW[expr.day.lower()[:3]]  # 1=Sun..7=Sat
        dow = ((days + 4) % 7 + 7) % 7 + 1  # Spark dayofweek
        delta = ((target - dow) % 7 + 7) % 7
        delta = jnp.where(delta == 0, 7, delta)
        return ColVal((days + delta).astype(jnp.int32), c.validity)
    if isinstance(expr, E.UnixTimestampOf):
        c = eval_expr(expr.child, ctx)
        us = (c.data.astype(jnp.int64) * 86_400_000_000
              if expr.child.dtype == T.DATE else c.data.astype(jnp.int64))
        return ColVal(us // 1_000_000, c.validity)  # // floors (pre-epoch)
    if isinstance(expr, E.FromUnixTime):
        c = eval_expr(expr.child, ctx)
        return ColVal(c.data.astype(jnp.int64) * 1_000_000, c.validity)
    if isinstance(expr, E.OctetLength):  # covers BitLength
        s = eval_expr(expr.child, ctx)
        assert isinstance(s, StringVal)
        lens = (s.offsets[1:] - s.offsets[:-1]).astype(jnp.int32)
        mul = 8 if isinstance(expr, E.BitLength) else 1
        return ColVal(lens * mul, s.validity)
    if isinstance(expr, (E.StringLeft, E.StringRight)):
        # left/right are substring sugar (Spark rewrites them the same way)
        n_chars = max(int(expr.n), 0)
        sub = (E.Substring(expr.children[0], 1, n_chars)
               if type(expr) is E.StringLeft
               else E.Substring(expr.children[0],
                                -n_chars if n_chars else 1, n_chars))
        return eval_expr(sub, ctx)
    if isinstance(expr, E.Nanvl):
        l = eval_expr(expr.left, ctx)
        r = eval_expr(expr.right, ctx)
        a = l.data.astype(jnp.float64)
        b = r.data.astype(jnp.float64)
        take_b = jnp.isnan(a)
        return ColVal(jnp.where(take_b, b, a),
                      jnp.where(take_b, r.validity, l.validity))
    if isinstance(expr, E.Rint):
        c = eval_expr(expr.child, ctx)
        # round half to even (java.lang.Math.rint)
        return ColVal(jnp.round(c.data.astype(jnp.float64)), c.validity)
    if isinstance(expr, E.AddMonths):
        l = eval_expr(expr.left, ctx)
        r = eval_expr(expr.right, ctx)
        days = l.data.astype(jnp.int32)
        y, m, d = _civil_from_days(days)
        tot = (y * 12 + (m - 1)) + r.data.astype(jnp.int32)
        ny = tot // 12
        nm = tot % 12 + 1
        # clamp the day to the target month's length (Spark add_months)
        ny2 = jnp.where(nm == 12, ny + 1, ny)
        nm2 = jnp.where(nm == 12, 1, nm + 1)
        mlen = (_days_from_civil(ny2, nm2, jnp.ones_like(ny))
                - _days_from_civil(ny, nm, jnp.ones_like(ny)))
        out = _days_from_civil(ny, nm, jnp.minimum(d, mlen))
        return ColVal(out.astype(jnp.int32), l.validity & r.validity)
    if isinstance(expr, E.Floor):
        c = eval_expr(expr.child, ctx)
        if isinstance(expr.child.dtype, T.DecimalType):
            raise NotImplementedError("decimal floor")
        if expr.child.dtype in T.INTEGRAL_TYPES:
            return ColVal(c.data.astype(jnp.int64), c.validity)
        f = jnp.floor if isinstance(expr, E.Floor) and not isinstance(expr, E.Ceil) \
            else jnp.ceil
        return _float_or_int_to_int(f(c.data.astype(jnp.float64)), c.validity, T.LONG)
    if isinstance(expr, E.Round):
        c = eval_expr(expr.child, ctx)
        dt = expr.child.dtype
        if isinstance(dt, T.DecimalType):
            raise NotImplementedError("decimal round")
        if dt in T.INTEGRAL_TYPES and expr.scale >= 0:
            return c
        # Spark ROUND_HALF_UP (away from zero), not banker's rounding
        m = 10.0 ** expr.scale
        d = c.data.astype(jnp.float64) * m
        rounded = jnp.sign(d) * jnp.floor(jnp.abs(d) + 0.5) / m
        return ColVal(rounded.astype(c.data.dtype) if dt in T.FRACTIONAL_TYPES
                      else rounded, c.validity)

    # --- datetime ---
    if isinstance(expr, (E.Year, E.Month, E.DayOfMonth, E.DayOfWeek,
                         E.DayOfYear, E.Quarter)):
        c = eval_expr(expr.child, ctx)
        days = c.data
        if expr.child.dtype == T.TIMESTAMP:
            days = (days // 86_400_000_000).astype(jnp.int32)
        if isinstance(expr, E.DayOfWeek):
            return ColVal(_day_of_week(days), c.validity)
        if isinstance(expr, E.DayOfYear):
            return ColVal(_day_of_year(days), c.validity)
        y, m, d = _civil_from_days(days)
        if isinstance(expr, E.Year):
            return ColVal(y, c.validity)
        if isinstance(expr, E.Month):
            return ColVal(m, c.validity)
        if isinstance(expr, E.Quarter):
            return ColVal((m + 2) // 3, c.validity)
        return ColVal(d, c.validity)
    if isinstance(expr, (E.DateAdd, E.DateSub)):
        l = eval_expr(expr.left, ctx)
        r = eval_expr(expr.right, ctx)
        sign = 1 if isinstance(expr, E.DateAdd) else -1
        return ColVal(
            (l.data.astype(jnp.int32) + sign * r.data.astype(jnp.int32)),
            l.validity & r.validity,
        )
    if isinstance(expr, E.DateDiff):
        l = eval_expr(expr.left, ctx)
        r = eval_expr(expr.right, ctx)
        return ColVal(
            l.data.astype(jnp.int32) - r.data.astype(jnp.int32),
            l.validity & r.validity,
        )

    # --- strings ---
    if isinstance(expr, E.Length):
        s = eval_expr(expr.child, ctx)
        assert isinstance(s, StringVal)
        # Spark length() counts characters; count UTF-8 non-continuation bytes
        is_start = (s.data & 0xC0) != 0x80
        starts = jnp.cumsum(
            jnp.concatenate([jnp.zeros(1, jnp.int32), is_start.astype(jnp.int32)])
        )
        return ColVal(
            (starts[s.offsets[1:]] - starts[s.offsets[:-1]]).astype(jnp.int32),
            s.validity,
        )
    if isinstance(expr, (E.Upper, E.Lower)):
        s = eval_expr(expr.child, ctx)
        assert isinstance(s, StringVal)
        d = s.data
        if isinstance(expr, E.Upper):
            shift = ((d >= ord("a")) & (d <= ord("z"))).astype(jnp.uint8) * 32
            d = d - shift
        else:
            shift = ((d >= ord("A")) & (d <= ord("Z"))).astype(jnp.uint8) * 32
            d = d + shift
        return StringVal(d, s.offsets, s.validity)
    if isinstance(expr, (E.StartsWith, E.EndsWith, E.Contains)):
        return _eval_string_search(expr, ctx)
    if isinstance(expr, E.Substring):
        return _eval_substring(expr, ctx)
    out = _eval_string_fns(expr, ctx)
    if out is not None:
        return out

    raise NotImplementedError(f"eval of {type(expr).__name__}")


_TRIG = {E.Sin: jnp.sin, E.Cos: jnp.cos, E.Tan: jnp.tan,
         E.Asin: jnp.arcsin, E.Acos: jnp.arccos, E.Atan: jnp.arctan,
         E.Sinh: jnp.sinh, E.Cosh: jnp.cosh, E.Tanh: jnp.tanh,
         E.ToDegrees: jnp.degrees, E.ToRadians: jnp.radians,
         E.Asinh: jnp.arcsinh, E.Acosh: jnp.arccosh, E.Atanh: jnp.arctanh,
         E.Cot: lambda x: 1.0 / jnp.tan(x),
         E.Sec: lambda x: 1.0 / jnp.cos(x),
         E.Csc: lambda x: 1.0 / jnp.sin(x)}


def _eval_string_fns(expr: E.Expression, ctx: EvalContext):
    """Dispatch to the vectorized string kernels (exprs/strings.py)."""
    from spark_rapids_tpu.exprs import regex as RX
    from spark_rapids_tpu.exprs import strings as S

    def sval(e: E.Expression) -> StringVal:
        v = eval_expr(e, ctx)
        assert isinstance(v, StringVal), f"{type(e).__name__} expects string"
        return v

    def back(v: StringVal) -> StringVal:
        return v

    if isinstance(expr, E.Concat):
        vals = [sval(c) for c in expr.children]
        acc = vals[0]
        for v in vals[1:]:
            acc = S.concat2(acc, v)
        return back(acc)
    if isinstance(expr, E.ConcatWs):
        vals = [sval(c) for c in expr.children]
        return back(S.concat_ws(expr.sep.encode("utf-8"), vals))
    if isinstance(expr, E.StringTrim):  # covers Left/Right subclasses
        chars = (expr.trim_str if expr.trim_str is not None else " ").encode()
        s = sval(expr.children[0])
        return back(S.trim(s, chars, left=expr.side in ("both", "left"),
                           right=expr.side in ("both", "right")))
    if isinstance(expr, E.StringReplace):
        return back(S.replace(sval(expr.children[0]),
                              expr.search.encode("utf-8"),
                              expr.replacement.encode("utf-8")))
    if isinstance(expr, E.Like):
        s = sval(expr.children[0])
        dfa = RX.like_to_dfa(expr.pattern, expr.escape)
        return ColVal(RX.match_strings(dfa, s.data, s.offsets), s.validity)
    if isinstance(expr, E.RLike):
        s = sval(expr.children[0])
        dfa = RX.compile_rlike(expr.pattern)
        return ColVal(RX.match_strings(dfa, s.data, s.offsets), s.validity)
    if isinstance(expr, E.StringInstr):
        s = sval(expr.children[0])
        return ColVal(S.first_match_pos(s, expr.substr.encode("utf-8")),
                      s.validity)
    if isinstance(expr, E.StringLocate):
        s = sval(expr.children[0])
        if expr.start < 1:
            # Spark: locate with start < 1 returns 0
            return ColVal(jnp.zeros((ctx.capacity,), jnp.int32), s.validity)
        return ColVal(
            S.first_match_pos(s, expr.substr.encode("utf-8"), expr.start),
            s.validity,
        )
    if isinstance(expr, E.StringLPad):  # covers StringRPad
        return back(S.pad(sval(expr.children[0]), max(expr.length, 0),
                          expr.pad.encode("utf-8"), left=expr.side_left))
    if isinstance(expr, E.StringRepeat):
        return back(S.repeat(sval(expr.children[0]), expr.times))
    if isinstance(expr, E.StringReverse):
        return back(S.reverse(sval(expr.children[0])))
    if isinstance(expr, E.StringTranslate):
        return back(S.translate(sval(expr.children[0]),
                                expr.matching.encode("utf-8"),
                                expr.replace.encode("utf-8")))
    if isinstance(expr, E.InitCap):
        return back(S.initcap(sval(expr.children[0])))
    if isinstance(expr, E.SubstringIndex):
        return back(S.substring_index(sval(expr.children[0]),
                                      expr.delim.encode("utf-8"), expr.count))
    if isinstance(expr, E.Hex):
        cdt = expr.children[0].dtype
        if cdt in (T.STRING, T.BINARY):
            return back(S.hex_encode(sval(expr.children[0])))
        # integral hex: no leading zeros, uppercase, two's complement
        c = eval_expr(expr.children[0], ctx)
        x = c.data.astype(jnp.int64)
        words = jax.lax.bitcast_convert_type(x, jnp.uint32)
        nibs = []
        for w in (words[..., 1], words[..., 0]):
            for k in range(7, -1, -1):
                nibs.append(((w >> jnp.uint32(4 * k)) & 15).astype(jnp.uint8))
        mat = jnp.stack(nibs, axis=1)  # (cap, 16) most-significant first
        nz = mat != 0
        # position of first nonzero nibble (all-zero -> emit single '0')
        first = jnp.argmax(nz, axis=1)
        any_nz = jnp.any(nz, axis=1)
        lens = jnp.where(any_nz, 16 - first, 1).astype(jnp.int32)
        offsets = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(lens).astype(jnp.int32)])
        out_bytes = 16 * mat.shape[0]
        j = jnp.arange(out_bytes, dtype=jnp.int32)
        rows = jnp.clip(S.row_ids(offsets, out_bytes), 0, mat.shape[0] - 1)
        rel = j - offsets[rows]
        nib = mat[rows, jnp.clip(16 - lens[rows] + rel, 0, 15)]
        ch = nib + jnp.where(nib < 10, jnp.uint8(48), jnp.uint8(55))
        in_range = j < offsets[-1]
        return StringVal(jnp.where(in_range, ch, jnp.uint8(0)), offsets,
                         c.validity)
    if isinstance(expr, E.Unhex):
        return back(S.unhex(sval(expr.children[0])))
    if isinstance(expr, E.Base64):
        return back(S.base64_encode(sval(expr.children[0])))
    if isinstance(expr, E.UnBase64):
        return back(S.unbase64(sval(expr.children[0])))
    if isinstance(expr, E.Overlay):
        # overlay with an explicit FOR length decomposes into substrings +
        # concat (the default length = char_length(replace) is per-row and
        # stays on the CPU engine)
        assert expr.length >= 0
        inp, repl = expr.children
        rew = E.Concat(E.Substring(inp, 1, max(expr.pos - 1, 0)), repl,
                       E.Substring(inp, expr.pos + expr.length, 1 << 29))
        return eval_expr(rew, ctx)
    if isinstance(expr, E.FindInSet):
        s = sval(expr.children[0])
        cap = ctx.batch.capacity
        idx = jnp.zeros(cap, jnp.int32)
        # compare against each item of the (static) comma list, first hit
        # wins; a needle containing ',' never matches (Spark)
        items = expr.items.split(",")
        for k in reversed(range(len(items))):
            lit_sv = _broadcast_literal(items[k], T.STRING, cap)
            eq = _string_eq(s, lit_sv, cap)
            idx = jnp.where(eq, jnp.int32(k + 1), idx)
        return ColVal(idx, s.validity)
    if isinstance(expr, E.Ascii):
        s = sval(expr.children[0])
        return ColVal(S.ascii_code(s), s.validity)
    if isinstance(expr, E.Chr):
        v = eval_expr(expr.children[0], ctx)
        assert isinstance(v, ColVal)
        return back(S.chr_of(v.data, v.validity))
    return None


def _dec_parts(v: ColVal, dt: T.DataType):
    """(scaled int64 data, scale) view of a decimal or integral operand —
    Spark implicitly treats an integral as decimal(d, 0) in mixed decimal
    arithmetic (DecimalPrecision integralToDecimal)."""
    if isinstance(dt, T.DecimalType):
        return v.data.astype(jnp.int64), dt.scale
    return v.data.astype(jnp.int64), 0


def _dec_to_f64(v: ColVal, dt: T.DecimalType) -> ColVal:
    return ColVal(v.data.astype(jnp.float64) / (10.0 ** dt.scale), v.validity)


def _wide_to_f64(v: "WideVal") -> jax.Array:
    lo_u = v.lo.astype(jnp.float64) + jnp.where(
        v.lo < 0, jnp.float64(2.0 ** 64), jnp.float64(0.0))
    return v.hi.astype(jnp.float64) * (2.0 ** 64) + lo_u


def _dec_any_to_f64(v, dt: T.DecimalType) -> jax.Array:
    if isinstance(v, WideVal):
        return _wide_to_f64(v) / (10.0 ** dt.scale)
    return v.data.astype(jnp.float64) / (10.0 ** dt.scale)


def _eval_arith_wide(expr, out_t: T.DecimalType, lt, rt, l, r,
                     valid) -> "WideVal":
    """DECIMAL128 add/sub/multiply on (hi, lo) limbs; overflow -> NULL
    (Spark non-ANSI; reference jni DecimalUtils.add128/multiply128)."""
    from spark_rapids_tpu.exec import int128 as I128

    if isinstance(expr, (E.Add, E.Subtract)):
        s = out_t.scale
        wl, ovf_l = _as_wide_checked(l, lt, s, out_t.precision)
        wr, ovf_r = _as_wide_checked(r, rt, s, out_t.precision)
        if isinstance(expr, E.Add):
            h, lo = I128.add(wl.hi, wl.lo, wr.hi, wr.lo)
        else:
            h, lo = I128.sub(wl.hi, wl.lo, wr.hi, wr.lo)
        ovf = I128.overflow_mask(h, lo, out_t.precision) | ovf_l | ovf_r
        z = jnp.zeros_like(h)
        return WideVal(jnp.where(ovf, z, h), jnp.where(ovf, z, lo),
                       valid & ~ovf)
    if isinstance(expr, E.Multiply):
        # out scale == s1 + s2: the raw product of the scaled values IS the
        # result, so no rescale — narrow pairs use the 64x64 fast path, one
        # wide operand the 128x64 multiply (in either order: half of the
        # narrow operand's limbs would be sign bits), two wide operands the
        # exact limb multiply (DecimalUtils.multiply128)
        if isinstance(l, ColVal) and isinstance(r, ColVal):
            h, lo = I128.mul_64x64(l.data.astype(jnp.int64),
                                   r.data.astype(jnp.int64))
            ovf = I128.overflow_mask(h, lo, out_t.precision)
        elif isinstance(l, ColVal) or isinstance(r, ColVal):
            w, n = (r, l) if isinstance(l, ColVal) else (l, r)
            h, lo, ovf = I128.mul_128x64(w.hi, w.lo,
                                         n.data.astype(jnp.int64),
                                         out_t.precision)
        else:
            h, lo, ovf = I128.mul_128_exact(l.hi, l.lo, r.hi, r.lo,
                                            out_t.precision)
        z = jnp.zeros_like(h)
        return WideVal(jnp.where(ovf, z, h), jnp.where(ovf, z, lo),
                       valid & ~ovf)
    if isinstance(expr, E.Divide):
        # Spark decimal divide: exact ROUND_HALF_UP at the result scale —
        # q = HALF_UP(a * 10^(s_out - s1 + s2) / b) through the 256/128
        # Knuth-D kernel (DecimalUtils.divide128 analog)
        s1 = lt.scale if isinstance(lt, T.DecimalType) else 0
        s2 = rt.scale if isinstance(rt, T.DecimalType) else 0
        k = out_t.scale - s1 + s2
        assert 0 <= k <= 76, "divide rescale outside device range (gated)"
        wl = _as_wide(l, lt, s1)
        wr = _as_wide(r, rt, s2)
        h, lo, ovf = I128.decimal_divide_128(wl.hi, wl.lo, wr.hi, wr.lo, k,
                                             out_t.precision)
        # div-by-zero is folded into ovf by the kernel: NULL either way
        ok = valid & ~ovf
        z = jnp.zeros_like(h)
        if _is_wide(out_t):
            return WideVal(jnp.where(ok, h, z), jnp.where(ok, lo, z), ok)
        return ColVal(jnp.where(ok, lo, z), ok)
    raise NotImplementedError(f"decimal128 {expr.symbol}")


def _wide_floor_div_pow10(h, l, k: int):
    """FLOOR((hi, lo) / 10^k) plus a remainder-nonzero flag, for the
    overflow-free mixed-scale comparison (divide the finer side instead of
    rescaling the coarser side up)."""
    from spark_rapids_tpu.exec import int128 as I128

    ah, al = I128.abs_(h, l)
    neg = I128.is_neg(h, l)
    rem_any = jnp.zeros_like(h, dtype=jnp.bool_)
    kk = k
    while kk > 0:
        step = min(kk, 9)
        d = jnp.full_like(h, 10 ** step)
        ah, al, rr = I128._udivmod_small(ah, al, d)
        rem_any = rem_any | (rr != 0)
        kk -= step
    # floor for negatives: -(q + (rem ? 1 : 0))
    qh, ql = ah, al
    nh, nl = I128.neg(qh, ql)
    bump = rem_any.astype(jnp.int64)
    nh2, nl2 = I128.sub(nh, nl, jnp.zeros_like(bump), bump)
    out_h = jnp.where(neg, nh2, qh)
    out_l = jnp.where(neg, nl2, ql)
    return out_h, out_l, rem_any


def _eval_compare_wide(expr, lt, rt, l, r, cap) -> ColVal:
    """DECIMAL128-aware comparisons: exact at mixed scales without
    overflow-prone up-rescaling."""
    from spark_rapids_tpu.exec import int128 as I128

    sa = lt.scale if isinstance(lt, T.DecimalType) else 0
    sb = rt.scale if isinstance(rt, T.DecimalType) else 0
    wl = _as_wide(l, lt, sa)
    wr = _as_wide(r, rt, sb)
    if sa == sb:
        lt_m = I128.cmp_lt(wl.hi, wl.lo, wr.hi, wr.lo)
        eq_m = I128.cmp_eq(wl.hi, wl.lo, wr.hi, wr.lo)
    elif sa > sb:
        qh, ql, rem = _wide_floor_div_pow10(wl.hi, wl.lo, sa - sb)
        lt_m = I128.cmp_lt(qh, ql, wr.hi, wr.lo)
        eq_m = I128.cmp_eq(qh, ql, wr.hi, wr.lo) & ~rem
    else:
        qh, ql, rem = _wide_floor_div_pow10(wr.hi, wr.lo, sb - sa)
        lt_m = (I128.cmp_lt(wl.hi, wl.lo, qh, ql)
                | (I128.cmp_eq(wl.hi, wl.lo, qh, ql) & rem))
        eq_m = I128.cmp_eq(wl.hi, wl.lo, qh, ql) & ~rem
    valid = l.validity & r.validity
    if isinstance(expr, E.EqualTo):
        return ColVal(eq_m, valid)
    if isinstance(expr, E.EqualNullSafe):
        both = l.validity & r.validity
        neither = ~l.validity & ~r.validity
        return ColVal((eq_m & both) | neither, _all_valid(cap))
    if isinstance(expr, E.LessThan):
        return ColVal(lt_m, valid)
    if isinstance(expr, E.GreaterThan):
        return ColVal(~lt_m & ~eq_m, valid)
    if isinstance(expr, E.LessThanOrEqual):
        return ColVal(lt_m | eq_m, valid)
    if isinstance(expr, E.GreaterThanOrEqual):
        return ColVal(~lt_m, valid)
    raise NotImplementedError(expr.symbol)


def _eval_arith(expr: E.BinaryArithmetic, ctx: EvalContext) -> ColVal:
    out_t = expr.dtype
    lt, rt = expr.left.dtype, expr.right.dtype
    l = eval_expr(expr.left, ctx)
    r = eval_expr(expr.right, ctx)
    valid = l.validity & r.validity

    if isinstance(out_t, T.DecimalType):
        if (_is_wide(out_t) or isinstance(l, WideVal)
                or isinstance(r, WideVal)):
            return _eval_arith_wide(expr, out_t, lt, rt, l, r, valid)
        a, sa = _dec_parts(l, lt)
        b, sb = _dec_parts(r, rt)
        if isinstance(expr, (E.Add, E.Subtract)):
            s = out_t.scale
            a = a * jnp.int64(10 ** (s - sa))
            b = b * jnp.int64(10 ** (s - sb))
            data = a + b if isinstance(expr, E.Add) else a - b
            return ColVal(data, valid)
        if isinstance(expr, E.Multiply):
            # out scale == sa + sb: raw product of scaled values
            return ColVal(a * b, valid)
        if isinstance(expr, E.Divide):
            return _eval_arith_wide(expr, out_t, lt, rt, l, r, valid)
        raise NotImplementedError(f"decimal {expr.symbol}")

    # decimal ⊗ float -> double (Spark casts the decimal side)
    if isinstance(lt, T.DecimalType):
        l, lt = ColVal(_dec_any_to_f64(l, lt), l.validity), T.DOUBLE
    if isinstance(rt, T.DecimalType):
        r, rt = ColVal(_dec_any_to_f64(r, rt), r.validity), T.DOUBLE

    np_dtype = T.numpy_dtype(out_t)
    a = l.data.astype(np_dtype)
    b = r.data.astype(np_dtype)

    if isinstance(expr, E.Add):
        return ColVal(a + b, valid)
    if isinstance(expr, E.Subtract):
        return ColVal(a - b, valid)
    if isinstance(expr, E.Multiply):
        return ColVal(a * b, valid)
    if isinstance(expr, E.Divide):
        a64 = l.data.astype(jnp.float64)
        b64 = r.data.astype(jnp.float64)
        if lt in T.FRACTIONAL_TYPES or rt in T.FRACTIONAL_TYPES:
            # float/float division follows IEEE (x/0 = inf), Spark keeps that
            return ColVal((a64 / b64).astype(np_dtype), valid)
        zero = r.data == 0
        safe = jnp.where(zero, 1.0, b64)
        return ColVal(a64 / safe, valid & ~zero)
    if isinstance(expr, E.IntegralDivide):
        zero = r.data == 0
        q = _trunc_div(l.data.astype(jnp.int64), r.data.astype(jnp.int64))
        return ColVal(jnp.where(zero, 0, q), valid & ~zero)
    if isinstance(expr, (E.Remainder, E.Pmod)):
        if jnp.issubdtype(np.dtype(np_dtype), np.floating):
            zero = jnp.isnan(b) | (b == 0)
        else:
            zero = r.data == 0
        rem = _java_rem(a, b)
        if isinstance(expr, E.Pmod):
            rem = _java_rem(rem + b, b)
        return ColVal(jnp.where(zero, jnp.zeros_like(rem), rem), valid & ~zero)
    raise NotImplementedError(expr.symbol)


#: entries a dict-coded column is compared with one by one before a gather
#: of the entries' answers is cheaper: the program grows by a compare each
DICT_COMPARE_ENTRIES = 64


def _dict_equals_literal(expr, ctx: EvalContext) -> Optional[ColVal]:
    """``dict-coded column = 'literal'`` on the codes: the literal is
    compared with the dictionary's few entries, and a row equals it where
    its code's entry does. Evaluating the column as a string first
    (``_column_to_val``) decodes every row into bytes, a byte-space gather
    of the whole batch: a second a 2^20-row batch on the v5e (PR 35: the
    `customer` filter of `sf10_q3_join1`). Up to ``DICT_COMPARE_ENTRIES``
    entries the codes are compared with each entry in turn, in registers;
    past that one gather a row reads its entry's answer (9 ms a 2^20-row
    batch there, whatever the dictionary's size). None where the
    expression's shape is another."""
    if not isinstance(expr, (E.EqualTo, E.EqualNullSafe)):
        return None
    for ref, lit in ((expr.left, expr.right), (expr.right, expr.left)):
        if not (isinstance(ref, E.ColumnRef) and isinstance(lit, E.Literal)
                and isinstance(lit.value, str)):
            continue
        c = ctx.batch.columns[ref.index]
        if not (c.is_dict and c.dict_size > 0):
            return None
        entries = ColumnarBatch([c.dictionary],
                                jnp.int32(c.dictionary.capacity))
        ectx = EvalContext(entries)
        same = _string_eq(ectx.column(0), eval_expr(lit, ectx),
                          ectx.capacity)
        code = c.data.astype(jnp.int32)
        if c.dict_size <= DICT_COMPARE_ENTRIES:
            eq = jnp.zeros(ctx.capacity, jnp.bool_)
            for d in range(c.dict_size):
                eq = eq | ((code == d) & same[d])
        else:
            eq = same[jnp.clip(code, 0, c.dict_size - 1)]
        if isinstance(expr, E.EqualTo):
            return ColVal(eq, c.validity)
        return ColVal(eq & c.validity, _all_valid(ctx.capacity))
    return None


def _eval_compare(expr: E.BinaryComparison, ctx: EvalContext) -> ColVal:
    on_codes = _dict_equals_literal(expr, ctx)
    if on_codes is not None:
        return on_codes
    l = eval_expr(expr.left, ctx)
    r = eval_expr(expr.right, ctx)
    cap = ctx.capacity

    if isinstance(l, StringVal) or isinstance(r, StringVal):
        assert isinstance(l, StringVal) and isinstance(r, StringVal)
        if isinstance(expr, E.EqualTo):
            return ColVal(_string_eq(l, r, cap), l.validity & r.validity)
        if isinstance(expr, E.EqualNullSafe):
            eq = _string_eq(l, r, cap)
            both = l.validity & r.validity
            neither = ~l.validity & ~r.validity
            return ColVal((eq & both) | neither, _all_valid(cap))
        raise NotImplementedError("string ordering comparison on device")

    lt, rt = expr.left.dtype, expr.right.dtype
    if isinstance(lt, T.DecimalType) or isinstance(rt, T.DecimalType):
        if lt in T.FRACTIONAL_TYPES or rt in T.FRACTIONAL_TYPES:
            # decimal vs float: compare as double
            a = (_dec_any_to_f64(l, lt) if isinstance(lt, T.DecimalType)
                 else l.data.astype(jnp.float64))
            b = (_dec_any_to_f64(r, rt) if isinstance(rt, T.DecimalType)
                 else r.data.astype(jnp.float64))
        elif isinstance(l, WideVal) or isinstance(r, WideVal):
            return _eval_compare_wide(expr, lt, rt, l, r, cap)
        else:
            # decimal vs decimal/integral: exact compare without rescaling
            # UP (10^diff multiply overflows int64 for large operands) —
            # compare (floor(a/10^d), remainder) against the coarser side
            da, sa = _dec_parts(l, lt)
            db, sb = _dec_parts(r, rt)
            if sa == sb:
                lt_m = da < db
                eq_m = da == db
            elif sa > sb:
                d = jnp.int64(10 ** (sa - sb))
                q = da // d  # floors toward -inf; rem in [0, d)
                rm = da - q * d
                lt_m = q < db
                eq_m = (q == db) & (rm == 0)
            else:
                d = jnp.int64(10 ** (sb - sa))
                q = db // d
                rm = db - q * d
                lt_m = (da < q) | ((da == q) & (rm > 0))
                eq_m = (da == q) & (rm == 0)
            valid = l.validity & r.validity
            if isinstance(expr, E.EqualTo):
                return ColVal(eq_m, valid)
            if isinstance(expr, E.EqualNullSafe):
                both = l.validity & r.validity
                neither = ~l.validity & ~r.validity
                return ColVal((eq_m & both) | neither, _all_valid(cap))
            if isinstance(expr, E.LessThan):
                return ColVal(lt_m, valid)
            if isinstance(expr, E.GreaterThan):
                return ColVal(~lt_m & ~eq_m, valid)
            if isinstance(expr, E.LessThanOrEqual):
                return ColVal(lt_m | eq_m, valid)
            if isinstance(expr, E.GreaterThanOrEqual):
                return ColVal(~lt_m, valid)
            raise NotImplementedError(expr.symbol)
        valid = l.validity & r.validity
        if isinstance(expr, E.EqualTo):
            return ColVal(a == b, valid)
        if isinstance(expr, E.EqualNullSafe):
            both = l.validity & r.validity
            neither = ~l.validity & ~r.validity
            return ColVal(((a == b) & both) | neither, _all_valid(cap))
        if isinstance(expr, E.LessThan):
            return ColVal(_nan_aware_lt(a, b), valid)
        if isinstance(expr, E.GreaterThan):
            return ColVal(_nan_aware_lt(b, a), valid)
        if isinstance(expr, E.LessThanOrEqual):
            return ColVal(~_nan_aware_lt(b, a), valid)
        if isinstance(expr, E.GreaterThanOrEqual):
            return ColVal(~_nan_aware_lt(a, b), valid)
        raise NotImplementedError(expr.symbol)

    ct = _numeric_common(lt, rt)

    def _coerce(data, src_t):
        if ct is None:
            return data
        if ct == T.TIMESTAMP and src_t == T.DATE:
            return data.astype(jnp.int64) * 86_400_000_000
        return data.astype(T.numpy_dtype(ct))

    a = _coerce(l.data, expr.left.dtype)
    b = _coerce(r.data, expr.right.dtype)
    valid = l.validity & r.validity
    if isinstance(expr, E.EqualTo):
        return ColVal(_nan_safe_eq(a, b), valid)
    if isinstance(expr, E.EqualNullSafe):
        eq = _nan_safe_eq(a, b)
        both = l.validity & r.validity
        neither = ~l.validity & ~r.validity
        return ColVal((eq & both) | neither, _all_valid(cap))
    if isinstance(expr, E.LessThan):
        return ColVal(_nan_aware_lt(a, b), valid)
    if isinstance(expr, E.GreaterThan):
        return ColVal(_nan_aware_lt(b, a), valid)
    if isinstance(expr, E.LessThanOrEqual):
        return ColVal(~_nan_aware_lt(b, a), valid)
    if isinstance(expr, E.GreaterThanOrEqual):
        return ColVal(~_nan_aware_lt(a, b), valid)
    raise NotImplementedError(expr.symbol)


def _numeric_common(a: T.DataType, b: T.DataType):
    if a == b:
        return None
    # Spark coerces date -> timestamp when compared against one
    if {a, b} == {T.DATE, T.TIMESTAMP}:
        return T.TIMESTAMP
    from spark_rapids_tpu.exprs.expr import _numeric_widen

    # raises TypeError for incompatible operands instead of silently
    # comparing raw representations
    return _numeric_widen(a, b)


def _eval_string_search(expr, ctx: EvalContext) -> ColVal:
    s = eval_expr(expr.left, ctx)
    assert isinstance(s, StringVal)
    pat = expr.right
    assert isinstance(pat, E.Literal) and pat.dtype == T.STRING, (
        "string search pattern must be a literal on device"
    )
    needle = np.frombuffer(str(pat.value).encode("utf-8"), dtype=np.uint8)
    m = len(needle)
    cap = ctx.capacity
    lens = s.offsets[1:] - s.offsets[:-1]
    if m == 0:
        return ColVal(jnp.ones((cap,), jnp.bool_), s.validity)
    nbytes = s.data.shape[0]
    # match[k] = bytes k..k+m-1 equal needle
    match = jnp.ones((nbytes,), jnp.bool_)
    for j, ch in enumerate(needle):
        shifted = jnp.roll(s.data, -j)
        match = match & (shifted == np.uint8(ch)) & (
            jnp.arange(nbytes, dtype=jnp.int32) + j < nbytes
        )
    rows = _string_row_ids(s.offsets, nbytes)
    rel = jnp.arange(nbytes, dtype=jnp.int32) - s.offsets[rows]
    in_row = rel <= lens[rows] - m  # match must fit within the row
    if isinstance(expr, E.StartsWith):
        ok = match & in_row & (rel == 0)
    elif isinstance(expr, E.EndsWith):
        ok = match & in_row & (rel == lens[rows] - m)
    else:
        ok = match & in_row
    hit = jax.ops.segment_max(
        ok.astype(jnp.int32), rows, num_segments=cap, indices_are_sorted=True
    )
    # empty segments yield INT32_MIN ("no match"); compare > 0
    return ColVal(hit > 0, s.validity)


def _eval_substring(expr: E.Substring, ctx: EvalContext) -> StringVal:
    s = eval_expr(expr.child, ctx)
    assert isinstance(s, StringVal)
    cap = ctx.capacity
    lens = (s.offsets[1:] - s.offsets[:-1]).astype(jnp.int32)
    pos, length = expr.pos, expr.length
    # Spark substringSQL: raw start may be negative (pos<0 counts from end and
    # may point before the string); the [start, start+length) window is then
    # clamped into [0, len], which can shorten the result (byte-level here:
    # ASCII round 1, matching cudf's byte-oriented substring for ASCII data)
    if pos > 0:
        raw_start = jnp.full_like(lens, pos - 1)
    elif pos == 0:
        raw_start = jnp.zeros_like(lens)
    else:
        raw_start = lens + pos
    start = jnp.clip(raw_start, 0, lens)
    end = jnp.clip(raw_start + jnp.int32(length), 0, lens)
    out_len = jnp.maximum(end - start, 0)
    new_offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(out_len).astype(jnp.int32)]
    )
    nbytes = s.data.shape[0]
    out_rows = _string_row_ids(new_offsets, nbytes)
    rel = jnp.arange(nbytes, dtype=jnp.int32) - new_offsets[out_rows]
    src = jnp.clip(s.offsets[out_rows] + start[out_rows] + rel, 0, nbytes - 1)
    out_data = s.data[src]
    return StringVal(out_data, new_offsets, s.validity)


# ---------------------------------------------------------------------------
# Projection compilation
# ---------------------------------------------------------------------------


def bind_projection(
    exprs: Sequence[E.Expression], schema: T.Schema
) -> List[E.Expression]:
    return [E.resolve(e, schema) for e in exprs]


def output_schema(exprs: Sequence[E.Expression]) -> T.Schema:
    fields = []
    for i, e in enumerate(exprs):
        name = e.name if isinstance(e, E.Alias) else f"c{i}"
        if isinstance(e, E.ColumnRef) and e.name:
            name = e.name
        fields.append(T.Field(name, e.dtype, e.nullable))
    return T.Schema(fields)


def project_batch(
    batch: ColumnarBatch, bound: Sequence[E.Expression], ansi: bool = False
) -> ColumnarBatch:
    """Evaluate a bound projection over a batch (trace-time: called under jit)."""
    ctx = EvalContext(batch, ansi)
    cols = [_val_to_column(eval_expr(e, ctx), e.dtype) for e in bound]
    # padding rows keep validity False
    active = batch.active_mask()
    cols = [
        DeviceColumn(c.dtype, c.data, c.validity & active, c.offsets,
                     c.dictionary, c.dict_size, c.dict_max_len, c.data2,
                     c.children)
        for c in cols
    ]
    return ColumnarBatch(cols, batch.num_rows)


def compile_bound_projection(
    bound: Sequence[E.Expression], ansi: bool = False
) -> Callable[[ColumnarBatch], ColumnarBatch]:
    """jit a pre-bound projection (cached by jax per capacity bucket)."""
    bound = tuple(bound)

    @jax.jit
    def run(batch):
        return project_batch(batch, bound, ansi)

    return run


def compile_projection(
    exprs: Sequence[E.Expression], schema: T.Schema, ansi: bool = False
) -> Callable[[ColumnarBatch], ColumnarBatch]:
    """Bind + jit a projection."""
    return compile_bound_projection(bind_projection(exprs, schema), ansi)

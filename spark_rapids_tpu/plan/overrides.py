"""Plan rewrite: tag -> convert -> explain, with CPU fallback.

Reference: GpuOverrides.scala (apply -> wrapAndTagPlan -> tag -> explain ->
doConvertPlan; :4541-4908) and the RapidsMeta wrapper tree
(RapidsMeta.scala:84 — willNotWorkOnGpu reason accumulation), plus
TypeChecks.scala per-operator type matrices and GpuTransitionOverrides
transition insertion. Same pipeline over the standalone logical plan:

  LogicalPlan -> PlanMeta tree --tag--> device-or-CPU decision per node
             --convert--> TpuExec/CpuExec tree (transitions implicit in
             CpuExec) --> explain string (NONE | NOT_ON_TPU | ALL)

Distribution: when a node's input has multiple partitions, the converter
inserts shuffle exchanges (hash for aggregate/join, range for global sort) —
the standalone analog of Spark's EnsureRequirements + the reference's
post-shuffle coalesce.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import List, Optional, Sequence

import numpy as np

from spark_rapids_tpu import support
from spark_rapids_tpu import types as T
from spark_rapids_tpu.config import conf as C
from spark_rapids_tpu.exec import (
    CoalesceBatchesExec, FilterExec, GlobalLimitExec, HashAggregateExec,
    HashJoinExec, ParquetScanExec, ProjectExec, SortExec, UnionExec,
)
from spark_rapids_tpu.exec.base import BatchSourceExec, TpuExec
from spark_rapids_tpu.exec.sort import SortOrder
from spark_rapids_tpu.exprs import expr as E
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.cpu import (
    CpuExec, CpuFilterExec, CpuLimitExec, CpuProjectExec, CpuSortExec,
)
from spark_rapids_tpu.shuffle import (
    HashPartitioner, RangePartitioner, ShuffleExchangeExec, SinglePartitioner,
)


# ---------------------------------------------------------------------------
# device support matrices (TypeChecks-lite)
# ---------------------------------------------------------------------------

_DEVICE_EXPRS = (
    E.ColumnRef, E.UnresolvedColumn, E.Literal, E.Alias, E.Cast,
    E.Add, E.Subtract, E.Multiply, E.Divide, E.IntegralDivide, E.Remainder,
    E.Pmod, E.UnaryMinus, E.Abs,
    E.EqualTo, E.EqualNullSafe, E.LessThan, E.LessThanOrEqual, E.GreaterThan,
    E.GreaterThanOrEqual, E.And, E.Or, E.Not, E.IsNull, E.IsNotNull, E.IsNaN,
    E.Coalesce, E.If, E.CaseWhen, E.In,
    E.Sqrt, E.Floor, E.Ceil, E.Round, E.Exp, E.Log, E.Pow,
    E.Log10, E.Log2, E.Log1p, E.Expm1, E.Cbrt, E.Signum,
    E.Sin, E.Cos, E.Tan, E.Asin, E.Acos, E.Atan, E.Sinh, E.Cosh, E.Tanh,
    E.Asinh, E.Acosh, E.Atanh, E.Cot, E.Sec, E.Csc,
    E.ToDegrees, E.ToRadians, E.Atan2, E.Hypot,
    E.BRound, E.Factorial, E.Positive, E.BitCount, E.BitGet,
    E.Murmur3Hash, E.XxHash64,
    E.Greatest, E.Least, E.NullIf, E.Nvl2,
    E.GetStructField, E.CreateNamedStruct, E.MapKeys, E.Size,
    E.GetJsonObject,
    E.ElementAt, E.ArrayContains,
    E.BitwiseAnd, E.BitwiseOr, E.BitwiseXor, E.BitwiseNot,
    E.ShiftLeft, E.ShiftRight, E.ShiftRightUnsigned,
    E.Year, E.Month, E.DayOfMonth, E.DayOfWeek, E.DayOfYear, E.Quarter,
    E.Hour, E.Minute, E.Second, E.WeekOfYear, E.LastDay, E.AddMonths,
    E.MonthsBetween, E.TruncDate, E.NextDay, E.UnixTimestampOf,
    E.FromUnixTime, E.Nanvl, E.Rint,
    E.FromUTCTimestamp, E.ToUTCTimestamp, E.MakeDate, E.MakeTimestamp,
    E.TimestampSeconds, E.TimestampMillis, E.TimestampMicros,
    E.UnixSeconds, E.UnixMillis, E.UnixMicros, E.UnixDate,
    E.DateFromUnixDate,
    E.OctetLength, E.BitLength, E.StringLeft, E.StringRight,
    E.DateAdd, E.DateSub, E.DateDiff,
    E.Length, E.Upper, E.Lower, E.StartsWith, E.EndsWith, E.Contains,
    E.Substring,
    E.Concat, E.ConcatWs, E.StringTrim, E.StringReplace, E.Like, E.RLike,
    E.StringInstr, E.StringLocate, E.StringLPad, E.StringRPad,
    E.StringRepeat, E.StringTrimLeft, E.StringTrimRight,
    E.StringReverse, E.StringTranslate, E.InitCap, E.SubstringIndex,
    E.Ascii, E.Chr, E.Hex, E.Unhex, E.Base64, E.UnBase64, E.Overlay,
    E.FindInSet,
    E.Sum, E.Count, E.Min, E.Max, E.Average, E.First, E.Last,
    E.VarianceSamp, E.VariancePop, E.StddevSamp, E.StddevPop,
    E.Skewness, E.Kurtosis,
    E.BoolAnd, E.BoolOr, E.CountIf, E.AnyValue,
    E.Corr, E.CovarSamp, E.CovarPop, E.MinBy, E.MaxBy,
)


# Device uploads of in-memory tables are cached per (table, batch_rows,
# partitions): several physical_plan() calls over the same arrow table (one
# query re-planned, or many queries over one source) share ONE set of
# device batches instead of re-uploading per plan. Entries die with the
# arrow table (weakref callback).
_DEVICE_SOURCE_CACHE: dict = {}


_UPLOAD_NS = 0
_UPLOAD_LOCK = threading.Lock()


def upload_counters() -> dict:
    """For obs/gauges.snapshot()."""
    return {"ingest_upload_ns_total": _UPLOAD_NS}


def _time_upload(batches, t0: int) -> None:
    """Waits for a table's host->device copies on a thread of its own, so
    that the gauge holds them and the plan does not: the copies land while
    the first programs load or compile."""
    import jax

    global _UPLOAD_NS
    jax.block_until_ready(batches)
    with _UPLOAD_LOCK:
        _UPLOAD_NS += time.perf_counter_ns() - t0


def _device_source_parts(table, batch_rows: int, partitions: int):
    import weakref

    key = (id(table), batch_rows, partitions)
    ent = _DEVICE_SOURCE_CACHE.get(key)
    if ent is not None and ent[0]() is table:
        return ent[1]
    from spark_rapids_tpu.columnar.batch import (
        batch_from_arrow, dictionary_encode_table)

    t0 = time.perf_counter_ns()
    t = dictionary_encode_table(table)
    cache: dict = {}
    batches = [batch_from_arrow(t.slice(i, batch_rows), dict_cache=cache)
               for i in range(0, max(t.num_rows, 1), batch_rows)]
    threading.Thread(target=_time_upload, args=(batches, t0),
                     name="ingest-upload-timer", daemon=True).start()
    n_parts = max(1, min(partitions, len(batches)))
    parts = [batches[p::n_parts] for p in range(n_parts)]
    try:
        ref = weakref.ref(table, lambda _: _DEVICE_SOURCE_CACHE.pop(key, None))
    except TypeError:
        return parts  # not weakref-able: don't cache
    _DEVICE_SOURCE_CACHE[key] = (ref, parts)
    return parts


def _is_wide(dt: T.DataType) -> bool:
    return (isinstance(dt, T.DecimalType)
            and dt.precision > T.DecimalType.MAX_LONG_DIGITS)


# operations with a decimal128 device implementation; anything else touching
# a wide value falls back (reference: cuDF decimal128 coverage is similarly
# narrower than decimal64's)
_WIDE_OK = (E.Alias, E.ColumnRef, E.UnresolvedColumn, E.Literal, E.Cast,
            E.Add, E.Subtract, E.Multiply, E.Divide, E.Abs, E.UnaryMinus,
            E.BinaryComparison, E.IsNull, E.IsNotNull,
            E.If, E.CaseWhen, E.Coalesce, E.Sum, E.Min, E.Max, E.Average,
            E.Count, E.First, E.Last, E.Greatest, E.Least)

# expressions with a device implementation over struct/map/array operands
# (the nested analog of _WIDE_OK); everything else touching a nested value
# falls back. Reference: incremental nested rules, GpuOverrides.scala:911.
_NESTED_OK = (E.Alias, E.ColumnRef, E.UnresolvedColumn,
              E.GetStructField, E.CreateNamedStruct, E.MapKeys, E.Size,
              E.ElementAt, E.ArrayContains, E.IsNull, E.IsNotNull)


def _is_nested(dt: T.DataType) -> bool:
    return isinstance(dt, (T.StructType, T.MapType, T.ArrayType))


def _struct_has_varwidth(dt: T.DataType) -> bool:
    if isinstance(dt, T.StructType):
        return any(not f.dtype.fixed_width or _struct_has_varwidth(f.dtype)
                   for f in dt.fields)
    return False


def check_expr(expr: E.Expression, schema: T.Schema) -> List[str]:
    """Reasons this expression can't run on device (empty = supported)."""
    reasons: List[str] = []

    def walk(e: E.Expression):
        if not isinstance(e, _DEVICE_EXPRS) or not getattr(
            e, "device_supported", True
        ):
            reasons.append(f"expression {type(e).__name__} not on device")
            return
        try:
            bound = E.resolve(e, schema)
            # central (operator, type) gate: placement never exceeds the
            # class's type_support declaration (spark_rapids_tpu.support;
            # TypeChecks.scala analog). The special cases below only ever
            # NARROW further — docs/supported_ops.md is generated from the
            # same declarations, so the docs are an upper bound on
            # placement by construction.
            decl = type(bound).type_support
            if decl is None:
                reasons.append(
                    f"{type(bound).__name__} has no type_support "
                    "declaration")
            else:
                for c in bound.children:
                    if not decl.ok(c.dtype):
                        reasons.append(
                            f"{type(bound).__name__} does not support "
                            f"{support.classify(c.dtype)} inputs")
                        break
                if not decl.ok(bound.dtype, output=True):
                    reasons.append(
                        f"{type(bound).__name__} does not support "
                        f"{support.classify(bound.dtype)} outputs")
            wide_touch = _is_wide(bound.dtype) or any(
                _is_wide(c.dtype) for c in bound.children)
            if wide_touch:
                if not isinstance(bound, _WIDE_OK):
                    reasons.append(
                        f"{type(bound).__name__} not on device for "
                        "decimal128")
                if isinstance(bound, E.Cast) and isinstance(
                        bound.to, T.DecimalType) and isinstance(
                        bound.children[0].dtype, T.DecimalType):
                    drop = bound.children[0].dtype.scale - bound.to.scale
                    if drop > 18:
                        reasons.append(
                            "decimal128 scale reduction > 18 not on device")
            # cast combos without a device kernel (reference: the CPU
            # fallback notes in GpuCast docs): float->string needs Java
            # shortest-round-trip formatting; string->decimal and ANSI
            # string casts stay on the CPU engine
            if isinstance(bound, E.Cast):
                cdt = bound.children[0].dtype
                if cdt in (T.FLOAT, T.DOUBLE) and bound.to in (
                        T.STRING, T.BINARY):
                    reasons.append("float to string cast not on device")
                if cdt in (T.STRING, T.BINARY):
                    if isinstance(bound.to, T.DecimalType):
                        reasons.append("string to decimal cast not on device")
                    if bound.ansi:
                        reasons.append("ANSI string cast not on device")
            # string ordering comparisons are CPU-only in round 1
            if isinstance(bound, (E.LessThan, E.LessThanOrEqual,
                                  E.GreaterThan, E.GreaterThanOrEqual)):
                if bound.left.dtype in (T.STRING, T.BINARY):
                    reasons.append("string ordering comparison not on device")
            # device kernels raise for decimal floor/ceil/round — tag to CPU
            # instead of crashing at execute time
            if isinstance(bound, (E.Floor, E.Round, E.BRound)) and isinstance(
                    bound.children[0].dtype, T.DecimalType):
                reasons.append("decimal floor/ceil/round not on device")
            # min_by/max_by device path needs a single-word order key and a
            # fixed-width (or dict) value gather
            if isinstance(bound, E.MinBy):
                odt = bound.children[1].dtype
                vdt = bound.children[0].dtype
                if (odt in T.FRACTIONAL_TYPES
                        or odt in (T.STRING, T.BINARY)
                        or isinstance(odt, T.DecimalType)
                        or vdt in (T.STRING, T.BINARY)
                        or isinstance(vdt, T.DecimalType)):
                    reasons.append(
                        "min_by/max_by ordering/value type not on device")
            # integral-divide/remainder still need exact trunc-division
            # wide paths; plain decimal Divide runs on device via the
            # Knuth-D kernel (int128.decimal_divide_128)
            if isinstance(bound, (E.IntegralDivide, E.Remainder, E.Pmod)):
                if any(isinstance(c.dtype, T.DecimalType)
                       for c in bound.children):
                    reasons.append("decimal division not on device")
            if isinstance(bound, E.Divide) and isinstance(
                    bound.dtype, T.DecimalType):
                s1 = (bound.left.dtype.scale
                      if isinstance(bound.left.dtype, T.DecimalType) else 0)
                s2 = (bound.right.dtype.scale
                      if isinstance(bound.right.dtype, T.DecimalType) else 0)
                k = bound.dtype.scale - s1 + s2
                if k < 0 or k > 76:
                    reasons.append(
                        "decimal divide rescale outside device range")
            # nested-type device coverage (reference:
            # GpuOverrides.scala:911 nested rules; map values / var-width
            # or decimal128 map keys stay on CPU this round). Central gate
            # first: any expression touching a nested value must be in
            # _NESTED_OK or the node falls back (mirrors _WIDE_OK).
            nested_touch = _is_nested(bound.dtype) or any(
                _is_nested(c.dtype) for c in bound.children)
            if nested_touch and not isinstance(bound, _NESTED_OK):
                reasons.append(
                    f"{type(bound).__name__} not on device for nested types")
            if isinstance(bound, E.MapKeys):
                kdt = bound.child.dtype.key
                if not kdt.fixed_width or _is_wide(kdt):
                    reasons.append(
                        "map_keys key type not on device")
            if isinstance(bound, E.ElementAt):
                lt0 = bound.left.dtype
                if isinstance(lt0, T.MapType):
                    if (not lt0.key.fixed_width or _is_wide(lt0.key)
                            or not lt0.value.fixed_width):
                        reasons.append(
                            "element_at key/value type not on device")
                elif isinstance(lt0, T.ArrayType):
                    if not lt0.element.fixed_width:
                        reasons.append(
                            "element_at element type not on device")
            if isinstance(bound, E.ArrayContains):
                lt0 = bound.left.dtype
                if not (isinstance(lt0, T.ArrayType)
                        and lt0.element.fixed_width
                        and bound.right.dtype.fixed_width
                        and not _is_wide(lt0.element)
                        and not _is_wide(bound.right.dtype)):
                    reasons.append("array_contains type not on device")
            if isinstance(bound, (E.FromUTCTimestamp, E.ToUTCTimestamp)):
                if not C.TZ_DB_ENABLED.get(C.get_active()):
                    reasons.append("timezone db disabled")
            if isinstance(bound, E.GetJsonObject):
                from spark_rapids_tpu.exprs import json_device as JD

                if JD.parse_path(bound.path) is None:
                    reasons.append(
                        f"json path {bound.path!r} not on device")
            # probe regex compilability (reference: RegexParser transpiler
            # bail-outs -> willNotWorkOnGpu); patterns outside the DFA
            # subset fall back to CPU
            if isinstance(bound, (E.Like, E.RLike)):
                from spark_rapids_tpu.exprs import regex as RX

                try:
                    if isinstance(bound, E.Like):
                        RX.like_to_dfa(bound.pattern, bound.escape)
                    else:
                        RX.compile_rlike(bound.pattern)
                except RX.RegexUnsupported as rex:
                    reasons.append(f"regex not on device: {rex}")
        except (TypeError, KeyError, NotImplementedError) as ex:
            reasons.append(str(ex))
        for c in e.children:
            walk(c)
        if isinstance(e, E.In):
            for it in e.items:
                walk(it)

    walk(expr)
    return reasons


# ---------------------------------------------------------------------------
# meta tree
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PlanMeta:
    node: L.LogicalPlan
    children: List["PlanMeta"]
    reasons: List[str] = dataclasses.field(default_factory=list)

    def will_not_work(self, reason: str) -> None:
        self.reasons.append(reason)

    @property
    def can_run_on_device(self) -> bool:
        return not self.reasons


def _with_children(plan: L.LogicalPlan, kids) -> L.LogicalPlan:
    """Rebuild a logical node with replacement children."""
    if isinstance(plan, L.Project):
        return L.Project(plan.exprs, kids[0])
    if isinstance(plan, L.Filter):
        return L.Filter(plan.condition, kids[0])
    if isinstance(plan, L.Aggregate):
        return L.Aggregate(plan.group_exprs, plan.agg_exprs, kids[0])
    if isinstance(plan, L.Window):
        return L.Window(plan.window_exprs, kids[0])
    if isinstance(plan, L.Sort):
        return L.Sort(plan.orders, kids[0], plan.is_global, plan.limit)
    if isinstance(plan, L.Join):
        return L.Join(kids[0], kids[1], plan.left_keys, plan.right_keys,
                      plan.join_type, plan.condition)
    if isinstance(plan, L.Limit):
        return L.Limit(plan.n, kids[0], plan.offset)
    if isinstance(plan, L.Union):
        return L.Union(kids)
    assert not kids, f"unknown parent node {type(plan).__name__}"
    return plan


# one planner at a time: Overrides.apply writes process-wide state
_APPLY_LOCK = threading.RLock()


class Overrides:
    """The rewrite rule (GpuOverrides analog)."""

    def __init__(self, conf: Optional[C.RapidsConf] = None,
                 shuffle_partitions: int = 4):
        self.conf = conf or C.RapidsConf()
        self.shuffle_partitions = shuffle_partitions
        self.cache_hit = False  # whether apply() was answered by the memo

    def _apply_path_rules(self, plan: L.LogicalPlan) -> None:
        """Rewrite scan paths per the configured replacement rules before
        anything reads footers (AlluxioUtils analog; io/paths.py). Rewrites
        from each node's preserved original paths so re-planning under a
        different conf stays correct."""
        from spark_rapids_tpu.io.paths import replace_paths

        if isinstance(plan, L.ParquetScan):
            raw = getattr(plan, "_raw_paths", None)
            if raw is None:
                raw = list(plan.paths)
                plan._raw_paths = raw
            plan.paths = replace_paths(raw, self.conf)
        for c in plan.children:
            self._apply_path_rules(c)

    # -- tag ---------------------------------------------------------------
    def wrap_and_tag(self, plan: L.LogicalPlan) -> PlanMeta:
        meta = PlanMeta(plan, [self.wrap_and_tag(c) for c in plan.children])
        if not C.SQL_ENABLED.get(self.conf):
            meta.will_not_work("spark.rapids.tpu.sql.enabled is false")
            return meta
        self._tag(meta)
        return meta

    def _tag(self, meta: PlanMeta) -> None:
        node = meta.node
        child_schema = (node.children[0].schema if node.children else None)
        # all scalar types (incl. DECIMAL128 two-limb) are device
        # REPRESENTABLE; per-operation wide-decimal support is gated in
        # check_expr / the node-specific blocks below
        if isinstance(node, L.Project):
            for e in node.exprs:
                for r in check_expr(e, child_schema):
                    meta.will_not_work(r)
        elif isinstance(node, L.Filter):
            for r in check_expr(node.condition, child_schema):
                meta.will_not_work(r)
        elif isinstance(node, L.Aggregate):
            for e in list(node.group_exprs) + list(node.agg_exprs):
                for r in check_expr(e, child_schema):
                    meta.will_not_work(r)
            for e in node.group_exprs:
                try:
                    gdt = E.resolve(e, child_schema).dtype
                    if _is_wide(gdt):
                        meta.will_not_work(
                            "decimal128 group key not on device")
                    if isinstance(gdt, (T.StructType, T.MapType,
                                        T.ArrayType)):
                        meta.will_not_work(
                            "nested group key not on device")
                except (TypeError, KeyError):
                    pass
        elif isinstance(node, L.Sort):
            for o in node.orders:
                for r in check_expr(o.child, child_schema):
                    meta.will_not_work(r)
                try:
                    sdt = E.resolve(o.child, child_schema).dtype
                    if isinstance(sdt, (T.StructType, T.MapType,
                                        T.ArrayType)):
                        meta.will_not_work("nested sort key not on device")
                except (TypeError, KeyError):
                    pass
        elif isinstance(node, L.Window):
            from spark_rapids_tpu.exprs import window as W

            for e in node.window_exprs:
                inner = e.child if isinstance(e, E.Alias) else e
                if not isinstance(inner, W.WindowExpression):
                    meta.will_not_work(f"not a window expression: {e!r}")
                    continue
                for p in inner.spec.partition_by:
                    for r in check_expr(p, child_schema):
                        meta.will_not_work(r)
                    pass  # wide-decimal partition keys sort/compare on
                    # device via two-limb sortable keys
                for o in inner.spec.order_by:
                    for r in check_expr(o.child, child_schema):
                        meta.will_not_work(r)
                    pass  # wide-decimal order keys: two-limb sort keys
                # the window function's inputs and result type must be
                # device-representable (e.g. sum(sum(decimal)) promotes
                # past DECIMAL64 -> CPU window)
                fn = inner.function
                for c in getattr(fn, "children", ()) or ():
                    for r in check_expr(c, child_schema):
                        meta.will_not_work(r)
                try:
                    bound_fn = E.resolve(fn, child_schema)
                    wide_fn = _is_wide(bound_fn.dtype) or any(
                        _is_wide(c.dtype)
                        for c in getattr(bound_fn, "children", ()))
                    # sum/avg/count/first/last ride the 128-bit prefix
                    # scans; min/max and the rest stay on the CPU engine
                    if wide_fn and not isinstance(
                            bound_fn, (E.Sum, E.Average, E.Count,
                                       E.First, E.Last)):
                        meta.will_not_work(
                            "decimal128 window function not on device")
                except (TypeError, KeyError, NotImplementedError) as ex:
                    meta.will_not_work(str(ex))
                # frame support (reference: GpuWindowExecMeta tags frame
                # kinds; unsupported frames must FALL BACK, not crash)
                fr = inner.spec.resolved_frame()
                bounded_range = (fr.kind == "range"
                                 and not fr.is_unbounded_both
                                 and not fr.is_running
                                 and not (fr.start == 0
                                          and fr.end is None))
                if bounded_range:
                    # device value-search (bisect) frames need a single
                    # ASCENDING integral/date order key
                    obs = inner.spec.order_by
                    ok = len(obs) == 1 and obs[0].ascending
                    if ok:
                        try:
                            odt = E.resolve(obs[0].child, child_schema).dtype
                            ok = (odt in (T.BYTE, T.SHORT, T.INT, T.LONG,
                                          T.DATE, T.TIMESTAMP)
                                  and not isinstance(odt, T.DecimalType))
                        except (TypeError, KeyError):
                            ok = False
                    if not ok:
                        meta.will_not_work(
                            "bounded RANGE frame needs one ascending "
                            "integral/date order key on device")
                if isinstance(fn, (E.Skewness, E.Kurtosis)):
                    meta.will_not_work(
                        "skewness/kurtosis window functions not on device")
        elif isinstance(node, L.Join):
            for e, s in ([(k, node.left.schema) for k in node.left_keys]
                         + [(k, node.right.schema) for k in node.right_keys]):
                for r in check_expr(e, s):
                    meta.will_not_work(r)
                try:
                    jdt = E.resolve(e, s).dtype
                    if _is_wide(jdt):
                        meta.will_not_work(
                            "decimal128 join key not on device")
                    if isinstance(jdt, (T.StructType, T.MapType,
                                        T.ArrayType)):
                        meta.will_not_work("nested join key not on device")
                except (TypeError, KeyError):
                    pass
            if node.condition is not None:
                pair = T.Schema(list(node.left.schema) + list(node.right.schema))
                for r in check_expr(node.condition, pair):
                    meta.will_not_work(r)
            # join gathers can duplicate rows; var-width STRUCT CHILDREN
            # have no per-child output byte bound yet (top-level strings and
            # map entries do) — such payloads stay on CPU
            for s in (node.left.schema, node.right.schema):
                for f in s:
                    if _struct_has_varwidth(f.dtype):
                        meta.will_not_work(
                            f"struct column {f.name} with var-width fields "
                            "not on device in joins")

    # -- convert -----------------------------------------------------------
    def _rewrite_distinct(self, plan: L.LogicalPlan) -> L.LogicalPlan:
        """Spark-style distinct-aggregate rewrite for the device engine.

        Aggregate(keys, [.., CountDistinct(x), ..]) becomes: the regular
        aggregate (distinct aggs dropped) joined with, per distinct agg, a
        Count over the (keys, x)-distinct sub-aggregate. The global case
        joins on a constant key. Nullable group keys stay unrewritten (the
        join would drop null-key groups) and fall back to the CPU aggregate,
        which implements count-distinct natively.
        (Reference: Spark's RewriteDistinctAggregates, which the plugin
        relies on upstream.)
        """
        kids = [self._rewrite_distinct(c) for c in plan.children]
        if kids != list(plan.children):
            plan = _with_children(plan, kids)
        if not isinstance(plan, L.Aggregate):
            return plan
        dist = [(i, e) for i, e in enumerate(plan.agg_exprs)
                if isinstance(e.child if isinstance(e, E.Alias) else e,
                              E.CountDistinct)]
        if not dist:
            return plan
        from spark_rapids_tpu.exec.aggregate import _strip_alias

        child_schema = plan.child.schema
        key_names = []
        for e in plan.group_exprs:
            b = E.resolve(e, child_schema)
            inner, name = _strip_alias(b)
            if not isinstance(inner, E.ColumnRef) or inner.nullable:
                return plan  # CPU fallback handles it natively
            key_names.append(name)

        def named(e):
            return _strip_alias(e)[1]

        regular = [e for i, e in enumerate(plan.agg_exprs)
                   if i not in {i0 for i0, _ in dist}]
        if key_names:
            reg_plan: L.LogicalPlan = L.Aggregate(
                list(plan.group_exprs), regular, plan.child)
            join_keys = key_names
        else:
            # global aggregate: join the one-row results on a constant key
            reg_plan = L.Project(
                [E.col(f.name) for f in
                 L.Aggregate([], regular, plan.child).schema]
                + [E.Alias(E.Literal(1, T.INT), "#one")],
                L.Aggregate([], regular, plan.child))
            join_keys = ["#one"]
        for n, (_, e) in enumerate(dist):
            func, name = _strip_alias(e)
            x_alias = f"#dx{n}"
            distinct_sub = L.Aggregate(
                list(plan.group_exprs) + [E.Alias(func.children[0], x_alias)],
                [], plan.child)
            cnt = L.Aggregate(
                [E.col(k) for k in key_names],
                [E.Alias(E.Count(E.col(x_alias)), name)], distinct_sub)
            if not key_names:
                cnt = L.Project(
                    [E.col(name), E.Alias(E.Literal(1, T.INT), "#one")], cnt)
            reg_plan = L.Join(reg_plan, cnt,
                              [E.col(k) for k in join_keys],
                              [E.col(k) for k in join_keys])
        # restore the original column order
        out = [E.col(named(e)) for e in plan.group_exprs] + \
              [E.col(named(e)) for e in plan.agg_exprs]
        return L.Project(out, reg_plan)

    def _fastpath_eligible(self, plan: L.LogicalPlan) -> bool:
        """True when every scan leaf is provably below the fastpath
        row/byte thresholds — sizes read from in-memory tables and parquet
        footers only (cbo.estimate_rows reads the same metadata). Any leaf
        we cannot bound disqualifies the query; an estimate that later
        grows only costs speed (single partition), never correctness."""
        if not self.conf[C.FASTPATH_ENABLED]:
            return False
        import os as _os

        rows = 0
        nbytes = 0
        stack = [plan]
        while stack:
            n = stack.pop()
            if n.children:
                stack.extend(n.children)
                continue
            if isinstance(n, L.InMemoryScan):
                rows += n.table.num_rows
                nbytes += n.table.nbytes
            elif isinstance(n, L.ParquetScan):
                if len(n.paths) > 16:
                    return False  # footer reads would swamp the win
                try:
                    import pyarrow.parquet as _pq

                    for p in n.paths:
                        rows += _pq.ParquetFile(p).metadata.num_rows
                        nbytes += _os.path.getsize(p)
                except Exception:
                    return False
            else:
                return False
        return (rows <= self.conf[C.FASTPATH_MAX_ROWS]
                and nbytes <= self.conf[C.FASTPATH_MAX_BYTES])

    def apply(self, plan: L.LogicalPlan) -> TpuExec:
        # Planning mutates process-wide state (active conf, faults/journal/
        # memtrack configuration, the plan memo) — one query plans at a
        # time so concurrent submissions (serve/) can't interleave those
        # writes. Execution itself runs outside this lock.
        with _APPLY_LOCK:
            return self._apply_locked(plan)

    def _apply_locked(self, plan: L.LogicalPlan) -> TpuExec:
        import time as _time

        from spark_rapids_tpu.exec import base as _base

        # session settings visible to exec-layer code without a threaded
        # conf (shrink pass, kernel caps) — the reference similarly
        # re-reads RapidsConf per plan (GpuOverrides.scala:4748)
        C.set_active(self.conf)
        from spark_rapids_tpu import faults as _faults
        _faults.configure(self.conf)
        _base.set_sync_metrics(self.conf[C.METRICS_SYNC])
        _base.set_metrics_level(self.conf[C.METRICS_LEVEL])
        from spark_rapids_tpu.obs import events as _journal
        from spark_rapids_tpu.obs import histo as _histo
        _journal.set_enabled(self.conf[C.METRICS_JOURNAL_ENABLED])
        _journal.set_capacity(self.conf[C.METRICS_JOURNAL_CAPACITY])
        _histo.set_enabled(self.conf[C.METRICS_HISTOGRAM_ENABLED])
        from spark_rapids_tpu.obs import memtrack as _mt
        _mt.configure(self.conf)
        from spark_rapids_tpu.plan import autotune as _at
        _at.configure(self.conf)
        prof = None
        if self.conf[C.PROFILE_ENABLED]:
            # per-query profile created up front so the planning phases
            # below journal in lifecycle order (submit -> plan-rewrite ->
            # reuse -> fusion); gauge/compile baselines are still taken at
            # start(), after planning, so the execute window stays clean
            from spark_rapids_tpu.obs import QueryProfile

            prof = QueryProfile(description=plan.describe(), conf=self.conf,
                                capture_trace=self.conf[C.PROFILE_TRACE])
        # plan-rewrite memo (plan/plan_cache.py): a repeat arrival of a
        # rename-equal plan under the same conf reuses the physical tree
        # built the first time instead of re-running the whole pipeline
        from spark_rapids_tpu.plan import plan_cache as _pc

        memo_key = None
        pinned: list = []
        if self.conf[C.PLAN_CACHE_ENABLED]:
            t_lk = _time.perf_counter_ns()
            memo_key = _pc.build_key(plan, self.conf,
                                     self.shuffle_partitions, pinned)
            entry = _pc.lookup(memo_key) if memo_key is not None else None
            if entry is not None:
                self.cache_hit = True
                lookup_ns = _time.perf_counter_ns() - t_lk
                if C.EXPLAIN.get(self.conf) != "NONE":
                    print("[plan-cache hit]\n" + entry.explain)
                if prof is not None:
                    prof.note_phase("plan-cache", lookup_ns)
                    prof.plan_explain = "[plan-cache hit]\n" + entry.explain
                    prof.start().attach(entry.ex)
                return entry.ex
        # small-query fast path: when every scan leaf is provably tiny the
        # fixed per-query machinery (shuffle, prefetch threads, semaphore)
        # costs more than the data — plan one partition and skip it all
        fastpath = self._fastpath_eligible(plan)
        orig_parts = self.shuffle_partitions
        if fastpath:
            self.shuffle_partitions = 1
        t0 = _time.perf_counter_ns()
        if C.SQL_ENABLED.get(self.conf):
            plan = self._rewrite_distinct(plan)
        self._apply_path_rules(plan)
        meta = self.wrap_and_tag(plan)
        from spark_rapids_tpu.plan import cbo as _cbo

        if self.conf[_cbo.CBO_ENABLED]:
            _cbo.CostBasedOptimizer(self.conf).optimize(meta)
        ex = self._convert(meta)
        self.shuffle_partitions = orig_parts
        t1 = _time.perf_counter_ns()
        # computation reuse BEFORE fusion: fused stages must see the
        # ReusedExchange/ReusedBroadcast leaves so a deduped subtree is
        # never re-fused (and rebuilt) per consumer (plan/reuse.py)
        from spark_rapids_tpu.plan.reuse import apply_reuse

        ex = apply_reuse(ex, self.conf)
        t2 = _time.perf_counter_ns()
        if C.FUSION_ENABLED.get(self.conf):
            from spark_rapids_tpu.exec.fused import fuse_exec

            ex = fuse_exec(ex, min_ops=C.FUSION_MIN_OPERATORS.get(self.conf),
                           agg_window=C.FUSION_AGG_WINDOW.get(self.conf))
        t3 = _time.perf_counter_ns()
        # async pipeline boundaries go in AFTER fusion: a fused stage is one
        # consumer, and its scan/shuffle inputs are exactly the seams the
        # prefetch workers overlap (exec/pipeline.py). The fast path skips
        # them: for a tiny single-partition query the worker threads cost
        # more than the overlap buys.
        if not fastpath:
            from spark_rapids_tpu.exec.pipeline import insert_prefetch

            ex = insert_prefetch(ex, self.conf)
        ex._fastpath = fastpath
        t4 = _time.perf_counter_ns()
        mode = C.EXPLAIN.get(self.conf)
        if mode != "NONE":
            print(explain(meta, mode))
        explain_all = (explain(meta, "ALL")
                       if memo_key is not None or prof is not None else "")
        if memo_key is not None:
            _pc.store(memo_key, ex, explain_all, fastpath, pinned,
                      self.conf)
        if prof is not None:
            prof.note_phase("plan-rewrite", t1 - t0)
            prof.note_phase("reuse", t2 - t1)
            prof.note_phase("fusion", t3 - t2)
            prof.note_phase("prefetch", t4 - t3)
            prof.plan_explain = explain_all
            prof.start().attach(ex)
        return ex

    def _convert(self, meta: PlanMeta) -> TpuExec:
        node = meta.node
        on_dev = meta.can_run_on_device
        if not on_dev and not C.CPU_FALLBACK_ENABLED.get(self.conf):
            raise NotImplementedError(
                f"{node.describe()} can't run on device: {meta.reasons}")
        kids = [self._convert(c) for c in meta.children]

        if isinstance(node, L.ParquetScan):
            if not on_dev:
                from spark_rapids_tpu.plan.cpu import CpuParquetScanExec

                return CpuParquetScanExec(node.paths, node.columns)
            return ParquetScanExec(
                node.paths, columns=node.columns, predicate=node.predicate,
                n_partitions=max(1, min(len(node.paths),
                                        self.shuffle_partitions)))
        if isinstance(node, L.InMemoryScan):
            if not on_dev:
                from spark_rapids_tpu.plan.cpu import CpuInMemoryScanExec

                return CpuInMemoryScanExec(node.table)
            return BatchSourceExec(
                _device_source_parts(node.table, node.batch_rows,
                                     node.partitions), node.schema)
        if isinstance(node, L.Project):
            return (ProjectExec(node.exprs, kids[0]) if on_dev
                    else CpuProjectExec(node.exprs, kids[0]))
        if isinstance(node, L.Filter):
            return (FilterExec(node.condition, kids[0]) if on_dev
                    else CpuFilterExec(node.condition, kids[0]))
        if isinstance(node, L.Aggregate):
            return self._convert_aggregate(node, kids[0], on_dev)
        if isinstance(node, L.Window):
            return self._convert_window(node, kids[0], on_dev)
        if isinstance(node, L.Sort):
            return self._convert_sort(node, kids[0], on_dev)
        if isinstance(node, L.Join):
            return self._convert_join(node, kids, on_dev)
        if isinstance(node, L.Limit):
            return (GlobalLimitExec(node.n, kids[0], offset=node.offset)
                    if on_dev else CpuLimitExec(node.n, kids[0], node.offset))
        if isinstance(node, L.Union):
            # widen mismatched branch types to the union schema (Spark
            # WidenSetOperationTypes inserts the same casts)
            target = node.schema
            cast_kids = []
            for ch, ex in zip(node.children, kids):
                if [f.dtype for f in ch.schema] != [f.dtype for f in target]:
                    exprs = [
                        E.Alias(E.Cast(E.col(cf.name), tf.dtype), tf.name)
                        if cf.dtype != tf.dtype else E.col(cf.name)
                        for cf, tf in zip(ch.schema, target)]
                    ex = (ProjectExec(exprs, ex) if not isinstance(
                        ex, CpuExec) else CpuProjectExec(exprs, ex))
                cast_kids.append(ex)
            kids = cast_kids
            if not on_dev:
                from spark_rapids_tpu.plan.cpu import CpuUnionExec

                return CpuUnionExec(*kids)
            return UnionExec(*kids)
        raise NotImplementedError(type(node).__name__)

    def _convert_aggregate(self, node: L.Aggregate, child: TpuExec,
                           on_dev: bool) -> TpuExec:
        if not on_dev:
            from spark_rapids_tpu.plan.cpu_agg import CpuAggregateExec

            return CpuAggregateExec(node.group_exprs, node.agg_exprs, child)
        if self._planned_parts(child) == 1:
            return HashAggregateExec(node.group_exprs, node.agg_exprs, child,
                                     mode="complete")
        partial = HashAggregateExec(node.group_exprs, node.agg_exprs, child,
                                    mode="partial")
        n_keys = len(node.group_exprs)
        if n_keys == 0:
            exchange: TpuExec = ShuffleExchangeExec(SinglePartitioner(),
                                                    partial)
        else:
            partial._prepare()
            # string keys carry a precomputed hash column (#gh1) in the
            # buffer schema: partition on it instead of re-hashing bytes
            part_cols = ([n_keys] if partial._hash_carry
                         else list(range(n_keys)))
            exchange = ShuffleExchangeExec(
                HashPartitioner(part_cols, self.shuffle_partitions),
                partial)
            exchange = self._maybe_aqe_read(exchange)
        return HashAggregateExec.final_from_partial(partial, exchange)

    def _maybe_aqe_read(self, exchange: TpuExec) -> TpuExec:
        """Wrap a hash/range exchange in an adaptive reader that coalesces
        small post-shuffle partitions (GpuCustomShuffleReaderExec analog);
        keys stay co-located so this is always sound for agg/sort."""
        if not C.AQE_ENABLED.get(self.conf):
            return exchange
        from spark_rapids_tpu.shuffle.aqe import AQEShuffleReadExec

        return AQEShuffleReadExec(exchange, self.conf)

    def _convert_window(self, node: L.Window, child: TpuExec,
                        on_dev: bool) -> TpuExec:
        if not on_dev:
            from spark_rapids_tpu.plan.cpu_agg import CpuWindowExec

            return CpuWindowExec(node.window_exprs, child)
        from spark_rapids_tpu.exec.misc import CoalesceBatchesExec
        from spark_rapids_tpu.exec.window import WindowExec
        from spark_rapids_tpu.exprs import window as W

        first = node.window_exprs[0]
        inner = first.child if isinstance(first, E.Alias) else first
        spec: W.WindowSpec = inner.spec
        if self._planned_parts(child) > 1:
            # co-partition rows by the window partition keys (hash exchange
            # when they are plain columns; otherwise everything to one
            # partition, Spark's single-partition window warning case)
            key_idx = []
            cs = child.output_schema
            for p in spec.partition_by:
                b = E.resolve(p, cs)
                if isinstance(b, E.ColumnRef):
                    key_idx.append(b.index)
                else:
                    key_idx = []
                    break
            if key_idx:
                exchange: TpuExec = ShuffleExchangeExec(
                    HashPartitioner(key_idx, self.shuffle_partitions), child)
                exchange = self._maybe_aqe_read(exchange)
            else:
                exchange = ShuffleExchangeExec(SinglePartitioner(), child)
            child = exchange
        # batch-streaming window groups (running / bounded-context — the
        # GpuRunningWindowExec / GpuBatchedBoundedWindowExec analogs,
        # GpuWindowExecMeta.scala:262-299) take a (partition, order)-sorted
        # STREAM of batches: out-of-core sort upstream, no single-batch
        # coalesce, so a window partition never has to fit in one batch.
        mode = WindowExec.plan_stream_mode(node.window_exprs,
                                           child.output_schema)
        if (mode is not None
                and C.WINDOW_STREAMING_ENABLED.get(self.conf)):
            from spark_rapids_tpu.exec.sort import SortExec
            orders = ([SortOrder(p) for p in spec.partition_by]
                      + list(spec.order_by))
            child = SortExec(
                orders, child, out_of_core=True,
                target_rows=C.SORT_OOC_TARGET_ROWS.get(self.conf))
            return WindowExec(node.window_exprs, child, streaming=True)
        # remaining frame shapes compute over one batch per partition
        child = CoalesceBatchesExec(child, require_single=True)
        return WindowExec(node.window_exprs, child)

    def _convert_sort(self, node: L.Sort, child: TpuExec,
                      on_dev: bool) -> TpuExec:
        if not on_dev:
            srt = CpuSortExec(node.orders, child)
            if node.limit is not None:
                from spark_rapids_tpu.plan.cpu import CpuLimitExec

                return CpuLimitExec(node.limit, srt, 0)
            return srt
        if node.limit is not None:
            from spark_rapids_tpu.exec.misc import take_ordered_and_project

            return take_ordered_and_project(node.orders, node.limit, child)
        if node.is_global and self._planned_parts(child) > 1:
            child = self._range_exchange(node, child)
        return SortExec(node.orders, child)

    def _range_exchange(self, node: L.Sort, child: TpuExec) -> TpuExec:
        """Sample the first sort key to build range bounds (GpuRangePartitioner
        sample-based bounds)."""
        first = node.orders[0]
        bound = E.resolve(first.child, child.output_schema)
        assert isinstance(bound, E.ColumnRef)
        if bound.dtype in (T.STRING, T.BINARY) or len(node.orders) > 1:
            # fall back to a single partition merge for non-range-able keys
            return ShuffleExchangeExec(SinglePartitioner(), child)
        from spark_rapids_tpu.columnar.batch import batch_to_arrow

        samples = []
        for p in range(child.num_partitions()):
            for b in child.execute(p):
                t = batch_to_arrow(b, child.output_schema)
                col = t.column(bound.index).drop_null().to_numpy(
                    zero_copy_only=False)
                if len(col):
                    samples.append(np.random.default_rng(0).choice(
                        col, min(len(col), 256)))
                break  # sample only the first batch per partition
        values = np.concatenate(samples) if samples else np.zeros(0)
        part = RangePartitioner.from_sample(
            values, self.shuffle_partitions, bound.index, first.ascending,
            first.nulls_first)
        # adjacent range partitions stay globally ordered when coalesced
        return self._maybe_aqe_read(ShuffleExchangeExec(part, child))

    def _convert_join(self, node: L.Join, kids: List[TpuExec],
                      on_dev: bool) -> TpuExec:
        left, right = kids
        if not on_dev:
            from spark_rapids_tpu.plan.cpu_agg import CpuJoinExec

            return CpuJoinExec(node.left_keys, node.right_keys,
                               node.join_type, left, right, node.condition)
        probe = left  # pre-exchange subtree the DPP scan walk descends
        # size-based strategy (GpuShuffledSizedHashJoinExec analog): a
        # small estimated build side broadcasts — neither side is
        # exchanged, the build executes once and is shared by every probe
        # partition (GpuBroadcastHashJoinExecBase)
        from spark_rapids_tpu.exec.join_bcast import BroadcastHashJoinExec
        from spark_rapids_tpu.plan import cbo as CBO

        if (self._planned_parts(left) > 1
                and node.join_type in BroadcastHashJoinExec.BROADCAST_TYPES
                and CBO.estimate_rows(node.right)
                <= C.JOIN_BROADCAST_ROWS.get(self.conf)):
            from spark_rapids_tpu.exec.dpp import ReplayExec

            cached = ReplayExec(right)
            self._try_dynamic_pruning(node, probe, cached)
            return BroadcastHashJoinExec(
                node.left_keys, node.right_keys, node.join_type,
                left, cached, condition=node.condition)
        if self._planned_parts(left) > 1:
            # shuffled join: co-partition both sides by key hash
            lk = [self._key_index(k, node.left.schema) for k in node.left_keys]
            rk = [self._key_index(k, node.right.schema) for k in node.right_keys]
            lex = ShuffleExchangeExec(
                HashPartitioner(lk, self.shuffle_partitions), left)
            rex = ShuffleExchangeExec(
                HashPartitioner(rk, self.shuffle_partitions), right)
            if C.AQE_ENABLED.get(self.conf):
                from spark_rapids_tpu.shuffle.aqe import pair_for_skew_join

                left, right = pair_for_skew_join(
                    lex, rex, node.join_type, self.conf)
            else:
                left, right = lex, rex
            # build = the RAW right exchange, not the AQE-paired reader: DPP
            # key collection still reuses the same materialized shuffle
            # blocks the join reads, but consulting the paired reader here
            # would re-enter the skew planner (and the left exchange's write
            # lock) from inside the left stage's own write — deadlock
            self._try_dynamic_pruning(node, probe, rex)
        elif self._planned_parts(right) > 1:
            # broadcast-style: collapse the build side into the stream's
            # single partition (GpuBroadcastHashJoin analog)
            right = ShuffleExchangeExec(SinglePartitioner(), right)
            self._try_dynamic_pruning(node, probe, right)
        else:
            # no exchange to reuse: materialize the build side once and
            # share it between the runtime filter and the join
            from spark_rapids_tpu.exec.dpp import ReplayExec

            cached = ReplayExec(right)
            if self._try_dynamic_pruning(node, probe, cached):
                right = cached
        return HashJoinExec(node.left_keys, node.right_keys, node.join_type,
                            left, right, condition=node.condition,
                            max_candidate_rows=C.JOIN_MAX_OUTPUT_ROWS.get(
                                self.conf))

    def _try_dynamic_pruning(self, node: L.Join, probe: TpuExec,
                             build: TpuExec) -> bool:
        """Attach a runtime key filter from the join's build side to a
        parquet scan under the probe (left) subtree, when dropping provably
        unmatched probe rows cannot change the join result
        (GpuDynamicPruningExpression analog; exec/dpp.py). ``build`` should
        be the join's actual build child (exchange / replay-cached) so key
        collection reuses the join's own materialization. Returns whether a
        filter was attached."""
        if not C.DPP_ENABLED.get(self.conf):
            return False
        # sound only when unmatched LEFT rows are never emitted
        if node.join_type not in ("inner", "left_semi", "right"):
            return False
        from spark_rapids_tpu.exec.dpp import DynamicPruningFilter

        # descend through schema-preserving operators only (a projection
        # could rename/derive the key column)
        cur = probe
        while isinstance(cur, (FilterExec, CoalesceBatchesExec)):
            cur = cur.children[0]
        if not isinstance(cur, ParquetScanExec):
            return False
        scan_cols = {f.name for f in cur.output_schema}
        attached = False
        for lk, rk in zip(node.left_keys, node.right_keys):
            try:
                lb = E.resolve(lk, node.left.schema)
                rb = E.resolve(rk, node.right.schema)
            except (TypeError, KeyError, NotImplementedError):
                continue
            if not isinstance(lb, E.ColumnRef) or lb.name not in scan_cols:
                continue
            if not isinstance(rb, E.ColumnRef):
                continue
            cur.dynamic_filters.append(DynamicPruningFilter(
                build, rb.index, lb.name,
                max_values=C.DPP_MAX_KEYS.get(self.conf)))
            attached = True
        return attached

    @staticmethod
    def _planned_parts(node: TpuExec) -> int:
        """Partition count for plan decisions without materializing stages
        (AQE readers answer with their pre-materialization estimate)."""
        from spark_rapids_tpu.shuffle.aqe import planning_scope

        with planning_scope():
            return node.num_partitions()

    @staticmethod
    def _key_index(k: E.Expression, schema: T.Schema) -> int:
        b = E.resolve(k, schema)
        assert isinstance(b, E.ColumnRef)
        return b.index


# ---------------------------------------------------------------------------
# explain
# ---------------------------------------------------------------------------


def explain(meta: PlanMeta, mode: str = "ALL") -> str:
    """Render the tag decisions (spark.rapids.sql.explain analog)."""
    lines: List[str] = []

    def walk(m: PlanMeta, depth: int):
        mark = "*" if m.can_run_on_device else "!"
        if mode == "ALL" or not m.can_run_on_device:
            line = f"{'  ' * depth}{mark} {m.node.describe()}"
            if m.reasons:
                line += "  cannot run on TPU because " + "; ".join(m.reasons)
            lines.append(line)
        for c in m.children:
            walk(c, depth + 1)

    walk(meta, 0)
    return "\n".join(lines) if lines else "(entire plan runs on TPU)"

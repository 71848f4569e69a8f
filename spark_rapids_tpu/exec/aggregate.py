"""Hash aggregation: sort-based segmented aggregation on device.

Reference: GpuHashAggregateExec (GpuAggregateExec.scala:1868) with its
partial-per-batch / merge / final-pass pipeline (GpuAggFirstPassIterator:742,
GpuMergeAggregateIterator:913, GpuAggFinalPassIterator:772). TPU-first
re-design:

- one fused XLA computation does pre-projection + grouping (hash-sort +
  exact-verified segment split, kernels.group_rows) + every segmented
  reduction for a batch — no per-aggregation kernel launches;
- cross-batch merge = device concat of partial buffers + one more grouped
  reduction over merge ops (sums of sums etc.), looped until a single batch
  remains — the analog of the reference's merge pass. The reference's
  repartition-fallback for oversized agg state maps to the split/retry
  machinery (mem/) + shuffle-level partials in the distributed plan.

Aggregate buffer layout per function (Spark-exact result types):
  Sum      -> [sum]              Count     -> [count]
  Min/Max  -> [min]/[max]        Average   -> [sum, count]
  First    -> [first]            Last      -> [last]
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, bucket_capacity
from spark_rapids_tpu.columnar.column import ColVal, DeviceColumn
from spark_rapids_tpu.exec.base import TpuExec, UnaryExec
from spark_rapids_tpu.exec import kernels as K
from spark_rapids_tpu.exprs import expr as E
from spark_rapids_tpu.exprs import eval as EV
from spark_rapids_tpu.utils.sync import host_get


@dataclasses.dataclass
class _AggSpec:
    """Lowered aggregate: which pre-projected input feeds which buffer ops."""

    func: E.AggregateExpression
    name: str
    input_index: Optional[int]  # index into the pre-projection, None = count(*)
    ops: List[str]  # per-buffer update op
    buffer_types: List[T.DataType]
    # two-input aggregates (corr/covar): per-op pre-projection index
    input_indices: Optional[List[Optional[int]]] = None
    # min_by/max_by: pre-projection index of the ORDERING column
    aux_index: Optional[int] = None

    @property
    def result_type(self) -> T.DataType:
        return self.func.dtype


# ---------------------------------------------------------------------------
# oversized-state repartition bookkeeping (reference: the repartition-based
# fallback of GpuAggregateExec.scala:208-314). Module-level so obs/gauges can
# export ``agg_repartition_total`` and obs/memtrack postmortems can name the
# bucket a thread was merging when the pool denied it.
# ---------------------------------------------------------------------------

_repart_lock = threading.Lock()
_repart_stats = {"total": 0, "max_depth": 0}
_active_repart: Dict[int, Tuple[int, int]] = {}  # thread id -> (depth, bucket)


def _note_repartition(level: int) -> None:
    with _repart_lock:
        _repart_stats["total"] += 1
        _repart_stats["max_depth"] = max(_repart_stats["max_depth"], level + 1)


def repartition_snapshot() -> Dict[str, int]:
    """Process-wide repartition stats: {"total", "max_depth"} (monotonic)."""
    with _repart_lock:
        return dict(_repart_stats)


def counters() -> Dict[str, int]:
    """obs/gauges feed."""
    with _repart_lock:
        return {"agg_repartition_total": _repart_stats["total"]}


def active_repartitions() -> List[Dict[str, int]]:
    """Threads currently merging a repartition bucket (postmortem context)."""
    with _repart_lock:
        return [{"thread": t, "depth": d, "bucket": b}
                for t, (d, b) in _active_repart.items()]


@contextlib.contextmanager
def _bucket_ctx(depth: int, bucket: int):
    tid = threading.get_ident()
    with _repart_lock:
        prev = _active_repart.get(tid)
        _active_repart[tid] = (depth, bucket)
    try:
        yield
    finally:
        with _repart_lock:
            if prev is None:
                _active_repart.pop(tid, None)
            else:
                _active_repart[tid] = prev


_MERGE_OP = {"sum": "sum", "count": "sum", "count_all": "sum", "min": "min",
             "max": "max", "first": "first", "last": "last", "sumsq": "sum",
             "sum3": "sum", "sum4": "sum",
             "minby_v": "minby_v", "minby_o": "minby_o",
             "maxby_v": "maxby_v", "maxby_o": "maxby_o"}


def _lower_agg(func: E.AggregateExpression, name: str,
               input_index: Optional[int]) -> _AggSpec:
    if isinstance(func, E.Count):
        op = "count" if func.children else "count_all"
        return _AggSpec(func, name, input_index, [op], [T.LONG])
    if isinstance(func, E.Sum):
        return _AggSpec(func, name, input_index, ["sum"], [func.dtype])
    if isinstance(func, E.Min):
        return _AggSpec(func, name, input_index, ["min"], [func.dtype])
    if isinstance(func, E.Max):
        return _AggSpec(func, name, input_index, ["max"], [func.dtype])
    if isinstance(func, E.Average):
        c = func.child.dtype
        sum_t = T.DecimalType(min(38, c.precision + 10), c.scale) if isinstance(
            c, T.DecimalType) else T.DOUBLE if c in T.FRACTIONAL_TYPES else T.LONG
        return _AggSpec(func, name, input_index, ["sum", "count"], [sum_t, T.LONG])
    if isinstance(func, (E.Skewness, E.Kurtosis)):
        # raw power-sum buffers up to the 4th moment
        return _AggSpec(func, name, input_index,
                        ["sum", "sumsq", "sum3", "sum4", "count"],
                        [T.DOUBLE] * 4 + [T.LONG])
    if isinstance(func, E._VarianceBase):
        # (sum, sum_sq, n) moment buffers; the final division happens in
        # _final_project (reference: cudf VARIANCE/STD groupby aggs)
        return _AggSpec(func, name, input_index, ["sum", "sumsq", "count"],
                        [T.DOUBLE, T.DOUBLE, T.LONG])
    if isinstance(func, (E.First, E.AnyValue)):
        return _AggSpec(func, name, input_index, ["first"], [func.dtype])
    if isinstance(func, E.Last):
        return _AggSpec(func, name, input_index, ["last"], [func.dtype])
    if isinstance(func, E.BoolAnd):  # covers BoolOr (subclass)
        op = "max" if isinstance(func, E.BoolOr) else "min"
        return _AggSpec(func, name, input_index, [op], [T.INT])
    if isinstance(func, E.CountIf):
        return _AggSpec(func, name, input_index, ["sum"], [T.LONG])
    raise NotImplementedError(f"aggregate {type(func).__name__}")


def _strip_alias(e: E.Expression) -> Tuple[E.Expression, str]:
    if isinstance(e, E.Alias):
        return e.child, e.name
    name = e.name if isinstance(e, E.ColumnRef) else repr(e)
    return e, name


class HashAggregateExec(UnaryExec):
    """Group-by aggregation over one partition's batches.

    ``mode``:
      - "complete": input rows -> final results (single-stage).
      - "partial":  input rows -> (keys + partial buffers) batches.
      - "final":    (keys + partial buffers) batches -> final results.
    The partial/final split is what the distributed plan uses around a
    shuffle, mirroring Spark/the reference's partial+merge aggregate pair.
    """

    shrink_output = True
    mem_site = "agg-state"

    def __init__(self, group_exprs: Sequence[E.Expression],
                 agg_exprs: Sequence[E.Expression], child: TpuExec,
                 mode: str = "complete"):
        assert mode in ("complete", "partial", "final")
        # Filter fusion: a FilterExec feeding an aggregation becomes the
        # aggregation's contributing mask — no compaction, no gather of the
        # payload columns, no row movement at all. (The reference reaches a
        # similar shape by fusing filter iterators into the agg input;
        # on TPU skipping the gather is the single biggest win.)
        self.pre_filter: Optional[E.Expression] = None
        from spark_rapids_tpu.exec.project import FilterExec

        if mode in ("complete", "partial") and isinstance(child, FilterExec):
            self.pre_filter = child.condition
            child = child.child
        super().__init__(child)
        self.mode = mode
        self.group_exprs = list(group_exprs)
        self.agg_exprs = list(agg_exprs)
        self._prepared = False
        self._prepare_lock = threading.Lock()
        self._register_metric("numAggBatches")
        self._register_metric("concatTimeNs")
        self._register_metric("numRepartitions")

    # -- lowering ----------------------------------------------------------
    def _prepare(self):
        if self._prepared:
            return
        with self._prepare_lock:
            if self._prepared:
                return
            self._prepare_locked()

    def _prepare_locked(self):
        in_schema = self.child.output_schema
        self._group_bound = [E.resolve(e, in_schema) for e in self.group_exprs]
        self._group_names = [
            _strip_alias(e)[1] for e in self._group_bound
        ]
        n_keys = len(self._group_bound)

        self._specs: List[_AggSpec] = getattr(self, "_specs", None) or []
        pre_exprs: List[E.Expression] = list(self._group_bound)
        agg_inputs = {}  # cache_key of an aggregate's input -> its index
        if not self._specs:
            for e in self.agg_exprs:
                func, name = _strip_alias(e)
                assert isinstance(func, E.AggregateExpression), f"not an agg: {e!r}"

                def rb(i):
                    # mode "final": children were bound against the
                    # pre-shuffle schema by final_from_partial(); only
                    # dtypes are used there
                    c = func.children[i]
                    return c if self.mode == "final" else E.resolve(
                        c, in_schema)

                if isinstance(func, E._CovarianceBase):
                    cx, cy = rb(0), rb(1)
                    if cx.dtype != T.DOUBLE:
                        cx = E.Cast(cx, T.DOUBLE)
                    if cy.dtype != T.DOUBLE:
                        cy = E.Cast(cy, T.DOUBLE)
                    # Spark covariance/corr aggregate only PAIRS where both
                    # sides are non-null
                    both = E.And(E.IsNotNull(cx), E.IsNotNull(cy))
                    null_d = E.Literal(None, T.DOUBLE)

                    def mk(x):
                        return E.If(both, x, null_d)

                    exprs = [mk(cx), mk(cy), mk(E.Multiply(cx, cy))]
                    if isinstance(func, E.Corr):
                        exprs += [mk(E.Multiply(cx, cx)),
                                  mk(E.Multiply(cy, cy))]
                    idxs = []
                    for ex in exprs:
                        idxs.append(len(pre_exprs))
                        pre_exprs.append(ex)
                    self._specs.append(_AggSpec(
                        type(func)(cx, cy), name, idxs[0],
                        ["sum"] * len(exprs) + ["count"],
                        [T.DOUBLE] * len(exprs) + [T.LONG],
                        input_indices=idxs + [idxs[0]]))
                    continue
                if isinstance(func, E.MinBy):  # covers MaxBy
                    cv, co = rb(0), rb(1)
                    vi = len(pre_exprs)
                    pre_exprs.append(cv)
                    oi = len(pre_exprs)
                    pre_exprs.append(co)
                    kind = "maxby" if isinstance(func, E.MaxBy) else "minby"
                    self._specs.append(_AggSpec(
                        type(func)(cv, co), name, vi,
                        [f"{kind}_v", f"{kind}_o"], [cv.dtype, co.dtype],
                        aux_index=oi))
                    continue
                if func.children:
                    bound_child = rb(0)
                    if (isinstance(func, E._VarianceBase)
                            and bound_child.dtype != T.DOUBLE):
                        # moments are computed in f64 (Spark casts the input)
                        bound_child = E.Cast(bound_child, T.DOUBLE)
                    if isinstance(func, E.BoolAnd):
                        # int buffer: segment min/max stay off bool dtype
                        bound_child = E.Cast(bound_child, T.INT)
                    if isinstance(func, E.CountIf):
                        bound_child = E.Cast(
                            E.Coalesce(bound_child, E.lit(False)), T.LONG)
                    func = type(func)(bound_child)
                    # one pre-projected column per distinct input:
                    # Sum(x) and Average(x) read the same one
                    idx = agg_inputs.setdefault(bound_child.cache_key(),
                                                len(pre_exprs))
                    if idx == len(pre_exprs):
                        pre_exprs.append(bound_child)
                else:
                    idx = None
                self._specs.append(_lower_agg(func, name, idx))
        self._pre_bound = tuple(pre_exprs)
        self._n_keys = n_keys
        self._filter_bound = (E.resolve(self.pre_filter, in_schema)
                              if self.pre_filter is not None else None)
        # hash-once aggregation: string group keys are hashed exactly once
        # (in the first pass); the 128-bit pair rides along as two LONG
        # buffer columns so merge passes regroup on ints, never re-hashing
        # or re-comparing bytes
        self._hash_carry = any(
            _strip_alias(e)[0].dtype in (T.STRING, T.BINARY)
            for e in self._group_bound)
        self._prepared = True

        from spark_rapids_tpu.exec.jit_cache import shared_jit

        # the key must capture EVERYTHING the traced closures depend on:
        # exprs, mode, input schema, and the fused pre-filter (keyed by
        # cache_key, not repr — repr omits non-child literals, VERDICT r5)
        base_key = ("agg", E.exprs_cache_key(self.group_exprs),
                    E.exprs_cache_key(self.agg_exprs),
                    self.mode, repr(self.child.output_schema),
                    self.pre_filter.cache_key()
                    if self.pre_filter is not None else None)
        self._base_key = base_key
        self._first_pass_fn = shared_jit(
            base_key + ("first",), lambda: self._first_pass)
        self._merge_pass_fn = shared_jit(
            base_key + ("merge",), lambda: self._merge_pass)

        self._final_project_fn = shared_jit(
            base_key + ("final",), lambda: self._final_project)

    # -- schemas -----------------------------------------------------------
    def _buffer_schema(self) -> T.Schema:
        self._prepare()
        fields = []
        for e in self._group_bound:
            inner, name = _strip_alias(e)
            fields.append(T.Field(name, inner.dtype, inner.nullable))
        if self._hash_carry:
            fields.append(T.Field("#gh1", T.LONG, False))
            fields.append(T.Field("#gh2", T.LONG, False))
        for s in self._specs:
            for bi, bt in enumerate(s.buffer_types):
                fields.append(T.Field(f"{s.name}#b{bi}", bt, True))
        return T.Schema(fields)

    @property
    def output_schema(self) -> T.Schema:
        self._prepare()
        if self.mode == "partial":
            return self._buffer_schema()
        fields = []
        for e in self._group_bound:
            inner, name = _strip_alias(e)
            fields.append(T.Field(name, inner.dtype, inner.nullable))
        for s in self._specs:
            fields.append(T.Field(s.name, s.result_type,
                                  s.func.nullable))
        return T.Schema(fields)

    def node_description(self) -> str:
        keys = ", ".join(map(repr, self.group_exprs))
        aggs = ", ".join(map(repr, self.agg_exprs))
        filt = (f" filter=[{self.pre_filter!r}]"
                if self.pre_filter is not None else "")
        return (f"TpuHashAggregate(mode={self.mode}) keys=[{keys}] "
                f"aggs=[{aggs}]{filt}")

    def _buffers_have_carry(self, buffers: ColumnarBatch) -> bool:
        """Whether a buffer batch carries the #gh1/#gh2 hash columns.

        Inferred from the column count (keys + [2 hash words] + buffers):
        complete-mode first passes never carry; partial-mode ones always do
        when a key is a plain string (_buffer_schema)."""
        n_bufs = sum(len(s.ops) for s in self._specs)
        return len(buffers.columns) == self._n_keys + 2 + n_bufs

    # -- device passes (traced) -------------------------------------------
    def _grouping(self, pre: ColumnarBatch, active):
        cap = pre.capacity
        if self._n_keys == 0:
            perm = jnp.arange(cap, dtype=jnp.int32)
            seg = jnp.zeros(cap, jnp.int32)
            num_groups = jnp.int32(1)  # global agg: always one output row
            group_starts = jnp.zeros(cap, jnp.int32)
            return K.GroupInfo(perm, seg, num_groups, group_starts)
        return K.group_rows(pre, list(range(self._n_keys)), active)

    @jax.named_scope("agg.first_pass")
    def _first_pass(self, batch: ColumnarBatch,
                    row_mask=None) -> ColumnarBatch:
        """pre-project + (fused filter) + group + per-buffer aggregations.
        ``row_mask``: the rows a filter below kept, where a fused stage
        left them in place instead of compacting (exec/fused.py)."""
        ctx = EV.EvalContext(batch)
        active = batch.active_mask() if row_mask is None else row_mask
        if self._filter_bound is not None:
            pv = EV.eval_expr(self._filter_bound, ctx)
            active = active & pv.data & pv.validity
        dense = self._dense_strides(batch)
        if dense is not None:
            return self._first_pass_dense(batch, ctx, active, dense)
        pre_cols = []
        for e in self._pre_bound:
            inner, _ = _strip_alias(e)
            if isinstance(inner, E.ColumnRef):
                # take the column as-is: keeps dictionary encoding (group-by
                # and gathers run on int32 codes, never raw bytes)
                pre_cols.append(batch.columns[inner.index])
                continue
            v = EV.eval_expr(e, ctx)
            if isinstance(v, EV.StringVal):
                pre_cols.append(DeviceColumn(T.STRING, v.data, v.validity, v.offsets))
            elif isinstance(v, EV.WideVal):
                # a DECIMAL128 input (a product of two decimals, say): both
                # limbs ride as the column, _wide_agg sums them
                pre_cols.append(DeviceColumn(e.dtype, v.lo, v.validity,
                                             data2=v.hi))
            else:
                pre_cols.append(DeviceColumn(e.dtype, v.data, v.validity))
        if not pre_cols:
            # global count(*)-only aggregation has no pre-projected columns;
            # a placeholder column carries the batch capacity through grouping
            pre_cols.append(DeviceColumn(
                T.BOOLEAN, jnp.zeros(batch.capacity, jnp.bool_),
                jnp.zeros(batch.capacity, jnp.bool_)))
        pre = ColumnarBatch(pre_cols, batch.num_rows)
        key_cols = list(range(self._n_keys))
        # hash carry is a property of the MODE, never of a batch's encoding:
        # per-batch layout decisions would concat misaligned buffers when
        # one batch dict-encoded a key and another kept it plain. Partial
        # mode carries (static shuffle schema); complete mode never does —
        # its merge pass regroups the (small) partials from the key bytes.
        use_carry = self._hash_carry and self.mode != "complete"
        if use_carry:
            h1 = K.hash_keys(pre, key_cols)
            h2 = K.hash_keys(pre, key_cols, variant=1)
            gi = K.group_rows_prehashed(h1, h2, active)
            return self._aggregate_grouped(pre, gi,
                                           [s.ops for s in self._specs],
                                           hashes=(h1, h2), row_mask=active)
        gi = self._grouping(pre, active)
        return self._aggregate_grouped(pre, gi, [s.ops for s in self._specs],
                                       row_mask=active)

    # -- dense-id aggregation path -----------------------------------------
    DENSE_MAX_IDS = 64  # masked-reduce fusion regime (kernels.dense_segment_sums)

    def _dense_strides(self, batch: ColumnarBatch):
        """Static dense-key layout, or None if ineligible.

        Eligible when every group key is a ColumnRef onto a dict-encoded or
        boolean column (static cardinality) and the combined id domain is
        small: aggregation then runs against a one-hot id matrix with no
        sort, no permutation gather and no scatter. A global aggregate (no
        keys) is the G=1 case. Integer and decimal sums and every count are
        int8 contractions over the values' own bytes on the MXU, exact in
        128 bits (kernels.dense_segment_reduce); double sums are fused
        masked reductions in f64 (kernels.dense_segment_sums)."""
        if self.mode != "complete":
            return None
        strides = []
        G = 1
        for e in self._group_bound:
            inner, _ = _strip_alias(e)
            if not isinstance(inner, E.ColumnRef):
                return None
            c = batch.columns[inner.index]
            if c.is_dict and c.dict_size > 0:
                card = c.dict_size + 1  # + null slot
            elif c.dtype == T.BOOLEAN:
                card = 3
            else:
                return None
            strides.append((inner.index, card))
            G *= card
        if G > self.DENSE_MAX_IDS:
            return None
        for s in self._specs:
            if s.input_indices is not None or s.aux_index is not None:
                return None  # multi-input aggs: sorted-segment path
            for op in s.ops:
                if op not in ("sum", "count", "count_all", "min", "max",
                              "first", "last"):
                    return None
            if s.input_index is not None:
                dt = self._pre_bound[s.input_index].dtype
                if dt in (T.STRING, T.BINARY) or isinstance(dt, T.ArrayType):
                    return None
        return strides, G

    def _first_pass_dense(self, batch: ColumnarBatch, ctx, active,
                          dense) -> ColumnarBatch:
        strides, G = dense
        cap = batch.capacity
        Gc = bucket_capacity(G, 16)
        ids = jnp.zeros(cap, jnp.int32)
        for ci, card in strides:
            c = batch.columns[ci]
            code = jnp.clip(c.data.astype(jnp.int32), 0, card - 2)
            code = jnp.where(c.validity, code, card - 1)  # null key slot
            ids = ids * card + code
        f64 = jnp.float64
        in_vals = {}
        for s in self._specs:
            ii = s.input_index
            if ii is not None and ii not in in_vals:
                in_vals[ii] = EV.eval_expr(self._pre_bound[ii], ctx)

        # Two reductions, both streaming, no scatter:
        #   flag_rows (bool 0/1) and int_rows (int64 values) -> int8
        #     contractions of each lane's own bytes against one one-hot
        #     (exact in 128 bits; the low word is Java's long-sum wrap);
        #     masked rows drop out by their id
        #   f64_rows   double values -> fused masked reductions (exact f64)
        flag_rows: List[jax.Array] = []  # counts[0] is the rows per id
        int_rows: List[jax.Array] = []
        f64_rows: List[jax.Array] = []
        plans = []  # per buffer: how to assemble from the lane outputs
        flag_cache = {}
        row_cache = {}  # dedups lanes shared by buffers (a sum and its count)

        def nullable(ii):
            return self._pre_bound[ii].nullable

        def flag_row(key, arr):
            if key not in flag_cache:
                flag_rows.append(arr)
                flag_cache[key] = len(flag_rows)
            return flag_cache[key]

        def int_row(kind, ii, valid, arr):
            # rows drop out of the reduce by their id (ids_live), so only a
            # null has to be zeroed (a computed value's overflow is one): a
            # column that holds none goes in as it is, and the contraction
            # reads it where it lies
            key = (kind, ii)
            if key not in row_cache:
                row_cache[key] = len(int_rows)
                e = _strip_alias(self._pre_bound[ii])[0]
                plain = isinstance(e, E.ColumnRef) and not e.nullable
                int_rows.append(arr if plain else jnp.where(valid, arr, 0))
            return row_cache[key]

        for s in self._specs:
            v = in_vals.get(s.input_index)
            ii = s.input_index
            for op, bt in zip(s.ops, s.buffer_types):
                if op == "count_all" or (op == "count" and not nullable(ii)):
                    plans.append(("count", 0, bt))  # row 0 = active count
                    continue
                if op == "count":
                    r = flag_row(("live", ii), active & v.validity)
                    plans.append(("count", r, bt))
                    continue
                if op in ("sumsq", "sum3", "sum4"):
                    power = {"sumsq": 2, "sum3": 3, "sum4": 4}[op]
                    live = active & v.validity
                    key = (op, ii)
                    if key not in row_cache:
                        row_cache[key] = len(f64_rows)
                        d, is_nan = K._float_canonical(v.data)
                        f64_rows.append(jnp.where(live, d ** power, 0.0))
                        row_cache[("pnan", ii)] = flag_row(
                            ("nan", ii), live & is_nan)
                    vrow = flag_row(("live", ii), live) \
                        if nullable(ii) else 0
                    plans.append(("fsum", row_cache[key],
                                  row_cache[("pnan", ii)], vrow, bt))
                    continue
                if op == "sum":
                    live = active & v.validity
                    wide_buf = (isinstance(bt, T.DecimalType)
                                and bt.precision > T.DecimalType.MAX_LONG_DIGITS)
                    if wide_buf or isinstance(v, EV.WideVal):
                        if isinstance(v, EV.WideVal):
                            # hi*2^64 + unsigned lo = (hi + [lo<0])*2^64 +
                            # signed lo: both lanes sum as signed int64
                            r = int_row("wlo", ii, v.validity, v.lo)
                            rh = int_row("whi", ii, v.validity,
                                         v.hi + (v.lo < 0))
                        else:
                            r = int_row("int", ii, v.validity, v.data)
                            rh = None
                        vrow = flag_row(("live", ii), live) \
                            if nullable(ii) else 0
                        plans.append(("wisum", r, rh, vrow, bt))
                        continue
                    if jnp.issubdtype(v.data.dtype, jnp.floating):
                        key = ("fsum", ii)
                        if key not in row_cache:
                            row_cache[key] = len(f64_rows)
                            # canonical values: NaNs -> 0 so they cannot
                            # poison the sums; NaN presence rides its own
                            # flag row
                            d, is_nan = K._float_canonical(v.data)
                            f64_rows.append(jnp.where(live, d, 0.0))
                            row_cache[("fnan", ii)] = flag_row(
                                ("nan", ii), live & is_nan)
                        nan_r = row_cache[("fnan", ii)]
                        vrow = flag_row(("live", ii), live) \
                            if nullable(ii) else 0
                        plans.append(("fsum", row_cache[key], nan_r, vrow,
                                      bt))
                        continue
                    r = int_row("int", ii, v.validity, v.data)
                    vrow = flag_row(("live", ii), live) \
                        if nullable(ii) else 0
                    plans.append(("isum", r, vrow, bt))
                    continue
                # min/max/first/last: scatter path over the tiny id domain
                if isinstance(v, EV.WideVal):
                    plans.append(("wseg", op, v, bt))
                else:
                    plans.append(("seg", op, v, bt))
        # barriers sit on the TINY (R, Gc) outputs so XLA cannot re-run a
        # whole reduction per consumer column, while the big row builds
        # still fuse INTO their reductions
        ids_live = jnp.where(active, ids, Gc)  # masked rows -> overflow slot
        ihi, ilo, counts, n_rows = jax.lax.optimization_barrier(
            K.dense_segment_reduce(int_rows, flag_rows, ids_live, Gc))
        counts = jnp.concatenate([n_rows[None], counts])
        fsums = jax.lax.optimization_barrier(
            K.dense_segment_sums(jnp.stack(f64_rows), ids, Gc)) \
            if f64_rows else None
        exists = counts[0] > 0
        g = jnp.arange(Gc, dtype=jnp.int32)
        in_domain = g < G
        exists = exists & in_domain

        # keys: decode group id -> per-key code, most-significant first
        key_cols: List[DeviceColumn] = []
        rem = g
        codes_rev = []
        for ci, card in reversed(strides):
            codes_rev.append((rem % card, ci, card))
            rem = rem // card
        for code, ci, card in reversed(codes_rev):
            c = batch.columns[ci]
            kvalid = exists & (code < card - 1)
            if c.is_dict:
                key_cols.append(DeviceColumn(
                    c.dtype, jnp.where(kvalid, code, 0).astype(jnp.int32),
                    kvalid, None, c.dictionary, c.dict_size, c.dict_max_len))
            else:
                key_cols.append(DeviceColumn(
                    T.BOOLEAN, (code == 1) & kvalid, kvalid))

        buf_cols: List[DeviceColumn] = []
        for plan in plans:
            if plan[0] == "count":
                _, r, bt = plan
                data = jnp.where(exists, counts[r].astype(jnp.int64), 0)
                # counts are never null (a rowless global agg counts 0)
                buf_cols.append(DeviceColumn(bt, data, jnp.ones(Gc, jnp.bool_)))
            elif plan[0] == "fsum":
                _, r, nan_r, vrow, bt = plan
                nan_any = counts[nan_r] > 0
                data = jnp.where(nan_any, jnp.float64(jnp.nan), fsums[r])
                valid = (counts[vrow] > 0) & exists
                data = jnp.where(valid, data, 0.0).astype(T.numpy_dtype(bt))
                buf_cols.append(DeviceColumn(bt, data, valid))
            elif plan[0] == "isum":
                _, r, vrow, bt = plan
                valid = (counts[vrow] > 0) & exists
                data = jnp.where(valid, ilo[r], 0).astype(T.numpy_dtype(bt))
                buf_cols.append(DeviceColumn(bt, data, valid))
            elif plan[0] == "wisum":
                _, r, rh, vrow, bt = plan
                valid = (counts[vrow] > 0) & exists
                hi = ihi[r] if rh is None else ihi[r] + ilo[rh]
                lo = jnp.where(valid, ilo[r], 0)
                hi = jnp.where(valid, hi, 0)
                buf_cols.append(DeviceColumn(bt, lo, valid, data2=hi))
            elif plan[0] == "wseg":
                _, op, v, bt = plan
                from spark_rapids_tpu.exec import int128 as I128

                live = active & v.validity
                idx = jnp.arange(cap, dtype=jnp.int32)
                seg = jnp.where(live, ids, Gc)
                if op in ("first", "last"):
                    pick = jnp.where(live, idx, cap if op == "first" else -1)
                    sel = (jax.ops.segment_min if op == "first"
                           else jax.ops.segment_max)(
                        pick, seg, num_segments=Gc + 1)[:Gc]
                else:
                    kh, kl = I128.sortable_keys(v.hi, v.lo)
                    if op == "min":
                        red, ident = jax.ops.segment_min, jnp.int64(2**63 - 1)
                    else:
                        red, ident = jax.ops.segment_max, jnp.int64(-2**63)
                    hm = jnp.where(live, kh, ident)
                    rh = red(hm, seg, num_segments=Gc + 1)[:Gc]
                    tie = live & (hm == rh[jnp.clip(ids, 0, Gc - 1)])
                    lm = jnp.where(tie, kl, ident)
                    rl = red(lm, seg, num_segments=Gc + 1)[:Gc]
                    isel = jnp.where(tie & (lm == rl[jnp.clip(ids, 0, Gc - 1)]),
                                     idx, cap)
                    sel = jax.ops.segment_min(isel, seg,
                                              num_segments=Gc + 1)[:Gc]
                any_v = jax.ops.segment_max(
                    live.astype(jnp.int32), seg, num_segments=Gc + 1)[:Gc] > 0
                valid = any_v & exists
                sel_c = jnp.clip(sel, 0, cap - 1)
                lo = jnp.where(valid, v.lo[sel_c], 0)
                hi = jnp.where(valid, v.hi[sel_c], 0)
                buf_cols.append(DeviceColumn(bt, lo, valid, data2=hi))
            else:
                _, op, v, bt = plan
                data, avalid = K.segment_agg(
                    v.data, v.validity, active, ids_live, Gc, op)
                valid = avalid & exists
                data = jnp.where(valid, data.astype(T.numpy_dtype(bt)),
                                 jnp.zeros((), T.numpy_dtype(bt)))
                buf_cols.append(DeviceColumn(bt, data, valid))

        if self._n_keys == 0:
            # global aggregate: exactly one output row, even over empty input
            return ColumnarBatch(key_cols + buf_cols, jnp.int32(1))
        table = ColumnarBatch(key_cols + buf_cols, jnp.int32(Gc))
        idx, n = K.filter_indices(exists, jnp.ones(Gc, jnp.bool_))
        return K.gather_batch(table, idx, n)

    @jax.named_scope("agg.merge_pass")
    def _merge_pass(self, buffers: ColumnarBatch) -> ColumnarBatch:
        """re-group partial buffers and combine with merge ops."""
        merge_ops = [[_MERGE_OP[op] for op in s.ops] for s in self._specs]
        if self._buffers_have_carry(buffers):
            h1 = buffers.columns[self._n_keys].data.astype(jnp.uint64)
            h2 = buffers.columns[self._n_keys + 1].data.astype(jnp.uint64)
            gi = K.group_rows_prehashed(h1, h2, buffers.active_mask())
            return self._aggregate_grouped(buffers, gi, merge_ops,
                                           buffers_input=True,
                                           hashes=(h1, h2))
        gi = self._grouping(buffers, buffers.active_mask())
        return self._aggregate_grouped(buffers, gi, merge_ops, buffers_input=True)

    def _aggregate_grouped(self, pre: ColumnarBatch, gi: K.GroupInfo,
                           ops_per_spec, buffers_input: bool = False,
                           hashes=None, row_mask=None) -> ColumnarBatch:
        cap = pre.capacity
        active = pre.active_mask() if row_mask is None else row_mask
        # ONE fused gather for every per-column [gi.perm] indexing below
        # (incl. the active mask as a synthetic lane): one XLA gather op
        # costs ~0.25s at 16M rows regardless of width (kernels.py note)
        perm_in = [DeviceColumn(T.BOOLEAN, active, jnp.ones(cap, jnp.bool_))]
        perm_src: dict = {}
        for ci, c in enumerate(pre.columns):
            if c.offsets is None and not c.is_wide_decimal:
                perm_src[ci] = len(perm_in)
                perm_in.append(c)
        perm_all = K.gather_columns(perm_in, gi.perm,
                                    jnp.ones(cap, jnp.bool_))
        perm_cols = {ci: perm_all[slot] for ci, slot in perm_src.items()}
        contributing = perm_all[0].data
        # sorted-segment layout: scan-based reducers instead of scatters
        seg_ends = K.segment_ends(gi.group_starts, gi.num_groups, cap)
        out_row_valid = jnp.arange(cap, dtype=jnp.int32) < gi.num_groups
        # keys: value at each group head (head -> original row via perm)
        head_rows = jnp.where(out_row_valid, gi.perm[jnp.clip(gi.group_starts, 0, cap - 1)], 0)
        out_cols: List[DeviceColumn] = list(K.gather_columns(
            pre.columns[: self._n_keys], head_rows, out_row_valid))
        if hashes is not None:
            for h in hashes:
                hv = h.astype(jnp.int64)[head_rows]
                out_cols.append(DeviceColumn(
                    T.LONG, jnp.where(out_row_valid, hv, 0), out_row_valid))
        buf_idx = self._n_keys + (2 if buffers_input and hashes is not None
                                  else 0)
        for s, ops in zip(self._specs, ops_per_spec):
            if ops and ops[0] in ("minby_v", "maxby_v"):
                out_cols.extend(self._minmax_by_agg(
                    s, pre, gi, contributing, seg_ends, out_row_valid, cap,
                    buffers_input, buf_idx))
                if buffers_input:
                    buf_idx += 2
                continue
            for bi, (op, bt) in enumerate(zip(ops, s.buffer_types)):
                if buffers_input:
                    src_i = buf_idx
                    buf_idx += 1
                elif s.input_indices is not None:
                    src_i = s.input_indices[bi]
                elif s.input_index is None:
                    src_i = None
                else:
                    src_i = s.input_index
                src = pre.columns[src_i] if src_i is not None else None
                if src is None:
                    vals = jnp.zeros(cap, jnp.int64)
                    valid = jnp.ones(cap, jnp.bool_)
                elif src_i in perm_cols:
                    vals = perm_cols[src_i].data
                    valid = perm_cols[src_i].validity
                else:
                    vals = src.data[gi.perm]
                    valid = src.validity[gi.perm]
                if (src is not None and src.is_dict
                        and op in ("min", "max", "first", "last")):
                    # dict strings: min/max/first/last reduce CODES (sorted
                    # dict -> code order is byte order, so this is exact),
                    # output keeps the dictionary. count/sum buffers are
                    # numeric and must NOT inherit the dictionary.
                    data, avalid = K.segment_agg(
                        vals, valid, contributing, gi.segment_ids, cap, op,
                        ends=seg_ends, starts=gi.group_starts)
                    v_out = avalid & out_row_valid
                    out_cols.append(DeviceColumn(
                        bt, jnp.where(v_out, data.astype(jnp.int32), 0),
                        v_out, None, src.dictionary, src.dict_size,
                        src.dict_max_len))
                    continue
                if src is not None and src.offsets is not None:
                    # min/max/first/last over strings: reduce row indices, gather
                    data, avalid = self._string_agg(src, gi, contributing, op, cap)
                    out_cols.append(
                        DeviceColumn(bt, data.data,
                                     avalid & out_row_valid, data.offsets)
                    )
                    continue
                wide_bt = (isinstance(bt, T.DecimalType) and bt.precision
                           > T.DecimalType.MAX_LONG_DIGITS)
                if src is not None and (src.is_wide_decimal or wide_bt):
                    out_cols.append(self._wide_agg(
                        src, gi, contributing, op, bt, cap, out_row_valid,
                        seg_ends))
                    continue
                seg_op = op
                if op in ("sumsq", "sum3", "sum4"):
                    power = {"sumsq": 2, "sum3": 3, "sum4": 4}[op]
                    vals = vals.astype(jnp.float64) ** power
                    seg_op = "sum"
                data, avalid = K.segment_agg(vals, valid, contributing, gi.segment_ids,
                                             cap, seg_op, ends=seg_ends,
                                             starts=gi.group_starts)
                np_t = T.numpy_dtype(bt)
                data = data.astype(np_t)
                out_cols.append(DeviceColumn(bt, jnp.where(out_row_valid & avalid, data,
                                                           jnp.zeros_like(data)),
                                             avalid & out_row_valid))
        return ColumnarBatch(out_cols, gi.num_groups)

    def _minmax_by_agg(self, s: _AggSpec, pre: ColumnarBatch,
                       gi: K.GroupInfo, contributing, seg_ends,
                       out_row_valid, cap: int, buffers_input: bool,
                       buf_idx: int) -> List[DeviceColumn]:
        """min_by/max_by: segment arg-min/max over the ordering column's
        order-preserving key, then gather the value (+ order, so merge
        passes can re-reduce). Reference: GpuMinBy/GpuMaxBy."""
        want_max = s.ops[0].startswith("maxby")
        if buffers_input:
            vsrc, osrc = pre.columns[buf_idx], pre.columns[buf_idx + 1]
        else:
            vsrc = pre.columns[s.input_index]
            osrc = pre.columns[s.aux_index]
        ov = osrc.data[gi.perm]
        ovv = osrc.validity[gi.perm]
        live = contributing & ovv
        # order-preserving uint64 key (int/date/bool/dict-code orderings;
        # floats/strings are planner-gated to the CPU engine)
        key = K._int_sortable(ov.astype(jnp.int64))
        win, any_v = K.segment_agg(key, ovv, contributing, gi.segment_ids,
                                   cap, "max" if want_max else "min",
                                   ends=seg_ends, starts=gi.group_starts)
        sel_flag = live & (key == win[jnp.clip(gi.segment_ids, 0, cap - 1)])
        pos = jnp.where(sel_flag, jnp.arange(cap, dtype=jnp.int32), cap)
        sel_pos, _ = K.segment_agg(pos, jnp.ones(cap, jnp.bool_), sel_flag,
                                   gi.segment_ids, cap, "min",
                                   ends=seg_ends, starts=gi.group_starts)
        spc = jnp.clip(sel_pos, 0, cap - 1).astype(jnp.int32)
        valid = any_v & out_row_valid
        vperm = vsrc.data[gi.perm]
        vvperm = vsrc.validity[gi.perm]
        vdata = jnp.where(valid & vvperm[spc], vperm[spc],
                          jnp.zeros_like(vperm[:1]))
        vcol = DeviceColumn(s.buffer_types[0], vdata, valid & vvperm[spc],
                            None, vsrc.dictionary, vsrc.dict_size,
                            vsrc.dict_max_len)
        odata = jnp.where(valid, ov[spc], jnp.zeros_like(ov[:1]))
        ocol = DeviceColumn(s.buffer_types[1], odata, valid, None,
                            osrc.dictionary, osrc.dict_size,
                            osrc.dict_max_len)
        return [vcol, ocol]

    def _wide_agg(self, src: DeviceColumn, gi: K.GroupInfo, contributing,
                  op: str, bt, cap: int, out_row_valid,
                  seg_ends) -> DeviceColumn:
        """Segment reduction over a DECIMAL128 (hi, lo) column — or a
        narrow int64 decimal whose sum buffer is wide (sign-extended)."""
        from spark_rapids_tpu.exec import int128 as I128

        if src.is_wide_decimal:
            lo = src.data[gi.perm]
            hi = src.data2[gi.perm]
        else:
            lo = src.data.astype(jnp.int64)[gi.perm]
            hi = jnp.where(lo < 0, jnp.int64(-1), jnp.int64(0))
        valid = src.validity[gi.perm]
        live = contributing & valid
        if op == "sum":
            # sorted segments: running sums and two gathers, no scatter-add
            # (93 ms for 2^20 int64 on the v5e; PERF.md PR 35)
            h, l, n_live = K.sorted_segment_sum_int128(
                jnp.where(live, hi, 0), jnp.where(live, lo, 0), live,
                gi.group_starts, seg_ends)
            v_out = (n_live > 0) & out_row_valid
            return DeviceColumn(bt, jnp.where(v_out, l, 0), v_out,
                                data2=jnp.where(v_out, h, 0))
        any_valid = jax.ops.segment_max(
            live.astype(jnp.int32), gi.segment_ids, num_segments=cap) > 0
        v_out = any_valid & out_row_valid
        if op in ("count", "count_all"):
            flags = contributing if op == "count_all" else live
            c = jax.ops.segment_sum(flags.astype(jnp.int64), gi.segment_ids,
                                    num_segments=cap)
            return DeviceColumn(bt, jnp.where(out_row_valid, c, 0),
                                out_row_valid)
        if op in ("min", "max", "first", "last"):
            idx = jnp.arange(cap, dtype=jnp.int32)
            if op in ("first", "last"):
                pick = jnp.where(live, idx, cap if op == "first" else -1)
                sel = (jax.ops.segment_min if op == "first"
                       else jax.ops.segment_max)(
                    pick, gi.segment_ids, num_segments=cap)
            else:
                # two-stage lexicographic reduce: signed hi, then unsigned lo
                kh, kl = I128.sortable_keys(hi, lo)
                if op == "min":
                    red, ident = jax.ops.segment_min, jnp.int64(2**63 - 1)
                else:
                    red, ident = jax.ops.segment_max, jnp.int64(-2**63)
                hm = jnp.where(live, kh, ident)
                rh = red(hm, gi.segment_ids, num_segments=cap)
                tie = live & (hm == rh[gi.segment_ids])
                lm = jnp.where(tie, kl, ident)
                rl = red(lm, gi.segment_ids, num_segments=cap)
                isel = jnp.where(tie & (lm == rl[gi.segment_ids]), idx, cap)
                sel = jax.ops.segment_min(isel, gi.segment_ids,
                                          num_segments=cap)
            rows = gi.perm[jnp.clip(sel, 0, cap - 1)]
            out = K.gather_column(src, rows, v_out)
            return DeviceColumn(bt, out.data, v_out, data2=out.data2)
        raise NotImplementedError(f"decimal128 segment {op}")

    def _string_agg(self, src: DeviceColumn, gi: K.GroupInfo, contributing,
                    op: str, cap: int):
        live = contributing & src.validity[gi.perm]
        if op in ("min", "max"):
            # order by 16-byte prefix keys (round-1 string min/max precision):
            # reduce the high word, then the low word among high-word ties
            pk = K.string_prefix_keys(src)
            hi, lo = pk[0][gi.perm], pk[1][gi.perm]
            ident = jnp.uint64(0xFFFFFFFFFFFFFFFF) if op == "min" else jnp.uint64(0)
            reducer = jax.ops.segment_min if op == "min" else jax.ops.segment_max
            hi_m = jnp.where(live, hi, ident)
            red_hi = reducer(hi_m, gi.segment_ids, num_segments=cap)
            tie = live & (hi_m == red_hi[gi.segment_ids])
            lo_m = jnp.where(tie, lo, ident)
            red_lo = reducer(lo_m, gi.segment_ids, num_segments=cap)
            isel = jnp.where(tie & (lo_m == red_lo[gi.segment_ids]),
                             jnp.arange(cap, dtype=jnp.int32), cap)
            sel = jax.ops.segment_min(isel, gi.segment_ids, num_segments=cap)
        elif op in ("first", "last"):
            idx = jnp.arange(cap, dtype=jnp.int32)
            pick = jnp.where(live, idx, cap if op == "first" else -1)
            sel = (jax.ops.segment_min if op == "first" else jax.ops.segment_max)(
                pick, gi.segment_ids, num_segments=cap)
        else:
            raise NotImplementedError(f"string {op}")
        any_valid = jax.ops.segment_max(live.astype(jnp.int32), gi.segment_ids,
                                        num_segments=cap) > 0
        sel_c = jnp.clip(sel, 0, cap - 1)
        rows = gi.perm[sel_c]
        row_valid = any_valid
        col = K.gather_column(src, rows, row_valid)
        return col, any_valid

    @jax.named_scope("agg.final_project")
    def _final_project(self, buffers: ColumnarBatch) -> ColumnarBatch:
        """buffers -> final values (Average division etc.)."""
        cap = buffers.capacity
        out_cols: List[DeviceColumn] = list(buffers.columns[: self._n_keys])
        bi = self._n_keys + (2 if self._buffers_have_carry(buffers)
                             else 0)  # skip #gh1/#gh2
        for s in self._specs:
            bufs = buffers.columns[bi: bi + len(s.ops)]
            bi += len(s.ops)
            rt = s.result_type
            if isinstance(s.func, E.Average):
                ssum, cnt = bufs
                nz = cnt.data > 0
                if ssum.is_wide_decimal:
                    from spark_rapids_tpu.exec import int128 as I128

                    in_t = s.func.child.dtype
                    # the sum intermediate overflows like Sum does -> NULL
                    sum_ovf = I128.overflow_mask(
                        ssum.data2, ssum.data, s.buffer_types[0].precision)
                    d = rt.scale - in_t.scale
                    S = 10 ** d
                    den = jnp.maximum(cnt.data, 1).astype(jnp.int64)
                    # divide FIRST, then scale the (small) remainder:
                    # sum*10^d could wrap 2^127 before dividing.
                    ah, al = I128.abs_(ssum.data2, ssum.data)
                    q1h, q1l, r = I128._udivmod_small(ah, al, den)
                    # |q1| >= 10^(p-d)  =>  |result| >= 10^p -> NULL
                    pre_ovf = I128.overflow_mask(q1h, q1l, rt.precision - d)
                    frac = r * jnp.int64(S)  # < 2^31 * 10^d
                    f_q = frac // den
                    f_r = frac - f_q * den
                    f_q = f_q + (2 * f_r >= den).astype(jnp.int64)
                    qh, ql = I128.mul_small(q1h, q1l, S)
                    qh, ql = I128.add(qh, ql, jnp.zeros_like(f_q), f_q)
                    nh, nl2 = I128.neg(qh, ql)
                    neg = I128.is_neg(ssum.data2, ssum.data)
                    qh = jnp.where(neg, nh, qh)
                    ql = jnp.where(neg, nl2, ql)
                    res_ovf = I128.overflow_mask(qh, ql, rt.precision)
                    valid = (ssum.validity & nz & ~sum_ovf & ~pre_ovf
                             & ~res_ovf)
                    wide_rt = (rt.precision
                               > T.DecimalType.MAX_LONG_DIGITS)
                    if wide_rt:
                        out_cols.append(DeviceColumn(
                            rt, jnp.where(valid, ql, 0), valid,
                            data2=jnp.where(valid, qh, 0)))
                    else:
                        fits = qh == jnp.where(ql < 0, jnp.int64(-1),
                                               jnp.int64(0))
                        valid = valid & fits
                        out_cols.append(DeviceColumn(
                            rt, jnp.where(valid, ql, 0), valid))
                    continue
                if isinstance(rt, T.DecimalType):
                    in_t = s.func.child.dtype
                    # avg = sum/count rounded HALF_UP at result scale
                    shift = 10 ** (rt.scale - in_t.scale)
                    num = ssum.data.astype(jnp.int64) * jnp.int64(shift)
                    den = jnp.maximum(cnt.data, 1)
                    q = num // den
                    r = num - q * den
                    neg = (num < 0)
                    # round half up (away from zero), truncating division fix
                    q_t = jnp.where(neg & (r != 0), q + 1, q)
                    r_t = jnp.abs(num - q_t * den)
                    data = q_t + jnp.where(2 * r_t >= den,
                                           jnp.where(neg, -1, 1), 0)
                else:
                    data = ssum.data.astype(jnp.float64) / jnp.maximum(
                        cnt.data, 1
                    ).astype(jnp.float64)
                valid = ssum.validity & nz
                out_cols.append(DeviceColumn(rt, jnp.where(valid, data, 0), valid))
            elif isinstance(s.func, (E.Skewness, E.Kurtosis)):
                s1, s2, s3, s4, cnt = bufs
                n = jnp.maximum(cnt.data, 1).astype(jnp.float64)
                mu = s1.data.astype(jnp.float64) / n
                S2 = s2.data - n * mu ** 2
                S2 = jnp.maximum(S2, 0.0)
                if isinstance(s.func, E.Skewness):
                    S3 = s3.data - 3 * mu * s2.data + 2 * n * mu ** 3
                    data = jnp.sqrt(n) * S3 / jnp.maximum(S2, 1e-300) ** 1.5
                    data = jnp.where(S2 <= 0, jnp.float64(jnp.nan), data)
                else:
                    S4 = (s4.data - 4 * mu * s3.data + 6 * mu ** 2 * s2.data
                          - 3 * n * mu ** 4)
                    data = n * S4 / jnp.maximum(S2, 1e-300) ** 2 - 3.0
                    data = jnp.where(S2 <= 0, jnp.float64(jnp.nan), data)
                valid = cnt.data > 0
                out_cols.append(DeviceColumn(
                    rt, jnp.where(valid, data, 0.0), valid))
            elif isinstance(s.func, E._VarianceBase):
                ssum, ssq, cnt = bufs
                n = jnp.maximum(cnt.data, 1).astype(jnp.float64)
                mean = ssum.data.astype(jnp.float64) / n
                m2 = ssq.data.astype(jnp.float64) - n * mean * mean
                m2 = jnp.maximum(m2, 0.0)  # FP guard: variance >= 0
                samp = isinstance(s.func, (E.VarianceSamp, E.StddevSamp))
                den = jnp.maximum(n - 1, 1) if samp else n
                var = m2 / den
                data = jnp.sqrt(var) if isinstance(
                    s.func, (E.StddevSamp, E.StddevPop)) else var
                # modern Spark (legacy.statisticalAggregate=false): a
                # single sample -> NULL for the _samp variants
                valid = (cnt.data > 1) if samp else (cnt.data > 0)
                out_cols.append(DeviceColumn(
                    rt, jnp.where(valid, data, 0.0), valid))
            elif isinstance(s.func, E._CovarianceBase):
                if isinstance(s.func, E.Corr):
                    sx, sy, sxy, sx2, sy2, cnt = bufs
                else:
                    sx, sy, sxy, cnt = bufs
                n = cnt.data.astype(jnp.float64)
                ns = jnp.maximum(n, 1.0)
                ck = sxy.data - sx.data * sy.data / ns
                if isinstance(s.func, E.CovarPop):
                    data = ck / ns
                    valid = cnt.data > 0
                elif isinstance(s.func, E.CovarSamp):
                    data = ck / jnp.maximum(n - 1.0, 1.0)
                    # Spark default nullOnDivideByZero: n<2 -> NULL
                    valid = cnt.data > 1
                else:  # Corr
                    mx = n * sx2.data - sx.data ** 2
                    my = n * sy2.data - sy.data ** 2
                    den = jnp.sqrt(jnp.maximum(mx, 0.0)
                                   * jnp.maximum(my, 0.0))
                    data = (n * sxy.data - sx.data * sy.data) / jnp.maximum(
                        den, 1e-300)
                    valid = (cnt.data > 0) & (den > 0)
                out_cols.append(DeviceColumn(
                    rt, jnp.where(valid, data, 0.0), valid))
            elif isinstance(s.func, E.CountIf):
                b = bufs[0]
                out_cols.append(DeviceColumn(
                    rt, jnp.where(b.validity, b.data, 0).astype(
                        T.numpy_dtype(rt)),
                    jnp.ones(cap, jnp.bool_)))
            else:
                b = bufs[0]
                if b.is_dict:
                    out_cols.append(b)  # dict string min/max/first/last
                elif b.offsets is not None:
                    out_cols.append(DeviceColumn(rt, b.data, b.validity, b.offsets))
                elif b.is_wide_decimal:
                    from spark_rapids_tpu.exec import int128 as I128

                    # Sum results: Spark overflow -> NULL past precision
                    ovf = I128.overflow_mask(b.data2, b.data, rt.precision)
                    valid = b.validity & ~ovf
                    out_cols.append(DeviceColumn(
                        rt, jnp.where(valid, b.data, 0), valid,
                        data2=jnp.where(valid, b.data2, 0)))
                else:
                    out_cols.append(
                        DeviceColumn(rt, b.data.astype(T.numpy_dtype(rt)), b.validity)
                    )
        return ColumnarBatch(out_cols, buffers.num_rows)

    # -- host orchestration ------------------------------------------------
    def do_execute(self, partition: int) -> Iterator[ColumnarBatch]:
        self._prepare()
        if self.mode == "final":
            partials = list(self.child.execute(partition))
        else:
            partials = []
            for batch in self.child.execute(partition):
                partials.append(self._first_pass_fn(batch))
                self.metrics["numAggBatches"].add(1)
        if not partials:
            if self._n_keys == 0 and self.mode in ("complete", "final"):
                # global agg over empty input still yields one row
                from spark_rapids_tpu.columnar.batch import empty_batch
                buf = empty_batch(self._buffer_schema().types(), 16)
                merged = self._merge_pass_fn(buf)
                yield self._final_project_fn(merged)
            return
        for merged in self._merge_all(partials):
            if self.mode == "partial":
                yield merged
            else:
                yield self._final_project_fn(merged)

    def _merge_to_one(self, partials: List[ColumnarBatch]) -> ColumnarBatch:
        """Concat partial buffers on device and merge until one batch."""
        if len(partials) == 1:
            # a lone first-pass output is already grouped; "final" input may
            # still hold duplicate keys from different map tasks
            if self.mode == "final":
                return self._merge_pass_fn(partials[0])
            return partials[0]
        while len(partials) > 1:
            with self.timer("concatTimeNs"):
                group = partials[:8]
                partials = partials[8:]
                cat = concat_jit(group)
            partials.insert(0, self._merge_pass_fn(cat))
        return partials[0]

    # -- oversized-state fallback ------------------------------------------
    # Reference: GpuAggregateExec.scala:208-314 — when the merged state will
    # not fit, hash-REPARTITION the partials into buckets (re-seeded hash per
    # level, bounded depth) and aggregate each bucket independently, instead
    # of asking split-retry to save a merge that is too big by construction.
    # Buckets hold disjoint key sets, so one merged batch per bucket is a
    # globally correct result and do_execute may emit several batches.

    def _repart_conf(self) -> Tuple[bool, int, int, int]:
        from spark_rapids_tpu.config import conf as C
        from spark_rapids_tpu.mem.pool import get_pool

        cfg = C.get_active()
        enabled = bool(C.AGG_REPARTITION_ENABLED.get(cfg)) and self._n_keys > 0
        target = int(C.AGG_REPARTITION_TARGET_BYTES.get(cfg))
        if target <= 0:
            # the merge working set is concat(inputs) + merged output: give
            # the cascade at most a quarter of the budget before bucketing
            target = max(get_pool().limit // 4, 1)
        return (enabled, target, int(C.AGG_REPARTITION_NUM_BUCKETS.get(cfg)),
                int(C.AGG_REPARTITION_MAX_DEPTH.get(cfg)))

    def _merge_all(self,
                   partials: List[ColumnarBatch]) -> Iterator[ColumnarBatch]:
        """Merge partials into one batch — or, when the combined state is
        oversized (or the pool denies the direct merge), into one batch per
        hash bucket via recursive repartitioning."""
        from spark_rapids_tpu.mem.pool import RetryOOM, SplitAndRetryOOM

        enabled, target, nbuckets, max_depth = self._repart_conf()
        state = sum(p.nbytes() for p in partials)
        if not enabled:
            yield self._merge_to_one(partials)
            return
        if len(partials) == 1 or state <= target:
            try:
                yield self._merge_to_one(list(partials))
                return
            except (RetryOOM, SplitAndRetryOOM):
                if len(partials) == 1:
                    raise  # nothing to bucket; with_retry paths own this
                # pool denied the merge mid-flight: fall through and
                # repartition from the (still referenced) original partials
        from spark_rapids_tpu import faults
        from spark_rapids_tpu.mem import retry as R

        attempts = 0
        while True:
            try:
                out = list(self._repartition_merge(
                    list(partials), 0, target, nbuckets, max_depth))
                break
            except RetryOOM:
                attempts += 1
                if attempts >= 3:
                    raise
                R._oom_backoff(attempts)
        if attempts:
            faults.note_recovered("agg.repartition")
        for merged in out:
            yield merged

    def _bucket_ids(self, batch: ColumnarBatch, salt: jax.Array,
                    nbuckets: int) -> jax.Array:
        """Per-row bucket id (traced). The carried #gh1 hash is re-seeded
        through splitmix64 with a level salt so every recursion level cuts
        the key space along an independent boundary."""
        if self._buffers_have_carry(batch):
            h = batch.columns[self._n_keys].data.astype(jnp.uint64)
        else:
            h = K.hash_keys(batch, list(range(self._n_keys)))
        return (K._splitmix64(h ^ salt)
                % jnp.uint64(nbuckets)).astype(jnp.int32)

    def _bucket_counts(self, batch: ColumnarBatch, salt: jax.Array,
                       nbuckets: int) -> jax.Array:
        ids = self._bucket_ids(batch, salt, nbuckets)
        active = jnp.arange(batch.capacity, dtype=jnp.int32) < batch.num_rows
        ids = jnp.where(active, ids, nbuckets)  # park inactive rows
        return jnp.bincount(ids, length=nbuckets + 1)[:nbuckets]

    def _bucket_extract(self, batch: ColumnarBatch, salt: jax.Array,
                        b: jax.Array, nbuckets: int,
                        out_cap: int) -> ColumnarBatch:
        ids = self._bucket_ids(batch, salt, nbuckets)
        active = jnp.arange(batch.capacity, dtype=jnp.int32) < batch.num_rows
        idx, n = K.filter_indices(ids == b, active)
        return K.gather_batch(batch, idx[:out_cap], n)

    def _repartition_merge(self, inputs: List, level: int, target: int,
                           nbuckets: int,
                           max_depth: int) -> Iterator[ColumnarBatch]:
        """Recursively hash-repartition ``inputs`` and merge each bucket.

        Two passes per input batch: a jitted count pass (one host sync),
        then one jitted extract per NON-EMPTY bucket with a static capacity
        sized to that bucket — only one bucket sub-batch is live at a time.
        Sub-batches go straight into SpillableBatch handles, so pool
        pressure sheds waiting buckets to host/disk through the same door
        as every other operator. ``inputs`` items may be plain batches
        (level 0) or SpillableBatch handles (recursion)."""
        from spark_rapids_tpu import faults
        from spark_rapids_tpu.exec.jit_cache import shared_jit
        from spark_rapids_tpu.mem import spill as S
        from spark_rapids_tpu.mem.pool import RetryOOM, SplitAndRetryOOM
        from spark_rapids_tpu.obs import events as _journal
        from spark_rapids_tpu.utils import task_metrics as TM

        faults.check("agg.repartition", level=level)
        _note_repartition(level)
        TM.add("agg_repartition_count", 1)
        TM.watermark("max_agg_repartition_depth", level + 1)
        self.metrics["numRepartitions"].add(1)
        _journal.emit("agg-repartition", level=level, buckets=nbuckets,
                      inputs=len(inputs))

        fw = S.get_framework()
        salt = jnp.uint64(((level + 1) * 0x9E3779B97F4A7C15)
                          & 0xFFFFFFFFFFFFFFFF)
        counts_fn = shared_jit(
            self._base_key + ("repart-counts", nbuckets),
            lambda: lambda batch, s: self._bucket_counts(batch, s, nbuckets))

        def _extract_fn(cap):
            return shared_jit(
                self._base_key + ("repart-extract", nbuckets, cap),
                lambda: lambda batch, s, b: self._bucket_extract(
                    batch, s, b, nbuckets, cap))

        buckets: List[List[S.SpillableBatch]] = [[] for _ in range(nbuckets)]
        try:
            for item in inputs:
                if isinstance(item, S.SpillableBatch):
                    with item as batch:
                        self._scatter_one(batch, salt, counts_fn, _extract_fn,
                                          buckets, fw)
                    item.close()  # bucketed: the source copy is dead weight
                else:
                    self._scatter_one(item, salt, counts_fn, _extract_fn,
                                      buckets, fw)
            del inputs  # device refs now live only in the bucket handles
            for b, hs in enumerate(buckets):
                if not hs:
                    continue
                with _bucket_ctx(level, b):
                    bucket_bytes = sum(h.nbytes for h in hs)
                    if (bucket_bytes > target and len(hs) > 1
                            and level + 1 < max_depth):
                        yield from self._repartition_merge(
                            hs, level + 1, target, nbuckets, max_depth)
                        continue
                    pinned: List[S.SpillableBatch] = []
                    try:
                        batches = []
                        for h in hs:
                            batches.append(h.get())
                            pinned.append(h)
                        merged = self._merge_to_one(batches)
                    except (RetryOOM, SplitAndRetryOOM):
                        del batches
                        for h in pinned:
                            h.unpin()
                        if level + 1 < max_depth and len(hs) > 1:
                            yield from self._repartition_merge(
                                hs, level + 1, target, nbuckets, max_depth)
                        else:
                            yield self._merge_last_resort(hs, fw)
                        continue
                    for h in pinned:
                        h.unpin()
                    for h in hs:
                        h.close()
                    yield merged
        finally:
            for hs in buckets:
                for h in hs:
                    h.close()  # idempotent; frees survivors on error exits

    def _scatter_one(self, batch: ColumnarBatch, salt: jax.Array, counts_fn,
                     extract_fn, buckets: List[List], fw) -> None:
        """Split one materialized batch across the bucket lists."""
        from spark_rapids_tpu.mem import spill as S

        counts = host_get(counts_fn(batch, salt), "agg.repartition_counts")
        for b, n in enumerate(counts):
            n = int(n)
            if n == 0:
                continue
            cap = bucket_capacity(n, 16)
            sub = extract_fn(cap)(batch, salt, jnp.int32(b))
            buckets[b].append(S.SpillableBatch(sub, fw))

    def _merge_last_resort(self, handles: List,
                           fw) -> ColumnarBatch:
        """Max repartition depth reached: merge each piece under the
        split-retry machinery (the true last resort), then cascade."""
        from spark_rapids_tpu.mem import retry as R

        merged = list(R.with_retry(handles, self._merge_pass_fn,
                                   framework=fw))
        return self._merge_to_one(merged)

    @staticmethod
    def final_from_partial(partial: "HashAggregateExec",
                           child: TpuExec) -> "HashAggregateExec":
        """Build the reduce-side aggregate consuming a partial's buffers."""
        partial._prepare()
        final = HashAggregateExec(
            [E.col(n) for n in partial._group_names], partial.agg_exprs,
            child, mode="final")
        final._specs = list(partial._specs)
        return final


_concat_fn = jax.jit(K.concat_device, static_argnums=(1, 2))


def _decode_col_jit(b: ColumnarBatch, ci: int) -> ColumnarBatch:
    if not b.columns[ci].is_dict:
        return b
    cols = list(b.columns)
    cols[ci] = _decode_col_fn(b.columns[ci])
    return ColumnarBatch(cols, b.num_rows)


_decode_col_fn = jax.jit(K.decode_dictionary)


def share_dictionaries(batches: Sequence[ColumnarBatch],
                       site: str) -> List[ColumnarBatch]:
    """``batches`` with each dict-coded column's dictionary made ONE device
    object where their contents are equal, so that ``concat_jit`` keeps
    the codes instead of decoding every row: a program's output holds the
    dictionary its input forwarded in a buffer of its own, so the batches
    a fused stage made from one ingest's share no object. The candidates'
    dictionaries (a few entries each) are read in ONE host sync (``site``)
    and compared on the host; a column whose dictionaries differ stays as
    it was, for ``concat_jit`` to decode."""
    import numpy as np
    from spark_rapids_tpu.utils.sync import host_get
    batches = list(batches)
    cand = []
    for ci, c in enumerate(batches[0].columns):
        ds = [b.columns[ci] for b in batches]
        if (c.is_dict and any(d.dictionary is not c.dictionary for d in ds)
                and all(d.is_dict and d.dict_size == c.dict_size
                        and d.dictionary.capacity == c.dictionary.capacity
                        and d.dictionary.data.shape == c.dictionary.data.shape
                        for d in ds)):
            cand.append(ci)
    if not cand:
        return batches
    got = host_get([[(b.columns[ci].dictionary.data,
                      b.columns[ci].dictionary.offsets,
                      b.columns[ci].dictionary.validity) for b in batches]
                    for ci in cand], site)
    for ci, dicts in zip(cand, got):
        if all(all(np.array_equal(x, y) for x, y in zip(d, dicts[0]))
               for d in dicts[1:]):
            one = batches[0].columns[ci].dictionary
            batches = [ColumnarBatch(
                [dataclasses.replace(c, dictionary=one) if i == ci else c
                 for i, c in enumerate(b.columns)], b.num_rows)
                for b in batches]
    return batches


def concat_jit(batches: Sequence[ColumnarBatch],
               out_capacity: Optional[int] = None) -> ColumnarBatch:
    """Device concat with capacity bucketing (jit cached per shape combo).

    ``out_capacity`` may be smaller than the capacity sum when the caller
    knows the live row total (coalesce compaction)."""
    if any(c.children is not None for c in batches[0].columns):
        # nested (struct/map) columns: host arrow concat (correct for every
        # layout; device nested concat is future work)
        from spark_rapids_tpu.columnar.batch import concat_batches
        from spark_rapids_tpu import types as _T

        schema = _T.Schema([_T.Field(f"c{i}", c.dtype, True)
                            for i, c in enumerate(batches[0].columns)])
        return concat_batches(list(batches), schema)
    # dict columns: codes are only comparable when every batch shares ONE
    # device dictionary (object identity, guaranteed for batches sliced from
    # one ingest); otherwise decode to plain bytes before concatenating
    for ci, c in enumerate(batches[0].columns):
        if c.is_dict or any(b.columns[ci].is_dict for b in batches):
            shared = all(
                b.columns[ci].dictionary is c.dictionary for b in batches)
            if not shared:
                batches = [_decode_col_jit(b, ci) for b in batches]
    out_cap = out_capacity or bucket_capacity(sum(b.capacity for b in batches))
    byte_caps = []
    for ci, c in enumerate(batches[0].columns):
        if c.offsets is not None:
            byte_caps.append(bucket_capacity(
                max(sum(b.columns[ci].byte_capacity for b in batches), 8), 8))
        else:
            byte_caps.append(0)
    return _concat_fn(list(batches), out_cap, tuple(byte_caps))


# type_support declarations (spark_rapids_tpu.support)
from spark_rapids_tpu.support import ALL_SCALAR, ts  # noqa: E402

HashAggregateExec.type_support = ts(
    ALL_SCALAR, note="grouping keys hashed full-width (incl. strings); "
    "aggregate input/output typing enforced per-function by check_expr")

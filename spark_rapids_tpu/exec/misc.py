"""Coalesce, limits, range, union.

Reference: GpuCoalesceBatches (GpuCoalesceBatches.scala:160 — CoalesceGoal
lattice TargetSize/RequireSingleBatch), limit.scala (GpuLocalLimitExec /
GpuGlobalLimitExec / GpuTakeOrderedAndProjectExec), GpuRangeExec, UnionExec.
"""

from __future__ import annotations

from functools import partial
from typing import Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, bucket_capacity
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.exec.base import LeafExec, TpuExec, UnaryExec
from spark_rapids_tpu.exec import kernels as K
from spark_rapids_tpu.exec.aggregate import concat_jit
from spark_rapids_tpu.exec.sort import SortOrder, TopNExec
from spark_rapids_tpu.exec.project import ProjectExec
from spark_rapids_tpu.exec.join import _pad_idx
from spark_rapids_tpu.exprs import expr as E


class CoalesceBatchesExec(UnaryExec):
    """Concatenate small batches up to a target row count (TargetSize goal);
    ``require_single`` concatenates everything (RequireSingleBatch goal)."""

    def __init__(self, child: TpuExec, target_rows: int = 1 << 20,
                 require_single: bool = False):
        super().__init__(child)
        self.target_rows = target_rows
        self.require_single = require_single
        self._register_metric("concatTimeNs")

    def node_description(self) -> str:
        goal = "RequireSingleBatch" if self.require_single else (
            f"TargetSize({self.target_rows})")
        return f"TpuCoalesceBatches [{goal}]"

    def do_execute(self, partition: int) -> Iterator[ColumnarBatch]:
        pending: List[ColumnarBatch] = []
        rows = 0
        for b in self.child.execute(partition):
            # coalescing decisions need real row counts (sparse batches keep
            # their full static capacity); the sync is the price of the
            # operator, and output capacity shrinks to the live rows below
            n = b.row_count()
            if not self.require_single and rows and rows + n > self.target_rows:
                yield self._flush(pending, rows)
                pending, rows = [], 0
            pending.append(b)
            rows += n
        if pending:
            yield self._flush(pending, rows)

    def _flush(self, pending: List[ColumnarBatch], rows: int) -> ColumnarBatch:
        if len(pending) == 1 and pending[0].capacity <= 2 * bucket_capacity(
                max(rows, 1)):
            return pending[0]
        with self.timer("concatTimeNs"):
            # out capacity = bucket of the LIVE rows: also compacts sparse
            # filter/join outputs (GpuCoalesceBatches sizing behavior)
            return concat_jit(pending, out_capacity=bucket_capacity(max(rows, 1)))


class LocalLimitExec(UnaryExec):
    """Limit rows within each partition."""

    def __init__(self, limit: int, child: TpuExec):
        super().__init__(child)
        self.limit = limit

    def node_description(self) -> str:
        return f"TpuLocalLimit {self.limit}"

    def do_execute(self, partition: int) -> Iterator[ColumnarBatch]:
        remaining = self.limit
        for b in self.child.execute(partition):
            if remaining <= 0:
                return
            n = b.row_count()
            if n <= remaining:
                remaining -= n
                yield b
            else:
                yield _truncate(b, remaining)
                return


class GlobalLimitExec(UnaryExec):
    """Limit across partitions (driver-side sequencing)."""

    def __init__(self, limit: int, child: TpuExec, offset: int = 0):
        super().__init__(child)
        self.limit = limit
        self.offset = offset

    def num_partitions(self) -> int:
        return 1

    def node_description(self) -> str:
        return f"TpuGlobalLimit {self.limit} offset={self.offset}"

    def do_execute(self, partition: int) -> Iterator[ColumnarBatch]:
        assert partition == 0
        to_skip = self.offset
        remaining = self.limit
        for p in range(self.child.num_partitions()):
            for b in self.child.execute(p):
                n = b.row_count()
                if to_skip:
                    if n <= to_skip:
                        to_skip -= n
                        continue
                    b = _drop_head(b, to_skip)
                    n -= to_skip
                    to_skip = 0
                if remaining <= 0:
                    return
                if n <= remaining:
                    remaining -= n
                    yield b
                else:
                    yield _truncate(b, remaining)
                    return


class SampleExec(UnaryExec):
    """Seeded Bernoulli row sample (GpuSampleExec analog, without-replacement
    path). Deterministic for a given (seed, partition, batch index): the mask
    comes from a counter-based PRNG key folded with those coordinates, the
    TPU-native analog of Spark's per-partition XORShift sampler."""

    def __init__(self, fraction: float, seed: int, child: TpuExec):
        super().__init__(child)
        assert 0.0 <= fraction <= 1.0
        self.fraction = fraction
        self.seed = seed

    def node_description(self) -> str:
        return f"TpuSample {self.fraction} seed={self.seed}"

    def do_execute(self, partition: int) -> Iterator[ColumnarBatch]:
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), partition)
        for bi, b in enumerate(self.child.execute(partition)):
            bkey = jax.random.fold_in(key, bi)
            keep, n = _sample_mask(b, bkey, self.fraction)
            cap = bucket_capacity(max(int(n), 1), 16)
            yield _sample_gather(b, keep, cap)


@jax.jit
def _sample_mask(b: ColumnarBatch, key, fraction):
    u = jax.random.uniform(key, (b.capacity,))
    keep = (u < fraction) & b.active_mask()
    return keep, jnp.sum(keep.astype(jnp.int32))


@partial(jax.jit, static_argnums=2)
def _sample_gather(b: ColumnarBatch, keep, cap: int):
    idx, n = K.filter_indices(keep, b.active_mask())
    idx = _pad_idx(idx, cap)
    row_valid = jnp.arange(cap, dtype=jnp.int32) < n
    cols = K.gather_columns(b.columns, idx, row_valid)
    return ColumnarBatch(cols, n.astype(jnp.int32))


def take_ordered_and_project(orders: Sequence[SortOrder], limit: int,
                             child: TpuExec,
                             project: Optional[Sequence[E.Expression]] = None
                             ) -> TpuExec:
    """GpuTakeOrderedAndProjectExec analog: a top-N per partition
    (exec/sort.py ``TopNExec``: the k best rows, never a sort of the
    partition), then, where there are several partitions, a top-N of their
    gathered results, + optional projection."""
    several = child.num_partitions() > 1
    merged = TopNExec(orders, limit, child, partial=several)
    if several:
        merged = TopNExec(orders, limit, _Gather(merged))
    if project is not None:
        return ProjectExec(project, merged)
    return merged


class _Gather(UnaryExec):
    """Collapse all child partitions into one (driver-style gather)."""

    def num_partitions(self) -> int:
        return 1

    def do_execute(self, partition: int) -> Iterator[ColumnarBatch]:
        for p in range(self.child.num_partitions()):
            yield from self.child.execute(p)


class RangeExec(LeafExec):
    """start/end/step long range generated directly on device
    (reference: GpuRangeExec in basicPhysicalOperators.scala)."""

    def __init__(self, start: int, end: int, step: int = 1,
                 n_partitions: int = 1, target_batch_rows: int = 1 << 20):
        super().__init__()
        assert step != 0
        self.start, self.end, self.step = start, end, step
        self.n_partitions = n_partitions
        self.target_batch_rows = target_batch_rows

    @property
    def output_schema(self) -> T.Schema:
        return T.Schema([T.Field("id", T.LONG, False)])

    def num_partitions(self) -> int:
        return self.n_partitions

    def do_execute(self, partition: int) -> Iterator[ColumnarBatch]:
        total = max(0, -(-(self.end - self.start) // self.step))
        per = -(-total // self.n_partitions)
        lo = partition * per
        hi = min(total, lo + per)
        pos = lo
        while pos < hi:
            n = min(self.target_batch_rows, hi - pos)
            cap = bucket_capacity(n)
            idx = jnp.arange(cap, dtype=jnp.int64)
            data = jnp.int64(self.start) + (jnp.int64(pos) + idx) * jnp.int64(self.step)
            valid = idx < n
            col = DeviceColumn(T.LONG, jnp.where(valid, data, 0), valid)
            yield ColumnarBatch([col], jnp.int32(n))
            pos += n


class UnionExec(TpuExec):
    """Concatenation of children outputs (GpuUnionExec): partitions of each
    child become partitions of the union."""

    def __init__(self, *children: TpuExec):
        super().__init__(*children)

    @property
    def output_schema(self) -> T.Schema:
        return self.children[0].output_schema

    def num_partitions(self) -> int:
        return sum(c.num_partitions() for c in self.children)

    def do_execute(self, partition: int) -> Iterator[ColumnarBatch]:
        for c in self.children:
            n = c.num_partitions()
            if partition < n:
                yield from c.execute(partition)
                return
            partition -= n


_truncate_jit = jax.jit(
    lambda b, n: ColumnarBatch(
        [DeviceColumn(c.dtype,
                      c.data,
                      c.validity & (jnp.arange(c.capacity, dtype=jnp.int32) < n),
                      c.offsets, c.dictionary, c.dict_size, c.dict_max_len,
                      c.data2)
         for c in b.columns],
        jnp.minimum(b.num_rows, n).astype(jnp.int32),
    )
)


def _truncate(b: ColumnarBatch, n: int) -> ColumnarBatch:
    return _truncate_jit(b, jnp.int32(n))


@jax.jit
def _drop_head_jit(b: ColumnarBatch, k: jax.Array) -> ColumnarBatch:
    cap = b.capacity
    idx = jnp.arange(cap, dtype=jnp.int32) + k
    n = jnp.maximum(b.num_rows - k, 0)
    return K.gather_batch(b, jnp.clip(idx, 0, cap - 1), n)


def _drop_head(b: ColumnarBatch, k: int) -> ColumnarBatch:
    return _drop_head_jit(b, jnp.int32(k))


# type_support declarations (spark_rapids_tpu.support): pass-through
# operators accept anything; RangeExec produces longs.
from spark_rapids_tpu.support import ALL, INTEGRAL, ts  # noqa: E402

CoalesceBatchesExec.type_support = ts(ALL, note="pass-through")
LocalLimitExec.type_support = ts(ALL, note="pass-through")
GlobalLimitExec.type_support = ts(ALL, note="pass-through")
SampleExec.type_support = ts(ALL, note="pass-through with Bernoulli mask")
UnionExec.type_support = ts(ALL, note="pass-through")
RangeExec.type_support = ts(INTEGRAL, note="produces a LongType column")

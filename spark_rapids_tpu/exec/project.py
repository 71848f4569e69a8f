"""Projection and filter operators.

Reference: GpuProjectExec / GpuFilterExec (basicPhysicalOperators.scala:365,
518). TPU-first: the bound expression tree AND the filter compaction lower
into one jit-compiled XLA computation per capacity bucket — there is no
per-expression kernel dispatch, XLA fuses the whole thing (this subsumes the
reference's tiered-projection CSE, basicPhysicalOperators.scala:806).
"""

from __future__ import annotations

import threading
from typing import Iterator, List, Sequence

import jax

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.base import UnaryExec, TpuExec
from spark_rapids_tpu.exec import kernels as K
from spark_rapids_tpu.exprs import expr as E
from spark_rapids_tpu.exprs import eval as EV


class ProjectExec(UnaryExec):
    def __init__(self, exprs: Sequence[E.Expression], child: TpuExec,
                 ansi: bool = False):
        super().__init__(child)
        self.exprs = list(exprs)
        self._bound = None
        self._ansi = ansi
        self._schema = None
        # parallel shuffle-write tasks / prefetch workers can hit a cold
        # node concurrently; RLock because batch_fn_key re-enters _bind
        self._bind_lock = threading.RLock()

    def _bind(self):
        with self._bind_lock:
            if self._bound is None:
                self._bound = tuple(
                    EV.bind_projection(self.exprs, self.child.output_schema)
                )
                self._schema = EV.output_schema(self._bound)
                from spark_rapids_tpu.exec.jit_cache import shared_jit

                self._run = shared_jit(self.batch_fn_key(),
                                       lambda: self.batch_fn())
        return self._bound

    @property
    def output_schema(self) -> T.Schema:
        self._bind()
        return self._schema

    def node_description(self) -> str:
        return f"TpuProject [{', '.join(map(repr, self.exprs))}]"

    @property
    def row_preserving(self) -> bool:
        """An output row is computed from its input row alone, and for a
        row that a filter below has dropped but not yet compacted away
        (exec/fused.py ``_OpSeg``) nothing can go wrong: outside ANSI mode
        an expression gives NULL where ANSI would raise."""
        return not self._ansi

    def batch_fn(self):
        self._bind()
        bound, ansi = self._bound, self._ansi
        return lambda batch: EV.project_batch(batch, bound, ansi)

    def batch_fn_key(self) -> tuple:
        if self._bound is None:
            self._bind()
        return ("project", E.exprs_cache_key(self._bound), self._ansi,
                repr(self.child.output_schema))

    def do_execute(self, partition: int) -> Iterator[ColumnarBatch]:
        self._bind()
        for batch in self.child.execute(partition):
            yield self._run(batch)


class FilterExec(UnaryExec):
    """Filter + compaction in one fused kernel."""

    shrink_output = True

    def __init__(self, condition: E.Expression, child: TpuExec,
                 ansi: bool = False):
        super().__init__(child)
        self.condition = condition
        self._bound = None
        self._ansi = ansi
        self._bind_lock = threading.RLock()

    def _bind(self):
        with self._bind_lock:
            if self._bound is None:
                self._bound = E.resolve(self.condition,
                                        self.child.output_schema)
                from spark_rapids_tpu.exec.jit_cache import shared_jit

                self._run = shared_jit(self.batch_fn_key(),
                                       lambda: self.batch_fn())
        return self._bound

    def node_description(self) -> str:
        return f"TpuFilter [{self.condition!r}]"

    def batch_fn(self):
        self._bind()
        bound, ansi = self._bound, self._ansi

        def run(batch):
            ctx = EV.EvalContext(batch, ansi)
            pred = EV.eval_expr(bound, ctx)
            keep = pred.data & pred.validity
            idx, n = K.filter_indices(keep, batch.active_mask())
            return K.gather_batch(batch, idx, n)
        return run

    def mask_fn(self):
        """fn(batch) -> the rows the predicate keeps, for a fused stage
        that lets the next consumer of a mask do the compacting; None in
        ANSI mode, where the predicate may not be evaluated for a row an
        earlier filter dropped."""
        if self._ansi:
            return None
        self._bind()
        bound = self._bound

        def keep(batch):
            pred = EV.eval_expr(bound, EV.EvalContext(batch, False))
            return pred.data & pred.validity
        return keep

    def batch_fn_key(self) -> tuple:
        if self._bound is None:
            self._bind()
        return ("filter", self._bound.cache_key(), self._ansi,
                repr(self.child.output_schema))

    def do_execute(self, partition: int) -> Iterator[ColumnarBatch]:
        self._bind()
        for batch in self.child.execute(partition):
            yield self._run(batch)


# type_support declarations (spark_rapids_tpu.support): the per-expression
# gate in plan/overrides.check_expr does the real typing; the operator
# itself passes any representable column through.
from spark_rapids_tpu.support import ALL, ts  # noqa: E402

ProjectExec.type_support = ts(
    ALL, note="per-expression typing enforced by check_expr")
FilterExec.type_support = ts(
    ALL, note="predicate typed by check_expr; non-predicate columns pass "
    "through")

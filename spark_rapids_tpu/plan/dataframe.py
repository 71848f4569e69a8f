"""DataFrame front-end: the user-facing API over the plan layer.

The reference has no front-end (Spark provides it); standalone, this thin
builder gives tests/benchmarks and users an ergonomic way to express the
same plans Spark would hand the plugin. It mirrors the PySpark column-API
subset that the reference accelerates.
"""

from __future__ import annotations

import time
from typing import List, Optional, Sequence

import pyarrow as pa

from spark_rapids_tpu import types as T
from spark_rapids_tpu.config import conf as C
from spark_rapids_tpu.exec.sort import SortOrder
from spark_rapids_tpu.exprs import expr as E
from spark_rapids_tpu.plan import logical as L


class DataFrame:
    def __init__(self, plan: L.LogicalPlan,
                 conf: Optional[C.RapidsConf] = None,
                 shuffle_partitions: int = 4):
        self.plan = plan
        self.conf = conf
        self.shuffle_partitions = shuffle_partitions

    def _with(self, plan: L.LogicalPlan) -> "DataFrame":
        return DataFrame(plan, self.conf, self.shuffle_partitions)

    # -- builders ----------------------------------------------------------
    def select(self, *exprs) -> "DataFrame":
        exprs = [E.col(e) if isinstance(e, str) else e for e in exprs]
        return self._with(L.Project(list(exprs), self.plan))

    def with_column(self, name: str, expr: E.Expression) -> "DataFrame":
        """Append (or replace in place) a named column, keeping all others
        (Spark ``withColumn``)."""
        exprs = []
        replaced = False
        for f in self.plan.schema.fields:
            if f.name == name:
                exprs.append(E.Alias(expr, name))
                replaced = True
            else:
                exprs.append(E.col(f.name))
        if not replaced:
            exprs.append(E.Alias(expr, name))
        return self._with(L.Project(exprs, self.plan))

    def filter(self, condition: E.Expression) -> "DataFrame":
        return self._with(L.Filter(condition, self.plan))

    where = filter

    def group_by(self, *keys) -> "GroupedDataFrame":
        keys = [E.col(k) if isinstance(k, str) else k for k in keys]
        return GroupedDataFrame(self, list(keys))

    def agg(self, *aggs) -> "DataFrame":
        return GroupedDataFrame(self, []).agg(*aggs)

    def sort(self, *orders, limit: Optional[int] = None) -> "DataFrame":
        os_: List[SortOrder] = []
        for o in orders:
            if isinstance(o, str):
                os_.append(SortOrder(E.col(o)))
            elif isinstance(o, SortOrder):
                os_.append(o)
            else:
                os_.append(SortOrder(o))
        return self._with(L.Sort(os_, self.plan, limit=limit))

    order_by = sort

    def join(self, other: "DataFrame", on=None, how: str = "inner",
             left_on=None, right_on=None,
             condition: Optional[E.Expression] = None) -> "DataFrame":
        if on is not None:
            if isinstance(on, str):
                on = [on]
            left_keys = [E.col(c) for c in on]
            right_keys = [E.col(c) for c in on]
        else:
            mk = lambda ks: [E.col(k) if isinstance(k, str) else k
                             for k in (ks if isinstance(ks, (list, tuple)) else [ks])]
            left_keys = mk(left_on)
            right_keys = mk(right_on)
        return self._with(L.Join(self.plan, other.plan, left_keys, right_keys,
                                 how, condition))

    def with_window(self, *window_exprs) -> "DataFrame":
        """Append window columns (all expressions must share one
        (partition, order) spec — Spark WindowExec shape)."""
        return self._with(L.Window(list(window_exprs), self.plan))

    def limit(self, n: int, offset: int = 0) -> "DataFrame":
        return self._with(L.Limit(n, self.plan, offset))

    def union(self, other: "DataFrame") -> "DataFrame":
        return self._with(L.Union([self.plan, other.plan]))

    # -- execution ---------------------------------------------------------
    @property
    def schema(self) -> T.Schema:
        return self.plan.schema

    def physical_plan(self):
        from spark_rapids_tpu.plan.overrides import Overrides

        # single-use handoff: device_plan_stats() leaves its (never-executed)
        # plan here so a following collect() doesn't re-run Overrides; an
        # executed plan is never cached (shuffle state is cleaned up on use),
        # and the handoff is dropped if planning inputs changed in between
        cached = getattr(self, "_pplan", None)
        self._pplan = None
        if cached is not None and cached[0] == (self.conf,
                                                self.shuffle_partitions):
            return cached[1]
        ov = Overrides(self.conf, self.shuffle_partitions)
        node = ov.apply(self.plan)
        self._plan_cache_hit = ov.cache_hit
        return node

    def _plan_for_execution(self):
        """``physical_plan()`` as the ``query:plan`` span of the running
        trace: a real interval on the executor thread, carrying the
        request's name and whether the plan memo answered."""
        from spark_rapids_tpu.obs import span as _span
        from spark_rapids_tpu.serve import context as _sctx

        qc = _sctx.current()
        with _span.task_span("query:plan", attrs=(
                {"query": qc.name} if qc is not None else None)) as sp:
            self._plan_cache_hit = None  # a handed-off plan: not looked up
            node = self.physical_plan()
            if sp is not None:
                sp.attrs["cache_hit"] = self._plan_cache_hit
        return node

    def explain(self) -> str:
        from spark_rapids_tpu.plan.overrides import Overrides, explain

        meta = Overrides(self.conf, self.shuffle_partitions).wrap_and_tag(
            self.plan)
        return explain(meta, "ALL")

    def device_plan_stats(self) -> dict:
        """Count device vs CPU-fallback nodes in the physical plan — the
        standalone analog of the reference's validate_execs_in_gpu_plan /
        assert_gpu_fallback_collect (integration_tests asserts.py:479-617)."""
        from spark_rapids_tpu.plan.cpu import CpuExec

        node = self.physical_plan()
        counts = {"total": 0, "device": 0}
        cpu_nodes = []

        def walk(n):
            counts["total"] += 1
            if isinstance(n, CpuExec):
                cpu_nodes.append(type(n).__name__)
            else:
                counts["device"] += 1
            for c in n.children:
                walk(c)

        walk(node)
        # hand off to a following collect(), keyed by the planning inputs
        self._pplan = ((self.conf, self.shuffle_partitions), node)
        return {
            "total": counts["total"],
            "device": counts["device"],
            "device_fraction": round(
                counts["device"] / max(counts["total"], 1), 3),
            "cpu_nodes": sorted(set(cpu_nodes)),
        }

    def _plan_key(self) -> str:
        """Stable identity of this logical plan for failure accounting
        (faults/blacklist.py)."""
        parts: List[str] = []

        def walk(n, d):
            parts.append("  " * d + n.describe())
            for c in n.children:
                walk(c, d + 1)

        walk(self.plan, 0)
        return "\n".join(parts)

    def _cpu_plan(self):
        """Re-plan with the device engine off (graceful degradation path)."""
        from spark_rapids_tpu.plan.overrides import Overrides

        base = self.conf or C.RapidsConf()
        return Overrides(base.with_overrides(**{C.SQL_ENABLED.key: False}),
                         self.shuffle_partitions).apply(self.plan)

    def to_arrow(self) -> pa.Table:
        """Execute, with per-plan failure handling: device failures retry
        and then blacklist the plan onto the CPU engine; escaped retryable
        OOMs get a bounded whole-query retry; everything else propagates
        (faults/blacklist.py classification)."""
        from spark_rapids_tpu import faults
        from spark_rapids_tpu.faults import blacklist as _bl
        from spark_rapids_tpu.obs import events as _journal

        base_conf = self.conf or C.RapidsConf()
        key = self._plan_key()
        if _bl.is_listed(key, base_conf):
            _journal.emit("degraded-to-cpu", reason="blacklisted")
            return self._execute_plan(self._cpu_plan())
        attempt = 0
        while True:
            attempt += 1
            from spark_rapids_tpu.serve import context as _sctx
            _sctx.check_cancel()  # no whole-query retry for a dead query
            try:
                out = self._execute_plan(self._plan_for_execution())
                if attempt > 1:
                    faults.note_recovered("query")
                return out
            except Exception as e:
                verdict = _bl.classify(key, e, base_conf)
                if verdict == _bl.DEGRADE:
                    faults.note_degraded("query")
                    return self._execute_plan(self._cpu_plan())
                if verdict != _bl.RETRY:
                    raise
                _journal.emit("query-retry", attempt=attempt,
                              error=type(e).__name__)

    def _execute_plan(self, node) -> pa.Table:
        import threading

        from spark_rapids_tpu.serve import context as _sctx

        ctx = _sctx.current()
        # one physical tree is stateful during execution (shuffle
        # registrations, fused-stage buffers) and the plan memo hands the
        # SAME tree to identical concurrent queries — serialize per tree,
        # polling the cancellation token while waiting for our turn
        tree_lock = node.__dict__.setdefault("_exec_lock", threading.Lock())
        while not tree_lock.acquire(timeout=0.05):
            if ctx is not None:
                ctx.check()
        try:
            return self._execute_plan_locked(node, ctx)
        finally:
            tree_lock.release()

    def _execute_plan_locked(self, node, ctx) -> pa.Table:
        from spark_rapids_tpu.columnar.batch import batch_to_arrow
        from spark_rapids_tpu.obs import memtrack as _mt
        from spark_rapids_tpu.obs import profile_for
        from spark_rapids_tpu.obs import span as _span
        from spark_rapids_tpu.plan.cpu import CpuExec

        schema = node.output_schema
        tables = []
        prof = profile_for(node)
        qid = prof.query_id if prof is not None else None
        # allocations from here to the end of the finally block attribute
        # to this query (thread-scoped: concurrent executors each carry
        # their own id, obs/memtrack.py); the leak audit settles the account
        _mt.begin_query(qid)
        pool = None
        if ctx is not None:
            ctx.query_id = qid
            if ctx.memory_budget:
                from spark_rapids_tpu.mem.pool import get_pool

                pool = get_pool()
                pool.set_query_budget(qid, ctx.memory_budget)
        had_error = True
        try:
            if isinstance(node, CpuExec):
                for p in range(node.num_partitions()):
                    if ctx is not None:
                        ctx.check()
                    tables.extend(node.execute_host(p))
            else:
                # each output-partition drain holds the device semaphore
                # (GpuSemaphore analog); the small-query fast path skips
                # the round-trip — its whole point is shedding fixed costs
                from spark_rapids_tpu.mem.semaphore import get_task_semaphore
                from spark_rapids_tpu.serve.context import (
                    QueryDeadlineExceeded,
                )

                sem = (None if getattr(node, "_fastpath", False)
                       else get_task_semaphore())
                for p in range(node.num_partitions()):
                    if sem is not None:
                        if ctx is None:
                            sem.acquire(p)
                        else:
                            # (query, partition) id: two queries draining
                            # partition 0 are different tasks, not one
                            # reentrant holder; the wait carries the
                            # query's deadline budget, priority, and
                            # cancellation hook
                            ctx.check()
                            if not sem.acquire((ctx.ctx_id, p),
                                               timeout_ms=ctx.remaining_ms(),
                                               cancel_check=ctx.check,
                                               priority=ctx.priority):
                                ctx.cancel("deadline")
                                raise QueryDeadlineExceeded(
                                    f"{ctx.name} exceeded its deadline "
                                    f"waiting for the task semaphore")
                    try:
                        for b in node.execute(p):
                            # device->host materialization cost feeds the
                            # CBO's measured xfer ns/row (plan/autotune.py;
                            # buffered, flushed at prof.finish below)
                            t0 = time.perf_counter_ns()
                            with _span.task_span("query:readback") as sp:
                                t = batch_to_arrow(b, schema)
                                if sp is not None:
                                    sp.attrs["rows"] = t.num_rows
                            tables.append(t)
                            if t.num_rows:
                                from spark_rapids_tpu.plan import (
                                    autotune as _at,
                                )
                                _at.observe("cbo", "global", "xfer",
                                            time.perf_counter_ns() - t0,
                                            t.num_rows)
                    finally:
                        if sem is not None:
                            sem.release(p if ctx is None
                                        else (ctx.ctx_id, p))
            had_error = False
        finally:
            # the query's close-out runs on the executor thread while the
            # device idles and the client waits: one span names it
            with _span.task_span("query:finish"):
                self._finish_query(node, prof, qid, pool, had_error)
        if not tables:
            return schema.to_arrow().empty_table()
        return pa.concat_tables(tables)

    def _finish_query(self, node, prof, qid, pool, had_error) -> None:
        """Everything after the last batch: profile close-out (gauge
        snapshots, node stats, autotune feedback and its file write), the
        shuffle cleanup walk, the leak audit."""
        from spark_rapids_tpu.obs import memtrack as _mt
        from spark_rapids_tpu.shuffle import ShuffleExchangeExec

        # close out the per-query profile (plan/overrides.py installed
        # it at plan time) before shuffle state is released
        if prof is not None:
            prof.finish(node)
        self._last_profile = prof

        # release shuffle files/blocks now that output is materialized
        from spark_rapids_tpu.exec.reuse import ReusedExchangeExec

        def walk(n):
            if isinstance(n, (ShuffleExchangeExec, ReusedExchangeExec)):
                n.cleanup()
            # a fused stage's constituents are not structural children,
            # but an absorbed join's build subtree hangs off the
            # constituent (exec/fused.py) and can contain exchanges
            # whose files would otherwise never be released
            for op in getattr(n, "fused_ops", ()):
                if len(op.children) == 2:
                    walk(op.children[1])
            for c in n.children:
                walk(c)

        walk(node)

        # query-end leak audit (MemoryCleaner analog): everything this
        # query allocated must be freed by now — cached materialization
        # entries are exempt (retained by design). Runs AFTER the
        # cleanup walk so legitimate releases have happened.
        try:
            audit = _mt.audit_query(qid, had_error=had_error)
            if prof is not None and not audit.get("skipped"):
                prof.memory["leak_audit"] = {
                    "leaked_bytes": audit["leaked_bytes"],
                    "retained_bytes": audit["retained_bytes"],
                }
        finally:
            if pool is not None:
                pool.clear_query_budget(qid)
            _mt.end_query(qid)

    def collect(self) -> List[dict]:
        return self.to_arrow().to_pylist()

    def last_profile(self):
        """The QueryProfile of the most recent execution of this DataFrame
        (None when profiling is disabled or nothing ran yet)."""
        return getattr(self, "_last_profile", None)

    def explain_analyze(self) -> str:
        """EXPLAIN ANALYZE: execute the query, then render the physical
        plan with per-node rows/batches/opTime inline (the reference's
        'explain with metrics' / AdaptiveSparkPlan final-plan view)."""
        self.to_arrow()
        prof = self.last_profile()
        if prof is None:  # profiling disabled: fall back to the static plan
            return self.explain()
        return prof.explain_analyze()


class GroupedDataFrame:
    def __init__(self, df: DataFrame, keys: List[E.Expression]):
        self.df = df
        self.keys = keys

    def agg(self, *aggs) -> DataFrame:
        return self.df._with(
            L.Aggregate(self.keys, list(aggs), self.df.plan))


def read_parquet(paths, columns=None, predicate=None,
                 conf: Optional[C.RapidsConf] = None) -> DataFrame:
    if isinstance(paths, str):
        paths = [paths]
    return DataFrame(L.ParquetScan(list(paths), columns, predicate), conf)


def from_arrow(table: pa.Table, conf: Optional[C.RapidsConf] = None,
               batch_rows: int = 1 << 20, partitions: int = 1) -> DataFrame:
    return DataFrame(L.InMemoryScan(table, batch_rows, partitions), conf)

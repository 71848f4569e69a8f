"""Physical operator base: the TPU analog of GpuExec.

Reference: GpuExec.scala:286 (base trait), whose contract is
``internalDoExecuteColumnar(): RDD[ColumnarBatch]`` plus a leveled metrics
framework (GpuMetric, GpuExec.scala:41-178). Here an operator produces an
iterator of TPU-resident ``ColumnarBatch`` per partition; the driver-side
plan layer (plan/) decides partitioning, and the shuffle layer moves data
between partition counts.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Iterator, List, Optional, Sequence

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch


ESSENTIAL = 0
MODERATE = 1
DEBUG = 2

_LEVEL_NAMES = {"ESSENTIAL": ESSENTIAL, "MODERATE": MODERATE, "DEBUG": DEBUG}

# Active metrics verbosity (spark.rapids.tpu.sql.metrics.level, applied by
# plan/overrides.py at plan time, GpuExec.scala:41 analog). Metrics declared
# ABOVE this level are registered as disabled placeholders: operator code
# can still add into them without existence checks, but collect_metrics /
# QueryProfile skip them and timers around them short-circuit.
_METRICS_LEVEL = MODERATE


def set_metrics_level(level) -> None:
    global _METRICS_LEVEL
    if isinstance(level, str):
        name = level.strip().upper()
        if name not in _LEVEL_NAMES:
            raise ValueError(
                f"unknown metrics level {level!r}: expected one of "
                f"{sorted(_LEVEL_NAMES)}")
        level = _LEVEL_NAMES[name]
    _METRICS_LEVEL = int(level)


def get_metrics_level() -> int:
    return _METRICS_LEVEL

# When True, every operator fences (forces execution + 1-element readback of)
# each batch it produces before yielding, so opTime metrics measure real
# execution rather than async dispatch. Because a child operator fences its
# own output first, each operator's opTime covers only the compute IT added.
# Costs one tiny device->host readback per batch per operator; leave off for
# throughput runs. Toggled by spark.rapids.tpu.metrics.sync (config/conf.py)
# via set_sync_metrics().
SYNC_METRICS = False


def set_sync_metrics(enabled: bool) -> None:
    global SYNC_METRICS
    SYNC_METRICS = bool(enabled)


class Metric:
    """Accumulating metric, summed across partitions (GpuMetric analog).

    ``add`` is locked: scan decode pools, upload stagers, prefetch workers
    and parallel shuffle-write tasks all enter the same operator's timers
    concurrently, and ``value += v`` alone would drop updates."""

    __slots__ = ("name", "level", "value", "enabled", "_lock")

    def __init__(self, name: str, level: int = MODERATE,
                 enabled: bool = True):
        self.name = name
        self.level = level
        self.value = 0
        self.enabled = enabled
        self._lock = threading.Lock()

    def add(self, v) -> None:
        with self._lock:
            self.value += v

    def __repr__(self):
        return f"{self.name}={self.value}"


class MetricsTimer:
    """Context manager adding elapsed ns to a metric (NvtxWithMetrics analog)."""

    def __init__(self, metric: Optional[Metric]):
        self.metric = metric

    def __enter__(self):
        if self.metric is not None and self.metric.enabled:
            self._t0 = time.perf_counter_ns()
        else:
            self._t0 = None
        return self

    def __exit__(self, *exc):
        if self._t0 is not None:
            self.metric.add(time.perf_counter_ns() - self._t0)
        return False


class TpuExec:
    """Base physical operator.

    Subclasses define ``output_schema`` and ``do_execute(partition)``; the
    base wires metrics and explain formatting.
    """

    # Operators whose outputs are front-packed and often far sparser than
    # their static capacity (filter/join/agg) opt in: the base execute
    # re-buckets each output down (columnar.batch.shrink_to_live) so
    # downstream kernels run at the smaller static shape.
    shrink_output = False

    # Memory-attribution site (obs/memtrack.py SITES) pushed with the
    # operator name around every batch pull, so pool allocations made
    # inside this operator's iterator (spill-handle registration, retry
    # splits) attribute to it. None keeps the ambient site.
    mem_site: Optional[str] = None

    #: declared (operator, type) support matrix (spark_rapids_tpu.support).
    #: Every exec class the plan rewrite (plan/overrides.py) may place on
    #: device must declare one; the type-support static pass enforces this
    #: and plan/docs renders docs/supported_ops.md from it.
    type_support = None

    def __init__(self, *children: "TpuExec"):
        self.children: List[TpuExec] = list(children)
        self.metrics: Dict[str, Metric] = {}
        self._register_metric("numOutputRows", ESSENTIAL)
        self._register_metric("numOutputBatches", MODERATE)
        self._register_metric("opTime", ESSENTIAL)
        # row counts are traced device scalars; summing them eagerly would
        # force a host sync per batch per operator and kill async dispatch
        # pipelining — they are resolved lazily in collect_metrics. The lock
        # covers concurrent partitions of one operator (parallel shuffle
        # writes / prefetch workers).
        self._pending_rows: List = []
        self._rows_lock = threading.Lock()

    # -- schema / partitioning --------------------------------------------
    @property
    def output_schema(self) -> T.Schema:
        raise NotImplementedError

    def num_partitions(self) -> int:
        if self.children:
            return self.children[0].num_partitions()
        return 1

    # -- execution ---------------------------------------------------------
    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.obs import histo as _histo
        from spark_rapids_tpu.obs import memtrack as _mt
        from spark_rapids_tpu.utils import tracing
        it = self.do_execute(partition)
        op_time = self.metrics["opTime"]
        name = type(self).__name__
        # per-batch latency distribution (p50/p95/p99 in profiles and
        # Prometheus); the flag is read once per execute(), the record is
        # one bit_length + two adds under a lock per batch
        batch_histo = (_histo.get("batch_op_ns")
                       if _histo.enabled() else None)
        while True:
            # per-batch operator range (utils/tracing.TraceRange): a
            # profiler annotation for the life of this next(), so a device
            # trace shows which operator the host was in, and at close an
            # event for the Chrome trace exporter while a capture window
            # (Profiler / QueryProfile with trace capture) is open; the
            # steady state pays the TraceMe's flag check and one flag read
            rng = tracing.TraceRange(name).open()
            t0 = rng.start_ns
            # HBM attribution context: pool allocations made while this
            # operator's iterator runs tag to (query, operator, site).
            # Nested execute() frames re-push, so the innermost active
            # operator wins — two thread-local writes per batch when on
            mem_tok = _mt.push_op(name, self.mem_site)
            try:
                batch = next(it)
            except StopIteration:
                rng.close(record=False)
                op_time.add(time.perf_counter_ns() - t0)
                return
            except BaseException:
                rng.close(record=False)
                raise
            finally:
                _mt.pop_op(mem_tok)
            if SYNC_METRICS:
                from spark_rapids_tpu.utils.sync import fence
                fence(batch)
            if self.shrink_output:
                from spark_rapids_tpu.config import conf as _C
                cfg = _C.get_active()
                if _C.SHRINK_TO_LIVE_ENABLED.get(cfg):
                    from spark_rapids_tpu.columnar.batch import shrink_to_live
                    batch = shrink_to_live(
                        batch, _C.SHRINK_TO_LIVE_MIN_CAPACITY.get(cfg))
            t1 = time.perf_counter_ns()
            op_time.add(t1 - t0)
            if batch_histo is not None:
                batch_histo.record(t1 - t0)
            rng.close(args={"partition": partition}, end_ns=t1)
            self.metrics["numOutputBatches"].add(1)
            with self._rows_lock:
                self._pending_rows.append(batch.num_rows)
                fold = (list(self._pending_rows)
                        if len(self._pending_rows) >= 64 else None)
                if fold is not None:
                    self._pending_rows.clear()
            if fold is not None:
                # fold into the host counter; the early scalars are long done
                # by now so this rarely blocks, and it bounds retained buffers
                from spark_rapids_tpu.utils.sync import host_get
                self.metrics["numOutputRows"].add(
                    sum(int(n) for n in host_get(fold, "metrics.rows")))
            yield batch

    def execute_all(self) -> Iterator[ColumnarBatch]:
        """All partitions, sequentially (test/driver convenience)."""
        for p in range(self.num_partitions()):
            yield from self.execute(p)

    def do_execute(self, partition: int) -> Iterator[ColumnarBatch]:
        raise NotImplementedError

    # -- whole-stage fusion protocol ---------------------------------------
    # Operators whose per-batch work is a PURE batch-in/batch-out function
    # (no host sync, no cross-batch state) implement batch_fn()/batch_fn_key
    # so the plan-time fusion pass (plan/overrides.py) can compose maximal
    # chains into one jitted program per stage (exec/fused.py). Returning
    # None marks the operator as a fusion BARRIER — it executes unfused,
    # which also preserves per-operator CPU-fallback semantics.

    def batch_fn(self):
        """Pure traceable fn(batch) -> batch, or None (fusion barrier)."""
        return None

    def batch_fn_key(self) -> tuple:
        """shared_jit key fragment capturing batch_fn's traced program."""
        raise NotImplementedError(type(self).__name__)

    def fused_out_cap(self, in_cap: int) -> int:
        """Static output capacity of batch_fn given an input capacity
        (fusion tracks it through the chain to key shape-dependent
        downstream segments, e.g. join probe byte bounds)."""
        return in_cap

    # -- metrics / explain -------------------------------------------------
    def _register_metric(self, name: str, level: int = MODERATE) -> Metric:
        m = Metric(name, level, enabled=level <= _METRICS_LEVEL)
        self.metrics[name] = m
        return m

    def timer(self, name: str) -> MetricsTimer:
        return MetricsTimer(self.metrics.get(name))

    def node_description(self) -> str:
        return type(self).__name__

    def explain(self, indent: int = 0) -> str:
        pad = "  " * indent
        lines = [f"{pad}{'+- ' if indent else ''}{self.node_description()}"]
        for c in self.children:
            lines.append(c.explain(indent + 1))
        return "\n".join(lines)

    def metrics_snapshot(self) -> Dict[str, int]:
        """This node's enabled metric values (pending device row scalars
        folded in first)."""
        with self._rows_lock:
            pending = list(self._pending_rows)
            self._pending_rows.clear()
        if pending:
            from spark_rapids_tpu.utils.sync import host_get
            self.metrics["numOutputRows"].add(
                sum(int(n) for n in host_get(pending, "metrics.rows")))
        return {m.name: m.value for m in self.metrics.values() if m.enabled}

    def collect_metrics(self) -> Dict[str, int]:
        out = {}

        def walk(node: "TpuExec"):
            name = type(node).__name__
            for k, v in node.metrics_snapshot().items():
                out[f"{name}.{k}"] = out.get(f"{name}.{k}", 0) + v
            # constituents of a fused stage are not structural children but
            # still carry attributed metrics; an absorbed join's build
            # subtree executes for real and hangs off the constituent
            # (exec/fused.py)
            for op in getattr(node, "fused_ops", ()):
                for k, v in op.metrics_snapshot().items():
                    oname = type(op).__name__
                    out[f"{oname}.{k}"] = out.get(f"{oname}.{k}", 0) + v
                if len(op.children) == 2:
                    walk(op.children[1])
            for c in node.children:
                walk(c)

        walk(self)
        return out


class LeafExec(TpuExec):
    def __init__(self):
        super().__init__()


class UnaryExec(TpuExec):
    def __init__(self, child: TpuExec):
        super().__init__(child)

    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def output_schema(self) -> T.Schema:
        return self.child.output_schema


class BinaryExec(TpuExec):
    def __init__(self, left: TpuExec, right: TpuExec):
        super().__init__(left, right)

    @property
    def left(self) -> TpuExec:
        return self.children[0]

    @property
    def right(self) -> TpuExec:
        return self.children[1]


class BatchSourceExec(LeafExec):
    """Leaf producing batches from pre-built device/host data (tests, cache)."""

    def __init__(self, batches_per_partition: Sequence[Sequence[ColumnarBatch]],
                 schema: T.Schema):
        super().__init__()
        self._parts = [list(bs) for bs in batches_per_partition]
        self._schema = schema

    @property
    def output_schema(self) -> T.Schema:
        return self._schema

    def num_partitions(self) -> int:
        return len(self._parts)

    def do_execute(self, partition: int) -> Iterator[ColumnarBatch]:
        yield from self._parts[partition]


# type_support declaration (see spark_rapids_tpu.support; grouped decl
# blocks like this one end each exec module — the static pass resolves
# module-level assignments as well as in-class attributes).
from spark_rapids_tpu.support import ALL, ts  # noqa: E402

BatchSourceExec.type_support = ts(
    ALL, note="in-memory batch source; carries whatever the batch holds")

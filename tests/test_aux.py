"""Aux subsystem tests: tracing/profiler, task metrics, LORE dump/replay,
docs generators (reference: NvtxWithMetrics usage, GpuTaskMetrics,
GpuLore, RapidsConf docs gen / TypeChecks supported_ops gen)."""

import os

import pytest

import numpy as np
import pyarrow as pa

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import batch_from_arrow, batch_to_arrow
from spark_rapids_tpu.exec import BatchSourceExec, FilterExec, HashJoinExec
from spark_rapids_tpu.exprs.expr import col, lit
from spark_rapids_tpu.exprs import expr as E
from spark_rapids_tpu.utils import (
    Profiler, TaskMetrics, TraceRange, dump_exec_input, replay, trace_events,
)
from spark_rapids_tpu.utils import task_metrics as TM


def test_trace_ranges_recorded(tmp_path):
    trace_events(clear=True)
    with Profiler(str(tmp_path / "prof")):
        with TraceRange("outer"):
            with TraceRange("inner"):
                pass
    # a collection inside the window is logged as host:gc
    ev = [e for e in trace_events(clear=True) if e["name"] != "host:gc"]
    names = [e["name"] for e in ev]
    assert names == ["inner", "outer"]  # exit order
    assert all(e["dur_ns"] >= 0 for e in ev)
    # outside a window, ranges don't record
    with TraceRange("quiet"):
        pass
    assert trace_events() == []


def test_task_metrics_lifecycle():
    m = TM.start_task(42)
    TM.add("retry_count", 2)
    TM.add("spill_to_host_bytes", 1 << 20)
    TM.watermark("max_device_bytes", 100)
    TM.watermark("max_device_bytes", 50)  # lower: no change
    with TM.timed("spill_time_ns"):
        pass
    done = TM.finish_task()
    assert done is m
    snap = m.snapshot()
    assert snap["retry_count"] == 2
    assert snap["spill_to_host_bytes"] == 1 << 20
    assert snap["max_device_bytes"] == 100
    assert snap["spill_time_ns"] >= 0
    assert TM.current() is None
    TM.add("retry_count", 1)  # no active task: silently ignored
    assert TM.get_task(42) is m


def _join_tables(rng):
    lt = pa.table({"k": pa.array(rng.integers(0, 10, 100), pa.int64()),
                   "v": pa.array(rng.normal(size=100), pa.float64())})
    rt = pa.table({"rk": pa.array(rng.integers(0, 10, 30), pa.int64()),
                   "w": pa.array(rng.integers(0, 99, 30), pa.int64())})
    return lt, rt


def test_lore_dump_and_replay(tmp_path, rng):
    lt, rt = _join_tables(rng)
    ls, rs = T.Schema.from_arrow(lt.schema), T.Schema.from_arrow(rt.schema)
    left = BatchSourceExec([[batch_from_arrow(lt.slice(i, 32), 16)
                             for i in range(0, 100, 32)]], ls)
    right = BatchSourceExec([[batch_from_arrow(rt, 16)]], rs)
    node = HashJoinExec([col("k")], [col("rk")], "inner", left, right)
    node = dump_exec_input(node, str(tmp_path / "lore"))
    orig = []
    for b in node.execute_all():
        orig.extend(batch_to_arrow(b, node.output_schema).to_pylist())
    assert os.path.exists(tmp_path / "lore" / "manifest.json")
    assert os.path.exists(tmp_path / "lore" / "child0_part0_batch1.parquet")
    # replay against the recorded inputs
    replayed_node = replay(
        str(tmp_path / "lore"),
        lambda l, r: HashJoinExec([col("k")], [col("rk")], "inner", l, r))
    got = []
    for b in replayed_node.execute_all():
        got.extend(batch_to_arrow(b, replayed_node.output_schema).to_pylist())
    assert sorted(got, key=repr) == sorted(orig, key=repr)


def test_docs_generators(tmp_path):
    from spark_rapids_tpu.plan.docs import generate_supported_ops, write_docs

    md = generate_supported_ops()
    assert "| Expression |" in md
    assert "RLike" in md and "HashAggregateExec" in md
    paths = write_docs(str(tmp_path / "docs"))
    assert all(os.path.exists(p) for p in paths)
    cfg = open(paths[0]).read()
    assert "spark.rapids.tpu" in cfg


# -- core dump (GpuCoreDumpHandler analog) ----------------------------------


def test_core_dump_snapshot(tmp_path):
    from spark_rapids_tpu.utils.core_dump import (
        core_dump_on_failure, dump_state, read_dump,
    )

    p = dump_state(str(tmp_path))
    snap = read_dump(p)
    assert snap["pool"]["limit_bytes"] > 0
    assert snap["device"]["devices"]
    assert snap["exception"] is None

    with pytest.raises(RuntimeError):
        with core_dump_on_failure(str(tmp_path)) as cd:
            raise RuntimeError("simulated device failure")
    snap = read_dump(cd.dump_path)
    assert snap["exception"]["type"] == "RuntimeError"
    assert "simulated device failure" in snap["exception"]["message"]


def test_core_dump_swallow_mode(tmp_path):
    from spark_rapids_tpu.utils.core_dump import core_dump_on_failure

    with core_dump_on_failure(str(tmp_path), reraise=False) as cd:
        raise ValueError("x")
    assert cd.dump_path is not None


# -- ColumnarRdd analog ------------------------------------------------------


def test_device_batches_handoff():
    import numpy as np
    import pyarrow as pa
    import jax

    from spark_rapids_tpu.plan import from_arrow
    from spark_rapids_tpu.plan.ml import device_batches
    from spark_rapids_tpu.exprs.expr import col, lit

    t = pa.table({"a": pa.array(np.arange(100, dtype=np.float64)),
                  "b": pa.array(np.arange(100), pa.int64())})
    df = from_arrow(t).filter(col("b") < lit(50))
    batches = list(device_batches(df))
    assert batches and all(isinstance(b.columns[0].data, jax.Array)
                           for b in batches)
    total = sum(int(b.num_rows) for b in batches)
    assert total == 50


def test_feature_matrix_stack():
    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.plan import from_arrow
    from spark_rapids_tpu.plan.ml import feature_matrix

    t = pa.table({
        "f1": pa.array([1.0, 2.0, None, 4.0], pa.float64()),
        "f2": pa.array([10.0, 20.0, 30.0, 40.0], pa.float64()),
        "y": pa.array([0.0, 1.0, 0.0, 1.0], pa.float64()),
    })
    x, y = feature_matrix(from_arrow(t), label_col="y")
    assert x.shape == (4, 2)
    assert y.shape == (4,)
    xs = np.asarray(x)
    assert np.isnan(xs[2, 0]) and xs[3, 1] == 40.0
    assert np.asarray(y).tolist() == [0.0, 1.0, 0.0, 1.0]

"""A fused stage with no join learns the capacity its output is packed to
(exec/fused.py ``TpuFusedStageExec._size``, ``_make_plain``), as a stage
with a join learns its first probe's: from the rows its first batch's
mask keeps (one ``fused.sizing`` sync, kept with the plan). Every later
batch is packed to that capacity inside its program, one ``fused.short``
sync at the partition's end checks that nothing was cut, and
``shrink_to_live`` no longer syncs a batch.

What is held: the rows equal the unfused chain's, in order; the learned
capacity is kept across requests; a batch that outgrows it runs the
partition again at the batches' own capacity, with no fallback; a stage
that does not end in a mask, whose first batch is under
``shrinkToLive.minCapacity``, or whose output holds a plain string's
bytes never sizes."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.config import conf as C
from spark_rapids_tpu.exprs.expr import Add, GreaterThan, col, lit
from spark_rapids_tpu.obs import gauges, span
from spark_rapids_tpu.plan import from_arrow
from spark_rapids_tpu.utils import tracing

BATCH = 8192
NBATCH = 4
SMALL = {C.SHRINK_TO_LIVE_MIN_CAPACITY.key: BATCH}


@pytest.fixture(autouse=True)
def _restore_active_conf():
    """The planner installs its conf process-wide (``C.set_active``): the
    lowered ``minCapacity`` must not reach the next test of this worker."""
    was = C._ACTIVE
    yield
    C._ACTIVE = was


def _stage(df):
    from spark_rapids_tpu.exec.fused import TpuFusedStageExec

    def find(node):
        if isinstance(node, TpuFusedStageExec):
            return node
        return next((s for s in map(find, node.children) if s), None)
    return find(df.physical_plan())


def _table(keep_per_batch):
    """``NBATCH`` batches of ``BATCH`` rows; ``v`` is 1 on the first
    ``keep_per_batch[i]`` rows of batch ``i`` (and of the batches after the
    list's end: its last entry), 0 elsewhere."""
    v = np.zeros(NBATCH * BATCH, np.int64)
    for b in range(NBATCH):
        n = keep_per_batch[min(b, len(keep_per_batch) - 1)]
        v[b * BATCH:b * BATCH + n] = 1
    k = np.arange(NBATCH * BATCH, dtype=np.int64)
    return pa.table({"k": pa.array(k * 3), "v": pa.array(v)})


def _df(t, conf):
    return (from_arrow(t, conf, batch_rows=BATCH, partitions=1)
            .filter(GreaterThan(col("v"), lit(0)))
            .select(Add(col("k"), col("v")).alias("kv"), col("k")))


def _run(df):
    """(answer, the request's host-sync sites, the counters' change)."""
    before = gauges.snapshot()
    tracing.set_capture(True, clear=True)
    try:
        with span.activate(span.new_trace()):
            out = df.to_arrow()
        events = tracing.trace_events(clear=True)
    finally:
        tracing.set_capture(False, clear=True)
    after = gauges.snapshot()
    sites = [e["args"]["site"] for e in events
             if e["name"] == "exec:host-sync"]
    moved = {k: after[k] - before[k] for k in (
        "fused_plain_learned_total", "fused_plain_unlearned_total",
        "fused_fallback_total")}
    return out, sites, moved


def _unfused(t):
    off = C.RapidsConf({**SMALL, "spark.rapids.tpu.sql.fusion.enabled": False})
    return _df(t, off).to_arrow()


@pytest.mark.parametrize("keep, learned", [(700, 2048), (100, 1024)],
                         ids=["2048", "1024"])
def test_a_plain_stage_packs_to_what_its_first_batch_kept(keep, learned):
    """Every batch keeps ``keep`` rows: the stage learns twice that, as a
    bucket, packs every batch there with one sync a partition, and keeps
    what it learned for the plan's next request."""
    t = _table([keep])
    df = _df(t, C.RapidsConf(SMALL))
    assert "TpuFusedStage" in df.physical_plan().explain()
    out, sites, moved = _run(df)
    assert _stage(df)._learned == {0: learned}
    assert out.equals(_unfused(t))
    assert out.num_rows == NBATCH * keep
    assert "shrink_to_live" not in sites
    assert sites.count("fused.short") == 1
    assert sites.count("fused.sizing") == 1
    assert moved == {"fused_plain_learned_total": 1,
                     "fused_plain_unlearned_total": 0,
                     "fused_fallback_total": 0}
    again, sites, moved = _run(df)
    assert again.equals(out)
    assert _stage(df)._learned == {0: learned}
    assert "fused.sizing" not in sites and "shrink_to_live" not in sites
    assert sites.count("fused.short") == 1
    assert moved["fused_plain_learned_total"] == 1


def test_rows_that_outgrow_what_was_learned_run_again_unshrunk():
    """The first batch keeps 10 rows (the stage learns 1,024), the second
    3,000: the partition runs again at the batches' own capacity, returns
    the same rows, counts no fallback and stops packing that partition."""
    t = _table([10, 3000, 5])
    df = _df(t, C.RapidsConf(SMALL))
    out, sites, moved = _run(df)
    assert out.equals(_unfused(t))
    assert out.num_rows == 10 + 3000 + 2 * 5
    assert _stage(df)._learned == {0: None}
    assert moved == {"fused_plain_learned_total": 0,
                     "fused_plain_unlearned_total": 1,
                     "fused_fallback_total": 0}
    assert sites.count("fused.short") == 1


@pytest.mark.parametrize("case", ["projection-only", "under-min-capacity",
                                  "plain-string-output"])
def test_a_stage_that_would_not_shrink_never_sizes(case):
    """A stage that does not end in a filter's mask, one whose first batch
    is under ``shrinkToLive.minCapacity``, and one whose output carries a
    string's byte buffer (``shrink_to_live`` re-buckets the bytes too) run
    as before: no ``fused.sizing`` dispatch, nothing learned."""
    t = _table([700])
    if case == "projection-only":
        df = (from_arrow(t, C.RapidsConf(SMALL), batch_rows=BATCH,
                         partitions=1)
              .select(Add(col("k"), col("v")).alias("kv"), col("v"))
              .select(Add(col("kv"), lit(1)).alias("kv1"), col("v")))
    elif case == "plain-string-output":
        t = t.append_column("s", pa.array(
            [f"row-{i}" for i in range(t.num_rows)]))
        df = (from_arrow(t, C.RapidsConf(SMALL), batch_rows=BATCH,
                         partitions=1)
              .filter(GreaterThan(col("v"), lit(0)))
              .select(col("s"), col("k")))
    else:
        df = _df(t, None)
    stage = _stage(df)
    assert stage is not None
    out, sites, moved = _run(df)
    assert "fused.sizing" not in sites and "fused.short" not in sites
    assert stage._learned == {}
    assert moved["fused_plain_learned_total"] == 0
    assert out.num_rows == (NBATCH * BATCH if case == "projection-only"
                            else NBATCH * 700)
